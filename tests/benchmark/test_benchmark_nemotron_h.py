"""The Nemotron-H (``nemotron_h``) family's files in the benchmark: what is
cut and what is kept, the assumed items, the traffic mix, the count
functions against numbers worked by hand, the new reader on made-up steps,
the probe under the served routing and its ten controls at a small size, a
rehearsal of the cell, and the benchmark's copy of the reference against
the program's."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "serve.reason-ssd-closed256"
NAME = "nemotron-3-nano-serve-14l-ep8"
MIX = "reason-ssd-closed256"
#: the accepted readings this cell reports under names of its own
OWN = {"ssd_slots_peak_share": "ssm_slots_peak_share",
       "moe_expert_time_share.ssd": "moe_expert_time_share",
       "moe_held_pair_share.ssd": "moe_held_pair_share",
       "moe_expert_load_imbalance.ssd": "moe_expert_load_imbalance",
       "moe_experts_touched_share.ssd": "moe_experts_touched_share"}
NEW = ("ssd_time_share", "ssd_decode_roofline", "ssd_prefill_roofline",
       "ssd_slots_peak_share", "moe_expert_roofline.ssd",
       "moe_expert_time_share.ssd", "moe_held_pair_share.ssd",
       "moe_expert_load_imbalance.ssd", "moe_experts_touched_share.ssd",
       "gqa16_attention_roofline")
JOINED = {"kv_pages_peak_share", "kv_fill_share", "compiles_in_window.serve",
          "program_compile_s", "program_trace_lower_s",
          "programs_on_path.setup", "program_cache_hit_share.setup",
          "sched_tokens_per_step", "budget_fill_share"}
#: PR 52's stall and collector readings: the host loop they read runs here,
#: and a stall decides a run's tokens a second (PERF.md section 6, PR 54)
STALLS = {"stall_steps.serve", "stall_lost_ms.serve", "stall_gc_ms.serve",
          "stall_offcpu_ms.serve", "gc_ms_per_step.serve",
          "idle_ms_per_step.gc"}


#: the tests below that form programs run where the program lies beside the
#: benchmark: ``test_benchmark_second_family.py`` runs this directory again in
#: a copy of the benchmark's files alone, inside 600 s, in a suite near its
#: limit, and what they hold does not depend on where the files lie
beside_the_program = pytest.mark.skipif(
    not os.path.isdir(os.path.join(ROOT, "deepspeed_tpu")),
    reason="a copy of the benchmark's files: the tree's own run forms the "
           "programs")


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CONFIG = load("configs", NAME + ".json")
PUBLISHED = load("published", "nvidia-nemotron-3-nano-30b-a3b.json")
TRAFFIC = load("traffic", MIX + ".json")
LATTICE = load("lattices", NAME + ".json")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)


def test_three_keys_are_cut_and_every_width_is_kept():
    assert CONFIG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert CONFIG["reduced_from"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128,
        "vocab_size": 131072}
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (14, 16, 16384)
    assert CONFIG["deployment_chips_per_layer"] == 8
    assert CONFIG["routed_experts_scored"] == 128 \
        == 8 * CONFIG["n_routed_experts"]
    assert (CONFIG["first_layer"], CONFIG["experts_first"]) == (6, 0)
    for key, value in PUBLISHED["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    widths = set(PUBLISHED["widths"])
    assert widths >= {k for k in PUBLISHED["config"] if k.endswith("_dim")} \
        | {"hidden_size", "intermediate_size", "moe_intermediate_size",
           "moe_shared_expert_intermediate_size", "num_experts_per_tok",
           "ssm_state_size", "mamba_num_heads", "n_groups", "conv_kernel",
           "expand"}
    assert not widths & set(CONFIG["reduced"])
    assert (PUBLISHED["experts_key"], PUBLISHED["layer_period"]) \
        == ("n_routed_experts", 7)
    # two whole blocks of the pattern, an eighth of the vocabulary in whole
    # lane tiles, the pattern whole
    pattern = CONFIG["hybrid_override_pattern"]
    assert len(pattern) == 52 and pattern[6:20] == "EMEMEM*" * 2
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) \
        == (23, 23, 6)
    assert CONFIG["vocab_size"] * 8 == 131072 \
        and CONFIG["vocab_size"] % 128 == 0
    assert CONFIG["published_counts"]["layers"] == 52 \
        and CONFIG["held_counts"]["parameters"] == 1_447_040_256
    assert "no published checkpoint" in CONFIG["departures"]["seeded_weights"]
    assert "8-chip expert-parallel" in CONFIG["deployment"]
    assert "CUDA" in CONFIG["departures"]["kernels"]


def test_every_assumed_item_has_its_why():
    assert set(PUBLISHED["assumed"]) == {
        "attention_rope", "mamba_gated_norm", "dt_clamp",
        "router_bias_scale", "ssm_state_dtype", "layer_pattern_period"}
    for key, item in PUBLISHED["assumed"].items():
        assert len(item["why"]) >= 40, key
        assert CONFIG[key] == item["value"]
        assert CONFIG["assumed"][key] == item["why"]
    from deepspeed_tpu.models import nemotron_h
    assert nemotron_h.BIAS_SCALE == CONFIG["router_bias_scale"]
    assert nemotron_h.DT_INIT == (CONFIG["time_step_min"],
                                  CONFIG["time_step_max"],
                                  CONFIG["time_step_floor"])


def test_the_published_file_is_the_catalogs_entry():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog in this environment")
    with open(path) as f:
        entry = next(json.loads(line) for line in f if
                     '"name": "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"' in line)
    assert PUBLISHED["config"] == entry["config"]
    assert PUBLISHED["source"] == entry["source_url"] == CONFIG["source"]


def test_the_traffic_file_holds_the_mix_and_no_engine_key():
    assert TRAFFIC["driver"] == "serve_closed_loop"
    assert (TRAFFIC["clients"], TRAFFIC["set_size"],
            TRAFFIC["ramp_per_step"]) == (256, 256, 4)
    assert TRAFFIC["prompt_len"] == {"dist": "uniform", "min": 65, "max": 128}
    assert TRAFFIC["new_tokens"] == {"dist": "loguniform", "min": 512,
                                     "max": 2048}
    assert TRAFFIC["warmup"] == {"min_seconds": 50.0, "quiet_steps": 64,
                                 "max_seconds": 600.0, "hints": MIX}
    assert (TRAFFIC["drain_s"], TRAFFIC["trace_slice_s"]) == (60.0, 3.0)
    engine_keys = set(CONFIG["engine"]) | {"engine", "serving", "lattice"}
    assert not engine_keys & set(TRAFFIC)
    assert not engine_keys & set(TRAFFIC["warmup"])
    longest = TRAFFIC["prompt_len"]["max"] + TRAFFIC["new_tokens"]["max"]
    assert longest <= CONFIG["engine"]["max_seq_len"]
    assert TRAFFIC["clients"] == CONFIG["engine"]["max_sequences"]
    # the warm-up, drain and traced slice of the Kimi-delta cell's mix, and
    # the other 256-caller cells' lengths and callers
    for other in ("reason-closed256", "reason-ssm-closed256",
                  "reason-kda-closed256"):
        theirs = load("traffic", other + ".json")
        for key in ("clients", "set_size", "ramp_per_step", "prompt_len",
                    "new_tokens", "trace_slice_s"):
            assert TRAFFIC[key] == theirs[key], (other, key)
    kda = load("traffic", "reason-kda-closed256.json")
    assert TRAFFIC["drain_s"] == kda["drain_s"] and {
        k: v for k, v in TRAFFIC["warmup"].items() if k != "hints"} == {
        k: v for k, v in kda["warmup"].items() if k != "hints"}


def test_the_cell_and_its_metrics_are_listed():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, MIX, 1)
    names = [w["name"] for w in SPEC["workloads"]]
    # nine cells with this one, still one on four chips (a later family
    # comes after)
    assert names.index(CELL) == 8 and sum(
        w["chips"] == 4 for w in SPEC["workloads"][:9]) == 1
    before = [c["name"] for c in SPEC["configs"]]
    entry = SPEC["configs"][before.index(NAME)]
    assert entry["reduced"] == CONFIG["reduced"]
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    per_layer = {m["name"]: m for m in SPEC["per_layer"]}
    listed = [m["name"] for m in SPEC["per_layer"]]
    at = listed.index(NEW[0])
    assert tuple(listed[at:at + len(NEW)]) == NEW
    assert "moe_expert_roofline.kda" in listed[:at]
    served = {n for n in before[:before.index(NAME)] if "serve" in n}
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL], name
        assert per_layer[name]["moves"] == "serve_tok_s"
        metric = load("metrics", name + ".json")
        assert (metric["unit"], metric["layer"], metric["better"],
                metric["source"]) == (
            per_layer[name]["unit"], per_layer[name]["layer"],
            per_layer[name]["better"], per_layer[name]["source"])
        # the seven serving configurations that were here, each its reason
        assert set(metric["not_reported"]) == served and len(served) == 7
        assert all(len(w) >= 20 for w in metric["not_reported"].values())
        assert len(metric["what"]) >= 80
    for mine, theirs in OWN.items():
        # the reading of the metric it is named after, nothing else
        a, b = load("metrics", mine + ".json"), load("metrics",
                                                     theirs + ".json")
        assert (a["unit"], a["layer"], a["better"], a["source"],
                a["reader"], a["args"]) == (
            b["unit"], b["layer"], b["better"], b["source"], b["reader"],
            b["args"]), mine
    for name, kind, pattern in (
            ("ssd_decode_roofline", "ssd_decode", "^ssd_state_update_decode"),
            ("ssd_prefill_roofline", "ssd_prefill", "^ssd_chunk_prefill"),
            ("gqa16_attention_roofline", "attention", "^paged_attention"),
            ("moe_expert_roofline.ssd", "experts", "^moe_expert_ffn")):
        how = load("metrics", name + ".json")
        assert (how["reader"], how["args"]) == (
            "nemotron_h_roofline", {"patterns": [pattern], "kind": kind})
        assert how["unit"] == "%" and how["better"] == "higher"
    assert load("metrics", "ssd_time_share.json")["args"] == {
        "patterns": ["^ssd_"], "of": "busy"}
    listed = {m["name"] for m in SPEC["per_layer"] + SPEC["end_to_end"]
              if CELL in m.get("workloads", [])}
    end_to_end = {m["name"] for m in SPEC["end_to_end"]}
    # tokens a second and the set-up, no tail in this PR
    assert listed & end_to_end == {"serve_tok_s"}
    for name in listed - end_to_end:
        assert per_layer[name]["moves"] in ("serve_tok_s", "setup_s"), name
    assert listed - end_to_end == JOINED | STALLS | set(NEW)
    for name in JOINED | STALLS:        # appended after the cells that were
        lists = per_layer[name]["workloads"]
        last = "delta" if name in STALLS else "kda"
        assert lists.index(CELL) > lists.index(
            f"serve.reason-{last}-closed256"), name
    # every metric that was here, written for the cell's driver, is joined
    # or left out with its reason
    for m in SPEC["per_layer"][:at]:
        drivers = load("metrics", m["name"] + ".json")["drivers"]
        if "serve_closed_loop" in drivers \
                and m["name"] not in JOINED | STALLS:
            assert len(CONFIG["not_reported"][m["name"]]) >= 20, m["name"]
    assert not listed & set(CONFIG["not_reported"])
    assert CONFIG["routed_pairs_per_token"] == 6 * 6
    assert CONFIG["mean_share_of_pairs_a_held_expert_and_layer"] == 1 / 96
    assert CONFIG["held_experts_times_layers"] == 16 * 6


def test_the_harness_rules_hold_with_the_cell():
    """The structural rules the other families' tests hold the tree to
    (``harness_checks``): the cell resolves, the cut keeps the floors, and
    every metric it leaves out says why."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import harness_checks as checks     # beside this file
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    checks.check_cell(SPEC, BENCH, cell)
    checks.check_metrics(SPEC, BENCH)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_counts_worked_by_hand():
    """ISSUE 54's table: an M layer 38,744,896, a * layer 23,399,040, an
    expert 9,977,856, an E layer with 16 held 179,948,288, embedding and
    head 88,083,072, 1,447,040,256 in all = 2.89 GB; a slot 2,134,016 B a
    layer, the state pool 3.29 GB, the page pool 1.07 GB; a decode step's
    states 6.4 GB; and the program's own count agrees."""
    from benchmark import flops_nemotron_h as flops
    from benchmark.builders.serve_nemotron_h import source_of
    from deepspeed_tpu.models.nemotron_h import nemotron_h_config
    c = CONFIG
    assert flops.letters(c) == "EMEMEM*" * 2
    assert (flops.ssd_layers(c), flops.routed_layers(c),
            flops.attention_layers(c)) == (6, 6, 2)
    assert (flops.inner(c), flops.conv_channels(c)) == (4096, 6144)
    ssd = 2688 * 10304 + 4096 * 2688 + 6144 * 4 + 6144 + 3 * 64 + 4096 + 2688
    assert flops.ssd_params(c) == ssd == 38_744_896
    attn = 2 * 2688 * 4096 + 2 * 2688 * 256 + 2688
    assert flops.attention_params(c) == attn == 23_399_040
    assert flops.expert_params(c) == 2 * 2688 * 1856 == 9_977_856
    routed = 16 * 9_977_856 + 2 * 2688 * 3712 + 2688 * 128 + 128 + 2688
    assert flops.routed_params(c) == routed == 179_948_288
    total = 6 * ssd + 6 * routed + 2 * attn + 2 * 16384 * 2688 + 2688
    assert flops.total_params(c) == total == 1_447_040_256 \
        == c["held_counts"]["parameters"]
    assert round(2 * total / 1e9, 2) == 2.89
    # whole, the model is the published 31.6B (30.9B without the embedding
    # and the head): the check that d_inner is heads x head dim, not expand
    # x hidden, and that an expert has two matrices
    whole = 23 * ssd + 6 * attn + 23 * (
        routed + (128 - 16) * 9_977_856) + 2688
    assert round(whole / 1e9, 1) == 30.9
    assert round((whole + 2 * 131072 * 2688) / 1e9, 1) == 31.6
    # 3.2B active: 6 of 128 experts a routed layer
    active = whole - 23 * (128 - 6) * 9_977_856
    assert round((active + 131072 * 2688) / 1e9, 1) == 3.2
    # the program counts matrices, the convolution and the per-head
    # vectors: the 15 norms' and 6 gated norms' gains and the 6 router
    # biases left out
    program = nemotron_h_config(source_of(c, False),
                                first_layer=c["first_layer"]).n_params()
    assert program == total - 15 * 2688 - 6 * 4096 - 6 * 128
    assert flops.state_bytes(c) == 128 * 4096 * 4 == 2_097_152
    assert flops.conv_tail_bytes(c) == 3 * 6144 * 2
    slot = 6 * 2_134_016
    assert flops.slot_bytes(c) == slot and round(slot / 1e6, 1) == 12.8
    assert round(257 * slot / 1e9, 2) == 3.29
    eng = c["engine"]
    page = eng["page_size"] * 2 * 1024          # two attention layers
    assert round((eng["num_pages"] + 1) * page / 1e9, 2) == 1.07
    # weights + state pool + page pool: 43% of the chip's 16.9e9 B
    assert 0.42 < (2 * total + 257 * slot + 8193 * page) / 16.9e9 < 0.44
    # a decode step: 256 rows x 6 layers x a state read and written
    operands = (3 * 4096 + 2 * 1024) * 4
    assert flops.update_decode_bytes(c, 256) \
        == 6 * 256 * (2 * 2_097_152 + operands)
    assert round(6 * 256 * 2 * 2_097_152 / 1e9, 1) == 6.4
    assert flops.chunk_prefill_bytes(c, 2, 200) \
        == 6 * (2 * 2 * 2_097_152 + 200 * operands)
    assert flops.chunk_prefill_ops(c, 200) == 6 * 200 * (
        8 * 2 * 128 * 128 + 64 * (2 * 128 * 64 + 4 * 128 * 64))
    # TWO attention layers: 1,024 B a context token and layer, 32 heads
    assert flops.attention_bytes(c, 190_000) == 190_000 * 1024 * 2
    assert flops.attention_flops(c, 190_000) == 4 * 32 * 128 * 190_000 * 2
    # a step's 1,536 pairs of 256 rows x 36 / 8 over 16 x 6 experts
    assert flops.grouped_expert_bytes(c, 96, 1152) \
        == 96 * 9_977_856 * 2 + 1152 * 2 * 2688 * 2
    assert flops.grouped_expert_flops(c, 1152) == 2 * 1152 * 9_977_856
    assert round(96 * 9_977_856 * 2 / 1e9, 1) == 1.9


class _Reduced:
    devices = [0]

    def name_ns(self, device, patterns):
        return 10_000_000           # 10 ms of kernel time


class _Profiler:
    first_step, steps = 1, 2
    started_at, stopped_at = 10.0, 20.0


class _Ctx:
    reduced, profiler, config = _Reduced(), _Profiler(), CONFIG
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    setup_s, process_start = 5.0, 0.0


def _span(end, attrs):
    return ("fastgen.step", end - 0.01, 0.01, 0, 0, attrs, end, None, 0)


def _tracer(monkeypatch, rows):
    import deepspeed_tpu.telemetry as telemetry

    class Tracer:
        def records(self):
            return rows

    monkeypatch.setattr(telemetry, "get_tracer", lambda: Tracer())


@pytest.mark.parametrize("name", ["ssd_decode_roofline",
                                  "ssd_prefill_roofline",
                                  "gqa16_attention_roofline",
                                  "moe_expert_roofline.ssd"])
def test_the_roofline_reader_reads_the_programs_counts(monkeypatch, name):
    """Two traced steps whose ``fastgen.step`` spans carry the program's
    counts (the third lies outside the slice); a program without the
    attributes (the parent) gives None, and does not raise."""
    from benchmark import flops_nemotron_h as flops
    from benchmark.readers import nemotron_h_roofline as reader
    _tracer(monkeypatch, [
        _span(12.0, {"ssd_rows_decode": 256, "ssd_tokens_prefill": 0,
                     "prefill_rows": 0, "moe_experts_touched": 96,
                     "moe_pairs_here": 1150}),
        _span(13.0, {"ssd_rows_decode": 254, "ssd_tokens_prefill": 200,
                     "prefill_rows": 2, "moe_experts_touched": 94,
                     "moe_pairs_here": 2100}),
        _span(25.0, {"ssd_rows_decode": 9, "ssd_tokens_prefill": 9,
                     "prefill_rows": 9, "moe_experts_touched": 9,
                     "moe_pairs_here": 9})])
    args = load("metrics", name + ".json")["args"]
    facts = {"step_decode_context": [5, 190_000, 191_000, 7]}
    share = reader.read(_Ctx, facts, args)
    c = CONFIG
    least = {
        "ssd_decode_roofline": flops.update_decode_bytes(c, 510) / 819e9,
        "ssd_prefill_roofline": max(
            flops.chunk_prefill_bytes(c, 2, 200) / 819e9,
            flops.chunk_prefill_ops(c, 200) / 197e12),
        "gqa16_attention_roofline": sum(
            flops.attention_bytes(c, n) / 819e9
            for n in (190_000, 191_000)),
        "moe_expert_roofline.ssd": (
            flops.grouped_expert_bytes(c, 96, 1150)
            + flops.grouped_expert_bytes(c, 94, 2100)) / 819e9}[name]
    assert share == pytest.approx(100 * least / 0.010)
    # at 16 query heads a KV head the decode walk is bound by bytes
    assert flops.attention_flops(c, 1000) / 197e12 \
        < flops.attention_bytes(c, 1000) / 819e9
    _tracer(monkeypatch, [_span(12.0, {"tokens": 256})])
    if name != "gqa16_attention_roofline":
        assert reader.read(_Ctx, facts, args) is None
    assert reader.read(_Ctx, {"step_decode_context": []}, args) is None


def test_the_span_ring_metrics_read_the_programs_counts(monkeypatch):
    from benchmark.readers import span_peak_share, span_ring
    _tracer(monkeypatch, [
        _span(12.0, {"moe_experts_touched": 96, "moe_pairs_here": 1152,
                     "moe_tokens": 256, "moe_expert_load_max": 30,
                     "ssm_slots_held": 255}),
        _span(13.0, {"moe_experts_touched": 90, "moe_pairs_here": 1100,
                     "moe_tokens": 256, "moe_expert_load_max": 34,
                     "ssm_slots_held": 256})])

    def read(name, reader=span_ring):
        return reader.read(_Ctx, {}, load("metrics", name + ".json")["args"])

    assert read("moe_experts_touched_share.ssd") == pytest.approx(
        100 * (96 + 90) / (2 * 96))
    assert read("moe_held_pair_share.ssd") == pytest.approx(
        100 * (1152 + 1100) / (512 * 36))
    assert read("moe_expert_load_imbalance.ssd") == pytest.approx(
        64 / ((1152 + 1100) / 96))
    assert read("ssd_slots_peak_share", span_peak_share) \
        == pytest.approx(100.0)
    _tracer(monkeypatch, [_span(12.0, {"tokens": 256})])
    assert read("moe_held_pair_share.ssd") is None
    assert read("ssd_slots_peak_share", span_peak_share) is None


def test_the_probes_tolerances_carry_their_reasons():
    pr = CONFIG["probe"]
    for key in ("logit_rel_rms", "outlier", "margin", "pairs",
                "sequence_outlier", "routing_off_share", "min_compared",
                "lengths"):
        assert len(pr[key + "_reason"]) >= 150, key
        assert "TO BE SET" not in pr[key + "_reason"], key
    # each limit of the comparison lies between its two readings, which its
    # reason gives (PERF.md has the runs)
    for key in ("logit_rel_rms", "outlier", "routing_off_share"):
        assert "chip" in pr[key + "_reason"], key
    assert 0 < pr["logit_rel_rms"] <= pr["outlier_rel_rms"] < 0.2
    assert pr["sequence_outlier_share"] < 1 and pr["outlier_share"] < 0.2
    assert 0 < pr["routing_off_share"] < 1
    assert (pr["long_steps"], pr["long_rows"], pr["prompts"],
            pr["wide_copies"]) == (2000, 4, 8, 2)
    assert 72 <= pr["min_len"] < pr["max_len"] <= 128


def test_the_lattice_and_the_hints_are_the_cells_own():
    """The buckets the cell is served under load as the program's own
    artifact, at the engine's page size, vocabulary and token budget; the
    hints name programs of its buckets only."""
    from benchmark.builders import serve_laguna
    from deepspeed_tpu.inference.v2.lattice import resolve_lattice
    from deepspeed_tpu.inference.v2.step_key import StepKey
    eng = CONFIG["engine"]
    assert eng["serving"]["lattice"] == f"auto:benchmark/lattices/{NAME}.json"
    serving = serve_laguna.serving_of(eng, rehearse=False)
    assert "lattice" not in serve_laguna.serving_of(eng, rehearse=True)
    lattice = resolve_lattice(
        serving["lattice"], page_size=eng["page_size"],
        vocab_size=CONFIG["vocab_size"],
        max_ragged_batch_size=eng["token_budget"])
    assert lattice.mined and LATTICE["p_buckets"] == [8, 40]
    assert LATTICE["q_buckets"] == [1, 128]
    longest = TRAFFIC["prompt_len"]["max"] + TRAFFIC["new_tokens"]["max"]
    assert lattice.bucket_p(-(-longest // eng["page_size"])) == 40
    hints = load("hints", MIX + ".json")["keys"]
    assert hints == LATTICE["keys"] and len(LATTICE["why"]) >= 200
    s, q, p = (set(LATTICE[k]) for k in ("s_buckets", "q_buckets",
                                         "p_buckets"))
    for key in hints + CONFIG["probe"]["programs"]:
        key = StepKey.parse(key)
        assert key.S in s and key.Q in q and key.P in p, key
    rows = eng["max_sequences"]
    for pages in (8, 40):
        for prev in (rows, 2 * rows):
            assert [rows, 1, pages, False, "chain", prev, True] in hints
        assert [rows, 1, pages, False, "mixed", 4, 128, 8, True,
                True] in hints
    assert LATTICE["s_buckets"] == [1, 4, rows] and len(hints) == 13
    own = CONFIG["probe"]["programs"]
    assert [k for k in own if len(k) > 4] \
        == [[1, 128, 8, True, "sample", True]]
    assert not [k for k in hints if k in own]


def _small_probe():
    """The configuration at its debug widths with a probe and an engine cut
    to a test's size."""
    config = json.loads(json.dumps(CONFIG))
    config["rehearse"].pop("probe_cut")
    # ONE block of the pattern: half the time to form a program, in a suite
    # near its limit (the rehearsal below runs both)
    config["rehearse"]["num_hidden_layers"] = 7
    config["engine"].update(page_size=16, num_pages=256, max_sequences=32,
                            token_budget=256, max_seq_len=512)
    config["probe"].update(
        prompts=2, min_len=20, max_len=40, decode_steps=8, long_rows=1,
        long_steps=32, wide_copies=2, wide_at=[14, 26], wide_steps=2,
        min_compared=2, programs=[],
        # float32 at debug widths: the limits of a rounding of sums, and
        # no near-tie falls the other way
        logit_rel_rms=1e-4, outlier_rel_rms=3e-3, margin=1e-3,
        outlier_share=0.0, sequence_outlier_share=0.5,
        routing_off_share=0.0)
    return config


@beside_the_program
def test_the_probe_passes_the_program_and_refuses_each_control():
    """ONE serving of the probe's waves through the slots and the pages
    with the served routing recorded, read against the sound reference
    UNDER THAT ROUTING (``ok``) and against the reference with each of the
    builder's ten controls planted: each reads ``ok: false``: the
    arithmetic's controls by the logits, the router's by
    ``routing_off_share`` (under the served routing its logits are the
    sound ones).  (One test: the serving is the cost.)"""
    from benchmark.builders import serve_nemotron_h as builder
    from benchmark.builders.serve_pangu_moe import probe_inputs
    from deepspeed_tpu.inference.v2 import FastGenScheduler
    config = _small_probe()
    cfg, params = builder.make_model(config, 11, True)
    assert cfg.layer_kinds == ("ffn", "ssd", "ffn", "ssd", "ffn", "ssd",
                               "full")
    assert (cfg.held_experts, cfg.n_routed_experts) == (4, 16)
    inputs = probe_inputs(config["probe"], 11, cfg.vocab_size)
    engine = builder.make_engine(cfg, params, config["engine"], True)
    assert len(builder.CONTROLS) == 10
    # four of the ten (a reference pass each: tests/test_nemotron_h.py
    # reads all ten against the program's reference)
    verdicts = builder.control_verdicts(
        engine, FastGenScheduler(engine), cfg, params, inputs,
        config["probe"], names=("float8_weights", "bf16_state",
                                "router_without_bias", "relu_for_relu2"))
    probe = verdicts.pop("sound")
    assert probe["ok"] and probe["routing_off_share"] == 0, probe
    assert engine.model.routing_sink is None
    assert probe["short"]["rows"] == 2 * 9 and probe["long"]["rows"] == \
        1 * (1 + 32 - 4) and probe["wide"]["rows"] == 4 * 5 + 1 * 4
    assert probe["compared"] == probe["matched"] == 3
    # a quarter of the experts is held: the program's count of the pairs
    # that fell to them is the reference's biased router's
    assert probe["pairs_counted"] == probe["pairs_reference"] > 0
    assert 5 < probe["held_pair_share"] < 60
    assert probe["rel_rms_max"] < 3e-4
    engine.state_manager.check_invariants()
    assert (engine.free_state_slots, engine.free_blocks) == (32, 256)
    for control, probe in verdicts.items():
        assert not probe["ok"], (control, probe)
        if control == "router_without_bias":
            assert probe["routing_off_share"] > 0.02, (control, probe)
            assert probe["rel_rms_max"] < 3e-4, (control, probe)
        else:
            assert probe["rel_rms_median"] \
                > config["probe"]["logit_rel_rms"], control


@beside_the_program
def test_the_builder_serves_the_seeded_bias_as_the_issue_states_it():
    """Nothing between the seed and the probe touches the selection bias:
    the builder's weights hold it as ``init_params`` drew it (normal at the
    configuration's ``router_bias_scale``, a row a routed layer over all
    128 scored experts), the engine's model holds that very tree, and the
    configuration names no set-up phase that would move it."""
    import numpy as np

    from benchmark.builders import serve_nemotron_h as builder
    config = _small_probe()
    config["rehearse"].update(n_routed_experts=16, routed_experts_scored=128,
                              num_experts_per_tok=6)
    cfg, params = builder.make_model(config, 5, True)
    engine = builder.make_engine(cfg, params, config["engine"], True)
    assert engine.model.params is params and engine.model.routing_sink is None
    stacks = params["periods"]
    bias = np.concatenate([np.asarray(stacks[k]["moe"]["router_bias"])
                           for k in stacks if "moe" in stacks[k]])
    assert bias.shape == (3, 128)      # one block: three routed layers
    assert 0.75 < bias.std() / CONFIG["router_bias_scale"] < 1.25
    assert abs(bias.mean()) < 0.005
    again = builder.make_model(config, 5, True)[1]["periods"]
    assert all(np.array_equal(np.asarray(again[k]["moe"]["router_bias"]),
                              np.asarray(stacks[k]["moe"]["router_bias"]))
               for k in stacks if "moe" in stacks[k])
    assert not {"balance", "balance_why"} & (set(CONFIG)
                                             | set(CONFIG["rehearse"]))
    assert set(CONFIG["departures"]) == {"seeded_weights", "experts_held",
                                         "context", "kernels"}
    assert "NOT even" in CONFIG["assumed"]["router_bias_scale"]
    for key in ("balance_router", "with_bias"):
        assert not hasattr(builder, key)


@beside_the_program
def test_the_benchmarks_reference_is_the_programs_reference():
    """The benchmark keeps its own copy, run a layer at a time; at a small
    size it gives what ``deepspeed_tpu/models/nemotron_h_reference.py``
    gives, and neither imports anything of the program."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.core import meta

    from benchmark import reference_nemotron_h as copy
    from benchmark.builders.serve_nemotron_h import (reference_sizes,
                                                     source_of)
    from deepspeed_tpu.models import nemotron_h_reference as plain
    from deepspeed_tpu.models.nemotron_h import NemotronHForCausalLM
    for module in (copy, plain):
        with open(module.__file__) as f:
            code = f.read().split('"""', 2)[2]
        assert "deepspeed_tpu" not in code and "import" in code
        assert "from ." not in code and "pallas" not in code
        assert 'default_matmul_precision("highest")' in code
    model = NemotronHForCausalLM(source_of(CONFIG, True), first_layer=6,
                                 dtype=jnp.float32)
    params = meta.unbox(model.init_params(jax.random.key(1)))
    tokens = np.random.default_rng(0).integers(0, 160, 37)
    sizes = reference_sizes(model.cfg)
    assert sizes == plain.sizes_of(model.cfg)
    got, pairs, off = copy.forward(params, tokens, sizes)
    want, counts, _ = plain.forward(params, jnp.asarray(tokens), sizes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert pairs.shape == (6, 37) and 0 <= int(pairs.min()) \
        and int(pairs.max()) <= 3 and not np.asarray(off).any()
    np.testing.assert_array_equal(np.asarray(pairs), np.asarray(counts))
    # the controls of the nearest precisions below the configuration's
    for low, least in (({"weight_precision": jnp.float8_e4m3fn}, 0.02),
                       ({"state_precision": jnp.bfloat16}, 2e-4)):
        rough = copy.forward(params, tokens, sizes, **low)[0]
        assert float(jnp.sqrt(jnp.mean((rough - got) ** 2)
                              / jnp.mean(got ** 2))) > least, low
    # the copy's own argument: under a routing handed in, a routed layer
    # multiplies THOSE experts (the router's own choice is still counted)
    routing = np.zeros((37, 6, 3), np.int32) + np.arange(3)  # experts 0-2
    forced, pairs_f, off = copy.forward(params, tokens, sizes,
                                        routing=routing)
    np.testing.assert_array_equal(np.asarray(pairs_f)[0],
                                  np.asarray(pairs)[0])  # the same input
    assert np.asarray(off).mean() > 0.5
    assert float(jnp.max(jnp.abs(forced - got))) > 1e-3


@beside_the_program
def test_the_harness_rehearses_the_cell():
    """``benchmark.run --rehearse`` of the cell on the CPU at the debug
    widths, under a mix cut to a test's size by hand (four callers, eight
    new tokens): the builder, the probe (cut to the rehearsal's size), the
    hints and the driver resolve; every time and rate comes back as
    ``null``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [ROOT] + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    run = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--rehearse", "--seed", str(2 ** 31 + 50), "--seconds", "1",
         "--trace", "0", "--traffic-set", "clients=4",
         "--traffic-set", "set_size=4",
         "--traffic-set", 'new_tokens={"dist":"uniform","min":8,"max":8}',
         "--traffic-set",
         'warmup={"min_seconds":0.5,"quiet_steps":16,"max_seconds":200}',
         "--traffic-set", "drain_s=20"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-2000:]
    result = json.loads(run.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 4
    assert result["device"]["platform"] == "cpu"
    assert set(result["metrics"]) == {"serve_tok_s", "setup_s"}
    assert all(m["value"] is None for m in result["metrics"].values())
    built = next(line for line in run.stdout.splitlines()
                 if line.startswith("built:"))
    assert "'experts_held': 4" in built and "'ok': True" in built
    assert "'state_slots': 256" in built
