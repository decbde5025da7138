"""The Ling-3.0 (``bailing_hybrid``) family's files in the benchmark: what
is cut and what is kept, the assumed items, the traffic mix, the count
functions against numbers worked by hand, the new reader on made-up steps,
the probe under the served routing and its seven controls at a small size,
a rehearsal of the cell,
and the benchmark's copy of the reference against the program's."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "serve.reason-kda-closed256"
NAME = "ling-3.0-flash-serve-7l-ep16"
MIX = "reason-kda-closed256"
#: the accepted readings this cell reports under names of its own
OWN = {"kda_slots_peak_share": "ssm_slots_peak_share",
       "mla_attn_time_share.kda": "mla_attn_time_share",
       "moe_expert_time_share.kda": "moe_expert_time_share",
       "moe_held_pair_share.kda": "moe_held_pair_share",
       "moe_expert_load_imbalance.kda": "moe_expert_load_imbalance",
       "moe_experts_touched_share.kda": "moe_experts_touched_share"}
NEW = ("kda_time_share", "kda_decode_roofline", "kda_prefill_roofline",
       "kda_slots_peak_share", "mla_decode_roofline.kda",
       "mla_attn_time_share.kda", "moe_expert_roofline.kda",
       "moe_expert_time_share.kda", "moe_held_pair_share.kda",
       "moe_expert_load_imbalance.kda", "moe_experts_touched_share.kda")
JOINED = {"kv_pages_peak_share", "kv_fill_share", "compiles_in_window.serve",
          "program_compile_s", "program_trace_lower_s",
          "programs_on_path.setup", "program_cache_hit_share.setup",
          "sched_tokens_per_step", "budget_fill_share"}


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CONFIG = load("configs", NAME + ".json")
PUBLISHED = load("published", "inclusionai-ling-3.0-flash.json")
TRAFFIC = load("traffic", MIX + ".json")
LATTICE = load("lattices", NAME + ".json")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)


def test_four_keys_are_cut_and_every_width_is_kept():
    assert CONFIG["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                 "num_experts", "vocab_size"]
    assert CONFIG["reduced_from"] == {
        "num_hidden_layers": 42, "first_k_dense_replace": 2,
        "num_experts": 512, "vocab_size": 157184}
    assert (CONFIG["num_hidden_layers"], CONFIG["first_k_dense_replace"],
            CONFIG["num_experts"], CONFIG["vocab_size"]) == (7, 1, 32, 39296)
    assert CONFIG["deployment_chips_per_layer"] == 16
    assert CONFIG["routed_experts_scored"] == 512 == 16 * CONFIG["num_experts"]
    assert (CONFIG["first_layer"], CONFIG["experts_first"]) == (1, 0)
    for key, value in PUBLISHED["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    widths = set(PUBLISHED["widths"])
    assert widths == {k for k in PUBLISHED["config"]
                      if k.endswith("_dim") or k.endswith("_rank")} | {
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "moe_shared_expert_intermediate_size", "num_experts_per_tok",
        "n_group", "topk_group", "short_conv_kernel_size"}
    assert not widths & set(CONFIG["reduced"])
    assert (PUBLISHED["experts_key"], PUBLISHED["leading_dense_key"],
            PUBLISHED["layer_period"]) == (
        "num_experts", "first_k_dense_replace", 6)
    # one whole period behind the dense layer, a quarter of the vocabulary
    # in whole lane tiles, the limit lists whole
    assert CONFIG["num_hidden_layers"] - CONFIG["first_k_dense_replace"] == 6
    assert CONFIG["vocab_size"] * 4 == 157184 and CONFIG["vocab_size"] \
        % 128 == 0 and (157184 // 8) % 128
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert len(CONFIG[key]) == 42 and not any(CONFIG[key][1:8]), key
        assert any(CONFIG[key])
    assert CONFIG["num_nextn_predict_layers"] == 1
    assert "neither built nor run" in \
        CONFIG["departures"]["multi_token_prediction"]
    assert "no published checkpoint" in CONFIG["departures"]["seeded_weights"]
    assert "16-chip expert-parallel" in CONFIG["deployment"]


def test_every_assumed_item_has_its_why():
    assert set(PUBLISHED["assumed"]) == {
        "kda_gate_form", "kda_output_gate", "qk_norm_form",
        "router_group_score", "router_bias_scale", "kda_state_dtype",
        "kda_conv_bias"}
    for key, item in PUBLISHED["assumed"].items():
        assert len(item["why"]) >= 40, key
        assert CONFIG[key] == item["value"]
        assert CONFIG["assumed"][key] == item["why"]
    from deepspeed_tpu.models import bailing_hybrid
    assert bailing_hybrid.BIAS_SCALE == CONFIG["router_bias_scale"]


def test_the_published_file_is_the_catalogs_entry():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog in this environment")
    with open(path) as f:
        entry = next(json.loads(line) for line in f
                     if '"name": "Ling-3.0-flash"' in line)
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
    # the other 256-caller cells' lengths and callers
    for other in ("reason-closed256", "reason-swa-closed256",
                  "reason-ssm-closed256", "reason-delta-closed256",
                  "reason-moe-closed256"):
        theirs = load("traffic", other + ".json")
        for key in ("clients", "set_size", "ramp_per_step", "prompt_len",
                    "new_tokens", "trace_slice_s"):
            assert TRAFFIC[key] == theirs[key], (other, key)


def test_the_cell_and_its_metrics_are_listed():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, MIX, 1)
    names = [w["name"] for w in SPEC["workloads"]]
    assert names.index(CELL) > names.index("serve.reason-moe-closed256")
    # eight cells with this one, still one on four chips (a later family
    # comes after)
    assert names.index(CELL) == 7 and sum(
        w["chips"] == 4 for w in SPEC["workloads"][:8]) == 1
    before = [c["name"] for c in SPEC["configs"]]
    entry = SPEC["configs"][before.index(NAME)]
    assert entry["reduced"] == CONFIG["reduced"]
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    per_layer = {m["name"]: m for m in SPEC["per_layer"]}
    listed = [m["name"] for m in SPEC["per_layer"]]
    at = listed.index(NEW[0])
    assert tuple(listed[at:at + len(NEW)]) == NEW
    assert "moe_expert_roofline.whole" in listed[:at]
    served = {n for n in before[:before.index(NAME)] if "serve" in n}
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL], name
        assert per_layer[name]["moves"] == "serve_tok_s"
        metric = load("metrics", name + ".json")
        assert (metric["unit"], metric["layer"], metric["better"],
                metric["source"]) == (
            per_layer[name]["unit"], per_layer[name]["layer"],
            per_layer[name]["better"], per_layer[name]["source"])
        # the six serving configurations that were here, each its reason
        assert set(metric["not_reported"]) == served and len(served) == 6
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
            ("kda_decode_roofline", "kda_decode", "^kda_state_update_decode"),
            ("kda_prefill_roofline", "kda_prefill", "^kda_chunk_prefill"),
            ("mla_decode_roofline.kda", "latent", "^mla_attention_decode"),
            ("moe_expert_roofline.kda", "experts", "^moe_expert_ffn")):
        how = load("metrics", name + ".json")
        assert (how["reader"], how["args"]) == (
            "bailing_hybrid_roofline", {"patterns": [pattern], "kind": kind})
        assert how["unit"] == "%" and how["better"] == "higher"
    assert load("metrics", "kda_time_share.json")["args"] == {
        "patterns": ["^kda_"], "of": "busy"}
    listed = {m["name"] for m in SPEC["per_layer"] + SPEC["end_to_end"]
              if CELL in m.get("workloads", [])}
    end_to_end = {m["name"] for m in SPEC["end_to_end"]}
    # tokens a second and the set-up, no tail in this PR
    assert listed & end_to_end == {"serve_tok_s"}
    for name in listed - end_to_end:
        assert per_layer[name]["moves"] in ("serve_tok_s", "setup_s"), name
    assert listed - end_to_end == JOINED | set(NEW)
    for name in JOINED:                 # appended after the cells that were
        lists = per_layer[name]["workloads"]
        assert lists.index(CELL) > lists.index(
            "serve.reason-moe-closed256"), name
    # every metric that was here, written for the cell's driver, is joined
    # or left out with its reason
    for m in SPEC["per_layer"][:at]:
        drivers = load("metrics", m["name"] + ".json")["drivers"]
        if "serve_closed_loop" in drivers and m["name"] not in JOINED:
            assert len(CONFIG["not_reported"][m["name"]]) >= 20, m["name"]
    assert not listed & set(CONFIG["not_reported"])
    assert CONFIG["routed_pairs_per_token"] == 8 * 6
    assert CONFIG["mean_share_of_pairs_a_held_expert_and_layer"] == 1 / 192
    assert CONFIG["held_experts_times_layers"] == 32 * 6


def test_counts_worked_by_hand():
    """ISSUE 50's arithmetic: a KDA mixer 52.6M, the latent mixer 31.9M, an
    expert 5.90M, 1.77B parameters in all = 3.54 GB; a slot 13.0 MB, the
    state pool 3.35 GB, the latent pool 0.67 GB; a decode step's states 6.4
    GB; and the program's own count agrees."""
    from benchmark import flops_bailing_hybrid as flops
    from benchmark.builders.serve_bailing_hybrid import source_of
    from deepspeed_tpu.models.bailing_hybrid import bailing_hybrid_config
    c = CONFIG
    assert flops.layer_kinds(c) == ["kda"] * 4 + ["latent", "kda", "kda"]
    assert (flops.kda_layers(c), flops.latent_layers(c),
            flops.routed_layers(c)) == (6, 1, 6)
    kda = 4 * 2560 * 4096 + 4096 * 2560 + 2 * 2560 * 32 \
        + 4 * 12288 + 32 + 4096 + 128
    assert flops.kda_params(c) == kda and round(kda / 1e6, 1) == 52.6
    latent = 2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 4096 * 2560
    assert flops.latent_params(c) == latent and round(latent / 1e6, 1) == 31.9
    assert flops.expert_params(c) == 3 * 2560 * 768 == 5_898_240
    routed = 2560 * 512 + 512 + 5_898_240 * (1 + 32)
    assert flops.feed_forward_params(c, True) == routed
    assert flops.feed_forward_params(c, False) == 3 * 2560 * 6144
    total = 6 * kda + latent + 3 * 2560 * 6144 + 6 * routed \
        + 2 * 39296 * 2560
    assert flops.total_params(c) == total
    assert round(total / 1e9, 2) == 1.77 and round(2 * total / 1e9, 2) == 3.54
    # the program counts matrices, A_log and dt_bias: the 15 norms' and the
    # 6 output norms' gains and the router's bias left out
    program = bailing_hybrid_config(source_of(c, False),
                                    first_layer=c["first_layer"]).n_params()
    assert program == total - 6 * 128 - 6 * 512
    assert flops.state_bytes(c) == 128 * 4096 * 4 == 2_097_152
    assert flops.conv_tail_bytes(c) == 3 * 12288 * 2
    slot = 6 * (2_097_152 + 73_728)
    assert flops.slot_bytes(c) == slot and round(slot / 1e6, 1) == 13.0
    assert round(257 * slot / 1e9, 2) == 3.35
    eng = c["engine"]
    page = eng["page_size"] * 640 * 2        # the plane padded to lane tiles
    assert round((eng["num_pages"] + 1) * page / 1e9, 2) == 0.67
    # weights + state pool + latent pool: 45% of the chip's 16.9e9 B
    assert 0.44 < (2 * total + 257 * slot + 8193 * page) / 16.9e9 < 0.46
    # a decode step: 256 rows x 6 layers x a state read and written
    operands = (5 * 4096 + 32) * 4
    assert flops.update_decode_bytes(c, 256) \
        == 6 * 256 * (2 * 2_097_152 + operands)
    assert round(6 * 256 * 2 * 2_097_152 / 1e9, 1) == 6.4
    assert flops.chunk_prefill_bytes(c, 2, 200) \
        == 6 * (2 * 2 * 2_097_152 + 200 * operands)
    assert flops.chunk_prefill_ops(c, 200) \
        == 6 * 200 * 32 * (4 * 16 * 128 + 6 * 128 * 128)
    # ONE latent layer: 576 values a context token, 32 heads
    assert flops.mla_decode_bytes(c, 190_000) == 190_000 * 576 * 2
    assert flops.mla_decode_flops(c, 190_000) \
        == 2 * 32 * (576 + 512) * 190_000
    # a step's 1,536 pairs of 256 rows x 48 / 16 over 32 x 6 experts
    assert flops.grouped_expert_bytes(c, 188, 768) \
        == 188 * 5_898_240 * 2 + 768 * 2 * 2560 * 2
    assert flops.grouped_expert_flops(c, 768) == 2 * 768 * 5_898_240
    assert round(192 * 5_898_240 * 2 / 1e9, 1) == 2.3


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


@pytest.mark.parametrize("name", ["kda_decode_roofline",
                                  "kda_prefill_roofline",
                                  "mla_decode_roofline.kda",
                                  "moe_expert_roofline.kda"])
def test_the_roofline_reader_reads_the_programs_counts(monkeypatch, name):
    """Two traced steps whose ``fastgen.step`` spans carry the program's
    counts (the third lies outside the slice); a program without the
    attributes (the parent) gives None, and does not raise."""
    from benchmark import flops_bailing_hybrid as flops
    from benchmark.readers import bailing_hybrid_roofline as reader
    _tracer(monkeypatch, [
        _span(12.0, {"kda_rows_decode": 256, "kda_tokens_prefill": 0,
                     "prefill_rows": 0, "moe_experts_touched": 190,
                     "moe_pairs_here": 770}),
        _span(13.0, {"kda_rows_decode": 254, "kda_tokens_prefill": 200,
                     "prefill_rows": 2, "moe_experts_touched": 188,
                     "moe_pairs_here": 1400}),
        _span(25.0, {"kda_rows_decode": 9, "kda_tokens_prefill": 9,
                     "prefill_rows": 9, "moe_experts_touched": 9,
                     "moe_pairs_here": 9})])
    args = load("metrics", name + ".json")["args"]
    facts = {"step_decode_context": [5, 190_000, 191_000, 7]}
    share = reader.read(_Ctx, facts, args)
    c = CONFIG
    least = {
        "kda_decode_roofline": flops.update_decode_bytes(c, 510) / 819e9,
        "kda_prefill_roofline": max(
            flops.chunk_prefill_bytes(c, 2, 200) / 819e9,
            flops.chunk_prefill_ops(c, 200) / 197e12),
        "mla_decode_roofline.kda": sum(
            flops.mla_decode_bytes(c, n) / 819e9
            for n in (190_000, 191_000)),
        "moe_expert_roofline.kda": (
            flops.grouped_expert_bytes(c, 190, 770)
            + flops.grouped_expert_bytes(c, 188, 1400)) / 819e9}[name]
    assert share == pytest.approx(100 * least / 0.010)
    # at 32 heads the latent decode is bound by bytes, not operations
    assert flops.mla_decode_flops(c, 1000) / 197e12 \
        < flops.mla_decode_bytes(c, 1000) / 819e9
    _tracer(monkeypatch, [_span(12.0, {"tokens": 256})])
    if name != "mla_decode_roofline.kda":
        assert reader.read(_Ctx, facts, args) is None
    assert reader.read(_Ctx, {"step_decode_context": []}, args) is None


def test_the_span_ring_metrics_read_the_programs_counts(monkeypatch):
    from benchmark.readers import span_peak_share, span_ring
    _tracer(monkeypatch, [
        _span(12.0, {"moe_experts_touched": 190, "moe_pairs_here": 768,
                     "moe_tokens": 256, "moe_expert_load_max": 11,
                     "ssm_slots_held": 255}),
        _span(13.0, {"moe_experts_touched": 186, "moe_pairs_here": 780,
                     "moe_tokens": 256, "moe_expert_load_max": 13,
                     "ssm_slots_held": 256})])

    def read(name, reader=span_ring):
        return reader.read(_Ctx, {}, load("metrics", name + ".json")["args"])

    assert read("moe_experts_touched_share.kda") == pytest.approx(
        100 * (190 + 186) / (2 * 192))
    assert read("moe_held_pair_share.kda") == pytest.approx(
        100 * (768 + 780) / (512 * 48))
    assert read("moe_expert_load_imbalance.kda") == pytest.approx(
        24 / ((768 + 780) / 192))
    assert read("kda_slots_peak_share", span_peak_share) \
        == pytest.approx(100.0)
    _tracer(monkeypatch, [_span(12.0, {"tokens": 256})])
    assert read("moe_held_pair_share.kda") is None
    assert read("kda_slots_peak_share", span_peak_share) is None


def test_the_probes_tolerances_carry_their_reasons():
    pr = CONFIG["probe"]
    for key in ("logit_rel_rms", "outlier", "margin", "pairs",
                "sequence_outlier", "routing_off", "min_compared",
                "lengths", "waves", "programs"):
        assert len(pr[key + "_reason"]) >= 150, key
        assert "TO BE READ" not in pr[key + "_reason"], key
    # each limit of the comparison lies between its two readings, which its
    # reason gives (PERF.md has the runs)
    for key in ("logit_rel_rms", "outlier", "pairs", "routing_off"):
        assert "chip" in pr[key + "_reason"], key
    assert 0 < pr["logit_rel_rms"] <= pr["outlier_rel_rms"] < 0.2
    # a fault in one sequence's slot is seen on the chip: the term refuses
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
    assert [rows, 1, 40, False, "mixed", 1, 128, 8, True, True] in hints
    # every decode step runs the callers' row bucket (the drain's last
    # rows the 4- and the 1-row one), a lone prompt is a segment of one
    # row: thirteen programs (35 never fitted the compile cache, PERF.md
    # section 6).  The probe's own, formed under ``routing_sink``, are no
    # step of the mix: the plain forwards, and a sampled step of ONE prompt
    assert LATTICE["s_buckets"] == [1, 4, rows] and len(hints) == 13
    assert lattice.bucket_s(5) == lattice.bucket_s(rows) == rows
    assert (lattice.bucket_s(1), lattice.bucket_s(2)) == (1, 4)
    own = CONFIG["probe"]["programs"]
    assert [k for k in own if len(k) > 4] \
        == [[1, 128, 8, True, "sample", True]]
    assert not [k for k in hints if k in own]


def _small_probe():
    """The configuration at its debug widths with a probe and an engine cut
    to a test's size."""
    config = json.loads(json.dumps(CONFIG))
    config["rehearse"].pop("probe_cut")
    config["engine"].update(page_size=16, num_pages=256, max_sequences=32,
                            token_budget=256, max_seq_len=512)
    config["probe"].update(
        prompts=2, min_len=20, max_len=40, decode_steps=8, long_rows=1,
        long_steps=32, wide_copies=2, wide_at=[14, 26], wide_steps=2,
        min_compared=2, programs=[],
        # float32 at debug widths: the limits of a rounding of sums, and
        # no near-tie falls the other way
        logit_rel_rms=3e-4, outlier_rel_rms=3e-3, margin=1e-3,
        outlier_share=0.0, sequence_outlier_share=0.5,
        routing_off_share=0.0)
    return config


def test_the_probe_passes_the_program_and_refuses_each_control():
    """ONE serving of the probe's waves through the slots and the latent
    pages with the served routing recorded, read against the sound
    reference UNDER THAT ROUTING (``ok``) and against the reference with
    each of the builder's seven controls planted: the sound program against
    a faulty reference reads what a faulty program reads against the sound
    one, and each reads ``ok: false``: the arithmetic's controls by the
    logits, the router's by ``routing_off_share`` (under the served routing
    their logits are the sound ones: the term is what sees a router of
    another rule).  (One test: the serving is the cost.)"""
    from benchmark.builders import serve_bailing_hybrid as builder
    from benchmark.builders.serve_pangu_moe import probe_inputs
    from deepspeed_tpu.inference.v2 import FastGenScheduler
    config = _small_probe()
    cfg, params = builder.make_model(config, 11, True)
    assert cfg.layer_kinds == ("kda",) * 4 + ("latent", "kda", "kda")
    assert (cfg.held_experts, cfg.n_routed_experts) == (4, 16)
    inputs = probe_inputs(config["probe"], 11, cfg.vocab_size)
    engine = builder.make_engine(cfg, params, config["engine"], True)
    assert set(builder.CONTROLS) == {
        "float8_weights", "bf16_state", "one_decay_a_head",
        "router_without_groups", "router_without_bias",
        "weights_from_the_biased_scores", "no_rope_on_the_latent_layer"}
    verdicts = builder.control_verdicts(
        engine, FastGenScheduler(engine), cfg, params, inputs,
        config["probe"])
    probe = verdicts.pop("sound")
    assert probe["ok"] and probe["routing_off_share"] == 0, probe
    assert engine.model.routing_sink is None
    assert probe["short"]["rows"] == 2 * 9 and probe["long"]["rows"] == \
        1 * (1 + 32 - 4) and probe["wide"]["rows"] == 4 * 5 + 1 * 4
    assert probe["compared"] == probe["matched"] == 3
    # a quarter of the experts is held: the program's count of the pairs
    # that fell to them is the reference's grouped, biased router's
    assert probe["pairs_counted"] == probe["pairs_reference"] > 0
    assert 5 < probe["held_pair_share"] < 60
    assert probe["rel_rms_max"] < 3e-4
    state = engine.state_manager
    state.check_invariants()
    assert (engine.free_state_slots, engine.free_blocks) == (32, 256)
    routers = {"router_without_groups", "router_without_bias"}
    for control, probe in verdicts.items():
        assert not probe["ok"], (control, probe)
        if control in routers:
            assert probe["routing_off_share"] > 0.1, (control, probe)
            assert probe["rel_rms_max"] < 3e-4, (control, probe)
        else:
            assert probe["rel_rms_median"] \
                > config["probe"]["logit_rel_rms"], control


def test_the_benchmarks_reference_is_the_programs_reference():
    """The benchmark keeps its own copy, run a layer at a time; at a small
    size it gives what ``deepspeed_tpu/models/bailing_hybrid_reference.py``
    gives, and neither imports anything of the program."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.core import meta

    from benchmark import reference_bailing_hybrid as copy
    from benchmark.builders.serve_bailing_hybrid import (reference_sizes,
                                                         source_of)
    from deepspeed_tpu.models import bailing_hybrid_reference as plain
    from deepspeed_tpu.models.bailing_hybrid import BailingHybridForCausalLM
    for module in (copy, plain):
        with open(module.__file__) as f:
            code = f.read().split('"""', 2)[2]
        assert "deepspeed_tpu" not in code and "import" in code
        assert "from ." not in code and "pallas" not in code
    model = BailingHybridForCausalLM(source_of(CONFIG, True), first_layer=1,
                                     dtype=jnp.float32)
    params = meta.unbox(model.init_params(jax.random.key(1)))
    tokens = np.random.default_rng(0).integers(0, 160, 37)
    sizes = reference_sizes(model.cfg)
    assert sizes == plain.sizes_of(model.cfg)
    got, pairs, off = copy.forward(params, tokens, sizes)
    want, counts = plain.forward(params, jnp.asarray(tokens), sizes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert pairs.shape == (6, 37) and 0 <= int(pairs.min()) \
        and int(pairs.max()) <= 3 and not np.asarray(off).any()
    np.testing.assert_array_equal(np.asarray(pairs), np.asarray(counts))
    # the controls of the nearest precisions below the configuration's
    for low in ({"weight_precision": jnp.float8_e4m3fn},
                {"state_precision": jnp.bfloat16}):
        rough = copy.forward(params, tokens, sizes, **low)[0]
        assert float(jnp.sqrt(jnp.mean((rough - got) ** 2)
                              / jnp.mean(got ** 2))) > 0.02, low
    # the copy's own argument: under a routing handed in, a routed layer
    # multiplies THOSE experts (the router's own choice is still counted)
    routing = np.zeros((37, 6, 3), np.int32) + np.arange(3)  # experts 0-2
    forced, pairs_f, off = copy.forward(params, tokens, sizes,
                                        routing=routing)
    np.testing.assert_array_equal(np.asarray(pairs_f)[0],
                                  np.asarray(pairs)[0])  # the same input
    assert np.asarray(off).mean() > 0.5
    assert float(jnp.max(jnp.abs(forced - got))) > 1e-3


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
