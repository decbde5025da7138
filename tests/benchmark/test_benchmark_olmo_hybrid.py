"""The Olmo-Hybrid family's files in the benchmark: that depth alone is cut,
the traffic mix, the count functions against numbers worked by hand, the new
reader on made-up steps, the probe and its controls at a small size, a
rehearsal of the cell, and the benchmark's copy of the reference against the
program's."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "serve.reason-delta-closed256"
NAME = "olmo-hybrid-7b-serve-4l"
NEW = ("delta_time_share", "delta_decode_roofline", "delta_prefill_roofline",
       "hybrid_attention_roofline", "delta_slots_peak_share")


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CONFIG = load("configs", NAME + ".json")
PUBLISHED = load("published", "allenai-olmo-hybrid-7b.json")
TRAFFIC = load("traffic", "reason-delta-closed256.json")
LATTICE = load("lattices", NAME + ".json")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)


def test_depth_alone_is_cut_to_one_whole_period():
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert CONFIG["reduced_from"] == {"num_hidden_layers": 32}
    assert CONFIG["num_hidden_layers"] == 4 == PUBLISHED["layer_period"]
    assert "deployment_chips_per_layer" not in CONFIG
    for key, value in PUBLISHED["config"].items():
        if key != "num_hidden_layers":
            assert CONFIG[key] == value, key
    # the nested groups are copied whole; the layers that are run are the
    # list's first four: one period
    assert len(CONFIG["layer_types"]) == 32
    assert CONFIG["layer_types"][:4] == ["linear_attention"] * 3 \
        + ["full_attention"]
    assert CONFIG["rope_parameters"] == {"rope_theta": None}
    assert (CONFIG["num_attention_heads"], CONFIG["num_key_value_heads"],
            CONFIG["linear_num_key_heads"], CONFIG["linear_num_value_heads"],
            CONFIG["vocab_size"]) == (30, 30, 30, 30, 100352)
    assert set(PUBLISHED["widths"]) == {
        "hidden_size", "intermediate_size", "linear_key_head_dim",
        "linear_value_head_dim", "linear_conv_kernel_dim", "head_dim"}
    # every _dim the family has is a width
    assert {k for k in PUBLISHED["config"] if k.endswith("_dim")} \
        <= set(PUBLISHED["widths"])
    entry = next(c for c in SPEC["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == CONFIG["source"] == PUBLISHED["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    # appended after the configurations that were here (a later family
    # comes after)
    configs = [c["name"] for c in SPEC["configs"]]
    assert configs.index(NAME) > configs.index("jamba2-3b-serve-28l")
    for key in ("weights", "context", "kernels", "depth"):
        assert len(CONFIG["departures"][key]) >= 80
    assert "0.001" in CONFIG["departures"]["weights"] \
        and "log(U(1, 16))" in CONFIG["departures"]["weights"]


def test_every_assumed_item_has_its_why():
    assert set(PUBLISHED["assumed"]) == {
        "head_dim", "position_encoding", "block_norm_order", "qk_norm",
        "linear_conv_bias", "delta_state_dtype"}
    for key, item in PUBLISHED["assumed"].items():
        assert len(item["why"]) >= 40, key
        assert CONFIG[key] == item["value"]
        assert CONFIG["assumed"][key] == item["why"]
    assert CONFIG["head_dim"] == CONFIG["hidden_size"] \
        // CONFIG["num_attention_heads"] == 128
    assert CONFIG["delta_state_dtype"] == "float32"


def test_the_published_file_is_the_catalogs_entry():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog in this environment")
    with open(path) as f:
        entry = next(json.loads(line) for line in f
                     if '"name": "Olmo-Hybrid-7B"' in line)
    assert PUBLISHED["config"] == entry["config"]
    assert PUBLISHED["source"] == entry["source_url"]
    assert entry["head_dim"] is None and entry["layers"] == 32


def test_the_traffic_file_holds_the_mix_and_no_engine_key():
    """The lengths and callers of the other 256-caller cells, so that the
    four differ by architecture alone."""
    assert TRAFFIC["driver"] == "serve_closed_loop"
    ssm = load("traffic", "reason-ssm-closed256.json")
    for key in ("clients", "ramp_per_step", "set_size", "prompt_len",
                "new_tokens", "drain_s", "trace_slice_s"):
        assert TRAFFIC[key] == ssm[key], key
    assert (TRAFFIC["clients"], TRAFFIC["set_size"],
            TRAFFIC["ramp_per_step"]) == (256, 256, 4)
    assert TRAFFIC["prompt_len"] == {"dist": "uniform", "min": 65, "max": 128}
    assert TRAFFIC["new_tokens"] == {"dist": "loguniform", "min": 512,
                                     "max": 2048}
    warm = TRAFFIC["warmup"]
    assert (warm["min_seconds"], warm["quiet_steps"], warm["max_seconds"],
            warm["hints"]) == (50.0, 64, 600.0, "reason-delta-closed256")
    others = {load("traffic", n)["set_seed"]
              for n in os.listdir(os.path.join(BENCH, "traffic"))
              if n != "reason-delta-closed256.json"
              and "set_seed" in load("traffic", n)}
    assert TRAFFIC["set_seed"] not in others
    engine_keys = set(CONFIG["engine"]) | {"engine", "serving", "lattice"}
    assert not engine_keys & set(TRAFFIC)
    assert TRAFFIC["prompt_len"]["max"] + TRAFFIC["new_tokens"]["max"] \
        <= CONFIG["engine"]["max_seq_len"]
    # one caller a state slot
    assert TRAFFIC["clients"] == CONFIG["engine"]["max_sequences"]


def test_the_cell_and_its_metrics_are_listed():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "reason-delta-closed256", 1)
    # appended after the cells that were here (a later family comes after)
    names = [w["name"] for w in SPEC["workloads"]]
    assert names.index(CELL) > names.index("serve.reason-ssm-closed256")
    assert len(cell["why"]) <= 200
    per_layer = {m["name"]: m for m in SPEC["per_layer"]}
    listed = [m["name"] for m in SPEC["per_layer"]]
    at = listed.index(NEW[0])
    assert listed[at:at + len(NEW)] == list(NEW)
    assert "ssm_slots_peak_share" in listed[:at]
    before = [c["name"] for c in SPEC["configs"]]
    served = {n for n in before[:before.index(NAME)] if "serve" in n}
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL], name
        assert per_layer[name]["moves"] == "serve_tok_s"
        metric = load("metrics", name + ".json")
        assert metric["unit"] == "%" == per_layer[name]["unit"]
        assert (metric["layer"], metric["better"], metric["source"]) == (
            per_layer[name]["layer"], per_layer[name]["better"],
            per_layer[name]["source"])
        # the four serving configurations that were here, each with its
        # reason
        assert set(metric["not_reported"]) == served and len(served) == 4
        assert all(len(w) >= 20 for w in metric["not_reported"].values())
    assert load("metrics", "delta_time_share.json")["args"] == {
        "patterns": ["^delta_"], "of": "busy"}
    # ssm_slots_peak_share's reading, under a name of this cell's own
    assert load("metrics", "delta_slots_peak_share.json")["args"] \
        == load("metrics", "ssm_slots_peak_share.json")["args"]
    assert "test" in CONFIG["not_reported"]["ssm_slots_peak_share"]
    listed = {m["name"] for m in SPEC["per_layer"] + SPEC["end_to_end"]
              if CELL in m.get("workloads", [])}
    end_to_end = {m["name"] for m in SPEC["end_to_end"]}
    reports = (listed & end_to_end) | {"setup_s"}
    assert {"serve_tok_s", "setup_s"} <= reports
    # every metric that was here, written for the cell's driver, whose
    # ``moves`` the cell reports is joined or left out with a reason
    for m in SPEC["per_layer"][:at]:
        name = m["name"]
        drivers = load("metrics", name + ".json")["drivers"]
        if "serve_closed_loop" in drivers and m["moves"] in reports \
                and name not in CONFIG["not_reported"]:
            assert CELL in m["workloads"], name
            # appended after the cells that were here
            assert m["workloads"].index(CELL) \
                > m["workloads"].index("serve.reason-ssm-closed256"), name
    assert {"kv_pages_peak_share", "kv_fill_share",
            "compiles_in_window.serve", "program_compile_s",
            "program_trace_lower_s", "programs_on_path.setup",
            "program_cache_hit_share.setup", "sched_tokens_per_step",
            "budget_fill_share"} <= listed
    if "itl_p95_ms" in reports:
        assert "paged_attn_time_share" in listed
    else:
        assert "itl_p95_ms" in CONFIG["not_reported"][
            "paged_attn_time_share"]
    assert not listed & set(CONFIG["not_reported"])
    assert {"paged_attention_roofline", "mla_attn_time_share",
            "mla_decode_roofline", "moe_expert_roofline.tok_s",
            "window_attn_time_share", "kv_window_pages_peak_share",
            "mixed_attention_roofline", "ssm_time_share",
            "ssm_decode_roofline", "ssm_prefill_roofline"} \
        <= set(CONFIG["not_reported"])
    for why in CONFIG["not_reported"].values():
        assert len(why) >= 20
    assert "1 of 4" in CONFIG["not_reported"]["paged_attention_roofline"]


def test_the_memory_the_issue_reckons():
    """Weights 3.21 GB, the state pool 1.76 GB, the page pool at the
    issue's 6,144 pages 6.04 GB: 11.0 GB of the chip's 16, over the floor
    of a quarter."""
    from benchmark import flops_olmo_hybrid as flops
    eng = CONFIG["engine"]
    weights = 2 * flops.total_params(CONFIG)
    state = (eng["max_sequences"] + 1) * flops.slot_bytes(CONFIG)
    pages = (6144 + 1) * eng["page_size"] * flops.kv_bytes_per_token(CONFIG)
    assert round(weights / 1e9, 2) == 3.21
    assert round(state / 1e9, 2) == 1.76 and round(pages / 1e9, 2) == 6.04
    assert round((weights + state + pages) / 1e9, 1) == 11.0
    held = (eng["num_pages"] + 1) * eng["page_size"] \
        * flops.kv_bytes_per_token(CONFIG)
    assert 0.25 * 16e9 < weights + state + held < 16e9
    assert "3.21 GB" in CONFIG["deployment"] \
        and "1.76 GB" in CONFIG["deployment"]
    # the mix's longest context fits the pool many times over
    longest = TRAFFIC["prompt_len"]["max"] + TRAFFIC["new_tokens"]["max"]
    assert eng["max_sequences"] * -(-longest // eng["page_size"]) \
        > eng["num_pages"] > eng["max_sequences"] * 12


def test_counts_worked_by_hand():
    """ISSUE 39's arithmetic: a linear mixer 88.7M (66.4M of q, k, v and
    gate, 0.23M of the two gates a head, 22.1M out), a full mixer 59.0M,
    the MLP 126.8M, a period 832.3M, embedding + head 770.7M; 2.28 MB a
    slot and layer, 15,360 B of K/V a token."""
    from benchmark import flops_olmo_hybrid as flops
    c = CONFIG
    assert flops.linear_layers(c) == 3 and flops.full_layers(c) == 1
    assert (flops.key_width(c), flops.value_width(c),
            flops.conv_channels(c)) == (2880, 5760, 11520)
    proj, gates, out = (3840 * (11520 + 5760), 3840 * 60, 5760 * 3840)
    assert (proj, gates, out) == (66_355_200, 230_400, 22_118_400)
    small = 4 * 11520 + 60 + 192
    assert flops.mixer_params(c) == proj + gates + out + small \
        == 88_750_332
    assert flops.attention_params(c) == 4 * 3840 * 3840 + 2 * 3840 \
        == 58_990_080
    assert flops.mlp_params(c) == 126_812_160
    period = 3 * 88_750_332 + 58_990_080 + 4 * 126_812_160
    assert round(period / 1e6, 1) == 832.5          # 832.3M of matrices
    assert flops.total_params(c) == period + 2 * 100352 * 3840 \
        == 1_603_193_076
    assert flops.state_bytes(c) == 96 * 5760 * 4 == 2_211_840
    assert flops.conv_tail_bytes(c) == 3 * 11520 * 2 == 69_120
    assert flops.slot_layer_bytes(c) == 2_280_960
    assert flops.slot_bytes(c) == 6_842_880
    assert flops.kv_bytes_per_token(c) == 15_360
    # a decode step of 256 rows: 3.45 GB through the update
    assert flops.token_operand_bytes(c) == (2 * 2880 + 2 * 5760 + 60) * 4 \
        == 69_360
    assert flops.update_decode_bytes(c, 256) \
        == 3 * 256 * (2 * 2_211_840 + 69_360) == 3_450_654_720
    assert flops.chunk_prefill_bytes(c, 2, 200) \
        == 3 * (2 * 4_423_680 + 200 * 69_360) == 68_158_080
    per_token_head = 2 * 64 * 96 + 2 * 64 * 192 + 6 * 96 * 192
    assert per_token_head == 147_456
    assert flops.chunk_prefill_ops(c, 200) == 3 * 200 * 30 * 147_456
    assert flops.attention_decode_bytes(c, 190_000) == 190_000 * 15_360
    # a bfloat16 state would halve what a row moves: a different result,
    # and the count follows the configuration's dtype, not a kernel's
    assert flops.state_bytes(dict(c, delta_state_dtype="bfloat16")) \
        == 1_105_920
    # the full depth: 24 linear and 8 full layers
    whole = dict(c, num_hidden_layers=32)
    assert (flops.linear_layers(whole), flops.full_layers(whole)) == (24, 8)
    assert round(flops.total_params(whole) / 1e9, 2) == 7.43


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


def _span(end, attrs):
    return ("fastgen.step", end - 0.01, 0.01, 0, 0, attrs, end, None, 0)


def _tracer(monkeypatch, rows):
    import deepspeed_tpu.telemetry as telemetry

    class Tracer:
        def records(self):
            return rows

    monkeypatch.setattr(telemetry, "get_tracer", lambda: Tracer())


def test_delta_roofline_reads_the_programs_counts(monkeypatch):
    """Two traced steps of 256 decode rows, one of which also prefills two
    prompts of 200 true tokens; the step outside the slice is left out; a
    program without the attributes (the parent) gives None, and does not
    raise."""
    from benchmark import flops_olmo_hybrid as flops
    from benchmark.readers import delta_roofline as reader
    rows = [_span(12.0, {"delta_rows_decode": 256, "delta_tokens_prefill": 0,
                         "prefill_rows": 0, "attn_tokens_full": 190_000}),
            _span(13.0, {"delta_rows_decode": 255,
                         "delta_tokens_prefill": 200, "prefill_rows": 2,
                         "attn_tokens_full": 189_000}),
            _span(25.0, {"delta_rows_decode": 9, "delta_tokens_prefill": 9,
                         "prefill_rows": 1, "attn_tokens_full": 77})]
    _tracer(monkeypatch, rows)
    decode = load("metrics", "delta_decode_roofline.json")["args"]
    prefill = load("metrics", "delta_prefill_roofline.json")["args"]
    attend = load("metrics", "hybrid_attention_roofline.json")["args"]
    assert decode == {"patterns": ["^delta_state_update_decode"],
                      "kind": "decode"}
    assert prefill == {"patterns": ["^delta_chunk_prefill"],
                       "kind": "prefill"}
    assert attend == {"patterns": ["^paged_attention"], "kind": "attention"}
    need = flops.update_decode_bytes(CONFIG, 511)
    assert reader.read(_Ctx, {}, decode) == pytest.approx(
        100 * (need / 819e9) / 0.010)
    assert reader.read(_Ctx, {}, decode) == pytest.approx(84.1, abs=0.1)
    # two prompt rows of 200 tokens: 83 us of bytes against 13 us of
    # operations, so the memory bounds it
    bytes_s = flops.chunk_prefill_bytes(CONFIG, 2, 200) / 819e9
    ops_s = flops.chunk_prefill_ops(CONFIG, 200) / 197e12
    assert bytes_s > 5 * ops_s
    assert reader.read(_Ctx, {}, prefill) == pytest.approx(
        100 * bytes_s / 0.010)
    assert reader.read(_Ctx, {}, attend) == pytest.approx(
        100 * (379_000 * 15_360 / 819e9) / 0.010)
    _tracer(monkeypatch, [_span(12.0, {"tokens": 256})])
    for args in (decode, prefill, attend):
        assert reader.read(_Ctx, {}, args) is None


def test_delta_slots_peak_share_takes_the_largest_of_the_slice(monkeypatch):
    from benchmark.readers import span_peak_share as reader
    args = load("metrics", "delta_slots_peak_share.json")["args"]
    _tracer(monkeypatch, [
        _span(12.0, {"ssm_slots_held": 250}),
        _span(13.0, {"ssm_slots_held": 256}),
        _span(25.0, {"ssm_slots_held": 300})])      # outside
    assert reader.read(_Ctx, {}, args) == pytest.approx(100.0)
    _tracer(monkeypatch, [_span(12.0, {"tokens": 256})])
    assert reader.read(_Ctx, {}, args) is None


def test_the_probes_tolerances_carry_their_reasons():
    probe = CONFIG["probe"]
    assert probe["decode_steps"] >= 16 and probe["long_steps"] == 2000
    for key in ("logit_rel_rms", "state_drift", "outlier",
                "sequence_outlier", "margin", "pairs", "lengths", "waves"):
        assert len(probe[key + "_reason"]) >= 80, key
    assert 0 < probe["logit_rel_rms"] < probe["outlier_rel_rms"]
    assert probe["outlier_share"] < probe["sequence_outlier_share"] < 1
    assert probe["min_compared"] >= (probe["prompts"]
                                     + probe["long_rows"]) // 2
    from benchmark.builders.serve_olmo_hybrid import CONTROLS
    assert set(CONTROLS) == {
        "beta_not_doubled", "decay_dropped", "l2norm_dropped",
        "gate_dropped", "qk_norm_dropped", "padded_conv_tail",
        "slot_not_zeroed", "bf16_state"}
    reasons = probe["logit_rel_rms_reason"] + probe["outlier_reason"] \
        + probe["state_drift_reason"]
    for control in ("bfloat16", "beta", "decay", "l2", "gate", "Q/K norm",
                    "tail", "zeroed"):
        assert control in reasons, control
    assert 1.0 < probe["state_drift_limit"] < 1.5
    # the wide steps run in the row bucket and at the page bucket the
    # window's own steps run in, on slots the short wave gave back
    eng = CONFIG["engine"]
    rows = probe["long_rows"] + probe["wide_copies"] * probe["prompts"]
    assert eng["max_sequences"] // 2 < rows <= eng["max_sequences"]
    assert [eng["max_sequences"], 1, max(LATTICE["p_buckets"]), False] \
        in probe["programs"]
    assert probe["max_len"] + probe["long_steps"] <= eng["max_seq_len"]


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
    assert lattice.bucket_p(3) == 8
    hints = load("hints", "reason-delta-closed256.json")["keys"]
    s, q, p = (set(LATTICE[k]) for k in ("s_buckets", "q_buckets",
                                         "p_buckets"))
    for key in hints + LATTICE["keys"] + CONFIG["probe"]["programs"]:
        key = StepKey.parse(key)
        assert key.S in s and key.Q in q and key.P in p, key
    rows = eng["max_sequences"]
    for prev in (rows, 2 * rows):
        assert [rows, 1, 40, False, "chain", prev, True] in hints
    for arrivals in (1, 2, 4):
        assert [rows, 1, 40, False, "mixed", arrivals, 128, 8, True,
                True] in hints


def _small_probe():
    """The configuration at its debug widths with a probe and an engine cut
    to a test's size."""
    config = json.loads(json.dumps(CONFIG))
    config["engine"].update(page_size=16, num_pages=512, max_sequences=32,
                            token_budget=256, max_seq_len=512)
    config["probe"].update(
        prompts=4, min_len=20, max_len=40, decode_steps=8, long_rows=2,
        long_steps=120, wide_copies=4, wide_at=[60, 110], wide_steps=2,
        min_compared=3, programs=[],
        # float32 at debug widths: the limits of a rounding of sums
        logit_rel_rms=3e-4, outlier_rel_rms=3e-3, margin=1e-3,
        # sums of a few float32 ulps: the ratio of two of them is loose
        state_drift_limit=3.0)
    return config


@pytest.fixture(scope="module")
def small_probe():
    from benchmark.builders import serve_olmo_hybrid as builder
    from benchmark.builders.serve_pangu_moe import probe_inputs
    from deepspeed_tpu.inference.v2 import FastGenScheduler
    config = _small_probe()
    cfg, params = builder.make_model(config, 11, True)
    inputs = probe_inputs(config["probe"], 11, cfg.vocab_size)
    engine = builder.make_engine(cfg, params, config["engine"], True)
    verdicts = builder.control_verdicts(
        engine, FastGenScheduler(engine), cfg, params, inputs,
        config["probe"])
    return config, engine, verdicts


def test_the_probe_passes_the_program_through_slots_and_pages(small_probe):
    config, engine, verdicts = small_probe
    probe = verdicts["sound"]
    assert probe["ok"], probe
    assert probe["short"]["rows"] == 4 * 9 and probe["long"]["rows"] == \
        2 * (1 + 120 - 4) and probe["wide"]["rows"] == 16 * 5 + 2 * 4
    assert probe["compared"] == probe["matched"] == 6
    assert probe["pairs_counted"] == probe["pairs_reference"] == 0
    assert probe["rel_rms_max"] < 3e-4
    state = engine.state_manager
    state.check_invariants()
    assert (state.free_state_slots, engine.free_blocks) == (32, 512)


@pytest.mark.parametrize("control", [
    "beta_not_doubled", "decay_dropped", "l2norm_dropped", "gate_dropped",
    "qk_norm_dropped", "padded_conv_tail", "slot_not_zeroed", "bf16_state"])
def test_each_control_fails_the_probe(small_probe, control):
    """The probe's controls, planted in the reference (the sound program
    against a faulty reference reads what a faulty program reads against
    the sound one), against ONE serving of the waves: each reads ``ok:
    false``."""
    config, _, verdicts = small_probe
    probe = verdicts[control]
    assert not probe["ok"], probe
    # (a reference whose keys are not l2-normed overflows under beta up to
    # 2: its reading is no number, which no limit passes either)
    assert not probe["rel_rms_median"] <= config["probe"]["logit_rel_rms"] \
        or probe["outlier_rows"] > config["probe"]["outlier_share"] \
        * probe["rows"]
    if control == "padded_conv_tail":
        # the prompt's own row is before the break: only what follows it
        assert probe["long"]["rel_rms_median"] > 1e-3


def test_the_benchmarks_reference_is_the_programs_reference():
    """The benchmark keeps its own copy; at a small size it gives what
    ``deepspeed_tpu/models/olmo_hybrid_reference.py`` gives, and neither
    imports anything of the program."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.core import meta

    from benchmark import reference_olmo_hybrid as copy
    from benchmark.builders.serve_olmo_hybrid import (reference_sizes,
                                                      source_of)
    from deepspeed_tpu.models import olmo_hybrid_reference as plain
    from deepspeed_tpu.models.olmo_hybrid import OlmoHybridForCausalLM
    for module in (copy, plain):
        with open(module.__file__) as f:
            code = f.read().split('"""', 2)[2]
        assert "deepspeed_tpu" not in code and "import" in code
        assert "from ." not in code and "pallas" not in code
    model = OlmoHybridForCausalLM(source_of(CONFIG, True),
                                  dtype=jnp.float32)
    assert model.cfg.layer_kinds == ("delta", "delta", "delta", "full") * 2
    params = meta.unbox(model.init_params(jax.random.key(1)))
    tokens = np.random.default_rng(0).integers(0, 160, 37)
    sizes = reference_sizes(model.cfg)
    assert sizes == plain.sizes_of(model.cfg)
    got, carry = copy.forward(params, tokens, sizes)
    want, _ = plain.forward(params, jnp.asarray(tokens), sizes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert len(carry) == 6 and carry[0][0].shape == (4, 16, 32)
    assert carry[0][1].shape == (3, 4 * (2 * 16 + 32))
    # the control of the nearest precision below the configuration's: a
    # bfloat16 state moves every row far more than rounding
    rough, _ = copy.forward(params, tokens, sizes,
                            state_precision=jnp.bfloat16)
    assert float(jnp.sqrt(jnp.mean((rough - got) ** 2)
                          / jnp.mean(got ** 2))) > 1e-3


def test_the_harness_rehearses_the_cell():
    """``benchmark.run --rehearse`` of the cell on the CPU at the debug
    widths, under a mix cut to a test's size by hand (four callers, eight
    new tokens): the builder, the probe at its full 2,000 steps, the hints,
    the driver and every metric file resolve; counts come back, every time,
    rate and share as ``null``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [ROOT] + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    run = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--rehearse", "--seed", str(2 ** 31 + 39), "--seconds", "1",
         "--trace", "1", "--traffic-set", "clients=4",
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
    units = {m["name"]: m["unit"] for m in SPEC["per_layer"]
             if CELL in m.get("workloads", [])}
    metrics = result["metrics"]
    assert metrics and set(metrics) <= set(units)
    for name, m in metrics.items():
        if units[name] not in ("count", "tokens"):
            assert m["value"] is None, name
    assert "delta_slots_peak_share" in metrics
    assert "paged_attention_roofline" not in metrics
    assert "ssm_slots_peak_share" not in metrics
    built = next(line for line in run.stdout.splitlines()
                 if line.startswith("built:"))
    assert "'state_slots': 256" in built and "'ok': True" in built
