"""The SmallThinker family's files in the benchmark: that depth alone is cut
and every expert is held, the traffic mix, the count functions against
numbers worked by hand, the new reader on made-up steps, the probe and its
six controls at a small size (where contexts pass the window), a rehearsal
of the cell, and the benchmark's copy of the reference against the
program's."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "serve.reason-moe-closed256"
NAME = "smallthinker-21b-serve-8l"
MIX = "reason-moe-closed256"
#: the accepted readings this cell reports under names of its own
OWN = {"window_attn_time_share.w4096": "window_attn_time_share",
       "kv_window_held_share.w4096": "kv_window_held_share",
       "kv_window_pages_peak_share.w4096": "kv_window_pages_peak_share",
       "moe_held_pair_share.whole": "moe_held_pair_share.tok_s",
       "moe_expert_load_imbalance.whole": "moe_expert_load_imbalance.tok_s",
       "moe_expert_time_share.whole": "moe_expert_time_share.tok_s",
       "moe_expert_roofline.whole": "moe_expert_roofline.tok_s"}
NEW = ("moe_experts_touched_share", "gqa7_attention_roofline") + tuple(OWN)


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CONFIG = load("configs", NAME + ".json")
PUBLISHED = load("published", "powerinfer-smallthinker-21ba3b-instruct.json")
TRAFFIC = load("traffic", MIX + ".json")
LATTICE = load("lattices", NAME + ".json")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)


def test_depth_alone_is_cut_to_two_whole_periods():
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert CONFIG["reduced_from"] == {"num_hidden_layers": 52}
    assert CONFIG["num_hidden_layers"] == 8 == 2 * PUBLISHED["layer_period"]
    assert "deployment_chips_per_layer" not in CONFIG
    for key, value in PUBLISHED["config"].items():
        if key != "num_hidden_layers":
            assert CONFIG[key] == value, key
    assert set(PUBLISHED["widths"]) == {
        "hidden_size", "head_dim", "moe_ffn_hidden_size",
        "sliding_window_size", "moe_num_active_primary_experts"}
    # every expert, every head, the whole vocabulary, the lists whole
    assert (PUBLISHED["experts_key"], CONFIG["moe_num_primary_experts"],
            CONFIG["routed_experts_scored"], CONFIG["experts_first"]) \
        == ("moe_num_primary_experts", 64, 64, 0)
    assert (CONFIG["num_attention_heads"], CONFIG["num_key_value_heads"],
            CONFIG["vocab_size"]) == (28, 4, 151936)
    for key in ("rope_layout", "sliding_window_layout"):
        assert CONFIG[key] == [0, 1, 1, 1] * 13, key
    assert "first num_hidden_layers entries" in \
        CONFIG["departures"]["per_layer_lists"]
    assert "no published checkpoint" in \
        CONFIG["departures"]["seeded_weights"]
    assert "pipeline" in CONFIG["deployment"]


def test_every_assumed_item_has_its_why():
    assert set(PUBLISHED["assumed"]) == {
        "router_input", "expert_activation", "attention_bias", "qk_norm",
        "rope_pairing"}
    for key, item in PUBLISHED["assumed"].items():
        assert len(item["why"]) >= 40, key
        assert CONFIG[key] == item["value"]
        assert CONFIG["assumed"][key] == item["why"]


def test_the_published_file_is_the_catalogs_entry():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog in this environment")
    with open(path) as f:
        entry = next(json.loads(line) for line in f
                     if '"name": "SmallThinker-21BA3B-Instruct"' in line)
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
    # no context reaches the window, and its whole life fits the engine
    longest = TRAFFIC["prompt_len"]["max"] + TRAFFIC["new_tokens"]["max"]
    assert longest < CONFIG["sliding_window_size"] \
        <= CONFIG["engine"]["max_seq_len"]
    assert TRAFFIC["clients"] == CONFIG["engine"]["max_sequences"]
    # the other 256-caller cells' lengths and callers
    for other in ("reason-closed256", "reason-swa-closed256",
                  "reason-ssm-closed256", "reason-delta-closed256"):
        theirs = load("traffic", other + ".json")
        for key in ("clients", "set_size", "ramp_per_step", "prompt_len",
                    "new_tokens", "trace_slice_s"):
            assert TRAFFIC[key] == theirs[key], (other, key)


def test_the_cell_and_its_metrics_are_listed():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, MIX, 1)
    # appended after the cells that were here (a later family comes after)
    last = "serve.reason-delta-closed256"
    names = [w["name"] for w in SPEC["workloads"]]
    assert names.index(CELL) > names.index(last)
    before = [c["name"] for c in SPEC["configs"]]
    entry = SPEC["configs"][before.index(NAME)]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    per_layer = {m["name"]: m for m in SPEC["per_layer"]}
    listed = [m["name"] for m in SPEC["per_layer"]]
    at = listed.index(NEW[0])
    assert tuple(listed[at:at + len(NEW)]) == NEW
    assert "delta_slots_peak_share" in listed[:at]
    served = {n for n in before[:before.index(NAME)] if "serve" in n}
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL], name
        assert per_layer[name]["moves"] == "serve_tok_s"
        metric = load("metrics", name + ".json")
        assert (metric["unit"], metric["layer"], metric["better"],
                metric["source"]) == (
            per_layer[name]["unit"], per_layer[name]["layer"],
            per_layer[name]["better"], per_layer[name]["source"])
        # the five serving configurations that were here, each its reason
        assert set(metric["not_reported"]) == served and len(served) == 5
        assert all(len(w) >= 20 for w in metric["not_reported"].values())
        assert len(metric["what"]) >= 80
    for mine, theirs in OWN.items():
        # the reading of the metric it is named after, nothing else (the
        # expert roofline through this family's own count functions)
        a, b = load("metrics", mine + ".json"), load("metrics",
                                                     theirs + ".json")
        assert (a["unit"], a["layer"], a["better"], a["source"]) \
            == (b["unit"], b["layer"], b["better"], b["source"])
        if mine == "moe_expert_roofline.whole":
            assert (a["reader"], a["args"]) == ("smallthinker_roofline", {
                "patterns": b["args"]["patterns"], "kind": "experts"})
        else:
            assert (a["reader"], a["args"]) == (b["reader"], b["args"])
        assert "test" in CONFIG["not_reported"][theirs]
    assert load("metrics", "gqa7_attention_roofline.json")["args"] == {
        "patterns": ["^paged_attention"], "kind": "attention"}
    listed = {m["name"] for m in SPEC["per_layer"] + SPEC["end_to_end"]
              if CELL in m.get("workloads", [])}
    end_to_end = {m["name"] for m in SPEC["end_to_end"]}
    # tokens a second and the set-up, no tail in this PR
    assert listed & end_to_end == {"serve_tok_s"}
    for name in listed - end_to_end:
        assert per_layer[name]["moves"] in ("serve_tok_s", "setup_s"), name
    joined = {"kv_pages_peak_share", "kv_fill_share",
              "compiles_in_window.serve", "program_compile_s",
              "program_trace_lower_s", "programs_on_path.setup",
              "program_cache_hit_share.setup", "sched_tokens_per_step",
              "budget_fill_share"}
    assert listed - end_to_end == joined | set(NEW)
    for name in joined:                 # appended after the cells that were
        lists = per_layer[name]["workloads"]
        assert lists.index(CELL) > lists.index(last), name
    # every metric that was here, written for the cell's driver, is joined
    # or left out with its reason
    for m in SPEC["per_layer"][:at]:
        drivers = load("metrics", m["name"] + ".json")["drivers"]
        if "serve_closed_loop" in drivers and m["name"] not in joined:
            assert len(CONFIG["not_reported"][m["name"]]) >= 20, m["name"]
    assert not listed & set(CONFIG["not_reported"])
    assert CONFIG["routed_pairs_per_token"] == 6 * 8
    assert CONFIG["mean_share_of_pairs_a_held_expert_and_layer"] == 1 / 512
    assert CONFIG["held_experts_times_layers"] == 64 * 8


def test_the_memory_the_issue_reckons():
    """Weights 7.93 GB, the full group 1.34 GB, the window group 4.03 GB:
    13.3 GB of the chip's 16 before activations, far over the floor of a
    quarter."""
    from benchmark import flops_smallthinker as flops
    eng = CONFIG["engine"]
    a_page = eng["page_size"] * flops.kv_bytes_per_token(CONFIG)
    full = (eng["num_pages"] + 1) * a_page * flops.layers_of_kind(CONFIG,
                                                                  "full")
    window = (eng["window_num_pages"] + 1) * a_page \
        * flops.layers_of_kind(CONFIG, "window")
    weights = 2 * flops.total_params(CONFIG)
    assert a_page * 2 == 256 * 1024 and a_page * 6 == 768 * 1024
    assert round(weights / 1e9, 2) == 7.93
    assert round(full / 1e9, 2) == 1.34 and round(window / 1e9, 2) == 4.03
    assert 0.25 * 16e9 < 13e9 < weights + full + window < 0.85 * 16e9
    for text in ("7.93 GB", "1.34 GB", "4.03 GB"):
        assert text in CONFIG["deployment"], text
    # the mix's longest context is 35 pages; the pools hold 20 a caller
    longest = TRAFFIC["prompt_len"]["max"] + TRAFFIC["new_tokens"]["max"]
    assert longest // eng["page_size"] + 1 == 35
    assert eng["num_pages"] == eng["window_num_pages"] \
        == 20 * eng["max_sequences"]


def test_counts_worked_by_hand():
    """ISSUE 47's arithmetic: attention 20.97M a layer, an expert 5.90M,
    a layer 398,627,840, the embedding and the head 388,956,160 each,
    3,966,937,600 in all; 6.04 GB of expert weights a step; 2,048 B of K/V
    a token and layer; and the program's own count agrees."""
    from benchmark import flops_smallthinker as flops
    from benchmark.builders.serve_smallthinker import source_of
    from deepspeed_tpu.models.smallthinker import smallthinker_config
    c = CONFIG
    attention = 2 * 2560 * 128 * (28 + 4)
    assert flops.attention_params(c) == attention == 20_971_520
    assert flops.expert_params(c) == 3 * 2560 * 768 == 5_898_240
    layer = attention + 2560 * 64 + 64 * 5_898_240 + 2 * 2560
    assert flops.layer_params(c) == layer == 398_627_840
    total = 8 * layer + 2 * 151936 * 2560 + 2560
    assert flops.total_params(c) == total == 3_966_937_600
    # the program counts matrices only: the 17 norms' gains left out
    assert smallthinker_config(source_of(c, False)).n_params() \
        == total - 17 * 2560
    assert flops.layer_kinds(c) == ["full", "window", "window", "window"] * 2
    assert (flops.layers_of_kind(c, "full"),
            flops.layers_of_kind(c, "window")) == (2, 6)
    assert flops.expert_bytes_per_step(c) == 8 * 64 * 5_898_240 * 2 \
        == 6_039_797_760
    assert flops.kv_bytes_per_token(c) == 2 * 4 * 128 * 2 == 2048
    # rows at contexts 100, 4,096, 4,097 and 6,000: a window layer attends
    # 100 + 4,096 + 4,096 + 4,096 of their 14,293 tokens
    assert flops.window_tokens(c, [100, 4096, 4097, 6000]) == 12388
    assert flops.attention_bytes(c, 14293, 12388) \
        == 2048 * (2 * 14293 + 6 * 12388)
    # score and value products: 4 x 128 FLOPs a head and attended token
    assert flops.attention_flops(c, 14293, 12388) \
        == 512 * 28 * (2 * 14293 + 6 * 12388)
    # a 256-row step at a mean context of 740: 3.1 GB of K/V (ISSUE 47)
    assert round(flops.attention_bytes(c, 256 * 740, 256 * 740) / 1e9, 1) \
        == 3.1
    # a step's 1,536 pairs over all 512 experts
    assert flops.grouped_expert_bytes(c, 512, 1536) \
        == 6_039_797_760 + 1536 * 2 * 2560 * 2
    assert flops.grouped_expert_flops(c, 1536) == 2 * 1536 * 5_898_240


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


def test_the_roofline_reader_reads_the_programs_counts(monkeypatch):
    """Two traced steps whose ``fastgen.step`` spans carry the program's
    counts; bytes bound both kernels; a program without the attributes
    (the parent) gives None, and does not raise."""
    from benchmark.readers import smallthinker_roofline as reader
    rows = [_span(12.0, {"attn_tokens_full": 190_000,
                         "attn_tokens_window": 190_000,
                         "moe_experts_touched": 512, "moe_pairs_here": 1536}),
            _span(13.0, {"attn_tokens_full": 191_000,
                         "attn_tokens_window": 191_000,
                         "moe_experts_touched": 500, "moe_pairs_here": 1530}),
            _span(25.0, {"attn_tokens_full": 9, "attn_tokens_window": 9,
                         "moe_experts_touched": 1, "moe_pairs_here": 1})]
    _tracer(monkeypatch, rows)
    attention = load("metrics", "gqa7_attention_roofline.json")["args"]
    share = reader.read(_Ctx, {}, attention)
    need = 2048 * 8 * 381_000
    assert share == pytest.approx(100 * (need / 819e9) / 0.010)
    assert 512 * 28 * 8 * 381_000 / 197e12 < need / 819e9
    experts = load("metrics", "moe_expert_roofline.whole.json")["args"]
    share = reader.read(_Ctx, {}, experts)
    need = (512 + 500) * 5_898_240 * 2 + (1536 + 1530) * 2 * 2560 * 2
    assert share == pytest.approx(100 * (need / 819e9) / 0.010)
    assert 2 * 1536 * 5_898_240 / 197e12 < 512 * 5_898_240 * 2 / 819e9
    _tracer(monkeypatch, [_span(12.0, {"tokens": 256})])
    assert reader.read(_Ctx, {}, attention) is None
    assert reader.read(_Ctx, {}, experts) is None


def test_the_span_ring_metrics_read_the_programs_counts(monkeypatch):
    from benchmark.readers import span_peak_share, span_ring
    _tracer(monkeypatch, [
        _span(12.0, {"moe_experts_touched": 512, "moe_pairs_here": 12288,
                     "moe_tokens": 256, "moe_expert_load_max": 40,
                     "kv_tokens_held_window": 190_000,
                     "kv_tokens_held": 190_000,
                     "kv_pages_reserved_window": 3200}),
        _span(13.0, {"moe_experts_touched": 496, "moe_pairs_here": 12288,
                     "moe_tokens": 256, "moe_expert_load_max": 44,
                     "kv_tokens_held_window": 191_000,
                     "kv_tokens_held": 191_000,
                     "kv_pages_reserved_window": 3328})])

    def read(name, reader=span_ring):
        return reader.read(_Ctx, {}, load("metrics", name + ".json")["args"])

    assert read("moe_experts_touched_share") == pytest.approx(
        100 * (512 + 496) / (2 * 512))
    assert read("moe_held_pair_share.whole") == pytest.approx(100.0)
    assert read("moe_expert_load_imbalance.whole") == pytest.approx(
        84 / (24576 / 512))
    assert read("kv_window_held_share.w4096") == pytest.approx(100.0)
    assert read("kv_window_pages_peak_share.w4096", span_peak_share) \
        == pytest.approx(100 * 3328 / 5120)
    _tracer(monkeypatch, [_span(12.0, {"tokens": 256})])
    assert read("moe_held_pair_share.whole") is None
    assert read("kv_window_pages_peak_share.w4096", span_peak_share) is None


def test_the_probes_tolerances_carry_their_reasons():
    probe = CONFIG["probe"]
    assert probe["decode_steps"] >= 16 and probe["long_steps"] == 2000
    for key in ("logit_rel_rms", "outlier", "sequence_outlier", "margin",
                "pairs", "lengths", "waves", "window"):
        assert len(probe[key + "_reason"]) >= 80, key
    # a short wave's median may sit among the near-tie rows (a third of all
    # rows): its limit lies over the line that tells such a row, and under
    # the smallest reading of float8 weights (0.127 on the chip)
    assert 0 < probe["outlier_rel_rms"] < probe["logit_rel_rms"] < 0.127
    # a third of all rows are near-tie rows in this family (8 routed
    # layers, top-6 of 64): the sequence rule is off and the floor of the
    # first tokens low, each with its reason
    assert probe["outlier_share"] < probe["sequence_outlier_share"] == 1.0
    assert 2 <= probe["min_compared"] < probe["prompts"]
    for key in ("sequence_outlier", "min_compared"):
        assert "third" in probe[key + "_reason"], key
    from benchmark.builders.serve_smallthinker import CONTROLS
    assert len(CONTROLS) == 6
    for control in ("float8", "SiLU", "post-attention", "global layers",
                    "window layers", "normalised"):
        assert control in probe["logit_rel_rms_reason"], control
    # the long rows end where the mix's longest requests do, under the
    # window: what holds the eviction is said where it is held
    assert probe["max_len"] + probe["long_steps"] \
        < CONFIG["sliding_window_size"]
    assert "tools/smallthinker_window.py" in probe["window_reason"]
    rows = probe["long_rows"] + probe["wide_copies"] * probe["prompts"]
    eng = CONFIG["engine"]
    assert eng["max_sequences"] // 2 < rows <= eng["max_sequences"]
    assert [eng["max_sequences"], 1, max(LATTICE["p_buckets"]), False] \
        in probe["programs"]


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
    hints = load("hints", MIX + ".json")["keys"]
    assert hints == LATTICE["keys"] and len(LATTICE["why"]) >= 200
    s, q, p = (set(LATTICE[k]) for k in ("s_buckets", "q_buckets",
                                         "p_buckets"))
    for key in hints + CONFIG["probe"]["programs"]:
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
    to a test's size: a window of 64 and pages of 16, so that 96 decode
    steps pass the window and window pages are evicted under the
    comparison (the chip's probe stays under its window of 4,096)."""
    config = json.loads(json.dumps(CONFIG))
    config["rehearse"].update(sliding_window_size=64, num_hidden_layers=4)
    config["rehearse"].pop("probe_cut")
    config["engine"].update(page_size=16, num_pages=512, window_num_pages=256,
                            max_sequences=32, token_budget=256,
                            max_seq_len=512)
    config["probe"].update(
        prompts=2, min_len=20, max_len=40, decode_steps=8, long_rows=1,
        long_steps=96, wide_copies=2, wide_at=[50, 90], wide_steps=2,
        min_compared=2, programs=[],
        # float32 at debug widths: the limits of a rounding of sums
        logit_rel_rms=3e-4, outlier_rel_rms=3e-3, margin=1e-3)
    return config


def test_the_probe_passes_the_program_and_refuses_each_control():
    """ONE serving of the probe's waves through both page groups, read
    against the sound reference (``ok``) and against the reference with
    each of the builder's six controls planted: the sound program against a faulty
    reference reads what a faulty program reads against the sound one,
    and each reads ``ok: false``.  (One test: the serving is the cost.)"""
    from benchmark.builders import serve_smallthinker as builder
    from benchmark.builders.serve_pangu_moe import probe_inputs
    from deepspeed_tpu.inference.v2 import FastGenScheduler
    config = _small_probe()
    cfg, params = builder.make_model(config, 11, True)
    inputs = probe_inputs(config["probe"], 11, cfg.vocab_size)
    engine = builder.make_engine(cfg, params, config["engine"], True)
    assert len(builder.CONTROLS) == 6
    controls = builder.CONTROLS
    verdicts = builder.control_verdicts(
        engine, FastGenScheduler(engine), cfg, params, inputs,
        config["probe"])
    probe = verdicts.pop("sound")
    assert probe["ok"], probe
    assert probe["short"]["rows"] == 2 * 9 and probe["long"]["rows"] == \
        1 * (1 + 96 - 4) and probe["wide"]["rows"] == 4 * 5 + 1 * 4
    assert probe["compared"] == probe["matched"] == 3
    # every expert is held: 3 pairs a token and layer, all of them here
    assert probe["pairs_counted"] == probe["pairs_reference"] > 0
    assert probe["held_pair_share"] == 100.0
    assert probe["rel_rms_max"] < 3e-4
    state = engine.state_manager
    assert state.window_pages_released >= (96 - 64) // 16
    state.check_invariants()
    assert (state.free_window_pages, engine.free_blocks) == (256, 512)
    assert set(verdicts) == set(controls)
    for control, probe in verdicts.items():
        assert not probe["ok"], (control, probe)
        assert probe["rel_rms_median"] > config["probe"]["logit_rel_rms"], \
            control


def test_the_benchmarks_reference_is_the_programs_reference():
    """The benchmark keeps its own copy, run a layer at a time; at a small
    size it gives what ``deepspeed_tpu/models/smallthinker_reference.py``
    gives, and neither imports anything of the program."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.core import meta

    from benchmark import reference_smallthinker as copy
    from benchmark.builders.serve_smallthinker import (reference_sizes,
                                                       source_of)
    from deepspeed_tpu.models import smallthinker_reference as plain
    from deepspeed_tpu.models.smallthinker import SmallThinkerForCausalLM
    for module in (copy, plain):
        with open(module.__file__) as f:
            code = f.read().split('"""', 2)[2]
        assert "deepspeed_tpu" not in code and "import" in code
        assert "from ." not in code and "pallas" not in code
    model = SmallThinkerForCausalLM(
        dict(source_of(CONFIG, True), sliding_window_size=16),
        dtype=jnp.float32)
    params = meta.unbox(model.init_params(jax.random.key(1)))
    tokens = np.random.default_rng(0).integers(0, 160, 37)
    sizes = reference_sizes(model.cfg)
    assert sizes == plain.sizes_of(model.cfg)
    got, pairs = copy.forward(params, tokens, sizes)
    want, counts = plain.forward(params, jnp.asarray(tokens), sizes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert pairs.shape == (8, 37) and int(pairs.min()) == 3
    np.testing.assert_array_equal(np.asarray(pairs), np.asarray(counts))
    # the control of the nearest precision below the configuration's:
    # float8 weights move every row far more than rounding
    rough, _ = copy.forward(params, tokens, sizes,
                            weight_precision=jnp.float8_e4m3fn)
    assert float(jnp.sqrt(jnp.mean((rough - got) ** 2)
                          / jnp.mean(got ** 2))) > 0.02


def test_the_harness_rehearses_the_cell():
    """``benchmark.run --rehearse`` of the cell on the CPU at the debug
    widths, under a mix cut to a test's size by hand (four callers, eight
    new tokens): the builder, the probe (cut to the rehearsal's size), the hints and the driver resolve; every time and rate
    comes back as ``null``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [ROOT] + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    run = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--rehearse", "--seed", str(2 ** 31 + 47), "--seconds", "1",
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
    # untraced: the cell's end-to-end metrics, each ``null`` on the CPU
    # (the per-layer metric files are read on made-up spans above)
    assert set(result["metrics"]) == {"serve_tok_s", "setup_s"}
    assert all(m["value"] is None for m in result["metrics"].values())
    built = next(line for line in run.stdout.splitlines()
                 if line.startswith("built:"))
    assert "'experts_held': 8" in built and "'ok': True" in built
    assert "'held_pair_share': 100.0" in built
