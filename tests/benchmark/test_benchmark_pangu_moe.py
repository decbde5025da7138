"""The openPangu-Ultra-MoE family's files in the benchmark: the cut and what
it keeps, the traffic mix, the count functions against numbers worked by
hand, the two roofline readers on made-up steps, and the benchmark's copy of
the reference against the program's at a small size."""

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CONFIG = load("configs", "pangu-ultra-moe-serve-5l-ep16.json")
PUBLISHED = load("published", "openpangu-ultra-moe-718b.json")
TRAFFIC = load("traffic", "reason-closed256.json")


def test_the_cut_is_exactly_the_four_reduced_keys():
    assert CONFIG["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                 "n_routed_experts", "vocab_size"]
    assert CONFIG["reduced_from"] == {
        "num_hidden_layers": 61, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 153600}
    assert (CONFIG["num_hidden_layers"], CONFIG["first_k_dense_replace"],
            CONFIG["n_routed_experts"], CONFIG["vocab_size"]) == (
        5, 1, 16, 19200)
    for key, value in PUBLISHED["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    # no width is cut, the router keeps every output, all heads are here
    assert all(CONFIG[w] == PUBLISHED["config"][w]
               for w in PUBLISHED["widths"])
    assert CONFIG["routed_experts_scored"] == 256
    assert CONFIG["deployment_chips_per_layer"] == 16
    assert CONFIG["num_nextn_predict_layers"] == 1
    assert "neither built nor run" in \
        CONFIG["departures"]["multi_token_prediction"]


def test_every_assumed_item_has_its_why():
    assert set(PUBLISHED["assumed"]) == {"scoring_func", "topk_method",
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
                     if '"openPangu-Ultra-MoE-718B"' in line)
    assert PUBLISHED["config"] == entry["config"]
    assert PUBLISHED["source"] == entry["source_url"] == CONFIG["source"]


def test_the_traffic_file_holds_the_mix_and_no_engine_key():
    assert TRAFFIC["driver"] == "serve_closed_loop"
    assert (TRAFFIC["clients"], TRAFFIC["set_size"]) == (256, 256)
    assert TRAFFIC["prompt_len"] == {"dist": "uniform", "min": 65, "max": 128}
    assert TRAFFIC["new_tokens"] == {"dist": "loguniform", "min": 512,
                                     "max": 2048}
    engine_keys = set(CONFIG["engine"]) | {"engine", "serving", "lattice"}
    assert not engine_keys & set(TRAFFIC)
    assert not engine_keys & set(TRAFFIC["warmup"])


def test_the_probes_tolerances_carry_their_reasons():
    probe = CONFIG["probe"]
    assert probe["decode_steps"] >= 16
    for key in ("logit_rel_rms", "outlier", "sequence_outlier", "margin",
                "pairs", "lengths"):
        assert len(probe[key + "_reason"]) >= 80, key
    assert 0 < probe["logit_rel_rms"] < probe["outlier_rel_rms"]
    assert probe["outlier_share"] < probe["sequence_outlier_share"] < 1
    assert probe["min_compared"] >= (probe["prompts"]
                                     + probe["long_rows"]) // 2


def test_the_probe_decodes_through_every_page_bucket_the_window_times():
    """The long rows' contexts pass 8, 16 and 32 pages (the decode
    kernel's 2, 4 and 8 groups of pages), and the wide steps put them
    beside enough short rows to fill the window's own row bucket, at
    contexts in the two largest page buckets."""
    probe, engine = CONFIG["probe"], CONFIG["engine"]
    page = engine["page_size"]
    last = probe["min_len"] + probe["long_steps"]
    assert last > 32 * page and probe["max_len"] + probe["long_steps"] \
        <= engine["max_seq_len"]
    rows = probe["long_rows"] + probe["wide_copies"] * probe["prompts"]
    assert engine["max_sequences"] // 2 < rows <= engine["max_sequences"]
    first, second = probe["wide_at"]
    assert 16 * page < probe["min_len"] + first \
        and probe["max_len"] + first + probe["wide_steps"] <= 32 * page
    assert 32 * page < probe["min_len"] + second \
        and second + probe["wide_steps"] <= probe["long_steps"]
    # the probe's own step programs are listed to be formed together
    assert [256, 1, 64, False] in probe["programs"]
    assert [256, 1, 32, False] in probe["programs"]


def _judged(rows, **first):
    from benchmark.builders.serve_pangu_moe import Rows, judge
    table = Rows()
    for wave, seq, err in rows:
        table.wave.append(wave)
        table.seq.append(seq)
        table.err.append(err)
    counts = dict({"compared": 12, "matched": 12, "pairs_counted": 1500,
                   "pairs_reference": 1506}, **first)
    return judge(table, counts, CONFIG["probe"])


def _sound_rows(outliers=()):
    """8 short sequences of 17 rows, 4 long ones of 500, 16 wide of 9, at
    the chip's sound reading; ``outliers`` are (sequence, row) pairs."""
    rows = [("short", f"s{i}", 0.012) for i in range(8) for _ in range(17)]
    rows += [("long", f"l{i}", 0.012) for i in range(4) for _ in range(500)]
    rows += [("wide", f"w0.{i}", 0.012) for i in range(16) for _ in range(9)]
    seen = {}
    for n, (wave, seq, err) in enumerate(rows):
        k = seen[seq] = seen.get(seq, -1) + 1
        if (seq, k) in outliers:
            rows[n] = (wave, seq, 0.15)
    return rows


@pytest.mark.parametrize("case,ok", [
    ("sound", True), ("scattered_ties", True), ("one_sequence_wrong", False),
    ("one_wide_row_wrong", False), ("a_wave_off", False),
    ("many_rows_off", False), ("first_token_off", False),
    ("too_few_first_tokens", False), ("pairs_off", False)])
def test_the_probes_verdict(case, ok):
    """What each limit of the probe catches, on made-up rows: near-ties
    scattered over the sequences pass; ONE sequence wholly wrong (17 of
    2,280 rows: under any share of all rows that the ties allow) does
    not."""
    first, rows = {}, _sound_rows()
    if case == "scattered_ties":
        rows = _sound_rows({(f"l{i}", k) for i in range(4)
                            for k in range(0, 500, 14)}
                           | {("s3", 2), ("s3", 9), ("w0.5", 4)})
    elif case == "one_sequence_wrong":
        rows = _sound_rows({("s5", k) for k in range(1, 17)})
    elif case == "one_wide_row_wrong":
        rows = _sound_rows({("w0.9", k) for k in range(1, 9)})
    elif case == "a_wave_off":
        rows = [(w, s, 0.02 if w == "wide" else e) for w, s, e in rows]
    elif case == "many_rows_off":
        rows = _sound_rows({(f"l{i}", k) for i in range(4)
                            for k in range(0, 500, 4)})
    elif case == "first_token_off":
        first = {"matched": 11}
    elif case == "too_few_first_tokens":
        first = {"compared": 5, "matched": 5}
    elif case == "pairs_off":
        first = {"pairs_counted": 1600}
    verdict = _judged(rows, **first)
    assert verdict["ok"] is ok
    if case == "one_sequence_wrong":
        assert verdict["sequence_outlier_worst"] == ["s5", 16, 17]
        assert verdict["outlier_rows"] < \
            CONFIG["probe"]["outlier_share"] * verdict["rows"] / 10


def _small_probe():
    """The configuration at its debug widths with a probe and an engine
    cut to a test's size: pages of 16 tokens, so that 300 decode steps
    reach the page bucket of 32."""
    config = json.loads(json.dumps(CONFIG))
    config["engine"].update(page_size=16, num_pages=512, max_sequences=32,
                            token_budget=256, max_seq_len=512)
    config["probe"].update(
        prompts=4, min_len=20, max_len=40, decode_steps=8, long_rows=2,
        long_steps=300, wide_copies=4, wide_at=[150, 290], wide_steps=2,
        min_compared=3, programs=[])
    return config


@pytest.fixture(scope="module")
def small_probe():
    from benchmark.builders import serve_pangu_moe as builder
    config = _small_probe()
    cfg, params = builder.make_model(config, 11, True)
    inputs = builder.probe_inputs(config["probe"], 11, cfg.vocab_size)
    want = builder.reference_side(params, cfg, builder.sequences_of(inputs))
    return config, cfg, params, inputs, want


def _probe_of(small_probe, engine=None, params=None, cfg=None, **probe):
    from benchmark.builders import serve_pangu_moe as builder
    from deepspeed_tpu.inference.v2 import FastGenScheduler
    config, cfg0, params0, inputs, want = small_probe
    if engine is None:
        engine = builder.make_engine(cfg or cfg0, params or params0,
                                     config["engine"], True)
    if probe.get("long_rows") == 0:
        inputs = dict(inputs, long=[])
        want = want[:len(inputs["short"])]
    return engine, builder.run_probe(
        engine, FastGenScheduler(engine), cfg or cfg0, inputs, want,
        dict(config["probe"], **probe))


@pytest.fixture(scope="module")
def small_engine(small_probe):
    """One engine for the sound run and the spoiled one: the probe
    flushes what it served, and the step programs are formed once."""
    from benchmark.builders import serve_pangu_moe as builder
    config, cfg, params, _, _ = small_probe
    return builder.make_engine(cfg, params, config["engine"], True)


def test_the_probe_passes_the_program_through_every_wave(small_probe,
                                                         small_engine):
    engine, probe = _probe_of(small_probe, small_engine)
    assert probe["ok"], probe
    assert probe["short"]["rows"] == 4 * 9 and probe["long"]["rows"] == \
        2 * (1 + 300 - 4) and probe["wide"]["rows"] == 16 * 5 + 2 * 4
    assert probe["compared"] == probe["matched"] == 6
    assert probe["pairs_counted"] == probe["pairs_reference"] > 0
    # the long rows met the page buckets 8, 16 and 32; the wide steps
    # ran 18 rows in the bucket of 32
    keys = set(engine.compiled_keys())
    assert {(2, 1, 8, False), (2, 1, 16, False), (2, 1, 32, False),
            (32, 1, 16, False), (32, 1, 32, False)} <= keys
    engine.state_manager.check_invariants()


def test_one_rows_spoiled_pages_fail_the_probe(small_probe, small_engine,
                                               monkeypatch):
    """The planted per-row fault: the first page of ONE row of the wide
    steps (the copy ``w2.1``) zeroed after its prefill.  Its decode rows
    alone go wrong, 4 of 722: far too few to move a median or the share
    of all rows, so the per-sequence limit is what refuses it."""
    put, state = small_engine.put, small_engine.state_manager
    uid = -3000 - (2 * 4 + 1)

    def spoiling(uids, tokens, *a, **kw):
        out = put(uids, tokens, *a, **kw)
        if uid in uids and len(tokens[0]) > 1:
            page = state.get_sequence(uid).pages[0]
            state.kv_cache.data = state.kv_cache.data.at[:, page].set(0.0)
        return out

    monkeypatch.setattr(small_engine, "put", spoiling)
    _, probe = _probe_of(small_probe, small_engine)
    limits = small_probe[0]["probe"]
    assert not probe["ok"]
    assert probe["sequence_outlier_worst"] == ["w2.1", 4, 5]
    assert 4 > limits["sequence_outlier_share"] * 5
    assert probe["outlier_rows"] == 4 < limits["outlier_share"] \
        * probe["rows"] / 10
    assert probe["rel_rms_median"] <= limits["logit_rel_rms"]
    assert probe["matched"] == probe["compared"] == 6


def test_a_router_that_scores_only_the_held_experts_fails_the_pairs(
        small_probe):
    """The control of ``pairs_tolerance``, run: with the router cut to
    the experts held here every chosen expert is here, and the program
    counts four times the reference's pairs (16 times at the published
    sizes)."""
    import dataclasses
    config, cfg, params, _, _ = small_probe
    held = cfg.held_experts
    layers = dict(params["layers"])
    layers["moe"] = dict(layers["moe"],
                         router=layers["moe"]["router"][..., :held])
    narrow = dataclasses.replace(cfg, n_routed_experts=held,
                                 experts_held=held, experts_first=0)
    _, probe = _probe_of(small_probe, params=dict(params, layers=layers),
                         cfg=narrow, long_rows=0)
    assert not probe["ok"]
    off = abs(probe["pairs_counted"] - probe["pairs_reference"]) \
        / probe["pairs_reference"]
    assert off > 1.0 > config["probe"]["pairs_tolerance"]


def test_parameter_counts_worked_by_hand():
    """ISSUE 27's arithmetic: attention 196.6M a layer, the dense FFN
    424.7M, an expert 47.2M, a routed layer as held 1,000.8M, 4.92B in
    all."""
    from benchmark import flops_pangu_moe as flops
    attention = (7680 * 1536 + 1536 * 128 * 192 + 7680 * 576
                 + 512 * 128 * 256 + 128 * 128 * 7680)
    assert flops.attention_params(CONFIG) == attention == 196_575_232
    assert flops.expert_params(CONFIG) == 3 * 7680 * 2048 == 47_185_920
    assert flops.dense_layer_params(CONFIG) == attention + 3 * 7680 * 18432
    routed = attention + 7680 * 256 + 17 * 47_185_920
    assert flops.routed_layer_params(CONFIG) == routed == 1_000_701_952
    assert flops.total_params(CONFIG) == (
        621_248_512 + 4 * routed + 2 * 19200 * 7680) == 4_918_968_320
    assert flops.latent_plane(CONFIG) == 576


def test_attention_and_expert_counts_worked_by_hand():
    from benchmark import flops_pangu_moe as flops
    # 1,000 context tokens: 576 values x 2 B x 5 layers each
    assert flops.mla_decode_bytes(CONFIG, 1000) == 1000 * 1152 * 5
    # every head against the plane (576) and the sum of its 512 values
    assert flops.mla_decode_flops(CONFIG, 1000) == \
        2 * 128 * (576 + 512) * 1000 * 5
    # 16 experts touched by 128 pairs: the weights dominate
    assert flops.grouped_expert_bytes(CONFIG, 16, 128) == \
        16 * 47_185_920 * 2 + 128 * 2 * 7680 * 2
    assert flops.grouped_expert_flops(CONFIG, 128) == 2 * 128 * 47_185_920


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


def test_mla_decode_roofline_is_bound_by_its_operations():
    from benchmark.readers import mla_decode_roofline as reader
    facts = {"step_decode_context": [9, 300_000, 200_000, 9]}
    # 500,000 context tokens: 2 x 128 x 1088 x 5 FLOPs each = 0.696 TFLOP
    # -> 3.534 ms; their bytes (2.88 GB) would take 3.516 ms
    share = reader.read(_Ctx, facts, {"patterns": ["^mla_attention_decode"]})
    assert share == pytest.approx(
        100 * (2 * 128 * 1088 * 5 * 500_000 / 197e12) / 0.010)
    assert share == pytest.approx(35.34, abs=0.01)
    assert reader.read(_Ctx, {"step_decode_context": []}, {"patterns": []}) \
        is None


def test_moe_expert_roofline_reads_the_programs_counts(monkeypatch):
    """Two traced steps whose ``fastgen.step`` spans carry the counts; a
    program without them (the parent) gives None, and does not raise."""
    import deepspeed_tpu.telemetry as telemetry
    from benchmark.readers import moe_expert_roofline as reader

    def span(end, attrs):
        return ("fastgen.step", end - 0.01, 0.01, 0, 0, attrs, end, None, 0)

    class Tracer:
        rows = [span(12.0, {"moe_experts_touched": 64,
                            "moe_pairs_here": 512}),
                span(13.0, {"moe_experts_touched": 60,
                            "moe_pairs_here": 500}),
                span(25.0, {"moe_experts_touched": 64,
                            "moe_pairs_here": 512})]    # outside the slice

        def records(self):
            return self.rows

    monkeypatch.setattr(telemetry, "get_tracer", lambda: Tracer())
    share = reader.read(_Ctx, {}, {"patterns": ["^moe_expert_ffn"]})
    weights = 124 * 47_185_920 * 2 + 1012 * 2 * 7680 * 2
    assert share == pytest.approx(100 * (weights / 819e9) / 0.010)
    Tracer.rows = [span(12.0, {"tokens": 256})]
    assert reader.read(_Ctx, {}, {"patterns": ["^moe_expert_ffn"]}) is None


def test_the_benchmarks_reference_is_the_programs_reference():
    """The benchmark keeps its own copy, run a layer at a time; at a small
    size it gives what ``deepspeed_tpu/models/pangu_moe_reference.py``
    gives, and the same pairs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.core import meta

    from benchmark import reference_pangu_moe as copy
    from benchmark.builders.serve_pangu_moe import (reference_sizes,
                                                    source_of)
    from deepspeed_tpu.models import pangu_moe_reference as plain
    from deepspeed_tpu.models.pangu_moe import PanguUltraMoEForCausalLM
    model = PanguUltraMoEForCausalLM(source_of(CONFIG, True), experts_first=4,
                                     dtype=jnp.float32)
    params = meta.unbox(model.init_params(jax.random.key(1)))
    tokens = np.random.default_rng(0).integers(0, 160, 37)
    got, pairs = copy.forward(params, tokens, reference_sizes(model.cfg))
    want, counts = plain.forward(params, jnp.asarray(tokens),
                                 plain.sizes_of(model.cfg))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert pairs.shape == (2, 37)
    np.testing.assert_array_equal(np.asarray(pairs).sum(1),
                                  np.asarray(counts).sum(1))
    # the control: float8 weights move every row far more than rounding
    rough, _ = copy.forward(params, tokens, reference_sizes(model.cfg),
                            weight_precision=jnp.float8_e4m3fn)
    assert float(jnp.sqrt(jnp.mean((rough - got) ** 2)
                          / jnp.mean(got ** 2))) > 0.02
