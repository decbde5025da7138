"""The Laguna family's files in the benchmark: the cut and what it keeps, the
traffic mix, the count functions against numbers worked by hand, the new
readers on made-up steps, the probe on planted faults at a small size, and
the benchmark's copy of the reference against the program's."""

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "serve.reason-swa-closed256"


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CONFIG = load("configs", "laguna-s-serve-5l-ep16.json")
PUBLISHED = load("published", "laguna-s-2.1.json")
TRAFFIC = load("traffic", "reason-swa-closed256.json")
LATTICE = load("lattices", "laguna-s-serve-5l-ep16.json")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)


def test_the_cut_is_exactly_the_three_reduced_keys():
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert CONFIG["reduced_from"] == {
        "num_hidden_layers": 48, "num_experts": 256, "vocab_size": 100352}
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (5, 16, 12544)
    for key, value in PUBLISHED["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    # no width is cut, the router keeps every output, the lists are whole
    assert all(CONFIG[w] == PUBLISHED["config"][w]
               for w in PUBLISHED["widths"])
    for key in ("layer_types", "mlp_layer_types", "gating_types",
                "num_attention_heads_per_layer"):
        assert len(CONFIG[key]) == 48, key
    assert CONFIG["mlp_only_layers"] == [0]
    assert CONFIG["leading_dense_layers"] == 1 \
        == PUBLISHED["assumed"]["leading_dense_layers"]["value"]
    assert PUBLISHED["leading_dense_key"] == "leading_dense_layers"
    assert (PUBLISHED["experts_key"], PUBLISHED["layer_period"]) \
        == ("num_experts", 4)
    assert CONFIG["routed_experts_scored"] == 256
    assert CONFIG["deployment_chips_per_layer"] == 16
    assert "first num_hidden_layers entries" in \
        CONFIG["departures"]["per_layer_lists"]
    # layers 0-4: the leading dense layer and one whole period
    assert CONFIG["layer_types"][:5] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert CONFIG["num_attention_heads_per_layer"][:5] == [48, 72, 72, 72,
                                                           48]


def test_every_assumed_item_has_its_why():
    assert set(PUBLISHED["assumed"]) == {
        "router_scoring", "shared_expert_gate", "qk_norm", "rope_pairing",
        "leading_dense_layers"}
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
                     if '"name": "Laguna-S-2.1"' in line)
    assert PUBLISHED["config"] == entry["config"]
    assert PUBLISHED["source"] == entry["source_url"] == CONFIG["source"]


def test_the_traffic_file_holds_the_mix_and_no_engine_key():
    assert TRAFFIC["driver"] == "serve_closed_loop"
    assert (TRAFFIC["clients"], TRAFFIC["set_size"],
            TRAFFIC["ramp_per_step"]) == (256, 256, 4)
    assert TRAFFIC["prompt_len"] == {"dist": "uniform", "min": 65, "max": 128}
    assert TRAFFIC["new_tokens"] == {"dist": "loguniform", "min": 512,
                                     "max": 2048}
    assert TRAFFIC["warmup"]["hints"] == "reason-swa-closed256"
    engine_keys = set(CONFIG["engine"]) | {"engine", "serving", "lattice"}
    assert not engine_keys & set(TRAFFIC)
    assert not engine_keys & set(TRAFFIC["warmup"])
    # every context passes the window, and its whole life fits the engine
    assert TRAFFIC["prompt_len"]["min"] + TRAFFIC["new_tokens"]["min"] \
        > CONFIG["sliding_window"]
    assert TRAFFIC["prompt_len"]["max"] + TRAFFIC["new_tokens"]["max"] \
        <= CONFIG["engine"]["max_seq_len"]


def test_the_cell_and_its_metrics_are_listed():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("laguna-s-serve-5l-ep16", "reason-swa-closed256", 1)
    assert any(c["name"] == "laguna-s-serve-5l-ep16"
               and c["reduced"] == CONFIG["reduced"] for c in SPEC["configs"])
    per_layer = {m["name"]: m for m in SPEC["per_layer"]}
    for name in ("window_attn_time_share", "mixed_attention_roofline",
                 "kv_window_held_share", "kv_window_pages_peak_share"):
        assert per_layer[name]["workloads"] == [CELL], name
        assert load("metrics", name + ".json")["unit"] == "%"
    listed = {m["name"] for m in SPEC["per_layer"] + SPEC["end_to_end"]
              if CELL in m.get("workloads", [])}
    # a saturated closed loop: tokens a second and the set-up, no tail (the
    # tail gap rides the host's unhidden path and swings past its bound),
    # so every per-layer metric of the cell moves one of those two
    end_to_end = {m["name"] for m in SPEC["end_to_end"]}
    assert listed & end_to_end == {"serve_tok_s"}
    assert "itl_p95_ms" in CONFIG["not_reported"]["kv_host_ms_per_step"]
    for name in listed - end_to_end:
        assert per_layer[name]["moves"] in ("serve_tok_s", "setup_s"), name
    assert {"moe_held_pair_share.tok_s", "moe_expert_load_imbalance.tok_s",
            "moe_expert_time_share.tok_s", "moe_expert_roofline.tok_s",
            "kv_pages_peak_share", "kv_fill_share",
            "compiles_in_window.serve", "program_compile_s"} <= listed
    for name in listed:
        if name.endswith(".tok_s"):
            # the reading of the metric it is named after, nothing else
            mine, theirs = (load("metrics", n + ".json")
                            for n in (name, name[:-len(".tok_s")]))
            assert (mine["reader"], mine["args"], mine["unit"]) \
                == (theirs["reader"], theirs["args"], theirs["unit"])
            assert per_layer[name]["workloads"] == [CELL]
    assert not listed & set(CONFIG["not_reported"])
    for why in CONFIG["not_reported"].values():
        assert len(why) >= 20


def test_the_pools_fill_the_memory_the_issue_reckons():
    """Weights 2.23 GB, the full group 4.29 GB, the window group 2.42 GB:
    8.9 GB of the chip's 16; one pool for five layers would take 10.7 GB
    for the same callers."""
    from benchmark import flops_laguna as flops
    eng = CONFIG["engine"]
    a_page = eng["page_size"] * flops.kv_bytes_per_token(CONFIG)
    full = (eng["num_pages"] + 1) * a_page * flops.layers_of_kind(CONFIG,
                                                                  "full")
    window = (eng["window_num_pages"] + 1) * a_page \
        * flops.layers_of_kind(CONFIG, "window")
    weights = 2 * flops.total_params(CONFIG)
    assert round(full / 1e9, 2) == 4.3 and round(window / 1e9, 2) == 2.42
    assert round(weights / 1e9, 2) == 2.23
    assert 8e9 < weights + full + window < 0.6 * 16 * 2 ** 30
    assert eng["window_num_pages"] <= 3072
    live = CONFIG["sliding_window"] // eng["page_size"] + 2
    assert eng["max_sequences"] * live <= eng["window_num_pages"]
    one_pool = (eng["num_pages"] + 1) * a_page * 5
    assert round((one_pool - full - window) / 1e9, 1) == 4.0


def test_parameter_counts_worked_by_hand():
    """ISSUE 31's arithmetic: a full layer's attention 44.19M, a sliding
    layer's 63.13M, the dense MLP 113.25M, an expert 9.437M, a routed
    layer as held less attention 161.2M, 1.113B in all; and the program's
    own count of what it holds agrees."""
    from benchmark import flops_laguna as flops
    from benchmark.builders.serve_laguna import source_of
    from deepspeed_tpu.models.laguna import laguna_config
    full = 3072 * 6144 + 2 * 3072 * 1024 + 6144 * 3072 + 3072 * 48
    sliding = 2 * 3072 * 9216 + 2 * 3072 * 1024 + 3072 * 72
    assert flops.attention_params(CONFIG, "full") == full == 44_187_648
    assert flops.attention_params(CONFIG, "window") == sliding == 63_135_744
    assert flops.expert_params(CONFIG) == 3 * 3072 * 1024 == 9_437_184
    extra = 3072 * 256 + 17 * 9_437_184
    assert flops.routed_layer_extra(CONFIG) == extra == 161_218_560
    total = (2 * full + 3 * sliding + 3 * 3072 * 12288 + 4 * extra
             + 2 * 12544 * 3072)
    assert flops.total_params(CONFIG) == total == 1_112_973_312
    assert laguna_config(source_of(CONFIG, False)).n_params() == total
    assert (flops.layers_of_kind(CONFIG, "full"),
            flops.layers_of_kind(CONFIG, "window")) == (2, 3)
    assert (flops.heads_of_kind(CONFIG, "full"),
            flops.heads_of_kind(CONFIG, "window")) == (48, 72)


def test_attention_counts_worked_by_hand():
    from benchmark import flops_laguna as flops
    # 4 KB of K and V a token a layer
    assert flops.kv_bytes_per_token(CONFIG) == 2 * 8 * 128 * 2 == 4096
    # rows at contexts 100, 512, 513 and 2,000: a window layer attends
    # 100 + 512 + 512 + 512 of their 3,125 tokens
    assert flops.window_tokens(CONFIG, [100, 512, 513, 2000]) == 1636
    assert flops.attention_bytes(CONFIG, 3125, 1636) \
        == 4096 * (2 * 3125 + 3 * 1636)
    # score and value products: 4 x 128 FLOPs a head and attended token
    assert flops.attention_flops(CONFIG, 3125, 1636) \
        == 512 * (2 * 48 * 3125 + 3 * 72 * 1636)
    # a 256-row step at a mean context of 1,100: 2.3 GB in the full
    # layers, 1.6 GB at most in the window layers (ISSUE 31)
    assert round(flops.attention_bytes(CONFIG, 256 * 1100, 0) / 1e9, 1) == 2.3
    assert round(flops.attention_bytes(CONFIG, 0, 256 * 512) / 1e9, 1) == 1.6


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


def test_mixed_attention_roofline_reads_the_programs_counts(monkeypatch):
    """Two traced steps whose ``fastgen.step`` spans carry what the decode
    rows attend in a layer of each kind; the bytes bound both (4 KB
    against 24.6 or 36.9 kFLOP a token a layer); a program without the
    attributes (the parent) gives None, and does not raise."""
    from benchmark.readers import mixed_attention_roofline as reader
    rows = [_span(12.0, {"attn_tokens_full": 280_000,
                         "attn_tokens_window": 130_000}),
            _span(13.0, {"attn_tokens_full": 281_000,
                         "attn_tokens_window": 131_000}),
            _span(25.0, {"attn_tokens_full": 9, "attn_tokens_window": 9})]
    _tracer(monkeypatch, rows)
    share = reader.read(_Ctx, {}, {"patterns": ["^paged_attention"]})
    need = 4096 * (2 * 561_000 + 3 * 261_000)
    assert share == pytest.approx(100 * (need / 819e9) / 0.010)
    assert share == pytest.approx(95.27, abs=0.01)
    flop_s = 512 * (2 * 48 * 561_000 + 3 * 72 * 261_000) / 197e12
    assert flop_s < need / 819e9
    _tracer(monkeypatch, [_span(12.0, {"tokens": 256})])
    assert reader.read(_Ctx, {}, {"patterns": ["^paged_attention"]}) is None


def test_span_peak_share_takes_the_largest_of_the_slice(monkeypatch):
    from benchmark.readers import span_peak_share as reader
    args = load("metrics", "kv_window_pages_peak_share.json")["args"]
    _tracer(monkeypatch, [
        _span(12.0, {"kv_pages_reserved_window": 2304}),
        _span(13.0, {"kv_pages_reserved_window": 2458}),
        _span(25.0, {"kv_pages_reserved_window": 3000})])   # outside
    assert reader.read(_Ctx, {}, args) == pytest.approx(100 * 2458 / 3072)
    _tracer(monkeypatch, [_span(12.0, {"tokens": 256})])
    assert reader.read(_Ctx, {}, args) is None


def test_kv_window_held_share_is_read_from_the_span_ring(monkeypatch):
    from benchmark.readers import span_ring as reader
    args = load("metrics", "kv_window_held_share.json")["args"]
    _tracer(monkeypatch, [
        _span(12.0, {"kv_tokens_held_window": 120_000,
                     "kv_tokens_held": 280_000}),
        _span(13.0, {"kv_tokens_held_window": 126_000,
                     "kv_tokens_held": 290_000})])

    class Ctx(_Ctx):
        setup_s, process_start = 5.0, 0.0

    assert reader.read(Ctx, {}, args) == pytest.approx(
        100 * 246_000 / 570_000)
    window = load("metrics", "window_attn_time_share.json")
    assert window["reader"] == "trace_name_share"
    assert window["args"]["patterns"] == ["^paged_attention_window"]


def test_the_probes_tolerances_carry_their_reasons():
    probe = CONFIG["probe"]
    assert probe["decode_steps"] >= 16
    for key in ("logit_rel_rms", "outlier", "sequence_outlier", "margin",
                "pairs", "lengths", "waves", "window"):
        assert len(probe[key + "_reason"]) >= 80, key
    assert 0 < probe["logit_rel_rms"] < probe["outlier_rel_rms"]
    assert probe["outlier_share"] < probe["sequence_outlier_share"] < 1
    assert probe["min_compared"] >= (probe["prompts"]
                                     + probe["long_rows"]) // 2
    for control in ("float8", "gate", "window", "rope"):
        assert control in probe["logit_rel_rms_reason"] \
            + probe["window_reason"], control


def test_the_probe_decodes_past_the_window_and_reuses_released_pages():
    """The long rows' contexts pass the 512-token window within their
    first quarter and end where the mix's longest requests do; the wide
    steps come after that, so that their rows reserve window pages the
    long rows gave back."""
    probe, engine = CONFIG["probe"], CONFIG["engine"]
    window = CONFIG["sliding_window"]
    assert probe["max_len"] + probe["long_steps"] // 4 > window
    assert probe["max_len"] + probe["long_steps"] <= engine["max_seq_len"]
    assert probe["min_len"] + probe["wide_at"][0] > 2 * window
    rows = probe["long_rows"] + probe["wide_copies"] * probe["prompts"]
    assert engine["max_sequences"] // 2 < rows <= engine["max_sequences"]
    # the wide steps run in the row bucket and at the page bucket the
    # window's own steps run in
    top = max(LATTICE["p_buckets"])
    assert [engine["max_sequences"], 1, top, False] in probe["programs"]


def test_the_lattice_is_an_artifact_of_the_cells_engine():
    """The buckets the cell is served under load as the program's own
    artifact, at the engine's page size, vocabulary and token budget; rows
    and tokens a row are bucketed as the default buckets them for this
    mix."""
    from deepspeed_tpu.inference.v2.lattice import (POWER_LATTICE,
                                                    resolve_lattice)
    from benchmark.builders import serve_laguna
    eng = CONFIG["engine"]
    serving = serve_laguna.serving_of(eng, rehearse=False)
    assert os.path.isabs(serving["lattice"].partition(":")[2])
    assert "lattice" not in serve_laguna.serving_of(eng, rehearse=True)
    lat = resolve_lattice(
        serving["lattice"], page_size=eng["page_size"],
        vocab_size=CONFIG["vocab_size"],
        max_ragged_batch_size=eng["token_budget"])
    assert lat.mined and lat.p_tops == (8, 40)
    for rows in range(1, eng["max_sequences"] + 1):
        assert lat.bucket_s(rows) == POWER_LATTICE.bucket_s(rows)
    lens = TRAFFIC["prompt_len"]
    for q in [1] + list(range(lens["min"], lens["max"] + 1)):
        assert lat.bucket_q(q) == POWER_LATTICE.bucket_q(q)
    assert len(LATTICE["why"]) >= 200


@pytest.mark.parametrize("context, bucket", [
    (65, 8), (512, 8), (513, 40), (2047, 40), (2048, 40), (2176, 40),
    (2560, 40), (2561, 64), (4096, 64)])
def test_one_page_bucket_holds_every_context_past_the_prompts(context,
                                                              bucket):
    """A step's page bucket follows its longest context: 8 pages while
    that is a prompt or the ramp's, 40 from there to past the mix's
    longest (prompt + new tokens, and the page being filled), and the
    default's power of two beyond."""
    from deepspeed_tpu.inference.v2.lattice import BucketLattice
    lat = BucketLattice(s_tops=tuple(LATTICE["s_buckets"]),
                        q_tops=tuple(LATTICE["q_buckets"]),
                        p_tops=tuple(LATTICE["p_buckets"]))
    page = CONFIG["engine"]["page_size"]
    assert LATTICE["page_size"] == page
    assert lat.bucket_p(-(-context // page)) == bucket
    longest = TRAFFIC["prompt_len"]["max"] + TRAFFIC["new_tokens"]["max"]
    assert lat.bucket_p(longest // page + 1) == max(LATTICE["p_buckets"])


def test_the_hints_and_the_probe_form_programs_of_the_lattice_only():
    """Every hinted key and every program of the probe is at a bucket of
    the lattice (a key at another page bucket would compile a program no
    step dispatches), the artifact's key set is the hints', and the steady
    state's keys are there: 256 rows at the top page bucket, chained
    after a plain and after a mixed step, and mixed with 1, 2 and 4
    arrivals."""
    from deepspeed_tpu.inference.v2.step_key import StepKey
    hints = load("hints", "reason-swa-closed256.json")["keys"]
    assert hints == LATTICE["keys"]
    s, q, p = (set(LATTICE[k]) for k in ("s_buckets", "q_buckets",
                                         "p_buckets"))
    for key in hints + CONFIG["probe"]["programs"]:
        key = StepKey.parse(key)
        assert key.S in s and key.Q in q and key.P in p, key
    rows, top = CONFIG["engine"]["max_sequences"], max(p)
    for prev in (rows, 2 * rows):
        assert [rows, 1, top, False, "chain", prev, True] in hints
    for arrivals in (1, 2, 4):
        assert [rows, 1, top, False, "mixed", arrivals, 128, 8, True,
                True] in hints


def _small_probe():
    """The configuration at its debug widths with a probe and an engine
    cut to a test's size: a window of 64 and pages of 16, so that 300
    decode steps pass the window and the page bucket of 16."""
    config = json.loads(json.dumps(CONFIG))
    config["rehearse"]["sliding_window"] = 64
    config["engine"].update(page_size=16, num_pages=512, window_num_pages=256,
                            max_sequences=32, token_budget=256,
                            max_seq_len=512)
    config["probe"].update(
        prompts=4, min_len=20, max_len=40, decode_steps=8, long_rows=2,
        long_steps=300, wide_copies=4, wide_at=[150, 290], wide_steps=2,
        min_compared=3, programs=[])
    return config


@pytest.fixture(scope="module")
def small_probe():
    from benchmark.builders import serve_laguna as builder
    from benchmark.builders.serve_pangu_moe import (probe_inputs,
                                                    sequences_of)
    config = _small_probe()
    cfg, params = builder.make_model(config, 11, True)
    inputs = probe_inputs(config["probe"], 11, cfg.vocab_size)
    want = builder.reference_side(params, cfg, sequences_of(inputs))
    engine = builder.make_engine(cfg, params, config["engine"], True)
    return config, cfg, params, inputs, want, engine


def _probe_of(small_probe, want=None):
    from benchmark.builders.serve_pangu_moe import run_probe
    from deepspeed_tpu.inference.v2 import FastGenScheduler
    config, cfg, _, inputs, sound, engine = small_probe
    return run_probe(engine, FastGenScheduler(engine), cfg, inputs,
                     want or sound, config["probe"])


def test_the_probe_passes_the_program_through_both_page_groups(small_probe):
    probe = _probe_of(small_probe)
    assert probe["ok"], probe
    assert probe["short"]["rows"] == 4 * 9 and probe["long"]["rows"] == \
        2 * (1 + 300 - 4) and probe["wide"]["rows"] == 16 * 5 + 2 * 4
    assert probe["compared"] == probe["matched"] == 6
    assert probe["pairs_counted"] == probe["pairs_reference"] > 0
    state = small_probe[5].state_manager
    # the long rows passed the window: their tables gave pages back, which
    # the wide rows then reserved; everything came back at the end
    assert state.window_pages_released >= 2 * (300 - 64) // 16
    state.check_invariants()
    assert state.free_window_pages == 256


@pytest.mark.parametrize("fault", [
    "dropped_gate", "window_less_a_page", "window_plus_a_page",
    "full_rope_on_window_layers", "float8_weights"])
def test_a_planted_fault_fails_the_probe(small_probe, fault):
    """The probe's controls, planted in the reference (the sound program
    against a faulty reference reads what a faulty program reads against
    the sound one): each fails the limit on a wave's median row."""
    import jax.numpy as jnp

    from benchmark.builders import serve_laguna as builder
    from benchmark.builders.serve_pangu_moe import sequences_of
    config, cfg, params, inputs, _, _ = small_probe
    sizes = builder.reference_sizes(cfg)
    controls = {
        "dropped_gate": dict(gate=False),
        "window_less_a_page": dict(window=sizes["window"] - 16),
        "window_plus_a_page": dict(window=sizes["window"] + 16),
        "full_rope_on_window_layers": dict(rope_window=sizes["rope_full"]),
        "float8_weights": dict(weight_precision=jnp.float8_e4m3fn)}[fault]
    want = builder.reference_side(params, cfg, sequences_of(inputs),
                                  **controls)
    probe = _probe_of(small_probe, want)
    assert not probe["ok"]
    assert probe["rel_rms_median"] > config["probe"]["logit_rel_rms"]
    if fault.startswith("window"):
        # rows inside the window are untouched: the short wave is sound
        assert probe["short"]["rel_rms_median"] < 1e-4
        assert probe["long"]["rel_rms_median"] \
            > config["probe"]["logit_rel_rms"]


def test_the_benchmarks_reference_is_the_programs_reference():
    """The benchmark keeps its own copy, run a layer at a time; at a small
    size it gives what ``deepspeed_tpu/models/laguna_reference.py`` gives,
    and the same pairs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.core import meta

    from benchmark import reference_laguna as copy
    from benchmark.builders.serve_laguna import reference_sizes, source_of
    from deepspeed_tpu.models import laguna_reference as plain
    from deepspeed_tpu.models.laguna import LagunaForCausalLM
    model = LagunaForCausalLM(
        dict(source_of(CONFIG, True), sliding_window=16), experts_first=4,
        dtype=jnp.float32)
    params = meta.unbox(model.init_params(jax.random.key(1)))
    tokens = np.random.default_rng(0).integers(0, 160, 37)
    got, pairs = copy.forward(params, tokens, reference_sizes(model.cfg))
    want, counts = plain.forward(params, jnp.asarray(tokens),
                                 plain.sizes_of(model.cfg))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert pairs.shape == (4, 37)
    np.testing.assert_array_equal(np.asarray(pairs), np.asarray(counts))
    # the control: float8 weights move every row far more than rounding
    rough, _ = copy.forward(params, tokens, reference_sizes(model.cfg),
                            weight_precision=jnp.float8_e4m3fn)
    assert float(jnp.sqrt(jnp.mean((rough - got) ** 2)
                          / jnp.mean(got ** 2))) > 0.02
