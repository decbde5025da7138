"""The Jamba family's files in the benchmark: that nothing is cut, the
traffic mix, the count functions against numbers worked by hand, the new
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
CELL = "serve.reason-ssm-closed256"
NAME = "jamba2-3b-serve-28l"


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CONFIG = load("configs", NAME + ".json")
PUBLISHED = load("published", "ai21-jamba2-3b.json")
TRAFFIC = load("traffic", "reason-ssm-closed256.json")
LATTICE = load("lattices", NAME + ".json")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)


def test_nothing_is_cut():
    assert CONFIG["reduced"] == [] and CONFIG["reduced_from"] == {}
    assert "deployment_chips_per_layer" not in CONFIG
    for key, value in PUBLISHED["config"].items():
        assert CONFIG[key] == value, key
    assert (CONFIG["num_hidden_layers"], CONFIG["num_attention_heads"],
            CONFIG["num_key_value_heads"], CONFIG["vocab_size"]) \
        == (28, 20, 1, 65536)
    assert set(PUBLISHED["widths"]) == {
        "hidden_size", "intermediate_size", "mamba_d_state", "mamba_d_conv",
        "mamba_dt_rank", "mamba_expand", "head_dim"}
    assert PUBLISHED["layer_period"] == 14 == CONFIG["attn_layer_period"]
    entry = next(c for c in SPEC["configs"] if c["name"] == NAME)
    assert entry["reduced"] == [] and entry["source"] == CONFIG["source"] \
        == PUBLISHED["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    for key in ("weights", "context", "kernels"):
        assert len(CONFIG["departures"][key]) >= 80
    assert "0.001" in CONFIG["departures"]["weights"] \
        and "log(1..16)" in CONFIG["departures"]["weights"]


def test_every_assumed_item_has_its_why():
    assert set(PUBLISHED["assumed"]) == {
        "head_dim", "layer_type_rule", "feed_forward", "ssm_state_dtype",
        "mamba_dt_b_c_rmsnorm"}
    for key, item in PUBLISHED["assumed"].items():
        assert len(item["why"]) >= 40, key
        assert CONFIG[key] == item["value"]
        assert CONFIG["assumed"][key] == item["why"]
    assert CONFIG["head_dim"] == CONFIG["hidden_size"] \
        // CONFIG["num_attention_heads"] == 128
    assert CONFIG["ssm_state_dtype"] == "float32"


def test_the_published_file_is_the_catalogs_entry():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog in this environment")
    with open(path) as f:
        entry = next(json.loads(line) for line in f
                     if '"name": "AI21-Jamba2-3B"' in line)
    assert PUBLISHED["config"] == entry["config"]
    assert PUBLISHED["source"] == entry["source_url"]
    assert entry["head_dim"] is None and entry["layers"] == 28


def test_the_traffic_file_holds_the_mix_and_no_engine_key():
    assert TRAFFIC["driver"] == "serve_closed_loop"
    assert (TRAFFIC["clients"], TRAFFIC["set_size"],
            TRAFFIC["ramp_per_step"]) == (256, 256, 4)
    assert TRAFFIC["prompt_len"] == {"dist": "uniform", "min": 65, "max": 128}
    assert TRAFFIC["new_tokens"] == {"dist": "loguniform", "min": 512,
                                     "max": 2048}
    warm = TRAFFIC["warmup"]
    assert (warm["min_seconds"], warm["quiet_steps"], warm["max_seconds"],
            warm["hints"]) == (50.0, 64, 600.0, "reason-ssm-closed256")
    assert (TRAFFIC["drain_s"], TRAFFIC["trace_slice_s"]) == (60.0, 3.0)
    others = {load("traffic", n)["set_seed"]
              for n in os.listdir(os.path.join(BENCH, "traffic"))
              if n != "reason-ssm-closed256.json"
              and "set_seed" in load("traffic", n)}
    assert TRAFFIC["set_seed"] not in others
    engine_keys = set(CONFIG["engine"]) | {"engine", "serving", "lattice"}
    assert not engine_keys & set(TRAFFIC)
    assert TRAFFIC["prompt_len"]["max"] + TRAFFIC["new_tokens"]["max"] \
        <= CONFIG["engine"]["max_seq_len"]
    # one caller a state slot: the slots bound the batch
    assert TRAFFIC["clients"] == CONFIG["engine"]["max_sequences"]


def test_the_cell_and_its_metrics_are_listed():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "reason-ssm-closed256", 1)
    # appended after the cells that were here (a later family comes after)
    names = [w["name"] for w in SPEC["workloads"]]
    assert names.index(CELL) > names.index("serve.reason-swa-closed256")
    assert len(cell["why"]) <= 200
    per_layer = {m["name"]: m for m in SPEC["per_layer"]}
    new = ("ssm_time_share", "ssm_decode_roofline", "ssm_prefill_roofline",
           "ssm_slots_peak_share")
    listed = [m["name"] for m in SPEC["per_layer"]]
    at = listed.index(new[0])
    assert listed[at:at + 4] == list(new) and "train_mfu" in listed[:at]
    for name in new:
        assert per_layer[name]["workloads"] == [CELL], name
        assert per_layer[name]["moves"] == "serve_tok_s"
        metric = load("metrics", name + ".json")
        assert metric["unit"] == "%" == per_layer[name]["unit"]
        assert (metric["layer"], metric["better"], metric["source"]) == (
            per_layer[name]["layer"], per_layer[name]["better"],
            per_layer[name]["source"])
        # the three configurations that were here, each with its reason
        assert set(metric["not_reported"]) == {
            "mistral-7b-serve-8l", "pangu-ultra-moe-serve-5l-ep16",
            "laguna-s-serve-5l-ep16"}
        assert all(len(w) >= 20 for w in metric["not_reported"].values())
    assert load("metrics", "ssm_time_share.json")["args"] == {
        "patterns": ["^ssm_"], "of": "busy"}
    assert load("metrics", "ssm_slots_peak_share.json")["args"] == {
        "names": ["^fastgen\\.step$"], "value": "attr:ssm_slots_held",
        "of_config": "engine.max_sequences"}
    assert per_layer["ssm_slots_peak_share"]["layer"] == "KV manager"
    listed = {m["name"] for m in SPEC["per_layer"] + SPEC["end_to_end"]
              if CELL in m.get("workloads", [])}
    end_to_end = {m["name"] for m in SPEC["end_to_end"]}
    reports = (listed & end_to_end) | {"setup_s"}
    assert {"serve_tok_s", "setup_s"} <= reports
    # every metric that was here, written for the cell's driver, whose
    # ``moves`` the cell reports is joined or left out with a reason, never
    # in silence (a later family's own metrics follow and are its to list)
    for m in SPEC["per_layer"][:at]:
        name = m["name"]
        drivers = load("metrics", name + ".json")["drivers"]
        if "serve_closed_loop" in drivers and m["moves"] in reports \
                and name not in CONFIG["not_reported"]:
            assert CELL in m["workloads"], name
    assert {"kv_pages_peak_share", "kv_fill_share",
            "compiles_in_window.serve", "program_compile_s",
            "program_trace_lower_s", "programs_on_path.setup",
            "program_cache_hit_share.setup"} <= listed
    assert not listed & set(CONFIG["not_reported"])
    assert {"paged_attention_roofline", "mla_attn_time_share",
            "mla_decode_roofline", "moe_expert_roofline",
            "window_attn_time_share", "kv_window_pages_peak_share"} \
        <= set(CONFIG["not_reported"])
    for why in CONFIG["not_reported"].values():
        assert len(why) >= 20
    assert "2 of 28" in CONFIG["not_reported"]["paged_attention_roofline"]


def test_the_memory_the_issue_reckons():
    """Weights 6.06 GB, the state pool 2.39 GB, the page pool 0.54 GB: 9.0
    GB of the chip's 16, over the floor of a quarter."""
    from benchmark import flops_jamba as flops
    eng = CONFIG["engine"]
    weights = 2 * flops.total_params(CONFIG)
    state = (eng["max_sequences"] + 1) * flops.slot_bytes(CONFIG)
    pages = (eng["num_pages"] + 1) * eng["page_size"] \
        * flops.kv_bytes_per_token(CONFIG)
    assert round(weights / 1e9, 2) == 6.06
    assert round(state / 1e9, 2) == 2.39 and round(pages / 1e9, 2) == 0.54
    assert 0.5 * 16e9 < weights + state + pages < 0.6 * 16e9
    assert "6.06 GB" in CONFIG["deployment"] \
        and "2.39 GB" in CONFIG["deployment"]
    # the mix's longest context fits the pool many times over: the slots
    # bound the batch, not the pages
    longest = TRAFFIC["prompt_len"]["max"] + TRAFFIC["new_tokens"]["max"]
    assert eng["max_sequences"] * -(-longest // eng["page_size"]) \
        > eng["num_pages"] > eng["max_sequences"] * 16


def test_counts_worked_by_hand():
    """ISSUE 34's arithmetic: a mixer 41.2M (26.2M + 0.98M + 0.82M + 13.1M
    and the small parts), an attention mixer 13.8M, the MLP 62.9M, 3.03B in
    all; 358 KB a slot and layer, 9.32 MB a slot, 1 KB of K/V a token."""
    from benchmark import flops_jamba as flops
    c = CONFIG
    assert flops.d_inner(c) == 5120 and flops.mamba_layers(c) == 26
    assert [i for i, k in enumerate(flops.layer_kinds(c))
            if k == "attention"] == [7, 21]
    in_, x, dt, out = (2560 * 10240, 5120 * 192, 160 * 5120 + 5120,
                       5120 * 2560)
    assert (in_, x, dt, out) == (26_214_400, 983_040, 824_320, 13_107_200)
    small = 5120 * 4 + 5120 + 5120 * 16 + 5120 + 160 + 16 + 16
    assert flops.mixer_params(c) == in_ + x + dt + out + small == 41_241_792
    assert flops.attention_params(c) == 2 * 2560 * 2560 + 2 * 2560 * 128 \
        == 13_762_560
    assert flops.mlp_params(c) == 62_914_560
    assert flops.total_params(c) == 26 * 41_241_792 + 2 * 13_762_560 \
        + 28 * 62_914_560 + 65536 * 2560 == 3_029_191_552
    assert flops.state_bytes(c) == 16 * 5120 * 4 == 327_680
    assert flops.conv_tail_bytes(c) == 3 * 5120 * 2 == 30_720
    assert flops.slot_layer_bytes(c) == 358_400
    assert flops.slot_bytes(c) == 9_318_400
    assert flops.kv_bytes_per_token(c) == 1024
    # a decode step of 256 rows: 4.8 GB through the recurrence
    row = 2 * 327_680 + (3 * 5120 + 32) * 4
    assert flops.recurrence_decode_bytes(c, 256) \
        == 26 * (256 * row + 17 * 5120 * 4) == 4_780_924_928
    assert flops.recurrence_prefill_bytes(c, 2, 200) \
        == 26 * (2 * 655_360 + 200 * 61_568 + 17 * 5120 * 4)
    assert flops.conv_decode_bytes(c, 256) == 26 * 256 * 61_440
    assert flops.recurrence_ops(c, 256) == 26 * 256 * 6 * 16 * 5120
    # a bfloat16 state would halve what a row moves: a different result,
    # and the count follows the configuration's dtype, not a kernel's
    assert flops.state_bytes(dict(c, ssm_state_dtype="bfloat16")) == 163_840


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


def test_ssm_roofline_reads_the_programs_counts(monkeypatch):
    """Two traced steps of 256 decode rows, one of which also prefills two
    prompts of 200 true tokens; the step outside the slice is left out; a
    program without the attributes (the parent) gives None, and does not
    raise."""
    from benchmark import flops_jamba as flops
    from benchmark.readers import ssm_roofline as reader
    rows = [_span(12.0, {"ssm_rows_decode": 256, "ssm_tokens_prefill": 0,
                         "prefill_rows": 0}),
            _span(13.0, {"ssm_rows_decode": 255, "ssm_tokens_prefill": 200,
                         "prefill_rows": 2}),
            _span(25.0, {"ssm_rows_decode": 9, "ssm_tokens_prefill": 9,
                         "prefill_rows": 1})]
    _tracer(monkeypatch, rows)
    decode = load("metrics", "ssm_decode_roofline.json")["args"]
    prefill = load("metrics", "ssm_prefill_roofline.json")["args"]
    assert decode == {"patterns": ["^ssm_state_update_decode"],
                      "kind": "decode"}
    assert prefill == {"patterns": ["^ssm_scan_prefill"], "kind": "prefill"}
    need = flops.recurrence_decode_bytes(CONFIG, 511, 2)
    assert reader.read(_Ctx, {}, decode) == pytest.approx(
        100 * (need / 819e9) / 0.010)
    assert reader.read(_Ctx, {}, decode) == pytest.approx(116.5, abs=0.1)
    need = flops.recurrence_prefill_bytes(CONFIG, 2, 200, 1)
    assert reader.read(_Ctx, {}, prefill) == pytest.approx(
        100 * (need / 819e9) / 0.010)
    _tracer(monkeypatch, [_span(12.0, {"tokens": 256})])
    assert reader.read(_Ctx, {}, decode) is None
    assert reader.read(_Ctx, {}, prefill) is None


def test_ssm_slots_peak_share_takes_the_largest_of_the_slice(monkeypatch):
    from benchmark.readers import span_peak_share as reader
    args = load("metrics", "ssm_slots_peak_share.json")["args"]
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
    from benchmark.builders.serve_jamba import CONTROLS
    assert set(CONTROLS) == {"bf16_state", "norms_dropped",
                             "padded_conv_tail", "skip_dropped",
                             "slot_not_zeroed"}
    for control in ("bfloat16", "norms", "tail", "D dropped", "zeroed"):
        assert control in probe["logit_rel_rms_reason"] \
            + probe["outlier_reason"], control
    assert 1.0 < probe["state_drift_limit"] < 1.15
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
    hints are the artifact's keys and name programs of its buckets only."""
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
    hints = load("hints", "reason-ssm-closed256.json")["keys"]
    assert hints == LATTICE["keys"]
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
    to a test's size."""
    config = json.loads(json.dumps(CONFIG))
    config["engine"].update(page_size=16, num_pages=512, max_sequences=32,
                            token_budget=256, max_seq_len=512)
    config["probe"].update(
        prompts=4, min_len=20, max_len=40, decode_steps=8, long_rows=2,
        long_steps=120, wide_copies=4, wide_at=[60, 110], wide_steps=2,
        min_compared=3, programs=[],
        # float32 at debug widths: the limits of a rounding of sums
        logit_rel_rms=1e-4, outlier_rel_rms=1e-3, margin=1e-3,
        # sums of a few float32 ulps: the ratio of two of them is loose
        state_drift_limit=3.0)
    return config


@pytest.fixture(scope="module")
def small_probe():
    from benchmark.builders import serve_jamba as builder
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
    assert probe["rel_rms_max"] < 1e-4
    state = engine.state_manager
    state.check_invariants()
    assert (state.free_state_slots, engine.free_blocks) == (32, 512)


@pytest.mark.parametrize("control", [
    "bf16_state", "norms_dropped", "padded_conv_tail", "skip_dropped",
    "slot_not_zeroed"])
def test_each_control_fails_the_probe(small_probe, control):
    """The probe's controls, planted in the reference (the sound program
    against a faulty reference reads what a faulty program reads against
    the sound one), against ONE serving of the waves: each reads ``ok:
    false``."""
    config, _, verdicts = small_probe
    probe = verdicts[control]
    assert not probe["ok"], probe
    assert probe["rel_rms_median"] > config["probe"]["logit_rel_rms"] \
        or probe["outlier_rows"] > config["probe"]["outlier_share"] \
        * probe["rows"]
    if control == "bf16_state":
        # the long rows drift: what ``state_drift_limit`` reads on the chip
        # (1.3 over this test's 120 steps, 1.15 over the chip's 2,000 at
        # the published widths, where bfloat16 activations set the floor)
        assert probe["state_drift"] > CONFIG["probe"]["state_drift_limit"]
    if control == "padded_conv_tail":
        # the prompt's own row is before the break: only what follows it
        assert probe["long"]["rel_rms_median"] > 1e-3


def test_the_benchmarks_reference_is_the_programs_reference():
    """The benchmark keeps its own copy; at a small size it gives what
    ``deepspeed_tpu/models/jamba_reference.py`` gives, and imports nothing
    of the program."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.core import meta

    from benchmark import reference_jamba as copy
    from benchmark.builders.serve_jamba import reference_sizes, source_of
    from deepspeed_tpu.models import jamba_reference as plain
    from deepspeed_tpu.models.jamba import JambaForCausalLM
    with open(copy.__file__) as f:
        assert "deepspeed_tpu" not in f.read().split('"""', 2)[2]
    model = JambaForCausalLM(source_of(CONFIG, True), dtype=jnp.float32)
    params = meta.unbox(model.init_params(jax.random.key(1)))
    tokens = np.random.default_rng(0).integers(0, 160, 37)
    sizes = reference_sizes(model.cfg)
    assert sizes == plain.sizes_of(model.cfg)
    got, carry = copy.forward(params, tokens, sizes)
    want, _ = plain.forward(params, jnp.asarray(tokens), sizes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert len(carry) == 6 and carry[0][0].shape == (8, 128)
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
    # the program may lie beside another root than this file's (the copy
    # test_benchmark_second_family.py makes): keep the caller's path
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [ROOT] + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    run = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--rehearse", "--seed", str(2 ** 31 + 34), "--seconds", "1",
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
    assert "ssm_slots_peak_share" in metrics
    assert "paged_attention_roofline" not in metrics
    built = next(line for line in run.stdout.splitlines()
                 if line.startswith("built:"))
    assert "'state_slots': 256" in built and "'ok': True" in built
