"""Health watchdog + flight recorder (ISSUE 5).

Covers the tentpole — non-finite sentinel on a REAL fp32 train loop fed
a NaN batch, the EWMA step-time anomaly detector (counter, warn-once
per storm, trace artifact), goodput accounting, serving step-cache
hit/miss/compile-on-path counters on a deliberately un-precompiled
bucket, the postmortem bundle (five artifacts, all loadable, written
automatically when an exception escapes ``train_batch`` / the FastGen
step loop), the ``/healthz`` endpoint — plus the satellites: the
monitor-write drop counter, the ``DS_POSTMORTEM_ON_EXIT`` handler, and
the disabled-path overhead bound for every new instrumentation site.
"""

import gc
import json
import math
import os
import signal
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.telemetry import (get_flight_recorder, get_registry,
                                     get_tracer, get_watchdog,
                                     trace_span)
from deepspeed_tpu.telemetry import metrics as tm

BUNDLE = {"registry.json", "trace.json", "config.json", "events.json",
          "env.json"}


@pytest.fixture(autouse=True)
def _watchdog_hygiene():
    """Every test starts disabled with clean watchdog/recorder state and
    default thresholds; the registry is zeroed after."""
    wd = get_watchdog()
    rec = get_flight_recorder()
    saved = (wd.enabled, wd.threshold, wd.warmup, wd.postmortem_dir,
             rec.postmortem_dir)
    telemetry.disable()
    get_tracer().clear()
    wd.reset()
    rec.clear()
    rec._crash_dumped = False
    yield
    telemetry.disable()
    (wd.enabled, wd.threshold, wd.warmup, wd.postmortem_dir,
     rec.postmortem_dir) = saved
    wd.reset()
    rec.clear()
    rec._crash_dumped = False
    get_tracer().clear()
    get_registry().reset()


@pytest.fixture
def warn_log(monkeypatch):
    """Captured logger.warning calls, rendered to strings."""
    calls = []
    from deepspeed_tpu.utils.logging import logger

    def capture(fmt, *args, **kw):
        try:
            calls.append(str(fmt) % args if args else str(fmt))
        except TypeError:
            calls.append(str(fmt))
    monkeypatch.setattr(logger, "warning", capture)
    return calls


@pytest.fixture(scope="module")
def train_engine():
    import deepspeed_tpu as dst
    from deepspeed_tpu.models.base import SimpleModel
    engine, _, _, _ = dst.initialize(
        model=SimpleModel(32),
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
            "zero_optimization": {"stage": 0},
            "steps_per_print": 10 ** 9,
        })
    return engine


def _train_batch_arrays(engine, fill=None):
    gbs = (engine.train_micro_batch_size_per_gpu()
           * engine.topology.batch_shard_size)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(gbs, 32)).astype(np.float32)
    if fill is not None:
        x[:] = fill
    return {"x": x,
            "y": rng.normal(size=(gbs, 32)).astype(np.float32)}


@pytest.fixture(scope="module")
def serving_engine():
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            KVCacheConfig,
                                            RaggedInferenceEngineConfig,
                                            RaggedInferenceModel,
                                            StateManagerConfig)
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    from flax.core import meta
    model_def = LlamaForCausalLM("debug", max_seq_len=128,
                                 dtype=jnp.float32)
    params = meta.unbox(model_def.init_params(jax.random.key(0)))
    cfg = model_def.cfg
    kv_cfg = KVCacheConfig(num_layers=cfg.num_layers,
                           kv_heads=cfg.kv_heads,
                           head_dim=cfg.dims_per_head, page_size=16,
                           num_pages=64, dtype=jnp.float32)
    econf = RaggedInferenceEngineConfig(
        state_manager=StateManagerConfig(max_tracked_sequences=8,
                                         max_ragged_sequence_count=8,
                                         max_ragged_batch_size=128))
    return InferenceEngineV2(
        RaggedInferenceModel(cfg, params, kv_config=kv_cfg), econf)


# ---------------------------------------------------------------------------
# non-finite sentinel on a real train loop
# ---------------------------------------------------------------------------

class TestNonFiniteSentinel:
    def test_nan_batch_fires_sentinel_warn_once(self, train_engine,
                                                warn_log):
        telemetry.enable()
        nan_batch = _train_batch_arrays(train_engine, fill=np.nan)
        base = tm.TRAIN_NONFINITE.value
        loss = train_engine.train_batch(nan_batch)
        assert math.isnan(loss)
        # loss AND grad_norm both came back non-finite (host-fetched)
        assert tm.TRAIN_NONFINITE.value >= base + 2
        first = [w for w in warn_log if "non-finite" in w]
        assert first, f"no non-finite warning in {warn_log}"
        # second NaN batch: counters grow, no new warnings (warn-once)
        n_warn = len([w for w in warn_log if "non-finite" in w])
        after = tm.TRAIN_NONFINITE.value
        train_engine.train_batch(nan_batch)
        assert tm.TRAIN_NONFINITE.value >= after + 2
        assert len([w for w in warn_log if "non-finite" in w]) == n_warn
        # flight recorder saw the verdicts
        kinds = {e["kind"] for e in get_flight_recorder().events()}
        assert "watchdog.nonfinite" in kinds
        # healthz verdict degrades
        assert get_watchdog().health()["status"] == "nonfinite"

    def test_goodput_gauges_fed_from_train_phases(self, train_engine):
        telemetry.enable()
        get_watchdog().reset()
        batch = _train_batch_arrays(train_engine)
        for _ in range(2):
            train_engine.train_batch(batch)
        snap = get_registry().snapshot()
        # the engine is past step 0 so the steps bill the step phase
        assert snap["ds_train_goodput_ratio"] > 0.0
        # both read the step phase; the wall-clock denominator advances
        # between the two snapshot reads, so compare approximately
        assert snap["ds_train_goodput_ratio"] == pytest.approx(
            snap["ds_train_step_fraction"], rel=0.05)
        fracs = [snap[f"ds_train_{p}_fraction"] for p in
                 ("compile", "input_wait", "step", "checkpoint", "idle")]
        assert all(0.0 <= f <= 1.0 for f in fracs)
        assert sum(fracs) == pytest.approx(1.0, abs=0.05)

    def test_handled_fp16_overflow_is_not_nonfinite(self):
        """A routine fp16 dynamic-loss-scale overflow (overflow IS
        ~isfinite(gnorm)) feeds only the skip counter — the non-finite
        verdict is reserved for applied steps, so /healthz never 503s a
        healthy loss-scaling run."""
        import deepspeed_tpu as dst
        from deepspeed_tpu.models.base import SimpleModel
        engine, _, _, _ = dst.initialize(
            model=SimpleModel(16),
            config={
                "train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
                "zero_optimization": {"stage": 0},
                "fp16": {"enabled": True},
                "steps_per_print": 10 ** 9,
            })
        telemetry.enable()
        gbs = 2 * engine.topology.batch_shard_size
        inf_batch = {"x": np.full((gbs, 16), np.inf, np.float32),
                     "y": np.zeros((gbs, 16), np.float32)}
        scale_before = engine.loss_scale
        engine.train_batch(inf_batch)
        assert engine.skipped_steps == 1
        assert engine.loss_scale <= scale_before
        assert tm.TRAIN_OVERFLOW_SKIP.value == 1
        assert tm.TRAIN_NONFINITE.value == 0
        assert get_watchdog().health()["status"] == "ok"

    def test_nonfinite_verdict_heals_after_calm_steps(self):
        """The /healthz verdict is recency-based: finite train steps
        clear it (the cumulative counter keeps the history)."""
        telemetry.enable()
        wd = get_watchdog()
        wd.note_nonfinite("loss", 3, float("nan"))
        assert wd.health()["status"] == "nonfinite"
        for i in range(wd.calm_steps + 1):
            wd.observe_step_time("train", 10.0, step=4 + i)
        assert wd.health()["status"] == "ok"
        assert tm.TRAIN_NONFINITE.value == 1   # history preserved

    def test_disabled_train_loop_records_nothing(self, train_engine):
        assert not telemetry.enabled()
        base = tm.TRAIN_NONFINITE.value
        train_engine.train_batch(
            _train_batch_arrays(train_engine, fill=np.nan))
        assert tm.TRAIN_NONFINITE.value == base
        assert get_flight_recorder().events() == []


# ---------------------------------------------------------------------------
# EWMA step-time anomaly detector
# ---------------------------------------------------------------------------

class TestAnomalyDetector:
    def test_slow_step_flagged_warn_once_and_trace_dumped(
            self, tmp_path, warn_log):
        telemetry.enable()
        wd = get_watchdog()
        wd.postmortem_dir = str(tmp_path)
        with trace_span("anomaly.filler"):
            pass
        for i in range(wd.warmup + 2):
            wd.observe_step_time("train", 10.0, step=i)
        base = tm.TRAIN_ANOMALY.value
        wd.observe_step_time("train", 200.0, step=99)
        assert tm.TRAIN_ANOMALY.value == base + 1
        storms = [w for w in warn_log if "anomaly storm" in w]
        assert len(storms) == 1 and "train" in storms[0]
        trace_path = tmp_path / "anomaly_train_step99.json"
        assert trace_path.exists()
        doc = json.load(open(trace_path))
        assert any(e["name"] == "anomaly.filler"
                   for e in doc["traceEvents"])
        # further anomalies in the same storm: counted, not re-warned
        wd.observe_step_time("train", 300.0, step=100)
        assert tm.TRAIN_ANOMALY.value == base + 2
        assert len([w for w in warn_log if "anomaly storm" in w]) == 1
        assert wd.health()["status"] == "anomaly"
        # calm steps end the storm; the next spike warns again
        for i in range(wd.calm_steps):
            wd.observe_step_time("train", 10.0, step=101 + i)
        assert wd.health()["status"] == "ok"
        wd.observe_step_time("train", 200.0, step=200)
        assert len([w for w in warn_log if "anomaly storm" in w]) == 2

    def test_anomalous_samples_do_not_move_the_ewma(self):
        telemetry.enable()
        wd = get_watchdog()
        for i in range(wd.warmup + 2):
            wd.observe_step_time("fastgen", 10.0, step=i)
        mean_before = wd._kinds["fastgen"].mean_ms
        wd.observe_step_time("fastgen", 500.0, step=50)
        assert wd._kinds["fastgen"].mean_ms == mean_before

    def test_no_verdicts_during_warmup(self):
        telemetry.enable()
        wd = get_watchdog()
        base = tm.TRAIN_ANOMALY.value
        wd.observe_step_time("train", 10.0, step=0)
        wd.observe_step_time("train", 500.0, step=1)  # warmup: ignored
        assert tm.TRAIN_ANOMALY.value == base


# ---------------------------------------------------------------------------
# the host's pauses (ISSUE 52): the collector's hook, one record a stall
# ---------------------------------------------------------------------------

def _warm(meter, n=10):
    """``n`` empty steps on the real clocks: the stream's mean is a few
    microseconds, so the floor of 50 ms is what a step has to pass."""
    for _ in range(n):
        meter.begin()
        meter.end(4, 0)


def _paused(meter, pause):
    """One metered step that runs ``pause(meter)``; the records it left."""
    _warm(meter)
    get_tracer().clear()
    meter.begin()
    pause(meter)
    meter.end(4, 0)
    return [r[5] for r in get_tracer().records() if r[0] == "fastgen.stall"]


def _collect(meter):
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < 0.08:
        gc.collect()


def _spin(meter):
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < 0.08:
        pass


def _sleep_beside_a_spinning_thread(meter):
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            sum(range(1000))
    other = threading.Thread(target=spin)
    other.start()
    time.sleep(0.1)
    stop.set()
    other.join()


def _wait_for_the_device(meter):
    t0 = time.perf_counter()
    time.sleep(0.08)                # as the drain's d2h blocks
    meter.wait += time.perf_counter() - t0


class TestStallRecord:
    @pytest.mark.parametrize("pause, cause", [
        (_collect, "gc"), (lambda m: time.sleep(0.08), "offcpu"),
        (_spin, "python"), (_sleep_beside_a_spinning_thread, "other_thread"),
        (_wait_for_the_device, "device")],
        ids=["gc", "offcpu", "python", "other_thread", "device"])
    def test_a_pause_is_one_record_that_names_its_cause(self, pause, cause,
                                                        warn_log):
        from deepspeed_tpu.telemetry.watchdog import (StepMeter,
                                                      install_collector)
        install_collector()
        assert not telemetry.enabled()
        base = tm.FASTGEN_STALL.value
        stall, = _paused(StepMeter(), pause)
        half = 0.5 * stall["lost_ms"]
        if cause == "python" and stall["cpu_ms"] < half \
                or cause == "other_thread" \
                and stall["proc_cpu_ms"] - stall["cpu_ms"] < half:
            # a loaded host took the core from the spinning thread for
            # most of the pause: by the record's own numbers that IS time
            # off the CPU, and the rule has to say so
            cause = "offcpu"
        assert stall["cause"] == cause, stall
        assert stall["wall_ms"] >= 80 and stall["lost_ms"] >= 79
        assert stall["cpu_ms"] <= stall["wall_ms"] - stall["wait_ms"] + 1
        assert stall["gc_ms"] <= stall["wall_ms"] + stall["between_ms"]
        if cause == "gc":
            assert stall["gc_gen2"] >= 1 and stall["gc_ms"] >= 40
        if cause == "other_thread":
            assert stall["proc_cpu_ms"] - stall["cpu_ms"] >= half
        if cause == "device":
            assert stall["wait_ms"] >= 80 > stall["offcpu_ms"]
        # counted, and ONE line with the record's fields; telemetry is off:
        # no anomaly verdict, no flight event, nothing else in the ring
        assert tm.FASTGEN_STALL.value == base + 1
        line, = [w for w in warn_log if "fastgen.stall" in w]
        assert f"cause={cause}" in line and "wall_ms=" in line
        assert tm.TRAIN_ANOMALY.value == 0
        assert get_flight_recorder().events() == []
        assert [r[0] for r in get_tracer().records()] == ["fastgen.stall"]

    def test_the_rule_is_three_times_the_mean_and_fifty_ms(self):
        """Fed by hand at 10 ms a step: 2.9x the mean is sound; 3.1x is the
        detector's anomaly and, under 50 ms, no stall; 60 ms is one."""
        from deepspeed_tpu.telemetry.watchdog import StepMeter
        wd, meter = get_watchdog(), StepMeter()
        meter.begin()               # the CPU clocks' baseline

        def step(ms):
            meter.t1 = meter.t0 + ms / 1e3
            wd.observe_serving_step(meter, rows=4)
            return [r for r in get_tracer().records()
                    if r[0] == "fastgen.stall"]

        for _ in range(30):         # the mean starts at 0: 9.99 by now
            assert step(10.0) == []
        assert step(29.0) == []
        assert wd._kinds["fastgen"].anomalies == 0
        assert step(45.0) == [] and wd._kinds["fastgen"].anomalies == 1
        rec, = step(60.0)
        assert rec[5]["wall_ms"] == 60.0 and rec[2] == pytest.approx(0.06)
        assert 45.0 < rec[5]["lost_ms"] < 50.0 < rec[5]["offcpu_ms"]
        # anomalous samples do not move the mean: it holds the sound ones
        assert 10.0 < wd._kinds["fastgen"].mean_ms < 14.0
        # the detector is one: with telemetry on the same sample is the
        # anomaly verdict AND the stall, counted once each
        telemetry.enable()
        base = (tm.TRAIN_ANOMALY.value, tm.FASTGEN_STALL.value)
        assert len(step(70.0)) == 2
        assert (tm.TRAIN_ANOMALY.value, tm.FASTGEN_STALL.value) \
            == (base[0] + 1, base[1] + 1)
        assert wd._kinds["fastgen"].anomalies == 3
        events = [e for e in get_flight_recorder().events()
                  if e["kind"] == "watchdog.anomaly"]
        assert len(events) == 1 and events[0]["stream"] == "fastgen"

    def test_a_pause_between_two_steps_reads_phase_between(self):
        from deepspeed_tpu.telemetry.watchdog import StepMeter
        meter = StepMeter()
        _warm(meter)
        get_tracer().clear()
        time.sleep(0.08)            # the caller's loop
        _warm(meter, 1)
        stall, = [r[5] for r in get_tracer().records()]
        assert (stall["phase"], stall["cause"]) == ("between", "offcpu")
        assert stall["between_ms"] >= 80 > 50 > stall["wall_ms"]
        assert stall["lost_ms"] >= 79 and stall["between_cpu_ms"] < 40

    def test_a_watchdog_switched_off_judges_nothing(self):
        from deepspeed_tpu.telemetry.watchdog import StepMeter
        get_watchdog().enabled = False
        assert _paused(StepMeter(), lambda m: time.sleep(0.06)) == []
        assert get_watchdog()._kinds == {}

    def test_the_meter_costs_microseconds_a_step(self):
        """What every serving step pays with telemetry off (measured:
        ``tools/step_meter_cost.py``, CHANGES.md; the bound here leaves a
        loaded CI host its noise)."""
        from deepspeed_tpu.telemetry.watchdog import StepMeter
        meter, n = StepMeter(), 20_000
        _warm(meter, 100)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(n):
                meter.begin()
                t = time.perf_counter()
                meter.admission += time.perf_counter() - t
                t = time.perf_counter()
                meter.wait += time.perf_counter() - t
                meter.end(4, 0)
            best = min(best, (time.perf_counter() - t0) / n)
        assert best < 20e-6, f"{best * 1e6:.2f} us a step"


class TestCollectorHook:
    def test_hooked_once_and_counts_with_telemetry_off(self):
        from deepspeed_tpu.telemetry.watchdog import (get_collector,
                                                      install_collector)
        hook = install_collector()
        assert install_collector() is hook is get_collector()
        assert gc.callbacks.count(hook) == 1
        hook.loop = "fastgen"
        before = (hook.seconds, hook.collections, hook.full,
                  tm.HOST_GC_SECONDS.value)
        gc.collect()
        gc.collect(0)
        assert hook.collections == before[1] + 2
        assert hook.full == before[2] + 1
        assert hook.seconds > before[0]
        assert tm.HOST_GC_SECONDS.value - before[3] \
            == pytest.approx(hook.seconds - before[0])
        assert get_tracer().records() == []      # no span while off

    @pytest.mark.parametrize("loop", ["fastgen", "train"])
    def test_a_collection_is_a_span_of_the_loop_that_stepped(self, loop):
        from deepspeed_tpu.telemetry.watchdog import install_collector
        hook = install_collector()
        telemetry.enable()
        hook.loop = None
        gc.collect(0)               # before any loop stepped: no name
        assert get_tracer().records() == []
        hook.loop = loop
        with trace_span(loop + ".outer") as outer:
            gc.collect(1)
        gc.collect(0)               # between two steps: a root
        hook.loop = None
        inner, parent, root = get_tracer().records()
        assert inner[0] == root[0] == loop + ".gc"
        assert inner[7] == outer.id == parent[6] and root[7] is None
        assert inner[5]["generation"] == 1 and root[5]["generation"] == 0
        assert inner[5]["collected"] >= 0
        assert parent[1] <= inner[1] \
            and inner[1] + inner[2] <= parent[1] + parent[2]
        # the name passes the filter of the benchmark's trace reduction
        import re
        assert re.match(r"^(bench\.|fastgen\.|engine\.|train\.|serving\.|"
                        r"zero\.|sched\.|kv\.)", inner[0])

    def test_the_loops_tell_the_hook_who_steps(self, train_engine,
                                               serving_engine):
        from deepspeed_tpu.inference.v2 import FastGenScheduler
        from deepspeed_tpu.telemetry.watchdog import get_collector
        hook = get_collector()
        assert gc.callbacks.count(hook) == 1     # an engine was built
        train_engine.train_batch(batch=_train_batch_arrays(train_engine))
        assert hook.loop == "train"
        FastGenScheduler(serving_engine).step()
        assert hook.loop == "fastgen"


# ---------------------------------------------------------------------------
# serving step-cache / recompile accounting
# ---------------------------------------------------------------------------

class TestStepCacheAccounting:
    def test_unprecompiled_bucket_counts_miss_then_hit(
            self, serving_engine):
        for c in (tm.FASTGEN_STEP_CACHE_HIT, tm.FASTGEN_STEP_CACHE_MISS,
                  tm.FASTGEN_COMPILE_ON_PATH):
            c.reset()
        serving_engine.put([501], [np.arange(4, dtype=np.int32)])
        # nothing was precompiled: the first put compiles on-path
        assert tm.FASTGEN_STEP_CACHE_MISS.value == 1
        assert tm.FASTGEN_COMPILE_ON_PATH.value == 1
        serving_engine.flush(501)
        # identical bucket again: pure cache hit, no new compile
        serving_engine.put([502], [np.arange(4, dtype=np.int32)])
        assert tm.FASTGEN_STEP_CACHE_HIT.value == 1
        assert tm.FASTGEN_STEP_CACHE_MISS.value == 1
        assert tm.FASTGEN_COMPILE_ON_PATH.value == 1
        serving_engine.flush(502)
        health = get_watchdog().health()["step_cache"]
        assert health["miss_total"] == 1 and health["hit_total"] == 1

    def test_strict_miss_counts_without_compiling(self, serving_engine):
        model = serving_engine.model
        for c in (tm.FASTGEN_STEP_CACHE_MISS,
                  tm.FASTGEN_COMPILE_ON_PATH):
            c.reset()
        model.strict_shapes = True
        try:
            with pytest.raises(RuntimeError, match="not precompiled"):
                serving_engine.put([503],
                                   [np.arange(16, dtype=np.int32)])
        finally:
            model.strict_shapes = False
            serving_engine.flush(503)
        assert tm.FASTGEN_STEP_CACHE_MISS.value == 1
        assert tm.FASTGEN_COMPILE_ON_PATH.value == 0

    def test_recompile_storm_warns_once_naming_keys(self, warn_log):
        wd = get_watchdog()
        key = (8, 1, 8, False, "sample", True)
        for _ in range(wd.storm_compiles):
            wd.note_step_cache(hit=False, key=key,
                               compiled_on_path=True)
        storms = [w for w in warn_log if "recompile storm" in w]
        assert len(storms) == 1
        assert repr(key) in storms[0] or str(key) in storms[0]
        # still inside the same storm: no second warning
        wd.note_step_cache(hit=False, key=key, compiled_on_path=True)
        assert len([w for w in warn_log if "recompile storm" in w]) == 1


# ---------------------------------------------------------------------------
# flight recorder: bundle schema + automatic crash invocation
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_postmortem_bundle_schema(self, tmp_path):
        telemetry.enable()
        rec = get_flight_recorder()
        rec.record("unit.test", detail="schema")
        with trace_span("pm.span"):
            pass
        out = str(tmp_path / "pm")
        paths = telemetry.dump_postmortem(out)
        # conditional artifacts ride iff their subsystem has state in
        # THIS process (engine builds arm the memory ledger; completed
        # requests fill the journey log) — suite ordering must not
        # decide this test
        from deepspeed_tpu.telemetry.journey import get_journey_log
        from deepspeed_tpu.telemetry.memory import get_memory_ledger
        expect = set(BUNDLE)
        if get_memory_ledger().armed:
            expect.add("memory.json")
        if get_journey_log().tail_json() is not None:
            expect.add("journeys.json")
        assert set(paths) == expect
        docs = {name: json.load(open(p)) for name, p in paths.items()}
        # registry snapshot: the full minted namespace, flat
        assert "ds_serving_steps_total" in docs["registry.json"]
        assert "ds_train_nonfinite_total" in docs["registry.json"]
        # chrome trace loads and holds the span
        assert any(e["name"] == "pm.span"
                   for e in docs["trace.json"]["traceEvents"])
        # event log holds the recorded event with its schema
        evts = docs["events.json"]["events"]
        mine = [e for e in evts if e["kind"] == "unit.test"]
        assert mine and mine[0]["detail"] == "schema"
        assert {"ts", "kind", "step"} <= set(mine[0])
        # env capture: process identity + health verdict, no backend touch
        env = docs["env.json"]
        assert env["pid"] == os.getpid()
        assert env["health"]["status"] in ("ok", "anomaly", "nonfinite")
        assert isinstance(docs["config.json"], dict)

    def test_event_ring_is_bounded(self):
        telemetry.enable()
        rec = get_flight_recorder()
        rec.resize(16)
        try:
            for i in range(50):
                rec.record("flood", i=i)
            evts = rec.events()
            assert len(evts) == 16
            assert evts[-1]["i"] == 49 and evts[0]["i"] == 34
        finally:
            rec.resize(1024)

    def test_crash_escaping_train_batch_dumps_bundle(self, train_engine,
                                                     tmp_path):
        telemetry.enable()
        rec = get_flight_recorder()
        rec.postmortem_dir = str(tmp_path / "crash")
        bad = {"x": np.zeros((3, 32), np.float32),
               "y": np.zeros((3, 32), np.float32)}  # indivisible batch
        with pytest.raises(ValueError):
            train_engine.train_batch(bad)
        bundle_dir = tmp_path / "crash"
        assert {p.name for p in bundle_dir.iterdir()} >= BUNDLE
        evts = json.load(open(bundle_dir / "events.json"))["events"]
        crash = [e for e in evts if e["kind"] == "crash"]
        assert crash and crash[0]["where"] == "train_batch"
        assert crash[0]["exc_type"] == "ValueError"
        # engine configs were captured at build time
        cfg = json.load(open(bundle_dir / "config.json"))
        assert "runtime" in cfg

    def test_crash_escaping_fastgen_step_dumps_bundle(
            self, serving_engine, tmp_path, monkeypatch):
        from deepspeed_tpu.inference.v2 import FastGenScheduler
        telemetry.enable()
        rec = get_flight_recorder()
        rec.postmortem_dir = str(tmp_path / "fg")
        sched = FastGenScheduler(serving_engine)
        monkeypatch.setattr(
            sched, "_step_impl",
            lambda on_token: (_ for _ in ()).throw(
                RuntimeError("injected step failure")))
        with pytest.raises(RuntimeError, match="injected step failure"):
            sched.step()
        assert {p.name
                for p in (tmp_path / "fg").iterdir()} >= BUNDLE
        evts = json.load(open(tmp_path / "fg" / "events.json"))["events"]
        assert any(e["kind"] == "crash"
                   and e["where"] == "fastgen.step" for e in evts)
        # second crash in the same process records but does not re-dump
        assert rec._crash_dumped

    def test_scheduler_lifecycle_events_recorded(self, serving_engine):
        from deepspeed_tpu.inference.v2 import (FastGenScheduler,
                                                SamplingParams)
        telemetry.enable()
        rec = get_flight_recorder()
        rec.clear()
        sched = FastGenScheduler(serving_engine)
        sched.submit(601, list(range(8)),
                     SamplingParams(max_new_tokens=2, temperature=0.0))
        sched.run_to_completion()
        kinds = [e["kind"] for e in rec.events()]
        assert "request.admit" in kinds
        assert "request.done" in kinds


# ---------------------------------------------------------------------------
# /healthz endpoint
# ---------------------------------------------------------------------------

def test_healthz_endpoint_serves_verdicts():
    from deepspeed_tpu.telemetry import (start_http_server,
                                         stop_http_server)
    telemetry.enable()
    srv = start_http_server(0)
    try:
        port = srv.server_address[1]
        url = f"http://127.0.0.1:{port}/healthz"
        body = json.loads(urllib.request.urlopen(url).read())
        assert body["status"] == "ok"
        assert body["uptime_s"] > 0
        assert body["telemetry_enabled"] is True
        assert "goodput" in body and "step_cache" in body
        # an unhealthy verdict flips the HTTP status to 503
        get_watchdog().note_nonfinite("loss", 0, float("nan"))
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            urllib.request.urlopen(url)
        assert exc_info.value.code == 503
        assert json.loads(
            exc_info.value.read())["status"] == "nonfinite"
    finally:
        stop_http_server()


# ---------------------------------------------------------------------------
# satellites
# ---------------------------------------------------------------------------

def test_monitor_write_drop_counter_and_warn_once(train_engine,
                                                  warn_log):
    def boom(*args):
        raise OSError("disk full")
    train_engine._monitor_write_warned = False
    base = tm.TRAIN_MONITOR_DROP.value
    train_engine._monitor_write(boom, [])
    train_engine._monitor_write(boom, [])
    assert tm.TRAIN_MONITOR_DROP.value == base + 2
    drops = [w for w in warn_log if "monitor write failed" in w]
    assert len(drops) == 1 and "OSError" in drops[0]


def test_exit_handlers_install_and_dump_idempotently(tmp_path,
                                                     monkeypatch):
    import deepspeed_tpu.telemetry.flight_recorder as fr
    rec = fr.get_flight_recorder()
    monkeypatch.setenv("DS_POSTMORTEM_ON_EXIT", "0")
    monkeypatch.setattr(fr, "_handlers_installed", False)
    assert not fr.maybe_install_exit_handlers()   # opt-in respected
    monkeypatch.setenv("DS_POSTMORTEM_ON_EXIT", "1")
    prev_sig = signal.getsignal(signal.SIGTERM)
    try:
        assert fr.maybe_install_exit_handlers()
        assert signal.getsignal(signal.SIGTERM) is not prev_sig
        rec.postmortem_dir = str(tmp_path / "exitpm")
        rec._exit_dumped = False
        rec.dump_on_exit(signum=signal.SIGTERM)
        bundle = tmp_path / "exitpm"
        assert {p.name for p in bundle.iterdir()} >= BUNDLE
        mtime = (bundle / "registry.json").stat().st_mtime_ns
        # idempotent: a second delivery (atexit after SIGTERM) is a
        # no-op, and never raises even with an unwritable dir
        rec.postmortem_dir = "/proc/definitely/not/writable"
        rec.dump_on_exit()
        assert (bundle / "registry.json").stat().st_mtime_ns == mtime
    finally:
        signal.signal(signal.SIGTERM, prev_sig)
        rec._exit_dumped = True   # keep the registered atexit a no-op


def test_telemetry_config_block_configures_watchdog():
    from deepspeed_tpu.runtime.config import load_config
    wd = get_watchdog()
    rec = get_flight_recorder()
    cfg = load_config({"telemetry": {
        "watchdog_threshold": 5.0, "watchdog_warmup": 3,
        "postmortem_dir": "/tmp/ds-pm-test",
        "flight_recorder_events": 64}})
    try:
        cfg.telemetry.apply()
        assert wd.threshold == 5.0 and wd.warmup == 3
        assert wd.postmortem_dir == "/tmp/ds-pm-test"
        assert rec.postmortem_dir == "/tmp/ds-pm-test"
        assert rec._events.maxlen == 64
        # keep-current convention: an empty block changes nothing
        load_config({}).telemetry.apply()
        assert wd.threshold == 5.0 and wd.warmup == 3
        # watchdog off: verdict entry points become no-ops
        load_config({"telemetry": {"watchdog": False}}).telemetry.apply()
        telemetry.enable()
        base = tm.TRAIN_ANOMALY.value
        for i in range(20):
            wd.observe_step_time("train", 10.0 if i < 19 else 500.0)
        assert tm.TRAIN_ANOMALY.value == base
    finally:
        rec.resize(1024)
        wd.configure(enabled=True, threshold=3.0, warmup=8)


def test_disabled_path_overhead_for_new_sites():
    """Watchdog + flight-recorder entry points keep the spine's
    disabled-path bound (<5µs/site, generous CI-noise margin)."""
    assert not telemetry.enabled()
    wd = get_watchdog()
    rec = get_flight_recorder()
    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        with wd.track("step"):
            pass
    per = (time.perf_counter() - t0) / n
    assert per < 5e-6, f"track: {per * 1e6:.2f}us disabled"
    t0 = time.perf_counter()
    for _ in range(n):
        rec.record("hot")
    per = (time.perf_counter() - t0) / n
    assert per < 5e-6, f"record: {per * 1e6:.2f}us disabled"
    t0 = time.perf_counter()
    for _ in range(n):
        wd.observe_step_time("train", 1.0)
    per = (time.perf_counter() - t0) / n
    assert per < 5e-6, f"observe: {per * 1e6:.2f}us disabled"
    assert rec.events() == []
    assert tm.TRAIN_ANOMALY.value == 0
