"""Telemetry spine (ISSUE 4): registry, tracer, SLO histograms.

Covers the tentpole's three pieces — metrics registry (percentile
correctness, snapshot, Prometheus text, HTTP endpoint), span tracer
(ring bounding, Chrome-trace schema, nesting across a REAL scheduler
step), serving SLO histograms (recorded at drain, parity with the
legacy ``ServingCounters`` facade) — plus the satellites: the
``_Timer.stop(reset=)`` fix, ``ThroughputTimer.avg_step_time``,
CSVMonitor handle reuse, ``MonitorMaster.write_registry_snapshot``,
the ``tools/check_metrics.py`` namespace lint, and the disabled-path
overhead bound.
"""

import json
import os
import sys
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.telemetry import (Counter, Gauge, Histogram,
                                     MetricsRegistry, get_registry,
                                     get_tracer, log_buckets, trace_span)
from deepspeed_tpu.telemetry import metrics as tm
from deepspeed_tpu.telemetry.tracer import SpanTracer
from deepspeed_tpu.utils.comms_logging import serving_counters


@pytest.fixture(autouse=True)
def _telemetry_hygiene():
    """Every test starts disabled with a clean tracer; the registry's
    counters/histograms are zeroed after (other suites reset() around
    their own measured windows, so zeroing is safe)."""
    telemetry.disable()
    get_tracer().clear()
    yield
    telemetry.disable()
    get_tracer().clear()
    get_registry().reset()


# ---------------------------------------------------------------------------
# registry: histogram percentiles, metric types, snapshot, exposition
# ---------------------------------------------------------------------------

class TestHistogram:
    def test_log_buckets_cover_range_geometrically(self):
        b = log_buckets(1.0, 100.0, ratio=2.0)
        assert b[0] == 1.0 and b[-1] >= 100.0
        ratios = [b[i + 1] / b[i] for i in range(len(b) - 1)]
        assert all(abs(r - 2.0) < 1e-9 for r in ratios)

    @pytest.mark.parametrize("dist", ["uniform", "lognormal", "bimodal"])
    def test_percentiles_match_numpy_within_bucket_error(self, dist):
        rng = np.random.default_rng(0)
        if dist == "uniform":
            samples = rng.uniform(0.5, 200.0, size=5000)
        elif dist == "lognormal":
            samples = np.exp(rng.normal(2.0, 1.0, size=5000))
        else:
            samples = np.concatenate([rng.uniform(1, 2, 2500),
                                      rng.uniform(80, 120, 2500)])
        h = Histogram("t", buckets=log_buckets(1e-2, 6e5))
        for s in samples:
            h.observe(float(s))
        # fixed-boundary buckets: worst-case relative error is one
        # bucket ratio (2**0.25 ~ 19%), typically far less.  Skip p50
        # for the bimodal set — its median falls in the density gap
        # between the modes, where any value in [2, 80] is a valid
        # rank-based answer and numpy's sample interpolation lands
        # mid-gap.
        quantiles = (90, 99) if dist == "bimodal" else (50, 90, 99)
        for q in quantiles:
            exact = float(np.percentile(samples, q))
            approx = h.percentile(q)
            assert approx == pytest.approx(exact, rel=0.25), \
                f"p{q}: {approx} vs numpy {exact}"
        if dist == "bimodal":
            assert 1.0 <= h.percentile(50) <= 80.0
        assert h.count == len(samples)
        assert h.mean == pytest.approx(float(samples.mean()), rel=1e-6)

    def test_empty_and_reset(self):
        h = Histogram("t")
        assert h.percentile(99) == 0.0 and h.mean == 0.0
        h.observe(5.0)
        h.reset()
        assert h.count == 0 and h.sum == 0.0

    def test_overflow_bucket(self):
        h = Histogram("t", buckets=[1.0, 2.0])
        h.observe(1e9)   # beyond the last bound
        assert h.count == 1
        assert h.percentile(99) == 2.0  # clamped to the last bound


class TestRegistry:
    def test_counter_gauge_roundtrip(self):
        r = MetricsRegistry()
        c = r.counter("ds_test_x_total")
        c.inc()
        c.inc(4)
        assert c.value == 5
        g = r.gauge("ds_test_g")
        g.set(2.5)
        assert r.snapshot() == {"ds_test_g": 2.5, "ds_test_x_total": 5}

    def test_same_name_returns_same_metric(self):
        r = MetricsRegistry()
        assert r.counter("a") is r.counter("a")
        with pytest.raises(TypeError):
            r.gauge("a")

    def test_callback_gauge_reads_live_value(self):
        r = MetricsRegistry()
        box = {"v": 1}
        r.gauge_fn("ds_test_live", lambda: box["v"])
        assert r.snapshot()["ds_test_live"] == 1
        box["v"] = 7
        assert r.snapshot()["ds_test_live"] == 7
        r.reset()  # reset keeps the binding
        assert r.snapshot()["ds_test_live"] == 7

    def test_snapshot_flattens_histograms(self):
        r = MetricsRegistry()
        h = r.histogram("ds_test_lat_ms")
        for v in (1.0, 2.0, 3.0):
            h.observe(v)
        snap = r.snapshot()
        for suffix in ("_p50", "_p90", "_p99", "_count", "_mean"):
            assert f"ds_test_lat_ms{suffix}" in snap
        assert snap["ds_test_lat_ms_count"] == 3

    def test_prometheus_text_exposition(self):
        r = MetricsRegistry()
        r.counter("ds_test_c_total", help="a counter").inc(3)
        r.gauge("ds_test_g").set(1.5)
        h = r.histogram("ds_test_h", buckets=[1.0, 10.0])
        h.observe(0.5)
        h.observe(5.0)
        text = r.prometheus_text()
        assert "# TYPE ds_test_c_total counter" in text
        assert "ds_test_c_total 3" in text
        assert "# HELP ds_test_c_total a counter" in text
        assert "# TYPE ds_test_g gauge" in text
        assert 'ds_test_h_bucket{le="1"} 1' in text
        assert 'ds_test_h_bucket{le="10"} 2' in text
        assert 'ds_test_h_bucket{le="+Inf"} 2' in text
        assert "ds_test_h_count 2" in text


# ---------------------------------------------------------------------------
# legacy facade parity + namespace lint
# ---------------------------------------------------------------------------

def test_serving_counters_facade_is_registry_backed():
    serving_counters.reset()
    serving_counters.record_step()
    serving_counters.record_program(h2d_bytes=100)
    serving_counters.record_d2h(8)
    serving_counters.record_prefix_lookup(64, 32)
    serving_counters.record_prefill(32)
    # legacy field names and the ds_serving_* registry metrics are ONE
    # storage
    assert serving_counters.steps == tm.SERVING_STEPS.value == 1
    assert serving_counters.programs == tm.SERVING_PROGRAMS.value == 1
    assert serving_counters.h2d_bytes == 100
    assert serving_counters.prefix_hit_tokens == 32
    snap = get_registry().snapshot()
    assert snap["ds_serving_steps_total"] == 1
    assert snap["ds_serving_h2d_bytes_total"] == 100
    assert snap["ds_serving_prefix_lookup_tokens_total"] == 64
    # legacy derived snapshot still works off the same storage
    legacy = serving_counters.snapshot()
    assert legacy["steps"] == 1 and legacy["prefix_hit_rate"] == 0.5
    serving_counters.reset()
    assert serving_counters.steps == 0 and tm.SERVING_STEPS.value == 0


def test_check_metrics_lint_clean():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import check_metrics
    assert check_metrics.check() == []


def test_check_metrics_lint_catches_drift(tmp_path):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import check_metrics
    # a DESIGN.md missing the table must flag every metric
    bad = tmp_path / "DESIGN.md"
    bad.write_text("# nothing documented\n")
    errors = check_metrics.check(design_path=str(bad))
    assert len(errors) >= len(get_registry().all_metrics())
    # off-convention names are rejected by the pattern
    assert check_metrics.NAME_RE.match("ds_serving_steps_total")
    assert not check_metrics.NAME_RE.match("ds_bogusarea_x")
    assert not check_metrics.NAME_RE.match("serving_steps")
    assert not check_metrics.NAME_RE.match("ds_serving_BadCase")


# ---------------------------------------------------------------------------
# tracer: ring bounding, schema, disabled-path cost
# ---------------------------------------------------------------------------

class TestTracer:
    def test_ring_buffer_bounds_retention(self):
        tr = SpanTracer(capacity=8)
        for i in range(20):
            tr.record(f"s{i}", float(i), 0.5)
        recs = tr.records()
        assert len(recs) == 8
        # oldest-first, and only the newest 8 survive
        assert [r[0] for r in recs] == [f"s{i}" for i in range(12, 20)]

    def test_resize_and_clear(self):
        tr = SpanTracer(capacity=4)
        tr.record("a", 0.0, 1.0)
        tr.resize(16)
        assert tr.records() == []
        tr.record("b", 0.0, 1.0)
        tr.clear()
        assert tr.records() == []

    def test_chrome_trace_json_schema(self, tmp_path):
        telemetry.enable()
        with trace_span("outer", {"k": "v"}):
            with trace_span("inner"):
                time.sleep(0.001)
        path = str(tmp_path / "trace.json")
        assert telemetry.dump_trace(path) == path
        doc = json.load(open(path))
        assert isinstance(doc["traceEvents"], list)
        events = {e["name"]: e for e in doc["traceEvents"]}
        assert {"outer", "inner"} <= set(events)
        for e in doc["traceEvents"]:
            # chrome://tracing / Perfetto complete-event schema
            assert e["ph"] == "X"
            for key in ("name", "ts", "dur", "pid", "tid", "args"):
                assert key in e
            assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert events["outer"]["args"]["k"] == "v"
        # nesting: inner lies within outer on the same thread
        o, i = events["outer"], events["inner"]
        assert o["tid"] == i["tid"]
        assert o["ts"] <= i["ts"]
        assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-3

    def test_disabled_spans_record_nothing(self):
        assert not telemetry.enabled()
        with trace_span("ghost"):
            pass
        assert all(r[0] != "ghost" for r in get_tracer().records())

    def test_disabled_path_overhead_under_bound(self):
        """The disabled path is one attribute read + a shared no-op
        context manager.  Bound ~1us/span with a generous CI-noise
        margin (serving-bench-env: CPU timings are noisy)."""
        assert not telemetry.enabled()
        n = 200_000
        t0 = time.perf_counter()
        for _ in range(n):
            with trace_span("hot"):
                pass
        per_span = (time.perf_counter() - t0) / n
        assert per_span < 5e-6, f"{per_span * 1e6:.2f}us/span disabled"

    def test_set_step_labels_records(self):
        telemetry.enable()
        get_tracer().set_step(41)
        with trace_span("x"):
            pass
        rec = [r for r in get_tracer().records() if r[0] == "x"][-1]
        assert rec[3] == 41


# ---------------------------------------------------------------------------
# HTTP endpoint
# ---------------------------------------------------------------------------

def test_metrics_http_endpoint_serves_all_views():
    from deepspeed_tpu.telemetry import (start_http_server,
                                         stop_http_server)
    serving_counters.reset()
    serving_counters.record_step()
    telemetry.enable()
    with trace_span("http.span"):
        pass
    srv = start_http_server(0)   # ephemeral port
    try:
        port = srv.server_address[1]
        base = f"http://127.0.0.1:{port}"
        text = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert "ds_serving_steps_total 1" in text
        snap = json.loads(urllib.request.urlopen(
            f"{base}/snapshot").read())
        assert snap["ds_serving_steps_total"] == 1
        trace = json.loads(urllib.request.urlopen(
            f"{base}/trace").read())
        assert any(e["name"] == "http.span"
                   for e in trace["traceEvents"])
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/nope")
    finally:
        stop_http_server()


# ---------------------------------------------------------------------------
# timer satellites
# ---------------------------------------------------------------------------

def test_timer_stop_reset_replaces_accumulator():
    from deepspeed_tpu.utils.timer import _Timer
    t = _Timer("t")
    t.start()
    t.stop()
    t.start()
    t.stop()
    assert t.count == 2
    two = t._elapsed
    t.start()
    time.sleep(0.002)
    t.stop(reset=True)       # REPLACES instead of accumulating
    assert t.count == 1
    assert t._elapsed >= 0.002
    assert t._elapsed != two
    t.start()
    t.stop(reset=True, record=False)
    assert t.count == 0 and t._elapsed == 0.0


def test_throughput_timer_avg_step_time_feeds_profiler():
    from deepspeed_tpu.utils.timer import ThroughputTimer
    tt = ThroughputTimer(batch_size=4, start_step=1)
    for _ in range(3):
        tt.start()
        time.sleep(0.001)
        tt.stop(global_step=True, report_speed=False)
    assert tt.avg_step_time() > 0.0
    assert tt.avg_samples_per_sec() > 0.0
    # registry-backed: the histogram saw every step, the gauge the rate
    assert tm.TRAIN_STEP_TIME_MS.count >= 3
    assert tm.TRAIN_SAMPLES_PER_SEC.value == pytest.approx(
        tt.avg_samples_per_sec())


# ---------------------------------------------------------------------------
# monitor satellites
# ---------------------------------------------------------------------------

def test_csv_monitor_reuses_handles_across_batches(tmp_path):
    from deepspeed_tpu.monitor.monitor import CSVMonitor
    from deepspeed_tpu.runtime.config import load_config
    cfg = load_config({"csv_monitor": {"enabled": True,
                                       "output_path": str(tmp_path)}})
    mon = CSVMonitor(cfg.csv_monitor)
    mon.write_events([("a/x", 1.0, 0), ("a/y", 2.0, 0)])
    assert len(mon._files) == 2          # cache actually used now
    f_first = mon._files["a/x"][0]
    mon.write_events([("a/x", 3.0, 1)])
    assert mon._files["a/x"][0] is f_first   # same open handle
    mon.close()
    body = open(os.path.join(str(tmp_path), cfg.csv_monitor.job_name,
                             "a_x.csv")).read()
    assert body.count("step") == 1       # header written exactly once
    assert "1.0" in body and "3.0" in body


def test_monitor_master_publishes_registry_snapshot(tmp_path):
    from deepspeed_tpu.monitor.monitor import MonitorMaster
    from deepspeed_tpu.runtime.config import load_config
    serving_counters.reset()
    serving_counters.record_step()
    cfg = load_config({"csv_monitor": {"enabled": True,
                                       "output_path": str(tmp_path)}})
    master = MonitorMaster(cfg)
    master.write_registry_snapshot(step=7)
    files = [os.path.join(r, f) for r, _, fs in os.walk(tmp_path)
             for f in fs]
    steps_csv = [f for f in files
                 if f.endswith("Telemetry_ds_serving_steps_total.csv")]
    assert steps_csv, f"no snapshot csv in {files}"
    assert "7,1.0" in open(steps_csv[0]).read()


def test_telemetry_config_block_applies():
    from deepspeed_tpu.runtime.config import load_config
    cfg = load_config({"telemetry": {"enabled": True, "trace_buffer": 128}})
    try:
        cfg.telemetry.apply()
        assert telemetry.enabled()
        assert get_tracer()._cap == 128
    finally:
        telemetry.disable()
        get_tracer().resize(int(os.environ.get("DS_TRACE_BUFFER",
                                               "65536")))
    # enabled: null inherits the process state
    cfg2 = load_config({})
    assert cfg2.telemetry.enabled is None
    cfg2.telemetry.apply()
    assert not telemetry.enabled()


# ---------------------------------------------------------------------------
# the real thing: spans + SLO histograms across a live scheduler
# ---------------------------------------------------------------------------

def _slo_engine():
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            KVCacheConfig,
                                            RaggedInferenceEngineConfig,
                                            RaggedInferenceModel,
                                            StateManagerConfig)
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    from flax.core import meta
    model_def = LlamaForCausalLM("debug", max_seq_len=256,
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
                                         max_ragged_batch_size=256))
    return InferenceEngineV2(
        RaggedInferenceModel(cfg, params, kv_config=kv_cfg), econf)


class TestSchedulerTelemetry:
    def test_spans_nest_and_slos_record_across_real_steps(self, tmp_path):
        from deepspeed_tpu.inference.v2 import (FastGenScheduler,
                                                SamplingParams)
        eng = _slo_engine()
        telemetry.enable()
        get_tracer().clear()
        for h in (tm.FASTGEN_TTFT_MS, tm.FASTGEN_ITL_MS,
                  tm.FASTGEN_QUEUE_WAIT_MS, tm.FASTGEN_STEP_MS):
            h.reset()
        serving_counters.reset()

        sched = FastGenScheduler(eng)
        n_req, max_new = 3, 4
        rng = np.random.default_rng(0)
        t_submit = time.perf_counter()
        for uid in range(n_req):
            sched.submit(uid, rng.integers(0, 32, size=12).tolist(),
                         SamplingParams(max_new_tokens=max_new,
                                        temperature=0.0))
        results = sched.run_to_completion()
        wall = time.perf_counter() - t_submit
        assert all(len(results[u]) == max_new for u in range(n_req))

        # -- SLO histograms recorded per request at drain time ----------
        assert tm.FASTGEN_TTFT_MS.count == n_req
        assert tm.FASTGEN_QUEUE_WAIT_MS.count == n_req
        assert tm.FASTGEN_ITL_MS.count == n_req * (max_new - 1)
        assert tm.FASTGEN_STEP_MS.count == serving_counters.steps > 0
        # percentile sanity vs the real wall clock: every latency is
        # positive and below the whole run's wall time
        snap = get_registry().snapshot()
        for key in ("ds_fastgen_ttft_ms_p99", "ds_fastgen_itl_ms_p50",
                    "ds_fastgen_queue_wait_ms_p50"):
            assert 0.0 < snap[key] < wall * 1e3 * 1.2, key
        # steps histogram and steps counter agree in the snapshot too
        assert snap["ds_fastgen_step_ms_count"] == \
            snap["ds_serving_steps_total"]

        # -- span nesting: step > admission/dispatch/drain --------------
        recs = get_tracer().records()
        by_name = {}
        for r in recs:
            by_name.setdefault(r[0], []).append(r)
        assert "fastgen.step" in by_name
        assert "fastgen.admission" in by_name
        assert "fastgen.drain" in by_name
        dispatch = [n for n in by_name if n.startswith("fastgen.dispatch.")]
        assert dispatch, f"no dispatch spans in {sorted(by_name)}"
        # engine + kv internals nest under the scheduler phases
        assert "engine.build_batch" in by_name
        assert "kv.flush" in by_name

        def contained(inner, outers):
            s, e = inner[1], inner[1] + inner[2]
            return any(o[1] <= s and e <= o[1] + o[2] + 1e-6
                       for o in outers)

        steps = by_name["fastgen.step"]
        for name in (["fastgen.admission", "fastgen.drain"] + dispatch):
            for rec in by_name[name]:
                assert contained(rec, steps), \
                    f"{name} span not inside any fastgen.step"
        # every span carries the scheduler step label monotonically
        step_labels = [r[3] for r in by_name["fastgen.step"]]
        assert step_labels == sorted(step_labels)

        # -- Chrome-trace round trip ------------------------------------
        path = str(tmp_path / "sched_trace.json")
        telemetry.dump_trace(path)
        doc = json.load(open(path))
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"fastgen.step", "fastgen.admission",
                "fastgen.drain"} <= names
        assert all(e["ph"] == "X" for e in doc["traceEvents"])
        ts = [e["ts"] for e in doc["traceEvents"]]
        assert ts == sorted(ts)   # dump orders by start time

    def test_disabled_scheduler_records_nothing(self):
        from deepspeed_tpu.inference.v2 import (FastGenScheduler,
                                                SamplingParams)
        eng = _slo_engine()
        for h in (tm.FASTGEN_TTFT_MS, tm.FASTGEN_ITL_MS,
                  tm.FASTGEN_QUEUE_WAIT_MS, tm.FASTGEN_STEP_MS):
            h.reset()
        get_tracer().clear()
        assert not telemetry.enabled()
        sched = FastGenScheduler(eng)
        sched.submit(0, list(range(8)),
                     SamplingParams(max_new_tokens=2, temperature=0.0))
        sched.run_to_completion()
        assert tm.FASTGEN_TTFT_MS.count == 0
        assert tm.FASTGEN_STEP_MS.count == 0
        # the only records a disabled scheduler leaves are the step
        # programs it formed (ISSUE 24: written whatever the switch says)
        # and one ``fastgen.stall`` a step that paused (ISSUE 52: here a
        # step that formed its program on the path, ``cause=compile``)
        stalls = [r for r in get_tracer().records()
                  if r[0] == "fastgen.stall"]
        assert all(r[5]["cause"] == "compile" for r in stalls)
        assert all(r[0].startswith(("engine.program", "fastgen.stall"))
                   for r in get_tracer().records())

    def test_train_batch_spans_and_monitor_snapshot(self, tmp_path):
        """Training side of the spine: train.* spans nest, the step-time
        histogram fills, and the full registry snapshot rides the
        monitor fan-out at the steps_per_print cadence."""
        import deepspeed_tpu as dst
        from deepspeed_tpu.models.base import SimpleModel
        hidden = 64
        engine, _, _, _ = dst.initialize(
            model=SimpleModel(hidden),
            config={
                "train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
                "zero_optimization": {"stage": 0},
                "steps_per_print": 1,
                "csv_monitor": {"enabled": True,
                                "output_path": str(tmp_path)},
                # config block (not env) turns the spine on
                "telemetry": {"enabled": True},
            })
        assert telemetry.enabled()
        get_tracer().clear()
        tm.TRAIN_STEP_TIME_MS.reset()
        gbs = (engine.train_micro_batch_size_per_gpu()
               * engine.topology.batch_shard_size)
        rng = np.random.default_rng(0)
        batch = {"x": rng.normal(size=(gbs, hidden)).astype(np.float32),
                 "y": rng.normal(size=(gbs, hidden)).astype(np.float32)}
        for _ in range(3):
            engine.train_batch(batch)

        # steps before start_step (=2, the JIT-compile warmup) are
        # excluded from the latency histogram, like avg_samples_per_sec
        assert tm.TRAIN_STEP_TIME_MS.count == 2
        by_name = {}
        for r in get_tracer().records():
            by_name.setdefault(r[0], []).append(r)
        assert {"train.batch", "train.place_batch",
                "train.step"} <= set(by_name)
        outer = by_name["train.batch"]
        for name in ("train.place_batch", "train.step"):
            for rec in by_name[name]:
                s, e = rec[1], rec[1] + rec[2]
                assert any(o[1] <= s and e <= o[1] + o[2] + 1e-6
                           for o in outer), f"{name} outside train.batch"
        # spans are labelled with the engine's global step
        assert {r[3] for r in outer} == {0, 1, 2}
        # registry snapshot rode the monitor at steps_per_print=1
        files = [os.path.join(r, f) for r, _, fs in os.walk(tmp_path)
                 for f in fs]
        assert any(f.endswith("Telemetry_ds_train_step_time_ms_p50.csv")
                   for f in files), files

    def test_kv_gauges_bound_to_live_allocator(self):
        eng = _slo_engine()
        snap = get_registry().snapshot()
        alloc = eng.state_manager.kv_cache.allocator
        assert snap["ds_kv_total_pages"] == alloc.total_pages == 64
        assert snap["ds_kv_free_pages"] == alloc.free_pages
        assert snap["ds_kv_live_pages"] == 0
