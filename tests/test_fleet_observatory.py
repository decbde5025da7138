"""Fleet observatory (ISSUE 11): time series, federation, SLO burn
rates.

Covers the tentpole's three layers — the bounded time-series ring
(windowed counter rates vs hand-computed deltas, delta-windowed
histogram percentiles, ring bounding, the <5µs disabled path), the
fleet federation (exact histogram merge in-process AND through live
``/snapshot?raw=1`` + ``/fleet`` endpoints, coherent degradation when
a replica dies), and the SRE-style burn-rate evaluator (ok→warn→page→
heal transitions on synthetic series, scale-up/scale-down/rebalance
advice records in the flight recorder) — plus the satellites:
``DS_METRICS_PORT=0`` → ephemeral port + ``ds_telemetry_port`` gauge,
``/snapshot?window=``, the ``timeseries.json`` seventh postmortem
artifact, and the config plumbing.

The acceptance demo — two LIVE engine replicas in subprocesses, one
killed mid-replay through the ``serving.preempt`` chaos site while the
federated view stays coherent and the evaluator pages with scale-up
advice — is chaos-marked and rides both tier-1 and the chaos tier.
"""

import json
import os
import sys
import time
import urllib.error
import urllib.request

import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.telemetry import (Federation, MetricsRegistry,
                                     get_federation, get_registry,
                                     get_slo_evaluator, get_timeseries,
                                     serve_registry)
from deepspeed_tpu.telemetry import metrics as tm
from deepspeed_tpu.telemetry.registry import (log_buckets,
                                              percentile_from_counts)
from deepspeed_tpu.telemetry.slo import SLOEvaluator
from deepspeed_tpu.telemetry.timeseries import TimeSeries

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools"))


@pytest.fixture(autouse=True)
def _fleet_hygiene():
    """Every test starts with telemetry off and clean fleet-observatory
    singletons (the test_telemetry hygiene convention)."""
    from deepspeed_tpu.telemetry.watchdog import get_watchdog
    telemetry.disable()
    get_timeseries().disable()
    get_slo_evaluator().reset()
    get_federation().clear()
    # /healthz reads the watchdog's verdict too: a storm an earlier test of
    # this worker left behind answers 503 before the SLO block is looked at
    get_watchdog().reset()
    yield
    telemetry.disable()
    get_timeseries().disable()
    get_slo_evaluator().reset()
    get_federation().clear()
    get_registry().reset()


def _shutdown(srv):
    srv.shutdown()
    srv.server_close()


# ---------------------------------------------------------------------------
# raw snapshot: the merge substrate
# ---------------------------------------------------------------------------

class TestRawSnapshot:
    def test_shape_and_untouched_gauge_exclusion(self):
        r = MetricsRegistry()
        r.counter("ds_fastgen_tokens_total").inc(5)
        r.gauge("ds_fastgen_running").set(3)
        r.gauge("ds_fastgen_preempted")          # never set: excluded
        r.histogram("ds_fastgen_ttft_ms").observe(12.0)
        raw = r.raw_snapshot()
        assert raw["counters"] == {"ds_fastgen_tokens_total": 5}
        assert raw["gauges"] == {"ds_fastgen_running": 3}
        h = raw["hists"]["ds_fastgen_ttft_ms"]
        assert h["count"] == 1 and h["sum"] == 12.0
        assert len(h["counts"]) == len(h["bounds"]) + 1
        assert sum(h["counts"]) == 1


# ---------------------------------------------------------------------------
# tentpole: exact histogram merge across replicas
# ---------------------------------------------------------------------------

def _seeded_pair_and_union(seed=0, n1=500, n2=300):
    """Two replica registries + a third observing the union of their
    samples (the ground truth the merge must reproduce exactly)."""
    import random
    rng = random.Random(seed)
    r1, r2, union = (MetricsRegistry() for _ in range(3))
    for r in (r1, r2, union):
        r.histogram("ds_fastgen_ttft_ms")
        r.counter("ds_fastgen_tokens_total")
    for _ in range(n1):
        v = rng.lognormvariate(3, 1)
        r1.histogram("ds_fastgen_ttft_ms").observe(v)
        union.histogram("ds_fastgen_ttft_ms").observe(v)
        r1.counter("ds_fastgen_tokens_total").inc()
        union.counter("ds_fastgen_tokens_total").inc()
    for _ in range(n2):
        v = rng.lognormvariate(4, 0.5)
        r2.histogram("ds_fastgen_ttft_ms").observe(v)
        union.histogram("ds_fastgen_ttft_ms").observe(v)
        r2.counter("ds_fastgen_tokens_total").inc(2)
        union.counter("ds_fastgen_tokens_total").inc(2)
    return r1, r2, union


class TestExactHistogramMerge:
    def test_merge_then_percentile_equals_union_percentile(self):
        r1, r2, union = _seeded_pair_and_union()
        fed = Federation()
        fed.add_registry("a", r1)
        fed.add_registry("b", r2)
        view = fed.scrape()
        m = view["hists"]["ds_fastgen_ttft_ms"]
        u = union.histogram("ds_fastgen_ttft_ms")
        assert m["counts"] == u.counts
        for q in (50, 90, 99, 99.9):
            # bit-equal, not approximately: same integer counts, same
            # interpolation arithmetic
            assert percentile_from_counts(
                m["bounds"], m["counts"], m["count"], q) \
                == u.percentile(q)
        assert view["counters"]["ds_fastgen_tokens_total"] \
            == union.counter("ds_fastgen_tokens_total").value

    def test_merge_through_live_endpoints_and_fleet_view(self):
        """The same bit-equality through the real wire: two replica
        servers scraped over HTTP, merged by a third server's /fleet
        endpoint."""
        r1, r2, union = _seeded_pair_and_union(seed=7)
        s1 = serve_registry(r1)
        s2 = serve_registry(r2)
        fed = Federation()
        fed.add_http("a", f"127.0.0.1:{s1.server_address[1]}")
        fed.add_http("b", f"127.0.0.1:{s2.server_address[1]}")
        s3 = serve_registry(MetricsRegistry(), federation=fed)
        try:
            base = f"http://127.0.0.1:{s3.server_address[1]}"
            view = json.loads(urllib.request.urlopen(
                f"{base}/fleet?json=1", timeout=5).read())
            u = union.histogram("ds_fastgen_ttft_ms")
            m = view["hists"]["ds_fastgen_ttft_ms"]
            assert m["counts"] == u.counts
            for q in (50, 90, 99):
                assert view["merged"][f"ds_fastgen_ttft_ms_p{q}"] \
                    == u.percentile(q)
            assert view["merged"]["ds_fastgen_tokens_total"] \
                == union.counter("ds_fastgen_tokens_total").value
            text = urllib.request.urlopen(
                f"{base}/fleet", timeout=5).read().decode()
            assert "ds_fleet_fastgen_ttft_ms_count" in text
            assert "ds_fleet_replicas_live 2" in text
        finally:
            for s in (s1, s2, s3):
                _shutdown(s)

    def test_gauge_rollups_keep_per_replica_series(self):
        r1, r2 = MetricsRegistry(), MetricsRegistry()
        r1.gauge("ds_fastgen_running").set(3)
        r2.gauge("ds_fastgen_running").set(9)
        fed = Federation()
        fed.add_registry("a", r1)
        fed.add_registry("b", r2)
        g = fed.scrape()["gauges"]["ds_fastgen_running"]
        assert g["per_replica"] == {"a": 3, "b": 9}
        assert (g["min"], g["max"], g["sum"]) == (3, 9, 12)


# ---------------------------------------------------------------------------
# tentpole: time-series ring
# ---------------------------------------------------------------------------

class _FakeSource:
    """Synthetic raw-snapshot source with exact, hand-controlled
    values — windowed queries are asserted against hand-computed
    deltas."""

    def __init__(self):
        self.bounds = log_buckets(1e-2, 6e5)
        self.counts = [0] * (len(self.bounds) + 1)
        self.n = 0
        self.sum = 0.0
        self.counters = {"ds_fastgen_tokens_total": 0,
                         "ds_fastgen_shed_total": 0}
        self.gauges = {}

    def observe(self, v):
        import bisect
        self.counts[bisect.bisect_left(self.bounds, v)] += 1
        self.n += 1
        self.sum += v

    def __call__(self):
        return {"counters": dict(self.counters),
                "gauges": dict(self.gauges),
                "hists": {"ds_fastgen_ttft_ms": {
                    "bounds": self.bounds,
                    "counts": list(self.counts),
                    "count": self.n, "sum": self.sum}}}


class TestTimeSeries:
    def test_windowed_rates_match_hand_computed_deltas(self):
        src = _FakeSource()
        ts = TimeSeries(source=src)
        ts.configure(interval_s=1.0, retention_s=100.0)
        tok = 0
        for i, inc in enumerate([0, 100, 250, 250, 400]):
            tok += inc
            src.counters["ds_fastgen_tokens_total"] = tok
            ts.sample_now(t=float(10 * i))       # t = 0, 10, 20, 30, 40
        # window 20s: base = sample at t=20 (tok=350), newest t=40
        # (tok=1000) -> delta 650 over 20s
        assert ts.counter_delta("ds_fastgen_tokens_total", 20.0) == 650
        assert ts.counter_rate("ds_fastgen_tokens_total", 20.0) \
            == 650 / 20.0
        # full window: delta 1000 over 40s
        assert ts.counter_rate("ds_fastgen_tokens_total", 100.0) \
            == 1000 / 40.0
        # a window smaller than one interval degrades to the last
        # delta, reporting the span it actually covered
        assert ts.counter_delta("ds_fastgen_tokens_total", 1.0) == 400
        snap = ts.window_snapshot(1.0)
        assert snap["_window_covered_s"] == 10.0

    def test_delta_windowed_histogram_percentiles(self):
        """The windowed percentile is the percentile of the window's
        observations ALONE — bit-equal to a fresh histogram fed only
        those observations."""
        from deepspeed_tpu.telemetry.registry import Histogram
        src = _FakeSource()
        ts = TimeSeries(source=src)
        ts.configure(interval_s=1.0, retention_s=100.0)
        import random
        rng = random.Random(3)
        old = [rng.lognormvariate(5, 1) for _ in range(400)]
        new = [rng.lognormvariate(2, 0.3) for _ in range(100)]
        for v in old:
            src.observe(v)
        ts.sample_now(t=0.0)
        for v in new:
            src.observe(v)
        ts.sample_now(t=10.0)
        ref = Histogram("ref")
        for v in new:
            ref.observe(v)
        w = ts.hist_window("ds_fastgen_ttft_ms", 15.0)
        assert w.count == 100
        for q in (50, 90, 99):
            assert w.percentile(q) == ref.percentile(q)
        # the lifetime histogram would tell a very different story
        lifetime = Histogram("all")
        for v in old + new:
            lifetime.observe(v)
        assert w.percentile(99) < lifetime.percentile(50)

    def test_counter_reset_inside_window_degrades_gracefully(self):
        src = _FakeSource()
        ts = TimeSeries(source=src)
        ts.configure(interval_s=1.0, retention_s=100.0)
        src.counters["ds_fastgen_tokens_total"] = 900
        ts.sample_now(t=0.0)
        src.counters["ds_fastgen_tokens_total"] = 40   # reset + 40
        ts.sample_now(t=10.0)
        assert ts.counter_delta("ds_fastgen_tokens_total", 60.0) == 40

    def test_ring_bounded_by_retention(self):
        src = _FakeSource()
        ts = TimeSeries(source=src)
        ts.configure(interval_s=1.0, retention_s=10.0)   # cap = 11
        for i in range(500):
            ts.sample_now(t=float(i))
        assert len(ts.samples()) <= 11
        # oldest retained sample stays within ~retention of newest
        samples = ts.samples()
        assert samples[-1]["t"] - samples[0]["t"] <= 10.0
        doc = ts.to_json()
        assert len(doc["samples"]) <= 11

    def test_disabled_path_under_bound(self):
        ts = get_timeseries()
        assert not ts.active
        n = 200_000
        t0 = time.perf_counter()
        for _ in range(n):
            ts.maybe_sample()
        per_call = (time.perf_counter() - t0) / n
        assert per_call < 5e-6, f"{per_call * 1e6:.2f}us/call disabled"

    def test_config_block_plumbs_through_both_configs(self):
        from deepspeed_tpu.inference.v2 import RaggedInferenceEngineConfig
        cfg = RaggedInferenceEngineConfig.from_dict({"telemetry": {
            "timeseries_interval_s": 0.5,
            "timeseries_retention_s": 60.0,
            "slo_objectives": [{
                "name": "tok", "kind": "throughput_min",
                "counter": "ds_fastgen_tokens_total",
                "min_per_s": 10}],
        }})
        cfg.telemetry.apply()
        ts = get_timeseries()
        assert ts.active and ts._interval_s == 0.5
        assert get_slo_evaluator().configured
        from deepspeed_tpu.runtime.config import load_config
        rc = load_config({"telemetry": {"timeseries_interval_s": 0.25}})
        rc.telemetry.apply()
        assert ts._interval_s == 0.25


# ---------------------------------------------------------------------------
# satellites: ephemeral port, /snapshot?window, /healthz slo block
# ---------------------------------------------------------------------------

class TestServerSatellites:
    def test_env_port_zero_binds_ephemeral_and_publishes_gauge(
            self, monkeypatch):
        from deepspeed_tpu.telemetry.server import (bound_port,
                                                    maybe_start_from_env,
                                                    stop_http_server)
        stop_http_server()
        monkeypatch.delenv("DS_METRICS_PORT", raising=False)
        assert maybe_start_from_env() is None    # unset = off
        monkeypatch.setenv("DS_METRICS_PORT", "0")
        srv = maybe_start_from_env()
        try:
            assert srv is not None
            port = srv.server_address[1]
            assert port > 0                       # ephemeral, but real
            assert bound_port() == port
            assert tm.TELEMETRY_PORT.value == port
            # a second replica on the same host binds its own port —
            # through serve_registry here (one singleton per process)
            srv2 = serve_registry(MetricsRegistry())
            assert srv2.server_address[1] not in (0, port)
            _shutdown(srv2)
        finally:
            stop_http_server()

    def test_snapshot_window_param_serves_delta_values(self):
        from deepspeed_tpu.telemetry.server import (start_http_server,
                                                    stop_http_server)
        ts = get_timeseries()
        ts.configure(interval_s=1.0, retention_s=60.0)
        tm.FASTGEN_TOKENS.inc(1000)
        tm.FASTGEN_TTFT_MS.observe(999.0)
        ts.sample_now(t=0.0)
        tm.FASTGEN_TOKENS.inc(50)
        tm.FASTGEN_TTFT_MS.observe(1.0)
        ts.sample_now(t=10.0)
        srv = start_http_server(0)
        try:
            base = f"http://127.0.0.1:{srv.server_address[1]}"
            win = json.loads(urllib.request.urlopen(
                f"{base}/snapshot?window=30", timeout=5).read())
            assert win["ds_fastgen_tokens_total"] == 50    # delta
            assert win["ds_fastgen_tokens_total_per_s"] == 5.0
            assert win["ds_fastgen_ttft_ms_count"] == 1
            assert win["ds_fastgen_ttft_ms_p99"] < 2.0     # window only
            life = json.loads(urllib.request.urlopen(
                f"{base}/snapshot", timeout=5).read())
            assert life["ds_fastgen_tokens_total"] == 1050
            raw = json.loads(urllib.request.urlopen(
                f"{base}/snapshot?raw=1", timeout=5).read())
            assert raw["counters"]["ds_fastgen_tokens_total"] == 1050
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(f"{base}/snapshot?window=nan9",
                                       timeout=5)
        finally:
            stop_http_server()

    def test_snapshot_window_without_sampler_is_400(self):
        from deepspeed_tpu.telemetry.server import (start_http_server,
                                                    stop_http_server)
        assert not get_timeseries().active
        srv = start_http_server(0)
        try:
            base = f"http://127.0.0.1:{srv.server_address[1]}"
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(f"{base}/snapshot?window=10",
                                       timeout=5)
            assert e.value.code == 400
        finally:
            stop_http_server()

    def test_healthz_carries_slo_block_and_pages_503(self):
        from deepspeed_tpu.telemetry.server import (start_http_server,
                                                    stop_http_server)
        telemetry.enable()
        src = _FakeSource()
        ts = TimeSeries(source=src)
        ts.configure(interval_s=1.0, retention_s=60.0)
        ev = get_slo_evaluator()
        ev.configure([{"name": "tok", "kind": "throughput_min",
                       "counter": "ds_fastgen_tokens_total",
                       "min_per_s": 100.0, "budget": 0.1,
                       "fast_window_s": 20.0, "slow_window_s": 40.0,
                       "page_burn": 2.0, "warn_burn": 0.5}])
        ev.attach(timeseries=ts)
        srv = start_http_server(0)
        try:
            base = f"http://127.0.0.1:{srv.server_address[1]}"
            health = json.loads(urllib.request.urlopen(
                f"{base}/healthz", timeout=5).read())
            assert health["slo"]["status"] == "ok"
            # rate collapses to 0 -> burn 10 -> page -> 503
            for i in range(5):
                ts.sample_now(t=float(10 * i))
            ev.evaluate(ts)
            assert ev.current()["status"] == "page"
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(f"{base}/healthz", timeout=5)
            assert e.value.code == 503
            body = json.loads(e.value.read())
            assert body["slo"]["objectives"]["tok"]["advice"] \
                == "scale_up"
        finally:
            stop_http_server()


# ---------------------------------------------------------------------------
# federation degradation: one replica down
# ---------------------------------------------------------------------------

class TestFederationDegraded:
    def test_dead_replica_flagged_stale_and_merge_stays_coherent(self):
        r1, r2 = MetricsRegistry(), MetricsRegistry()
        r1.counter("ds_fastgen_tokens_total").inc(100)
        r2.counter("ds_fastgen_tokens_total").inc(40)
        s1 = serve_registry(r1)
        s2 = serve_registry(r2)
        fed = Federation(stale_after_s=0.2)
        fed.add_http("a", f"127.0.0.1:{s1.server_address[1]}")
        fed.add_http("b", f"127.0.0.1:{s2.server_address[1]}")
        try:
            view = fed.scrape()
            assert view["live"] == 2 and view["stale"] == 0
            assert view["counters"]["ds_fastgen_tokens_total"] == 140
            _shutdown(s2)                      # replica b dies
            r1.counter("ds_fastgen_tokens_total").inc(60)
            time.sleep(0.25)                   # cross the stale bound
            view2 = fed.scrape()
            assert view2["replicas"]["b"]["stale"]
            assert view2["replicas"]["b"]["error"]
            assert not view2["replicas"]["a"]["stale"]
            assert view2["live"] == 1 and view2["stale"] == 1
            # coherent: the survivor's progress shows AND the dead
            # replica's last-good contribution is retained — the fleet
            # counter is monotone through the kill, not a cliff
            assert view2["counters"]["ds_fastgen_tokens_total"] == 200
            assert tm.FLEET_REPLICAS_STALE.value == 1
        finally:
            _shutdown(s1)

    def test_never_scraped_replica_contributes_nothing(self):
        r1 = MetricsRegistry()
        r1.counter("ds_fastgen_tokens_total").inc(7)
        fed = Federation(stale_after_s=60.0)
        fed.add_registry("a", r1)
        fed.add_http("ghost", "127.0.0.1:1")   # nothing listens there
        view = fed.scrape()
        assert view["replicas"]["ghost"]["stale"]
        assert view["counters"]["ds_fastgen_tokens_total"] == 7


# ---------------------------------------------------------------------------
# tentpole: burn-rate verdict machine
# ---------------------------------------------------------------------------

class TestSLOBurnRate:
    def _latency_rig(self, **over):
        src = _FakeSource()
        ts = TimeSeries(source=src)
        ts.configure(interval_s=1.0, retention_s=200.0)
        ev = SLOEvaluator()
        spec = {"name": "ttft_p99", "kind": "latency",
                "hist": "ds_fastgen_ttft_ms", "threshold_ms": 100.0,
                "quantile": 99, "fast_window_s": 20.0,
                "slow_window_s": 40.0, "page_burn": 6.0,
                "warn_burn": 2.0}
        spec.update(over)
        ev.configure([spec])
        ev.attach(timeseries=ts)
        return src, ts, ev

    def test_transitions_ok_warn_page_heal_with_advice_records(self):
        telemetry.enable()
        rec = telemetry.get_flight_recorder()
        rec.clear()
        src, ts, ev = self._latency_rig()
        t = iter(range(0, 10_000, 10))
        statuses = []

        def phase(n_good, n_bad, steps):
            for _ in range(steps):
                for _ in range(n_good):
                    src.observe(5.0)
                for _ in range(n_bad):
                    src.observe(500.0)
                ts.sample_now(t=float(next(t)))
                statuses.append(ev.current()["status"])

        pages0 = tm.SLO_PAGES.value
        phase(100, 0, 4)       # ok: 0% bad
        phase(100, 3, 4)       # ~3% bad vs 1% budget -> burn ~3: warn
        phase(100, 12, 4)      # ~11% bad -> burn ~10: page
        phase(100, 0, 6)       # heal
        assert statuses[3] == "ok"
        assert "warn" in statuses[4:8]
        assert "page" in statuses[8:12]
        assert statuses[-1] == "ok"
        assert tm.SLO_PAGES.value == pages0 + 1
        events = [e for e in rec.events()
                  if e["kind"] == "slo.verdict"]
        path = [(e["prev"], e["status"]) for e in events]
        assert ("warn", "page") in path
        assert path[-1][1] == "ok"              # the heal is recorded
        advice = [e for e in rec.events() if e["kind"] == "slo.advice"]
        assert advice and advice[0]["action"] == "scale_up"

    def test_fast_spike_alone_does_not_page(self):
        """Multi-window: one terrible sample inside a calm slow window
        is a blip, not a page."""
        telemetry.enable()
        src, ts, ev = self._latency_rig(fast_window_s=10.0,
                                        slow_window_s=200.0)
        t = iter(range(0, 100_000, 10))
        for _ in range(20):                     # long healthy history
            for _ in range(100):
                src.observe(5.0)
            ts.sample_now(t=float(next(t)))
        for _ in range(40):                     # one bad burst: the
            src.observe(500.0)                  # fast window burns hard
        ts.sample_now(t=float(next(t)))         # (~28x) but the slow
        ev.evaluate(ts)                         # window stays ~2x
        v = ev.current()["objectives"]["ttft_p99"]
        assert v["fast_burn"] > 6.0
        assert ev.current()["status"] != "page"

    def test_throughput_min_pages_on_rate_collapse(self):
        telemetry.enable()
        src = _FakeSource()
        ts = TimeSeries(source=src)
        ts.configure(interval_s=1.0, retention_s=200.0)
        ev = SLOEvaluator()
        ev.configure([{"name": "goodput", "kind": "throughput_min",
                       "counter": "ds_fastgen_tokens_total",
                       "min_per_s": 100.0, "budget": 0.1,
                       "fast_window_s": 20.0, "slow_window_s": 40.0,
                       "page_burn": 2.0, "warn_burn": 0.5,
                       "scale_down_below_per_s": 200.0}])
        ev.attach(timeseries=ts)
        t = iter(range(0, 10_000, 10))
        tok = [0]

        def run(rate_per_s, steps):
            for _ in range(steps):
                tok[0] += rate_per_s * 10
                src.counters["ds_fastgen_tokens_total"] = tok[0]
                ts.sample_now(t=float(next(t)))

        run(500, 6)
        assert ev.current()["status"] == "ok"
        run(40, 6)             # 60% shortfall -> burn 6: page
        assert ev.current()["status"] == "page"
        v = ev.current()["objectives"]["goodput"]
        assert v["advice"] == "scale_up"
        run(150, 8)            # above min, under low-water: scale-down
        assert ev.current()["status"] == "ok"
        rec = telemetry.get_flight_recorder()
        down = [e for e in rec.events()
                if e["kind"] == "slo.advice"
                and e["action"] == "scale_down"]
        assert down

    def test_balance_objective_advises_rebalance(self):
        telemetry.enable()
        r1, r2 = MetricsRegistry(), MetricsRegistry()
        c1 = r1.counter("ds_fastgen_tokens_total")
        c2 = r2.counter("ds_fastgen_tokens_total")
        fed = Federation()
        fed.add_registry("hot", r1)
        fed.add_registry("cold", r2)
        src = _FakeSource()
        ts = TimeSeries(source=src)
        ts.configure(interval_s=1.0, retention_s=60.0)
        ev = SLOEvaluator()
        ev.configure([{"name": "balance", "kind": "balance",
                       "counter": "ds_fastgen_tokens_total",
                       "max_ratio": 4.0, "fast_window_s": 10.0,
                       "slow_window_s": 10.0}])
        ev.attach(timeseries=ts, federation=fed)
        c1.inc(10), c2.inc(10)
        fed.scrape()
        fed.replica_rates("ds_fastgen_tokens_total")   # baseline
        time.sleep(0.05)
        c1.inc(1000), c2.inc(10)                       # 100:1 imbalance
        fed.scrape()
        ts.sample_now(t=0.0)
        ts.sample_now(t=10.0)
        ev.evaluate(ts)
        v = ev.current()["objectives"]["balance"]
        assert v["status"] == "page" and v["advice"] == "rebalance"

    def test_objective_validation_raises_early(self):
        ev = SLOEvaluator()
        with pytest.raises(ValueError):
            ev.configure([{"name": "x", "kind": "nonsense"}])
        with pytest.raises(ValueError):
            ev.configure([{"name": "x", "kind": "latency"}])  # no hist
        with pytest.raises(ValueError):
            ev.configure([{"kind": "latency", "hist": "h",
                           "threshold_ms": 5}])               # no name


# ---------------------------------------------------------------------------
# satellite: timeseries.json seventh postmortem artifact
# ---------------------------------------------------------------------------

class TestPostmortemArtifact:
    def test_seventh_artifact_ships_the_ring(self, tmp_path):
        telemetry.enable()
        ts = get_timeseries()
        ts.configure(interval_s=1.0, retention_s=60.0)
        tm.FASTGEN_TOKENS.inc(5)
        ts.sample_now(t=0.0)
        tm.FASTGEN_TOKENS.inc(5)
        ts.sample_now(t=1.0)
        paths = telemetry.dump_postmortem(str(tmp_path / "pm"))
        assert "timeseries.json" in paths
        with open(paths["timeseries.json"]) as f:
            doc = json.load(f)
        assert len(doc["samples"]) == 2
        assert doc["samples"][-1]["counters"][
            "ds_fastgen_tokens_total"] >= 10

    def test_artifact_absent_when_sampler_off(self, tmp_path):
        telemetry.enable()
        assert not get_timeseries().active
        paths = telemetry.dump_postmortem(str(tmp_path / "pm"))
        assert "timeseries.json" not in paths
        assert "registry.json" in paths        # the base bundle intact


# ---------------------------------------------------------------------------
# acceptance demo: two live replicas, one killed mid-replay
# ---------------------------------------------------------------------------

class TestTwoReplicaKillDemo:
    def test_fleet_coherent_and_evaluator_pages_through_replica_kill(
            self):
        """Two live engine replicas replay the checked-in CAPTURED
        trace (ISSUE 9 anonymized synthesis); one is killed mid-replay
        via the serving.preempt chaos site.  The federated view must
        stay coherent (dead replica stale-flagged, merged counters
        monotone, survivor still serving) while the burn-rate
        evaluator pages with scale-up advice."""
        # The demo's signal is "fleet token rate tracks live-replica
        # count".  That premise needs at least one core per replica:
        # on a single-core box the two replicas serialize, so the
        # fleet rate is CPU-bound — killing r1 frees the core, the
        # survivor's step rate roughly doubles, the total rate never
        # drops below the goodput floor, and there is nothing for the
        # evaluator to page on.
        if (os.cpu_count() or 1) < 2:
            pytest.skip("replica-kill demo needs >= 2 cores; with the "
                        "replicas serialized on one core the fleet "
                        "token rate tracks CPU time, not live-replica "
                        "count")
        from fleetctl import ReplicaProc
        telemetry.enable()
        trace = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "traces", "sample_200.jsonl")
        # limit 4 keeps step compute small vs the pacing sleep: the
        # fleet token rate then tracks live-replica count, not CPU
        # contention (see fleetctl.run_kill_demo)
        common = ["--trace", trace, "--trace-limit", "4",
                  "--rounds", "150", "--step-sleep-s", "0.05"]
        reps = [
            ReplicaProc("r0", common + ["--seed", "0"]),
            ReplicaProc("r1", common + ["--seed", "1"],
                        env_extra={"DS_CHAOS": "serving.preempt:at=90"}),
        ]
        try:
            targets = [(r.label, r.port(timeout=240)) for r in reps]
            fed = Federation(stale_after_s=1.0)
            for label, port in targets:
                fed.add_http(label, f"127.0.0.1:{port}")
            ts = TimeSeries(source=fed.merged_raw)
            ts.configure(interval_s=0.2, retention_s=300.0)
            ev = SLOEvaluator()
            ev.attach(timeseries=ts, federation=fed)
            # measure the both-alive fleet rate after compile warmup,
            # then pin the goodput objective to 80% of it
            for r in reps:
                assert r.wait_line("round=0 done", 240.0) is not None, \
                    f"{r.label} never finished warmup (exit=" \
                    f"{r.proc.poll()})"
            # warm rate: POLL instead of one fixed 2.4 s window — on a
            # 1-core box the two replica subprocesses serialize, and a
            # single window can straddle a scheduling gap where neither
            # replica committed a token (rate reads 0 and the demo
            # flakes).  Keep sampling until the both-alive rate is
            # visibly positive; the r1-alive assertion below still
            # guards against pinning the objective to a post-kill rate.
            warm = None
            ts.sample_now()
            warm_deadline = time.monotonic() + 120.0
            while time.monotonic() < warm_deadline:
                time.sleep(0.3)
                ts.sample_now()
                warm = ts.counter_rate("ds_fastgen_tokens_total", 5.0)
                if warm and warm > 0:
                    break
            assert warm and warm > 0, \
                "fleet token rate never went positive while both " \
                "replicas were alive"
            # the FIRST positive reading on a serialized box can be a
            # thin trickle (one replica's tokens in an otherwise idle
            # window); pinning the objective to it would set the
            # goodput floor so low the post-kill half-fleet still
            # clears it and the evaluator never pages.  Sample a few
            # more seconds and take the best observed both-alive rate.
            settle_deadline = time.monotonic() + 4.0
            while time.monotonic() < settle_deadline:
                time.sleep(0.3)
                ts.sample_now()
                rate = ts.counter_rate("ds_fastgen_tokens_total", 5.0)
                if rate and rate > warm:
                    warm = rate
            assert reps[1].proc.poll() is None, \
                "r1 died before the both-alive rate was measured"
            ev.configure([{
                "name": "fleet_goodput", "kind": "throughput_min",
                "counter": "ds_fastgen_tokens_total",
                "min_per_s": 0.8 * warm, "budget": 0.1,
                "fast_window_s": 2.0, "slow_window_s": 4.0,
                "page_burn": 2.0, "warn_burn": 0.5}])

            fleet_tok = []
            paged = None
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                time.sleep(0.2)
                ts.sample_now()
                view = fed.scrape()
                fleet_tok.append(
                    view["counters"]["ds_fastgen_tokens_total"])
                if paged is None and ev.current()["status"] == "page":
                    paged = view
                    break
                if reps[0].wait_line("FLEET_REPLICA done", 0.01):
                    # survivor finished its whole workload: a page now
                    # would be the end-of-traffic artifact, not the
                    # kill signal — fail loudly instead
                    break
            assert paged is not None, \
                "evaluator never paged after the replica kill"
            # the kill actually happened through the chaos site
            assert reps[1].proc.poll() == 17     # EXIT_PREEMPTED
            assert reps[1].wait_line("FLEET_REPLICA preempted", 5.0)
            # advice record: page + scale_up, in the flight recorder
            v = ev.current()["objectives"]["fleet_goodput"]
            assert v["advice"] == "scale_up"
            advice = [e for e in telemetry.get_flight_recorder().events()
                      if e["kind"] == "slo.advice"
                      and e["action"] == "scale_up"]
            assert advice
            # fleet view coherent: dead replica flagged stale, merged
            # counter monotone through the kill, survivor untouched
            assert paged["replicas"]["r1"]["stale"]
            assert not paged["replicas"]["r0"]["stale"]
            assert fleet_tok == sorted(fleet_tok)
            surv = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{targets[0][1]}/snapshot?raw=1",
                timeout=5).read())
            assert surv["counters"]["ds_fastgen_tokens_total"] > 0
            assert reps[0].proc.poll() is None   # survivor still alive
        finally:
            for r in reps:
                r.terminate()
