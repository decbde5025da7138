"""Telemetry spine (ISSUE 4): one observability layer across training
and serving.

Three pieces:

- **metrics registry** (:mod:`.registry`): named counters / gauges /
  log-bucketed histograms with a flat ``snapshot()`` and a Prometheus
  text endpoint (:mod:`.server`, ``DS_METRICS_PORT``, off by default).
  All names are minted in the :mod:`.metrics` catalog
  (``ds_<area>_<name>``) and linted by ``tools/check_metrics.py``.
- **span tracer** (:mod:`.tracer`): ``trace_span("fastgen.dispatch")``
  records into a bounded ring buffer, exportable as Chrome-trace JSON
  via :func:`dump_trace` (Perfetto-loadable); a
  ``jax.profiler.TraceAnnotation`` is emitted under the same name so
  host spans line up with device timelines in captured profiles.
- **SLO histograms**: TTFT / inter-token latency / queue wait /
  step wall time recorded per request at drain time by the
  FastGenScheduler.

Everything is gated on one process-wide flag (``DS_TELEMETRY=1``,
:func:`enable`, or the ``telemetry`` config block); the disabled path is
a single branch with no allocation.
"""

from __future__ import annotations

from .registry import (Counter, Gauge, Histogram,  # noqa: F401
                       MetricsRegistry, get_registry, log_buckets)
from . import metrics  # noqa: F401  — mint the full ds_* catalog
from .server import (maybe_start_from_env,  # noqa: F401
                     start_http_server, stop_http_server)
from .state import state  # noqa: F401
from .tracer import (SpanTracer, dump_trace,  # noqa: F401
                     get_tracer, program_table, register_program,
                     trace_span)
from .watchdog import Watchdog, get_watchdog  # noqa: F401
from .flight_recorder import (FlightRecorder,  # noqa: F401
                              dump_postmortem, get_flight_recorder,
                              maybe_install_exit_handlers)
from .workload_trace import (WorkloadTrace,  # noqa: F401
                             get_workload_trace,
                             maybe_configure_from_env)
from .timeseries import (TimeSeries, WindowHist,  # noqa: F401
                         get_timeseries)
from .timeseries import \
    maybe_configure_from_env as _timeseries_from_env
from .federation import (Federation,  # noqa: F401
                         get_federation)
from .federation import \
    maybe_configure_from_env as _federation_from_env
from .slo import SLOEvaluator, get_slo_evaluator  # noqa: F401
from .journey import (Journey, JourneyLog,  # noqa: F401
                      get_journey_log)
from .memory import MemoryLedger, get_memory_ledger  # noqa: F401
from .server import serve_registry  # noqa: F401


def enabled() -> bool:
    return state.enabled


def enable() -> None:
    set_enabled(True)


def disable() -> None:
    set_enabled(False)


def set_enabled(on: bool) -> None:
    state.enabled = bool(on)


def apply_settings(enabled: "bool | None", metrics_port: int = 0,
                   trace_buffer: int = 0,
                   watchdog: "bool | None" = None,
                   watchdog_threshold: float = 0.0,
                   watchdog_warmup: int = -1,
                   postmortem_dir: str = "",
                   flight_recorder_events: int = 0,
                   workload_trace_path: str = "",
                   workload_trace_max_mb: int = 0,
                   timeseries_interval_s: float = 0.0,
                   timeseries_retention_s: float = 0.0,
                   fleet_targets: str = "",
                   slo_objectives: "list | None" = None) -> None:
    """Push a ``telemetry`` config block into the process-wide state —
    the single implementation behind both the runtime config's and the
    inference-v2 config's ``TelemetryConfig.apply()``.  ``enabled=None``
    keeps the current process flag; ``trace_buffer`` 0 keeps current
    capacity; ``metrics_port`` 0 means off, -1 binds an EPHEMERAL port
    (the ``DS_METRICS_PORT=0`` semantics — N replicas on one host never
    collide).  ISSUE 5 knobs follow the same keep-current convention:
    ``watchdog=None``, ``watchdog_threshold=0``, ``watchdog_warmup=-1``,
    ``postmortem_dir=""``, ``flight_recorder_events=0``; so do the
    ISSUE 9 workload-trace knobs (``workload_trace_path=""``,
    ``workload_trace_max_mb=0``) and the ISSUE 11 fleet-observatory
    knobs: ``timeseries_interval_s``/``timeseries_retention_s`` of 0
    keep current (a positive interval starts the background sampler),
    ``fleet_targets=""`` keeps the current federation membership, and
    ``slo_objectives=None``/``[]`` keeps the current objective set (a
    non-empty list replaces it and attaches the evaluator to the
    time-series sampler)."""
    if enabled is not None:
        set_enabled(enabled)
    if trace_buffer:
        get_tracer().resize(trace_buffer)
    if workload_trace_path or workload_trace_max_mb:
        get_workload_trace().configure(workload_trace_path,
                                       max_mb=workload_trace_max_mb)
    get_watchdog().configure(enabled=watchdog,
                             threshold=watchdog_threshold,
                             warmup=watchdog_warmup,
                             postmortem_dir=postmortem_dir)
    if postmortem_dir:
        get_flight_recorder().postmortem_dir = postmortem_dir
    if flight_recorder_events:
        get_flight_recorder().resize(flight_recorder_events)
    if timeseries_interval_s or timeseries_retention_s:
        ts = get_timeseries()
        ts.configure(interval_s=timeseries_interval_s,
                     retention_s=timeseries_retention_s)
        if timeseries_interval_s:
            ts.start_thread()
    if fleet_targets:
        get_federation().configure_targets(fleet_targets)
    if slo_objectives:
        ev = get_slo_evaluator()
        ev.configure(slo_objectives)
        ev.attach(timeseries=get_timeseries(),
                  federation=get_federation())
        if not get_timeseries().active:
            # objectives without a sampler are DEAD: the on-sample
            # hook never fires, so /healthz would report configured
            # SLOs as forever-ok — loud, not silent
            from ..utils.logging import logger
            logger.warning(
                "telemetry.slo_objectives configured but the "
                "time-series sampler is off — burn rates will never "
                "be evaluated; set telemetry.timeseries_interval_s "
                "(or DS_TIMESERIES) to arm them")
    if metrics_port:
        try:
            start_http_server(0 if metrics_port < 0 else metrics_port)
        except OSError as e:
            # every rank shares the config — only one bind per host can
            # win a FIXED port, and the losers must still build their
            # engine
            from ..utils.logging import logger
            logger.warning(
                "telemetry.metrics_port=%d: endpoint not started "
                "(%s) — continuing without it", metrics_port, e)


# honor DS_METRICS_PORT as soon as telemetry is imported (the import is
# reached via deepspeed_tpu.utils.comms_logging, i.e. any engine build)
maybe_start_from_env()
# honor DS_POSTMORTEM_ON_EXIT the same way (atexit + SIGTERM bundle)
maybe_install_exit_handlers()
# honor DS_WORKLOAD_TRACE the same way (workload ledger capture)
maybe_configure_from_env()
# honor DS_TIMESERIES / DS_FLEET_TARGETS the same way (ISSUE 11)
_timeseries_from_env()
_federation_from_env()
