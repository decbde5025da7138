"""What one run hands to its builder, driver and readers."""

from __future__ import annotations

import contextlib
import os
import shutil
import tempfile
import time
from typing import Optional


class SliceProfiler:
    """Profiles the last ``slice_s`` seconds of the window (``--trace 1``
    only), with the program's telemetry switched on for that slice so its
    spans appear as host annotations beside the harness's own."""

    WINDOW_SPAN = "bench.traced"

    def __init__(self, enabled: bool, slice_s: float, keep_dir: str = ""):
        self.enabled = enabled
        self.slice_s = slice_s
        self.keep_dir = keep_dir
        self.dir: Optional[str] = None
        self.started_at: Optional[float] = None
        self.stopped_at: Optional[float] = None
        self.first_step: Optional[int] = None
        self.steps = 0
        self._span = None

    def tick(self, elapsed: float, seconds: float, step_index: int) -> None:
        """Called once per loop turn; starts the trace when the window has
        ``slice_s`` seconds left."""
        if (not self.enabled or self.started_at is not None
                or elapsed < seconds - self.slice_s):
            return
        import jax

        import deepspeed_tpu.telemetry as telemetry
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        telemetry.set_enabled(True)
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(self.WINDOW_SPAN)
        self._span.__enter__()
        self.started_at = time.perf_counter()
        self.first_step = step_index

    def finish(self, step_index: int) -> None:
        if self.started_at is None or self.stopped_at is not None:
            return
        import jax

        import deepspeed_tpu.telemetry as telemetry
        self.steps = step_index - self.first_step
        self.stopped_at = time.perf_counter()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        telemetry.set_enabled(False)

    def trace_file(self) -> Optional[str]:
        if not self.dir:
            return None
        for root, _, files in os.walk(self.dir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return os.path.join(root, f)
        return None

    def cleanup(self) -> None:
        if self.dir and self.keep_dir:
            os.makedirs(self.keep_dir, exist_ok=True)
            src = self.trace_file()
            if src:
                shutil.copy(src, os.path.join(self.keep_dir,
                                              os.path.basename(src)))
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


class Context:
    def __init__(self, *, cell: dict, config: dict, traffic: dict,
                 peaks: Optional[dict], seed: int, seconds: float,
                 trace: bool, rehearse: bool, process_start: float,
                 keep_trace: str = ""):
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.peaks = peaks              # None in a rehearsal
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.rehearse = rehearse
        self.chips = int(cell["chips"])
        self.process_start = process_start
        self.setup_s: Optional[float] = None
        self.profiler = SliceProfiler(
            trace, float(traffic.get("trace_slice_s", 3.0)), keep_trace)
        self.reduced = None             # trace_reduce.Reduced, after the run

    def window_opens(self) -> float:
        """Stamp the end of set-up; returns the window's first instant."""
        now = time.perf_counter()
        self.setup_s = now - self.process_start
        return now

    def annotate(self, name: str):
        """A host span in the profiler's trace (free outside a trace)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)
