"""FLOPs profiler.

TPU-native analogue of ``deepspeed/profiling/flops_profiler/profiler.py``
(``FlopsProfiler`` :28, functional-patch flop counting :514+, model-tree
report ``print_model_profile`` :282).  The reference patches
``torch.nn.functional`` to count MACs per module hook; under XLA the
compiler itself knows the cost of the optimized program, so:

* totals come from the compiled executable's ``cost_analysis()`` (flops +
  bytes accessed of the *post-fusion* HLO — more truthful than analytic
  per-op counting, which misses fusion);
* the per-component breakdown comes from counting jaxpr equations grouped
  by the model's own scope names (jax source-info tracebacks), giving the
  module-tree view the reference prints;
* wall-clock utilization = measured step time vs device peak FLOPs.

Engine hook: ``flops_profiler.profile_step`` triggers one profiled step and
prints the report (reference engine.py:1858, :2193).
"""

from __future__ import annotations

import time
from collections import defaultdict
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from ..utils.logging import logger

# Peak dense bf16 FLOP/s per chip (Google Cloud TPU documentation), keyed
# by a substring of ``device_kind``; extend as generations appear.  A
# device that is not here has NO peak: utilization is not reported for it.
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6e": 918e12,
}


# Peak HBM bytes/s per chip (the same pages' "HBM bandwidth"), keyed as
# ``PEAK_FLOPS``.  A device that is not here has no bandwidth, and so no
# ridge: nothing is sized by an assumed chip's.
HBM_BYTES_PER_S = {
    "TPU v4": 1200e9,
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
    "TPU v5p": 2765e9,
    "TPU v6e": 1640e9,
}


def _published(table: Dict[str, float], what: str, where: str) -> float:
    kind = str(jax.devices()[0].device_kind)
    for name, peak in table.items():
        if name.lower() in kind.lower():
            return peak
    raise LookupError(
        f"no published {what} for device_kind {kind!r} — add it to "
        f"profiling.flops_profiler.{where} with its source")


def device_peak_flops() -> float:
    """Published peak of the device JAX computes on; an unknown
    ``device_kind`` is an error, never a default."""
    return _published(PEAK_FLOPS, "peak FLOP/s", "PEAK_FLOPS")


def _device_peak_flops() -> Optional[float]:
    """The peak, or None where the device has none (reports then carry
    measured FLOP/s only, no utilization)."""
    try:
        return device_peak_flops()
    except LookupError:
        return None


def _device_hbm_bytes_per_s() -> Optional[float]:
    """The published HBM bandwidth of the device JAX computes on, or
    None where it has none."""
    try:
        return _published(HBM_BYTES_PER_S, "HBM bandwidth",
                          "HBM_BYTES_PER_S")
    except LookupError:
        return None


def _format_count(n: Optional[float], unit: str = "") -> str:
    if n is None:
        return "n/a"
    for scale, suffix in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")):
        if abs(n) >= scale:
            return f"{n / scale:.2f} {suffix}{unit}"
    return f"{n:.2f} {unit}"


def count_params(params: Any) -> int:
    return sum(int(np.prod(np.shape(l))) for l in jax.tree.leaves(params))


def compiled_cost(fn: Callable, *args, **kwargs) -> Dict[str, float]:
    """FLOPs/bytes of the post-fusion XLA executable for ``fn(*args)``."""
    lowered = jax.jit(fn).lower(*args, **kwargs)
    compiled = lowered.compile()
    cost = compiled.cost_analysis() or {}
    # cost_analysis may return a list per computation on some backends
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "transcendentals": float(cost.get("transcendentals", 0.0)),
    }


def jaxpr_op_breakdown(fn: Callable, *args, **kwargs) -> Dict[str, int]:
    """Equation counts per primitive (the 'module tree' analogue: which ops
    dominate the traced program before fusion)."""
    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
    counts: Dict[str, int] = defaultdict(int)

    def walk(jp):
        for eqn in jp.eqns:
            counts[eqn.primitive.name] += 1
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):  # nested ClosedJaxpr (scan/cond/jit)
                    walk(v.jaxpr)

    try:
        walk(jaxpr.jaxpr)
    except Exception:  # jaxpr internals drift — breakdown is best-effort
        logger.debug("jaxpr walk failed", exc_info=True)
    return dict(counts)


class FlopsProfiler:
    """Profile a jitted step: compiled FLOPs, params, latency, utilization.

    Reference API surface (``profiler.py``): ``start_profile`` /
    ``stop_profile`` / ``get_total_flops`` / ``get_total_params`` /
    ``get_total_duration`` / ``print_model_profile`` / ``end_profile``.
    """

    def __init__(self, fn: Optional[Callable] = None, params: Any = None):
        self.fn = fn
        self.params = params
        self._cost: Dict[str, float] = {}
        self._ops: Dict[str, int] = {}
        self._duration: float = 0.0
        self._started = False

    # -- reference-parity control surface -------------------------------
    def start_profile(self) -> None:
        self._started = True

    def profile(self, fn: Callable, *args, repeats: int = 3,
                **kwargs) -> Dict[str, Any]:
        """Measure one callable: compiled cost + timed execution."""
        self._cost = compiled_cost(fn, *args, **kwargs)
        try:
            self._ops = jaxpr_op_breakdown(fn, *args, **kwargs)
        except Exception:
            self._ops = {}
        compiled = jax.jit(fn)
        out = compiled(*args, **kwargs)  # warmup (compile cached by lower)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(repeats):
            out = compiled(*args, **kwargs)
        jax.block_until_ready(out)
        self._duration = (time.perf_counter() - t0) / repeats
        return self.summary()

    def stop_profile(self) -> None:
        self._started = False

    def end_profile(self) -> None:
        self._cost, self._ops, self._duration = {}, {}, 0.0

    # -- accessors ------------------------------------------------------
    def get_total_flops(self, as_string: bool = False):
        f = self._cost.get("flops", 0.0)
        return _format_count(f, "FLOPs") if as_string else f

    def get_total_params(self, as_string: bool = False):
        n = count_params(self.params) if self.params is not None else 0
        return _format_count(n) if as_string else n

    def get_total_duration(self, as_string: bool = False):
        return (f"{self._duration * 1e3:.2f} ms" if as_string
                else self._duration)

    def summary(self) -> Dict[str, Any]:
        flops = self._cost.get("flops", 0.0)
        peak = _device_peak_flops()
        util = (flops / self._duration / peak
                if peak and self._duration else None)
        return {
            "flops": flops,
            "bytes_accessed": self._cost.get("bytes_accessed", 0.0),
            "duration_s": self._duration,
            "flops_per_s": flops / self._duration if self._duration else 0.0,
            "mfu": util,
            "params": self.get_total_params(),
            "top_ops": sorted(self._ops.items(), key=lambda kv: -kv[1])[:10],
        }

    def print_model_profile(self, profile_step: int = 0,
                            module_depth: int = -1, top_modules: int = 1,
                            detailed: bool = True,
                            output_file: Optional[str] = None) -> str:
        s = self.summary()
        lines = [
            "-" * 60,
            f"DeepSpeed-TPU Flops Profiler (step {profile_step})",
            "-" * 60,
            f"params:               {_format_count(s['params'])}",
            f"fwd+bwd+step flops:   {_format_count(s['flops'], 'FLOPs')}",
            f"HBM bytes accessed:   {_format_count(s['bytes_accessed'], 'B')}",
            f"step latency:         {s['duration_s'] * 1e3:.2f} ms",
            f"achieved throughput:  {_format_count(s['flops_per_s'], 'FLOPS')}",
        ]
        if s["mfu"] is not None:
            lines.append(f"model flops util:     {s['mfu']:.1%}")
        if detailed and s["top_ops"]:
            lines.append("top primitives (trace eqn counts):")
            for name, cnt in s["top_ops"]:
                lines.append(f"  {name:<28} {cnt}")
        lines.append("-" * 60)
        report = "\n".join(lines)
        if output_file:
            with open(output_file, "w", encoding="utf-8") as fh:
                fh.write(report + "\n")
        else:
            print(report)
        return report


def get_model_profile(fn: Callable, args: Tuple = (),
                      kwargs: Optional[dict] = None,
                      params: Any = None,
                      print_profile: bool = True,
                      as_string: bool = False):
    """One-shot profile (reference ``get_model_profile``): returns
    (flops, macs≈flops/2, params)."""
    prof = FlopsProfiler(params=params)
    prof.profile(fn, *args, **(kwargs or {}))
    if print_profile:
        prof.print_model_profile()
    flops = prof.get_total_flops(as_string)
    params_n = prof.get_total_params(as_string)
    macs = (_format_count(prof.get_total_flops() / 2, "MACs")
            if as_string else prof.get_total_flops() / 2)
    return flops, macs, params_n
