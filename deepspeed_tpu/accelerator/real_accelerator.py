"""Accelerator detection/selection (reference ``accelerator/real_accelerator.py:51``).

``get_accelerator()`` picks TPU when the first JAX device is a TPU, else
CPU.  Override with ``DS_ACCELERATOR=tpu|cpu`` (same env var as the
reference).  :func:`on_tpu` is the ONE place the library asks "am I on the
chip": it reads the device itself, and every kernel/path selection goes
through it — on a TPU the Pallas kernels are the only path (a lowering
error propagates), on CPU (the tests) the ``jnp`` references are selected
explicitly by this answer, never by a fall-through.
"""

from __future__ import annotations

import os
from typing import Optional

from .abstract_accelerator import DeepSpeedAccelerator
from ..utils.logging import logger

SUPPORTED_ACCELERATOR_LIST = ["tpu", "cpu"]

_accelerator: Optional[DeepSpeedAccelerator] = None


def device_platform() -> str:
    """Platform of the device JAX computes on (``"tpu"`` / ``"cpu"``)."""
    import jax
    return jax.devices()[0].platform


def on_tpu() -> bool:
    return device_platform() == "tpu"


def get_accelerator() -> DeepSpeedAccelerator:
    global _accelerator
    if _accelerator is not None:
        return _accelerator

    name = os.environ.get("DS_ACCELERATOR")
    if name is not None and name not in SUPPORTED_ACCELERATOR_LIST:
        raise ValueError(
            f"DS_ACCELERATOR={name!r} not in {SUPPORTED_ACCELERATOR_LIST}")
    if name == "cpu":
        # An explicit CPU request must NEVER initialize the JAX backend:
        # jax.devices() would touch (and possibly hang on) a TPU
        # held by another process — the exact situation DS_ACCELERATOR=cpu
        # exists to avoid.
        from .cpu_accelerator import CPU_Accelerator
        _accelerator = CPU_Accelerator()
        logger.info("Setting accelerator to %s (explicit, backend "
                    "untouched)", _accelerator.device_name())
        return _accelerator
    backend = device_platform()
    if name is None:
        name = "cpu" if backend == "cpu" else "tpu"
    elif name == "tpu" and backend == "cpu":
        # reference real_accelerator.py validates the requested device is
        # actually importable/usable before committing to it
        raise RuntimeError(
            "DS_ACCELERATOR=tpu but the JAX device is a 'cpu' — no "
            "TPU is attached (or JAX_PLATFORMS forces cpu). Unset "
            "DS_ACCELERATOR to auto-detect, or fix the TPU runtime.")

    if name == "tpu":
        from .tpu_accelerator import TPU_Accelerator
        _accelerator = TPU_Accelerator()
    else:
        from .cpu_accelerator import CPU_Accelerator
        _accelerator = CPU_Accelerator()
    logger.info("Setting accelerator to %s", _accelerator.device_name())
    return _accelerator


def set_accelerator(accel: DeepSpeedAccelerator) -> None:
    global _accelerator
    _accelerator = accel
