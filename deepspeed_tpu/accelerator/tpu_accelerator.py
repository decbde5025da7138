"""TPU accelerator implementation (the reference's per-device
implementations: ``accelerator/hpu_accelerator.py:15`` is the template for
a non-CUDA device; this is its TPU equivalent on JAX)."""

from __future__ import annotations

from typing import Any, Dict

import jax

from .abstract_accelerator import DeepSpeedAccelerator


class TPU_Accelerator(DeepSpeedAccelerator):
    _name = "tpu"
    _communication_backend_name = "xla"

    def device_count(self) -> int:
        return jax.device_count()

    def current_device(self) -> Any:
        return jax.devices()[0]

    def memory_stats(self, device_index: int | None = None) -> Dict[str, int]:
        dev = jax.local_devices()[device_index or 0]
        try:
            return dict(dev.memory_stats() or {})
        except Exception:
            return {}

    def is_fp16_supported(self) -> bool:
        # fp16 compute is emulated on TPU; bf16 is native. We still accept
        # fp16 configs (loss scaling path) but compute in bf16 under the hood.
        return True
