"""Process-wide telemetry on/off switch.

A single attribute read (``state.enabled``) is the whole disabled-path
cost of every span/SLO site, so the flag lives in its own tiny module
that imports nothing but stdlib — the registry, tracer, and every
instrumented hot path share it without import cycles.

Enabled via ``DS_TELEMETRY=1`` (read once at import), the runtime
``telemetry`` config block, or :func:`deepspeed_tpu.telemetry.enable`.
"""

from __future__ import annotations

import os


class _TelemetryState:
    __slots__ = ("enabled",)

    def __init__(self, enabled: bool):
        self.enabled = enabled


state = _TelemetryState(
    os.environ.get("DS_TELEMETRY", "") not in ("", "0"))
