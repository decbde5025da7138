"""Serving state snapshot bundles + the preemption trigger (ISSUE 8).

On spot/preemptible TPU VMs the dominant production failure is the
process dying out from under the engine: a SIGTERM and a short grace
window, after which every in-flight request, KV page, and prefix-cache
entry is lost.  This module is the on-disk half of the fix — a single
**atomic, versioned, checksummed bundle** holding everything
``FastGenScheduler.snapshot()`` serializes (requests, RNG key data, KV
page contents, the prefix-cache index, scheduler counters), written
with the checkpoint engine's tmp+fsync+rename and OSError-retry
machinery so a crash mid-snapshot leaves the previous bundle readable —
plus the SIGTERM handler (``DS_DRAIN_ON_SIGTERM=1``) that drives
drain→snapshot inside the grace budget, chaining with the flight
recorder's postmortem handler.

Bundle layout (version 1)::

    MAGIC "DSSNAP01" | blake2b-16(body) | body
    body = u64 meta_len | u64 payload_len | meta JSON | npz payload

The checksum covers meta AND payload, so a truncated or corrupted file
fails :func:`read_bundle` with a structured :class:`SnapshotError` —
never a hang, never silent partial state.  XLA executables are
process-local and never ride the bundle; instead (ISSUE 14) the meta
carries the engine's **compiled-key manifest** + lattice digest, and
``restore()`` precompiles exactly those keys up front — against a warm
persistent compile cache (``utils/compile_cache.py``) each one is a
disk load, so restore-to-first-token
stays ~flat vs a warm process.  Deliberately NOT captured: telemetry
latency stamps (process-relative clocks).
"""

from __future__ import annotations

import hashlib
import io
import json
import os
import signal
import struct
from typing import Any, Dict, Optional, Tuple

import numpy as np

MAGIC = b"DSSNAP01"
SNAPSHOT_VERSION = 1
_DIGEST_SIZE = 16
_HEADER = struct.Struct("<QQ")


class SnapshotError(RuntimeError):
    """A snapshot bundle could not be written, read, or applied
    (corrupt/truncated file, version or geometry mismatch, non-empty
    restore target).  Restore failures are always this, loudly —
    resuming generation from partial state would silently corrupt
    every affected request."""


#: manifest key for arrays whose dtype numpy can't natively round-trip
_SPECIAL_DTYPES = "__special_dtypes__"


def _encode_arrays(arrays: Dict[str, np.ndarray]
                   ) -> Dict[str, np.ndarray]:
    """npz-safe projection: extension dtypes (bfloat16/fp8 via
    ml_dtypes — the KV cache's default dtype) ride as raw bytes plus a
    (dtype, shape) manifest; native dtypes pass through untouched."""
    enc, special = {}, {}
    for k, v in arrays.items():
        v = np.asarray(v)
        if v.dtype.type.__module__ == "numpy":
            enc[k] = v
        else:
            special[k] = {"dtype": v.dtype.name, "shape": list(v.shape)}
            enc[k] = np.frombuffer(v.tobytes(), dtype=np.uint8)
    if special:
        enc[_SPECIAL_DTYPES] = np.frombuffer(
            json.dumps(special).encode("utf-8"), dtype=np.uint8)
    return enc


def _decode_arrays(arrays: Dict[str, np.ndarray]
                   ) -> Dict[str, np.ndarray]:
    manifest = arrays.pop(_SPECIAL_DTYPES, None)
    if manifest is None:
        return arrays
    try:
        import ml_dtypes  # noqa: F401 — registers bfloat16/fp8 names
    except ImportError:
        pass
    try:
        special = json.loads(manifest.tobytes().decode("utf-8"))
        for k, spec in special.items():
            arrays[k] = np.frombuffer(
                arrays[k].tobytes(),
                dtype=np.dtype(spec["dtype"])).reshape(spec["shape"])
    except Exception as e:
        raise SnapshotError(f"bundle dtype manifest undecodable: {e}")
    return arrays


def _bundle_segments(meta: dict, arrays: Dict[str, np.ndarray]) -> list:
    """The bundle as an ordered list of buffers (MAGIC, digest, header,
    meta, payload) — callers stream them to disk without ever holding a
    concatenated copy (a bundle is KV-pool-sized; the SIGTERM path has
    a grace budget to make)."""
    buf = io.BytesIO()
    np.savez(buf, **_encode_arrays(arrays))
    payload = buf.getbuffer()
    meta_b = json.dumps(meta).encode("utf-8")
    header = _HEADER.pack(len(meta_b), len(payload))
    h = hashlib.blake2b(digest_size=_DIGEST_SIZE)
    for seg in (header, meta_b, payload):
        h.update(seg)
    return [MAGIC, h.digest(), header, meta_b, payload]


def pack_bundle(meta: dict, arrays: Dict[str, np.ndarray]) -> bytes:
    """Serialize (meta, arrays) into the checksummed wire format as one
    bytes object (in-memory round-trips; the file writer streams
    :func:`_bundle_segments` instead)."""
    return b"".join(_bundle_segments(meta, arrays))


def unpack_bundle(data: bytes) -> Tuple[dict, Dict[str, np.ndarray]]:
    """Validate and decode the wire format (:class:`SnapshotError` on
    any inconsistency).  Views, not slices — no copy of the
    KV-pool-sized payload beyond the npz decode itself."""
    if len(data) < len(MAGIC) + _DIGEST_SIZE + _HEADER.size:
        raise SnapshotError(
            f"bundle too short ({len(data)} bytes) — truncated?")
    mv = memoryview(data)
    if bytes(mv[:len(MAGIC)]) != MAGIC:
        raise SnapshotError("not a serving snapshot bundle (bad magic)")
    digest = bytes(mv[len(MAGIC):len(MAGIC) + _DIGEST_SIZE])
    body = mv[len(MAGIC) + _DIGEST_SIZE:]
    if hashlib.blake2b(body, digest_size=_DIGEST_SIZE).digest() != digest:
        raise SnapshotError(
            "bundle checksum mismatch — truncated or corrupted")
    meta_len, payload_len = _HEADER.unpack_from(body)
    if len(body) != _HEADER.size + meta_len + payload_len:
        raise SnapshotError(
            f"bundle length inconsistent (header says "
            f"{meta_len}+{payload_len}, body has "
            f"{len(body) - _HEADER.size})")
    try:
        meta = json.loads(bytes(body[_HEADER.size:
                                     _HEADER.size + meta_len]))
    except ValueError as e:
        raise SnapshotError(f"bundle meta is not valid JSON: {e}")
    version = meta.get("version")
    if version != SNAPSHOT_VERSION:
        raise SnapshotError(
            f"unsupported snapshot version {version!r} "
            f"(this build reads {SNAPSHOT_VERSION})")
    payload = body[_HEADER.size + meta_len:]
    try:
        with np.load(io.BytesIO(payload), allow_pickle=False) as z:
            arrays = {k: z[k] for k in z.files}
    except Exception as e:
        raise SnapshotError(f"bundle payload undecodable: {e}")
    return meta, _decode_arrays(arrays)


def write_bundle(path: str, meta: dict, arrays: Dict[str, np.ndarray],
                 retries: int = 3, backoff_s: float = 0.05) -> str:
    """Write a bundle ATOMICALLY (tmp + fsync + rename, retried on
    ``OSError`` with backoff — the checkpoint engine's durability
    machinery).  The ``ckpt.io_error`` injection site fires inside the
    write, so chaos tests prove a crash mid-snapshot leaves the
    previous bundle at ``path`` readable."""
    from ...checkpoint.engine import _atomic_write_bytes, with_retries
    from ...runtime.fault_injection import (InjectedCheckpointFault,
                                            get_fault_injector)
    segments = _bundle_segments(meta, arrays)

    def _write():
        get_fault_injector().maybe_raise(
            "ckpt.io_error", InjectedCheckpointFault,
            "injected I/O error writing serving snapshot")
        _atomic_write_bytes(path, segments)

    with_retries("snapshot", _write, retries, backoff_s)
    return path


def read_bundle(path: str) -> Tuple[dict, Dict[str, np.ndarray]]:
    """Read and validate a bundle; :class:`SnapshotError` on anything
    less than a complete, checksummed, version-matched file."""
    try:
        with open(path, "rb") as f:
            data = f.read()
    except OSError as e:
        raise SnapshotError(f"cannot read bundle {path}: {e}")
    return unpack_bundle(data)


# -- the real trigger: SIGTERM drain-and-snapshot ----------------------------

_drain_installed = False
#: (weakref to the CURRENT scheduler, bundle path, grace) — the handler
#: reads this at signal time, so building a replacement scheduler (the
#: restore-in-process pattern) retargets drain coverage instead of
#: leaving SIGTERM bound to a dead scheduler's empty state, and the
#: weakref never pins a discarded engine's KV pool in memory
_drain_target: Optional[tuple] = None


def install_drain_handler(scheduler, path: str,
                          grace_s: Optional[float] = None) -> bool:
    """Install (once per process) a SIGTERM handler that drives
    ``drain_and_snapshot(path, grace_s)`` on the MOST RECENTLY
    registered scheduler, then CHAINS to the previously-installed
    handler (the flight recorder's postmortem dump under
    ``DS_POSTMORTEM_ON_EXIT=1`` keeps firing), finally re-delivering
    the signal so the process still dies with the conventional exit
    status.  Calling again retargets the handler at the new scheduler
    (returns True); returns False only when signal installation is
    impossible (off the main thread / restricted env).  The handler
    runs at an arbitrary bytecode boundary — a step caught
    mid-dispatch is drained, not replayed, which is exactly the
    committed-state contract ``snapshot()`` needs (the chained step's
    tokens are committed at drain; host bookkeeping commits at
    dispatch)."""
    global _drain_installed, _drain_target
    import weakref
    _drain_target = (weakref.ref(scheduler), path, grace_s)
    if _drain_installed:
        return True
    try:
        prev = signal.getsignal(signal.SIGTERM)

        def _on_sigterm(signum, frame):
            target = _drain_target
            sched = target[0]() if target is not None else None
            if sched is not None:
                try:
                    sched.drain_and_snapshot(target[1], target[2])
                except Exception:
                    pass    # the process is dying; never mask the signal
            if callable(prev) and prev not in (signal.SIG_IGN,
                                               signal.SIG_DFL):
                prev(signum, frame)
            else:
                signal.signal(signal.SIGTERM, signal.SIG_DFL)
                os.kill(os.getpid(), signum)

        signal.signal(signal.SIGTERM, _on_sigterm)
    except (ValueError, OSError):
        return False    # not the main thread / restricted env
    _drain_installed = True
    return True


def maybe_install_drain_handler(scheduler, path: str,
                                grace_s: Optional[float] = None) -> bool:
    """Honor ``DS_DRAIN_ON_SIGTERM=1``: wire preemption (SIGTERM on
    spot/preemptible VMs) to drain→snapshot.  No-op unless the env var
    is set AND a bundle path is configured."""
    if os.environ.get("DS_DRAIN_ON_SIGTERM", "") in ("", "0") or not path:
        return False
    return install_drain_handler(scheduler, path, grace_s)
