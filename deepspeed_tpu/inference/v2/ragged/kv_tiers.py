"""Host/disk prefix-cache tier below the device page pool (ISSUE 16).

The device prefix cache is exactly the otherwise-idle pool, so a busy
replica's eviction horizon is minutes: a multi-turn conversation that
pauses for coffee re-pays its whole prefill.  This store gives evicted
pages two more lives — parked pages that ``StateManager.ensure_free``
would reclaim are *demoted* here instead:

    device pool --evict--> host DRAM ring --overflow--> disk files

Entries are keyed by the SAME chained blake2b cumulative-prefix digests
the device :class:`~.prefix_cache.PrefixCache` uses, so identity (and
the dedup/affinity machinery built on it) is tier-invariant.  Promotion
(``take_many``) removes the entry and hands the page blob back for a
device scatter; disk reads for a whole digest chain are submitted to
the in-tree AIO handle first and awaited together, so a multi-page
promotion overlaps its file reads.

Failure contract: this is a CACHE.  Any I/O error — torn file, short
read, unwritable dir, or the ``kv.tier_io_error`` chaos site — drops
the affected entry and reads as a clean miss (the caller prefills the
suffix as if the tier were cold); a corrupt hit is structurally
impossible because a failed read never returns a blob.  When the native
AIO extension isn't built, plain buffered file I/O is used instead —
the tier never adds a hard dependency.

Accounting (DS_KV_DEBUG): every digest this store has accepted is in
exactly one of {host ring, disk, in-flight promotion}; ``host_pages +
disk_pages + inflight_pages == indexed_pages`` is audited by
``check_invariants`` (wired into ``StateManager.check_invariants``).
"""

from __future__ import annotations

import os
import shutil
import tempfile
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from ....runtime.fault_injection import get_fault_injector
from ....telemetry import metrics as tm
from ....utils.logging import logger
from .kv_cache import PageBlob


class _DiskMeta:
    """Host-side record of one on-disk page entry (shapes/dtypes never
    persist — the store is per-process, like the device cache)."""

    __slots__ = ("path", "shape", "dtype", "scale_shape", "scale_dtype",
                 "nbytes")

    def __init__(self, path, shape, dtype, scale_shape, scale_dtype,
                 nbytes):
        self.path = path
        self.shape = shape
        self.dtype = dtype
        self.scale_shape = scale_shape
        self.scale_dtype = scale_dtype
        self.nbytes = nbytes


def _blob_nbytes(blob) -> int:
    """Byte footprint of one page blob (ndarray or quantized
    :class:`PageBlob` — both expose ``nbytes``)."""
    return int(getattr(blob, "nbytes", 0))


class TieredPageStore:
    """Bounded host ring + bounded disk spill for single-page KV blobs.

    ``put`` / ``take_many`` move whole single-page blobs (ndarray
    ``[L, 1, 2, K, page, D]`` or :class:`PageBlob` when quantized) —
    quantized payloads travel quantized; the tier never re-encodes.
    """

    def __init__(self, host_pages: int, disk_pages: int = 0,
                 disk_dir: Optional[str] = None,
                 bytes_per_page: int = 0) -> None:
        if host_pages < 1:
            raise ValueError(
                f"tier host ring needs >= 1 page, got {host_pages}")
        self._host_cap = int(host_pages)
        self._disk_cap = max(0, int(disk_pages))
        # byte-audited disk bound (ISSUE 20 bugfix): the page-count cap
        # alone never audited FILE bytes, so oversized entries (or a
        # bytes_per_page drift) could hold unbounded disk; with a known
        # page footprint the disk tier is bounded in BYTES too
        self._bytes_per_page = max(0, int(bytes_per_page))
        self._disk_bytes_cap = self._disk_cap * self._bytes_per_page
        self._host_bytes = 0
        self._disk_bytes = 0
        #: digest -> blob, LRU order (oldest first)
        self._host: "OrderedDict[bytes, object]" = OrderedDict()
        #: digest -> _DiskMeta, LRU order (oldest first)
        self._disk: "OrderedDict[bytes, _DiskMeta]" = OrderedDict()
        #: digests handed out by take_many but not yet re-landed on
        #: device by the caller (transient; audited, see module doc)
        self._inflight = 0
        self._indexed = 0
        self._dir = None
        self._own_dir = False
        self._aio = None
        self._aio_failed = False
        if self._disk_cap:
            if disk_dir:
                os.makedirs(disk_dir, exist_ok=True)
                self._dir = disk_dir
            else:
                self._dir = tempfile.mkdtemp(prefix="ds_kv_tier_")
                self._own_dir = True
        # observable lifetime counters (bench/tests; the ds_kv_tier_*
        # metrics aggregate the same events process-wide)
        self.demoted_pages = 0
        self.promoted_pages = 0
        self.spilled_pages = 0
        self.io_errors = 0

    # -- population view ------------------------------------------------------
    @property
    def host_pages(self) -> int:
        return len(self._host)

    @property
    def disk_pages(self) -> int:
        return len(self._disk)

    @property
    def host_bytes(self) -> int:
        """Bytes resident in the host DRAM ring (ledger accountant)."""
        return self._host_bytes

    @property
    def disk_bytes(self) -> int:
        """Bytes held as disk tier files (ledger accountant; audited
        against the ``kv_tier_disk_pages`` byte bound)."""
        return self._disk_bytes

    @property
    def inflight_pages(self) -> int:
        return self._inflight

    @property
    def indexed_pages(self) -> int:
        return self._indexed

    def contains(self, digest: bytes) -> Optional[str]:
        """Which tier holds ``digest`` ("host"/"disk"), else None."""
        if digest in self._host:
            return "host"
        if digest in self._disk:
            return "disk"
        return None

    # -- AIO (in-tree ops/aio, plain-file fallback) ---------------------------
    def _get_aio(self):
        """The shared AIO handle, or None when the native extension
        isn't built (plain buffered I/O then; same files, same
        contract)."""
        if self._aio is None and not self._aio_failed:
            try:
                from ....ops.aio import AsyncIOHandle
                self._aio = AsyncIOHandle()
            except Exception as e:
                self._aio_failed = True
                logger.info(
                    "kv tier: native AIO unavailable (%s: %s) — disk "
                    "tier uses plain file I/O", type(e).__name__, e)
        return self._aio

    def _write_file(self, path: str, parts: List[np.ndarray]) -> None:
        aio = self._get_aio()
        if aio is not None:
            off = 0
            for arr in parts:
                arr = np.ascontiguousarray(arr)
                aio.sync_pwrite(arr, path, off)
                off += arr.nbytes
            return
        with open(path, "wb") as f:
            for arr in parts:
                f.write(np.ascontiguousarray(arr).tobytes())

    def _read_file_plain(self, meta: _DiskMeta) -> object:
        with open(meta.path, "rb") as f:
            raw = f.read()
        payload = np.frombuffer(
            raw, dtype=meta.dtype,
            count=int(np.prod(meta.shape))).reshape(meta.shape)
        if meta.scale_shape is None:
            if len(raw) != payload.nbytes:
                raise OSError(f"torn tier file {meta.path}")
            return payload.copy()
        scale = np.frombuffer(
            raw[payload.nbytes:], dtype=meta.scale_dtype,
            count=int(np.prod(meta.scale_shape))).reshape(meta.scale_shape)
        if len(raw) != payload.nbytes + scale.nbytes:
            raise OSError(f"torn tier file {meta.path}")
        return PageBlob(payload.copy(), scale.copy())

    # -- demotion (device evict -> host -> disk) ------------------------------
    def put(self, digest: bytes, blob) -> bool:
        """Accept one evicted page's blob under its chain digest.
        Returns False (and counts an I/O error where applicable) when
        the entry was dropped instead of stored — always a clean miss
        later, never an error surfaced to the eviction path."""
        if digest in self._host or digest in self._disk:
            # first writer wins, like the device prefix index
            if digest in self._host:
                self._host.move_to_end(digest)
            return False
        try:
            get_fault_injector().maybe_raise(
                "kv.tier_io_error", OSError,
                "injected tier I/O error (demotion)")
        except OSError:
            self.io_errors += 1
            tm.KV_TIER_IO_ERRORS.inc()
            return False
        self._host[digest] = blob
        self._host_bytes += _blob_nbytes(blob)
        self._indexed += 1
        self.demoted_pages += 1
        tm.KV_TIER_DEMOTED.inc()
        while len(self._host) > self._host_cap:
            d, spill = self._host.popitem(last=False)
            self._host_bytes -= _blob_nbytes(spill)
            if not self._spill_to_disk(d, spill):
                self._indexed -= 1  # dropped from the tier entirely
        return True

    def _evict_disk_lru(self) -> None:
        """Drop the disk tier's LRU entry and its file (count or byte
        bound exceeded)."""
        d, meta = self._disk.popitem(last=False)
        self._disk_bytes -= meta.nbytes
        self._indexed -= 1
        try:
            os.unlink(meta.path)
        except OSError:
            pass

    def _spill_to_disk(self, digest: bytes, blob) -> bool:
        """Host-ring overflow: write the LRU entry's bytes to one file
        per digest; a full disk tier drops ITS LRU file first.  Any
        failure drops the entry (clean miss)."""
        if not self._disk_cap or self._dir is None:
            return False
        while len(self._disk) >= self._disk_cap:
            self._evict_disk_lru()
        path = os.path.join(self._dir, digest.hex() + ".kvp")
        quantized = isinstance(blob, PageBlob)
        payload = blob.payload if quantized else np.asarray(blob)
        scale = blob.scale if quantized else None
        new_bytes = int(payload.nbytes) + (int(scale.nbytes)
                                           if quantized else 0)
        if self._disk_bytes_cap:
            # byte-audited bound (ISSUE 20 bugfix): page count alone
            # never audited file SIZES — an oversized entry could hold
            # disk_cap × its own footprint.  Delete LRU files until the
            # new entry fits; an entry bigger than the whole bound is
            # dropped (clean miss), never stored over-bound.
            evicted = 0
            while (self._disk
                   and self._disk_bytes + new_bytes
                   > self._disk_bytes_cap):
                self._evict_disk_lru()
                evicted += 1
            if evicted:
                tm.MEM_PRESSURE.inc()
                self._record("mem.pressure", tier="disk",
                             evicted_files=evicted,
                             disk_bytes=self._disk_bytes,
                             bound_bytes=self._disk_bytes_cap)
            if self._disk_bytes + new_bytes > self._disk_bytes_cap:
                return False
        try:
            get_fault_injector().maybe_raise(
                "kv.tier_io_error", OSError,
                "injected tier I/O error (disk spill)")
            parts = [payload] + ([scale] if quantized else [])
            self._write_file(path, parts)
        except (OSError, RuntimeError) as e:
            self.io_errors += 1
            tm.KV_TIER_IO_ERRORS.inc()
            logger.warning("kv tier: disk spill failed (%s) — entry "
                           "dropped (clean miss)", e)
            try:
                os.unlink(path)
            except OSError:
                pass
            return False
        self._disk[digest] = _DiskMeta(
            path, payload.shape, payload.dtype,
            scale.shape if quantized else None,
            scale.dtype if quantized else None,
            new_bytes)
        self._disk_bytes += new_bytes
        self.spilled_pages += 1
        return True

    # -- promotion (tier -> device) -------------------------------------------
    def take_many(self, digests: List[bytes]
                  ) -> Tuple[List[object], List[str]]:
        """Remove and return the blobs for a CONTIGUOUS run of chain
        digests, stopping at the first miss or failed read.  Disk reads
        for the whole run are submitted to AIO before any is awaited,
        so a deep-chain promotion overlaps its file I/O.  Returns
        ``(blobs, tiers)`` with ``tiers[i]`` in {"host", "disk"}."""
        plan: List[Tuple[bytes, str]] = []
        for d in digests:
            t = self.contains(d)
            if t is None:
                break
            plan.append((d, t))
        if not plan:
            return [], []
        aio = self._get_aio()
        pending: Dict[bytes, tuple] = {}
        fi = get_fault_injector()
        if aio is not None:
            for d, t in plan:
                if t != "disk":
                    continue
                meta = self._disk[d]
                try:
                    payload = np.empty(meta.shape, meta.dtype)
                    reqs = [(payload, aio.pread(payload, meta.path, 0))]
                    scale = None
                    if meta.scale_shape is not None:
                        scale = np.empty(meta.scale_shape,
                                         meta.scale_dtype)
                        reqs.append((scale, aio.pread(
                            scale, meta.path, payload.nbytes)))
                    pending[d] = (payload, scale, reqs)
                except (OSError, RuntimeError):
                    pending[d] = None
        blobs: List[object] = []
        tiers: List[str] = []
        for d, t in plan:
            try:
                fi.maybe_raise("kv.tier_io_error", OSError,
                               "injected tier I/O error (promotion)")
                if t == "host":
                    got_blob = self._host.pop(d)
                    self._host_bytes -= _blob_nbytes(got_blob)
                    blobs.append(got_blob)
                    tiers.append("host")
                    self._inflight += 1
                    continue
                meta = self._disk[d]
                if d in pending:
                    got = pending.pop(d)
                    if got is None:
                        raise OSError(f"tier read submit failed for "
                                      f"{meta.path}")
                    payload, scale, reqs = got
                    for _, req in reqs:
                        aio.wait(req)
                    blob = payload if scale is None \
                        else PageBlob(payload, scale)
                else:
                    blob = self._read_file_plain(meta)
            except (OSError, RuntimeError, ValueError) as e:
                # failed/torn read: drop the entry and everything past
                # it in the run — the chain is only usable contiguously
                self.io_errors += 1
                tm.KV_TIER_IO_ERRORS.inc()
                logger.warning("kv tier: promotion read failed (%s) — "
                               "entry dropped (clean miss)", e)
                self._drop(d)
                break
            del self._disk[d]
            self._disk_bytes -= meta.nbytes
            try:
                os.unlink(meta.path)
            except OSError:
                pass
            blobs.append(blob)
            tiers.append("disk")
            self._inflight += 1
        # any disk reads submitted past the break are abandoned; their
        # entries stay resident for a later promotion
        self.promoted_pages += len(blobs)
        if blobs:
            tm.KV_TIER_PROMOTED.inc(len(blobs))
        return blobs, tiers

    def landed(self, n: int) -> None:
        """The caller scattered ``n`` promoted pages onto device —
        close their in-flight accounting."""
        self._inflight -= n
        self._indexed -= n

    def discard(self, digest: bytes) -> None:
        """Forget ``digest`` if held (no error when absent) — called
        when the device index re-acquires a prefix through a path other
        than promotion (re-prefill, handoff import), so a digest is
        never both device-indexed and tier-resident."""
        self._drop(digest)

    def _drop(self, digest: bytes) -> None:
        blob = self._host.pop(digest, None)
        if blob is not None:
            self._host_bytes -= _blob_nbytes(blob)
            self._indexed -= 1
            return
        meta = self._disk.pop(digest, None)
        if meta is not None:
            self._disk_bytes -= meta.nbytes
            self._indexed -= 1
            try:
                os.unlink(meta.path)
            except OSError:
                pass

    def clear(self) -> None:
        """Drop every entry (bench cold-start with the store kept)."""
        self._host.clear()
        self._host_bytes = 0
        for meta in self._disk.values():
            try:
                os.unlink(meta.path)
            except OSError:
                pass
        self._disk.clear()
        self._disk_bytes = 0
        self._indexed = self._inflight

    # -- invariants / lifecycle -----------------------------------------------
    def check_invariants(self) -> None:
        """Tier accounting audit (DS_KV_DEBUG): host + disk + inflight
        == indexed, caps respected, every disk entry's file present."""
        if (len(self._host) + len(self._disk) + self._inflight
                != self._indexed):
            raise RuntimeError(
                f"KV tier invariant: host({len(self._host)}) + "
                f"disk({len(self._disk)}) + inflight({self._inflight}) "
                f"!= indexed({self._indexed})")
        if len(self._host) > self._host_cap:
            raise RuntimeError(
                f"KV tier invariant: host ring {len(self._host)} over "
                f"cap {self._host_cap}")
        if len(self._disk) > max(self._disk_cap, 0):
            raise RuntimeError(
                f"KV tier invariant: disk tier {len(self._disk)} over "
                f"cap {self._disk_cap}")
        if (self._disk_bytes_cap
                and self._disk_bytes > self._disk_bytes_cap):
            raise RuntimeError(
                f"KV tier invariant: disk tier {self._disk_bytes}B "
                f"over byte bound {self._disk_bytes_cap}B")
        if self._disk_bytes != sum(m.nbytes
                                   for m in self._disk.values()):
            raise RuntimeError(
                "KV tier invariant: disk byte ledger "
                f"({self._disk_bytes}) != sum of entry sizes")
        for meta in self._disk.values():
            if not os.path.exists(meta.path):
                raise RuntimeError(
                    f"KV tier invariant: disk entry lost its file "
                    f"{meta.path}")

    def stats(self) -> dict:
        return {"host_pages": len(self._host),
                "disk_pages": len(self._disk),
                "host_bytes": self._host_bytes,
                "disk_bytes": self._disk_bytes,
                "inflight_pages": self._inflight,
                "demoted_pages": self.demoted_pages,
                "promoted_pages": self.promoted_pages,
                "spilled_pages": self.spilled_pages,
                "io_errors": self.io_errors}

    def close(self) -> None:
        """Release the AIO handle and every disk entry's file; the
        store is unusable afterwards.  Files are unlinked even in a
        user-provided directory (ISSUE 20 bugfix): the in-memory index
        dies with the process, so files left behind were permanent
        orphans that no later process could ever read back."""
        if self._aio is not None:
            try:
                self._aio.close()
            except Exception:
                pass
            self._aio = None
        self._host.clear()
        self._host_bytes = 0
        for meta in self._disk.values():
            try:
                os.unlink(meta.path)
            except OSError:
                pass
        self._disk.clear()
        self._disk_bytes = 0
        self._inflight = 0
        self._indexed = 0
        if self._own_dir and self._dir:
            shutil.rmtree(self._dir, ignore_errors=True)

    @staticmethod
    def _record(event: str, **fields) -> None:
        from ....telemetry.flight_recorder import get_flight_recorder
        get_flight_recorder().record(event, **fields)
