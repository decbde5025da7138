"""Blocked (paged) KV cache on device.

Reference: ``inference/v2/ragged/kv_cache.py:40`` (``BlockedKVCache``)
— there, per-layer torch tensors + an allocator, with offload hooks.
TPU-native layout: ONE stacked array per cache group

    kv : [num_layers, num_pages + 1, 2, kv_heads, page_size, head_dim]

(a latent pool, ``planes=1``, holds ``[..., 1, 1, page_size, plane]``: one
plane a token instead of K and V by head) and the whole cache is a single donated buffer across forwards.  Inside
a step program it is the layer loop's carry, never a per-layer slice:
the cache write and the attention kernels take the pool and a layer
index and address ``pool[layer, page]`` themselves, so XLA updates the
buffer in place and nothing pool-sized is copied (a slice scanned out
and stacked back was 62 % of the serve step's device time, PERF.md PR
25; no allocator traffic on device either).  Page 0 is the null page
(see blocked_allocator.py) — real pages are 1..num_pages.  The last two
dims are one head's ``[page_size, head_dim]`` tile of one page: the
block the Pallas paged-attention kernel DMAs per grid step (the TPU
lowering only accepts blocks whose last two dims are tile-aligned array
dims).  Every host codec (offload, snapshot, handoff, tier, fetch)
addresses pages on axis 1 and is layout-agnostic past it.

Quantized pages (ISSUE 16): with ``quantization="int8"`` the device
store is an :class:`~deepspeed_tpu.ops.paged_attention.KVPages` pair —
int8 codes at the layout above plus a per-(token, kv-head) fp32 scale
sidecar ``[L, num_pages+1, 2, K, page_size]``.  Host-side page blobs
become :class:`PageBlob` (payload + scales travel together through
offload/snapshot/handoff), and ``bytes_per_page`` accounts the true
quantized footprint so a byte budget buys ~2x the pages.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from ....ops.paged_attention import KV_QUANT_FORMATS, KVPages
from .blocked_allocator import BlockedAllocator


@dataclasses.dataclass(frozen=True)
class KVCacheConfig:
    num_layers: int
    kv_heads: int
    head_dim: int
    page_size: int = 64
    num_pages: int = 1024
    dtype: Any = jnp.bfloat16
    #: "none" (fp pages at ``dtype``) or "int8" (block-scaled codes +
    #: fp32 scale per head_dim block)
    quantization: str = "none"
    #: cache planes a token holds in a layer, which is what an attention
    #: kind declares of its cache: 2 = K and V by head; 1 = one latent
    #: plane ``[c ; k_r]`` (``ops/mla_attention.py``), held as a single
    #: "head" of ``head_dim`` = the plane padded to whole 128-lane tiles
    planes: int = 2

    def __post_init__(self):
        if self.quantization not in KV_QUANT_FORMATS:
            raise ValueError(
                f"unknown kv quantization {self.quantization!r} "
                f"(supported: {KV_QUANT_FORMATS})")
        if self.latent and self.quantized:
            raise ValueError(
                "a latent page pool has no int8 page format yet: "
                "kv_quantization must be 'none' for this model")

    @property
    def latent(self) -> bool:
        return self.planes == 1

    @property
    def quantized(self) -> bool:
        return self.quantization != "none"

    @property
    def bytes_per_page(self) -> int:
        elems = (self.num_layers * self.page_size * self.planes
                 * self.kv_heads * self.head_dim)
        if self.quantized:
            # 1 byte per code + one fp32 scale per head_dim block: the
            # honest footprint, so pages_for_memory converts a byte
            # budget into ~2x resident pages (the ISSUE 16 lever)
            scales = (self.num_layers * self.page_size * self.planes
                      * self.kv_heads)
            return elems + scales * 4
        itemsize = jnp.dtype(self.dtype).itemsize
        return elems * itemsize

    def total_bytes(self) -> int:
        return self.bytes_per_page * (self.num_pages + 1)

    def cache_shape(self, num_layers: Optional[int] = None) -> tuple:
        """The device page-store shape (module docstring); the int8
        scale sidecar is this minus the trailing ``head_dim``."""
        return (self.num_layers if num_layers is None else num_layers,
                self.num_pages + 1, self.planes, self.kv_heads,
                self.page_size, self.head_dim)


@dataclasses.dataclass(frozen=True)
class StatePoolConfig:
    """The state pool of a model with layers that keep a recurrent state
    instead of pages (``ops/ssm.py``, ``ops/delta_rule.py``): what such a
    layer kind declares of its cache (``cache_kinds.CacheKind.slot_shape``).
    One slot a sequence holds, for every such layer, the recurrent state
    ``state`` = ``[rows, width]`` in ``state_dtype`` (Mamba-1: ``[d_state,
    d_inner]``; the gated delta rule: ``[d_k, heads * d_v]``) and the
    convolution's tail ``tail`` = ``[positions, channels]`` in
    ``conv_dtype``, its rows laid end to end, oldest first, and cut into
    rows of one lane tile (``ops/ssm.py::conv_slot_shape``, which says why
    and what a width that is no whole number of lane tiles gets; the row
    count is rounded up to a sublane tile, as the chip pads it anyway, so
    ``bytes_per_slot`` counts the tail's own values); slot ``num_slots`` is
    the scratch slot padding rows are sent to.  ``kind`` is the layer kind that holds the
    slots: it names the step span's row and token counts."""
    num_layers: int
    state: Tuple[int, int]
    tail: Tuple[int, int]
    kind: str = "ssm"
    num_slots: int = 1
    state_dtype: Any = jnp.float32
    conv_dtype: Any = jnp.bfloat16

    def shapes(self) -> tuple:
        from ....ops.ssm import conv_slot_shape
        lead = (self.num_layers, self.num_slots + 1)
        return (lead + tuple(self.state),
                lead + conv_slot_shape(self.tail[0] * self.tail[1]))

    @property
    def bytes_per_slot(self) -> int:
        return self.num_layers * (
            self.state[0] * self.state[1]
            * jnp.dtype(self.state_dtype).itemsize
            + self.tail[0] * self.tail[1]
            * jnp.dtype(self.conv_dtype).itemsize)

    def total_bytes(self) -> int:
        return self.bytes_per_slot * (self.num_slots + 1)


def pages_for_memory(cfg: KVCacheConfig, budget_bytes: int) -> int:
    """How many pages fit in ``budget_bytes`` (reference sizes its cache
    from a memory fraction the same way)."""
    return max(1, budget_bytes // cfg.bytes_per_page)


class PageBlob:
    """Host-side blob of quantized pages: int8 payload
    ``[L, n, 2, K, page, D]`` + fp32 scales ``[L, n, 2, K, page]``
    traveling as one unit through offload / snapshot / handoff codecs.
    Mimics the ndarray surface those codecs touch (``shape`` and
    ``nbytes`` of the payload, axis-1 column selection), so the fp path
    keeps returning plain ndarrays unchanged."""

    __slots__ = ("payload", "scale")

    def __init__(self, payload, scale):
        import numpy as np
        self.payload = np.asarray(payload)
        self.scale = np.asarray(scale)

    @property
    def shape(self):
        return self.payload.shape

    @property
    def nbytes(self) -> int:
        return self.payload.nbytes + self.scale.nbytes

    def select(self, cols) -> "PageBlob":
        """Column selection along the page axis (the selective-import
        codec's ``blob[:, cols]``)."""
        return PageBlob(self.payload[:, cols], self.scale[:, cols])

    def __getitem__(self, idx):
        return PageBlob(self.payload[idx], self.scale[idx])


def blob_columns(blob, cols):
    """``blob[:, cols]`` for plain ndarrays and :class:`PageBlob`."""
    if isinstance(blob, PageBlob):
        return blob.select(cols)
    return blob[:, cols]


def concat_blobs(blobs):
    """Concatenate page blobs along the page axis (tier promotion
    reassembles a digest chain's single-page blobs into one scatter)."""
    import numpy as np
    if isinstance(blobs[0], PageBlob):
        return PageBlob(
            np.concatenate([b.payload for b in blobs], axis=1),
            np.concatenate([b.scale for b in blobs], axis=1))
    return np.concatenate([np.asarray(b) for b in blobs], axis=1)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_pages(data, idx, blob):
    # data/blob may be KVPages pytrees: scatter each leaf at the same
    # page columns (payload and scales stay paired by construction)
    return jax.tree.map(lambda d, b: d.at[:, idx].set(b), data, blob)


class BlockedKVCache:
    """Device cache array + host page allocator."""

    def __init__(self, cfg: KVCacheConfig,
                 sharding: Optional[jax.sharding.Sharding] = None):
        self.cfg = cfg
        self.allocator = BlockedAllocator(cfg.num_pages)
        shape = cfg.cache_shape()
        if cfg.quantized:
            data = KVPages(jnp.zeros(shape, jnp.int8),
                           jnp.zeros(shape[:-1], jnp.float32))
            if sharding is not None:
                data = KVPages(
                    jax.device_put(data.payload, sharding),
                    jax.device_put(data.scale,
                                   self._scale_sharding(sharding)))
            self.data = data
        elif sharding is not None:
            self.data = jax.device_put(
                jnp.zeros(shape, cfg.dtype), sharding)
        else:
            self.data = jnp.zeros(shape, cfg.dtype)

    @staticmethod
    def _scale_sharding(sharding):
        """The scale sidecar drops the head_dim axis, so its sharding is
        the payload's minus the last entry (kv heads stay sharded
        identically); non-named shardings fall back to replication."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        if isinstance(sharding, NamedSharding):
            return NamedSharding(sharding.mesh,
                                 P(*tuple(sharding.spec)[:5]))
        return None

    @property
    def free_pages(self) -> int:
        return self.allocator.free_pages

    def reserve(self, num_pages: int):
        return self.allocator.allocate(num_pages)

    def release(self, pages) -> None:
        """Drop one reference per page and reclaim what reaches zero.
        Prefix-shared pages survive their other holders (allocator
        refcounts); double-freeing a page raises instead of silently
        corrupting the free list.  Cache-retention release paths live in
        ``StateManager._release_pages`` (pages the prefix cache still
        indexes are parked, not reclaimed)."""
        if len(pages):
            self.allocator.free(pages)

    @staticmethod
    def _transfer_bucket(n: int) -> int:
        """Page-transfer ops pad their index vector to a power-of-two
        bucket (padding rows target the null page, whose contents are
        garbage by contract) so the gather/scatter programs compile
        once per BUCKET instead of once per distinct page count — the
        disagg handoff (ISSUE 13) runs one export/import per scheduler
        sweep, and an XLA compile per novel size would dominate the
        transfer it exists to speed up.  Snapshot and preemption
        offload/restore ride the same fix."""
        b = 1
        while b < n:
            b *= 2
        return b

    # -- sequence offload/restore (reference kv_cache.py:166-184) --------
    def read_pages(self, pages):
        """Copy the given pages to host WITHOUT freeing them — the
        page-transfer export half shared by serving snapshots (ISSUE 8)
        and the disagg handoff (ISSUE 13).  Returns the host blob
        [L, n, 2, K, page, D] (a :class:`PageBlob` when quantized);
        ``restore_pages`` is the matching import."""
        import numpy as np
        pages = list(pages)
        n = len(pages)
        idx = np.zeros(self._transfer_bucket(n), np.int32)
        idx[:n] = pages
        jidx = jnp.asarray(idx)
        if self.cfg.quantized:
            return PageBlob(
                np.asarray(self.data.payload[:, jidx])[:, :n],
                np.asarray(self.data.scale[:, jidx])[:, :n])
        blob = np.asarray(self.data[:, jidx])
        return blob[:, :n]

    def offload_pages(self, pages):
        """Copy the given pages to HOST memory and free them on device —
        the preemption half of the reference's offload/restore hooks
        (evict a long sequence's KV under pressure, bring it back
        later).  Returns the host blob [L, n, 2, K, page, D]."""
        blob = self.read_pages(pages)
        self.release(list(pages))
        return blob

    def restore_pages(self, blob) -> "np.ndarray":
        """Allocate fresh pages and write a host blob back; returns the
        new page ids (the sequence's table must be updated to them).
        The scatter DONATES the cache buffer — an out-of-place update
        would transiently need ~2x the KV pool, an OOM exactly in the
        memory-pressure situation preemption exists to relieve.
        Padding columns (bucketed shape) scatter zeros into the null
        page, which holds garbage by contract."""
        import numpy as np
        n = blob.shape[1]
        pages = self.reserve(n)
        b = self._transfer_bucket(n)
        idx = np.zeros(b, np.int32)
        idx[:n] = pages

        def pad_cols(arr, dtype):
            arr = np.asarray(arr)
            if b == n:
                return jnp.asarray(arr, dtype)
            pad = np.zeros(arr.shape[:1] + (b - n,) + arr.shape[2:],
                           dtype=arr.dtype)
            return jnp.asarray(np.concatenate([arr, pad], axis=1), dtype)

        if self.cfg.quantized:
            if not isinstance(blob, PageBlob):
                raise TypeError(
                    "quantized cache restore requires a PageBlob "
                    "(payload + scales); got a bare array — the source "
                    "pool's quantization mode must match")
            dev_blob = KVPages(pad_cols(blob.payload, jnp.int8),
                               pad_cols(blob.scale, jnp.float32))
        else:
            if isinstance(blob, PageBlob):
                raise TypeError(
                    "fp cache restore got a quantized PageBlob — the "
                    "source pool's quantization mode must match")
            dev_blob = pad_cols(blob, self.cfg.dtype)
        self.data = _scatter_pages(self.data, jnp.asarray(idx), dev_blob)
        return np.asarray(pages)


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_slot(data, slot, rows):
    return tuple(d.at[:, slot].set(r.astype(d.dtype))
                 for d, r in zip(data, rows))


class StateSlotBlob:
    """Host copy of one slot: the rows ``[L, *state]`` and ``[L, rows,
    tail / rows]`` it held (:class:`StatePoolConfig`), in the pool's
    dtypes."""

    __slots__ = ("h", "conv")

    def __init__(self, h, conv):
        import numpy as np
        self.h, self.conv = np.asarray(h), np.asarray(conv)

    @property
    def nbytes(self) -> int:
        return self.h.nbytes + self.conv.nbytes


class StatePool:
    """The device state pool ``(h, conv)`` (:class:`StatePoolConfig`) and
    its host slot allocator.  ``data`` is donated to every step program
    and put back, as a page pool is."""

    def __init__(self, cfg: StatePoolConfig):
        self.cfg = cfg
        h, conv = cfg.shapes()
        self.data = (jnp.zeros(h, cfg.state_dtype),
                     jnp.zeros(conv, cfg.conv_dtype))
        self._free = list(range(cfg.num_slots - 1, -1, -1))
        self._held = set()

    @property
    def scratch(self) -> int:
        return self.cfg.num_slots

    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def held_slots(self) -> int:
        return len(self._held)

    def is_held(self, slot: int) -> bool:
        return slot in self._held

    def reserve(self) -> int:
        """One free slot.  Its rows hold what the last holder left: the
        program zeroes them on the sequence's first step, not the host."""
        if not self._free:
            from .blocked_allocator import KVAllocationError
            raise KVAllocationError("state pool: no free slot")
        slot = self._free.pop()
        self._held.add(slot)
        return slot

    def release(self, slot: int) -> None:
        if slot not in self._held:
            raise ValueError(f"state slot {slot} released but not held")
        self._held.remove(slot)
        self._free.append(slot)

    def read_slot(self, slot: int) -> StateSlotBlob:
        return StateSlotBlob(*(d[:, slot] for d in self.data))

    def write_slot(self, slot: int, blob: StateSlotBlob) -> None:
        self.data = _write_slot(self.data, jnp.int32(slot),
                                (jnp.asarray(blob.h),
                                 jnp.asarray(blob.conv)))
