"""Paged (blocked-KV) attention for ragged inference batches.

TPU-native replacement for the FastGen ragged kernel set
(``inference/v2/kernels/ragged_ops/``: ``blocked_flash`` paged
attention, ``linear_blocked_kv_rotary`` fused KV-write+RoPE,
``logits_gather``).  The CUDA path splits sequences into "atoms" sized
to thread blocks; on TPU the ragged batch is instead padded to a static
``[S, Q]`` grid (see ragged/batch.py) and the three kernels become:

* ``write_kv``        — scatter new K/V into cache pages (null page 0
                        absorbs padding writes, keeping shapes static).
* ``paged_attention`` — gather each slot's pages and run masked GQA
                        attention over ``[S, C]`` context; everything is
                        dense einsum -> MXU, raggedness lives in masks.
* ``gather_last``     — last-token hidden-state gather for logits.

Both cache ops take the WHOLE pool ``[L, P+1, 2, K, page, D]`` and a
layer index, never one layer's slice: inside a step program the pool
stays in its donated buffer (it is the layer loop's carry), the write
addresses ``(layer, page, k/v, head, slot)`` directly and the kernels
read ``pool[layer]`` through their BlockSpec index maps.  A per-layer
slice taken out and stacked back costs two layer-sized copies a layer,
and a scatter with window dims between its indexed dims a re-layout of
the layer each way (PERF.md, PR 25).

``paged_decode_attention`` is the Pallas ragged kernel, in two forms.  A
decode step (Q=1) WALKS each row's own live pages: a grid over rows, the
pool left in HBM, a ring of page tiles copied ahead of the matmuls.  Q>1
rows (chunks with per-row causal limits: Ragged Paged Attention,
2604.15464), int8 pages and ALiBi keep a ``(slot, block of kv heads,
group of pages)`` grid whose index maps read the page table.  Either way a
live KV page is DMA'd HBM->VMEM once, whole (K and V of all its heads are
one contiguous block).  The jnp formulation is the semantics ground truth
and the CPU/CI path; ``paged_attention`` auto-selects.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..accelerator import on_tpu

MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)

#: largest Q * gqa_groups query block the ragged Pallas kernel accepts
#: before falling back to the jnp gather path (VMEM: the q block and the
#: [rows, page] score tile must fit alongside the fp32 accumulator)
MAX_KERNEL_Q_ROWS = 4096

#: supported serving_optimization.kv_quantization values
KV_QUANT_FORMATS = ("none", "int8")


@jax.tree_util.register_pytree_node_class
class KVPages:
    """Block-scaled int8 KV page store (ISSUE 16): the quantized twin of
    the plain ``[..., 2, K, page, D]`` cache array.

    ``payload`` holds the int8 codes at the fp layout's exact shape;
    ``scale`` is the per-(token, kv-head) fp32 sidecar — one scale per
    ``head_dim`` block (``payload.shape[:-1]``, so a page's scales are
    one lane-major ``[page]`` row per head), the EQuARX block
    discipline the comm path already uses.  Per-token scales mean a
    decode append never rescales previously-written content: each
    written row carries its own amax, so pages are immutable after
    write exactly like the fp path (the prefix-sharing contract).

    Registered as a pytree so it rides every existing seam unchanged:
    the layer loop carries both leaves, ``jit`` donation donates both,
    and the engine's opaque ``kv_cache.data`` threading never looks
    inside."""

    __slots__ = ("payload", "scale")

    def __init__(self, payload, scale):
        self.payload = payload
        self.scale = scale

    def tree_flatten(self):
        return (self.payload, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self):
        return self.payload.shape

    @property
    def dtype(self):
        return self.payload.dtype

    def __repr__(self):
        return (f"KVPages(payload={self.payload.shape}, "
                f"scale={self.scale.shape})")


def quantize_kv_blocks(kv: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 block quantization over the trailing ``head_dim``
    axis: returns ``(codes int8 [..., D], scales f32 [...])`` with
    ``codes * scales ~= kv``.  Computed in fp32 (a bf16 divide would
    waste code points); an all-zero block gets scale 0 and codes 0."""
    kvf = kv.astype(jnp.float32)
    scale = jnp.max(jnp.abs(kvf), axis=-1) / 127.0            # [...]
    codes = jnp.round(kvf / jnp.maximum(scale, 1e-30)[..., None])
    return (jnp.clip(codes, -127, 127).astype(jnp.int8),
            scale.astype(jnp.float32))


def dequantize_kv_blocks(codes: jax.Array, scale: jax.Array,
                         dtype=jnp.float32) -> jax.Array:
    """Inverse of :func:`quantize_kv_blocks`."""
    return (codes.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _stack_planes(k_new: jax.Array, v_new: Optional[jax.Array]) -> jax.Array:
    """New cache rows ``[S, Q, planes, K, D]``: K and V, or the one plane
    of a pool that holds one."""
    if v_new is None:
        return k_new[:, :, None]
    return jnp.stack([k_new, v_new], axis=2)


def token_positions(start_pos: jax.Array, q_len_max: int) -> jax.Array:
    """pos[s, i] = start_pos[s] + i  (int32, [S, Q])."""
    return start_pos[:, None] + jnp.arange(q_len_max, dtype=jnp.int32)[None, :]


def write_kv(kv: jax.Array, layer, k_new: jax.Array, v_new: jax.Array,
             page_table: jax.Array, start_pos: jax.Array,
             q_lens: jax.Array, *, use_kernel: Optional[bool] = None,
             interpret: bool = False) -> jax.Array:
    """Write new KV into the cache pages of layer ``layer`` of the pool.

    kv    : [L, num_pages+1, 2, K, page_size, D] (or :class:`KVPages`)
            — per (layer, page) one contiguous ``[2, K, page_size, D]``
            block, which the Pallas kernels DMA whole or by head (the
            lowering needs the last two block dims tile-aligned)
    layer : int32 scalar (the layer loop's counter, or a constant)
    k_new/v_new : [S, Q, K, D]; ``v_new`` None for a pool of ONE plane a
            token (``[L, num_pages+1, 1, K, page_size, D]``: the latent
            cache of ``ops/mla_attention.py``)
    Returns the updated pool (functional; the pool is donated at the
    jit boundary and carried through the layer loop, and both forms
    below update it in place).  A quantized pool quantizes at append:
    codes and scales land at the same (layer, page, head, slot), so a
    row is always self-consistent.

    ``use_kernel`` None = auto (on TPU, or anywhere with
    ``interpret=True``): the aliased tile kernel :func:`kv_write_pages`.
    Otherwise — the CPU path and the semantics ground truth — one XLA
    scatter that indexes EVERY leading dim ``(layer, page, k/v, head,
    slot)`` and leaves the ``D`` row as its only window: no window dim
    sits between indexed dims, so the compiler needs no other layout
    for the pool than the one it has.  The chip runs that scatter in
    place too, but row by row: 0.078 ms a layer for a 64-row decode
    step and 0.585 ms for a 4 x 128 prefill piece, against the kernel's
    0.061 and 0.016 (0.33 / 0.82 against 0.053 / 0.019 with int8 pages;
    v5e, Mistral-7B head geometry, PERF.md PR 25).
    """
    if use_kernel is None:
        use_kernel = interpret or on_tpu()
    if use_kernel:
        return kv_write_pages(kv, layer, k_new, v_new, page_table,
                              start_pos, q_lens, interpret=interpret)
    S, Q, K, D = k_new.shape
    quantized = isinstance(kv, KVPages)
    page_size = (kv.payload if quantized else kv).shape[4]
    pos = token_positions(start_pos, Q)                     # [S, Q]
    valid = jnp.arange(Q, dtype=jnp.int32)[None, :] < q_lens[:, None]
    pages = jnp.take_along_axis(page_table, pos // page_size, axis=1)
    pages = jnp.where(valid, pages, 0)                      # null page
    kv_new = _stack_planes(k_new, v_new)
    planes = kv_new.shape[2]
    kv_new = kv_new.reshape(S * Q, planes, K, D)
    # index arrays broadcast to the update's leading [S*Q, planes, K]
    idx = (jnp.asarray(layer, jnp.int32), pages.reshape(-1, 1, 1),
           jnp.arange(planes, dtype=jnp.int32)[None, :, None],
           jnp.arange(K, dtype=jnp.int32)[None, None, :],
           (pos % page_size).reshape(-1, 1, 1))
    if quantized:
        codes, scales = quantize_kv_blocks(kv_new)
        return KVPages(kv.payload.at[idx].set(codes, mode="drop"),
                       kv.scale.at[idx].set(scales, mode="drop"))
    return kv.at[idx].set(kv_new.astype(kv.dtype), mode="drop")


def _kv_write_kernel(l_ref, pid_ref, off_ref, ql_ref, *refs, has_scale):
    """One (row, touched page) grid step of the in-place cache write:
    read the page's ``[2, K, page, D]`` tiles, replace the slots this
    row's new tokens own, write the tiles back to the same address (the
    pool is aliased input -> output, so nothing else of it moves).

    Slot ``t`` of the page holds token ``i = off + t`` of the row
    (``off`` < 0 where the row starts mid-page); it is replaced iff
    ``0 <= i < q_lens``.  Mosaic has no gather and no unaligned
    dynamic sublane slice, so the new rows are shifted into place by a
    one-hot ``[page, Q]`` matmul — exact: every output row sums ONE
    product by 1.0 (bf16 and int8 codes in bf16, anything else in fp32
    at HIGHEST precision).  Q = 1 needs no shift, only a broadcast.
    """
    if has_scale:
        new_ref, nscale_ref, tile_ref, stile_ref, out_ref, sout_ref = refs
    else:
        new_ref, tile_ref, out_ref = refs
    s, j = pl.program_id(0), pl.program_id(1)
    off, q_lens = off_ref[s, j], ql_ref[s]
    planes, K, page, _ = tile_ref.shape
    q_pad = new_ref.shape[2]                # 1: a decode row, no shift
    exact = new_ref.dtype in (jnp.bfloat16, jnp.int8)
    mm_dtype = jnp.bfloat16 if exact else jnp.float32
    precision = None if exact else jax.lax.Precision.HIGHEST

    # payload: tokens along sublanes
    tok = off + jax.lax.broadcasted_iota(jnp.int32, (page, 1), 0)
    own = (tok >= 0) & (tok < q_lens)                      # [page, 1]
    if q_pad > 1:
        pick = (jax.lax.broadcasted_iota(jnp.int32, (page, q_pad), 1)
                == tok).astype(mm_dtype)                   # [page, Q]
    for kv in range(planes):
        for k in range(K):
            if q_pad == 1:
                new = new_ref[kv, k]                       # [1, D]
            else:
                new = jax.lax.dot_general(
                    pick, new_ref[kv, k].astype(mm_dtype),
                    (((1,), (0,)), ((), ())), precision=precision,
                    preferred_element_type=jnp.float32
                ).astype(out_ref.dtype)                    # [page, D]
            out_ref[kv, k] = jnp.where(own, new, tile_ref[kv, k])
    if not has_scale:
        return
    # scale sidecar: tokens along lanes
    tok = off + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    own = (tok >= 0) & (tok < q_lens)                      # [1, page]
    if q_pad > 1:
        pick = (jax.lax.broadcasted_iota(jnp.int32, (q_pad, page), 0)
                == tok).astype(jnp.float32)                # [Q, page]
    for kv in range(planes):
        if q_pad == 1:
            new = nscale_ref[kv]                           # [K, 1]
        else:
            new = jax.lax.dot_general(
                nscale_ref[kv], pick, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)        # [K, page]
        sout_ref[kv] = jnp.where(own, new, stile_ref[kv])


def kv_write_pages(kv: jax.Array, layer, k_new: jax.Array,
                   v_new: jax.Array, page_table: jax.Array,
                   start_pos: jax.Array, q_lens: jax.Array, *,
                   interpret: bool = False,
                   name: str = "kv_write") -> jax.Array:
    """Pallas cache write, in place: a ``(row, touched page)`` grid that
    read-modify-writes only the page tiles a row's new tokens land in
    (:func:`_kv_write_kernel`), the pool aliased input -> output.  The
    page ids ride the BlockSpec index maps through scalar prefetch, as
    in the attention kernel.  Same contract as :func:`write_kv`; a
    padding token is written nowhere (a row with nothing to write
    rewrites the null page with its own content).  ``name`` is the
    kernel's name in a trace less its ``_decode`` / ``_prefill`` ending
    (a pool of another kind writes under a name of its own)."""
    S, Q, K, D = k_new.shape
    has_scale = isinstance(kv, KVPages)
    kv_arr = kv.payload if has_scale else kv
    page_size = kv_arr.shape[4]
    # pages a row of Q tokens can touch from an arbitrary first slot
    J = min((Q + 2 * page_size - 2) // page_size, page_table.shape[1])
    first = start_pos // page_size                          # [S]
    pj = first[:, None] + jnp.arange(J, dtype=jnp.int32)[None, :]
    touched = (pj * page_size < (start_pos + q_lens)[:, None])
    touched &= (q_lens > 0)[:, None]
    pids = jnp.take_along_axis(
        page_table, jnp.minimum(pj, page_table.shape[1] - 1), axis=1)
    pids = jnp.where(touched, pids, 0).astype(jnp.int32)    # null page
    offs = (pj * page_size - start_pos[:, None]).astype(jnp.int32)

    # [S, Q, 2, K, D] -> per row [2, K, Q, D], Q padded to whole lanes
    # of the one-hot (activation-sized; the pool itself is not touched)
    q_pad = Q if Q == 1 else -(-Q // 128) * 128
    kv_new = _stack_planes(k_new, v_new)
    planes = kv_new.shape[2]
    if has_scale:
        kv_new, scales = quantize_kv_blocks(kv_new)
        scales = jnp.pad(scales.transpose(0, 2, 3, 1),      # [S,2,K,Q]
                         ((0, 0),) * 3 + ((0, q_pad - Q),))
    kv_new = jnp.pad(kv_new.astype(kv_arr.dtype).transpose(0, 2, 3, 1, 4),
                     ((0, 0),) * 3 + ((0, q_pad - Q), (0, 0)))

    def at_row(*block):
        return pl.BlockSpec((None,) + block,
                            lambda s, j, l, pid, off, ql:
                            (s,) + (0,) * len(block))

    def at_page(*block):
        return pl.BlockSpec((None, None) + block,
                            lambda s, j, l, pid, off, ql:
                            (l[0], pid[s, j]) + (0,) * len(block))

    tiles = at_page(planes, K, page_size, D)
    if has_scale:
        rows = at_page(planes, K, page_size)
        in_specs = [at_row(planes, K, q_pad, D), at_row(planes, K, q_pad),
                    tiles, rows]
        inputs = (kv_new, scales, kv.payload, kv.scale)
        out_specs, out_shape = [tiles, rows], [
            jax.ShapeDtypeStruct(kv.payload.shape, kv.payload.dtype),
            jax.ShapeDtypeStruct(kv.scale.shape, kv.scale.dtype)]
        aliases = {6: 0, 7: 1}      # operands count the 4 prefetched
    else:
        in_specs = [at_row(planes, K, q_pad, D), tiles]
        inputs = (kv_new, kv)
        out_specs = tiles
        out_shape = jax.ShapeDtypeStruct(kv.shape, kv.dtype)
        aliases = {5: 0}
    out = pl.pallas_call(
        functools.partial(_kv_write_kernel, has_scale=has_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(S, J),
            in_specs=in_specs, out_specs=out_specs),
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        # not ``paged_attention*``: that pattern is the attention
        # kernels' share and roofline (benchmark/metrics)
        name=name + ("_decode" if Q == 1 else "_prefill"),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), pids, offs,
      q_lens.astype(jnp.int32), *inputs)
    return KVPages(*out) if has_scale else out


def paged_attention(q: jax.Array, kv: jax.Array, layer,
                    page_table: jax.Array, start_pos: jax.Array,
                    q_lens: jax.Array, *,
                    sm_scale: float | None = None,
                    use_kernel: Optional[bool] = None,
                    alibi_slopes: Optional[jax.Array] = None,
                    window: Optional[int] = None,
                    interpret: bool = False,
                    name: str = "paged_attention") -> jax.Array:
    """Masked GQA attention of [S, Q] new tokens over their paged context.

    q       : [S, Q, H, D]    (H = K * groups)
    kv      : [L, num_pages+1, 2, K, page_size, D] (new KV already written)
    layer   : int32 scalar, the layer of the pool to attend over
    Returns : [S, Q, H, D]

    Ragged buckets route to the Pallas kernel (``use_kernel`` None =
    auto: on TPU, or anywhere with ``interpret=True``) — the kernel
    handles ANY Q with per-query causal limits, so a fused mixed
    prefill+decode step is one kernel launch, not a per-Q-bucket split
    (arxiv 2604.15464's single-kernel ragged serving).  Oversized query
    blocks (Q * groups > ``MAX_KERNEL_Q_ROWS``) and the CPU default fall
    back to the dense-gather jnp path.  ``interpret`` runs the kernel in
    Pallas interpret mode (CPU testing), independent of path selection.
    """
    S, Q, H, D = q.shape
    quantized = isinstance(kv, KVPages)
    K_heads = (kv.payload if quantized else kv).shape[3]
    if use_kernel is None:
        use_kernel = ((interpret or on_tpu())
                      and Q * (H // K_heads) <= MAX_KERNEL_Q_ROWS)
    if use_kernel:
        return paged_decode_attention(
            q, kv, layer, page_table, start_pos,
            sm_scale=sm_scale, alibi_slopes=alibi_slopes,
            window=window, interpret=interpret, name=name)
    K = K_heads
    G = H // K
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(D)

    # dequantizes the gathered context only — a resident int8 cache
    # stays int8
    k, v = paged_context(kv, layer, page_table, dtype=q.dtype)
    C = k.shape[1]

    qg = q.reshape(S, Q, K, G, D)
    scores = jnp.einsum("sqkgd,sckd->skgqc", qg, k).astype(jnp.float32) * scale

    pos = token_positions(start_pos, Q)                     # [S, Q]
    ctx = jnp.arange(C, dtype=jnp.int32)
    if alibi_slopes is not None:
        # ALiBi: per-q-head bias linear in the absolute key position
        # (context row c IS position c — pages fill in order); head
        # h = k*G + g matches the grouped reshape above
        sl = jnp.asarray(alibi_slopes, jnp.float32).reshape(K, G)
        scores = scores + (sl[None, :, :, None, None]
                           * ctx[None, None, None, None, :])
    # context element c visible to query (s, i) iff c <= pos[s, i]; the
    # page gather places context position c at row c of the flattened
    # pages exactly (pages are filled in order).
    mask = ctx[None, None, :] <= pos[:, :, None]            # [S, Q, C]
    if window is not None:  # Mistral sliding window: (pos-window, pos]
        mask &= ctx[None, None, :] > pos[:, :, None] - window
    # null-page / unallocated-page rows beyond the sequence never pass
    # the causal check since pos < allocated capacity * page_size.
    scores = jnp.where(mask[:, None, None, :, :], scores, MASK_VALUE)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("skgqc,sckd->sqkgd", probs, v)
    return out.reshape(S, Q, H, D)


# ---------------------------------------------------------------------------
# Pallas ragged kernel (any Q: decode rows AND prefill-chunk rows)
# ---------------------------------------------------------------------------

# (these sit below the write kernel: Mosaic keeps a kernel's source lines
# in the program's text, so lines added above ``kv_write_pages`` change
# every program that writes pages, the latent kind's too, and miss their
# cached executables)

#: page slots one grid step of the ragged kernel attends over, at most
#: (8 x 64 tokens): a page a step leaves the step's fixed cost larger
#: than its work
PAGES_PER_STEP = 8

#: what :func:`kernel_blocks` lets a grid step take of the DEFAULT scoped
#: VMEM limit (16 MiB on v5e; a kernel that asks for more than the default
#: can hang the chip inside a mixed step program: PERF.md, PR 27), and the
#: float32 score tiles it counts a step as holding, plain and under ALiBi
VMEM_BUDGET = 14 * 2 ** 20
SCORE_TILES = 1
ALIBI_TILES = 2

#: K/V bytes one grid step fetches, at most, where it holds every KV head
#: of its page slots (one buffer of them; :func:`kernel_blocks`).  2 MiB is
#: what 8 slots of a 256 KB page are (8 KV heads x 64 tokens x 128,
#: bfloat16, K and V): the step the 8-KV-head configurations were fitted
#: at in PR 28, which they keep.  A page of 30 such heads is 960 KB: 2
#: slots.  Timed alone at 256 rows (v5e, PERF.md PR 41; ms at contexts
#: uniform in 100-2,200): (15 heads, 8 slots) 8.87, (30, 4) 7.42, (30, 2)
#: 6.83, (30, 1) 6.56, and 6.56-6.58 for all three of 30 heads once no
#: slot fetches the null page: what a wide group costs is `group` fetches
#: of the null page a row, so a target under 2 MiB would serve wide pages
#: by 4% and take the 8-head configurations' group of 8 with it (their own
#: sweep: PERF.md, section 7)
STEP_BYTES = 2 * 2 ** 20


def _attend_tile(q, plane, ctx0, start, m_scr, l_scr, acc_scr, *, sm_scale,
                 window, groups, bias=None, scales=None):
    """Flash-style attention of a row's queries against ONE tile of its
    context, all KV heads a batched contraction: the arithmetic of both
    paged kernels (the grid form's step, a chunk of the walk's tile).

    q : [heads, rows, D]  row r = q_idx * G + g, so its causal limit is
                          ``start + r // G + 1``
    plane(i) : [heads, span, D]  the tile's K (0) or V (1) plane; column
                          c is context position ``ctx0() + c`` (both
                          evaluated where the arithmetic reaches them:
                          the grid form's text is PR 41's to the letter,
                          ``tests/test_chip_compile.py``)
    ``m_scr`` / ``l_scr`` / ``acc_scr`` ``[heads, rows, 1 | D]`` carry the
    running max / denominator / weighted sum in float32.  ``bias(ctx)``
    adds to the scores; ``scales(i)`` ``[heads, 1, span]`` multiply the
    score (0) and the probability (1) columns (int8 pages)."""
    k = plane(0)
    rows, span = q.shape[1], k.shape[1]
    scores = jax.lax.dot_general(                         # [heads, rows, span]
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * sm_scale
    if scales is not None:
        scores = scores * scales(0)
    ctx = ctx0() + jax.lax.broadcasted_iota(jnp.int32, (rows, span), 1)
    if bias is not None:
        scores = scores + bias(ctx)
    # per-row causal limit: row r is query index r // G
    ctx_len = start + 1 + jax.lax.broadcasted_iota(
        jnp.int32, (rows, span), 0) // groups
    keep = ctx < ctx_len
    if window is not None:
        keep &= ctx >= ctx_len - window
    scores = jnp.where(keep[None], scores, MASK_VALUE)
    m_prev = m_scr[:]                                      # [heads, rows, 1]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=2, keepdims=True))
    pexp = jnp.exp(scores - m_new)
    alpha = jnp.exp(m_prev - m_new)
    m_scr[:] = m_new
    l_scr[:] = l_scr[:] * alpha + jnp.sum(pexp, axis=2, keepdims=True)
    if scales is not None:
        pexp = pexp * scales(1)
    acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
        pexp.astype(q.dtype), plane(1), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)


def _decode_kernel(l_ref, pt_ref, sp_ref, *refs, page_size, group, heads,
                   sm_scale, has_alibi, has_scale, window, q_len, groups):
    """One (slot, block of kv heads, group of pages) grid step of
    flash-style ragged attention: ``heads`` KV heads of the row against
    ``group`` whole pages at once, the heads a batched contraction.

    q_ref : [heads, Q*G, D]  (this slot's queries by kv head; row
                            r = q_idx * G + g, so per-row causal limit
                            ctx_len_r = start_pos + r // G + 1)
    page refs (``group`` of them) : [2, heads, page_size, D]  one cache
                            page each, the K and V planes of the block's
                            heads in ONE fetch, DMA'd via the page table
                            (see the index maps in the caller); a head
                            attends the group's pages as one
                            ``[group * page_size, D]`` context
    scale refs (``group``) : [2, K, page_size]  per-token block scales of
                            EVERY head of the page — present ONLY when
                            ``has_scale`` (quantized int8 pages, ISSUE
                            16).  A scale is constant over ``D``, so it
                            factors out of both matmuls and is applied to
                            the ``[rows, span]`` score / probability tile
                            as a lane-major row: int8 codes feed the MXU
                            directly and HBM traffic stays int8-sized
    slopes_ref : [K, G]    per-q-head ALiBi slopes — present ONLY when
                            ``has_alibi`` (the kernel is specialized
                            statically so non-ALiBi models pay nothing)
    Q = 1 is the decode specialization; Q > 1 rows are prefill chunks
    whose own new tokens are already in the cache (write_kv runs before
    attention), so the causal mask is exactly the jnp path's
    ``ctx <= pos``.  Rows beyond a slot's q_len compute garbage that the
    caller's logits gather / KV null page ignore.
    Scratch m/l/acc ``[heads, Q*G, 1 | D]`` carry the running max /
    denominator / weighted sum across the page-group axis (the innermost,
    sequential grid dim).
    """
    refs = list(refs)
    slopes_ref = refs.pop(0) if has_alibi else None
    q_ref = refs.pop(0)
    page_refs = [refs.pop(0) for _ in range(group)]
    scale_refs = [refs.pop(0) for _ in range(group)] if has_scale else ()
    o_ref, m_scr, l_scr, acc_scr = refs
    s, kh, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    rows, span = q_len * groups, group * page_size

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # the LAST query row sees the longest context; earlier rows mask
    start = sp_ref[s]
    live = j * span < start + q_len
    if window is not None:
        # groups wholly below the FIRST row's window start contribute
        # nothing (the banded-decode analogue of the flash kernel's k_lo)
        live &= (j + 1) * span > start + 1 - window

    def side_by_side(tiles, axis):
        return tiles[0] if group == 1 else jnp.concatenate(tiles, axis=axis)

    def plane(i):
        """The group's pages of K (0) or V (1): ``[heads, span, D]``."""
        # int8 codes are exact in the query dtype; their per-token scale
        # multiplies the score column instead of the [span, D] tile
        return side_by_side([p[i] for p in page_refs], 1).astype(q_ref.dtype)

    def by_head(of_head):
        """``[heads, ...]`` of what ``of_head(k)`` yields for each of the
        step's heads, ``k`` its index among all K (a leading dim is not
        tiled: stacking along it moves nothing)."""
        return jnp.stack([of_head(kh * heads + h) for h in range(heads)])

    def scales(i):
        """The pages' scale rows of the step's heads: ``[heads, 1, span]``."""
        return by_head(lambda k: side_by_side(
            [sc[i, pl.ds(k, 1), :] for sc in scale_refs], 1))

    @pl.when(live)
    def _attend():
        bias = None
        if has_alibi:  # additive bias linear in the absolute key position
            # row r = q_idx * G + g: split the row dim so the per-head
            # slope is a plain broadcast (Mosaic lowers reshapes and
            # rank-2 iota; it rejects 1-D iota and in-kernel gathers)
            def bias(ctx):
                pos = ctx.astype(jnp.float32).reshape(q_len, groups, span)
                return by_head(lambda k: (
                    slopes_ref[pl.ds(k, 1), :][:, :, None] * pos
                ).reshape(rows, span))
        _attend_tile(q_ref[:], plane, lambda: j * span, start, m_scr, l_scr,
                     acc_scr, sm_scale=sm_scale, window=window,
                     groups=groups, bias=bias,
                     scales=scales if has_scale else None)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        o_ref[:] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)
                    ).astype(o_ref.dtype)


def _flatten_context(pages: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Gathered pages ``[S, P, 2, K, page, D]`` -> token-major K and V
    contexts ``[S, P*page, K, D]`` (context row c IS position c)."""
    S, P, _, K, page_size, D = pages.shape
    kv = pages.transpose(2, 0, 1, 4, 3, 5).reshape(2, S, P * page_size, K, D)
    return kv[0], kv[1]


def _round_up(n: int, tile: int) -> int:
    return -(-n // tile) * tile


def _row_bytes(lanes: int, q_itemsize: int) -> int:
    """VMEM a query row of one KV head costs a step of either form: the
    query and output blocks in two buffers, the float32 accumulator and
    its quotient, the running max and denominator at a lane tile each."""
    return 2 * 2 * lanes * q_itemsize + 2 * lanes * 4 + 3 * 128 * 4


def step_vmem_bytes(heads: int, group: int, rows: int, kv_heads: int,
                    head_dim: int, page_size: int, q_itemsize: int,
                    kv_itemsize: int, has_scale: bool = False,
                    has_alibi: bool = False) -> int:
    """What a grid step of ``heads`` KV heads and ``group`` page slots
    holds in VMEM, by :func:`kernel_blocks`' account.

    Fitted to what the chip's compiler takes for the batched form (found
    by lowering ``vmem_limit_bytes`` until it refuses; v5e, PERF.md PR
    28) and erring high: the pages and their scale rows,
    double-buffered; a query row of a head ``row_bytes`` (query and
    output blocks in two buffers, the float32 accumulator and its
    quotient, the running max and denominator at a lane tile each); and
    ``SCORE_TILES`` float32 ``[heads, rows, span]`` score tiles
    (``ALIBI_TILES`` more under a bias).
    """
    rows = _round_up(rows, 8)
    lanes = _round_up(head_dim, 128)
    row_bytes = _row_bytes(lanes, q_itemsize)
    tiles = SCORE_TILES + (ALIBI_TILES if has_alibi else 0)
    # one page slot of one head, K and V, in two buffers; the slot's
    # scale rows come whole whatever the step's heads
    slot_bytes = 2 * 2 * page_size * lanes * kv_itemsize
    scale_bytes = (2 * 2 * _round_up(kv_heads, 8) * _round_up(page_size, 128)
                   * 4 if has_scale else 0)
    span = _round_up(group * page_size, 128)
    return (group * (heads * slot_bytes + scale_bytes)
            + heads * rows * (row_bytes + tiles * span * 4))


def kernel_blocks(rows: int, kv_heads: int, head_dim: int, page_size: int,
                  page_slots: int, q_itemsize: int, kv_itemsize: int,
                  has_scale: bool = False,
                  has_alibi: bool = False) -> Tuple[int, int]:
    """``(heads, group)``: how many KV heads and how many page slots one
    grid step of the ragged kernel holds, read from the call's shapes.

    A grid step is sized by its bytes.  In the pool's layout a page's K
    and V of ALL heads are one contiguous block, so a step takes every
    head (one fetch a page) and ``group`` is the widest of
    ``PAGES_PER_STEP``, 4, 2, 1 that divides the page bucket and keeps
    the step's pages at or under ``STEP_BYTES``: 8 slots of a 256 KB
    page (8 KV heads), 2 of a 960 KB one (30 heads), 1 of anything
    larger.  A wider group of fewer heads moves the same bytes a step and
    loses (v5e, PERF.md PR 41: 6.95 ms for 4.82 at 256 rows of 740
    tokens).  Each slot of a group is a buffer of its own, and under
    tables that name the null page where a row's pages end it fetched
    that page once a row: ``group`` fetches a row, and with a block of
    SOME heads, whose index carries the head block, once more for each
    head block (1.69 of the 2.1 ms; :func:`fetch_table` now keeps such a
    slot on the block it holds).  The rest is the head split itself, two
    strided pieces a page and two passes over a row (0.55 ms); the
    arithmetic on dead pages hides behind the fetches.

    Where every head does not fit ``VMEM_BUDGET`` at that group
    (:func:`step_vmem_bytes`) the step's size is its query rows', not its
    pages' (a prompt chunk of several query heads a KV head), and the
    blocks are PR 28's: the widest group first, then the largest divisor
    of K that fits (K, K/2, ... 1 for a power of two; 30, 15, 10, 6, ...
    for 30 heads).  A 128-token chunk of 4 query heads a KV head takes
    half the heads and 8 pages, and the largest block
    ``MAX_KERNEL_Q_ROWS`` admits one head and one page a step: the
    kernel's form before PR 28, which is also what a shape that fits
    nowhere gets.
    """
    def fits(heads, group):
        return step_vmem_bytes(
            heads, group, rows, kv_heads, head_dim, page_size, q_itemsize,
            kv_itemsize, has_scale, has_alibi) <= VMEM_BUDGET

    groups = [g for g in (PAGES_PER_STEP, 4, 2, 1) if page_slots % g == 0]
    # what a step fetches of one page slot: K and V of every head
    page_bytes = 2 * kv_heads * page_size * head_dim * kv_itemsize
    by_bytes = next(g for g in groups
                    if g == 1 or g * page_bytes <= STEP_BYTES)
    if fits(kv_heads, by_bytes):
        return kv_heads, by_bytes
    for group in groups:
        for heads in range(kv_heads, 0, -1):
            if kv_heads % heads == 0 and fits(heads, group):
                return heads, group
    return 1, 1


def paged_decode_attention(q: jax.Array, kv: jax.Array, layer,
                           page_table: jax.Array, start_pos: jax.Array, *,
                           sm_scale: float | None = None,
                           alibi_slopes: Optional[jax.Array] = None,
                           window: Optional[int] = None,
                           interpret: bool = False,
                           name: str = "paged_attention") -> jax.Array:
    """Pallas ragged paged attention: [S, Q] queries over paged KV.

    TPU-native counterpart of the reference's blocked_flash atoms
    (``inference/v2/kernels/ragged_ops/atom_builder/`` splits sequences
    into KV blocks per thread block; here a group of whole pages IS the
    block and the page table names them through scalar prefetch).

    q: [S, Q, H, D]; kv: [L, num_pages+1, 2, K, page_size, D] with
    ``layer`` an int32 scalar (a scalar-prefetch operand: the kernels
    address ``pool[layer, page]``, so no layer is ever sliced out of the
    pool); page_table: [S, P]; start_pos: [S].  Returns [S, Q, H, D].

    Two forms, chosen from what the call can see.  A decode step (Q = 1,
    plain pages, no bias) is a WALK over each row's own live pages
    (:func:`paged_walk_attention`): a row costs its context whatever the
    page bucket.  Prompt chunks and speculative rows (Q > 1), int8 pages
    (:class:`KVPages`) and ALiBi keep the grid over the bucket
    (:func:`paged_grid_attention`), which no cell times, as does a page
    too large for the walk's tiles (:func:`walk_blocks`).  Both carry
    the kernel name ``name + "_decode"`` / ``"_prefill"``."""
    S, Q, H, D = q.shape
    has_scale = isinstance(kv, KVPages)
    if Q == 1 and not has_scale and alibi_slopes is None:
        K, page_size = kv.shape[3:5]
        group, sub = walk_blocks(H // K, K, D, page_size, page_table.shape[1],
                                 q.dtype.itemsize, kv.dtype.itemsize)
        if group:
            return paged_walk_attention(
                q, kv, layer, page_table, start_pos, group=group, sub=sub,
                sm_scale=float(sm_scale if sm_scale is not None
                               else 1.0 / np.sqrt(D)),
                window=window, interpret=interpret, name=name)
    return paged_grid_attention(
        q, kv, layer, page_table, start_pos, sm_scale=sm_scale,
        alibi_slopes=alibi_slopes, window=window, interpret=interpret,
        name=name)


def paged_grid_attention(q: jax.Array, kv: jax.Array, layer,
                         page_table: jax.Array, start_pos: jax.Array, *,
                         sm_scale: float | None = None,
                         alibi_slopes: Optional[jax.Array] = None,
                         window: Optional[int] = None,
                         interpret: bool = False,
                         name: str = "paged_attention") -> jax.Array:
    """The grid form of :func:`paged_decode_attention`, for any Q: Q > 1
    rows carry prefill chunks with per-row causal limits, so one launch
    serves a fused mixed prefill+decode ragged batch (the single-kernel
    serving formulation of Ragged Paged Attention, arxiv 2604.15464).

    Grid ``(S, K // heads, P // group)`` with ``heads`` and ``group``
    from :func:`kernel_blocks`.  In the pool's layout one page's K and V
    of all heads are one contiguous block, so a page is ONE fetch; the
    pool is passed once per page slot of a group, each with its own
    index map, so the pipeline fetches a group's pages side by side.  A
    group wholly past the row's context (or under its window) is skipped,
    and what a row sees is decided by POSITION (``start_pos``, the
    window), never by page id.  ``page_table`` is the engine's: the null
    page past a row's pages and under its window
    (``SequenceDescriptor.page_table``, ``evict_pages_below``).  The
    index maps read :func:`fetch_table` of it instead: a slot that holds
    nothing for the row names the block its buffer already holds, and
    the pipeline, which copies nothing when consecutive steps name the
    same block, fetches nothing for it, where the null page cost a fetch
    a slot and row (0.31 of a 2.56 ms call at 8 KV heads, 0.27 of 6.83
    at 30; v5e, PERF.md PR 41).  A dead group still costs its grid step
    (0.05-0.09 us at 30 KV heads).  The borrowed page's columns are
    masked like the null page's, so outputs are the same to the bit; a
    table of real pages in dead slots would be attended as correctly,
    and fetched for nothing in every group of the bucket.
    """
    S, Q, H, D = q.shape
    has_scale = isinstance(kv, KVPages)
    kv_arr = kv.payload if has_scale else kv
    K, page_size = kv_arr.shape[3:5]
    G = H // K
    P_pages = page_table.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(D)
    has_alibi = alibi_slopes is not None
    heads, group = kernel_blocks(Q * G, K, D, page_size, P_pages,
                                 q.dtype.itemsize, kv_arr.dtype.itemsize,
                                 has_scale, has_alibi)

    # fold GQA per kv head: [S, K, Q*G, D], row r = q_idx * G + g
    qg = q.reshape(S, Q, K, G, D).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(S, K, Q * G, D)

    # index maps receive (s, kh, j, *scalar_prefetch_refs)
    row_spec = pl.BlockSpec((None, heads, Q * G, D),
                            lambda s, kh, j, l, pt, sp: (s, kh, 0, 0))

    def page_spec(i):
        return pl.BlockSpec(
            (None, None, 2, heads, page_size, D),
            lambda s, kh, j, l, pt, sp:
            (l[0], pt[s, j * group + i], 0, kh, 0, 0))

    def scale_spec(i):
        # scale sidecar [L, P+1, 2, K, page] -> the page's full
        # [2, K, page] block (last two block dims = array dims, which the
        # lowering requires); the kernel row-slices a head's.  Same
        # page-table indirection as the payload: the BlockSpec DMA is
        # the gather
        return pl.BlockSpec(
            (None, None, 2, K, page_size),
            lambda s, kh, j, l, pt, sp: (l[0], pt[s, j * group + i], 0, 0, 0))

    in_specs = [row_spec] + [page_spec(i) for i in range(group)]
    inputs = (qg,) + (kv_arr,) * group
    if has_scale:
        in_specs += [scale_spec(i) for i in range(group)]
        inputs += (kv.scale,) * group
    if has_alibi:
        in_specs = [pl.BlockSpec((K, G), lambda s, kh, j, l, pt, sp: (0, 0))
                    ] + in_specs
        inputs = (jnp.asarray(alibi_slopes, jnp.float32).reshape(K, G),
                  ) + inputs

    kernel = functools.partial(
        _decode_kernel, page_size=page_size, group=group, heads=heads,
        sm_scale=scale, has_alibi=has_alibi, has_scale=has_scale,
        window=window, q_len=Q, groups=G)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S, K // heads, P_pages // group),
            in_specs=in_specs,
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((heads, Q * G, 1), jnp.float32),
                pltpu.VMEM((heads, Q * G, 1), jnp.float32),
                pltpu.VMEM((heads, Q * G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, K, Q * G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        # named by the kind of row it serves, so a trace splits the
        # kernel's time between decoding rows and prefill chunks (and,
        # through ``name``, between the page groups of a model that has
        # two: the window group's calls are ``paged_attention_window_*``)
        name=name + ("_decode" if Q == 1 else "_prefill"),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      fetch_table(page_table.astype(jnp.int32), group),
      start_pos.astype(jnp.int32), *inputs)
    out = out.reshape(S, K, Q, G, D).transpose(0, 2, 1, 3, 4)
    return out.reshape(S, Q, H, D)


def fetch_table(page_table: jax.Array, group: int) -> jax.Array:
    """The table the ragged kernel's index maps READ, from the engine's
    ``[S, P]`` table and the kernel's page group: a slot that holds the
    null page (id 0: past the row's pages, or under its window) takes the
    page of the nearest live slot BEFORE it in its column of the
    ``[S, P // group, group]`` view (``p - group``, ``p - 2 * group``,
    ...), which is the block that slot's pipeline buffer already holds,
    so the pipeline fetches nothing for it.  Live slots are never
    altered, and a dead slot with no live one before it keeps the null
    page (a whole table where a row is one group: nothing comes before).

    "The last page that is not null" is associative, so the columns are
    scanned by doubling: ``log2(P // group)`` shifts and selects over
    ``[S, P]`` integers, which the compiler fuses, shares between the
    unrolled layers of a page group and lifts out of a scanned stack
    (``tests/test_chip_compile.py``).  (A running maximum and a gather
    say the same and cost 0.03-0.16 ms a call on a v5e, a third of what
    the rule saves: PERF.md PR 44.)  For READS only: the cache write,
    eviction, the dense gather (:func:`paged_context`) and everything on
    the host keep the engine's table, since a dead slot must never be
    written through a borrowed page id."""
    P = page_table.shape[1]
    shift = group
    with jax.named_scope("fetch_table"):
        while shift < P:
            # the table ``shift`` slots to the right, nulls moving in
            before = jax.lax.pad(page_table, jnp.zeros((), page_table.dtype),
                                 ((0, 0, 0), (shift, -shift, 0)))
            page_table = jax.lax.select(page_table != 0, page_table, before)
            shift *= 2
    return page_table


def slots_held(page_table: np.ndarray, group: int) -> Tuple[int, int]:
    """(held, live) page slots of a host table ``[S, P]`` under
    :func:`fetch_table`: ``held`` counts the dead slots of a group with a
    live slot that name a borrowed block (each a fetch of the null page
    the row no longer makes), ``live`` the slots that hold a page."""
    S, P = page_table.shape
    live = (page_table != 0).reshape(S, max(P // group, 1), -1)
    seen = np.logical_or.accumulate(live, axis=1)
    held = ~live & seen & live.any(axis=2, keepdims=True)
    return int(held.sum()), int(live.sum())


#: tiles of a decode row's walk in VMEM at once: the one under the matmuls
#: and two being copied (``ops/mla_attention.py``: with one tile ahead a
#: short tile cannot cover a long one's copy).  The walk's look-ahead
#: (``ahead``) is written for these two
TILES_IN_FLIGHT = 3

#: K/V bytes one chunk of a walk's tile is attended at.  A chunk's
#: arithmetic is one dependent chain (scores, max, exp, sum, weighted
#: sum: ~0.5 us whatever its width), so a chunk must hold at least the
#: bytes that take the memory as long to deliver, and a row's last tile
#: is cut to whole chunks.  512 KB is 2 slots of a 256 KB page (8 KV
#: heads) and 16 of a 32 KB one (1 KV head, where chunks of 2 slots read
#: 1.31 ms for 0.49: v5e, PERF.md PR 45)
CHUNK_BYTES = 512 * 2 ** 10


def walk_blocks(rows: int, kv_heads: int, head_dim: int, page_size: int,
                page_slots: int, q_itemsize: int,
                kv_itemsize: int) -> Tuple[int, int]:
    """``(group, sub)``: the page slots a tile of the decode walk holds
    and the page slots a chunk of it is attended at, read from the call's
    shapes.  Both are sized by bytes alone: a tile is ``STEP_BYTES`` of
    whole pages (every KV head of a page is one copy), at least one page,
    no more than a row's table holds and no more than ``VMEM_BUDGET``
    leaves for ``TILES_IN_FLIGHT`` tiles beside the row's blocks and a
    chunk's scores; a chunk is ``CHUNK_BYTES`` of them and divides the
    tile.  Neither has to divide the page bucket.  ``(0, 0)`` where not
    even one page a tile fits: the call keeps the grid form, which
    splits a page by head."""
    lanes = _round_up(head_dim, 128)
    page_bytes = 2 * kv_heads * _round_up(page_size, 8) * lanes * kv_itemsize
    sub = min(max(CHUNK_BYTES // page_bytes, 1), page_slots)
    # the row's blocks and three float32 score tiles of a chunk
    fixed = kv_heads * _round_up(rows, 8) * (
        _row_bytes(lanes, q_itemsize)
        + 3 * _round_up(sub * page_size, 128) * 4)
    group = min(max(STEP_BYTES // page_bytes, 1),
                (VMEM_BUDGET - fixed) // (TILES_IN_FLIGHT * page_bytes),
                _round_up(page_slots, sub))
    if group < 1:
        return 0, 0
    sub = min(sub, group)
    return group - group % sub, sub


def _walk_kernel(l_ref, pt_ref, sp_ref, first_ref, live_ref, q_ref, kv_ref,
                 o_ref, tile, sem, walked, m_scr, l_scr, acc_scr, *,
                 page_size, group, sub, sm_scale, window, groups):
    """One decode row of the walk: all KV heads of the row against the
    row's OWN pages, slots ``first_ref[s]`` on of its table,
    ``live_ref[s]`` of them, ``group`` to a tile.  The pool stays in HBM;
    a live page is ONE copy (K and V of every head) to its place in a
    tile, laid out ``[2, K, sub * page, D]`` a chunk so that a chunk's K
    and V planes are read as they lie, and the tiles two places ahead in
    the walk (the row's next ones, then the next rows' first) are in
    flight under this tile's matmuls.  A tile is attended chunk by chunk
    as far as its live pages reach (:func:`_attend_tile`); no step, copy
    or wait exists for a slot outside the row's range.  ``walked`` counts
    the tiles of the rows before, so the ring of three tiles turns on
    across rows (``ops/mla_attention.py::_decode_kernel`` is the same
    walk over one plane)."""
    s = pl.program_id(0)
    chunks = group // sub
    layer = l_ref[0]

    def tiles_of(row):
        return jax.lax.div(live_ref[row] + (group - 1), group)

    def live_in(row, g):
        """Live pages of tile ``g`` of ``row`` (none, or fewer, past its
        range or past the last row)."""
        return jnp.minimum(live_ref[row] - g * group, group)

    def copies(row, g, slot, wait):
        """Start (or wait for) the copies of the live pages of tile ``g``
        of ``row``: none past the row's range, none for a row past the
        last.  A wait only needs a copy of the same size."""
        base = 0 if wait else first_ref[row] + g * group

        def one(i, carry):
            page = 0 if wait else pt_ref[row, base + i]
            copy = pltpu.make_async_copy(
                kv_ref.at[layer, page],
                tile.at[slot * chunks + jax.lax.div(i, sub), :, :,
                        pl.ds(pl.multiple_of(jax.lax.rem(i, sub) * page_size,
                                             page_size), page_size)],
                sem.at[slot])
            copy.wait() if wait else copy.start()
            return carry

        return jax.lax.fori_loop(0, live_in(row, g), one, 0)

    tiles, tiles_next = tiles_of(s), tiles_of(s + 1)

    def ahead(g):
        """The place in the walk two tiles after tile ``g`` of this row:
        a row past the last has no tile, every other at least one."""
        over = g + (TILES_IN_FLIGHT - 1) - tiles
        here, next_row = over < 0, over < tiles_next
        pick = jax.lax.select       # (``jnp.where`` is a jit of its own:
        # a kernel in 33-59 step programs pays for every trace of it)
        return (pick(here, s, pick(next_row, s + 1, s + 2)),
                pick(here, over + tiles,
                     pick(next_row, over, jnp.zeros_like(over))))

    @pl.when(s == 0)
    def _first():
        # columns of a tile that no copy has reached yet are multiplied by
        # probabilities of exactly 0: they must hold numbers
        tile[...] = jnp.zeros_like(tile)
        walked[0] = 0
        # the walk's first two tiles: ``ahead(-2)`` is tile 0 of row 0
        jax.lax.fori_loop(0, TILES_IN_FLIGHT - 1, lambda k, carry: copies(
            *ahead(k - (TILES_IN_FLIGHT - 1)), k, wait=False), 0)

    start = sp_ref[s]
    first_tile = walked[0]
    column = first_ref[s] * page_size       # of the row's first live slot
    m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def walk(g, carry):
        slot = jax.lax.rem(first_tile + g, TILES_IN_FLIGHT)
        copies(*ahead(g), jax.lax.rem(slot + (TILES_IN_FLIGHT - 1),
                                      TILES_IN_FLIGHT), wait=False)
        copies(s, g, slot, wait=True)

        def attend(c, carry):
            at = slot * chunks + c
            _attend_tile(q_ref[...], lambda i: tile[at, i], lambda: (
                column + (g * group + c * sub) * page_size), start,
                m_scr, l_scr, acc_scr, sm_scale=sm_scale, window=window,
                groups=groups)
            return carry

        # a row's last tile: only the chunks its live pages reach
        jax.lax.fori_loop(0, jax.lax.div(live_in(s, g) + (sub - 1), sub),
                          attend, 0)
        return carry

    jax.lax.fori_loop(0, tiles, walk, 0)
    walked[0] = first_tile + tiles
    o_ref[...] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                  ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "group", "sub", "sm_scale", "window", "interpret", "name"))
def paged_walk_attention(q: jax.Array, kv: jax.Array, layer,
                         page_table: jax.Array, start_pos: jax.Array, *,
                         group: int, sub: int, sm_scale: float,
                         window: Optional[int] = None,
                         interpret: bool = False,
                         name: str = "paged_attention") -> jax.Array:
    """The decode step of :func:`paged_decode_attention` as a walk: q
    ``[S, 1, H, D]`` (one new token a row) over the pool ``[L, P+1, 2, K,
    page, D]``.  The grid runs over ROWS, in order; the page table, the
    contexts and each row's live range ride scalar prefetch, the pool is
    left in HBM and the kernel copies each row's live pages itself
    (:func:`_walk_kernel`), so a row costs what its own context costs
    whatever the page bucket ``P`` of its step, which is only the table's
    width here.  A row's live pages are slots ``first .. last`` of ITS
    table, from positions alone: ``last = start_pos // page``, ``first``
    the page its window starts in (0 without one; a window group's
    rebased table and a full table that holds nulls under the window read
    the same), never from a page id.  Jitted, so that the kernel's body
    is traced once a shape and not once a layer of every step program
    that has the shape."""
    S, _, H, D = q.shape
    K, page_size = kv.shape[3:5]
    G = H // K
    chunks = group // sub
    start_pos = start_pos.astype(jnp.int32)
    zero = jnp.zeros_like(start_pos)

    def page_of(pos, top):      # (``lax``: ``jnp`` forms are jits of their
        # own, traced in every step program; a negative position is slot 0)
        return jax.lax.clamp(zero, jax.lax.div(pos, zero + page_size), top)

    last = page_of(start_pos, zero + (page_table.shape[1] - 1))
    first = zero if window is None else page_of(start_pos + (1 - window), last)
    # no pages for the rows the copies look ahead to past the last one
    first, live = (jax.lax.pad(x, jnp.int32(0),
                               [(0, TILES_IN_FLIGHT - 1, 0)])
                   for x in (first, last - first + 1))
    row = pl.BlockSpec((None, K, G, D), lambda s, *_: (s, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_walk_kernel, page_size=page_size, group=group,
                          sub=sub, sm_scale=sm_scale, window=window,
                          groups=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(S,),
            in_specs=[row, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row,
            scratch_shapes=[
                pltpu.VMEM((TILES_IN_FLIGHT * chunks, 2, K, sub * page_size,
                            D), kv.dtype),
                pltpu.SemaphoreType.DMA((TILES_IN_FLIGHT,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((K, G, 1), jnp.float32),
                pltpu.VMEM((K, G, 1), jnp.float32),
                pltpu.VMEM((K, G, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((S, K, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        # the grid form's names: a trace's shares and rooflines match them
        name=name + "_decode",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), page_table.astype(jnp.int32),
      start_pos, first, live, q.reshape(S, K, G, D), kv)
    return out.reshape(S, 1, H, D)


def gather_last(x: jax.Array, q_lens: jax.Array) -> jax.Array:
    """Last valid token's hidden state per slot: [S, Q, E] -> [S, E]
    (reference ``logits_gather`` kernel)."""
    idx = jnp.maximum(q_lens - 1, 0)
    return jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]


def attention_reference(q, k_ctx, v_ctx, start_pos, q_lens,
                        window=None) -> jax.Array:
    """Dense ground-truth for tests: same masking over an unpaged
    [S, C, K, D] context."""
    S, Q, H, D = q.shape
    K = k_ctx.shape[2]
    qg = q.reshape(S, Q, K, H // K, D)
    scores = jnp.einsum("sqkgd,sckd->skgqc", qg, k_ctx).astype(jnp.float32)
    scores = scores / np.sqrt(D)
    C = k_ctx.shape[1]
    pos = token_positions(start_pos, Q)
    mask = jnp.arange(C)[None, None, :] <= pos[:, :, None]
    if window is not None:
        mask &= jnp.arange(C)[None, None, :] > pos[:, :, None] - window
    scores = jnp.where(mask[:, None, None, :, :], scores, MASK_VALUE)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_ctx.dtype)
    out = jnp.einsum("skgqc,sckd->sqkgd", probs, v_ctx)
    return out.reshape(S, Q, H, D)


def paged_context(kv: jax.Array, layer, page_table: jax.Array,
                  dtype=jnp.float32) -> Tuple[jax.Array, jax.Array]:
    """Token-major K and V contexts ``[S, C, K, D]`` of one layer of the
    pool: ONE gather of ``pool[layer, page_table]`` (``[S, P, 2, K,
    page, D]``; the layer is an index of the gather, not a slice taken
    first).  A quantized pool dequantizes to ``dtype``."""
    layer = jnp.asarray(layer, jnp.int32)     # an index of the gather
    if isinstance(kv, KVPages):
        # [S, P, 2, K, page] scales broadcast over D
        pages = dequantize_kv_blocks(kv.payload[layer, page_table],
                                     kv.scale[layer, page_table], dtype)
    else:
        pages = kv[layer, page_table]
    return _flatten_context(pages)
