"""Flash attention — Pallas TPU kernel.

TPU-native replacement for the reference's fused attention kernels
(``csrc/transformer/`` softmax/attention CUDA kernels and the
``blocked_flash`` FastGen path, ``inference/v2/kernels/ragged_ops/``):
blockwise softmax with running max/denominator so the S x S score matrix
never materializes in HBM.

Layout: q is [B, H, S, D], k and v are [B, K, S, D] at their own head
count (H % K == 0): the kernels index a query head's K/V block by
``head // groups`` and sum dK / dV over a group in float32, so no caller
repeats K and V.  Causal / sliding-window masking skips fully-masked
blocks and builds the mask only where the band's edge crosses a block: a
row of up to ``UNROLL_BLOCKS`` blocks has its pairs written out, a longer
one walks in one loop (``_each_block``, ``_walk``).  Backward is the
two-kernel flash backward (dkv sweep over the q blocks of a k block and
over the group's query heads, dq sweep over k blocks) with the
``delta = rowsum(dO * O)`` precomputation.

On a TPU the kernel is the only path (a lowering error propagates);
on CPU (the tests) the public entry point selects the jnp reference
explicitly — see :func:`~deepspeed_tpu.accelerator.on_tpu`.

The per-row log-sum-exp (and the backward's ``delta``) travel between
the kernels as lane-major ``[B, H, 1, S]`` rows: the TPU lowering needs
the last two block dims tile-aligned, which a ``[B, H, S]`` vector block
is not, and a trailing unit dim would pad 128x in HBM.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..accelerator import on_tpu

DEFAULT_MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)


def _band_keep(q_idx_base, k_idx_base, block_q, block_k, causal, window,
               k_major=False):
    """Block-local keep mask for banded (causal / sliding-window)
    attention: q attends k iff q_pos >= k_pos (causal) and
    q_pos - k_pos < window (Mistral (t-window, t] semantics).  Shared by
    all three kernels so the band definition cannot diverge.
    ``k_major`` builds the mask for a transposed ``[block_k, block_q]``
    score tile (the dkv kernel)."""
    shape = (block_k, block_q) if k_major else (block_q, block_k)
    q_pos = q_idx_base + jax.lax.broadcasted_iota(
        jnp.int32, shape, 1 if k_major else 0)
    k_pos = k_idx_base + jax.lax.broadcasted_iota(
        jnp.int32, shape, 0 if k_major else 1)
    keep = jnp.ones(shape, bool)
    if causal:
        keep &= q_pos >= k_pos
    if window is not None:
        keep &= (q_pos - k_pos) < window
    return keep


def _band_inside(q_idx_base, k_idx_base, block_q, block_k, causal, window):
    """Whether ``_band_keep`` of the block at these (static) bases is all
    true: its first row may see its last column, and its last row still
    has its first column in the window."""
    inside = True
    if causal:
        inside &= q_idx_base >= k_idx_base + block_k - 1
    if window is not None:
        inside &= q_idx_base + block_q - 1 - k_idx_base < window
    return inside


#: A walk of at most this many blocks is WRITTEN OUT (``_each_block``): on the
#: chip a loop's body does not overlap what stands around it, and the same
#: pairs in straight line run the forward a third faster (PERF.md, PR 51);
#: 4 blocks are at most 10 causal pairs a kernel, and more is program text
#: that every step program which carries the kernel compiles.
UNROLL_BLOCKS = 4


def _each_block(idx, blocks, emit):
    """``emit(idx)`` for a kernel whose grid axis of ``blocks`` blocks is at
    ``idx``: up to UNROLL_BLOCKS, ``emit(i)`` with ``i`` a Python int under
    ``idx == i``, so that the block's walk has static bounds and
    ``_walk`` writes it out; beyond, ``emit`` of the traced index."""
    if blocks == 1:
        emit(0)
    elif blocks <= UNROLL_BLOCKS:
        for i in range(blocks):
            pl.when(idx == i)(functools.partial(emit, i))
    else:
        emit(idx)


def _k_range(q_idx, block_q, block_k, seq_k, causal, window):
    """The k blocks ``lo .. hi`` that some row of q block ``q_idx`` may see
    (Python ints for a static index): up to the block the diagonal
    crosses, from the block in which row 0's window starts (blocks under it
    are fully masked and skipped: the flash win for long sliding-window
    rows)."""
    static = isinstance(q_idx, int)
    lo, hi = (0 if static else jnp.int32(0)), pl.cdiv(seq_k, block_k)
    if causal:
        rows = (q_idx + 1) * block_q
        hi = (min if static else jnp.minimum)(
            hi, rows // block_k + (rows % block_k != 0))
    if window is not None:
        lo = (max if static else jnp.maximum)(
            lo, (q_idx * block_q - window + 1) // block_k)
    return lo, hi


def _q_range(k_idx, block_q, block_k, seq_q, causal, window):
    """``_k_range`` for the q blocks that see k block ``k_idx``: from the
    first on or under its diagonal to the one that holds the last row with
    its first column in the window (k_pos_max + window - 1)."""
    static = isinstance(k_idx, int)
    lo, hi = (0 if static else jnp.int32(0)), pl.cdiv(seq_q, block_q)
    if causal:
        lo = (k_idx * block_k) // block_q
    if window is not None:
        last = k_idx * block_k + block_k - 1 + window - 1
        hi = (min if static else jnp.minimum)(hi, last // block_q + 1)
    return lo, hi


def _walk(body, lo, hi, carry, inside, traced):
    """``body(masked, i, carry)`` over the blocks ``lo .. hi`` in rising
    order, the mask built only where the band's edge crosses a block.

    Static bounds (``_each_block``): written out, block ``i`` masked unless
    ``inside(i)``.  Traced bounds follow ``traced`` (``_traced_masks``):
    "first" / "last": that block is the ONE of the walk that the diagonal
    crosses; the others lie wholly inside the band and run bare in one
    loop, and the diagonal's block is masked in straight line outside it
    (a loop of its own costs more than the masks it saves).  A bool: one
    loop that masks every block, or none."""
    if isinstance(lo, int) and isinstance(hi, int):
        for i in range(lo, hi):
            carry = body(not inside(i), i, carry)
        return carry
    bare = functools.partial(body, False)
    if traced == "first":
        return jax.lax.fori_loop(lo + 1, hi, bare, body(True, lo, carry))
    if traced == "last":
        return body(True, hi - 1, jax.lax.fori_loop(lo, hi - 1, bare, carry))
    return jax.lax.fori_loop(lo, hi, functools.partial(body, traced), carry)


def _traced_masks(side, block_q, block_k, causal, window):
    """``_walk``'s ``traced`` for a walk whose diagonal block comes ``side``
    ("first" / "last"): ``side`` where the band is the causal one alone and
    the blocks are square, else whether there is a band at all (a window
    that binds, unlike blocks: every block masked, as before PR 51)."""
    if causal and window is None and block_q == block_k:
        return side
    return causal or window is not None


# ---------------------------------------------------------------------------
# reference (and CPU fallback)
# ---------------------------------------------------------------------------

def mha_reference(q, k, v, causal: bool = True, sm_scale: Optional[float] = None,
                  window: Optional[int] = None):
    """[B,H,S,D] attention in fp32 softmax — semantics ground truth.
    ``window``: sliding-window size incl. self (HF Mistral semantics:
    position t attends to (t - window, t]).  K and V of fewer heads are
    repeated to the query heads (GQA: head h reads K/V head h // groups)."""
    d = q.shape[-1]
    groups = q.shape[1] // k.shape[1]
    if groups > 1:
        k, v = (jnp.repeat(x, groups, axis=1) for x in (k, v))
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(d)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    s_q, s_k = scores.shape[-2:]
    mask = jnp.ones((s_q, s_k), bool)
    if causal:
        mask &= jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q)
    if window is not None:
        q_pos = jnp.arange(s_q)[:, None] + (s_k - s_q)
        k_pos = jnp.arange(s_k)[None, :]
        mask &= (q_pos - k_pos) < window
    if causal or window is not None:
        scores = jnp.where(mask, scores, DEFAULT_MASK_VALUE)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale, causal,
                block_k, seq_k, blocks_q, window):
    block_q = q_ref.shape[0]
    d = q_ref.shape[1]
    q = q_ref[:]  # [block_q, d]
    band = (block_q, block_k, causal, window)

    def block(q_idx):
        def body(masked, ki, carry):
            m_prev, l_prev, acc = carry
            k = k_ref[pl.ds(ki * block_k, block_k), :]  # [block_k, d]
            v = v_ref[pl.ds(ki * block_k, block_k), :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale  # [bq, bk]
            if masked:
                s = jnp.where(_band_keep(q_idx * block_q, ki * block_k, *band),
                              s, DEFAULT_MASK_VALUE)
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc

        m0 = jnp.full((block_q, 1), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((block_q, 1), jnp.float32)
        acc0 = jnp.zeros((block_q, d), jnp.float32)
        m, l, acc = _walk(
            body, *_k_range(q_idx, block_q, block_k, seq_k, causal, window),
            (m0, l0, acc0),
            lambda ki: _band_inside(q_idx * block_q, ki * block_k, *band),
            _traced_masks("last", *band))
        l = jnp.maximum(l, 1e-30)
        o_ref[:] = (acc / l).astype(o_ref.dtype)
        lse_ref[:] = (m + jnp.log(l)).T  # [1, block_q] lane-major row

    _each_block(pl.program_id(2), blocks_q, block)


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret, window):
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    groups = h // k.shape[1]
    block_q = min(block_q, s_q)
    block_k = min(block_k, s_k)
    grid = (b, h, pl.cdiv(s_q, block_q))

    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                               block_k=block_k, seq_k=s_k, blocks_q=grid[2],
                               window=window)
    # a query head's K/V block is its group's: consecutive heads of a group
    # name the block the buffer holds, which is not fetched again
    kv_spec = pl.BlockSpec((None, None, s_k, d),
                           lambda bi, hi, qi: (bi, hi // groups, 0, 0))
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            kv_spec, kv_spec,
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, 1, block_q),
                         lambda bi, hi, qi: (bi, hi, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, s_q), jnp.float32),
        ],
        name="flash_attention_fwd",
        interpret=interpret,
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *group_sum, sm_scale, causal, block_q,
                    seq_q, blocks_k, window, groups):
    """Grid (batch, KV head, k block, group member): dK and dV of a k block
    summed over the q blocks on or under its diagonal and, the blocks
    resident across the innermost axis, over the group's query heads, in
    float32."""
    member = pl.program_id(3)
    block_k = k_ref.shape[0]
    d = k_ref.shape[1]
    k = k_ref[:]
    v = v_ref[:]
    band = (block_q, block_k, causal, window)

    def block(k_idx):
        def body(masked, qi, carry):
            # k-major (transposed) score tile [bk, bq]: the per-query lse /
            # delta rows broadcast along sublanes and every matmul below is
            # a plain (non-transposed-lhs) MXU product
            dk, dv = carry
            rows = pl.ds(qi * block_q, block_q)
            q = q_ref[rows, :]
            do = do_ref[rows, :]
            lse = lse_ref[:, rows]      # [1, bq]
            delta = delta_ref[:, rows]
            st = jax.lax.dot_general(
                k, q, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if masked:
                st = jnp.where(_band_keep(qi * block_q, k_idx * block_k,
                                          *band, k_major=True),
                               st, DEFAULT_MASK_VALUE)
            pt = jnp.exp(st - lse)  # [bk, bq]
            dv = dv + jax.lax.dot_general(
                pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dpt = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32)
            dst = pt * (dpt - delta) * sm_scale
            dk = dk + jax.lax.dot_general(
                dst.astype(q.dtype), q, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return dk, dv

        dk0 = jnp.zeros((block_k, d), jnp.float32)
        dv0 = jnp.zeros((block_k, d), jnp.float32)
        dk, dv = _walk(
            body, *_q_range(k_idx, block_q, block_k, seq_q, causal, window),
            (dk0, dv0),
            lambda qi: _band_inside(qi * block_q, k_idx * block_k, *band),
            _traced_masks("first", *band))
        if groups == 1:
            dk_ref[:] = dk.astype(dk_ref.dtype)
            dv_ref[:] = dv.astype(dv_ref.dtype)
            return
        dk_acc, dv_acc = group_sum      # heads in rising order

        @pl.when(member == 0)
        def _():
            dk_acc[:] = dk
            dv_acc[:] = dv

        @pl.when(member > 0)
        def _():
            dk_acc[:] += dk
            dv_acc[:] += dv

        @pl.when(member == groups - 1)
        def _():
            dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
            dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)

    _each_block(pl.program_id(2), blocks_k, block)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, *, sm_scale, causal, block_k, seq_k, blocks_q,
                   window):
    block_q = q_ref.shape[0]
    d = q_ref.shape[1]
    q = q_ref[:]
    do = do_ref[:]
    lse = lse_ref[:].T      # [1, bq] row -> [bq, 1] column, once per block
    delta = delta_ref[:].T
    band = (block_q, block_k, causal, window)

    def block(q_idx):
        def body(masked, ki, dq):
            k = k_ref[pl.ds(ki * block_k, block_k), :]
            v = v_ref[pl.ds(ki * block_k, block_k), :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if masked:
                s = jnp.where(_band_keep(q_idx * block_q, ki * block_k, *band),
                              s, DEFAULT_MASK_VALUE)
            p = jnp.exp(s - lse)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * sm_scale
            return dq + jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        dq = _walk(
            body, *_k_range(q_idx, block_q, block_k, seq_k, causal, window),
            jnp.zeros((block_q, d), jnp.float32),
            lambda ki: _band_inside(q_idx * block_q, ki * block_k, *band),
            _traced_masks("last", *band))
        dq_ref[:] = dq.astype(dq_ref.dtype)

    _each_block(pl.program_id(2), blocks_q, block)


def _flash_bwd(res, g, sm_scale, causal, block_q, block_k, interpret, window):
    q, k, v, out, lse = res
    b, h, s_q, d = q.shape
    kv_heads, s_k = k.shape[1:3]
    groups = h // kv_heads
    block_q = min(block_q, s_q)
    block_k = min(block_k, s_k)
    blocks_q, blocks_k = pl.cdiv(s_q, block_q), pl.cdiv(s_k, block_k)
    # same lane-major [B, H, 1, S] row layout as lse
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, :, None, :]

    def head(bi, ki, gi, mi):
        return (bi, ki * groups + mi, 0, 0)

    whole_q = pl.BlockSpec((None, None, s_q, d), head)
    row_q = pl.BlockSpec((None, None, 1, s_q), head)
    block_kv = pl.BlockSpec((None, None, block_k, d),
                            lambda bi, ki, gi, mi: (bi, ki, gi, 0))
    dkv_kernel = functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale,
                                   causal=causal, block_q=block_q, seq_q=s_q,
                                   blocks_k=blocks_k, window=window,
                                   groups=groups)
    # the member axis innermost: dK / dV blocks stay resident across it and
    # a long row needs no whole [S, d] accumulators
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b, kv_heads, blocks_k, groups),
        in_specs=[whole_q, block_kv, block_kv, whole_q, row_q, row_q],
        out_specs=[block_kv, block_kv],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32)] * 2
        if groups > 1 else [],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary", "arbitrary")),
        name="flash_attention_bwd_dkv",
        interpret=interpret,
    )(q, k, v, g, lse, delta)

    dq_kernel = functools.partial(_bwd_dq_kernel, sm_scale=sm_scale,
                                  causal=causal, block_k=block_k, seq_k=s_k,
                                  blocks_q=blocks_q, window=window)
    kv_spec = pl.BlockSpec((None, None, s_k, d),
                           lambda bi, hi, qi: (bi, hi // groups, 0, 0))
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, h, blocks_q),
        in_specs=[
            pl.BlockSpec((None, None, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            kv_spec, kv_spec,
            pl.BlockSpec((None, None, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, 1, block_q), lambda bi, hi, qi: (bi, hi, 0, qi)),
            pl.BlockSpec((None, None, 1, block_q), lambda bi, hi, qi: (bi, hi, 0, qi)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, d),
                               lambda bi, hi, qi: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        name="flash_attention_bwd_dq",
        interpret=interpret,
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                     window):
    out, _ = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                        window)
    return out


def _flash_attention_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                         window):
    out, lse = _named_residuals(*_flash_fwd(q, k, v, sm_scale, causal, block_q,
                                            block_k, interpret, window))
    return out, (q, k, v, out, lse)


def _flash_attention_bwd(sm_scale, causal, block_q, block_k, interpret, window,
                         res, g):
    return _flash_bwd(res, g, sm_scale, causal, block_q, block_k, interpret,
                      window)


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def _fit_block(block: int, seq: int) -> int:
    """Largest lane-aligned (multiple of 128) block <= ``block`` that
    divides ``seq``, else the whole sequence as one block.  The kernels
    slice whole-sequence K/V (forward, dq) and Q/lse rows (dkv) by block
    index, so a ragged last block would read out of bounds."""
    if seq <= block:
        return seq
    for b in range(block - block % 128, 0, -128):
        if seq % b == 0:
            return b
    return seq


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: int = 512,
                    block_k: int = 512,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None) -> jax.Array:
    """Blockwise attention, q [B,H,S,D], k and v [B,K,S,D] with H % K == 0
    (GQA: query head h attends K/V head h // (H // K)).  ``interpret=None``
    (the default) compiles the kernel on a TPU and computes the jnp
    reference on CPU; an explicit bool always runs the kernel (True =
    Pallas interpreter, the tests' parity mode)."""
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    if causal and window is not None and window >= k.shape[2]:
        window = None       # q - k < seq <= window: it cannot bind
    if interpret is None:
        if not on_tpu():
            return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale,
                                 window=window)
        interpret = False
    return _flash_attention(q, k, v, sm_scale, causal,
                            _fit_block(block_q, q.shape[2]),
                            _fit_block(block_k, k.shape[2]),
                            interpret, window)


# ---------------------------------------------------------------------------
# what a jax.checkpoint around the caller may keep
# ---------------------------------------------------------------------------
# (Below the entry point on purpose: serving's step programs carry this
# file's line numbers in their Mosaic kernels, so lines added above
# ``flash_attention`` would start one serving process cold.)

from jax.ad_checkpoint import checkpoint_name  # noqa: E402

#: The forward kernel's two outputs as the backward kernels take them,
#: ``out`` [B,H,S,D] and ``lse`` [B,H,1,S].  A remat policy that saves both
#: names (``models/transformer.py::resolve_remat_policy``) leaves the
#: recomputed forward without the kernel: a ``pallas_call`` is dropped whole
#: or not at all, so a policy that keeps ``out`` alone, or ``out`` in another
#: layout, runs it a second time.
RESIDUAL_NAMES = ("flash_out", "flash_lse")


def _named_residuals(out, lse):
    return tuple(map(checkpoint_name, (out, lse), RESIDUAL_NAMES))
