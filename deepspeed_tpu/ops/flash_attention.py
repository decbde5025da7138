"""Flash attention — Pallas TPU kernel.

TPU-native replacement for the reference's fused attention kernels
(``csrc/transformer/`` softmax/attention CUDA kernels and the
``blocked_flash`` FastGen path, ``inference/v2/kernels/ragged_ops/``):
blockwise softmax with running max/denominator so the S x S score matrix
never materializes in HBM.

Layout: q, k, v are [B, H, S, D] (callers fold GQA groups into H).
Causal masking skips fully-masked k-blocks.  Backward is the standard
two-kernel flash backward (dkv sweep over q-blocks, dq sweep over
k-blocks) with the delta = rowsum(dO * O) precomputation.

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


# ---------------------------------------------------------------------------
# reference (and CPU fallback)
# ---------------------------------------------------------------------------

def mha_reference(q, k, v, causal: bool = True, sm_scale: Optional[float] = None,
                  window: Optional[int] = None):
    """[B,H,S,D] attention in fp32 softmax — semantics ground truth.
    ``window``: sliding-window size incl. self (HF Mistral semantics:
    position t attends to (t - window, t])."""
    d = q.shape[-1]
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
                block_k, seq_k, window):
    q_idx = pl.program_id(2)
    block_q = q_ref.shape[0]
    d = q_ref.shape[1]
    q = q_ref[:]  # [block_q, d]

    num_k = pl.cdiv(seq_k, block_k)
    if causal:
        # highest k block that intersects this q block's diagonal
        num_k = jnp.minimum(num_k, (q_idx + 1) * block_q // block_k
                            + ((q_idx + 1) * block_q % block_k != 0))
    k_lo = jnp.int32(0)
    if window is not None:
        # first k block any row of this q block can see: row 0's window
        # start is q_idx*block_q - window + 1 (blocks below it are fully
        # masked and skipped — the flash win for long sliding-window seqs)
        k_lo = jnp.maximum(
            jnp.int32(0), (q_idx * block_q - window + 1) // block_k)

    m0 = jnp.full((block_q, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)

    def body(ki, carry):
        m_prev, l_prev, acc = carry
        k = k_ref[pl.ds(ki * block_k, block_k), :]  # [block_k, d]
        v = v_ref[pl.ds(ki * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [bq, bk]
        if causal or window is not None:
            s = jnp.where(_band_keep(q_idx * block_q, ki * block_k, block_q,
                                     block_k, causal, window),
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

    m, l, acc = jax.lax.fori_loop(k_lo, num_k, body, (m0, l0, acc0))
    l = jnp.maximum(l, 1e-30)
    o_ref[:] = (acc / l).astype(o_ref.dtype)
    lse_ref[:] = (m + jnp.log(l)).T  # [1, block_q] lane-major row


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret, window):
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    block_q = min(block_q, s_q)
    block_k = min(block_k, s_k)
    grid = (b, h, pl.cdiv(s_q, block_q))

    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                               block_k=block_k, seq_k=s_k, window=window)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, s_k, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, s_k, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
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
                    dk_ref, dv_ref, *, sm_scale, causal, block_q, seq_q,
                    window):
    k_idx = pl.program_id(2)
    block_k = k_ref.shape[0]
    d = k_ref.shape[1]
    k = k_ref[:]
    v = v_ref[:]

    num_q = pl.cdiv(seq_q, block_q)
    q0 = jnp.int32(0)
    if causal:
        q0 = (k_idx * block_k) // block_q  # first q block on/under diagonal
    if window is not None:
        # last q that sees this k block: k_pos_max + window - 1
        q_hi_pos = k_idx * block_k + block_k - 1 + window - 1
        num_q = jnp.minimum(num_q, q_hi_pos // block_q + 1)

    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d), jnp.float32)

    def body(qi, carry):
        # k-major (transposed) score tile [bk, bq]: the per-query lse /
        # delta rows broadcast along sublanes and every matmul below is
        # a plain (non-transposed-lhs) MXU product
        dk, dv = carry
        q = q_ref[pl.ds(qi * block_q, block_q), :]
        do = do_ref[pl.ds(qi * block_q, block_q), :]
        lse = lse_ref[:, pl.ds(qi * block_q, block_q)]      # [1, bq]
        delta = delta_ref[:, pl.ds(qi * block_q, block_q)]
        st = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * sm_scale
        if causal or window is not None:
            st = jnp.where(_band_keep(qi * block_q, k_idx * block_k, block_q,
                                      block_k, causal, window, k_major=True),
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

    dk, dv = jax.lax.fori_loop(q0, num_q, body, (dk0, dv0))
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, *, sm_scale, causal, block_k, seq_k, window):
    q_idx = pl.program_id(2)
    block_q = q_ref.shape[0]
    d = q_ref.shape[1]
    q = q_ref[:]
    do = do_ref[:]
    lse = lse_ref[:].T      # [1, bq] row -> [bq, 1] column, once per block
    delta = delta_ref[:].T

    num_k = pl.cdiv(seq_k, block_k)
    if causal:
        num_k = jnp.minimum(num_k, (q_idx + 1) * block_q // block_k
                            + ((q_idx + 1) * block_q % block_k != 0))
    k_lo = jnp.int32(0)
    if window is not None:
        k_lo = jnp.maximum(
            jnp.int32(0), (q_idx * block_q - window + 1) // block_k)

    dq0 = jnp.zeros((block_q, d), jnp.float32)

    def body(ki, dq):
        k = k_ref[pl.ds(ki * block_k, block_k), :]
        v = v_ref[pl.ds(ki * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal or window is not None:
            s = jnp.where(_band_keep(q_idx * block_q, ki * block_k, block_q,
                                     block_k, causal, window),
                          s, DEFAULT_MASK_VALUE)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        return dq + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(k_lo, num_k, body, dq0)
    dq_ref[:] = dq.astype(dq_ref.dtype)


def _flash_bwd(res, g, sm_scale, causal, block_q, block_k, interpret, window):
    q, k, v, out, lse = res
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    block_q = min(block_q, s_q)
    block_k = min(block_k, s_k)
    # same lane-major [B, H, 1, S] row layout as lse
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, :, None, :]

    dkv_kernel = functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale,
                                   causal=causal, block_q=block_q, seq_q=s_q,
                                   window=window)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b, h, pl.cdiv(s_k, block_k)),
        in_specs=[
            pl.BlockSpec((None, None, s_q, d), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, block_k, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((None, None, block_k, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((None, None, s_q, d), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, 1, s_q), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, 1, s_q), lambda bi, hi, ki: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_k, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((None, None, block_k, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        name="flash_attention_bwd_dkv",
        interpret=interpret,
    )(q, k, v, g, lse, delta)

    dq_kernel = functools.partial(_bwd_dq_kernel, sm_scale=sm_scale,
                                  causal=causal, block_k=block_k, seq_k=s_k,
                                  window=window)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, h, pl.cdiv(s_q, block_q)),
        in_specs=[
            pl.BlockSpec((None, None, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, s_k, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, s_k, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
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
    """Blockwise attention, [B,H,S,D].  GQA callers fold groups into H or
    repeat kv.  ``interpret=None`` (the default) compiles the kernel on
    a TPU and computes the jnp reference on CPU; an explicit bool always
    runs the kernel (True = Pallas interpreter, the tests' parity mode)."""
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
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
