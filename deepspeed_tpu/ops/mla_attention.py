"""Latent (MLA) attention over a paged latent cache.

The cache holds ONE plane per token and layer, ``[c ; k_r]`` after norm
and rope (``kv_lora_rank + qk_rope_head_dim`` values, 512 + 64), padded
with zeros to whole 128-lane tiles (640) — the chip tiles the minor dim
of an array to 128 lanes whatever its logical size, so the padded plane
is what a 576-wide one would occupy anyway, and ``bytes_per_page``
counts it.  The pool keeps the six dims of the K/V pool with one plane
and one "head": ``[L, P+1, 1, 1, page, 640]``, so every host codec
(offload, snapshot, handoff, tier) and the aliased write kernel of
``ops/paged_attention.py`` address it unchanged.

* ``latent_write``       — the in-place page write, kernels
                           ``latent_write_decode`` / ``latent_write_prefill``.
* ``mla_paged_attention`` — the ABSORBED form over the paged planes:
                           ``q_abs = [q_n W_kb^K ; q_r ; 0]`` against the
                           plane gives the score, the plane's first 512
                           values are what the probabilities sum.  Q = 1
                           runs the Pallas kernel ``mla_attention_decode``:
                           one page fetch serves all heads (a
                           ``[H, 640] x [640, pages*64]`` matmul a grid
                           step); Q > 1 with history (a continued chunk,
                           a speculative row) takes the ``jnp`` gather.
* ``mla_fresh_attention`` — the EXPANDED form for a pure prefill, whose
                           context is its own tokens: 192-wide scores,
                           128-wide values, Pallas kernel
                           ``mla_attention_prefill``.

The ``jnp`` forms are the semantics ground truth and the CPU path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..accelerator import on_tpu
from .paged_attention import (MASK_VALUE, KVPages, kv_write_pages,
                              token_positions, write_kv)

LANES = 128

#: pages one decode grid step attends over (8 x 64 tokens): a page a step
#: leaves the step's fixed cost larger than its work
PAGES_PER_STEP = 8

#: longest pure prefill the one-block kernel takes ([Q, Q] float32 scores
#: of one head in VMEM); longer pieces take the ``jnp`` form
MAX_FRESH_Q = 1024


def plane_width(latent_dim: int) -> int:
    """The plane's width in the pool: whole 128-lane tiles."""
    return -(-latent_dim // LANES) * LANES


def latent_write(kv: jax.Array, layer, plane: jax.Array,
                 page_table: jax.Array, start_pos: jax.Array,
                 q_lens: jax.Array, *, use_kernel: Optional[bool] = None,
                 interpret: bool = False) -> jax.Array:
    """Write the new tokens' planes ``[S, Q, W]`` into layer ``layer`` of
    the pool ``[L, P+1, 1, 1, page, W]`` in place (``write_kv``'s
    contract, one plane instead of K and V by head)."""
    if isinstance(kv, KVPages):
        raise ValueError("a latent pool has no int8 page format")
    if use_kernel is None:
        use_kernel = interpret or on_tpu()
    new = plane[:, :, None, :]                              # one "head"
    if use_kernel:
        return kv_write_pages(kv, layer, new, None, page_table, start_pos,
                              q_lens, interpret=interpret,
                              name="latent_write")
    return write_kv(kv, layer, new, None, page_table, start_pos, q_lens,
                    use_kernel=False)


def _decode_kernel(l_ref, pt_ref, sp_ref, q_ref, *refs, page_size, group,
                   rank, sm_scale):
    """One (row, group of pages) grid step of the absorbed decode: all
    heads of the row against ``group`` planes' pages at once, flash-style
    running max / denominator / sum across the groups."""
    pages, (o_ref, m_scr, l_scr, acc_scr) = refs[:group], refs[group:]
    s, j = pl.program_id(0), pl.program_id(1)
    span = group * page_size
    ctx_len = sp_ref[s] + 1

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j * span < ctx_len)
    def _attend():
        q = q_ref[:]                                        # [H, W]
        tile = jnp.concatenate([p[:] for p in pages], axis=0)  # [span, W]
        scores = jax.lax.dot_general(
            q, tile, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [H, span]
        ctx = j * span + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(ctx < ctx_len, scores, MASK_VALUE)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        pexp = jnp.exp(scores - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * alpha + jnp.sum(pexp, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            pexp.astype(q.dtype), tile[:, :rank],
            preferred_element_type=jnp.float32)             # [H, rank]

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[:] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)
                    ).astype(o_ref.dtype)


def mla_decode_attention(q_abs: jax.Array, kv: jax.Array, layer,
                         page_table: jax.Array, start_pos: jax.Array, *,
                         rank: int, sm_scale: float,
                         interpret: bool = False) -> jax.Array:
    """Pallas absorbed decode: q_abs ``[S, H, W]`` (one new token a row),
    pool ``[L, P+1, 1, 1, page, W]``; returns ``[S, H, rank]``.  The
    page ids ride the index maps through scalar prefetch; the pool is
    passed once per page of a group, each with its own index map, so the
    pipeline fetches a group's pages side by side."""
    S, H, W = q_abs.shape
    page_size = kv.shape[4]
    P_pages = page_table.shape[1]
    group = next(g for g in (PAGES_PER_STEP, 4, 2, 1) if P_pages % g == 0)

    def page_spec(g):
        return pl.BlockSpec(
            (None, None, None, None, page_size, W),
            lambda s, j, l, pt, sp: (l[0], pt[s, j * group + g], 0, 0, 0, 0))

    row = pl.BlockSpec((None, H, W), lambda s, j, l, pt, sp: (s, 0, 0))
    out = pl.BlockSpec((None, H, rank), lambda s, j, l, pt, sp: (s, 0, 0))
    return pl.pallas_call(
        functools.partial(_decode_kernel, page_size=page_size, group=group,
                          rank=rank, sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(S, P_pages // group),
            in_specs=[row] + [page_spec(g) for g in range(group)],
            out_specs=out,
            scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, rank), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((S, H, rank), q_abs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="mla_attention_decode",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      page_table.astype(jnp.int32), start_pos.astype(jnp.int32), q_abs,
      *([kv] * group))


def mla_paged_attention(q_abs: jax.Array, kv: jax.Array, layer,
                        page_table: jax.Array, start_pos: jax.Array,
                        q_lens: jax.Array, *, rank: int, sm_scale: float,
                        use_kernel: Optional[bool] = None,
                        interpret: bool = False) -> jax.Array:
    """Absorbed latent attention of ``[S, Q]`` new tokens over their
    paged planes (the new planes already written).

    q_abs : [S, Q, H, W]   ``[q_n W_kb^K ; q_r ; 0]``
    kv    : [L, P+1, 1, 1, page, W]
    Returns [S, Q, H, rank]: the probabilities' sum of the planes' first
    ``rank`` values, which ``W_kb^V`` then takes to the value heads.
    Softmax in float32."""
    S, Q, H, W = q_abs.shape
    if use_kernel is None:
        use_kernel = (interpret or on_tpu()) and Q == 1
    if use_kernel and Q == 1:
        return mla_decode_attention(
            q_abs[:, 0], kv, layer, page_table, start_pos, rank=rank,
            sm_scale=sm_scale, interpret=interpret)[:, None]
    planes = kv[jnp.asarray(layer, jnp.int32), page_table]  # [S,P,1,1,pg,W]
    ctx = planes.reshape(S, -1, W)                          # [S, C, W]
    scores = jnp.einsum("sqhw,scw->shqc", q_abs, ctx,
                        preferred_element_type=jnp.float32) * sm_scale
    pos = token_positions(start_pos, Q)
    mask = jnp.arange(ctx.shape[1], dtype=jnp.int32)[None, None, :] \
        <= pos[:, :, None]                                  # [S, Q, C]
    scores = jnp.where(mask[:, None], scores, MASK_VALUE)
    probs = jax.nn.softmax(scores, axis=-1).astype(q_abs.dtype)
    return jnp.einsum("shqc,scr->sqhr", probs, ctx[..., :rank])


def _fresh_kernel(q_ref, k_ref, v_ref, o_ref, *, sm_scale):
    """A block of heads of one row's pure prefill: whole ``[Q, Q]``
    scores a head, causal, softmax in float32."""
    heads, Q, _ = q_ref.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    for h in range(heads):
        scores = jax.lax.dot_general(
            q_ref[h], k_ref[h], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        scores = jnp.where(col <= row, scores, MASK_VALUE)
        m = jnp.max(scores, axis=1, keepdims=True)
        pexp = jnp.exp(scores - m)
        out = jnp.dot(pexp.astype(v_ref.dtype), v_ref[h],
                      preferred_element_type=jnp.float32)
        o_ref[h] = (out / jnp.sum(pexp, axis=1, keepdims=True)
                    ).astype(o_ref.dtype)


def mla_fresh_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        sm_scale: float, use_kernel: Optional[bool] = None,
                        interpret: bool = False) -> jax.Array:
    """Expanded causal attention of a pure prefill over its own tokens:
    q, k ``[S, Q, H, d_qk]`` (nope and rope parts side by side), v
    ``[S, Q, H, d_v]`` -> ``[S, Q, H, d_v]``.  Positions past a row's
    length are garbage in and out: under the causal mask they reach no
    position that is read."""
    S, Q, H, d_qk = q.shape
    d_v = v.shape[-1]
    if use_kernel is None:
        use_kernel = interpret or on_tpu()
    if not use_kernel or Q > MAX_FRESH_Q or Q % 8:
        scores = jnp.einsum("sqhd,skhd->shqk", q, k,
                            preferred_element_type=jnp.float32) * sm_scale
        causal = jnp.arange(Q)[None, :] <= jnp.arange(Q)[:, None]
        scores = jnp.where(causal, scores, MASK_VALUE)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("shqk,skhd->sqhd", probs, v)
    # whole lanes for the contraction: zeros add nothing to a score
    pad = plane_width(d_qk) - d_qk
    qh, kh = (jnp.pad(t.transpose(0, 2, 1, 3), ((0, 0),) * 3 + ((0, pad),))
              for t in (q, k))
    vh = v.transpose(0, 2, 1, 3)
    heads = next(h for h in (8, 4, 2, 1)
                 if H % h == 0 and h * Q <= MAX_FRESH_Q)

    def block(width):
        return pl.BlockSpec((None, heads, Q, width),
                            lambda s, h: (s, h, 0, 0))

    out = pl.pallas_call(
        functools.partial(_fresh_kernel, sm_scale=sm_scale),
        grid=(S, H // heads),
        in_specs=[block(d_qk + pad), block(d_qk + pad), block(d_v)],
        out_specs=block(d_v),
        out_shape=jax.ShapeDtypeStruct((S, H, Q, d_v), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="mla_attention_prefill",
        interpret=interpret,
    )(qh, kh, vh)
    return out.transpose(0, 2, 1, 3)
