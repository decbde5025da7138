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
                           runs the Pallas kernel ``mla_attention_decode``,
                           a grid over ROWS: inside, a loop over the row's
                           own live pages, 8 to a ``[512, 640]`` tile,
                           copied from the pool in HBM page by page to
                           their offsets of the tile, three tiles in VMEM
                           (the copies run two tiles ahead, across rows);
                           one tile serves all heads (a ``[H, 640] x
                           [640, 512]`` and a ``[H, 512] x [512, 512]``
                           matmul), a row's last tile only to half its
                           columns where its live pages end there.  No
                           step, copy or wait exists for a page slot past
                           the context: a row costs its context, whatever
                           the page bucket.  Q > 1 with history (a continued
                           chunk, a speculative row) takes the ``jnp``
                           gather.
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

#: pages one tile of a decode row's walk holds (8 x 64 tokens): a page a
#: tile leaves the tile's fixed cost larger than its work
PAGES_PER_STEP = 8

#: tiles of the walk in VMEM at once: the one under the matmuls and two
#: being copied.  Contexts differ row by row, so with one tile ahead a
#: short tile cannot cover a long one's copy (0.66 -> 0.62 ms a call).
#: The kernel's look-ahead (``ahead``) is written for these two
TILES_IN_FLIGHT = 3

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


def _lanes(x, width):
    """A lane-replicated ``[H, 128]`` statistic over ``width`` columns:
    whole lane tiles are repeated, any other width is broadcast from the
    first column."""
    if width % LANES == 0:
        return pltpu.repeat(x, width // LANES, axis=1)
    return x[:, :1]


def _decode_kernel(l_ref, pt_ref, sp_ref, live_ref, q_ref, kv_ref, o_ref, tile,
                   sem, walked, m_scr, l_scr, acc_scr, *, page_size, group,
                   rank, sm_scale):
    """One row of the absorbed decode: all heads of the row against the
    row's OWN pages (``live_ref``: how many), ``group`` pages a tile,
    flash-style running max / denominator / sum across the tiles.  The
    pool stays in HBM; a live page is one copy to its row offset of a
    ``[group * page, W]`` tile, and the tiles two places ahead in the
    walk (the row's next ones, then the next rows' first) are in flight
    under this tile's matmuls.  ``walked`` counts the tiles of the rows
    before, so the ring of three tiles turns on across rows."""
    s = pl.program_id(0)
    span = group * page_size
    layer = l_ref[0]

    def tiles_of(row):
        return jax.lax.div(live_ref[row] + (group - 1), group)

    def live_in(row, g):
        """Live pages of tile ``g`` of ``row`` (none, or fewer, past its
        context or past the last row)."""
        return jnp.minimum(live_ref[row] - g * group, group)

    def copies(row, g, slot, wait):
        """Start (or wait for) the copies of the live pages of tile ``g``
        of ``row``: none past the row's context, none for a row past the
        last.  A wait only needs a copy of the same size."""
        def one(i, carry):
            page = 0 if wait else pt_ref[row, g * group + i]
            copy = pltpu.make_async_copy(
                kv_ref.at[layer, page, 0, 0],
                tile.at[slot, pl.ds(pl.multiple_of(i * page_size, page_size),
                                    page_size)],
                sem.at[slot])
            copy.wait() if wait else copy.start()
            return carry

        jax.lax.fori_loop(0, live_in(row, g), one, 0)

    tiles, tiles_next = tiles_of(s), tiles_of(s + 1)

    def ahead(g):
        """The place in the walk two tiles after tile ``g`` of this row:
        a row past the last has no tile, every other at least one."""
        over = g + (TILES_IN_FLIGHT - 1) - tiles
        here, next_row = over < 0, over < tiles_next
        return (jnp.where(here, s, jnp.where(next_row, s + 1, s + 2)),
                jnp.where(here, over + tiles, jnp.where(next_row, over, 0)))

    @pl.when(s == 0)
    def _first():
        # columns of a tile that no copy has reached yet are multiplied by
        # probabilities of exactly 0: they must hold numbers
        tile[...] = jnp.zeros_like(tile)
        walked[0] = 0
        copies(0, 0, 0, wait=False)
        copies(*ahead(-1), 1, wait=False)

    ctx_len = sp_ref[s] + 1
    first = walked[0]
    m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def attend(g, slot, width):
        """The first ``width`` columns of the tile in ``slot``."""
        q = q_ref[...]                                      # [H, W]
        scores = jax.lax.dot_general(
            q, tile[slot, pl.ds(0, width), :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [H, width]
        ctx = g * span + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(ctx < ctx_len, scores, MASK_VALUE)
        m_prev = m_scr[...]                                 # [H, 128]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        pexp = jnp.exp(scores - _lanes(m_new, width))
        alpha = jnp.exp(m_prev - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * alpha + jnp.sum(pexp, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * _lanes(alpha, rank) + jnp.dot(
            pexp.astype(q.dtype), tile[slot, pl.ds(0, width), pl.ds(0, rank)],
            preferred_element_type=jnp.float32)             # [H, rank]

    def walk(g, carry):
        slot = jax.lax.rem(first + g, TILES_IN_FLIGHT)
        copies(*ahead(g), jax.lax.rem(slot + (TILES_IN_FLIGHT - 1),
                                      TILES_IN_FLIGHT), wait=False)
        copies(s, g, slot, wait=True)
        # a tile of no more than half its pages live (a row's last) is
        # multiplied to half its columns; the mask ends the context inside
        half = live_in(s, g) <= group // 2
        pl.when(half)(functools.partial(attend, g, slot, span // 2))
        pl.when(jnp.logical_not(half))(
            functools.partial(attend, g, slot, span))
        return carry

    jax.lax.fori_loop(0, tiles, walk, 0)
    walked[0] = first + tiles
    o_ref[...] = (acc_scr[...] * _lanes(
        1.0 / jnp.maximum(l_scr[...], 1e-30), rank)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "sm_scale", "interpret"))
def mla_decode_attention(q_abs: jax.Array, kv: jax.Array, layer,
                         page_table: jax.Array, start_pos: jax.Array, *,
                         rank: int, sm_scale: float,
                         interpret: bool = False) -> jax.Array:
    """Pallas absorbed decode: q_abs ``[S, H, W]`` (one new token a row),
    pool ``[L, P+1, 1, 1, page, W]``; returns ``[S, H, rank]``.  The
    grid runs over rows; the page ids, the contexts and each row's count
    of live pages ride scalar prefetch, the pool is left in HBM and the
    kernel copies each row's live pages itself (``_decode_kernel``), so
    a row costs what its own context costs whatever the page bucket
    ``P`` of its step.  The rows run in order: a tile in flight belongs
    to a later row.  Jitted, so that the kernel's body is traced once a
    shape and not once a layer stack of every step program that has the
    shape."""
    S, H, W = q_abs.shape
    page_size = kv.shape[4]
    group = PAGES_PER_STEP
    start_pos = start_pos.astype(jnp.int32)
    # no pages for the rows the copies look ahead to past the last one
    live = jnp.pad(start_pos // page_size + 1, (0, TILES_IN_FLIGHT - 1))
    row = pl.BlockSpec((None, H, W), lambda s, l, pt, sp, n: (s, 0, 0))
    out = pl.BlockSpec((None, H, rank), lambda s, l, pt, sp, n: (s, 0, 0))
    return pl.pallas_call(
        functools.partial(_decode_kernel, page_size=page_size, group=group,
                          rank=rank, sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(S,),
            in_specs=[row, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=out,
            scratch_shapes=[
                pltpu.VMEM((TILES_IN_FLIGHT, group * page_size, W), kv.dtype),
                pltpu.SemaphoreType.DMA((TILES_IN_FLIGHT,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((H, LANES), jnp.float32),
                pltpu.VMEM((H, LANES), jnp.float32),
                pltpu.VMEM((H, rank), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((S, H, rank), q_abs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="mla_attention_decode",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      page_table.astype(jnp.int32), start_pos, live, q_abs, kv)


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
