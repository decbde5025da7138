"""A dropless routed layer that holds only some of the experts.

One chip of an expert-parallel group holds ``held`` of a layer's ``E``
routed experts (``first .. first + held - 1``).  The router still scores
every token over all ``E`` experts and picks its ``k`` largest; the
token-expert pairs that fall to experts held here are sorted by expert,
padded per expert to whole row tiles and run through ONE grouped SwiGLU
matmul (the Pallas kernel ``moe_expert_ffn``); pairs of experts held
elsewhere add nothing.  The result is this chip's partial sum — the
shares of all chips, the shared expert counted once, add up to the
uncut layer (``tests/test_pangu_moe.py``).  Nothing here stands in for
the other chips or their exchange.

``moe/gating.py``'s one-hot ``[T, E, C]`` dispatch needs ``C = T`` when
nothing may be dropped: at 256 experts sixteen times the useful work.

Expert weights are stored ``[held, F, e]`` for all three projections
(gate and up out-major, down in-major), so every block the kernel
streams is ``tf`` whole rows of the hidden width: contiguous in HBM.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..accelerator import on_tpu


def route_sigmoid_topk(x2d: jax.Array, w_router: jax.Array, top_k: int,
                       scaling: float, norm_topk: bool = True
                       ) -> Tuple[jax.Array, jax.Array]:
    """Sigmoid scores over ALL experts in float32, the ``top_k`` largest,
    their scores normalised over the chosen ones and scaled:
    ``w_i = scaling * s_i / (sum_{j in I} s_j + 1e-20)``.  No groups, no
    selection bias.  Returns (experts [T, k] int32, weights [T, k] f32).

    The product is taken at HIGHEST precision: a bf16 pass can swap the
    k-th and (k+1)-th expert of a token, which changes its output by a
    whole expert and not by a rounding."""
    scores = jax.nn.sigmoid(jnp.dot(
        x2d.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    top, experts = _largest(scores, top_k)
    if norm_topk:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), top * scaling


#: columns of the expert width one grid step takes.  With 32 or 64 rows a
#: tile the kernel's blocks (rows in and out, the float32 sum, three
#: double-buffered weight slices) stay under 12 MB: inside the chip's
#: default scoped VMEM.  A kernel that asked for more (slices of 256, a
#: 96 MB limit) ran alone and HUNG the chip inside a mixed step program,
#: whose other operations hold VMEM of their own (PERF.md, PR 27).
FF_SLICE = 64


def _largest(scores: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """``jax.lax.top_k`` for a small ``k`` as ``k`` arg-max passes (largest
    first, the lower index of equal scores first, as there): on the chip
    the sort behind ``top_k`` costs 0.4 ms for 256 rows of 256 scores, a
    tenth of a layer (PERF.md, PR 27)."""
    cols = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    vals, idxs = [], []
    for _ in range(k):
        idx = jnp.argmax(scores, axis=-1).astype(jnp.int32)
        vals.append(jnp.max(scores, axis=-1))
        idxs.append(idx)
        scores = jnp.where(cols == idx[..., None], -jnp.inf, scores)
    return jnp.stack(vals, -1), jnp.stack(idxs, -1)


def row_tile(tokens: int, per_expert: float = 0.0) -> int:
    """Rows of one grouped-matmul tile: a decode step's experts see a
    handful of pairs each, a prefill piece's a few dozen.  One tile per
    expert streams that expert's weights once, and every further tile of
    an expert streams them AGAIN: so 32 rows only while they are twice
    ``per_expert``, the pairs an expert sees under an even routing (0:
    the caller does not know how many experts were scored)."""
    return 32 if tokens <= 256 and per_expert <= 16 else 64


def _plan(experts, valid, first: int, held: int, tm: int):
    """Where each token-expert pair goes in the expert-sorted, tile-padded
    row layout.  Returns (token of each padded row [M], destination row
    of each pair [T, k] (M: the pair is not here), expert of each row
    tile [M / tm], tiles in use [1], pairs per held expert [held]); M is
    the bound that holds whatever the routing: every pair here."""
    T, k = experts.shape
    n = T * k
    M = _rows_bound(n, held, tm)
    local = experts - first
    here = (local >= 0) & (local < held) & valid[:, None]
    key = jnp.where(here, local, held).reshape(n)          # absent last
    counts = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                     dtype=jnp.int32)                       # [held]
    padded = -(-counts // tm) * tm
    pad_start = jnp.cumsum(padded) - padded
    start = jnp.cumsum(counts) - counts
    order = jnp.argsort(key, stable=True)                   # [n]
    sorted_key = key[order]
    safe = jnp.minimum(sorted_key, held - 1)
    dest_sorted = jnp.where(
        sorted_key < held,
        pad_start[safe] + jnp.arange(n, dtype=jnp.int32) - start[safe], M)
    dest = jnp.zeros(n, jnp.int32).at[order].set(dest_sorted)
    # slot M takes the pairs that are not here, and is cut off
    row_token = jnp.zeros(M + 1, jnp.int32).at[dest_sorted].set(
        (order // k).astype(jnp.int32))[:M]
    tiles = jnp.arange(M // tm)
    tile_end = jnp.cumsum(padded) // tm                     # [held]
    used = tile_end[-1]
    tile_expert = jnp.minimum(
        jnp.sum(tiles[:, None] >= tile_end[None, :], axis=1), held - 1)
    # a tile past the last one in use repeats its expert: no new DMA
    tile_expert = jnp.where(tiles < used, tile_expert,
                            tile_expert[jnp.maximum(used - 1, 0)])
    return (row_token, dest.reshape(T, k), tile_expert.astype(jnp.int32),
            used.astype(jnp.int32).reshape(1), counts)


def _rows_bound(pairs: int, held: int, tm: int) -> int:
    """Rows that hold ``pairs`` pairs however they fall on ``held``
    experts, each expert's padded to whole tiles."""
    return -(-(pairs + held * (tm - 1)) // tm) * tm


def _ffn_kernel(act, l_ref, te_ref, used_ref, x_ref, wg_ref, wu_ref, wd_ref,
                o_ref, acc_ref):
    """One (row tile, slice of the expert width) grid step: the tile's
    rows through ``tf`` columns of its expert's gate (under ``act``) and up
    projections and the matching rows of its down projection, summed over
    the slices in float32."""
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _zero():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(i < used_ref[0])
    def _tile():
        x = x_ref[:]                                        # [tm, e]
        dims = (((1,), (1,)), ((), ()))                     # x @ w.T
        gate = jax.lax.dot_general(x, wg_ref[:], dims,
                                   preferred_element_type=jnp.float32)
        up = jax.lax.dot_general(x, wu_ref[:], dims,
                                 preferred_element_type=jnp.float32)
        h = (act(gate) * up).astype(x.dtype)                # [tm, tf]
        acc_ref[:] += jnp.dot(h, wd_ref[:],
                              preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _out():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def grouped_expert_ffn(x_rows: jax.Array, tile_expert: jax.Array,
                       used: jax.Array, layer, wg: jax.Array, wu: jax.Array,
                       wd: jax.Array, *, tm: int, act: str = "silu",
                       interpret: bool = False) -> jax.Array:
    """The grouped gated MLP over expert-sorted rows ``[M, e]``: row tile
    ``i`` belongs to expert ``tile_expert[i]`` of layer ``layer`` of the
    stacked weights ``[L, held, F, e]``; tiles from ``used`` on are
    skipped (their output rows are never read).  The layer is an index
    of the block maps, as in the cache kernels: a layer's weights sliced
    out of the stack for a custom call would be copied, 1.5 GB a layer
    at the published widths."""
    M, e = x_rows.shape
    F = wg.shape[2]
    tf = next(t for t in (FF_SLICE, 128, F) if F % t == 0)
    nf = F // tf

    def frozen(i, j, used):
        # a skipped tile keeps the block of the step before it
        live = i < used[0]
        return jnp.where(live, i, jnp.maximum(used[0] - 1, 0)), \
            jnp.where(live, j, nf - 1)

    def rows(i, j, l, te, used):
        return frozen(i, j, used)[0], 0

    def weights(i, j, l, te, used):
        return l[0], te[i], frozen(i, j, used)[1], 0

    w_spec = pl.BlockSpec((None, None, tf, e), weights)
    return pl.pallas_call(
        functools.partial(_ffn_kernel, ACTS[act]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(M // tm, nf),
            in_specs=[pl.BlockSpec((tm, e), rows), w_spec, w_spec, w_spec],
            out_specs=pl.BlockSpec((tm, e),
                                   lambda i, j, l, te, used: (i, 0)),
            scratch_shapes=[pltpu.VMEM((tm, e), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((M, e), x_rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="moe_expert_ffn",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), tile_expert, used, x_rows,
      wg, wu, wd)


def _grouped_reference(x_rows, tile_expert, used, layer, wg, wu, wd, *, tm,
                       act: str = "silu"):
    """The kernel's arithmetic in ``jnp`` (the CPU path)."""
    wg, wu, wd = wg[layer], wu[layer], wd[layer]
    M, e = x_rows.shape
    xt = x_rows.reshape(M // tm, tm, e)
    f32 = jnp.float32
    gate = jnp.einsum("nte,nfe->ntf", xt, wg[tile_expert],
                      preferred_element_type=f32)
    up = jnp.einsum("nte,nfe->ntf", xt, wu[tile_expert],
                    preferred_element_type=f32)
    h = (ACTS[act](gate) * up).astype(x_rows.dtype)
    out = jnp.einsum("ntf,nfe->nte", h, wd[tile_expert],
                     preferred_element_type=f32)
    live = (jnp.arange(M // tm) < used[0])[:, None, None]
    return jnp.where(live, out, 0).reshape(M, e).astype(x_rows.dtype)


def held_experts_ffn(x2d: jax.Array, experts: jax.Array,
                     weights: jax.Array, params, first: int, *,
                     layer=None, valid: Optional[jax.Array] = None,
                     use_kernel: Optional[bool] = None,
                     interpret: bool = False, plan=None, act: str = "silu"
                     ) -> Tuple[jax.Array, jax.Array]:
    """``sum_i w_i E_i(x)`` over the chosen experts that are held here.

    x2d [T, e]; experts / weights [T, k] from a router of :data:`ROUTERS`;
    params ``{"wg", "wu", "wd"}`` each ``[held, F, e]``, or the layers'
    stack ``[L, held, F, e]`` with ``layer`` the one to use (an int32
    scalar: the layer loop's counter); ``valid`` [T] marks real tokens;
    ``plan``: :func:`plan_rows` made earlier; ``act``: the gate's (ACTS).
    Returns (partial result [T, e] in x2d's dtype, pairs per held expert
    [held] int32)."""
    T, e = x2d.shape
    wg, wu, wd = (params[n].astype(x2d.dtype) for n in ("wg", "wu", "wd"))
    if layer is None:
        wg, wu, wd, layer = wg[None], wu[None], wd[None], 0
    held = wg.shape[1]
    if valid is None:
        valid = jnp.ones(T, bool)
    if use_kernel is None:
        use_kernel = interpret or on_tpu()
    # a plan made ahead brings its tile: its rows over its tiles
    tm = plan[0].shape[0] // plan[2].shape[0] if plan else row_tile(T)
    row_token, dest, tile_expert, used, counts = plan or _plan(
        experts, valid, first, held, tm)
    ffn = (functools.partial(grouped_expert_ffn, interpret=interpret)
           if use_kernel else _grouped_reference)
    # the bound that always holds is 16 times what even routing sends
    # here; its unused tiles cost their grid steps and no weights (a
    # quarter of the bound with a fallback to all of it was 0.10 ms of
    # 2.56 faster a layer call at 256 tokens: not worth a second kernel
    # in every step program; PERF.md, PR 27)
    y_rows = ffn(x2d[row_token], tile_expert, used, layer, wg, wu, wd, tm=tm,
                 act=act)
    rows = row_token.shape[0]
    picked = y_rows[jnp.minimum(dest, rows - 1)].astype(jnp.float32)
    out = jnp.sum(jnp.where((dest < rows)[..., None],
                            picked * weights[..., None], 0.0), axis=1)
    return out.astype(x2d.dtype), counts


def dense_held_reference(x2d, experts, weights, params, first: int,
                         act: str = "silu"):
    """Ground truth for tests: every held expert over every token, masked
    by the routing, float32."""
    f32 = jnp.float32
    x = x2d.astype(f32)
    wg, wu, wd = (params[n].astype(f32) for n in ("wg", "wu", "wd"))
    h = ACTS[act](jnp.einsum("te,xfe->xtf", x, wg)) \
        * jnp.einsum("te,xfe->xtf", x, wu)
    out = jnp.einsum("xtf,xfe->xte", h, wd)                 # [held, T, e]
    held = wg.shape[0]
    gate = jnp.sum(
        jnp.where((experts - first)[..., None] == jnp.arange(held),
                  weights[..., None], 0.0), axis=1)         # [T, held]
    return jnp.einsum("tx,xte->te", gate, out)


def route_softmax_topk(x2d: jax.Array, w_router: jax.Array, top_k: int,
                       scaling: float, norm_topk: bool = True
                       ) -> Tuple[jax.Array, jax.Array]:
    """:func:`route_sigmoid_topk` with the other scoring function: a
    float32 softmax over ALL experts, the ``top_k`` largest, normalised
    over the chosen ones and scaled (the qwen2_moe lineage's router).
    The experts chosen are those of the largest logits either way; only
    the weights differ."""
    scores = jax.nn.softmax(jnp.dot(
        x2d.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    top, experts = _largest(scores, top_k)
    if norm_topk:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), top * scaling


#: the scoring functions a configuration names (``router_scoring``)
ROUTERS = {"sigmoid": route_sigmoid_topk, "softmax": route_softmax_topk}


#: the gate's activation a configuration names (``expert_act``): SwiGLU's
#: and the ReLU of a ReGLU expert.  Static in the kernel: a name is one
#: Mosaic text
ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def plan_rows(experts: jax.Array, valid: Optional[jax.Array], first: int,
              held: int, scored: int = 0):
    """:func:`held_experts_ffn`'s row layout for ``experts`` [T, k], made
    where the routing is known, which may be before the layer's mixer (a
    router that reads the mixer's input): ``held_experts_ffn(...,
    plan=...)`` then only gathers, multiplies and scatters.  ``scored``:
    the experts the router chose among, which sizes the tile
    (:func:`row_tile`) by the pairs an expert sees."""
    T, k = experts.shape
    if valid is None:
        valid = jnp.ones(T, bool)
    return _plan(experts, valid, first, held,
                 row_tile(T, T * k / scored if scored else 0.0))
