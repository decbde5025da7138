"""A dropless routed layer that holds only some of the experts.

One chip of an expert-parallel group holds ``held`` of a layer's ``E``
routed experts (``first .. first + held - 1``).  The router still scores
every token over all ``E`` experts and picks its ``k`` largest; the
token-expert pairs that fall to experts held here are sorted by expert,
padded per expert to whole row tiles and run through ONE grouped matmul
(the Pallas kernel ``moe_expert_ffn``: a gated expert of three matrices,
``down(act(gate x) * up x)``, or an ungated one of two, ``down(act(up
x))``, by the activation's name: :data:`ACTS`); pairs of experts held
elsewhere add nothing.  The result is this chip's partial sum — the
shares of all chips, the shared expert counted once, add up to the
uncut layer (``tests/test_pangu_moe.py``).  Nothing here stands in for
the other chips or their exchange.

``moe/gating.py``'s one-hot ``[T, E, C]`` dispatch needs ``C = T`` when
nothing may be dropped: at 256 experts sixteen times the useful work.

Expert weights are stored ``[held, F, e]`` for all three projections
(gate and up out-major, down in-major; an ungated expert has no ``wg``), so
every block the kernel streams is ``tf`` whole rows of the hidden width:
contiguous in HBM.
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
    selection bias (:func:`route_sigmoid_grouped` has both).  Returns
    (experts [T, k] int32, weights [T, k] f32).

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


#: columns of the expert width one work item of the kernel takes.  With 32
#: or 64 rows a tile the kernel's scratch (rows in and out, the float32
#: sum, the ring of weight slices: ``RING_BYTES``) stays under 12 MB: inside
#: the chip's default scoped VMEM.  A kernel that asked for more (slices of
#: 256, a 96 MB limit) ran alone and HUNG the chip inside a mixed step
#: program, whose other operations hold VMEM of their own (PERF.md, PR 27).
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


#: bytes of VMEM the ring of weight slices may take.  Beside a 64-row
#: tile's blocks at the widest hidden size served (7,680: two tiles of rows
#: in, one out, the float32 sum, 4.9 MB) it keeps the kernel under the
#: default scoped VMEM: a kernel that asked for more HUNG the chip inside a
#: mixed step program (PERF.md, PR 27)
RING_BYTES = 6 * 2 ** 20

#: sets of slices past which a longer ring buys nothing (v5e, PERF.md PR 48:
#: two sets read 0.6% over three at 328 KB a slice and the same at 393 and
#: 983 KB; four and six read what three do)
RING_SETS = 3


def width_slice(F: int) -> int:
    """Columns of an expert width ``F`` one work item takes."""
    return next(t for t in (FF_SLICE, 128, F) if F % t == 0)


def ring_sets(tf: int, e: int, itemsize: int, matrices: int = 3) -> int:
    """Sets (gate, up, down; up, down of an ungated expert: ``matrices``)
    of ``[tf, e]`` weight slices in the kernel's ring, from their bytes: as
    many as :data:`RING_BYTES` hold, two at least (one under the matmuls,
    one on its way), :data:`RING_SETS` at most.  2 at Pangu's 983 KB a
    slice, 3 at 393 and 328 KB."""
    return max(2, min(RING_BYTES // (matrices * tf * e * itemsize),
                      RING_SETS))


def _ffn_kernel(act, l_ref, te_ref, used_ref, x_ref, *refs, tm, tf, nf,
                sets, gated=True):
    """The walk over the row tiles IN USE, ``used_ref[0]`` of them: every
    operand stays in HBM and the kernel copies what it multiplies.  A work
    item is (row tile ``i``, slice ``j`` of the expert width): the tile's
    rows through ``tf`` columns of its expert's gate (under ``act``) and up
    projections and the matching rows of its down projection, a tile's
    slices summed in float32 in the order ``j = 0 .. nf - 1``; an expert
    that is not ``gated`` has no gate projection, ``act`` is its up
    projection's, and an item copies TWO slices (``refs``: the weights,
    three or two, then the output and the scratch).  The
    slices of an item are one set of the ring; the set an item leaves is
    filled with the item ``sets`` places on in the walk BEFORE the next
    item's copies are waited for, across tile boundaries, so the memory
    always has a set queued behind the one on its way.  The next tile's
    rows are copied under this tile's slices and a tile's result is written
    back under the next tile's.  No step, copy, zeroing or write-back
    exists for a tile past ``used``: its output rows are left as they were
    (``ops/paged_attention.py::_walk_kernel`` and ``ops/mla_attention.py::
    _decode_kernel`` are the same walk over pages)."""
    w_refs, (o_ref, rows_in, rows_out, ring, acc, sem_in, sem_out,
             sem_w) = refs[:-8], refs[-8:]
    layer, used = l_ref[0], used_ref[0]
    items = used * nf

    def weights(s, wait):
        """Start (or wait for) the copies of work item ``s``.  A wait only
        needs a copy of the same size."""
        i, j = jax.lax.div(s, nf), jax.lax.rem(s, nf)
        expert = 0 if wait else te_ref[i]
        at = pl.ds(0 if wait else pl.multiple_of(j * tf, tf), tf)
        slot = jax.lax.rem(s, sets)
        for k, w_ref in enumerate(w_refs):
            copy = pltpu.make_async_copy(w_ref.at[layer, expert, at],
                                         ring.at[slot, k], sem_w.at[slot])
            copy.wait() if wait else copy.start()

    def rows(i, wait):
        at = pl.ds(0 if wait else pl.multiple_of(i * tm, tm), tm)
        slot = jax.lax.rem(i, 2)
        copy = pltpu.make_async_copy(x_ref.at[at], rows_in.at[slot],
                                     sem_in.at[slot])
        copy.wait() if wait else copy.start()

    def result(i):
        return pltpu.make_async_copy(
            rows_out, o_ref.at[pl.ds(pl.multiple_of(i * tm, tm), tm)],
            sem_out.at[0])

    @pl.when(used > 0)
    def _first():
        rows(0, wait=False)

    for s in range(sets):
        pl.when(s < items)(lambda: weights(s, wait=False))

    def tile(i, carry):
        pl.when(i + 1 < used)(lambda: rows(i + 1, wait=False))
        rows(i, wait=True)
        tile_rows = rows_in.at[jax.lax.rem(i, 2)]
        acc[...] = jnp.zeros_like(acc)

        def piece(j, carry):
            s = i * nf + j
            slot = jax.lax.rem(s, sets)
            weights(s, wait=True)
            x = tile_rows[...]                                # [tm, e]
            dims = (((1,), (1,)), ((), ()))                 # x @ w.T
            if gated:
                gate = jax.lax.dot_general(
                    x, ring[slot, 0], dims,
                    preferred_element_type=jnp.float32)
            up = jax.lax.dot_general(x, ring[slot, len(w_refs) - 2], dims,
                                     preferred_element_type=jnp.float32)
            h = (act(gate) * up if gated else act(up)).astype(x.dtype)
            acc[...] += jnp.dot(h, ring[slot, len(w_refs) - 1],
                                preferred_element_type=jnp.float32)
            pl.when(s + sets < items)(
                lambda: weights(s + sets, wait=False))
            return carry

        jax.lax.fori_loop(0, nf, piece, 0)
        pl.when(i > 0)(lambda: result(i - 1).wait())
        rows_out[...] = acc[...].astype(rows_out.dtype)
        result(i).start()
        return carry

    jax.lax.fori_loop(0, used, tile, 0)
    pl.when(used > 0)(lambda: result(used - 1).wait())


@functools.partial(jax.jit, static_argnames=("tm", "act", "interpret"))
def grouped_expert_ffn(x_rows: jax.Array, tile_expert: jax.Array,
                       used: jax.Array, layer, wg: Optional[jax.Array],
                       wu: jax.Array, wd: jax.Array, *, tm: int,
                       act: str = "silu", interpret: bool = False
                       ) -> jax.Array:
    """The grouped MLP over expert-sorted rows ``[M, e]``: row tile
    ``i`` belongs to expert ``tile_expert[i]`` of layer ``layer`` of the
    stacked weights ``[L, held, F, e]`` (``wg`` None under an ungated
    ``act``).  The kernel walks the ``used``
    tiles in use and nothing else (:func:`_ffn_kernel`): the rows of a tile
    from ``used`` on are NOT WRITTEN and hold whatever the buffer held, a
    NaN as soon as anything; a caller reads them only to drop them.  The
    layer is an index of the copies, as in the cache kernels: a layer's
    weights sliced out of the stack for a custom call would be copied,
    1.5 GB a layer at the published widths.  The ring of weight slices is
    sized by bytes from the shapes (:func:`ring_sets`).  Jitted, as the
    attention walks are: the body is traced once a shape and lowered once a
    step program, not once a layer of a period (un-jitted, a program of
    four routed layers a period took 0.3-0.7 s longer to form than the
    grid form's; PERF.md, PR 48)."""
    M, e = x_rows.shape
    F = wu.shape[2]
    tf = width_slice(F)
    mats = [w for w in (wg, wu, wd) if w is not None]
    assert (wg is None) == (act in UNGATED), act
    sets = ring_sets(tf, e, x_rows.dtype.itemsize, len(mats))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_ffn_kernel, ACTS[act], tm=tm, tf=tf, nf=F // tf,
                          sets=sets, gated=wg is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(1,),
            in_specs=[hbm] * (1 + len(mats)), out_specs=hbm,
            scratch_shapes=[
                pltpu.VMEM((2, tm, e), x_rows.dtype),
                pltpu.VMEM((tm, e), x_rows.dtype),
                pltpu.VMEM((sets, len(mats), tf, e), x_rows.dtype),
                pltpu.VMEM((tm, e), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((1,)),
                pltpu.SemaphoreType.DMA((sets,))]),
        out_shape=jax.ShapeDtypeStruct((M, e), x_rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="moe_expert_ffn",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), tile_expert, used, x_rows,
      *mats)


def _grouped_reference(x_rows, tile_expert, used, layer, wg, wu, wd, *, tm,
                       act: str = "silu"):
    """The kernel's arithmetic in ``jnp`` (the CPU path)."""
    wu, wd = wu[layer], wd[layer]
    M, e = x_rows.shape
    xt = x_rows.reshape(M // tm, tm, e)
    f32 = jnp.float32
    up = jnp.einsum("nte,nfe->ntf", xt, wu[tile_expert],
                    preferred_element_type=f32)
    if wg is None:
        h = ACTS[act](up).astype(x_rows.dtype)
    else:
        gate = jnp.einsum("nte,nfe->ntf", xt, wg[layer][tile_expert],
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
                     interpret: bool = False, plan=None, act: str = "silu",
                     tile: int = 0) -> Tuple[jax.Array, jax.Array]:
    """``sum_i w_i E_i(x)`` over the chosen experts that are held here.

    x2d [T, e]; experts / weights [T, k] from a router of :data:`ROUTERS`;
    params ``{"wg", "wu", "wd"}`` each ``[held, F, e]``, or the layers'
    stack ``[L, held, F, e]`` with ``layer`` the one to use (an int32
    scalar: the layer loop's counter), no ``wg`` under an ungated ``act``;
    ``valid`` [T] marks real tokens; ``plan``: :func:`plan_rows` made
    earlier; ``act``: the gate's, or an ungated expert's own (ACTS);
    ``tile``: the rows of a tile where no plan brings them (0:
    :func:`row_tile`).
    Returns (partial result [T, e] in x2d's dtype, pairs per held expert
    [held] int32)."""
    T, e = x2d.shape
    wg, wu, wd = (None if n == "wg" and act in UNGATED
                  else params[n].astype(x2d.dtype)
                  for n in ("wg", "wu", "wd"))
    if layer is None:
        wg, wu, wd, layer = (wg if wg is None else wg[None], wu[None],
                             wd[None], 0)
    held = wu.shape[1]
    if valid is None:
        valid = jnp.ones(T, bool)
    if use_kernel is None:
        use_kernel = interpret or on_tpu()
    # a plan made ahead brings its tile: its rows over its tiles
    tm = plan[0].shape[0] // plan[2].shape[0] if plan \
        else tile or row_tile(T)
    row_token, dest, tile_expert, used, counts = plan or _plan(
        experts, valid, first, held, tm)
    ffn = (functools.partial(grouped_expert_ffn, interpret=interpret)
           if use_kernel else _grouped_reference)
    # the bound that always holds is 16 times what even routing sends
    # here: the kernel walks the tiles in use and leaves the rows of every
    # other tile UNWRITTEN (the ``jnp`` form zeroes them).  They are only
    # ever read as the clamped row of a pair that is not here, which the
    # ``where`` below drops: a NaN there must not pass, and a product with
    # it would (tests/test_held_walk.py poisons them)
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
    wu, wd = params["wu"].astype(f32), params["wd"].astype(f32)
    h = jnp.einsum("te,xfe->xtf", x, wu)
    h = ACTS[act](h) if act in UNGATED else ACTS[act](jnp.einsum(
        "te,xfe->xtf", x, params["wg"].astype(f32))) * h
    out = jnp.einsum("xtf,xfe->xte", h, wd)                 # [held, T, e]
    held = wu.shape[0]
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


def route_sigmoid_grouped(x2d: jax.Array, w_router: jax.Array, top_k: int,
                          scaling: float, norm_topk: bool = True, *,
                          bias: jax.Array, groups: int, keep: int
                          ) -> Tuple[jax.Array, jax.Array]:
    """:func:`route_sigmoid_topk` with expert groups and a selection bias
    (the deepseek_v3 lineage's ``noaux_tc``): ``c = s + bias`` CHOOSES, a
    group of ``E / groups`` neighbouring experts scores the sum of its two
    largest ``c``, the ``top_k`` largest ``c`` inside the ``keep`` best
    groups are taken, and their weights come from ``s`` (not ``c``),
    normalised over the chosen ones and scaled."""
    scores = jax.nn.sigmoid(jnp.dot(
        x2d.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    T, E = scores.shape
    choice = (scores + bias.astype(jnp.float32)).reshape(T, groups, -1)
    _, kept = _largest(jnp.sum(_largest(choice, 2)[0], axis=-1), keep)
    in_kept = jnp.any(
        kept[:, :, None] == jnp.arange(groups, dtype=jnp.int32), axis=1)
    _, experts = _largest(jnp.where(in_kept[:, :, None], choice,
                                    -jnp.inf).reshape(T, E), top_k)
    top = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), top * scaling


#: the scoring functions a configuration names (``router_scoring``)
ROUTERS = {"sigmoid": route_sigmoid_topk, "softmax": route_softmax_topk,
           "sigmoid_grouped": route_sigmoid_grouped}


#: the activation a configuration names (``expert_act``): the GATE's of a
#: three-matrix expert (SwiGLU's, and the ReLU of a ReGLU expert), or, of
#: the names in :data:`UNGATED`, the up projection's of a two-matrix expert
#: ``down(act(up x))`` that has no gate (relu2: ``relu(.)^2``).  Static in
#: the kernel: a name is one Mosaic text
ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu,
        "relu2": lambda up: jnp.square(jax.nn.relu(up))}
UNGATED = frozenset({"relu2"})


def plan_rows(experts: jax.Array, valid: Optional[jax.Array], first: int,
              held: int, scored: int = 0, tile: int = 0):
    """:func:`held_experts_ffn`'s row layout for ``experts`` [T, k], made
    where the routing is known, which may be before the layer's mixer (a
    router that reads the mixer's input): ``held_experts_ffn(...,
    plan=...)`` then only gathers, multiplies and scatters.  ``scored``:
    the experts the router chose among, which sizes the tile
    (:func:`row_tile`) by the pairs an expert sees; ``tile``: the tile's
    rows, from a caller that knows them (0: that rule)."""
    T, k = experts.shape
    if valid is None:
        valid = jnp.ones(T, bool)
    return _plan(experts, valid, first, held,
                 tile or row_tile(T, T * k / scored if scored else 0.0))
