"""Selective state-space (Mamba-1) sequence state for ragged serving.

A Mamba layer carries, a sequence, a recurrent state ``h`` of
``[d_state, d_inner]`` float32 and the last ``d_conv - 1`` inputs of its
causal convolution.  Neither grows with the context and neither can be
cut into pages of positions, so they live in a STATE POOL beside the page
pool (``inference/v2/ragged/kv_cache.py::StatePool``): one slot a
sequence,

    h    : [L_ssm, slots + 1, d_state, d_inner]      float32
    conv : [L_ssm, slots + 1, 8, (d_conv - 1) * d_inner / 8] bfloat16

``d_inner`` minor (5120 is 40 lane tiles, 16 is not one); the tail's
``[d_conv - 1, d_inner]`` rows laid end to end and cut into 8 rows
(:func:`conv_rows`), because a second-minor dim of 3 is one the chip's
compiler lays out one way at a program's edge and another inside its loop
(two copies of the pool a step; ``tests/test_chip_compile.py``), and 8 rows
of whole lane tiles are a block the kernel can write.  The last slot is
the scratch slot that rows with nothing to write are sent to (as the page
pools have their null page).  Both pools are donated at the jit boundary
and carried through the layer loop; every op here takes the WHOLE pool
and a layer index and updates ``pool[layer, slot]`` in place.

* :func:`ssm_scan` — the recurrence, for rows of ``Q`` tokens from each
  row's slot: ``h_t = exp(dt_t (x) A) * h_{t-1} + (dt_t * x_t) (x) B_t``,
  ``y_t = h_t C_t + D * x_t``.  Mamba-1's ``A`` is a full ``[d_inner,
  d_state]`` diagonal, so a chunk has no matrix form (that takes one decay
  a head: ``ops/delta_rule.py`` has it, for the gated delta rule):
  the recurrence is elementwise on the vector unit, sequential in ``t``,
  parallel over ``d_inner``.  On a TPU it is a Pallas kernel named
  ``ssm_state_update_decode`` (Q = 1: one token a row, the state read and
  written once) or ``ssm_scan_prefill`` (Q > 1), both pools aliased input
  -> output, the slot ids riding the BlockSpec index maps through scalar
  prefetch.  The same call writes the row's new convolution tail into
  ``conv[layer, slot]`` (an XLA scatter did it row by row: 30 KB a row in
  1.1 us, a fifth of the decode step, PERF.md PR 34).  The jnp form is the
  semantics ground truth and the CPU path.
* :func:`conv_step` — the depthwise causal convolution over the slot's
  tail (one XLA gather, 30 KB a row and layer) and the new tokens, and
  the tail of the row's TRUE last tokens for :func:`ssm_scan` to write.

A row that starts at position 0 (``fresh``) starts from a zero state and
a zero tail whatever its slot held: a reused slot is zeroed by the
program, not by a host write.  A padded position has ``dt = 0``
(``exp(0) = 1``, ``dt x = 0``) and moves nothing.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..accelerator import on_tpu

#: bytes of one grid step's token blocks (dt, x and y, two buffers each)
#: that :func:`_d_block` keeps a prefill step under: far inside the
#: default scoped VMEM limit (a kernel that asks for more hangs the chip
#: inside a step program, PERF.md PR 27)
TOKEN_BLOCK_BUDGET = 6 * 2 ** 20


def _d_block(d: int, Q: int) -> int:
    """Lanes of ``d_inner`` one grid step holds: all of them for a decode
    row, else the largest whole-tile divisor under the budget."""
    if Q == 1 or d % 128:
        return d
    tiles = d // 128
    for n in range(tiles, 0, -1):
        if tiles % n == 0 and Q * n * 128 * 4 * 6 <= TOKEN_BLOCK_BUDGET:
            return n * 128
    return 128


def conv_rows(width: int) -> int:
    """Rows a slot's convolution tail of ``width`` values is cut into."""
    return 8 if width % 8 == 0 else 1


def ssm_scan_reference(h_pool, conv_pool, layer, slots, fresh, dt, x, B, C,
                       A_t, D, new_tail):
    """The recurrence as a plain ``lax.scan`` over positions (module
    docstring): (y ``[S, Q, d]`` float32, the h pool, the conv pool)."""
    f32 = jnp.float32
    h0 = h_pool[layer, slots].astype(f32)                   # [S, N, d]
    h0 = jnp.where(fresh[:, None, None], 0.0, h0)

    def step(h, inp):
        dt_t, x_t, b_t, c_t = inp             # [S, d] [S, d] [S, N] [S, N]
        h = jnp.exp(dt_t[:, None, :] * A_t) * h \
            + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        return h, jnp.sum(h * c_t[:, :, None], axis=1) + D * x_t

    h, ys = jax.lax.scan(step, h0, tuple(
        a.astype(f32).swapaxes(0, 1) for a in (dt, x, B, C)))
    return (ys.swapaxes(0, 1),
            h_pool.at[layer, slots].set(h.astype(h_pool.dtype)),
            conv_pool.at[layer, slots].set(
                new_tail.reshape((-1,) + conv_pool.shape[2:])))


def _ssm_kernel(l_ref, slot_ref, fresh_ref, dt_ref, x_ref, b_ref, c_ref,
                a_ref, d_ref, tail_ref, h_ref, conv_ref, y_ref, hout_ref,
                tout_ref, *, q_len):
    """One (row, block of ``d_inner``) grid step: the row's state block
    ``[N, blk]`` read, ``q_len`` steps of the recurrence, the state
    written back to the same address (the pool is aliased input ->
    output, so nothing else of it moves).  ``b_ref`` / ``c_ref`` hold B
    and C as ``[N, Q]`` (the state dim on sublanes, as in ``h``): step
    ``t`` takes its column by a masked lane sum.  The row's new
    convolution tail goes to ``conv[layer, slot]`` whole (that pool is
    aliased too and never read here)."""
    del l_ref, slot_ref, conv_ref
    s = pl.program_id(0)
    tout_ref[...] = tail_ref[...]
    f32 = jnp.float32
    h = h_ref[...].astype(f32)
    h = jnp.where(fresh_ref[s] > 0, jnp.zeros_like(h), h)
    a, dvec = a_ref[...], d_ref[...]                       # [N, blk] [1, blk]

    def step(h, dt, x, b, c):
        # dt, x: [1, blk]; b, c: [N, 1]
        h = jnp.exp(dt * a) * h + (dt * x) * b
        return h, jnp.sum(h * c, axis=0, keepdims=True) + dvec * x

    if q_len == 1:
        # the rows' dt, x and y come as [rows, d] blocks of 8 rows (whole
        # (8, 128) tiles for the operations around the kernel), fetched
        # and written back once for the 8 grid steps that share them
        r = s % dt_ref.shape[0]
        h, y = step(h, dt_ref[pl.ds(r, 1), :], x_ref[pl.ds(r, 1), :],
                    b_ref[...], c_ref[...])
        y_ref[pl.ds(r, 1), :] = y
    else:
        lane = jax.lax.broadcasted_iota(jnp.int32, b_ref.shape, 1)
        bT, cT = b_ref[...], c_ref[...]

        def body(t, h):
            pick = lane == t
            b = jnp.sum(jnp.where(pick, bT, 0.0), axis=1, keepdims=True)
            c = jnp.sum(jnp.where(pick, cT, 0.0), axis=1, keepdims=True)
            h, y = step(h, dt_ref[pl.ds(t, 1), :], x_ref[pl.ds(t, 1), :],
                        b, c)
            y_ref[pl.ds(t, 1), :] = y
            return h

        h = jax.lax.fori_loop(0, q_len, body, h)
    hout_ref[...] = h.astype(hout_ref.dtype)


def ssm_scan_kernel(h_pool, conv_pool, layer, slots, fresh, dt, x, B, C,
                    A_t, D, new_tail, *, interpret: bool = False):
    """Pallas form of :func:`ssm_scan_reference`, in place."""
    S, Q, d = x.shape
    rows, width = conv_pool.shape[2:]
    N = A_t.shape[0]
    blk = _d_block(d, Q)
    f32 = jnp.float32

    tokens = pl.BlockSpec((None, Q, blk), lambda s, j, l, sl, fr: (s, 0, j))
    y_shape = (S, Q, d)
    if Q == 1:
        # one token a row: [S, d], eight rows a block (kernel docstring)
        rb = min(8, S)
        assert S % rb == 0, "row buckets are powers of two"
        tokens = pl.BlockSpec((rb, blk),
                              lambda s, j, l, sl, fr: (s // rb, j))
        dt, x, y_shape = dt.reshape(S, d), x.reshape(S, d), (S, d)

    cols = pl.BlockSpec((None, N, Q), lambda s, j, l, sl, fr: (s, 0, 0))
    state = pl.BlockSpec((None, None, N, blk),
                         lambda s, j, l, sl, fr: (l[0], sl[s], 0, j))
    tail = pl.BlockSpec((None, None, rows, width),
                        lambda s, j, l, sl, fr: (l[0], sl[s], 0, 0))
    y, h_pool, conv_pool = pl.pallas_call(
        functools.partial(_ssm_kernel, q_len=Q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(S, d // blk),
            in_specs=[tokens, tokens, cols, cols,
                      pl.BlockSpec((N, blk),
                                   lambda s, j, l, sl, fr: (0, j)),
                      pl.BlockSpec((1, blk),
                                   lambda s, j, l, sl, fr: (0, j)),
                      pl.BlockSpec((None, rows, width),
                                   lambda s, j, l, sl, fr: (s, 0, 0)),
                      state, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[tokens, state, tail]),
        out_shape=[jax.ShapeDtypeStruct(y_shape, f32),
                   jax.ShapeDtypeStruct(h_pool.shape, h_pool.dtype),
                   jax.ShapeDtypeStruct(conv_pool.shape, conv_pool.dtype)],
        # operands count the 3 prefetched
        input_output_aliases={10: 1, 11: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        # ``^ssm_`` finds both and no pattern of the attention or cache
        # write kernels does (benchmark/metrics)
        name="ssm_state_update_decode" if Q == 1 else "ssm_scan_prefill",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
      fresh.astype(jnp.int32), dt.astype(f32), x.astype(f32),
      B.astype(f32).swapaxes(1, 2), C.astype(f32).swapaxes(1, 2),
      A_t.astype(f32), D.astype(f32).reshape(1, d),
      new_tail.astype(conv_pool.dtype).reshape(S, rows, width), h_pool,
      conv_pool)
    return y.reshape(S, Q, d), h_pool, conv_pool


def ssm_scan(h_pool: jax.Array, conv_pool: jax.Array, layer,
             slots: jax.Array, fresh: jax.Array, dt: jax.Array,
             x: jax.Array, B: jax.Array, C: jax.Array, A_t: jax.Array,
             D: jax.Array, new_tail: jax.Array, *,
             use_kernel: Optional[bool] = None, interpret: bool = False
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``Q`` steps of the recurrence for ``S`` rows from their slots, and
    the rows' new convolution tails written.

    h_pool : [L, slots + 1, N, d], the state's dtype
    conv_pool : [L, slots + 1, rows, (K - 1) * d / rows]
    layer  : int32 scalar (the layer loop's counter among the state
             layers, or a constant)
    slots  : [S] int32, the scratch slot for a row with nothing to step
    fresh  : [S] bool, the row starts from a zero state
    dt, x  : [S, Q, d] (``dt`` after the softplus, 0 at padded positions)
    B, C   : [S, Q, N]
    A_t    : [N, d] = ``-exp(A_log)`` transposed;  D : [d]
    new_tail : [S, K - 1, d], :func:`conv_step`'s
    Returns (y [S, Q, d] float32, the updated h pool, the updated conv
    pool).  ``use_kernel`` None = auto (on TPU, or anywhere with
    ``interpret=True``)."""
    if use_kernel is None:
        use_kernel = interpret or on_tpu()
    impl = functools.partial(ssm_scan_kernel, interpret=interpret) \
        if use_kernel else ssm_scan_reference
    return impl(h_pool, conv_pool, layer, slots, fresh, dt, x, B, C, A_t,
                D, new_tail)


def conv_step(conv_pool: jax.Array, layer, slots: jax.Array,
              fresh: jax.Array, q_lens: jax.Array, x: jax.Array,
              w: jax.Array, b: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """The causal depthwise convolution of ``x`` ``[S, Q, d]`` behind each
    row's tail ``pool[layer, slot]`` (``[d_conv - 1, d]`` laid end to
    end in :func:`conv_rows` rows; zero for a ``fresh`` row), ``w``
    ``[d_conv, d]`` (tap ``k`` weighs the input ``d_conv - 1 - k``
    positions back), ``b`` ``[d]`` (None: no bias).  The tail kept is
    that of the row's TRUE last tokens, ``q_lens`` of them new: padding
    to the ``Q`` bucket does not enter it.  Returns (``conv(x) + b`` in
    float32, the new tails ``[S, d_conv - 1, d]`` for :func:`ssm_scan` to
    write)."""
    S, Q, _ = x.shape
    K = w.shape[0]
    tail = conv_pool[layer, slots].reshape(S, K - 1, -1)
    tail = jnp.where(fresh[:, None, None], jnp.zeros_like(tail), tail)
    w = w.astype(jnp.float32)

    def bias():
        return 0.0 if b is None else b.astype(jnp.float32)

    if Q == 1:
        # one token a row: taps and shift on [S, d] arrays (a [S, 4, d]
        # concatenation is re-laid out, 10 MB a layer at 256 rows)
        x0 = x[:, 0].astype(tail.dtype)
        out = bias() + x0.astype(jnp.float32) * w[K - 1] \
            + sum(tail[:, k].astype(jnp.float32) * w[k]
                  for k in range(K - 1))
        shifted = jnp.concatenate([tail[:, 1:], x0[:, None]], axis=1)
        return out[:, None], jnp.where(q_lens[:, None, None] > 0, shifted,
                                       tail)
    xp = jnp.concatenate([tail, x.astype(tail.dtype)], axis=1)
    out = bias() + sum(
        xp[:, k:k + Q].astype(jnp.float32) * w[k] for k in range(K))
    idx = q_lens[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
    return out, jnp.take_along_axis(xp, idx[:, :, None], axis=1)
