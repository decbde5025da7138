"""Selective state-space sequence state for ragged serving: Mamba-1's
diagonal recurrence and Mamba-2's scalar-decay (SSD) one.

A Mamba layer carries, a sequence, a recurrent state ``h`` of
``[d_state, d_inner]`` float32 and the last ``d_conv - 1`` inputs of its
causal convolution.  Neither grows with the context and neither can be
cut into pages of positions, so they live in a STATE POOL beside the page
pool (``inference/v2/ragged/kv_cache.py::StatePool``): one slot a
sequence,

    h    : [L_ssm, slots + 1, d_state, d_inner]      float32
    conv : [L_ssm, slots + 1, (d_conv - 1) * channels / 128, 128] bfloat16

``d_inner`` minor (5120 is 40 lane tiles, 16 is not one); the tail's
``[d_conv - 1, channels]`` rows laid end to end, oldest first, and cut
into ROWS OF ONE LANE TILE (:func:`conv_slot_shape`: 120 rows at 5,120
channels; the row count rounded up to a sublane tile, 270 -> 272 at
11,520, which is what the chip pads it to anyway).  A second-minor dim of
3 is one the chip's compiler lays out one way at a program's edge and
another inside its loop (two copies of the pool a step;
``tests/test_chip_compile.py``); rows of ONE tile are what lets
:func:`conv_tail_decode` put eight SEQUENCES on a register's sublanes by a
strided load (a slot's row ``n`` of eight slots copied side by side), so
that a decode row's tail is convolved and shifted where it lies, with no
gather and no re-layout.  A bfloat16 row pair ``(2n, 2n + 1)`` is one
row of 32-bit words: a tap is a whole number of them at every width
that is a multiple of 256.  The last slot is the scratch slot that rows
with nothing to write are sent to (as the page pools have their null
page).  Both pools are donated at the jit boundary and carried through
the layer loop; every op here takes the WHOLE pool and a layer index and
updates ``pool[layer, slot]`` in place.

* :func:`ssm_scan` — Mamba-1's recurrence, for rows of ``Q`` tokens from
  each row's slot: ``h_t = exp(dt_t (x) A) * h_{t-1} + (dt_t * x_t) (x)
  B_t``, ``y_t = h_t C_t + D * x_t``.  Mamba-1's ``A`` is a full
  ``[d_inner, d_state]`` diagonal, one decay a (channel, state) PAIR, so a
  chunk has no matrix form: the recurrence is elementwise on the vector
  unit, sequential in ``t``, parallel over ``d_inner``.  On a TPU it is a
  Pallas kernel named ``ssm_state_update_decode`` (Q = 1: one token a row,
  the state read and written once) or ``ssm_scan_prefill`` (Q > 1), both
  pools aliased input -> output, the slot ids riding the BlockSpec index
  maps through scalar prefetch.  The scan kernel's call also writes the
  row's new convolution tail into ``conv[layer, slot]`` (an XLA scatter
  did it row by row: 30 KB a row in 1.1 us, a fifth of the decode step,
  PERF.md PR 34); the update kernel has no tail to carry
  (:func:`conv_step` wrote it).  The jnp form is the semantics ground truth
  and the CPU path.
* :func:`ssd_scan` — Mamba-2's: ``H`` heads of ``P`` channels (``d_inner =
  H P``), ONE scalar decay a head and step, ``a_t = exp(dt_t A)``, and
  ``B``, ``C`` shared by the ``H / G`` heads of a group: ``S_t = a_t
  S_{t-1} + (dt_t x_t) (x) B_t``, ``y_t = S_t C_t + D x_t``, a head's state
  ``[P, N]`` held as the slot's lanes ``h P .. h P + P - 1`` of ``[N, H
  P]``.  One decay a head is what gives a chunk a MATRIX form (the gated
  delta rule's chunk in ``ops/delta_rule.py`` rests on the same): inside a
  chunk ``Y = (L o (C B^T)) (dt X)`` with ``L_ts = prod_{s<r<=t} a_r``, on
  the matrix unit.  Kernels ``ssd_state_update_decode`` (Q = 1) and
  ``ssd_chunk_prefill`` (Q > 1), the pools, the tail and the ``fresh``
  rule as above.
* :func:`conv_step` — the depthwise causal convolution over the slot's
  tail and the new tokens.  At one token a row (a decode row) ONE kernel,
  ``conv_tail_decode``, reads each row's tail from its slot, convolves,
  and writes the tail shifted by the new input back to the slot: the
  tail's bytes move twice (PERF.md PR 55).  At ``Q > 1`` the tails are
  one XLA gather and the tail of the row's TRUE last tokens goes to the
  scan kernel to write.

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
from .delta_rule import _HP, _NT, _TN, chunk_len

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


def conv_slot_shape(width: int) -> Tuple[int, int]:
    """``(rows, lanes)`` a slot's convolution tail of ``width`` values is
    laid out in (module docstring): rows of one lane tile, a whole number
    of sublane tiles of them (the last rows past ``width`` are padding
    nothing reads); a width that is no whole number of lane tiles (a debug
    model's) is cut into 8 rows, or left as one."""
    if width % 128 == 0:
        return -(-width // (128 * 8)) * 8, 128
    rows = 8 if width % 8 == 0 else 1
    return rows, width // rows


def slot_tails(slots_block: jax.Array, positions: int,
               channels: int) -> jax.Array:
    """Slots' tails as they lie in the pool ``[S, rows, lanes]`` ->
    ``[S, positions, channels]``."""
    S = slots_block.shape[0]
    return slots_block.reshape(S, -1)[:, :positions * channels].reshape(
        S, positions, channels)


def tails_to_slots(conv_pool: jax.Array, tails: jax.Array) -> jax.Array:
    """Tails ``[S, positions, channels]`` as ``conv_pool``'s slots hold
    them, ``[S, rows, lanes]`` in the pool's dtype."""
    S = tails.shape[0]
    rows, lanes = conv_pool.shape[2:]
    flat = tails.astype(conv_pool.dtype).reshape(S, -1)
    return jnp.pad(flat, ((0, 0), (0, rows * lanes - flat.shape[1]))
                   ).reshape(S, rows, lanes)


def write_tails(conv_pool, layer, slots, new_tail):
    """The conv pool with the rows' new tails at their slots, the jnp form;
    the pool as it is where there are none (``None``: a decode row's was
    written by :func:`conv_step`)."""
    if new_tail is None:
        return conv_pool
    return conv_pool.at[layer, slots].set(tails_to_slots(conv_pool, new_tail))


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
            write_tails(conv_pool, layer, slots, new_tail))


def _ssm_kernel(l_ref, slot_ref, fresh_ref, dt_ref, x_ref, b_ref, c_ref,
                a_ref, d_ref, *rest, q_len):
    """One (row, block of ``d_inner``) grid step: the row's state block
    ``[N, blk]`` read, ``q_len`` steps of the recurrence, the state
    written back to the same address (the pool is aliased input ->
    output, so nothing else of it moves).  ``b_ref`` / ``c_ref`` hold B
    and C as ``[N, Q]`` (the state dim on sublanes, as in ``h``): step
    ``t`` takes its column by a masked lane sum.  ``rest``: (state; y,
    state out), or with new tails to write (tail, state, conv pool; y,
    state, tail out): the row's new convolution tail goes to ``conv[layer,
    slot]`` whole (that pool is aliased too and never read here)."""
    del l_ref, slot_ref
    s = pl.program_id(0)
    if len(rest) == 3:
        h_ref, y_ref, hout_ref = rest
    else:
        tail_ref, h_ref, _, y_ref, hout_ref, tout_ref = rest
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
    in_specs = [tokens, tokens, cols, cols,
                pl.BlockSpec((N, blk), lambda s, j, l, sl, fr: (0, j)),
                pl.BlockSpec((1, blk), lambda s, j, l, sl, fr: (0, j))]
    operands = [dt.astype(f32), x.astype(f32), B.astype(f32).swapaxes(1, 2),
                C.astype(f32).swapaxes(1, 2), A_t.astype(f32),
                D.astype(f32).reshape(1, d)]
    pools, pool_specs = [h_pool], [state]
    if new_tail is not None:
        rows, width = conv_pool.shape[2:]
        in_specs.append(pl.BlockSpec((None, rows, width),
                                     lambda s, j, l, sl, fr: (s, 0, 0)))
        operands.append(tails_to_slots(conv_pool, new_tail))
        pools.append(conv_pool)
        pool_specs.append(pl.BlockSpec(
            (None, None, rows, width),
            lambda s, j, l, sl, fr: (l[0], sl[s], 0, 0)))
    first = 3 + len(operands)           # operands count the 3 prefetched
    y, *pools = pl.pallas_call(
        functools.partial(_ssm_kernel, q_len=Q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(S, d // blk),
            in_specs=in_specs + [state] + [
                pl.BlockSpec(memory_space=pl.ANY)] * (len(pools) - 1),
            out_specs=[tokens] + pool_specs),
        out_shape=[jax.ShapeDtypeStruct(y_shape, f32)] + [
            jax.ShapeDtypeStruct(a.shape, a.dtype) for a in pools],
        input_output_aliases={first + i: 1 + i for i in range(len(pools))},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        # ``^ssm_`` finds both and no pattern of the attention or cache
        # write kernels does (benchmark/metrics)
        name="ssm_state_update_decode" if Q == 1 else "ssm_scan_prefill",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
      fresh.astype(jnp.int32), *operands, *pools)
    if new_tail is not None:
        conv_pool = pools[1]
    return y.reshape(S, Q, d), pools[0], conv_pool


def ssm_scan(h_pool: jax.Array, conv_pool: jax.Array, layer,
             slots: jax.Array, fresh: jax.Array, dt: jax.Array,
             x: jax.Array, B: jax.Array, C: jax.Array, A_t: jax.Array,
             D: jax.Array, new_tail: jax.Array, *,
             use_kernel: Optional[bool] = None, interpret: bool = False
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``Q`` steps of the recurrence for ``S`` rows from their slots, and
    the rows' new convolution tails written.

    h_pool : [L, slots + 1, N, d], the state's dtype
    conv_pool : [L, slots + 1, rows, lanes] (:func:`conv_slot_shape`)
    layer  : int32 scalar (the layer loop's counter among the state
             layers, or a constant)
    slots  : [S] int32, the scratch slot for a row with nothing to step
    fresh  : [S] bool, the row starts from a zero state
    dt, x  : [S, Q, d] (``dt`` after the softplus, 0 at padded positions)
    B, C   : [S, Q, N]
    A_t    : [N, d] = ``-exp(A_log)`` transposed;  D : [d]
    new_tail : [S, K - 1, d], :func:`conv_step`'s; None for decode rows
             (it wrote them: the conv pool is returned as it came)
    Returns (y [S, Q, d] float32, the updated h pool, the updated conv
    pool).  ``use_kernel`` None = auto (on TPU, or anywhere with
    ``interpret=True``)."""
    if use_kernel is None:
        use_kernel = interpret or on_tpu()
    impl = functools.partial(ssm_scan_kernel, interpret=interpret) \
        if use_kernel else ssm_scan_reference
    return impl(h_pool, conv_pool, layer, slots, fresh, dt, x, B, C, A_t,
                D, new_tail)


#: bytes of VMEM one call of :func:`conv_tail_decode` may hold (two steps'
#: tails in and out, the rows' blocks twice): inside the default scoped
#: limit of 16 MiB (:data:`TOKEN_BLOCK_BUDGET`'s note)
CONV_STEP_BYTES = 10 * 2 ** 20
#: sequences of one grid step of it, at most
CONV_STEP_ROWS = 32


def _conv_tail_kernel(l_ref, slot_ref, fresh_ref, ql_ref, x_ref, w_ref, b_ref,
                      pool_ref, out_ref, pout_ref, tin, tout, rsem, wsem, *,
                      rows, has_bias):
    """One grid step: ``rb`` sequences' convolution at one new token each,
    their tails read from and written back to their slots of the pool,
    which stays in HBM (aliased input -> output).  A slot is ``rows`` rows
    of one lane tile, the ``K - 1`` taps end to end, oldest first; it is
    ONE copy to its place in ``tin`` and one from ``tout``, the next
    step's slots in flight under this step's arithmetic and the last
    step's on their way back (two buffers each way).  The arithmetic takes
    EIGHT sequences at a time on a register's sublanes (sixteen on two's
    where the inputs are 16-bit: a tile of theirs): row ``n`` of their
    slots is one strided load (the slots lie ``held`` rows apart).
    A 16-bit pool is read as 32-bit words, a word row the row pair ``(2n,
    2n + 1)``, low half first: a tap is a whole number of word rows, so
    the shift by one token moves whole words and only the newest tap is
    packed.  A ``fresh`` row's slot is zeroed where it landed; a row with
    ``q_lens == 0`` writes nothing back."""
    del pool_ref      # aliased: read and written through ``pout_ref``
    g, steps = pl.program_id(0), pl.num_programs(0)
    layer = l_ref[0]
    rb, c = x_ref.shape
    K = w_ref.shape[0]
    # sequences the arithmetic takes at a time: a register's sublanes, two
    # registers' where the inputs come in 16 bits (a tile of theirs)
    group = min(8 * 4 // x_ref.dtype.itemsize, rb)
    held = tin.shape[1] // rb                   # rows a slot has in VMEM
    pack = 4 // tin.dtype.itemsize              # values of a 32-bit word
    words, held_w = rows // pack, held // pack
    span = 128 * pack                           # channels of a word row
    tap_w = c // span                           # word rows of one tap
    f32, u32 = jnp.float32, jnp.uint32

    def reads(step, buf, wait):
        def one(r, carry):
            copy = pltpu.make_async_copy(
                pout_ref.at[layer, slot_ref[step * rb + r]],
                tin.at[buf, pl.ds(pl.multiple_of(r * held, held), rows)],
                rsem.at[buf])
            copy.wait() if wait else copy.start()
            return carry
        jax.lax.fori_loop(0, rb, one, 0)

    def writes(step, buf, wait):
        def one(r, carry):
            row = step * rb + r

            @pl.when(ql_ref[row] > 0)
            def _():
                src = tout.at[buf, pl.ds(pl.multiple_of(r * held_w, held_w),
                                         words)]
                copy = pltpu.make_async_copy(
                    src.bitcast(tin.dtype) if pack > 1 else src,
                    pout_ref.at[layer, slot_ref[row]], wsem.at[buf])
                copy.wait() if wait else copy.start()
            return carry
        jax.lax.fori_loop(0, rb, one, 0)

    buf = jax.lax.rem(g, 2)

    @pl.when(g == 0)
    def _first():
        reads(0, 0, wait=False)

    @pl.when(g + 1 < steps)
    def _ahead():
        reads(g + 1, 1 - buf, wait=False)

    reads(g, buf, wait=True)

    @pl.when(g >= 2)
    def _drain():           # ``tout[buf]`` is the step before last's
        writes(g - 2, buf, wait=True)

    def zero_fresh(r, carry):
        @pl.when(fresh_ref[g * rb + r] > 0)
        def _():
            tin[buf, pl.ds(pl.multiple_of(r * held, held), held), :] = \
                jnp.zeros((held, 128), tin.dtype)
        return carry
    jax.lax.fori_loop(0, rb, zero_fresh, 0)

    tin_w = tin.bitcast(u32) if pack > 1 else tin   # [2, rb * held_w, 128]

    def halves(word):
        """The float32 values of a word row, low half first."""
        if pack == 1:
            return [word.astype(f32)]
        return [pltpu.bitcast(word << 16, f32),
                pltpu.bitcast(word & jnp.uint32(0xffff0000), f32)]

    def one_group(k, carry):
        first = pl.multiple_of(k * group, group)
        seqs = pl.ds(first, group)

        def word_rows(n):
            return pl.ds(first * held_w + n, group, stride=held_w)

        def one_word_row(s, carry):
            """Word row ``s`` of every tap: ``span`` channels."""
            old = [tin_w[buf, word_rows(j * tap_w + s), :]
                   for j in range(K - 1)]
            taps = [halves(word) for word in old]
            new = []
            for h in range(pack):
                lanes = pl.ds(pl.multiple_of(s * span + h * 128, 128), 128)
                # conv_step's jnp form, term by term
                x0 = x_ref[seqs, lanes].astype(tin.dtype).astype(f32)
                new.append(x0)
                acc = x0 * w_ref[K - 1:K, lanes]
                if has_bias:
                    acc = b_ref[:, lanes] + acc
                conv = taps[0][h] * w_ref[0:1, lanes]
                for j in range(1, K - 1):
                    conv = conv + taps[j][h] * w_ref[j:j + 1, lanes]
                out_ref[seqs, lanes] = acc + conv
            for j in range(1, K - 1):
                tout[buf, word_rows((j - 1) * tap_w + s), :] = old[j]
            tout[buf, word_rows((K - 2) * tap_w + s), :] = \
                new[0].astype(tout.dtype) if pack == 1 else \
                (pltpu.bitcast(new[0], u32) >> 16) | pltpu.bitcast(new[1], u32)
            return carry

        jax.lax.fori_loop(0, tap_w, one_word_row, 0)
        for n in range((K - 1) * tap_w, words):     # the padding: zeros
            tout[buf, word_rows(n), :] = jnp.zeros((group, 128), tout.dtype)
        return carry

    jax.lax.fori_loop(0, rb // group, one_group, 0)
    writes(g, buf, wait=False)

    @pl.when(g == steps - 1)
    def _last():
        writes(g, buf, wait=True)

        @pl.when(g >= 1)
        def _():
            writes(g - 1, 1 - buf, wait=True)


def _held_rows(rows: int) -> int:
    """Rows a slot of ``rows`` rows has in the kernel's buffers: whole
    tiles of a 16-bit dtype, an ODD number of them.  The strided loads
    step from slot to slot: at 128 rows apart (5,120 channels: 8 tiles)
    eight sequences' rows fell on the same banks and a call took 51.6 us
    for 39.4 at 144 (PERF.md PR 55); 6, 9, 17 and 18 tiles read alike."""
    return (-(-rows // 16) | 1) * 16


def conv_step_rows(S: int, rows: int, c: int, K: int, itemsize: int) -> int:
    """Sequences of one grid step of :func:`conv_tail_decode`, from the
    call's shapes: the largest power of two up to :data:`CONV_STEP_ROWS`
    whose buffers stay under :data:`CONV_STEP_BYTES`."""
    held = _held_rows(rows)
    rb = min(S, CONV_STEP_ROWS)
    while rb > 8 and (4 * rb * held * 128 * itemsize + 16 * rb * c
                      + 8 * (K + 1) * c) > CONV_STEP_BYTES:
        rb //= 2
    return rb


@functools.partial(jax.jit, static_argnames=("interpret",))
def conv_tail_decode(conv_pool, layer, slots, fresh, q_lens, x, w, b=None, *,
                     interpret: bool = False):
    """:func:`conv_step` at one token a row, in place
    (:func:`_conv_tail_kernel`): ``x`` ``[S, c]``; returns (``conv(x) + b``
    ``[S, c]`` float32, the conv pool with the rows' tails shifted).
    Jitted, so that the kernel's body is traced once a shape and not once
    a segment of every step program that has the shape."""
    S, c = x.shape
    K = w.shape[0]
    rows = conv_pool.shape[2]
    itemsize = conv_pool.dtype.itemsize
    rb = conv_step_rows(S, rows, c, K, itemsize)
    assert S % rb == 0, "row buckets are powers of two"
    held = _held_rows(rows)
    f32 = jnp.float32
    bias = jnp.zeros((c,), f32) if b is None else b
    seqs = pl.BlockSpec((rb, c), lambda g, *_: (g, 0))

    def whole(n):
        return pl.BlockSpec((n, c), lambda g, *_: (0, 0))

    return pl.pallas_call(
        functools.partial(_conv_tail_kernel, rows=rows,
                          has_bias=b is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(S // rb,),
            in_specs=[seqs, whole(K), whole(1),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[seqs, pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[
                pltpu.VMEM((2, rb * held, 128), conv_pool.dtype),
                pltpu.VMEM((2, rb * held * itemsize // 4, 128),
                           jnp.uint32 if itemsize < 4 else conv_pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct((S, c), f32),
                   jax.ShapeDtypeStruct(conv_pool.shape, conv_pool.dtype)],
        # operands count the 4 prefetched
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        # no pattern of the readers' finds it: not the update kernels'
        # (``^ssm_``, ``^ssd_``, ``^delta_``, ``^kda_``), not the copies'
        # (``^copy[._]``, ``dynamic-slice``), not the attention or cache
        # write kernels' (benchmark/metrics)
        name="conv_tail_decode",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
      fresh.astype(jnp.int32), q_lens.astype(jnp.int32),
      # as it comes where it is in the pool's dtype already (the rounding
      # to it is the identity) and whole tiles of it a grid step
      x if x.dtype == conv_pool.dtype and rb % (32 // itemsize) == 0
      else x.astype(f32),
      w.astype(f32), bias.astype(f32).reshape(1, c), conv_pool)


def _conv_kernel_takes(conv_pool, c: int, K: int) -> bool:
    """Whether a slot's layout is one :func:`_conv_tail_kernel` reads:
    rows of one lane tile, a tap a whole number of 32-bit word rows."""
    rows, lanes = conv_pool.shape[2:]
    itemsize = conv_pool.dtype.itemsize
    return (lanes == 128 and K > 1 and itemsize in (2, 4)
            and c % (128 * 4 // itemsize) == 0
            and rows * 128 >= (K - 1) * c)


def conv_step(conv_pool: jax.Array, layer, slots: jax.Array,
              fresh: jax.Array, q_lens: jax.Array, x: jax.Array,
              w: jax.Array, b: Optional[jax.Array] = None, *,
              use_kernel: Optional[bool] = None, interpret: bool = False
              ) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """The causal depthwise convolution of ``x`` ``[S, Q, d]`` behind each
    row's tail ``pool[layer, slot]`` (``[d_conv - 1, d]`` laid end to end
    in :func:`conv_slot_shape`; zero for a ``fresh`` row), ``w``
    ``[d_conv, d]`` (tap ``k`` weighs the input ``d_conv - 1 - k``
    positions back), ``b`` ``[d]`` (None: no bias).  The tail kept is
    that of the row's TRUE last tokens, ``q_lens`` of them new: padding
    to the ``Q`` bucket does not enter it.  Returns (``conv(x) + b`` in
    float32, the conv pool, the new tails).  At ``Q == 1`` the pool comes
    back with the rows' tails shifted by their new input (a row with
    ``q_lens == 0`` is at the scratch slot, whose content is nobody's) and
    there are no new tails (None); on a TPU that is
    :func:`conv_tail_decode` (``use_kernel`` None = auto: there, or
    anywhere with ``interpret=True``), else the jnp form below, which is
    the ground truth of both.  At ``Q > 1`` the pool comes back as it is
    and the new tails ``[S, d_conv - 1, d]`` are for the scan to write."""
    S, Q, c = x.shape
    K = w.shape[0]
    if use_kernel is None:
        use_kernel = interpret or on_tpu()
    if Q == 1 and use_kernel and _conv_kernel_takes(conv_pool, c, K):
        out, conv_pool = conv_tail_decode(
            conv_pool, layer, slots, fresh, q_lens, x[:, 0], w, b,
            interpret=interpret)
        return out[:, None], conv_pool, None
    tail = slot_tails(conv_pool[layer, slots], K - 1, c)
    tail = jnp.where(fresh[:, None, None], jnp.zeros_like(tail), tail)
    w = w.astype(jnp.float32)

    def bias():
        return 0.0 if b is None else b.astype(jnp.float32)

    if Q == 1:
        # one token a row: taps and shift on [S, d] arrays
        x0 = x[:, 0].astype(tail.dtype)
        out = bias() + x0.astype(jnp.float32) * w[K - 1] \
            + sum(tail[:, k].astype(jnp.float32) * w[k]
                  for k in range(K - 1))
        shifted = jnp.concatenate([tail[:, 1:], x0[:, None]], axis=1)
        return out[:, None], write_tails(conv_pool, layer, slots, jnp.where(
            q_lens[:, None, None] > 0, shifted, tail)), None
    xp = jnp.concatenate([tail, x.astype(tail.dtype)], axis=1)
    out = bias() + sum(
        xp[:, k:k + Q].astype(jnp.float32) * w[k] for k in range(K))
    idx = q_lens[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
    return out, conv_pool, jnp.take_along_axis(xp, idx[:, :, None], axis=1)


# -- Mamba-2 (SSD): one scalar decay a head, B and C a group of heads ---------

#: tokens a chunk of the matrix form takes at most: the published
#: ``chunk_size``.  A grid step holds ONE group's lanes of the state (``[N,
#: H P / G]``: 256 KB at the published widths) and the chunk's blocks of
#: that group, under 2 MB in all: far inside the default scoped VMEM
#: (:data:`TOKEN_BLOCK_BUDGET`'s note), so nothing asks for a shorter chunk
SSD_CHUNK = 128
#: a row bucket shorter than this is walked by the ``jnp`` form (a sublane
#: tile of positions is the least a chunk's blocks can be)
SSD_MIN_CHUNK = 8


def ssd_chunk_len(Q: int) -> int:
    """Tokens a chunk takes of a row bucket of ``Q``: the largest power of
    two up to :data:`SSD_CHUNK` that divides it (the delta rule's rule)."""
    return chunk_len(Q, SSD_CHUNK)


def ssd_scan_reference(h_pool, conv_pool, layer, slots, fresh, dt, x, B, C,
                       A, D, new_tail):
    """Mamba-2's recurrence as a plain ``lax.scan`` over positions (module
    docstring): (y ``[S, Q, H P]`` float32, the h pool, the conv pool)."""
    f32 = jnp.float32
    S, Q, H = dt.shape
    N = h_pool.shape[2]
    P, G = x.shape[-1] // H, B.shape[-1] // N
    h0 = h_pool[layer, slots].astype(f32).reshape(S, N, H, P)
    h0 = jnp.where(fresh[:, None, None, None], 0.0, h0)
    A, D = A.astype(f32), D.astype(f32)

    def heads(a):                   # [S, G N] -> [S, N, H]: a head's group's
        return jnp.repeat(a.reshape(S, G, N), H // G, axis=1).swapaxes(1, 2)

    def step(h, inp):
        dt_t, x_t, b_t, c_t = inp           # [S, H] [S, H, P] [S, G N] x2
        h = jnp.exp(dt_t * A)[:, None, :, None] * h \
            + heads(b_t)[..., None] * (dt_t[..., None] * x_t)[:, None]
        return h, jnp.sum(h * heads(c_t)[..., None], axis=1) \
            + D[:, None] * x_t

    h, ys = jax.lax.scan(step, h0, (
        dt.astype(f32).swapaxes(0, 1),
        x.astype(f32).reshape(S, Q, H, P).swapaxes(0, 1),
        B.astype(f32).swapaxes(0, 1), C.astype(f32).swapaxes(0, 1)))
    return (ys.swapaxes(0, 1).reshape(S, Q, H * P),
            h_pool.at[layer, slots].set(
                h.reshape(S, N, H * P).astype(h_pool.dtype)),
            write_tails(conv_pool, layer, slots, new_tail))


def _ssd_decode_kernel(l_ref, slot_ref, fresh_ref, a_ref, x_ref, b_ref,
                       c_ref, h_ref, y_ref, hout_ref, *, groups):
    """One row: its whole state ``[N, H P]`` read, stepped once and written
    back to the same address (the pool is aliased input -> output), walked
    a GROUP's lanes at a time: the group's ``B`` and ``C`` are columns
    ``[N, 1]`` of ``b_ref`` / ``c_ref`` (``[N, G]``, the state dim on
    sublanes as in ``h``) spread over its lanes, the decay ``a`` and ``dt
    x`` come spread over the lanes already, as ``[8, H P]`` blocks of 8
    rows (:func:`_ssm_kernel`'s decode form).  The row's convolution tail
    is not this kernel's (:func:`conv_step` wrote it)."""
    del l_ref, slot_ref
    s = pl.program_id(0)
    r = s % a_ref.shape[0]
    fresh = fresh_ref[s] > 0
    width = h_ref.shape[1] // groups
    for g in range(groups):
        lanes = slice(g * width, (g + 1) * width)
        row = (pl.ds(r, 1), lanes)
        h = h_ref[:, lanes].astype(jnp.float32)
        h = jnp.where(fresh, jnp.zeros_like(h), h) * a_ref[row] \
            + b_ref[:, g:g + 1] * x_ref[row]
        y_ref[row] = jnp.sum(h * c_ref[:, g:g + 1], axis=0, keepdims=True)
        hout_ref[:, lanes] = h.astype(hout_ref.dtype)


def _ssd_chunk_kernel(l_ref, slot_ref, fresh_ref, x_ref, cs_ref, b_ref, c_ref,
                      tail_ref, h_ref, conv_ref, y_ref, hout_ref, tout_ref,
                      *, heads, P):
    """One (row, group, chunk) grid step: the chunk's matrix form for the
    ``heads`` heads of one group, from and to the group's lanes of the
    state in ``hout_ref``, which stays in VMEM across the row's chunks (the
    innermost grid dim) and goes back to the row's slot after the last; the
    first chunk takes it from the slot, or zeros for a fresh row.  ``cs_ref``
    ``[heads, C]``: the running sums of ``log a = dt A`` inside the chunk, a
    head a row.  With ``M = tril(C B^T)`` (the group's) and ``L_ts = exp(cs_t
    - cs_s)`` (a head's), head ``h``::

        Y_h  = (L_h o M) (dt X)_h + exp(cs) o (C S_h)
        S_h' = exp(cs_last) S_h + (B o exp(cs_last - cs))^T (dt X)_h

    A head is ``P`` = 64 lanes, half a lane tile: the heads are taken a
    PAIR a tile, each head's product over the tile's 128 lanes with the
    other head's lanes of ``dt X`` zeroed, and the pair's two summed."""
    del l_ref, slot_ref, conv_ref
    s, j, c = (pl.program_id(i) for i in range(3))
    f32 = jnp.float32

    @pl.when(c == 0)
    def _start():
        st = h_ref[...]
        hout_ref[...] = jnp.where(fresh_ref[s] > 0, jnp.zeros_like(st), st)

    @pl.when((c == 0) & (j == 0))
    def _tail():
        tout_ref[...] = tail_ref[...]

    Cn = x_ref.shape[0]
    rc = cs_ref[...]                                        # [heads, C]
    cols = rc.T                                             # [C, heads]
    b, cm = b_ref[...], c_ref[...]                          # [C, N]
    r_i = jax.lax.broadcasted_iota(jnp.int32, (Cn, Cn), 0)
    c_i = jax.lax.broadcasted_iota(jnp.int32, (Cn, Cn), 1)
    lower = r_i >= c_i
    m = jnp.where(lower, jax.lax.dot_general(cm, b, _NT, **_HP), 0.0)
    per = max(128 // P, 1)                                  # heads a tile
    tile = per * P
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    for p in range(heads // per):
        lanes = slice(p * tile, (p + 1) * tile)
        xp = x_ref[:, lanes]                                # [C, tile]
        st = hout_ref[:, lanes].astype(f32)                 # [N, tile]
        y = jnp.zeros((Cn, tile), f32)
        new = jnp.zeros_like(st)
        grow = jnp.zeros((Cn, tile), f32)                   # exp(cs), by lane
        keep = jnp.zeros((1, tile), f32)                    # exp(cs_last)
        for k in range(per):
            h = p * per + k
            mine = (lane >= k * P) & (lane < (k + 1) * P)
            col, row = cols[:, h:h + 1], rc[h:h + 1]        # [C, 1] [1, C]
            last = row[:, Cn - 1:Cn]                        # [1, 1]
            xh = jnp.where(mine, xp, 0.0)
            ratio = jnp.where(lower, jnp.exp(jnp.minimum(col - row, 0.0)),
                              0.0)
            y = y + jnp.dot(ratio * m, xh, **_HP)
            new = new + jax.lax.dot_general(
                b * jnp.exp(last - col), xh, _TN, **_HP)
            grow = jnp.where(mine, jnp.exp(col), grow)
            keep = jnp.where(mine, jnp.exp(jnp.broadcast_to(
                last, (1, tile))), keep)
        y_ref[:, lanes] = y + grow * jnp.dot(cm, st, **_HP)
        hout_ref[:, lanes] = (keep * st + new).astype(hout_ref.dtype)


def ssd_scan_kernel(h_pool, conv_pool, layer, slots, fresh, dt, x, B, C, A,
                    D, new_tail, *, interpret: bool = False):
    """Pallas form of :func:`ssd_scan_reference`, in place: the update
    kernel at ``Q = 1``, the chunked matrix form behind it.  What is
    elementwise over the tokens (the decay, ``dt x``, the running sums,
    ``D x``) is XLA's, around the call."""
    S, Q, H = dt.shape
    W = x.shape[-1]
    P, N = W // H, h_pool.shape[2]
    G = B.shape[-1] // N
    f32 = jnp.float32
    dt, x = dt.astype(f32), x.astype(f32)
    la = dt * A.astype(f32)                                 # log a, [S, Q, H]
    dtx = (dt[..., None] * x.reshape(S, Q, H, P)).reshape(S, Q, W)
    skip = jnp.repeat(D.astype(f32), P) * x
    prefetch = (jnp.asarray(layer, jnp.int32).reshape(1),
                slots.astype(jnp.int32), fresh.astype(jnp.int32))
    if Q == 1:
        assert new_tail is None, "a decode row's tail is conv_step's"
        rb = min(8, S)
        assert S % rb == 0, "row buckets are powers of two"
        token = pl.BlockSpec((rb, W), lambda s, l, sl, fr: (s // rb, 0))
        cols = pl.BlockSpec((None, N, G), lambda s, l, sl, fr: (s, 0, 0))
        state = pl.BlockSpec((None, None, N, W),
                             lambda s, l, sl, fr: (l[0], sl[s], 0, 0))
        y, h_pool = pl.pallas_call(
            functools.partial(_ssd_decode_kernel, groups=G),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(S,),
                in_specs=[token, token, cols, cols, state],
                out_specs=[token, state]),
            out_shape=[jax.ShapeDtypeStruct((S, W), f32),
                       jax.ShapeDtypeStruct(h_pool.shape, h_pool.dtype)],
            # operands count the 3 prefetched
            input_output_aliases={7: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            # ``^ssd_`` finds both kernels and no pattern of the attention,
            # cache-write, Mamba-1 or delta-rule kernels does
            name="ssd_state_update_decode",
            interpret=interpret,
        )(*prefetch, jnp.repeat(jnp.exp(la[:, 0]), P, axis=-1), dtx[:, 0],
          B.astype(f32).reshape(S, G, N).swapaxes(1, 2),
          C.astype(f32).reshape(S, G, N).swapaxes(1, 2), h_pool)
        return y.reshape(S, 1, W) + skip, h_pool, conv_pool

    rows, width = conv_pool.shape[2:]
    tail_in = tails_to_slots(conv_pool, new_tail)
    out_shape = [None, jax.ShapeDtypeStruct(h_pool.shape, h_pool.dtype),
                 jax.ShapeDtypeStruct(conv_pool.shape, conv_pool.dtype)]
    Cn = ssd_chunk_len(Q)
    hg, gw = H // G, W // G
    # the running sums of log a inside each chunk, a head a row:
    # [S, G, chunks, H / G, C]
    cs = jnp.cumsum(la.reshape(S, Q // Cn, Cn, G, hg), axis=2).transpose(
        0, 3, 1, 4, 2)
    token = pl.BlockSpec((None, Cn, gw),
                         lambda s, j, c, l, sl, fr: (s, c, j))
    group = pl.BlockSpec((None, Cn, N), lambda s, j, c, l, sl, fr: (s, c, j))
    state = pl.BlockSpec((None, None, N, gw),
                         lambda s, j, c, l, sl, fr: (l[0], sl[s], 0, j))
    tail = pl.BlockSpec((None, None, rows, width),
                        lambda s, j, c, l, sl, fr: (l[0], sl[s], 0, 0))
    out_shape[0] = jax.ShapeDtypeStruct((S, Q, W), f32)
    y, h_pool, conv_pool = pl.pallas_call(
        functools.partial(_ssd_chunk_kernel, heads=hg, P=P),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(S, G, Q // Cn),
            in_specs=[token,
                      pl.BlockSpec((None, None, None, hg, Cn),
                                   lambda s, j, c, l, sl, fr:
                                   (s, j, c, 0, 0)),
                      group, group,
                      pl.BlockSpec((None, rows, width),
                                   lambda s, j, c, l, sl, fr: (s, 0, 0)),
                      state, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[token, state, tail]),
        out_shape=out_shape,
        input_output_aliases={8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        name="ssd_chunk_prefill",
        interpret=interpret,
    )(*prefetch, dtx, cs, B.astype(f32), C.astype(f32), tail_in, h_pool,
      conv_pool)
    return y + skip, h_pool, conv_pool


def ssd_scan(h_pool: jax.Array, conv_pool: jax.Array, layer,
             slots: jax.Array, fresh: jax.Array, dt: jax.Array,
             x: jax.Array, B: jax.Array, C: jax.Array, A: jax.Array,
             D: jax.Array, new_tail: jax.Array, *,
             use_kernel: Optional[bool] = None, interpret: bool = False
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``Q`` steps of Mamba-2's recurrence for ``S`` rows from their
    slots, and the rows' new convolution tails written.

    h_pool : [L, slots + 1, N, H P], the state's dtype (head ``h``'s
             ``[N, P]`` at lanes ``h P ..``)
    conv_pool : [L, slots + 1, rows, lanes] (:func:`conv_slot_shape` of
             (K - 1) * (H P + 2 G N))
    layer  : int32 scalar (the layer's index among the Mamba-2 layers)
    slots  : [S] int32, the scratch slot for a row with nothing to step
    fresh  : [S] bool, the row starts from a zero state
    dt     : [S, Q, H] (after the softplus, 0 at padded positions)
    x      : [S, Q, H P];  B, C : [S, Q, G N], head ``h`` reads group
             ``h // (H / G)``
    A      : [H] = ``-exp(A_log)``;  D : [H]
    new_tail : [S, K - 1, H P + 2 G N], :func:`conv_step`'s; None for
             decode rows (it wrote them)
    Returns (y [S, Q, H P] float32, the updated h pool, the updated conv
    pool).  ``use_kernel`` None = auto (on TPU, or anywhere with
    ``interpret=True``); a row bucket whose chunk would be shorter than
    :data:`SSD_MIN_CHUNK` is walked by the reference."""
    if use_kernel is None:
        use_kernel = interpret or on_tpu()
    Q = dt.shape[1]
    if not use_kernel or (Q > 1 and ssd_chunk_len(Q) < SSD_MIN_CHUNK):
        impl = ssd_scan_reference
    else:
        impl = functools.partial(ssd_scan_kernel, interpret=interpret)
    return impl(h_pool, conv_pool, layer, slots, fresh, dt, x, B, C, A, D,
                new_tail)
