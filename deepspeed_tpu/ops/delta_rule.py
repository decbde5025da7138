"""Gated delta rule (Gated DeltaNet linear attention) sequence state for
ragged serving.

A gated delta-rule layer carries, a sequence and head ``h``, a MATRIX
state ``S_h`` of ``[d_k, d_v]`` float32 and the last ``conv - 1`` inputs of
its causal convolution (over q, k AND v).  Like a Mamba layer's
(``ops/ssm.py``, whose pool and conventions these are) neither grows with
the context: they live in one slot of the state pool
(``inference/v2/ragged/kv_cache.py::StatePool``),

    state : [L_delta, slots + 1, d_k, H * d_v]                 float32
    conv  : [L_delta, slots + 1, rows, 128]  (``ops/ssm.py::conv_slot_shape``
            of (conv - 1) * H * (2 d_k + d_v) values)

head ``h``'s state the lanes ``[h d_v, (h + 1) d_v)`` of the minor dim (30
x 192 = 5760 is 45 lane tiles), the scratch slot last.  Per token, with
``q`` and ``k`` l2-normalised by the caller (``q`` scaled), ``alpha =
exp(g)`` in (0, 1] and ``beta`` in (0, 2)::

    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

One decay A HEAD (a scalar, where Mamba-1's is a full ``[d_state,
d_inner]`` diagonal) is what gives a chunk of ``C`` tokens a matrix form.
Write ``u_t = beta_t (v_t - alpha_t S_{t-1}^T k_t)``, so that ``S_t =
alpha_t S_{t-1} + k_t u_t^T``, and ``gamma_t`` the running product of the
chunk's ``alpha``.  Then with ``A[t, i] = beta_t (gamma_t / gamma_i) (k_t .
k_i)`` for ``i < t``::

    (I + A) U = diag(beta) V - diag(beta gamma) K S_0
    O   = diag(gamma) Q S_0 + (M . Q K^T) U,   M[t, i] = gamma_t / gamma_i, i <= t
    S_C = gamma_C S_0 + (diag(gamma_C / gamma) K)^T U

``K K^T``, ``Q K^T``, one unit-lower-triangular ``C x C`` solve and ``[C,
d_k] x [d_k, d_v]`` products into and out of the state: matmuls, not a
token-by-token walk on the vector unit.  ``A`` is strictly lower
triangular, so ``(I + A)^-1 = (I - A)(I + A^2)(I + A^4)...``, ``log2 C``
squarings (:func:`_chunk`).  The ratios are taken as ``exp`` of
differences of the running sum of ``g = log alpha``, never as a quotient.

* :func:`delta_rule` — ``Q`` tokens a row from and to each row's slot, a
  prompt's new convolution tail written by the same call (a decode row's
  is written by the convolution, ``ops/ssm.py::conv_step``).  On a TPU a
  Pallas kernel named ``delta_state_update_decode`` (Q = 1: the row's
  whole state read,
  decayed, corrected by the rank-one term, read out and written back in
  place, on the vector unit: one token is no matmul) or
  ``delta_chunk_prefill`` (Q > 1: the matrix form, chunk by chunk, the
  state carried in the output block), the pools they write aliased input
  -> output, slot ids by scalar prefetch.  :func:`delta_rule_reference`,
  the plain ``lax.scan`` over positions, is the semantics ground truth and
  the CPU path.

A DECAY A KEY CHANNEL (Kimi-delta, KDA: ``g`` of one more dimension, ``[..,
H, dk]``) is the same recurrence with ``Diag(alpha_t)`` over the state's
rows in place of the scalar, ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t)
S_{t-1} + beta_t k_t v_t^T``; the head-scalar rule is its broadcast case.
The same two kernels serve it, under the names ``kda_state_update_decode``
and ``kda_chunk_prefill``: the update kernel takes ``alpha`` as it takes
the keys (``[dk, H]`` columns spread over a head's lanes); the ratios of
the chunk form differ by channel and move INSIDE the products
(:func:`_chunk_channel`), which bounds a chunk at
:data:`MAX_CHANNEL_CHUNK` tokens under a gate bounded below.

A ``fresh`` row (position 0) starts from a zero state whatever its slot
held.  A padded position has ``g = 0`` and ``beta = 0`` (``alpha = 1``,
``u = 0``) and moves nothing.  The convolution is ``ops/ssm.py::conv_step``
over the concatenated q, k, v channels.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..accelerator import on_tpu

#: tokens of one chunk of the matrix form, at most: the solve is
#: ``2 log2(C)`` products of ``C x C`` matrices, which pass the chunk's
#: other products in cost above 64
MAX_CHUNK = 64
#: tokens of one chunk under a decay a key channel, at most:
#: :func:`_chunk_channel` takes ``exp(-G)`` of the running sum of ``g``,
#: which stays inside float32 only while ``C * max |g| < 88``.  16 is what
#: a gate bounded below by -5 allows (KDA's ``kda_lower_bound``)
MAX_CHANNEL_CHUNK = 16
#: the shortest chunk the prefill kernel takes (a sublane tile of its
#: transposes); a shorter row bucket is walked token by token
MIN_CHUNK = 8
#: heads one grid step of the prefill kernel holds, at most (its body is
#: unrolled over them)
MAX_HEAD_BLOCK = 8

_HP = dict(preferred_element_type=jnp.float32,
           precision=jax.lax.Precision.HIGHEST)
_NT = (((1,), (1,)), ((), ()))          # a @ b^T
_TN = (((0,), (0,)), ((), ()))          # a^T @ b


def chunk_len(Q: int, most: int = MAX_CHUNK) -> int:
    """Tokens a chunk of the matrix form takes of a row bucket of ``Q``:
    the largest power of two up to ``most`` that divides it."""
    C = 1
    while C * 2 <= most and Q % (C * 2) == 0:
        C *= 2
    return C


def _lane_groups(heads: int, dv: int) -> list:
    """Divisors ``n`` of ``heads`` whose ``n * dv`` lanes are whole lane
    tiles, ascending; ``[heads]`` (the whole minor dim) where none is."""
    return [n for n in range(1, heads)
            if heads % n == 0 and (n * dv) % 128 == 0] or [heads]


def _decode_group(heads: int, dv: int) -> int:
    """Heads of one lane group of the decode kernel: the smallest of
    :func:`_lane_groups` wider than one lane tile where there is one (the
    chip's compiler refuses the kernel's dynamic one-row loads beside a
    group of exactly 128 lanes: "dynamic load with unaligned indices")."""
    groups = _lane_groups(heads, dv)
    return next((n for n in groups if n * dv > 128), groups[0])


def delta_rule_reference(state_pool, conv_pool, layer, slots, fresh, q, k,
                         v, g, beta, new_tail):
    """The recurrence as a plain ``lax.scan`` over positions (module
    docstring): (o ``[S, Q, H * dv]`` float32, the state pool, the conv
    pool)."""
    f32 = jnp.float32
    S, Q, H, dk = q.shape
    dv = v.shape[-1] // H
    s0 = state_pool[layer, slots].astype(f32).reshape(S, dk, H, dv)
    s0 = jnp.where(fresh[:, None, None, None], 0.0, s0)
    ein = functools.partial(jnp.einsum,
                            precision=jax.lax.Precision.HIGHEST)

    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp     # [S,H,dk] x2, [S,H,dv], [S,H] x2
        # [S, H]: one decay a head; [S, H, dk]: one a key channel (KDA)
        s = s * (jnp.exp(g_t)[:, None, :, None] if g_t.ndim == 2
                 else jnp.exp(g_t).swapaxes(1, 2)[..., None])
        u = b_t[..., None] * (v_t - ein("shk,skhv->shv", k_t, s))
        s = s + ein("shk,shv->skhv", k_t, u)
        return s, ein("shk,skhv->shv", q_t, s)

    s, o = jax.lax.scan(step, s0, tuple(
        a.astype(f32).swapaxes(0, 1)
        for a in (q, k, v.reshape(S, Q, H, dv), g, beta)))
    from .ssm import write_tails
    return (o.swapaxes(0, 1).reshape(S, Q, H * dv),
            state_pool.at[layer, slots].set(
                s.reshape(S, dk, H * dv).astype(state_pool.dtype)),
            write_tails(conv_pool, layer, slots, new_tail))


def _unit_lower_inverse(n, diagonal):
    """``(I - N)^-1 = (I + N)(I + N^2)(I + N^4)...`` for a strictly lower
    triangular ``n`` ``[C, C]`` (``diagonal``: the mask ``r == c``)."""
    t, p = jnp.where(diagonal, 1.0, n), n
    for _ in range(max(n.shape[0].bit_length() - 2, 0)):
        p = jnp.dot(p, p, **_HP)
        t = t + jnp.dot(t, p, **_HP)
    return t


def _chunk(s0, q, k, v, g_row, b_row, g_col, b_col):
    """One head's chunk in matrix form (module docstring).  ``s0`` ``[dk,
    dv]``; ``q``, ``k`` ``[C, dk]``; ``v`` ``[C, dv]``; ``g`` the running
    sum of ``log alpha`` inside the chunk and ``beta``, each as a row ``[1,
    C]`` and as a column ``[C, 1]``.  Returns (o ``[C, dv]``, the state
    after the chunk)."""
    C = q.shape[0]
    r = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    # gamma_t / gamma_i where i <= t (the difference is <= 0 there)
    ratio = jnp.exp(jnp.minimum(g_col - g_row, 0.0))
    kk = jax.lax.dot_general(k, k, _NT, **_HP)
    qk = jax.lax.dot_general(q, k, _NT, **_HP)
    # (I + A)^-1 = (I + N)(I + N^2)(I + N^4)..., N = -A strictly lower
    t = _unit_lower_inverse(
        jnp.where(r > c, -(b_col * ratio) * kk, 0.0), r == c)
    decay = jnp.exp(g_col)
    u = jnp.dot(t, b_col * (v - decay * jnp.dot(k, s0, **_HP)), **_HP)
    o = decay * jnp.dot(q, s0, **_HP) \
        + jnp.dot(jnp.where(r >= c, ratio * qk, 0.0), u, **_HP)
    # gamma_C, spread over lanes first (the chip's compiler takes no
    # broadcast of one value over sublanes and lanes at once)
    g_last = g_row[:, C - 1:C]
    s_new = jnp.exp(jnp.broadcast_to(g_last, (1, s0.shape[1]))) * s0 \
        + jax.lax.dot_general(k * jnp.exp(g_last - g_col), u, _TN, **_HP)
    return o, s_new


def _chunk_channel(s0, q, k, v, G, b_col):
    """:func:`_chunk` under a decay a KEY CHANNEL (KDA): ``G`` ``[C, dk]``
    the running sum of ``g = log alpha`` inside the chunk.  The ratios
    ``gamma_t / gamma_i`` differ by channel, so they move INSIDE the
    products: with ``K+ = K e^G``, ``K- = K e^-G``, ``Q+ = Q e^G``::

        A = diag(beta) tril(K+ K-^T, -1)
        (I + A) U = diag(beta) (V - K+ S_0)
        O   = Q+ S_0 + tril(Q+ K-^T) U
        S_C = Diag(e^{G_C}) S_0 + (K- e^{G_C})^T U

    ``e^-G`` is why a chunk is :data:`MAX_CHANNEL_CHUNK` tokens at most; a
    masked product above the diagonal is at most ``dk e^{|G_C|}``, finite
    there too."""
    C = q.shape[0]
    r = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    grow, shrink = jnp.exp(G), jnp.exp(-G)
    kp, km, qp = k * grow, k * shrink, q * grow
    kk = jax.lax.dot_general(kp, km, _NT, **_HP)
    qk = jax.lax.dot_general(qp, km, _NT, **_HP)
    t = _unit_lower_inverse(jnp.where(r > c, -b_col * kk, 0.0), r == c)
    u = jnp.dot(t, b_col * (v - jnp.dot(kp, s0, **_HP)), **_HP)
    o = jnp.dot(qp, s0, **_HP) \
        + jnp.dot(jnp.where(r >= c, qk, 0.0), u, **_HP)
    # e^{G_C} a key channel: a column over the state's rows
    last = jnp.broadcast_to(G.T[:, C - 1:C], s0.shape)
    s_new = jnp.exp(last) * s0 + jax.lax.dot_general(
        km * grow[C - 1:C], u, _TN, **_HP)
    return o, s_new


def _chunk_rows(g, beta, C):
    """``g`` and ``beta`` ``[S, Q, H]`` as the prefill kernel takes them:
    ``[S, H, Q / C, 8, C]``, row 0 the running sum of ``g`` inside each
    chunk, row 1 ``beta`` (8 rows: a sublane tile)."""
    S, Q, H = g.shape

    def chunks(a):                                  # [S, Q, H] -> [S, n, C, H]
        return a.astype(jnp.float32).reshape(S, Q // C, C, H)

    rows = jnp.stack([jnp.cumsum(chunks(g), axis=2), chunks(beta)], axis=0)
    rows = rows.transpose(1, 4, 2, 0, 3)            # [S, H, n, 2, C]
    return jnp.pad(rows, ((0, 0),) * 3 + ((0, 6), (0, 0)))


def delta_chunk_reference(state_pool, conv_pool, layer, slots, fresh, q, k,
                          v, g, beta, new_tail):
    """:func:`delta_rule_reference` in the chunked matrix form, as plain
    ``jax.numpy`` (:func:`_chunk` vmapped over rows and heads, scanned over
    chunks): what the prefill kernel computes, for the tests."""
    f32 = jnp.float32
    S, Q, H, dk = q.shape
    dv = v.shape[-1] // H
    channel = g.ndim == 4
    C = chunk_len(Q, MAX_CHANNEL_CHUNK if channel else MAX_CHUNK)
    s0 = state_pool[layer, slots].astype(f32).reshape(S, dk, H, dv)
    s0 = jnp.where(fresh[:, None, None, None], 0.0, s0).transpose(0, 2, 1, 3)
    rows = _chunk_rows(jnp.zeros_like(beta) if channel else g, beta, C)

    def chunks(a):                                       # [S,Q,H,x]->[n,S,H,C,x]
        return a.astype(f32).reshape(S, Q // C, C, H, -1).transpose(
            1, 0, 3, 2, 4)

    def one(s, q_, k_, v_, rc, G=None):
        if channel:
            o, s = _chunk_channel(s, q_, k_, v_, G, rc[1][:, None])
        else:
            o, s = _chunk(s, q_, k_, v_, rc[0:1], rc[1:2], rc[0][:, None],
                          rc[1][:, None])
        return s, o

    def step(s, inp):
        return jax.vmap(jax.vmap(one))(s, *inp)

    s, o = jax.lax.scan(step, s0, (
        chunks(q), chunks(k), chunks(v.reshape(S, Q, H, dv)),
        rows.transpose(2, 0, 1, 3, 4))
        + ((jnp.cumsum(chunks(g), axis=3),) if channel else ()))
    o = o.transpose(1, 0, 3, 2, 4).reshape(S, Q, H * dv)
    from .ssm import write_tails
    return (o, state_pool.at[layer, slots].set(
        s.transpose(0, 2, 1, 3).reshape(S, dk, H * dv).astype(
            state_pool.dtype)),
        write_tails(conv_pool, layer, slots, new_tail))


def _decode_kernel(l_ref, slot_ref, fresh_ref, qT_ref, kT_ref, v_ref, a_ref,
                   b_ref, s_ref, o_ref, sout_ref, *, heads, dv, group,
                   channel=False):
    """One row: its whole state ``[dk, H * dv]`` read, stepped once and
    written back to the same address (the pool is aliased input ->
    output).  The state is walked in lane groups of ``group`` heads (whole
    lane tiles: two heads of 192 are three); inside a group head ``j``'s
    key and query columns ``[dk, 1]`` are spread over its ``dv`` lanes by a
    select, and the two contractions over ``dk`` are sublane sums.  ``v``,
    ``alpha`` and ``beta`` come spread over the lanes already, as ``[8,
    H * dv]`` blocks of 8 rows (``ops/ssm.py``'s decode form); under
    ``channel`` (a decay a key channel, KDA) ``alpha`` comes as the keys
    do, ``[dk, H]``, and scales the state's rows one by one.  The row's
    convolution tail is not this kernel's (``conv_step`` wrote it)."""
    del l_ref, slot_ref
    s = pl.program_id(0)
    r = s % v_ref.shape[0]
    fresh = fresh_ref[s] > 0
    width = group * dv
    lane = jax.lax.broadcasted_iota(jnp.int32, (s_ref.shape[0], width), 1)

    def spread(ref, first):
        out = jnp.broadcast_to(ref[:, first:first + 1], lane.shape)
        for j in range(1, group):
            out = jnp.where(lane >= j * dv, ref[:, first + j:first + j + 1],
                            out)
        return out

    for p in range(heads // group):
        lanes = slice(p * width, (p + 1) * width)
        row = (pl.ds(r, 1), lanes)
        st = s_ref[:, lanes].astype(jnp.float32)
        st = jnp.where(fresh, jnp.zeros_like(st), st) * (
            spread(a_ref, p * group) if channel else a_ref[row])
        key = spread(kT_ref, p * group)
        u = b_ref[row] * (v_ref[row]
                          - jnp.sum(key * st, axis=0, keepdims=True))
        st = st + key * u
        o_ref[row] = jnp.sum(spread(qT_ref, p * group) * st, axis=0,
                             keepdims=True)
        sout_ref[:, lanes] = st.astype(sout_ref.dtype)


def delta_state_update_decode(state_pool, conv_pool, layer, slots, fresh, q,
                              k, v, g, beta, new_tail, *,
                              interpret: bool = False):
    """Pallas form of :func:`delta_rule_reference` at ``Q = 1``, in
    place; under a decay a key channel (``g`` ``[S, 1, H, dk]``) the kernel
    is named ``kda_state_update_decode``."""
    assert new_tail is None, "a decode row's tail is conv_step's"
    channel = g.ndim == 4
    S, _, H, dk = q.shape
    W = v.shape[-1]
    dv = W // H
    f32 = jnp.float32
    rb = min(8, S)
    assert S % rb == 0, "row buckets are powers of two"

    def lanes(a):                       # [S, 1, H] -> [S, H * dv]
        return jnp.repeat(a[:, 0].astype(f32), dv, axis=-1)

    cols = pl.BlockSpec((None, dk, H), lambda s, l, sl, fr: (s, 0, 0))
    token = pl.BlockSpec((rb, W), lambda s, l, sl, fr: (s // rb, 0))
    state = pl.BlockSpec((None, None, dk, W),
                         lambda s, l, sl, fr: (l[0], sl[s], 0, 0))
    o, state_pool = pl.pallas_call(
        functools.partial(_decode_kernel, heads=H, dv=dv,
                          group=_decode_group(H, dv), channel=channel),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(S,),
            in_specs=[cols, cols, token, cols if channel else token, token,
                      state],
            out_specs=[token, state]),
        out_shape=[jax.ShapeDtypeStruct((S, W), f32),
                   jax.ShapeDtypeStruct(state_pool.shape, state_pool.dtype)],
        # operands count the 3 prefetched
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        # ``^delta_`` finds both kernels and no pattern of the attention,
        # cache-write or state-space kernels does (benchmark/metrics);
        # ``^kda_`` the two under a decay a key channel
        name="kda_state_update_decode" if channel
        else "delta_state_update_decode",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
      fresh.astype(jnp.int32), q[:, 0].astype(f32).swapaxes(1, 2),
      k[:, 0].astype(f32).swapaxes(1, 2), v.astype(f32).reshape(S, W),
      jnp.exp(g[:, 0].astype(f32)).swapaxes(1, 2) if channel
      else lanes(jnp.exp(g.astype(f32))), lanes(beta), state_pool)
    return o.reshape(S, 1, W), state_pool, conv_pool


def _prefill_kernel(l_ref, slot_ref, fresh_ref, q_ref, k_ref, v_ref,
                    rows_ref, *rest, heads, dv):
    """One (row, block of ``heads`` heads, chunk) grid step: the chunk's
    matrix form (:func:`_chunk`) a head, from and to the block's state in
    ``sout_ref``, which stays in VMEM across the row's chunks (the
    innermost grid dim) and goes back to the row's slot after the last.
    The first chunk takes the state from the slot, or zeros for a fresh
    row.  ``rest``: (tail, state, conv pool; o, state, tail out), behind
    the running sums ``G`` ``[heads, C, dk]`` under a decay a key channel
    (:func:`_chunk_channel`)."""
    del l_ref, slot_ref
    g_ref = rest[0] if len(rest) == 7 else None
    tail_ref, s_ref, _, o_ref, sout_ref, tout_ref = rest[-6:]
    s, j, c = (pl.program_id(i) for i in range(3))

    @pl.when(c == 0)
    def _start():
        st = s_ref[...]
        sout_ref[...] = jnp.where(fresh_ref[s] > 0, jnp.zeros_like(st), st)

    @pl.when((c == 0) & (j == 0))
    def _tail():
        tout_ref[...] = tail_ref[...]

    for h in range(heads):
        lanes = slice(h * dv, (h + 1) * dv)
        rc = rows_ref[h]                                    # [8, C]
        cols = rc.T
        if g_ref is None:
            o, st = _chunk(sout_ref[:, lanes].astype(jnp.float32), q_ref[h],
                           k_ref[h], v_ref[:, lanes], rc[0:1], rc[1:2],
                           cols[:, 0:1], cols[:, 1:2])
        else:
            o, st = _chunk_channel(
                sout_ref[:, lanes].astype(jnp.float32), q_ref[h], k_ref[h],
                v_ref[:, lanes], g_ref[h], cols[:, 1:2])
        o_ref[:, lanes] = o
        sout_ref[:, lanes] = st.astype(sout_ref.dtype)


def delta_chunk_prefill(state_pool, conv_pool, layer, slots, fresh, q, k, v,
                        g, beta, new_tail, *, interpret: bool = False):
    """Pallas form of :func:`delta_rule_reference` at ``Q > 1``: the
    chunked matrix form, in place; under a decay a key channel (``g``
    ``[S, Q, H, dk]``) the kernel is named ``kda_chunk_prefill`` and a
    chunk is :data:`MAX_CHANNEL_CHUNK` tokens at most."""
    from .ssm import tails_to_slots
    channel = g.ndim == 4
    S, Q, H, dk = q.shape
    W = v.shape[-1]
    dv = W // H
    rows, width = conv_pool.shape[2:]
    f32 = jnp.float32
    C = chunk_len(Q, MAX_CHANNEL_CHUNK if channel else MAX_CHUNK)
    assert C >= MIN_CHUNK, (Q, C)
    groups = _lane_groups(H, dv)
    hb = max([n for n in groups if n <= MAX_HEAD_BLOCK] or groups[:1])

    heads = pl.BlockSpec((None, hb, C, dk),
                         lambda s, j, c, l, sl, fr: (s, j, c, 0))
    token = pl.BlockSpec((None, C, hb * dv),
                         lambda s, j, c, l, sl, fr: (s, c, j))
    state = pl.BlockSpec((None, None, dk, hb * dv),
                         lambda s, j, c, l, sl, fr: (l[0], sl[s], 0, j))
    tail = pl.BlockSpec((None, None, rows, width),
                        lambda s, j, c, l, sl, fr: (l[0], sl[s], 0, 0))
    o, state_pool, conv_pool = pl.pallas_call(
        functools.partial(_prefill_kernel, heads=hb, dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(S, H // hb, Q // C),
            in_specs=[heads, heads, token,
                      pl.BlockSpec((None, hb, None, 8, C),
                                   lambda s, j, c, l, sl, fr:
                                   (s, j, c, 0, 0)),
                      *([heads] if channel else []),
                      pl.BlockSpec((None, rows, width),
                                   lambda s, j, c, l, sl, fr: (s, 0, 0)),
                      state, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[token, state, tail]),
        out_shape=[jax.ShapeDtypeStruct((S, Q, W), f32),
                   jax.ShapeDtypeStruct(state_pool.shape, state_pool.dtype),
                   jax.ShapeDtypeStruct(conv_pool.shape, conv_pool.dtype)],
        # operands count the 3 prefetched (and the running sums)
        input_output_aliases={9: 1, 10: 2} if channel else {8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        name="kda_chunk_prefill" if channel else "delta_chunk_prefill",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
      fresh.astype(jnp.int32), q.astype(f32).swapaxes(1, 2),
      k.astype(f32).swapaxes(1, 2), v.astype(f32),
      *((_chunk_rows(jnp.zeros_like(beta), beta, C), jnp.cumsum(
          g.astype(f32).reshape(S, Q // C, C, H, dk), axis=2).reshape(
              S, Q, H, dk).swapaxes(1, 2)) if channel
        else (_chunk_rows(g, beta, C),)),
      tails_to_slots(conv_pool, new_tail), state_pool, conv_pool)
    return o, state_pool, conv_pool


def delta_rule(state_pool: jax.Array, conv_pool: jax.Array, layer,
               slots: jax.Array, fresh: jax.Array, q: jax.Array,
               k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
               new_tail: jax.Array, *, use_kernel: Optional[bool] = None,
               interpret: bool = False
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``Q`` steps of the gated delta rule for ``S`` rows from their
    slots, and the rows' new convolution tails written.

    state_pool : [L, slots + 1, dk, H * dv], the state's dtype
    conv_pool  : [L, slots + 1, rows, lanes] (``ops/ssm.py::
             conv_slot_shape``)
    layer  : int32 scalar (the layer's index among the delta layers)
    slots  : [S] int32, the scratch slot for a row with nothing to step
    fresh  : [S] bool, the row starts from a zero state
    q, k   : [S, Q, H, dk], l2-normalised (``q`` scaled by dk ** -0.5)
    v      : [S, Q, H * dv]
    g      : [S, Q, H] = log alpha <= 0, 0 at padded positions; or [S,
             Q, H, dk], one decay a KEY CHANNEL (KDA: the kernels
             ``kda_*``), then >= -88 / :data:`MAX_CHANNEL_CHUNK` a token
    beta   : [S, Q, H], 0 at padded positions
    new_tail : [S, K - 1, channels], ``ops/ssm.py::conv_step``'s; None
             for decode rows (it wrote them: the conv pool is returned as
             it came)
    Returns (o [S, Q, H * dv] float32, the updated state pool, the updated
    conv pool).  ``use_kernel`` None = auto (on TPU, or anywhere with
    ``interpret=True``); a row bucket shorter than :data:`MIN_CHUNK` is
    walked by the reference."""
    if use_kernel is None:
        use_kernel = interpret or on_tpu()
    Q = q.shape[1]
    if not use_kernel or 1 < Q < MIN_CHUNK:
        impl = delta_rule_reference
    else:
        impl = functools.partial(
            delta_state_update_decode if Q == 1 else delta_chunk_prefill,
            interpret=interpret)
    return impl(state_pool, conv_pool, layer, slots, fresh, q, k, v, g,
                beta, new_tail)
