"""Plain reference of Jamba (``models/jamba.py``): what the served program
is held to.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
one sequence at a time, every layer over the whole sequence, from a zero
state: no cache, no page, no slot, no kernel, no batching.  HF
``modeling_jamba.py`` semantics, for a sequence ``u`` ``[T, e]`` entering
a layer::

    both kinds:  x = u + mixer(rmsnorm(u) * g_in)
                 x = x + (silu(h W_g) * (h W_i)) W_o,  h = rmsnorm(x) * g_ff
    attention:   q = h Wq, k = h Wk, v = h Wv by head (no bias, NO rope);
                 softmax(q k^T / sqrt(dh)) under the causal mask, the query
                 heads of a group over their one K/V head; Wo
    Mamba:       [x ; z] = h W_in
                 x_t = silu(b_conv + sum_k w_conv[k] x_{t-(d_conv-1)+k})
                       (four shifted products; inputs before the
                       sequence are zero)
                 [dt ; B ; C] = x W_x^T;  dt, B, C = rmsnorm(.) * gain, each
                 dt = softplus(dt W_dt + b_dt);  A = -exp(A_log)
                 h_t = exp(dt_t (x) A) * h_{t-1} + (dt_t * x_t) (x) B_t
                 y_t = h_t C_t + D * x_t           (a plain lax.scan over t)
                 out = (y * silu(z)) W_out
    then the final norm and the (tied) head.

It reads the served bfloat16 weights and upcasts ONE LAYER AT A TIME (a
jitted layer function, the layers iterated in python), so that the whole
model's float32 copy never exists: 3.03B parameters fit beside the served
model on the chip.

Departures from the source, all of them: the order of the layer types is
the family's rule (``i % attn_layer_period == attn_layer_offset``: the
catalog does not give it); ``head_dim`` = hidden / heads (the source's is
null); the recurrent state is integrated in float32 (the published
kernels do); weights are seeded, not published.

``sizes``: ``eps head_dim kinds d_conv d_state dt_rank`` and, for the
probe's controls (each plants ONE fault that the comparison has to see),
``norms`` (False: the three norms on dt, B, C left out), ``skip`` (False:
``D * x`` left out).  ``tail_break``: a position ``n``: from ``n`` on, the
convolution sees zeros in place of the inputs before ``n``, as a program
would that kept the tail of a prompt's PADDED last tokens.
``state_precision``: a dtype the recurrent state is rounded through after
every step (bfloat16 for the control that has to come out as not
correct).  ``carry_in``: per Mamba layer ``(h, tail)`` to start from in
place of zeros (a slot not zeroed at reuse).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def sizes_of(cfg, **controls) -> dict:
    """``sizes`` from the program's configuration (plain attribute reads);
    ``controls``: ``norms=False``, ``skip=False``."""
    return dict(dict(
        eps=cfg.norm_eps, head_dim=cfg.dims_per_head,
        kinds=tuple(cfg.layer_kinds), d_conv=cfg.ssm_conv,
        d_state=cfg.ssm_state_dim, dt_rank=cfg.ssm_dt_rank), **controls)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + jnp.asarray(eps, x.dtype)) * gain


def swiglu(x, wi, wg, wo):
    return (jax.nn.silu(x @ wg) * (x @ wi)) @ wo


def attention(h, ap, sizes):
    """h [T, e] -> [T, e]: causal softmax attention, no rope."""
    T, d = h.shape[0], sizes["head_dim"]
    q = (h @ ap["wq"]).reshape(T, -1, d)
    k = (h @ ap["wk"]).reshape(T, -1, d)
    v = (h @ ap["wv"]).reshape(T, -1, d)
    K = k.shape[1]
    q = q.reshape(T, K, -1, d)                              # [T, K, G, d]
    s = jnp.einsum("tkgd,ukd->kgtu", q, k).astype(jnp.float32) * d ** -0.5
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("kgtu,ukd->tkgd", p.astype(h.dtype), v)
    return o.reshape(T, -1) @ ap["wo"]


def mamba_mixer(u, mp, h0, tail0, brk, sizes, state_precision):
    """u [T, e] from state ``h0`` [N, d] and conv tail ``tail0``
    [d_conv - 1, d] -> (out [T, e], h_T, the last d_conv - 1 inputs)."""
    T = u.shape[0]
    n, r, K = sizes["d_state"], sizes["dt_rank"], sizes["d_conv"]
    eps = sizes["eps"]
    d = mp["w_in"].shape[1] // 2
    xz = u @ mp["w_in"]
    x, z = xz[:, :d], xz[:, d:]
    xp = jnp.concatenate([tail0, x])                        # [K-1+T, d]
    conv, t = mp["conv_b"], jnp.arange(T)
    for k in range(K):                                      # shifted products
        lost = (brk >= 0) & (t >= brk) & (t + k - (K - 1) < brk)
        conv = conv + jnp.where(lost[:, None], 0.0, xp[k:k + T]) \
            * mp["conv_w"][k]
    x = jax.nn.silu(conv)
    dbc = x @ mp["w_x"].T
    dt, B, C = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
    if sizes.get("norms", True):
        dt = rms_norm(dt, mp["dt_norm"]["scale"], eps)
        B = rms_norm(B, mp["b_norm"]["scale"], eps)
        C = rms_norm(C, mp["c_norm"]["scale"], eps)
    dt = jax.nn.softplus(dt @ mp["w_dt"] + mp["b_dt"])
    A = -jnp.exp(mp["A_log_t"])                             # [N, d]
    D = mp["D"] if sizes.get("skip", True) else jnp.zeros_like(mp["D"])

    def step(h, inp):
        dt_t, x_t, b_t, c_t = inp
        h = jnp.exp(dt_t[None, :] * A) * h \
            + (dt_t * x_t)[None, :] * b_t[:, None]
        if state_precision is not None:
            # an explicit rounding: a cast there and back is one the
            # chip's compiler may drop (excess precision is allowed)
            fi = jnp.finfo(state_precision)
            h = jax.lax.reduce_precision(h, fi.nexp, fi.nmant)
        return h, jnp.sum(h * c_t[:, None], axis=0) + D * x_t

    h, y = jax.lax.scan(step, h0, (dt, x, B, C))
    return (y * jax.nn.silu(z)) @ mp["w_out"], h, xp[T:]


@functools.partial(jax.jit, static_argnames=(
    "kind", "sizes_key", "precision", "weight_precision",
    "state_precision"))
def _layer(x, lp, carry, brk, kind, sizes_key, precision, weight_precision,
           state_precision):
    """One layer over the whole sequence, its weights cast here."""
    sizes = dict(sizes_key)
    eps = sizes["eps"]

    def cast(a):
        if weight_precision is not None and a.ndim >= 2:
            a = a.astype(weight_precision)
        return a.astype(precision)

    lp = jax.tree.map(cast, lp)
    h = rms_norm(x, lp["norm1"]["scale"], eps)
    if kind == "ssm":
        out, *carry = mamba_mixer(h, lp["mixer"], *carry, brk, sizes,
                                  state_precision)
    else:
        out = attention(h, lp["attn"], sizes)
    x = x + out
    mlp = lp["mlp"]
    return x + swiglu(rms_norm(x, lp["norm2"]["scale"], eps),
                      mlp["wi"], mlp["wg"], mlp["wo"]), tuple(carry)


@functools.partial(jax.jit, static_argnames=(
    "eps", "precision", "weight_precision"))
def _head(x, gain, lm_head, eps, precision, weight_precision):
    x = rms_norm(x, gain.astype(precision), eps)
    if weight_precision is not None:
        lm_head = lm_head.astype(weight_precision)
    return (x @ lm_head.astype(precision)).astype(jnp.float32)


def layers_of(params, kinds):
    """The layers in order (``models/jamba.py``'s tree, read as data):
    layer ``i`` is the next entry of its kind's stack."""
    at = dict.fromkeys(kinds, 0)
    for kind in kinds:
        yield jax.tree.map(lambda a, n=at[kind]: a[n],
                           params["layers"][kind])
        at[kind] += 1


def forward(params, token_ids, sizes, precision=jnp.float32,
            weight_precision=None, state_precision=None, carry_in=None,
            tail_break=None):
    """token_ids [T] of one sequence, ``params`` the program's unboxed
    tree (read as data) -> (logits [T, V] float32, per Mamba layer the
    (state, conv tail) after the last position)."""
    key = tuple(sorted(sizes.items()))
    carries, at = [], 0
    brk = jnp.int32(-1 if tail_break is None else tail_break)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][jnp.asarray(token_ids)].astype(precision)
        for lp, kind in zip(layers_of(params, sizes["kinds"]),
                            sizes["kinds"]):
            carry = ()
            if kind == "ssm":
                d = lp["mixer"]["D"].shape[0]
                carry = carry_in[at] if carry_in is not None else (
                    jnp.zeros((sizes["d_state"], d), precision),
                    jnp.zeros((sizes["d_conv"] - 1, d), precision))
                at += 1
            x, carry = _layer(x, lp, carry, brk, kind, key, precision,
                              weight_precision, state_precision)
            if kind == "ssm":
                carries.append(carry)
        head = params["lm_head"] if "lm_head" in params \
            else params["embed"]["tokens"].T
        logits = _head(x, params["final_norm"]["scale"], head,
                       sizes["eps"], precision, weight_precision)
    return logits, carries
