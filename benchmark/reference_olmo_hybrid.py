"""The benchmark's own copy of the plain reference of Olmo-Hybrid
(``olmo_hybrid``): what the served program is held to on the chip.

The arithmetic is ``deepspeed_tpu/models/olmo_hybrid_reference.py``'s,
copied so that a later change to the program's file cannot move the
yardstick: float32 ``jax.numpy`` at ``highest`` matmul precision, one
sequence at a time, every layer over the whole sequence from a zero state,
the gated delta rule token by token (a plain ``lax.scan`` over positions:
no chunk, no matrix form), the convolution four shifted products,
attention under a causal mask with no rope; no cache, no page, no slot, no
kernel; it imports nothing of the program.  For a sequence ``x`` ``[T, e]``
entering a layer::

    both kinds:  h = x + rmsnorm(mixer(x)) * g_1
                 out = h + rmsnorm((silu(h W_g) * (h W_i)) W_o) * g_2
    full:        q = rmsnorm(x Wq) * g_q, k = rmsnorm(x Wk) * g_k (each over
                 its WHOLE width, all heads together), v = x Wv; by head;
                 softmax(q k^T / sqrt(dh)) under the causal mask, NO rope;
                 Wo
    linear:      [q ; k ; v] = x W_qkv
                 c_t = silu(sum_j w_conv[j] c_{t-(K-1)+j}) for every channel
                       of q, k, v (inputs before the sequence are zero)
                 q_h = l2norm(q_h) / sqrt(dk), k_h = l2norm(k_h)
                 beta_h = 2 sigmoid((W_b x)_h)
                 alpha_h = exp(-exp(A_log_h) softplus((W_a x)_h + dt_bias_h))
                 S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1}
                       + beta_t k_t v_t^T            (a head, [dk, dv])
                 o_t = S_t^T q_t
                 y_h = rmsnorm(o_h) * g_o * silu((W_g x)_h);  W_o concat(y)
    then the final norm and the head.

``l2norm(a) = a / sqrt(sum(a^2) + 1e-6)``.  It reads the served bfloat16
weights and upcasts ONE LAYER AT A TIME (a jitted layer function, the
layers iterated in python), so that the whole model's float32 copy never
exists beside the served model on the chip; the head is taken in blocks
of rows.

Departures from the source, all of them: ``head_dim`` = hidden / heads
(the source's is null); no rope where ``rope_theta`` is null; the norm on
each sub-layer's output and the Q/K norm over the whole width (the OLMo
family has no key for them); no convolution bias; the state integrated in
float32; weights are seeded, not published.

``sizes``: ``eps head_dim kinds conv heads dk dv`` and, for the probe's
controls (each plants ONE fault that the comparison has to see),
``beta_doubled`` (False: ``beta = sigmoid``), ``decay`` (False: ``alpha =
1``), ``l2norm`` (False: q and k as the convolution left them), ``gate``
(False: ``silu(W_g x)`` left out), ``qk_norm`` (False: the full layer's
two norms left out).  ``tail_break``: a position ``n``: from ``n`` on, the
convolution sees zeros in place of the inputs before ``n``, as a program
would that kept the tail of a prompt's PADDED last tokens.
``state_precision``: a dtype the matrix state is rounded through after
every step (bfloat16: the nearest precision below the configuration's,
which has to come out as not correct).  ``weight_precision``: a dtype
every weight matrix is rounded through first.  ``carry_in``: per linear
layer ``(state, tail)`` to start from in place of zeros (a slot not zeroed
at reuse).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: rows of the head taken at once ([rows, vocabulary] float32)
HEAD_ROWS = 512


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + jnp.asarray(eps, x.dtype)) * gain


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                             + jnp.asarray(1e-6, x.dtype))


def swiglu(x, wi, wg, wo):
    return (jax.nn.silu(x @ wg) * (x @ wi)) @ wo


def attention(x, ap, sizes):
    """x [T, e] -> [T, e]: causal softmax attention, Q/K norm, no rope."""
    T, d, eps = x.shape[0], sizes["head_dim"], sizes["eps"]
    q, k = x @ ap["wq"], x @ ap["wk"]
    if sizes.get("qk_norm", True):
        q = rms_norm(q, ap["q_norm"]["scale"], eps)
        k = rms_norm(k, ap["k_norm"]["scale"], eps)
    q, k = q.reshape(T, -1, d), k.reshape(T, -1, d)
    v = (x @ ap["wv"]).reshape(T, -1, d)
    K = k.shape[1]
    q = q.reshape(T, K, -1, d)                              # [T, K, G, d]
    s = jnp.einsum("tkgd,ukd->kgtu", q, k).astype(jnp.float32) * d ** -0.5
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("kgtu,ukd->tkgd", p.astype(x.dtype), v)
    return o.reshape(T, -1) @ ap["wo"]


def delta_mixer(x, mp, s0, tail0, brk, sizes, state_precision):
    """x [T, e] from state ``s0`` [H, dk, dv] and conv tail ``tail0``
    [K - 1, H (2 dk + dv)] -> (out [T, e], S_T, the last K - 1 inputs)."""
    T = x.shape[0]
    H, dk, dv, K = (sizes[n] for n in ("heads", "dk", "dv", "conv"))
    xp = jnp.concatenate([tail0, x @ mp["w_qkv"]])          # [K-1+T, C]
    conv, t = 0.0, jnp.arange(T)
    for j in range(K):                                      # shifted products
        lost = (brk >= 0) & (t >= brk) & (t + j - (K - 1) < brk)
        conv = conv + jnp.where(lost[:, None], 0.0, xp[j:j + T]) \
            * mp["conv_w"][j]
    qkv = jax.nn.silu(conv)
    q = qkv[:, :H * dk].reshape(T, H, dk)
    k = qkv[:, H * dk:2 * H * dk].reshape(T, H, dk)
    v = qkv[:, 2 * H * dk:].reshape(T, H, dv)
    if sizes.get("l2norm", True):
        q, k = l2_norm(q), l2_norm(k)
    q = q * dk ** -0.5
    ab = x @ mp["w_ab"].T                                   # [T, 2 H]
    beta = jax.nn.sigmoid(ab[:, H:]) \
        * (2.0 if sizes.get("beta_doubled", True) else 1.0)
    alpha = jnp.exp(-jnp.exp(mp["A_log"])
                    * jax.nn.softplus(ab[:, :H] + mp["dt_bias"]))
    if not sizes.get("decay", True):
        alpha = jnp.ones_like(alpha)

    def step(S, inp):
        q_t, k_t, v_t, a_t, b_t = inp        # [H,dk] [H,dk] [H,dv] [H] [H]
        S = S * a_t[:, None, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hk,hkv->hv", k_t, S))
        S = S + k_t[:, :, None] * u[:, None, :]
        if state_precision is not None:
            # an explicit rounding: a cast there and back is one the
            # chip's compiler may drop (excess precision is allowed)
            fi = jnp.finfo(state_precision)
            S = jax.lax.reduce_precision(S, fi.nexp, fi.nmant)
        return S, jnp.einsum("hk,hkv->hv", q_t, S)

    S, o = jax.lax.scan(step, s0, (q, k, v, alpha, beta))
    y = rms_norm(o, mp["o_norm"]["scale"], sizes["eps"])
    if sizes.get("gate", True):
        y = y * jax.nn.silu((x @ mp["w_gate"]).reshape(T, H, dv))
    return y.reshape(T, -1) @ mp["w_out"], S, xp[T:]


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
    if kind == "delta":
        out, *carry = delta_mixer(x, lp["mixer"], *carry, brk, sizes,
                                  state_precision)
    else:
        out = attention(x, lp["attn"], sizes)
    x = x + rms_norm(out, lp["norm1"]["scale"], eps)
    mlp = lp["mlp"]
    return x + rms_norm(swiglu(x, mlp["wi"], mlp["wg"], mlp["wo"]),
                        lp["norm2"]["scale"], eps), tuple(carry)


@functools.partial(jax.jit, static_argnames=(
    "eps", "precision", "weight_precision"))
def _head(x, gain, lm_head, eps, precision, weight_precision):
    x = rms_norm(x, gain.astype(precision), eps)
    if weight_precision is not None:
        lm_head = lm_head.astype(weight_precision)
    return (x @ lm_head.astype(precision)).astype(jnp.float32)


def layers_of(params, kinds):
    """The layers in order (``models/olmo_hybrid.py``'s tree, read as
    data): layer ``i`` is the next entry of its kind's stack."""
    at = dict.fromkeys(kinds, 0)
    for kind in kinds:
        yield jax.tree.map(lambda a, n=at[kind]: a[n],
                           params["layers"][kind])
        at[kind] += 1


def forward(params, token_ids, sizes, precision=jnp.float32,
            weight_precision=None, state_precision=None, carry_in=None,
            tail_break=None):
    """token_ids [T] of one sequence, ``params`` the program's unboxed
    tree (read as data) -> (logits [T, V] float32, per linear layer the
    (state, conv tail) after the last position)."""
    key = tuple(sorted(sizes.items()))
    carries, at = [], 0
    brk = jnp.int32(-1 if tail_break is None else tail_break)
    H, dk, dv = sizes["heads"], sizes["dk"], sizes["dv"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][jnp.asarray(token_ids)].astype(precision)
        for lp, kind in zip(layers_of(params, sizes["kinds"]),
                            sizes["kinds"]):
            carry = ()
            if kind == "delta":
                carry = carry_in[at] if carry_in is not None else (
                    jnp.zeros((H, dk, dv), precision),
                    jnp.zeros((sizes["conv"] - 1, H * (2 * dk + dv)),
                              precision))
                at += 1
            x, carry = _layer(x, lp, carry, brk, kind, key, precision,
                              weight_precision, state_precision)
            if kind == "delta":
                carries.append(carry)
        head = params["lm_head"] if "lm_head" in params \
            else params["embed"]["tokens"].T
        logits = jnp.concatenate([
            _head(x[lo:lo + HEAD_ROWS], params["final_norm"]["scale"], head,
                  sizes["eps"], precision, weight_precision)
            for lo in range(0, x.shape[0], HEAD_ROWS)])
    return logits, carries
