"""The benchmark's own copy of the plain reference of openPangu-Ultra-MoE
(``pangu_ultra_moe``): what the served program is held to on the chip.

The arithmetic is ``deepspeed_tpu/models/pangu_moe_reference.py``'s, copied
so that a later change to the program's file cannot move the yardstick:
float32 at ``highest`` matmul precision, expanded (not absorbed) latent
attention, no cache, no kernel; it imports nothing of the program.  It
differs from that file in how it is RUN, not in what it computes: one
layer at a time from the bfloat16 weights (cast inside a jitted layer
function, the routed experts one after another, the attention heads 16
at a time), so that it fits on the chip beside the weights at the probe's
longest sequence; and it also returns, per routed layer and token,
how many of the token's chosen experts are held here.

Departures from the source, as there: sigmoid scoring over all experts,
no groups, no selection bias, the chosen ones normalised and scaled
(ASSUMED: no key in the config); rope over interleaved pairs (ASSUMED);
the multi-token-prediction module NOT BUILT; experts held elsewhere add
nothing (``experts_first`` and the expert weights' leading dim).

``sizes``: ``eps rope_theta qk_nope_head_dim qk_rope_head_dim
kv_lora_rank top_k routed_scaling_factor norm_topk_prob experts_first
sandwich_norm``; ``precision``: the dtype everything is computed in
(float32; bfloat16 for a control); ``weight_precision``: a dtype every
weight matrix is rounded through first (float8 for the control that has to
come out as not correct: the nearest precision below the configuration's).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + jnp.asarray(eps, x.dtype)) * gain


def rope(x, positions, theta):
    """x [T, H, d] rotated over interleaved pairs (x[2i], x[2i+1])."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def swiglu(x, wi, wg, wo):
    return (jax.nn.silu(x @ wg) * (x @ wi)) @ wo


def attention(x, ap, sizes):
    T = x.shape[0]
    dn, rkv = sizes["qk_nope_head_dim"], sizes["kv_lora_rank"]
    eps, theta = sizes["eps"], sizes["rope_theta"]
    pos = jnp.arange(T)
    cq = rms_norm(x @ ap["wq_a"], ap["q_norm"]["scale"], eps)
    q = jnp.einsum("tr,rhd->thd", cq, ap["wq_b"])
    q_n, q_r = q[..., :dn], rope(q[..., dn:], pos, theta)
    ckr = x @ ap["wkv_a"]
    c = rms_norm(ckr[:, :rkv], ap["kv_norm"]["scale"], eps)
    k_r = rope(ckr[:, None, rkv:], pos, theta)[:, 0]
    k_n = jnp.einsum("tr,rhd->thd", c, ap["wkv_b_k"])
    v = jnp.einsum("tr,rhd->thd", c, ap["wkv_b_v"])
    scale = jnp.asarray((dn + sizes["qk_rope_head_dim"]) ** -0.5, x.dtype)
    causal = pos[None, :] <= pos[:, None]

    def heads(qn, qr, kn, vh):
        """A block of heads, ``[h, T, d]`` each: the ``[h, T, T]`` scores
        of all 128 heads at once would not fit beside the weights at the
        probe's longest sequence."""
        scores = (jnp.einsum("htd,hsd->hts", qn, kn)
                  + jnp.einsum("htd,sd->hts", qr, k_r)) * scale
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hts,hsd->htd", probs, vh)

    H = q.shape[1]
    block = next(b for b in (16, 8, 4, 2, 1) if H % b == 0)
    out = jax.lax.map(
        lambda xs: heads(*xs),
        tuple(a.transpose(1, 0, 2).reshape(H // block, block, T, -1)
              for a in (q_n, q_r, k_n, v)))
    out = out.reshape(H, T, -1).transpose(1, 0, 2)
    return jnp.einsum("thd,hde->te", out, ap["wo"])


def routed_ffn(x, mp, experts, layer, sizes, cast):
    """(held experts' partial sum + shared expert, held pairs a token);
    ``experts`` the layers' stack ``[L, held, F, e]``, read at ``layer``."""
    scores = jax.nn.sigmoid(x @ cast(mp["router"]))
    top, chosen = jax.lax.top_k(scores, sizes["top_k"])
    if sizes.get("norm_topk_prob", True):
        top = top / (jnp.sum(top, -1, keepdims=True)
                     + jnp.asarray(1e-20, top.dtype))
    weights = top * jnp.asarray(sizes["routed_scaling_factor"], top.dtype)
    first = sizes.get("experts_first", 0)
    held = experts["wg"].shape[1]

    def one(y, i):
        w = jnp.sum(jnp.where(chosen == first + i, weights, 0), axis=-1)
        wg, wu, wd = (cast(experts[n][layer, i]) for n in ("wg", "wu", "wd"))
        return y + w[:, None] * ((jax.nn.silu(x @ wg.T) * (x @ wu.T)) @ wd), \
            None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(held))
    here = jnp.sum((chosen >= first) & (chosen < first + held), axis=-1)
    if "shared" in mp:
        sh = jax.tree.map(cast, mp["shared"])
        y = y + swiglu(x, sh["wi"], sh["wg"], sh["wo"])
    return y, here


@functools.partial(jax.jit, static_argnames=(
    "sizes_key", "precision", "weight_precision"))
def _layer(x, stack, layer, sizes_key, precision, weight_precision):
    """Layer ``layer`` of a stack of one kind, read from the stack inside
    the program: a layer sliced out first would be copied (2 GB at the
    published widths)."""
    sizes = dict(sizes_key)
    eps, sandwich = sizes["eps"], sizes.get("sandwich_norm", True)

    def cast(a):
        if weight_precision is not None and a.ndim >= 2:
            a = a.astype(weight_precision)
        return a.astype(precision)

    experts = None
    if "moe" in stack:
        experts = stack["moe"]["experts"]
        stack = dict(stack, moe={k: v for k, v in stack["moe"].items()
                                 if k != "experts"})
    lp = jax.tree.map(lambda a: a[layer], stack)
    gains = {k: cast(lp[k]["scale"]) for k in lp if k.startswith("norm")}
    a = attention(rms_norm(x, gains["norm1"], eps),
                  jax.tree.map(cast, lp["attn"]), sizes)
    if sandwich:
        a = rms_norm(a, gains["norm1_post"], eps)
    x = x + a
    h = rms_norm(x, gains["norm2"], eps)
    if experts is not None:
        f, here = routed_ffn(h, lp["moe"], experts, layer, sizes, cast)
    else:
        mlp = jax.tree.map(cast, lp["mlp"])
        f, here = swiglu(h, mlp["wi"], mlp["wg"], mlp["wo"]), None
    if sandwich:
        f = rms_norm(f, gains["norm2_post"], eps)
    return x + f, here


@functools.partial(jax.jit, static_argnames=(
    "eps", "precision", "weight_precision"))
def _head(x, gain, lm_head, eps, precision, weight_precision):
    x = rms_norm(x, gain.astype(precision), eps)
    if weight_precision is not None:
        lm_head = lm_head.astype(weight_precision)
    return (x @ lm_head.astype(precision)).astype(jnp.float32)


def forward(params, token_ids, sizes, precision=jnp.float32,
            weight_precision=None):
    """token_ids [T] of one sequence, ``params`` the program's unboxed tree
    (read as data) -> (logits [T, V] float32, held pairs [routed layers,
    T] int32)."""
    key = tuple(sorted(sizes.items()))
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][jnp.asarray(token_ids)].astype(precision)
        pairs = []
        for name in ("dense_layers", "layers"):
            if name not in params:
                continue
            n = jax.tree.leaves(params[name])[0].shape[0]
            for i in range(n):
                x, here = _layer(x, params[name], jnp.int32(i), key,
                                 precision, weight_precision)
                if here is not None:
                    pairs.append(here)
        logits = _head(x, params["final_norm"]["scale"], params["lm_head"],
                       sizes["eps"], precision, weight_precision)
    return logits, jnp.stack(pairs)
