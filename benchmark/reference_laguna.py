"""The benchmark's own copy of the plain reference of Laguna (``laguna``):
what the served program is held to on the chip.

The arithmetic is ``deepspeed_tpu/models/laguna_reference.py``'s, copied so
that a later change to the program's file cannot move the yardstick:
float32 at ``highest`` matmul precision, one sequence at a time, every layer
over the whole sequence under its own mask (causal; and ``i - j < window``
on a window layer), no cache, no page, no kernel; it imports nothing of the
program.  It differs from that file in how it is RUN, not in what it
computes: one layer at a time from the bfloat16 weights (cast inside a
jitted layer function, the routed experts one after another, the KV heads'
score blocks one after another), so that it fits on the chip beside the
weights at the probe's longest sequence; and it returns, per routed layer
and token, how many of the token's chosen experts are held here.

Departures from the source, as there: softmax scoring over all experts, the
chosen ones normalised and scaled, no gate on the shared expert, no Q/K norm
(ASSUMED: no key in the config); rope over interleaved pairs (ASSUMED);
experts held elsewhere add nothing (``experts_first`` and the expert
stack's second dim).

``sizes``: ``eps head_dim kinds window rope_full rope_window top_k scaling
norm_topk_prob experts_first`` (a rope is ``(theta, rotated dims, yarn)``,
``yarn`` = ``(factor, original positions, beta_fast, beta_slow,
attention_factor)`` or ``()``) and, for the probe's controls, ``gate``
(False: the per-head output gate left out); ``precision``: the dtype
everything is computed in (float32; bfloat16 for a control);
``weight_precision``: a dtype every weight matrix is rounded through first
(float8 for the control that has to come out as not correct: the nearest
precision below the configuration's).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + jnp.asarray(eps, x.dtype)) * gain


def inverse_frequencies(theta, dims, yarn):
    freqs = theta ** (-jnp.arange(0, dims, 2, dtype=jnp.float32) / dims)
    if not yarn:
        return freqs
    factor, original, beta_fast, beta_slow, _ = yarn

    def correction_dim(rotations):
        return dims * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dims - 1)
    ramp = jnp.clip((jnp.arange(dims // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return freqs / factor * ramp + freqs * (1.0 - ramp)


def rope(x, positions, theta, dims, yarn):
    """x [T, H, D]: the first ``dims`` dims rotated over interleaved pairs
    (x[2i], x[2i+1]), cos and sin times YaRN's attention factor."""
    ang = positions.astype(jnp.float32)[:, None] * inverse_frequencies(
        theta, dims, yarn)
    scale = yarn[4] if yarn else 1.0
    sin = (jnp.sin(ang) * scale)[:, None, :].astype(x.dtype)
    cos = (jnp.cos(ang) * scale)[:, None, :].astype(x.dtype)
    head, tail = x[..., :dims], x[..., dims:]
    x1, x2 = head[..., 0::2], head[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                    axis=-1).reshape(head.shape)
    return jnp.concatenate([out, tail], axis=-1)


def swiglu(x, wi, wg, wo):
    return (jax.nn.silu(x @ wg) * (x @ wi)) @ wo


def attention(x, ap, kind, sizes):
    T = x.shape[0]
    pos = jnp.arange(T)
    theta, dims, yarn = sizes["rope_" + kind]
    D = sizes["head_dim"]
    # head n of a projection = its columns n*D .. n*D + D - 1
    q = rope((x @ ap["wq"]).reshape(T, -1, D), pos, theta, dims, yarn)
    k = rope((x @ ap["wk"]).reshape(T, -1, D), pos, theta, dims, yarn)
    v = (x @ ap["wv"]).reshape(T, -1, D)
    H, K = q.shape[1], k.shape[1]
    keep = pos[None, :] <= pos[:, None]
    if kind == "window":
        keep &= pos[:, None] - pos[None, :] < sizes["window"]
    scale = jnp.asarray(D ** -0.5, x.dtype)

    def one_kv_head(xs):
        """The ``H / K`` query heads of one KV head, ``[G, T, D]``: the
        scores of all heads at once would not fit beside the weights at
        the probe's longest sequence."""
        qg, kh, vh = xs
        scores = jnp.einsum("gtd,sd->gts", qg, kh) * scale
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return jnp.einsum("gts,sd->gtd", probs, vh)

    out = jax.lax.map(one_kv_head, (
        q.reshape(T, K, H // K, D).transpose(1, 2, 0, 3),   # head n = k*G+g
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    out = out.transpose(2, 0, 1, 3).reshape(T, H, D)
    if sizes.get("gate", True):
        out = out * jax.nn.sigmoid(x @ ap["wgate"])[..., None]
    return out.reshape(T, H * D) @ ap["wo"]


def routed_ffn(x, mp, experts, layer, sizes, cast):
    """(held experts' partial sum + shared expert, held pairs a token);
    ``experts`` the routed layers' stack ``[L, held, F, e]``, read at
    ``layer``."""
    scores = jax.nn.softmax(x @ cast(mp["router"]), axis=-1)
    top, chosen = jax.lax.top_k(scores, sizes["top_k"])
    if sizes.get("norm_topk_prob", True):
        top = top / (jnp.sum(top, -1, keepdims=True)
                     + jnp.asarray(1e-20, top.dtype))
    weights = top * jnp.asarray(sizes["scaling"], top.dtype)
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
    "kind", "sizes_key", "precision", "weight_precision"))
def _layer(x, lp, experts, routed, kind, sizes_key, precision,
           weight_precision):
    """One layer (``lp`` its weights, one period's slice taken by the
    caller: a few hundred MB at the published widths) of kind ``kind``;
    ``experts`` the routed layers' stack, read at ``routed`` inside."""
    sizes = dict(sizes_key)
    eps = sizes["eps"]

    def cast(a):
        if weight_precision is not None and a.ndim >= 2:
            a = a.astype(weight_precision)
        return a.astype(precision)

    a = attention(rms_norm(x, cast(lp["norm1"]["scale"]), eps),
                  jax.tree.map(cast, lp["attn"]), kind, sizes)
    x = x + a
    h = rms_norm(x, cast(lp["norm2"]["scale"]), eps)
    if "moe" in lp:
        f, here = routed_ffn(h, lp["moe"], experts, routed, sizes, cast)
    else:
        mlp = jax.tree.map(cast, lp["mlp"])
        f, here = swiglu(h, mlp["wi"], mlp["wg"], mlp["wo"]), None
    return x + f, here


@functools.partial(jax.jit, static_argnames=(
    "eps", "precision", "weight_precision"))
def _head(x, gain, lm_head, eps, precision, weight_precision):
    x = rms_norm(x, gain.astype(precision), eps)
    if weight_precision is not None:
        lm_head = lm_head.astype(weight_precision)
    return (x @ lm_head.astype(precision)).astype(jnp.float32)


def layers_of(params):
    """The layers in order: leading dense, the periods' layers, the tail."""
    out = [params["dense_layers"][f"l{i}"]
           for i in range(len(params.get("dense_layers", {})))]
    stacks = params.get("periods", {})
    if stacks:
        for p in range(jax.tree.leaves(stacks)[0].shape[0]):
            out += [jax.tree.map(lambda a, p=p: a[p], stacks[f"l{j}"])
                    for j in range(len(stacks))]
    out += [params["tail"][f"l{i}"]
            for i in range(len(params.get("tail", {})))]
    return out


def forward(params, token_ids, sizes, precision=jnp.float32,
            weight_precision=None):
    """token_ids [T] of one sequence, ``params`` the program's unboxed tree
    (read as data) -> (logits [T, V] float32, held pairs [routed layers,
    T] int32)."""
    key = tuple(sorted(sizes.items()))
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][jnp.asarray(token_ids)].astype(precision)
        pairs, routed = [], 0
        for lp, kind in zip(layers_of(params), sizes["kinds"]):
            x, here = _layer(x, lp, params.get("experts"), jnp.int32(routed),
                             kind, key, precision, weight_precision)
            if here is not None:
                pairs.append(here)
                routed += 1
        logits = _head(x, params["final_norm"]["scale"], params["lm_head"],
                       sizes["eps"], precision, weight_precision)
    return logits, jnp.stack(pairs)
