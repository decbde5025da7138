"""The benchmark's own copy of the plain reference of SmallThinker
(``smallthinker``): what the served program is held to on the chip.

The arithmetic is ``deepspeed_tpu/models/smallthinker_reference.py``'s,
copied so that a later change to the program's file cannot move the
yardstick: float32 at ``highest`` matmul precision, one sequence at a time,
every layer over the whole sequence under its own mask (causal; and ``i - j
< window`` on a window layer), every expert over every token and masked by
the routing, no cache, no page, no kernel; it imports nothing of the
program.  It differs from that file in how it is RUN, not in what it
computes: one layer at a time from the bfloat16 weights (cast inside a
jitted layer function, the 64 experts one after another, the KV heads'
score blocks one after another), so that it fits on the chip beside the
weights at the probe's longest sequence; and it returns, per layer and
token, how many of the token's chosen experts are held here.

The layer, with ``x`` the residual stream::

    a = rmsnorm(x) g_in;   s = a Wr (float32, over all E experts)
    p = softmax(s);  X = the top_k largest;  w_e = p_e / sum_X p
    q, k, v = a Wq, a Wk, a Wv;  a window layer ropes q and k (theta, all
    dims, interleaved pairs), a global layer leaves them as projected
    h = x + attention(q, k, v) Wo     causal; i - j < window on a window layer
    b = rmsnorm(h) g_post
    x' = h + sum_{e in X} w_e Wd_e (relu(Wg_e b) * (Wu_e b))

Departures from the source, as there: the router reads the NORMED attention
input, the gate's activation is ReLU, no attention bias and no Q/K norm,
rope over interleaved pairs (all ASSUMED: no key in the config); the two
per-layer lists read as one list of kinds.

``sizes``: ``eps head_dim kinds window rope_theta top_k norm_topk_prob
experts_first`` and, for the probe's controls, ``roped`` (the kinds under
rope: ``("window",)``), ``act`` ("relu") and ``router_reads`` ("mixer";
"ffn": the post-attention norm's output); ``precision``: the dtype
everything is computed in (float32); ``weight_precision``: a dtype every
weight matrix is rounded through first (float8 for the control that has to
come out as not correct: the nearest precision below the configuration's).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

ACTS = {"relu": jax.nn.relu, "silu": jax.nn.silu}


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + jnp.asarray(eps, x.dtype)) * gain


def rope(x, positions, theta):
    """x [T, H, D]: every dim rotated over interleaved pairs (x[2i],
    x[2i+1])."""
    D = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def attention(a, ap, kind, sizes):
    T, D = a.shape[0], sizes["head_dim"]
    pos = jnp.arange(T)
    # head n of a projection = its columns n*D .. n*D + D - 1
    q = (a @ ap["wq"]).reshape(T, -1, D)
    k = (a @ ap["wk"]).reshape(T, -1, D)
    v = (a @ ap["wv"]).reshape(T, -1, D)
    if kind in sizes["roped"]:
        q, k = (rope(q, pos, sizes["rope_theta"]),
                rope(k, pos, sizes["rope_theta"]))
    H, K = q.shape[1], k.shape[1]
    keep = pos[None, :] <= pos[:, None]
    if kind == "window":
        keep &= pos[:, None] - pos[None, :] < sizes["window"]
    scale = jnp.asarray(D ** -0.5, a.dtype)

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
    out = out.transpose(2, 0, 1, 3).reshape(T, H * D)
    return out @ ap["wo"]


def route(a, router, sizes):
    """(experts [T, k], weights [T, k]) over ALL experts."""
    probs = jax.nn.softmax(a @ router, axis=-1)
    top, chosen = jax.lax.top_k(probs, sizes["top_k"])
    if sizes.get("norm_topk_prob", True):
        top = top / (jnp.sum(top, -1, keepdims=True)
                     + jnp.asarray(1e-20, top.dtype))
    return chosen, top


def experts_ffn(b, chosen, weights, experts, layer, sizes, cast):
    """(the held experts' sum, held pairs a token); ``experts`` the
    layers' stack ``[L, held, F, e]``, read at ``layer``, every expert
    over every token and masked by the routing."""
    act = ACTS[sizes.get("act", "relu")]
    first = sizes.get("experts_first", 0)
    held = experts["wg"].shape[1]

    def one(y, i):
        w = jnp.sum(jnp.where(chosen == first + i, weights, 0), axis=-1)
        wg, wu, wd = (cast(experts[n][layer, i]) for n in ("wg", "wu", "wd"))
        return y + w[:, None] * ((act(b @ wg.T) * (b @ wu.T)) @ wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(b), jnp.arange(held))
    here = jnp.sum((chosen >= first) & (chosen < first + held), axis=-1)
    return y, here


@functools.partial(jax.jit, static_argnames=(
    "kind", "sizes_key", "precision", "weight_precision"))
def _layer(x, lp, experts, layer, kind, sizes_key, precision,
           weight_precision):
    """One layer (``lp`` its weights, one period's slice taken by the
    caller) of kind ``kind``; ``experts`` the layers' stack, read at
    ``layer`` inside."""
    sizes = dict(sizes_key)
    eps = sizes["eps"]

    def cast(a):
        if weight_precision is not None and a.ndim >= 2:
            a = a.astype(weight_precision)
        return a.astype(precision)

    a = rms_norm(x, cast(lp["norm1"]["scale"]), eps)
    before = sizes.get("router_reads", "mixer") == "mixer"
    router = cast(lp["moe"]["router"])
    if before:                  # routed from the attention block's input
        chosen, weights = route(a, router, sizes)
    h = x + attention(a, jax.tree.map(cast, lp["attn"]), kind, sizes)
    b = rms_norm(h, cast(lp["norm2"]["scale"]), eps)
    if not before:
        chosen, weights = route(b, router, sizes)
    y, here = experts_ffn(b, chosen, weights, experts, layer, sizes, cast)
    return h + y, here


@functools.partial(jax.jit, static_argnames=(
    "eps", "precision", "weight_precision"))
def _head(x, gain, lm_head, eps, precision, weight_precision):
    x = rms_norm(x, gain.astype(precision), eps)
    if weight_precision is not None:
        lm_head = lm_head.astype(weight_precision)
    return (x @ lm_head.astype(precision)).astype(jnp.float32)


def layers_of(params):
    """The layers in order: the periods' layers, then the tail."""
    out = []
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
    (read as data) -> (logits [T, V] float32, held pairs [layers, T]
    int32)."""
    key = tuple(sorted(sizes.items()))
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][jnp.asarray(token_ids)].astype(precision)
        pairs = []
        for i, (lp, kind) in enumerate(zip(layers_of(params),
                                           sizes["kinds"])):
            x, here = _layer(x, lp, params["experts"], jnp.int32(i), kind,
                             key, precision, weight_precision)
            pairs.append(here)
        logits = _head(x, params["final_norm"]["scale"], params["lm_head"],
                       sizes["eps"], precision, weight_precision)
    return logits, jnp.stack(pairs)
