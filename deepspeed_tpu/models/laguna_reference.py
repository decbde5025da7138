"""Plain reference forward pass of Laguna (``model_type: laguna``).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
one sequence at a time, every layer over the whole sequence under its own
mask, no cache, no page, no kernel, no batching; it imports nothing of
the serving path.  The served program (``inference/v2``) is held to it on
logits.

The layer (x in R^e, layer l of kind t(l), H_t query heads, K KV heads)::

    h   = rmsnorm(x) * g_in
    q   = h Wq -> [H_t, D];  k = h Wk -> [K, D];  v = h Wv -> [K, D]
    q,k = rope_t(q, k, pos)   full:   the first partial_rotary_factor of
                                      the dims, YaRN, cos and sin scaled
                                      by attention_factor
                              window: all dims, plain, its own base
    a_i = softmax_j(q_i . k_j / sqrt(D)) v_j  over j <= i, and
          i - j < sliding_window on window layers; head n uses KV head
          n // (H_t / K)
    o   = concat_n(sigmoid(h Wg)_n * a_n) Wo
    x   = x + o;  h2 = rmsnorm(x) * g_post
    dense layers:  y = (silu(h2 W1) * (h2 W3)) W2
    routed layers: s = softmax(float32(h2) Wr) over all experts;
                   E = the top_k largest;  w_e = scaling * s_e / sum_E s
                   y = sum_{e in E, held here} w_e swiglu_e(h2)
                       + swiglu_shared(h2)
    x   = x + y

Departures from the source, all of them:

* ASSUMED, the source's config has no key for them: the router's scores
  are a softmax over all experts (the qwen2_moe lineage its key names
  follow); the shared expert has no gate and Q and K no norm; rope pairs
  are interleaved ``(x[2i], x[2i+1])``.
* EXPERTS HELD: ``sizes["experts_first"]`` / the experts' stack say which
  routed experts are here; an expert held elsewhere adds nothing, so the
  result is the partial sum the served share computes.  With every
  expert held it is the whole model.

``sizes`` are plain numbers and tuples (``eps``, ``head_dim``, ``kinds`` a layer,
``window``, ``rope`` {kind: (theta, rotated dims, yarn tuple or ())},
``top_k``, ``scaling``, ``norm_topk_prob``, ``experts_first``);
``params`` is the unboxed tree of ``models/laguna.py``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, gain, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gain.astype(F32)


def inverse_frequencies(theta, dims, yarn):
    """Of ``dims`` rotated dims; ``yarn`` = (factor, original positions,
    beta_fast, beta_slow, attention_factor) or ()."""
    freqs = theta ** (-jnp.arange(0, dims, 2, dtype=F32) / dims)
    if not yarn:
        return freqs
    factor, original, beta_fast, beta_slow, _ = yarn

    def correction_dim(rotations):
        return dims * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dims - 1)
    ramp = jnp.clip((jnp.arange(dims // 2, dtype=F32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    # interpolated where the ramp is 1 (low frequencies), else as it was
    return freqs / factor * ramp + freqs * (1.0 - ramp)


def rope(x, positions, theta, dims, yarn):
    """x [T, H, D]: the first ``dims`` dims rotated over interleaved
    pairs, the rest passed through."""
    ang = positions.astype(F32)[:, None] * inverse_frequencies(
        theta, dims, yarn)                                  # [T, dims/2]
    scale = yarn[4] if yarn else 1.0
    sin, cos = jnp.sin(ang)[:, None, :] * scale, jnp.cos(ang)[:, None, :] * scale
    head, tail = x[..., :dims], x[..., dims:]
    x1, x2 = head[..., 0::2], head[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                    axis=-1).reshape(head.shape)
    return jnp.concatenate([out, tail], axis=-1)


def swiglu(x, p):
    return (jax.nn.silu(x @ p["wg"].astype(F32)) * (x @ p["wi"].astype(F32))
            ) @ p["wo"].astype(F32)


def attention(x, ap, kind, sizes):
    """x [T, e] of ONE sequence, causal over its own tokens (and the
    window, on a window layer)."""
    T = x.shape[0]
    pos = jnp.arange(T)
    theta, dims, yarn = sizes["rope"][kind]
    D = sizes["head_dim"]
    # head n of a projection = its columns n*D .. n*D + D - 1
    q = (x @ ap["wq"].astype(F32)).reshape(T, -1, D)
    k = (x @ ap["wk"].astype(F32)).reshape(T, -1, D)
    v = (x @ ap["wv"].astype(F32)).reshape(T, -1, D)
    q, k = rope(q, pos, theta, dims, yarn), rope(k, pos, theta, dims, yarn)
    H, K = q.shape[1], k.shape[1]
    qg = q.reshape(T, K, H // K, D)                 # head n = k * G + g
    scores = jnp.einsum("tkgd,skd->kgts", qg, k) / jnp.sqrt(F32(D))
    keep = pos[None, :] <= pos[:, None]
    if kind == "window":
        keep &= pos[:, None] - pos[None, :] < sizes["window"]
    probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("kgts,skd->tkgd", probs, v).reshape(T, H, D)
    if sizes.get("gate", True):             # False: a test's planted fault
        out = out * jax.nn.sigmoid(x @ ap["wgate"].astype(F32))[..., None]
    return out.reshape(T, H * D) @ ap["wo"].astype(F32)


def route(x, router, sizes):
    """(experts [T, k], weights [T, k]) over ALL experts."""
    scores = jax.nn.softmax(x @ router.astype(F32), axis=-1)
    top, experts = jax.lax.top_k(scores, sizes["top_k"])
    if sizes.get("norm_topk_prob", True):
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    return experts, top * sizes["scaling"]


def routed_ffn(x, mp, experts_of_layer, sizes):
    """Held experts' partial sum + the shared expert; also the pairs that
    fell to each held expert."""
    experts, weights = route(x, mp["router"], sizes)
    held = experts_of_layer["wg"].shape[0]
    y = jnp.zeros_like(x)
    counts = []
    for i in range(held):
        here = experts == sizes.get("experts_first", 0) + i
        w = jnp.sum(jnp.where(here, weights, 0.0), axis=-1)   # [T]
        counts.append(jnp.sum(here, axis=-1))
        wg, wu, wd = (experts_of_layer[n][i].astype(F32)
                      for n in ("wg", "wu", "wd"))
        y = y + w[:, None] * ((jax.nn.silu(x @ wg.T) * (x @ wu.T)) @ wd)
    if "shared" in mp:
        y = y + swiglu(x, mp["shared"])
    return y, jnp.stack(counts)                             # [held, T]


def layers_of(params):
    """The layers in order: leading dense, the periods' layers, the tail."""
    out = [params["dense_layers"][f"l{i}"]
           for i in range(len(params.get("dense_layers", {})))]
    stacks = params.get("periods", {})
    if stacks:
        periods = jax.tree.leaves(stacks)[0].shape[0]
        for p in range(periods):
            out += [jax.tree.map(lambda a, p=p: a[p], stacks[f"l{j}"])
                    for j in range(len(stacks))]
    out += [params["tail"][f"l{i}"] for i in range(len(params.get("tail", {})))]
    return out


def layer(x, lp, kind, experts_of_layer, sizes):
    """One layer over x [T, e]; returns (x, pairs of each held expert and
    token [held, T] or None)."""
    eps = sizes["eps"]
    x = x + attention(rms_norm(x, lp["norm1"]["scale"], eps), lp["attn"],
                      kind, sizes)
    h = rms_norm(x, lp["norm2"]["scale"], eps)
    if "moe" in lp:
        y, counts = routed_ffn(h, lp["moe"], experts_of_layer, sizes)
        return x + y, counts
    return x + swiglu(h, lp["mlp"]), None


def forward(params, token_ids, sizes):
    """token_ids [T] of one sequence -> (logits [T, V] float32, pairs that
    fell to held experts, a routed layer and token [routed layers, T])."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"].astype(F32)[token_ids]
        pairs, routed = [], 0
        for lp, kind in zip(layers_of(params), sizes["kinds"]):
            ex = None
            if "moe" in lp:
                ex = {n: params["experts"][n][routed]
                      for n in ("wg", "wu", "wd")}
                routed += 1
            x, counts = layer(x, lp, kind, ex, sizes)
            if counts is not None:
                pairs.append(jnp.sum(counts, axis=0))
        x = rms_norm(x, params["final_norm"]["scale"], sizes["eps"])
        logits = x @ params["lm_head"].astype(F32)
        return logits, (jnp.stack(pairs) if pairs
                        else jnp.zeros((0, x.shape[0]), jnp.int32))


def sizes_of(cfg) -> dict:
    """``sizes`` from a ``TransformerConfig`` (plain attribute reads)."""
    d = cfg.dims_per_head
    rotated = int(d * cfg.rope_pct)
    rotated -= rotated % 2
    return dict(
        eps=cfg.norm_eps, head_dim=d, kinds=tuple(cfg.layer_kinds),
        window=cfg.sliding_window,
        rope={"full": (cfg.rope_theta, rotated, tuple(cfg.rope_yarn)),
              "window": (cfg.window_rope_theta, d, ())},
        top_k=cfg.moe_top_k, scaling=cfg.routed_scaling_factor,
        norm_topk_prob=cfg.norm_topk_prob, experts_first=cfg.experts_first)
