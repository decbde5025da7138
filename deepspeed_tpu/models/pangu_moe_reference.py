"""Plain reference forward pass of openPangu-Ultra-MoE (pangu_ultra_moe).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
expanded (not absorbed) latent attention, no cache, no kernel, no
batching tricks; it imports nothing of the serving path.  The served
program (``inference/v2``) is held to it on logits.

Departures from the source, all of them:

* ASSUMED, the source's config has no key for them — scoring is a
  sigmoid over all experts with no groups and no selection bias, the
  ``num_experts_per_tok`` largest are normalised over themselves and
  multiplied by ``routed_scaling_factor`` (the deepseek_v3 lineage);
  rope pairs are interleaved ``(x[2i], x[2i+1])``.
* NOT BUILT — the multi-token-prediction module
  (``num_nextn_predict_layers``): the source's own causal-LM forward
  skips it, it adds nothing to the next-token logits.
* EXPERTS HELD — ``sizes["experts_first"]`` / the leading dim of the
  expert weights say which routed experts are here; an expert held
  elsewhere adds nothing, so the result is the same partial sum that the
  served share computes.  With every expert held it is the whole model.

``sizes`` are plain numbers (``kv_lora_rank``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``eps``, ``rope_theta``, ``top_k``,
``routed_scaling_factor``, ``norm_topk_prob``, ``experts_first``,
``sandwich_norm``); ``params`` is the unboxed tree of
``models/pangu_moe.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, gain, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gain.astype(F32)


def rope(x, positions, theta):
    """x [..., T, H, d] rotated over interleaved pairs; positions [T]."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[:, None] * freqs            # [T, d/2]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def swiglu(x, p):
    return (jax.nn.silu(x @ p["wg"].astype(F32)) * (x @ p["wi"].astype(F32))
            ) @ p["wo"].astype(F32)


def attention(x, ap, sizes):
    """x [T, e] of ONE sequence, causal over its own tokens."""
    T = x.shape[0]
    dn, dr = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    rkv, eps = sizes["kv_lora_rank"], sizes["eps"]
    pos = jnp.arange(T)
    cq = rms_norm(x @ ap["wq_a"].astype(F32), ap["q_norm"]["scale"], eps)
    q = jnp.einsum("tr,rhd->thd", cq, ap["wq_b"].astype(F32))
    q_n, q_r = q[..., :dn], rope(q[..., dn:], pos, sizes["rope_theta"])
    ckr = x @ ap["wkv_a"].astype(F32)
    c = rms_norm(ckr[:, :rkv], ap["kv_norm"]["scale"], eps)
    k_r = rope(ckr[:, None, rkv:], pos, sizes["rope_theta"])   # [T, 1, dr]
    k_n = jnp.einsum("tr,rhd->thd", c, ap["wkv_b_k"].astype(F32))
    v = jnp.einsum("tr,rhd->thd", c, ap["wkv_b_v"].astype(F32))
    scores = (jnp.einsum("thd,shd->hts", q_n, k_n)
              + jnp.einsum("thd,sd->hts", q_r, k_r[:, 0])) / jnp.sqrt(
                  F32(dn + dr))
    causal = pos[None, :] <= pos[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hts,shd->thd", probs, v)
    return jnp.einsum("thd,hde->te", out, ap["wo"].astype(F32))


def route(x, router, sizes):
    """(experts [T, k], weights [T, k]) over ALL experts."""
    scores = jax.nn.sigmoid(x @ router.astype(F32))
    top, experts = jax.lax.top_k(scores, sizes["top_k"])
    if sizes.get("norm_topk_prob", True):
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    return experts, top * sizes["routed_scaling_factor"]


def routed_ffn(x, mp, sizes):
    """Held experts' partial sum + the shared expert; also the pairs that
    fell to each held expert."""
    experts, weights = route(x, mp["router"], sizes)
    ex = mp["experts"]
    held = ex["wg"].shape[0]
    y = jnp.zeros_like(x)
    counts = []
    for i in range(held):
        w = jnp.sum(jnp.where(experts == sizes.get("experts_first", 0) + i,
                              weights, 0.0), axis=-1)       # [T]
        counts.append(jnp.sum(w > 0))
        wg, wu, wd = (ex[n][i].astype(F32) for n in ("wg", "wu", "wd"))
        y = y + w[:, None] * ((jax.nn.silu(x @ wg.T) * (x @ wu.T)) @ wd)
    if "shared" in mp:
        y = y + swiglu(x, mp["shared"])
    return y, jnp.stack(counts)


def layer(x, lp, sizes):
    """One layer over x [T, e]; returns (x, pairs per held expert or
    None)."""
    eps, sandwich = sizes["eps"], sizes.get("sandwich_norm", True)
    a = attention(rms_norm(x, lp["norm1"]["scale"], eps), lp["attn"], sizes)
    if sandwich:
        a = rms_norm(a, lp["norm1_post"]["scale"], eps)
    x = x + a
    h = rms_norm(x, lp["norm2"]["scale"], eps)
    counts = None
    if "moe" in lp:
        f, counts = routed_ffn(h, lp["moe"], sizes)
    else:
        f = swiglu(h, lp["mlp"])
    if sandwich:
        f = rms_norm(f, lp["norm2_post"]["scale"], eps)
    return x + f, counts


def layers_of(params):
    """The layers in order, dense prefix first."""
    out = []
    for name in ("dense_layers", "layers"):
        if name in params:
            n = jax.tree.leaves(params[name])[0].shape[0]
            out += [jax.tree.map(lambda a, i=i: a[i], params[name])
                    for i in range(n)]
    return out


def forward(params, token_ids, sizes):
    """token_ids [T] of one sequence -> (logits [T, V] float32, pairs per
    held expert of every routed layer [routed layers, held])."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"].astype(F32)[token_ids]
        pairs = []
        for lp in layers_of(params):
            x, counts = layer(x, lp, sizes)
            if counts is not None:
                pairs.append(counts)
        x = rms_norm(x, params["final_norm"]["scale"], sizes["eps"])
        return x @ params["lm_head"].astype(F32), jnp.stack(pairs)


def sizes_of(cfg) -> dict:
    """``sizes`` from a ``TransformerConfig`` (plain attribute reads)."""
    return dict(
        kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, eps=cfg.norm_eps,
        rope_theta=cfg.rope_theta, top_k=cfg.moe_top_k,
        routed_scaling_factor=cfg.routed_scaling_factor,
        norm_topk_prob=cfg.norm_topk_prob,
        experts_first=cfg.experts_first, sandwich_norm=cfg.sandwich_norm)
