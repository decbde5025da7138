"""openPangu-Ultra-MoE (``model_type: pangu_ultra_moe``) — a served family.

Latent attention (MLA), sandwich norms, ``first_k_dense_replace`` leading
dense layers, then routed layers with sigmoid top-k scoring over
``n_routed_experts`` and one shared expert.  Source:
``huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B`` (the
deepseek_v3 lineage).  The family is SERVED (``inference/v2``,
:class:`~deepspeed_tpu.inference.v2.model_implementations.
PanguUltraMoEInferenceModel`), as one chip of an expert-parallel group
where a whole routed layer does not fit one: ``experts_held`` of a
layer's experts are here.  Its plain reference is
``models/pangu_moe_reference.py``.

Not in the source's config, so ASSUMED: sigmoid scoring with no groups
and no selection bias (the lineage's convention); rope over interleaved
pairs ``(x[2i], x[2i+1])`` (a permutation of seeded weights against the
half-split form).  NOT BUILT: the multi-token-prediction module
(``num_nextn_predict_layers``): it adds nothing to the next-token
logits, and a draft module fed the target's hidden state is something
the ``draft_spec`` program cannot run yet.  The training forward pass has no
such block either (``models/transformer.py::forward`` refuses).

Parameter tree (stacked over the layers of a kind)::

    embed.tokens [V, e]   lm_head [e, V]   final_norm
    dense_layers   [first_k_dense, ...]  attn, mlp {wi, wg, wo}, 4 norms
    layers         [the rest, ...]       attn, moe {router [e, E],
                       experts {wg, wu, wd: [held, F, e]},
                       shared {wi, wg, wo}}, 4 norms
    attn: wq_a [e, r_q], q_norm, wq_b [r_q, H, d_n + d_r],
          wkv_a [e, r_kv + d_r], kv_norm, wkv_b_k [r_kv, H, d_n],
          wkv_b_v [r_kv, H, d_v], wo [H, d_v, e]

A routed expert's weights are drawn from its GLOBAL index, so the share
that holds experts 32..47 holds the uncut model's experts 32..47.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from .transformer import CausalLM, TransformerConfig, _boxed


def pangu_moe_config(source: Dict[str, Any], *, experts_first: int = 0,
                     max_seq_len: int = 4096,
                     dtype=jnp.bfloat16) -> TransformerConfig:
    """The repo's configuration from the source's own ``config.json``
    keys.  ``n_routed_experts`` is the experts HELD by this process when
    the dict also gives ``n_routed_experts_scored`` (a chip's share: the
    router keeps that many outputs); otherwise all are held."""
    assert source.get("hidden_act", "silu") == "silu"
    assert not source.get("tie_word_embeddings", False)
    assert not source.get("attention_bias", False)
    scored = source.get("n_routed_experts_scored",
                        source["n_routed_experts"])
    return TransformerConfig(
        vocab_size=source["vocab_size"], hidden_size=source["hidden_size"],
        intermediate_size=source["intermediate_size"],
        num_layers=source["num_hidden_layers"],
        num_heads=source["num_attention_heads"],
        num_kv_heads=source["num_attention_heads"],
        head_dim=source["qk_nope_head_dim"] + source["qk_rope_head_dim"],
        max_seq_len=max_seq_len, norm="rmsnorm",
        norm_eps=source["rms_norm_eps"], activation="silu_gated",
        pos_emb="rope", rope_theta=float(source["rope_theta"]),
        q_lora_rank=source["q_lora_rank"],
        kv_lora_rank=source["kv_lora_rank"],
        qk_nope_head_dim=source["qk_nope_head_dim"],
        qk_rope_head_dim=source["qk_rope_head_dim"],
        v_head_dim=source["v_head_dim"],
        sandwich_norm=bool(source.get("sandwich_norm", False)),
        n_routed_experts=scored,
        experts_held=source["n_routed_experts"],
        experts_first=experts_first,
        n_shared_experts=source.get("n_shared_experts", 0),
        moe_top_k=source["num_experts_per_tok"],
        moe_intermediate_size=source["moe_intermediate_size"],
        routed_scaling_factor=float(source.get("routed_scaling_factor", 1.0)),
        norm_topk_prob=bool(source.get("norm_topk_prob", True)),
        first_k_dense=source["first_k_dense_replace"],
        dtype=dtype)


def _normal(key, shape, fan_in, dtype):
    return jax.random.normal(key, shape, dtype) * jnp.asarray(
        fan_in ** -0.5, dtype)


def _gain(dim, dtype):
    return {"scale": _boxed(jnp.ones((dim,), dtype), ("norm",))}


def _attn_init(cfg: TransformerConfig, key, dtype):
    e, h = cfg.hidden_size, cfg.num_heads
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq_a": _boxed(_normal(ks[0], (e, rq), e, dtype), ("embed", None)),
        "q_norm": _gain(rq, dtype),
        "wq_b": _boxed(_normal(ks[1], (rq, h, dn + dr), rq, dtype),
                       (None, "heads", None)),
        "wkv_a": _boxed(_normal(ks[2], (e, rkv + dr), e, dtype),
                        ("embed", None)),
        "kv_norm": _gain(rkv, dtype),
        "wkv_b_k": _boxed(_normal(ks[3], (rkv, h, dn), rkv, dtype),
                          (None, "heads", None)),
        "wkv_b_v": _boxed(_normal(ks[4], (rkv, h, dv), rkv, dtype),
                          (None, "heads", None)),
        "wo": _boxed(_normal(ks[5], (h, dv, e), h * dv, dtype),
                     ("heads", None, "embed")),
    }


def _mlp_init(e, f, key, dtype):
    ks = jax.random.split(key, 3)
    return {"wi": _boxed(_normal(ks[0], (e, f), e, dtype), ("embed", "mlp")),
            "wg": _boxed(_normal(ks[1], (e, f), e, dtype), ("embed", "mlp")),
            "wo": _boxed(_normal(ks[2], (f, e), f, dtype), ("mlp", "embed"))}


def _experts_init(cfg: TransformerConfig, key, dtype):
    """``[held, F, e]`` each, an expert's weights from its global index."""
    e, f = cfg.hidden_size, cfg.moe_intermediate_size
    ids = cfg.experts_first + jnp.arange(cfg.held_experts)

    def one(i):
        ks = jax.random.split(jax.random.fold_in(key, i), 3)
        return (_normal(ks[0], (f, e), e, dtype),
                _normal(ks[1], (f, e), e, dtype),
                _normal(ks[2], (f, e), f, dtype))

    wg, wu, wd = jax.vmap(one)(ids)
    names = ("expert", "mlp", "embed")
    return {"wg": _boxed(wg, names), "wu": _boxed(wu, names),
            "wd": _boxed(wd, names)}


def _layer_init(cfg: TransformerConfig, key, dtype, routed: bool):
    e = cfg.hidden_size
    ks = jax.random.split(key, 5)
    p = {"attn": _attn_init(cfg, ks[0], dtype),
         "norm1": _gain(e, dtype), "norm2": _gain(e, dtype)}
    if cfg.sandwich_norm:
        p["norm1_post"] = _gain(e, dtype)
        p["norm2_post"] = _gain(e, dtype)
    if not routed:
        p["mlp"] = _mlp_init(e, cfg.intermediate_size, ks[1], dtype)
        return p
    p["moe"] = {
        "router": _boxed(_normal(ks[2], (e, cfg.n_routed_experts), e,
                                 jnp.float32), ("embed", None)),
        "experts": _experts_init(cfg, ks[3], dtype)}
    if cfg.n_shared_experts:
        p["moe"]["shared"] = _mlp_init(
            e, cfg.moe_intermediate_size * cfg.n_shared_experts, ks[4],
            dtype)
    return p


def _stack(layers):
    from flax.core import meta
    return jax.tree.map(
        lambda *xs: _boxed(jnp.stack([x.value for x in xs]),
                           ("layers",) + xs[0].names),
        *layers, is_leaf=lambda x: isinstance(x, meta.Partitioned))


def init_latent_params(cfg: TransformerConfig, rng) -> Dict[str, Any]:
    """Seeded weights, drawn directly in ``cfg.dtype``: a float32 copy of
    a chip's share (4.9B parameters at published widths) would not fit
    beside the share itself.  Layer ``i``'s weights depend on ``i`` and
    the seed alone, an expert's on its global index too."""
    dtype = cfg.dtype
    e, v = cfg.hidden_size, cfg.vocab_size
    keys = jax.random.split(rng, 4)
    dense = min(cfg.first_k_dense, cfg.num_layers) \
        if cfg.n_routed_experts else cfg.num_layers
    params: Dict[str, Any] = {
        "embed": {"tokens": _boxed(
            jax.random.normal(keys[0], (v, e), dtype)
            * jnp.asarray(0.02, dtype), ("vocab", "embed"))},
        "final_norm": _gain(e, dtype),
        "lm_head": _boxed(_normal(keys[1], (e, v), e, dtype),
                          ("embed", "vocab")),
    }
    for name, lo, hi, routed in (("dense_layers", 0, dense, False),
                                 ("layers", dense, cfg.num_layers, True)):
        if hi > lo:
            params[name] = _stack([
                _layer_init(cfg, jax.random.fold_in(keys[2], i), dtype,
                            routed) for i in range(lo, hi)])
    return params


class PanguUltraMoEForCausalLM(CausalLM):
    """Seeded weights from the source's keys; served through
    ``inference/v2`` (no training loss: the block has no training
    forward pass yet)."""

    def __init__(self, source: Dict[str, Any], **overrides):
        super().__init__(pangu_moe_config(source, **overrides))

    def init_params(self, rng):
        return init_latent_params(self.cfg, rng)

    def logits(self, params, batch, rng=None):
        raise NotImplementedError(
            "pangu_ultra_moe is a served family: use inference/v2, or "
            "models/pangu_moe_reference.py for a plain forward pass")
