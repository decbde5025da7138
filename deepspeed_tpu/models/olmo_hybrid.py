"""Olmo-Hybrid (``model_type: olmo_hybrid``, Ai2) — a served family.

Gated delta-rule linear-attention layers (Gated DeltaNet) and full
multi-head attention layers in one model, ``layer_types`` naming each layer
``linear_attention`` or ``full_attention`` (three to one, the full layer
last of a period of 4 as published).  Both kinds take the OLMo 2 / 3 block
order, the norm on a sub-layer's OUTPUT: ``h = x + norm(mixer(x))``, ``out
= h + norm(mlp(h))``; the feed-forward is the llama block's SwiGLU.  No
positional encoding (``rope_parameters.rope_theta`` is null: the recurrent
layers order the tokens).  Source:
``huggingface.co/allenai/Olmo-Hybrid-7B``.

* Full layer: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` K/V heads (30 over 30), RMSNorm with a learned
  gain over the WHOLE width of ``q`` and of ``k`` (all heads together),
  causal softmax at ``1/sqrt(head_dim)``, no rope, no bias.
* Linear layer (``H = linear_num_value_heads`` heads, keys of ``dk =
  linear_key_head_dim``, values of ``dv = linear_value_head_dim``):
  ``[q ; k ; v] = W_qkv x``; a causal depthwise convolution over the last
  ``linear_conv_kernel_dim`` positions of every channel, no bias, then
  SiLU; ``q_h = l2norm(q_h) / sqrt(dk)``, ``k_h = l2norm(k_h)``; ``beta =
  2 sigmoid(W_b x)`` (``linear_allow_neg_eigval``; ``sigmoid`` alone
  without it); ``alpha = exp(-exp(A_log) softplus(W_a x + dt_bias))``; the
  recurrence of ``ops/delta_rule.py``; ``y_h = rmsnorm(o_h) * gain *
  silu((W_g x)_h)``; ``W_o concat(y)``.

The family is SERVED (``inference/v2``,
:class:`~deepspeed_tpu.inference.v2.model_implementations.
OlmoHybridInferenceModel`): a full layer keeps its K/V in pages, a linear
layer its matrix state and convolution tail in one slot of the state pool
(``layer_kinds``: "delta" / "full").  Its plain reference is
``models/olmo_hybrid_reference.py``.

Parameter tree::

    embed.tokens [V, e]   final_norm   lm_head [e, V] (unless tied)
    layers {delta, full}   the layers of each kind in order, stacked
                           [layers of the kind, ...] (``models/jamba.py``
                           says why one flat stack a kind)
    a linear layer: norm1, norm2 (the output norms), mlp {wi, wg, wo},
        mixer {w_qkv [e, H (2 dk + dv)] (q, k, v in that order, heads
        inside each), w_gate [e, H dv], w_ab [2 H, e] (the rows of W_a,
        then of W_b: 60 columns are no whole lane tile), conv_w [K, H (2
        dk + dv)], A_log [H] f32, dt_bias [H] f32, o_norm {scale [dv]},
        w_out [H dv, e]}
    a full layer: norm1, norm2, mlp, attn {wq [e, H dh], wk, wv [e, K dh],
        q_norm {scale [H dh]}, k_norm {scale [K dh]}, wo [H dh, e]}

``conv_w[k]`` weighs the input ``K - 1 - k`` positions back.

Seeded weights (the benchmark's departure from published ones): the
projections normal over fan-in; ``A_log = log(U(1, 16))`` a head and
``dt_bias`` the inverse softplus of a step drawn log-uniform in [0.001,
0.1] (the Gated DeltaNet / Mamba-2 initialisation), so that a state
neither dies nor blows up over thousands of steps.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .jamba import DT_RANGE
from .pangu_moe import _gain, _mlp_init, _normal, _stack
from .transformer import CausalLM, TransformerConfig, _boxed

#: the uniform range ``exp(A_log)`` is seeded from
A_RANGE = (1.0, 16.0)

_KIND = {"linear_attention": "delta", "full_attention": "full"}


def olmo_hybrid_config(source: Dict[str, Any], *, max_seq_len: int = 4096,
                       dtype=jnp.bfloat16,
                       state_dtype=jnp.float32) -> TransformerConfig:
    """The repo's configuration from the source's own ``config.json``
    keys."""
    assert source.get("hidden_act", "silu") == "silu"
    assert not source.get("attention_bias", False)
    rope = (source.get("rope_parameters") or {}).get("rope_theta")
    if rope is not None:
        raise ValueError(
            "models/olmo_hybrid.py: a rope_theta is not built for this "
            "family (the published configuration has none: position comes "
            "from the recurrent layers)")
    L = source["num_hidden_layers"]
    kinds = tuple(_KIND[t] for t in source["layer_types"][:L])
    assert len(kinds) == L, "layer_types names every layer"
    heads = source["num_attention_heads"]
    lin = source["linear_num_value_heads"]
    if source["linear_num_key_heads"] != lin:
        raise ValueError(
            "models/olmo_hybrid.py: fewer key heads than value heads in a "
            "linear layer (grouped keys) is not built")
    return TransformerConfig(
        vocab_size=source["vocab_size"], hidden_size=source["hidden_size"],
        intermediate_size=source["intermediate_size"], num_layers=L,
        num_heads=heads, num_kv_heads=source["num_key_value_heads"],
        head_dim=source.get("head_dim")
        or source["hidden_size"] // heads,
        max_seq_len=max_seq_len, norm="rmsnorm",
        norm_eps=source["rms_norm_eps"], activation="silu_gated",
        pos_emb="none", layer_kinds=kinds,
        heads_by_kind=(("full", heads),), post_norm=True, qk_norm=True,
        delta_heads=lin, delta_key_dim=source["linear_key_head_dim"],
        delta_value_dim=source["linear_value_head_dim"],
        delta_conv=source["linear_conv_kernel_dim"],
        delta_neg_eigval=bool(source.get("linear_allow_neg_eigval", False)),
        ssm_state_dtype=state_dtype,
        tie_embeddings=bool(source.get("tie_word_embeddings", False)),
        dtype=dtype)


def _mixer_init(cfg: TransformerConfig, key, dtype):
    e, H = cfg.hidden_size, cfg.delta_heads
    qk, dv = H * cfg.delta_key_dim, H * cfg.delta_value_dim
    K, f32 = cfg.delta_conv, jnp.float32
    ks = jax.random.split(key, 7)
    lo, hi = (math.log(v) for v in DT_RANGE)
    dt = jnp.exp(jax.random.uniform(ks[5], (H,), f32, lo, hi))
    return {
        "w_qkv": _boxed(_normal(ks[0], (e, 2 * qk + dv), e, dtype),
                        ("embed", "mlp")),
        "w_gate": _boxed(_normal(ks[1], (e, dv), e, dtype),
                         ("embed", "mlp")),
        "w_ab": _boxed(_normal(ks[2], (2 * H, e), e, dtype),
                       (None, "embed")),
        "conv_w": _boxed(_normal(ks[3], (K, 2 * qk + dv), K, dtype),
                         (None, "mlp")),
        "A_log": _boxed(jnp.log(jax.random.uniform(ks[6], (H,), f32,
                                                   *A_RANGE)), (None,)),
        # softplus(dt_bias) = dt
        "dt_bias": _boxed(dt + jnp.log(-jnp.expm1(-dt)), (None,)),
        "o_norm": _gain(cfg.delta_value_dim, dtype),
        "w_out": _boxed(_normal(ks[4], (dv, e), dv, dtype),
                        ("mlp", "embed")),
    }


def _attn_init(cfg: TransformerConfig, key, dtype):
    e, h, k, d = (cfg.hidden_size, cfg.num_heads, cfg.kv_heads,
                  cfg.dims_per_head)
    ks = jax.random.split(key, 4)
    return {
        "wq": _boxed(_normal(ks[0], (e, h * d), e, dtype),
                     ("embed", "heads")),
        "wk": _boxed(_normal(ks[1], (e, k * d), e, dtype), ("embed", "kv")),
        "wv": _boxed(_normal(ks[2], (e, k * d), e, dtype), ("embed", "kv")),
        "q_norm": _gain(h * d, dtype), "k_norm": _gain(k * d, dtype),
        "wo": _boxed(_normal(ks[3], (h * d, e), h * d, dtype),
                     ("heads", "embed")),
    }


def _layer_init(cfg: TransformerConfig, i: int, key, dtype):
    """Layer ``i``'s weights, from ``i`` and the seed alone."""
    e = cfg.hidden_size
    ks = jax.random.split(jax.random.fold_in(key, i), 2)
    p = {"norm1": _gain(e, dtype), "norm2": _gain(e, dtype),
         "mlp": _mlp_init(e, cfg.intermediate_size, ks[1], dtype)}
    if cfg.layer_kinds[i] == "delta":
        p["mixer"] = _mixer_init(cfg, ks[0], dtype)
    else:
        p["attn"] = _attn_init(cfg, ks[0], dtype)
    return p


def init_olmo_hybrid_params(cfg: TransformerConfig, rng) -> Dict[str, Any]:
    """Seeded weights, drawn directly in ``cfg.dtype``."""
    dtype = cfg.dtype
    e, v = cfg.hidden_size, cfg.vocab_size
    keys = jax.random.split(rng, 3)
    params: Dict[str, Any] = {
        "embed": {"tokens": _boxed(
            jax.random.normal(keys[0], (v, e), dtype)
            * jnp.asarray(0.02, dtype), ("vocab", "embed"))},
        "final_norm": _gain(e, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _boxed(_normal(keys[1], (e, v), e, dtype),
                                   ("embed", "vocab"))
    params["layers"] = {
        kind: _stack([_layer_init(cfg, i, keys[2], dtype)
                      for i, k in enumerate(cfg.layer_kinds) if k == kind])
        for kind in dict.fromkeys(cfg.layer_kinds)}
    return params


class OlmoHybridForCausalLM(CausalLM):
    """Seeded weights from the source's keys; served through
    ``inference/v2`` (no training loss: the chunked form has no backward
    here)."""

    def __init__(self, source: Dict[str, Any], **overrides):
        super().__init__(olmo_hybrid_config(source, **overrides))

    def init_params(self, rng):
        return init_olmo_hybrid_params(self.cfg, rng)

    def logits(self, params, batch, rng=None):
        raise NotImplementedError(
            "olmo_hybrid is a served family: use inference/v2, or "
            "models/olmo_hybrid_reference.py for a plain forward pass")
