"""Ling-3.0 (``model_type: bailing_hybrid``, inclusionAI) — a served family.

Kimi-delta (KDA) linear-attention layers and latent-attention (MLA) layers
in ONE model, ``layer_group_size`` layers a period with the latent layer
last (published layer ``i`` is latent where ``(i + 1) % layer_group_size ==
0``); ``first_k_dense_replace`` leading layers keep a dense SwiGLU, every
other layer routes over ``num_experts`` experts beside one shared expert.
Pre-norm residuals, RMSNorm, no bias, untied head.  Source:
``huggingface.co/inclusionAI/Ling-3.0-flash``.

* KDA layer (Kimi Linear, arXiv:2510.26692; ``H`` heads of ``d =
  head_dim``): ``q, k, v = silu(conv(W x))`` (a causal depthwise
  convolution over the last ``short_conv_kernel_size`` positions, no
  bias); ``q_h = l2norm(q_h) / sqrt(d)``, ``k_h = l2norm(k_h)``; a
  log-decay A KEY CHANNEL ``g = kda_lower_bound * sigmoid(exp(A_log_h) *
  (W_f x + dt_bias))`` in ``(kda_lower_bound, 0)`` (the safe gate), ``beta =
  sigmoid(W_beta x)`` a head; the recurrence of ``ops/delta_rule.py`` under
  that decay, ``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t
  k_t v_t^T``, ``o_t = S_t^T q_t``; ``y_h = rmsnorm(o_h) * gain *
  sigmoid((W_g x)_h)`` (one gate a head:
  ``gated_attention_proj_granularity_type`` ``head_wise``); ``W_o
  concat(y)``.
* Latent layer: ``models/pangu_moe.py``'s block with the query projected
  directly (``q_lora_rank`` null: no low-rank step, no query norm), rope
  over interleaved pairs of the ``qk_rope_head_dim`` dims.
* Router (``topk_method`` ``noaux_tc``): sigmoid scores ``s`` over all
  experts, ``c = s + bias`` chooses (a group's score the sum of its two
  largest ``c``; the ``topk_group`` best of ``n_group`` groups; the
  ``num_experts_per_tok`` largest ``c`` inside them), the weights are
  ``routed_scaling_factor * s_i / sum s_j`` over the chosen
  (``moe/held.py::route_sigmoid_grouped``).

ASSUMED (the published keys do not settle them; the benchmark's
``published/inclusionai-ling-3.0-flash.json`` carries each with its why):
the safe gate's form; sigmoid for the head-wise output gate, applied to the
KDA layers only; ``use_qk_norm`` = the KDA layers' l2 normalisation (no norm
added to the latent layer); the bias's scale.  NOT BUILT: the
multi-token-prediction layer; a non-zero ``expert_swiglu_limit_list`` /
``share_expert_swiglu_limit_list`` entry of a held layer RAISES (its form
is not published).

The family is SERVED (``inference/v2``, :class:`~deepspeed_tpu.inference.
v2.model_implementations.BailingHybridInferenceModel`) as one chip of an
expert-parallel group: the latent layers' planes in pages, a KDA layer's
matrix state and convolution tail in one slot of the state pool
(``layer_kinds``: "kda" / "latent"), ``experts_held`` of a layer's experts
here.  Its plain reference is ``models/bailing_hybrid_reference.py``.

Parameter tree::

    embed.tokens [V, e]   final_norm   lm_head [e, V]
    dense_layers {l<i>}   the leading layers, each a tree of its own
    runs {r<j>}           the layers behind them in RUNS of like kinds (KDA
                          x 3, the latent layer, KDA x 2 in one period),
                          each run's layers stacked: ONE scan, one body in
                          the step program, a run (``model.py::_layer_loop``
                          (c): a body a layer made programs the compile
                          cache could not hold)
    experts {wg, wu, wd}  [routed layers, held, F, e]
    a KDA layer: norm1, norm2, mixer {w_qkv [e, 3 H d] (q, k, v, heads
        inside each), w_f [H d, e] (out-major: the chip's compiler re-laid
        an [e, H d] one out three times a step), w_bg [2 H, e] (the rows of
        W_beta, then of W_g), conv_w [K, 3 H d], A_log [H] f32, dt_bias
        [H d] f32,
        o_norm {scale [d]}, w_out [H d, e]}
    a latent layer: norm1, norm2, attn {wq [e, H, d_n + d_r], wkv_a,
        kv_norm, wkv_b_k, wkv_b_v, wo}
    a dense layer: mlp {wi, wg, wo}; a routed one: moe {router [e, E] f32,
        router_bias [E] f32, shared {wi, wg, wo}}

Seeded weights (the benchmark's departure from published ones):
projections normal over fan-in; ``exp(A_log)`` uniform in :data:`A_RANGE`
a head; ``dt_bias`` such that a channel's decay at ``W_f x = 0`` is a step
drawn log-uniform from :data:`G_RANGE` (memories of a token to hundreds of
tokens, data-dependent around it); ``router_bias`` normal at
:data:`BIAS_SCALE`.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .laguna import _experts_init
from .pangu_moe import _gain, _mlp_init, _normal, _stack
from .transformer import CausalLM, TransformerConfig, _boxed, kind_runs

#: the uniform range ``exp(A_log)`` is seeded from: the slope of the gate in
#: its input (``lower * exp(A_log) * sigmoid'``).  On the chip a state held
#: in bfloat16 (the probe's nearest-precision control) reads 1.45 times the
#: sound median and a state drift of 1.24-1.28 under this range, 1.3 times
#: and no drift under (1, 4): the milder gate keeps a channel's memory long
#: enough for the state's rounding to show over 2,000 steps
A_RANGE = (0.25, 1.0)
#: ``-g`` of a channel at ``W_f x = 0`` is seeded log-uniform from this
G_RANGE = (0.002, 2.0)
#: the standard deviation the selection bias is seeded with: the 8 largest
#: of 512 sigmoid scores lie at 0.91-0.95, 0.01-0.04 apart, so a bias at
#: 0.1 IS the choice (on the chip: 31% of the held experts touched a step,
#: the fullest at 30 times the mean); at 0.02 it moves a quarter of the
#: pairs and the routing stays even (94% touched, the fullest 3.5 times)
BIAS_SCALE = 0.02


def bailing_hybrid_config(source: Dict[str, Any], *, experts_first: int = 0,
                          first_layer: int = 0, max_seq_len: int = 4096,
                          dtype=jnp.bfloat16,
                          state_dtype=jnp.float32) -> TransformerConfig:
    """The repo's configuration from the source's own ``config.json``
    keys.  ``first_layer``: the published index of the first layer held
    (a stage of the depth: the pattern and the limit lists are read from
    there).  ``num_experts`` is the experts HELD by this process when the
    dict also gives ``num_experts_scored`` (a chip's share: the router
    keeps that many outputs); otherwise all are held."""
    assert source.get("hidden_act", "silu") == "silu"
    assert not source.get("tie_word_embeddings", False)
    assert not source.get("use_bias", False) \
        and not source.get("use_qkv_bias", False)
    L, group = source["num_hidden_layers"], source["layer_group_size"]
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        limits = list(source.get(key) or [])[first_layer:first_layer + L]
        if any(limits):
            raise ValueError(
                f"models/bailing_hybrid.py: {key} is non-zero on a held "
                f"layer ({limits} from layer {first_layer}): the limit's "
                "form is not published and is not built")
    if source.get("q_lora_rank"):
        raise ValueError("models/bailing_hybrid.py: a low-rank query "
                         "(q_lora_rank) is not built for this family")
    for key, want in (("score_function", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("kda_safe_gate", True),
                      ("no_kda_lora", True), ("linear_silu", True),
                      ("use_qk_norm", True), ("group_norm_size", 1),
                      ("gated_attention_proj_granularity_type", "head_wise"),
                      ("rope_interleave", True),
                      ("moe_router_enable_expert_bias", True)):
        if source.get(key, want) != want:
            raise ValueError(f"models/bailing_hybrid.py: {key}="
                             f"{source[key]!r} is not built (only {want!r})")
    heads, d = source["num_attention_heads"], source["head_dim"]
    if source.get("num_kv_heads_for_linear_attn", 0) not in (0, heads):
        raise ValueError("models/bailing_hybrid.py: fewer key heads than "
                         "query heads in a KDA layer is not built")
    shared = source.get("num_shared_experts", 0)
    assert not shared or source["moe_shared_expert_intermediate_size"] \
        == source["moe_intermediate_size"]
    kinds = tuple("latent" if (first_layer + i + 1) % group == 0 else "kda"
                  for i in range(L))
    return TransformerConfig(
        vocab_size=source["vocab_size"], hidden_size=source["hidden_size"],
        intermediate_size=source["intermediate_size"], num_layers=L,
        num_heads=heads, num_kv_heads=heads,
        head_dim=source["qk_nope_head_dim"] + source["qk_rope_head_dim"],
        max_seq_len=max_seq_len, norm="rmsnorm",
        norm_eps=source["rms_norm_eps"], activation="silu_gated",
        pos_emb="rope", rope_theta=float(source["rope_theta"]),
        kv_lora_rank=source["kv_lora_rank"],
        qk_nope_head_dim=source["qk_nope_head_dim"],
        qk_rope_head_dim=source["qk_rope_head_dim"],
        v_head_dim=source["v_head_dim"],
        n_routed_experts=source.get("num_experts_scored",
                                    source["num_experts"]),
        experts_held=source["num_experts"], experts_first=experts_first,
        n_shared_experts=shared,
        moe_top_k=source["num_experts_per_tok"],
        moe_intermediate_size=source["moe_intermediate_size"],
        routed_scaling_factor=float(source.get("routed_scaling_factor", 1.0)),
        norm_topk_prob=bool(source.get("norm_topk_prob", True)),
        first_k_dense=source["first_k_dense_replace"],
        router_scoring="sigmoid_grouped", router_groups=source["n_group"],
        router_topk_groups=source["topk_group"], layer_kinds=kinds,
        delta_heads=heads, delta_key_dim=d, delta_value_dim=d,
        delta_conv=source["short_conv_kernel_size"],
        kda_lower_bound=float(source["kda_lower_bound"]),
        ssm_state_dtype=state_dtype, dtype=dtype)


def _kda_init(cfg: TransformerConfig, key, dtype):
    e, H, d = cfg.hidden_size, cfg.delta_heads, cfg.delta_key_dim
    K, f32 = cfg.delta_conv, jnp.float32
    ks = jax.random.split(key, 7)
    a = jax.random.uniform(ks[5], (H,), f32, *A_RANGE)
    lo, hi = (math.log(v) for v in G_RANGE)
    step = jnp.exp(jax.random.uniform(ks[6], (H, d), f32, lo, hi)) \
        / abs(cfg.kda_lower_bound)
    return {
        "w_qkv": _boxed(_normal(ks[0], (e, 3 * H * d), e, dtype),
                        ("embed", "mlp")),
        "w_f": _boxed(_normal(ks[1], (H * d, e), e, dtype),
                      ("mlp", "embed")),
        "w_bg": _boxed(_normal(ks[2], (2 * H, e), e, dtype),
                       (None, "embed")),
        "conv_w": _boxed(_normal(ks[3], (K, 3 * H * d), K, dtype),
                         (None, "mlp")),
        "A_log": _boxed(jnp.log(a), (None,)),
        # sigmoid(exp(A_log) dt_bias) = step
        "dt_bias": _boxed(((jnp.log(step) - jnp.log1p(-step))
                           / a[:, None]).reshape(H * d), (None,)),
        "o_norm": _gain(d, dtype),
        "w_out": _boxed(_normal(ks[4], (H * d, e), H * d, dtype),
                        ("mlp", "embed")),
    }


def _latent_init(cfg: TransformerConfig, key, dtype):
    e, h, rkv = cfg.hidden_size, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 5)
    return {
        "wq": _boxed(_normal(ks[0], (e, h, dn + dr), e, dtype),
                     ("embed", "heads", None)),
        "wkv_a": _boxed(_normal(ks[1], (e, rkv + dr), e, dtype),
                        ("embed", None)),
        "kv_norm": _gain(rkv, dtype),
        "wkv_b_k": _boxed(_normal(ks[2], (rkv, h, dn), rkv, dtype),
                          (None, "heads", None)),
        "wkv_b_v": _boxed(_normal(ks[3], (rkv, h, dv), rkv, dtype),
                          (None, "heads", None)),
        "wo": _boxed(_normal(ks[4], (h, dv, e), h * dv, dtype),
                     ("heads", None, "embed")),
    }


def _layer_init(cfg: TransformerConfig, i: int, key, dtype):
    """Layer ``i``'s weights but its routed experts, from ``i`` and the
    seed alone."""
    e = cfg.hidden_size
    ks = jax.random.split(jax.random.fold_in(key, i), 5)
    p = {"norm1": _gain(e, dtype), "norm2": _gain(e, dtype)}
    if cfg.layer_kinds[i] == "kda":
        p["mixer"] = _kda_init(cfg, ks[0], dtype)
    else:
        p["attn"] = _latent_init(cfg, ks[0], dtype)
    if i < cfg.first_k_dense:
        p["mlp"] = _mlp_init(e, cfg.intermediate_size, ks[1], dtype)
        return p
    p["moe"] = {
        "router": _boxed(_normal(ks[2], (e, cfg.n_routed_experts), e,
                                 jnp.float32), ("embed", None)),
        "router_bias": _boxed(BIAS_SCALE * jax.random.normal(
            ks[4], (cfg.n_routed_experts,), jnp.float32), (None,))}
    if cfg.n_shared_experts:
        p["moe"]["shared"] = _mlp_init(
            e, cfg.moe_intermediate_size * cfg.n_shared_experts, ks[3],
            dtype)
    return p


def init_bailing_hybrid_params(cfg: TransformerConfig, rng
                               ) -> Dict[str, Any]:
    """Seeded weights, drawn directly in ``cfg.dtype``."""
    dtype = cfg.dtype
    e, v = cfg.hidden_size, cfg.vocab_size
    keys = jax.random.split(rng, 4)
    dense = min(cfg.first_k_dense, cfg.num_layers)
    params: Dict[str, Any] = {
        "embed": {"tokens": _boxed(
            jax.random.normal(keys[0], (v, e), dtype)
            * jnp.asarray(0.02, dtype), ("vocab", "embed"))},
        "final_norm": _gain(e, dtype),
        "lm_head": _boxed(_normal(keys[1], (e, v), e, dtype),
                          ("embed", "vocab")),
    }

    def one(i):
        return _layer_init(cfg, i, keys[2], dtype)

    if dense:
        params["dense_layers"] = {f"l{i}": one(i) for i in range(dense)}
    at = dense
    params["runs"] = {}
    for j, (_, n) in enumerate(kind_runs(cfg.layer_kinds[dense:])):
        params["runs"][f"r{j}"] = _stack([one(at + m) for m in range(n)])
        at += n
    if cfg.num_layers > dense:
        params["experts"] = _experts_init(cfg, keys[3], dtype)
    return params


class BailingHybridForCausalLM(CausalLM):
    """Seeded weights from the source's keys; served through
    ``inference/v2`` (no training loss: neither mixer has a backward
    here)."""

    def __init__(self, source: Dict[str, Any], **overrides):
        super().__init__(bailing_hybrid_config(source, **overrides))

    def init_params(self, rng):
        return init_bailing_hybrid_params(self.cfg, rng)

    def logits(self, params, batch, rng=None):
        raise NotImplementedError(
            "bailing_hybrid is a served family: use inference/v2, or "
            "models/bailing_hybrid_reference.py for a plain forward pass")
