"""Laguna (``model_type: laguna``, poolside) — a served family.

Layers of two attention kinds in one model: ``layer_types`` says which
layers attend the whole context (``full_attention``) and which the last
``sliding_window`` positions (``sliding_attention``), and
``num_attention_heads_per_layer`` gives each kind its own count of query
heads over the same ``num_key_value_heads``.  Every head's attention
output is multiplied by ``sigmoid(h Wg)_n`` (``gating: per-head``) before
``Wo``.  The full kind rotates ``partial_rotary_factor`` of a head's dims
under YaRN, the window kind all of them under a plain rope of its own
base.  ``mlp_only_layers`` keep a dense SwiGLU; every other layer routes
``num_experts_per_tok`` of ``num_experts`` small experts beside one shared
expert.  Source: ``huggingface.co/poolside/Laguna-S-2.1``.

The family is SERVED (``inference/v2``,
:class:`~deepspeed_tpu.inference.v2.model_implementations.
LagunaInferenceModel`): the two kinds keep their K/V in two page groups,
so a window layer's pages go back to the pool as the context passes the
window while a full layer's stay.  Where a routed layer does not fit one
chip, ``experts_held`` of its experts are here (``moe/held.py``).  Its
plain reference is ``models/laguna_reference.py``.

Not in the source's config, so ASSUMED: the router is a float32 softmax
over all experts, the ``num_experts_per_tok`` largest normalised over
themselves and multiplied by ``moe_routed_scaling_factor`` (the key names
are the qwen2_moe lineage's, whose router is that softmax); no gate on
the shared expert and no Q/K norm (no key names one); rope over
interleaved pairs ``(x[2i], x[2i+1])`` (a permutation of seeded weights
against the half-split form).

Parameter tree (``cfg.layer_kinds`` is the layers in order)::

    embed.tokens [V, e]   lm_head [e, V]   final_norm
    dense_layers {l0, ...}   the leading dense layers, a tree each
    periods      {l0, ...}   layer j of every whole period of the layer
                             pattern, stacked over the periods
    tail         {l0, ...}   the layers after the last whole period
    experts {wg, wu, wd: [routed layers, held, F, e]}
    a layer: attn {wq [e, H_kind * d], wk, wv [e, K * d],
             wo [H_kind * d, e], wgate [e, H_kind]}, norm1, norm2, and
             mlp {wi, wg, wo} or moe {router [e, E], shared {wi, wg, wo}}

The attention projections are stored as the matrices the products take
(heads folded into the columns, head n = columns n*d .. n*d + d - 1):
stored ``[e, H, d]``, every step program re-laid each of them out before
its product (0.3 GB copied a step at the published widths;
``tests/test_chip_compile.py``).

The held experts of ALL routed layers are one stack, which the expert
kernel addresses by the routed layer's index (scanned with the layers, a
layer's experts would be sliced out of the stack for the custom call).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .pangu_moe import _gain, _mlp_init, _normal, _stack
from .transformer import (CausalLM, TransformerConfig, _boxed,
                          layer_runs)

KINDS = {"full_attention": "full", "sliding_attention": "window"}


def laguna_config(source: Dict[str, Any], *, experts_first: int = 0,
                  max_seq_len: int = 4096,
                  dtype=jnp.bfloat16) -> TransformerConfig:
    """The repo's configuration from the source's own ``config.json``
    keys.  The per-layer lists may be longer than ``num_hidden_layers``
    (a cut in depth keeps the published lists whole): the first
    ``num_hidden_layers`` entries are read.  ``num_experts`` is the
    experts HELD by this process when the dict also gives
    ``num_experts_scored`` (a chip's share: the router keeps that many
    outputs); otherwise all are held."""
    L = source["num_hidden_layers"]
    assert not source.get("tie_word_embeddings", False)
    assert not source.get("attention_bias", False)
    assert source.get("gating", "per-head") == "per-head"
    assert all(g == "per_head"
               for g in source.get("gating_types", ["per_head"] * L)[:L])
    assert not source.get("moe_router_logit_softcapping", 0)
    assert not source.get("moe_apply_router_weight_on_input", False)
    assert source.get("decoder_sparse_step", 1) == 1
    kinds = tuple(KINDS[t] for t in source["layer_types"][:L])
    heads = dict(zip(kinds, source["num_attention_heads_per_layer"][:L]))
    assert [heads[k] for k in kinds] == list(
        source["num_attention_heads_per_layer"][:L]), \
        "query heads differ between layers of one kind"
    assert heads.get("full", source["num_attention_heads"]) \
        == source["num_attention_heads"]
    dense = sorted(i for i in source.get("mlp_only_layers", []) if i < L)
    assert dense == list(range(len(dense))), \
        "dense layers are served only as a leading run"
    mlp_types = source.get("mlp_layer_types")
    if mlp_types is not None:
        assert [t == "dense" for t in mlp_types[:L]] \
            == [i < len(dense) for i in range(L)], \
            "mlp_only_layers and mlp_layer_types disagree"
    shared = source.get("shared_expert_intermediate_size", 0)
    width = source["moe_intermediate_size"]
    assert shared % width == 0, "the shared expert is whole expert widths"
    rope = source["rope_parameters"]
    full, window = rope["full_attention"], rope["sliding_attention"]
    assert window.get("rope_type", "default") == "default"
    assert window.get("partial_rotary_factor", 1) == 1
    yarn: Tuple[float, ...] = ()
    if full.get("rope_type", "default") == "yarn":
        yarn = (float(full["factor"]),
                float(full["original_max_position_embeddings"]),
                float(full["beta_fast"]), float(full["beta_slow"]),
                float(full["attention_factor"]))
    scored = source.get("num_experts_scored", source["num_experts"])
    return TransformerConfig(
        vocab_size=source["vocab_size"], hidden_size=source["hidden_size"],
        intermediate_size=source["intermediate_size"], num_layers=L,
        num_heads=source["num_attention_heads"],
        num_kv_heads=source["num_key_value_heads"],
        head_dim=source["head_dim"], max_seq_len=max_seq_len,
        norm="rmsnorm", norm_eps=source["rms_norm_eps"],
        activation="silu_gated", pos_emb="rope",
        rope_theta=float(full["rope_theta"]),
        rope_pct=float(full.get("partial_rotary_factor", 1.0)),
        rope_yarn=yarn, window_rope_theta=float(window["rope_theta"]),
        sliding_window=source["sliding_window"],
        layer_kinds=kinds, heads_by_kind=tuple(sorted(heads.items())),
        head_gate=True, router_scoring="softmax",
        n_routed_experts=scored, experts_held=source["num_experts"],
        experts_first=experts_first, n_shared_experts=shared // width,
        moe_top_k=source["num_experts_per_tok"],
        moe_intermediate_size=width,
        routed_scaling_factor=float(
            source.get("moe_routed_scaling_factor", 1.0)),
        norm_topk_prob=bool(source.get("norm_topk_prob", True)),
        first_k_dense=len(dense), dtype=dtype)


def group_layers(cfg: TransformerConfig) -> Dict[str, int]:
    """Layers of each attention kind: the layers of its page group."""
    return {kind: cfg.layer_kinds.count(kind) for kind in ("full", "window")}


def rope_frequencies(cfg: TransformerConfig, kind: str
                     ) -> Tuple[jax.Array, float]:
    """(inverse frequencies of the rotated pairs, what cos and sin are
    multiplied by) of one attention kind.  The window kind: every dim,
    plain.  The full kind: ``rope_pct`` of the dims, under YaRN blended
    between interpolation (low frequencies) and extrapolation (high)."""
    d = cfg.dims_per_head
    if kind == "window":
        return cfg.window_rope_theta ** (
            -jnp.arange(0, d, 2, dtype=jnp.float32) / d), 1.0
    d = int(d * cfg.rope_pct)
    d -= d % 2
    extrapolated = cfg.rope_theta ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if not cfg.rope_yarn:
        return extrapolated, 1.0
    factor, original, beta_fast, beta_slow, attention_factor = cfg.rope_yarn

    def correction_dim(rotations):
        return d * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return (extrapolated / factor * ramp + extrapolated * (1.0 - ramp),
            attention_factor)


def rope_table(cfg: TransformerConfig, kind: str, positions: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """(sin, cos) ``[..., rotated dims / 2]`` of one kind, as
    ``transformer.apply_rope`` takes them."""
    freqs, scale = rope_frequencies(cfg, kind)
    angles = positions[..., None].astype(jnp.float32) * freqs
    return jnp.sin(angles) * scale, jnp.cos(angles) * scale


def _attn_init(cfg: TransformerConfig, heads: int, key, dtype):
    e, k, d = cfg.hidden_size, cfg.kv_heads, cfg.dims_per_head
    ks = jax.random.split(key, 5)
    return {
        "wq": _boxed(_normal(ks[0], (e, heads * d), e, dtype),
                     ("embed", "heads")),
        "wk": _boxed(_normal(ks[1], (e, k * d), e, dtype),
                     ("embed", "kv")),
        "wv": _boxed(_normal(ks[2], (e, k * d), e, dtype),
                     ("embed", "kv")),
        "wo": _boxed(_normal(ks[3], (heads * d, e), heads * d, dtype),
                     ("heads", "embed")),
        "wgate": _boxed(_normal(ks[4], (e, heads), e, dtype),
                        ("embed", "heads")),
    }


def _layer_init(cfg: TransformerConfig, i: int, key, dtype):
    """Layer ``i``'s weights but its routed experts, from ``i`` and the
    seed alone."""
    e = cfg.hidden_size
    ks = jax.random.split(jax.random.fold_in(key, i), 4)
    heads = dict(cfg.heads_by_kind)[cfg.layer_kinds[i]]
    p = {"attn": _attn_init(cfg, heads, ks[0], dtype),
         "norm1": _gain(e, dtype), "norm2": _gain(e, dtype)}
    if i < cfg.first_k_dense:
        p["mlp"] = _mlp_init(e, cfg.intermediate_size, ks[1], dtype)
        return p
    p["moe"] = {"router": _boxed(
        _normal(ks[2], (e, cfg.n_routed_experts), e, jnp.float32),
        ("embed", None))}
    if cfg.n_shared_experts:
        p["moe"]["shared"] = _mlp_init(
            e, cfg.moe_intermediate_size * cfg.n_shared_experts, ks[3],
            dtype)
    return p


def _experts_init(cfg: TransformerConfig, key, dtype):
    """``[routed layers, held, F, e]`` each: an expert's weights from its
    layer and its GLOBAL index, so the share that holds experts 32..47
    holds the uncut model's experts 32..47."""
    e, f = cfg.hidden_size, cfg.moe_intermediate_size
    ids = cfg.experts_first + jnp.arange(cfg.held_experts)

    def one(layer, i):
        ks = jax.random.split(
            jax.random.fold_in(jax.random.fold_in(key, layer), i), 3)
        return (_normal(ks[0], (f, e), e, dtype),
                _normal(ks[1], (f, e), e, dtype),
                _normal(ks[2], (f, e), f, dtype))

    layers = jnp.arange(cfg.first_k_dense, cfg.num_layers)
    wg, wu, wd = jax.vmap(lambda l: jax.vmap(lambda i: one(l, i))(ids)
                          )(layers)
    names = ("layers", "expert", "mlp", "embed")
    return {"wg": _boxed(wg, names), "wu": _boxed(wu, names),
            "wd": _boxed(wd, names)}


def init_laguna_params(cfg: TransformerConfig, rng) -> Dict[str, Any]:
    """Seeded weights, drawn directly in ``cfg.dtype``."""
    dtype = cfg.dtype
    e, v = cfg.hidden_size, cfg.vocab_size
    keys = jax.random.split(rng, 4)
    dense, runs, periods, _ = layer_runs(cfg)
    period = sum(n for _, n in runs)
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
    if periods:
        params["periods"] = {
            f"l{j}": _stack([one(dense + p * period + j)
                             for p in range(periods)])
            for j in range(period)}
    tail = range(dense + periods * period, cfg.num_layers)
    if tail:
        params["tail"] = {f"l{n}": one(i) for n, i in enumerate(tail)}
    if cfg.num_layers > dense:
        params["experts"] = _experts_init(cfg, keys[3], dtype)
    return params


class LagunaForCausalLM(CausalLM):
    """Seeded weights from the source's keys; served through
    ``inference/v2`` (no training loss: the training forward pass has one
    head count and one attention kind)."""

    def __init__(self, source: Dict[str, Any], **overrides):
        super().__init__(laguna_config(source, **overrides))

    def init_params(self, rng):
        return init_laguna_params(self.cfg, rng)

    def logits(self, params, batch, rng=None):
        raise NotImplementedError(
            "laguna is a served family: use inference/v2, or "
            "models/laguna_reference.py for a plain forward pass")
