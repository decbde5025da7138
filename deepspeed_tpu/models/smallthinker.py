"""SmallThinker (``model_name: smallthinker_*``, PowerInfer) — a served
family.

Every layer routes ``moe_num_active_primary_experts`` of
``moe_num_primary_experts`` small ReLU-gated (ReGLU) experts, no shared
expert, no dense layer, and the ROUTER READS THE ATTENTION BLOCK'S INPUT:
its scores come from the layer's first norm, before attention, so what a
token's experts are is known while attention still runs.  Two per-layer
lists give the layers their kinds: ``sliding_window_layout`` (1: the layer
attends the last ``sliding_window_size`` positions, 0: the whole context)
and ``rope_layout`` (1: q and k under a plain rope over all of a head's
dims, 0: no positional encoding).  The published model pairs them, a
global layer without rope then three window layers with it, and that
pairing is what is served: the two lists must agree layer by layer.
Source: ``huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct``.

The family is SERVED (``inference/v2``,
:class:`~deepspeed_tpu.inference.v2.model_implementations.
SmallThinkerInferenceModel`): the two kinds keep their K/V in two page
groups with ONE head count, and every expert of a layer is held here
(``moe/held.py`` with ``first`` 0 and ``held`` all of them) unless
``moe_num_primary_experts_scored`` says the router scores more.  Its plain
reference is ``models/smallthinker_reference.py``.

Not in the source's config, so ASSUMED: the router's input is the NORMED
attention input (the family is described as "router placed before
attention"); the gate's activation is ReLU ("sparse ReGLU"; the config
has no ``hidden_act``); no attention bias and no Q/K norm (no key names
either); rope over interleaved pairs ``(x[2i], x[2i+1])`` (a permutation
of seeded weights against the half-split form).

Parameter tree (``cfg.layer_kinds`` is the layers in order)::

    embed.tokens [V, e]   lm_head [e, V]   final_norm
    periods {l0, ...}   layer j of every whole period of the layer
                        pattern, stacked over the periods
    tail    {l0, ...}   the layers after the last whole period
    experts {wg, wu, wd: [layers, held, F, e]}
    a layer: attn {wq [e, H * d], wk, wv [e, K * d], wo [H * d, e]},
             norm1, norm2, moe {router [e, E] float32}

as Laguna's (``models/laguna.py``: the projections stored as the matrices
the products take, the experts of all layers one stack the kernel
addresses by the layer's index).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from .pangu_moe import _gain, _normal, _stack
from .transformer import CausalLM, TransformerConfig, _boxed, layer_runs


def smallthinker_config(source: Dict[str, Any], *, experts_first: int = 0,
                        max_seq_len: int = 4096,
                        dtype=jnp.bfloat16) -> TransformerConfig:
    """The repo's configuration from the source's own ``config.json``
    keys.  The per-layer lists may be longer than ``num_hidden_layers``
    (a cut in depth keeps the published lists whole): the first
    ``num_hidden_layers`` entries are read.  ``moe_num_primary_experts``
    is the experts HELD by this process when the dict also gives
    ``moe_num_primary_experts_scored``; otherwise all are held."""
    L = source["num_hidden_layers"]
    window = list(source["sliding_window_layout"][:L])
    rope = list(source["rope_layout"][:L])
    if len(window) != L or window != rope:
        raise ValueError(
            "models/smallthinker.py: sliding_window_layout and rope_layout "
            f"must name all {L} layers and agree layer by layer (a window "
            "layer is roped, a global layer is not): a global layer under "
            "rope or a window layer without it is a third and fourth "
            f"attention kind that no page group serves; got {window} and "
            f"{rope}")
    assert not source.get("tie_word_embeddings", False)
    assert source.get("rope_scaling") is None
    assert source.get("moe_primary_router_apply_softmax", True), \
        "the router's weights are a softmax's"
    kinds = tuple("window" if w else "full" for w in window)
    heads = source["num_attention_heads"]
    scored = source.get("moe_num_primary_experts_scored",
                        source["moe_num_primary_experts"])
    return TransformerConfig(
        vocab_size=source["vocab_size"], hidden_size=source["hidden_size"],
        # no layer has a dense block: the width and ``activation`` are idle
        intermediate_size=source["moe_ffn_hidden_size"], num_layers=L,
        num_heads=heads, num_kv_heads=source["num_key_value_heads"],
        head_dim=source["head_dim"], max_seq_len=max_seq_len,
        norm="rmsnorm", norm_eps=source["rms_norm_eps"], pos_emb="rope",
        rope_theta=float(source["rope_theta"]),
        sliding_window=source["sliding_window_size"],
        layer_kinds=kinds,
        heads_by_kind=tuple((k, heads) for k in sorted(set(kinds))),
        nope_kinds=("full",), router_scoring="softmax",
        router_reads="mixer", expert_act="relu",
        n_routed_experts=scored,
        experts_held=source["moe_num_primary_experts"],
        experts_first=experts_first,
        moe_top_k=source["moe_num_active_primary_experts"],
        moe_intermediate_size=source["moe_ffn_hidden_size"],
        norm_topk_prob=bool(source.get("norm_topk_prob", True)),
        dtype=dtype)


def _layer_init(cfg: TransformerConfig, i: int, key, dtype):
    """Layer ``i``'s weights but its experts, from ``i`` and the seed
    alone."""
    e, h, k, d = (cfg.hidden_size, cfg.num_heads, cfg.kv_heads,
                  cfg.dims_per_head)
    ks = jax.random.split(jax.random.fold_in(key, i), 5)
    return {
        "attn": {
            "wq": _boxed(_normal(ks[0], (e, h * d), e, dtype),
                         ("embed", "heads")),
            "wk": _boxed(_normal(ks[1], (e, k * d), e, dtype),
                         ("embed", "kv")),
            "wv": _boxed(_normal(ks[2], (e, k * d), e, dtype),
                         ("embed", "kv")),
            "wo": _boxed(_normal(ks[3], (h * d, e), h * d, dtype),
                         ("heads", "embed"))},
        "norm1": _gain(e, dtype), "norm2": _gain(e, dtype),
        "moe": {"router": _boxed(
            _normal(ks[4], (e, cfg.n_routed_experts), e, jnp.float32),
            ("embed", None))}}


def _experts_init(cfg: TransformerConfig, key, dtype):
    """``[layers, held, F, e]`` each: an expert's weights from its layer
    and its GLOBAL index.  The layers are drawn one after another
    (``lax.map``): all 64 experts of 8 layers at once would hold the
    random bits of 6 GB of weights beside them."""
    e, f = cfg.hidden_size, cfg.moe_intermediate_size
    ids = cfg.experts_first + jnp.arange(cfg.held_experts)

    def one(layer, i):
        ks = jax.random.split(
            jax.random.fold_in(jax.random.fold_in(key, layer), i), 3)
        return (_normal(ks[0], (f, e), e, dtype),
                _normal(ks[1], (f, e), e, dtype),
                _normal(ks[2], (f, e), f, dtype))

    wg, wu, wd = jax.lax.map(
        lambda l: jax.vmap(lambda i: one(l, i))(ids),
        jnp.arange(cfg.num_layers))
    names = ("layers", "expert", "mlp", "embed")
    return {"wg": _boxed(wg, names), "wu": _boxed(wu, names),
            "wd": _boxed(wd, names)}


def init_smallthinker_params(cfg: TransformerConfig, rng) -> Dict[str, Any]:
    """Seeded weights, drawn directly in ``cfg.dtype``."""
    dtype = cfg.dtype
    e, v = cfg.hidden_size, cfg.vocab_size
    keys = jax.random.split(rng, 4)
    _, runs, periods, _ = layer_runs(cfg)
    period = sum(n for _, n in runs)
    params: Dict[str, Any] = {
        "embed": {"tokens": _boxed(
            jax.random.normal(keys[0], (v, e), dtype)
            * jnp.asarray(0.02, dtype), ("vocab", "embed"))},
        "final_norm": _gain(e, dtype),
        "lm_head": _boxed(_normal(keys[1], (e, v), e, dtype),
                          ("embed", "vocab")),
        "experts": _experts_init(cfg, keys[3], dtype),
    }

    def one(i):
        return _layer_init(cfg, i, keys[2], dtype)

    if periods:
        params["periods"] = {
            f"l{j}": _stack([one(p * period + j) for p in range(periods)])
            for j in range(period)}
    tail = range(periods * period, cfg.num_layers)
    if tail:
        params["tail"] = {f"l{n}": one(i) for n, i in enumerate(tail)}
    return params


class SmallThinkerForCausalLM(CausalLM):
    """Seeded weights from the source's keys; served through
    ``inference/v2`` (no training loss: the training forward pass routes
    no held experts and has one attention kind)."""

    def __init__(self, source: Dict[str, Any], **overrides):
        super().__init__(smallthinker_config(source, **overrides))

    def init_params(self, rng):
        return init_smallthinker_params(self.cfg, rng)

    def logits(self, params, batch, rng=None):
        raise NotImplementedError(
            "smallthinker is a served family: use inference/v2, or "
            "models/smallthinker_reference.py for a plain forward pass")
