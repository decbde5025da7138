"""Nemotron-H / Nemotron 3 Nano (``model_type: nemotron_h``, NVIDIA) — a
served family.

Every layer is ONE sub-layer behind ONE norm and ONE residual, ``x <- x +
sub_i(rmsnorm(x))``, ``sub_i`` by the letter of ``hybrid_override_pattern``:
``M`` a Mamba-2 mixer, ``E`` a routed feed-forward, ``*`` attention.  RMSNorm,
no bias but the convolution's, no positional encoding, untied head.  Source:
``huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``.

* ``M`` (Mamba-2 / SSD; ``H = mamba_num_heads`` heads of ``P =
  mamba_head_dim`` channels, ``d = H P`` whatever ``expand`` says, state ``N
  = ssm_state_size``, ``G = n_groups``): ``[z | xBC | dt] = W_in u`` (widths
  ``d | d + 2 G N | H``); ``xBC = silu(conv(xBC) + b)``, depthwise and causal
  over the last ``conv_kernel`` positions; ``x`` ``[H, P]``, ``B``, ``C`` ``[G,
  N]``, head ``h`` reads group ``h // (H / G)``; ``dt = softplus(dt +
  dt_bias)``, ``a = exp(dt A)``, ``A = -exp(A_log)``, one a head; ``S_t = a_t
  S_{t-1} + (dt_t x_t) (x) B_t``, ``y_t = S_t C_t + D x_t``
  (``ops/ssm.py::ssd_scan``); then the gated GROUP norm: ``y silu(z)``,
  RMSNorm over each group of ``d / G`` channels, a gain over all ``d``;
  ``W_out y``.
* ``E``: ``s = sigmoid(W_r x)`` in float32 over all ``n_routed_experts``; the
  ``num_experts_per_tok`` largest of ``s + bias`` are chosen (``n_group`` 1:
  ``moe/held.py::route_sigmoid_grouped`` with one group); their weights are
  ``routed_scaling_factor s_i / sum s_j``; an expert is ``W_down relu(W_up
  x)^2``, TWO matrices and no gate (``mlp_hidden_act`` ``relu2``); the shared
  expert the same form at ``moe_shared_expert_intermediate_size``.
* ``*``: grouped-query attention without bias and WITHOUT a positional
  encoding (the family's report; ``rope_theta`` and ``partial_rotary_factor``
  are not read by its attention), causal, scale ``head_dim ** -0.5``.

ASSUMED (the published keys do not settle them; the benchmark's
``published/nvidia-nemotron-3-nano-30b-a3b.json`` carries each with its
why): no rope; the gated norm's group size ``d / n_groups`` and the gate
before the norm; no clamp on ``dt``; the selection bias exists and is
seeded at :data:`BIAS_SCALE`.  NOT BUILT: a ``-`` (dense feed-forward)
letter of the pattern raises.

The family is SERVED (``inference/v2``, :class:`~deepspeed_tpu.inference.
v2.model_implementations.NemotronHInferenceModel`) as one chip of an
expert-parallel group: the attention layers' K/V in pages, a Mamba-2
layer's state and convolution tail in one slot of the state pool, an ``E``
layer caches nothing (``layer_kinds``: "ssd" / "ffn" / "full",
``half_blocks``), ``experts_held`` of a layer's experts here.  Its plain
reference is ``models/nemotron_h_reference.py``.

Parameter tree::

    embed.tokens [V, e]   final_norm   lm_head [e, V]
    periods {l<j>}   layer j of every whole period of the layer pattern,
                     stacked over the periods (``model.py::_layer_loop``
                     (a): the scan's operand; a period's layers differ in
                     their trees, so each has its own body in the program)
    tail {l<n>}      the layers after the last whole period
    experts {wu, wd} [routed layers, held, F, e]   (no ``wg``)
    an M layer: norm1, mixer {w_in [e, 2 d + 2 G N + H], conv_w [K, d + 2
        G N], conv_b [d + 2 G N], A_log, D, dt_bias [H] f32, norm {scale
        [d]}, w_out [d, e]}
    a * layer: norm1, attn {wq [e, heads * dh], wk, wv [e, kv * dh], wo}
    an E layer: norm1, moe {router [e, E] f32, router_bias [E] f32,
        shared {wi [e, Fs], wo [Fs, e]}}

Seeded weights (the benchmark's departure from published ones), the
family's own initialisation: projections normal over fan-in; ``A_log =
log(U(1, 16))``; ``D = 1``; ``dt_bias`` the inverse softplus of a step
log-uniform in [``time_step_min``, ``time_step_max``] floored at
``time_step_floor``; ``router_bias`` normal at :data:`BIAS_SCALE`.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .jamba import _attn_init
from .pangu_moe import _gain, _normal, _stack
from .transformer import (CausalLM, TransformerConfig, _boxed, layer_runs)

#: the pattern's letters as layer kinds (``ragged/cache_kinds.py``)
KINDS = {"M": "ssd", "E": "ffn", "*": "full"}
#: the uniform range ``exp(A_log)`` is seeded from (the family's own)
A_RANGE = (1.0, 16.0)
#: (``time_step_min``, ``time_step_max``, ``time_step_floor``) where the
#: source gives none
DT_INIT = (0.001, 0.1, 1e-4)
#: the standard deviation the selection bias is seeded with: PERF.md
#: section 6, PR 50 (at 0.1 the bias IS the choice of a seeded routing)
BIAS_SCALE = 0.02
#: rows of a held-expert tile, at a decode step too (``moe/held.py::
#: row_tile`` gives 32 up to 256 tokens): at the published widths an expert
#: is 20 MB that every further tile of its rows streams again, and rows that
#: route alike put all 256 rows of a decode step on a layer's six experts.
#: On the chip an expert with all 256 rows cost the layer's call 0.212 ms at
#: 32 rows a tile and 0.106 at 64, for 0.528 and 0.535 a call under an even
#: routing (PERF.md section 6, PR 54: the host out of the way)
ROW_TILE = 64


def nemotron_h_config(source: Dict[str, Any], *, experts_first: int = 0,
                      first_layer: int = 0, max_seq_len: int = 4096,
                      dtype=jnp.bfloat16,
                      state_dtype=jnp.float32) -> TransformerConfig:
    """The repo's configuration from the source's own ``config.json``
    keys.  ``first_layer``: the published index of the first layer held (a
    stage of the depth: ``hybrid_override_pattern`` is read from there,
    ``num_hidden_layers`` entries of it).  ``n_routed_experts`` is the
    experts HELD by this process when the dict also gives
    ``n_routed_experts_scored`` (a chip's share: the router keeps that many
    outputs); otherwise all are held."""
    for key, want in (("mlp_hidden_act", "relu2"),
                      ("mamba_hidden_act", "silu"), ("use_bias", False),
                      ("mlp_bias", False), ("attention_bias", False),
                      ("mamba_proj_bias", False), ("use_conv_bias", True),
                      ("tie_word_embeddings", False), ("n_group", 1),
                      ("topk_group", 1), ("sliding_window", None)):
        if source.get(key, want) != want:
            raise ValueError(f"models/nemotron_h.py: {key}="
                             f"{source[key]!r} is not built (only {want!r})")
    L = source["num_hidden_layers"]
    letters = source["hybrid_override_pattern"][first_layer:first_layer + L]
    if len(letters) != L or set(letters) - set(KINDS):
        raise ValueError(
            f"models/nemotron_h.py: layers {first_layer}.."
            f"{first_layer + L - 1} of hybrid_override_pattern read "
            f"{letters!r}: {L} letters of {sorted(KINDS)} are built (a "
            "dense feed-forward layer, '-', is not)")
    width = source["moe_intermediate_size"]
    shared = source.get("n_shared_experts", 0) \
        * source.get("moe_shared_expert_intermediate_size", 0)
    assert shared % width == 0, "the shared expert is whole expert widths"
    heads = source["num_attention_heads"]
    return TransformerConfig(
        vocab_size=source["vocab_size"], hidden_size=source["hidden_size"],
        intermediate_size=source["intermediate_size"], num_layers=L,
        num_heads=heads, num_kv_heads=source["num_key_value_heads"],
        head_dim=source.get("head_dim") or source["hidden_size"] // heads,
        max_seq_len=max_seq_len, norm="rmsnorm",
        norm_eps=source["norm_eps"], activation="relu2", pos_emb="none",
        layer_kinds=tuple(KINDS[c] for c in letters),
        heads_by_kind=(("full", heads),), half_blocks=True,
        ssm_state_dim=source["ssm_state_size"],
        ssm_conv=source["conv_kernel"],
        ssm_heads=source["mamba_num_heads"],
        ssm_head_dim=source["mamba_head_dim"],
        ssm_groups=source["n_groups"], ssm_state_dtype=state_dtype,
        n_routed_experts=source.get("n_routed_experts_scored",
                                    source["n_routed_experts"]),
        experts_held=source["n_routed_experts"],
        experts_first=experts_first, n_shared_experts=shared // width,
        moe_top_k=source["num_experts_per_tok"],
        moe_intermediate_size=width,
        routed_scaling_factor=float(source.get("routed_scaling_factor",
                                               1.0)),
        norm_topk_prob=bool(source.get("norm_topk_prob", True)),
        router_scoring="sigmoid_grouped", router_groups=1,
        router_topk_groups=1, expert_act="relu2", moe_row_tile=ROW_TILE,
        dtype=dtype)


def _mixer_init(cfg: TransformerConfig, key, dtype, dt_init):
    e, d, H = cfg.hidden_size, cfg.ssm_inner, cfg.ssm_heads
    ch = d + 2 * cfg.ssm_groups * cfg.ssm_state_dim
    K, f32 = cfg.ssm_conv, jnp.float32
    ks = jax.random.split(key, 6)
    lo, hi, floor = dt_init
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        ks[4], (H,), f32, math.log(lo), math.log(hi))), floor)
    return {
        "w_in": _boxed(_normal(ks[0], (e, d + ch + H), e, dtype),
                       ("embed", "mlp")),
        "conv_w": _boxed(_normal(ks[1], (K, ch), K, dtype), (None, "mlp")),
        "conv_b": _boxed(_normal(ks[2], (ch,), 100, dtype), ("mlp",)),
        "A_log": _boxed(jnp.log(jax.random.uniform(
            ks[5], (H,), f32, *A_RANGE)), (None,)),
        "D": _boxed(jnp.ones((H,), f32), (None,)),
        # softplus(dt_bias) = dt
        "dt_bias": _boxed(dt + jnp.log(-jnp.expm1(-dt)), (None,)),
        "norm": _gain(d, dtype),
        "w_out": _boxed(_normal(ks[3], (d, e), d, dtype), ("mlp", "embed")),
    }


def _layer_init(cfg: TransformerConfig, i: int, key, dtype, dt_init):
    """Layer ``i``'s weights but its routed experts, from ``i`` and the
    seed alone."""
    e = cfg.hidden_size
    ks = jax.random.split(jax.random.fold_in(key, i), 4)
    p = {"norm1": _gain(e, dtype)}
    kind = cfg.layer_kinds[i]
    if kind == "ssd":
        p["mixer"] = _mixer_init(cfg, ks[0], dtype, dt_init)
    elif kind == "full":
        p["attn"] = _attn_init(cfg, ks[0], dtype)
    else:
        p["moe"] = {
            "router": _boxed(_normal(ks[1], (e, cfg.n_routed_experts), e,
                                     jnp.float32), ("embed", None)),
            "router_bias": _boxed(BIAS_SCALE * jax.random.normal(
                ks[2], (cfg.n_routed_experts,), jnp.float32), (None,))}
        if cfg.n_shared_experts:
            fs = cfg.moe_intermediate_size * cfg.n_shared_experts
            k1, k2 = jax.random.split(ks[3])
            p["moe"]["shared"] = {
                "wi": _boxed(_normal(k1, (e, fs), e, dtype),
                             ("embed", "mlp")),
                "wo": _boxed(_normal(k2, (fs, e), fs, dtype),
                             ("mlp", "embed"))}
    return p


def _experts_init(cfg: TransformerConfig, key, dtype):
    """``[routed layers, held, F, e]`` each of TWO: an expert's weights
    from its layer's place among the routed ones and its GLOBAL index, so
    the share that holds experts 32..47 holds the uncut model's."""
    e, f = cfg.hidden_size, cfg.moe_intermediate_size
    ids = cfg.experts_first + jnp.arange(cfg.held_experts)

    def one(layer, i):
        ks = jax.random.split(
            jax.random.fold_in(jax.random.fold_in(key, layer), i), 2)
        return (_normal(ks[0], (f, e), e, dtype),
                _normal(ks[1], (f, e), f, dtype))

    layers = jnp.arange(cfg.layer_kinds.count("ffn"))
    wu, wd = jax.vmap(lambda l: jax.vmap(lambda i: one(l, i))(ids))(layers)
    names = ("layers", "expert", "mlp", "embed")
    return {"wu": _boxed(wu, names), "wd": _boxed(wd, names)}


def init_nemotron_h_params(cfg: TransformerConfig, rng,
                           dt_init=DT_INIT) -> Dict[str, Any]:
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
    }

    def one(i):
        return _layer_init(cfg, i, keys[2], dtype, dt_init)

    params["periods"] = {
        f"l{j}": _stack([one(p * period + j) for p in range(periods)])
        for j in range(period)}
    tail = range(periods * period, cfg.num_layers)
    if tail:
        params["tail"] = {f"l{n}": one(i) for n, i in enumerate(tail)}
    if "ffn" in cfg.layer_kinds:
        params["experts"] = _experts_init(cfg, keys[3], dtype)
    return params


class NemotronHForCausalLM(CausalLM):
    """Seeded weights from the source's keys; served through
    ``inference/v2`` (no training loss: the chunk form has no backward
    here, and the training path has no held-experts layer)."""

    def __init__(self, source: Dict[str, Any], **overrides):
        super().__init__(nemotron_h_config(source, **overrides))
        self.dt_init = tuple(source.get(k, d) for k, d in zip(
            ("time_step_min", "time_step_max", "time_step_floor"), DT_INIT))

    def init_params(self, rng):
        return init_nemotron_h_params(self.cfg, rng, self.dt_init)

    def logits(self, params, batch, rng=None):
        raise NotImplementedError(
            "nemotron_h is a served family: use inference/v2, or "
            "models/nemotron_h_reference.py for a plain forward pass")
