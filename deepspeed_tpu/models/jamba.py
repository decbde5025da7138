"""Jamba (``model_type: jamba``, AI21) — a served family.

Mamba-1 layers and attention layers in one model: layer ``i`` is an
attention layer where ``i % attn_layer_period == attn_layer_offset`` and
a Mamba layer otherwise (7 and 21 of 28 at the published period of 14).
Both kinds are pre-norm, ``h += mixer(norm_in(h))``, ``h += mlp(norm_ff
(h))``, and with ``num_experts`` 1 every layer's feed-forward is the
llama block's dense SwiGLU.  No positional encoding of any kind (the
recurrence orders the tokens).  Source:
``huggingface.co/ai21labs/AI21-Jamba2-3B``, HF ``modeling_jamba.py``
semantics.

* Attention layer: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` K/V heads (20 over 1), no bias, no rope, causal
  softmax at ``1/sqrt(head_dim)``.
* Mamba layer (``d = mamba_expand * hidden``, ``N = mamba_d_state``,
  ``R = mamba_dt_rank``): ``[x ; z] = W_in u``; ``x = silu(conv(x) +
  b_conv)`` over the last ``mamba_d_conv`` positions; ``[dt ; B ; C] =
  W_x x``; Jamba's own norms ``dt = rms(dt)``, ``B = rms(B)``, ``C =
  rms(C)`` (learned gains); ``dt = softplus(W_dt dt + b_dt)``; ``A =
  -exp(A_log)``; the recurrence of ``ops/ssm.py``; ``out = W_out (y *
  silu(z))``.

The family is SERVED (``inference/v2``,
:class:`~deepspeed_tpu.inference.v2.model_implementations.
JambaInferenceModel`): an attention layer keeps its K/V in pages, a Mamba
layer its recurrent state and convolution tail in one slot of the state
pool.  Its plain reference is ``models/jamba_reference.py``.  The routed
form of the family (``num_experts > 1``) is not built and raises.

Parameter tree (``cfg.layer_kinds`` is the layers in order, "ssm" or
"full")::

    embed.tokens [V, e]   final_norm   (lm_head [e, V] unless tied)
    layers {ssm, full}     the layers of each kind in order, stacked
                           [layers of the kind, ...]: layer i of the model
                           is entry (layers of its kind before i) of its
                           kind's stack.  One flat stack a kind and no
                           stack of periods: a scan over periods whose
                           operand is a period's run of layers slices the
                           run out (1.5 GB copied a period at the
                           published widths; tests/test_chip_compile.py)
    a Mamba layer: norm1, norm2, mlp {wi, wg, wo}, mixer {w_in [e, 2d],
        conv_w [d_conv, d], conv_b [d], w_x [R + 2N, d], dt_norm, b_norm,
        c_norm, w_dt [R, d], b_dt [d], A_log_t [N, d] f32, D [d] f32,
        w_out [d, e]}
    an attention layer: norm1, norm2, mlp, attn {wq [e, H * dh],
        wk, wv [e, K * dh], wo [H * dh, e]}

``conv_w[k]`` weighs the input ``d_conv - 1 - k`` positions back (HF's
``conv1d.weight[:, 0, k]``); ``A_log_t`` is HF's ``A_log`` transposed,
``d`` minor as the state pool is; ``w_x`` is HF's ``x_proj.weight`` as
stored, ``[R + 2N, d]`` (192 columns are no whole lane tiles: stored ``[d,
192]``, every step program re-laid the stack out before its loop).

Seeded weights (the benchmark's departure from published ones): the
projections as the llama block's (normal over fan-in); ``A_log =
log(1..N)`` in every channel and ``D = 1`` (the family's initialisation);
``b_dt`` the inverse softplus of a time step drawn log-uniform in [0.001,
0.1] (the Mamba initialisation), so that a state neither dies nor blows
up over thousands of steps.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .pangu_moe import _gain, _mlp_init, _normal, _stack
from .transformer import CausalLM, TransformerConfig, _boxed

#: the log-uniform range of a seeded time step (Mamba's dt_min, dt_max)
DT_RANGE = (0.001, 0.1)


def jamba_config(source: Dict[str, Any], *, max_seq_len: int = 4096,
                 dtype=jnp.bfloat16,
                 state_dtype=jnp.float32) -> TransformerConfig:
    """The repo's configuration from the source's own ``config.json``
    keys."""
    if source.get("num_experts", 1) > 1:
        raise ValueError(
            "models/jamba.py: num_experts > 1 (the family's routed "
            "feed-forward) is not built; only the dense form is served")
    assert source.get("hidden_act", "silu") == "silu"
    assert not source.get("mamba_proj_bias", False)
    assert source.get("mamba_conv_bias", True)
    assert not source.get("sliding_window")
    L = source["num_hidden_layers"]
    period, offset = source["attn_layer_period"], source["attn_layer_offset"]
    kinds = tuple("full" if i % period == offset else "ssm"
                  for i in range(L))
    heads = source["num_attention_heads"]
    return TransformerConfig(
        vocab_size=source["vocab_size"], hidden_size=source["hidden_size"],
        intermediate_size=source["intermediate_size"], num_layers=L,
        num_heads=heads, num_kv_heads=source["num_key_value_heads"],
        head_dim=source.get("head_dim")
        or source["hidden_size"] // heads,
        max_seq_len=max_seq_len, norm="rmsnorm",
        norm_eps=source["rms_norm_eps"], activation="silu_gated",
        pos_emb="none", layer_kinds=kinds,
        heads_by_kind=(("full", heads),),
        ssm_state_dim=source["mamba_d_state"],
        ssm_conv=source["mamba_d_conv"],
        ssm_dt_rank=source["mamba_dt_rank"],
        ssm_expand=source["mamba_expand"], ssm_state_dtype=state_dtype,
        tie_embeddings=bool(source.get("tie_word_embeddings", True)),
        dtype=dtype)


def _mixer_init(cfg: TransformerConfig, key, dtype):
    e, d = cfg.hidden_size, cfg.ssm_inner
    n, r, k = cfg.ssm_state_dim, cfg.ssm_dt_rank, cfg.ssm_conv
    ks = jax.random.split(key, 7)
    lo, hi = (math.log(v) for v in DT_RANGE)
    dt = jnp.exp(jax.random.uniform(ks[5], (d,), jnp.float32, lo, hi))
    f32 = jnp.float32
    return {
        "w_in": _boxed(_normal(ks[0], (e, 2 * d), e, dtype),
                       ("embed", "mlp")),
        "conv_w": _boxed(_normal(ks[1], (k, d), k, dtype), (None, "mlp")),
        "conv_b": _boxed(_normal(ks[6], (d,), 100, dtype), ("mlp",)),
        "w_x": _boxed(_normal(ks[2], (r + 2 * n, d), d, dtype),
                      (None, "mlp")),
        "dt_norm": _gain(r, dtype), "b_norm": _gain(n, dtype),
        "c_norm": _gain(n, dtype),
        "w_dt": _boxed(_normal(ks[3], (r, d), r, dtype), (None, "mlp")),
        # softplus(b_dt) = dt
        "b_dt": _boxed(dt + jnp.log(-jnp.expm1(-dt)), ("mlp",)),
        "A_log_t": _boxed(jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=f32))[:, None], (n, d)),
            (None, "mlp")),
        "D": _boxed(jnp.ones((d,), f32), ("mlp",)),
        "w_out": _boxed(_normal(ks[4], (d, e), d, dtype),
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
        "wo": _boxed(_normal(ks[3], (h * d, e), h * d, dtype),
                     ("heads", "embed")),
    }


def _layer_init(cfg: TransformerConfig, i: int, key, dtype):
    """Layer ``i``'s weights, from ``i`` and the seed alone."""
    e = cfg.hidden_size
    ks = jax.random.split(jax.random.fold_in(key, i), 2)
    p = {"norm1": _gain(e, dtype), "norm2": _gain(e, dtype),
         "mlp": _mlp_init(e, cfg.intermediate_size, ks[1], dtype)}
    if cfg.layer_kinds[i] == "ssm":
        p["mixer"] = _mixer_init(cfg, ks[0], dtype)
    else:
        p["attn"] = _attn_init(cfg, ks[0], dtype)
    return p


def init_jamba_params(cfg: TransformerConfig, rng) -> Dict[str, Any]:
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

    def one(i):
        return _layer_init(cfg, i, keys[2], dtype)

    params["layers"] = {
        kind: _stack([one(i) for i, k in enumerate(cfg.layer_kinds)
                      if k == kind])
        for kind in dict.fromkeys(cfg.layer_kinds)}
    return params


class JambaForCausalLM(CausalLM):
    """Seeded weights from the source's keys; served through
    ``inference/v2`` (no training loss: the scan has no backward here)."""

    def __init__(self, source: Dict[str, Any], **overrides):
        super().__init__(jamba_config(source, **overrides))

    def init_params(self, rng):
        return init_jamba_params(self.cfg, rng)

    def logits(self, params, batch, rng=None):
        raise NotImplementedError(
            "jamba is a served family: use inference/v2, or "
            "models/jamba_reference.py for a plain forward pass")
