"""Per-architecture inference-v2 model implementations.

Reference: ``inference/v2/model_implementations/`` — one directory per
arch (llama_v2, mistral, mixtral, falcon, opt, phi, qwen, qwen_v2; here
also bloom, gpt_neox, gpt2, gptj, pangu_ultra_moe, laguna, jamba,
olmo_hybrid and smallthinker), each
a ``DSTransformerModelBase`` subclass hard-coding that family's
invariants (llama_v2/model.py:22, mistral/model.py, ...), chosen by
``engine_factory`` from the checkpoint's ``model_type``.

TPU-native shape: all families share ONE compiled core
(:class:`~deepspeed_tpu.inference.v2.model.RaggedInferenceModel` over the
functional transformer), so an "implementation" here is a thin subclass
that (a) asserts the family's architectural invariants at construction —
catching a mis-mapped checkpoint at build time the way the reference's
per-arch containers would fail to bind weights — and (b) applies
family-specific serving defaults.  ``implementation_for`` is the
``model_type`` -> class chooser (reference engine_factory.py dispatch +
modules/heuristics.py:36 ``instantiate_*``).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple, Type

import jax

from .model import RaggedInferenceModel


class LlamaV2InferenceModel(RaggedInferenceModel):
    """reference model_implementations/llama_v2/model.py:22."""
    MODEL_TYPES: Tuple[str, ...] = ("llama",)

    def __init__(self, cfg, params, **kw):
        assert cfg.norm == "rmsnorm" and cfg.pos_emb == "rope", \
            f"llama family expects rmsnorm+rope, got {cfg.norm}/{cfg.pos_emb}"
        assert "gated" in cfg.activation, "llama family is gated-MLP"
        super().__init__(cfg, params, **kw)


class MistralInferenceModel(LlamaV2InferenceModel):
    """reference model_implementations/mistral: llama shape + sliding
    window.  HF mistral checkpoints ship sliding_window=4096 (or None on
    later revisions — both are valid; when set, the paged decode kernel
    skips out-of-window pages)."""
    MODEL_TYPES = ("mistral",)


class MixtralInferenceModel(RaggedInferenceModel):
    """reference model_implementations/mixtral: mistral attention +
    block-sparse MoE (the routed mlp self-wires from cfg.moe_num_experts;
    serving uses dropless dispatch)."""
    MODEL_TYPES = ("mixtral",)

    def __init__(self, cfg, params, **kw):
        assert cfg.moe_num_experts > 1, \
            "mixtral checkpoint mapped without experts — wrong policy?"
        super().__init__(cfg, params, **kw)


class FalconInferenceModel(RaggedInferenceModel):
    """reference model_implementations/falcon: parallel attention+MLP
    residual for the new-decoder-architecture; the loader also supports
    sequential-residual falcon variants (checkpoint/hf.py load_falcon),
    so no residual-layout invariant is asserted here."""
    MODEL_TYPES = ("falcon",)


class OPTInferenceModel(RaggedInferenceModel):
    """reference model_implementations/opt: learned positions (+2 HF
    offset folded into the table at load), pre-LN, relu."""
    MODEL_TYPES = ("opt",)

    def __init__(self, cfg, params, **kw):
        assert cfg.pos_emb == "learned", "OPT expects learned positions"
        super().__init__(cfg, params, **kw)


class PhiInferenceModel(RaggedInferenceModel):
    """reference model_implementations/phi: partial rotary + parallel
    residual (phi-2) / phi-3 llama-like."""
    MODEL_TYPES = ("phi", "phi3")


class Qwen2InferenceModel(RaggedInferenceModel):
    """reference model_implementations/qwen_v2: llama geometry +
    attention-only qkv biases (+ gated sliding window)."""
    MODEL_TYPES = ("qwen2",)

    def __init__(self, cfg, params, **kw):
        assert cfg.qkv_bias, "qwen2 expects attention qkv biases"
        super().__init__(cfg, params, **kw)


class BloomInferenceModel(RaggedInferenceModel):
    """bloom: ALiBi + embedding layernorm (beyond the reference's v2 set;
    v1 kernel-injection covered it there)."""
    MODEL_TYPES = ("bloom",)

    def __init__(self, cfg, params, **kw):
        assert cfg.pos_emb == "alibi", "bloom expects ALiBi"
        super().__init__(cfg, params, **kw)


class PanguUltraMoEInferenceModel(RaggedInferenceModel):
    """openPangu-Ultra-MoE (``models/pangu_moe.py``; no counterpart in the
    reference): latent (MLA) attention over a latent page pool, sandwich
    norms, leading dense layers, then routed layers of which this
    process holds ``experts_held`` experts (one chip of an
    expert-parallel group) beside the shared expert."""
    MODEL_TYPES = ("pangu_ultra_moe",)

    def __init__(self, cfg, params, **kw):
        assert cfg.kv_lora_rank > 0 and cfg.qk_rope_head_dim > 0, \
            "pangu_ultra_moe is a latent-attention family: kv_lora_rank"
        assert cfg.norm == "rmsnorm" and cfg.pos_emb == "rope"
        assert cfg.n_routed_experts >= cfg.moe_top_k >= 1
        held = cfg.held_experts
        assert 0 <= cfg.experts_first \
            and cfg.experts_first + held <= cfg.n_routed_experts, \
            "the experts held here lie outside the router's outputs"
        assert 0 <= cfg.first_k_dense <= cfg.num_layers
        super().__init__(cfg, params, **kw)
        routed = self.params.get("layers")
        assert routed is None or routed["moe"]["experts"]["wg"].shape[1] \
            == held, "expert weights do not match experts_held"


class LagunaInferenceModel(RaggedInferenceModel):
    """Laguna (``models/laguna.py``; no counterpart in the reference):
    full and window attention layers in one model over two page groups,
    a head count a kind, a per-head output gate, leading dense layers,
    then routed layers of which this process holds ``experts_held``
    experts beside the shared expert."""
    MODEL_TYPES = ("laguna",)

    def __init__(self, cfg, params, **kw):
        assert set(cfg.layer_kinds) == {"full", "window"} \
            and len(cfg.layer_kinds) == cfg.num_layers, \
            "laguna names a kind for every layer, and has both"
        assert cfg.sliding_window and cfg.head_gate
        assert cfg.norm == "rmsnorm" and cfg.pos_emb == "rope"
        heads = dict(cfg.heads_by_kind)
        assert all(h % cfg.kv_heads == 0 for h in heads.values())
        assert cfg.n_routed_experts >= cfg.moe_top_k >= 1
        held = cfg.held_experts
        assert 0 <= cfg.experts_first \
            and cfg.experts_first + held <= cfg.n_routed_experts, \
            "the experts held here lie outside the router's outputs"
        assert 0 <= cfg.first_k_dense <= cfg.num_layers
        super().__init__(cfg, params, **kw)
        experts = self.params.get("experts")
        assert experts is None or experts["wg"].shape[:2] \
            == (cfg.num_layers - cfg.first_k_dense, held), \
            "expert weights do not match the routed layers or experts_held"

    def rope_table(self, cfg, kind, positions):
        """YaRN over part of a full head's dims, a plain rope of its own
        base over all of a window head's."""
        from ...models.laguna import rope_table
        return rope_table(cfg, kind, positions)


class JambaInferenceModel(RaggedInferenceModel):
    """Jamba (``models/jamba.py``; no counterpart in the reference):
    Mamba-1 layers and attention layers in one model, the attention
    layers' K/V in pages and the Mamba layers' recurrent state and
    convolution tail in one slot of the state pool a sequence, no
    positional encoding, the llama block's SwiGLU in every layer."""
    MODEL_TYPES = ("jamba",)

    def __init__(self, cfg, params, **kw):
        assert set(cfg.layer_kinds) == {"full", "ssm"} \
            and len(cfg.layer_kinds) == cfg.num_layers, \
            "jamba names a kind for every layer, and has both"
        assert cfg.ssm_state_dim > 0 and cfg.ssm_dt_rank > 0 \
            and cfg.ssm_conv > 1
        assert cfg.norm == "rmsnorm" and cfg.pos_emb == "none"
        assert cfg.num_heads % cfg.kv_heads == 0
        assert not cfg.n_routed_experts and not cfg.moe_num_experts, \
            "the routed form of the family is not built"
        super().__init__(cfg, params, **kw)


class OlmoHybridInferenceModel(RaggedInferenceModel):
    """Olmo-Hybrid (``models/olmo_hybrid.py``; no counterpart in the
    reference): gated delta-rule linear-attention layers and full
    attention layers in one model, the full layers' K/V in pages and the
    linear layers' matrix state and convolution tail in one slot of the
    state pool a sequence, the norm on each sub-layer's output, a Q/K
    norm over the whole width, no positional encoding, the llama block's
    SwiGLU in every layer."""
    MODEL_TYPES = ("olmo_hybrid",)

    def __init__(self, cfg, params, **kw):
        assert set(cfg.layer_kinds) == {"full", "delta"} \
            and len(cfg.layer_kinds) == cfg.num_layers, \
            "olmo_hybrid names a kind for every layer, and has both"
        assert cfg.delta_heads > 0 and cfg.delta_key_dim > 0 \
            and cfg.delta_value_dim > 0 and cfg.delta_conv > 1
        assert cfg.norm == "rmsnorm" and cfg.pos_emb == "none"
        assert cfg.post_norm and cfg.qk_norm
        assert cfg.num_heads % cfg.kv_heads == 0
        super().__init__(cfg, params, **kw)


class GPTNeoXInferenceModel(RaggedInferenceModel):
    MODEL_TYPES = ("gpt_neox",)


class GPT2InferenceModel(RaggedInferenceModel):
    MODEL_TYPES = ("gpt2",)


class GPTJInferenceModel(RaggedInferenceModel):
    MODEL_TYPES = ("gptj",)


class SmallThinkerInferenceModel(RaggedInferenceModel):
    """SmallThinker (``models/smallthinker.py``; no counterpart in the
    reference): global layers without a positional encoding and window
    layers under rope in one model over two page groups at ONE head
    count, every layer routed from its ATTENTION block's input
    (``cfg.router_reads``) over ReLU-gated experts (``cfg.expert_act``),
    of which this process holds ``experts_held`` (all of them in the
    family's serving cut), no shared expert, no dense layer."""
    MODEL_TYPES = ("smallthinker",)

    def __init__(self, cfg, params, **kw):
        assert set(cfg.layer_kinds) == {"full", "window"} \
            and len(cfg.layer_kinds) == cfg.num_layers, \
            "smallthinker names a kind for every layer, and has both"
        assert cfg.sliding_window and cfg.nope_kinds == ("full",)
        assert cfg.norm == "rmsnorm" and cfg.pos_emb == "rope"
        assert len({h for _, h in cfg.heads_by_kind}) == 1 \
            and cfg.num_heads % cfg.kv_heads == 0 and not cfg.head_gate
        assert cfg.router_reads == "mixer" and cfg.expert_act == "relu"
        assert cfg.n_routed_experts >= cfg.moe_top_k >= 1
        assert not cfg.first_k_dense and not cfg.n_shared_experts
        held = cfg.held_experts
        assert 0 <= cfg.experts_first \
            and cfg.experts_first + held <= cfg.n_routed_experts, \
            "the experts held here lie outside the router's outputs"
        super().__init__(cfg, params, **kw)
        experts = self.params.get("experts")
        assert experts is None or experts["wg"].shape[:2] \
            == (cfg.num_layers, held), \
            "expert weights do not match the layers or experts_held"

    def rope_table(self, cfg, kind, positions):
        """A plain rope over all of a window head's dims; none for a
        kind of ``cfg.nope_kinds`` (``_kv_mixer`` leaves q and k as
        projected where a kind's entry is None)."""
        if kind in cfg.nope_kinds:
            return None
        return RaggedInferenceModel.rope_table(self, cfg, kind, positions)


class _RoutingSink:
    """A served routed family whose comparison must follow the routing that
    was served (a near-tie of hundreds of scores falls either way under
    bfloat16): mixed in before ``RaggedInferenceModel``."""
    #: a callable ``(experts [T, k])`` that every routed layer of a program
    #: TRACED while it is set calls, in layer order, with what its router
    #: chose (a host callback: such a program is never cached).  The
    #: benchmark's probe hands the record to its reference; None, as it is
    #: served: no trace of it in a program.
    routing_sink = None

    def _route(self, lp, h, ctx, layout: bool = False):
        out = super()._route(lp, h, ctx, layout)
        if self.routing_sink is not None:
            jax.debug.callback(self.routing_sink, out[0], ordered=True)
        return out


class BailingHybridInferenceModel(_RoutingSink, RaggedInferenceModel):
    """Ling-3.0 (``models/bailing_hybrid.py``; no counterpart in the
    reference): Kimi-delta (KDA) linear-attention layers and latent
    attention layers in one model, the latent layers' planes in pages and
    the KDA layers' matrix state and convolution tail in one slot of the
    state pool a sequence, leading dense layers, then routed layers behind
    a grouped, biased router of which this process holds ``experts_held``
    experts beside the shared expert."""
    MODEL_TYPES = ("bailing_hybrid",)

    def __init__(self, cfg, params, **kw):
        assert set(cfg.layer_kinds) <= {"kda", "latent"} \
            and "latent" in cfg.layer_kinds \
            and len(cfg.layer_kinds) == cfg.num_layers, \
            "bailing_hybrid names a kind for every layer, a latent one too"
        assert cfg.kv_lora_rank > 0 and cfg.qk_rope_head_dim > 0 \
            and not cfg.q_lora_rank
        assert cfg.delta_heads > 0 and cfg.delta_key_dim > 0 \
            and cfg.delta_value_dim > 0 and cfg.delta_conv > 1
        assert cfg.kda_lower_bound < 0
        assert cfg.norm == "rmsnorm" and cfg.pos_emb == "rope"
        assert cfg.router_scoring == "sigmoid_grouped" \
            and cfg.n_routed_experts % cfg.router_groups == 0 \
            and 1 <= cfg.router_topk_groups <= cfg.router_groups
        assert cfg.n_routed_experts >= cfg.moe_top_k >= 1
        held = cfg.held_experts
        assert 0 <= cfg.experts_first \
            and cfg.experts_first + held <= cfg.n_routed_experts, \
            "the experts held here lie outside the router's outputs"
        assert 0 <= cfg.first_k_dense <= cfg.num_layers
        super().__init__(cfg, params, **kw)
        experts = self.params.get("experts")
        assert experts is None or experts["wg"].shape[:2] \
            == (cfg.num_layers - cfg.first_k_dense, held), \
            "expert weights do not match the routed layers or experts_held"


class NemotronHInferenceModel(_RoutingSink, RaggedInferenceModel):
    """Nemotron-H (``models/nemotron_h.py``; no counterpart in the
    reference): every layer ONE sub-layer behind one norm and one residual
    (``cfg.half_blocks``): Mamba-2 mixers (a state and a convolution tail in
    one slot of the state pool a sequence), attention layers without a
    positional encoding (K/V in pages) and routed feed-forward layers that
    cache NOTHING, behind a biased sigmoid router over two-matrix relu^2
    experts of which this process holds ``experts_held`` beside the shared
    expert."""
    MODEL_TYPES = ("nemotron_h",)

    def __init__(self, cfg, params, **kw):
        assert set(cfg.layer_kinds) <= {"ssd", "ffn", "full"} \
            and "full" in cfg.layer_kinds \
            and len(cfg.layer_kinds) == cfg.num_layers, \
            "nemotron_h names a kind for every layer, an attention one too"
        assert cfg.half_blocks and cfg.norm == "rmsnorm" \
            and cfg.pos_emb == "none" and not cfg.first_k_dense
        assert cfg.ssm_heads > 0 and cfg.ssm_head_dim > 0 \
            and cfg.ssm_heads % cfg.ssm_groups == 0 and cfg.ssm_conv > 1
        assert cfg.num_heads % cfg.kv_heads == 0
        assert cfg.expert_act == "relu2" and cfg.activation == "relu2" \
            and cfg.router_scoring == "sigmoid_grouped" \
            and cfg.router_groups == 1
        assert cfg.n_routed_experts >= cfg.moe_top_k >= 1
        held = cfg.held_experts
        assert 0 <= cfg.experts_first \
            and cfg.experts_first + held <= cfg.n_routed_experts, \
            "the experts held here lie outside the router's outputs"
        super().__init__(cfg, params, **kw)
        experts = self.params.get("experts")
        assert experts is None or ("wg" not in experts and experts[
            "wu"].shape[:2] == (cfg.layer_kinds.count("ffn"), held)), \
            "expert weights do not match the routed layers or experts_held"


_IMPLEMENTATIONS: Tuple[Type[RaggedInferenceModel], ...] = (
    LlamaV2InferenceModel, MistralInferenceModel, MixtralInferenceModel,
    FalconInferenceModel, OPTInferenceModel, PhiInferenceModel,
    Qwen2InferenceModel, BloomInferenceModel, PanguUltraMoEInferenceModel,
    LagunaInferenceModel, JambaInferenceModel, OlmoHybridInferenceModel,
    GPTNeoXInferenceModel, GPT2InferenceModel, GPTJInferenceModel,
    SmallThinkerInferenceModel, BailingHybridInferenceModel,
    NemotronHInferenceModel,
)


def implementation_for(model_type: str) -> Type[RaggedInferenceModel]:
    """model_type -> implementation class (reference engine_factory
    dispatch).  Unknown archs get the generic shared core — the policies
    registry already validated the weight mapping."""
    mt = model_type.lower()
    for impl in _IMPLEMENTATIONS:
        if mt in impl.MODEL_TYPES:
            return impl
    return RaggedInferenceModel


def supported_model_types() -> Dict[str, str]:
    return {t: impl.__name__ for impl in _IMPLEMENTATIONS
            for t in impl.MODEL_TYPES}
