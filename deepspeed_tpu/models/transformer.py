"""Functional transformer core — shared implementation behind the model
families (LLaMA, GPT, BERT, Mixtral presets in sibling modules).

TPU-native design choices (cf. reference per-arch containers in
``deepspeed/module_inject/containers/`` and inference-v2 model
implementations ``inference/v2/model_implementations/``):

* **Pure functions over pytrees** — params are nested dicts of arrays
  boxed with ``flax.core.meta.Partitioned`` logical axis names
  ('embed', 'heads', 'kv', 'mlp', 'vocab', 'layers', 'norm'); the ZeRO
  partitioner maps names -> mesh axes per parallelism config.
* **Stacked layers + lax.scan** — all transformer layers live in one
  stacked tree (leading 'layers' dim).  One compile of the layer body,
  O(1) HLO size in depth, and ``jax.checkpoint`` on the body is the
  activation-checkpointing unit (reference
  ``runtime/activation_checkpointing/checkpointing.py`` becomes a remat
  policy).
* **Sequence parallelism as sharding constraints** — Ulysses' two
  all-to-alls (reference ``sequence/layer.py:65`` DistributedAttention)
  are expressed by resharding activations seq-sharded -> head-sharded
  around attention; XLA inserts the all-to-alls on the 'seq' axis.
* **bf16 compute, fp32 softmax/normalization accumulations.**
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax.core import meta
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..accelerator import on_tpu
from ..parallel.topology import BATCH_AXES as BATCH  # batch-dim mesh axes


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: Optional[int] = None  # None -> MHA
    head_dim: Optional[int] = None      # None -> hidden/heads
    max_seq_len: int = 4096
    norm: str = "rmsnorm"              # rmsnorm | layernorm
    norm_eps: float = 1e-5
    # silu_gated | gelu (tanh approx) | gelu_exact | gelu_gated | relu
    activation: str = "silu_gated"
    pos_emb: str = "rope"              # rope | learned | alibi | none
    # layernorm over the token embeddings (BLOOM word_embeddings_layernorm)
    embed_layernorm: bool = False
    rope_theta: float = 10000.0
    rope_pct: float = 1.0              # partial rotary (GPT-NeoX/phi)
    causal: bool = True
    # Mistral/Mixtral sliding-window attention (HF sliding_window): each
    # position attends to the last `sliding_window` positions only.
    # None = full causal.  Served by the flash kernel's banded block
    # bounds on TPU and the dense mask on the einsum path; inference v2
    # masks (and skips out-of-window pages in the decode kernel) — KV
    # pages are still retained for the full context, so size num_pages
    # for O(context), not O(window).
    sliding_window: Optional[int] = None
    # attention-only biases (Qwen2: qkv bias, no o/mlp bias); use_bias
    # adds biases everywhere (GPT-2/NeoX style)
    qkv_bias: bool = False
    # x + attn(ln1 x) + mlp(ln2 x) (GPT-NeoX use_parallel_residual)
    parallel_residual: bool = False
    # MoE geometry (mixtral): >0 means the mlp block holds stacked
    # expert weights and forward needs a routed mlp_fn
    moe_num_experts: int = 0
    moe_top_k: int = 2
    # latent attention (MLA; models/pangu_moe.py): kv_lora_rank > 0 selects
    # it.  The cache holds one [c ; k_r] plane of kv_lora_rank + qk_rope_head_dim
    # values a token; scores are qk_nope + qk_rope_head_dim wide, values v_head_dim
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # x + N2(Attn(N1 x)), x + N4(FFN(N3 x)): a norm after each sub-layer too
    sandwich_norm: bool = False
    # a routed layer of another kind than moe_num_experts' (moe/held.py):
    # scores over n_routed_experts, the moe_top_k largest normalised and
    # scaled, every expert a gated MLP of moe_intermediate_size beside
    # n_shared_experts always-on ones.  Held here (one chip of an expert-
    # parallel group): experts_first .. + experts_held (0 = all); the
    # first first_k_dense layers keep the dense MLP.
    n_routed_experts: int = 0
    experts_held: int = 0
    experts_first: int = 0
    n_shared_experts: int = 0
    moe_intermediate_size: int = 0
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    first_k_dense: int = 0
    # router_scoring: "sigmoid" | "softmax" over all experts, before the
    # top-k; "sigmoid_grouped": the top-k among the router_topk_groups best
    # of router_groups expert groups, chosen by score + a selection bias.
    # router_reads: "ffn" (the feed-forward's normed input) | "mixer" (the
    # mixer's).  expert_act: "silu" | "relu", the gate's of a three-matrix
    # expert, or "relu2", a two-matrix expert's own (moe/held.py::ACTS)
    router_scoring: str = "sigmoid"
    router_groups: int = 0
    router_topk_groups: int = 0
    router_reads: str = "ffn"
    expert_act: str = "silu"
    # rows of the held experts' grouped-matmul tile where the family knows
    # better than moe/held.py::row_tile's rule for an even routing (0: the
    # rule).  Every further tile of ONE expert streams its weights again,
    # so a hot expert costs by its tiles
    moe_row_tile: int = 0
    # layers of two attention kinds in one model (models/laguna.py): one
    # entry a layer, "full" or "window" (sliding_window wide); () = every
    # layer of one kind.  A kind has its own query heads (heads_by_kind),
    # page group (inference/v2/ragged) and rope (window: window_rope_theta,
    # all dims; full: rope_theta over rope_pct of them under rope_yarn =
    # (factor, original positions, beta_fast, beta_slow, attention_factor));
    # a kind of nope_kinds has no positional encoding (models/smallthinker.py)
    layer_kinds: Tuple[str, ...] = ()
    heads_by_kind: Tuple[Tuple[str, int], ...] = ()
    window_rope_theta: float = 0.0
    rope_yarn: Tuple[float, ...] = ()
    nope_kinds: Tuple[str, ...] = ()
    # one gate a query head: o = concat_n(sigmoid(h Wg)_n * a_n) Wo
    head_gate: bool = False
    # state-space (Mamba-1) layers beside attention layers in one model
    # (models/jamba.py): ``layer_kinds`` names them "ssm".  ssm_state_dim
    # > 0 says the model has such layers, each a mixer of ssm_expand x
    # hidden channels with a recurrent state of ssm_state_dim a channel
    # (held in ssm_state_dtype), a causal convolution over ssm_conv
    # positions and a time step projected up from ssm_dt_rank.  What a
    # sequence carries of such a layer is a slot of the state pool
    # (ops/ssm.py), not pages
    ssm_state_dim: int = 0
    ssm_conv: int = 4
    ssm_dt_rank: int = 0
    ssm_expand: int = 2
    ssm_state_dtype: Any = jnp.float32
    # Mamba-2 (SSD) layers (models/nemotron_h.py): ``layer_kinds`` names
    # them "ssd".  ssm_heads > 0 says the model has such layers, each a
    # mixer of ssm_heads heads of ssm_head_dim channels (ssm_inner = their
    # product, whatever ssm_expand says) with ONE decay a head and step, B
    # and C shared by the heads of each of ssm_groups groups, a state of
    # ssm_state_dim a channel and a convolution over ssm_conv positions of
    # x, B and C (ops/ssm.py::ssd_scan)
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    # every layer is ONE sub-layer behind one norm and one residual
    # (models/nemotron_h.py): a mixer kind has no feed-forward behind it,
    # and the kind "ffn" is a feed-forward alone (no mixer, no cache)
    half_blocks: bool = False
    # gated delta-rule (linear-attention) layers beside attention layers in
    # one model (models/olmo_hybrid.py): ``layer_kinds`` names them
    # "delta".  delta_heads > 0 says the model has such layers, each a
    # mixer of delta_heads heads with keys of delta_key_dim and values of
    # delta_value_dim, a causal convolution over delta_conv positions of
    # q, k and v, and a matrix state [delta_key_dim, delta_value_dim] a
    # head (held in ssm_state_dtype, the state pool's dtype whatever kind
    # holds it; ops/delta_rule.py).  delta_neg_eigval: beta = 2 sigmoid(.)
    # in (0, 2), so the transition's eigenvalue along k lies in (-1, 1)
    delta_heads: int = 0
    delta_key_dim: int = 0
    delta_value_dim: int = 0
    delta_conv: int = 4
    delta_neg_eigval: bool = False
    # Kimi-delta (KDA) layers (models/bailing_hybrid.py): ``layer_kinds``
    # names them "kda".  The delta_* sizes above are theirs; the decay is
    # one a KEY CHANNEL, exp(kda_lower_bound * sigmoid(.)) (the bounded
    # gate: what lets a chunk of 16 tokens take a matrix form), and one
    # sigmoid gate a head scales the normed output
    kda_lower_bound: float = 0.0
    # x + N(mixer(x)), x + N(mlp(x)): the norm on the sub-layer's OUTPUT
    # and none on its input (the OLMo 2 / 3 order)
    post_norm: bool = False
    # RMSNorm (a learned gain) over the whole width of q and of k, all
    # heads together, before the positional encoding and the cache write
    qk_norm: bool = False
    tie_embeddings: bool = False
    use_bias: bool = False
    dropout: float = 0.0
    scan_layers: bool = True
    remat: bool = True
    # what a layer's checkpoint keeps for the backward (resolve_remat_policy
    # lists the names).  "auto": the richest rung of REMAT_RUNGS that fits
    # the memory an engine reports at trace time (remat_budget), and
    # nothing_saveable where nobody reports any
    remat_policy: str = "auto"
    # reference activation_checkpointing.partition_activations
    # (checkpointing.py:487): saved layer-boundary residuals are sharded
    # along the sequence dim over the model-parallel axes, 1/(sp*tp)
    # memory per device; XLA re-gathers at recompute
    partition_activations: bool = False
    # auto: Pallas flash kernel whenever the mask is pure-causal (TPU;
    # jnp reference off-TPU) | flash: force | einsum: dense path
    attention_impl: str = "auto"
    # sequence-parallel mechanism when the mesh has a 'seq' axis:
    # "ulysses" reshards tokens->heads around attention (two
    # all-to-alls); "ring" keeps tokens seq-sharded and circulates K/V
    # blocks over ppermute (context parallelism — O(S/P) activation
    # memory with no head-divisibility requirement).  Wired from engine
    # config sequence_parallel.mode.
    sp_mode: str = "ulysses"
    flash_block_q: int = 512
    flash_block_k: int = 512
    # sparse embedding gradients (reference engine.py:2535 sparse
    # allreduce): backward ships the [B*S,E] cotangent, not [V,E]
    sparse_gradients: bool = False
    dtype: Any = jnp.bfloat16

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def dims_per_head(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def latent_dim(self) -> int:
        """Values one token's latent cache plane holds (0: K/V heads)."""
        return self.kv_lora_rank + self.qk_rope_head_dim \
            if self.kv_lora_rank else 0

    @property
    def held_experts(self) -> int:
        return self.experts_held or self.n_routed_experts

    @property
    def ssm_inner(self) -> int:
        """Channels of a state-space mixer (0: the model has none)."""
        if self.ssm_heads:
            return self.ssm_heads * self.ssm_head_dim
        return self.ssm_expand * self.hidden_size if self.ssm_state_dim \
            else 0

    def n_params(self) -> int:
        """Matmul parameters held by this process (norm gains left out):
        of a routed layer the experts held here, not the layer's."""
        e, f, l, v = self.hidden_size, self.intermediate_size, self.num_layers, self.vocab_size
        h, k, d = self.num_heads, self.kv_heads, self.dims_per_head
        attn = e * h * d + 2 * e * k * d + h * d * e
        if self.kv_lora_rank:
            attn = ((e + h * (self.qk_nope_head_dim + self.qk_rope_head_dim))
                    * self.q_lora_rank if self.q_lora_rank
                    else e * h * (self.qk_nope_head_dim
                                  + self.qk_rope_head_dim)) + (
                    e * self.latent_dim
                    + self.kv_lora_rank * h * (self.qk_nope_head_dim
                                               + self.v_head_dim)
                    + h * self.v_head_dim * e)
        attn *= l
        if self.layer_kinds:        # a head count (and a gate) a kind
            heads = dict(self.heads_by_kind)
            di, n, r = self.ssm_inner, self.ssm_state_dim, self.ssm_dt_rank
            # a state-space mixer: in, x, dt and out projections, the
            # convolution, A and D (its three small norms left out)
            mixer = (e * 2 * di + di * (r + 2 * n) + r * di + di + di * e
                     + di * (self.ssm_conv + 1) + di * n + di)
            # a delta-rule mixer: q, k, v, gate, the two gates a head and
            # the output projection, the convolution, A and the step's bias
            dh, qk, dv = (self.delta_heads, self.delta_heads
                          * self.delta_key_dim, self.delta_heads
                          * self.delta_value_dim)
            delta = (e * (2 * qk + 2 * dv + 2 * dh) + dv * e
                     + self.delta_conv * (2 * qk + dv) + 2 * dh)
            # a KDA mixer: q, k, v, the decay gate a key channel, beta and
            # the output gate a head, the output projection, the
            # convolution, A a head and the gate's bias a channel
            kda = (e * (3 * qk + dv + 2 * dh) + dv * e
                   + self.delta_conv * (2 * qk + dv) + dh + qk)
            # a Mamba-2 mixer: the one in projection (z, x, B, C, dt), the
            # output projection, the convolution and its bias, A, D and
            # the step's bias a head
            xbc = di + 2 * self.ssm_groups * n
            ssd = (e * (di + xbc + self.ssm_heads) + di * e
                   + xbc * (self.ssm_conv + 1) + 3 * self.ssm_heads)
            latent = attn // l
            attn = sum(mixer if kind == "ssm" else
                       ssd if kind == "ssd" else
                       0 if kind == "ffn" else
                       delta if kind == "delta" else
                       kda if kind == "kda" else
                       latent if kind == "latent" else
                       2 * e * heads.get(kind, h) * d + 2 * e * k * d
                       + (e * heads.get(kind, h) if self.head_gate else 0)
                       for kind in self.layer_kinds)
        mlp = e * f * (3 if "gated" in self.activation else 2)
        dense = l
        routed = 0
        if self.n_routed_experts:
            dense = min(self.first_k_dense, l)
            routed = (l - dense) * (
                e * self.n_routed_experts
                + 3 * e * self.moe_intermediate_size
                * (self.held_experts + self.n_shared_experts))
        if self.half_blocks:        # the "ffn" layers alone, all routed
            dense = 0
            routed = self.layer_kinds.count("ffn") * (
                e * self.n_routed_experts
                + (2 if self.expert_act == "relu2" else 3) * e
                * self.moe_intermediate_size
                * (self.held_experts + self.n_shared_experts))
        return (attn + dense * mlp + routed
                + v * e * (1 if self.tie_embeddings else 2))


# ---------------------------------------------------------------------------
# param construction
# ---------------------------------------------------------------------------

def _boxed(value: jax.Array, names: Tuple[Optional[str], ...]):
    return meta.Partitioned(value, names=names)


def _dense_init(rng, shape, fan_in, dtype=jnp.float32):
    return jax.random.normal(rng, shape, dtype) * (fan_in ** -0.5)


def init_params(cfg: TransformerConfig, rng: jax.Array) -> Dict[str, Any]:
    """Initialize (boxed) parameters; stacked over layers when scanning."""
    if cfg.kv_lora_rank:
        raise NotImplementedError(
            "a latent-attention family builds its own parameters: "
            "models/pangu_moe.py::PanguUltraMoEForCausalLM.init_params")
    e, f, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    h, k, d = cfg.num_heads, cfg.kv_heads, cfg.dims_per_head
    L = cfg.num_layers
    keys = jax.random.split(rng, 12)

    def stack(init_one):
        """init per-layer then stack (scan) or keep list-of-dicts."""
        ps = [init_one(jax.random.fold_in(keys[0], i)) for i in range(L)]
        if cfg.scan_layers:
            return jax.tree.map(
                lambda *xs: _boxed(jnp.stack([x.value for x in xs]),
                                   ("layers",) + xs[0].names),
                *ps,
                is_leaf=lambda x: isinstance(x, meta.Partitioned))
        return {f"layer_{i}": p for i, p in enumerate(ps)}

    def layer_init(key):
        ks = jax.random.split(key, 8)
        p = {
            "attn": {
                "wq": _boxed(_dense_init(ks[0], (e, h, d), e), ("embed", "heads", None)),
                "wk": _boxed(_dense_init(ks[1], (e, k, d), e), ("embed", "kv", None)),
                "wv": _boxed(_dense_init(ks[2], (e, k, d), e), ("embed", "kv", None)),
                "wo": _boxed(_dense_init(ks[3], (h, d, e), h * d), ("heads", None, "embed")),
            },
            "mlp": {
                "wi": _boxed(_dense_init(ks[4], (e, f), e), ("embed", "mlp")),
                "wo": _boxed(_dense_init(ks[5], (f, e), f), ("mlp", "embed")),
            },
            "norm1": _norm_init(cfg, e),
            "norm2": _norm_init(cfg, e),
        }
        if "gated" in cfg.activation:
            p["mlp"]["wg"] = _boxed(_dense_init(ks[6], (e, f), e), ("embed", "mlp"))
        if cfg.use_bias or cfg.qkv_bias:
            p["attn"]["bq"] = _boxed(jnp.zeros((h, d)), ("heads", None))
            p["attn"]["bk"] = _boxed(jnp.zeros((k, d)), ("kv", None))
            p["attn"]["bv"] = _boxed(jnp.zeros((k, d)), ("kv", None))
        if cfg.use_bias:
            p["attn"]["bo"] = _boxed(jnp.zeros((e,)), ("embed",))
            p["mlp"]["bi"] = _boxed(jnp.zeros((f,)), ("mlp",))
            p["mlp"]["bo"] = _boxed(jnp.zeros((e,)), ("embed",))
        return p

    params: Dict[str, Any] = {
        "embed": {"tokens": _boxed(
            jax.random.normal(keys[1], (v, e)) * 0.02, ("vocab", "embed"))},
        "layers": stack(layer_init),
        "final_norm": _norm_init(cfg, e),
    }
    if cfg.pos_emb == "learned":
        params["embed"]["positions"] = _boxed(
            jax.random.normal(keys[2], (cfg.max_seq_len, e)) * 0.02, (None, "embed"))
    if cfg.embed_layernorm:
        params["embed"]["norm"] = _norm_init(cfg, e)
    if not cfg.tie_embeddings:
        params["lm_head"] = _boxed(_dense_init(keys[3], (e, v), e), ("embed", "vocab"))
    return params


def _norm_init(cfg: TransformerConfig, dim: int):
    p = {"scale": _boxed(jnp.ones((dim,)), ("norm",))}
    if cfg.norm == "layernorm":
        p["bias"] = _boxed(jnp.zeros((dim,)), ("norm",))
    return p


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _constrain(x: jax.Array, *spec) -> jax.Array:
    """Sharding constraint that degrades to no-op outside a mesh context.

    Inside a ``shard_map`` region (e.g. the CollectiveScheduler's
    batch-axes-manual backward), entries naming manually-bound axes are
    pruned — those dims are already physically sharded by the region —
    while entries over still-automatic axes (tensor/seq under
    partial-auto) keep guiding GSPMD."""
    from ..utils.jax_compat import manual_axis_names
    manual = manual_axis_names()
    if manual:
        def prune(entry):
            if entry is None:
                return None
            axes = entry if isinstance(entry, tuple) else (entry,)
            kept = tuple(a for a in axes if a not in manual)
            return kept if len(kept) > 1 else (kept[0] if kept else None)
        spec = tuple(prune(e) for e in spec)
        if all(e is None for e in spec):
            return x
    try:
        return jax.lax.with_sharding_constraint(x, P(*spec))
    except (ValueError, RuntimeError):
        return x


def _wval(p, dtype) -> jax.Array:
    """Weight leaf -> compute-dtype array.  Channel-quantized leaves
    ({'q', 'scale'} from ops/fp_quantizer.quantize_channelwise) dequant
    lazily — XLA fuses the cast+scale into the consuming einsum."""
    if isinstance(p, dict) and "q" in p:
        from ..ops.fp_quantizer import dequantize_channelwise
        return dequantize_channelwise(p, dtype)
    return p.astype(dtype)


def _norm_apply(cfg: TransformerConfig, p, x: jax.Array) -> jax.Array:
    x32 = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + cfg.norm_eps) * p["scale"].astype(jnp.float32)
    else:
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def rope_table(cfg: TransformerConfig, positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    d = int(cfg.dims_per_head * cfg.rope_pct)
    d -= d % 2
    freqs = cfg.rope_theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,d/2]
    return jnp.sin(angles), jnp.cos(angles)


def _rotate_pairs(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """``x [B,S,H,rot]`` rotated pair by pair, ``(x[2i], x[2i+1])`` by the
    angle of ``sin[..., i]`` / ``cos[..., i]`` (``[B,S,rot/2]``), in fp32:
    ONE elementwise pass over ``x`` in the layout it arrives in,

        out = x * cos2 + swap(x) * sin2

    with ``cos2 = (c0, c0, c1, c1, ...)``, ``sin2 = (-s0, +s0, -s1, +s1,
    ...)`` and ``swap(x)[2i] = x[2i+1]``, ``swap(x)[2i+1] = x[2i]``.  The
    swap is a product with a constant 0/1 permutation matrix accumulated in
    fp32, which is exact (each output is one input times 1.0, plus zeros):
    one pass of the matrix unit for a bfloat16 ``x``, the highest precision
    for anything wider.  Term for term it is ``x1*cos - x2*sin``,
    ``x2*cos + x1*sin`` of the strided-pair form (``x[..., 0::2]``,
    ``x[..., 1::2]``, ``stack``), which the chip's compiler answers with a
    pair-major float32 layout and six passes over HBM (docs/DESIGN.md,
    "Why the rope's pair swap is a product")."""
    rot = x.shape[-1]
    cos2 = jnp.repeat(cos, 2, axis=-1)[:, :, None, :]
    sin2 = (jnp.repeat(sin, 2, axis=-1)
            * jnp.tile(jnp.asarray([-1.0, 1.0], jnp.float32), rot // 2)
            )[:, :, None, :]
    lanes = np.arange(rot)
    swap = jnp.asarray(lanes[:, None] == (lanes ^ 1)[None, :])
    wide = x.dtype != jnp.bfloat16
    operand = x.astype(jnp.float32) if wide else x
    swapped = jnp.einsum(
        "bshd,de->bshe", operand, swap.astype(operand.dtype),
        precision=jax.lax.Precision.HIGHEST if wide else None,
        preferred_element_type=jnp.float32)
    out = x.astype(jnp.float32) * cos2 + swapped * sin2
    return out.astype(x.dtype)


def _rope_rotation_fwd(x, sin, cos):
    return _rotate_pairs(x, sin, cos), (sin, cos)


def _rope_rotation_bwd(tables, g):
    # the rotation by the opposite angle, written the same way: the
    # cotangent in ITS dtype goes through the permutation.  (Autodiff of
    # _rotate_pairs would transpose the product into float32 x bfloat16 at
    # one pass and round ``g * sin2`` to bfloat16 before the add.)  The
    # tables come from integer positions and take no cotangent
    sin, cos = tables
    return _rotate_pairs(g, -sin, cos), jnp.zeros_like(sin), \
        jnp.zeros_like(cos)


# the rules call the plain function: forward mode over the backward
# (runtime/eigenvalue.py) cannot pass through a custom_vjp
_rope_rotation = jax.custom_vjp(_rotate_pairs)
_rope_rotation.defvjp(_rope_rotation_fwd, _rope_rotation_bwd)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x: [B,S,H,D]; interleaved-pair rotation in fp32.  When the rope
    table covers fewer than D/2 frequencies (partial rotary,
    ``rope_pct < 1``), only the leading ``2*n_freq`` dims rotate and the
    tail passes through (GPT-NeoX ``rotary_pct`` semantics)."""
    rot = 2 * sin.shape[-1]
    out = _rope_rotation(x[..., :rot], sin, cos)
    if rot == x.shape[-1]:
        return out
    return jnp.concatenate([out, x[..., rot:]], axis=-1)


def _activation(cfg: TransformerConfig, gate, up):
    if cfg.activation == "silu_gated":
        return jax.nn.silu(gate) * up
    if cfg.activation == "gelu_gated":
        return jax.nn.gelu(gate) * up
    if cfg.activation == "relu":
        return jax.nn.relu(up)
    if cfg.activation == "relu2":       # squared ReLU, no gate
        return jnp.square(jax.nn.relu(up))
    if cfg.activation == "gelu_exact":  # HF "gelu" = erf, not tanh approx
        return jax.nn.gelu(up, approximate=False)
    return jax.nn.gelu(up)


def _ambient_mesh():
    """The Mesh active at trace time (None when single-device/absent)."""
    from ..parallel.topology import ambient_mesh
    m = ambient_mesh()
    return m if m is not None and m.devices.size > 1 else None


def flash_dot_product_attention(cfg: TransformerConfig, q, kv_k, kv_v) -> jax.Array:
    """Causal attention via the Pallas flash kernel (ops/flash_attention.py).

    q: [B,S,H,D], k/v: [B,S,K,D] -> [B,S,H,D].  Replaces the reference's
    fused attention kernels (csrc/transformer/ softmax+attention CUDA) on
    the training path: no [B,H,S,S] score tensor ever reaches HBM.

    K/V reach the kernel at their own head count (GQA).  Under a >1-device
    mesh the kernel runs inside shard_map (batch over the batch axes, heads over
    'seq'+'tensor' — the Ulysses layout), since GSPMD cannot partition a
    pallas_call on its own.
    """
    from ..ops.flash_attention import flash_attention

    qf = q.transpose(0, 2, 1, 3)      # [B,H,S,D]
    kf = kv_k.transpose(0, 2, 1, 3)   # [B,K,S,D]
    vf = kv_v.transpose(0, 2, 1, 3)

    def per_shard(qs, ks, vs):
        # K/V at their own head count: the kernels read a query head's
        # K/V by head // groups and sum dK / dV over a group
        return flash_attention(qs, ks, vs, causal=True,
                               block_q=cfg.flash_block_q,
                               block_k=cfg.flash_block_k,
                               window=cfg.sliding_window)

    mesh = _ambient_mesh()
    if mesh is not None:
        from ..utils.jax_compat import shard_map
        batch_axes = tuple(a for a in BATCH if a in mesh.axis_names)
        head_axes = tuple(a for a in ("seq", "tensor") if a in mesh.axis_names)
        head_shards = 1
        for a in head_axes:
            head_shards *= mesh.shape[a]
        if kf.shape[1] % max(head_shards, 1) != 0:
            # GQA with fewer kv heads than head shards (e.g. 2 kv heads
            # over seq*tensor = 4): repeat kv up to the q heads BEFORE
            # the manual region so the head split divides — same
            # semantics, and flash still beats the einsum fallback for
            # any nontrivial sequence length
            groups = qf.shape[1] // kf.shape[1]
            kf = jnp.repeat(kf, groups, axis=1)
            vf = jnp.repeat(vf, groups, axis=1)
        spec = P(batch_axes or None, head_axes or None, None, None)
        out = shard_map(per_shard, mesh=mesh,
                        in_specs=(spec, spec, spec), out_specs=spec,
                        check_vma=False)(qf, kf, vf)
    else:
        out = per_shard(qf, kf, vf)
    return out.transpose(0, 2, 1, 3)


def ring_dot_product_attention(cfg: TransformerConfig, q, kv_k, kv_v
                               ) -> jax.Array:
    """Causal attention with tokens kept SEQ-SHARDED: K/V blocks travel
    the 'seq' ring via ppermute (sequence/ring.py) while queries stay
    put — context parallelism as the reference-parity alternative to the
    Ulysses all-to-all sandwich.  q: [B,S,H,D], k/v: [B,S,K,D]."""
    from ..sequence.ring import ring_attention

    qf = q.transpose(0, 2, 1, 3)      # [B,H,S,D]
    kf = kv_k.transpose(0, 2, 1, 3)
    vf = kv_v.transpose(0, 2, 1, 3)
    groups = qf.shape[1] // kf.shape[1]
    if groups > 1:  # ring attends full heads; lift GQA before the ring
        kf = jnp.repeat(kf, groups, axis=1)
        vf = jnp.repeat(vf, groups, axis=1)

    mesh = _ambient_mesh()
    from ..utils.jax_compat import shard_map
    batch_axes = tuple(a for a in BATCH if a in mesh.axis_names)
    head_axes = _divisible_head_axes(qf.shape[1], ("tensor",))
    spec = P(batch_axes or None, head_axes or None, "seq", None)
    out = shard_map(
        functools.partial(ring_attention, axis_name="seq", causal=True,
                          window=cfg.sliding_window),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)(qf, kf, vf)
    return out.transpose(0, 2, 1, 3)


def _ring_ok(cfg: TransformerConfig, seq_len: int,
             batch: Optional[int] = None) -> bool:
    """Trace-time check for the ring layout: a real 'seq' axis whose size
    divides the sequence, plus exact batch divisibility (shard_map)."""
    mesh = _ambient_mesh()
    if mesh is None or mesh.shape.get("seq", 1) <= 1:
        return False
    if seq_len % mesh.shape["seq"] != 0:
        return False
    if batch is not None:
        batch_shards = 1
        for a in BATCH:
            if a in mesh.axis_names:
                batch_shards *= mesh.shape[a]
        if batch % batch_shards != 0:
            return False
    return True


def _flash_ok(cfg: TransformerConfig, n_heads: int, n_kv: int,
              batch: Optional[int] = None) -> bool:
    """Trace-time check that the flash layout divides the active mesh.

    Unlike the einsum path (where GSPMD pads awkward shapes), shard_map
    requires exact divisibility of both the head layout over
    ('seq','tensor') and — when known — the batch over the batch axes."""
    mesh = _ambient_mesh()
    if mesh is None:
        return True
    head_shards = 1
    for a in ("seq", "tensor"):
        if a in mesh.axis_names:
            head_shards *= mesh.shape[a]
    if batch is not None:
        batch_shards = 1
        for a in BATCH:
            if a in mesh.axis_names:
                batch_shards *= mesh.shape[a]
        if batch % batch_shards != 0:
            return False
    # kv heads that don't divide the shards are repeated up to n_heads
    # before the manual region (flash_dot_product_attention), so q-head
    # divisibility is the only hard constraint
    return n_heads % head_shards == 0 and head_shards <= n_heads


def _divisible_head_axes(n: int, axes=("seq", "tensor")) -> tuple:
    """Maximal prefix of ``axes`` (present in the mesh) whose sizes all
    divide ``n`` exactly — GSPMD pads non-divisible shardings, which
    costs an involuntary full rematerialization per transition."""
    mesh = _ambient_mesh()
    if mesh is None:
        return ()
    out = []
    for a in axes:
        size = mesh.shape.get(a, 1)
        if size > 1:
            if n % size != 0:
                break
            out.append(a)
            n //= size
    return tuple(out)


def alibi_slopes(n_heads: int) -> np.ndarray:
    """Per-head ALiBi slopes (geometric in 2^(-8/n), with the standard
    interleave extension for non-power-of-two head counts)."""
    def pow2(n):
        start = 2.0 ** (-(2.0 ** -(np.log2(n) - 3)))
        return [start ** (i + 1) for i in range(n)]
    k = 2 ** int(np.floor(np.log2(n_heads)))
    slopes = pow2(k)
    if k < n_heads:
        slopes += pow2(2 * k)[0::2][: n_heads - k]
    return np.asarray(slopes, np.float32)


def dot_product_attention(cfg: TransformerConfig, q, kv_k, kv_v,
                          mask: Optional[jax.Array],
                          attn_bias: Optional[jax.Array] = None) -> jax.Array:
    """Grouped-query attention, fp32 softmax.  q: [B,S,H,D], k/v: [B,S,K,D].

    Hot op #1 (reference csrc/transformer softmax/attention kernels).
    This dense einsum formulation serves arbitrary masks and non-TPU CI;
    the pure-causal training path uses flash_dot_product_attention.

    GQA sharding: the head dim splits into (k, g); when the Ulysses head
    shards exceed the kv-head count, k takes the axes that divide it and
    g takes the remainder, keeping every intermediate exactly-sharded
    (no GSPMD padding -> no involuntary remat in fwd or transpose).
    """
    b, s, hq, dd = q.shape
    k_heads = kv_k.shape[2]
    groups = hq // k_heads
    k_axes = _divisible_head_axes(k_heads)
    g_axes = _divisible_head_axes(
        groups, tuple(a for a in ("seq", "tensor") if a not in k_axes))
    q = q.reshape(b, s, k_heads, groups, dd)
    q = _constrain(q, BATCH, None, k_axes or None, g_axes or None, None)
    kv_k = _constrain(kv_k, BATCH, None, k_axes or None, None)
    kv_v = _constrain(kv_v, BATCH, None, k_axes or None, None)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, kv_k) / np.sqrt(dd)
    scores = scores.astype(jnp.float32)
    if attn_bias is not None:  # ALiBi: [B,H,T] additive, per q-head
        scores = scores + attn_bias.reshape(
            b, k_heads, groups, 1, attn_bias.shape[-1])
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    scores = _constrain(scores, BATCH, k_axes or None, g_axes or None,
                        None, None)
    probs = jax.nn.softmax(scores, axis=-1).astype(kv_v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, kv_v)
    out = _constrain(out, BATCH, None, k_axes or None, g_axes or None, None)
    return out.reshape(b, s, hq, dd)


def _attention_block(cfg: TransformerConfig, p, x, sin, cos, mask,
                     use_flash: bool = False, attn_bias=None,
                     use_ring: bool = False):
    dtype = cfg.dtype
    wq, wk, wv, wo = (p["wq"].astype(dtype), p["wk"].astype(dtype),
                      p["wv"].astype(dtype), p["wo"].astype(dtype))
    q = jnp.einsum("bse,ehd->bshd", x, wq)
    k = jnp.einsum("bse,ekd->bskd", x, wk)
    v = jnp.einsum("bse,ekd->bskd", x, wv)
    if cfg.use_bias or cfg.qkv_bias:
        q = q + p["bq"].astype(dtype)
        k = k + p["bk"].astype(dtype)
        v = v + p["bv"].astype(dtype)
    if cfg.pos_emb == "rope":
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
    # what attention reads, for the policies that keep it (_NAMED_POLICIES):
    # K and V at their own head count, before any repeat up to H heads, and
    # all three as the projections left them.  Measured at the train cell's
    # shapes (PERF.md, PR 38): keeping them in the flash kernels' layout
    # instead costs 0.6 ms a layer more, keeping K and V repeated 200 MB a
    # layer more for no time
    q = checkpoint_name(q, "attn_q")
    k = checkpoint_name(k, "attn_k")
    v = checkpoint_name(v, "attn_v")
    if use_ring:
        # ring CP: tokens STAY seq-sharded; no head resharding at all
        q = _constrain(q, BATCH, "seq", None, None)
        k = _constrain(k, BATCH, "seq", None, None)
        v = _constrain(v, BATCH, "seq", None, None)
        out = ring_dot_product_attention(cfg, q, k, v)
        out = checkpoint_name(out, "attn_out")
        out = jnp.einsum("bshd,hde->bse", out, wo)
        if cfg.use_bias:
            out = out + p["bo"].astype(dtype)
        return _constrain(out, BATCH, "seq", None)
    # Ulysses resharding: tokens seq-sharded -> heads ('seq'+'tensor')-sharded.
    # XLA materializes this as the two all-to-alls of reference
    # sequence/layer.py:65, but fused into the surrounding program.
    # kv heads take only the axes that DIVIDE them (GQA may have fewer kv
    # heads than head shards; padding a non-divisible sharding costs an
    # involuntary full remat per transition).
    q_axes = _divisible_head_axes(cfg.num_heads)
    kv_axes = _divisible_head_axes(cfg.kv_heads)
    # staged like the return leg below: S-over-seq + H-over-tensor first,
    # then full head sharding — each hop is a plannable all-to-all, and
    # the TRANSPOSE of this staging keeps the backward cotangents off the
    # replicate-repartition fallback too
    if _divisible_head_axes(q.shape[1], ("seq",)):
        t_q = _divisible_head_axes(cfg.num_heads, ("tensor",))
        t_kv = _divisible_head_axes(cfg.kv_heads, ("tensor",))
        q = _constrain(q, BATCH, "seq", t_q or None, None)
        k = _constrain(k, BATCH, "seq", t_kv or None, None)
        v = _constrain(v, BATCH, "seq", t_kv or None, None)
    q = _constrain(q, BATCH, None, q_axes or None, None)
    k = _constrain(k, BATCH, None, kv_axes or None, None)
    v = _constrain(v, BATCH, None, kv_axes or None, None)
    if use_flash:
        out = flash_dot_product_attention(cfg, q, k, v)
    else:
        out = dot_product_attention(cfg, q, k, v, mask, attn_bias)
    # the output as the projection below reads it.  Where the flash
    # KERNEL ran, the policies keep its own out and lse instead
    # (ops/flash_attention.RESIDUAL_NAMES; resolve_remat_policy) and this
    # one is a transpose away
    out = checkpoint_name(out, "attn_out")
    # Ulysses return leg, staged: go heads-(seq+tensor) -> (S over seq,
    # H over tensor) FIRST — a single plannable all-to-all — so the wo
    # einsum below is Megatron row-parallel (psum over 'tensor') with an
    # S-sharded output.  Without the stage, GSPMD sees heads-sharded ->
    # seq-sharded directly and falls back to an involuntary full
    # rematerialization (replicate + repartition) of the [B,S,H,D]
    # activation every layer.
    stage_axes = _divisible_head_axes(out.shape[2], ("tensor",))
    if _divisible_head_axes(out.shape[1], ("seq",)):
        out = _constrain(out, BATCH, "seq", stage_axes or None, None)
    out = jnp.einsum("bshd,hde->bse", out, wo)
    if cfg.use_bias:
        out = out + p["bo"].astype(dtype)
    return _constrain(out, BATCH, "seq", None)


def _mlp_block(cfg: TransformerConfig, p, x):
    dtype = cfg.dtype
    up = jnp.einsum("bse,ef->bsf", x, _wval(p["wi"], dtype))
    if cfg.use_bias:
        up = up + p["bi"].astype(dtype)
    gate = jnp.einsum("bse,ef->bsf", x, _wval(p["wg"], dtype)) \
        if "wg" in p else None
    h = _activation(cfg, gate, up) if gate is not None else _activation(cfg, None, up)
    h = _constrain(h, BATCH, "seq", "tensor")
    out = jnp.einsum("bsf,fe->bse", h, _wval(p["wo"], dtype))
    if cfg.use_bias:
        out = out + p["bo"].astype(dtype)
    return _constrain(out, BATCH, "seq", None)


#: the ``jax.named_scope``s of the training forward: ``embed`` and ``head``
#: in ``forward``, ``attn`` and ``mlp`` in ``_layer_body``, ``loss`` in
#: ``CausalLM.loss``.  Each names a module in every phase of a train step
#: (JAX carries a scope through jvp and transpose); the engine reads them
#: back from the compiled step (``CausalLM.scopes``,
#: ``DeepSpeedEngine.step_scope_table``).
MODULE_SCOPES = ("embed", "attn", "mlp", "head", "loss")


def _layer_body(cfg: TransformerConfig, layer_params, x, sin, cos, mask,
                mlp_fn=None, use_flash: bool = False, attn_bias=None,
                use_ring: bool = False):
    """Returns (x, aux) — aux is 0 for dense MLPs, the load-balancing loss
    for MoE mlp_fns (accumulated through the layer scan)."""
    # the scopes (MODULE_SCOPES) sit around the CALLS: the serving step
    # imports _mlp_block / _norm_apply / apply_rope, whose bodies stay bare
    with jax.named_scope("attn"):
        h = _norm_apply(cfg, layer_params["norm1"], x)
        attn_out = _attention_block(cfg, layer_params["attn"], h, sin, cos,
                                    mask, use_flash=use_flash,
                                    attn_bias=attn_bias, use_ring=use_ring)
    if cfg.parallel_residual:
        # GPT-NeoX: mlp sees ln2(x), both branches add to the SAME input
        with jax.named_scope("mlp"):
            h2 = _norm_apply(cfg, layer_params["norm2"], x)
            mlp_out = (mlp_fn or _mlp_block)(cfg, layer_params["mlp"], h2)
        aux = jnp.zeros((), jnp.float32)
        if isinstance(mlp_out, tuple):
            mlp_out, aux = mlp_out
        return x + attn_out + mlp_out, aux
    with jax.named_scope("attn"):
        x = checkpoint_name(x + attn_out, "attn_residual")
    with jax.named_scope("mlp"):
        h = _norm_apply(cfg, layer_params["norm2"], x)
        mlp_out = (mlp_fn or _mlp_block)(cfg, layer_params["mlp"], h)
        aux = jnp.zeros((), jnp.float32)
        if isinstance(mlp_out, tuple):
            mlp_out, aux = mlp_out
        return x + mlp_out, aux


_REMAT_POLICIES = {
    "nothing_saveable": jax.checkpoint_policies.nothing_saveable,
    "everything_saveable": jax.checkpoint_policies.everything_saveable,
    "dots_saveable": jax.checkpoint_policies.dots_saveable,
    "dots_with_no_batch_dims_saveable":
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
}

#: The policies that keep NAMED values of a layer beside its input, by what
#: they keep beyond attention's output.  Where the flash kernel ran, "the
#: output" is the kernel's own ``out`` and ``lse`` in the kernel's layout
#: (``ops/flash_attention.RESIDUAL_NAMES``): the backward kernels read those,
#: so keeping the [B,S,H,D] ``attn_out`` instead (what ``save_attn_out`` did
#: before PR 38) left the recomputed forward with its flash call.
#:   save_attn_out       the recomputed forward runs no flash kernel (with
#:                       it) / no probs x V (einsum, ring); 68 MB a layer at
#:                       Mistral-7B widths and 8,192 tokens a device, for
#:                       1.7 ms of a layer's 92.6 (forward + backward alone
#:                       on one v5e chip; PERF.md, PR 38)
#:   save_attn           and none of the q/k/v projections and ropes: q and k
#:                       after rope and v are kept too (K/V at their own head
#:                       count, before the GQA repeat); 169 MB for 4.9 ms
#:   save_attn_residual  nor the output projection: ``x + attn_out`` is kept
#:                       too; 236 MB for 6.3 ms.  Left to recompute: the two
#:                       norms, the MLP's gate and up, the activation
#: The einsum and ring paths have no ``lse``: they keep q, k, v and the
#: output, and recompute the scores and the softmax.
_QKV = ("attn_q", "attn_k", "attn_v")
_NAMED_POLICIES = {"save_attn_out": (), "save_attn": _QKV,
                   "save_attn_residual": _QKV + ("attn_residual",)}

#: what ``remat_policy="auto"`` chooses among, poorest first
REMAT_RUNGS = ("nothing_saveable", "save_attn", "save_attn_residual")


def resolve_remat_policy(name: str, flash_kernel: bool = False):
    """Remat-policy lookup incl. the host-offload variants backing the
    reference's ``cpu_checkpointing`` (checkpointing.py:487): checkpoints
    are saved to pinned host memory and fetched back for the backward,
    trading HBM for PCIe/host traffic exactly like the CUDA path.
    ``flash_kernel``: attention runs the Pallas kernel, whose output has
    names of its own."""
    if name == "auto":      # nobody reported a budget (remat_budget)
        name = REMAT_RUNGS[0]
    if name in _REMAT_POLICIES:
        return _REMAT_POLICIES[name]
    if flash_kernel:
        from ..ops.flash_attention import RESIDUAL_NAMES as output
    else:
        output = ("attn_out",)
    if name in _NAMED_POLICIES:
        return jax.checkpoint_policies.save_only_these_names(
            *output, *_NAMED_POLICIES[name])
    if name == "offload_attn_out":
        return jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
            names_which_can_be_offloaded=list(output),
            offload_src="device", offload_dst="pinned_host")
    if name == "offload_dots":
        return jax.checkpoint_policies.offload_dot_with_no_batch_dims(
            "device", "pinned_host")
    raise ValueError(
        f"unknown remat policy {name!r}; known: "
        f"{sorted([*_REMAT_POLICIES, *_NAMED_POLICIES, 'auto', 'offload_attn_out', 'offload_dots'])}")


# -- remat_policy="auto": the richest rung the device's memory holds ---------

#: kept free beside everything the rule below reckons, for the compiler's
#: scheduler: a ZeRO-3 step left with under ~1 GB gathers its weights late.
#: The train cell (PERF.md, PR 38) with rung 2 compiles to 0.91 GB under the
#: chip's limit and runs 2.3% SLOWER than with rung 1 (1.35 GB under), which
#: is the rung this margin lets the rule take there
REMAT_MARGIN_BYTES = 750_000_000


def remat_rung_bytes(cfg: TransformerConfig, tokens: int,
                     tensor_shards: int = 1) -> Dict[str, int]:
    """Bytes a layer each rung of REMAT_RUNGS keeps on a device that holds
    ``tokens`` tokens of a micro-batch, beside the layer's input: q and the
    output (H heads), k and v (K heads), the float32 log-sum-exp a query
    head and token; rung 2 the residual after attention too."""
    c, d = jnp.dtype(cfg.dtype).itemsize, cfg.dims_per_head
    heads, kv = (-(-n // tensor_shards) for n in (cfg.num_heads, cfg.kv_heads))
    attn = tokens * (d * (2 * heads + 2 * kv) * c + heads * 4)
    return dict(zip(REMAT_RUNGS,
                    (0, attn, attn + tokens * cfg.hidden_size * c)))


def remat_working_set(cfg: TransformerConfig, tokens: int,
                      grads_bytes: int = 0, tensor_shards: int = 1) -> int:
    """Bytes a train step holds on a device beside its state, its parameter
    copy and a rung's residuals, reckoned from the shapes: the layers'
    inputs (what every policy keeps), and the larger of the loss's moment
    (the logits in float32 and once in the compute dtype; the embedding and
    the head gathered whole) and a layer's backward (the gradients of the
    stack, ``grads_bytes``; the layer's recomputed values and their
    cotangents as far as they live at once, about two MLP-wide and four
    model-wide arrays; the weights of two layers gathered whole, one in use
    and one in flight).  Held to the chip compiler's own count for the
    train cell's step in ``tests/test_chip_compile.py`` (PERF.md, PR 38:
    2% over it at 12 layers, 6% at 2)."""
    c, e = jnp.dtype(cfg.dtype).itemsize, cfg.hidden_size
    f, v = (-(-n // tensor_shards)
            for n in (cfg.intermediate_size, cfg.vocab_size))
    boundaries = cfg.num_layers * tokens * e * c
    loss = tokens * v * (4 + c) + \
        (1 if cfg.tie_embeddings else 2) * cfg.vocab_size * e * c
    h, k, d = cfg.num_heads, cfg.kv_heads, cfg.dims_per_head
    layer_weights = (2 * e * (h + k) * d
                     + e * f * (3 if "gated" in cfg.activation else 2)) * c
    layer = grads_bytes + tokens * (2 * f + 4 * e) * c + 2 * layer_weights
    return boundaries + max(loss, layer)


def choose_remat_policy(cfg: TransformerConfig, tokens: int,
                        budget_bytes: int, tensor_shards: int = 1
                        ) -> Tuple[str, int]:
    """(the richest rung of REMAT_RUNGS whose residuals over all layers fit
    ``budget_bytes``, the bytes a layer it keeps).  No budget: today's."""
    rungs = remat_rung_bytes(cfg, tokens, tensor_shards)
    fits = [name for name in REMAT_RUNGS
            if cfg.num_layers * rungs[name] <= max(budget_bytes, 0)]
    return fits[-1], rungs[fits[-1]]


@dataclasses.dataclass
class RematBudget:
    """What an engine sees of ONE device's memory when it builds a train
    step (bytes), and what the model made of it when the step was traced.
    ``limit_bytes`` 0: the backend reports no limit (the CPU), and "auto"
    stays ``nothing_saveable``."""
    limit_bytes: int = 0
    state_bytes: int = 0    # what the engine holds there between steps
    params_bytes: int = 0   # the compute-dtype copy of its parameter shard
    grads_bytes: int = 0    # the gradients alive while layers run backward
    # -- written by ``choose`` (None: no trace asked) --
    policy: Optional[str] = None
    layer_bytes: int = 0
    budget_bytes: int = 0   # what was left for residuals, all layers

    def choose(self, cfg: TransformerConfig, tokens: int,
               tensor_shards: int = 1) -> str:
        if self.limit_bytes:
            self.budget_bytes = (
                self.limit_bytes - self.state_bytes - self.params_bytes
                - remat_working_set(cfg, tokens, self.grads_bytes,
                                    tensor_shards) - REMAT_MARGIN_BYTES)
        self.policy, self.layer_bytes = choose_remat_policy(
            cfg, tokens, self.budget_bytes, tensor_shards)
        if self.limit_bytes:
            from ..utils.logging import log_dist
            log_dist(
                f"remat_policy auto -> {self.policy}: {self.layer_bytes} B "
                f"a layer x {cfg.num_layers} of {self.budget_bytes} B free "
                f"for residuals ({tokens} tokens a device; limit "
                f"{self.limit_bytes}, state {self.state_bytes}, parameter "
                f"copy {self.params_bytes}, gradients {self.grads_bytes}, "
                f"margin {REMAT_MARGIN_BYTES})", ranks=[0])
        return self.policy


_BUDGET: List[RematBudget] = []


@contextlib.contextmanager
def remat_budget(budget: RematBudget):
    """While a loss is traced under it, ``remat_policy="auto"`` is
    ``budget.choose(...)`` on the shapes the trace sees."""
    _BUDGET.append(budget)
    try:
        yield budget
    finally:
        _BUDGET.pop()


def _layer_policy(cfg: TransformerConfig, rows: int, seq: int,
                  use_flash: bool):
    """The checkpoint policy of the layer stack for a [rows, seq] batch."""
    name = cfg.remat_policy
    if name == "auto" and _BUDGET:
        # the share of the batch one device holds, under the ambient mesh
        mesh, shards, tensor = _ambient_mesh(), 1, 1
        if mesh is not None:
            for a in (*BATCH, "seq"):
                shards *= mesh.shape.get(a, 1)
            tensor = mesh.shape.get("tensor", 1)
        name = _BUDGET[-1].choose(cfg, -(-rows * seq // shards), tensor)
    return resolve_remat_policy(name, flash_kernel=use_flash and on_tpu())


def forward(cfg: TransformerConfig, params, input_ids: jax.Array,
            positions: Optional[jax.Array] = None,
            attention_mask: Optional[jax.Array] = None,
            mlp_fn=None, return_aux: bool = False) -> jax.Array:
    """Token ids [B,S] -> logits [B,S,V] (fp32); with ``return_aux``,
    returns (logits, accumulated MoE aux loss)."""
    if cfg.kv_lora_rank:
        raise NotImplementedError(
            "the training forward pass has no latent-attention block yet: "
            "this family is served (inference/v2) and held to the plain "
            "reference models/pangu_moe_reference.py")
    if cfg.layer_kinds:
        raise NotImplementedError(
            "the training forward pass has one head count and one attention "
            "kind for every layer: this family is served (inference/v2) and "
            "held to the plain reference models/laguna_reference.py")
    params = meta.unbox(params) if _has_boxes(params) else params
    b, s = input_ids.shape

    # Flash is valid only for the standard dense-causal case: default
    # positions (no packing) and no padding mask.  Decided at trace time.
    pure_causal = (cfg.causal and attention_mask is None
                   and positions is None and cfg.pos_emb != "alibi"
                   and s > 1)
    # ring CP replaces the Ulysses reshard entirely when configured
    use_ring = (cfg.sp_mode == "ring" and pure_causal
                and _ring_ok(cfg, s, batch=b))
    use_flash = (not use_ring
                 and cfg.attention_impl != "einsum"
                 and pure_causal
                 and _flash_ok(cfg, cfg.num_heads, cfg.kv_heads, batch=b))
    if cfg.attention_impl == "flash" and not (use_flash or use_ring):
        raise ValueError(
            "attention_impl='flash' requires causal attention with default "
            "positions, no attention_mask, and a mesh the head layout divides")

    if positions is None:
        if cfg.pos_emb == "learned" and attention_mask is not None:
            # padded batches: positions count only attended tokens
            # (HF OPTLearnedPositionalEmbedding cumsum semantics — left
            # or right padding yields the same logits as transformers)
            am = attention_mask.astype(jnp.int32)
            positions = jnp.clip(jnp.cumsum(am, axis=-1) - 1, 0)
        else:
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    # Gather from an explicitly replicated table: the ZeRO JIT all-gather
    # of [V,E] happens once, the gather output is then born replicated and
    # the batch/seq constraint below is a cheap local slice (letting XLA
    # derive the output sharding from a vocab/fsdp-sharded table instead
    # triggers an involuntary full remat of the gathered activations).
    with jax.named_scope("embed"):
        table = _constrain(params["embed"]["tokens"].astype(cfg.dtype))
        if cfg.sparse_gradients:
            from ..runtime.sparse_tensor import embedding_lookup
            x = embedding_lookup(table, input_ids)
        else:
            x = table[input_ids]
        if cfg.pos_emb == "learned":
            x = x + params["embed"]["positions"].astype(cfg.dtype)[positions]
        if cfg.embed_layernorm:  # BLOOM word_embeddings_layernorm
            x = _norm_apply(cfg, params["embed"]["norm"], x)
        x = _constrain(x, BATCH, "seq", None)

    # mask: [B, S(q), S(k)]  (not needed on the flash path — the kernel
    # applies causality blockwise)
    if use_flash or use_ring:
        mask = None
    elif cfg.causal:
        mask = positions[:, :, None] >= positions[:, None, :]
    else:
        mask = jnp.ones((b, s, s), bool)
    if mask is not None and cfg.sliding_window is not None:
        mask = mask & ((positions[:, :, None] - positions[:, None, :])
                       < cfg.sliding_window)
    if attention_mask is not None and mask is not None:
        mask = mask & attention_mask[:, None, :].astype(bool)

    sin, cos = rope_table(cfg, positions) if cfg.pos_emb == "rope" else (None, None)

    # ALiBi: additive per-head bias that depends only on the KEY position
    # (softmax is shift-invariant along each query row, so slope*(t-s)
    # and slope*t are equivalent under the causal mask)
    attn_bias = None
    if cfg.pos_emb == "alibi":
        slopes = jnp.asarray(alibi_slopes(cfg.num_heads))
        attn_bias = slopes[None, :, None] * positions[:, None, :].astype(
            jnp.float32)                                      # [B,H,T]

    body = functools.partial(_layer_body, cfg, mlp_fn=mlp_fn,
                             use_flash=use_flash, attn_bias=attn_bias,
                             use_ring=use_ring)

    # partition_activations: the layer-boundary residual (what the scan
    # carry chain / checkpoint saves) is sharded along seq over the
    # model-parallel axes — 1/(sp*tp) activation memory per device
    part_axes = (_divisible_head_axes(s, ("seq", "tensor"))
                 if cfg.partition_activations else ())

    def bound(y):
        return _constrain(y, BATCH, part_axes, None) if part_axes else y

    policy = _layer_policy(cfg, b, s, use_flash) if cfg.remat else None
    aux_total = jnp.zeros((), jnp.float32)
    if cfg.scan_layers:
        def scan_body(carry, layer_params):
            x, aux_acc = carry
            y, aux = body(layer_params, x, sin, cos, mask)
            return (bound(y), aux_acc + aux), None
        if cfg.remat:
            scan_body = jax.checkpoint(scan_body, policy=policy,
                                       prevent_cse=False)
        (x, aux_total), _ = jax.lax.scan(scan_body, (bound(x), aux_total),
                                         params["layers"])
    else:
        for i in range(cfg.num_layers):
            lp = params["layers"][f"layer_{i}"]
            fn = body
            if cfg.remat:
                fn = jax.checkpoint(body, policy=policy, prevent_cse=False)
            x, aux = fn(lp, bound(x), sin, cos, mask)
            aux_total = aux_total + aux

    with jax.named_scope("head"):
        x = _norm_apply(cfg, params["final_norm"], x)
        if cfg.tie_embeddings:
            logits = jnp.einsum("bse,ve->bsv", x, params["embed"]["tokens"].astype(cfg.dtype))
        else:
            logits = jnp.einsum("bse,ev->bsv", x, params["lm_head"].astype(cfg.dtype))
        if "lm_head_bias" in params:  # phi family ships an lm_head bias
            logits = logits + params["lm_head_bias"].astype(cfg.dtype)
        logits = _constrain(logits, BATCH, "seq", "tensor")
        logits = logits.astype(jnp.float32)
    if return_aux:
        return logits, aux_total
    return logits


def _has_boxes(params) -> bool:
    found = False
    for leaf in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, meta.Partitioned)):
        if isinstance(leaf, meta.Partitioned):
            found = True
        break
    return found


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       mask: Optional[jax.Array] = None) -> jax.Array:
    """Token-level CE in fp32; labels < 0 are ignored."""
    valid = labels >= 0 if mask is None else (mask.astype(bool) & (labels >= 0))
    safe_labels = jnp.maximum(labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe_labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * valid
    return nll.sum() / jnp.maximum(valid.sum(), 1)


class CausalLM:
    """Engine-protocol causal LM over the transformer core.  Batch dict:
    {'input_ids': [B,S] int32, optional 'labels' (default: shifted inputs),
    optional 'attention_mask'}."""

    #: the named scopes ``loss`` enters, for the engine's scope table
    scopes = MODULE_SCOPES

    def __init__(self, cfg: TransformerConfig):
        self.cfg = cfg

    def init_params(self, rng):
        return init_params(self.cfg, rng)

    def logits(self, params, batch, rng=None):
        return forward(self.cfg, params, batch["input_ids"],
                       positions=batch.get("positions"),
                       attention_mask=batch.get("attention_mask"))

    def loss(self, params, batch, rng=None):
        logits = self.logits(params, batch, rng)
        with jax.named_scope("loss"):
            if "labels" in batch:
                labels = batch["labels"]
                return cross_entropy_loss(logits, labels,
                                          batch.get("attention_mask"))
            # next-token prediction: shift
            labels = batch["input_ids"][:, 1:]
            mask = batch.get("attention_mask")
            return cross_entropy_loss(
                logits[:, :-1], labels,
                mask[:, 1:] if mask is not None else None)


def layer_kinds(cfg: TransformerConfig) -> Tuple[str, ...]:
    """Every layer's kind (``inference/v2/ragged/cache_kinds.py``): the
    configuration's own list, or for a model that names none one kind for
    all its layers, "latent" under latent attention and else "full"."""
    return cfg.layer_kinds or (
        ("latent" if cfg.latent_dim else "full",) * cfg.num_layers)


def kind_runs(kinds) -> List[Tuple[str, int]]:
    """The maximal runs of like kinds in ``kinds``, as (kind, length)."""
    runs: List[Tuple[str, int]] = []
    for kind in kinds:
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1] + 1)
        else:
            runs.append((kind, 1))
    return runs


def layer_runs(cfg: TransformerConfig
               ) -> Tuple[int, List[Tuple[str, int]], int, int]:
    """(the leading layers that stand outside the pattern,
    ``cfg.first_k_dense``; the runs of like layers inside one period of the
    pattern that follows them as (kind, length); whole periods; layers
    after them).  The period is the shortest the kinds repeat with."""
    kinds = layer_kinds(cfg)
    leading = min(cfg.first_k_dense, len(kinds))
    rest = kinds[leading:]
    period = next((p for p in range(1, len(rest) + 1)
                   if all(rest[i] == rest[i % p]
                          for i in range(len(rest)))), 1)
    runs = kind_runs(rest[:period])
    periods = len(rest) // period
    return leading, runs, periods, len(rest) - periods * period
