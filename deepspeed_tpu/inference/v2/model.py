"""Ragged inference model over the shared transformer core.

Reference: ``inference/v2/model_implementations/inference_transformer_base.py``
(``DSTransformerModelBase``) + per-arch models (llama_v2/model.py:22,
mistral, mixtral, …).  There, a from-scratch module layer re-implements
every op class against CUDA kernels.  Here the *training* transformer
core (models/transformer.py) is reused: the same params, norms and
projections, with attention swapped for the paged ragged formulation
(ops/paged_attention.py).  The layer loop CARRIES the whole KV pool
beside the activations and scans only the weights and a layer counter:
every layer writes and reads ``pool[layer]`` in place, so inside a step
program the pool never leaves its donated buffer — no per-layer slice
is taken out, nothing is stacked back, no op changes its layout
(``tests/test_chip_compile.py`` holds the compiled programs to that).

Every distinct batch bucket shape ``(S, Q, P)`` compiles exactly once;
the KV cache is donated so decoding is allocation-free on device.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import os
import threading
import time
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...models import transformer as T
from ...moe import held
from ...ops.delta_rule import delta_rule
from ...ops.mla_attention import (latent_write, mla_fresh_attention,
                                  mla_paged_attention, plane_width)
from ...ops.paged_attention import (KVPages, gather_last, token_positions,
                                    write_kv)
from ...ops.ssm import conv_step, ssd_scan, ssm_scan
from ...telemetry import get_tracer
from ...telemetry import metrics as tm
from ...telemetry.watchdog import get_watchdog
from ...telemetry.workload_trace import get_workload_trace
from ...utils.compile_cache import thread_cache_counts
from .lattice import POWER_LATTICE
from .ragged import KVCacheConfig, RaggedBatch
from .ragged.cache_kinds import CACHE_KINDS, TableLayout, slot_kind
from .ragged.kv_cache import StatePoolConfig
from .step_key import (STEP_KINDS, StepKey, step_avals, step_program,
                       trunk_params)


def serving_peak_flops() -> Optional[float]:
    """Peak FLOP/s denominator for the serving MFU gauges: an explicit
    ``DS_PEAK_FLOPS`` (the operator's statement) wins, else the
    ``device_kind`` table (profiling.flops_profiler).  A device with no
    published peak has no denominator: None, and every utilization
    derived from it reads 0 / None — "not measured", never a CPU rate
    over an assumed chip's peak."""
    env = os.environ.get("DS_PEAK_FLOPS", "")
    if env:
        return float(env)
    from ...profiling.flops_profiler import _device_peak_flops
    return _device_peak_flops()


def serving_tokens_at_ridge(params: Any) -> Optional[float]:
    """Tokens a step carries where the device stops waiting for the
    weights and starts waiting for its matrix unit: a step streams every
    weight once (``bytes_per_weight`` each, the served tree's own mean:
    2 in bf16, near 1 under weight-only fp8 / int8) and multiplies each
    token by it (2 FLOPs), so ``peak_flops * bytes_per_weight / (2 *
    hbm_bytes_per_s)``: 240 for bf16 weights on a v5e.  Under it a
    further token rides the stream for nearly nothing, past it every
    token costs its full time.  From the published tables alone (no
    ``DS_PEAK_FLOPS``: that one sizes a gauge, this one a schedule);
    None where the device has no entry in either, and the scheduler
    then admits as if there were no ridge."""
    from ...profiling.flops_profiler import (_device_hbm_bytes_per_s,
                                             _device_peak_flops)
    peak, hbm = _device_peak_flops(), _device_hbm_bytes_per_s()
    if not peak or not hbm:
        return None
    weights = [w for w in jax.tree.leaves(params)
               if getattr(w, "ndim", 0) >= 2]
    elements = sum(int(w.size) for w in weights)
    if not elements:
        return None
    nbytes = sum(int(w.size) * w.dtype.itemsize for w in weights)
    return peak * (nbytes / elements) / (2.0 * hbm)


def utilization(flops_per_s: float, peak: Optional[float]) -> float:
    """``flops_per_s / peak``; 0.0 where the device has no peak."""
    return flops_per_s / peak if peak else 0.0


def _write_new_kv(k, v, kv, layer, page_table, start_pos, q_lens):
    """``write_kv`` with its head-split arguments first, the order
    ``RaggedInferenceModel._per_shard_heads`` builds its specs from."""
    return write_kv(kv, layer, k, v, page_table, start_pos, q_lens)


class Segment(NamedTuple):
    """One segment of a step: rows of one geometry (a ``RaggedBatch``'s
    device vectors) and whether every row starts at position 0, which
    lets attention skip the pages (``fresh``, static).  A mixed step has
    two, every other kind one."""
    token_ids: jax.Array        # [S, Q]
    q_lens: jax.Array           # [S]
    start_pos: jax.Array        # [S]
    page_table: jax.Array       # [S, P]
    fresh: bool = False


def _end_to_end(parts: Sequence[jax.Array]) -> jax.Array:
    """The segments' per-token arrays ``[S_i, Q_i, ...]`` as the one array
    the token-wise operations run over: several segments' tokens laid end
    to end as rows of one token each, ``[T, 1, ...]``; a lone segment's
    array as it is (its rows are its tokens end to end already)."""
    if len(parts) == 1:
        return parts[0]
    return jnp.concatenate([p.reshape((-1, 1) + p.shape[2:]) for p in parts])


def _per_segment(x: jax.Array, segments: Sequence[Segment]
                 ) -> List[jax.Array]:
    """:func:`_end_to_end` undone: each segment's tokens of ``x`` as
    ``[S, Q, ...]``, for what differs by kind of row (the page write,
    attention, the last-token gather)."""
    if len(segments) == 1:
        return [x]
    out, at = [], 0
    for seg in segments:
        S, Q = seg.token_ids.shape
        out.append(x[at:at + S * Q].reshape((S, Q) + x.shape[2:]))
        at += S * Q
    return out


def _positions(segments: Sequence[Segment]) -> jax.Array:
    """Every token's position in its sequence, :func:`_end_to_end`."""
    return _end_to_end([token_positions(seg.start_pos, seg.token_ids.shape[1])
                        for seg in segments])


def _true_positions(seg: Segment) -> jax.Array:
    """``[S, Q]``: which of a segment's positions hold a token."""
    return (jnp.arange(seg.token_ids.shape[1], dtype=jnp.int32)[None, :]
            < seg.q_lens[:, None])


class Pass(NamedTuple):
    """What one pass of the trunk hands each of its layers
    (``_forward_hidden`` makes it once)."""
    cfg: T.TransformerConfig
    #: by layer kind that caches pages: the segments under the table of
    #: the kind's page group (the window group's rebased)
    by_group: Dict[str, List[Segment]]
    #: a segment's (state slot, starts from zeros, true positions)
    rows: List[Tuple[jax.Array, jax.Array, jax.Array]]
    #: by layer kind under rope: (sin, cos)
    ropes: Dict[str, Tuple[jax.Array, jax.Array]]
    #: held experts: of every token of the pass, whether it is a true one
    valid: Optional[jax.Array]
    #: where each pool stands in the carry, by what it is (``pool_names``)
    places: Dict[str, int] = {}


class Mixer(NamedTuple):
    """How the layers of one kind mix tokens (:data:`MIXERS`, below the
    model): ``run(model, h, pools, weights, layer, *, kind, ctx) ->
    (output, pools)``; the key of its ``weights`` in a layer's tree; which
    ``pools`` it writes, by what they are (``RaggedInferenceModel.
    pool_names``: "pages", "window", "state", "conv")."""
    run: Callable
    weights: str
    pools: Tuple[str, ...]


def _write_then_attend(segments: Sequence[Segment], new, pool, write, fresh,
                       paged):
    """The half of an attention mixer that runs segment by segment, over
    ``new`` (arrays over all tokens) taken apart: each segment in turn
    writes its new tokens (``write(pool, seg, *parts)``) and attends,
    ``fresh(*parts)`` over them alone where every row starts at position
    0 (None: no such module), else ``paged(pool, seg, *parts)``.  Returns
    (the outputs end to end, the pool)."""
    out = []
    for seg, *parts in zip(segments, *(
            _per_segment(a, segments) for a in new)):
        pool = write(pool, seg, *parts)
        out.append(fresh(*parts) if seg.fresh and fresh is not None
                   else paged(pool, seg, *parts))
    return _end_to_end(out), pool


# q, k and v by head, ``[S, Q, heads, d]``, from either format the families
# store the projections in (the parameters' format: no weight is re-laid)

def _qkv_by_axis(h, ap, cfg, rope, norm):
    """Weights with the heads as an axis, ``[e, heads, d]`` (the seed
    families; what ``quantize_weights`` packs)."""
    dtype = cfg.dtype
    q = jnp.einsum("sqe,ehd->sqhd", h, T._wval(ap["wq"], dtype))
    k = jnp.einsum("sqe,ekd->sqkd", h, T._wval(ap["wk"], dtype))
    v = jnp.einsum("sqe,ekd->sqkd", h, T._wval(ap["wv"], dtype))
    if cfg.use_bias or cfg.qkv_bias:
        q = q + ap["bq"].astype(dtype)
        k = k + ap["bk"].astype(dtype)
        v = v + ap["bv"].astype(dtype)
    if rope is not None:
        q, k = T.apply_rope(q, *rope), T.apply_rope(k, *rope)
    return q, k, v


def _qkv_folded(h, ap, cfg, rope, norm):
    """Weights stored as the matrices the products take, the heads folded
    into their columns (head n = columns n*d .. n*d + d - 1); under
    ``cfg.qk_norm`` q and k normed over their whole width."""
    def heads(w, gain=None, rotate=False):
        y = jnp.einsum("sqe,ef->sqf", h, w.astype(cfg.dtype))
        if gain is not None:
            y = norm(gain, y)
        y = y.reshape(y.shape[:2] + (-1, cfg.dims_per_head))
        return T.apply_rope(y, *rope) if rotate and rope is not None else y

    return (heads(ap["wq"], ap["q_norm"] if cfg.qk_norm else None, True),
            heads(ap["wk"], ap["k_norm"] if cfg.qk_norm else None, True),
            heads(ap["wv"]))


def _rebox_from_cfg(cfg: T.TransformerConfig, params):
    """Attach logical-axis metadata to an UNBOXED param tree (HF imports
    arrive as plain arrays) by zipping with the model family's own
    abstract init — exact AutoTP classification with no name heuristics
    (the reference's tp_parser walk, module_inject/auto_tp.py:283).
    Leaves without a counterpart in the canonical tree (e.g. phi's
    lm_head_bias) stay unboxed and therefore replicated."""
    import jax

    def ref_tree():
        p = T.init_params(cfg, jax.random.key(0))
        if cfg.moe_num_experts > 0:
            from ...moe.layer import MoEConfig, init_moe_params
            moe_cfg = MoEConfig(num_experts=cfg.moe_num_experts,
                                top_k=cfg.moe_top_k,
                                activation=cfg.activation)
            one = init_moe_params(moe_cfg, cfg.hidden_size,
                                  cfg.intermediate_size, jax.random.key(1))
            if cfg.scan_layers:
                p["layers"]["mlp"] = jax.tree.map(
                    lambda x: T.meta.Partitioned(
                        jax.numpy.broadcast_to(
                            x.value, (cfg.num_layers,) + x.value.shape),
                        names=("layers",) + x.names),
                    one,
                    is_leaf=lambda x: isinstance(x, T.meta.Partitioned))
            else:
                for i in range(cfg.num_layers):
                    p["layers"][f"layer_{i}"]["mlp"] = one
        return p

    abstract = jax.eval_shape(ref_tree)
    names: Dict[Tuple, Tuple] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            abstract,
            is_leaf=lambda x: isinstance(x, T.meta.Partitioned))[0]:
        if isinstance(leaf, T.meta.Partitioned):
            key = tuple(getattr(p, "key", getattr(p, "idx", None))
                        for p in path)
            names[key] = tuple(leaf.names)

    def box(path, leaf):
        key = tuple(getattr(p, "key", getattr(p, "idx", None))
                    for p in path)
        nm = names.get(key)
        if nm is not None and len(nm) == getattr(leaf, "ndim", -1):
            return T.meta.Partitioned(leaf, names=nm)
        return leaf

    return jax.tree_util.tree_map_with_path(box, params)


class RaggedInferenceModel:
    """Stateless compiled step over (params, kv, batch arrays)."""

    def __init__(self, cfg: T.TransformerConfig, params: Any,
                 kv_config: Optional[KVCacheConfig] = None,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 mlp_fn: Optional[Callable] = None,
                 attention_impl: Optional[str] = None,
                 window_kv_config: Optional[KVCacheConfig] = None):
        self.cfg = cfg
        self.mesh = mesh
        if mlp_fn is None and cfg.moe_num_experts > 0:
            # self-wire the routed MoE mlp (mixtral): drop_tokens=False —
            # inference must not zero out capacity-overflow tokens
            from ...moe.layer import MoEConfig, moe_forward
            moe_cfg = MoEConfig(num_experts=cfg.moe_num_experts,
                                top_k=cfg.moe_top_k,
                                activation=cfg.activation,
                                drop_tokens=False)

            def mlp_fn(c, p, x, _moe=moe_cfg):
                return moe_forward(_moe, p, x, is_training=False)
        self.mlp_fn = mlp_fn
        # implementations chosen through the registry/heuristics seam
        # (reference heuristics.instantiate_attention); attention_impl
        # pins a named implementation, None lets the heuristic pick
        from .modules import instantiate
        self._norm_impl = instantiate("norm", cfg)
        self._norm = self._norm_impl
        self._embed = instantiate("embedding", cfg)
        self.kv_config_explicit = kv_config is not None
        # what the attention kind declares of its cache: K and V by
        # head, or one latent plane a token
        self.kv_config = kv_config or (KVCacheConfig(
            num_layers=cfg.num_layers, kv_heads=1,
            head_dim=plane_width(cfg.latent_dim), planes=1,
            dtype=cfg.dtype) if cfg.latent_dim else KVCacheConfig(
            num_layers=cfg.num_layers, kv_heads=cfg.kv_heads,
            head_dim=cfg.dims_per_head, dtype=cfg.dtype))
        #: the window group's cache (a model of two attention kinds: its
        #: full layers' K/V in ``kv_config``'s pool, its window layers' in
        #: this one); None: one page group
        self.window_kv_config: Optional[KVCacheConfig] = None
        #: the state pool (a model with state-space or delta-rule layers:
        #: a slot a sequence instead of pages, of the shape the kind
        #: declares; ``ops/ssm.py``, ``ops/delta_rule.py``); None: every
        #: layer kind caches pages.  The engine sizes ``num_slots``
        self.state_config: Optional[StatePoolConfig] = None
        # layers by what their kind caches (cache_kinds.py): a page
        # group's name, or "slot"
        layers = collections.Counter(
            "slot" if CACHE_KINDS[kind].slot else CACHE_KINDS[kind].group
            for kind in cfg.layer_kinds)
        if layers:
            assert layers["full"], "a model of kinds has full layers"
            self.kv_config = dataclasses.replace(
                self.kv_config, num_layers=layers["full"])
        if layers["slot"]:
            kind = slot_kind(cfg.layer_kinds)
            state, tail = CACHE_KINDS[kind].slot_shape(cfg)
            self.state_config = StatePoolConfig(
                num_layers=layers["slot"], state=state, tail=tail,
                kind=kind, state_dtype=cfg.ssm_state_dtype,
                conv_dtype=cfg.dtype)
        if layers["window"]:
            self.window_kv_config = window_kv_config or dataclasses.replace(
                self.kv_config, num_layers=layers["window"])
        #: the attention modules, held ONE way: by layer kind (a model of
        #: one kind holds ``{"full": ...}``).  ``_kind_cfg``: what each
        #: kind's were built from (beside a window group the full kind
        #: has no window, and each kind its own head count and kernel
        #: name); ``_attention``: the paged module; ``_fresh_attention``:
        #: the one for a pure prefill (None: ALiBi has none).  The latent
        #: kind's cache plane and modules are its own (mla_attention.py)
        self._kind_cfg: Dict[str, T.TransformerConfig] = {}
        self._attention = {"latent": mla_paged_attention}
        self._fresh_attention = {"latent": mla_fresh_attention}
        if not cfg.latent_dim:
            heads = dict(cfg.heads_by_kind)
            self._kind_cfg = {
                kind: cfg if not layers["window"] else dataclasses.replace(
                    cfg, num_heads=heads[kind], sliding_window=(
                        cfg.sliding_window if CACHE_KINDS[kind].windowed
                        else None))
                for kind in ("full", "window")
                if kind == "full" or layers[kind]}
            self._attention = {
                kind: instantiate("ragged_attention", kc, name=attention_impl)
                for kind, kc in self._kind_cfg.items()}
            self._fresh_attention = {}
            for kind, kc in self._kind_cfg.items():
                try:
                    self._fresh_attention[kind] = instantiate(
                        "fresh_prefill_attention", kc)
                except (KeyError, ValueError):
                    self._fresh_attention[kind] = None
        #: which mesh axis shards heads/ffn/vocab (and the KV head dim):
        #: the serving ``tp`` axis when present, else the training-side
        #: ``tensor`` axis.  None until a mesh is applied.
        self._tp_axis: Optional[str] = None
        #: cross-shard logits collective encoding (ISSUE 18): "none" =
        #: the fp all-gather GSPMD derives from the vocab-sharded lm
        #: head (tokenwise identical to tp=1), "int8" = block-scaled
        #: codes + one fp32 scale per row per shard assembled inside
        #: the compiled program via shard_map.  Set by the engine from
        #: ``serving.tp_collective_quantization`` BEFORE any precompile
        #: (it changes the traced programs, like ``keyed_sampling``).
        self.tp_collective_quantization = "none"
        if mesh is None and T._has_boxes(params):
            params = T.meta.unbox(params)
        self.params = params
        if mesh is not None:
            self.mesh = None        # apply_mesh owns the assignment
            self.apply_mesh(mesh)
        self._step_cache: Dict[StepKey, Callable] = {}
        #: schedule-invariant sampling (ISSUE 13): when True every
        #: sampling-capable step kind takes two extra [S] int32 inputs
        #: (row uid, generation position) and draws each row's token
        #: from a key derived ONLY from (base key, uid, position) —
        #: sampled output becomes independent of batch composition and
        #: step count, which is what lets a disaggregated prefill ->
        #: decode handoff (or a migration) continue a sampled request
        #: tokenwise identical to the fused single-engine run.  Set by
        #: the engine from ``serving.keyed_sampling`` BEFORE any
        #: precompile — it changes the traced program signatures, so it
        #: is an engine-build-time fact, not a per-step toggle.
        self.keyed_sampling = False
        #: the bucket lattice (ISSUE 14): the engine sets the one it
        #: serves under (mined tops from ``serving.lattice =
        #: "auto:<path>"``, else this power-of-two default), and the
        #: mixed step's traced-in token-vector pad below buckets with
        #: it.  Engine-build-time, like ``keyed_sampling``: it shapes
        #: the compiled program set.
        self.lattice = POWER_LATTICE
        #: model-drafted speculation (ISSUE 17): the draft trunk's
        #: config + param tree, set by the engine BEFORE any precompile
        #: (like ``keyed_sampling`` — they shape the traced "draft_spec"
        #: / "draft_fill" program signatures).  The draft is the SAME
        #: family at fewer layers (``spec_draft_layers``; 0 = the
        #: self-draft degenerate case sharing every target layer), so
        #: ``draft_params`` shares the target's arrays — embed, final
        #: norm and lm head are always shared, layer trees are slices
        #: (scan-stacked) or per-layer references.  None/None = no
        #: draft model built.
        self.draft_cfg = None
        self.draft_params = None
        # -- per-program cost accounting (ISSUE 9): flops/bytes from
        # compiled.cost_analysis() per step-cache key, accumulated per
        # dispatch so serving throughput gets a hardware denominator
        # (ds_fastgen_program_flops / ds_fastgen_mfu)
        self._program_costs: Dict[tuple, Dict[str, float]] = {}
        #: every step-cache key traffic actually DISPATCHED (vs merely
        #: precompiled) — the compiled-key manifest snapshot bundles
        #: and replica factories carry (ISSUE 14): a restored/spawned
        #: engine precompiles exactly these, not the whole lattice
        self._dispatched_keys: set = set()
        #: per step-cache key, how many times its program runs a trunk
        #: (the most any one weight stack is streamed: 1 for every kind),
        #: counted while the program is traced on the forming thread
        self._trunk_passes: Dict[StepKey, int] = {}
        self._forming = threading.local()
        self._last_key: Optional[StepKey] = None
        self._flops_dispatched = 0.0
        self._bytes_dispatched = 0.0
        self._cost_t0: Optional[float] = None
        self._cost_gauges_bound = False

    # -- weight-only quantization ------------------------------------------
    def quantize_weights(self, fmt: str = "fp8_e4m3") -> None:
        """Quantize the per-layer projection weights channelwise into
        ``fmt`` storage (reference inference v2 core_ops quantized GEMM,
        FP6/FP8): HBM traffic per decode step halves (fp8) or better;
        dequant fuses into each einsum's operand feed via
        models/transformer._wval.  Norm scales, biases, embeddings and
        the lm head stay full precision (quality-critical, small).

        Rewrites ``self.params`` (callers sharing the model object see
        quantized weights); idempotent for the same ``fmt``, raises on a
        format change."""
        from ...ops.fp_quantizer import (SUPPORTED_FORMATS,
                                         quantize_channelwise)
        if fmt not in SUPPORTED_FORMATS:
            raise ValueError(f"unknown quantization format {fmt!r} "
                             f"(supported: {sorted(SUPPORTED_FORMATS)})")
        if self.cfg.latent_dim or self.cfg.layer_kinds:
            raise ValueError(
                "weight-only quantization does not cover the latent-"
                "attention / held-experts / two-kind / state-space "
                "blocks yet")
        prior = getattr(self, "_quantized_fmt", None)
        if prior is not None:
            if prior != fmt:
                raise ValueError(
                    f"model already quantized as {prior!r}; cannot "
                    f"re-quantize as {fmt!r}")
            return

        def q_block(block, batch_dims, per_leaf=False):
            """``per_leaf``: every leading dim beyond the [in, out]
            matrix gets its own scales — MoE expert weights
            [layers?, experts, in, out] must not share one absmax
            across experts (one outlier expert would coarsen all)."""
            out = {}
            for k2, v in block.items():
                if (k2.startswith("w") and hasattr(v, "ndim")
                        and v.ndim >= 2 + batch_dims):
                    bd = v.ndim - 2 if per_leaf else batch_dims
                    out[k2] = quantize_channelwise(v, fmt, batch_dims=bd)
                else:
                    out[k2] = v
            return out

        layers = self.params["layers"]
        if isinstance(layers, dict) and "attn" in layers:   # scan-stacked
            # leading layers dim gets per-layer scales
            layers = dict(layers, attn=q_block(layers["attn"], 1),
                          mlp=q_block(layers["mlp"], 1, per_leaf=True))
        else:                                               # per-layer
            layers = {k2: dict(lp, attn=q_block(lp["attn"], 0),
                               mlp=q_block(lp["mlp"], 0, per_leaf=True))
                      for k2, lp in layers.items()}
        self.params = dict(self.params, layers=layers)
        self._quantized_fmt = fmt
        self._step_cache.clear()
        self._program_costs.clear()   # quantized programs re-cost

    # -- tensor-parallel sharding (ISSUE 18) -------------------------------
    def apply_mesh(self, mesh: jax.sharding.Mesh) -> None:
        """Shard this model's params onto ``mesh`` along its ``tp``
        (serving) or ``tensor`` (training) axis: heads/ffn/vocab over
        the axis (the AutoTP analogue — reference
        module_inject/auto_tp.py slices Linears row/col; GSPMD derives
        the same split + collectives from these specs).  Logical axes
        come from the Partitioned boxes the model init attached; an
        unboxed tree (HF import, or a model built without a mesh) is
        re-boxed from the family's own init first.  Engine-build-time:
        call BEFORE ``quantize_weights`` (quantized leaves carry no
        logical axes) and before any precompile — the step cache is
        cleared because every compiled program changes."""
        axis = next((a for a in ("tp", "tensor") if a in mesh.axis_names),
                    None)
        if axis is None:
            raise ValueError(
                f"mesh axes {mesh.axis_names} have no 'tp' or 'tensor' "
                "axis to shard the serving program over")
        if getattr(self, "_quantized_fmt", None) is not None:
            raise ValueError(
                "apply_mesh must run before quantize_weights — "
                "quantized leaves carry no logical-axis metadata")
        params = self.params
        if not T._has_boxes(params):
            # HF-imported trees are unboxed; recover the logical axes
            # from the family's own init so AutoTP actually shards
            params = _rebox_from_cfg(self.cfg, params)
        from ...runtime.zero.partitioner import logical_to_mesh_spec
        rules = {"heads": axis, "kv": axis, "mlp": axis,
                 "vocab": axis, "expert": "expert"}

        def _shard(leaf):
            if isinstance(leaf, T.meta.Partitioned):
                spec = logical_to_mesh_spec(tuple(leaf.names), rules)
                # drop axes absent from this mesh (a tp-only serving
                # mesh has no 'expert' axis) or not dividing the dim
                # (reference AutoTP keeps indivisible modules
                # unsharded)
                entries = []
                for i, entry in enumerate(spec):
                    axes = (entry if isinstance(entry, tuple)
                            else (entry,)) if entry else ()
                    axes = tuple(a for a in axes
                                 if a in mesh.axis_names)
                    size = 1
                    for a in axes:
                        size *= mesh.shape[a]
                    ok = axes and leaf.value.shape[i] % size == 0
                    entries.append(
                        (axes if len(axes) > 1 else axes[0])
                        if ok else None)
                return jax.device_put(
                    leaf.value,
                    jax.sharding.NamedSharding(mesh, P(*entries)))
            return jax.device_put(
                leaf, jax.sharding.NamedSharding(mesh, P()))

        self.params = jax.tree.map(
            _shard, params,
            is_leaf=lambda x: isinstance(x, T.meta.Partitioned))
        self.mesh = mesh
        self._tp_axis = axis
        # the norm module is a Pallas custom call on a TPU, which GSPMD
        # refuses to partition even over replicated operands: run it as
        # a fully-replicated manual region (activations and norm params
        # are replicated under tp)
        from ...utils.jax_compat import shard_map
        self._norm = shard_map(self._norm_impl, mesh=mesh,
                               in_specs=(P(), P()), out_specs=P(),
                               check_vma=False)
        cache = getattr(self, "_step_cache", None)
        if cache:
            cache.clear()
            self._program_costs.clear()   # sharded programs re-cost

    @property
    def tp_degree(self) -> int:
        """Size of the tensor-parallel axis (1 = unsharded)."""
        if self.mesh is None or self._tp_axis is None:
            return 1
        return int(self.mesh.shape[self._tp_axis])

    def _tp_quant_active(self) -> bool:
        """Whether the int8 block-scaled logits collective replaces the
        fp all-gather: needs a mesh, the int8 encoding selected, and a
        vocab the axis divides (an indivisible vocab stays replicated,
        so there is no collective to quantize)."""
        return (self.mesh is not None and self._tp_axis is not None
                and self.tp_collective_quantization == "int8"
                and self.tp_degree > 1
                and self.cfg.vocab_size % self.tp_degree == 0)

    # -- sharding of the KV cache ------------------------------------------
    def kv_sharding(self) -> Optional[jax.sharding.Sharding]:
        if self.mesh is None:
            return None
        # [L, pages, 2, K, page, D]: partition kv heads over the tp
        # axis — each shard's page slab holds only its head slice,
        # while page ids/tables (host-side int32) stay replicated, so
        # the allocator/prefix-cache/tiering view is shard-invariant
        axis = self._tp_axis
        if axis is not None and self.kv_config.kv_heads % max(
                self.mesh.shape.get(axis, 1), 1) == 0:
            return jax.sharding.NamedSharding(
                self.mesh, P(None, None, None, axis, None, None))
        return jax.sharding.NamedSharding(self.mesh, P())

    # -- forward ------------------------------------------------------------
    @property
    def has_fresh(self) -> bool:
        """Whether pure-prefill batches have their own attention path
        (without one the key's fresh flag is inert: ALiBi)."""
        return any(self._fresh_attention.values())

    # dslint: hot-path
    def run_step(self, key: StepKey, kv, batches: Sequence[RaggedBatch],
                 sampling: Optional[tuple] = None,
                 prev: Optional[tuple] = None, span=None):
        """Run the step program of ``key``: the one call every dispatch
        makes.  ``kv`` is the pool (or pair) of the kind's trunk, donated;
        ``batches`` the key's segments in order; ``prev`` a chain key's
        ``(prev_tokens, gather_idx)``, which take the token ids' place;
        ``sampling`` a sampling kind's ``(rng, temps, top_ks, top_ps,
        row_uids, row_pos)``, the last two read only under
        ``keyed_sampling``.  Callers that never sample a row the host
        reads (padding, mid-prefill) may pass anything for it — its draw
        is garbage nobody consumes.  Returns what the program returns:
        ``(output, new kv)``, or the new pool alone where the kind has
        no output (``STEP_KINDS``).  ``span``: the caller's open
        ``engine.dispatch`` span; a live one is told ``prepare_ms`` (its
        start to the executable's call) and ``call_ms`` (the call: h2d of
        the host arrays and the enqueue), a dead one costs no clock."""
        step = self._get_step(key)
        operands = []
        for b in batches:
            operands += (b.token_ids, b.q_lens, b.start_pos, b.page_table)
        if prev is not None:
            operands[:1] = (prev[0], jnp.asarray(prev[1], jnp.int32))
        if sampling is not None:
            rng, temps, top_ks, top_ps, row_uids, row_pos = sampling
            operands += (rng, jnp.asarray(temps, jnp.float32),
                         jnp.asarray(top_ks, jnp.int32),
                         jnp.asarray(top_ps, jnp.float32))
            if self.keyed_sampling:
                if row_uids is None or row_pos is None:
                    raise ValueError(
                        "keyed_sampling model requires row_uids/row_pos "
                        "for every sampling-capable step")
                operands += (jnp.asarray(row_uids, jnp.int32),
                             jnp.asarray(row_pos, jnp.int32))
        params = trunk_params(self, STEP_KINDS[key.kind].trunk)
        if span is None or not span.live:
            return step(params, kv, *operands)
        called = time.perf_counter()
        out = step(params, kv, *operands)
        span.set("prepare_ms", (called - span.t0) * 1e3)
        span.set("call_ms", (time.perf_counter() - called) * 1e3)
        return out

    @property
    def table(self) -> TableLayout:
        """The columns of this model's segment tables, from what its
        layer kinds cache (``ragged/cache_kinds.py``)."""
        return TableLayout.of(self.cfg.layer_kinds or ("full",),
                              self.cfg.sliding_window,
                              self.kv_config.page_size)

    @property
    def pool_names(self) -> Tuple[str, ...]:
        """What the engine hands a step program as ``kv``, in its order
        (``engine._pool``), each by what it is: the one page group's pool
        ("pages": K/V by head, or the latent plane), then where the model
        has them the window group's pool, then the state pool's two
        arrays.  A mixer names the pools it writes by these names
        (:data:`MIXERS`)."""
        return ("pages",) \
            + (("window",) if self.window_kv_config is not None else ()) \
            + (("state", "conv") if self.state_config is not None else ())

    @property
    def last_trunk_passes(self) -> int:
        """Trunk passes of the newest dispatch's program (the
        ``fastgen.step`` span's ``trunk_passes``)."""
        return self._trunk_passes.get(self._last_key, 0)

    @property
    def last_program(self) -> str:
        """Kind of the newest dispatch's program (the ``fastgen.step``
        span's ``program``)."""
        return self._last_key.kind if self._last_key is not None else ""

    @property
    def step_tail(self) -> int:
        """int32 counts a sampled-token vector carries past its rows: a
        model with held experts appends (token-expert pairs that fell to
        experts held here, summed over the routed layers; the fullest
        held expert's pairs in one layer; held experts with a pair, summed
        over the routed layers), so the counts ride the step's one d2h.
        A mixed step's are those of its one pass over both segments'
        tokens.  The chain key's ``prev_len`` stays the row bucket
        (``step_key.step_avals`` adds the tail)."""
        return 3 if self.cfg.n_routed_experts else 0

    def _get_step(self, key) -> Callable:
        """The executable of ``key`` (a :class:`StepKey`, or its bare
        tuple), forming it on the request path where it is missing."""
        if type(key) is not StepKey:
            key = StepKey.parse(key)
        if not self.has_fresh and key.fresh:
            # no fresh-prefill implementation (ALiBi): the flag is inert,
            # so normalize the cache key to the False variant the
            # precompiled lattice contains (direct callers may hand us a
            # batch built without fresh_supported=False)
            key = key.with_fresh(False)
        fn = self._step_cache.get(key)
        if fn is None:
            # recompile accounting (ISSUE 5): a miss here IS the
            # request path — either a strict-shapes refusal or an XLA
            # compile eaten as a TTFT spike.  The watchdog counts both
            # and warns on recompile storms, naming the uncovered key.
            if getattr(self, "strict_shapes", False):
                get_watchdog().note_step_cache(hit=False, key=key)
                raise RuntimeError(
                    f"batch bucket {key} (S, Q, P, fresh[, kind, ...]) "
                    "was not precompiled — live serving would eat this "
                    "XLA compile as a TTFT spike.  Widen "
                    "InferenceEngineV2.precompile(...) (sampling=True "
                    "covers the fused sample/chain variants) or disable "
                    "strict_shapes.")
            get_watchdog().note_step_cache(hit=False, key=key,
                                           compiled_on_path=True)

            # AOT-compile at the first call (the caller's concrete args
            # ARE this key's avals — shapes are fully determined by the
            # key) instead of caching a lazily-compiling jit wrapper:
            # identical executable, but the COMPILED object is in hand,
            # so on-path compiles feed the same cost_analysis()
            # accounting as the precompiled lattice (ISSUE 9)
            def compile_on_call(*args, _key=key):
                return self._form_program(_key, args, run=True)

            self._step_cache[key] = compile_on_call
            fn = compile_on_call
        else:
            get_watchdog().note_step_cache(hit=True)
        self._account_dispatch(key)
        return fn

    def _form_program(self, key, args, run: bool = False):
        """Trace, lower and compile the step program of ``key`` for
        ``args`` (arrays or avals) and put the executable into the step
        cache: the one place a program forms, on the request path
        (``run``: the waiting call is made here too and its result
        returned) or ahead of it.  Every phase is a span, written
        whether or not telemetry is on: a formation costs seconds and
        set-up, where most of them happen, runs with telemetry off."""
        tracer = get_tracer()
        with tracer.span("engine.program",
                         {"key": key, "on_path": run}) as prog:
            before = thread_cache_counts()
            with tracer.span("engine.program.trace"):
                self._forming.passes = {}
                traced = jax.jit(step_program(self, key),
                                 donate_argnums=(1,)).trace(*args)
                self._trunk_passes[key] = max(
                    self._forming.passes.values(), default=0)
                del self._forming.passes
            with tracer.span("engine.program.lower"):
                lowered = traced.lower()
            with tracer.span("engine.program.compile") as comp:
                compiled = lowered.compile()
                after = thread_cache_counts()
                # XLA compiled it, or the persistent cache held it
                cache = ("hit" if after["hits"] > before["hits"] else
                         "miss" if after["misses"] > before["misses"]
                         else "off")
                comp.set("cache", cache)
            prog.set("cache", cache)
            with tracer.span("engine.program.cost"):
                self._note_program_cost(key, compiled)
            self._step_cache[key] = compiled
            if not run:
                return None
            # _get_step already accounted this dispatch, but the cost
            # was unknown then — bill it now so on-path and precompiled
            # keys agree from dispatch 1
            self._account_cost(key)
            with tracer.span("engine.program.first_run"):
                return compiled(*args)

    # -- per-program cost / MFU accounting (ISSUE 9) -------------------------
    def _note_program_cost(self, key, compiled) -> None:
        """Capture flops / bytes-accessed of one compiled executable
        (post-fusion HLO, the flops_profiler convention).  Best-effort:
        a backend without cost_analysis leaves the key unaccounted."""
        try:
            cost = compiled.cost_analysis() or {}
        except Exception:
            return
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        self._program_costs[key] = {
            "flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
        }

    def _account_dispatch(self, key) -> None:
        """One program dispatch of ``key`` (every forward/sample/chain/
        mixed call funnels through ``_get_step`` exactly once): feed the
        workload trace's key-occupancy summary and the cost window
        behind the ds_fastgen_program_flops / _mfu gauges.  Always-on
        (ServingCounters convention): a dict lookup + float adds."""
        self._dispatched_keys.add(key)
        self._last_key = key
        wt = get_workload_trace()
        if wt.active:
            wt.note_step_key(key)
        self._account_tp_collective(key)
        self._account_cost(key)

    def _account_tp_collective(self, key) -> None:
        """Analytic interconnect accounting for the logits collective
        (host-side adds — nothing touches the device).  Wire bytes are
        what each shard RECEIVES, summed over shards: fp all-gather
        moves N*V*(tp-1) fp32 entries; the int8 encoding moves the
        same entries as 1-byte codes plus one fp32 scale per row per
        remote shard.  The fp32-equivalent counter is always fed, so
        ``collective_bytes / collective_fp_bytes`` reads as the
        encoding's compression ratio."""
        tp = self.tp_degree
        if tp <= 1:
            return
        n = STEP_KINDS[key.kind].logits_rows(key)
        if not n:
            return
        v = int(self.cfg.vocab_size)
        fp_bytes = n * v * (tp - 1) * 4
        if self._tp_quant_active():
            wire = n * v * (tp - 1) + n * tp * (tp - 1) * 4
        else:
            wire = fp_bytes
        tm.FASTGEN_SHARD_COLLECTIVE_BYTES.inc(wire)
        tm.FASTGEN_SHARD_COLLECTIVE_FP_BYTES.inc(fp_bytes)

    def _account_cost(self, key) -> None:
        cost = self._program_costs.get(key)
        if cost is None:
            return
        if self._cost_t0 is None:
            self._cost_t0 = time.perf_counter()
        self._flops_dispatched += cost["flops"]
        self._bytes_dispatched += cost["bytes"]
        tm.FASTGEN_PROGRAM_FLOPS.set(cost["flops"])
        tm.FASTGEN_PROGRAM_BYTES.set(cost["bytes"])
        if not self._cost_gauges_bound:
            self._bind_cost_gauges()

    def _bind_cost_gauges(self) -> None:
        """Bind the rate gauges once costs exist.  Wall-relative (like
        ds_train_goodput_ratio): the window opens at the first costed
        dispatch and reading long after serving stopped dilutes the
        rate — ``reset_cost_window()`` re-opens it for a measured
        window.  Weakref: the registry must not keep a discarded model
        (and its params) alive."""
        self._cost_gauges_bound = True
        import weakref
        ref = weakref.ref(self)
        peak = serving_peak_flops()

        def rate(attr, scale=1.0):
            def _read(r=ref, a=attr, s=scale):
                m = r()
                if m is None or m._cost_t0 is None or not s:
                    return 0.0    # not s: no published peak, no MFU
                wall = max(time.perf_counter() - m._cost_t0, 1e-9)
                return getattr(m, a) / wall / s
            return _read

        tm.FASTGEN_MFU.bind(rate("_flops_dispatched", peak))
        tm.FASTGEN_BYTES_PER_S.bind(rate("_bytes_dispatched"))
        # per-shard view (ISSUE 18): cost_analysis() reports the whole
        # logical program; each of the tp shards executes 1/tp of it
        # against ONE device's peak, so the per-shard gauges divide the
        # dispatched totals by the mesh degree (tp=1 ⇒ they read the
        # same as the global pair)
        tp = float(max(self.tp_degree, 1))
        tm.FASTGEN_SHARD_MFU.bind(
            rate("_flops_dispatched", peak and peak * tp))
        tm.FASTGEN_SHARD_BYTES_PER_S.bind(
            rate("_bytes_dispatched", tp))

    def reset_cost_window(self) -> None:
        """Re-open the MFU/bytes-per-s window (bench measured-window
        control); the per-key cost table survives."""
        self._flops_dispatched = 0.0
        self._bytes_dispatched = 0.0
        self._cost_t0 = None

    def cost_summary(self) -> Dict[str, Any]:
        """Per-program cost table + window totals — the serving
        analogue of the training flops profiler's report."""
        wall = (max(time.perf_counter() - self._cost_t0, 1e-9)
                if self._cost_t0 is not None else 0.0)
        peak = serving_peak_flops()
        return {
            "programs": {repr(k): dict(v)
                         for k, v in self._program_costs.items()},
            "flops_dispatched": self._flops_dispatched,
            "bytes_dispatched": self._bytes_dispatched,
            "window_s": wall,
            "peak_flops": peak,
            "mfu": (utilization(self._flops_dispatched / wall, peak)
                    if wall else 0.0),
            "bytes_per_s": (self._bytes_dispatched / wall if wall
                            else 0.0),
        }

    def precompile_step(self, key: Sequence, kv_aval) -> None:
        """AOT-compile one (S, Q, P, fresh[, kind, ...]) bucket
        (reference: FastGen's CUDA graphs are captured at engine build;
        under XLA the analogue is lower().compile() before serving so no
        bucket compiles on the request path).  ``ValueError`` for a key
        that names no program."""
        key = StepKey.parse(key)
        if key in self._step_cache:
            return
        # the COMPILED executable goes into the cache: later calls with
        # the bucket's exact shapes dispatch straight to it (jit's own
        # dispatch cache is not populated by AOT lowering)
        self._form_program(key, step_avals(self, key, kv_aval))

    def compiled_programs(self) -> Dict[tuple, Any]:
        """Step-cache key -> compiled executable, for inspection
        (``as_text()`` shows a program's kernels and collectives)."""
        return dict(self._step_cache)

    def _lm_head(self, params):
        cfg = self.cfg
        return (params["embed"]["tokens"].astype(cfg.dtype).T
                if cfg.tie_embeddings
                else params["lm_head"].astype(cfg.dtype))

    # dslint: hot-path
    def _assemble_logits(self, x2d, lm_head, bias=None):
        """[N, E] hidden rows -> [N, V] fp32 logits, replicated on
        every shard.  Unsharded (or ``tp_collective_quantization =
        "none"``): a plain matmul — under a mesh the vocab-sharded lm
        head leaves the product sharded on V and GSPMD inserts the fp
        all-gather where sampling forces replication, tokenwise
        identical to tp=1.  "int8": the gather is taken over explicitly
        via shard_map — each shard computes its [N, V/tp] slice in
        fp32, encodes it as block-scaled int8 (one symmetric fp32
        scale per row per shard, the PR 1/PR 16 quantizer idiom:
        scale = max|x| / 127), all-gathers codes + scales (~4x fewer
        interconnect bytes than fp32), and decodes — every shard
        reconstructs the same [N, V] array, so sampling stays
        shard-deterministic.  Numeric contract: each row's per-shard
        max round-trips exactly; any other entry moves by at most
        scale/2, so argmax is preserved whenever the top-1 margin
        exceeds half the largest per-shard quantization step (see
        DESIGN.md "Sharded serving").  Bias lands after assembly
        (replicated, [V]-small)."""
        cfg = self.cfg
        if not self._tp_quant_active():
            logits = jnp.einsum("ne,ev->nv", x2d, lm_head)
            if bias is not None:
                logits = logits + bias.astype(cfg.dtype)
            return logits.astype(jnp.float32)
        from ...utils.jax_compat import shard_map
        mesh, axis = self.mesh, self._tp_axis

        def local(xl, wl):
            # wl: this shard's [E, V/tp] vocab slice (contiguous —
            # shard i holds columns [i*V/tp, (i+1)*V/tp))
            part = jnp.einsum("ne,ev->nv", xl, wl).astype(jnp.float32)
            scale = jnp.max(jnp.abs(part), axis=-1) / 127.0      # [N]
            codes = jnp.clip(
                jnp.round(part / jnp.maximum(scale, 1e-30)[:, None]),
                -127, 127).astype(jnp.int8)
            codes = jax.lax.all_gather(codes, axis)    # [tp, N, V/tp]
            scales = jax.lax.all_gather(scale, axis)   # [tp, N]
            full = codes.astype(jnp.float32) * scales[:, :, None]
            # shard order along dim 0 IS vocab-slice order: interleave
            # back to one contiguous [N, V]
            return jnp.moveaxis(full, 0, 1).reshape(xl.shape[0], -1)

        logits = shard_map(local, mesh=mesh,
                           in_specs=(P(), P(None, axis)),
                           out_specs=P(), check_vma=False)(
            x2d.astype(cfg.dtype), lm_head)
        if bias is not None:
            logits = logits + bias.astype(jnp.float32)
        return logits

    def _forward_hidden(self, params, kv, segments: Sequence[Segment],
                        cfg=None, stats_out: Optional[list] = None):
        """The ONE trunk of every step kind and every family: embed -> the
        layers (:meth:`_layer_loop`) -> final norm, ONE pass of the weights
        over all tokens of all ``segments`` (:func:`_end_to_end`); only
        the cache write and attention (or the recurrence) run segment by
        segment, inside each layer.  ``kv`` is what the engine hands in,
        in its order: one pool, or (full group's pool, window group's),
        or (page pool, the state pool's ``h``, its ``conv``).  Returns (x,
        new kv), ``x`` in :func:`_end_to_end`'s layout ([S, Q, E] for one
        segment): the step kinds differ only in which positions they
        unembed (each row's last for the logits/sample kinds, EVERY one
        for the spec verify).  ``cfg`` overrides the trunk geometry (the
        DRAFT trunk of model-drafted speculation: same family and modules,
        fewer layers); None = the target.  ``stats_out``: a list that
        receives the held-experts counts (:attr:`step_tail`) of the pass."""
        cfg = cfg if cfg is not None else self.cfg
        passes = getattr(self._forming, "passes", None)
        if passes is not None:          # a program is being traced
            passes[id(cfg)] = passes.get(id(cfg), 0) + 1
        x = self._embed(params["embed"]["tokens"].astype(cfg.dtype),
                        _end_to_end([seg.token_ids for seg in segments]))
        pos = _positions(segments)
        if cfg.pos_emb == "learned":
            safe = jnp.minimum(pos, cfg.max_seq_len - 1)
            x = x + params["embed"]["positions"].astype(cfg.dtype)[safe]
        if cfg.embed_layernorm:  # BLOOM word_embeddings_layernorm
            x = self._norm(params["embed"]["norm"], x)
        pools = kv if type(kv) is tuple else (kv,)
        places = {name: i for i, name in enumerate(self.pool_names)}
        # the page group of each attending kind (the latent plane lies in
        # the one page group)
        group_of = {kind: CACHE_KINDS[kind].group if kind in CACHE_KINDS
                    else "full" for kind in dict.fromkeys(T.layer_kinds(cfg))
                    if MIXERS[kind].weights == "attn"}
        valid = _end_to_end([_true_positions(seg) for seg in segments]
                            ).reshape(-1) if self.step_tail else None
        # each segment's wide table (ragged/batch.py) taken apart ONCE
        # into what each cache kind's layers read
        by_group: Dict[str, List[Segment]] = {k: [] for k in group_of}
        rows = []
        for seg in segments:
            parts = self.table.split(seg.page_table, seg.token_ids.shape[1])
            for kind, group in group_of.items():
                start = seg.start_pos
                if group == "window":
                    # the window group's short table starts at the page of
                    # absolute index ``base``, so its rows are REBASED by
                    # ``base`` pages: the cache write and attention use
                    # positions only to find a token's slot and to mask,
                    # which depend on differences of positions alone (the
                    # rope is applied before, from the absolute positions)
                    start = start - parts["base"] * self.kv_config.page_size
                by_group[kind].append(
                    seg._replace(page_table=parts[group], start_pos=start))
            if "slot" in parts:     # the pool's last slot is the scratch one
                rows.append((
                    jnp.clip(parts["slot"], 0,
                             pools[places["state"]].shape[1] - 1),
                    seg.start_pos == 0, _true_positions(seg)))
        ropes = {kind: self.rope_table(cfg, kind, pos) for kind in group_of
                 } if cfg.pos_emb == "rope" else {}
        ctx = Pass(cfg, by_group, rows, ropes, valid, places)
        # the pools are the loop's CARRY beside the activations (and the
        # held-experts counts, last): as scanned xs/ys a pool would be
        # sliced out and stacked back, two layer-sized copies a layer and
        # a pool-sized one after the loop
        carry = (x, *pools)
        if self.step_tail:
            carry += (jnp.zeros((3,), jnp.int32),)
        x, *pools = self._layer_loop(carry, params, ctx)
        if self.step_tail:
            stats = pools.pop()
            if stats_out is not None:
                stats_out.append(stats)
        return (self._norm(params["final_norm"], x),
                tuple(pools) if type(kv) is tuple else pools[0])

    def rope_table(self, cfg, kind: str, positions):
        """(sin, cos) of the ``kind`` layers' rope, as ``T.apply_rope``
        takes them.  A family with ropes of its own names them in its
        class (``model_implementations.py``)."""
        if kind == "latent":            # a latent head's rotated dims alone
            cfg = dataclasses.replace(cfg, head_dim=cfg.qk_rope_head_dim,
                                      rope_pct=1.0)
        return T.rope_table(cfg, positions)

    def _layer_loop(self, carry, params, ctx: Pass):
        """The ONE loop over the layers, along the layer pattern
        (``transformer.layer_runs``): the leading layers that stand
        outside it, ONE scan over its whole periods whose body is the
        period's runs of like layers, the tail unrolled; a family of one
        kind is a pattern of period 1.  A layer writes and reads its own
        kind's pools at its index among the layers of its kind, a routed
        one the held experts' stack at its index past the leading layers.
        The weights stay where the family's ``init_params`` puts them
        (the layouts: ``docs/DESIGN.md``, "A layer kind is an entry"),
        and the tree says, read ONCE below, which of two ways reaches a
        layer's: (a) it is handed them, a tree of its own or the scan's
        OPERAND, stacked over the periods; (b) ``layers`` is ``{kind: the
        kind's flat stack}``: the scans run over a COUNTER and take a
        layer out by index, as a scan takes its operand's (a stack of
        periods scanned as an operand would have each period's run sliced
        out whole), a run of several a scan of its own (7 Mamba, the
        attention layer, 6 Mamba: a program holds two Mamba bodies and
        one attention body, not fourteen); or (c) ``runs`` is ``{r<j>:
        run j's layers, stacked}``: every layer behind the leading ones in
        maximal runs of like kinds, a run of several ONE scan over its
        stack as the operand (a family that holds ONE period whose layers
        differ in their trees: under (a) it would be a body a layer).
        Which family takes which, and why three: ``docs/DESIGN.md``."""
        cfg, i32 = ctx.cfg, jnp.int32
        kinds = T.layer_kinds(cfg)
        leading, runs, periods, tail = T.layer_runs(cfg)
        period = sum(n for _, n in runs)
        per = collections.Counter(kinds[leading:leading + period])
        start = collections.Counter(kinds[:leading])
        # the tree's layout, read HERE and nowhere below
        layers = params.get("layers") or {}
        flat = layers if layers and set(layers) <= set(MIXERS) else None
        in_runs = params.get("runs")                        # (c)
        if not flat:    # (a): every layer of a period under its own name
            runs = [(kind, 1) for kind, n in runs for _ in range(n)]
        stacks = {} if flat else params.get("periods") or {"l0": layers}
        dense, lone = params.get("dense_layers", {}), params.get("tail", {})
        # the leading layers are ONE stack (then all of one kind)
        stacked = bool(leading and not flat and "l0" not in dense)
        # the routed layers' held experts are ONE stack, addressed by the
        # kernel through a layer's index: scanned with the layers, each
        # layer's 1.5 GB would be sliced out for the custom call
        experts = params.get("experts")
        moe = stacks.get("l0", {}).get("moe", {})
        if "experts" in moe:
            experts = moe["experts"]
            stacks = {"l0": dict(stacks["l0"], moe={
                k: v for k, v in moe.items() if k != "experts"})}

        # where the layers of period ``p`` stand: ``index`` among their
        # kind's (``met``: the kind's before it in the period), ``routed``
        # among the routed layers, counted from ``first``.  In full ``start[
        # kind] + p * per[kind] + met`` and ``p * period + j`` from 0; each
        # trunk that became this loop left out the terms that were nothing
        # to it, and every program keeps its text (``ROADMAP.md`` D16)
        first = 0
        if periods and period == 1:
            # the scan's counter runs over the kind's layers themselves
            first = start[kinds[leading]]

            def index(kind, p, met):
                return p

            def routed(p, j):
                return p
        else:
            def index(kind, p, met):
                at = p * per[kind]
                return (start[kind] + at if leading else at) + met

            # a routed layer's place among the routed ones of its period
            # (every layer of it, or under ``half_blocks`` the "ffn" ones)
            place = {j: n for n, j in enumerate(
                j for j, (kind, _) in enumerate(runs)
                if kind == "ffn" or not cfg.half_blocks)}

            def routed(p, j):
                return p * len(place) + place[j] \
                    if experts is not None and j in place else None

        def layer(carry, kind, at, lp=None, routed=None):
            """Layer ``at`` of its kind, ``lp`` its weights (a)."""
            if flat:                                        # (b)
                at = i32(at)
                lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
                    a, at, 0, keepdims=False), flat[kind])
            return self._layer_body(carry, lp, kind=kind, at=at, ctx=ctx,
                                    routed=routed, experts=(experts, first))

        def outside(carry, i, lp):
            """Layer ``i``, one that no scan runs over."""
            return layer(carry, kinds[i], kinds[:i].count(kinds[i]), lp,
                         kinds[leading:i].count("ffn") if cfg.half_blocks
                         else i - leading)

        def one_period(carry, xs):
            lps, p = xs
            met = collections.Counter()
            for j, (kind, n) in enumerate(runs):
                at = index(kind, p, met[kind])
                if n > 1:
                    carry, _ = jax.lax.scan(
                        lambda c, m, kind=kind, at=at: (
                            layer(c, kind, at + m), None),
                        carry, jnp.arange(n, dtype=i32))
                else:
                    carry = layer(carry, kind, at, lps.get(f"l{j}"),
                                  routed(p, j))
                met[kind] += n
            return carry, None

        if stacked:     # a scan of its own, whatever pattern stands behind
            carry, _ = jax.lax.scan(        # (``0 +``: the text again)
                lambda c, xs: (layer(c, kinds[0], xs[1], xs[0]), None),
                carry, (dense, 0 + jnp.arange(leading, dtype=i32)))
        else:
            for i in range(leading):
                carry = outside(carry, i, dense.get(f"l{i}"))
        if in_runs is not None:
            # (c): one body a run in the program, not one a layer (a step
            # program of seven bodies was 56 MB of code and never fitted
            # the compile cache)
            at = leading
            for j, (kind, n) in enumerate(T.kind_runs(kinds[leading:])):
                stack, met = in_runs[f"r{j}"], kinds[:at].count(kind)
                if n > 1:
                    carry, _ = jax.lax.scan(
                        lambda c, xs, kind=kind, met=met, at=at: (layer(
                            c, kind, met + xs[1], xs[0],
                            at - leading + xs[1]), None),
                        carry, (stack, jnp.arange(n, dtype=i32)))
                else:
                    carry = layer(carry, kind, met, jax.tree.map(
                        lambda a: a[0], stack), at - leading)
                at += n
            return carry
        counter = jnp.arange(periods, dtype=i32)
        if periods and period == 1 and leading:
            counter = first + counter
        if periods and cfg.scan_layers:
            carry, _ = jax.lax.scan(one_period, carry, (stacks, counter))
        else:
            for p in range(periods):
                carry, _ = one_period(carry, (
                    {"l0": layers[f"layer_{p}"]}, counter[p]))
        for n, i in enumerate(range(cfg.num_layers - tail, cfg.num_layers)):
            carry = outside(carry, i, lone.get(f"l{n}"))
        return carry

    # dslint: hot-path
    def _step_impl(self, params, kv, token_ids, q_lens, start_pos,
                   page_table, fresh: bool = False):
        return self._last_token_logits(
            params, kv, [Segment(token_ids, q_lens, start_pos, page_table,
                                 fresh)])

    def _last_token_logits(self, params, kv, segments: Sequence[Segment],
                           stats_out: Optional[list] = None):
        """The trunk over ``segments``, then fp32 logits of each row's
        last token, the segments' rows in order: (one [rows, V] lm-head
        product, new kv)."""
        x, kv = self._forward_hidden(params, kv, segments,
                                     stats_out=stats_out)
        last = jnp.concatenate(
            [gather_last(xs, seg.q_lens)
             for xs, seg in zip(_per_segment(x, segments), segments)])
        logits = self._assemble_logits(
            last, self._lm_head(params),
            params.get("lm_head_bias"))  # phi family ships one
        return logits, kv

    def _sample_tokens(self, logits, rng, temps, top_ks, top_ps,
                       row_uids, row_pos, greedy_only: bool):
        """The one sampling reduction every sampling-capable step kind
        shares: static greedy specialization, keyed per-row draws when
        ``keyed_sampling`` (row key = f(base, uid, position) — schedule
        invariant), else the step-keyed ``sample_dynamic``."""
        if greedy_only:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if row_uids is not None:
            from .sampling import derive_row_keys, sample_keyed
            keys = derive_row_keys(rng, row_uids, row_pos)
            return sample_keyed(logits, keys, temps, top_ks, top_ps)
        from .sampling import sample_dynamic
        return sample_dynamic(logits, rng, temps, top_ks, top_ps)

    # dslint: hot-path
    def _sample_step_impl(self, params, kv, token_ids, q_lens, start_pos,
                          page_table, rng, temps, top_ks, top_ps,
                          row_uids=None, row_pos=None,
                          fresh: bool = False, greedy_only: bool = False):
        """Forward + on-device sampling in ONE traced program: the [S, V]
        logits never leave the device — only int32 tokens do."""
        return self._sample_last_tokens(
            params, kv, [Segment(token_ids, q_lens, start_pos, page_table,
                                 fresh)],
            rng, temps, top_ks, top_ps, row_uids, row_pos, greedy_only)

    def _sample_last_tokens(self, params, kv, segments, rng, temps, top_ks,
                            top_ps, row_uids, row_pos, greedy_only: bool):
        """One pass of the trunk over ``segments``, one sampling
        reduction over their rows: (tokens [rows, padded to the slot
        bucket where there are several segments] + :attr:`step_tail`,
        new kv)."""
        stats = [] if self.step_tail else None
        logits, kv = self._last_token_logits(params, kv, segments,
                                             stats_out=stats)
        tokens = self._sample_tokens(logits, rng, temps, top_ks, top_ps,
                                     row_uids, row_pos, greedy_only)
        # pad the token vector to the slot bucket: the segments' rows are
        # an arbitrary sum, and a later chained step keys on the EXACT
        # prev-token length — bucketing here collapses the chain-key
        # space back to the lattice's slot tops (one compile, not one
        # per segment-sum); a mined lattice supplies its own tops
        pad = (self.lattice.bucket_s(tokens.shape[0]) - tokens.shape[0]
               if len(segments) > 1 else 0)
        if pad:
            tokens = jnp.concatenate(
                [tokens, jnp.zeros((pad,), jnp.int32)])
        if stats:
            tokens = jnp.concatenate([tokens, stats[0]])
        return tokens, kv

    # dslint: hot-path
    def _chained_step_impl(self, params, kv, prev_tokens, gather_idx,
                           q_lens, start_pos, page_table, rng, temps,
                           top_ks, top_ps, row_uids=None, row_pos=None,
                           greedy_only: bool = False):
        """Decode step whose token ids are gathered on device from the
        previous step's sampled tokens (slot mapping is host-known), so
        consecutive decode steps chain with no host round-trip."""
        token_ids = jnp.take(prev_tokens, gather_idx)[:, None]  # [S, 1]
        return self._sample_step_impl(
            params, kv, token_ids, q_lens, start_pos, page_table, rng,
            temps, top_ks, top_ps, row_uids, row_pos,
            fresh=False, greedy_only=greedy_only)

    # dslint: hot-path
    def _spec_step_impl(self, params, kv, token_ids, q_lens, start_pos,
                        page_table, rng, temps, top_ks, top_ps,
                        row_uids=None, row_pos=None,
                        greedy_only: bool = False):
        """Verify drafted tokens in one traced program.  Row layout:
        ``token_ids[s] = [last_committed, d_1..d_k, pad...]`` with
        ``q_lens[s] = 1 + k`` (k may be 0).  The forward writes KV for
        every valid position (rejected drafts land in pages the next
        step overwrites write-before-read — the chained step's
        optimistic-token discipline, generalized) and emits the model's
        own next token at EVERY position.  Per row: the accepted count
        is the longest prefix of drafts matching the model's emissions
        (greedy: argmax exact-match, so committed tokens are bit-equal
        to non-speculative greedy; stochastic: ``sample_dynamic``'s own
        draw at each position — the emitted token is ALWAYS the model's
        sample, drafts only decide how many positions commit at once),
        plus the correction/bonus token at position ``accepted``.
        Returns [S, 2] int32: (accepted_count, corrected_token)."""
        x, kv = self._forward_hidden(
            params, kv, [Segment(token_ids, q_lens, start_pos, page_table)])
        # EVERY position unembeds (the verify reads all of them) —
        # flattened through the shared assembly so the tp collective
        # (fp or int8) covers the spec kinds too
        Sx, Qx, E = x.shape
        logits = self._assemble_logits(
            x.reshape(Sx * Qx, E), self._lm_head(params),
            params.get("lm_head_bias")).reshape(Sx, Qx, -1)  # [S, Q, V]
        S, Q, V = logits.shape
        if greedy_only:
            emitted = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            # keyed mode: position j of row s emits the token at
            # generation index row_pos[s] + j — fold per position so a
            # spec-committed block is bit-equal to the same tokens
            # drawn one step at a time (the non-spec keyed stream)
            sq_uids = (jnp.repeat(row_uids, Q) if row_uids is not None
                       else None)
            sq_pos = ((row_pos[:, None]
                       + jnp.arange(Q, dtype=jnp.int32)[None, :]
                       ).reshape(-1) if row_uids is not None else None)
            emitted = self._sample_tokens(
                logits.reshape(S * Q, V), rng,
                jnp.repeat(temps, Q), jnp.repeat(top_ks, Q),
                jnp.repeat(top_ps, Q), sq_uids, sq_pos,
                greedy_only=False).reshape(S, Q)
        # accepted = leading run of draft positions whose draft equals
        # the model's emission ONE POSITION EARLIER (emitted[j] is the
        # model's choice for the token AT input position j+1)
        drafts = token_ids[:, 1:]                            # [S, Q-1]
        col = jnp.arange(Q - 1, dtype=jnp.int32)[None, :]
        ok = (emitted[:, :-1] == drafts) & (col < (q_lens - 1)[:, None])
        accepts = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1),
                          axis=1).astype(jnp.int32)          # [S]
        corrected = jnp.take_along_axis(emitted, accepts[:, None],
                                        axis=1)[:, 0]
        return jnp.stack([accepts, corrected], axis=1), kv   # [S, 2]

    # dslint: hot-path
    def _draft_spec_step_impl(self, params, kv, token_ids, q_lens,
                              start_pos, page_table, rng, temps, top_ks,
                              top_ps, row_uids=None, row_pos=None,
                              greedy_only: bool = False):
        """Device-resident model-drafted speculation (ISSUE 17 tentpole):
        ``params = {"target", "draft"}``, ``kv = (target_kv, draft_kv)``
        (donated as one tuple).  The draft loop runs Q iterations of a
        Q=1 draft-trunk forward under ``lax.scan``: iteration j feeds
        the previous emission (iteration 0 feeds ``token_ids[:, 0]``,
        the last committed token) at position ``start_pos + j`` with a
        per-iteration q-len mask ``j < q_lens`` — so a row with
        q_lens = 1+r writes draft KV for ALL r+1 of its input positions
        (the full-accept case leaves the draft pool contiguous through
        the last committed token; rejected positions are overwritten
        write-before-read next step, the same discipline as the target
        pool).  Drafts are always the draft trunk's greedy argmax —
        they are proposals; the VERIFY reduction's emitted tokens
        (target argmax, or keyed/stochastic draws) alone decide what
        commits, which is what makes greedy model-drafted spec
        bit-equal to spec-off and keyed sampling schedule-invariant.
        Returns ([S, 2+k] int32, (target_kv, draft_kv)) with k = Q-1:
        accepted count, corrected token, then the k drafted tokens."""
        target_kv, draft_kv = kv
        dcfg = self.draft_cfg
        dparams = params["draft"]
        S, Q = token_ids.shape
        lm_head = self._lm_head(dparams)
        bias = (dparams["lm_head_bias"].astype(self.cfg.dtype)
                if "lm_head_bias" in dparams else None)

        def draft_iter(carry, j):
            dkv, tok = carry
            qj = jnp.where(j < q_lens, 1, 0).astype(jnp.int32)
            x, dkv = self._forward_hidden(
                dparams, dkv,
                [Segment(tok[:, None], qj, start_pos + j, page_table)],
                cfg=dcfg)
            # shared assembly: the per-iteration [S, V] draft logits
            # ride the same tp collective (fp or int8) as the verify
            logits = self._assemble_logits(x[:, 0, :], lm_head, bias)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (dkv, nxt), nxt

        (draft_kv, _), emitted = jax.lax.scan(
            draft_iter, (draft_kv, token_ids[:, 0]),
            jnp.arange(Q, dtype=jnp.int32))
        # emitted[j] is d_{j+1}; the verify row is [t0, d_1..d_{Q-1}]
        # (iteration Q-1's emission only exists to write d_{Q-1}'s
        # draft KV for the full-accept case — it is discarded)
        tok_mat = jnp.concatenate(
            [token_ids[:, :1], jnp.transpose(emitted[:Q - 1])], axis=1)
        out, target_kv = self._spec_step_impl(
            params["target"], target_kv, tok_mat, q_lens, start_pos,
            page_table, rng, temps, top_ks, top_ps, row_uids, row_pos,
            greedy_only=greedy_only)
        return (jnp.concatenate([out, tok_mat[:, 1:]], axis=1),
                (target_kv, draft_kv))

    # dslint: hot-path
    def _draft_fill_step_impl(self, params, kv, token_ids, q_lens,
                              start_pos, page_table):
        """Draft-trunk-only forward that writes draft KV for the
        batch's positions (``params`` = draft params, ``kv`` = the
        draft pool, donated).  No unembed consumer, no output but the
        pool — the catch-up path moves ZERO bytes device->host."""
        _, kv = self._forward_hidden(
            params, kv, [Segment(token_ids, q_lens, start_pos, page_table)],
            cfg=self.draft_cfg)
        return kv

    # dslint: hot-path
    def _mixed_sample_step_impl(self, params, kv, d_tok, d_ql, d_sp,
                                d_pt, p_tok, p_ql, p_sp, p_pt, rng,
                                temps, top_ks, top_ps,
                                row_uids=None, row_pos=None,
                                fresh_p: bool = False,
                                greedy_only: bool = False):
        """Two-segment fused step, decode rows [S_d, 1] and prefill rows
        [S_p, Q]: ONE pass of the trunk over the tokens of both (distinct
        sequences, so segment order is free), each layer writing and
        attending segment by segment, one lm-head product over the
        S_d + S_p last tokens, sampled once — one compiled program, no
        cross-geometry padding, every weight streamed once."""
        return self._sample_last_tokens(
            params, kv, [Segment(d_tok, d_ql, d_sp, d_pt),
                         Segment(p_tok, p_ql, p_sp, p_pt, fresh_p)],
            rng, temps, top_ks, top_ps, row_uids, row_pos, greedy_only)

    def _layer_body(self, carry, lp, *, kind, at, ctx: Pass, routed,
                    experts):
        """ONE layer of kind ``kind`` over (x, the pools in the engine's
        order, the held-experts counts where the model has them), the same
        out: the kind's mixer (:data:`MIXERS`) at layer ``at`` of its pools,
        then the feed-forward (``routed``: the layer's place among the routed
        ones, from the number ``experts`` comes with; HERE is where a router
        reads), each behind a norm of its input (``post_norm``: its output).
        Under ``cfg.half_blocks`` a layer is ONE of the two
        (:meth:`_half_block`)."""
        cfg = ctx.cfg
        x, *rest = carry
        mixer = MIXERS[kind]
        if cfg.half_blocks:
            return self._half_block(x, rest, lp, mixer, kind=kind, at=at,
                                    ctx=ctx, routed=routed, experts=experts)
        h = x if cfg.post_norm else self._norm(lp["norm1"], x)
        plan = (self._route(lp, h, ctx, layout=True) if "moe" in lp
                and cfg.router_reads == "mixer" else None)
        out = self._mix(mixer, h, rest, lp, at, kind, ctx)
        if cfg.sandwich_norm:
            out = self._norm(lp["norm1_post"], out)
        if cfg.post_norm:
            out = self._norm(lp["norm1"], out.astype(x.dtype))
        feed = functools.partial(
            self._feed_forward, lp, ctx=ctx, routed=routed, experts=experts,
            counts=rest[-1] if self.step_tail else None, plan=plan)
        if cfg.parallel_residual:
            mlp_out, counts = feed(self._norm(lp["norm2"], x))
            x = x + out.astype(x.dtype) + mlp_out.astype(x.dtype)
        else:
            x = x + out.astype(x.dtype)
            out, counts = feed(
                x if cfg.post_norm else self._norm(lp["norm2"], x))
            if cfg.sandwich_norm:
                out = self._norm(lp["norm2_post"], out.astype(x.dtype))
            if cfg.post_norm:
                out = self._norm(lp["norm2"], out.astype(x.dtype))
            x = x + out.astype(x.dtype)
        if counts is not None:
            rest[-1] = counts
        return (x, *rest)

    def _feed_forward(self, lp, h, *, ctx, routed, experts, counts, plan):
        """A layer's feed-forward: the dense block (or the self-wired
        ``mlp_fn``, its MoE aux dropped), or the routed layer's held share
        (``moe/held.py``; ``plan``: :meth:`_route`'s from before the mixer)
        plus the shared expert, the pass's counts: (output, counts)."""
        cfg = ctx.cfg
        if "moe" not in lp:
            out = (self.mlp_fn or T._mlp_block)(cfg, lp["mlp"], h)
            return (out[0] if isinstance(out, tuple) else out), counts
        mp, (stack, first) = lp["moe"], experts
        S, Q, E = h.shape
        h2 = h.reshape(S * Q, E)
        # routed here, unless the router read the mixer's input (``plan``)
        chosen, weights, rows = plan or self._route(lp, h2, ctx)
        out, pairs = held.held_experts_ffn(
            h2, chosen, weights, stack, cfg.experts_first, plan=rows,
            layer=routed - first if first else routed, valid=ctx.valid,
            act=cfg.expert_act, tile=cfg.moe_row_tile)
        out = out.reshape(S, Q, E)
        if "shared" in mp:
            out = out + T._mlp_block(cfg, mp["shared"], h)
        return out, jnp.stack([counts[0] + jnp.sum(pairs),
                               jnp.maximum(counts[1], jnp.max(pairs)),
                               counts[2] + jnp.sum(pairs > 0)])

    def _kv_mixer(self, h, pools, ap, layer, *, kind, ctx: Pass):
        """K/V attention of ``h`` (all tokens of the segments) over
        ``pool[layer]``, the "full" and the "window" kind alike: the
        projections once over all tokens (with the biases, the
        whole-width Q/K norm and the kind's rope the configuration has),
        the cache write and the kernel segment by segment, each head's
        output through its sigmoid gate under ``cfg.head_gate``."""
        cfg, kc, (pool,) = ctx.cfg, self._kind_cfg[kind], pools
        dtype = cfg.dtype
        folded = getattr(ap["wq"], "ndim", 3) == 2
        q, k, v = (_qkv_folded if folded else _qkv_by_axis)(
            h, ap, cfg, ctx.ropes.get(kind), self._norm)
        write = self._per_shard_heads(_write_new_kv, kc, 2, pool_out=True)
        # pure prefill: every slot's context IS its own new tokens: flash
        # over [S(batch), H, Q, D], no paged gather (reference
        # blocked_flash prefill atoms); padding-tail rows are garbage but
        # only feed rows that logits_gather ignores and KV slots the null
        # page swallows
        fresh = self._fresh_attention[kind] and self._per_shard_heads(
            self._fresh_attention[kind], kc, 3)
        paged = self._per_shard_heads(self._attention[kind], kc, 1)
        attn, pool = _write_then_attend(
            ctx.by_group[kind], (q, k, v), pool,
            lambda pool, seg, qs, ks, vs: write(
                ks, vs, pool, layer, seg.page_table, seg.start_pos,
                seg.q_lens),
            fresh,
            lambda pool, seg, qs, ks, vs: paged(
                qs, pool, layer, seg.page_table, seg.start_pos, seg.q_lens))
        if cfg.head_gate:
            gate = jax.nn.sigmoid(jnp.einsum(
                "sqe,eh->sqh", h, ap["wgate"].astype(dtype),
                preferred_element_type=jnp.float32))
            attn = (attn.astype(jnp.float32) * gate[..., None]).astype(dtype)
        if folded:
            return jnp.einsum("sqf,fe->sqe",
                              attn.reshape(attn.shape[:2] + (-1,)),
                              ap["wo"].astype(dtype)), (pool,)
        out = jnp.einsum("sqhd,hde->sqe", attn, T._wval(ap["wo"], dtype))
        if cfg.use_bias:
            out = out + ap["bo"].astype(dtype)
        return out, (pool,)

    def _latent_mixer(self, h, pools, ap, layer, *, kind, ctx: Pass):
        """Latent attention of ``h`` (all tokens of the segments): the
        projections once over all of them, then each segment writes its
        new tokens' ``[c ; k_r]`` planes into ``pool[layer]`` and
        attends — expanded (192-wide scores, 128-wide values) for a pure
        prefill, absorbed over the paged planes otherwise."""
        cfg, (kv,), (sin, cos) = ctx.cfg, pools, ctx.ropes[kind]
        dtype = cfg.dtype
        dn, rkv = cfg.qk_nope_head_dim, cfg.kv_lora_rank
        scale = float(dn + cfg.qk_rope_head_dim) ** -0.5
        if "wq_a" in ap:
            cq = self._norm(ap["q_norm"], jnp.einsum(
                "sqe,er->sqr", h, ap["wq_a"].astype(dtype)))
            q = jnp.einsum("sqr,rhd->sqhd", cq, ap["wq_b"].astype(dtype))
        else:       # no low-rank query (``q_lora_rank`` null): no norm
            q = jnp.einsum("sqe,ehd->sqhd", h, ap["wq"].astype(dtype))
        q_n, q_r = q[..., :dn], T.apply_rope(q[..., dn:], sin, cos)
        ckr = jnp.einsum("sqe,er->sqr", h, ap["wkv_a"].astype(dtype))
        c = self._norm(ap["kv_norm"], ckr[..., :rkv])
        k_r = T.apply_rope(ckr[:, :, None, rkv:], sin, cos)[:, :, 0]
        S, Q = h.shape[:2]
        pad = kv.shape[-1] - rkv - k_r.shape[-1]
        plane = jnp.concatenate([c, k_r, jnp.zeros((S, Q, pad), dtype)], -1)
        w_k, w_v = ap["wkv_b_k"].astype(dtype), ap["wkv_b_v"].astype(dtype)

        def fresh(plane_s, cs, k_rs, q_ns, q_rs):
            k_n = jnp.einsum("sqr,rhd->sqhd", cs, w_k)
            k = jnp.concatenate([k_n, jnp.broadcast_to(
                k_rs[:, :, None, :], k_n.shape[:3] + k_rs.shape[-1:])], -1)
            return self._fresh_attention[kind](
                jnp.concatenate([q_ns, q_rs], -1), k,
                jnp.einsum("sqr,rhd->sqhd", cs, w_v), sm_scale=scale)

        def paged(kv, seg, plane_s, cs, k_rs, q_ns, q_rs):
            q_abs = jnp.concatenate(
                [jnp.einsum("sqhd,rhd->sqhr", q_ns, w_k), q_rs,
                 jnp.zeros(q_rs.shape[:3] + (pad,), dtype)], -1)
            ctx_ = self._attention[kind](
                q_abs, kv, layer, seg.page_table, seg.start_pos, seg.q_lens,
                rank=rkv, sm_scale=scale)
            return jnp.einsum("sqhr,rhd->sqhd", ctx_, w_v)

        out, kv = _write_then_attend(
            ctx.by_group[kind], (plane, c, k_r, q_n, q_r), kv,
            lambda kv, seg, plane_s, *_: latent_write(
                kv, layer, plane_s, seg.page_table, seg.start_pos,
                seg.q_lens), fresh, paged)
        return jnp.einsum("sqhd,hde->sqe", out, ap["wo"].astype(dtype)), (kv,)

    def _ssm_mixer(self, u, pools, mp, layer, *, kind, ctx: Pass):
        """The Mamba mixer of ``u`` (all tokens of the segments): its four
        projections once over all of them; the convolution and the
        recurrence segment by segment, each row from and to its slot of
        ``pool[layer]`` (``ops/ssm.py``).  Returns (output in ``u``'s
        layout, (h pool, conv pool))."""
        cfg, segments, rows, (h_pool, conv_pool) = (
            ctx.cfg, ctx.by_group["full"], ctx.rows, pools)
        dtype, f32 = cfg.dtype, jnp.float32
        d, n, r = cfg.ssm_inner, cfg.ssm_state_dim, cfg.ssm_dt_rank
        xz = jnp.einsum("sqe,ef->sqf", u, mp["w_in"].astype(dtype))
        x, z = xz[..., :d], xz[..., d:]
        conv, tails = [], []
        for xs, seg, (slots, fresh, _) in zip(
                _per_segment(x, segments), segments, rows):
            out, conv_pool, tail = conv_step(
                conv_pool, layer, slots, fresh, seg.q_lens, xs,
                mp["conv_w"], mp["conv_b"])
            conv.append(jax.nn.silu(out))
            tails.append(tail)
        x = _end_to_end(conv)                               # float32
        dbc = jnp.einsum("sqd,rd->sqr", x.astype(dtype),
                         mp["w_x"].astype(dtype),
                         preferred_element_type=f32)

        def rms(a, gain):
            return a * jax.lax.rsqrt(
                jnp.mean(a * a, -1, keepdims=True) + cfg.norm_eps) \
                * gain["scale"].astype(f32)

        dt = rms(dbc[..., :r], mp["dt_norm"])
        B = rms(dbc[..., r:r + n], mp["b_norm"])
        C = rms(dbc[..., r + n:], mp["c_norm"])
        dt = jax.nn.softplus(jnp.einsum(
            "sqr,rd->sqd", dt.astype(dtype), mp["w_dt"].astype(dtype),
            preferred_element_type=f32) + mp["b_dt"].astype(f32))
        A_t = -jnp.exp(mp["A_log_t"].astype(f32))
        ys = []
        for (slots, fresh, valid), tail, dts, xs, Bs, Cs in zip(
                rows, tails, *(_per_segment(a, segments)
                               for a in (dt, x, B, C))):
            # a padded position moves nothing: exp(0) = 1, dt x = 0
            y, h_pool, conv_pool = ssm_scan(
                h_pool, conv_pool, layer, slots, fresh,
                jnp.where(valid[..., None], dts, 0.0), xs, Bs, Cs, A_t,
                mp["D"], tail)
            ys.append(y)
        y = _end_to_end(ys) * jax.nn.silu(z.astype(f32))
        return jnp.einsum("sqd,de->sqe", y.astype(dtype),
                          mp["w_out"].astype(dtype)), (h_pool, conv_pool)

    def _delta_mixer(self, u, pools, mp, layer, *, kind, ctx: Pass):
        """The gated delta-rule mixer of ``u`` (all tokens of the
        segments): its projections once over all of them; the
        convolution over q, k and v and the recurrence segment by segment,
        each row from and to its slot of ``pool[layer]``
        (``ops/delta_rule.py``; the convolution is ``ops/ssm.py``'s).
        Returns (output in ``u``'s layout, (state pool, conv pool))."""
        cfg, dtype, f32 = ctx.cfg, ctx.cfg.dtype, jnp.float32
        H = cfg.delta_heads
        qkv = jnp.einsum("sqe,ef->sqf", u, mp["w_qkv"].astype(dtype))
        gate = jnp.einsum("sqe,ef->sqf", u, mp["w_gate"].astype(dtype))
        ab = jnp.einsum("sqe,fe->sqf", u, mp["w_ab"].astype(dtype),
                        preferred_element_type=f32)
        # log alpha and beta a head
        g = -jnp.exp(mp["A_log"].astype(f32)) * jax.nn.softplus(
            ab[..., :H] + mp["dt_bias"].astype(f32))
        beta = jax.nn.sigmoid(ab[..., H:]) \
            * (2.0 if cfg.delta_neg_eigval else 1.0)
        y, s_pool, conv_pool = self._delta_recurrence(
            qkv, g, beta, pools, mp, layer, ctx)
        y = y.reshape(gate.shape) * jax.nn.silu(gate.astype(f32))
        return jnp.einsum("sqd,de->sqe", y.astype(dtype),
                          mp["w_out"].astype(dtype)), (s_pool, conv_pool)

    def _per_shard_heads(self, fn, cfg, n_head_args: int,
                         pool_out: bool = False):
        """Run a cache op or an attention module per tp shard over its
        own head slice.

        Attention and the cache write are independent per KV head, and on
        a TPU they are Pallas custom calls — which GSPMD cannot partition
        (it would all-gather the KV pages onto every chip, every layer;
        the write's scatter indexes the head dim, which GSPMD may answer
        the same way).  Under a tp mesh ``fn`` is therefore
        ``shard_map``-ped over the axis: its first ``n_head_args``
        arguments are ``[S, Q, heads, D]`` activations split on heads, the
        KV pool (payload and int8 scale alike) is split on its KV-head
        dim, and the layer index and the host-built int32 batch vectors
        are replicated.  The result is head-split activations, or with
        ``pool_out`` the pool.  Head h = k * G + g, so contiguous head
        shards line up with contiguous KV-head shards.  ALiBi slopes are a
        per-head constant the attention modules close over, so attention
        of those models (and everything of head counts the axis does not
        divide) stays on the GSPMD path."""
        axis = self._tp_axis
        if self.mesh is None or axis is None:
            return fn
        tp = self.mesh.shape[axis]
        if tp == 1 or cfg.kv_heads % tp or cfg.num_heads % tp:
            return fn
        if cfg.pos_emb == "alibi" and not pool_out:
            return fn
        from ...utils.jax_compat import shard_map
        heads = P(None, None, axis, None)
        pool = P(None, None, None, axis, None, None)    # [L,P+1,2,K,page,D]

        def spec_of(i, arg):
            if i < n_head_args:
                return heads
            if isinstance(arg, KVPages):
                return KVPages(pool, P(*pool[:-1]))
            return pool if arg.ndim == 6 else P()

        def run(*args):
            specs = tuple(spec_of(i, a) for i, a in enumerate(args))
            out = specs[n_head_args] if pool_out else heads
            return shard_map(fn, mesh=self.mesh, in_specs=specs,
                             out_specs=out, check_vma=False)(*args)
        return run

    # -- KV requirements (engine contract) ----------------------------------
    def get_kv_requirements(self, seen_tokens: int, allocated_pages: int,
                            max_new_tokens: int, max_new_pages: int
                            ) -> Tuple[int, int]:
        """(tokens schedulable, pages needed) given page headroom —
        reference ``DSTransformerModelBase.get_kv_requirements``."""
        page = self.kv_config.page_size
        capacity = allocated_pages * page - seen_tokens
        if max_new_tokens <= capacity:
            return max_new_tokens, 0
        need = -(-(max_new_tokens - capacity) // page)
        if need <= max_new_pages:
            return max_new_tokens, need
        tokens = capacity + max_new_pages * page
        return max(tokens, 0), max_new_pages

    def decode_page_group(self, page_slots: int, kind: str = "full") -> int:
        """Page slots a grid step of the paged K/V kernel holds for a
        decode row of a ``kind`` layer over a table of ``page_slots``
        (``ops/paged_attention.py::kernel_blocks`` at the shapes of the
        call a tp shard makes): the group the step span's page-slot
        counts are taken at (``engine.take_slots_held``).  (Down here:
        lines added above a kernel's call move the callers' line numbers
        that Mosaic writes into every step program.)"""
        from ...ops.paged_attention import kernel_blocks
        cfg = self._kind_cfg[kind]
        kv = self.window_kv_config if kind == "window" else self.kv_config
        alibi = cfg.pos_emb == "alibi"
        tp = self.tp_degree
        if alibi or cfg.kv_heads % tp or cfg.num_heads % tp:
            tp = 1                  # not split by head: _per_shard_heads
        itemsize = jnp.dtype(cfg.dtype).itemsize
        return kernel_blocks(
            cfg.num_heads // cfg.kv_heads, kv.kv_heads // tp, kv.head_dim,
            kv.page_size, page_slots, itemsize,
            1 if kv.quantized else jnp.dtype(kv.dtype).itemsize,
            kv.quantized, alibi)[1]

    def _route(self, lp, h, ctx: Pass, layout: bool = False):
        """The routing of ``h`` ([S, Q, E], or its tokens [T, E]) over all
        experts of layer ``lp``: (experts [T, k], weights [T, k], rows).
        ``layout``: ``rows`` is the held experts' row layout
        (``moe/held.py::plan_rows``), made HERE so that nothing of the
        routing waits for what runs before the feed-forward (a router that
        reads the mixer's input: ``_layer_body``); else None, and
        ``held_experts_ffn`` makes its own, as the programs that route
        behind the mixer always had it."""
        cfg = ctx.cfg
        chosen, weights = held.ROUTERS[cfg.router_scoring](
            h if h.ndim == 2 else h.reshape(-1, h.shape[-1]),
            lp["moe"]["router"], cfg.moe_top_k, cfg.routed_scaling_factor,
            cfg.norm_topk_prob, **(dict(
                bias=lp["moe"]["router_bias"], groups=cfg.router_groups,
                keep=cfg.router_topk_groups) if cfg.router_groups else {}))
        rows = held.plan_rows(chosen, ctx.valid, cfg.experts_first,
                              cfg.held_experts, cfg.n_routed_experts,
                              cfg.moe_row_tile) if layout else None
        return chosen, weights, rows


    def _half_block(self, x, rest, lp, mixer, *, kind, at, ctx: Pass, routed,
                    experts):
        """:meth:`_layer_body` for a model whose layers are ONE sub-layer
        each (``cfg.half_blocks``): ``x + sub(norm(x))``, ``sub`` the kind's
        mixer, or for a kind that has none (``MIXERS[kind].run`` None: it
        caches nothing) the feed-forward.  One norm, one residual: the
        other half is not traced."""
        h = self._norm(lp["norm1"], x)
        if mixer.run is None:
            out, counts = self._feed_forward(
                lp, h, ctx=ctx, routed=routed, experts=experts,
                counts=rest[-1] if self.step_tail else None, plan=None)
            if counts is not None:
                rest[-1] = counts
        else:
            out = self._mix(mixer, h, rest, lp, at, kind, ctx)
        return (x + out.astype(x.dtype), *rest)

    def _mix(self, mixer, h, rest, lp, at, kind, ctx: Pass):
        """``mixer`` over ``h`` at layer ``at`` of the pools it names, which
        it finds in ``rest`` (the carry behind ``x``) and leaves there as
        written; returns its output."""
        held_at = [ctx.places[name] for name in mixer.pools]
        out, written = mixer.run(
            self, h, [rest[i] for i in held_at], lp[mixer.weights], at,
            kind=kind, ctx=ctx)
        for i, pool in zip(held_at, written):
            rest[i] = pool
        return out

    def _delta_recurrence(self, qkv, g, beta, pools, mp, layer, ctx: Pass):
        """What the delta-rule mixers share behind their projections: the
        convolution over q, k and v and the recurrence segment by segment,
        each row from and to its slot of ``pool[layer]``
        (``ops/delta_rule.py`` under ``g``: one decay a head ``[.., H]`` or
        a key channel ``[.., H, dk]``; the convolution is ``ops/ssm.py``'s),
        then the norm over each head's values.  Returns (y ``[.., H, dv]``
        float32, state pool, conv pool)."""
        cfg, rows, (s_pool, conv_pool) = ctx.cfg, ctx.rows, pools
        # the segments under any page group's table: rows and lengths
        segments = next(iter(ctx.by_group.values()))
        f32 = jnp.float32
        H, dk, dv = cfg.delta_heads, cfg.delta_key_dim, cfg.delta_value_dim

        def l2(a):
            return a * jax.lax.rsqrt(
                jnp.sum(a * a, -1, keepdims=True) + 1e-6)

        outs = []
        for (slots, fresh, valid), seg, xs, gs, bs in zip(
                rows, segments, *(_per_segment(a, segments)
                                  for a in (qkv, g, beta))):
            conv, conv_pool, tail = conv_step(
                conv_pool, layer, slots, fresh, seg.q_lens, xs,
                mp["conv_w"])
            conv = jax.nn.silu(conv)                        # float32
            by_head = conv.shape[:2] + (H, -1)
            q = l2(conv[..., :H * dk].reshape(by_head)) * dk ** -0.5
            k = l2(conv[..., H * dk:2 * H * dk].reshape(by_head))
            # a padded position moves nothing: alpha = 1, beta = 0
            o, s_pool, conv_pool = delta_rule(
                s_pool, conv_pool, layer, slots, fresh, q, k,
                conv[..., 2 * H * dk:],
                jnp.where(valid[(...,) + (None,) * (gs.ndim - 2)], gs, 0.0),
                jnp.where(valid[..., None], bs, 0.0), tail)
            outs.append(o)
        o = _end_to_end(outs)
        o = o.reshape(o.shape[:2] + (H, dv))
        return o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                                 + cfg.norm_eps) \
            * mp["o_norm"]["scale"].astype(f32), s_pool, conv_pool

    def _kda_mixer(self, u, pools, mp, layer, *, kind, ctx: Pass):
        """The Kimi-delta (KDA) mixer of ``u`` (all tokens of the
        segments): :meth:`_delta_mixer`'s shape with ONE DECAY A KEY
        CHANNEL, ``g = lower * sigmoid(exp(A_log) (W_f u + dt_bias))`` in
        ``(lower, 0)`` (the bounded gate), ``beta = sigmoid``, and one
        sigmoid gate a head on the normed output; the recurrence is
        ``ops/delta_rule.py``'s under ``g`` ``[.., H, dk]``.  Returns
        (output in ``u``'s layout, (state pool, conv pool))."""
        cfg, dtype, f32 = ctx.cfg, ctx.cfg.dtype, jnp.float32
        H, dk = cfg.delta_heads, cfg.delta_key_dim
        qkv = jnp.einsum("sqe,ef->sqf", u, mp["w_qkv"].astype(dtype))
        f = jnp.einsum("sqe,fe->sqf", u, mp["w_f"].astype(dtype),
                       preferred_element_type=f32) + mp["dt_bias"].astype(f32)
        bg = jnp.einsum("sqe,fe->sqf", u, mp["w_bg"].astype(dtype),
                        preferred_element_type=f32)
        g = cfg.kda_lower_bound * jax.nn.sigmoid(
            jnp.exp(mp["A_log"].astype(f32))[:, None]
            * f.reshape(f.shape[:2] + (H, dk)))
        beta, gate = jax.nn.sigmoid(bg[..., :H]), jax.nn.sigmoid(bg[..., H:])

        y, s_pool, conv_pool = self._delta_recurrence(
            qkv, g, beta, pools, mp, layer, ctx)
        y = y * gate[..., None]
        return jnp.einsum("sqd,de->sqe",
                          y.reshape(y.shape[:2] + (-1,)).astype(dtype),
                          mp["w_out"].astype(dtype)), (s_pool, conv_pool)

    def _ssd_mixer(self, u, pools, mp, layer, *, kind, ctx: Pass):
        """The Mamba-2 mixer of ``u`` (all tokens of the segments): its ONE
        in projection (``[z | x B C | dt]``) once over all of them; the
        convolution over x, B and C and the recurrence segment by segment,
        each row from and to its slot of ``pool[layer]`` (``ops/ssm.py::
        ssd_scan``); then the gate BEFORE the norm, ``y silu(z)``, an
        RMSNorm over each of the ``ssm_groups`` groups of channels under a
        gain over all of them, and the output projection.  Returns (output
        in ``u``'s layout, (h pool, conv pool))."""
        cfg, rows, (h_pool, conv_pool) = ctx.cfg, ctx.rows, pools
        segments = next(iter(ctx.by_group.values()))
        dtype, f32 = cfg.dtype, jnp.float32
        d, H, G = cfg.ssm_inner, cfg.ssm_heads, cfg.ssm_groups
        gn = G * cfg.ssm_state_dim
        zxd = jnp.einsum("sqe,ef->sqf", u, mp["w_in"].astype(dtype),
                         preferred_element_type=f32)
        z, xbc = zxd[..., :d], zxd[..., d:2 * d + 2 * gn]
        dt = jax.nn.softplus(zxd[..., 2 * d + 2 * gn:]
                             + mp["dt_bias"].astype(f32))
        A = -jnp.exp(mp["A_log"].astype(f32))
        ys = []
        for (slots, fresh, valid), seg, xs, dts in zip(
                rows, segments, *(_per_segment(a, segments)
                                  for a in (xbc, dt))):
            conv, conv_pool, tail = conv_step(
                conv_pool, layer, slots, fresh, seg.q_lens, xs,
                mp["conv_w"], mp["conv_b"])
            conv = jax.nn.silu(conv)                        # float32
            # a padded position moves nothing: exp(0) = 1, dt x = 0
            y, h_pool, conv_pool = ssd_scan(
                h_pool, conv_pool, layer, slots, fresh,
                jnp.where(valid[..., None], dts, 0.0), conv[..., :d],
                conv[..., d:d + gn], conv[..., d + gn:], A, mp["D"], tail)
            ys.append(y)
        y = _end_to_end(ys) * jax.nn.silu(z)
        y = y.reshape(y.shape[:2] + (G, d // G))
        y = (y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                               + cfg.norm_eps)).reshape(z.shape) \
            * mp["norm"]["scale"].astype(f32)
        return jnp.einsum("sqd,de->sqe", y.astype(dtype),
                          mp["w_out"].astype(dtype)), (h_pool, conv_pool)


#: a layer kind is an entry here, one in ``cache_kinds.py::CACHE_KINDS``
#: (what it caches) and its kernel
MIXERS: Dict[str, Mixer] = {
    "full": Mixer(RaggedInferenceModel._kv_mixer, "attn", ("pages",)),
    "window": Mixer(RaggedInferenceModel._kv_mixer, "attn", ("window",)),
    "latent": Mixer(RaggedInferenceModel._latent_mixer, "attn", ("pages",)),
    "ssm": Mixer(RaggedInferenceModel._ssm_mixer, "mixer",
                 ("state", "conv")),
    "delta": Mixer(RaggedInferenceModel._delta_mixer, "mixer",
                   ("state", "conv")),
    "kda": Mixer(RaggedInferenceModel._kda_mixer, "mixer",
                 ("state", "conv")),
    "ssd": Mixer(RaggedInferenceModel._ssd_mixer, "mixer",
                 ("state", "conv")),
    # a feed-forward alone (``cfg.half_blocks``): no mixer, no pool
    "ffn": Mixer(None, "", ()),
}
