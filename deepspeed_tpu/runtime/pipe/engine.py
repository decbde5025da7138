"""PipelineEngine — pipeline-parallel training (reference
``runtime/pipe/engine.py:56`` ``PipelineEngine``).

TPU-native redesign.  The reference executes a 1F1B instruction stream
(schedule.py) with host-dispatched p2p sends/recvs per micro-batch.  Under
XLA the entire pipelined step is ONE compiled program:

  reference                               here
  ---------                               ----
  per-instruction host dispatch           ``lax.scan`` over pipeline ticks
  p2p.send/recv (NCCL) + tensor-meta      ``lax.ppermute`` over the 'pipe'
  handshake (engine.py:939)               mesh axis (static shapes: no
                                          handshake needed)
  explicit BackwardPass instructions +    JAX AD through the scan+ppermute
  grad buffer management                  (transpose of ppermute is the
                                          reverse-direction ppermute — the
                                          backward pipeline comes out of
                                          the chain rule)
  PipelineModule layer partitioning       stage-stacked params: the layer
  onto ranks (module.py:387)              dim [L,...] reshaped to
                                          [S, L/S, ...], S sharded on
                                          'pipe' via shard_map
  activation-checkpointed stages          ``jax.checkpoint`` on the stage
  (module.py:340 exec_range_func)         body (saves only stage I/O)

Memory/throughput model: both schedules run T = M + S - 1 ticks (bubble
fraction (S-1)/T).  The default "1f1b" schedule fuses embedding into
stage 0 and loss into the last stage, so neither the [M, b, s, e]
embedding/output buffers nor any full-batch logits ever materialize —
the role the reference's 1F1B ``TrainSchedule`` (schedule.py:189) plays
for activation memory.  MEASURED (compiled temp buffers, llama-debug,
pipe=2 x data=4): 2.2x below the "gpipe" stack-outputs schedule at M=8
and 3.1x at M=16 (tests/test_pipeline.py
test_1f1b_schedule_uses_less_memory_than_gpipe keeps the ordering
honest).  Tensor/sequence/ZeRO axes stay in GSPMD "auto" mode inside
the loop, so one program composes PP with TP/SP/DP/ZeRO shardings.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax.core import meta
from jax.sharding import PartitionSpec as P

from ...models import transformer as tfm
from ...accelerator import on_tpu
from ...utils.jax_compat import shard_map as _compat_shard_map
from ...utils.logging import log_dist
from ..engine import DeepSpeedEngine
from .module import PipelineModule

PIPE_AXIS = "pipe"


# ---------------------------------------------------------------------------
# the SPMD pipeline loop
# ---------------------------------------------------------------------------

def gpipe_spmd(mesh,
               num_stages: int,
               stage_fn: Callable,
               stage_params: Any,
               x: jax.Array,
               consts: Any = (),
               remat: bool = True,
               first_fn: Optional[Callable] = None,
               last_fn: Optional[Callable] = None,
               edge_params: Any = None,
               stage_aux: bool = False,
               consts_batched: Any = None) -> Any:
    """Differentiable pipelined map over the 'pipe' mesh axis.

    ``stage_params`` leaves carry a leading stage dim (global size S,
    sharded over 'pipe').  ``x``: [M, ...mb shape...] micro-batched input,
    replicated over 'pipe' (sharded over data axes in auto mode).
    ``stage_fn(local_stage_params, activation, consts, mb_id) ->
    activation`` must be shape-preserving; ``mb_id`` is the micro-batch
    index this stage is processing at the current tick (for indexing
    per-micro-batch consts such as attention masks).

    Two output modes:

    * **stack** (``last_fn=None``): returns last-stage outputs [M, ...],
      replicated over 'pipe' — the GPipe formulation; the full [M, ...]
      buffer threads through the scan carry.
    * **reduce** (``last_fn`` given): ``last_fn(out, consts, mb_id)``
      runs at the LAST stage as each micro-batch completes and its pytree
      result is SUMMED over micro-batches — the memory-bounded schedule
      (reference ``TrainSchedule`` 1F1B, runtime/pipe/schedule.py:189,
      exists to bound in-flight activations to O(stages); here the same
      bound comes from never materializing the [M, ...] output buffer or
      any full-batch logits — the carry holds one boundary activation
      plus scalar accumulators, and remat re-derives the rest).

    ``first_fn(edge_params, inp_mb, consts, mb_id)`` optionally maps the
    raw stage-0 input (e.g. token ids) to the activation shape, so the
    [M, ...] pipeline input can stay narrow (ids, not embeddings).

    ``edge_params`` carries the DIFFERENTIABLE leaves first_fn/last_fn
    need (embedding table, final norm, lm head).  Everything the region
    touches must enter through arguments — shard_map closure capture of
    sharded arrays clashes with the Manual-mode mesh — and ``consts`` is
    stop-gradiented, so differentiable edge weights get their own slot.

    ``stage_aux``: stage_fn returns ``(activation, aux_scalar)`` and the
    call returns ``(result, aux_total)`` — the MoE gating load-balance
    loss threaded through the pipeline carry (differentiable; only
    active ticks contribute, and the per-stage accumulators are summed
    over 'pipe').  aux_total sums over micro-batches; divide by M for
    the per-forward mean the dense path reports.
    """
    S = num_stages
    if S == 1:
        sp = jax.tree.map(lambda a: a[0], stage_params)
        body = jax.checkpoint(stage_fn) if remat else stage_fn
        M = x.shape[0]

        def one(im):
            mb_id, inp = im
            act = first_fn(edge_params, inp, consts, mb_id) if first_fn else inp
            out = body(sp, act, consts, mb_id)
            aux = jnp.zeros((), jnp.float32)
            if stage_aux:
                out, aux = out
            res = last_fn(edge_params, out, consts, mb_id) if last_fn else out
            return res, aux
        res, auxs = jax.lax.map(one, (jnp.arange(M), x))
        if last_fn:
            res = jax.tree.map(lambda a: a.sum(0), res)
        return (res, auxs.sum()) if stage_aux else res

    param_specs = jax.tree.map(lambda _: P(PIPE_AXIS), stage_params)
    perm = [(i, (i + 1) % S) for i in range(S)]

    # Batch-parallel axes go MANUAL alongside 'pipe' (fully-manual
    # region): differentiating a PARTIAL-auto region hits hard
    # partitioner bugs on this JAX version (scalar-residual _SpecError,
    # unsupported PartitionId), while a
    # fully-manual region differentiates fine.  Leaves of x/consts whose
    # dim 1 is the global micro-batch width shard over these axes; the
    # activation's dim 0 is that batch dim by the first_fn/stage_fn
    # contract.  Tensor/seq axes (if any) stay auto — grad through that
    # combination remains unsupported on this JAX version.
    batch_axes = tuple(a for a in ("data", "expert", "fsdp", "hpz")
                       if mesh.shape.get(a, 1) > 1)
    x0 = jax.tree.leaves(x)[0]
    b_global = x0.shape[1] if x0.ndim >= 2 else None
    n_bshards = int(np.prod([mesh.shape[a] for a in batch_axes])) \
        if batch_axes else 1
    batch_manual = bool(batch_axes) and b_global is not None \
        and b_global % n_bshards == 0
    if not batch_manual:
        batch_axes, n_bshards = (), 1

    def _batched(leaf) -> bool:
        return (batch_manual and np.ndim(leaf) >= 2
                and np.shape(leaf)[1] == b_global)

    # Which consts leaves carry the batch at dim 1?  Callers that know
    # (PipelineEngine.loss) pass ``consts_batched`` explicitly — the
    # dim-1-width heuristic mis-shards any replicated const whose second
    # dim coincidentally equals the micro-batch width (e.g. an [s, s]
    # table with s == b).
    if consts_batched is None:
        consts_flags = jax.tree.map(_batched, consts)
    else:
        consts_flags = jax.tree.map(
            lambda _a, f: bool(f) and batch_manual, consts, consts_batched)

    def _local_sds(a, f):
        """ShapeDtypeStruct with the batch dim localized to one shard."""
        shape = tuple(a.shape)
        if f:
            shape = (shape[0], shape[1] // n_bshards) + shape[2:]
        return jax.ShapeDtypeStruct(shape, a.dtype)

    # shape inference OUTSIDE the Manual-mode region (eval_shape inside
    # shard_map trips on mixed Manual/Auto mesh contexts), on LOCAL
    # (per-batch-shard) micro-batch shapes
    x0_sds = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            _local_sds(a, _batched(a)).shape[1:], a.dtype), x)
    consts_sds = jax.tree.map(_local_sds, consts, consts_flags)
    if first_fn is None:
        act_sds = jax.tree.leaves(x0_sds)[0]
    else:
        act_sds = jax.eval_shape(first_fn, edge_params, x0_sds,
                                 consts_sds, 0)
        act_sds = jax.ShapeDtypeStruct(act_sds.shape, act_sds.dtype)
    acc_sds = (jax.eval_shape(last_fn, edge_params, act_sds, consts_sds, 0)
               if last_fn is not None else None)
    # On XLA-CPU, x and edge_params cross the region boundary in fp32:
    # the shard_map transpose psums the cotangent of a replicated input
    # over 'pipe', and XLA-CPU's all-reduce promotion pass miscompiles
    # sub-fp32 all-reduces.  On TPU the widening is skipped — an fp32
    # copy of the embedding/lm-head per stage would be real HBM.
    widen = not on_tpu()

    def _to_f32(t):
        if not widen:
            return t
        return jax.tree.map(
            lambda a: a.astype(jnp.float32)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, t)

    x_dtypes = jax.tree.map(lambda a: a.dtype, x)
    x_in = _to_f32(x)
    edge_dtypes = jax.tree.map(lambda a: a.dtype, edge_params)
    edge_in = _to_f32(edge_params)
    # Partial-auto shard_map on this JAX version appends the auto axes
    # to every input's dim-0 names, so a RANK-0 leaf trips the spec
    # check (_SpecError on float32[]).  Lift scalars to rank 1 at the
    # boundary and unlift inside.
    consts_ndims = jax.tree.map(jnp.ndim, consts)
    consts_in = jax.tree.map(
        lambda a, n: jnp.asarray(a)[None] if n == 0 else a,
        consts, consts_ndims)

    def _data_spec(flag: bool) -> P:
        return P(None, batch_axes) if flag else P()

    if last_fn is None and batch_manual:
        # stack mode: [1, M, b_local, ...] output keeps its batch shard
        out_specs = (P(PIPE_AXIS, None, batch_axes), P(PIPE_AXIS))
    else:
        # reduce-mode accumulators are psum'd over every manual axis
        out_specs = P(PIPE_AXIS)

    @functools.partial(
        _compat_shard_map, mesh=mesh,
        in_specs=(param_specs, jax.tree.map(lambda _: P(), edge_params),
                  jax.tree.map(lambda a: _data_spec(_batched(a)), x),
                  jax.tree.map(lambda _a, f: _data_spec(f),
                               consts_in, consts_flags)),
        out_specs=out_specs,
        # only axes that actually have devices go auto: pipe-(x batch)
        # meshes stay FULLY manual, which this JAX version can
        # differentiate (partial-auto grad hits known partitioner bugs)
        auto=frozenset(a for a in mesh.axis_names
                       if a != PIPE_AXIS and a not in batch_axes
                       and mesh.shape[a] > 1),
        check_vma=False)
    def region(sp, edge, x, consts):
        sp = jax.tree.map(lambda a: a[0], sp)  # [1, ...] -> local stage slice
        consts = jax.tree.map(lambda a, n: a[0] if n == 0 else a,
                              consts, consts_ndims)
        x = jax.tree.map(lambda a, d: a.astype(d), x, x_dtypes)
        edge = jax.tree.map(lambda a, d: a.astype(d), edge, edge_dtypes)
        consts = jax.tree.map(jax.lax.stop_gradient, consts)
        stage = jax.lax.axis_index(PIPE_AXIS)
        M = jax.tree.leaves(x)[0].shape[0]
        T = M + S - 1
        body = jax.checkpoint(stage_fn) if remat else stage_fn

        act0 = jnp.zeros(act_sds.shape, act_sds.dtype)

        def tick_common(act, t):
            # stage 0 consumes micro-batch t; later stages consume the
            # activation ppermuted in at the previous tick.  At tick t,
            # stage s is working on micro-batch t - s.
            mb0 = jnp.clip(t, 0, M - 1)
            x_t = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, mb0, 0,
                                                       keepdims=False), x)
            if first_fn is None:
                inp = jnp.where(stage == 0, x_t, act)
            else:
                # only stage 0 pays the embedding gather (predicate is
                # uniform across the non-pipe mesh axes, like last_fn)
                inp = jax.lax.cond(
                    stage == 0,
                    lambda: first_fn(edge, x_t, consts, mb0).astype(
                        act.dtype),
                    lambda: act)
            mb_id = jnp.clip(t - stage, 0, M - 1)
            out = body(sp, inp, consts, mb_id)
            aux = jnp.zeros((), jnp.float32)
            if stage_aux:
                out, aux = out
            # this stage did real work at tick t iff its micro-batch
            # index is in range (fill/drain ticks recompute clipped mbs)
            active = jnp.logical_and(t >= stage, t - stage < M)
            return out, jnp.where(active, aux, 0.0)

        if last_fn is None:
            def tick(carry, t):
                act, outputs, aux_acc = carry
                out, aux = tick_common(act, t)
                # last stage finishes micro-batch t-(S-1) at tick t.
                out_idx = jnp.clip(t - (S - 1), 0, M - 1)
                upd = jax.lax.dynamic_update_index_in_dim(
                    outputs, out, out_idx, 0)
                outputs = jnp.where(t >= S - 1, upd, outputs)
                nxt = jax.lax.ppermute(out, PIPE_AXIS, perm)
                return (nxt, outputs, aux_acc + aux), None

            init = (act0, jnp.zeros((M,) + act0.shape, act0.dtype),
                    jnp.zeros((), jnp.float32))
            (_, outputs, aux_acc), _ = jax.lax.scan(tick, init,
                                                    jnp.arange(T))
            # Stack per-stage output buffers over 'pipe': the caller
            # slices the last stage's (the only meaningful one).
            aux_tot = jax.lax.psum(aux_acc, PIPE_AXIS)  # sum stages
            if batch_axes:  # per-shard group-local aux -> batch mean
                aux_tot = jax.lax.pmean(aux_tot, batch_axes)
            return outputs[None], aux_tot[None]

        # reduce mode: accumulate last_fn contributions, no [M] buffer
        acc0 = jax.tree.map(lambda l: jnp.zeros(l.shape, l.dtype), acc_sds)

        def tick(carry, t):
            act, acc, aux_acc = carry
            out, aux = tick_common(act, t)
            out_mb = jnp.clip(t - (S - 1), 0, M - 1)
            valid = jnp.logical_and(t >= S - 1, stage == S - 1)
            # lax.cond: non-last stages (and fill ticks) skip the
            # norm+head+CE entirely instead of computing and masking it —
            # the predicate is uniform across the non-pipe mesh axes, so
            # auto-mode collectives inside the branch stay consistent
            contrib = jax.lax.cond(
                valid,
                lambda: last_fn(edge, out, consts, out_mb),
                lambda: jax.tree.map(
                    lambda l: jnp.zeros(l.shape, l.dtype), acc_sds))
            acc = jax.tree.map(lambda a, c: a + c, acc, contrib)
            nxt = jax.lax.ppermute(out, PIPE_AXIS, perm)
            return (nxt, acc, aux_acc + aux), None

        (_, acc, aux_acc), _ = jax.lax.scan(
            tick, (act0, acc0, jnp.zeros((), jnp.float32)), jnp.arange(T))
        # only the last stage accumulated; psum broadcasts it to all —
        # and with manual batch axes, the per-shard (loss sum, count)
        # accumulators sum into the GLOBAL totals (exact: the caller's
        # loss_sum / count is then the global token-weighted mean)
        acc = jax.tree.map(
            lambda a: jax.lax.psum(a, (PIPE_AXIS,) + batch_axes), acc)
        aux_tot = jax.lax.psum(aux_acc, PIPE_AXIS)
        if batch_axes:
            aux_tot = jax.lax.pmean(aux_tot, batch_axes)
        return jax.tree.map(lambda a: a[None], acc), aux_tot[None]

    res, aux = region(stage_params, edge_in, x_in, consts_in)
    if last_fn is None:
        out = res[-1]
    else:
        out = jax.tree.map(lambda a: a[0], res)
    return (out, aux[0]) if stage_aux else out


# ---------------------------------------------------------------------------
# stage-stacking of parameters
# ---------------------------------------------------------------------------

def stack_stages(boxed_params: Any, num_stages: int, layers_name: str = "layers"):
    """Reshape every boxed leaf's '<layers_name>' dim [L,...] -> [S, L/S,...]
    and prepend a 'stages' logical axis (mapped to the 'pipe' mesh axis by
    the partitioner).  Non-layer leaves pass through unchanged."""

    def fix(leaf):
        if not isinstance(leaf, meta.Partitioned):
            return leaf
        names = tuple(leaf.names)
        if layers_name not in names:
            return leaf
        dim = names.index(layers_name)
        if dim != 0:
            raise ValueError(f"'{layers_name}' dim must lead, got names={names}")
        L = leaf.value.shape[0]
        if L % num_stages != 0:
            raise ValueError(
                f"num_layers {L} not divisible by {num_stages} pipeline stages")
        new = leaf.value.reshape((num_stages, L // num_stages)
                                 + leaf.value.shape[1:])
        return meta.Partitioned(new, names=("stages",) + names)

    return jax.tree.map(fix, boxed_params,
                        is_leaf=lambda x: isinstance(x, meta.Partitioned))


# ---------------------------------------------------------------------------
# pipelined transformer LM
# ---------------------------------------------------------------------------

class PipelinedCausalLM:
    """Engine-protocol adapter running a transformer-family CausalLM
    (models/transformer.py) under pipeline parallelism.

    Layout: embedding / final norm / lm head are replicated over 'pipe'
    (their compute is tiny or amortized across the whole batch and their
    grads arrive via the shard_map transpose psum); the L transformer
    layers are split into S contiguous stages of L/S layers each.
    """

    def __init__(self, model, num_stages: int, schedule: str = "1f1b"):
        self.inner = model
        self.cfg: tfm.TransformerConfig = model.cfg
        if not self.cfg.scan_layers:
            raise ValueError("pipeline requires scan_layers=True (stacked params)")
        if schedule not in ("1f1b", "gpipe"):
            raise ValueError(f"unknown pipeline schedule {schedule!r}")
        self.num_stages = num_stages
        self.schedule = schedule
        self.mesh = None  # set by PipelineEngine once topology exists
        # MoE: the gating aux loss threads through the pipeline carry
        # (gpipe_spmd stage_aux); gate noise is disabled under the
        # pipeline (rng cannot enter the Manual-mode region as a
        # closure), matching the deterministic top-k default
        self.moe_cfg = getattr(model, "moe_cfg", None)

    def init_params(self, rng):
        return stack_stages(self.inner.init_params(rng), self.num_stages)

    # -- loss ------------------------------------------------------------
    def loss(self, params, batch, rng=None, is_training=True):
        """batch leaves are micro-batched: {'input_ids': [M, mb, s], ...}."""
        assert self.mesh is not None, "PipelineEngine must set .mesh"
        cfg = self.cfg
        ids = batch["input_ids"]
        M, b, s = ids.shape

        positions = batch.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s), (M, b, s))
        else:
            positions = positions.reshape(M, b, s)

        # per-micro-batch mask [M,b,s,s] — each stage indexes its current
        # micro-batch's slice via the mb_id the pipeline loop provides.
        if cfg.causal:
            mask = positions[:, :, :, None] >= positions[:, :, None, :]
        else:
            mask = jnp.ones((M, b, s, s), bool)
        attn_mask = batch.get("attention_mask")
        if attn_mask is not None:
            mask = mask & attn_mask.reshape(M, b, s)[:, :, None, :].astype(bool)
        sin, cos = tfm.rope_table(cfg, positions) if cfg.pos_emb == "rope" \
            else (jnp.zeros((M, b, s, 1)), jnp.zeros((M, b, s, 1)))

        # ALiBi: [M, b, H, s] per-micro-batch additive bias (key-position
        # linear; see models/transformer.forward); None otherwise
        abias_all = None
        if cfg.pos_emb == "alibi":
            slopes = jnp.asarray(tfm.alibi_slopes(cfg.num_heads))
            abias_all = (slopes[None, None, :, None]
                         * positions[:, :, None, :].astype(jnp.float32))

        labels_all = batch.get("labels")
        if labels_all is not None:
            labels_all = labels_all.reshape(M, b, s)

        moe_cfg = self.moe_cfg
        if moe_cfg is not None:
            from ...moe.layer import moe_forward
            training = is_training  # eval regime: eval_capacity_factor
            if (getattr(moe_cfg, "noisy_gate_policy", None)
                    and not getattr(self, "_gate_noise_warned", False)):
                self._gate_noise_warned = True
                log_dist(
                    "PipelineEngine: noisy_gate_policy="
                    f"{moe_cfg.noisy_gate_policy!r} is DISABLED under the "
                    "pipeline (rng cannot enter the Manual-mode region); "
                    "gating is deterministic top-k here",
                    level=__import__("logging").WARNING)

            def mlp_fn(c, p, h):
                return moe_forward(moe_cfg, p, h, is_training=training)
        else:
            mlp_fn = None

        def stage_fn(stage_layers, act, consts, mb_id):
            sin, cos, mask = jax.tree.map(
                lambda c: jax.lax.dynamic_index_in_dim(c, mb_id, 0,
                                                       keepdims=False),
                consts[:3])
            ab = (jax.lax.dynamic_index_in_dim(consts[3], mb_id, 0,
                                               keepdims=False)
                  if cfg.pos_emb == "alibi" else None)

            def layer(carry, lp):
                h, aux_acc = carry
                y, aux = tfm._layer_body(cfg, lp, h, sin, cos, mask,
                                         mlp_fn=mlp_fn, attn_bias=ab)
                return (y, aux_acc + aux), None
            (out, aux), _ = jax.lax.scan(
                layer, (act, jnp.zeros((), jnp.float32)), stage_layers)
            return (out, aux) if moe_cfg is not None else out

        def head_and_ce(edge, h_mb, consts, mb_id):
            """Final norm + lm head + CE for ONE micro-batch ->
            (weighted loss sum, valid-token count)."""
            h = tfm._norm_apply(cfg, edge["final_norm"], h_mb)
            if cfg.tie_embeddings:
                logits = jnp.einsum(
                    "bse,ve->bsv", h,
                    edge["embed"]["tokens"].astype(cfg.dtype))
            else:
                logits = jnp.einsum(
                    "bse,ev->bsv", h, edge["lm_head"].astype(cfg.dtype))
            logits = logits.astype(jnp.float32)
            _, _, _, _, c_ids, c_labels, c_am, _ = consts
            am = (jax.lax.dynamic_index_in_dim(c_am, mb_id, 0,
                                               keepdims=False)
                  if c_am is not None else None)
            def _valid_count(lab, m):
                # mirror cross_entropy_loss: labels < 0 are ignored, and
                # the attention mask gates validity
                v = lab >= 0
                if m is not None:
                    v = v & m.astype(bool)
                return v.sum().astype(jnp.float32)

            if c_labels is not None:
                lab = jax.lax.dynamic_index_in_dim(c_labels, mb_id, 0,
                                                   keepdims=False)
                ce = tfm.cross_entropy_loss(logits, lab, am)
                count = _valid_count(lab, am)
            else:
                lab = jax.lax.dynamic_index_in_dim(c_ids, mb_id, 0,
                                                   keepdims=False)[:, 1:]
                am1 = am[:, 1:] if am is not None else None
                ce = tfm.cross_entropy_loss(logits[:, :-1], lab, am1)
                count = _valid_count(lab, am1)
            return ce * count, count

        # micro-batch entry: embed token ids at stage 0 (keeps the [M,...]
        # pipeline input at id width — the [M,b,s,e] embedding buffer of
        # the stack schedule never exists)
        def embed_mb(edge, ids_mb, consts, mb_id):
            x = edge["embed"]["tokens"].astype(cfg.dtype)[ids_mb]
            if cfg.pos_emb == "learned":
                pos_mb = jax.lax.dynamic_index_in_dim(
                    consts[7], mb_id, 0, keepdims=False)
                x = x + edge["embed"]["positions"].astype(cfg.dtype)[pos_mb]
            if cfg.embed_layernorm:  # BLOOM word_embeddings_layernorm
                x = tfm._norm_apply(cfg, edge["embed"]["norm"], x)
            return x

        if self.schedule == "1f1b":
            edge = {"embed": params["embed"],
                    "final_norm": params["final_norm"]}
            if not cfg.tie_embeddings:
                edge["lm_head"] = params["lm_head"]
            am_c = (attn_mask.reshape(M, b, s)
                    if attn_mask is not None else None)
            abias_c = (abias_all if abias_all is not None
                       else jnp.zeros((M, 1), jnp.float32))  # never indexed
            res = gpipe_spmd(
                self.mesh, self.num_stages, stage_fn, params["layers"], ids,
                consts=(sin, cos, mask, abias_c, ids, labels_all, am_c,
                        positions),
                consts_batched=(True, True, True, abias_all is not None,
                                True,
                                None if labels_all is None else True,
                                None if am_c is None else True, True),
                remat=cfg.remat,
                first_fn=embed_mb, last_fn=head_and_ce, edge_params=edge,
                stage_aux=moe_cfg is not None)
            if moe_cfg is not None:
                (loss_sum, count), aux = res
                # aux summed over micro-batches -> per-forward mean, the
                # dense path's convention (mixtral loss = ce + aux)
                return loss_sum / jnp.maximum(count, 1.0) + aux / M
            loss_sum, count = res
            return loss_sum / jnp.maximum(count, 1.0)

        # gpipe: stack all outputs, one full-batch head/CE
        x = params["embed"]["tokens"].astype(cfg.dtype)[ids]
        if cfg.pos_emb == "learned":
            x = x + params["embed"]["positions"].astype(cfg.dtype)[positions]
        if cfg.embed_layernorm:
            x = tfm._norm_apply(cfg, params["embed"]["norm"], x)
        outputs = gpipe_spmd(self.mesh, self.num_stages, stage_fn,
                             params["layers"], x,
                             consts=(sin, cos, mask,
                                     abias_all if abias_all is not None
                                     else jnp.zeros((M, 1), jnp.float32)),
                             consts_batched=(True, True, True,
                                             abias_all is not None),
                             remat=cfg.remat,
                             stage_aux=moe_cfg is not None)   # [M,b,s,e]
        aux_mean = jnp.zeros((), jnp.float32)
        if moe_cfg is not None:
            outputs, aux_tot = outputs
            aux_mean = aux_tot / M
        h = tfm._norm_apply(cfg, params["final_norm"],
                            outputs.reshape(M * b, s, -1))
        if cfg.tie_embeddings:
            logits = jnp.einsum("bse,ve->bsv", h,
                                params["embed"]["tokens"].astype(cfg.dtype))
        else:
            logits = jnp.einsum("bse,ev->bsv", h,
                                params["lm_head"].astype(cfg.dtype))
        logits = logits.astype(jnp.float32)

        attn_flat = attn_mask.reshape(M * b, s) if attn_mask is not None else None
        if "labels" in batch:
            labels = batch["labels"].reshape(M * b, s)
            return tfm.cross_entropy_loss(logits, labels,
                                          attn_flat) + aux_mean
        labels = ids.reshape(M * b, s)[:, 1:]
        return tfm.cross_entropy_loss(
            logits[:, :-1], labels,
            attn_flat[:, 1:] if attn_flat is not None else None) + aux_mean

    def eval_loss(self, params, batch, rng=None):
        """Non-micro-batched batch: add a leading M=1 dim; MoE gating
        runs in the eval regime (eval_capacity_factor, no noise)."""
        batch = {k: v[None] if hasattr(v, "ndim") else v
                 for k, v in batch.items()}
        return self.loss(params, batch, rng, is_training=False)


# ---------------------------------------------------------------------------
# generic homogeneous PipelineModule path
# ---------------------------------------------------------------------------

class PipelinedModule:
    """Engine adapter for a :class:`PipelineModule` whose layers all share
    one param structure (the stackable case; heterogeneous stage support
    goes through :class:`PipelinedCausalLM`-style model adapters instead).

    Batch dict: {'x': [M, mb, ...], 'y': [M, mb, ...]} with
    ``module.loss_fn(out, y) -> scalar``.
    """

    def __init__(self, module: PipelineModule, num_stages: int,
                 schedule: str = "1f1b"):
        if module.loss_fn is None:
            raise ValueError("PipelineModule needs loss_fn for training")
        if schedule not in ("1f1b", "gpipe"):
            raise ValueError(f"unknown pipeline schedule {schedule!r}")
        self.module = module
        self.num_stages = num_stages
        self.schedule = schedule
        self.mesh = None
        L = len(module)
        if L % num_stages != 0:
            raise ValueError(
                f"{L} layers not divisible by {num_stages} stages")
        # homogeneity check
        shapes = [jax.eval_shape(l.init_params, jax.random.key(0))
                  for l in module._built]
        treedefs = {str(jax.tree.structure(sh)) for sh in shapes}
        leaf_shapes = {tuple((l.shape, str(l.dtype))
                             for l in jax.tree.leaves(sh)) for sh in shapes}
        if len(treedefs) > 1 or len(leaf_shapes) > 1:
            raise ValueError(
                "pipeline stage stacking requires homogeneous layer specs; "
                "wrap heterogeneous edges (embed/head) outside the pipeline "
                "body (see PipelinedCausalLM)")
        self._layer0 = module._built[0]

    def init_params(self, rng):
        per_layer = self.module.init_layer_params(rng, range(len(self.module)))
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
        L = len(self.module)
        S = self.num_stages
        return jax.tree.map(
            lambda a: meta.Partitioned(
                a.reshape((S, L // S) + a.shape[1:]),
                names=("stages", "layers") + (None,) * (a.ndim - 1)),
            stacked)

    def loss(self, params, batch, rng=None):
        assert self.mesh is not None
        x, y = batch["x"], batch["y"]
        M = x.shape[0]
        apply_layer = self._layer0.__call__

        def stage_fn(stage_layers, act, consts, mb_id):
            def layer(carry, lp):
                return apply_layer(lp, carry), None
            out, _ = jax.lax.scan(layer, act, stage_layers)
            return out

        if self.schedule == "1f1b":
            loss_fn = self.module.loss_fn

            def last_fn(edge, out, consts, mb_id):
                y_mb = jax.lax.dynamic_index_in_dim(consts[0], mb_id, 0,
                                                    keepdims=False)
                return loss_fn(out, y_mb)

            total = gpipe_spmd(self.mesh, self.num_stages, stage_fn,
                               params, x, consts=(y,), last_fn=last_fn,
                               consts_batched=(True,))
            # Micro-batch average, matching the reference pipeline
            # engine (its total_loss accumulates per-micro-batch losses
            # and divides by micro_batches).  CONTRACT: loss_fn must
            # return a per-micro-batch MEAN for this to equal the flat
            # batch mean; a sum-style or unevenly-masked loss_fn gets
            # the reference's mean-of-means semantics, not the flat
            # mean — use schedule="gpipe" for exact flat-batch loss.
            return total / M

        outputs = gpipe_spmd(self.mesh, self.num_stages, stage_fn,
                             params, x)
        flat_out = outputs.reshape((-1,) + outputs.shape[2:])
        flat_y = y.reshape((-1,) + y.shape[2:])
        return self.module.loss_fn(flat_out, flat_y)

    def eval_loss(self, params, batch, rng=None):
        batch = {k: v[None] for k, v in batch.items()}
        return self.loss(params, batch, rng)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class PipelineEngine(DeepSpeedEngine):
    """Training engine with pipeline parallelism (reference
    runtime/pipe/engine.py:56).

    ``train_batch`` consumes gradient_accumulation_steps micro-batches and
    runs them through the pipelined step as one XLA program.  The number of
    stages comes from config ``pipeline.stages`` / mesh 'pipe' axis.
    """

    def __init__(self, model: Any = None, config: Any = None, **kw):
        from ..config import load_config
        cfg = load_config(config)
        stages = cfg.tpu.mesh.get("pipe", cfg.pipeline.stages or 1)
        if isinstance(model, PipelineModule):
            adapter: Any = PipelinedModule(model, stages,
                                           schedule=cfg.pipeline.schedule)
        elif hasattr(model, "cfg") and isinstance(model.cfg, tfm.TransformerConfig):
            adapter = PipelinedCausalLM(model, stages,
                                         schedule=cfg.pipeline.schedule)
        else:
            raise ValueError(
                "PipelineEngine needs a PipelineModule or a transformer-family "
                f"model with .cfg; got {type(model)}")
        self._pipe_adapter = adapter
        self.num_stages = stages
        # pipeline consumes all micro-batches inside one loss evaluation
        self._fused_microbatches = True
        super().__init__(model=adapter, config=cfg, **kw)
        if self.topology.pp_world_size != stages:
            raise ValueError(
                f"mesh 'pipe' axis ({self.topology.pp_world_size}) != "
                f"pipeline stages ({stages})")
        log_dist(f"PipelineEngine: {stages} stages x "
                 f"{self.gradient_accumulation_steps()} micro-batches "
                 f"(bubble {(stages - 1)}/{self.gradient_accumulation_steps() + stages - 1})",
                 ranks=[0])

    def _build_train_step(self):
        self._pipe_adapter.mesh = self.topology.mesh
        return super()._build_train_step()

    def _build_eval_step(self):
        self._pipe_adapter.mesh = self.topology.mesh
        return super()._build_eval_step()

    @property
    def micro_batches(self) -> int:
        return self.gradient_accumulation_steps()

    def schedule(self, stage_id: Optional[int] = None):
        """The 1F1B instruction stream this step corresponds to (for
        introspection/tests; the XLA executor fuses it)."""
        from .schedule import TrainSchedule
        return TrainSchedule(self.micro_batches, self.num_stages,
                             stage_id if stage_id is not None else 0)
