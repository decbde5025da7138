"""Fused AdamW — Pallas multi-tensor-style optimizer kernel.

Reference: ``csrc/adam/multi_tensor_adam.cu`` (FusedAdam) + host
``csrc/adam/cpu_adam.cpp``.  The CUDA version exists to amortize kernel
launches over many small tensors; on TPU the same economics are achieved
by updating the *flattened shard* in one kernel: params/grads/moments are
raveled into one fp32 vector per dtype group and the whole Adam update is
a single elementwise pass (one HBM read/write per buffer).  XLA fuses the
optax chain nearly as well, so this kernel is an opt-in fast path
(``optimizer.type = "fusedadam"`` with ``tpu.fused_kernel=true``) and the
numerical ground truth for the optax path's tests.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..accelerator import on_tpu

_LANES = 1024  # rows are reshaped to [n // _LANES, _LANES] for VPU tiling
#: rows per grid step: 7 double-buffered fp32 row blocks of AdamW at 128
#: rows are 7 MiB of the 16 MiB scoped VMEM (256 rows do not fit)
_BLOCK_ROWS = 128


def _adamw_kernel(p_ref, g_ref, m_ref, v_ref, sc_ref,
                  new_p_ref, new_m_ref, new_v_ref):
    """One elementwise pass: m, v, bias-corrected AdamW update.
    sc_ref (SMEM, [7]): lr, b1, b2, eps, wd, bc1, bc2 — the bias
    corrections ``1 - b**step`` are computed by the caller (Mosaic has
    no ``powf``; they are two scalars per step, not per element)."""
    lr = sc_ref[0]
    b1 = sc_ref[1]
    b2 = sc_ref[2]
    eps = sc_ref[3]
    wd = sc_ref[4]
    bc1 = sc_ref[5]
    bc2 = sc_ref[6]

    g = g_ref[:].astype(jnp.float32)
    p = p_ref[:].astype(jnp.float32)
    m = b1 * m_ref[:] + (1.0 - b1) * g
    v = b2 * v_ref[:] + (1.0 - b2) * g * g
    update = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * p
    new_p_ref[:] = (p - lr * update).astype(new_p_ref.dtype)
    new_m_ref[:] = m
    new_v_ref[:] = v


def _bias_corrections(b1, b2, step):
    step = jnp.asarray(step, jnp.float32)
    return 1.0 - jnp.power(b1, step), 1.0 - jnp.power(b2, step)


def fused_adamw_flat(p: jax.Array, g: jax.Array, m: jax.Array, v: jax.Array,
                     lr, b1: float, b2: float, eps: float, wd: float, step,
                     block_rows: int = _BLOCK_ROWS, interpret: bool | None = None):
    """Apply fused AdamW to flat 1-D buffers; returns (p, m, v)."""
    n = p.shape[0]
    pad = (-n) % _LANES
    if pad:
        p, g, m, v = (jnp.pad(x, (0, pad)) for x in (p, g, m, v))
    rows = (n + pad) // _LANES
    shape2 = (rows, _LANES)
    p2, g2, m2, v2 = (x.reshape(shape2) for x in (p, g, m, v))
    scalars = jnp.asarray([lr, b1, b2, eps, wd, *_bias_corrections(
        b1, b2, step)], jnp.float32)

    if interpret is None:
        interpret = not on_tpu()
    block_rows = min(block_rows, rows)
    grid = (pl.cdiv(rows, block_rows),)
    row_spec = pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0))
    new_p, new_m, new_v = pl.pallas_call(
        _adamw_kernel,
        grid=grid,
        in_specs=[row_spec, row_spec, row_spec, row_spec,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[row_spec, row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(shape2, p.dtype),
                   jax.ShapeDtypeStruct(shape2, jnp.float32),
                   jax.ShapeDtypeStruct(shape2, jnp.float32)],
        name="fused_adamw",
        interpret=interpret,
    )(p2, g2, m2, v2, scalars)
    out = (new_p.ravel(), new_m.ravel(), new_v.ravel())
    if pad:
        out = tuple(x[:n] for x in out)
    return out


# ---------------------------------------------------------------------------
# Lion (reference csrc/lion/fused_lion* + cpu_lion.cpp)
# ---------------------------------------------------------------------------

def _lion_kernel(p_ref, g_ref, m_ref, sc_ref, new_p_ref, new_m_ref):
    """sign-momentum update: u = sign(b1*m + (1-b1)*g);
    p -= lr*(u + wd*p); m = b2*m + (1-b2)*g.
    sc_ref (SMEM, [4]): lr, b1, b2, wd."""
    lr = sc_ref[0]
    b1 = sc_ref[1]
    b2 = sc_ref[2]
    wd = sc_ref[3]
    g = g_ref[:].astype(jnp.float32)
    p = p_ref[:].astype(jnp.float32)
    m = m_ref[:]
    u = jnp.sign(b1 * m + (1.0 - b1) * g)
    new_p_ref[:] = (p - lr * (u + wd * p)).astype(new_p_ref.dtype)
    new_m_ref[:] = b2 * m + (1.0 - b2) * g


def fused_lion_flat(p, g, m, lr, b1: float, b2: float, wd: float,
                    block_rows: int = _BLOCK_ROWS, interpret: bool | None = None):
    """Apply fused Lion to flat 1-D buffers; returns (p, m)."""
    n = p.shape[0]
    pad = (-n) % _LANES
    if pad:
        p, g, m = (jnp.pad(x, (0, pad)) for x in (p, g, m))
    rows = (n + pad) // _LANES
    shape2 = (rows, _LANES)
    p2, g2, m2 = (x.reshape(shape2) for x in (p, g, m))
    scalars = jnp.asarray([lr, b1, b2, wd], jnp.float32)
    if interpret is None:
        interpret = not on_tpu()
    block_rows = min(block_rows, rows)
    row_spec = pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0))
    new_p, new_m = pl.pallas_call(
        _lion_kernel,
        grid=(pl.cdiv(rows, block_rows),),
        in_specs=[row_spec, row_spec, row_spec,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(shape2, p.dtype),
                   jax.ShapeDtypeStruct(shape2, jnp.float32)],
        name="fused_lion",
        interpret=interpret,
    )(p2, g2, m2, scalars)
    out = (new_p.ravel(), new_m.ravel())
    if pad:
        out = tuple(x[:n] for x in out)
    return out


class FusedLionState(NamedTuple):
    count: jax.Array
    mu: optax.Updates


def fused_lion(learning_rate, b1: float = 0.9, b2: float = 0.99,
               weight_decay: float = 0.0) -> optax.GradientTransformation:
    """optax transform running the Pallas Lion kernel per (raveled) leaf
    — matches ``optax.lion`` numerics (decoupled decay)."""

    def init_fn(params):
        return FusedLionState(
            count=jnp.zeros((), jnp.int32),
            mu=jax.tree.map(lambda p: jnp.zeros(p.size, jnp.float32),
                            params))

    def update_fn(grads, state, params):
        if params is None:
            raise ValueError("fused_lion requires params")
        count = state.count + 1
        lr = learning_rate(count) if callable(learning_rate) else learning_rate
        flat_p, treedef = jax.tree.flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state.mu)
        new_p, new_m = [], []
        for p, g, m in zip(flat_p, flat_g, flat_m):
            pf, mf = fused_lion_flat(
                p.ravel().astype(jnp.float32),
                g.ravel().astype(jnp.float32), m,
                lr, b1, b2, weight_decay)
            new_p.append(pf.reshape(p.shape).astype(p.dtype))
            new_m.append(mf)
        updates = jax.tree.unflatten(
            treedef, [np_ - p for np_, p in zip(new_p, flat_p)])
        return updates, FusedLionState(
            count=count, mu=jax.tree.unflatten(treedef, new_m))

    return optax.GradientTransformation(init_fn, update_fn)


# ---------------------------------------------------------------------------
# LAMB (reference csrc/lamb/fused_lamb_cuda_kernel.cu: per-tensor trust
# ratio over the Adam-style update)
# ---------------------------------------------------------------------------

def _lamb_stage1_kernel(p_ref, g_ref, m_ref, v_ref, sc_ref,
                        u_ref, new_m_ref, new_v_ref, norms_ref):
    """Elementwise Adam-style update u (incl. decoupled wd term) + this
    block's partial squared norms of p and u (one (8, 128) fp32 tile per
    grid step — the smallest block the TPU lowering accepts — holding
    sum(p*p) in lane 0 and sum(u*u) in lane 1; summed by the caller).
    sc_ref (SMEM, [6]): b1, b2, eps, wd, bc1, bc2 (bias corrections
    from the caller, as in ``_adamw_kernel``)."""
    b1 = sc_ref[0]
    b2 = sc_ref[1]
    eps = sc_ref[2]
    wd = sc_ref[3]
    bc1 = sc_ref[4]
    bc2 = sc_ref[5]
    g = g_ref[:].astype(jnp.float32)
    p = p_ref[:].astype(jnp.float32)
    m = b1 * m_ref[:] + (1.0 - b1) * g
    v = b2 * v_ref[:] + (1.0 - b2) * g * g
    u = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * p
    u_ref[:] = u
    new_m_ref[:] = m
    new_v_ref[:] = v
    lane = jax.lax.broadcasted_iota(jnp.int32, norms_ref.shape, 1)
    norms_ref[:] = jnp.where(lane == 0, jnp.sum(p * p),
                             jnp.where(lane == 1, jnp.sum(u * u), 0.0))


def fused_lamb_flat(p, g, m, v, lr, b1: float, b2: float, eps: float,
                    wd: float, step, block_rows: int = _BLOCK_ROWS,
                    interpret: bool | None = None):
    """Fused LAMB on flat 1-D buffers; returns (p, m, v).

    Stage 1 (Pallas): moments + Adam-style update + per-block norm
    partials in one elementwise pass.  The per-TENSOR trust ratio
    ||p|| / ||u|| and the final axpy are O(1)+O(n) XLA ops fused into
    the surrounding program (the CUDA version's second kernel)."""
    n = p.shape[0]
    pad = (-n) % _LANES
    if pad:
        p, g, m, v = (jnp.pad(x, (0, pad)) for x in (p, g, m, v))
    rows = (n + pad) // _LANES
    shape2 = (rows, _LANES)
    p2, g2, m2, v2 = (x.reshape(shape2) for x in (p, g, m, v))
    scalars = jnp.asarray([b1, b2, eps, wd, *_bias_corrections(
        b1, b2, step)], jnp.float32)
    if interpret is None:
        interpret = not on_tpu()
    block_rows = min(block_rows, rows)
    nblocks = pl.cdiv(rows, block_rows)
    row_spec = pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0))
    u, new_m, new_v, norms = pl.pallas_call(
        _lamb_stage1_kernel,
        grid=(nblocks,),
        in_specs=[row_spec, row_spec, row_spec, row_spec,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[row_spec, row_spec, row_spec,
                   pl.BlockSpec((8, 128), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct(shape2, jnp.float32),
                   jax.ShapeDtypeStruct(shape2, jnp.float32),
                   jax.ShapeDtypeStruct(shape2, jnp.float32),
                   jax.ShapeDtypeStruct((nblocks * 8, 128), jnp.float32)],
        name="fused_lamb_stage1",
        interpret=interpret,
    )(p2, g2, m2, v2, scalars)
    pn = jnp.sqrt(norms[::8, 0].sum())
    un = jnp.sqrt(norms[::8, 1].sum())
    ratio = jnp.where((pn > 0) & (un > 0), pn / un, 1.0)
    new_p = (p2 - lr * ratio * u).astype(p.dtype)
    out = (new_p.ravel(), new_m.ravel(), new_v.ravel())
    if pad:
        out = tuple(x[:n] for x in out)
    return out


def fused_lamb(learning_rate, b1: float = 0.9, b2: float = 0.999,
               eps: float = 1e-6, weight_decay: float = 0.0
               ) -> optax.GradientTransformation:
    """optax transform running the Pallas LAMB kernel per leaf (the
    trust ratio is per PARAM TENSOR, reference FusedLamb semantics)."""

    def init_fn(params):
        z = jax.tree.map(lambda p: jnp.zeros(p.size, jnp.float32), params)
        return FusedAdamState(count=jnp.zeros((), jnp.int32),
                              mu=z, nu=jax.tree.map(jnp.zeros_like, z))

    def update_fn(grads, state, params):
        if params is None:
            raise ValueError("fused_lamb requires params")
        count = state.count + 1
        lr = learning_rate(count) if callable(learning_rate) else learning_rate
        flat_p, treedef = jax.tree.flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state.mu)
        flat_v = treedef.flatten_up_to(state.nu)
        new_p, new_m, new_v = [], [], []
        for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v):
            pf, mf, vf = fused_lamb_flat(
                p.ravel().astype(jnp.float32),
                g.ravel().astype(jnp.float32), m, v,
                lr, b1, b2, eps, weight_decay, count.astype(jnp.float32))
            new_p.append(pf.reshape(p.shape).astype(p.dtype))
            new_m.append(mf)
            new_v.append(vf)
        updates = jax.tree.unflatten(
            treedef, [np_ - p for np_, p in zip(new_p, flat_p)])
        return updates, FusedAdamState(
            count=count,
            mu=jax.tree.unflatten(treedef, new_m),
            nu=jax.tree.unflatten(treedef, new_v))

    return optax.GradientTransformation(init_fn, update_fn)


class FusedAdamState(NamedTuple):
    count: jax.Array
    mu: optax.Updates
    nu: optax.Updates


def fused_adamw(learning_rate, b1: float = 0.9, b2: float = 0.999,
                eps: float = 1e-8, weight_decay: float = 0.0
                ) -> optax.GradientTransformation:
    """optax transform whose update runs the Pallas kernel per leaf
    (leaves are raveled; shape restored afterwards)."""

    def init_fn(params):
        z = jax.tree.map(lambda p: jnp.zeros(p.size, jnp.float32), params)
        return FusedAdamState(count=jnp.zeros((), jnp.int32),
                              mu=z, nu=jax.tree.map(jnp.zeros_like, z))

    def update_fn(grads, state: FusedAdamState, params):
        if params is None:
            raise ValueError("fused_adamw requires params")
        count = state.count + 1
        lr = learning_rate(count) if callable(learning_rate) else learning_rate

        flat_p, treedef = jax.tree.flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state.mu)
        flat_v = treedef.flatten_up_to(state.nu)
        new_p, new_m, new_v = [], [], []
        for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v):
            pf, mf, vf = fused_adamw_flat(
                p.ravel().astype(jnp.float32), g.ravel().astype(jnp.float32),
                m, v, lr, b1, b2, eps, weight_decay,
                count.astype(jnp.float32))
            new_p.append(pf.reshape(p.shape).astype(p.dtype))
            new_m.append(mf)
            new_v.append(vf)
        updates = jax.tree.unflatten(
            treedef, [np_ - p for np_, p in zip(new_p, flat_p)])
        return updates, FusedAdamState(
            count=count,
            mu=jax.tree.unflatten(treedef, new_m),
            nu=jax.tree.unflatten(treedef, new_v))

    return optax.GradientTransformation(init_fn, update_fn)
