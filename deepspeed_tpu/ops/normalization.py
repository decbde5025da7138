"""RMSNorm / LayerNorm Pallas kernels.

Reference: ``csrc/transformer/inference/csrc/{layer_norm.cu, rms_norm.cu}``
and inference-v2 ``kernels/core_ops/cuda_{layer,rms}_norm`` (incl. the
fused residual-add variants).  One VMEM pass per row block: fp32 moments,
optional fused residual add, cast back to input dtype.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..accelerator import on_tpu


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[:] = (x * jax.lax.rsqrt(var + eps)
                * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _rmsnorm_res_kernel(x_ref, res_ref, w_ref, o_ref, res_o_ref, *, eps):
    s = x_ref[:].astype(jnp.float32) + res_ref[:].astype(jnp.float32)
    res_o_ref[:] = s.astype(res_o_ref.dtype)
    var = jnp.mean(s * s, axis=-1, keepdims=True)
    o_ref[:] = (s * jax.lax.rsqrt(var + eps)
                * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _layernorm_kernel(x_ref, w_ref, b_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    o_ref[:] = ((x - mean) * jax.lax.rsqrt(var + eps)
                * w_ref[:].astype(jnp.float32)
                + b_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


#: fp32 working set one grid step may hold across its row blocks — with
#: double-buffered DMA this keeps the kernels inside the 16 MiB of scoped
#: VMEM at any width (the fused-residual variant has four row blocks)
_VMEM_BUDGET = 8 << 20


def _row_call(kernel, args, out_shapes, d, block_rows, interpret):
    lead = args[0].shape[0]
    n_row_blocks = sum(a.ndim > 1 for a in args) + len(out_shapes)
    fit = _VMEM_BUDGET // (n_row_blocks * d * 4) // 8 * 8
    block_rows = min(block_rows, lead, max(8, fit))
    grid = (pl.cdiv(lead, block_rows),)
    specs = []
    for a in args:
        if a.ndim == 1:  # scale/bias
            specs.append(pl.BlockSpec((d,), lambda i: (0,)))
        else:
            specs.append(pl.BlockSpec((block_rows, d), lambda i: (i, 0)))
    out_specs = [pl.BlockSpec((block_rows, d), lambda i: (i, 0))
                 for _ in out_shapes]
    single = len(out_shapes) == 1
    return pl.pallas_call(
        kernel, grid=grid, in_specs=specs,
        out_specs=out_specs[0] if single else out_specs,
        out_shape=out_shapes[0] if single else out_shapes,
        name=getattr(kernel, "func", kernel).__name__.strip("_"),
        interpret=interpret)(*args)


def rmsnorm(x: jax.Array, weight: jax.Array, eps: float = 1e-6,
            residual: Optional[jax.Array] = None,
            block_rows: int = 256, interpret: Optional[bool] = None):
    """x: [..., D].  With ``residual``, computes the FastGen fused
    (residual-add -> norm) and returns (normed, new_residual)."""
    if interpret is None:
        interpret = not on_tpu()
    shape = x.shape
    d = shape[-1]
    x2 = x.reshape(-1, d)
    if residual is None:
        out = _row_call(functools.partial(_rmsnorm_kernel, eps=eps),
                        [x2, weight], [jax.ShapeDtypeStruct(x2.shape, x.dtype)],
                        d, block_rows, interpret)
        return out.reshape(shape)
    r2 = residual.reshape(-1, d)
    out, res = _row_call(
        functools.partial(_rmsnorm_res_kernel, eps=eps),
        [x2, r2, weight],
        [jax.ShapeDtypeStruct(x2.shape, x.dtype),
         jax.ShapeDtypeStruct(x2.shape, x.dtype)],
        d, block_rows, interpret)
    return out.reshape(shape), res.reshape(shape)


def layernorm(x: jax.Array, weight: jax.Array, bias: jax.Array,
              eps: float = 1e-5, block_rows: int = 256,
              interpret: Optional[bool] = None):
    if interpret is None:
        interpret = not on_tpu()
    shape = x.shape
    d = shape[-1]
    x2 = x.reshape(-1, d)
    out = _row_call(functools.partial(_layernorm_kernel, eps=eps),
                    [x2, weight, bias],
                    [jax.ShapeDtypeStruct(x2.shape, x.dtype)],
                    d, block_rows, interpret)
    return out.reshape(shape)
