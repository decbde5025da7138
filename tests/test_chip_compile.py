"""Every Pallas kernel compiled for the chip, without the chip.

The TPU's compiler is installed here and compiles for a chip that is
described and not attached (guide ``on-chip-measurement`` section 2):
``jax.jit(f).lower(shapes).compile()`` raises what the chip's compiler
would raise — block shapes the Mosaic lowering refuses, VMEM overruns,
ops it cannot legalize — none of which interpret mode (every other kernel
test) can see.  Shapes are the smoke model's: Mistral-7B head geometry
(32 heads / 8 KV heads, head_dim 128), seq 2048, page 64.

Nothing runs: a compile that passes is not a chip run.  The topology is
described inside a module-scoped fixture (only the worker that is handed
this file loads the TPU library, and only once a test has started), the
compiles happen in the test's own process, and the persistent compile
cache is off around them (an entry written for a described chip cannot be
read back without one).  Keep every such test in THIS file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.ops.flash_attention import flash_attention
from deepspeed_tpu.ops.fused_optimizer import (fused_adamw_flat,
                                               fused_lamb_flat,
                                               fused_lion_flat)
from deepspeed_tpu.ops.normalization import layernorm, rmsnorm
from deepspeed_tpu.ops.paged_attention import (MAX_KERNEL_Q_ROWS, KVPages,
                                               paged_attention)
from deepspeed_tpu.ops.quantization import (dequantize_blockwise,
                                            quantize_blockwise)

HEADS, KV_HEADS, HEAD_DIM, SEQ, PAGE, POOL = 32, 8, 128, 2048, 64, 512


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """``chip(shape, dtype)`` -> an abstract array on the described chip;
    the persistent compile cache stays off while this module runs."""
    from jax.experimental.compilation_cache import compilation_cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was_on)


def compile_for_chip(fn, *shapes, kernel: str, calls: int = 1):
    """Compile ``fn`` and require ``calls`` Mosaic custom calls of the
    Pallas kernel named ``kernel`` in the executable's text."""
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    found = sum(1 for line in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line
                and kernel in line)
    assert found == calls, (kernel, found)


# -- flash attention (the training default, and serving's fresh prefill) ----

def _qkv(chip, batch=1, heads=HEADS):
    return (chip((batch, heads, SEQ, HEAD_DIM), jnp.bfloat16),) * 3


@pytest.mark.parametrize("batch,heads", [(1, 32), (4, 16)])
def test_flash_forward(chip, batch, heads):
    compile_for_chip(
        lambda q, k, v: flash_attention(q, k, v, interpret=False),
        *_qkv(chip, batch, heads), kernel="flash_attention_fwd")


@pytest.mark.parametrize("window", [None, 4096, 1024])
def test_flash_forward_and_backward(chip, window):
    def grads(q, k, v):
        return jax.grad(lambda q, k, v: flash_attention(
            q, k, v, window=window, interpret=False
        ).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dkv",
                   "flash_attention_bwd_dq"):
        compile_for_chip(grads, *_qkv(chip), kernel=kernel)


def test_flash_short_unaligned_sequence(chip):
    """A prefill bucket below one lane tile: the [1, S] lse row block
    equals the array, which the lowering accepts as it does (8, 128)
    multiples."""
    q = chip((8, HEADS, 48, HEAD_DIM), jnp.bfloat16)
    compile_for_chip(
        lambda q, k, v: flash_attention(q, k, v, interpret=False),
        q, q, q, kernel="flash_attention_fwd")


# -- paged attention (every serving step) -----------------------------------

def _paged_args(chip, slots, rows, int8):
    pages_per_seq = SEQ // PAGE
    shape = (POOL + 1, 2, KV_HEADS, PAGE, HEAD_DIM)
    kv = (KVPages(chip(shape, jnp.int8), chip(shape[:-1], jnp.float32))
          if int8 else chip(shape, jnp.bfloat16))
    return (chip((slots, rows, HEADS, HEAD_DIM), jnp.bfloat16), kv,
            chip((slots, pages_per_seq), jnp.int32),
            chip((slots,), jnp.int32), chip((slots,), jnp.int32))


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("window", [None, 4096], ids=["full", "window"])
@pytest.mark.parametrize("slots,rows", [(64, 1), (8, 128), (2, 512)],
                         ids=["decode", "mixed", "chunk"])
def test_paged_attention(chip, slots, rows, window, int8):
    compile_for_chip(
        lambda q, kv, table, start, lens: paged_attention(
            q, kv, table, start, lens, use_kernel=True, window=window,
            interpret=False),
        *_paged_args(chip, slots, rows, int8), kernel="paged_attention")


def test_paged_attention_largest_query_block(chip):
    """``MAX_KERNEL_Q_ROWS`` is what the VMEM budget actually compiles:
    Q x groups = 4096 rows fit the 16 MiB scoped limit (8192 need 26.85
    MiB and are refused, so larger blocks take the dense-gather path)."""
    rows = MAX_KERNEL_Q_ROWS // (HEADS // KV_HEADS)
    compile_for_chip(
        lambda q, kv, table, start, lens: paged_attention(
            q, kv, table, start, lens, use_kernel=True, interpret=False),
        *_paged_args(chip, 2, rows, int8=False), kernel="paged_attention")
    with pytest.raises(Exception, match="vmem"):
        compile_for_chip(
            lambda q, kv, table, start, lens: paged_attention(
                q, kv, table, start, lens, use_kernel=True, interpret=False),
            *_paged_args(chip, 1, 2 * rows, int8=False),
            kernel="paged_attention")


# -- norms, fused optimizers, block quantization ------------------------------

def test_rmsnorm_and_layernorm(chip):
    x = chip((8192, 4096), jnp.bfloat16)
    w = chip((4096,), jnp.float32)
    compile_for_chip(lambda x, w: rmsnorm(x, w, interpret=False), x, w,
                     kernel="rmsnorm_kernel")
    compile_for_chip(
        lambda x, r, w: rmsnorm(x, w, residual=r, interpret=False), x, x, w,
        kernel="rmsnorm_res_kernel")
    compile_for_chip(lambda x, w, b: layernorm(x, w, b, interpret=False),
                     x, w, w, kernel="layernorm_kernel")


@pytest.mark.parametrize("name", ["adamw", "lamb", "lion"])
def test_fused_optimizers(chip, name):
    """One FFN matrix of the model as a flat fp32 shard; the bias
    corrections are computed outside the kernel (Mosaic has no powf)."""
    p = chip((4096 * 14336,), jnp.float32)
    step = chip((), jnp.float32)
    if name == "adamw":
        compile_for_chip(
            lambda p, g, m, v, s: fused_adamw_flat(
                p, g, m, v, 1e-4, 0.9, 0.999, 1e-8, 0.01, s,
                interpret=False), p, p, p, p, step, kernel="fused_adamw")
    elif name == "lamb":
        compile_for_chip(
            lambda p, g, m, v, s: fused_lamb_flat(
                p, g, m, v, 1e-4, 0.9, 0.999, 1e-6, 0.01, s,
                interpret=False), p, p, p, p, step,
            kernel="fused_lamb_stage1")
    else:
        compile_for_chip(
            lambda p, g, m: fused_lion_flat(p, g, m, 1e-4, 0.9, 0.99, 0.01,
                                            interpret=False),
            p, p, p, kernel="fused_lion")


def test_blockwise_quantization(chip):
    """Row-gridded: the whole-tensor single block did not compile in
    bounded time at this size."""
    compile_for_chip(
        lambda x: quantize_blockwise(x, interpret=False)[:2],
        chip((4096, 4096), jnp.bfloat16), kernel="quantize_blockwise")
    rows = 4096 * 4096 // 512
    compile_for_chip(
        lambda q, s: dequantize_blockwise(q, s, 0, (4096, 4096),
                                          jnp.bfloat16, interpret=False),
        chip((rows, 512), jnp.int8), chip((rows,), jnp.float32),
        kernel="dequantize_blockwise")
