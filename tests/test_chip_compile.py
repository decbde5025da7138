"""Every Pallas kernel, and the serve step programs, compiled for the chip
without the chip.

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

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.inference.v2.step_key import (StepKey, step_avals,
                                                 step_program)
from deepspeed_tpu.ops.flash_attention import flash_attention
from deepspeed_tpu.ops.fused_optimizer import (fused_adamw_flat,
                                               fused_lamb_flat,
                                               fused_lion_flat)
from deepspeed_tpu.ops.normalization import layernorm, rmsnorm
from deepspeed_tpu.ops import paged_attention as paged_ops
from deepspeed_tpu.ops.paged_attention import (MAX_KERNEL_Q_ROWS, KVPages,
                                               kernel_blocks,
                                               paged_attention,
                                               paged_grid_attention,
                                               walk_blocks, write_kv)
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


def kernel_calls(text: str, kernel: str) -> list:
    """The lines of a compiled program's text that are Mosaic custom calls
    of the Pallas kernel named ``kernel``."""
    return [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            and kernel in line]


def compile_for_chip(fn, *shapes, kernel: str, calls: int = 1) -> str:
    """Compile ``fn`` and require ``calls`` Mosaic custom calls of the
    Pallas kernel named ``kernel`` in the executable's text, which is
    returned."""
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    found = len(kernel_calls(text, kernel))
    assert found == calls, (kernel, found)
    return text


def scoped_vmem_asked(text: str, kernel: str) -> list:
    """What the Mosaic custom calls named ``kernel*`` of a compiled
    program ASK for as their scoped-VMEM limit (``vmem_limit_bytes``): an
    empty list a call under the default, which is what the program's text
    shows as ``"scoped_memory_configs":[]`` (beside
    ``used_scoped_memory_configs``, what the call was given)."""
    calls = kernel_calls(text, kernel)
    assert calls, kernel
    return [re.search(r'"scoped_memory_configs":\[([^\]]*)\]', line).group(1)
            for line in calls]


# -- flash attention (the training default, and serving's fresh prefill) ----

def _qkv(chip, batch=1, heads=HEADS):
    return (chip((batch, heads, SEQ, HEAD_DIM), jnp.bfloat16),) * 3


@pytest.mark.parametrize("batch,heads", [(1, 32), (4, 16)])
def test_flash_forward(chip, batch, heads):
    compile_for_chip(
        lambda q, k, v: flash_attention(q, k, v, interpret=False),
        *_qkv(chip, batch, heads), kernel="flash_attention_fwd")


#: the default scoped VMEM limit: what a kernel that asks for no limit of its
#: own may use (PERF.md, PR 27: a kernel that asks for more hangs the chip)
DEFAULT_SCOPED_VMEM = 16 * 2 ** 20


def flash_kernels(text: str) -> dict:
    """name -> the scoped VMEM the chip's compiler gave it, for the flash
    kernels' Mosaic custom calls of a compiled program's text."""
    found = {}
    for line in kernel_calls(text, "flash_attention"):
        name = re.search(r"(flash_attention_\w+?)\)*/pallas_call", line)
        used = re.search(r'"used_scoped_memory_configs":\[[^\]]*"size":"(\d+)"',
                         line)
        found[name.group(1)] = int(used.group(1))
    return found


@pytest.mark.parametrize("kv_heads,seq,window", [
    (32, SEQ, None), (8, SEQ, None), (8, SEQ, 4096), (8, SEQ, 1024),
    (8, 2560, None), (8, 2 * SEQ, None), (2, SEQ, None)])
def test_flash_forward_and_backward(chip, kv_heads, seq, window):
    """The forward and its backward with K/V at their own head count, at
    the train cell's row (four blocks: every walk written out), at five and
    eight blocks (walks in loops) and at 16 query heads a KV head.  Every
    name begins as the benchmark's readers expect
    (``benchmark/metrics/flash_*.json``), no kernel asks for a VMEM limit
    and each stays under the default one."""
    def grads(q, k, v):
        return jax.grad(lambda q, k, v: flash_attention(
            q, k, v, window=window, interpret=False
        ).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)
    q = chip((2, HEADS, seq, HEAD_DIM), jnp.bfloat16)
    kv = chip((2, kv_heads, seq, HEAD_DIM), jnp.bfloat16)
    text = jax.jit(grads).lower(q, kv, kv).compile().as_text()
    kernels = flash_kernels(text)
    assert sorted(kernels) == ["flash_attention_bwd_dkv",
                               "flash_attention_bwd_dq",
                               "flash_attention_fwd"]
    assert scoped_vmem_asked(text, "flash_attention") == [""] * len(kernels)
    assert max(kernels.values()) < DEFAULT_SCOPED_VMEM, kernels


def test_flash_short_unaligned_sequence(chip):
    """A prefill bucket below one lane tile: the [1, S] lse row block
    equals the array, which the lowering accepts as it does (8, 128)
    multiples."""
    q = chip((8, HEADS, 48, HEAD_DIM), jnp.bfloat16)
    compile_for_chip(
        lambda q, k, v: flash_attention(q, k, v, interpret=False),
        q, q, q, kernel="flash_attention_fwd")


# -- paged attention (every serving step) -----------------------------------

def _pool(chip, int8, layers=2, pages=POOL):
    shape = (layers, pages + 1, 2, KV_HEADS, PAGE, HEAD_DIM)
    return (KVPages(chip(shape, jnp.int8), chip(shape[:-1], jnp.float32))
            if int8 else chip(shape, jnp.bfloat16))


def _paged_args(chip, slots, rows, int8, page_slots=SEQ // PAGE):
    """(q, pool, layer, page table, start_pos, q_lens)"""
    return (chip((slots, rows, HEADS, HEAD_DIM), jnp.bfloat16),
            _pool(chip, int8), chip((), jnp.int32),
            chip((slots, page_slots), jnp.int32),
            chip((slots,), jnp.int32), chip((slots,), jnp.int32))


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("window", [None, 4096], ids=["full", "window"])
@pytest.mark.parametrize("slots,rows,page_slots,heads", [
    (64, 1, 32, 8), (64, 1, 8, 8), (64, 1, 64, 8), (8, 128, 32, 4),
    (2, 512, 32, 1),
    # K x Q*G on each side of where a step stops holding every head
    (8, 32, 32, 8), (8, 96, 32, 4)],
    ids=["decode", "decode-8", "decode-64", "mixed", "chunk", "all-heads",
         "half-the-heads"])
def test_paged_attention(chip, slots, rows, page_slots, heads, window, int8):
    assert kernel_blocks(rows * (HEADS // KV_HEADS), KV_HEADS, HEAD_DIM,
                         PAGE, page_slots, 2, 1 if int8 else 2,
                         int8) == (heads, 8)
    compile_for_chip(
        lambda q, kv, layer, table, start, lens: paged_attention(
            q, kv, layer, table, start, lens, use_kernel=True,
            window=window, interpret=False),
        *_paged_args(chip, slots, rows, int8, page_slots),
        kernel="paged_attention")


#: (configuration file, query heads of the kind, blocks at Q = 1 and at a
#: 128-token prompt row, each at the page buckets 8 and 40): what
#: ``kernel_blocks`` gives the five serving configurations' attention
#: layers.  The latent family's kernels (``mla_attention_*``) take no
#: blocks from it.
SERVING_BLOCKS = [
    ("mistral-7b-serve-8l", 32, (8, 8), (4, 8)),
    ("laguna-s-serve-5l-ep16", 48, (8, 8), (2, 8)),          # full layers
    ("laguna-s-serve-5l-ep16", 72, (8, 8), (2, 8)),          # window layers
    ("jamba2-3b-serve-28l", 20, (1, 8), (1, 4)),
    # 30 KV heads, one query head each, a page of 960 KB: a decode step is
    # sized by its bytes, all 30 heads of 2 slots (1.9 MB; PR 41: timed
    # alone the quickest of the blocks that fit, (15, 8) before).  A prompt
    # row's 128 query rows a head leave no room for every head: its blocks
    # are the rows', the widest group and the largest divisor that fits
    ("olmo-hybrid-7b-serve-4l", 30, (30, 2), (10, 8)),
    # 4 KV heads, 7 query heads each, a page of 128 KB: every head of 8
    # slots a decode step (1 MB), half the heads of 8 a prompt row's
    # (both kinds of layer: one head count)
    ("smallthinker-21b-serve-8l", 28, (4, 8), (2, 8)),
]


def _attention_shape(name: str) -> tuple:
    """(KV heads, head dim, page size) of a serving configuration of the
    benchmark, whose pages are bfloat16."""
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           name + ".json")) as f:
        c = json.load(f)
    assert c["engine"]["kv_dtype"] == "bfloat16"
    return (c["num_key_value_heads"], c["head_dim"],
            c["engine"]["page_size"])


@pytest.mark.parametrize("name,heads,decode,prompt", SERVING_BLOCKS,
                         ids=[f"{n}-{h}" for n, h, _, _ in SERVING_BLOCKS])
def test_kernel_blocks_of_the_serving_configurations(name, heads, decode,
                                                     prompt):
    K, D, page = _attention_shape(name)
    assert heads % K == 0
    for q, want in ((1, decode), (128, prompt)):
        for buckets in (8, 40):
            got = kernel_blocks(q * (heads // K), K, D, page, buckets, 2, 2)
            assert got == want, (name, q, buckets, got)
            assert K % got[0] == 0 and buckets % got[1] == 0
            assert got != (1, 1) or K == 1


#: (id, configuration file, query heads of the kind, decode rows, table
#: width, window, the walk's (tile, chunk) in page slots): the decode call
#: of each serving configuration's attention layers at its cell's rows and
#: page bucket.  A tile is ``STEP_BYTES`` and a chunk ``CHUNK_BYTES`` of
#: whole pages whatever the bucket: 8 and 2 slots of a 256 KB page, 2 and
#: 1 of a 960 KB one, the table's 40 (rounded up to chunks of 16) of a
#: 32 KB one
SERVING_WALKS = [
    ("mistral-64", "mistral-7b-serve-8l", 32, 64, 8, None, (8, 2)),
    ("mistral-256", "mistral-7b-serve-8l", 32, 256, 8, None, (8, 2)),
    ("laguna-full", "laguna-s-serve-5l-ep16", 48, 256, 40, None, (8, 2)),
    ("laguna-window", "laguna-s-serve-5l-ep16", 72, 256, 16, 512, (8, 2)),
    ("jamba", "jamba2-3b-serve-28l", 20, 256, 40, None, (48, 16)),
    ("olmo", "olmo-hybrid-7b-serve-4l", 30, 256, 40, None, (2, 1)),
    # a 128 KB page: 16 slots a tile, 4 a chunk; the window group's table
    # is 72 slots at a window of 4,096 (65 live pages and the one being
    # filled, in whole groups of 8), of which the walk visits the live ones
    ("smallthinker-full", "smallthinker-21b-serve-8l", 28, 256, 40, None,
     (16, 4)),
    ("smallthinker-window", "smallthinker-21b-serve-8l", 28, 256, 72, 4096,
     (16, 4)),
]


@pytest.mark.parametrize("name,heads,rows,page_slots,window,blocks",
                         [c[1:] for c in SERVING_WALKS],
                         ids=[c[0] for c in SERVING_WALKS])
def test_the_decode_walk_at_the_serving_shapes(chip, name, heads, rows,
                                               page_slots, window, blocks):
    """A decode step's call lowers for the chip as the walk (PR 45) at the
    five serving shapes, under the default scoped-VMEM limit, with the
    pool left where it is (an ``ANY`` operand: no pipelined page)."""
    K, D, page = _attention_shape(name)
    assert walk_blocks(heads // K, K, D, page, page_slots, 2, 2) == blocks
    kernel = "paged_attention_window" if window else "paged_attention"
    text = compile_for_chip(
        lambda q, kv, layer, table, start, lens: paged_attention(
            q, kv, layer, table, start, lens, use_kernel=True,
            window=window, interpret=False, name=kernel),
        chip((rows, 1, heads, D), jnp.bfloat16),
        chip((2, 257, 2, K, page, D), jnp.bfloat16), chip((), jnp.int32),
        chip((rows, page_slots), jnp.int32), chip((rows,), jnp.int32),
        chip((rows,), jnp.int32), kernel=kernel + "_decode")
    assert walk_calls(text) == 1
    assert scoped_vmem_asked(text, kernel + "_decode") == [""]


def fetch_table_steps(page_slots: int, group: int) -> int:
    """Selects ``fetch_table`` makes of a table ``page_slots`` wide under a
    page group of ``group``: one a doubling of the shift."""
    return max(int(np.ceil(np.log2(page_slots / group))), 0)


def fetch_table_selects(text: str) -> int:
    """The selects of ``fetch_table`` (its operations run under a scope of
    that name) that a compiled program's text holds, inside fusions too."""
    return sum(" select(" in line and "fetch_table/" in line
               for line in text.splitlines())


def asked_of_fetch_table(monkeypatch) -> list:
    """(page slots, group) of every call of ``fetch_table`` from here on,
    as they are traced."""
    asked, rule = [], paged_ops.fetch_table
    monkeypatch.setattr(
        paged_ops, "fetch_table", lambda table, group: asked.append(
            (table.shape[1], group)) or rule(table, group))
    return asked


def walk_calls(text: str) -> int:
    """The Mosaic custom calls of a compiled program's text that are the
    decode walk (``paged_walk_attention``): a paged attention kernel whose
    pool operand stays where it is (``pl.ANY``: the kernel copies a row's
    pages itself), so it has no pipelined page operand: its operands are
    the five prefetched scalars, the queries and the pool."""
    return sum(len(re.findall(r"%[\w.-]+", line.split("custom-call(")[1]
                              .split(")")[0])) == 7
               for line in kernel_calls(text, "paged_attention"))


def mosaic_texts(lowered_text: str) -> list:
    """The Mosaic modules of a lowered program's Pallas calls, as text with
    their source locations dropped (a caller's moved line is no change of
    the kernel)."""
    import base64

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir
    texts = []
    for body in re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22',
                           lowered_text):
        context = mlir.make_ir_context()
        context.allow_unregistered_dialects = True
        with context:
            module = ir.Module.parse(base64.b64decode(body))
            texts.append(module.operation.get_asm(enable_debug_info=False))
    return texts


#: (KV heads, query heads, window) of the serving configurations' attention
#: layers, and per (Q, page slots) the first 16 hex digits of the SHA-256
#: of the kernel's Mosaic text at 256 decode rows / 4 prompt rows of 128
#: over a ``[2, 257, 2, K, 64, 128]`` bfloat16 pool.  Read on PR 41's tree
#: and unmoved by PR 44 (the fetch table changes what the index maps are
#: GIVEN, nothing of the kernel): a change here is a change of the kernel,
#: and every cached step program that holds it forms anew.
KERNEL_TEXTS = {
    "mistral": (8, 32, None, {
        (1, 8): "d87ed520d42599dd", (1, 40): "4346f45a471ff353",
        (128, 8): "bd540c30e7a0837d", (128, 40): "94034f8d4b1da81c"}),
    "laguna-full": (8, 48, None, {
        (1, 8): "9437ebfb0c3f7669", (1, 40): "60c94cc8b06ec047",
        (128, 8): "988b9cdb8d1b436b", (128, 40): "f0e95fe0e59fba59"}),
    # the window group's own table too: 16 slots a decode row, 18 a
    # 128-token prompt row
    "laguna-window": (8, 72, 512, {
        (1, 8): "b5e728ee121cef50", (1, 40): "bcda121c25ac0b4a",
        (128, 8): "41a4a1bead0ed216", (128, 40): "2b23d6777eb45ecb",
        (1, 16): "aa27fecb0685e40d", (128, 18): "cf9073bcfb3b90e6"}),
    "jamba": (1, 20, None, {
        (1, 8): "19cd7c44e5e89967", (1, 40): "ef1e12b50a457918",
        (128, 8): "d7f4616537d90c19", (128, 40): "c0e67a71ec666752"}),
    "olmo": (30, 30, None, {
        (1, 8): "cf278955ef408b12", (1, 40): "2ae4c60734b977b2",
        (128, 8): "625d87dc1a61f275", (128, 40): "d328baadb1ce3cb9"}),
}


@pytest.mark.parametrize("name,rows,page_slots", [
    (name, rows, slots) for name, shape in KERNEL_TEXTS.items()
    for rows, slots in shape[3]],
    ids=lambda v: str(v))
def test_the_fetch_table_leaves_the_kernels_text_alone(
        chip, monkeypatch, name, rows, page_slots):
    """The grid form's lowered text for the described chip is the
    parent's at every pinned shape (at Q = 1 too, which int8 pages and
    ALiBi still take: PR 45 moved its arithmetic into a function both
    forms call and changed nothing of it), and is the same whether its
    index maps read the fetch table or the engine's table as given: the
    rule is integer operations BESIDE the kernel."""
    import hashlib
    K, heads, window, digests = KERNEL_TEXTS[name]
    kernel = "paged_attention_window" if window else "paged_attention"

    def texts():
        lowered = jax.jit(
            lambda q, kv, table, start: paged_grid_attention(
                q, kv, 1, table, start, window=window, name=kernel)
        ).lower(chip((256 if rows == 1 else 4, rows, heads, 128),
                     jnp.bfloat16),
                chip((2, 257, 2, K, PAGE, 128), jnp.bfloat16),
                chip((256 if rows == 1 else 4, page_slots), jnp.int32),
                chip((256 if rows == 1 else 4,), jnp.int32)).as_text()
        return lowered, mosaic_texts(lowered)

    program, under_rule = texts()
    monkeypatch.setattr(paged_ops, "fetch_table", lambda table, group: table)
    as_given, without = texts()
    assert len(under_rule) == 1 and under_rule == without
    group = kernel_blocks(rows * (heads // K), K, 128, PAGE, page_slots,
                          2, 2)[1]
    # ... and the rule is in the program wherever a row is several groups
    assert (program.count("stablehlo.select")
            - as_given.count("stablehlo.select")
            == fetch_table_steps(page_slots, group))
    assert hashlib.sha256(under_rule[0].encode()).hexdigest()[:16] \
        == digests[rows, page_slots]


@pytest.mark.parametrize("slots,rows,page_slots", [
    (256, 1, 40), (256, 1, 8), (4, 128, 8), (4, 128, 40)],
    ids=["decode-40", "decode-8", "prompt-8", "prompt-40"])
def test_paged_attention_at_thirty_kv_heads(chip, slots, rows, page_slots):
    """One query head a KV head, 30 of them (a page is 960 KB a layer):
    the blocks ``kernel_blocks`` gives there compile under the default
    VMEM limit."""
    compile_for_chip(
        lambda q, kv, layer, table, start, lens: paged_attention(
            q, kv, layer, table, start, lens, use_kernel=True,
            interpret=False),
        chip((slots, rows, 30, HEAD_DIM), jnp.bfloat16),
        chip((1, 257, 2, 30, PAGE, HEAD_DIM), jnp.bfloat16),
        chip((), jnp.int32), chip((slots, page_slots), jnp.int32),
        chip((slots,), jnp.int32), chip((slots,), jnp.int32),
        kernel="paged_attention")


@pytest.mark.parametrize("slots,rows,int8", [
    (64, 1, False), (16, 5, True), (8, 128, False), (8, 128, True)],
    ids=["decode", "spec-int8", "mixed", "mixed-int8"])
def test_paged_attention_under_alibi(chip, slots, rows, int8):
    """The bias costs a step score tiles of its own: ``kernel_blocks``
    counts them, so a chunk holds fewer heads a step than without."""
    from deepspeed_tpu.models.transformer import alibi_slopes
    slopes = alibi_slopes(HEADS)
    compile_for_chip(
        lambda q, kv, layer, table, start, lens: paged_attention(
            q, kv, layer, table, start, lens, use_kernel=True,
            alibi_slopes=slopes, interpret=False),
        *_paged_args(chip, slots, rows, int8), kernel="paged_attention")


def test_paged_attention_largest_query_block(chip):
    """``MAX_KERNEL_Q_ROWS`` is what the VMEM budget actually compiles:
    Q x groups = 4096 rows fit the 16 MiB scoped limit (8192 need 26.85
    MiB and are refused, so larger blocks take the dense-gather path)."""
    rows = MAX_KERNEL_Q_ROWS // (HEADS // KV_HEADS)
    compile_for_chip(
        lambda q, kv, layer, table, start, lens: paged_attention(
            q, kv, layer, table, start, lens, use_kernel=True,
            interpret=False),
        *_paged_args(chip, 2, rows, int8=False), kernel="paged_attention")
    with pytest.raises(Exception, match="vmem"):
        compile_for_chip(
            lambda q, kv, layer, table, start, lens: paged_attention(
                q, kv, layer, table, start, lens, use_kernel=True,
                interpret=False),
            *_paged_args(chip, 1, 2 * rows, int8=False),
            kernel="paged_attention")


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("slots,rows", [(64, 1), (8, 128), (2, 512), (16, 5)],
                         ids=["decode", "mixed", "chunk", "spec"])
def test_kv_write(chip, slots, rows, int8):
    """The cache write's tile kernel: a whole page of every head per grid
    step, the one-hot shift as a matmul, the pool aliased in -> out."""
    new = chip((slots, rows, KV_HEADS, HEAD_DIM), jnp.bfloat16)
    _, kv, layer, table, start, lens = _paged_args(chip, slots, rows, int8)
    compile_for_chip(
        lambda kv, layer, k, v, table, start, lens: write_kv(
            kv, layer, k, v, table, start, lens, use_kernel=True,
            interpret=False),
        kv, layer, new, new, table, start, lens, kernel="kv_write")


# -- whole serve step programs: the KV pool never leaves its buffer ---------

#: step-cache keys of the benchmark's serving cell (64 decoding rows, one
#: 4 x 128 prefill piece, 8 pages a row), at two layers
STEP_KEYS = {
    "chain": (64, 1, 8, False, "chain", 64, True),
    "sample-fresh": (4, 128, 8, True, "sample", True),
    "mixed": (64, 1, 8, False, "mixed", 4, 128, 8, True, True),
}
#: opcodes (and the fusions XLA names after them) that move data
MOVERS = ("copy", "transpose", "dynamic-slice", "dynamic-update-slice")
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
             "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
             "u64": 8}


def _array_bytes(dtype: str, dims: str) -> int:
    """Bytes of an HLO array type ``dtype[dims]``."""
    return _ITEMSIZE[dtype] * int(np.prod(
        [int(d) for d in dims.split(",") if d] or [1]))


_HLO_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*([a-z0-9]+)"
                       r"\[([\d,]*)\](?:\{[^}]*\})?\s+([\w\-]+)\(")


def pool_sized_movers(text: str, floor: int) -> list:
    """(name, opcode, shape) of every instruction of a compiled program,
    inside fused computations too, that moves data and yields an array of
    ``floor`` bytes or more."""
    found = []
    for line in text.splitlines():
        m = _HLO_LINE.match(line)
        if not m or m.group(2) not in _ITEMSIZE:
            continue
        name, dtype, dims, opcode = m.groups()
        size = _array_bytes(dtype, dims)
        moves = opcode in MOVERS or (
            opcode == "fusion" and any(w in name for w in MOVERS))
        if moves and size >= floor:
            found.append((name, opcode, f"{dtype}[{dims}]"))
    return found


def stack_shaped_movers(text: str, params) -> list:
    """The movers of a compiled program that yield an array of a whole
    weight stack's shape (a leaf ``[layers > 1, ...]`` of three or more
    dimensions: the scanned projections, the held experts).  A program
    that ran the trunk a second time re-laid the stacked ``wq``, ``wk``
    and ``wv`` out before its second layer loop (``copy.91-93``, 0.8 ms
    of every mixed step at the benchmark's widths; PERF.md, PR 30)."""
    stacks = {",".join(str(d) for d in leaf.shape)
              for leaf in jax.tree.leaves(params)
              if leaf.ndim >= 3 and leaf.shape[0] > 1}
    return [m for m in pool_sized_movers(text, 0)
            if m[2][m[2].index("[") + 1:-1] in stacks]


def test_pool_sized_movers_reads_a_program_text():
    text = """
  %copy.98 = bf16[1025,2,8,64,128]{4,2,3,1,0} copy(%x)
  ROOT %copy_dynamic-update-slice_fusion.2 = bf16[8,1025,2,8,64,128]{5,4,3,2,1,0:T(8,128)(2,1)} fusion(%a, %b), kind=kLoop
  %kv_write_decode.9 = bf16[8,1025,2,8,64,128]{5,4,3,2,1,0} custom-call(%p)
  %copy.3 = bf16[64,1,8,128]{3,2,1,0} copy(%y)
  %get-tuple-element.7 = bf16[8,1025,2,8,64,128]{5,4,3,2,1,0} get-tuple-element(%w), index=1
"""
    assert [m[0] for m in pool_sized_movers(text, 268_697_600)] == [
        "copy.98", "copy_dynamic-update-slice_fusion.2"]
    weights = {"wq": np.zeros((8, 1025, 2, 8, 64, 128), np.int8),
               "norm": np.zeros((8, 128)), "one": np.zeros((1, 8, 64, 128))}
    assert [m[0] for m in stack_shaped_movers(text, weights)] == [
        "copy_dynamic-update-slice_fusion.2"]


@pytest.mark.parametrize("kind,int8,scan_layers", [
    # the cell's three kinds; then the mixed program (both segments in
    # every layer), for the quantized pool and the unrolled layer loop
    ("chain", False, True), ("sample-fresh", False, True),
    ("mixed", False, True), ("mixed", True, True), ("mixed", False, False),
    ("mixed", True, False)],
    ids=lambda v: {True: "y", False: "n"}.get(v, v))
def test_step_program_leaves_the_pool_in_place(chip, monkeypatch, kind, int8,
                                               scan_layers):
    """Inside a compiled step program the KV pool never leaves its donated
    buffer (PR 25): (a) no copy, transpose, dynamic-slice or
    dynamic-update-slice, alone or as a fusion, yields one pool layer's
    bytes or more; (b) the program's temporaries stay under one pool
    layer.  Mistral-7B widths, two layers, a 512-page pool (1024 int8
    pages: a layer of the pool outweighs every weight matrix, so the floor
    of (a) catches the pool alone)."""
    from deepspeed_tpu.accelerator import real_accelerator
    from deepspeed_tpu.inference.v2.model_implementations import (
        MistralInferenceModel)
    from deepspeed_tpu.inference.v2.ragged import KVCacheConfig
    from deepspeed_tpu.models.llama import LlamaForCausalLM

    # trace the paths the chip takes (Pallas kernels), not the CPU's
    monkeypatch.setattr(real_accelerator, "device_platform", lambda: "tpu")
    layers, pages = 2, POOL * (2 if int8 else 1)
    model = LlamaForCausalLM(
        "7b", intermediate_size=14336, num_kv_heads=KV_HEADS,
        sliding_window=4096, num_layers=layers, max_seq_len=4096,
        scan_layers=scan_layers)
    params = jax.eval_shape(lambda key: jax.tree.map(
        lambda x: x.astype(model.cfg.dtype), model.init_params(key)),
        jax.random.key(0))
    serve = MistralInferenceModel(model.cfg, params, kv_config=KVCacheConfig(
        num_layers=layers, kv_heads=KV_HEADS, head_dim=HEAD_DIM,
        page_size=PAGE, num_pages=pages,
        quantization="int8" if int8 else "none"))
    pool = _pool(chip, int8, layers, pages)
    key = StepKey.parse(STEP_KEYS[kind])
    avals = jax.tree.map(
        lambda a: chip(a.shape, a.dtype) if hasattr(a, "shape") else a,
        step_avals(serve, key, pool))
    compiled = jax.jit(step_program(serve, key),
                       donate_argnums=(1,)).lower(*avals).compile()
    payload = jax.tree.leaves(pool)[0]
    layer_bytes = int(np.prod(payload.shape[1:])) * payload.dtype.itemsize
    text = compiled.as_text()
    assert pool_sized_movers(text, layer_bytes) == []
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes
    # a page bucket of 8 is one page group: the kernel's index maps read
    # the engine's table, and the program holds nothing of the fetch table
    assert fetch_table_selects(text) == 0
    if scan_layers:
        # one trunk pass: no stacked weight is re-laid out for a second loop
        assert stack_shaped_movers(text, params) == []


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


# -- the latent kind: MLA kernels, held experts, its step programs -----------

MLA_HEADS, MLA_PLANE, MLA_RANK = 128, 640, 512      # openPangu-Ultra-MoE
MLA_POOL = 8192


def _latent_pool(chip, layers=5, pages=MLA_POOL):
    return chip((layers, pages + 1, 1, 1, PAGE, MLA_PLANE), jnp.bfloat16)


@pytest.mark.parametrize("rows,pages", [(256, 32), (256, 8), (64, 64),
                                        (256, 64)])
def test_mla_decode_kernel(chip, rows, pages):
    """Rows of the cell's step programs at its page buckets; (256, 64) is
    the bucket the cell's longest rows ask for.  Three ``[512, 640]``
    tiles, the query, float32 scores and accumulator fit the DEFAULT
    scoped VMEM: a latent step program that asked for more hung the chip
    (``PERF.md`` section 6, PR 27)."""
    from deepspeed_tpu.ops.mla_attention import mla_paged_attention
    text = compile_for_chip(
        lambda q, kv, pt, sp, ql: mla_paged_attention(
            q, kv, jnp.int32(2), pt, sp, ql, rank=MLA_RANK, sm_scale=0.07,
            use_kernel=True),
        chip((rows, 1, MLA_HEADS, MLA_PLANE), jnp.bfloat16),
        _latent_pool(chip), chip((rows, pages), jnp.int32),
        chip((rows,), jnp.int32), chip((rows,), jnp.int32),
        kernel="mla_attention_decode")
    assert scoped_vmem_asked(text, "mla_attention_decode") == [""]


@pytest.mark.parametrize("rows,q,kernel", [
    (256, 1, "latent_write_decode"), (4, 128, "latent_write_prefill")])
def test_latent_write_kernel(chip, rows, q, kernel):
    from deepspeed_tpu.ops.mla_attention import latent_write
    compile_for_chip(
        lambda plane, kv, pt, sp, ql: latent_write(
            kv, jnp.int32(1), plane, pt, sp, ql, use_kernel=True),
        chip((rows, q, MLA_PLANE), jnp.bfloat16), _latent_pool(chip),
        chip((rows, 32), jnp.int32), chip((rows,), jnp.int32),
        chip((rows,), jnp.int32), kernel=kernel)


@pytest.mark.parametrize("rows,q", [(4, 128), (1, 1024)])
def test_mla_prefill_kernel(chip, rows, q):
    """192-wide scores, 128-wide values: two head sizes in one kernel."""
    from deepspeed_tpu.ops.mla_attention import mla_fresh_attention
    compile_for_chip(
        lambda q_, k, v: mla_fresh_attention(q_, k, v, sm_scale=0.07,
                                             use_kernel=True),
        chip((rows, q, MLA_HEADS, 192), jnp.bfloat16),
        chip((rows, q, MLA_HEADS, 192), jnp.bfloat16),
        chip((rows, q, MLA_HEADS, 128), jnp.bfloat16),
        kernel="mla_attention_prefill")


@pytest.mark.parametrize("tokens", [256, 512])
def test_moe_expert_ffn_kernel(chip, tokens):
    """16 held experts of width 2048 over hidden 7680, the layers' stack
    addressed by the layer's index."""
    from deepspeed_tpu.moe.held import held_experts_ffn
    stack = {n: chip((4, 16, 2048, 7680), jnp.bfloat16)
             for n in ("wg", "wu", "wd")}
    compile_for_chip(
        lambda x, e, w, p, l: held_experts_ffn(x, e, w, p, 32, layer=l,
                                               use_kernel=True),
        chip((tokens, 7680), jnp.bfloat16), chip((tokens, 8), jnp.int32),
        chip((tokens, 8), jnp.float32), stack, chip((), jnp.int32),
        kernel="moe_expert_ffn")


#: sets of weight slices in the expert kernel's ring, by served family (the
#: families' shapes: ``tools/time_expert_tiles.py::FAMILIES``)
EXPERT_RING_SETS = {"pangu": 2, "laguna": 3, "smallthinker": 3}


@pytest.mark.parametrize("name", sorted(EXPERT_RING_SETS))
def test_the_expert_kernels_walk_fits_the_default_vmem(chip, name):
    """The three served families' call at 384 tokens (a mixed step's, tiles
    of 64 rows): the walk compiles for the chip without asking for more
    scoped VMEM than the default, its ring is the bytes' (``ring_sets``) and
    its scratch stays under 12 MB: a kernel that asked for more hung the
    chip inside a mixed step program (PERF.md, PR 27)."""
    import os
    import sys

    from deepspeed_tpu.moe import held
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    from time_expert_tiles import FAMILIES
    E, scored, k, F, e, act, told = FAMILIES[name]
    sets = EXPERT_RING_SETS[name]
    assert held.ring_sets(held.FF_SLICE, e, 2) == sets
    # two tiles of rows in, one out, the float32 sum, the ring
    assert (3 * 2 + 4) * 64 * e + sets * 3 * held.FF_SLICE * e * 2 \
        < 12 * 10 ** 6
    stack = {n: chip((2, E, F, e), jnp.bfloat16) for n in ("wg", "wu", "wd")}

    def layer(x, ex, w, p, l):
        plan = held.plan_rows(ex, None, 0, E, scored if told else 0)
        assert plan[0].shape[0] // plan[2].shape[0] == 64
        return held.held_experts_ffn(x, ex, w, p, 0, layer=l, plan=plan,
                                     use_kernel=True, act=act)

    text = compile_for_chip(
        layer, chip((384, e), jnp.bfloat16), chip((384, k), jnp.int32),
        chip((384, k), jnp.float32), stack, chip((), jnp.int32),
        kernel="moe_expert_ffn")
    assert set(scoped_vmem_asked(text, "moe_expert_ffn")) == {""}


PANGU_STEP_KEYS = {
    "chain": (256, 1, 32, False, "chain", 256, True),
    "mixed": (256, 1, 64, False, "mixed", 4, 128, 8, True, True),
}


@pytest.mark.parametrize("kind", sorted(PANGU_STEP_KEYS))
def test_pangu_step_program_moves_no_pool_and_no_expert_stack(
        chip, monkeypatch, kind):
    """The benchmark's cell at published widths (1 dense + 2 routed
    layers here): inside a compiled step program neither the latent pool
    nor a layer of the held experts' stack (503 MB: scanned, it would be
    sliced out for the custom call) is copied, and the temporaries stay
    under that too."""
    import json
    import os

    from flax.core import meta

    from benchmark.builders.serve_pangu_moe import source_of
    from deepspeed_tpu.accelerator import real_accelerator
    from deepspeed_tpu.inference.v2.model_implementations import (
        PanguUltraMoEInferenceModel)
    from deepspeed_tpu.inference.v2.ragged import KVCacheConfig
    from deepspeed_tpu.models.pangu_moe import PanguUltraMoEForCausalLM

    monkeypatch.setattr(real_accelerator, "device_platform", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "pangu-ultra-moe-serve-5l-ep16.json")) as f:
        config = json.load(f)
    layers = 3
    model = PanguUltraMoEForCausalLM(
        dict(source_of(config, False), num_hidden_layers=layers))
    params = jax.eval_shape(lambda k: meta.unbox(model.init_params(k)),
                            jax.random.key(0))
    serve = PanguUltraMoEInferenceModel(model.cfg, params, kv_config=(
        KVCacheConfig(num_layers=layers, kv_heads=1, head_dim=MLA_PLANE,
                      planes=1, page_size=PAGE, num_pages=MLA_POOL)))
    pool = _latent_pool(chip, layers)
    key = StepKey.parse(PANGU_STEP_KEYS[kind])
    avals = jax.tree.map(
        lambda a: chip(a.shape, a.dtype) if hasattr(a, "shape") else a,
        step_avals(serve, key, pool))
    compiled = jax.jit(step_program(serve, key),
                       donate_argnums=(1,)).lower(*avals).compile()
    text = compiled.as_text()
    for kernel in ("mla_attention_decode", "latent_write_decode",
                   "moe_expert_ffn"):
        assert any('custom_call_target="tpu_custom_call"' in line
                   and kernel in line for line in text.splitlines()), kernel
    # one call a layer stack (scanned), none asking past the default VMEM
    assert set(scoped_vmem_asked(text, "mla_attention_decode")) == {""}
    expert_layer = 16 * 2048 * 7680 * 2
    assert expert_layer < int(np.prod(pool.shape[1:])) * 2
    assert pool_sized_movers(text, expert_layer) == []
    assert stack_shaped_movers(text, params) == []
    assert compiled.memory_analysis().temp_size_in_bytes < expert_layer


LAGUNA_STEP_KEYS = {
    "chain": (256, 1, 32, False, "chain", 256, True),
    "mixed": (256, 1, 64, False, "mixed", 4, 128, 8, True, True),
    # a prefill that is no fresh one (a later chunk of a prompt): the
    # ragged kernel's 128-token block at 6 and 9 query heads a KV head
    "chunk": (4, 128, 16, False, "sample", True),
    # the cell's own page bucket (its lattice's top of 40 pages, no power
    # of two: five groups of 8 page slots a decode row)
    "chain-p40": (256, 1, 40, False, "chain", 256, True),
    "mixed-p40": (256, 1, 40, False, "mixed", 2, 128, 8, True, True),
    "drain-p40": (16, 1, 40, False, "chain", 32, True),
}


@pytest.mark.parametrize("kind", sorted(LAGUNA_STEP_KEYS))
def test_laguna_step_program_moves_no_pool_and_no_expert_stack(
        chip, monkeypatch, kind):
    """The benchmark's cell at published widths (all five layers: the
    dense one and a whole period): the step programs lower for the chip
    with GQA groups of 6 and 9 (a decode block of 9 rows a KV head, a
    prefill block of 1,152), the window group's calls run under a name of
    their own, and neither group's pool nor the held experts' stack (four
    layers of 302 MB) is copied, sliced out or re-laid out."""
    import json
    import os

    from flax.core import meta

    from benchmark.builders.serve_laguna import source_of
    from deepspeed_tpu.accelerator import real_accelerator
    from deepspeed_tpu.inference.v2.model_implementations import (
        LagunaInferenceModel)
    from deepspeed_tpu.inference.v2.ragged import KVCacheConfig
    from deepspeed_tpu.models.laguna import LagunaForCausalLM

    monkeypatch.setattr(real_accelerator, "device_platform", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "laguna-s-serve-5l-ep16.json")) as f:
        config = json.load(f)
    model = LagunaForCausalLM(source_of(config, False))
    params = jax.eval_shape(lambda k: meta.unbox(model.init_params(k)),
                            jax.random.key(0))
    pages = {"full": 1024, "window": 512}
    serve = LagunaInferenceModel(
        model.cfg, params,
        kv_config=KVCacheConfig(num_layers=2, kv_heads=8, head_dim=128,
                                page_size=PAGE, num_pages=pages["full"]),
        window_kv_config=KVCacheConfig(
            num_layers=3, kv_heads=8, head_dim=128, page_size=PAGE,
            num_pages=pages["window"]))
    pool = (chip((2, pages["full"] + 1, 2, 8, PAGE, 128), jnp.bfloat16),
            chip((3, pages["window"] + 1, 2, 8, PAGE, 128), jnp.bfloat16))
    key = StepKey.parse(LAGUNA_STEP_KEYS[kind])
    avals = jax.tree.map(
        lambda a: chip(a.shape, a.dtype) if hasattr(a, "shape") else a,
        step_avals(serve, key, pool))
    # the window group's table rides the page table: 16 slots and a base
    assert (key.S, key.P + 16 + 1) in [a.shape for a in avals[2:]]
    asked = asked_of_fetch_table(monkeypatch)
    compiled = jax.jit(step_program(serve, key),
                       donate_argnums=(1,)).lower(*avals).compile()
    text = compiled.as_text()
    if key.kind == "chain":
        # decode rows alone: both page groups' calls walk each row's own
        # pages (PR 45), and the program holds nothing of the fetch table
        assert asked == [] and fetch_table_selects(text) == 0
        assert walk_calls(text) == len(
            kernel_calls(text, "paged_attention"))
    row = "decode" if key.Q == 1 else "prefill"
    for kernel in (f"paged_attention_{row}", f"paged_attention_window_{row}",
                   f"kv_write_{row}", "moe_expert_ffn"):
        assert any('custom_call_target="tpu_custom_call"' in line
                   and kernel in line for line in text.splitlines()), kernel
    # the smallest thing that must not move: a layer of the window pool
    # (134 MB here; a layer of the experts' stack is 302 MB, the full
    # pool's layers 268 MB)
    layer_bytes = (pages["window"] + 1) * 2 * 8 * PAGE * 128 * 2
    expert_layer = 16 * 3 * 3072 * 1024 * 2
    assert layer_bytes < expert_layer
    assert pool_sized_movers(text, layer_bytes) == []
    assert stack_shaped_movers(text, params) == []
    # the mixed step's 768 tokens hold 180 MB of temporaries (its 8,704
    # expert rows alone 53 MB): under a layer of the experts' stack
    assert compiled.memory_analysis().temp_size_in_bytes < expert_layer


# -- the state-space family: its two kernels and its step programs ----------

SSM_LAYERS, SSM_SLOTS, SSM_STATE, SSM_INNER = 26, 256, 16, 5120


def _conv_pool(chip, layers, channels):
    """A family's conv pool: three taps a slot, in the pool's own layout."""
    from deepspeed_tpu.ops.ssm import conv_slot_shape
    return chip((layers, SSM_SLOTS + 1) + conv_slot_shape(3 * channels),
                jnp.bfloat16)


def _state_pools(chip, layers=SSM_LAYERS):
    return (chip((layers, SSM_SLOTS + 1, SSM_STATE, SSM_INNER), jnp.float32),
            _conv_pool(chip, layers, SSM_INNER))


def _tail(chip, rows, q, channels):
    """A prompt's new tails; a decode row has none to hand on (its tail is
    ``conv_step``'s to write)."""
    return None if q == 1 else chip((rows, 3, channels), jnp.bfloat16)


def _tails_floor(key, conv_layer: int, channels: int) -> int:
    """The size from which nothing may move outside a kernel in a family's
    step program: a layer of its conv pool; in a CHAIN program (decode rows
    alone) the tails of HALF its 256 rows, since those are read, convolved
    and shifted where they lie (``conv_tail_decode``, PERF.md PR 55): the
    gather, the two re-layouts and the shifted copy that stood around the
    update kernels each moved twice that, 256 / 257 of a layer."""
    if key.kind != "chain":
        return conv_layer
    return SSM_SLOTS // 2 * 3 * channels * 2


@pytest.mark.parametrize("rows,q,kernel", [
    (256, 1, "ssm_state_update_decode"), (4, 128, "ssm_scan_prefill"),
    (8, 128, "ssm_scan_prefill"), (2, 64, "ssm_scan_prefill")])
def test_state_space_kernels(chip, rows, q, kernel):
    """The update kernel (a row's whole [16, 5120] float32 state a grid
    step) and the scan kernel (d_inner in blocks under the default VMEM
    limit) at the published widths, both pools aliased in -> out."""
    from deepspeed_tpu.ops.ssm import _d_block, ssm_scan
    f32 = jnp.float32
    assert _d_block(SSM_INNER, 1) == SSM_INNER
    assert _d_block(SSM_INNER, 128) == 1280
    h, conv = _state_pools(chip)
    compile_for_chip(
        lambda h, conv, layer, slots, fresh, dt, x, B, C, A, D, tail:
        ssm_scan(h, conv, layer, slots, fresh, dt, x, B, C, A, D, tail,
                 use_kernel=True),
        h, conv, chip((), jnp.int32), chip((rows,), jnp.int32),
        chip((rows,), jnp.bool_), chip((rows, q, SSM_INNER), f32),
        chip((rows, q, SSM_INNER), f32), chip((rows, q, SSM_STATE), f32),
        chip((rows, q, SSM_STATE), f32), chip((SSM_STATE, SSM_INNER), f32),
        chip((SSM_INNER,), f32), _tail(chip, rows, q, SSM_INNER),
        kernel=kernel)


@pytest.mark.parametrize("channels", [5120, 6144, 11520, 12288],
                         ids=["jamba", "nemotron", "olmo-hybrid", "ling"])
def test_conv_tail_decode(chip, channels, rows=SSM_SLOTS):
    """A decode segment's convolution at the four state-holding cells'
    channel counts: ONE kernel over the conv pool in place (aliased in ->
    out, left in HBM), under the default scoped-VMEM limit.  (That nothing
    of the tails' size moves around it is held on the chain programs.)"""
    from deepspeed_tpu.ops.ssm import conv_step, conv_step_rows
    f32 = jnp.float32
    conv = _conv_pool(chip, 3, channels)
    # 32 sequences a grid step where their buffers fit, 16 past 10 MB
    assert conv_step_rows(rows, conv.shape[2], channels, 4, 2) \
        == (32 if channels < 11520 else 16)
    text = compile_for_chip(
        lambda conv, layer, slots, fresh, q_lens, x, w, b:
        conv_step(conv, layer, slots, fresh, q_lens, x, w, b,
                  use_kernel=True)[:2],
        conv, chip((), jnp.int32), chip((rows,), jnp.int32),
        chip((rows,), jnp.bool_), chip((rows,), jnp.int32),
        chip((rows, 1, channels), f32), chip((4, channels), f32),
        chip((channels,), f32), kernel="conv_tail_decode")
    assert set(scoped_vmem_asked(text, "conv_tail_decode")) == {""}


JAMBA_STEP_KEYS = {
    "chain-p40": (256, 1, 40, False, "chain", 256, True),
    "mixed-p40": (256, 1, 40, False, "mixed", 2, 128, 8, True, True),
    "sample-fresh": (4, 128, 8, True, "sample", True),
    # a prefill that is no fresh one (a later piece of a prompt): the scan
    # from the slot's carried state, the ragged kernel at 20 query heads
    # over the one KV head (a block of 2,560 query rows)
    "chunk": (4, 128, 8, False, "sample", True),
}


@pytest.mark.parametrize("kind", sorted(JAMBA_STEP_KEYS))
def test_jamba_step_program_moves_no_pool_and_no_weight_stack(
        chip, monkeypatch, kind):
    """The benchmark's cell at published widths and full depth (28 layers:
    26 Mamba, attention at 7 and 21): the step programs lower for the chip
    with a group of 20 query heads over 1 KV head, the state-space kernels
    run under their own names, and nothing the size of a layer of the
    state pool's small array (the convolution tails: 7.9 MB), let alone of
    the 2.2 GB state pool, the page pool or a weight stack, is copied,
    sliced out or re-laid out: the in-place update is held by this.  The
    temporaries stay under one Mamba layer's weights."""
    import dataclasses
    import json
    import os

    from flax.core import meta

    from benchmark.builders.serve_jamba import source_of
    from deepspeed_tpu.accelerator import real_accelerator
    from deepspeed_tpu.inference.v2.model_implementations import (
        JambaInferenceModel)
    from deepspeed_tpu.inference.v2.ragged import KVCacheConfig
    from deepspeed_tpu.models.jamba import JambaForCausalLM

    monkeypatch.setattr(real_accelerator, "device_platform", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "jamba2-3b-serve-28l.json")) as f:
        config = json.load(f)
    model = JambaForCausalLM(source_of(config, False))
    assert model.cfg.layer_kinds.count("ssm") == SSM_LAYERS
    params = jax.eval_shape(lambda k: meta.unbox(model.init_params(k)),
                            jax.random.key(0))
    pages = 2048
    serve = JambaInferenceModel(model.cfg, params, kv_config=KVCacheConfig(
        num_layers=2, kv_heads=1, head_dim=128, page_size=PAGE,
        num_pages=pages))
    serve.state_config = dataclasses.replace(serve.state_config,
                                             num_slots=SSM_SLOTS)
    pool = (chip((2, pages + 1, 2, 1, PAGE, 128), jnp.bfloat16),
            *_state_pools(chip))
    assert [tuple(a.shape) for a in pool[1:]] \
        == list(serve.state_config.shapes())
    key = StepKey.parse(JAMBA_STEP_KEYS[kind])
    avals = jax.tree.map(
        lambda a: chip(a.shape, a.dtype) if hasattr(a, "shape") else a,
        step_avals(serve, key, pool))
    # the state slot rides the page table's last column: no new operand,
    # no new field of the key
    assert (key.S, key.P + 1) in [a.shape for a in avals[2:]]
    asked = asked_of_fetch_table(monkeypatch)
    compiled = jax.jit(step_program(serve, key),
                       donate_argnums=(1,)).lower(*avals).compile()
    text = compiled.as_text()
    if key.kind == "chain":
        # decode rows alone: the walk (PR 45), nothing of the fetch table
        assert asked == [] and fetch_table_selects(text) == 0
        assert walk_calls(text) == len(
            kernel_calls(text, "paged_attention"))
    row = "decode" if key.Q == 1 else "prefill"
    kernels = [f"kv_write_{row}", "ssm_state_update_decode" if key.Q == 1
               else "ssm_scan_prefill"]
    if key.Q == 1:
        kernels.append("conv_tail_decode")
    if key.kind == "mixed":
        kernels += ["ssm_scan_prefill", "kv_write_prefill"]
    if not key.fresh or key.kind == "mixed":
        kernels.append("paged_attention_decode" if key.kind == "mixed"
                       else f"paged_attention_{row}")
    for kernel in kernels:
        assert any('custom_call_target="tpu_custom_call"' in line
                   and kernel in line for line in text.splitlines()), kernel
    # the smallest thing that must not move: one layer of the conv pool
    conv_layer = (SSM_SLOTS + 1) * 3 * SSM_INNER * 2
    kv_layer = (pages + 1) * 2 * PAGE * 128 * 2
    mamba_layer = 2 * 104_000_000
    assert conv_layer < kv_layer < mamba_layer
    moved = [m for m in pool_sized_movers(text, _tails_floor(
        key, conv_layer, SSM_INNER))
             # ONE layer's weights taken out of its kind's stack, mostly
             # inside the fusion that feeds the product: what a scan over
             # layers does (the attention layer's 13 MB wq and wo are
             # copied out, 0.03 ms a step)
             if not m[2].startswith("bf16[1,")]
    # activations of the step's 768 tokens, not pools
    assert all(m[2] in ("f32[512,1,5120]", "bf16[4,131,5120]",
                        "bf16[8,131,5120]") for m in moved), moved
    assert stack_shaped_movers(text, params) == []
    assert compiled.memory_analysis().temp_size_in_bytes < mamba_layer


# -- the delta-rule family: its two kernels and its step programs -----------

DELTA_LAYERS, DELTA_HEADS, DELTA_DK, DELTA_DV = 3, 30, 96, 192
DELTA_CHANNELS = DELTA_HEADS * (2 * DELTA_DK + DELTA_DV)


def _delta_pools(chip):
    return (chip((DELTA_LAYERS, SSM_SLOTS + 1, DELTA_DK,
                  DELTA_HEADS * DELTA_DV), jnp.float32),
            _conv_pool(chip, DELTA_LAYERS, DELTA_CHANNELS))


@pytest.mark.parametrize("rows,q,kernel", [
    (256, 1, "delta_state_update_decode"), (4, 128, "delta_chunk_prefill"),
    (1, 1024, "delta_chunk_prefill"), (4, 8, "delta_chunk_prefill")])
def test_delta_rule_kernels(chip, rows, q, kernel):
    """The update kernel (a row's whole [96, 5760] float32 state a grid
    step, in and out in two buffers each under the default VMEM limit) and
    the chunked kernel (6 heads and one chunk a grid step, whatever the
    row bucket) at the published widths, both pools aliased in -> out."""
    from deepspeed_tpu.ops.delta_rule import chunk_len, delta_rule
    f32 = jnp.float32
    assert [chunk_len(n) for n in (1, 8, 128, 1024, 96)] \
        == [1, 8, 64, 64, 32]
    state, conv = _delta_pools(chip)
    width = DELTA_HEADS * DELTA_DV
    compile_for_chip(
        lambda state, conv, layer, slots, fresh, q_, k, v, g, beta, tail:
        delta_rule(state, conv, layer, slots, fresh, q_, k, v, g, beta,
                   tail, use_kernel=True),
        state, conv, chip((), jnp.int32), chip((rows,), jnp.int32),
        chip((rows,), jnp.bool_),
        chip((rows, q, DELTA_HEADS, DELTA_DK), f32),
        chip((rows, q, DELTA_HEADS, DELTA_DK), f32),
        chip((rows, q, width), f32), chip((rows, q, DELTA_HEADS), f32),
        chip((rows, q, DELTA_HEADS), f32),
        _tail(chip, rows, q, DELTA_CHANNELS), kernel=kernel)


#: the window's two programs: a chained decode step, and a mixed step (256
#: decode rows and two fresh prompts: both delta kernels, both page writes,
#: the paged kernel at 30 / 30 heads); a prompt row's paged kernel alone is
#: ``test_paged_attention_at_thirty_kv_heads``
OLMO_STEP_KEYS = {
    "chain-p40": (256, 1, 40, False, "chain", 256, True),
    "mixed-p40": (256, 1, 40, False, "mixed", 2, 128, 8, True, True),
}


@pytest.mark.parametrize("kind", sorted(OLMO_STEP_KEYS))
def test_olmo_hybrid_step_program_moves_no_pool_and_no_weight_stack(
        chip, monkeypatch, kind):
    """The benchmark's cell at published widths (one period: 3 delta-rule
    layers and the full layer at 30 / 30 heads): the step programs lower
    for the chip, the delta-rule kernels run under their own names, and
    nothing the size of a layer of the state pool's small array (the
    convolution tails: 17.8 MB), let alone of the 1.7 GB state pool, the
    page pool or a weight stack, is copied, sliced out or re-laid out: the
    in-place update is held by this."""
    import dataclasses
    import json
    import os

    from flax.core import meta

    from benchmark.builders.serve_olmo_hybrid import source_of
    from deepspeed_tpu.accelerator import real_accelerator
    from deepspeed_tpu.inference.v2.model_implementations import (
        OlmoHybridInferenceModel)
    from deepspeed_tpu.inference.v2.ragged import KVCacheConfig
    from deepspeed_tpu.models.olmo_hybrid import OlmoHybridForCausalLM

    monkeypatch.setattr(real_accelerator, "device_platform", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "olmo-hybrid-7b-serve-4l.json")) as f:
        config = json.load(f)
    model = OlmoHybridForCausalLM(source_of(config, False))
    assert model.cfg.layer_kinds == ("delta", "delta", "delta", "full")
    params = jax.eval_shape(lambda k: meta.unbox(model.init_params(k)),
                            jax.random.key(0))
    pages = 1024
    serve = OlmoHybridInferenceModel(
        model.cfg, params, kv_config=KVCacheConfig(
            num_layers=1, kv_heads=30, head_dim=128, page_size=PAGE,
            num_pages=pages))
    serve.state_config = dataclasses.replace(serve.state_config,
                                             num_slots=SSM_SLOTS)
    pool = (chip((1, pages + 1, 2, 30, PAGE, 128), jnp.bfloat16),
            *_delta_pools(chip))
    assert [tuple(a.shape) for a in pool[1:]] \
        == list(serve.state_config.shapes())
    key = StepKey.parse(OLMO_STEP_KEYS[kind])
    avals = jax.tree.map(
        lambda a: chip(a.shape, a.dtype) if hasattr(a, "shape") else a,
        step_avals(serve, key, pool))
    assert (key.S, key.P + 1) in [a.shape for a in avals[2:]]
    asked = asked_of_fetch_table(monkeypatch)
    compiled = jax.jit(step_program(serve, key),
                       donate_argnums=(1,)).lower(*avals).compile()
    text = compiled.as_text()
    if key.kind == "chain":
        # decode rows alone: the walk (PR 45), nothing of the fetch table
        assert asked == [] and fetch_table_selects(text) == 0
        assert walk_calls(text) == len(
            kernel_calls(text, "paged_attention"))
    row = "decode" if key.Q == 1 else "prefill"
    kernels = [f"kv_write_{row}", "delta_state_update_decode"
               if key.Q == 1 else "delta_chunk_prefill"]
    if key.Q == 1:
        kernels.append("conv_tail_decode")
    if key.kind == "mixed":
        kernels += ["delta_chunk_prefill", "kv_write_prefill"]
    if not key.fresh or key.kind == "mixed":
        kernels.append("paged_attention_decode" if key.kind == "mixed"
                       else f"paged_attention_{row}")
    for kernel in kernels:
        assert any('custom_call_target="tpu_custom_call"' in line
                   and kernel in line for line in text.splitlines()), kernel
    # the smallest thing that must not move: one layer of the conv pool
    conv_layer = (SSM_SLOTS + 1) * 3 * DELTA_CHANNELS * 2
    kv_layer = (pages + 1) * 2 * 30 * PAGE * 128 * 2
    delta_layer = 2 * 215_000_000
    assert conv_layer < delta_layer < kv_layer
    moved = [m for m in pool_sized_movers(text, _tails_floor(
        key, conv_layer, DELTA_CHANNELS))
             # ONE layer's weights taken out of its kind's stack, mostly
             # inside the fusion that feeds the product (what a scan over
             # layers does)
             if not m[2].startswith("bf16[1,")]
    assert moved == [], moved
    assert stack_shaped_movers(text, params) == []
    assert compiled.memory_analysis().temp_size_in_bytes < delta_layer


# -- the train cell's step: what the layers' checkpoint keeps (PR 38) --------

TRAIN_LAYERS, TRAIN_ROWS = 2, 4
#: one device's limit under which the rule (models/transformer.py::
#: RematBudget) buys the richest rung for the two-layer step and no more
#: than 0.3 GB beyond it (the cell's own twelve layers under the chip's
#: 15.75 GiB buy rung 1)
TRAIN_LIMIT = 6_100_000_000
#: the products a layer's rope adds to attention's matmuls in a pass that
#: runs it: the pair swap of q and of k (models/transformer.py::
#: _rotate_pairs)
ROPE_SWAPS = 2


@pytest.fixture(scope="module")
def train_step_programs(topo, chip):
    """``compiled(policy)`` -> (the compiled train step of the benchmark's
    train cell at two layers on the described four chips under that
    ``remat_policy``, its scope table as the engine exports it); each
    compiled once.  ZeRO-3 over ``{fsdp: 4}``, bf16 under fp32 masters and
    AdamW, 4 rows x 2048 tokens a chip: ``benchmark/configs/
    mistral-7b-zero3-fsdp4.json``'s ``train`` block, state as shapes."""
    from unittest import mock

    from deepspeed_tpu.accelerator import get_accelerator, real_accelerator
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    from deepspeed_tpu.parallel.topology import MeshTopology, TopologyConfig
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from deepspeed_tpu.runtime.zero.partitioner import unbox

    def shapes(tree, shardings):
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=s), tree, shardings)

    class ShapesEngine(DeepSpeedEngine):
        """An engine whose parameters and state are shapes on the described
        chips: it builds and lowers its step and runs nothing."""

        def _init_params(self):
            self._abstract_params = jax.eval_shape(self.module.init_params,
                                                   self._rng)
            return self._abstract_params

        def _init_state(self, params):
            params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, self.master_dtype), unbox(params))
            state = jax.eval_shape(self._make_state, params)
            self._state_shardings_cache = self._state_shardings(
                state, self.partitioner.master_shardings(
                    self._abstract_params))
            return shapes(state, self._state_shardings_cache)

    done = {}

    def compiled(policy):
        if policy in done:
            return done[policy]
        model = LlamaForCausalLM(
            "7b", intermediate_size=14336, num_kv_heads=KV_HEADS,
            sliding_window=4096, num_layers=TRAIN_LAYERS, max_seq_len=SEQ,
            vocab_size=32000,
            **({} if policy == "auto" else {"remat_policy": policy}))
        kept = []
        real_compile = jax.stages.Lowered.compile
        with mock.patch.object(real_accelerator, "device_platform",
                               lambda: "tpu"), \
                mock.patch.object(type(get_accelerator()), "total_memory",
                                  lambda self, index=None: TRAIN_LIMIT), \
                mock.patch.object(
                    jax.stages.Lowered, "compile",
                    lambda low: kept.append(real_compile(low)) or kept[-1]):
            engine = ShapesEngine(
                model=model, rng=jax.random.key(0),
                topology=MeshTopology(TopologyConfig(fsdp=4),
                                      devices=topo.devices),
                config={"train_micro_batch_size_per_gpu": TRAIN_ROWS,
                        "gradient_accumulation_steps": 1,
                        "optimizer": {"type": "adamw",
                                      "params": {"lr": 1e-4}},
                        "zero_optimization": {"stage": 3},
                        "bf16": {"enabled": True}, "gradient_clipping": 1.0,
                        "checkpoint": {"async_save": False}})
            ids = np.zeros((1, 4 * TRAIN_ROWS, SEQ), np.int32)
            # as a step that ran with telemetry on notes itself
            engine._scoped_step = [engine._train_step, {
                "input_ids": jax.ShapeDtypeStruct(
                    ids.shape, ids.dtype,
                    sharding=engine._batch_leaf_sharding(ids, True))}, None]
            table = engine.step_scope_table()
        done[policy] = kept[-1], table
        return done[policy]
    return compiled


def _train_program_facts(compiled, table):
    """(flash forward calls by phase, matmul instructions by (phase,
    module)) of a compiled train step, read through its scope table."""
    import collections
    from deepspeed_tpu.telemetry import program_scopes
    text = compiled.as_text()
    comps, _ = program_scopes._computations(text)
    opcode = {name: op for body in comps.values() for name, op, *_ in body}
    line = {m.group(1): ln for ln in text.splitlines()
            for m in [_HLO_LINE.match(ln) or re.match(
                r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", ln)] if m}
    flash, matmuls = collections.Counter(), collections.Counter()
    for name, (phase, module) in table["instructions"].items():
        if kernel_calls(line.get(name, ""), "flash_attention_fwd"):
            flash[phase] += 1
        called = re.search(r"calls=%?([\w.\-]+)", line.get(name, ""))
        ops = [opcode.get(name)] + [
            op for _, op, *_ in comps.get(called.group(1), [])] \
            if called else [opcode.get(name)]
        if any(op in ("dot", "convolution") for op in ops):
            matmuls[phase, module] += 1
    return flash, matmuls


@pytest.mark.parametrize("policy", ["save_attn_out", "save_attn", "auto"])
def test_train_step_keeps_what_its_policy_names(train_step_programs, policy):
    """The compiled step of the train cell (two layers) under each policy
    that keeps named values, against ``nothing_saveable``: which attention
    work is left in the recomputed forward, what the residuals cost in the
    chip compiler's own count against the rule's, and that nothing re-lays
    a stack of them out."""
    from deepspeed_tpu.models import transformer as T
    base, base_table = train_step_programs("nothing_saveable")
    compiled, table = train_step_programs(policy)
    assert not table["stale"] and not base_table["stale"]

    # today's default: every layer's forward twice, the flash kernel too;
    # attention's matmuls are the four projections and the rope's pair swap
    # of q and of k (PR 49: a product with a 0/1 permutation, fused with
    # the rotation)
    flash, matmuls = _train_program_facts(base, base_table)
    assert (flash["forward"], flash["recompute"]) == (1, 1)
    assert matmuls["recompute", "attn"] == 4 + ROPE_SWAPS
    assert matmuls["recompute", "mlp"] == 2
    assert (base_table["remat_policy"], base_table["remat_layer_bytes"],
            base_table["remat_budget_bytes"]) == ("nothing_saveable", 0, 0)

    # one flash forward a layer and pass whatever is kept beyond it; the
    # MLP's gate and up stay recomputed
    flash, matmuls = _train_program_facts(compiled, table)
    assert (flash["forward"], flash["recompute"]) == (1, 0)
    assert matmuls["recompute", "mlp"] == 2
    assert matmuls["forward", "attn"] == 4 + ROPE_SWAPS
    assert matmuls["forward", "mlp"] == 3
    model_cfg = T.TransformerConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_layers=TRAIN_LAYERS, num_heads=HEADS, num_kv_heads=KV_HEADS)
    tokens = TRAIN_ROWS * SEQ
    rungs = T.remat_rung_bytes(model_cfg, tokens)
    if policy == "save_attn_out":
        # out and lse alone: the projections and ropes run again
        assert matmuls["recompute", "attn"] == 4 + ROPE_SWAPS
        kept = tokens * HEADS * (HEAD_DIM * 2 + 4)
        assert table["remat_policy"] == "save_attn_out"
    elif policy == "save_attn":
        # rung 1: the output projection alone (q and k are kept as the rope
        # left them: no swap runs again)
        assert matmuls["recompute", "attn"] == 1
        kept = rungs["save_attn"]
        assert table["remat_policy"] == "save_attn"
    else:
        # "auto" under TRAIN_LIMIT: the richest rung, no attention matmul
        assert matmuls["recompute", "attn"] == 0
        kept = rungs["save_attn_residual"]
        assert (table["remat_policy"], table["remat_layer_bytes"]) \
            == ("save_attn_residual", kept)
        need = TRAIN_LAYERS * kept
        assert need <= table["remat_budget_bytes"] < need + 300_000_000

    # the chip compiler's count grows by what the rule reckons, within a
    # fifth ...
    def peak(c):
        return c.memory_analysis().peak_memory_in_bytes
    grown = peak(compiled) - peak(base)
    assert 0.8 * TRAIN_LAYERS * kept <= grown <= 1.2 * TRAIN_LAYERS * kept
    # ... and the rule's whole reckoning of the step is the compiler's peak
    # within a fifth (the state, the parameter copy, the working set)
    held = peak(base) - T.remat_working_set(
        model_cfg, tokens, grads_bytes=_train_shard_bytes(model_cfg, 2))
    state = _train_shard_bytes(model_cfg, 12 + 2)
    assert 0.8 * state <= held <= 1.2 * state
    # no copy or transpose yields a whole stack of residuals
    stacks = {f"{TRAIN_LAYERS},{TRAIN_ROWS},{dims}"
              for h in (HEADS, KV_HEADS)
              for dims in (f"{SEQ},{h},{HEAD_DIM}", f"{h},{SEQ},{HEAD_DIM}")} \
        | {f"{TRAIN_LAYERS},{TRAIN_ROWS},{SEQ},{HEADS * HEAD_DIM}",
           f"{TRAIN_LAYERS},{TRAIN_ROWS},{HEADS},1,{SEQ}"}
    assert [m for m in pool_sized_movers(compiled.as_text(), 0)
            if m[1] != "dynamic-update-slice" and "dynamic-update-slice"
            not in m[0] and m[2][m[2].index("[") + 1:-1] in stacks] == []


def _train_shard_bytes(cfg, bytes_a_parameter, shards=4):
    """Bytes of one device's share of the model's matmul parameters."""
    return cfg.n_params() // shards * bytes_a_parameter


# -- the attention block: what moves outside its matmuls and kernels (PR 49) -

_HLO_SHAPE = re.compile(r"\b(%s)\[([\d,]*)\]" % "|".join(_ITEMSIZE))


def bytes_outside_matmuls(text: str) -> list:
    """``(bytes, name, opcode)``, most first, of every instruction of a
    compiled program's ENTRY computation that is neither a matmul fusion
    (one whose computation holds a ``dot`` or a ``convolution``) nor a
    Mosaic call: the bytes of its operands and of its result, what it moves
    through HBM at the least.  Left out: what leaves no event (parameters,
    constants, tuples, bitcasts, a ``ConcatBitcast``); an async pair counts
    once, at its ``-done``, as its result read and written."""
    from deepspeed_tpu.telemetry import program_scopes

    def nbytes(shapes):
        return sum(_array_bytes(*shape)
                   for shape in _HLO_SHAPE.findall(shapes))

    comps, entry = program_scopes._computations(text)
    line = {m.group(1): m.group(2) for m in map(
        program_scopes._INSTRUCTION.match, text.splitlines()) if m}
    result = {i.name: nbytes(line[i.name].split(f" {i.opcode}(")[0])
              for i in comps[entry]}
    found = []
    for i in comps[entry]:
        if i.opcode in program_scopes._SILENT or i.opcode.endswith("-start"):
            continue
        if i.opcode == "custom-call" and re.search(
                r'custom_call_target="(tpu_custom_call|ConcatBitcast)"',
                line[i.name]):
            continue
        if any(op in ("dot", "convolution")
               for _, op, *_ in comps.get(i.fused, [])):
            continue
        moved = 2 * result[i.name] if i.opcode.endswith("-done") else \
            result[i.name] + sum(result.get(o, 0) for o in i.mentions)
        found.append((moved, i.name, i.opcode))
    return sorted(found, reverse=True)


def test_bytes_outside_matmuls_reads_a_program_text():
    text = """
%fused_dot (a: bf16[8,128], b: bf16[128,128]) -> f32[8,128] {
  %a = bf16[8,128]{1,0} parameter(0)
  %b = bf16[128,128]{1,0} parameter(1)
  ROOT %dot.1 = f32[8,128]{1,0} dot(%a, %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}

%fused_add (a: f32[8,128]) -> f32[8,128] {
  %a.1 = f32[8,128]{1,0} parameter(0)
  ROOT %add.1 = f32[8,128]{1,0} add(%a.1, %a.1)
}

ENTRY %main (x: bf16[8,128], w: bf16[128,128]) -> f32[8,128] {
  %x = bf16[8,128]{1,0} parameter(0)
  %w = bf16[128,128]{1,0} parameter(1)
  %fusion.1 = f32[8,128]{1,0} fusion(%x, %w), kind=kOutput, calls=%fused_dot
  %copy-start = (bf16[8,128]{1,0:S(1)}, bf16[8,128]{1,0}, u32[]) copy-start(%x)
  %copy-done = bf16[8,128]{1,0:S(1)} copy-done(%copy-start)
  %kernel.2 = f32[8,128]{1,0} custom-call(%fusion.1), custom_call_target="tpu_custom_call"
  %copy.3 = f32[8,128]{0,1} copy(%kernel.2)
  ROOT %fusion.2 = f32[8,128]{1,0} fusion(%copy.3), kind=kLoop, calls=%fused_add
}
"""
    assert bytes_outside_matmuls(text) == [
        (8192, "fusion.2", "fusion"), (8192, "copy.3", "copy"),
        (4096, "copy-done", "copy-done")]


def test_attention_block_moves_little_outside_its_matmuls(chip, monkeypatch):
    """One attention block, forward and backward at the train cell's shapes
    (``[4, 2048, 4096]`` bfloat16, 32 / 8 heads of 128, the flash kernels):
    what its program moves through HBM outside matmuls and kernels.  Under
    the strided-pair rope (``x[..., 0::2]``, ``jnp.stack``) that was 5.41
    GB a layer: float32 copies of q with the pair index as the MAJOR
    dimension, gathered and scattered by ``kCustom`` fusions.  With the
    pair swap a product fused with the rotation 0.88 GB were left: the GQA
    repeat, the group sum of dK / dV, the activation's prefetch; without
    the repeat and the group sum (PR 51: the flash kernels take K/V at
    their own head count) 0.68 GB."""
    from deepspeed_tpu.accelerator import real_accelerator
    from deepspeed_tpu.models import transformer as T

    monkeypatch.setattr(real_accelerator, "device_platform", lambda: "tpu")
    cfg = T.TransformerConfig(
        vocab_size=32000, hidden_size=HEADS * HEAD_DIM,
        intermediate_size=14336, num_layers=1, num_heads=HEADS,
        num_kv_heads=KV_HEADS, max_seq_len=SEQ, sliding_window=4096,
        dtype=jnp.bfloat16)
    hidden = HEADS * HEAD_DIM
    x = chip((TRAIN_ROWS, SEQ, hidden), jnp.bfloat16)
    params = {"wq": chip((hidden, HEADS, HEAD_DIM), jnp.bfloat16),
              "wk": chip((hidden, KV_HEADS, HEAD_DIM), jnp.bfloat16),
              "wv": chip((hidden, KV_HEADS, HEAD_DIM), jnp.bfloat16),
              "wo": chip((HEADS, HEAD_DIM, hidden), jnp.bfloat16)}

    def block(params, x, positions, g):
        def attend(params, x):
            sin, cos = T.rope_table(cfg, positions)
            return T._attention_block(cfg, params, x, sin, cos, None,
                                      use_flash=True)
        out, vjp = jax.vjp(attend, params, x)
        return out, vjp(g)

    text = jax.jit(block).lower(
        params, x, chip((TRAIN_ROWS, SEQ), jnp.int32), x).compile().as_text()
    assert sorted(flash_kernels(text)) == [
        "flash_attention_bwd_dkv", "flash_attention_bwd_dq",
        "flash_attention_fwd"]
    moved = bytes_outside_matmuls(text)
    assert sum(m[0] for m in moved) < 800_000_000, moved[:12]
    # K and V reach the kernels at their own head count (PR 51): nothing
    # repeats them to the query heads or sums dK / dV over a group
    group = f"[{TRAIN_ROWS},{KV_HEADS},{HEADS // KV_HEADS},{SEQ},{HEAD_DIM}]"
    assert group not in text
    # no gather or scatter of the lane dimension's pairs ...
    assert [ln for ln in text.splitlines() if "kind=kCustom" in ln] == []
    # ... and no float32 re-layout of q (or of its cotangent)
    q_bytes = 4 * TRAIN_ROWS * SEQ * HEADS * HEAD_DIM
    assert [m for m in pool_sized_movers(text, q_bytes)
            if m[1] == "copy" and m[2].startswith("f32")] == []


# -- the smallthinker family: the expert kernel's gate, its step programs ----

#: the first 16 hex digits of the SHA-256 of ``moe_expert_ffn``'s Mosaic
#: text at 256 tokens, by (family's shapes, the gate's activation), read on
#: PR 48's tree (the walk over the tiles in use; PR 47's grid form read
#: cebe6ed9, 5814e844, f2eb75d0, 537dda43): a change here is a change of
#: the kernel, and every cached step program of the three families that
#: hold experts forms anew
EXPERT_KERNEL_TEXTS = {
    # layers, experts held, expert width, hidden, experts a token
    "pangu": ((4, 16, 2048, 7680, 8), {"silu": "a5beb69507223dbe",
                                        "relu": "57081945c75492fe"}),
    "smallthinker": ((8, 64, 768, 2560, 6), {"silu": "324490e0d18cadc8",
                                             "relu": "f589a1d7259b47ba"}),
}


@pytest.mark.parametrize("name,act", [
    (name, act) for name, (_, digests) in EXPERT_KERNEL_TEXTS.items()
    for act in digests])
def test_the_gate_is_static_in_the_expert_kernels_text(chip, name, act):
    import hashlib

    from deepspeed_tpu.moe.held import held_experts_ffn
    (L, E, F, e, k), digests = EXPERT_KERNEL_TEXTS[name]
    stack = {n: chip((L, E, F, e), jnp.bfloat16) for n in ("wg", "wu", "wd")}
    lowered = jax.jit(lambda x, ex, w, p, l: held_experts_ffn(
        x, ex, w, p, 0, layer=l, use_kernel=True, act=act)).lower(
        chip((256, e), jnp.bfloat16), chip((256, k), jnp.int32),
        chip((256, k), jnp.float32), stack, chip((), jnp.int32)).as_text()
    text, = mosaic_texts(lowered)
    assert ("maximumf" in text) == (act == "relu")
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digests[act]


#: the cell's chained, mixed and drain programs, and both page buckets of
#: its lattice: one program a kind (a compile is 10-30 s of a suite near
#: its limit: the ramp's mixed step stands for the bucket of 8; the other
#: three programs of kind x bucket hold the same calls at the other width)
SMALLTHINKER_STEP_KEYS = {
    "chain-p40": (256, 1, 40, False, "chain", 256, True),
    "mixed-p8": (256, 1, 8, False, "mixed", 4, 128, 8, True, True),
    "drain-p40": (16, 1, 40, False, "chain", 32, True),
}


@pytest.mark.parametrize("kind", sorted(SMALLTHINKER_STEP_KEYS))
def test_smallthinker_step_program_moves_no_pool_and_no_expert_stack(
        chip, monkeypatch, kind):
    """The benchmark's cell at published widths (all eight layers: two
    periods, all 64 experts a layer): the step programs lower for the chip
    with a GQA group of 7 over 4 KV heads in both page groups, the window
    group's calls under a name of their own at a 72-slot table, the ReLU
    expert kernel over 64 experts x 12 width slices, and neither group's
    pool nor the experts' stack (eight layers of 755 MB) is copied, sliced
    out or re-laid out."""
    import json
    import os

    from flax.core import meta

    from benchmark.builders.serve_smallthinker import source_of
    from deepspeed_tpu.accelerator import real_accelerator
    from deepspeed_tpu.inference.v2.model_implementations import (
        SmallThinkerInferenceModel)
    from deepspeed_tpu.inference.v2.ragged import KVCacheConfig
    from deepspeed_tpu.models.smallthinker import SmallThinkerForCausalLM
    from deepspeed_tpu.moe.held import _rows_bound, row_tile

    monkeypatch.setattr(real_accelerator, "device_platform", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "smallthinker-21b-serve-8l.json")) as f:
        config = json.load(f)
    model = SmallThinkerForCausalLM(source_of(config, False))
    assert model.cfg.layer_kinds == ("full", "window", "window",
                                     "window") * 2
    params = jax.eval_shape(lambda k: meta.unbox(model.init_params(k)),
                            jax.random.key(0))
    assert params["experts"]["wg"].shape == (8, 64, 768, 2560)
    # 1,536 pairs of a 256-row step, 24 an expert: tiles of 64 rows (an
    # expert's second tile streams its weights again), a bound of 5,568
    # rows however the pairs fall; 3,520 under the tile of a router that
    # scores 256 experts
    assert row_tile(256, 256 * 6 / 64) == 64 and row_tile(256) == 32
    assert _rows_bound(256 * 6, 64, 64) == 5568
    assert _rows_bound(256 * 6, 64, 32) == 3520
    pages = {"full": 1024, "window": 512}
    serve = SmallThinkerInferenceModel(
        model.cfg, params,
        kv_config=KVCacheConfig(num_layers=2, kv_heads=4, head_dim=128,
                                page_size=PAGE, num_pages=pages["full"]),
        window_kv_config=KVCacheConfig(
            num_layers=6, kv_heads=4, head_dim=128, page_size=PAGE,
            num_pages=pages["window"]))
    pool = (chip((2, pages["full"] + 1, 2, 4, PAGE, 128), jnp.bfloat16),
            chip((6, pages["window"] + 1, 2, 4, PAGE, 128), jnp.bfloat16))
    key = StepKey.parse(SMALLTHINKER_STEP_KEYS[kind])
    avals = jax.tree.map(
        lambda a: chip(a.shape, a.dtype) if hasattr(a, "shape") else a,
        step_avals(serve, key, pool))
    # the window group's table rides the page table: 72 slots and a base
    assert (key.S, key.P + 72 + 1) in [a.shape for a in avals[2:]]
    asked = asked_of_fetch_table(monkeypatch)
    compiled = jax.jit(step_program(serve, key),
                       donate_argnums=(1,)).lower(*avals).compile()
    text = compiled.as_text()
    if key.kind == "chain":
        # decode rows alone: both page groups' calls walk each row's own
        # pages, and the program holds nothing of the fetch table
        assert asked == [] and fetch_table_selects(text) == 0
        assert walk_calls(text) == len(
            kernel_calls(text, "paged_attention"))
    for kernel in ("paged_attention_decode", "paged_attention_window_decode",
                   "kv_write_decode", "moe_expert_ffn"):
        assert any('custom_call_target="tpu_custom_call"' in line
                   and kernel in line for line in text.splitlines()), kernel
    assert set(scoped_vmem_asked(text, "moe_expert_ffn")) == {""}
    # the smallest thing that must not move: a layer of the window pool
    # (67 MB here; a layer of the experts' stack is 755 MB)
    layer_bytes = (pages["window"] + 1) * 2 * 4 * PAGE * 128 * 2
    expert_layer = 64 * 3 * 768 * 2560 * 2
    assert layer_bytes < expert_layer
    assert pool_sized_movers(text, layer_bytes) == []
    assert stack_shaped_movers(text, params) == []
    assert compiled.memory_analysis().temp_size_in_bytes < expert_layer


# -- the Kimi-delta (KDA) family: its two kernels and its step programs ------

KDA_LAYERS, KDA_HEADS, KDA_D = 6, 32, 128
KDA_CHANNELS = 3 * KDA_HEADS * KDA_D


def _kda_pools(chip):
    return (chip((KDA_LAYERS, SSM_SLOTS + 1, KDA_D, KDA_HEADS * KDA_D),
                 jnp.float32),
            _conv_pool(chip, KDA_LAYERS, KDA_CHANNELS))


@pytest.mark.parametrize("rows,q,kernel", [
    (256, 1, "kda_state_update_decode"), (4, 128, "kda_chunk_prefill"),
    (1, 1024, "kda_chunk_prefill"), (4, 8, "kda_chunk_prefill")])
def test_kda_kernels(chip, rows, q, kernel):
    """The delta rule's kernels under a decay a KEY CHANNEL at the published
    widths (32 heads of [128, 128]): the update kernel (a row's whole [128,
    4096] float32 state a grid step, walked in lane groups of two heads: a
    group of one lane tile does not compile) and the chunked kernel (8
    heads and one chunk of 16 a grid step), under names of their own, both
    pools aliased in -> out."""
    from deepspeed_tpu.ops.delta_rule import (MAX_CHANNEL_CHUNK, _decode_group,
                                              chunk_len, delta_rule)
    f32 = jnp.float32
    assert [chunk_len(n, MAX_CHANNEL_CHUNK) for n in (1, 8, 128, 1024, 96)] \
        == [1, 8, 16, 16, 16]
    assert _decode_group(KDA_HEADS, KDA_D) == 2
    assert _decode_group(DELTA_HEADS, DELTA_DV) == 2    # as it always was
    state, conv = _kda_pools(chip)
    heads = (rows, q, KDA_HEADS, KDA_D)
    compile_for_chip(
        lambda state, conv, layer, slots, fresh, q_, k, v, g, beta, tail:
        delta_rule(state, conv, layer, slots, fresh, q_, k, v, g, beta,
                   tail, use_kernel=True),
        state, conv, chip((), jnp.int32), chip((rows,), jnp.int32),
        chip((rows,), jnp.bool_), chip(heads, f32), chip(heads, f32),
        chip((rows, q, KDA_HEADS * KDA_D), f32), chip(heads, f32),
        chip((rows, q, KDA_HEADS), f32),
        _tail(chip, rows, q, KDA_CHANNELS), kernel=kernel)


#: the window's two programs: a chained decode step, and a mixed step (256
#: decode rows and the lattice's 4-row prompt segment: both KDA kernels,
#: both latent writes, the latent decode and prefill kernels at 32 heads,
#: the held experts); and one of the PROBE's own, a plain forward formed
#: under ``routing_sink`` (a host callback a routed layer)
LING_STEP_KEYS = {
    "chain-p40": (256, 1, 40, False, "chain", 256, True),
    "mixed-p40": (256, 1, 40, False, "mixed", 4, 128, 8, True, True),
    "probe-p40": (256, 1, 40, False),
}


@pytest.mark.parametrize("kind", sorted(LING_STEP_KEYS))
def test_ling_step_program_moves_no_pool_and_no_weight_stack(
        chip, monkeypatch, kind):
    """The benchmark's cell at published widths (the dense KDA layer and
    one period: KDA x 3, the latent layer, KDA x 2; 32 of 512 experts held):
    the step programs lower for the chip with a latent page pool AND a state
    pool in one carry, the KDA kernels run under their own names, and
    nothing the size of a layer of the conv pool (18.9 MB), let alone of
    the 3.2 GB state pool, the latent pool or a layer of the held experts'
    stack, is copied, sliced out or re-laid out."""
    import dataclasses
    import json
    import os

    from flax.core import meta

    from benchmark.builders.serve_bailing_hybrid import source_of
    from deepspeed_tpu.accelerator import real_accelerator
    from deepspeed_tpu.inference.v2.model_implementations import (
        BailingHybridInferenceModel)
    from deepspeed_tpu.inference.v2.ragged import KVCacheConfig
    from deepspeed_tpu.models.bailing_hybrid import BailingHybridForCausalLM

    monkeypatch.setattr(real_accelerator, "device_platform", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "ling-3.0-flash-serve-7l-ep16.json")) as f:
        config = json.load(f)
    model = BailingHybridForCausalLM(source_of(config, False),
                                     first_layer=config["first_layer"])
    assert model.cfg.layer_kinds == ("kda",) * 4 + ("latent", "kda", "kda")
    params = jax.eval_shape(lambda k: meta.unbox(model.init_params(k)),
                            jax.random.key(0))
    pages = 1024
    serve = BailingHybridInferenceModel(
        model.cfg, params, kv_config=KVCacheConfig(
            num_layers=1, kv_heads=1, head_dim=MLA_PLANE, planes=1,
            page_size=PAGE, num_pages=pages))
    serve.state_config = dataclasses.replace(serve.state_config,
                                             num_slots=SSM_SLOTS)
    assert serve.pool_names == ("pages", "state", "conv")
    pool = (_latent_pool(chip, 1, pages), *_kda_pools(chip))
    assert [tuple(a.shape) for a in pool[1:]] \
        == list(serve.state_config.shapes())
    key = StepKey.parse(LING_STEP_KEYS[kind])
    heard = []
    if kind.startswith("probe"):
        serve.routing_sink = heard.append
    avals = jax.tree.map(
        lambda a: chip(a.shape, a.dtype) if hasattr(a, "shape") else a,
        step_avals(serve, key, pool))
    assert (key.S, key.P + 1) in [a.shape for a in avals[2:]]
    compiled = jax.jit(step_program(serve, key),
                       donate_argnums=(1,)).lower(*avals).compile()
    text = compiled.as_text()
    # the record of the routing is in the probe's programs and in no other
    assert ("xla_ffi_python_cpu_callback" in text or "host" in text.lower()
            and "callback" in text.lower()) == kind.startswith("probe"), kind
    kernels = ["kda_state_update_decode", "conv_tail_decode",
               "mla_attention_decode", "latent_write_decode",
               "moe_expert_ffn"]
    if key.kind == "mixed":
        kernels += ["kda_chunk_prefill", "latent_write_prefill"]
    for kernel in kernels:
        assert kernel_calls(text, kernel), kernel
    assert not kernel_calls(text, "delta_")
    assert set(scoped_vmem_asked(text, "kda_")) == {""}
    # the smallest thing that must not move: one layer of the conv pool
    conv_layer = (SSM_SLOTS + 1) * 3 * KDA_CHANNELS * 2
    expert_layer = 32 * 3 * 768 * 2560 * 2
    assert conv_layer < expert_layer
    # what the chip's compiler re-lays out for the 768-token products of a
    # mixed step (and not for a decode step's 256): two thirds of a KDA
    # layer's w_qkv and its w_f / w_out, 42 and 31 MB, six layers: 0.44 GB
    # a mixed step, counted in PERF.md; never a pool, never the experts
    relaid = ("bf16[8192,2560]", "bf16[768,8,2560]") \
        if key.kind == "mixed" else ()
    if key.kind == "chain":
        # over half the decode rows' tails (9.4 MB) and under a layer of
        # the conv pool: the held experts' gathered rows and the 256
        # tokens' picked rows a pair, activations of the routed layers
        relaid = ("bf16[3040,2560]", "bf16[256,8,2560]")
    moved = [m for m in pool_sized_movers(text, _tails_floor(
        key, conv_layer, KDA_CHANNELS))
             # ONE period's layer taken out of its stack of one period
             if not m[2].startswith("bf16[1,") and m[2] not in relaid]
    assert moved == [], moved
    assert stack_shaped_movers(text, params) == []
    assert compiled.memory_analysis().temp_size_in_bytes < expert_layer


# -- the Mamba-2 (SSD) family: its two kernels and its step programs ---------

SSD_LAYERS, SSD_HEADS, SSD_P, SSD_GROUPS, SSD_STATE = 6, 64, 64, 8, 128
SSD_INNER = SSD_HEADS * SSD_P
SSD_CHANNELS = SSD_INNER + 2 * SSD_GROUPS * SSD_STATE


def _ssd_pools(chip):
    return (chip((SSD_LAYERS, SSM_SLOTS + 1, SSD_STATE, SSD_INNER),
                 jnp.float32),
            _conv_pool(chip, SSD_LAYERS, SSD_CHANNELS))


@pytest.mark.parametrize("rows,q,kernel", [
    (256, 1, "ssd_state_update_decode"), (4, 128, "ssd_chunk_prefill"),
    (1, 1024, "ssd_chunk_prefill"), (4, 8, "ssd_chunk_prefill")])
def test_ssd_kernels(chip, rows, q, kernel):
    """Mamba-2's kernels at the published widths (64 heads of 64 over a
    state of 128, B and C in 8 groups): the update kernel (a row's whole
    [128, 4096] float32 state a grid step, walked a group's 512 lanes at a
    time) and the chunked kernel (one group's lanes and one chunk of up to
    128 tokens a grid step, the heads a pair a lane tile), both pools
    aliased in -> out, under the default scoped-VMEM limit."""
    from deepspeed_tpu.ops.ssm import ssd_chunk_len, ssd_scan
    f32 = jnp.float32
    assert [ssd_chunk_len(n) for n in (1, 8, 128, 1024, 192, 72)] \
        == [1, 8, 128, 128, 64, 8]
    h, conv = _ssd_pools(chip)
    text = compile_for_chip(
        lambda h, conv, layer, slots, fresh, dt, x, B, C, A, D, tail:
        ssd_scan(h, conv, layer, slots, fresh, dt, x, B, C, A, D, tail,
                 use_kernel=True),
        h, conv, chip((), jnp.int32), chip((rows,), jnp.int32),
        chip((rows,), jnp.bool_), chip((rows, q, SSD_HEADS), f32),
        chip((rows, q, SSD_INNER), f32),
        chip((rows, q, SSD_GROUPS * SSD_STATE), f32),
        chip((rows, q, SSD_GROUPS * SSD_STATE), f32),
        chip((SSD_HEADS,), f32), chip((SSD_HEADS,), f32),
        _tail(chip, rows, q, SSD_CHANNELS), kernel=kernel)
    assert set(scoped_vmem_asked(text, "ssd_")) == {""}


#: the window's two programs: a chained decode step, and a mixed step (256
#: decode rows and the lattice's 4-row prompt segment: both Mamba-2 kernels,
#: both page writes, the paged kernels at 32 query heads over 2 KV heads,
#: the two-matrix held experts).  (The probe's own programs, formed under
#: ``routing_sink``, are the Ling test's mechanism: a compile is 10 s of a
#: suite near its limit)
NEMOTRON_STEP_KEYS = {
    "chain-p40": (256, 1, 40, False, "chain", 256, True),
    "mixed-p40": (256, 1, 40, False, "mixed", 4, 128, 8, True, True),
}


@pytest.mark.parametrize("kind", sorted(NEMOTRON_STEP_KEYS))
def test_nemotron_step_program_moves_no_pool_and_no_expert_stack(
        chip, monkeypatch, kind):
    """The benchmark's cell at published widths (published layers 6-19:
    two periods of E M E M E M *, 16 of 128 experts held): the step programs
    lower for the chip with layers that are a mixer OR a feed-forward alone,
    a page pool of the TWO attention layers AND a state pool in one carry,
    the Mamba-2 kernels under their own names, the expert kernel over TWO
    matrices, and nothing the size of a layer of the conv pool (9.5 MB), let
    alone of the 3.2 GB state pool, the page pool or a layer of the held
    experts' stack, is copied, sliced out or re-laid out."""
    import dataclasses
    import json
    import os

    from flax.core import meta

    from benchmark.builders.serve_nemotron_h import source_of
    from deepspeed_tpu.accelerator import real_accelerator
    from deepspeed_tpu.inference.v2.model_implementations import (
        NemotronHInferenceModel)
    from deepspeed_tpu.inference.v2.ragged import KVCacheConfig
    from deepspeed_tpu.models.nemotron_h import NemotronHForCausalLM
    from deepspeed_tpu.moe.held import _rows_bound, row_tile

    monkeypatch.setattr(real_accelerator, "device_platform", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "nemotron-3-nano-serve-14l-ep8.json")) as f:
        config = json.load(f)
    model = NemotronHForCausalLM(source_of(config, False),
                                 first_layer=config["first_layer"])
    assert model.cfg.layer_kinds == ("ffn", "ssd", "ffn", "ssd", "ffn",
                                     "ssd", "full") * 2
    params = jax.eval_shape(lambda k: meta.unbox(model.init_params(k)),
                            jax.random.key(0))
    assert sorted(params["periods"]) == [f"l{j}" for j in range(7)]
    assert set(params["experts"]) == {"wu", "wd"}
    assert params["experts"]["wu"].shape == (6, 16, 1856, 2688)
    # 1,536 pairs of a 256-row step over 128 scored: 12 an expert, for which
    # the rule gives tiles of 32 rows; the family says 64 (rows that route
    # alike: ``models/nemotron_h.py::ROW_TILE``)
    assert row_tile(256, 256 * 6 / 128) == 32
    assert model.cfg.moe_row_tile == 64
    pages = 1024
    serve = NemotronHInferenceModel(
        model.cfg, params, kv_config=KVCacheConfig(
            num_layers=2, kv_heads=2, head_dim=128, page_size=PAGE,
            num_pages=pages))
    serve.state_config = dataclasses.replace(serve.state_config,
                                             num_slots=SSM_SLOTS)
    assert serve.pool_names == ("pages", "state", "conv")
    pool = (chip((2, pages + 1, 2, 2, PAGE, 128), jnp.bfloat16),
            *_ssd_pools(chip))
    assert [tuple(a.shape) for a in pool[1:]] \
        == list(serve.state_config.shapes())
    key = StepKey.parse(NEMOTRON_STEP_KEYS[kind])
    avals = jax.tree.map(
        lambda a: chip(a.shape, a.dtype) if hasattr(a, "shape") else a,
        step_avals(serve, key, pool))
    assert (key.S, key.P + 1) in [a.shape for a in avals[2:]]
    compiled = jax.jit(step_program(serve, key),
                       donate_argnums=(1,)).lower(*avals).compile()
    text = compiled.as_text()
    # no record of the routing in a program of the window
    assert "xla_ffi_python_cpu_callback" not in text
    kernels = ["ssd_state_update_decode", "conv_tail_decode",
               "paged_attention_decode", "kv_write_decode", "moe_expert_ffn"]
    if key.kind == "mixed":
        kernels += ["ssd_chunk_prefill"]
    for kernel in kernels:
        assert kernel_calls(text, kernel), kernel
    assert not kernel_calls(text, "ssm_") and not kernel_calls(text, "kda_")
    # a period's three routed layers and three Mamba-2 layers each have a
    # body of their own in the period's scan: three calls each, not six
    assert len(kernel_calls(text, "moe_expert_ffn")) == 3
    assert len(kernel_calls(text, "ssd_state_update_decode")) == 3
    assert set(scoped_vmem_asked(text, "ssd_")) == {""}
    assert set(scoped_vmem_asked(text, "moe_expert_ffn")) == {""}
    # the smallest thing that must not move: one layer of the conv pool
    conv_layer = (SSM_SLOTS + 1) * 3 * SSD_CHANNELS * 2
    expert_layer = 16 * 2 * 1856 * 2688 * 2
    tokens = key.padded_tokens
    rows_bound = _rows_bound(tokens * 6, 16, model.cfg.moe_row_tile)
    assert conv_layer < expert_layer
    moved = [m for m in pool_sized_movers(text, _tails_floor(
        key, conv_layer, SSD_CHANNELS))
             # ONE period's layer taken out of its stack of two periods,
             # inside the fusion of the product that reads it
             if not m[2].startswith("bf16[1,")
             # the held experts' gathered rows, the bound that holds however
             # the pairs fall (activations: ``held._rows_bound``)
             and m[2] != f"bf16[{rows_bound},2688]"
             # a mixed step's 768 tokens: the experts' picked rows a pair,
             # and the Mamba-2 output re-laid by group for its gated norm
             # (12.6 MB of activations a layer: PERF.md section 7)
             and m[2] not in ("bf16[768,6,2688]", "f32[96,8,8,512]")
             # a chain step's 256 tokens' picked rows a pair: over half
             # the decode rows' tails (4.7 MB), under a conv pool's layer
             and not (key.kind == "chain" and m[2] == "bf16[256,6,2688]")]
    assert moved == [], moved
    assert stack_shaped_movers(text, params) == []
    assert compiled.memory_analysis().temp_size_in_bytes < expert_layer
