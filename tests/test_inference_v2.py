"""Inference v2 (FastGen-equivalent) tests.

Mirrors the reference suites ``tests/unit/inference/v2/ragged/`` (allocator
and manager logic) and ``tests/unit/inference/v2/kernels/ragged_ops/``
(paged attention numerics), plus an end-to-end check that ragged paged
decoding reproduces the full-sequence forward exactly.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (
    BlockedAllocator, InferenceEngineV2, KVCacheConfig,
    RaggedInferenceEngineConfig, RaggedInferenceModel, SamplingParams,
    SchedulingError, SchedulingResult, StateManagerConfig, generate, sample)
from deepspeed_tpu.inference.v2.ragged import build_batch, SequenceDescriptor
from deepspeed_tpu.models.llama import LlamaForCausalLM
from deepspeed_tpu.models.transformer import forward
from deepspeed_tpu.ops import paged_attention as pa
from flax.core import meta


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------

class TestBlockedAllocator:
    def test_allocate_free_cycle(self):
        a = BlockedAllocator(8)
        p1 = a.allocate(3)
        assert a.free_pages == 5
        assert len(set(p1.tolist())) == 3
        assert all(1 <= p <= 8 for p in p1)
        p2 = a.allocate(5)
        assert a.free_pages == 0
        assert set(p1.tolist()) | set(p2.tolist()) == set(range(1, 9))
        with pytest.raises(ValueError):
            a.allocate(1)
        a.free(p1)
        assert a.free_pages == 3
        p3 = a.allocate(3)
        assert set(p3.tolist()) == set(p1.tolist())

    def test_invalid_free(self):
        a = BlockedAllocator(4)
        with pytest.raises(ValueError):
            a.free([0])       # null page is not allocatable
        with pytest.raises(ValueError):
            a.free([5])


# ---------------------------------------------------------------------------
# paged attention numerics
# ---------------------------------------------------------------------------

#: the ops take the whole pool and a layer index; these tests write and
#: read layer 1 of a two-layer pool whose layer 0 is noise
LAYER = 1


class TestPagedAttention:
    def _setup(self, S=3, Q=4, K=2, G=2, D=16, page=8, pages=32, hist=(5, 0, 11)):
        rng = np.random.default_rng(0)
        H = K * G
        kv = jnp.zeros((pages + 1, 2, K, page, D), jnp.float32)
        alloc = BlockedAllocator(pages)
        descs, ctx_k, ctx_v = [], [], []
        max_pages = 8
        table = np.zeros((S, max_pages), np.int32)
        start = np.zeros(S, np.int32)
        q_lens = np.zeros(S, np.int32)
        for s in range(S):
            h = hist[s]
            total = h + Q
            n_pages = -(-total // page)
            pgs = alloc.allocate(n_pages)
            table[s, :n_pages] = pgs
            start[s] = h
            q_lens[s] = Q
            # fill history KV
            if h:
                hk = rng.standard_normal((h, K, D)).astype(np.float32)
                hv = rng.standard_normal((h, K, D)).astype(np.float32)
                for t in range(h):
                    kv = kv.at[pgs[t // page], 0, :, t % page].set(hk[t])
                    kv = kv.at[pgs[t // page], 1, :, t % page].set(hv[t])
            else:
                hk = np.zeros((0, K, D), np.float32)
                hv = np.zeros((0, K, D), np.float32)
            ctx_k.append(hk)
            ctx_v.append(hv)
        q = jnp.asarray(rng.standard_normal((S, Q, H, D)), jnp.float32)
        k_new = jnp.asarray(rng.standard_normal((S, Q, K, D)), jnp.float32)
        v_new = jnp.asarray(rng.standard_normal((S, Q, K, D)), jnp.float32)
        noise = jnp.asarray(rng.standard_normal(kv.shape), jnp.float32)
        kv = jnp.stack([noise, kv])           # [L=2, P+1, 2, K, page, D]
        return (q, k_new, v_new, kv, jnp.asarray(table), jnp.asarray(start),
                jnp.asarray(q_lens), ctx_k, ctx_v, page)

    def test_write_then_attend_matches_dense(self):
        (q, k_new, v_new, kv, table, start, q_lens,
         ctx_k, ctx_v, page) = self._setup()
        S, Q, H, D = q.shape
        K = k_new.shape[2]
        kv = pa.write_kv(kv, LAYER, k_new, v_new, table, start, q_lens)
        out = pa.paged_attention(q, kv, LAYER, table, start, q_lens)

        # dense reference: per-slot history + new tokens, aligned to C rows
        C = table.shape[1] * page
        k_ctx = np.zeros((S, C, K, D), np.float32)
        v_ctx = np.zeros((S, C, K, D), np.float32)
        for s in range(S):
            h = len(ctx_k[s])
            k_ctx[s, :h] = ctx_k[s]
            v_ctx[s, :h] = ctx_v[s]
            k_ctx[s, h:h + Q] = np.asarray(k_new[s])
            v_ctx[s, h:h + Q] = np.asarray(v_new[s])
        ref = pa.attention_reference(q, jnp.asarray(k_ctx), jnp.asarray(v_ctx),
                                     start, q_lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_pallas_decode_kernel_matches_jnp(self):
        """Q=1 Pallas decode (interpret mode on CPU) == jnp gather path."""
        (q, k_new, v_new, kv, table, start, q_lens,
         _, _, _) = self._setup(Q=1, D=128, hist=(5, 0, 11))
        kv = pa.write_kv(kv, LAYER, k_new, v_new, table, start, q_lens)
        ref = pa.paged_attention(q, kv, LAYER, table, start, q_lens,
                                 interpret=False)  # jnp path off-TPU
        out = pa.paged_decode_attention(q, kv, LAYER, table, start, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_sliding_window_paged_matches_dense(self):
        """Mistral sliding window over paged KV == dense windowed
        reference, for both the jnp gather path and the Pallas decode
        kernel (interpret mode), incl. sequences longer than the window."""
        window = 6
        (q, k_new, v_new, kv, table, start, q_lens,
         ctx_k, ctx_v, page) = self._setup(hist=(5, 0, 11))
        S, Q, H, D = q.shape
        K = k_new.shape[2]
        kv = pa.write_kv(kv, LAYER, k_new, v_new, table, start, q_lens)
        out = pa.paged_attention(q, kv, LAYER, table, start, q_lens,
                                 use_kernel=False, window=window)
        C = table.shape[1] * page
        k_ctx = np.zeros((S, C, K, D), np.float32)
        v_ctx = np.zeros((S, C, K, D), np.float32)
        for s in range(S):
            h = len(ctx_k[s])
            k_ctx[s, :h] = ctx_k[s]
            v_ctx[s, :h] = ctx_v[s]
            k_ctx[s, h:h + Q] = np.asarray(k_new[s])
            v_ctx[s, h:h + Q] = np.asarray(v_new[s])
        ref = pa.attention_reference(q, jnp.asarray(k_ctx),
                                     jnp.asarray(v_ctx), start, q_lens,
                                     window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        # window must change the answer where history exceeds it
        full = pa.paged_attention(q, kv, LAYER, table, start, q_lens,
                                  use_kernel=False)
        assert not np.allclose(np.asarray(out)[2], np.asarray(full)[2])

    def test_sliding_window_decode_kernel_matches_jnp(self):
        window = 4
        (q, k_new, v_new, kv, table, start, q_lens,
         _, _, _) = self._setup(Q=1, D=128, hist=(5, 0, 11))
        kv = pa.write_kv(kv, LAYER, k_new, v_new, table, start, q_lens)
        ref = pa.paged_attention(q, kv, LAYER, table, start, q_lens,
                                 use_kernel=False, window=window)
        out = pa.paged_decode_attention(q, kv, LAYER, table, start,
                                        window=window, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_pallas_decode_kernel_alibi_matches_jnp(self):
        """ALiBi bias agrees between the Pallas kernel (interpret) and
        the jnp gather path (the bloom decode hot path)."""
        from deepspeed_tpu.models.transformer import alibi_slopes
        (q, k_new, v_new, kv, table, start, q_lens,
         _, _, _) = self._setup(Q=1, D=128, hist=(5, 0, 11))
        H = q.shape[2]
        slopes = alibi_slopes(H)
        kv = pa.write_kv(kv, LAYER, k_new, v_new, table, start, q_lens)
        ref = pa.paged_attention(q, kv, LAYER, table, start, q_lens,
                                 use_kernel=False, alibi_slopes=slopes)
        out = pa.paged_decode_attention(q, kv, LAYER, table, start,
                                        alibi_slopes=slopes, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_pallas_decode_kernel_gqa_groups(self):
        (q, k_new, v_new, kv, table, start, q_lens,
         _, _, _) = self._setup(S=4, Q=1, K=2, G=4, D=128,
                                hist=(0, 7, 16, 40))
        kv = pa.write_kv(kv, LAYER, k_new, v_new, table, start, q_lens)
        ref = pa.paged_attention(q, kv, LAYER, table, start, q_lens,
                                 interpret=False)
        out = pa.paged_decode_attention(q, kv, LAYER, table, start, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_rotated_keys_written_alike_by_scatter_and_kernel(self):
        """The model rotates K and then writes it: the XLA scatter and
        the tile kernel (interpret mode) store the same rotated rows."""
        from deepspeed_tpu.models.transformer import apply_rope, rope_table
        from deepspeed_tpu.models.llama import llama_config
        (q, k_new, v_new, kv, table, start, q_lens,
         _, _, _) = self._setup()
        cfg = llama_config("debug", head_dim=16)
        pos = pa.token_positions(start, k_new.shape[1])
        sin, cos = rope_table(cfg, pos)
        k_rot = apply_rope(k_new, sin, cos)
        scatter = pa.write_kv(kv, LAYER, k_rot, v_new, table, start, q_lens,
                              use_kernel=False)
        kernel = pa.write_kv(kv, LAYER, k_rot, v_new, table, start, q_lens,
                             interpret=True)
        np.testing.assert_array_equal(np.asarray(kernel), np.asarray(scatter))

    def test_padding_slot_writes_go_to_null_page(self):
        q, k_new, v_new, kv, table, start, q_lens = self._setup()[:7]
        q_lens = q_lens.at[1].set(0)  # slot 1 becomes padding
        kv2 = pa.write_kv(kv, LAYER, k_new, v_new, table, start, q_lens)
        # slot 1's pages must be untouched
        pages_1 = np.asarray(table[1])
        pages_1 = pages_1[pages_1 > 0]
        np.testing.assert_array_equal(np.asarray(kv2[LAYER, pages_1]),
                                      np.asarray(kv[LAYER, pages_1]))
        np.testing.assert_array_equal(np.asarray(kv2[0]), np.asarray(kv[0]))


    def test_cache_layout_is_one_page_tile_per_head(self):
        """[L, P+1, 2, K, page, D]: token t of a sequence lands in its page
        at [layer, page_id, k/v, :, t % page] — a page's [2, K, page, D]
        block is contiguous, and the Pallas kernels DMA it whole."""
        (q, k_new, v_new, kv, table, start, q_lens,
         _, _, page) = self._setup()
        S, Q, K, D = k_new.shape
        assert kv.shape[2:] == (2, K, page, D)
        kv2 = pa.write_kv(kv, LAYER, k_new, v_new, table, start, q_lens)
        for s in range(S):
            for i in range(Q):
                t = int(start[s]) + i
                pid = int(table[s, t // page])
                np.testing.assert_array_equal(
                    np.asarray(kv2[LAYER, pid, 0, :, t % page]),
                    np.asarray(k_new[s, i]))
                np.testing.assert_array_equal(
                    np.asarray(kv2[LAYER, pid, 1, :, t % page]),
                    np.asarray(v_new[s, i]))
        # and the testing helper reads it back token-major
        k_ctx, v_ctx = pa.paged_context(kv2, LAYER, table)
        assert k_ctx.shape == (S, table.shape[1] * page, K, D)
        s, t = 2, int(start[2]) + 1
        np.testing.assert_array_equal(np.asarray(k_ctx[s, t]),
                                      np.asarray(k_new[s, 1]))

    @pytest.mark.parametrize("window", [None, 6])
    @pytest.mark.parametrize("q_rows", [1, 4])
    def test_int8_pages_kernel_matches_dense_gather(self, q_rows, window):
        """Quantized pages: scale sidecar [L, P+1, 2, K, page], applied by
        the kernel to the score / probability tile instead of the
        [page, D] payload — same answers as the dense-gather path that
        dequantizes the gathered context."""
        (q, k_new, v_new, kv, table, start, q_lens,
         _, _, page) = self._setup(Q=q_rows)
        layer = pa.KVPages(jnp.zeros(kv.shape, jnp.int8),
                           jnp.zeros(kv.shape[:-1], jnp.float32))
        # history rows quantize through the same append path
        codes, scales = pa.quantize_kv_blocks(kv)
        layer = pa.KVPages(codes, scales)
        layer = pa.write_kv(layer, LAYER, k_new, v_new, table, start, q_lens)
        assert layer.scale.shape == layer.payload.shape[:-1]
        dense = pa.paged_attention(q, layer, LAYER, table, start, q_lens,
                                   use_kernel=False, window=window)
        kernel = pa.paged_attention(q, layer, LAYER, table, start, q_lens,
                                    use_kernel=True, window=window,
                                    interpret=True)
        np.testing.assert_allclose(np.asarray(kernel), np.asarray(dense),
                                   rtol=2e-5, atol=2e-5)


def _ragged_inputs(Q, K, G, D, page, P, ctxs, fmt, seed=0):
    """A two-layer pool of noise (the null page and layer 0 included), a
    query block and a page table whose row ``s`` holds ``ceil(ctxs[s] /
    page)`` distinct pages and the null page in every slot after them;
    row ``s`` attends ``ctxs[s]`` tokens, its ``Q`` new ones the last."""
    rng = np.random.default_rng(seed)
    S = len(ctxs)
    dtype = jnp.bfloat16 if fmt == "bf16" else jnp.float32
    pool = jnp.asarray(rng.standard_normal(
        (2, S * P + 1, 2, K, page, D)), dtype)
    if fmt == "int8":
        pool = pa.KVPages(*pa.quantize_kv_blocks(pool))
    table = np.zeros((S, P), np.int32)
    free = iter(rng.permutation(S * P) + 1)
    for s, ctx in enumerate(ctxs):
        assert Q <= ctx <= P * page, (Q, ctx, P * page)
        for slot in range(-(-ctx // page)):
            table[s, slot] = next(free)
    q = jnp.asarray(rng.standard_normal((S, Q, K * G, D)), dtype)
    start = jnp.asarray([ctx - Q for ctx in ctxs], jnp.int32)
    return q, pool, jnp.asarray(table), start


def _row_contexts(Q, page, P):
    """Contexts of one batch: ending mid-page, exactly on a page boundary,
    inside the first page (or as near as ``Q`` allows) and in the
    bucket's last page."""
    cap = P * page
    mid = min(max(Q + page // 2 + 1, (P // 2) * page + page // 2 + 1),
              cap - 1)
    edge = max(-(-Q // page), P // 2, 1) * page
    return (mid, edge, max(Q, 3), cap - 2 if cap - 2 >= Q else cap)


def _assert_kernel_matches_gather(q, pool, table, start, fmt, **kw):
    lens = jnp.full(start.shape, q.shape[1], jnp.int32)
    want = pa.paged_attention(q, pool, LAYER, table, start, lens,
                              use_kernel=False, **kw)
    got = pa.paged_attention(q, pool, LAYER, table, start, lens,
                             use_kernel=True, interpret=True, **kw)
    # bfloat16 rounds the probabilities before the second matmul in both
    # forms, normalised in one and not in the other
    tol = 2e-2 if fmt == "bf16" else 5e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def _blocks_of(q, pool, P, alibi=False):
    """``kernel_blocks`` as ``paged_decode_attention`` calls it."""
    arr = pool.payload if isinstance(pool, pa.KVPages) else pool
    K, page, D = arr.shape[3:]
    return pa.kernel_blocks(q.shape[1] * (q.shape[2] // K), K, D, page, P,
                            q.dtype.itemsize, arr.dtype.itemsize,
                            isinstance(pool, pa.KVPages), alibi)


class TestRaggedKernelParity:
    """The ragged Pallas kernel (interpret mode) against the dense-gather
    ``jnp`` form, over the block shapes ``kernel_blocks`` picks (PR 28):
    all KV heads of a group of pages a grid step, fewer heads for a large
    query block, nothing for a slot past a row's context."""

    @pytest.mark.parametrize("alibi", [False, True], ids=["rope", "alibi"])
    @pytest.mark.parametrize("window", [False, True], ids=["full", "window"])
    @pytest.mark.parametrize("fmt", ["bf16", "int8"])
    @pytest.mark.parametrize("Q", [1, 5, 128])
    def test_rows_formats_masks_and_bias(self, Q, fmt, window, alibi):
        from deepspeed_tpu.models.transformer import alibi_slopes
        K, G, D, P = 2, 2, 32, 8
        page = 32 if Q > 8 else 8
        ctxs = _row_contexts(Q, page, P)
        q, pool, table, start = _ragged_inputs(Q, K, G, D, page, P, ctxs, fmt)
        assert _blocks_of(q, pool, P, alibi) == (K, 8)
        _assert_kernel_matches_gather(
            q, pool, table, start, fmt,
            # shorter than three of the four contexts, and than a group
            window=(page * 2 + 3) if window else None,
            alibi_slopes=alibi_slopes(K * G) if alibi else None)

    @pytest.mark.parametrize("fmt", ["bf16", "int8"])
    @pytest.mark.parametrize("Q", [1, 5])
    @pytest.mark.parametrize("P,group", [(1, 1), (2, 2), (4, 4), (8, 8),
                                         (5, 1), (12, 4), (16, 8)])
    def test_page_buckets(self, P, group, Q, fmt):
        K, G, D, page = 2, 2, 32, 8
        ctxs = _row_contexts(Q, page, P)
        q, pool, table, start = _ragged_inputs(Q, K, G, D, page, P, ctxs, fmt)
        assert _blocks_of(q, pool, P) == (K, group)
        _assert_kernel_matches_gather(q, pool, table, start, fmt)

    @pytest.mark.parametrize("fmt", ["bf16", "int8"])
    @pytest.mark.parametrize("Q", [1, 5])
    @pytest.mark.parametrize("K,G", [(1, 4), (8, 2), (8, 1)],
                             ids=["multi-query", "grouped", "one-to-one"])
    def test_head_layouts(self, K, G, Q, fmt):
        D, page, P = 32, 8, 4
        ctxs = _row_contexts(Q, page, P)
        q, pool, table, start = _ragged_inputs(Q, K, G, D, page, P, ctxs, fmt)
        assert _blocks_of(q, pool, P) == (K, 4)
        _assert_kernel_matches_gather(q, pool, table, start, fmt,
                                      window=page + 3)

    @pytest.mark.parametrize("fmt,window,alibi,heads", [
        ("bf16", False, False, 4), ("int8", True, False, 2),
        ("int8", False, True, 2)])
    def test_fewer_heads_a_step(self, fmt, window, alibi, heads):
        """A 128-token chunk at Mistral's head geometry: the K x Q*G rows
        do not fit beside 8 pages, so the head axis comes back as a grid
        dim and a step holds half the heads (fewer for the float32
        queries of the int8 cases, fewer again under a bias)."""
        from deepspeed_tpu.models.transformer import alibi_slopes
        Q, K, G, D, page, P = 128, 8, 4, 128, 64, 8
        q, pool, table, start = _ragged_inputs(
            Q, K, G, D, page, P, (128 + 37, 510), fmt)
        assert _blocks_of(q, pool, P, alibi) == (heads, 8)
        _assert_kernel_matches_gather(
            q, pool, table, start, fmt, window=150 if window else None,
            alibi_slopes=alibi_slopes(K * G) if alibi else None)

    @pytest.mark.parametrize("rows,K,D,page,P,int8,blocks", [
        (4, 8, 128, 64, 8, False, (8, 8)),        # the cell's decode step
        (4, 8, 128, 64, 64, True, (8, 8)),
        (4, 8, 128, 64, 24, False, (8, 8)),
        (4, 8, 128, 64, 12, False, (8, 4)),
        (4, 8, 128, 64, 6, False, (8, 2)),
        (4, 8, 128, 64, 5, False, (8, 1)),
        (20, 8, 128, 64, 8, False, (8, 8)),       # speculative rows, Q = 5
        (128, 8, 128, 64, 8, False, (8, 8)),      # a 32-token chunk
        (512, 8, 128, 64, 8, False, (4, 8)),      # a 128-token chunk
        (1024, 8, 128, 64, 8, False, (2, 8)),
        (2048, 8, 128, 64, 8, False, (1, 8)),
        (4096, 8, 128, 64, 8, False, (1, 1)),     # MAX_KERNEL_Q_ROWS
        (32, 1, 128, 64, 8, False, (1, 8)),       # multi-query
        # PR 41: a step is sized by its bytes, every head of a page in it.
        # K = H = 32: a page is 1 MiB, 2 slots are STEP_BYTES (was (16, 8):
        # 8 slots of half the heads, 4 MiB a step)
        (1, 32, 128, 64, 8, False, (32, 2)),
        # head_dim 256: a 512 KB page, 4 slots (was (8, 8): 4 MiB a step)
        (2, 8, 256, 64, 8, False, (8, 4)),
        # 1 MiB pages of 256 tokens: 2 slots of every head (was (4, 8):
        # half the heads, a page in two strided pieces a head)
        (4, 8, 128, 256, 8, False, (8, 2)),
        (1, 30, 128, 64, 40, False, (30, 2)),     # 960 KB pages (Olmo)
        (1, 30, 128, 64, 5, False, (30, 1)),
        (128, 30, 128, 64, 8, False, (10, 8)),    # its prompt row: the rows'
    ])
    def test_blocks_follow_the_shapes(self, rows, K, D, page, P, int8,
                                      blocks):
        assert pa.kernel_blocks(rows, K, D, page, P, 2, 1 if int8 else 2,
                                int8) == blocks

    @pytest.mark.parametrize("alibi", [False, True], ids=["rope", "alibi"])
    @pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
    @pytest.mark.parametrize("D,page", [(128, 16), (128, 64), (256, 64),
                                        (128, 256)])
    @pytest.mark.parametrize("K", [1, 8, 30, 32])
    def test_a_step_is_sized_by_its_bytes(self, K, D, page, int8, alibi):
        """The rule's own properties, over pages of 8 KB (one head) to
        1 MiB and more (32 heads of 256 tokens: 4 MiB): the blocks divide
        the shapes and fit the account; every head is in the step whenever
        the step fits at the group the bytes give, and then the step's
        pages weigh at most ``STEP_BYTES`` unless it holds one."""
        itemsize = 1 if int8 else 2
        page_bytes = 2 * K * page * D * itemsize
        for rows in (1, 4, 20, 128, 512, 4096):
            for P in (1, 5, 6, 8, 12, 40):
                heads, group = pa.kernel_blocks(rows, K, D, page, P, 2,
                                                itemsize, int8, alibi)
                account = functools.partial(
                    pa.step_vmem_bytes, rows=rows, kv_heads=K, head_dim=D,
                    page_size=page, q_itemsize=2, kv_itemsize=itemsize,
                    has_scale=int8, has_alibi=alibi)
                case = (rows, P, heads, group)
                assert K % heads == 0 and P % group == 0, case
                assert group in (8, 4, 2, 1), case
                assert ((heads, group) == (1, 1)
                        or account(heads, group) <= pa.VMEM_BUDGET), case
                by_bytes = max(g for g in (8, 4, 2, 1) if P % g == 0
                               and (g == 1 or g * page_bytes
                                    <= pa.STEP_BYTES))
                if account(K, by_bytes) <= pa.VMEM_BUDGET:
                    assert (heads, group) == (K, by_bytes), case
                    assert (group == 1
                            or group * page_bytes <= pa.STEP_BYTES), case
                else:       # the query rows size the step: PR 28's blocks,
                    # the widest group first, then the most heads that fit
                    assert all(
                        account(h, g) > pa.VMEM_BUDGET
                        for g in (8, 4, 2, 1) if P % g == 0
                        for h in range(1, K + 1) if K % h == 0
                        and (g > group or (g == group and h > heads))), case

    @pytest.mark.parametrize("Q", [1, 128], ids=["decode", "prompt-row"])
    @pytest.mark.parametrize("P", [8, 40])
    def test_thirty_kv_heads_of_one_query_head(self, P, Q):
        """Olmo's full layer (30 / 30 heads, a 960 KB page) under the
        blocks the rule gives it, against the dense reference: contexts
        that end inside a group of 2 slots (its second slot the null
        page), at a group's edge, inside the first page and in the
        bucket's last page, tables null-padded."""
        K, D, page = 30, 128, 64
        span = 2 * page
        ctxs = (max((P // 4) * span + 10, Q + 1), max(P // 4, 1) * span,
                max(Q, 3), P * page - 2)
        q, pool, table, start = _ragged_inputs(Q, K, 1, D, page, P, ctxs,
                                               "bf16")
        assert _blocks_of(q, pool, P) == ((30, 2) if Q == 1 else (10, 8))
        got = pa.paged_decode_attention(q, pool, LAYER, table, start,
                                        interpret=True)
        lens = jnp.full(start.shape, Q, jnp.int32)
        want = pa.attention_reference(
            q.astype(jnp.float32),
            *pa.paged_context(pool, LAYER, table, jnp.float32), start, lens)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want), rtol=2e-2, atol=2e-2)


def _placed_by_hand(pool, layer, k_new, v_new, table, start, q_lens):
    """The per-layer reference of the cache write: numpy loops that put
    token i of row s at ``[layer, table[s, pos // page], k/v, :, pos %
    page]`` and touch nothing else (a padding token lands nowhere)."""
    out = np.array(pool)
    page = out.shape[4]
    for s in range(k_new.shape[0]):
        for i in range(int(q_lens[s])):
            pos = int(start[s]) + i
            pid = int(table[s, pos // page])
            out[layer, pid, 0, :, pos % page] = np.asarray(k_new[s, i])
            out[layer, pid, 1, :, pos % page] = np.asarray(v_new[s, i])
    return out


#: name -> (Q, page, start_pos per row, q_lens per row, window, int8)
POOL_CASES = {
    "decode": (1, 8, (5, 0, 11), (1, 1, 1), None, False),
    "chunk_crossing_a_page": (6, 8, (5, 13, 3), (6, 6, 4), None, False),
    "fresh_128_token_prefill": (128, 16, (0, 0), (128, 97), None, False),
    "padded_rows": (4, 8, (5, 0, 11), (4, 0, 2), None, False),
    "sliding_window": (4, 8, (5, 0, 11), (4, 4, 4), 6, False),
    "int8_pages": (4, 8, (5, 0, 11), (4, 3, 4), None, True),
    "int8_decode": (1, 8, (5, 0, 11), (1, 1, 0), None, True),
}


class TestPoolAndLayerOps:
    """``write_kv`` and ``paged_attention`` take the whole pool and a
    layer index (PR 25); both of their forms — the XLA scatter with the
    dense gather, and the two Pallas kernels in interpret mode — equal
    the per-layer reference, at a layer other than 0, and leave every
    other layer, page and slot of the pool bit-identical."""

    @pytest.mark.parametrize("form", ["jnp", "kernel"])
    @pytest.mark.parametrize("case", sorted(POOL_CASES))
    def test_write_and_attend_match_per_layer_reference(self, case, form):
        Q, page, start, q_lens, window, int8 = POOL_CASES[case]
        S, K, G, D, L, layer = len(start), 2, 2, 16, 3, 1
        per_seq = -(-(max(start) + Q) // page)
        rng = np.random.default_rng(7)
        table = (1 + rng.permutation(S * per_seq)).reshape(S, per_seq)
        table = jnp.asarray(table, jnp.int32)
        start, q_lens = (jnp.asarray(x, jnp.int32) for x in (start, q_lens))
        shape = (L, S * per_seq + 1, 2, K, page, D)
        pool = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        q, k_new, v_new = (jnp.asarray(rng.standard_normal((S, Q, n, D)),
                                       jnp.float32) for n in (K * G, K, K))
        kernel = dict(use_kernel=True, interpret=True)
        how = kernel if form == "kernel" else dict(use_kernel=False)

        if int8:
            codes, scales = pa.quantize_kv_blocks(pool)
            got = pa.write_kv(pa.KVPages(codes, scales), layer, k_new,
                              v_new, table, start, q_lens, **how)
            # codes and scales of a row sit at the same address
            new_codes, new_scales = pa.quantize_kv_blocks(
                jnp.stack([k_new, v_new]))
            want = _placed_by_hand(codes, layer, *new_codes, table, start,
                                   q_lens)
            want_scale = _placed_by_hand(
                scales[..., None], layer, *new_scales[..., None], table,
                start, q_lens)[..., 0]
            np.testing.assert_array_equal(
                np.asarray(got.scale)[:, 1:], want_scale[:, 1:])
            got_payload = got.payload
            want_pool = pa.KVPages(jnp.asarray(want),
                                   jnp.asarray(want_scale))
        else:
            got = pa.write_kv(pool, layer, k_new, v_new, table, start,
                              q_lens, **how)
            want = _placed_by_hand(pool, layer, k_new, v_new, table, start,
                                   q_lens)
            got_payload, want_pool = got, jnp.asarray(want)
        # every real page of every layer: the new rows, and nothing else
        # (page 0 is the null page, where the scatter parks padding)
        np.testing.assert_array_equal(np.asarray(got_payload)[:, 1:],
                                      want[:, 1:])
        np.testing.assert_array_equal(np.asarray(got_payload)[layer - 1],
                                      want[layer - 1])

        out = pa.paged_attention(q, got, layer, table, start, q_lens,
                                 window=window, **how)
        k_ctx, v_ctx = pa.paged_context(want_pool, layer, table)
        ref = pa.attention_reference(q, k_ctx, v_ctx, start, q_lens,
                                     window=window)
        rows = np.arange(Q)[None, :] < np.asarray(q_lens)[:, None]
        np.testing.assert_allclose(np.asarray(out)[rows],
                                   np.asarray(ref)[rows],
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("scan_layers", [True, False],
                             ids=["scanned", "unrolled"])
    def test_layer_loop_carries_the_pool(self, scan_layers):
        """Both layer loops hand every layer the whole pool: a chunked
        prefill plus one decode step equals the full-sequence forward,
        and each layer's pages hold that layer's own K/V."""
        model_def = LlamaForCausalLM("debug", max_seq_len=256,
                                     dtype=jnp.float32,
                                     scan_layers=scan_layers)
        params = meta.unbox(model_def.init_params(jax.random.key(0)))
        cfg = model_def.cfg
        kv_cfg = KVCacheConfig(num_layers=cfg.num_layers,
                               kv_heads=cfg.kv_heads,
                               head_dim=cfg.dims_per_head, page_size=16,
                               num_pages=16, dtype=jnp.float32)
        eng = InferenceEngineV2(
            RaggedInferenceModel(cfg, params, kv_config=kv_cfg),
            RaggedInferenceEngineConfig(state_manager=StateManagerConfig(
                max_tracked_sequences=4, max_ragged_sequence_count=4,
                max_ragged_batch_size=64)))
        prompt = np.random.default_rng(4).integers(0, 128, 24).astype(
            np.int32)
        eng.put([0], [prompt[:10]])
        logits = eng.put([0], [prompt[10:]])
        full = forward(cfg, params, prompt[None, :])
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   np.asarray(full[0, -1]),
                                   rtol=5e-2, atol=5e-2)
        kv = np.asarray(eng._state.kv_cache.data)
        assert kv.shape[0] == cfg.num_layers
        written = np.abs(kv).sum(axis=(2, 3, 4, 5)) > 0      # [L, P+1]
        assert (written[:, 1:].sum(axis=1) == 2).all()       # 24 tokens
        assert not np.allclose(kv[0], kv[1])


class TestPageCodecsRoundTripTheLayout:
    """Offload / snapshot / handoff / tier codecs address pages on axis 1
    of ``[L, P+1, 2, K, page, D]`` and carry the rest opaquely."""

    def _cache(self, quantization="none"):
        from deepspeed_tpu.inference.v2.ragged.kv_cache import (
            BlockedKVCache)
        cfg = KVCacheConfig(num_layers=2, kv_heads=2, head_dim=8,
                            page_size=4, num_pages=6, dtype=jnp.float32,
                            quantization=quantization)
        cache = BlockedKVCache(cfg)
        assert cfg.cache_shape() == (2, 7, 2, 2, 4, 8)
        rng = np.random.default_rng(0)
        if cfg.quantized:
            assert cache.data.scale.shape == cfg.cache_shape()[:-1]
            cache.data = pa.KVPages(
                jnp.asarray(rng.integers(-127, 128, cfg.cache_shape()),
                            jnp.int8),
                jnp.asarray(rng.random(cfg.cache_shape()[:-1]),
                            jnp.float32))
        else:
            cache.data = jnp.asarray(rng.normal(size=cfg.cache_shape()),
                                     jnp.float32)
        return cache

    @pytest.mark.parametrize("quantization", ["none", "int8"])
    def test_read_offload_restore(self, quantization):
        from deepspeed_tpu.inference.v2.ragged.kv_cache import (
            PageBlob, blob_columns, concat_blobs)
        cache = self._cache(quantization)
        leaves = jax.tree.leaves(cache.data)
        before = [np.asarray(x) for x in leaves]
        pages = cache.reserve(3)
        blob = cache.read_pages(pages)
        assert blob.shape == (2, 3, 2, 2, 4, 8)
        if quantization == "int8":
            assert isinstance(blob, PageBlob)
            assert blob.scale.shape == (2, 3, 2, 2, 4)
        # tier / selective-import codecs: column split and reassembly
        again = concat_blobs([blob_columns(blob, [i]) for i in range(3)])
        blob2 = cache.offload_pages(pages)
        new_pages = cache.restore_pages(again)
        for got, want in zip(jax.tree.leaves(cache.data), before):
            np.testing.assert_array_equal(
                np.asarray(got)[:, new_pages], want[:, np.asarray(pages)])
        for a, b in zip(jax.tree.leaves((blob2.payload, blob2.scale)
                                        if quantization == "int8"
                                        else blob2),
                        jax.tree.leaves((again.payload, again.scale)
                                        if quantization == "int8"
                                        else again)):
            np.testing.assert_array_equal(a, b)

    def test_tier_store_keeps_page_blobs_whole(self, tmp_path):
        from deepspeed_tpu.inference.v2.ragged.kv_tiers import (
            TieredPageStore)
        cache = self._cache("int8")
        pages = cache.reserve(2)
        blob = cache.read_pages(pages)
        store = TieredPageStore(host_pages=1, disk_pages=4,
                                disk_dir=str(tmp_path))
        try:
            digests = [b"a" * 16, b"b" * 16]
            for i, digest in enumerate(digests):
                store.put(digest, blob[:, i:i + 1])   # host ring holds 1
            assert {store.contains(d) for d in digests} == {"host", "disk"}
            blobs, tiers = store.take_many(digests)
            assert sorted(tiers) == ["disk", "host"]
            for i, got in enumerate(blobs):
                assert got.shape == (2, 1, 2, 2, 4, 8)
                np.testing.assert_array_equal(got.payload,
                                              blob.payload[:, i:i + 1])
                np.testing.assert_array_equal(got.scale,
                                              blob.scale[:, i:i + 1])
        finally:
            store.close()


# ---------------------------------------------------------------------------
# engine contract
# ---------------------------------------------------------------------------

def _tiny_engine(num_pages=64, max_batch=256, max_seqs=8):
    # fp32: random-init bf16 logits produce exact argmax ties that make
    # greedy decode path-dependent across compiled shapes
    model_def = LlamaForCausalLM("debug", max_seq_len=256,
                                 dtype=jnp.float32)
    params = meta.unbox(model_def.init_params(jax.random.key(0)))
    cfg = model_def.cfg
    kv_cfg = KVCacheConfig(num_layers=cfg.num_layers, kv_heads=cfg.kv_heads,
                           head_dim=cfg.dims_per_head, page_size=16,
                           num_pages=num_pages, dtype=jnp.float32)
    model = RaggedInferenceModel(cfg, params, kv_config=kv_cfg)
    econf = RaggedInferenceEngineConfig(
        state_manager=StateManagerConfig(
            max_tracked_sequences=max_seqs,
            max_ragged_sequence_count=max_seqs,
            max_ragged_batch_size=max_batch))
    return InferenceEngineV2(model, econf), model_def, params


class TestEngineV2:
    def test_put_and_kv_accounting(self):
        eng, _, _ = _tiny_engine()
        rng = np.random.default_rng(0)
        p1 = rng.integers(0, 100, 20)
        p2 = rng.integers(0, 100, 5)
        logits = eng.put([1, 2], [p1, p2])
        assert logits.shape == (2, eng.model.cfg.vocab_size)
        assert eng.seen_tokens(1) == 20 and eng.seen_tokens(2) == 5
        # 20 tokens @ page 16 -> 2 pages; 5 tokens -> 1 page
        assert eng.free_blocks == 64 - 3
        eng.put([1], [np.array([7])])
        assert eng.seen_tokens(1) == 21
        eng.flush(1)
        assert eng.free_blocks == 64 - 1
        eng.flush(2)
        assert eng.free_blocks == 64

    def test_scheduling_limits(self):
        eng, _, _ = _tiny_engine(num_pages=4, max_batch=64, max_seqs=2)
        # KV limit: 4 pages * 16 = 64 tokens capacity
        assert eng.can_schedule([1], [65]) == SchedulingResult.KVCacheLimitExceeded
        assert eng.can_schedule([1], [64]) == SchedulingResult.Success
        assert eng.can_schedule([1, 2, 3], [4, 4, 4]) == \
            SchedulingResult.BatchSequenceLimitExceeded
        with pytest.raises(SchedulingError):
            eng.put([1], [np.zeros(65, np.int32)])

    def test_query(self):
        eng, _, _ = _tiny_engine(num_pages=4)
        tokens, blocks = eng.query(42, 20, 4)
        assert tokens == 20 and blocks == 2
        tokens, blocks = eng.query(42, 100, 2)
        assert tokens == 32 and blocks == 2  # trimmed to block headroom


# ---------------------------------------------------------------------------
# end-to-end: ragged paged decode == full forward
# ---------------------------------------------------------------------------

class TestEndToEnd:
    def test_prefill_logits_match_full_forward(self):
        eng, model_def, params = _tiny_engine()
        rng = np.random.default_rng(1)
        prompt = rng.integers(0, 128, 33).astype(np.int32)
        logits = eng.put([0], [prompt])
        full = forward(model_def.cfg, params, prompt[None, :])
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   np.asarray(full[0, -1]),
                                   rtol=5e-2, atol=5e-2)

    def test_chunked_prefill_then_decode_matches_full(self):
        """Split prefill across two put()s, then decode two tokens; every
        decode logit must match a fresh full-sequence forward."""
        eng, model_def, params = _tiny_engine()
        rng = np.random.default_rng(2)
        prompt = rng.integers(0, 128, 24).astype(np.int32)
        eng.put([0], [prompt[:16]])
        logits = eng.put([0], [prompt[16:]])
        seq = list(prompt)
        for _ in range(2):
            full = forward(model_def.cfg, params,
                           np.asarray(seq, np.int32)[None, :])
            np.testing.assert_allclose(np.asarray(logits[0]),
                                       np.asarray(full[0, -1]),
                                       rtol=5e-2, atol=5e-2)
            nxt = int(np.argmax(np.asarray(logits[0])))
            seq.append(nxt)
            logits = eng.put([0], [np.array([nxt], np.int32)])

    def test_generate_matches_engine_greedy(self):
        """Scheduler-driven batched generation must equal per-sequence
        engine-driven greedy decode (same compiled path — bf16 argmax
        ties make a full-forward comparison path-dependent)."""
        eng, model_def, params = _tiny_engine()
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, 128, n).astype(np.int32).tolist()
                   for n in (7, 19, 12)]
        outs = generate(eng, prompts,
                        SamplingParams(max_new_tokens=4), token_budget=32)
        for prompt, out in zip(prompts, outs):
            ref_eng, _, _ = _tiny_engine()
            logits = ref_eng.put([0], [np.asarray(prompt, np.int32)])
            ref = []
            for _ in range(4):
                tok = int(np.argmax(np.asarray(logits[0])))
                ref.append(tok)
                logits = ref_eng.put([0], [np.array([tok], np.int32)])
            assert out == ref


class TestTensorParallelInference:
    def test_tp_sharded_matches_single_device(self):
        """AutoTP analogue: boxed params + mesh(tensor=2) shard heads/ffn
        over 'tensor' and produce the same logits as replicated."""
        from deepspeed_tpu.parallel.topology import (MeshTopology,
                                                     TopologyConfig)
        model_def = LlamaForCausalLM("debug", max_seq_len=256,
                                     dtype=jnp.float32)
        boxed = model_def.init_params(jax.random.key(0))
        cfg = model_def.cfg
        kv_cfg = KVCacheConfig(num_layers=cfg.num_layers,
                               kv_heads=cfg.kv_heads,
                               head_dim=cfg.dims_per_head, page_size=16,
                               num_pages=32, dtype=jnp.float32)
        topo = MeshTopology(TopologyConfig(tensor=2, data=4),
                            devices=jax.devices()[:8])
        model_tp = RaggedInferenceModel(cfg, boxed, kv_config=kv_cfg,
                                        mesh=topo.mesh)
        # wq [embed, heads, dim] must actually be sharded over 'tensor'
        wq_shard = model_tp.params["layers"]["attn"]["wq"].sharding
        assert "tensor" in str(wq_shard.spec)
        eng_tp = InferenceEngineV2(model_tp)
        model_1 = RaggedInferenceModel(cfg, boxed, kv_config=kv_cfg)
        eng_1 = InferenceEngineV2(model_1)
        prompt = np.arange(20, dtype=np.int32) % 128
        with topo.mesh:
            l_tp = np.asarray(eng_tp.put([0], [prompt]))
        l_1 = np.asarray(eng_1.put([0], [prompt]))
        np.testing.assert_allclose(l_tp, l_1, rtol=1e-4, atol=1e-4)


class TestScheduler:
    def test_deadlock_raises_instead_of_spinning(self):
        from deepspeed_tpu.inference.v2 import FastGenScheduler
        eng, _, _ = _tiny_engine(num_pages=2)  # 32-token KV capacity
        sched = FastGenScheduler(eng, token_budget=16)
        sched.submit(0, list(range(100)))      # can never fit
        with pytest.raises(RuntimeError, match="deadlock"):
            sched.run_to_completion()

    def test_mixed_sampling_params_respected(self):
        """Greedy and stochastic requests in the same batch must each be
        sampled with their own params."""
        from deepspeed_tpu.inference.v2 import FastGenScheduler
        eng, model_def, params = _tiny_engine()
        sched = FastGenScheduler(eng, token_budget=64)
        rng = np.random.default_rng(5)
        p_greedy = rng.integers(0, 128, 9).tolist()
        p_stoch = rng.integers(0, 128, 9).tolist()
        sched.submit(0, p_greedy, SamplingParams(max_new_tokens=3))
        sched.submit(1, p_stoch,
                     SamplingParams(max_new_tokens=3, temperature=1.0))
        results = sched.run_to_completion()
        # greedy request must match engine-driven greedy decode exactly
        ref_eng, _, _ = _tiny_engine()
        logits = ref_eng.put([0], [np.asarray(p_greedy, np.int32)])
        ref = []
        for _ in range(3):
            tok = int(np.argmax(np.asarray(logits[0])))
            ref.append(tok)
            logits = ref_eng.put([0], [np.array([tok], np.int32)])
        assert results[0] == ref
        assert len(results[1]) == 3


class TestSampling:
    def test_greedy(self):
        logits = jnp.asarray([[0.0, 3.0, 1.0], [2.0, 0.0, -1.0]])
        toks = sample(logits, jax.random.key(0))
        assert toks.tolist() == [1, 0]

    def test_top_k_restricts_support(self):
        logits = jnp.asarray([[0.0, 5.0, 4.9, -10.0]])
        for seed in range(20):
            tok = int(sample(logits, jax.random.key(seed),
                             temperature=1.0, top_k=2)[0])
            assert tok in (1, 2)

    def test_top_p_restricts_support(self):
        logits = jnp.asarray([[10.0, 9.9, -10.0, -10.0]])
        for seed in range(20):
            tok = int(sample(logits, jax.random.key(seed),
                             temperature=1.0, top_p=0.9)[0])
            assert tok in (0, 1)


# ---------------------------------------------------------------------------
# module registry / heuristics seam
# ---------------------------------------------------------------------------

class TestModuleRegistry:
    def test_heuristic_picks_supported_impl(self):
        from deepspeed_tpu.inference.v2 import modules as M
        impl = M.instantiate("ragged_attention", None)
        assert callable(impl)
        # off-TPU the pallas impl's supports() gate rejects; dense wins
        from deepspeed_tpu.accelerator import on_tpu
        if not on_tpu():
            assert "dense_gather" in M.implementations("ragged_attention")

    def test_named_selection_and_errors(self):
        from deepspeed_tpu.inference.v2 import modules as M
        assert callable(M.instantiate("ragged_attention", None,
                                      name="dense_gather"))
        with pytest.raises(KeyError):
            M.instantiate("ragged_attention", None, name="nope")
        with pytest.raises(KeyError):
            M.instantiate("not_an_op_class")

    def test_register_new_impl_wins_by_priority(self):
        from deepspeed_tpu.inference.v2 import modules as M
        try:
            @M.register("ragged_attention", "test_custom", priority=99)
            def _custom(cfg):
                return lambda *a: "custom"
            impl = M.instantiate("ragged_attention", None)
            assert impl() == "custom"
        finally:  # deregister to not leak into other tests
            M._REGISTRY["ragged_attention"] = [
                i for i in M._REGISTRY["ragged_attention"]
                if i.name != "test_custom"]

    def test_duplicate_name_rejected(self):
        from deepspeed_tpu.inference.v2 import modules as M
        with pytest.raises(ValueError):
            M.register("ragged_attention", "dense_gather")(lambda c: None)

    def test_model_resolves_through_registry(self):
        from deepspeed_tpu.inference.v2.model import RaggedInferenceModel
        from deepspeed_tpu.models.llama import llama_config
        from flax.core import meta as fmeta
        from deepspeed_tpu.models.transformer import init_params
        cfg = llama_config("debug")
        params = fmeta.unbox(init_params(cfg, jax.random.key(0)))
        m = RaggedInferenceModel(cfg, params, attention_impl="dense_gather")
        assert callable(m._attention["full"])


# ---------------------------------------------------------------------------
# weight-only quantized inference
# ---------------------------------------------------------------------------

class TestQuantizedInference:
    def _engine(self, quant=None):
        from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                                RaggedInferenceEngineConfig,
                                                RaggedInferenceModel)
        from deepspeed_tpu.models.llama import LlamaForCausalLM
        model = LlamaForCausalLM("debug", dtype=jnp.float32)
        params = meta.unbox(model.init_params(jax.random.key(0)))
        cfg = RaggedInferenceEngineConfig.from_dict(
            {"quantization": quant} if quant else {})
        cfg.kv_cache.num_pages = 64
        return InferenceEngineV2(RaggedInferenceModel(model.cfg, params), cfg)

    def test_channelwise_roundtrip(self):
        from deepspeed_tpu.ops.fp_quantizer import (dequantize_channelwise,
                                                    quantize_channelwise)
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.normal(size=(64, 3, 32)), jnp.float32)
        for fmt, rel in [("fp8_e4m3", 2 ** -3), ("int8", 2 ** -7),
                         ("fp6_e3m2", 2 ** -2), ("fp4_e2m1", 2 ** -1)]:
            packed = quantize_channelwise(w, fmt)
            assert packed["q"].shape == w.shape
            assert packed["scale"].shape == (1, 1, 32)
            back = np.asarray(dequantize_channelwise(packed, jnp.float32))
            err = np.abs(back - np.asarray(w))
            bound = np.abs(np.asarray(w)).max(axis=(0, 1), keepdims=True) * rel
            assert (err <= bound + 1e-6).mean() > 0.99, fmt

    @pytest.mark.parametrize("fmt", ["fp8_e4m3", "int8"])
    def test_quantized_generate_close_to_full_precision(self, fmt):
        from deepspeed_tpu.inference.v2 import SamplingParams, generate
        prompts = [[1, 5, 9, 2, 17], [3, 4]]
        sp = SamplingParams(max_new_tokens=4, temperature=0.0)
        full = generate(self._engine(), prompts, sp)
        quant = generate(self._engine({"enabled": True, "fmt": fmt}),
                         prompts, sp)
        # greedy decode from the same weights: 8-bit channelwise noise
        # rarely flips an argmax on a random-init debug model; require
        # most tokens identical rather than exact equality
        flat_f = [t for seq in full for t in seq]
        flat_q = [t for seq in quant for t in seq]
        same = sum(a == b for a, b in zip(flat_f, flat_q))
        assert same >= len(flat_f) // 2, (full, quant)

    def test_quantized_params_are_small(self):
        eng_q = self._engine({"enabled": True, "fmt": "fp8_e4m3"})
        layers = eng_q._model.params["layers"]
        wq = layers["attn"]["wq"]
        assert isinstance(wq, dict) and wq["q"].dtype == jnp.float8_e4m3fn
        # norms/embeddings untouched
        assert not isinstance(layers["norm1"]["scale"], dict)
        assert not isinstance(eng_q._model.params["embed"]["tokens"], dict)

    def test_quantized_moe_generates(self):
        """MoE expert weights route through _wval too (regression:
        moe_forward crashed on {'q','scale'} dict leaves)."""
        from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                                RaggedInferenceEngineConfig,
                                                RaggedInferenceModel,
                                                SamplingParams, generate)
        from deepspeed_tpu.models.mixtral import MixtralForCausalLM
        model = MixtralForCausalLM("debug", num_experts=2, top_k=1,
                                   dtype=jnp.float32)
        import dataclasses
        cfg = dataclasses.replace(model.cfg, moe_num_experts=2, moe_top_k=1)
        params = meta.unbox(model.init_params(jax.random.key(0)))
        ecfg = RaggedInferenceEngineConfig.from_dict(
            {"quantization": {"enabled": True, "fmt": "fp8_e4m3"}})
        ecfg.kv_cache.num_pages = 64
        eng = InferenceEngineV2(RaggedInferenceModel(cfg, params), ecfg)
        outs = generate(eng, [[1, 5, 9]], SamplingParams(max_new_tokens=3))
        assert len(outs[0]) == 3

    def test_requantize_format_change_rejected(self):
        eng = self._engine({"enabled": True, "fmt": "fp8_e4m3"})
        with pytest.raises(ValueError):
            eng._model.quantize_weights("int8")
        eng._model.quantize_weights("fp8_e4m3")  # same fmt: no-op

    def test_unknown_format_rejected_without_poisoning(self):
        """Regression: a typo'd fmt must raise ValueError and leave the
        model un-quantized so the corrected call succeeds."""
        eng = self._engine()
        with pytest.raises(ValueError, match="fp8"):
            eng._model.quantize_weights("fp8")  # typo for fp8_e4m3
        eng._model.quantize_weights("fp8_e4m3")  # recovers cleanly
        assert isinstance(eng._model.params["layers"]["attn"]["wq"], dict)

    def test_moe_experts_get_per_expert_scales(self):
        """Regression: stacked-expert mlp weights [L, experts, in, out]
        must not share one absmax across experts."""
        from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                                RaggedInferenceEngineConfig,
                                                RaggedInferenceModel)
        from deepspeed_tpu.models.mixtral import MixtralForCausalLM
        import dataclasses
        model = MixtralForCausalLM("debug", num_experts=2, top_k=1,
                                   dtype=jnp.float32)
        cfg = dataclasses.replace(model.cfg, moe_num_experts=2, moe_top_k=1)
        params = meta.unbox(model.init_params(jax.random.key(0)))
        ecfg = RaggedInferenceEngineConfig.from_dict(
            {"quantization": {"enabled": True, "fmt": "fp8_e4m3"}})
        ecfg.kv_cache.num_pages = 64
        eng = InferenceEngineV2(RaggedInferenceModel(cfg, params), ecfg)
        wi = eng._model.params["layers"]["mlp"]["wi"]  # [L, E, in, out]
        L, E = wi["q"].shape[:2]
        assert wi["scale"].shape[:2] == (L, E), wi["scale"].shape


class TestSlidingWindowServing:
    def test_ragged_model_matches_core_forward(self):
        """End-to-end Mistral-semantics serving check: prefill+decode
        through RaggedInferenceModel with sliding_window set must match
        the training core's windowed einsum forward token for token."""
        from deepspeed_tpu.models.transformer import forward
        model_def = LlamaForCausalLM("debug", max_seq_len=256,
                                     sliding_window=8, dtype=jnp.float32)
        params = meta.unbox(model_def.init_params(jax.random.key(0)))
        cfg = model_def.cfg
        kv_cfg = KVCacheConfig(num_layers=cfg.num_layers,
                               kv_heads=cfg.kv_heads,
                               head_dim=cfg.dims_per_head, page_size=16,
                               num_pages=64, dtype=jnp.float32)
        model = RaggedInferenceModel(cfg, params, kv_config=kv_cfg)
        eng = InferenceEngineV2(model, RaggedInferenceEngineConfig(
            state_manager=StateManagerConfig(
                max_tracked_sequences=4, max_ragged_sequence_count=4,
                max_ragged_batch_size=256)))
        rng = np.random.default_rng(0)
        prompt = rng.integers(0, cfg.vocab_size, 24)  # 3x the window

        # prefill + 4 greedy decode steps through the paged engine
        toks = list(prompt)
        logits = eng.put([1], [np.asarray(prompt)])
        for _ in range(4):
            nxt = int(np.argmax(np.asarray(logits)[0]))
            toks.append(nxt)
            logits = eng.put([1], [np.array([nxt])])

        # dense core forward over the full final sequence (einsum path
        # applies the window via the mask)
        ids = jnp.asarray(np.asarray(toks)[None, :], jnp.int32)
        ref_logits = np.asarray(forward(cfg, params, ids))[0]
        ref_toks = list(prompt)
        for i in range(len(prompt) - 1, len(toks) - 1):
            ref_toks.append(int(np.argmax(ref_logits[i])))
        assert ref_toks == toks, (ref_toks[-6:], toks[-6:])


    def test_window_eviction_bounds_live_kv(self):
        """Decode far past the window: pages wholly below the window are
        returned to the pool (live KV = O(window)) and the logits still
        match the training core's windowed forward exactly."""
        from deepspeed_tpu.models.transformer import forward
        window, page = 8, 4
        model_def = LlamaForCausalLM("debug", max_seq_len=256,
                                     sliding_window=window,
                                     dtype=jnp.float32)
        params = meta.unbox(model_def.init_params(jax.random.key(0)))
        cfg = model_def.cfg
        kv_cfg = KVCacheConfig(num_layers=cfg.num_layers,
                               kv_heads=cfg.kv_heads,
                               head_dim=cfg.dims_per_head, page_size=page,
                               num_pages=64, dtype=jnp.float32)
        model = RaggedInferenceModel(cfg, params, kv_config=kv_cfg)
        eng = InferenceEngineV2(model, RaggedInferenceEngineConfig(
            state_manager=StateManagerConfig(
                max_tracked_sequences=2, max_ragged_sequence_count=2,
                max_ragged_batch_size=256)))
        rng = np.random.default_rng(0)
        prompt = rng.integers(0, cfg.vocab_size, 6)
        toks = list(prompt)
        logits = eng.put([1], [np.asarray(prompt)])
        for _ in range(30):  # run to ~36 tokens: 4.5x the window
            nxt = int(np.argmax(np.asarray(logits)[0]))
            toks.append(nxt)
            logits = eng.put([1], [np.array([nxt])])

        sd = eng.state_manager.get_sequence(1)
        live = [p for p in sd.pages if p != 0]
        # live pages bounded by window coverage (+1 partial +1 tail)
        assert len(live) <= window // page + 2, (len(live), sd.pages)
        assert len(sd.pages) > len(live), "nothing was evicted"
        # allocator got the dead pages back
        used = 64 - eng.free_blocks
        assert used == len(live), (used, len(live))

        # semantics unchanged vs the dense windowed core
        ids = jnp.asarray(np.asarray(toks)[None, :], jnp.int32)
        ref_logits = np.asarray(forward(cfg, params, ids))[0]
        ref_next = int(np.argmax(ref_logits[-1]))
        got_next = int(np.argmax(np.asarray(logits)[0]))
        assert ref_next == got_next


class TestPrecompileLattice:
    def test_precompile_covers_serving_and_strict_catches_misses(self):
        # a small lattice (two slots, prompts to 16 tokens, one page
        # bucket: a fifth of the step programs max_prompt=32 over four
        # slots and 256 new tokens would form)
        eng, _, _ = _tiny_engine(num_pages=64, max_batch=64, max_seqs=2)
        keys = eng.precompile(max_prompt=16, max_new_tokens=16, strict=True)
        assert keys, "empty precompile lattice"
        # every serving shape below the bounds must now dispatch without
        # a fresh compile: run prefill + decode inside strict mode
        rng = np.random.default_rng(0)
        p1 = rng.integers(0, 100, 12)
        p2 = rng.integers(0, 100, 5)
        logits = eng.put([1, 2], [p1, p2])
        assert logits.shape[0] == 2
        eng.put([1], [np.array([7])])  # decode bucket
        # a shape OUTSIDE the lattice raises instead of compiling
        eng.flush(2)                    # (two slots: make room)
        big = rng.integers(0, 100, 32)  # prompt > max_prompt bucket
        with pytest.raises(RuntimeError, match="not precompiled"):
            eng.put([3], [big])
        eng.model.strict_shapes = False
        eng.put([3], [big])  # and compiles fine when strictness is off


class TestFreshPrefillFlash:
    def test_fresh_bucket_uses_flash_and_matches_paged(self):
        """Pure-prefill buckets route through the flash implementation
        (fresh=True key) and must produce the same logits as the paged
        gather path on identical params/prompt."""
        rng = np.random.default_rng(0)
        prompt = rng.integers(0, 100, 24)

        def build():
            eng, model_def, params = _tiny_engine()
            return eng

        eng = build()
        logits = eng.put([1], [np.asarray(prompt)])
        keys = list(eng.model._step_cache)
        assert any(len(k) > 3 and k[3] for k in keys), \
            f"no fresh bucket compiled: {keys}"

        eng2 = build()
        eng2.model._fresh_attention = {"full": None}  # force paged path
        logits2 = eng2.put([1], [np.asarray(prompt)])
        np.testing.assert_allclose(np.asarray(logits), np.asarray(logits2),
                                   rtol=2e-5, atol=2e-5)

        # continued prefill (history present) must NOT take the fresh path
        eng.put([1], [rng.integers(0, 100, 8)])
        cont = [k for k in eng.model._step_cache
                if len(k) > 3 and k[1] == 8]
        assert cont and not any(k[3] for k in cont)


class TestKVOffloadRestore:
    def test_preempt_and_resume_matches_uninterrupted(self):
        """Offload a mid-decode sequence's KV to host (pages return to
        the pool), restore it, continue decoding — identical tokens to
        an uninterrupted run (reference kv_cache offload/restore hooks)."""
        rng = np.random.default_rng(0)
        prompt = rng.integers(0, 100, 20)

        def decode(eng, logits, n):
            toks = []
            for _ in range(n):
                nxt = int(np.argmax(np.asarray(logits)[0]))
                toks.append(nxt)
                logits = eng.put([1], [np.array([nxt])])
            return toks, logits

        ref_eng, _, _ = _tiny_engine()
        ref_logits = ref_eng.put([1], [np.asarray(prompt)])
        ref_toks, _ = decode(ref_eng, ref_logits, 8)

        eng, _, _ = _tiny_engine()
        logits = eng.put([1], [np.asarray(prompt)])
        toks_a, logits = decode(eng, logits, 4)
        free_before = eng.free_blocks
        eng.offload_sequence(1)
        assert eng.free_blocks > free_before, "offload freed no pages"
        # another sequence can use the freed pages meanwhile
        eng.put([2], [rng.integers(0, 100, 12)])
        eng.flush(2)
        eng.restore_sequence(1)
        toks_b, _ = decode(eng, logits, 4)
        assert toks_a + toks_b == ref_toks

    def test_scheduler_preempts_and_resumes_under_kv_pressure(self):
        """A KV pool too small for all sequences at once: the SplitFuse
        scheduler preempts the largest sequence (KV to host), finishes
        the others, restores it, and every request still completes with
        full-length outputs."""
        from deepspeed_tpu.inference.v2 import (FastGenScheduler,
                                                SamplingParams)
        # pool: 12 pages x 16 = 192 token capacity
        eng, _, _ = _tiny_engine(num_pages=12, max_batch=256, max_seqs=4)
        rng = np.random.default_rng(0)
        sched = FastGenScheduler(eng)
        sp = SamplingParams(max_new_tokens=24, temperature=0.0)
        lens = [100, 60, 40]  # 200 + decode > pool: must preempt
        for uid, n in enumerate(lens):
            sched.submit(uid, rng.integers(0, 100, n).tolist(), sp)
        outs = sched.run_to_completion()
        assert sorted(outs) == [0, 1, 2]
        assert all(len(v) == 24 for v in outs.values()), \
            {k: len(v) for k, v in outs.items()}
        assert not sched._preempted
