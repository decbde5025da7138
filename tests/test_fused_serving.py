"""Fused single-program serving step (ISSUE 2).

Covers the tentpole's three legs — fused mixed-batch forward, on-device
sampling, async double-buffered scheduling — plus the measured
"one program per step, token-sized transfer" acceptance claims via the
serving counters, the ragged Pallas kernel's Q>1 generalization, and the
greedy-RNG / group-merge satellites.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (
    FastGenScheduler, InferenceEngineV2, KVCacheConfig,
    RaggedInferenceEngineConfig, RaggedInferenceModel, SamplingParams,
    ServingOptimizationConfig, StateManagerConfig, generate, sample,
    sample_dynamic)
from deepspeed_tpu.inference.v2.ragged import batch as rb
from deepspeed_tpu.models.llama import LlamaForCausalLM
from deepspeed_tpu.ops import paged_attention as pa
from deepspeed_tpu.utils.comms_logging import serving_counters
from flax.core import meta


@pytest.fixture(autouse=True)
def _kv_debug(monkeypatch):
    """DS_KV_DEBUG=1 (ISSUE 3 CI satellite): every FastGenScheduler
    built here audits the KV page-accounting invariant after every step,
    so scheduler changes can't silently leak or double-use pages."""
    monkeypatch.setenv("DS_KV_DEBUG", "1")


SPLIT = ServingOptimizationConfig(fused_step=False,
                                  on_device_sampling=False,
                                  async_scheduling=False)
FUSED_SYNC = ServingOptimizationConfig(fused_step=True,
                                       on_device_sampling=True,
                                       async_scheduling=False)


def _tiny_engine(num_pages=64, max_batch=256, max_seqs=8, serving=None):
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
    if serving is not None:
        econf.serving = serving
    return InferenceEngineV2(model, econf)


# ---------------------------------------------------------------------------
# config: serving_optimization escape hatch
# ---------------------------------------------------------------------------

def test_serving_optimization_config_escape_hatch():
    cfg = RaggedInferenceEngineConfig.from_dict(
        {"serving_optimization": {"enabled": False, "fused_step": True}})
    assert not cfg.serving.fused_step            # master switch wins
    assert not cfg.serving.on_device_sampling
    assert not cfg.serving.async_scheduling
    cfg = RaggedInferenceEngineConfig.from_dict(
        {"serving_optimization": {"async_scheduling": False}})
    assert cfg.serving.fused_step and not cfg.serving.async_scheduling
    assert RaggedInferenceEngineConfig.from_dict({}).serving.fused_step


def test_runtime_config_block_flows_to_v2():
    from deepspeed_tpu.runtime.config import load_config
    rc = load_config({"serving_optimization": {"enabled": False}})
    v2 = RaggedInferenceEngineConfig.from_dict(
        {"serving_optimization": rc.serving_optimization.to_v2_dict()})
    assert not v2.serving.fused_step


# ---------------------------------------------------------------------------
# satellite: lattice floors are exported constants, not introspection
# ---------------------------------------------------------------------------

def test_bucket_floor_constants_are_the_default_lattice_floors():
    from deepspeed_tpu.inference.v2.lattice import POWER_LATTICE
    assert POWER_LATTICE.shape(1, 1, 1) == (rb.MIN_SLOTS, 1, rb.MIN_PAGES)
    assert not POWER_LATTICE.mined and POWER_LATTICE.digest == ""


# ---------------------------------------------------------------------------
# tentpole (a): fused mixed-batch forward == per-bucket split, bit level
# ---------------------------------------------------------------------------

class TestFusedSplitParity:
    def _pair(self):
        return (_tiny_engine(serving=FUSED_SYNC),
                _tiny_engine(serving=SPLIT))

    def _check(self, ef, es, uids, toks):
        lf = np.asarray(ef.put(uids, toks))
        ls = np.asarray(es.put(uids, toks))
        np.testing.assert_allclose(lf, ls, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(lf.argmax(-1), ls.argmax(-1))

    def test_prefill_only_step(self):
        ef, es = self._pair()
        rng = np.random.default_rng(0)
        toks = [rng.integers(0, 128, 20), rng.integers(0, 128, 5)]
        self._check(ef, es, [1, 2], toks)

    def test_decode_only_step(self):
        ef, es = self._pair()
        rng = np.random.default_rng(1)
        toks = [rng.integers(0, 128, 12), rng.integers(0, 128, 7)]
        ef.put([1, 2], toks), es.put([1, 2], toks)
        self._check(ef, es, [1, 2],
                    [np.array([3], np.int32), np.array([9], np.int32)])

    def test_mixed_prefill_decode_step(self):
        """The SplitFuse signature step: a decode row (Q=1) fused with a
        prefill chunk (Q=16) in one superbucket must reproduce the seed
        per-bucket split bit-for-bit at greedy level."""
        ef, es = self._pair()
        rng = np.random.default_rng(2)
        p1 = rng.integers(0, 128, 12)
        ef.put([1], [p1]), es.put([1], [p1])
        p2 = rng.integers(0, 128, 13)
        self._check(ef, es, [1, 2], [np.array([5], np.int32), p2])

    def test_fused_put_runs_one_program_for_mixed_batch(self):
        ef, _ = self._pair()
        rng = np.random.default_rng(3)
        ef.put([1], [rng.integers(0, 128, 12)])
        before = serving_counters.programs
        ef.put([1, 2], [np.array([5], np.int32),
                        rng.integers(0, 128, 9)])
        assert serving_counters.programs - before == 1

    def test_split_put_runs_one_program_per_bucket(self):
        _, es = self._pair()
        rng = np.random.default_rng(3)
        es.put([1], [rng.integers(0, 128, 12)])
        before = serving_counters.programs
        logits0 = serving_counters.logits_exposed_bytes
        es.put([1, 2], [np.array([5], np.int32),
                        rng.integers(0, 128, 9)])
        assert serving_counters.programs - before == 2
        # the put() contract materializes [n, V] logits to the host
        # boundary — the buffer the fused sampling path never creates
        assert serving_counters.logits_exposed_bytes - logits0 == \
            2 * es.model.cfg.vocab_size * 4


# ---------------------------------------------------------------------------
# tentpole (b): on-device sampling — dynamic per-row params
# ---------------------------------------------------------------------------

class TestSampleDynamic:
    def test_greedy_rows_are_argmax(self):
        logits = jnp.asarray([[0.0, 3.0, 1.0], [2.0, 0.0, -1.0]])
        toks = sample_dynamic(logits, jax.random.key(0),
                              jnp.zeros(2), jnp.zeros(2, jnp.int32),
                              jnp.ones(2))
        assert toks.tolist() == [1, 0]

    def test_per_row_top_k_restricts_support(self):
        logits = jnp.asarray([[0.0, 5.0, 4.9, -10.0],
                              [0.0, 5.0, 4.9, -10.0]])
        temps = jnp.asarray([1.0, 1.0])
        top_ks = jnp.asarray([2, 0], jnp.int32)   # row 1 unrestricted
        top_ps = jnp.ones(2)
        for seed in range(20):
            toks = sample_dynamic(logits, jax.random.key(seed),
                                  temps, top_ks, top_ps)
            assert int(toks[0]) in (1, 2)

    def test_per_row_top_p_restricts_support(self):
        logits = jnp.asarray([[10.0, 9.9, -10.0, -10.0]])
        for seed in range(20):
            toks = sample_dynamic(logits, jax.random.key(seed),
                                  jnp.asarray([1.0]),
                                  jnp.zeros(1, jnp.int32),
                                  jnp.asarray([0.9]))
            assert int(toks[0]) in (0, 1)

    def test_mixed_rows_one_call(self):
        """Greedy and stochastic rows coexist in one kernel call; the
        greedy row is deterministic across seeds."""
        logits = jnp.asarray([[0.0, 3.0, 1.0, -1.0],
                              [0.0, 5.0, 4.9, -10.0]])
        temps = jnp.asarray([0.0, 1.0])
        top_ks = jnp.asarray([0, 2], jnp.int32)
        top_ps = jnp.ones(2)
        for seed in range(10):
            toks = sample_dynamic(logits, jax.random.key(seed),
                                  temps, top_ks, top_ps)
            assert int(toks[0]) == 1
            assert int(toks[1]) in (1, 2)

    def test_matches_grouped_sample_distributionally(self):
        """slow-ish smoke: dynamic per-row top-k sampling draws from the
        same support with roughly the same frequencies as the grouped
        static kernel."""
        rng = np.random.default_rng(0)
        logits = jnp.asarray(rng.normal(size=(1, 64)), jnp.float32)
        counts_d = np.zeros(64)
        counts_s = np.zeros(64)
        for seed in range(200):
            key = jax.random.key(seed)
            counts_d[int(sample_dynamic(
                logits, key, jnp.asarray([0.8]),
                jnp.asarray([8], jnp.int32), jnp.asarray([0.95]))[0])] += 1
            counts_s[int(sample(logits, key, temperature=0.8, top_k=8,
                                top_p=0.95)[0])] += 1
        # identical support
        np.testing.assert_array_equal(counts_d > 0, counts_s > 0)
        assert (counts_d > 0).sum() <= 8


# ---------------------------------------------------------------------------
# acceptance: one program per scheduler step, token-sized d2h transfers
# ---------------------------------------------------------------------------

class TestServingCounters:
    def test_mixed_step_is_one_program_and_decode_d2h_is_token_sized(self):
        eng = _tiny_engine()           # fused + on-device + async default
        sched = FastGenScheduler(eng)
        rng = np.random.default_rng(0)
        sp = SamplingParams(max_new_tokens=8, temperature=0.0)
        sched.submit(0, rng.integers(0, 128, 20).tolist(), sp)
        sched.step()                   # prefill 0 (fresh bucket)
        sched.submit(1, rng.integers(0, 128, 9).tolist(), sp)

        # mixed step: decode row (uid 0) + prefill chunk (uid 1)
        progs0 = serving_counters.programs
        sched.step()
        assert serving_counters.programs - progs0 == 1
        assert sched.last_step_scheduled == 2

        # steady decode steps: one program each, d2h strictly token-sized
        vocab_bytes = eng.model.cfg.vocab_size * 4
        for _ in range(3):
            progs0 = serving_counters.programs
            d2h0 = serving_counters.d2h_bytes
            logits0 = serving_counters.logits_exposed_bytes
            out = sched.step()
            assert serving_counters.programs - progs0 == 1
            assert serving_counters.logits_exposed_bytes == logits0, \
                "fused decode materialized vocab-wide logits to the host"
            d2h = serving_counters.d2h_bytes - d2h0
            assert 0 < d2h < vocab_bytes // 8, d2h  # O(batch) int32 tokens
            assert out                              # lagged tokens flow

    def test_scheduler_split_override_reaches_per_bucket_put(self):
        """A serving= override on the SCHEDULER must reach the seed
        per-Q-bucket forward even when the ENGINE config is fused —
        regression: put() consulted only the engine config, so the
        escape hatch (and the bench comparison leg) still measured the
        fused superbucket program."""
        eng = _tiny_engine()               # engine config: fused default
        sched = FastGenScheduler(eng, serving=SPLIT)
        rng = np.random.default_rng(0)
        sp = SamplingParams(max_new_tokens=4, temperature=0.0)
        sched.submit(0, rng.integers(0, 128, 20).tolist(), sp)
        sched.step()                       # prefill 0
        sched.submit(1, rng.integers(0, 128, 9).tolist(), sp)
        progs0 = serving_counters.programs
        out = sched.step()                 # mixed: decode 0 + prefill 1
        assert serving_counters.programs - progs0 == 2  # per-bucket split
        assert out                         # split path: same-step tokens

    def test_async_uses_chained_steps(self):
        """Steady-state decode must dispatch through the device-side
        token gather (chain step-cache keys), not host token_ids."""
        eng = _tiny_engine()
        sched = FastGenScheduler(eng)
        rng = np.random.default_rng(0)
        sp = SamplingParams(max_new_tokens=6, temperature=0.0)
        sched.submit(0, rng.integers(0, 128, 8).tolist(), sp)
        sched.submit(1, rng.integers(0, 128, 5).tolist(), sp)
        sched.run_to_completion()
        assert any(len(k) > 4 and k[4] == "chain"
                   for k in eng.model._step_cache), \
            list(eng.model._step_cache)


# ---------------------------------------------------------------------------
# tentpole (c): async double buffering — token-lag correctness
# ---------------------------------------------------------------------------

class TestAsyncScheduling:
    def _outs(self, serving, prompts, params):
        eng = _tiny_engine(serving=serving)
        return generate(eng, prompts, params, token_budget=48)

    def test_async_matches_split_greedy(self):
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, 128, n).tolist() for n in (7, 19, 12)]
        sp = SamplingParams(max_new_tokens=5, temperature=0.0)
        assert self._outs(None, prompts, sp) == \
            self._outs(SPLIT, prompts, sp)

    def test_async_matches_sync_fused_greedy(self):
        rng = np.random.default_rng(6)
        prompts = [rng.integers(0, 128, n).tolist() for n in (11, 4)]
        sp = SamplingParams(max_new_tokens=6, temperature=0.0)
        assert self._outs(None, prompts, sp) == \
            self._outs(FUSED_SYNC, prompts, sp)

    def test_stop_token_misprediction_rolls_back(self):
        """A stop token is only detectable one step late under double
        buffering; the optimistically-dispatched extra token must be
        discarded and outputs must equal the split path's exactly."""
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, 128, n).tolist() for n in (9, 14)]
        ref = self._outs(SPLIT, prompts,
                         SamplingParams(max_new_tokens=8, temperature=0.0))
        stop = ref[0][3]   # uid 0 stops mid-stream at its 4th token
        sp = SamplingParams(max_new_tokens=8, temperature=0.0,
                            stop_token=stop)
        got = self._outs(None, prompts, sp)
        want = self._outs(SPLIT, prompts, sp)
        assert got == want
        assert got[0][-1] == stop and len(got[0]) <= 8

    def test_preemption_and_restore_under_async_loop(self):
        """KV pool too small for all sequences: the async double-buffered
        loop must still preempt (offload to host), restore, and finish
        every request with full-length output — matching the split path."""
        def run(serving):
            eng = _tiny_engine(num_pages=12, max_batch=256, max_seqs=4,
                               serving=serving)
            sched = FastGenScheduler(eng)
            rng = np.random.default_rng(0)
            sp = SamplingParams(max_new_tokens=24, temperature=0.0)
            for uid, n in enumerate([100, 60, 40]):
                sched.submit(uid, rng.integers(0, 100, n).tolist(), sp)
            outs = sched.run_to_completion()
            assert not sched._preempted and sched._inflight is None
            return outs

        outs = run(None)
        assert sorted(outs) == [0, 1, 2]
        assert all(len(v) == 24 for v in outs.values())
        assert outs == run(SPLIT)

    def test_stochastic_async_completes_with_full_lengths(self):
        rng = np.random.default_rng(8)
        prompts = [rng.integers(0, 128, n).tolist() for n in (6, 10)]
        sp = SamplingParams(max_new_tokens=5, temperature=1.0, top_k=16)
        outs = self._outs(None, prompts, sp)
        assert all(len(o) == 5 for o in outs)


# ---------------------------------------------------------------------------
# satellite: greedy steps never consume RNG; greedy groups merge
# ---------------------------------------------------------------------------

class TestGreedyRng:
    def test_group_key_merges_greedy_params(self):
        from deepspeed_tpu.inference.v2.scheduler import _group_key
        a = _group_key(SamplingParams(temperature=0.0, top_k=5))
        b = _group_key(SamplingParams(temperature=0.0, top_p=0.3))
        assert a == b == (0.0, 0, 1.0)
        assert _group_key(SamplingParams(temperature=0.7, top_k=5)) != a

    @pytest.mark.parametrize("serving", [None, "split"], ids=["fused", "split"])
    def test_greedy_run_leaves_rng_untouched(self, serving):
        eng = _tiny_engine(serving=SPLIT if serving == "split" else None)
        sched = FastGenScheduler(eng)
        key0 = np.asarray(jax.random.key_data(sched._rng)).copy()
        rng = np.random.default_rng(9)
        sp = SamplingParams(max_new_tokens=4, temperature=0.0)
        sched.submit(0, rng.integers(0, 128, 7).tolist(),
                     SamplingParams(max_new_tokens=4, top_k=3))  # temp 0
        sched.submit(1, rng.integers(0, 128, 9).tolist(), sp)
        sched.run_to_completion()
        np.testing.assert_array_equal(
            np.asarray(jax.random.key_data(sched._rng)), key0)

    def test_stochastic_run_consumes_rng(self):
        eng = _tiny_engine(serving=SPLIT)
        sched = FastGenScheduler(eng)
        key0 = np.asarray(jax.random.key_data(sched._rng)).copy()
        rng = np.random.default_rng(10)
        sched.submit(0, rng.integers(0, 128, 5).tolist(),
                     SamplingParams(max_new_tokens=2, temperature=1.0))
        sched.run_to_completion()
        assert not np.array_equal(
            np.asarray(jax.random.key_data(sched._rng)), key0)


# ---------------------------------------------------------------------------
# ragged Pallas kernel: Q > 1 rows (prefill chunks) in one launch
# ---------------------------------------------------------------------------

LAYER = 1


class TestRaggedKernelMixedQ:
    def _setup(self, S=3, Q=4, K=2, G=2, D=128, page=8, pages=32,
               hist=(5, 0, 11)):
        from deepspeed_tpu.inference.v2 import BlockedAllocator
        rng = np.random.default_rng(0)
        H = K * G
        kv = jnp.zeros((pages + 1, 2, K, page, D), jnp.float32)
        alloc = BlockedAllocator(pages)
        table = np.zeros((S, 8), np.int32)
        start = np.zeros(S, np.int32)
        q_lens = np.zeros(S, np.int32)
        for s in range(S):
            h = hist[s]
            n_pages = -(-(h + Q) // page)
            pgs = alloc.allocate(n_pages)
            table[s, :n_pages] = pgs
            start[s] = h
            q_lens[s] = Q
            for t in range(h):
                kv = kv.at[pgs[t // page], :, :, t % page].set(
                    jnp.asarray(rng.standard_normal((2, K, D)), jnp.float32))
        q = jnp.asarray(rng.standard_normal((S, Q, H, D)), jnp.float32)
        k_new = jnp.asarray(rng.standard_normal((S, Q, K, D)), jnp.float32)
        v_new = jnp.asarray(rng.standard_normal((S, Q, K, D)), jnp.float32)
        # the ops take the whole pool and a layer index: layer 1 of two
        kv = pa.write_kv(jnp.stack([kv[::-1], kv]), LAYER, k_new, v_new,
                         jnp.asarray(table), jnp.asarray(start),
                         jnp.asarray(q_lens))
        return (q, kv, jnp.asarray(table), jnp.asarray(start),
                jnp.asarray(q_lens))

    def test_q4_matches_jnp(self):
        q, kv, table, start, q_lens = self._setup()
        ref = pa.paged_attention(q, kv, LAYER, table, start, q_lens,
                                 use_kernel=False)
        out = pa.paged_decode_attention(q, kv, LAYER, table, start, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_q4_window_matches_jnp(self):
        q, kv, table, start, q_lens = self._setup(hist=(5, 0, 11))
        ref = pa.paged_attention(q, kv, LAYER, table, start, q_lens,
                                 use_kernel=False, window=6)
        out = pa.paged_decode_attention(q, kv, LAYER, table, start, window=6,
                                        interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_q4_alibi_matches_jnp(self):
        from deepspeed_tpu.models.transformer import alibi_slopes
        q, kv, table, start, q_lens = self._setup()
        slopes = alibi_slopes(q.shape[2])
        ref = pa.paged_attention(q, kv, LAYER, table, start, q_lens,
                                 use_kernel=False, alibi_slopes=slopes)
        out = pa.paged_decode_attention(q, kv, LAYER, table, start,
                                        alibi_slopes=slopes, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_q8_gqa_groups_match_jnp(self):
        q, kv, table, start, q_lens = self._setup(S=2, Q=8, K=2, G=4,
                                                  hist=(7, 16))
        ref = pa.paged_attention(q, kv, LAYER, table, start, q_lens,
                                 use_kernel=False)
        out = pa.paged_decode_attention(q, kv, LAYER, table, start, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_oversized_q_block_falls_back_to_jnp(self):
        """Auto-select must refuse query blocks past MAX_KERNEL_Q_ROWS
        (VMEM) even when a kernel backend is available."""
        q, kv, table, start, q_lens = self._setup(S=1, Q=4, K=2, G=2,
                                                  hist=(3,))
        import unittest.mock as mock
        with mock.patch.object(pa, "MAX_KERNEL_Q_ROWS", 4):
            with mock.patch.object(pa, "paged_decode_attention",
                                   side_effect=AssertionError) as m:
                pa.paged_attention(q, kv, LAYER, table, start, q_lens,
                                   interpret=True)
                assert not m.called


# ---------------------------------------------------------------------------
# superbucket AOT lattice: sampling variants + strict serving
# ---------------------------------------------------------------------------

class TestSamplingLattice:
    def test_precompiled_lattice_covers_fused_serving_under_strict(self):
        eng = _tiny_engine(num_pages=64, max_batch=64, max_seqs=2)
        keys = eng.precompile(max_prompt=8, max_new_tokens=8, strict=True,
                              sampling=True)
        kinds = {k[4] for k in keys if len(k) > 4}
        assert kinds == {"sample", "chain"}, kinds
        sched = FastGenScheduler(eng)   # fused + async default
        rng = np.random.default_rng(0)
        sp = SamplingParams(max_new_tokens=6, temperature=0.0)
        sched.submit(0, rng.integers(0, 128, 8).tolist(), sp)
        sched.step()
        # a mid-decode arrival forms a mixed step: under strict shapes
        # it must serve through the lattice-covered split programs (the
        # quadratic mixed-key space is not AOT-enumerated), not raise
        sched.submit(1, rng.integers(0, 128, 5).tolist(), sp)
        outs = sched.run_to_completion()   # strict: any miss raises
        assert all(len(v) == 6 for v in outs.values())

    def test_strict_prefill_superbucket_outside_lattice_serves_split(self):
        """Slot/Q bucket rounding can push bucket(S)*bucket(Q) past
        max_ragged_batch_size even when the admitted token count fits —
        keys the AOT lattice deliberately skips.  Under strict shapes
        such a prefill-only step must serve through the per-bucket split
        programs, not strict-miss (regression: both the fused sample key
        and put(fused=True)'s logits superbucket crashed here)."""
        eng = _tiny_engine(num_pages=64, max_batch=64, max_seqs=4)
        eng.precompile(max_prompt=32, max_new_tokens=8, strict=True,
                       sampling=True)
        sched = FastGenScheduler(eng)
        rng = np.random.default_rng(0)
        sp = SamplingParams(max_new_tokens=2, temperature=0.0)
        # 24+24+10 = 58 tokens fit the 64 budget, but the fused
        # superbucket is (4, 32, ...) with S*Q = 128 > 64
        for uid, n in enumerate([24, 24, 10]):
            sched.submit(uid, rng.integers(0, 128, n).tolist(), sp)
        outs = sched.run_to_completion()
        assert all(len(v) == 2 for v in outs.values()), outs

    def test_strict_lattice_without_sampling_falls_back_to_split(self):
        """Seed workflow: precompile(strict=True) with the default
        sampling=False, then serve through the scheduler.  The fused
        default must drop to the (fully precompiled) split path instead
        of raising a strict-miss on its first sample-step key."""
        eng = _tiny_engine(num_pages=64, max_batch=64, max_seqs=2)
        eng.precompile(max_prompt=8, max_new_tokens=8, strict=True)
        sched = FastGenScheduler(eng)      # fused + async default config
        assert not sched._fused and not sched._async
        rng = np.random.default_rng(0)
        sp = SamplingParams(max_new_tokens=4, temperature=0.0)
        sched.submit(0, rng.integers(0, 128, 8).tolist(), sp)
        outs = sched.run_to_completion()
        assert len(outs[0]) == 4
