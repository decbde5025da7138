"""Sharded fused serving (ISSUE 18).

Tensor-parallel ONE-program serving over the suite's simulated 8-device
CPU mesh (conftest forces --xla_force_host_platform_device_count=8):
weights shard along the ``tp`` axis, KV pages partition along KV heads,
and sampling stays on-device behind the in-program logits all-gather.
The acceptance claims covered here:

- tp=2 output is tokenwise identical to tp=1 across greedy / keyed-
  sampled / spec / mixed shared-prefix workloads (the shard-invariant
  identity claim — page ids, prefix digests and RNG keys never depend
  on the mesh);
- the int8 block-scaled collective moves strictly fewer analytic wire
  bytes than fp at parity-grade output;
- snapshot/handoff bundles are shard-count independent: a tp=2 bundle
  restores on tp=1 (and vice versa) tokenwise identical, and a disagg
  pool hands off across differing shard counts;
- the d2h contract stays token-sized and a strict precompiled lattice
  serves tp traffic with 0 on-path compiles.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from deepspeed_tpu.inference.v2 import (
    FastGenScheduler, InferenceEngineV2, KVCacheConfig,
    RaggedInferenceEngineConfig, RaggedInferenceModel, SamplingParams,
    ServingOptimizationConfig, StateManagerConfig)
from deepspeed_tpu.models.llama import LlamaForCausalLM
from deepspeed_tpu.telemetry import metrics as tm
from deepspeed_tpu.utils.comms_logging import serving_counters


@pytest.fixture(autouse=True)
def _kv_debug(monkeypatch):
    """DS_KV_DEBUG=1: every scheduler here audits the page-accounting
    invariant after every step — on the PER-SHARD allocator view, since
    page ids/tables are replicated and the allocator is shard-invariant
    by construction."""
    monkeypatch.setenv("DS_KV_DEBUG", "1")


_PARTS = {}


def _model_parts():
    if not _PARTS:
        # fp32 (test_fused_serving convention): random-init bf16 logits
        # produce exact argmax ties that make greedy path-dependent
        model_def = LlamaForCausalLM("debug", max_seq_len=256,
                                     dtype=jnp.float32)
        _PARTS["cfg"] = model_def.cfg
        _PARTS["params"] = meta.unbox(
            model_def.init_params(jax.random.key(0)))
    return _PARTS["cfg"], _PARTS["params"]


def _engine(serving=None, num_pages=96, max_seqs=8, max_batch=256):
    cfg, params = _model_parts()
    kv_cfg = KVCacheConfig(num_layers=cfg.num_layers,
                           kv_heads=cfg.kv_heads,
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


def _sv(tp=1, quant="none", **kw):
    return ServingOptimizationConfig(tp_degree=tp,
                                     tp_collective_quantization=quant,
                                     **kw)


def _workload(seed=1):
    """Mixed shared-prefix workload: greedy + keyed-sampled + stop-token
    rows, three of four sharing a two-page prefix."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, 128, 32)
    prompts = [np.concatenate([shared, rng.integers(0, 128, 9)]),
               np.concatenate([shared, rng.integers(0, 128, 21)]),
               rng.integers(0, 128, 18),
               np.concatenate([shared, rng.integers(0, 128, 5)])]
    params = [SamplingParams(temperature=0.0, max_new_tokens=10),
              SamplingParams(temperature=0.9, top_k=30,
                             max_new_tokens=8),
              SamplingParams(temperature=0.0, max_new_tokens=12,
                             stop_token=5),
              SamplingParams(temperature=0.7, top_p=0.9,
                             max_new_tokens=6)]
    return prompts, params


def _run(engine, prompts, params, seed=7, serving=None):
    """seed=None: the scheduler's default base key (what DisaggPool's
    factories get — keyed draws must share the base key to compare)."""
    sched = FastGenScheduler(
        engine, serving=serving,
        **({} if seed is None else {"rng": jax.random.key(seed)}))
    for i, p in enumerate(prompts):
        sched.submit(i, p, params[i])
    return sched.run_to_completion()


# ---------------------------------------------------------------------------
# config plumbing: both trees, digest, engine guards
# ---------------------------------------------------------------------------

def test_runtime_config_carries_tp_to_v2():
    from deepspeed_tpu.runtime.config import load_config
    rc = load_config({"serving_optimization": {
        "tp_degree": 2, "tp_collective_quantization": "int8"}})
    d = rc.serving_optimization.to_v2_dict()
    assert d["tp_degree"] == 2
    assert d["tp_collective_quantization"] == "int8"
    v2 = RaggedInferenceEngineConfig.from_dict(
        {"serving_optimization": d})
    assert v2.serving.tp_degree == 2
    assert v2.serving.tp_collective_quantization == "int8"


def test_tpu_kernels_run_per_shard_under_the_mesh():
    """GSPMD refuses to partition a Mosaic custom call (even over
    replicated operands), so under tp the attention modules run in a
    shard_map over their head slice and the norm in a replicated manual
    region; without a mesh, and for ALiBi (closed-over per-head slopes),
    the module is called as is.  The cache write closes over nothing and
    indexes the sharded head dim: it runs per shard for ALiBi models too."""
    import dataclasses
    eng = _engine(serving=_sv(tp=2))
    model = eng._model
    fn = object()
    assert model._per_shard_heads(fn, model.cfg, 1) is not fn
    assert model._norm is not model._norm_impl
    alibi = dataclasses.replace(model.cfg, pos_emb="alibi")
    assert model._per_shard_heads(fn, alibi, 1) is fn
    assert model._per_shard_heads(fn, alibi, 2, pool_out=True) is not fn
    plain = _engine(serving=_sv(tp=1))._model
    assert plain._per_shard_heads(fn, plain.cfg, 1) is fn
    assert plain._norm is plain._norm_impl


def test_engine_guards():
    with pytest.raises(ValueError, match="tp_collective_quantization"):
        _engine(serving=_sv(quant="fp4"))
    with pytest.raises(ValueError, match="host_platform_device_count"):
        _engine(serving=_sv(tp=64))     # more than the 8 forced devices


def test_mesh_and_kv_pages_are_head_partitioned():
    eng = _engine(serving=_sv(tp=2))
    model = eng._model
    assert model.tp_degree == 2 and model._tp_axis == "tp"
    assert float(tm.FASTGEN_SHARD_COUNT.value) == 2.0
    data = eng.state_manager.kv_cache.data
    # [L, pages, 2, K, page, D]: each shard holds only its head slice
    shards = data.addressable_shards
    assert len(shards) == 2
    k = model.kv_config.kv_heads
    for s in shards:
        assert s.data.shape[3] == k // 2
        assert s.data.shape[:3] == data.shape[:3]


# ---------------------------------------------------------------------------
# tokenwise parity: tp=2 == tp=1 across the step kinds
# ---------------------------------------------------------------------------

class TestTokenwiseParity:
    def test_mixed_greedy_keyed_shared_prefix(self):
        """The acceptance workload: greedy + keyed-sampled rows over a
        shared prefix — prefill (mixed), decode, chain, prefix-cache
        hits and keyed RNG all shard-invariant."""
        prompts, params = _workload()
        ref = _run(_engine(serving=_sv(keyed_sampling=True)),
                   prompts, params)
        got = _run(_engine(serving=_sv(tp=2, keyed_sampling=True)),
                   prompts, params)
        assert got == ref

    def test_spec_parity(self):
        """Speculative verification buckets shard too: repetition-heavy
        prompts so the n-gram drafter actually drafts."""
        prompts = [[7, 8, 9] * 6, [3, 4] * 9, [11, 12, 13] * 5]
        params = [SamplingParams(max_new_tokens=8)] * 3
        sv1 = _sv(speculative=True, spec_max_draft=3)
        sv2 = _sv(tp=2, speculative=True, spec_max_draft=3)
        ref = _run(_engine(serving=sv1), prompts, params)
        got = _run(_engine(serving=sv2), prompts, params)
        assert got == ref
        assert tm.FASTGEN_SPEC_ACCEPTED.value > 0

    def test_model_drafted_spec_parity(self):
        """draft_spec/draft_fill shard: the draft trunk's per-iteration
        logits ride the same collective as the verify."""
        prompts, params = _workload(seed=3)
        sv1 = _sv(speculative=True, spec_max_draft=2,
                  spec_drafter="model", keyed_sampling=True)
        sv2 = _sv(tp=2, speculative=True, spec_max_draft=2,
                  spec_drafter="model", keyed_sampling=True)
        ref = _run(_engine(serving=sv1), prompts, params)
        got = _run(_engine(serving=sv2), prompts, params)
        assert got == ref


# ---------------------------------------------------------------------------
# int8 quantized collective: parity-grade output, strictly fewer bytes
# ---------------------------------------------------------------------------

class TestQuantizedCollective:
    def test_int8_parity_and_fewer_wire_bytes(self):
        prompts, params = _workload(seed=5)
        ref = _run(_engine(serving=_sv(keyed_sampling=True)),
                   prompts, params)
        b0 = tm.FASTGEN_SHARD_COLLECTIVE_BYTES.value
        f0 = tm.FASTGEN_SHARD_COLLECTIVE_FP_BYTES.value
        got = _run(_engine(serving=_sv(tp=2, quant="int8",
                                       keyed_sampling=True)),
                   prompts, params)
        # CPU XLA is deterministic, so the bounded-error int8 decode
        # reproduces the fp stream exactly on the debug model — the
        # "parity-grade output" acceptance bar
        assert got == ref
        wire = tm.FASTGEN_SHARD_COLLECTIVE_BYTES.value - b0
        fp = tm.FASTGEN_SHARD_COLLECTIVE_FP_BYTES.value - f0
        assert 0 < wire < fp

    def test_fp_collective_bytes_equal_fp_equivalent(self):
        prompts, params = _workload(seed=6)
        b0 = tm.FASTGEN_SHARD_COLLECTIVE_BYTES.value
        f0 = tm.FASTGEN_SHARD_COLLECTIVE_FP_BYTES.value
        _run(_engine(serving=_sv(tp=2)), prompts, params)
        wire = tm.FASTGEN_SHARD_COLLECTIVE_BYTES.value - b0
        fp = tm.FASTGEN_SHARD_COLLECTIVE_FP_BYTES.value - f0
        assert wire == fp > 0


# ---------------------------------------------------------------------------
# d2h stays token-sized + strict lattice serves tp with 0 on-path compiles
# ---------------------------------------------------------------------------

class TestContracts:
    def test_decode_d2h_token_sized_under_tp(self):
        """The transfer contract is unchanged by tp: logits assemble
        in-program (all-gather), sampling stays on device, and steady
        decode steps move only O(batch) int32 tokens d2h."""
        cfg, _ = _model_parts()
        vocab_bytes = int(cfg.vocab_size) * 4
        sched = FastGenScheduler(_engine(serving=_sv(tp=2)))
        rng = np.random.default_rng(2)
        for i in range(3):
            sched.submit(i, rng.integers(0, 128, 12),
                         SamplingParams(max_new_tokens=8))
        sched.step()
        for _ in range(3):
            d2h0 = serving_counters.d2h_bytes
            logits0 = serving_counters.logits_exposed_bytes
            progs0 = serving_counters.programs
            sched.step()
            assert serving_counters.programs - progs0 == 1
            assert serving_counters.logits_exposed_bytes == logits0, \
                "sharded decode must not expose logits to the host"
            d2h = serving_counters.d2h_bytes - d2h0
            assert 0 < d2h < vocab_bytes // 8, d2h
        while sched.has_work:
            sched.step()

    def test_strict_lattice_zero_on_path_compiles(self):
        # a small lattice (36 step programs to form under the mesh, not
        # 93: two slots, prompts of up to 4 tokens); the third request
        # waits for a slot
        eng = _engine(serving=_sv(tp=2, keyed_sampling=True),
                      max_seqs=2, max_batch=16)
        eng.precompile(max_prompt=4, max_new_tokens=4, sampling=True,
                       strict=True)
        before = tm.FASTGEN_COMPILE_ON_PATH.value
        rng = np.random.default_rng(8)
        prompts = [rng.integers(0, 128, n) for n in (4, 3, 4)]
        params = [SamplingParams(max_new_tokens=4),
                  SamplingParams(temperature=0.8, top_k=16,
                                 max_new_tokens=4),
                  SamplingParams(max_new_tokens=4)]
        _run(eng, prompts, params)    # strict: any on-path miss raises
        assert tm.FASTGEN_COMPILE_ON_PATH.value == before


# ---------------------------------------------------------------------------
# shard-count-independent bundles: snapshot + disagg handoff across tp
# ---------------------------------------------------------------------------

class TestCrossShardBundles:
    def _interrupted(self, tp_a, tp_b, k=3, seed=7):
        """Run k steps at tp_a, snapshot, restore at tp_b, finish."""
        prompts, params = _workload(seed=9)
        sva = _sv(tp=tp_a, keyed_sampling=True)
        svb = _sv(tp=tp_b, keyed_sampling=True)
        s1 = FastGenScheduler(_engine(serving=sva),
                              rng=jax.random.key(seed))
        for i, p in enumerate(prompts):
            s1.submit(i, p, params[i])
        got = {}
        cb = lambda u, t: got.setdefault(u, []).append(t)  # noqa: E731
        for _ in range(k):
            s1.step(on_token=cb)
        bundle = s1.snapshot(on_token=cb)
        s2 = FastGenScheduler(_engine(serving=svb),
                              rng=jax.random.key(seed))
        s2.restore(bundle)
        got.update(s2.run_to_completion())
        return got

    def test_snapshot_tp2_restores_on_tp1_and_reverse(self):
        prompts, params = _workload(seed=9)
        ref = _run(_engine(serving=_sv(keyed_sampling=True)),
                   prompts, params, seed=7)
        assert self._interrupted(2, 1) == ref
        assert self._interrupted(1, 2) == ref
        assert self._interrupted(2, 2) == ref

    def test_disagg_handoff_across_shard_counts(self):
        """A tp=2 prefill pool hands off to a tp=1 decode pool (the
        PageBlob layout is shard-count independent — ``read_pages``
        gathers the logical array; restore scatters under the target
        mesh) and the DisaggPool control plane is unchanged."""
        from deepspeed_tpu.serving import DisaggPool
        prompts, params = _workload(seed=4)
        pf = lambda: FastGenScheduler(_engine(             # noqa: E731
            serving=_sv(tp=2, role="prefill", keyed_sampling=True)))
        df = lambda: FastGenScheduler(_engine(             # noqa: E731
            serving=_sv(tp=1, role="decode", keyed_sampling=True)))
        pool = DisaggPool(pf, df, handoff_every=2)
        for i, p in enumerate(prompts):
            pool.submit(i, p, params[i])
        res = pool.run_to_completion()
        assert not pool.errors
        ref = _run(_engine(serving=_sv(keyed_sampling=True)),
                   prompts, params, seed=None)
        assert res == ref
