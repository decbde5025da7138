"""Recompile-proof cold starts (ISSUE 14): persistent compile cache,
mined auto-lattice, warm-born replicas.

Covers the satellite test matrix:
- non-power-of-two lattice tokenwise parity vs the power-of-two default
  on mixed + speculative workloads under ``strict_shapes`` (the disagg
  kinds-partition of a mined lattice is covered structurally);
- compile-cache reuse: a second engine (and, heavy-marked, a second
  PROCESS) compiling the same keys pays zero true compiles;
- a config-digest change lands in a fresh cache namespace (miss, never
  a wrong executable);
- corrupt/missing cache dirs degrade to plain compiles with a warning;
- snapshot bundles carry the compiled-key manifest and ``restore()``
  precompiles from it; pool ``scale_up`` and ``DisaggPool`` spawns are
  born warm from manifests;
- the watchdog recompile-storm warning names the ``analyze_trace
  --emit-lattice`` remediation.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (FastGenScheduler,
                                        InferenceEngineV2, KVCacheConfig,
                                        RaggedInferenceEngineConfig,
                                        RaggedInferenceModel,
                                        SamplingParams,
                                        StateManagerConfig)
from deepspeed_tpu.inference.v2.config import ServingOptimizationConfig
from deepspeed_tpu.utils import compile_cache as cc
from deepspeed_tpu.inference.v2 import lattice as dsl
from deepspeed_tpu.telemetry import metrics as tm

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SAMPLE_TRACE = os.path.join(REPO_ROOT, "tools", "traces",
                            "sample_200.jsonl")

PAGE = 16


@pytest.fixture
def warn_log(monkeypatch):
    """Captured logger.warning calls (the repo logger doesn't
    propagate, so caplog can't see it — the test_watchdog pattern)."""
    calls = []
    from deepspeed_tpu.utils.logging import logger

    def capture(fmt, *args, **kw):
        try:
            calls.append(str(fmt) % args if args else str(fmt))
        except TypeError:
            calls.append(str(fmt))
    monkeypatch.setattr(logger, "warning", capture)
    return calls


@pytest.fixture()
def persistent_cache(monkeypatch):
    """conftest switches JAX's persistent cache off for the suite; the
    tests that are about it switch it on here.  The env var would
    outrank the config field under test, so it is cleared."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_enable_compilation_cache", True)
    yield
    cc.disable_compile_cache()
    jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(scope="module")
def debug_model_parts():
    from flax.core import meta as flax_meta
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    md = LlamaForCausalLM("debug", max_seq_len=128, dtype=jnp.float32)
    params = flax_meta.unbox(md.init_params(jax.random.key(0)))
    return md.cfg, params


def _build(cfg, params, lattice="", cache="", serving=None,
           max_seqs=8, num_pages=192):
    kv_cfg = KVCacheConfig(num_layers=cfg.num_layers,
                           kv_heads=cfg.kv_heads,
                           head_dim=cfg.dims_per_head, page_size=PAGE,
                           num_pages=num_pages, dtype=jnp.float32)
    model = RaggedInferenceModel(cfg, params, kv_config=kv_cfg)
    sv = serving or ServingOptimizationConfig()
    sv.lattice = lattice
    sv.compile_cache_dir = cache
    econf = RaggedInferenceEngineConfig(
        state_manager=StateManagerConfig(
            max_tracked_sequences=max_seqs,
            max_ragged_sequence_count=max_seqs,
            max_ragged_batch_size=128),
        serving=sv)
    return InferenceEngineV2(model, econf)


def _hand_artifact(path, vocab_size, s=(1, 3, 8), q=(1, 5, 12),
                   p=(8,), digest=None, spec_q=0):
    """A small NON-power lattice artifact over hand-picked tops."""
    keys = dsl.enumerate_lattice_keys(
        s, q, p, page_size=PAGE, max_ragged_batch_size=128,
        has_fresh=True, sampling=True, spec_q=spec_q)
    art = {"kind": "ds_lattice", "version": 1,
           "config_digest": (digest if digest is not None else
                             dsl.lattice_config_digest(PAGE, vocab_size)),
           "page_size": PAGE, "vocab_size": vocab_size,
           "max_ragged_batch_size": 128,
           "has_fresh": True,
           "s_buckets": list(s), "q_buckets": list(q),
           "p_buckets": list(p),
           "keys": [list(k) for k in keys],
           "source": "test", "requests": 0, "dispatches": 0}
    dsl.write_artifact(art, path)
    return art


def _run_workload(engine, prompts, params_list):
    sched = FastGenScheduler(engine)
    for i, (p, sp) in enumerate(zip(prompts, params_list)):
        assert sched.submit(i, p, sp) is None
    return sched.run_to_completion()


# ---------------------------------------------------------------------------
# lattice mining + artifact plumbing (no engines)
# ---------------------------------------------------------------------------
class TestLatticeMining:
    def test_fit_buckets_reexport(self):
        from tools.analyze_trace import fit_buckets
        assert fit_buckets is dsl.fit_buckets
        assert dsl.fit_buckets([5, 6, 17, 100]) == [6, 17, 100]

    def test_bucket_pick_non_power_and_overflow(self):
        lat = dsl.BucketLattice(s_tops=(1, 3, 8), q_tops=(1, 5, 12),
                                p_tops=(8, 11))
        assert lat.bucket_s(2) == 3
        assert lat.bucket_q(6) == 12
        assert lat.bucket_p(9) == 11
        # past the largest top: power-of-two fallback, never an error
        assert lat.bucket_s(9) == 16
        assert lat.bucket_q(13) == 16

    def test_mine_lattice_from_sample_trace_is_smaller_than_power(self):
        from tools import replay_trace
        trace = replay_trace.load_trace(SAMPLE_TRACE)
        art = dsl.mine_lattice(trace, source=SAMPLE_TRACE)
        assert art["kind"] == "ds_lattice"
        assert art["config_digest"] == dsl.lattice_config_digest(
            int(trace["meta"]["page_size"]),
            int(trace["meta"]["vocab_size"]))
        from deepspeed_tpu.inference.v2.engine import lattice_keys
        requests = trace["requests"]
        power = lattice_keys(
            max_prompt=max(int(r["prompt_len"]) for r in requests),
            max_new_tokens=max(int(r["gen_len"]) for r in requests),
            max_concurrency=32,
            page_size=int(trace["meta"]["page_size"]),
            max_ragged_batch_size=768, has_fresh=True, sampling=True)
        # strictly smaller precompiled set on the mined trace
        assert len(art["keys"]) < len(power)

    def test_emit_lattice_cli_round_trip(self, tmp_path):
        from tools import analyze_trace
        out = tmp_path / "lat.json"
        rc = analyze_trace.main(["--trace", SAMPLE_TRACE,
                                 "--emit-lattice", str(out),
                                 "--json", str(tmp_path / "rep.json")])
        assert rc == 0
        doc = dsl.load_artifact(str(out))
        assert doc["keys"] and doc["q_buckets"]
        rep = json.loads((tmp_path / "rep.json").read_text())
        assert rep["emitted_lattice"]["config_digest"] == \
            doc["config_digest"]

    def test_artifact_validation_errors(self, tmp_path):
        bad = tmp_path / "bad.json"
        bad.write_text("not json at all {")
        with pytest.raises(dsl.LatticeError):
            dsl.load_artifact(str(bad))
        wrong_kind = tmp_path / "wk.json"
        wrong_kind.write_text(json.dumps({"kind": "something"}))
        with pytest.raises(dsl.LatticeError):
            dsl.load_artifact(str(wrong_kind))
        with pytest.raises(dsl.LatticeError):
            dsl.resolve_lattice("auto:/no/such/file", page_size=PAGE,
                                vocab_size=256)
        with pytest.raises(dsl.LatticeError):
            dsl.resolve_lattice("bogus-spec", page_size=PAGE,
                                vocab_size=256)

    def test_digest_mismatch_refuses_not_silently_cold(self, tmp_path):
        path = str(tmp_path / "lat.json")
        _hand_artifact(path, vocab_size=256)
        # page-size change -> digest mismatch -> structured refusal
        with pytest.raises(dsl.LatticeError, match="digest"):
            dsl.resolve_lattice(f"auto:{path}", page_size=32,
                                vocab_size=256)
        # a LARGER engine batch budget than mine-time also refuses:
        # keys the larger budget can form were excluded at mine time
        with pytest.raises(dsl.LatticeError, match="batch"):
            dsl.resolve_lattice(f"auto:{path}", page_size=PAGE,
                                vocab_size=256,
                                max_ragged_batch_size=512)
        # matching geometry resolves
        lat = dsl.resolve_lattice(f"auto:{path}", page_size=PAGE,
                                  vocab_size=256,
                                  max_ragged_batch_size=128)
        assert lat is not None and lat.q_tops == (1, 5, 12)

    def test_resolve_from_raw_trace_mines_on_the_fly(self):
        from tools import replay_trace
        meta = replay_trace.load_trace(SAMPLE_TRACE)["meta"]
        lat = dsl.resolve_lattice(
            f"auto:{SAMPLE_TRACE}",
            page_size=int(meta["page_size"]),
            vocab_size=int(meta["vocab_size"]))
        assert lat is not None and len(lat.keys) > 0

    def test_mixed_keys_classify_as_prefill(self):
        from deepspeed_tpu.inference.v2.engine import lattice_kind_of
        mixed = (4, 1, 8, False, "mixed", 8, 12, 8, False, True)
        assert lattice_kind_of(mixed) == "prefill"


# ---------------------------------------------------------------------------
# non-power lattice tokenwise parity under strict_shapes
# ---------------------------------------------------------------------------
class TestAutoLatticeParity:
    @pytest.fixture(scope="class")
    def engines(self, tmp_path_factory, request):
        """The auto engine runs STRICT over its precompiled mined
        lattice (proving live traffic never leaves it); the power
        baseline compiles lazily — parity is about token values, and
        a strict full power lattice costs minutes of AOT for no extra
        coverage (test_fused_serving owns strict power-lattice
        coverage)."""
        cfg, params = request.getfixturevalue("debug_model_parts")
        tmp = tmp_path_factory.mktemp("lat")
        apath = str(tmp / "lat.json")
        _hand_artifact(apath, vocab_size=cfg.vocab_size, spec_q=3)
        auto = _build(cfg, params, lattice=f"auto:{apath}")
        auto.precompile(max_prompt=12, sampling=True, strict=True,
                        spec_max_draft=2)
        power = _build(cfg, params)
        return auto, power

    def test_auto_lattice_is_smaller(self, engines):
        auto, _ = engines
        from deepspeed_tpu.inference.v2.engine import lattice_keys
        power_keys = lattice_keys(
            max_prompt=12, max_new_tokens=8, max_concurrency=8,
            page_size=PAGE, max_ragged_batch_size=128, has_fresh=True,
            sampling=True, spec_max_draft=2)
        assert 0 < len(auto.model._step_cache) < len(power_keys)

    def test_mixed_workload_tokenwise_identical(self, engines):
        auto, power = engines
        prompts = [list(range(2, 2 + n)) for n in (5, 12, 3, 9, 7)]
        params = [SamplingParams(max_new_tokens=6)] * 5
        out_a = _run_workload(auto, prompts, params)
        out_p = _run_workload(power, prompts, params)
        assert all(out_a[i] == out_p[i] for i in range(5))

    def test_stochastic_workload_tokenwise_identical(self, engines):
        auto, power = engines
        prompts = [list(range(3, 3 + n)) for n in (4, 11)]
        params = [SamplingParams(temperature=0.9, top_k=8,
                                 max_new_tokens=5)] * 2
        out_a = _run_workload(auto, prompts, params)
        out_p = _run_workload(power, prompts, params)
        assert all(out_a[i] == out_p[i] for i in range(2))

    def test_spec_workload_tokenwise_identical(self, engines):
        auto, power = engines
        # repetition-heavy prompts so the n-gram drafter actually drafts
        prompts = [[7, 8, 9] * 4] * 3
        params = [SamplingParams(max_new_tokens=8)] * 3
        sv = ServingOptimizationConfig(speculative=True,
                                       spec_max_draft=2)
        outs = []
        for eng in engines:
            sched = FastGenScheduler(eng, serving=sv)
            for i, (p, sp) in enumerate(zip(prompts, params)):
                sched.submit(i, p, sp)
            outs.append(sched.run_to_completion())
        assert all(outs[0][i] == outs[1][i] for i in range(3))

    def test_strict_auto_lattice_served_zero_on_path_compiles(
            self, engines):
        auto, _ = engines
        c0 = tm.FASTGEN_COMPILE_ON_PATH.value
        prompts = [list(range(2, 2 + n)) for n in (5, 12)]
        _run_workload(auto, prompts,
                      [SamplingParams(max_new_tokens=4)] * 2)
        assert tm.FASTGEN_COMPILE_ON_PATH.value == c0

    def test_kinds_filter_shrinks_auto_lattice(self, engines):
        auto, _ = engines
        full = auto._auto_lattice_keys(sampling=True, spec_max_draft=0,
                                       kinds=None)
        dec = auto._auto_lattice_keys(sampling=True, spec_max_draft=0,
                                      kinds=("decode", "chain"))
        assert 0 < len(dec) < len(full)
        from deepspeed_tpu.inference.v2.engine import lattice_kind_of
        assert all(lattice_kind_of(k) in ("decode", "chain")
                   for k in dec)


# ---------------------------------------------------------------------------
# persistent compile cache
# ---------------------------------------------------------------------------
@pytest.mark.usefixtures("persistent_cache")
class TestCompileCache:

    def test_unwritable_cache_dir_degrades_with_warning(
            self, tmp_path, warn_log, debug_model_parts):
        cfg, params = debug_model_parts
        blocker = tmp_path / "a_file"
        blocker.write_text("not a directory")
        eng = _build(cfg, params, cache=str(blocker / "nested"))
        assert eng._compile_cache_dir is None
        assert any("compile cache disabled" in m for m in warn_log)
        # serving still works (plain compiles)
        out = _run_workload(eng, [[2, 3, 4]],
                            [SamplingParams(max_new_tokens=3)])
        assert len(out[0]) == 3

    def test_second_engine_loads_instead_of_compiling(
            self, tmp_path, debug_model_parts):
        cfg, params = debug_model_parts
        cache = str(tmp_path / "cc")
        eng1 = _build(cfg, params, cache=cache)
        m0 = tm.FASTGEN_COMPILE_CACHE_MISS.value
        eng1.precompile(max_prompt=2, max_concurrency=2, sampling=False)
        assert tm.FASTGEN_COMPILE_CACHE_MISS.value > m0  # true compiles
        # a FRESH model (empty step cache), same config digest
        eng2 = _build(cfg, params, cache=cache)
        h0 = tm.FASTGEN_COMPILE_CACHE_HIT.value
        m0 = tm.FASTGEN_COMPILE_CACHE_MISS.value
        eng2.precompile(max_prompt=2, max_concurrency=2, sampling=False)
        assert tm.FASTGEN_COMPILE_CACHE_MISS.value == m0  # 0 true
        assert tm.FASTGEN_COMPILE_CACHE_HIT.value > h0    # all loads

    def test_digest_change_is_a_miss_not_a_wrong_executable(
            self, tmp_path, debug_model_parts):
        cfg, params = debug_model_parts
        cache = str(tmp_path / "cc")
        eng1 = _build(cfg, params, cache=cache)
        eng1.precompile(max_prompt=2, max_concurrency=2, sampling=False)
        dir1 = eng1._compile_cache_dir
        # another pool size changes every step program: same directory
        # (placement never depends on the config), different jax keys
        eng2 = _build(cfg, params, cache=cache, num_pages=96)
        assert eng2._compile_cache_dir == dir1 == cache
        h0 = tm.FASTGEN_COMPILE_CACHE_HIT.value
        m0 = tm.FASTGEN_COMPILE_CACHE_MISS.value
        eng2.precompile(max_prompt=2, max_concurrency=2, sampling=False)
        assert tm.FASTGEN_COMPILE_CACHE_MISS.value > m0
        assert tm.FASTGEN_COMPILE_CACHE_HIT.value == h0
        # and the engine still serves correct output
        out = _run_workload(eng2, [[2, 3, 4, 5]],
                            [SamplingParams(max_new_tokens=3)])
        assert len(out[0]) == 3

    def test_corrupt_cache_entries_degrade_to_recompile(
            self, tmp_path, debug_model_parts):
        cfg, params = debug_model_parts
        cache = str(tmp_path / "cc")
        eng1 = _build(cfg, params, cache=cache)
        eng1.precompile(max_prompt=2, max_concurrency=2, sampling=False)
        active = eng1._compile_cache_dir
        entries = [os.path.join(active, f) for f in os.listdir(active)
                   if not f.startswith(".")]
        assert entries
        for e in entries:
            if os.path.isfile(e):
                with open(e, "wb") as f:
                    f.write(b"garbage" * 16)
        eng2 = _build(cfg, params, cache=cache)
        # corrupt entries must not raise — recompile and keep serving
        eng2.precompile(max_prompt=2, max_concurrency=2, sampling=False)
        out = _run_workload(eng2, [[2, 3, 4]],
                            [SamplingParams(max_new_tokens=2)])
        assert len(out[0]) == 2

    def test_two_process_cache_reuse(self, tmp_path, debug_model_parts):
        """Second PROCESS compiling the same keys: 0 true compiles."""
        cache = str(tmp_path / "cc")
        script = (
            "import json, sys\n"
            "import jax, jax.numpy as jnp\n"
            "from flax.core import meta as fm\n"
            "from deepspeed_tpu.models.llama import LlamaForCausalLM\n"
            "from deepspeed_tpu.inference.v2 import (InferenceEngineV2,"
            " KVCacheConfig, RaggedInferenceEngineConfig,"
            " RaggedInferenceModel, StateManagerConfig)\n"
            "from deepspeed_tpu.inference.v2.config import"
            " ServingOptimizationConfig\n"
            "from deepspeed_tpu.telemetry import metrics as tm\n"
            "md = LlamaForCausalLM('debug', max_seq_len=64,"
            " dtype=jnp.float32)\n"
            "params = fm.unbox(md.init_params(jax.random.key(0)))\n"
            "kv = KVCacheConfig(num_layers=md.cfg.num_layers,"
            " kv_heads=md.cfg.kv_heads, head_dim=md.cfg.dims_per_head,"
            " page_size=16, num_pages=64, dtype=jnp.float32)\n"
            "model = RaggedInferenceModel(md.cfg, params, kv_config=kv)\n"
            "econf = RaggedInferenceEngineConfig("
            "state_manager=StateManagerConfig(max_tracked_sequences=2,"
            " max_ragged_sequence_count=2, max_ragged_batch_size=32),"
            " serving=ServingOptimizationConfig("
            f"compile_cache_dir={cache!r}))\n"
            "eng = InferenceEngineV2(model, econf)\n"
            "eng.precompile(max_prompt=2, max_concurrency=2,"
            " sampling=False)\n"
            "print(json.dumps({'hits':"
            " tm.FASTGEN_COMPILE_CACHE_HIT.value, 'misses':"
            " tm.FASTGEN_COMPILE_CACHE_MISS.value}))\n")

        def run():
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            env.pop("JAX_COMPILATION_CACHE_DIR", None)
            p = subprocess.run([sys.executable, "-c", script],
                               capture_output=True, text=True,
                               timeout=600, env=env, cwd=REPO_ROOT)
            assert p.returncode == 0, p.stderr[-2000:]
            return json.loads(p.stdout.strip().splitlines()[-1])

        first = run()
        assert first["misses"] > 0
        second = run()
        assert second["misses"] == 0, second
        assert second["hits"] > 0


# ---------------------------------------------------------------------------
# warm-born replicas: snapshot manifests, pool scale_up, disagg spawn
# ---------------------------------------------------------------------------
@pytest.mark.usefixtures("persistent_cache")
class TestWarmBorn:
    def test_snapshot_manifest_and_restore_precompiles(
            self, debug_model_parts, tmp_path):
        cfg, params = debug_model_parts
        eng = _build(cfg, params)
        sched = FastGenScheduler(eng)
        for i in range(3):
            sched.submit(i, list(range(2, 12 + i)),
                         SamplingParams(max_new_tokens=6))
        for _ in range(3):
            sched.step()
        path = str(tmp_path / "b.snap")
        sched.snapshot(path)
        from deepspeed_tpu.inference.v2.snapshot import read_bundle
        meta, _ = read_bundle(path)
        manifest = [tuple(k) for k in meta["compiled"]["keys"]]
        assert manifest, "snapshot bundle must carry the compiled-key " \
                         "manifest"
        # dispatched-only: the manifest is what traffic formed, which
        # is a subset of everything compiled
        assert set(manifest) <= set(
            eng.compiled_keys(dispatched_only=False))

        eng2 = _build(cfg, params)
        sched2 = FastGenScheduler(eng2).restore(path)
        # warm birth: every manifest key is compiled BEFORE serving
        assert set(manifest) <= set(eng2.model._step_cache)
        # and the restored run still completes
        out = sched2.run_to_completion()
        assert all(len(v) == 6 for v in out.values())

    def test_restore_skips_manifest_on_lattice_digest_mismatch(
            self, debug_model_parts, tmp_path, warn_log):
        cfg, params = debug_model_parts
        apath = str(tmp_path / "lat.json")
        _hand_artifact(apath, vocab_size=cfg.vocab_size)
        eng = _build(cfg, params, lattice=f"auto:{apath}")
        sched = FastGenScheduler(eng)
        sched.submit(0, [2, 3, 4, 5], SamplingParams(max_new_tokens=4))
        sched.step()
        path = str(tmp_path / "b.snap")
        sched.snapshot(path)
        # restore onto a power-lattice engine: digest differs -> the
        # manifest precompile is skipped with a warning, restore works
        eng2 = _build(cfg, params)
        sched2 = FastGenScheduler(eng2).restore(path)
        assert any("lattice digest" in m for m in warn_log)
        out = sched2.run_to_completion()
        assert len(out[0]) == 4

    def test_pool_scale_up_is_born_warm(self, debug_model_parts,
                                        tmp_path):
        from deepspeed_tpu.serving import ReplicaPool
        cfg, params = debug_model_parts
        cache = str(tmp_path / "cc")

        def factory(label):
            # warm spawn only engages with an active compile cache —
            # without one the manifest would be true compiles paid
            # inside scale_up, so the pool deliberately stays lazy
            return FastGenScheduler(_build(cfg, params, num_pages=96,
                                           cache=cache))

        try:
            pool = ReplicaPool(factory, replicas=1,
                               policy="least_backlog")
            for i in range(3):
                pool.submit(i, list(range(2, 10 + i)),
                            SamplingParams(max_new_tokens=4))
            pool.run_to_completion()
            manifest = pool.compiled_manifest()
            assert manifest
            label = pool.scale_up()
            assert label is not None
            new_eng = pool._replicas[label].engine
            # the spawn precompiled the fleet's traffic keys (as cache
            # loads) before joining
            assert set(manifest) <= set(new_eng.model._step_cache)
        finally:
            cc.disable_compile_cache()

    def test_pool_scale_up_stays_lazy_without_cache(
            self, debug_model_parts):
        from deepspeed_tpu.serving import ReplicaPool
        cfg, params = debug_model_parts
        # "without cache" = JAX's persistent cache switched off (an
        # empty compile_cache_dir now means the in-checkout default)
        jax.config.update("jax_enable_compilation_cache", False)

        def factory(label):
            return FastGenScheduler(_build(cfg, params, num_pages=96))

        pool = ReplicaPool(factory, replicas=1, policy="least_backlog")
        for i in range(2):
            pool.submit(i, list(range(2, 9 + i)),
                        SamplingParams(max_new_tokens=3))
        pool.run_to_completion()
        assert pool.compiled_manifest()
        label = pool.scale_up()
        # no compile cache: the spawn joins immediately and compiles
        # lazily — nothing precompiled at birth
        assert not pool._replicas[label].engine.model._step_cache

    def test_disagg_manifest_round_trip(self, debug_model_parts,
                                        tmp_path):
        from deepspeed_tpu.serving import DisaggPool
        cfg, params = debug_model_parts
        cache = str(tmp_path / "cc")

        def mk(role):
            sv = ServingOptimizationConfig(role=role,
                                           keyed_sampling=True)
            # warm birth engages only with an active compile cache
            # (the ReplicaPool gate, shared)
            return lambda: FastGenScheduler(
                _build(cfg, params, serving=sv, num_pages=96,
                       cache=cache))

        try:
            pool = DisaggPool(mk("prefill"), mk("decode"))
            for i in range(2):
                pool.submit(i, list(range(2, 9 + i)),
                            SamplingParams(max_new_tokens=4))
            pool.run_to_completion()
            man = pool.compiled_manifest()
            assert man["prefill"] and man["decode"]
            pool2 = DisaggPool(mk("prefill"), mk("decode"),
                               manifest=man)
            assert set(tuple(k) for k in man["prefill"]) <= set(
                pool2.prefill._engine.model._step_cache)
            assert set(tuple(k) for k in man["decode"]) <= set(
                pool2.decode._engine.model._step_cache)
        finally:
            cc.disable_compile_cache()


# ---------------------------------------------------------------------------
# watchdog remediation message
# ---------------------------------------------------------------------------
class TestStormRemediation:
    def test_storm_warning_names_emit_lattice_remediation(
            self, warn_log):
        from deepspeed_tpu.telemetry.watchdog import get_watchdog
        wd = get_watchdog()
        # reset the warn-once latch regardless of earlier tests
        wd._in_compile_storm = False
        wd._compile_times.clear()
        wd._compile_keys.clear()
        for i in range(wd.storm_compiles):
            wd.note_step_cache(hit=False, key=(4, 1, 8, False, i),
                               compiled_on_path=True)
        msgs = [m for m in warn_log if "recompile storm" in m]
        assert msgs, "storm warning did not fire"
        assert "--emit-lattice" in msgs[0]
        assert "analyze_trace" in msgs[0]
        assert "persistent compile cache" in msgs[0]
