"""The step program's key (``inference/v2/step_key.py``): one owner of the
key's layout, the table of step kinds and the bucket rule.

What these hold: a ``StepKey`` IS the bare tuple every manifest, artifact
and hint file stores; every key the benchmark's hints name has a row in
the table; the default lattice is the power-of-two arithmetic it replaced;
the key the strict scheduler predicts is the key the dispatch forms; and a
program formed ahead of time from the table's avals is the one the live
operands call.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from deepspeed_tpu.inference.v2 import (
    InferenceEngineV2, KVCacheConfig, RaggedInferenceEngineConfig,
    RaggedInferenceModel, SamplingParams, ServingOptimizationConfig,
    StateManagerConfig)
from deepspeed_tpu.inference.v2.lattice import (POWER_LATTICE, BucketLattice,
                                                _pick)
from deepspeed_tpu.inference.v2.ragged.batch import (MIN_PAGES, MIN_SLOTS,
                                                     _bucket)
from deepspeed_tpu.inference.v2.step_key import (LATTICE_KINDS, STEP_KINDS,
                                                 StepKey, lattice_kind_of)
from deepspeed_tpu.models.llama import LlamaForCausalLM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: one key a kind, as the bare tuple the tree stored before ``StepKey``
BARE = {
    "logits": (4, 128, 8, True),
    "sample": (4, 128, 8, True, "sample", False),
    "chain": (64, 1, 8, False, "chain", 64, True),
    "spec": (8, 8, 16, False, "spec", True),
    "draft_spec": (8, 8, 16, False, "draft_spec", False),
    "draft_fill": (2, 32, 8, False, "draft_fill"),
    "mixed": (64, 1, 8, False, "mixed", 4, 128, 8, True, True),
}


def test_the_table_has_the_seven_kinds():
    assert sorted(STEP_KINDS) == sorted(BARE)


# -- (a) a StepKey is its tuple ------------------------------------------------

@pytest.mark.parametrize("kind", sorted(BARE))
def test_key_round_trips_and_is_its_bare_tuple(kind):
    bare = BARE[kind]
    key = StepKey.parse(bare)
    assert type(key) is StepKey and key.kind == kind
    assert key == bare and bare == key and hash(key) == hash(bare)
    assert repr(key) == repr(bare) and json.dumps(key) == json.dumps(bare)
    assert {bare: 1}[key] == 1 and {key: 1}[bare] == 1
    assert sorted([key, (1, 1, 8, False)], key=repr)[0] == (1, 1, 8, False)
    again = StepKey.parse(json.loads(json.dumps(key)))
    assert again == key and type(again) is StepKey
    assert StepKey.parse(key) is key
    # the constructor of the kind forms the same key from its fields
    shapes = [key.decode, key.prefill] if kind == "mixed" else [key[:4]]
    assert StepKey.form(kind, shapes, greedy=bool(key.greedy),
                        prev_len=key.prev_len or 0) == bare
    assert (key.S, key.Q, key.P) == bare[:3]
    assert key.with_fresh(key.fresh) == key
    assert key.with_fresh(not key.fresh).fresh is (not key.fresh)


def test_readers_name_the_fields():
    chain = StepKey.chain((64, 1, 8, False), 128, True)
    assert (chain.prev_len, chain.greedy, chain.fresh) == (128, True, False)
    mixed = StepKey.mixed((64, 1, 8, False), (4, 128, 16, True), False)
    assert mixed.decode == (64, 1, 8, False)
    assert mixed.prefill == (4, 128, 16, True)
    assert mixed.fresh is True and mixed.greedy is False
    assert mixed.with_fresh(False) == (64, 1, 8, False, "mixed",
                                       4, 128, 16, False, False)
    assert mixed.padded_tokens == 64 + 4 * 128
    sample = StepKey.sample((4, 128, 8, True), True)
    assert sample.padded_tokens == 512 and sample.prev_len is None
    assert StepKey.logits((4, 1, 8, False)).greedy is None
    # the kinds whose rows always have history pin the fresh flag
    for kind in ("spec", "draft_spec", "draft_fill"):
        assert StepKey.form(kind, [(4, 8, 8, True)], greedy=True).fresh is False


@pytest.mark.parametrize("bad", [
    (4, 1, 8), (4, 1, 8, False, "sample"), (4, 1, 8, False, "logits"),
    (4, 1, 8, False, "chain", True), (4, 1, 8, False, "verify", True),
    (4, 1, 8, False, "mixed", 4, 128, 8, True), (4, 1, 8, 0),
    (4, 1, 8, False, "sample", 1), (4, 1, 8, False, "chain", True, True),
    (4, 1, 8, False, 7), (0, 1, 8, False), 5, None],
    ids=repr)
def test_parse_refuses_what_names_no_program(bad):
    with pytest.raises(ValueError):
        StepKey.parse(bad)


# -- (b) the benchmark's hints -------------------------------------------------

@pytest.mark.parametrize("name", ["short-closed64", "reason-closed256"])
def test_every_hinted_key_has_a_row(name):
    with open(os.path.join(ROOT, "benchmark", "hints", name + ".json")) as f:
        keys = json.load(f)["keys"]
    assert keys
    for k in keys:
        key = StepKey.parse(k)
        assert key.kind in STEP_KINDS and json.loads(json.dumps(key)) == k
        assert lattice_kind_of(key) in LATTICE_KINDS


# -- (c) the bucket rule, once -------------------------------------------------

def test_default_lattice_is_the_power_of_two_arithmetic():
    lat = POWER_LATTICE
    for n in range(1, 601):
        assert lat.bucket_s(n) == _bucket(n, MIN_SLOTS)
    for n in range(1, 5001):
        assert lat.bucket_q(n) == _bucket(n)
    for n in range(0, 301):
        assert lat.bucket_p(n) == _bucket(max(n, 1), MIN_PAGES)
    for rows, q, pages, min_q in [(1, 1, 1, 1), (3, 17, 9, 1), (64, 1, 8, 1),
                                  (65, 129, 65, 1), (5, 2, 3, 6),
                                  (600, 5000, 300, 1)]:
        assert lat.shape(rows, q, pages, min_q) == (
            _bucket(rows, MIN_SLOTS), _bucket(max(q, min_q)),
            _bucket(pages, MIN_PAGES))
    assert not lat.mined and lat.digest == "" and lat.keys == ()


def test_mined_lattice_picks_its_tops():
    lat = BucketLattice(s_tops=(3, 6, 48), q_tops=(17, 66, 300),
                        p_tops=(8, 24))
    assert lat.mined
    for n in range(1, 200):
        assert lat.bucket_s(n) == _pick(n, (3, 6, 48), MIN_SLOTS)
        assert lat.bucket_p(n) == _pick(n, (8, 24), MIN_PAGES)
    for n in range(1, 700):
        assert lat.bucket_q(n) == _pick(n, (1, 17, 66, 300), 1)
    assert lat.shape(4, 18, 9) == (6, 66, 24)
    assert lat.shape(4, 2, 9, min_q=20) == (6, 66, 24)
    # past the largest top: power-of-two growth over the floor
    assert lat.shape(49, 301, 25) == (64, 512, 32)


# -- (e) the lattice class of a key, from the table ----------------------------

@pytest.mark.parametrize("bare,want", [
    ((4, 128, 8, True), "prefill"), ((4, 1, 8, False), "decode"),
    ((4, 128, 8, False, "sample", True), "prefill"),
    ((4, 1, 8, False, "sample", False), "decode"),
    (BARE["chain"], "chain"), (BARE["spec"], "spec"),
    (BARE["draft_spec"], "spec"), (BARE["draft_fill"], "spec"),
    (BARE["mixed"], "prefill")], ids=repr)
def test_lattice_class_of_a_key(bare, want):
    from deepspeed_tpu.inference.v2 import engine
    assert lattice_kind_of(bare) == want
    assert engine.lattice_kind_of is lattice_kind_of


# -- (d) predicted key == dispatched key, and the table's avals are the live
# operands ---------------------------------------------------------------------

PAGE = 16
K = 3


@pytest.fixture(scope="module")
def parts():
    model = LlamaForCausalLM("debug", max_seq_len=256, dtype=jnp.float32)
    return model.cfg, meta.unbox(model.init_params(jax.random.key(0)))


def _engine(parts, keyed=False):
    cfg, params = parts
    model = RaggedInferenceModel(cfg, params, kv_config=KVCacheConfig(
        num_layers=cfg.num_layers, kv_heads=cfg.kv_heads,
        head_dim=cfg.dims_per_head, page_size=PAGE, num_pages=64,
        dtype=jnp.float32))
    return InferenceEngineV2(model, RaggedInferenceEngineConfig(
        state_manager=StateManagerConfig(
            max_tracked_sequences=8, max_ragged_sequence_count=8,
            max_ragged_batch_size=256),
        serving=ServingOptimizationConfig(
            speculative=True, spec_drafter="model", spec_max_draft=K,
            spec_draft_layers=1, prefix_caching=False,
            keyed_sampling=keyed)))


class _Walk:
    """Drives one engine through every kind of dispatch, in an order in
    which each is legal; before a dispatch it takes the engine's
    prediction (where the scheduler would ask for one), forms that
    program ahead of time from the table, and forbids forming another."""

    def __init__(self, eng, keyed):
        self.eng, self.keyed = eng, keyed
        self.rng = jax.random.key(3)
        self.greedy = [SamplingParams(), SamplingParams()]
        self.seen = {}

    def expect(self, kind, key, run):
        eng = self.eng
        assert eng.precompile_keys([tuple(key)]) == 1
        assert eng.has_program(tuple(key)) and eng.has_kind(kind)
        before = set(eng.compiled_keys())
        eng.model.strict_shapes = True     # a second program would raise
        try:
            out = run()
        finally:
            eng.model.strict_shapes = False
        new = set(eng.compiled_keys()) - before
        assert new == {key}, (kind, key, new)
        assert all(type(k) is StepKey for k in new)
        self.seen[kind] = key
        return out

    def pos(self, *p):
        return list(p) if self.keyed else None

    def run(self):
        eng, rng, sp = self.eng, self.rng, self.greedy
        prompt = np.arange(1, 7, dtype=np.int32)
        one = [np.asarray([5], np.int32)]
        # logits: a fresh prefill through put()
        key = eng.predict_step_key([0], [prompt])
        assert key == (1, 8, 8, True)
        self.expect("logits", key, lambda: eng.put([0], [prompt]))
        # sample: a fresh prefill, then a decode step of both rows
        key = eng.predict_step_key([1], [prompt], "sample", greedy=True)
        assert key == (1, 8, 8, True, "sample", True)
        self.expect("sample", key, lambda: eng.step_sample(
            [1], [prompt], sp[:1], rng, row_pos=self.pos(0)))
        key = eng.predict_step_key([0, 1], one * 2, "sample", greedy=True)
        assert key == (2, 1, 8, False, "sample", True)
        toks, rows = self.expect("sample", key, lambda: eng.step_sample(
            [0, 1], one * 2, sp, rng, row_pos=self.pos(1, 1)))
        assert rows == [0, 1]
        # chain: the decode step's tokens never leave the device
        key = eng.predict_step_key([0, 1], one * 2, "chain", greedy=True,
                                   prev_tokens=toks)
        assert key == (2, 1, 8, False, "chain", 2, True)
        self.expect("chain", key, lambda: eng.step_decode_chained(
            [0, 1], toks, [0, 1], sp, rng, row_pos=self.pos(2, 2)))
        # spec: [last, drafts...] rows, padded to the one spec bucket
        rows = [np.asarray([5, 6, 7], np.int32), np.asarray([5], np.int32)]
        key = eng.predict_step_key([0, 1], rows, "spec", greedy=True,
                                   min_q=1 + K)
        assert key == (2, 4, 8, False, "spec", True)
        out = self.expect("spec", key, lambda: eng.step_spec(
            [0, 1], rows, sp, rng, min_q=1 + K, row_pos=self.pos(3, 3)))
        assert out.shape == (2, 2)
        eng.commit_spec([0, 1], [1, 1])
        # draft_fill: committed history the draft pool has not seen
        lag = eng.draft_lag(0)
        assert lag > 0
        hist = [np.arange(1, 1 + lag, dtype=np.int32)]
        key = eng.predict_step_key([0], hist, "draft_fill")
        assert key == (1, 16, 8, False, "draft_fill")
        self.expect("draft_fill", key,
                    lambda: eng.step_draft_fill([0], hist))
        assert eng.draft_lag(0) == 0
        # draft_spec: the draft trunk proposes inside the program
        rows = [np.asarray([5, 0, 0, 0], np.int32)]
        key = eng.predict_step_key([0], rows, "draft_spec", greedy=True,
                                   min_q=1 + K)
        assert key == (1, 4, 8, False, "draft_spec", True)
        out = self.expect("draft_spec", key, lambda: eng.step_draft_spec(
            [0], rows, sp[:1], rng, min_q=1 + K, row_pos=self.pos(4)))
        assert out.shape == (1, 2 + K)
        eng.commit_spec([0], [1])
        # mixed: two decode rows and a new prompt in one program; no
        # scheduler predicts it (a strict one splits such a step), so
        # the key is written out
        key = StepKey.mixed((2, 1, 8, False), (1, 8, 8, True), True)
        toks, rows = self.expect("mixed", key, lambda: eng.step_sample(
            [0, 2, 1], [one[0], prompt, one[0]], sp + sp[:1], rng,
            row_pos=self.pos(5, 0, 4)))
        # tokens come back in segment order, padded to the slot bucket
        assert rows == [0, 2, 1] and toks.shape == (4,)
        assert set(self.seen) == set(STEP_KINDS)


@pytest.mark.parametrize("keyed", [False, True], ids=["plain", "keyed"])
def test_predicted_key_is_the_dispatched_key_for_every_kind(parts, keyed):
    _Walk(_engine(parts, keyed), keyed).run()


def test_keyed_engine_stepped_without_positions_raises(parts):
    eng = _engine(parts, keyed=True)
    with pytest.raises(ValueError, match="row_uids/row_pos"):
        eng.step_sample([0], [np.arange(1, 7, dtype=np.int32)],
                        [SamplingParams()], jax.random.key(0))


def test_precompile_keys_skips_what_names_no_program(parts):
    eng = _engine(parts)
    assert eng.precompile_keys([[1, 1, 8, False, "sample"],
                                [1, 1, 8, False, "nope", True],
                                [1, 1, 8, False, "sample", True]]) == 1
    assert eng.compiled_keys(dispatched_only=False) == [
        (1, 1, 8, False, "sample", True)]
    assert not eng.has_kind("chain", "spec")


# -- the seam of the step program's trunk: a layer kind is an entry ----------

def _layer_kinds():
    from deepspeed_tpu.inference.v2.model import MIXERS
    from deepspeed_tpu.inference.v2.ragged.cache_kinds import CACHE_KINDS
    return sorted(set(CACHE_KINDS) | set(MIXERS) | {"latent"})


@pytest.mark.parametrize("kind", _layer_kinds())
def test_every_layer_kind_has_a_mixer_and_every_mixer_a_known_kind(kind):
    """``model.py::MIXERS`` has the keys of ``CACHE_KINDS`` (the latent kind
    among them since PR 50: its plane lies in the one page group's pool),
    each entry names a method of the model, and the pools it writes, BY
    NAME (``RaggedInferenceModel.pool_names``), are the ones its kind caches
    in: the state pool's two arrays, the window group's pool, or the
    pages."""
    from deepspeed_tpu.inference.v2.model import MIXERS
    from deepspeed_tpu.inference.v2.ragged.cache_kinds import CACHE_KINDS
    assert kind in MIXERS and (kind in CACHE_KINDS or kind == "latent")
    mixer = MIXERS[kind]
    cache = CACHE_KINDS.get(kind)
    if mixer.run is None:
        # a feed-forward alone (PR 54): no mixer, and it caches nothing
        assert (mixer.weights, mixer.pools) == ("", ())
        assert not cache.slot and not cache.group and not cache.windowed
        return
    assert getattr(RaggedInferenceModel, mixer.run.__name__) is mixer.run
    assert mixer.pools == (("state", "conv") if cache is not None
                           and cache.slot else
                           ("window",) if cache is not None and cache.windowed
                           else ("pages",))
    assert mixer.weights == ("mixer" if mixer.pools == ("state", "conv")
                             else "attn")


def test_the_step_program_imports_no_family_by_name():
    """``model.py`` is every family's trunk: of ``deepspeed_tpu/models`` it
    imports the shared core alone (a family's own functions reach it
    through the family's class in ``model_implementations.py``)."""
    import ast
    path = os.path.join(ROOT, "deepspeed_tpu", "inference", "v2", "model.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    families = {name[:-3] for name in os.listdir(
        os.path.join(ROOT, "deepspeed_tpu", "models"))
        if name.endswith(".py")} - {"__init__", "transformer"}
    assert {"laguna", "jamba", "olmo_hybrid", "pangu_moe"} <= families
    named = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            module = (node.module or "").split(".")
            named |= set(module) | ({a.name for a in node.names}
                                    if module[-1:] == ["models"] else set())
        elif isinstance(node, ast.Import):
            named |= {part for a in node.names for part in a.name.split(".")}
    assert "transformer" in named and not named & families


def _pattern_cfg(kinds=(), leading=0, layers=0, latent=False):
    from deepspeed_tpu.models.transformer import TransformerConfig
    return TransformerConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_layers=layers or len(kinds), num_heads=4,
        layer_kinds=tuple(kinds), first_k_dense=leading,
        heads_by_kind=tuple((k, 4) for k in dict.fromkeys(kinds)),
        **(dict(kv_lora_rank=8, qk_rope_head_dim=4, qk_nope_head_dim=4,
                v_head_dim=8, q_lora_rank=8) if latent else {}))


F, W = "full", "window"
PATTERNS = {
    # (kinds, leading, layers, latent) -> (leading, runs, periods, tail)
    # a family of one kind is a pattern of period 1
    "seed": (((), 0, 4, False), (0, [(F, 1)], 4, 0)),
    "latent": (((), 1, 3, True), (1, [("latent", 1)], 2, 0)),
    "latent-all-leading": (((), 5, 3, True), (3, [], 0, 0)),
    # the cases ``models/laguna.py::layer_plan`` had: a leading dense
    # layer, then (window x 3, full) once, with a tail, twice with a tail
    "leading-one-period": (((F, W, W, W, F), 1, 0, False),
                           (1, [(W, 3), (F, 1)], 1, 0)),
    "leading-period-tail": (((F, W, W, W, F, W, W, W), 1, 0, False),
                            (1, [(W, 3), (F, 1)], 1, 3)),
    "leading-two-periods-tail": (((F,) + (W, W, W, F) * 2 + (W,), 1, 0,
                                  False), (1, [(W, 3), (F, 1)], 2, 1)),
    # the leading layers stand OUTSIDE the pattern: counted into it, the
    # same list has another period
    "no-leading": (((F, W, W, W, F), 0, 0, False),
                   (0, [(F, 1), (W, 3)], 1, 1)),
    "runs": ((("ssm",) * 2 + (F,) + ("ssm",) * 3, 0, 0, False),
             (0, [("ssm", 2), (F, 1), ("ssm", 1)], 1, 2)),
    "runs-and-tail": ((("delta", "delta", F) * 2 + ("delta",), 0, 0, False),
                      (0, [("delta", 2), (F, 1)], 2, 1)),
}


@pytest.mark.parametrize("name", sorted(PATTERNS))
def test_the_period_finder(name):
    """``models/transformer.py::layer_runs``: the ONE search for the
    shortest period of a layer pattern, the leading layers outside it."""
    from deepspeed_tpu.models.transformer import layer_kinds, layer_runs
    given, want = PATTERNS[name]
    cfg = _pattern_cfg(*given)
    assert layer_runs(cfg) == want
    leading, runs, periods, tail = want
    period = [kind for kind, n in runs for _ in range(n)]
    kinds = layer_kinds(cfg)
    assert len(kinds) == cfg.num_layers
    assert list(kinds[leading:cfg.num_layers - tail]) == period * periods
