"""A mixed step runs the trunk once (ISSUE 30).

The ``mixed`` program lays its decode rows' and its prefill rows' tokens
end to end and streams every weight once; only the page write and
attention run segment by segment.  What these hold: (a) its sampled
tokens, its logits (through a tap this file puts on the sampling
reduction) and the pool it leaves equal those of a ``sample`` program on
the decode segment followed by one on the prefill segment, for every
served family's trunk; (b) the held-experts counts in the token vector's
tail are those of the one pass; (c) the lowered program holds one layer
loop a weight stack and reads each stacked weight once; (d) forming a
program counts its trunk passes, which the ``fastgen.step`` span carries.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from deepspeed_tpu.inference.v2 import (
    InferenceEngineV2, KVCacheConfig, RaggedInferenceEngineConfig,
    RaggedInferenceModel, ServingOptimizationConfig, StateManagerConfig)
from deepspeed_tpu.inference.v2.config import KVCacheUserConfig
from deepspeed_tpu.inference.v2.model_implementations import (
    implementation_for)
from deepspeed_tpu.inference.v2.ragged import RaggedBatch
from deepspeed_tpu.inference.v2.step_key import (STEP_KINDS, StepKey,
                                                 step_avals, step_program)
from deepspeed_tpu.models.gpt import GPTForCausalLM
from deepspeed_tpu.models.llama import LlamaForCausalLM
from deepspeed_tpu.models.mixtral import MixtralForCausalLM
from deepspeed_tpu.models.pangu_moe import PanguUltraMoEForCausalLM
from test_pangu_moe import SOURCE as PANGU

PAGE, P = 16, 8
S_D, Q_P = 4, 32
#: the decode rows' committed lengths; their pages
HISTORY = ((20, (1, 2)), (9, (3,)), (32, (4, 5, 6)))
#: the prefill rows' new tokens; their pages (the third of each holds
#: what a continued row appends past its first 16 tokens)
PROMPTS = ((32, (7, 8, 12)), (17, (9, 10, 13)), (5, (11, 14)))
CONTINUED_FROM = 16

def _engine(family, serving=None):
    """A tiny float32 engine of ``family``: its model and its pool are
    what the cases drive, through ``model.run_step`` as a dispatch does."""
    serving = serving or ServingOptimizationConfig()
    state = StateManagerConfig(max_tracked_sequences=8,
                               max_ragged_sequence_count=8,
                               max_ragged_batch_size=256)
    if family == "latent":
        model = PanguUltraMoEForCausalLM(PANGU, experts_first=4,
                                         dtype=jnp.float32)
        params = meta.unbox(model.init_params(jax.random.key(3)))
        return InferenceEngineV2(
            implementation_for("pangu_ultra_moe")(model.cfg, params),
            RaggedInferenceEngineConfig(
                state_manager=state, serving=serving,
                kv_cache=KVCacheUserConfig(page_size=PAGE, num_pages=64,
                                           dtype=jnp.float32)))
    if family == "mixtral":
        model = MixtralForCausalLM("debug", num_experts=4, top_k=2,
                                   max_seq_len=256, dtype=jnp.float32)
        cfg = dataclasses.replace(model.cfg, moe_num_experts=4, moe_top_k=2)
    elif family == "gpt":       # learned positions, layernorm, biases
        model = GPTForCausalLM("debug", max_seq_len=256, dtype=jnp.float32)
        cfg = model.cfg
    else:
        model = LlamaForCausalLM("debug", max_seq_len=256, dtype=jnp.float32)
        cfg = model.cfg
    if family == "alibi":
        cfg = dataclasses.replace(cfg, pos_emb="alibi")
    if family == "int8":
        serving = ServingOptimizationConfig(kv_quantization="int8")
    if family == "tp2":
        serving = ServingOptimizationConfig(tp_degree=2)
    params = meta.unbox(model.init_params(jax.random.key(0)))
    served = RaggedInferenceModel(cfg, params, kv_config=KVCacheConfig(
        num_layers=cfg.num_layers, kv_heads=cfg.kv_heads,
        head_dim=cfg.dims_per_head, page_size=PAGE, num_pages=64,
        dtype=jnp.float32))
    return InferenceEngineV2(served, RaggedInferenceEngineConfig(
        state_manager=state, serving=serving))


def _batch(rows, S, Q, fresh):
    """``rows``: (new tokens, start position, pages) a live row; the
    rest of the ``S`` slots are padding (no tokens, the null page)."""
    tok = np.zeros((S, Q), np.int32)
    lens, start = np.zeros(S, np.int32), np.zeros(S, np.int32)
    table = np.zeros((S, P), np.int32)
    for i, (new, at, pages) in enumerate(rows):
        tok[i, :len(new)], lens[i], start[i] = new, len(new), at
        table[i, :len(pages)] = pages
    return RaggedBatch(tok, lens, start, table, list(range(len(rows))),
                       fresh=fresh)


class _Drive:
    """One model, one pool, greedy rows: runs a step program over hand-
    made batches and keeps the logits its sampling reduction saw."""

    def __init__(self, family, monkeypatch):
        self.eng = _engine(family)
        self.model = self.eng.model
        self.logits = []
        inner = self.model._sample_tokens

        def tapped(logits, *args, **kwargs):
            jax.debug.callback(
                lambda x: self.logits.append(np.asarray(x)), logits)
            return inner(logits, *args, **kwargs)
        monkeypatch.setattr(self.model, "_sample_tokens", tapped)

    def pool(self):
        return self.eng._pool("target")

    def run(self, kind, pool, batches):
        n = sum(b.num_slots for b in batches)
        key = StepKey.form(kind, [b.shape_key for b in batches], True)
        sampling = (jax.random.key(0), np.zeros(n, np.float32),
                    np.zeros(n, np.int32), np.ones(n, np.float32),
                    None, None)
        tokens, pool = self.model.run_step(key, pool, batches, sampling)
        jax.effects_barrier()
        return np.asarray(tokens), pool


def _copy(pool):
    return jax.tree.map(jnp.copy, pool)


def _live(pool):
    """A pool's pages without the null page, which padding rows write."""
    return [np.asarray(leaf)[:, 1:] for leaf in jax.tree.leaves(pool)]


def _scenario(drive, fresh_p, prefill_rows, vocab):
    """The pool with the decode rows' histories (and a continued row's
    first tokens) in it, then the step's two segments."""
    rng = np.random.default_rng(5)
    has_fresh = drive.model.has_fresh
    hist = [(rng.integers(0, vocab, n), 0, pages) for n, pages in HISTORY]
    first, pool = drive.run("sample", drive.pool(),
                            [_batch(hist, S_D, Q_P, has_fresh)])
    decode = [(first[i:i + 1], n, pages)
              for i, (n, pages) in enumerate(HISTORY)]
    at = 0 if fresh_p else CONTINUED_FROM
    S_p = 1 if prefill_rows == 1 else 4
    rows = PROMPTS[:prefill_rows]
    if at:
        head = [(rng.integers(0, vocab, at), 0, pages) for _, pages in rows]
        _, pool = drive.run("sample", pool,
                            [_batch(head, S_p, at, has_fresh)])
    prefill = [(rng.integers(0, vocab, n), at, pages) for n, pages in rows]
    drive.logits.clear()
    return pool, (_batch(decode, S_D, 1, False),
                  _batch(prefill, S_p, Q_P, fresh_p and has_fresh))


CASES = [
    # family, the prefill rows start at 0, live prefill rows (3 of a
    # bucket of 4 leave a padded row)
    ("llama", True, 1), ("llama", True, 3),
    ("llama", False, 1), ("llama", False, 3),
    ("alibi", True, 3), ("alibi", False, 1),   # no fresh path: paged
    ("int8", True, 3), ("int8", False, 3),
    ("latent", True, 1), ("latent", True, 3), ("latent", False, 3),
    ("tp2", True, 3), ("tp2", False, 3),
    ("mixtral", True, 3), ("gpt", False, 3),
]


@pytest.mark.parametrize(
    "family,fresh_p,prefill_rows", CASES,
    ids=[f"{f}-{'fresh' if fr else 'continued'}-{n}" for f, fr, n in CASES])
def test_one_pass_equals_a_pass_a_segment(monkeypatch, family, fresh_p,
                                          prefill_rows):
    drive = _Drive(family, monkeypatch)
    tail = drive.model.step_tail
    counts = {}
    drive.now = "set-up"
    if tail:
        # every routed layer's pairs a held expert, by pass
        from deepspeed_tpu.moe import held
        inner = held.held_experts_ffn

        def counted(*args, layer=None, **kwargs):
            out, c = inner(*args, layer=layer, **kwargs)
            jax.debug.callback(
                lambda l, c: counts.setdefault(drive.now, {}).__setitem__(
                    int(l), np.asarray(c)), layer, c)
            return out, c
        monkeypatch.setattr(held, "held_experts_ffn", counted)
    pool, (decode, prefill) = _scenario(drive, fresh_p, prefill_rows,
                                        drive.model.cfg.vocab_size)
    drive.now = "decode"
    tok_d, two = drive.run("sample", _copy(pool), [decode])
    drive.now = "prefill"
    tok_p, two = drive.run("sample", two, [prefill])
    want_logits = np.concatenate(drive.logits)
    drive.logits.clear()
    drive.now = "mixed"
    tok_m, one = drive.run("mixed", _copy(pool), [decode, prefill])
    (got_logits,) = drive.logits

    S_p, live_d, live_p = prefill.num_slots, len(decode.uids), prefill_rows
    rows = np.r_[np.arange(live_d), S_D + np.arange(live_p)]
    assert tok_m.shape == (drive.model.lattice.bucket_s(S_D + S_p) + tail,)
    np.testing.assert_array_equal(
        tok_m[rows], np.r_[tok_d[:live_d], tok_p[:live_p]])
    np.testing.assert_allclose(got_logits[rows], want_logits[rows],
                               rtol=2e-4, atol=2e-4)
    for got, want in zip(_live(one), _live(two)):
        if got.dtype == np.int8:
            # a value on a rounding boundary may land a code apart
            assert np.abs(got.astype(int) - want.astype(int)).max() <= 1
        else:
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    if tail:
        # pairs: the two passes' sum; experts touched and the fullest
        # expert: those of the two segments' tokens together
        both = {l: counts["decode"][l] + counts["prefill"][l]
                for l in counts["decode"]}
        want = [sum(int(c.sum()) for c in both.values()),
                max(int(c.max()) for c in both.values()),
                sum(int((c > 0).sum()) for c in both.values())]
        assert tok_m[-tail:].tolist() == want
        assert tok_d[-tail] + tok_p[-tail] == want[0]
        assert {l: c.tolist() for l, c in counts["mixed"].items()} == {
            l: c.tolist() for l, c in both.items()}


# -- the lowered program: one loop a stack, every stacked weight read once ---

def _lowered_mixed(family):
    eng = _engine(family)
    model = eng.model
    key = StepKey.mixed((S_D, 1, P, False), (4, Q_P, P, True), True)
    text = jax.jit(step_program(model, key), donate_argnums=(1,)).lower(
        *step_avals(model, key, eng._pool("target"))).as_text()
    stacks = [jax.tree.leaves(model.params[name])
              for name in ("dense_layers", "layers") if name in model.params]
    return text, stacks


def stack_reads(text: str, stacks) -> tuple:
    """(layer loops, the most reads of any stacked matrix) of a lowered
    program's text: a ``stablehlo.while`` is a layer loop, and inside one
    every matrix of the stack it scans is read by one ``dynamic_slice``
    of the stacked shape to one layer's (the norms' vectors share their
    shape with other values and are not counted)."""
    loops = len(re.findall(r"stablehlo\.while\(", text))
    reads = 0
    for leaves in stacks:
        for shape in {tuple(leaf.shape) for leaf in leaves
                      if leaf.ndim >= 3}:
            dims = "x".join(str(d) for d in shape)
            one = "x".join(str(d) for d in (1,) + shape[1:])
            n = len(re.findall(
                rf"dynamic_slice.*\(tensor<{dims}x\w+>.*-> tensor<{one}x",
                text))
            same = sum(1 for leaf in leaves if tuple(leaf.shape) == shape)
            assert n % same == 0, (shape, n, same)
            reads = max(reads, n // same)
    return loops, reads


def test_stack_reads_reads_a_program_text():
    text = """
    %5:3 = stablehlo.while(%iterArg = %arg2) : tensor<2x64x64xf32>
      %7 = stablehlo.dynamic_slice %iterArg, %6, %c, %c, sizes = [1, 64, 64] : (tensor<2x64x64xf32>, tensor<i32>, tensor<i32>, tensor<i32>) -> tensor<1x64x64xf32>
    %9:3 = stablehlo.while(%iterArg = %arg2) : tensor<2x64x64xf32>
      %11 = stablehlo.dynamic_slice %iterArg, %6, %c, %c, sizes = [1, 64, 64] : (tensor<2x64x64xf32>, tensor<i32>, tensor<i32>, tensor<i32>) -> tensor<1x64x64xf32>
"""
    assert stack_reads(text, [[np.zeros((2, 64, 64))]]) == (2, 2)


@pytest.mark.parametrize("family,loops", [("llama", 1), ("latent", 2)])
def test_mixed_program_has_one_layer_loop_a_stack(family, loops):
    text, stacks = _lowered_mixed(family)
    assert len(stacks) == loops
    assert stack_reads(text, stacks) == (loops, 1)


# -- the engagement counter ---------------------------------------------------

def test_forming_a_program_counts_its_trunk_passes():
    eng = _engine("llama", ServingOptimizationConfig(
        speculative=True, spec_drafter="model", spec_max_draft=3,
        spec_draft_layers=1, prefix_caching=False))
    keys = [(4, 32, 8, True), (4, 32, 8, True, "sample", True),
            (4, 1, 8, False, "chain", 4, True),
            (4, 4, 8, False, "spec", True),
            (4, 4, 8, False, "draft_spec", True),
            (2, 32, 8, False, "draft_fill"),
            (4, 1, 8, False, "mixed", 4, 32, 8, True, True)]
    assert eng.precompile_keys(keys) == len(keys)
    assert sorted(STEP_KINDS) == sorted(StepKey.parse(k).kind for k in keys)
    assert {k: eng.model._trunk_passes[k] for k in keys} == dict.fromkeys(
        keys, 1)
