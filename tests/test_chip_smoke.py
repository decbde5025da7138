"""chip_smoke.py refuses to pass without a chip, and the compile-cache
helper can be placed from outside (the contract the chip tool relies on)."""

import json
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from deepspeed_tpu.utils import compile_cache as cc

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_smoke(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(cwd, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=300, env=env, cwd=cwd)
    lines = proc.stdout.strip().splitlines()
    return proc.returncode, (json.loads(lines[-1]) if lines else None)


@pytest.mark.parametrize("args", [(), ("--chips", "4")],
                         ids=["one_chip", "four_chips"])
def test_smoke_fails_without_a_chip(args):
    rc, last = _run_smoke(REPO_ROOT, *args)
    assert rc != 0
    assert last["ok"] is False and last["phase"] == "device"
    assert "no TPU" in last["error"]


def test_smoke_fails_alone_in_a_directory(tmp_path):
    """The script without the program beside it never prints a result."""
    with open(os.path.join(REPO_ROOT, "chip_smoke.py")) as src:
        (tmp_path / "chip_smoke.py").write_text(src.read())
    rc, last = _run_smoke(str(tmp_path))
    assert rc != 0 and last["ok"] is False


def test_agreement_counts_tokens_before_the_first_divergence():
    a = {0: [5, 1, 2, 3, 4], 1: [9, 7, 7]}
    same = chip_smoke.agreement(a, a)
    assert same == {"first_token_exact": True, "rest_agreement": 1.0,
                    "rest_positionwise": 1.0}
    b = {0: [5, 1, 9, 3, 4], 1: [8, 7, 7]}
    got = chip_smoke.agreement(a, b)
    assert got["first_token_exact"] is False
    # request 0 agrees on 1 of 4 later tokens before diverging (3 of 4
    # position-wise), request 1 on 2 of 2
    assert got["rest_agreement"] == 0.5
    assert got["rest_positionwise"] == round(5 / 6, 4)


@pytest.mark.parametrize("ties,ok", [
    (None, False), ({}, False),
    ({3: {"tokens": [5, 9], "gap": 0.03, "top2": True}}, True),
    ({3: {"tokens": [5, 9], "gap": 0.03, "top2": False}}, False),
    ({3: {"tokens": [5, 9], "gap": 0.2, "top2": True}}, False),
    ({3: {"tokens": [5, 9], "gap": 0.03, "top2": True},
      4: {"tokens": [1, 2], "gap": 0.5, "top2": True}}, False)],
    ids=["not-looked-at", "none-found", "near-tie", "not-the-top-two",
         "wide-gap", "one-of-two-wide"])
def test_first_tokens_may_differ_only_at_a_near_tie(ties, ok):
    agree = {"first_token_exact": False, "rest_agreement": 0.5}
    if ok:
        chip_smoke.require_agreement(agree, "a and b", ties)
    else:
        with pytest.raises(RuntimeError, match="a and b tokens disagree"):
            chip_smoke.require_agreement(agree, "a and b", ties)
    chip_smoke.require_agreement(dict(agree, first_token_exact=True), "x")
    with pytest.raises(RuntimeError):       # the later tokens still count
        chip_smoke.require_agreement(dict(agree, rest_agreement=0.1), "x",
                                     {3: {"gap": 0.0, "top2": True}})


def test_first_token_ties_reads_the_prompts_plain_forward():
    import jax.numpy as jnp
    import numpy as np
    from flax.core import meta

    from deepspeed_tpu.models.llama import LlamaForCausalLM
    from deepspeed_tpu.models.transformer import forward
    model = LlamaForCausalLM("debug", max_seq_len=256, dtype=jnp.float32)
    params = meta.unbox(model.init_params(jax.random.key(0)))
    prompts = [list(range(3, 40)), list(range(50, 70))]
    rows = [np.asarray(forward(model.cfg, params, jnp.asarray([p]))[0, -1])
            for p in prompts]
    top = [np.argsort(-r)[:3].tolist() for r in rows]
    a = {0: [top[0][0], 1], 1: [top[1][0], 1]}
    assert chip_smoke.first_token_ties(model.cfg, params, prompts, a, a,
                                       jax.devices()[:1]) == {}
    b = {0: [top[0][0], 2], 1: [top[1][2], 1]}
    ties = chip_smoke.first_token_ties(model.cfg, params, prompts, a, b,
                                       jax.devices()[:1])
    assert list(ties) == [1] and ties[1]["top2"] is False
    assert ties[1]["tokens"] == sorted([top[1][0], top[1][2]])
    assert ties[1]["gap"] == pytest.approx(
        rows[1][top[1][0]] - rows[1][top[1][2]], abs=2e-4)
    b[1][0] = top[1][1]
    assert chip_smoke.first_token_ties(
        model.cfg, params, prompts, a, b, jax.devices()[:1])[1]["top2"]


def test_custom_call_counting_needs_the_kernel_on_the_call_line():
    text = ('%a = custom-call(), custom_call_target="tpu_custom_call", '
            'metadata={op_name="jit(f)/paged_attention/pallas_call"}\n'
            '%b = fusion(), metadata={op_name="jit(f)/paged_attention"}\n'
            '%c = custom-call(), custom_call_target="tpu_custom_call", '
            'metadata={op_name="jit(f)/rmsnorm_kernel/pallas_call"}\n')
    assert chip_smoke.custom_calls(text) == 2
    assert chip_smoke.custom_calls(text, "paged_attention") == 1
    assert chip_smoke.custom_calls(text, "flash_attention_fwd") == 0


class TestCompileCachePlacement:
    @pytest.fixture(autouse=True)
    def _cache_on(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        jax.config.update("jax_enable_compilation_cache", True)
        before = jax.config.jax_compilation_cache_dir
        yield
        cc.disable_compile_cache()
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_enable_compilation_cache", False)

    def test_env_var_places_the_cache_and_code_sets_nothing(
            self, tmp_path, monkeypatch):
        placed = str(tmp_path / "from_outside")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
        before = jax.config.jax_compilation_cache_dir
        # the env var outranks the config field, too
        assert cc.ensure_compile_cache(str(tmp_path / "cfg")) == placed
        assert cc.active_cache_dir() == placed
        assert jax.config.jax_compilation_cache_dir == before
        assert not (tmp_path / "cfg").exists()

    def test_unset_uses_the_fixed_in_checkout_path(self, tmp_path,
                                                   monkeypatch):
        assert cc.DEFAULT_CACHE_DIR == os.path.join(REPO_ROOT, ".jax_cache")
        # same rule, pointed at a scratch directory for the write
        fixed = str(tmp_path / ".jax_cache")
        monkeypatch.setattr(cc, "DEFAULT_CACHE_DIR", fixed)
        assert cc.ensure_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed

    def test_config_field_is_used_as_given(self, tmp_path):
        given = str(tmp_path / "cfg")
        assert cc.ensure_compile_cache(given) == given
        assert jax.config.jax_compilation_cache_dir == given
        assert os.path.isdir(given)

    def test_master_switch_off_means_no_cache(self, tmp_path):
        jax.config.update("jax_enable_compilation_cache", False)
        assert cc.ensure_compile_cache(str(tmp_path / "cfg")) is None
        assert cc.active_cache_dir() is None

    def test_training_and_serving_share_the_helper(self):
        import inspect

        import deepspeed_tpu
        from deepspeed_tpu.inference.v2 import engine
        for module in (deepspeed_tpu, engine):
            assert "ensure_compile_cache(" in inspect.getsource(module)
