"""Builder ``serve_fastgen``: one ``InferenceEngineV2`` + ``FastGenScheduler``
over seeded bf16 weights at the widths a configuration file gives.

``mistral_7b`` / ``serve_params`` / the engine construction are copied from
``chip_smoke.py`` (PR 21 proved them on the chip); the benchmark keeps its
own copy so that a later change to the smoke cannot move the yardstick.
The program is entered only through ``LlamaForCausalLM``,
``MistralInferenceModel``, ``InferenceEngineV2`` and ``FastGenScheduler``.
"""

from __future__ import annotations

import dataclasses

import numpy as np


def sized(config: dict, rehearse: bool) -> dict:
    """The configuration as it is run: a rehearsal takes the file's debug
    widths in place of the published ones."""
    return dict(config, **config.get("rehearse", {})) if rehearse else config


def widths(config: dict, rehearse: bool) -> dict:
    """The configuration's sizes under the repo's own argument names."""
    c = sized(config, rehearse)
    assert c["hidden_act"] == "silu" and not c["tie_word_embeddings"]
    return dict(
        hidden_size=c["hidden_size"], intermediate_size=c["intermediate_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        num_layers=c["num_hidden_layers"], vocab_size=c["vocab_size"],
        sliding_window=c["sliding_window"], norm_eps=c["rms_norm_eps"],
        rope_theta=c["rope_theta"])


def make_model(config: dict, rehearse: bool, max_seq_len: int):
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    return LlamaForCausalLM("7b", max_seq_len=max_seq_len,
                            **widths(config, rehearse))


def seeded_key(seed: int):
    import jax
    return jax.random.key(int(seed) % (2 ** 32))


def serve_params(model, seed: int):
    """Seeded weights made on the device in one jitted call, in the
    serving dtype, boxed with their logical axes."""
    import jax
    init = jax.jit(lambda key: jax.tree.map(
        lambda x: x.astype(model.cfg.dtype), model.init_params(key)))
    return init(seeded_key(seed))


def reference_first_tokens(model, params, prompts):
    """The repo's plain forward pass (no cache, no paging, no kernel:
    ``attention_impl="einsum"``) in float32 at highest matmul precision on
    the same weights; returns per prompt (arg-max id of the last
    position's logits, gap between the two largest logits).  Prompts are
    right-padded to one length: under a causal mask the padding cannot
    reach the position that is read."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer import forward
    cfg = dataclasses.replace(model.cfg, dtype=jnp.float32,
                              attention_impl="einsum", remat=False)
    width = -(-max(len(p) for p in prompts) // 64) * 64
    ids = np.zeros((len(prompts), width), np.int32)
    for i, p in enumerate(prompts):
        ids[i, :len(p)] = p
    last = jnp.asarray([len(p) - 1 for p in prompts])

    def plain(params, ids):
        logits = forward(cfg, params, ids)
        rows = logits[jnp.arange(ids.shape[0]), last].astype(jnp.float32)
        top2 = jax.lax.top_k(rows, 2)
        return top2[1][:, 0], top2[0][:, 0] - top2[0][:, 1]

    with jax.default_matmul_precision("highest"):
        tok, gap = jax.jit(plain)(params, jnp.asarray(ids))
    return np.asarray(tok).tolist(), np.asarray(gap).tolist()


@dataclasses.dataclass
class ServeSystem:
    kind: str
    cfg: object
    engine: object
    sched: object
    vocab: int
    num_pages: int
    probe: dict
    devices: list


def build(config: dict, seed: int, devices, rehearse: bool) -> ServeSystem:
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import (
        FastGenScheduler, InferenceEngineV2, RaggedInferenceEngineConfig,
        SamplingParams, ServingOptimizationConfig, StateManagerConfig)
    from deepspeed_tpu.inference.v2.config import KVCacheUserConfig
    from deepspeed_tpu.inference.v2.model_implementations import (
        MistralInferenceModel)

    eng = config["engine"]
    model = make_model(config, rehearse, eng["max_seq_len"])
    cfg = model.cfg
    params = serve_params(model, seed)

    # the probe's reference side, before the engine takes its memory
    pr = config["probe"]
    rng = np.random.default_rng([int(seed) % (2 ** 63), 7])
    lens = np.linspace(pr["min_len"], pr["max_len"], pr["prompts"]).astype(int)
    prompts = [rng.integers(0, cfg.vocab_size, int(l)).tolist() for l in lens]
    want, gaps = reference_first_tokens(model, params, prompts)

    engine = InferenceEngineV2(
        MistralInferenceModel(cfg, params),
        RaggedInferenceEngineConfig(
            state_manager=StateManagerConfig(
                max_tracked_sequences=eng["max_sequences"],
                max_ragged_sequence_count=eng["max_sequences"],
                max_ragged_batch_size=eng["token_budget"]),
            kv_cache=KVCacheUserConfig(
                page_size=eng["page_size"], num_pages=eng["num_pages"],
                dtype=jnp.dtype(eng["kv_dtype"])),
            serving=ServingOptimizationConfig(**eng["serving"])))
    sched = FastGenScheduler(engine)

    # the served side: the first token of each probe prompt
    # (in waves of a few, so that their prefill forms the step program the
    # traffic's own ramp forms)
    got = []
    wave = int(pr.get("wave", len(prompts)))
    for lo in range(0, len(prompts), wave):
        for uid in range(lo, min(lo + wave, len(prompts))):
            sched.submit(-1 - uid, prompts[uid],
                         SamplingParams(max_new_tokens=1))
        out = sched.run_to_completion()
        got += [out[-1 - uid][0]
                for uid in range(lo, min(lo + wave, len(prompts)))]
    compared = [(g == w) for g, w, gap in zip(got, want, gaps)
                if gap >= pr["margin"]]
    probe = {"served": got, "reference": want,
             "top2_gap": [round(float(g), 4) for g in gaps],
             "compared": len(compared), "matched": int(sum(compared)),
             "ok": bool(all(compared)
                        and len(compared) >= pr["min_compared"])}
    return ServeSystem("serve", cfg, engine, sched, cfg.vocab_size,
                       eng["num_pages"], probe, list(devices))


def describe(system: ServeSystem) -> dict:
    return {"kind": system.kind, "layers": system.cfg.num_layers,
            "params": system.cfg.n_params(), "pages": system.num_pages,
            "probe": system.probe}
