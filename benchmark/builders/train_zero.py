"""Builder ``train_zero``: ``dst.initialize`` over seeded weights at the
widths a configuration file gives, with the file's own train config (ZeRO
stage, dtype, optimizer, mesh).  Copied from ``chip_smoke.run_train``."""

from __future__ import annotations

import dataclasses

from .serve_fastgen import seeded_key, sized, widths


@dataclasses.dataclass
class TrainSystem:
    kind: str
    cfg: object
    engine: object
    seq_len: int
    rows: int
    vocab: int
    devices: list


def build(config: dict, seed: int, devices, rehearse: bool) -> TrainSystem:
    import deepspeed_tpu as dst
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    from deepspeed_tpu.parallel.topology import single_device_topology

    c = sized(config, rehearse)
    model = LlamaForCausalLM("7b", max_seq_len=c["seq_len"],
                             **widths(config, rehearse))
    train = config["train"]
    mesh = train.get("tpu", {}).get("mesh")
    engine, _, _, _ = dst.initialize(
        model=model, config=train, rng=seeded_key(seed),
        topology=None if mesh else single_device_topology())
    return TrainSystem("train", model.cfg, engine, c["seq_len"],
                       engine.train_batch_size(), model.cfg.vocab_size,
                       list(devices))


def describe(system: TrainSystem) -> dict:
    return {"kind": system.kind, "layers": system.cfg.num_layers,
            "params": system.cfg.n_params(), "rows": system.rows,
            "seq_len": system.seq_len, "devices": len(system.devices)}
