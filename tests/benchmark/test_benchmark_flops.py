"""``benchmark/flops.py`` against numbers worked by hand at the two
configurations' shapes."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import flops  # noqa: E402


def config(name):
    return json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                       name + ".json")))


SERVE = config("mistral-7b-serve-8l")
TRAIN = config("mistral-7b-zero3-fsdp4")


def test_parameters():
    # q and o: 4096 x 4096 each; k and v: 4096 x 1024 each; MLP 3 x 4096 x 14336
    assert flops.layer_params(SERVE) == 2 * 16777216 + 2 * 4194304 \
        + 3 * 58720256 == 218103808
    assert flops.head_params(SERVE) == 32000 * 4096 == 131072000
    assert flops.total_params(SERVE) == 8 * 218103808 + 2 * 131072000
    assert flops.matmul_params(TRAIN) == TRAIN["num_hidden_layers"] \
        * 218103808 + 131072000


def test_paged_bytes_and_flops():
    # K and V of one token in one layer: 2 x 8 heads x 128 x 2 B = 4 KB
    assert flops.kv_bytes_per_token_layer(SERVE) == 4096
    assert flops.paged_bytes(SERVE, 1000) == 1000 * 4096 * 8
    # QK^T and PV: 2 x 2 x 32 heads x 128 per context token per layer
    assert flops.paged_decode_flops(SERVE, 1000) == 16384 * 1000 * 8
    # a 128-token prompt under the causal mask: half of 2 matmuls of
    # 2 x 128 x 128 x 128 x 32 heads, 8 layers
    assert flops.paged_prefill_flops(SERVE, 128 * 128) == \
        2 * 2 * 128 * 128 * 128 * 32 // 2 * 8 == 1073741824


def test_causal_flash_and_train_flops():
    # one score-sized matmul, causal: 2 x 2048^2 x 128 x 32 / 2
    unit = 2048 * 2048 * 128 * 32
    assert flops.causal_attention_flops(TRAIN, 2048, 1) == unit
    layers = TRAIN["num_hidden_layers"]
    assert flops.flash_train_flops(TRAIN, 2048, rows=4) == 4 * layers * 7 \
        * unit
    per_token = 6 * (layers * 218103808 + 131072000) \
        + layers * 6 * unit / 2048
    assert flops.train_flops_per_token(TRAIN, 2048) == pytest.approx(
        per_token)
    assert 5.5e9 < per_token / layers * 4 < 6.5e9   # ~6.2 GFLOP at depth 4


def test_paged_attention_roofline_counts_both_kinds_of_row():
    """One traced step: decoding rows with 100,000 context tokens and a
    3,000-token prompt whose prefill ended; kernel time 10 ms."""
    from benchmark.readers import paged_attention_roofline as reader

    class Reduced:
        devices = [0]

        def name_ns(self, device, patterns):
            return 10_000_000

    class Profiler:
        first_step, steps = 1, 1

    class Ctx:
        reduced, profiler, config = Reduced(), Profiler(), SERVE
        peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}

    facts = {"step_decode_context": [7, 100_000, 7],
             "step_prefill_tokens": [7, 3_000, 7],
             "step_prefill_sq": [7, 9_000_000, 7]}
    # bytes: 103,000 tokens x 4 KB x 8 layers = 3.375 GB -> 4.121 ms
    # FLOPs: (16384 x 100,000 + 8192 x 9e6) x 8 = 0.603 TFLOP -> 3.06 ms
    share = reader.read(Ctx, facts, {"patterns": ["^paged_attention"]})
    assert share == pytest.approx(100 * (103_000 * 4096 * 8 / 819e9) / 0.010)
    assert share == pytest.approx(41.21, abs=0.01)
    # a long prompt alone is bound by its FLOPs
    facts = {"step_decode_context": [0, 0], "step_prefill_tokens": [0, 3_000],
             "step_prefill_sq": [0, 9_000_000]}
    assert reader.read(Ctx, facts, {"patterns": []}) == pytest.approx(
        100 * (8192 * 9e6 * 8 / 197e12) / 0.010)
