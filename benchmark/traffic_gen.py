"""The one general traffic generator: a mix is a data file of parameters.

A mix fixes a *set* of sizes and gaps (drawn once from the mix's own
``set_seed``); ``--seed`` only permutes that set and fills the token ids,
so every seed offers the same work in another order and seeds differ no
more than two runs of one seed do.

Distributions (``{"dist": ..., ...}``): ``uniform`` and ``loguniform``
(min, max, inclusive) and ``exponential`` (mean).  Sizes are rounded to
whole tokens.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray          # int32 [prompt_len]
    new_tokens: int
    due_s: float = 0.0          # open loop: offset from the window's start


def _rng(*words: int) -> np.random.Generator:
    return np.random.default_rng([int(w) % (2 ** 63) for w in words])


def draw_set(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` values of one distribution, as floats."""
    dist = spec["dist"]
    if dist == "uniform":
        return rng.uniform(spec["min"], spec["max"], n)
    if dist == "loguniform":
        return np.exp(rng.uniform(math.log(spec["min"]),
                                  math.log(spec["max"]), n))
    if dist == "exponential":
        return rng.exponential(spec["mean"], n)
    raise ValueError(f"unknown distribution {dist!r}")


def _sizes(spec: dict, n: int, set_seed: int, lane: int) -> np.ndarray:
    vals = np.rint(draw_set(spec, n, _rng(set_seed, lane, n))).astype(int)
    if "min" in spec:
        vals = np.clip(vals, int(spec["min"]), int(spec["max"]))
    return vals


def requests(mix: dict, n: int, seed: int, vocab: int,
             first_uid: int = 0) -> List[Request]:
    """``n`` requests of the mix: its fixed set of prompt and output
    lengths, each permuted by ``seed``; token ids uniform from ``seed``."""
    set_seed = int(mix.get("set_seed", 0))
    order = _rng(seed, 1, n, first_uid)
    prompt_lens = order.permutation(_sizes(mix["prompt_len"], n, set_seed, 1))
    new_tokens = order.permutation(_sizes(mix["new_tokens"], n, set_seed, 2))
    ids = _rng(seed, 2, n, first_uid)
    prompts = [ids.integers(0, vocab, int(l), dtype=np.int32)
               for l in prompt_lens]
    return [Request(first_uid + i, prompts[i], int(new_tokens[i]))
            for i in range(n)]


def arrivals_offered(mix: dict, seconds: float) -> int:
    """The size of the mix's set for a stream of ``seconds``."""
    return max(1, math.ceil(float(mix["rate_rps"]) * seconds))


def arrival_times(mix: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times in [0, seconds) of an open loop at ``rate_rps``: the
    mix's fixed set of inter-arrival gaps (``arrivals``: a distribution
    with mean 1, default exponential, i.e. Poisson arrivals), permuted by
    ``seed`` and scaled to the rate."""
    rate = float(mix["rate_rps"])
    n = arrivals_offered(mix, seconds)
    spec = dict(mix.get("arrivals", {"dist": "exponential"}), mean=1.0)
    gaps = draw_set(spec, n, _rng(int(mix.get("set_seed", 0)), 4, n))
    gaps = _rng(seed, 3, n).permutation(gaps) / rate
    due = np.cumsum(gaps) - gaps[0]
    return due[due < seconds]


def open_loop(mix: dict, seconds: float, seed: int, vocab: int,
              first_uid: int = 0) -> List[Request]:
    # the whole set is drawn whatever the seed; the seed's order of gaps
    # decides how many of it fall due before ``seconds``
    due = arrival_times(mix, seconds, seed)
    reqs = requests(mix, arrivals_offered(mix, seconds), seed, vocab,
                    first_uid)[:len(due)]
    for r, t in zip(reqs, due):
        r.due_s = float(t)
    return reqs
