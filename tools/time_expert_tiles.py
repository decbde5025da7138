#!/usr/bin/env python3
"""Time the held experts' kernel (``moe/held.py::grouped_expert_ffn``,
``moe_expert_ffn``) alone on the chip at the three served families' shapes
(PERF.md, PR 48):

    chiprun -- python3 tools/time_expert_tiles.py \
        --beside parent=.parent/deepspeed_tpu/moe/held.py \
        --out chiprun_out/expert_tiles.json

One row of output a (family, tokens, routing): ms a call, ``--calls`` calls
back to back on the host's clock.  The plan (``plan_rows``) and the gather
of the rows are made OUTSIDE the timed call: the call is the kernel's
custom call and nothing else.  The routings differ in the tiles in use,
which is what the kernel's walk follows:

* ``one``:   one tile in use: a tile's pairs at one held expert, every
  other pair at an expert held elsewhere;
* ``even``:  one tile an expert: every held expert sees its even share of
  the step's pairs (``tokens x k / scored``), what a deployment's router
  sends;
* ``three``: ``even`` with one expert at two tiles and a pair, so three
  tiles of one expert follow each other and stream its weights again.

``--beside NAME=PATH`` times another ``held.py`` (the parent's, while both
forms exist) on the same operands and compares the rows of the tiles in
use.  Beside each time stands what the weights of the tiles in use take at
the chip's 819 GB/s (``ms_at_819``): a tile streams its expert's three
matrices whole.  ``--interpret`` with ``--family`` of tiny shapes rehearses
it on the CPU.
"""
import argparse
import functools
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import chip_timing

#: held experts, experts scored, experts a token, expert width, hidden
#: size, the gate's activation, and whether the family's caller tells
#: ``row_tile`` how many experts were scored (``model.py::_route``)
FAMILIES = {
    "pangu": (16, 256, 8, 2048, 7680, "silu", False),
    "laguna": (16, 256, 10, 1024, 3072, "silu", False),
    "smallthinker": (64, 64, 6, 768, 2560, "relu", True),
}
ROUTINGS = ("one", "even", "three")
HBM_BYTES_A_S = 819e9


def pair_counts(routing: str, tokens: int, k: int, held: int, scored: int,
                tm: int) -> np.ndarray:
    """Pairs each held expert sees under ``routing``."""
    even = max(tokens * k // scored, 1)
    assert even <= tm, (even, tm)
    counts = np.full(held, even)
    if routing == "one":
        counts[:] = 0
        counts[0] = min(tm, tokens)
    elif routing == "three":
        # the other experts give way where every pair is here already
        counts[:] = min(even, (tokens * k - 2 * tm - 1) // (held - 1))
        counts[held // 2] = 2 * tm + 1
    assert counts.max() <= tokens and counts.sum() <= tokens * k
    return counts


def routing_of(counts: np.ndarray, tokens: int, k: int,
               elsewhere: int) -> np.ndarray:
    """Experts ``[tokens, k]`` that send ``counts[x]`` pairs to held expert
    ``x`` and every other pair to ``elsewhere`` (an expert no chip of this
    call holds).  Pairs are dealt token by token, so an expert with at most
    ``tokens`` pairs never has a token twice."""
    flat = np.full(tokens * k, elsewhere, np.int32)
    flat[:counts.sum()] = np.repeat(np.arange(len(counts)), counts)
    return flat.reshape(k, tokens).T.copy()


def tiles_in_use(counts: np.ndarray, tm: int) -> int:
    return int(np.sum(-(-counts // tm)))


def load_held(name: str, path: str):
    """A ``held.py`` at ``path`` as a module of this package (its relative
    imports are this tree's)."""
    spec = importlib.util.spec_from_file_location(
        "deepspeed_tpu.moe._held_beside_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--family", action="append", default=[],
                    metavar="NAME[=HELD,SCORED,K,F,E,ACT,TOLD]",
                    help="a served family, or one of other shapes (the CPU "
                         "rehearsal); default: the three served")
    ap.add_argument("--tokens", type=int, nargs="+", default=[256, 384])
    ap.add_argument("--routings", nargs="+", default=list(ROUTINGS),
                    choices=ROUTINGS)
    ap.add_argument("--beside", action="append", default=[],
                    metavar="NAME=PATH", help="another held.py to time")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--calls", type=int, default=100)
    ap.add_argument("--seed", type=int, default=48)
    ap.add_argument("--interpret", action="store_true",
                    help="the CPU rehearsal (tiny shapes)")
    ap.add_argument("--out", default="chiprun_out/expert_tiles.json")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.moe import held as here

    families = {}
    for spec in args.family or sorted(FAMILIES):
        name, _, shape = spec.partition("=")
        if shape:
            *ints, act, told = shape.split(",")
            families[name] = (*map(int, ints), act, told == "1")
        else:
            families[name] = FAMILIES[name]
    forms = {"here": here}
    for spec in args.beside:
        name, path = spec.split("=", 1)
        forms[name] = load_held(name, path)
    device = jax.devices()[0]
    print(f"device {device.platform} {device.device_kind}", flush=True)
    dtype = jnp.float32 if args.interpret else jnp.bfloat16

    beat = chip_timing.start_watchdog()
    ms_a_call = functools.partial(chip_timing.ms_a_call, calls=args.calls,
                                  beat=beat)

    results = []
    for family, (held, scored, k, F, e, act, told) in families.items():
        key = jax.random.PRNGKey(args.seed)
        stack = [jax.random.normal(jax.random.fold_in(key, n),
                                   (args.layers, held, F, e), dtype) * 0.02
                 for n in range(3)]
        layer = jnp.int32(args.layers - 1)
        for tokens in args.tokens:
            x = jax.random.normal(jax.random.fold_in(key, tokens),
                                  (tokens, e), dtype)
            tm = here.row_tile(tokens, tokens * k / scored if told else 0.0)
            for routing in args.routings:
                counts = pair_counts(routing, tokens, k, held, scored, tm)
                experts = jnp.asarray(routing_of(counts, tokens, k, scored))
                row_token, _, tile_expert, used, seen = jax.jit(
                    lambda ex: here._plan(ex, jnp.ones(tokens, bool), 0,
                                          held, tm))(experts)
                assert int(used[0]) == tiles_in_use(counts, tm)
                assert np.array_equal(np.asarray(seen), counts)
                x_rows = x[row_token]
                live = int(used[0]) * tm
                weights_bytes = int(used[0]) * 3 * F * e * x.dtype.itemsize
                row = {"family": family, "tokens": tokens, "routing": routing,
                       "tm": tm, "tiles": int(tile_expert.shape[0]),
                       "used": int(used[0]),
                       "sets": here.ring_sets(here.width_slice(F), e,
                                              x.dtype.itemsize),
                       "ms_at_819": round(
                           weights_bytes / HBM_BYTES_A_S * 1e3, 4),
                       "ms": {}, "compile_s": {}, "max_abs_diff": 0.0}
                first = None
                for name, form in forms.items():
                    t0 = time.monotonic()
                    run = jax.jit(lambda xr, te, u, l, wg, wu, wd, f=form: (
                        f.grouped_expert_ffn(
                            xr, te, u, l, wg, wu, wd, tm=tm, act=act,
                            interpret=args.interpret))).lower(
                        x_rows, tile_expert, used, layer, *stack).compile()
                    beat[0] = time.monotonic()
                    row["compile_s"][name] = round(beat[0] - t0, 3)
                    ms, out = ms_a_call(run, x_rows, tile_expert, used,
                                        layer, *stack)
                    row["ms"][name] = round(ms, 4)
                    out = np.asarray(out[:live], np.float32)
                    assert np.isfinite(out).all(), (family, routing, name)
                    if first is None:
                        first = out
                    row["max_abs_diff"] = max(
                        row["max_abs_diff"],
                        float(np.max(np.abs(out - first), initial=0.0)))
                results.append(row)
                print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": device.device_kind, "args": vars(args),
                   "rows": results}, f, indent=1)
    print(json.dumps({"ok": True, "rows": len(results)}))


if __name__ == "__main__":
    main()
