#!/usr/bin/env python3
"""Time the flash attention kernels alone on the chip, one form against
another on the same operands: the table that says which of a change's moves
pays before a cell is run (PERF.md, PR 51).

    chiprun -- python3 tools/time_flash_blocks.py --form tree \
        --form parent=.parent/deepspeed_tpu/ops/flash_attention.py:repeat \
        --out chiprun_out/flash_blocks.json

One row of output a (shape, form): ms a call, ``--calls`` calls back to back
on the host's clock, of the forward alone (``fwd``) and of the forward with
its backward (``fwd_bwd``: ``jax.vjp`` under a random cotangent; ``bwd`` is
the difference).  The shapes are the train cell's call a chip (4 rows of
2,048 tokens, 32 query / 8 KV heads of 128, blocks of 512, a window of 4,096
that cannot bind), the same under a window that binds (1,024) and serving's
fresh prefill (one block of 128 tokens).

A form is ``NAME[=PATH][:repeat]``: this tree's ``ops/flash_attention.py``
or the one at ``PATH`` (the parent's, or a variant under trial, while both
exist); ``repeat``: K and V repeated to the query heads before the call and
dK / dV summed over a group by autodiff behind it, which is how every caller
reached the kernels before they took K/V at their own head count.  The
default forms are this tree's two.  Every form's outputs are compared with
the first form's.  ``--interpret`` with ``--shape`` of tiny sizes rehearses
it on the CPU.
"""
import argparse
import functools
import importlib
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_timing

#: rows, query heads, KV heads, tokens, head size, block, window
SHAPES = {
    "cell": (4, 32, 8, 2048, 128, 512, 4096),
    "window1024": (4, 32, 8, 2048, 128, 512, 1024),
    "one_block": (64, 32, 8, 128, 128, 512, 4096),
}


def load_form(spec):
    """(name, module, repeat) of a form's spec; a module at a path is
    loaded as one of this package (its relative imports are this tree's)."""
    head, *flags = spec.split(":")
    name, _, path = head.partition("=")
    if path:
        found = importlib.util.spec_from_file_location(
            "deepspeed_tpu.ops._flash_beside_" + name, path)
        module = importlib.util.module_from_spec(found)
        found.loader.exec_module(module)
    else:
        module = importlib.import_module("deepspeed_tpu.ops.flash_attention")
    assert set(flags) <= {"repeat"}, spec
    return ":".join([name, *flags]), module, "repeat" in flags


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", action="append", default=[],
                    metavar="NAME[=ROWS,H,K,S,D,BLOCK,WINDOW]",
                    help="a shape of SHAPES, or one of its own (WINDOW 0: "
                         "none); default: all of SHAPES")
    ap.add_argument("--form", action="append", default=[],
                    metavar="NAME[=PATH][:repeat]")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=51)
    ap.add_argument("--interpret", action="store_true",
                    help="the CPU rehearsal (tiny shapes)")
    ap.add_argument("--out", default="chiprun_out/flash_blocks.json")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    shapes = {}
    for spec in args.shape or sorted(SHAPES):
        name, _, sizes = spec.partition("=")
        shapes[name] = tuple(map(int, sizes.split(","))) if sizes \
            else SHAPES[name]
    forms = [load_form(spec) for spec in args.form or ("tree", "tree:repeat")]
    device = jax.devices()[0]
    print(f"device {device.platform} {device.device_kind}", flush=True)
    dtype = jnp.float32 if args.interpret else jnp.bfloat16

    beat = chip_timing.start_watchdog()
    ms_a_call = functools.partial(chip_timing.ms_a_call, calls=args.calls,
                                  beat=beat)

    def apart(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))

    results = []
    for shape, (B, H, K, S, D, block, window) in shapes.items():
        key = jax.random.PRNGKey(args.seed)
        q, g = (jax.random.normal(jax.random.fold_in(key, n), (B, H, S, D),
                                  dtype) for n in (0, 3))
        k, v = (jax.random.normal(jax.random.fold_in(key, n), (B, K, S, D),
                                  dtype) for n in (1, 2))
        first = None
        for name, module, repeat in forms:
            def attend(q, k, v, module=module, repeat=repeat):
                if repeat:
                    k, v = (jnp.repeat(x, H // K, axis=1) for x in (k, v))
                return module.flash_attention(
                    q, k, v, causal=True, block_q=block, block_k=block,
                    window=window or None, interpret=args.interpret)

            def backward(q, k, v, g, attend=attend):
                return jax.vjp(attend, q, k, v)[1](g)

            t0 = time.monotonic()
            try:
                fwd = jax.jit(attend).lower(q, k, v).compile()
                bwd = jax.jit(backward).lower(q, k, v, g).compile()
            except Exception as e:      # the chip's compiler refused it
                print(f"{shape} {name}: refused: "
                      f"{str(e).splitlines()[0][:300]}", flush=True)
                continue
            beat[0] = time.monotonic()
            row = {"shape": shape, "form": name,
                   "compile_s": round(beat[0] - t0, 3)}
            row["fwd_ms"], out = ms_a_call(fwd, q, k, v)
            row["fwd_bwd_ms"], grads = ms_a_call(bwd, q, k, v, g)
            row["bwd_ms"] = row["fwd_bwd_ms"] - row["fwd_ms"]
            first = first or (out, *grads)
            row["max_abs_diff"] = [apart(a, b)
                                   for a, b in zip((out, *grads), first)]
            results.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": device.device_kind, "args": vars(args),
                   "rows": results}, f, indent=1)
    print(json.dumps({"ok": True, "rows": len(results)}))


if __name__ == "__main__":
    main()
