#!/usr/bin/env python3
"""Time a decode segment's convolution (``ops/ssm.py::conv_step`` at one
token a row: ``conv_tail_decode``) alone on the chip at the four
state-holding families' channel counts (PERF.md, PR 55):

    chiprun -- python3 tools/time_conv_tail.py \
        --beside parent=.parent/deepspeed_tpu/ops/ssm.py \
        --out chiprun_out/conv_tail.json

One row of output a (family, rows, sequences a grid step): ms a LAYER, a
timed call being ``--layers-a-call`` of them in one program (a loop over
the pool's layers, the pool donated and carried as a step program carries
it: one call of the kernel alone is shorter than its dispatch), ``--calls``
calls back to back on the host's clock; beside it what the tails' bytes
there and back take at the chip's 819 GB/s (``ms_at_819``), and what the
kernel computed ON THE CHIP (the order of a 32-bit word's halves is the
chip's, not the interpreter's): ``out_equals_host``, its output against the
same sum on the host, every product and sum rounded to float32;
``slots_equal_jnp``, every slot but the scratch slot against the jnp form's;
``jnp_equals_host`` says whether the chip's compiler kept the jnp form to
that arithmetic (it keeps an input's excess precision at some shapes).
``--beside NAME=PATH`` times another ``ssm.py``'s ``conv_step`` on the same
operands (the parent's: its gather, re-layouts, taps and shifted copy,
WITHOUT the copy through its update kernel).  ``--interpret`` with
``--family`` of tiny shapes rehearses it on the CPU.
"""
import argparse
import importlib.util
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import chip_timing

#: channels under the convolution, layers that hold a tail, and whether
#: the rows' inputs come in the pool's dtype (Jamba's projection gives
#: bfloat16; the others' convolution follows a float32 product)
FAMILIES = {"jamba": (5120, 26, 1), "nemotron": (6144, 6, 0),
            "olmo-hybrid": (11520, 3, 0), "ling": (12288, 6, 0)}
HBM_BYTES_A_S = 819e9
TAPS = 4


def many(step, layers: int, n: int, donate: bool = True):
    """``step(pool, layer, *operands) -> (out, pool)`` as ONE program of
    ``n`` steps over the pool's layers in turn, the pool donated (but to a
    step that only reads it)."""
    import jax

    def run(pool, *operands):
        def body(i, carry):
            return step(carry[1], i % layers, *operands)
        return jax.lax.fori_loop(
            1, n, body, step(pool, 0, *operands))
    return jax.jit(run, donate_argnums=(0,) if donate else ())


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--family", action="append", default=[],
                    help="NAME=channels,layers,inputs-in-16-bits (0 / 1) "
                    "(default: the four served)")
    ap.add_argument("--rows", type=int, nargs="+", default=[256])
    ap.add_argument("--step-rows", type=int, nargs="+", default=[0],
                    help="sequences a grid step (0: the rule's own)")
    ap.add_argument("--beside", action="append", default=[],
                    help="NAME=PATH of another ssm.py to time beside")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--layers-a-call", type=int, default=104)
    ap.add_argument("--seed", type=int, default=55)
    ap.add_argument("--interpret", action="store_true",
                    help="rehearse on the CPU (tiny --family shapes)")
    ap.add_argument("--out", default="chiprun_out/conv_tail.json")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops import ssm

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.interpret:
        sys.exit(f"no chip: {device.platform} (use --interpret to rehearse)")
    families = dict(FAMILIES)
    if args.family:
        families = {n: tuple(int(v) for v in rest.split(","))
                    for n, rest in (f.split("=") for f in args.family)}
    beside = {}
    for item in args.beside:
        name, path = item.split("=", 1)
        spec = importlib.util.spec_from_file_location(
            f"deepspeed_tpu.ops._beside_{name}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        beside[name] = module.conv_step

    beat = chip_timing.start_watchdog()
    rule_rows = ssm.CONV_STEP_ROWS
    rng = np.random.default_rng(args.seed)
    results = []
    for name, (c, layers, narrow) in families.items():
        for S in args.rows:
            slots_n = max(S, 8)
            shape = ssm.conv_slot_shape((TAPS - 1) * c)
            pool = jnp.asarray(rng.normal(
                size=(layers, slots_n + 1) + shape), jnp.bfloat16)
            idle = rng.random(S) < 0.03
            slots = jnp.asarray(np.where(
                idle, slots_n, rng.permutation(slots_n)[:S]), jnp.int32)
            operands = (jnp.int32(layers - 1), slots,
                        jnp.asarray(rng.random(S) < 0.02),
                        jnp.asarray(~idle, jnp.int32),
                        jnp.asarray(rng.normal(size=(S, 1, c)),
                                    jnp.bfloat16 if narrow else jnp.float32),
                        jnp.asarray(rng.normal(size=(TAPS, c)), jnp.float32),
                        jnp.asarray(rng.normal(size=(c,)), jnp.float32))
            want_out, want_pool, _ = jax.jit(
                lambda pool, *a: ssm.conv_step(pool, *a, use_kernel=False))(
                    pool, *operands)
            # the same sum on the host, every product and sum rounded to
            # float32 and the input to the pool's dtype first (a compiler
            # that keeps excess precision reads otherwise)
            np_t = np.array(ssm.slot_tails(
                pool[layers - 1, slots], TAPS - 1, c).astype(jnp.float32))
            np_t[np.asarray(operands[2])] = 0
            np_x, np_w, np_b = (np.asarray(a, np.float32) for a in (
                operands[4][:, 0].astype(jnp.bfloat16), *operands[5:]))
            host_out = (np_b + np_x * np_w[3]) + (
                (np_t[:, 0] * np_w[0] + np_t[:, 1] * np_w[1])
                + np_t[:, 2] * np_w[2])
            ms_floor = 2 * int((~idle).sum()) * (TAPS - 1) * c * 2 \
                / HBM_BYTES_A_S * 1e3
            for rb in args.step_rows:
                ssm.CONV_STEP_ROWS = rb or rule_rows
                ssm.conv_tail_decode.clear_cache()
                def step(pool, layer, *a):
                    return ssm.conv_step(pool, layer, *a, use_kernel=True,
                                         interpret=args.interpret)[:2]

                run = many(step, layers, args.layers_a_call)
                state = [jnp.array(pool)]

                def call(*a):
                    out, state[0] = run(state[0], *a)
                    return out

                out, got_pool = jax.jit(step)(jnp.array(pool), *operands)
                ms, _ = chip_timing.ms_a_call(call, *operands[1:],
                                              calls=args.calls, beat=beat)
                row = {"family": name, "channels": c, "rows": S,
                       "step_rows": ssm.conv_step_rows(
                           S, shape[0], c, TAPS, 2),
                       "ms": round(ms / args.layers_a_call, 4),
                       "ms_at_819": round(ms_floor, 4),
                       "out_equals_host": bool(np.array_equal(
                           np.asarray(out)[:, 0], host_out)),
                       "slots_equal_jnp": bool(np.array_equal(
                           np.asarray(got_pool)[:, :-1],
                           np.asarray(want_pool)[:, :-1])),
                       "jnp_equals_host": bool(np.array_equal(
                           np.asarray(want_out)[:, 0], host_out)),
                       "out_equals_jnp": bool(np.array_equal(out, want_out))}
                results.append(row)
                print(json.dumps(row), flush=True)
            for other, conv_step in beside.items():
                old = jnp.asarray(np.asarray(pool.astype(jnp.float32))[
                    :, :, :(TAPS - 1) * c // 128].reshape(
                        layers, slots_n + 1, 8, -1), jnp.bfloat16)
                def step(pool, layer, *a):
                    # its new tails as its update kernel takes them
                    out, tail = conv_step(pool, layer, *a)
                    return (out, tail.reshape(S, 8, -1)), pool

                run = many(step, layers, args.layers_a_call, donate=False)
                ms, _ = chip_timing.ms_a_call(run, old, *operands[1:],
                                              calls=args.calls, beat=beat)
                row = {"family": name, "channels": c, "rows": S,
                       "beside": other,
                       "ms": round(ms / args.layers_a_call, 4)}
                results.append(row)
                print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": device.device_kind, "args": vars(args),
                   "rows": results}, f, indent=1)
    # (the CPU fuses a product into the sum behind it, in the kernel's body
    # as in the jnp form: a rehearsal holds the two to each other)
    exact = "out_equals_jnp" if args.interpret else "out_equals_host"
    print(json.dumps({"ok": all(
        r.get(exact, True) and r.get("slots_equal_jnp", True)
        for r in results), "rows": len(results)}))


if __name__ == "__main__":
    main()
