#!/usr/bin/env python3
"""Time ``paged_decode_attention`` alone on the chip under every
``(heads, group)`` that fits ``VMEM_BUDGET``, with ``kernel_blocks``
overridden by hand: the sweep that ``STEP_BYTES`` is set from (PERF.md,
PR 41).

    chiprun -- python3 tools/time_paged_blocks.py --kv-heads 30 \
        --out chiprun_out/blocks.json

One row of output a (page bucket, blocks): ms a call (``--calls`` calls back
to back on the host's clock) at each context, under four tables whose
live slots are the same and whose slots past a row's context hold

* ``null``:   the null page, as the engine's tables do;
* ``repeat``: the page the slot's buffer already holds (the live page
  ``group`` slots back), so that a dead slot fetches nothing: the
  program's own ``fetch_table`` of the ``null`` table, so ``null`` less
  ``repeat`` is what the null page's fetches cost;
* ``carry``:  ``repeat`` carried across rows as well: a dead slot with no
  live one before it in its row keeps the previous row's block (a short
  row's first group).  The program does NOT do this: ``repeat`` less
  ``carry`` is what it would be worth;
* ``real``:   pages of their own, fetched in every group of the bucket.

These four reach the kernel's index maps AS GIVEN (``fetch_table`` is
switched off around the call's lowering); ``program`` is the ``null`` table
through the call as the step programs make it, the rule and its integer
operations inside: it should read ``repeat``'s time.

A context of 1 token is one live group a row and the rest of the bucket
dead: a grid step's fixed cost.  Contexts at a group's edge (512, 1024)
against one page past it (576) split a last group's cost into its fetch
and its arithmetic.  Every output is compared with the first blocks'.

Those rows are the GRID form (``paged_grid_attention``: prompt chunks,
int8 pages, ALiBi).  A decode row's call is the WALK over each row's own
pages (``paged_walk_attention``, PR 45): one ``walk`` row a ``(group, sub)``
of ``--walk`` (the rule's own, ``walk_blocks``, first), timed under the
``null`` table, the only one it can tell from the others (it reads no slot
past a row's range).  ``--grid rule`` times the grid form at the rule's
blocks alone, as the walk's yardstick.

``--window 512 --buckets 16`` times a window layer's call (Laguna's: 72
query heads over 8 KV heads): the table is the window group's, ``--buckets``
slots wide, holding a row's pages from the first one its window still
reaches (at most 10 of the 16 at a window of 512 tokens and pages of 64),
and ``start_pos`` counts from that page, as ``model.py::_forward_hidden`` rebases
it.
"""
import argparse
import functools
import json
import os
import sys
import time
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import chip_timing
from deepspeed_tpu.ops import paged_attention as pa


def tables(ctxs, page, P, group, pages):
    """The four page tables ``[S, P]`` of the module's docstring, for rows
    that hold ``ctxs`` tokens from their first slot on."""
    S = len(ctxs)
    live = -(-np.asarray(ctxs) // page)                     # pages a row
    per_row = pages // S
    own = 1 + (np.arange(S)[:, None] * per_row + np.arange(P)[None]) % pages
    null = np.where(np.arange(P)[None] >= live[:, None], 0, own)
    repeat = np.asarray(pa.fetch_table(jnp.asarray(null, jnp.int32), group))
    # the columns of all rows end to end: the last live slot so far
    columns = null.reshape(-1, group)
    at = np.where(columns != 0, np.arange(len(columns))[:, None], 0)
    carry = np.take_along_axis(columns, np.maximum.accumulate(at, axis=0),
                               axis=0).reshape(S, P)
    return {"null": null, "repeat": repeat, "carry": carry, "real": own}


def held_from(ctxs, page, window, Q):
    """Tokens a window group's table holds for rows of ``ctxs`` tokens
    whose last ``Q`` are the queries: everything from the page the FIRST
    query's window starts in (all of it without a window)."""
    ctxs = np.asarray(ctxs)
    if window is None:
        return ctxs
    base = np.maximum(ctxs - Q - window + 1, 0) // page
    return ctxs - base * page


def candidates(args, rows, P):
    """Blocks that divide the shapes and fit the account, the rule's own
    first."""
    out = [pa.kernel_blocks(rows, args.kv_heads, args.head_dim, args.page,
                            P, 2, 2)]
    for heads in range(args.kv_heads, args.min_heads - 1, -1):
        for group in (8, 4, 2, 1):
            if (args.kv_heads % heads == 0 and P % group == 0
                    and (heads, group) not in out
                    and pa.step_vmem_bytes(
                        heads, group, rows, args.kv_heads, args.head_dim,
                        args.page, 2, 2) <= pa.VMEM_BUDGET):
                out.append((heads, group))
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--kv-heads", type=int, default=30)
    ap.add_argument("--q-per-kv", type=int, default=1)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--page", type=int, default=64)
    ap.add_argument("--rows", type=int, default=256)
    ap.add_argument("--q-len", type=int, default=1)
    ap.add_argument("--pages", type=int, default=8960)
    ap.add_argument("--buckets", type=int, nargs="+", default=[8, 40])
    ap.add_argument("--contexts", type=int, nargs="+",
                    default=[1, 100, 400, 512, 576, 640, 740, 1024, 1300,
                             2100])
    ap.add_argument("--mix", type=int, nargs=2, default=[100, 2200],
                    help="a last column of contexts uniform in this range")
    ap.add_argument("--window", type=int, default=None,
                    help="a window layer's call: tables rebased to the "
                         "window's first page, --buckets slots wide")
    ap.add_argument("--min-heads", type=int, default=5)
    ap.add_argument("--grid", choices=["all", "rule", "none"], default="all",
                    help="the grid form's blocks to time: every one that "
                         "fits, the rule's own, none")
    ap.add_argument("--walk", nargs="*", default=[], metavar="GROUP,SUB",
                    help="tiles of the walk to time beside the rule's own")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=41)
    ap.add_argument("--interpret", action="store_true",
                    help="the CPU rehearsal (tiny shapes)")
    ap.add_argument("--out", default="chiprun_out/paged_blocks.json")
    args = ap.parse_args()

    K, G, D, page, S, Q = (args.kv_heads, args.q_per_kv, args.head_dim,
                           args.page, args.rows, args.q_len)
    rng = np.random.default_rng(args.seed)
    key = jax.random.PRNGKey(args.seed)
    # a pool of distinct pages from one random block, tiled (its values
    # are read, not judged: parity is the tests')
    block = jax.random.normal(key, (1, 257, 2, K, page, D), jnp.bfloat16)
    # (one program, whole blocks: a concatenation or a slice of the result
    # would hold a second pool)
    reps = -(-(args.pages + 1) // 257)
    pool = jax.jit(lambda b: jnp.broadcast_to(
        b[:, None], (1, reps) + b.shape[1:]).reshape(
            (1, reps * 257) + b.shape[2:]))(block)
    q = jax.random.normal(jax.random.fold_in(key, 1), (S, Q, K * G, D),
                          jnp.bfloat16)
    device = jax.devices()[0]
    print(f"device {device.platform} {device.device_kind}; pool "
          f"{pool.nbytes / 1e9:.2f} GB, a page {pool[0, 0].nbytes} B",
          flush=True)

    beat = chip_timing.start_watchdog()
    ms_a_call = functools.partial(chip_timing.ms_a_call, calls=args.calls,
                                  beat=beat)

    def apart(out, ref):
        return float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                     - ref.astype(jnp.float32))))

    results = []
    name = "paged_attention_window" if args.window else "paged_attention"
    for P in args.buckets:
        # a window table holds a window and the pages at its two ends
        # whatever the context
        cap = P * page if args.window is None else max(args.contexts)
        ctx_sets = {str(c): np.full(S, c) for c in args.contexts
                    if Q <= c <= cap}
        lo, hi = max(args.mix[0], Q), min(args.mix[1], cap)
        ctx_sets["mix"] = rng.integers(lo, hi + 1, S)
        first = {}
        grid = candidates(args, Q * G, P)
        for heads, group in {"all": grid, "rule": grid[:1],
                             "none": []}[args.grid]:
            def call(q, kv, table, start):
                return pa.paged_grid_attention(
                    q, kv, 0, table, start, window=args.window,
                    interpret=args.interpret, name=name)
            shapes = (q, pool, jnp.zeros((S, P), jnp.int32),
                      jnp.zeros((S,), jnp.int32))
            t0 = time.monotonic()
            try:
                with mock.patch.object(pa, "kernel_blocks",
                                       lambda *a, **k: (heads, group)):
                    program = jax.jit(call).lower(*shapes)
                    # the tables as given: the rule off.  (Another
                    # function object: jit keeps a function's trace, and
                    # would hand back the one with the rule inside.)
                    with mock.patch.object(pa, "fetch_table",
                                           lambda table, group: table):
                        run = jax.jit(lambda *a: call(*a)).lower(*shapes)
                    assert P <= group or program.as_text() != run.as_text()
                    program, run = program.compile(), run.compile()
            except Exception as e:      # the chip's compiler refused it
                print(f"P={P} ({heads}, {group}): refused: "
                      f"{str(e).splitlines()[0][:200]}", flush=True)
                continue
            beat[0] = time.monotonic()
            row = {"bucket": P, "heads": heads, "group": group,
                   "step_bytes": 2 * heads * group * page * D * 2,
                   "compile_s": round(beat[0] - t0, 3), "ms": {},
                   "max_abs_diff": 0.0}
            for ctx_name, ctxs in ctx_sets.items():
                held = held_from(ctxs, page, args.window, Q)
                start = jnp.asarray(held - Q, jnp.int32)
                kinds = tables(held, page, P, group, args.pages)
                for kind, table in (*kinds.items(),
                                    ("program", kinds["null"])):
                    ms, out = ms_a_call(
                        program if kind == "program" else run, q, pool,
                        jnp.asarray(table, jnp.int32), start)
                    row["ms"].setdefault(ctx_name, {})[kind] = round(ms, 4)
                    if kind == "null":      # one answer whatever the blocks
                        ref = first.setdefault(ctx_name, out)
                    if kind in ("null", "program"):
                        row["max_abs_diff"] = max(row["max_abs_diff"],
                                                  apart(out, ref))
            results.append(row)
            print(json.dumps(row), flush=True)
        # (0, 0): a page too large for the walk's tiles keeps the grid form
        walks = [pa.walk_blocks(G, K, D, page, P, 2, 2)] if Q == 1 else []
        walks += [b for b in (tuple(map(int, w.split(","))) for w in args.walk)
                  if walks and b not in walks]
        for group, sub in (b for b in walks if walks[0][0]):
            t0 = time.monotonic()
            try:
                run = jax.jit(lambda q, kv, table, start: (
                    pa.paged_walk_attention(
                        q, kv, 0, table, start, group=group, sub=sub,
                        sm_scale=float(D) ** -0.5, window=args.window,
                        interpret=args.interpret, name=name))).lower(
                    q, pool, jnp.zeros((S, P), jnp.int32),
                    jnp.zeros((S,), jnp.int32)).compile()
            except Exception as e:      # the chip's compiler refused it
                print(f"P={P} walk ({group}, {sub}): refused: "
                      f"{str(e).splitlines()[0][:200]}", flush=True)
                continue
            beat[0] = time.monotonic()
            row = {"bucket": P, "form": "walk", "group": group, "sub": sub,
                   "tile_bytes": 2 * K * group * page * D * 2,
                   "compile_s": round(beat[0] - t0, 3), "ms": {},
                   "max_abs_diff": 0.0}
            for ctx_name, ctxs in ctx_sets.items():
                held = held_from(ctxs, page, args.window, Q)
                table = tables(held, page, P, 1, args.pages)["null"]
                ms, out = ms_a_call(run, q, pool,
                                    jnp.asarray(table, jnp.int32),
                                    jnp.asarray(held - Q, jnp.int32))
                row["ms"][ctx_name] = {"walk": round(ms, 4)}
                if ctx_name in first:
                    row["max_abs_diff"] = max(row["max_abs_diff"],
                                              apart(out, first[ctx_name]))
            results.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": device.device_kind, "args": vars(args),
                   "rows": results}, f, indent=1)
    print(json.dumps({"ok": True, "rows": len(results)}))


if __name__ == "__main__":
    main()
