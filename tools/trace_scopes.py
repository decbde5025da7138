#!/usr/bin/env python3
"""What a kept trace of a step program spent where, by the program's own
scopes: the thirty operations of the first device with most self time,
each with its phase and module (``*``: a scope inherited from a
neighbour, no metadata's own), the phase x module matrix in milliseconds a
step (the arithmetic of the benchmark's reader ``trace_scope_share``, for
every scope at once), and how much of each phase rests on inherited scopes:

    python tools/trace_scopes.py <file.xplane.pb> <table.json>

``python -m benchmark.run ... --trace 1 --keep-trace <dir>`` keeps both
files (the table is ``DeepSpeedEngine.step_scope_table()`` as JSON)."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.readers.trace_scope_share import innermost_times  # noqa: E402
from benchmark.trace_reduce import load  # noqa: E402


def by_scope(red, table):
    """``(steps, [(name, ns, phase, module)] most first)`` of the first
    device over the whole steps of the traced window."""
    own, _, _, steps = innermost_times(
        red.devices[min(red.devices)], red.window, table["entry_order"])
    rows = [(name, ns, *table["instructions"].get(name, ("other", "none")))
            for name, ns in own.items()]
    return max(steps, 1), sorted(rows, key=lambda r: -r[1])


def main(trace, table_file, top=30):
    with open(table_file) as f:
        table = json.load(f)
    steps, rows = by_scope(load(trace), table)
    busy = sum(r[1] for r in rows)
    print(f"{steps} whole steps, {busy / steps / 1e6:.3f} ms busy a step"
          + (" (STALE table: only JAX's own markers)" if table["stale"]
             else ""))
    inherited = set(table.get("inherited", ()))
    for name, ns, phase, module in rows[:top]:
        print(f"{ns / steps / 1e6:10.3f} ms {100 * ns / busy:6.2f}%  "
              f"{phase:<15}{module:<7}{name}"
              + ("*" if name in inherited else ""))
    cell = {}
    for _, ns, phase, module in rows:
        cell[phase, module] = cell.get((phase, module), 0) + ns
    phases = sorted({p for p, _ in cell})
    modules = sorted({m for _, m in cell})
    print(f"{'ms a step':<15}" + "".join(f"{m:>10}" for m in modules)
          + f"{'all':>10}{'share':>9}")
    for p in phases:
        line = [cell.get((p, m), 0) for m in modules]
        print(f"{p:<15}" + "".join(f"{ns / steps / 1e6:10.2f}" for ns in line)
              + f"{sum(line) / steps / 1e6:10.2f}"
              + f"{100 * sum(line) / busy:8.2f}%")
    col = [sum(cell.get((p, m), 0) for p in phases) for m in modules]
    print(f"{'all':<15}" + "".join(f"{ns / steps / 1e6:10.2f}" for ns in col)
          + f"{busy / steps / 1e6:10.2f}")
    print(f"{'share':<15}" + "".join(f"{100 * ns / busy:9.2f}%"
                                      for ns in col))
    mine = [r for r in rows if r[0] in inherited]
    print(f"inherited scopes: {len(mine)} instructions with events, "
          f"{sum(r[1] for r in mine) / steps / 1e6:.3f} ms a step, "
          f"{100 * sum(r[1] for r in mine) / busy:.3f}% of busy")
    for p in phases:
        ns = sum(r[1] for r in mine if r[2] == p)
        if ns:
            print(f"  {p:<15}{ns / steps / 1e6:10.3f} ms{100 * ns / busy:8.3f}%"
                  "  " + " ".join([r[0] for r in mine if r[2] == p][:5]))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
