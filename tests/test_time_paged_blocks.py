"""``tools/time_paged_blocks.py``: the tables that split a paged-attention
call's time (PERF.md, PR 41) are what its docstring says, and the tool runs
end to end on the CPU in interpret mode."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))


def test_tables_differ_only_past_a_rows_context():
    import time_paged_blocks as tool
    page, P, group = 8, 8, 4
    ctxs = np.array([1, 20, 32, 33, 64])
    live = -(-ctxs // page)
    t = tool.tables(ctxs, page, P, group, pages=100)
    for s, n in enumerate(live):
        for kind in ("null", "repeat", "real"):
            np.testing.assert_array_equal(t[kind][s, :n], t["real"][s, :n])
        assert (t["null"][s, n:] == 0).all()
        assert (t["real"][s] > 0).all() and len(set(t["real"][s])) == P
        for p in range(n, P):
            # the block the slot's buffer holds already: the same slot of
            # the group before, or the null page in a row's first group
            want = t["repeat"][s, p - group] if p >= group else 0
            assert t["repeat"][s, p] == want


@pytest.mark.parametrize("window", [None, 16], ids=["full", "window"])
def test_the_tool_runs_on_the_cpu(tmp_path, window):
    """End to end in interpret mode, a full layer's call and a window
    layer's (``--window``: a table ``--buckets`` slots wide whatever the
    context): the five columns of every context, the rule inside the call
    (``program``) as correct as the tables as given, and the decode walk's
    rows beside the grid form's."""
    out = tmp_path / "blocks.json"
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "time_paged_blocks.py"),
         "--kv-heads", "4", "--head-dim", "32", "--page", "8", "--rows", "3",
         "--pages", "300", "--buckets", "4", "--contexts", "1", "20",
         "--mix", "3", "30", "--min-heads", "2", "--calls", "1",
         "--walk", "3,1", "--interpret", "--out", str(out)]
        + (["--window", str(window)] if window else []),
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    rows = json.loads(out.read_text())["rows"]
    # the decode walk's rows (PR 45): the rule's own tile, then --walk's
    walks = [r for r in rows if r.get("form") == "walk"]
    rows = [r for r in rows if r.get("form") != "walk"]
    assert [(r["group"], r["sub"]) for r in walks] == [(4, 4), (3, 1)]
    for r in walks:
        assert set(r["ms"]) == {"1", "20", "mix"}
        assert set(r["ms"]["mix"]) == {"walk"}
        assert r["max_abs_diff"] < 2e-2
    assert (rows[0]["heads"], rows[0]["group"]) == (4, 4)   # the rule's own
    assert {(r["heads"], r["group"]) for r in rows} == {
        (h, g) for h in (4, 2) for g in (4, 2, 1)}
    for r in rows:
        assert set(r["ms"]) == {"1", "20", "mix"}
        assert set(r["ms"]["mix"]) == {"null", "repeat", "carry", "real",
                                       "program"}
        assert r["max_abs_diff"] < 2e-2
