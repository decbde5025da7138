"""``tools/time_flash_blocks.py`` runs end to end on the CPU in interpret
mode, and its forms answer alike (PERF.md, PR 51)."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("shape,window", [("rows=2,4,2,128,32,32,0", None),
                                          ("band=1,4,1,128,32,32,48", 48)])
def test_the_tool_runs_on_the_cpu(tmp_path, shape, window):
    """Tiny shapes, four blocks a row (the walks written out): the tree's
    kernels with K/V at their own head count, the same with K/V repeated by
    the caller and the tree's own file once more as a form at a path, a row
    each, equal to float32 rounding."""
    out = tmp_path / "flash.json"
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "time_flash_blocks.py"),
         "--shape", shape, "--calls", "1", "--interpret",
         "--form", "tree", "--form", "tree:repeat", "--form",
         "again=" + os.path.join(ROOT, "deepspeed_tpu", "ops",
                                 "flash_attention.py") + ":repeat",
         "--out", str(out)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    rows = json.loads(out.read_text())["rows"]
    assert [r["form"] for r in rows] == ["tree", "tree:repeat",
                                         "again:repeat"]
    for row in rows:
        assert row["fwd_ms"] > 0 and row["fwd_bwd_ms"] > 0
        assert max(row["max_abs_diff"]) < 1e-5, row
