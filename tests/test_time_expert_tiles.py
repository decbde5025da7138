"""``tools/time_expert_tiles.py``: its routings put the tiles in use that
its docstring says (PERF.md, PR 48), at the three served families' shapes,
and the tool runs end to end on the CPU in interpret mode."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))


@pytest.mark.parametrize("tokens", [256, 384])
@pytest.mark.parametrize("family", ["laguna", "pangu", "smallthinker"])
def test_routings_put_the_tiles_in_use_they_name(family, tokens):
    import time_expert_tiles as tool

    from deepspeed_tpu.moe import held
    n_held, scored, k, _, _, _, told = tool.FAMILIES[family]
    tm = held.row_tile(tokens, tokens * k / scored if told else 0.0)
    # Pangu and Laguna at 256 rows under tiles of 32, every other call 64
    assert tm == (32 if not told and tokens == 256 else 64)
    want = {"one": 1, "even": n_held, "three": n_held + 2}
    for routing in tool.ROUTINGS:
        counts = tool.pair_counts(routing, tokens, k, n_held, scored, tm)
        experts = tool.routing_of(counts, tokens, k, scored)
        assert experts.shape == (tokens, k)
        # a token picks an expert once, as a router's top-k does
        here = np.sort(np.where(experts < n_held, experts, -1), axis=1)
        assert not ((here[:, 1:] == here[:, :-1]) & (here[:, 1:] >= 0)).any()
        np.testing.assert_array_equal(
            [(experts == x).sum() for x in range(n_held)], counts)
        assert tool.tiles_in_use(counts, tm) == want[routing]
        plan = held._plan(jnp.asarray(experts), jnp.ones(tokens, bool), 0,
                          n_held, tm)
        assert int(plan[3][0]) == want[routing]
        assert plan[2].shape[0] == held._rows_bound(tokens * k, n_held,
                                                    tm) // tm
    # what a deployment's router sends: the even share of the step's pairs
    even = tool.pair_counts("even", tokens, k, n_held, scored, tm)
    assert even.sum() == tokens * k * n_held // scored


def test_the_tool_runs_on_the_cpu(tmp_path):
    """End to end in interpret mode at tiny shapes, the tree's own
    ``held.py`` once more as the form beside it: every routing a row, the
    two forms equal on the rows of the tiles in use."""
    out = tmp_path / "tiles.json"
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "time_expert_tiles.py"),
         "--family", "tiny=4,16,2,128,64,silu,0", "--tokens", "72",
         "--calls", "1", "--interpret", "--beside",
         "again=" + os.path.join(ROOT, "deepspeed_tpu", "moe", "held.py"),
         "--out", str(out)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    rows = json.loads(out.read_text())["rows"]
    assert [(r["routing"], r["used"]) for r in rows] == [
        ("one", 1), ("even", 4), ("three", 6)]
    for r in rows:
        assert (r["family"], r["tm"], r["tiles"]) == ("tiny", 32, 9)
        assert set(r["ms"]) == {"here", "again"}
        assert r["max_abs_diff"] == 0.0
