"""``tools/time_conv_tail.py``: the tool runs end to end on the CPU in
interpret mode (PERF.md, PR 55), and its families are the four served
cells' channel counts in the pool's own layout."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))


def test_the_families_are_the_served_cells():
    import time_conv_tail as tool

    from deepspeed_tpu.ops.ssm import conv_slot_shape
    shapes = {name: conv_slot_shape((tool.TAPS - 1) * c)
              for name, (c, _, _) in tool.FAMILIES.items()}
    assert shapes == {"jamba": (120, 128), "nemotron": (144, 128),
                      "olmo-hybrid": (272, 128), "ling": (288, 128)}


def test_the_tool_runs_on_the_cpu(tmp_path):
    """Interpret mode at tiny shapes, inputs in 32 and in 16 bits: the
    kernel's output and slots equal the jnp form's (the verdict a
    rehearsal gives; on the chip it is the host's float32 sum)."""
    out = tmp_path / "conv_tail.json"
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "time_conv_tail.py"),
         "--family", "wide=256,2,0", "--family", "narrow=256,2,1",
         "--rows", "16", "--calls", "1", "--layers-a-call", "2",
         "--interpret", "--out", str(out)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    assert json.loads(done.stdout.splitlines()[-1]) == {"ok": True,
                                                        "rows": 2}
    rows = json.load(open(out))["rows"]
    assert [r["family"] for r in rows] == ["wide", "narrow"]
    assert all(r["out_equals_jnp"] and r["slots_equal_jnp"] for r in rows)
