"""The reader ``trace_scope_share`` against a reduced trace and a scope
table worked by hand, and the nine metric files that PR 37 added.  CPU
only; the numbers are the arithmetic's, not a device's."""

import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.readers import trace_scope_share as reader  # noqa: E402

import harness_checks  # noqa: E402  (beside this file)

BENCH = os.path.join(ROOT, "benchmark")
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "train.zero3-fsdp4"
NEW = ["train_forward_time_share", "train_recompute_time_share",
       "train_backward_time_share", "train_update_time_share",
       "train_unattributed_time_share", "train_mlp_time_share",
       "train_attn_time_share", "train_head_loss_time_share",
       "idle_ms_per_step.train"]
PHASES = NEW[:5]
#: the shares tile 100%, so a step that sheds overhead RAISES the share of
#: the work ``train_mfu`` counts.  Each share's better direction is the one
#: towards its share of the counted FLOPs (forward 32%, backward 64%, the
#: MLP 75%; recompute, update and unattributed 0): below it ``higher``
BETTER = {"train_forward_time_share": "higher",
          "train_backward_time_share": "higher",
          "train_mlp_time_share": "higher"}


def table(stale=False):
    return {
        "instructions": {
            "cast.1": ["params", "none"],
            "while.2": ["forward", "none"], "fusion.3": ["forward", "mlp"],
            "fusion.4": ["forward", "attn"],
            "while.5": ["backward", "none"],
            "fusion.6": ["recompute", "mlp"],
            "fusion.7": ["backward", "mlp"], "fusion.8": ["backward", "head"],
            "adam.9": ["optimizer", "none"]},
        "inherited": ["fusion.4", "cast.1"],
        "containers": ["while.2", "while.5"],
        "entry_order": ["zero.0", "cast.1", "while.2", "fusion.8", "while.5",
                        "adam.9"],
        "stale": stale}


def step(t0):
    """One device step of 1000 ns from ``t0``: the cast [0, 50); a forward
    ``while`` [50, 350) holding an MLP fusion [60, 200) and an attention
    one [200, 340), so 20 ns of its own; the head's backward [350, 400);
    a backward ``while`` [400, 900) holding a recomputed MLP [400, 550),
    its backward [550, 850) and an instruction the table lacks [850, 890),
    so 10 ns of its own; the optimizer [900, 980); idle [980, 1000)."""
    return [(n, t0 + a, t0 + b) for n, a, b in [
        ("cast.1", 0, 50), ("while.2", 50, 350), ("fusion.3", 60, 200),
        ("fusion.4", 200, 340), ("fusion.8", 350, 400),
        ("while.5", 400, 900), ("fusion.6", 400, 550),
        ("fusion.7", 550, 850), ("copy.99", 850, 890),
        ("adam.9", 900, 980)]]


def reduced(window=(0, 3000)):
    """Three steps from 0, 1000 and 2000: the whole steps of any window
    that holds at least two starts of ``cast.1`` are steps like the one
    above, 980 ns busy of 1000."""
    ops = [e for t0 in (0, 1000, 2000) for e in step(t0)]
    return tr.Reduced({0: ops, 1: []}, [], window)


def ctx(red, programs=None):
    return types.SimpleNamespace(
        reduced=red, profiler=types.SimpleNamespace(keep_dir=""),
        scope_tables=programs if programs is not None
        else {"train.step": table()})


def args_of(name):
    return harness_checks.load(BENCH, "metrics", name)["args"]


WANT = {"train_forward_time_share": 300, "train_recompute_time_share": 150,
        "train_backward_time_share": 50 + 10 + 300,
        "train_update_time_share": 50 + 80,
        "train_unattributed_time_share": 40,
        "train_mlp_time_share": 140 + 150 + 300,
        "train_attn_time_share": 140, "train_head_loss_time_share": 50}


@pytest.mark.parametrize("name", sorted(WANT))
def test_each_share_by_hand(name):
    """Nesting under a ``while`` (its own 20 and 10 ns are the phase's, what
    it holds is each instruction's), an instruction the table lacks
    (``other``), whole steps of a window that opens at a step's start."""
    got = reader.read(ctx(reduced()), {}, args_of(name))
    assert got == pytest.approx(100.0 * WANT[name] / 980)


def test_the_five_phases_tile_the_busy_time():
    c = ctx(reduced((430, 2700)))
    got = [reader.read(c, {}, args_of(n)) for n in PHASES]
    assert sum(got) == pytest.approx(100.0)


@pytest.mark.parametrize("window", [(430, 2700), (0, 2100), (990, 2001)])
def test_a_slice_that_opens_mid_step_reads_whole_steps(window):
    """The window opens inside the first step's backward loop (or closes
    inside the last step): the stretch runs from the first to the last
    start of the instruction that opens the step, here ``cast.1``
    (``zero.0`` leads the entry computation and leaves no event), so the
    share is a whole step's, not weighted by where the slice fell."""
    c = ctx(reduced(window))
    for name in WANT:
        assert reader.read(c, {}, args_of(name)) == pytest.approx(
            100.0 * WANT[name] / 980), name
    events = c.reduced.devices[0]
    lo, hi, steps = reader.whole_steps(events, *window,
                                       table()["entry_order"])
    assert lo % 1000 == 0 and hi % 1000 == 0 and hi - lo == 1000 * steps > 0


def test_a_window_with_one_start_is_read_as_it_is():
    # [1100, 1900) holds no start of the opener: the window itself, whose
    # first event is clipped
    red = reduced((1100, 1900))
    assert reader.whole_steps(red.devices[0], 1100, 1900,
                              table()["entry_order"]) == (1100, 1900, 0)
    got = reader.read(ctx(red), {}, args_of("train_forward_time_share"))
    assert got == pytest.approx(100.0 * 250 / 800)


def test_of_the_window_counts_the_idle_time_in():
    got = reader.read(ctx(reduced()), {}, dict(
        args_of("train_update_time_share"), of="window"))
    assert got == pytest.approx(100.0 * 130 / 1000)


def test_a_stale_table_reads_only_what_jax_marks():
    """An executable cached by a tree without the program's scopes: its
    instruction names match the trace, its ``op_name`` paths lack every
    scope of the program, so only forward, recompute and backward (JAX's own
    markers) are read."""
    c = ctx(reduced(), {"train.step": table(stale=True)})
    read = {n: reader.read(c, {}, args_of(n)) for n in WANT}
    assert {n for n, v in read.items() if v is not None} == {
        "train_forward_time_share", "train_recompute_time_share",
        "train_backward_time_share"}
    assert read["train_recompute_time_share"] == pytest.approx(
        100.0 * 150 / 980)


def test_nothing_to_read():
    args = args_of("train_forward_time_share")
    # no table (a program that exports none), no trace, no device
    assert reader.read(ctx(reduced(), {"train.step": None}), {}, args) is None
    assert reader.read(ctx(None), {}, args) is None
    assert reader.read(ctx(tr.Reduced({}, [], (0, 10))), {}, args) is None
    # nothing ran in the window
    assert reader.read(ctx(tr.Reduced({0: []}, [], (0, 10))), {},
                       args) is None


def test_the_table_is_asked_for_once_and_kept_beside_a_kept_trace(
        tmp_path, capsys, monkeypatch):
    telemetry = pytest.importorskip("deepspeed_tpu.telemetry")
    if not hasattr(telemetry, "program_table"):
        pytest.skip("a program from before it exported a table")
    calls = []
    monkeypatch.setattr(telemetry, "program_table",
                        lambda name: calls.append(name) or table())
    c = types.SimpleNamespace(
        reduced=reduced(),
        profiler=types.SimpleNamespace(keep_dir=str(tmp_path / "kept")))
    for name in WANT:
        assert reader.read(c, {}, args_of(name)) is not None
    assert calls == ["train.step"]
    out = capsys.readouterr().out.splitlines()
    line = next(l for l in out if l.startswith("scope_table: "))
    said = json.loads(line.partition(": ")[2])
    assert said["found"] and not said["stale"] and said["instructions"] == 9
    # ... and how much of the busy time rests on inherited scopes, once
    lines = [l for l in out if l.startswith("scope_inherited: ")]
    assert len(lines) == 1
    said = json.loads(lines[0].partition(": ")[2])
    assert said["whole_steps"] == 2 and said["instructions"] == 2
    assert said["share_of_busy_pct"] == pytest.approx(
        100.0 * (140 + 50) / 980, abs=1e-3)
    with open(tmp_path / "kept" / "train.step.scopes.json") as f:
        assert json.load(f) == table()


def test_the_tool_prints_the_matrix(capsys, tmp_path, monkeypatch):
    # the program's tool beside the reader (not under the benchmark's paths)
    tool = pytest.importorskip("tools.trace_scopes")
    steps, rows = tool.by_scope(reduced((430, 2700)), table())
    assert steps == 1 and sum(r[1] for r in rows) == 980
    assert rows[0] == ("fusion.7", 300, "backward", "mlp")
    assert ("copy.99", 40, "other", "none") in rows


# ---------------------------------------------------------------------------
# the files
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", NEW)
def test_the_metric_file_resolves(name):
    how = harness_checks.load(BENCH, "metrics", name)
    entry = next(m for m in SPEC["per_layer"] if m["name"] == name)
    assert how["drivers"] == ["train_steps"]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == how["moves"] == "train_tok_s_chip"
    assert entry["better"] == how["better"] == BETTER.get(name, "lower")
    assert os.path.exists(os.path.join(BENCH, "readers",
                                       how["reader"] + ".py"))
    if how["reader"] == "trace_scope_share":
        assert how["args"]["program"] == "train.step"
        assert set(how["args"].get("phase", [])) <= set(program_phases())
        from deepspeed_tpu.models.transformer import MODULE_SCOPES
        assert set(how["args"].get("module", [])) <= set(MODULE_SCOPES)


def program_phases():
    """Every phase the program's table can name: the engine's own scopes,
    what JAX's markers tell apart, and what nothing names."""
    from deepspeed_tpu.runtime.engine import TRAIN_SCOPES
    from deepspeed_tpu.telemetry import program_scopes
    return ([p for p in TRAIN_SCOPES.values() if p]
            + list(program_scopes.MARKED) + [program_scopes.NOBODY[0]])


def test_the_phase_metrics_name_every_phase_once():
    named = [p for n in PHASES for p in args_of(n)["phase"]]
    assert sorted(named) == sorted(program_phases())


def test_the_entries_are_appended_and_the_checks_pass():
    # after everything that was there, in one run (a later PR, or the
    # second-family fixture, appends after them)
    names = [m["name"] for m in SPEC["per_layer"]]
    first = names.index(NEW[0])
    assert first >= 56 and names[first:first + len(NEW)] == NEW
    harness_checks.check_metrics(SPEC, BENCH)


def test_the_rehearsal_of_the_train_cell_prints_no_device_number():
    """``--rehearse --trace 1`` on the CPU: the nine metrics find no device
    in the trace and are left out; what is printed is a count or ``null``,
    and no breakdown."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [ROOT] + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    run = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--rehearse", "--seed", str(2 ** 31 + 37), "--seconds", "1",
         "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-2000:]
    result = json.loads(run.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    units = {m["name"]: m["unit"] for m in SPEC["per_layer"]
             if CELL in m.get("workloads", [])}
    assert set(NEW) <= set(units)
    assert result["metrics"] and set(result["metrics"]) <= set(units)
    assert not set(result["metrics"]) & set(NEW)
    for name, m in result["metrics"].items():
        if units[name] not in ("count", "tokens"):
            assert m["value"] is None, name
    assert "breakdown" not in result
    assert "busy_s" not in result["device"]
