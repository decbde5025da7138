"""The benchmark's own files hang together, and its clocks measure what
PERF.md says they measure.  CPU only; nothing here yields a device number."""

import collections
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import serving, traffic_gen  # noqa: E402
from benchmark.drivers import (serve_closed_loop, serve_open_loop,  # noqa: E402
                               train_steps)

BENCH = os.path.join(ROOT, "benchmark")
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load(kind, name):
    return json.load(open(os.path.join(BENCH, kind, name + ".json")))


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_resolves_to_files_that_parse(cell):
    config, traffic = load("configs", cell["config"]), load("traffic",
                                                            cell["traffic"])
    assert os.path.exists(os.path.join(
        BENCH, "builders", config["builder"] + ".py"))
    assert os.path.exists(os.path.join(
        BENCH, "drivers", traffic["driver"] + ".py"))
    entry = next(c for c in SPEC["configs"] if c["name"] == cell["config"])
    assert entry["file"] == f"benchmark/configs/{cell['config']}.json"
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    # every width is the published Mistral-7B-v0.1 one
    assert [config[k] for k in (
        "hidden_size", "intermediate_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "sliding_window", "vocab_size")
        ] == [4096, 14336, 32, 8, 128, 4096, 32000]
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200


def test_names_units_and_moves():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and all(0 < m["bound"] <= 0.1
                                    for m in e2e.values())
    cells = [c["name"] for c in SPEC["workloads"]]
    names = cells + list(e2e) + [m["name"] for m in SPEC["per_layer"]] \
        + [c["name"] for c in SPEC["configs"]]
    assert len(set(names)) == len(names)
    assert all(NAME.match(n) for n in names)

    def reported(metric):
        return set(metric.get("workloads", cells))

    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in SPEC["per_layer"]:
        how = load("metrics", m["name"])
        assert (how["unit"], how["layer"], how["moves"]) == (
            m["unit"], m["layer"], m["moves"]), m["name"]
        assert os.path.exists(os.path.join(
            BENCH, "readers", how["reader"] + ".py"))
        assert reported(m) <= reported(e2e[m["moves"]]), m["name"]
        # a metric lists the cells whose driver it is written for
        for cell in SPEC["workloads"]:
            driver = load("traffic", cell["traffic"])["driver"]
            assert (cell["name"] in reported(m)) == (
                driver in how["drivers"]), (m["name"], cell["name"])
    for cell in cells:          # set-up, one more end-to-end, one per-layer
        assert sum(cell in reported(m) for m in SPEC["end_to_end"]) >= 2
        assert any(cell in reported(m) for m in SPEC["per_layer"])


@pytest.mark.parametrize("mix", ["short-closed64"])
def test_generators_follow_the_seed(mix):
    spec = dict(load("traffic", mix), rate_rps=20.0)
    a, b, c = (traffic_gen.open_loop(spec, 5.0, s, 32000)
               for s in (2 ** 31 + 9, 2 ** 31 + 9, 5))
    assert len(a) == len(b) > 50 and abs(len(a) - len(c)) <= 10
    assert all(x.due_s == y.due_s and x.new_tokens == y.new_tokens
               and np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert any(len(x.prompt) != len(y.prompt) for x, y in zip(a, c))

    # another seed offers the same set of sizes and gaps in another order
    # (the order decides how many fall due before the end, so the streams
    # may differ by the few requests at the cut)
    def apart(f):
        ca, cc = (collections.Counter(f(r)) for r in (a, c))
        return sum(((ca - cc) + (cc - ca)).values())
    cut = 2 * (100 - min(len(a), len(c)))
    assert apart(lambda r: [len(x.prompt) for x in r]) <= cut
    assert apart(lambda r: [x.new_tokens for x in r]) <= cut
    assert apart(lambda r: np.round(np.diff([x.due_s for x in r]), 6)) \
        <= cut + 4
    lo, hi = spec["prompt_len"]["min"], spec["prompt_len"]["max"]
    assert all(lo <= len(x.prompt) <= hi for x in a)


class StalledScheduler:
    """Delivers one token per live request per step, but its first step
    stalls for 0.5 s of the fake clock."""

    def __init__(self, clock):
        self.clock, self.live, self.steps = clock, {}, 0
        self.last_step_scheduled = 0

    def submit(self, uid, prompt, params):
        self.live[uid] = params.max_new_tokens

    @property
    def has_work(self):
        return bool(self.live)

    def step(self, on_token=None):
        self.clock.now += 0.5 if self.steps == 0 else 0.01
        self.steps += 1
        self.last_step_scheduled = len(self.live)
        for uid in list(self.live):
            on_token(uid, 7)
            self.live[uid] -= 1
            if not self.live[uid]:
                del self.live[uid]


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def fake_system(clock):
    """A scheduler on a fake clock behind an engine whose step cache the
    test can grow (``System.engine.keys``)."""
    class Manager:
        def check_invariants(self):
            pass

    class Engine:
        free_blocks = 10
        state_manager = Manager()
        keys = [("a",)]

        def compiled_keys(self, dispatched_only=True):
            return list(self.keys)

        def precompile_keys(self, keys):
            return sum(1 for k in keys if tuple(k)[0] != "unknown")

    class System:
        vocab, probe, engine, num_pages = 100, {"ok": True}, Engine(), 10
        sched = StalledScheduler(clock)
    return System


class Marks:
    """What ``serving.WindowMarks`` reads, without a profiler."""

    def __init__(self, system, loop):
        self.system, self.loop, self.at = system, loop, {}

    def read(self, tag):
        self.at["counts" + tag] = {"hits": 0, "misses": 0}
        self.at["programs" + tag] = set(self.system.engine.compiled_keys())
        self.at["live" + tag] = self.loop.live


def test_open_loop_times_from_the_due_time(monkeypatch):
    clock = FakeClock()
    system = fake_system(clock)
    monkeypatch.setattr(serve_open_loop.time, "sleep",
                        lambda s: setattr(clock, "now", clock.now + s))
    reqs = [traffic_gen.Request(i, np.zeros(4, np.int32), 3, due)
            for i, due in enumerate((0.0, 0.1, 0.2))]
    loop = serving.ServeLoop(system, clock=clock)
    t0 = serve_open_loop.serve_stream(loop, reqs, seconds=1.0, drain_s=1.0)
    facts = serving.check_and_reduce(loop, t0, 1.0, [0, 1, 2])
    assert facts["attempted"] == 3 and facts["failed"] == 0
    assert facts["correct"]
    # request 1 was due at 0.1 s, submitted only when the stalled step
    # returned at 0.5 s, and saw its first token a step later at 0.51 s
    late = sorted(facts["gen_late_ms"])
    assert late[0] == pytest.approx(0.0) and late[1] == pytest.approx(300.0)
    assert late[2] == pytest.approx(400.0)
    ttft = sorted(facts["ttft_ms"])
    assert ttft == pytest.approx([310.0, 410.0, 500.0])
    assert facts["itl_p95_ms"] == pytest.approx(10.0)
    # all the work of the window: 3 prompts of 4 tokens + 3 x 3 generated
    assert facts["serve_tok_s"] == pytest.approx(21.0)
    # each prompt's prefill is charged to the step that delivered its
    # first token: the stalled step carried the first prompt alone
    assert loop.step_prefill_tokens[0] == 4 and loop.step_prefill_sq[0] == 16
    assert sum(loop.step_prefill_tokens) == 12


def test_rehearsal_waits_until_no_program_forms():
    clock = FakeClock()
    system = fake_system(clock)
    loop = serving.ServeLoop(system, clock=clock)
    spec = {"min_seconds": 1.0, "quiet_steps": 3, "max_seconds": 10.0}
    warm = serving.Rehearsal(system, loop, spec)
    loop.step_wall_ms = [0.0] * 5
    assert not warm.ready(0.5)            # quiet, but too early
    system.engine.keys = [("a",), ("b",)]
    assert not warm.ready(2.0)            # a program formed at step 5
    loop.step_wall_ms = [0.0] * 7
    assert not warm.ready(2.1)            # 2 quiet steps
    loop.step_wall_ms = [0.0] * 8
    assert warm.ready(2.2)
    assert warm.report == {"seconds": 2.2, "steps": 8, "program_events": 1}
    with pytest.raises(SystemExit, match="still forming"):
        warm.ready(10.5)


def test_hints_are_a_head_start_not_a_requirement(tmp_path, monkeypatch):
    system = fake_system(FakeClock())
    assert serving.warm_hints(system, None)["listed"] == 0
    hints = os.path.join(BENCH, "hints", "short-closed64.json")
    listed = len(json.load(open(hints))["keys"])
    got = serving.warm_hints(system, "short-closed64")
    assert (got["listed"], got["compiled"], got["skipped"]) == (
        listed, listed, 0)
    # a key this build cannot form is counted, and stops nothing
    monkeypatch.setattr(system.engine, "precompile_keys",
                        lambda keys: 0, raising=False)
    got = serving.warm_hints(system, "short-closed64")
    assert (got["compiled"], got["skipped"]) == (0, listed)


@pytest.mark.parametrize("forms_in_window", [False, True])
def test_closed_loop_window_and_compile_in_window(forms_in_window):
    """The window opens when every caller is in flight and the rehearsal is
    quiet; a program formed inside it makes the run not ``correct``."""
    clock = FakeClock()
    system = fake_system(clock)
    mix = dict(load("traffic", "short-closed64"), clients=3, set_size=3,
               ramp_per_step=1)
    loop = serving.ServeLoop(system, clock=clock)
    supply = serve_closed_loop.Supply(mix, 5, system.vocab)
    marks = Marks(system, loop)
    warm = serving.Rehearsal(system, loop, {
        "min_seconds": 0.0, "quiet_steps": 4, "max_seconds": 60.0})
    opened = {}

    def at_open():
        opened["live"], opened["steps"] = loop.live, system.sched.steps
        marks.read("0")
        if forms_in_window:
            system.engine.keys = system.engine.keys + [("late",)]

    t0, sent = serve_closed_loop.serve_clients(
        loop, supply, 3, 1, warm.ready, seconds=1.0, drain_s=5.0,
        at_open=at_open, at_close=lambda: marks.read("1"))
    assert opened == {"live": 3, "steps": 4}
    assert sent and all(loop.stamps[u].submitted >= t0 for u in sent)
    facts = serving.finish(loop, system, marks, {}, t0, 1.0, sent)
    assert facts["failed"] == 0 and facts["attempted"] == len(sent)
    assert facts["compiles_in_window"] == int(forms_in_window)
    assert facts["correct"] == (not forms_in_window)


def test_open_loop_rehearsal_then_window(monkeypatch):
    """The rehearsal stream is served unmeasured; the measured stream's due
    times count from the window's first instant."""
    clock = FakeClock()
    system = fake_system(clock)
    system.sched.steps = 1            # no stalled first step
    monkeypatch.setattr(serve_open_loop.time, "sleep",
                        lambda s: setattr(clock, "now", clock.now + s))
    warm_reqs = [traffic_gen.Request(1000 + i, np.zeros(4, np.int32), 50,
                                     0.01 * i) for i in range(5)]
    reqs = [traffic_gen.Request(i, np.zeros(4, np.int32), 3, 0.1 * i)
            for i in range(4)]
    loop = serving.ServeLoop(system, clock=clock)
    warm = serving.Rehearsal(system, loop, {
        "min_seconds": 0.2, "quiet_steps": 5, "max_seconds": 60.0})
    t0 = serve_open_loop.serve_stream(loop, reqs, 1.0, 1.0, warm_reqs,
                                      warm.ready, ramp_per_step=2)
    assert t0 >= 100.2 and warm.report["steps"] >= 5
    assert all(loop.stamps[r.uid].due == pytest.approx(t0 + r.due_s)
               for r in reqs)
    facts = serving.check_and_reduce(loop, t0, 1.0, [r.uid for r in reqs])
    assert facts["attempted"] == 4 and facts["failed"] == 0
    assert max(facts["ttft_ms"]) <= 20.0 + 1e-6


def test_train_rate_counts_the_step_that_straddles_the_end(monkeypatch):
    """Every step started inside the window counts, in tokens and in time:
    a stall in the last one lowers the rate."""
    clock = FakeClock()
    monkeypatch.setattr(train_steps.time, "perf_counter", clock)
    costs = iter([1.0, 1.0] + [1.0, 1.0, 1.0, 3.0] + [99.0])

    class Engine:
        losses = iter([10.4, 10.3, 9.0, 8.0, 7.0, 6.0])

        def train_batch(self, batch):
            clock.now += next(costs)
            return next(self.losses)

    class Device:
        def memory_stats(self):
            return {"peak_bytes_in_use": 7}

    class System:
        engine, vocab, rows, seq_len = Engine(), 32000, 2, 8
        devices = [Device(), Device()]

    class Profiler:
        def tick(self, *a):
            pass

        def finish(self, n):
            pass

    class Ctx:
        traffic = {"batches": 2, "warmup_steps": 2}
        config = {"loss": {"first_within": 1.0}}
        seed, seconds, profiler = 3, 3.5, Profiler()

        def window_opens(self):
            return clock()

        def annotate(self, name):
            import contextlib
            return contextlib.nullcontext()

    facts = train_steps.run(Ctx(), System)
    # steps start at 0, 1, 2 and 3 s of a 3.5 s window; the fourth runs to
    # 6 s: 4 steps x 16 tokens over 6 s on 2 chips
    assert facts["attempted"] == facts["steps"] == 4 and facts["correct"]
    assert facts["train_tok_s_chip"] == pytest.approx(4 * 16 / 6.0 / 2)
    assert facts["step_wall_ms"] == pytest.approx([1e3, 1e3, 1e3, 3e3])


def test_no_tpu_no_result():
    """Without an accelerator the command fails and prints no metric."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert run.returncode != 0
    assert '"metrics"' not in run.stdout and "no TPU" in run.stderr
