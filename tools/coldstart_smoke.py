#!/usr/bin/env python
"""Cold-start smoke + bench driver (ISSUE 14).

Proves the recompile-proof cold-start story end to end, across REAL
process boundaries:

- **prime** (child process A): mine a lattice artifact from the
  checked-in workload trace, build an engine with
  ``lattice="auto:<artifact>"`` + a persistent compile cache dir,
  precompile the mined lattice (true XLA compiles, written to disk),
  run the trace once as the tokenwise reference, then snapshot a
  partially-served run — the bundle carries the compiled-key manifest.
  Also measures the **warm** control: restoring the bundle into a
  second engine over the same (already-compiled) model in-process.
- **resume** (child process B): a COLD process builds the same engine
  against the warm cache dir, ``restore()``s the bundle (the manifest
  precompile is all disk loads), finishes the restored requests, then
  replays the full trace — asserting tokenwise parity with the
  reference, ``ds_fastgen_compile_on_path_total == 0`` over the
  replay, and ZERO true compiles (cache loads only).
- optionally **resume without a cache** (child process C): the same
  cold restore paying true compiles — the baseline the cache is
  measured against (bench mode only; the CI smoke skips it).

CLI::

    python tools/coldstart_smoke.py [--check] [--full] [--limit 32]
        [--trace tools/traces/sample_200.jsonl] [--json out.json]

``--check`` exits non-zero unless parity holds and the warm-cache
resume is recompile-free (the ``tools/ci.sh`` smoke mode); ``--full``
adds the no-cache cold leg.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

DEFAULT_TRACE = os.path.join(REPO_ROOT, "tools", "traces",
                             "sample_200.jsonl")


def _load_requests(trace_path: str, limit: int):
    from tools import replay_trace
    trace = replay_trace.load_trace(trace_path)
    requests = [r for r in trace["requests"] if r.get("outcome") == "ok"]
    if limit:
        requests = requests[:limit]
    if not requests:
        raise ValueError(f"{trace_path}: no replayable requests")
    return trace, requests


def _build_engine(trace, requests, artifact: str, cache_dir: str):
    from deepspeed_tpu.inference.v2 import ServingOptimizationConfig
    from tools import replay_trace
    if not cache_dir:
        # the no-cache arm: an empty dir would mean the in-checkout
        # default, so switch JAX's persistent cache off instead
        import jax
        jax.config.update("jax_enable_compilation_cache", False)
    serving = ServingOptimizationConfig(
        lattice=f"auto:{artifact}" if artifact else "",
        compile_cache_dir=cache_dir or "")
    return replay_trace.build_replay_engine(trace["meta"], requests,
                                            serving=serving)


def _prompts(trace, requests, engine):
    from tools import replay_trace
    page = int(trace["meta"].get("page_size", 16))
    vocab = min(int(trace["meta"].get("vocab_size", 0))
                or engine.model.cfg.vocab_size,
                engine.model.cfg.vocab_size)
    return replay_trace.synthesize_prompts(requests, page, vocab), page


def _submit_all(sched, requests, prompts) -> None:
    """The ONE requests -> SamplingParams -> submit mapping every
    phase shares (prime reference, partial run, resume replay) — the
    parity gates compare their outputs, so the mapping must not
    fork."""
    from deepspeed_tpu.inference.v2 import SamplingParams
    for i, r in enumerate(requests):
        sched.submit(i, prompts[i], SamplingParams(
            temperature=float(r.get("temperature", 0.0)),
            top_k=int(r.get("top_k", 0)),
            top_p=float(r.get("top_p", 1.0)),
            max_new_tokens=max(1, int(r["gen_len"]))))


def _run_all(engine, requests, prompts) -> Dict[int, List[int]]:
    """One full deterministic pass (speed=0) collecting every token."""
    from deepspeed_tpu.inference.v2 import FastGenScheduler
    sched = FastGenScheduler(engine)
    _submit_all(sched, requests, prompts)
    out = sched.run_to_completion()
    return {int(u): [int(t) for t in toks] for u, toks in out.items()}


def _phase_prime(args) -> Dict[str, Any]:
    import jax  # noqa: F401 — backend init before timers
    from deepspeed_tpu.inference.v2 import (FastGenScheduler,
                                            SamplingParams)
    from deepspeed_tpu.inference.v2 import lattice as dslattice
    from deepspeed_tpu.telemetry import metrics as tm
    from tools.replay_trace import _reset_engine

    trace, requests = _load_requests(args.trace, args.limit)
    artifact = dslattice.mine_lattice(trace, source=args.trace)
    dslattice.write_artifact(artifact, args.artifact)

    engine = _build_engine(trace, requests, args.artifact, args.cache_dir)
    prompts, page = _prompts(trace, requests, engine)

    # the mined lattice, compiled cold (true XLA compiles -> disk)
    h0, m0 = (tm.FASTGEN_COMPILE_CACHE_HIT.value,
              tm.FASTGEN_COMPILE_CACHE_MISS.value)
    t0 = time.perf_counter()
    keys = engine.precompile(
        max_prompt=max(int(r["prompt_len"]) for r in requests),
        sampling=True)
    precompile_wall = time.perf_counter() - t0

    # tokenwise reference: the uninterrupted run
    ref_sched = FastGenScheduler(engine)
    _submit_all(ref_sched, requests, prompts)
    ref_tokens: Dict[int, List[int]] = {i: [] for i in range(len(requests))}
    for uid, toks in ref_sched.run_to_completion().items():
        ref_tokens[int(uid)] = [int(t) for t in toks]
    compile_on_path_ref = tm.FASTGEN_COMPILE_ON_PATH.value

    # partially-served run -> snapshot (manifest rides the bundle)
    _reset_engine(engine)
    part = FastGenScheduler(engine)
    _submit_all(part, requests, prompts)
    for _ in range(args.presteps):
        part.step()
    part.snapshot(args.bundle)
    # requests that COMPLETED before/at the snapshot drain are not in
    # the bundle; their reference tokens are the resume leg's parity
    # source for the missing uids
    bundled = set()
    from deepspeed_tpu.inference.v2.snapshot import read_bundle
    meta, _ = read_bundle(args.bundle)
    for group in meta["requests"].values():
        for d in group:
            bundled.add(int(d["uid"]))
    pre_done = {i: ref_tokens[i] for i in range(len(requests))
                if i not in bundled}

    # warm control: restore into a fresh engine over the SAME
    # (already-compiled) model — the in-process stand-in for a warm
    # process's restore-to-first-token
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    import dataclasses as _dc
    warm_cfg = _dc.replace(engine._config)
    warm_engine = InferenceEngineV2(engine.model, warm_cfg)
    first_tok = []
    t0 = time.perf_counter()
    warm_sched = FastGenScheduler(warm_engine).restore(args.bundle)
    restore_warm_ms = (time.perf_counter() - t0) * 1e3
    guard = 0
    while not first_tok and warm_sched.has_work and guard < 64:
        warm_sched.step(on_token=lambda u, t: first_tok.append(
            time.perf_counter()))
        guard += 1
    warm_first_token_ms = ((first_tok[0] - t0) * 1e3 if first_tok
                           else None)

    h1, m1 = (tm.FASTGEN_COMPILE_CACHE_HIT.value,
              tm.FASTGEN_COMPILE_CACHE_MISS.value)
    return {
        "requests": len(requests),
        "page_size": page,
        "lattice_keys_auto": len(keys),
        "precompile_wall_cold_s": round(precompile_wall, 3),
        "cache_hits": h1 - h0,
        "cache_misses": m1 - m0,
        "compile_on_path_ref": compile_on_path_ref,
        "manifest_keys": len(meta["compiled"]["keys"]),
        "restore_warm_ms": round(restore_warm_ms, 2),
        "restore_warm_first_token_ms": (
            round(warm_first_token_ms, 2)
            if warm_first_token_ms is not None else None),
        "ref_tokens": {str(u): t for u, t in ref_tokens.items()},
        "pre_done": {str(u): t for u, t in pre_done.items()},
    }


def _phase_resume(args) -> Dict[str, Any]:
    from deepspeed_tpu.inference.v2 import FastGenScheduler
    from deepspeed_tpu.telemetry import metrics as tm
    from tools.replay_trace import _reset_engine

    trace, requests = _load_requests(args.trace, args.limit)
    with open(args.ref) as f:
        prime = json.load(f)
    ref_tokens = {int(u): t for u, t in prime["ref_tokens"].items()}
    pre_done = {int(u): t for u, t in prime["pre_done"].items()}

    engine = _build_engine(trace, requests, args.artifact, args.cache_dir)
    prompts, _ = _prompts(trace, requests, engine)

    # restore-to-first-token: the bundle's compiled-key manifest
    # precompiles inside restore() — disk loads against a warm cache,
    # true compiles without one
    h0, m0 = (tm.FASTGEN_COMPILE_CACHE_HIT.value,
              tm.FASTGEN_COMPILE_CACHE_MISS.value)
    first_tok: List[float] = []
    delivered: Dict[int, List[int]] = {}

    def tap(u: int, t: int) -> None:
        if not first_tok:
            first_tok.append(time.perf_counter())
        delivered.setdefault(int(u), []).append(int(t))

    t0 = time.perf_counter()
    sched = FastGenScheduler(engine).restore(args.bundle)
    restore_ms = (time.perf_counter() - t0) * 1e3
    # the restore window's cache facts (the manifest precompile runs
    # INSIDE restore) — read before the separate full-lattice
    # precompile below, whose loads must not inflate them
    restore_hits = tm.FASTGEN_COMPILE_CACHE_HIT.value - h0
    restore_misses = tm.FASTGEN_COMPILE_CACHE_MISS.value - m0
    base = {int(r.uid): [int(t) for t in r.generated]
            for r in (list(sched._pending)
                      + list(sched._running.values())
                      + list(sched._preempted.values()))}
    stalls = 0
    while sched.has_work:
        out = sched.step(on_token=tap)
        stalls = (stalls + 1 if sched.last_step_scheduled == 0
                  and not out else 0)
        if stalls > 64:
            raise RuntimeError("restored run stalled")
    first_token_ms = ((first_tok[0] - t0) * 1e3 if first_tok else None)
    totals = {u: base[u] + delivered.get(u, []) for u in base}
    resume_parity = (
        all(totals[u] == ref_tokens.get(u) for u in base)
        and set(ref_tokens) - set(base) == set(pre_done))

    # the full-lattice precompile is all loads on a warm cache (the
    # second-process half of the tentpole claim)
    t0 = time.perf_counter()
    engine.precompile(
        max_prompt=max(int(r["prompt_len"]) for r in requests),
        sampling=True)
    precompile_wall = time.perf_counter() - t0

    # replay the full trace on the restored engine: the acceptance
    # window — zero on-path compiles, zero true compiles (loads only)
    _reset_engine(engine)
    c0 = tm.FASTGEN_COMPILE_ON_PATH.value
    m2 = tm.FASTGEN_COMPILE_CACHE_MISS.value
    replay_out = _run_all(engine, requests, prompts)
    replay_parity = all(
        replay_out.get(i, []) == ref_tokens[i]
        for i in range(len(requests)))
    return {
        "restore_ms": round(restore_ms, 2),
        "restore_to_first_token_ms": (round(first_token_ms, 2)
                                      if first_token_ms is not None
                                      else None),
        "precompile_wall_s": round(precompile_wall, 3),
        "restore_cache_hits": restore_hits,
        "restore_cache_misses": restore_misses,
        "resume_parity": bool(resume_parity),
        "replay_parity": bool(replay_parity),
        "replay_compile_on_path": tm.FASTGEN_COMPILE_ON_PATH.value - c0,
        "replay_cache_misses": tm.FASTGEN_COMPILE_CACHE_MISS.value - m2,
    }


def _spawn(phase: str, args, cache_dir: str, json_out: str,
           ref: Optional[str] = None) -> Dict[str, Any]:
    cmd = [sys.executable, os.path.abspath(__file__),
           "--phase", phase, "--trace", args.trace,
           "--limit", str(args.limit), "--artifact", args.artifact,
           "--bundle", args.bundle, "--cache-dir", cache_dir,
           "--presteps", str(args.presteps), "--json", json_out]
    if ref:
        cmd += ["--ref", ref]
    env = dict(os.environ)
    # the --cache-dir flag is the only placement control
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(
            f"coldstart phase {phase} failed (rc={proc.returncode}):\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-4000:]}")
    with open(json_out) as f:
        return json.load(f)


def run_coldstart(trace: str = DEFAULT_TRACE, limit: int = 32,
                  full: bool = False, presteps: int = 3,
                  workdir: Optional[str] = None) -> Dict[str, Any]:
    """Drive prime + resume (+ optional no-cache cold resume) across
    real process boundaries; returns the combined report.  A
    self-created workdir (``workdir=None``) is removed afterwards —
    the compile-cache tree holds one entry per compiled program, and
    CI/bench hosts run this every pass."""
    import shutil
    created = workdir is None
    tmp = workdir or tempfile.mkdtemp(prefix="ds_coldstart_")
    try:
        return _run_coldstart_impl(tmp, trace, limit, full, presteps)
    finally:
        if created:
            shutil.rmtree(tmp, ignore_errors=True)


def _run_coldstart_impl(tmp: str, trace: str, limit: int, full: bool,
                        presteps: int) -> Dict[str, Any]:
    ns = argparse.Namespace(
        trace=trace, limit=limit, presteps=presteps,
        artifact=os.path.join(tmp, "lattice.json"),
        bundle=os.path.join(tmp, "serving.snap"))
    cache = os.path.join(tmp, "compile_cache")
    prime = _spawn("prime", ns, cache, os.path.join(tmp, "a.json"))
    warm_cache = _spawn("resume", ns, cache, os.path.join(tmp, "b.json"),
                        ref=os.path.join(tmp, "a.json"))
    report = {
        "coldstart_requests": prime["requests"],
        "coldstart_lattice_keys_auto": prime["lattice_keys_auto"],
        "coldstart_manifest_keys": prime["manifest_keys"],
        "coldstart_precompile_wall_cold_s":
            prime["precompile_wall_cold_s"],
        "coldstart_precompile_wall_warmcache_s":
            warm_cache["precompile_wall_s"],
        "coldstart_cache_misses_prime": prime["cache_misses"],
        "coldstart_restore_ttft_warm_ms":
            prime["restore_warm_first_token_ms"],
        "coldstart_restore_ttft_warmcache_ms":
            warm_cache["restore_to_first_token_ms"],
        "coldstart_restore_warmcache_cache_hits":
            warm_cache["restore_cache_hits"],
        "coldstart_restore_warmcache_true_compiles":
            warm_cache["restore_cache_misses"],
        "coldstart_replay_compile_on_path":
            warm_cache["replay_compile_on_path"],
        "coldstart_replay_true_compiles":
            warm_cache["replay_cache_misses"],
        "coldstart_resume_parity": warm_cache["resume_parity"],
        "coldstart_replay_parity": warm_cache["replay_parity"],
    }
    if full:
        nocache = _spawn("resume", ns, "", os.path.join(tmp, "c.json"),
                         ref=os.path.join(tmp, "a.json"))
        report["coldstart_restore_ttft_nocache_ms"] = \
            nocache["restore_to_first_token_ms"]
        report["coldstart_precompile_wall_nocache_s"] = \
            nocache["precompile_wall_s"]
        report["coldstart_nocache_parity"] = nocache["resume_parity"]
    return report


def coldstart_gates(report: Dict[str, Any]) -> List[str]:
    """Hard gate findings (empty = green).  Timing ratios are soft —
    CPU-debug walls are noisy — but structural facts are not."""
    problems = []
    if not report.get("coldstart_resume_parity"):
        problems.append("restored run is not tokenwise identical to "
                        "the uninterrupted reference")
    if not report.get("coldstart_replay_parity"):
        problems.append("cold-process replay is not tokenwise "
                        "identical to the reference")
    if report.get("coldstart_replay_compile_on_path", 1) != 0:
        problems.append(
            f"cold process + warm cache replay executed "
            f"{report.get('coldstart_replay_compile_on_path')} XLA "
            "compiles on the request path (want 0)")
    if report.get("coldstart_replay_true_compiles", 1) != 0:
        problems.append(
            f"cold process + warm cache replay paid "
            f"{report.get('coldstart_replay_true_compiles')} TRUE "
            "compiles (want 0: cache loads only)")
    if report.get("coldstart_restore_warmcache_true_compiles", 1) != 0:
        problems.append(
            f"warm-cache restore paid "
            f"{report.get('coldstart_restore_warmcache_true_compiles')}"
            " true compiles (want 0: manifest precompile should be "
            "loads)")
    if not report.get("coldstart_restore_warmcache_cache_hits"):
        problems.append("warm-cache restore loaded nothing from the "
                        "persistent cache")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--phase", default="",
                    help="(internal) child phase: prime|resume")
    ap.add_argument("--trace", default=DEFAULT_TRACE)
    ap.add_argument("--limit", type=int, default=32)
    ap.add_argument("--presteps", type=int, default=3,
                    help="scheduler steps before the mid-flight "
                    "snapshot in the prime phase")
    ap.add_argument("--artifact", default="")
    ap.add_argument("--bundle", default="")
    ap.add_argument("--cache-dir", default="")
    ap.add_argument("--ref", default="",
                    help="(internal) prime-phase JSON for parity")
    ap.add_argument("--json", default="")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero unless every hard gate holds "
                    "(CI smoke mode)")
    ap.add_argument("--full", action="store_true",
                    help="also run the no-cache cold leg (bench mode)")
    args = ap.parse_args(argv)

    if args.phase:
        out = (_phase_prime(args) if args.phase == "prime"
               else _phase_resume(args))
        with open(args.json or "/dev/stdout", "w") as f:
            json.dump(out, f, indent=1)
        return 0

    report = run_coldstart(trace=args.trace, limit=args.limit,
                           full=args.full, presteps=args.presteps)
    print(json.dumps(report, indent=1))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1)
    problems = coldstart_gates(report)
    if args.check and problems:
        print("coldstart_smoke: GATES FAILED", file=sys.stderr)
        for p in problems:
            print(f"coldstart_smoke:   {p}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
