"""Run one cell of ``BENCHMARK.json`` once.

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: check the device, set up (weights from the seed, the probe
against the plain forward pass, warm-up), measure for ``--seconds``, print
the contract's JSON object as the last line of stdout.  ``--trace 0``
prints the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics
and the breakdown of a profiled slice of the window.

A cell is its entry in ``BENCHMARK.json`` plus the files that entry names:
``configs/<config>.json`` (names its ``builder``), ``traffic/<traffic>.json``
(names its ``driver``) and, for every per-layer metric that lists the cell,
``metrics/<metric>.json`` (names its ``reader``).  Nothing here branches on
a cell's, a configuration's or a metric's name.

There is no silent CPU path: without a TPU listed in ``peaks.json`` the run
exits non-zero and prints no result.  ``--rehearse`` runs the same control
flow on the CPU at the configuration's debug widths for the tests; every
time, rate and share it prints is ``null``.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: units a CPU rehearsal may print: counts, never a time, rate or share
COUNT_UNITS = ("count", "tokens")


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has: "
                     + ", ".join(c["name"] for c in spec["workloads"]))


def check_device(chips: int, peaks: dict) -> list:
    """The cell's chips, or an error: never another platform."""
    import jax
    devices = jax.devices()
    kind = devices[0].device_kind
    if devices[0].platform != "tpu":
        raise SystemExit(f"JAX found no TPU (platform "
                         f"{devices[0].platform!r}); use --rehearse for a "
                         "CPU rehearsal that prints no device number")
    if kind not in peaks["devices"]:
        raise SystemExit(f"device kind {kind!r} is not in peaks.json")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX reports "
                         f"{len(devices)}")
    return devices[:chips]


def device_report(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, debug widths, counts only (for the tests)")
    ap.add_argument("--traffic-set", action="append", default=[],
                    metavar="KEY=JSON",
                    help="override one parameter of the traffic file (for "
                         "a sweep by hand; the driver never passes it)")
    ap.add_argument("--keep-trace", default="",
                    help="directory to copy the .xplane.pb of a traced "
                         "run into")
    args = ap.parse_args(argv)

    spec = load_json(ROOT, "BENCHMARK.json")
    cell = find_cell(spec, args.workload)
    config = load_json(HERE, "configs", cell["config"] + ".json")
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    for item in args.traffic_set:
        key, _, value = item.partition("=")
        traffic[key] = json.loads(value)
    seconds = float(args.seconds if args.seconds is not None
                    else spec["run_seconds"])

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}")
    import jax
    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
        devices, peaks = jax.devices()[:cell["chips"]], None
    else:
        peaks = load_json(HERE, "peaks.json")
        devices = check_device(cell["chips"], peaks)
        peaks = peaks["devices"][devices[0].device_kind]

    # the program's log lines go to stderr: stdout ends in the result
    from deepspeed_tpu.utils.logging import logger
    for handler in logger.handlers:
        handler.setStream(sys.stderr)

    if not args.rehearse:
        # the program's own placement rule (JAX_COMPILATION_CACHE_DIR, else
        # <checkout>/.jax_cache/), asked for before the first compile: the
        # engines call it too, but only after the weights and the probe's
        # reference have compiled
        from deepspeed_tpu.utils.compile_cache import ensure_compile_cache
        ensure_compile_cache()

    from .context import Context
    ctx = Context(cell=cell, config=config, traffic=traffic, peaks=peaks,
                  seed=args.seed, seconds=seconds, trace=bool(args.trace),
                  rehearse=args.rehearse, process_start=PROCESS_START,
                  keep_trace=args.keep_trace)
    builder = importlib.import_module(
        f"benchmark.builders.{config['builder']}")
    driver = importlib.import_module(f"benchmark.drivers.{traffic['driver']}")
    system = builder.build(config, args.seed, devices, args.rehearse)
    print(f"built: {builder.describe(system)}", flush=True)
    facts = driver.run(ctx, system)
    facts["setup_s"] = ctx.setup_s
    print("facts: " + json.dumps(
        {k: v for k, v in facts.items() if not isinstance(v, list)},
        default=str), flush=True)

    device = device_report(devices)
    metrics, breakdown = {}, None
    if args.trace:
        from . import trace_reduce
        path = ctx.profiler.trace_file()
        if path:
            ctx.reduced = trace_reduce.load(
                path, window_span=ctx.profiler.WINDOW_SPAN)
            device["busy_s"] = ctx.reduced.busy_s()
            device["window_s"] = ctx.reduced.window_s()
            breakdown = ctx.reduced.breakdown()
        ctx.profiler.cleanup()
        for m in spec["per_layer"]:
            if not applies(m, cell["name"]):
                continue
            how = load_json(HERE, "metrics", m["name"] + ".json")
            reader = importlib.import_module(
                f"benchmark.readers.{how['reader']}")
            value = reader.read(ctx, facts, how.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in spec["end_to_end"]:
            if applies(m, cell["name"]) and facts.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": facts[m["name"]],
                                      "unit": m["unit"]}
    if args.rehearse:
        for m in metrics.values():
            if m["unit"] not in COUNT_UNITS:
                m["value"] = None
        device.pop("busy_s", None)
        device.pop("window_s", None)
        breakdown = None

    result = {"correct": bool(facts["correct"]),
              "attempted": int(facts["attempted"]),
              "failed": int(facts["failed"]),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
