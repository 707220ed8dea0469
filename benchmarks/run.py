#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell of BENCHMARK.json and prints, as the last line of stdout,
one JSON object: `correct`, `attempted`, `failed`, `metrics`, `device`
(and `breakdown` when traced).  With `--trace 0` the metrics are the
cell's end-to-end metrics, with `--trace 1` its per-layer metrics.
Everything else it prints goes on earlier lines, prefixed `[bench]`.

    python benchmarks/run.py --workload <cell> --sweep 2,3,4,5 --seconds 20

is not a cell: it steps an open loop's rate on one warm tenant and
prints what each rate did (benchmarks/README.md, "Finding the knee").

It exits non-zero and prints no result when JAX finds no accelerator or
fewer chips than the cell asks for, or when the system under test is not
beside it.  See benchmarks/README.md for how cells, configurations,
traffic mixes and metrics are added as files.
"""
import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def measure(cell, args, devices, clock, process_start):
    """Run the cell's job and read its metrics; returns the result
    object.  `devices` are the JAX devices the cell uses."""
    from benchmarks.harness import device, peaks, spec

    job = importlib.import_module("benchmarks.jobs." + cell.traffic["job"])
    window = job.run(cell, args, devices, clock, process_start)
    window.device = device.describe(devices)
    window.scalars["chips"] = cell.chips
    if devices[0].platform != "cpu":
        window.peaks = peaks.peaks_for(devices[0].device_kind)
    metrics = {}
    for entry in cell.per_layer if args.trace else cell.end_to_end:
        definition = spec.metric_definition(entry["name"])
        reader = importlib.import_module(
            "benchmarks.readers." + definition["reader"])
        value = reader.read(window, **definition.get("args", {}))
        if value is not None:
            metrics[entry["name"]] = {"value": float(value),
                                      "unit": entry["unit"]}
    print("[bench] memory_stats %s" % json.dumps(devices[0].memory_stats()),
          flush=True)
    print("[bench] scalars %s" % json.dumps(window.scalars), flush=True)
    print("[bench] notes %s" % json.dumps(window.notes), flush=True)
    result = {"correct": bool(window.correct), "attempted": window.attempted,
              "failed": window.failed, "metrics": metrics,
              "device": window.device}
    if window.trace is not None:
        result["device"]["busy_s"] = window.trace["busy_s"]
        result["device"]["window_s"] = window.trace["window_s"]
        result["breakdown"] = {"device_ops": window.trace["device_ops"],
                               "idle_gaps": window.trace["idle_gaps"]}
    return result


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated open-loop rates (requests/s)")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse(argv)
    try:
        from benchmarks.harness import device, spec
        bench = spec.load_benchmark()
        cell = spec.Cell(bench, args.workload)
    except (ImportError, OSError, KeyError, ValueError, RuntimeError) as e:
        sys.exit("benchmark: cannot load the cell: %s: %s"
                 % (type(e).__name__, e))
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    try:
        import jax

        # import BEFORE any backend touch: the package places the compile
        # cache at import (mxnet_tpu.base.compile_cache_dir)
        import mxnet_tpu  # noqa: F401
    except ImportError as e:
        sys.exit("benchmark: the system under test is not importable from "
                 "%s: %s" % (ROOT, e))
    # sub-second programs are cached too: every later run finds them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = device.require_accelerator(cell.chips)
    clock = device.CompileClock()
    print("[bench] cell %s on %d x %s; compile cache %s" % (
        cell.name, cell.chips, devices[0].device_kind,
        mxnet_tpu.base.compile_cache_dir()), flush=True)
    if args.sweep:
        job = importlib.import_module("benchmarks.jobs." + cell.traffic["job"])
        job.sweep(cell, args, devices, [float(r) for r in args.sweep.split(",")])
        return
    result = measure(cell, args, devices, clock, PROCESS_START)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
