"""The per-layer metrics that read the program's spans and counters
(PR 23) come out of the CPU rehearsal: the tiny cells of
data/rehearsal/ run with the `per_layer` list of the real BENCHMARK.json,
so every metric definition added since the rehearsal's own list was
written is read too."""
import argparse
import time

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import device, spec

from test_rehearsal import DEVICE_ONLY, REHEARSAL

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
REHEARSED = {m["name"] for m in
             spec.load_benchmark(REHEARSAL, "cells.json")["per_layer"]}


@pytest.fixture(scope="module")
def clock():
    return device.CompileClock()


@pytest.mark.parametrize("cell_name", CELLS)
def test_traced_run_reports_every_per_layer_metric_of_the_benchmark(
        cell_name, clock):
    import jax

    bench = dict(spec.load_benchmark(REHEARSAL, "cells.json"),
                 per_layer=BENCH["per_layer"])
    cell = spec.Cell(bench, cell_name, REHEARSAL)
    args = argparse.Namespace(workload=cell_name, seed=0, seconds=2.0,
                              trace=1)
    result = bench_run.measure(cell, args, jax.devices()[:cell.chips], clock,
                               time.perf_counter())
    names = {m["name"] for m in cell.per_layer}
    assert names - REHEARSED, "nothing new to rehearse in this cell"
    assert set(result["metrics"]) == names - DEVICE_ONLY
    for name in names - REHEARSED:
        assert result["metrics"][name]["value"] > 0, name
    assert result["correct"] is True
