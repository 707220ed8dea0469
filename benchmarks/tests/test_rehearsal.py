"""Each job kind end to end at a tiny size on the CPU, through the same
`measure` the command runs, and the command's refusals.  The tiny
configurations and mixes are data/rehearsal/, under the real cells'
names, so every real metric definition is read."""
import argparse
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import device, spec

HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "data", "rehearsal")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}
# what only a chip can say: read from a device trace, the device's
# memory statistics, or against its published peak
DEVICE_ONLY = {"device.idle_share", "device.idle_share_open",
               "device.idle_share_sat", "device.mfu", "coll.exposed_share",
               "device.peak_mem_gb"}


def test_one_definition_serves_the_names_it_lists():
    base = spec.metric_definition("device.idle_share")
    assert spec.metric_definition("device.idle_share_sat") == base
    with pytest.raises(spec.SpecError):
        spec.metric_definition("device.idle_share_nowhere")


@pytest.fixture(scope="module")
def clock():
    return device.CompileClock()


def _measure(cell_name, trace, clock):
    import jax

    bench = spec.load_benchmark(REHEARSAL, "cells.json")
    cell = spec.Cell(bench, cell_name, REHEARSAL)
    args = argparse.Namespace(workload=cell_name, seed=0, seconds=2.0,
                              trace=trace)
    result = bench_run.measure(cell, args, jax.devices()[:cell.chips], clock,
                               time.perf_counter())
    return cell, json.loads(json.dumps(result))  # it must serialise


CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


@pytest.mark.parametrize("cell_name", CELLS)
def test_untraced_run_reports_the_cells_end_to_end_metrics(cell_name, clock):
    cell, result = _measure(cell_name, 0, clock)
    assert set(result) == RESULT_KEYS
    assert set(result["device"]) == DEVICE_KEYS
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in cell.end_to_end:
        got = result["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0


@pytest.mark.parametrize("cell_name", CELLS)
def test_traced_run_reports_per_layer_metrics_but_no_device_number(
        cell_name, clock):
    cell, result = _measure(cell_name, 1, clock)
    # off the chip: no busy_s/window_s, no breakdown, no device metric
    assert set(result) == RESULT_KEYS
    assert set(result["device"]) == DEVICE_KEYS
    names = {m["name"] for m in cell.per_layer}
    assert set(result["metrics"]) == names - DEVICE_ONLY
    assert result["metrics"] and result["correct"] is True


def _run_command(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"), "--workload",
         "opt1b3_offline", "--seed", "0", "--seconds", "1", "--trace", "0",
         *extra], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_the_command_refuses_to_run_without_an_accelerator():
    done = _run_command(spec.ROOT)
    assert done.returncode != 0 and done.stdout == ""
    assert "CPU" in done.stderr


def test_the_command_refuses_to_run_without_the_system_under_test(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(spec.ROOT, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", ".run"))
    done = _run_command(tmp_path)
    assert done.returncode != 0 and done.stdout == ""
    assert "not importable" in done.stderr


def test_an_unknown_cell_is_refused_by_name():
    done = subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"), "--workload",
         "no_such_cell"], cwd=spec.ROOT, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
    assert done.returncode != 0 and done.stdout == ""
    assert "no_such_cell" in done.stderr


def test_the_sweep_prints_one_line_per_rate(capsys):
    import jax

    from benchmarks.jobs import generate

    bench = spec.load_benchmark(REHEARSAL, "cells.json")
    cell = spec.Cell(bench, "opt1b3_chat_k80", REHEARSAL)
    args = argparse.Namespace(seed=0, seconds=1.0)
    generate.sweep(cell, args, jax.devices()[:1], [10.0, 20.0])
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("[sweep] ")]
    assert [ln.split()[1] for ln in lines] == ["rate_per_s=10.0",
                                               "rate_per_s=20.0"]
    assert all("left_waiting=" in ln and "ttft_p90_ms=" in ln
               and "late_p99_ms=" in ln for ln in lines)
