"""PR 46: `batcher.mixed_share_*` = 100 x `serving.prefill.mixed` ÷
`serving.decode.sessions`, read by the benchmark's own `ratio` reader from
a definition file this PR adds — on the CPU rehearsal of a saturated cell
whose model has the mixed step it reads above 0, on Granite's (Mamba-2)
and Mistral-Small-4's (latent attention), which keep two programs, exactly
0, and in all three every
per-layer metric the real BENCHMARK.json lists for the cell still reads a
number.  Counts, not times: what a CPU run can say."""
import argparse
import json
import os
import time

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import common, device, spec

REHEARSAL = os.path.join(spec.ROOT, "benchmarks", "tests", "data",
                         "rehearsal")
# what only a chip can say (benchmarks/tests/test_rehearsal.py)
DEVICE_ONLY = {"device.idle_share", "device.idle_share_open",
               "device.idle_share_sat", "device.mfu", "coll.exposed_share",
               "device.peak_mem_gb"}


def _rehearsal_bench():
    """The tiny cells under the real cells' names — `cells.json` and the
    rows later PRs brought as files of `cells.d` — with the REAL list of
    per-layer metrics."""
    bench = spec.load_benchmark(REHEARSAL, "cells.json")
    extra = os.path.join(REHEARSAL, "cells.d")
    for part in sorted(os.listdir(extra)):
        with open(os.path.join(extra, part)) as f:
            for key, rows in json.load(f).items():
                bench[key] = bench[key] + rows
    return dict(bench, per_layer=spec.load_benchmark()["per_layer"])


@pytest.mark.parametrize("cell_name,mixed", [
    ("opt1b3_offline", True), ("granite4h_docs_c16", False),
    ("mistralsmall4_reason_c32", False)])
def test_the_rehearsal_reads_the_mixed_share(cell_name, mixed, tmp_path,
                                             monkeypatch):
    import jax

    # a trace directory of this test's own: `tests/test_benchmarks_guard.py`
    # runs the benchmark's rehearsals in another worker, and each traced
    # window empties the shared one first
    monkeypatch.setattr(common, "TRACE_DIR", str(tmp_path / "trace"))
    cell = spec.Cell(_rehearsal_bench(), cell_name, REHEARSAL)
    args = argparse.Namespace(workload=cell_name, seed=0, seconds=2.0,
                              trace=1)
    result = bench_run.measure(cell, args, jax.devices()[:cell.chips],
                               device.CompileClock(), time.perf_counter())
    assert result["correct"] is True and result["failed"] == 0
    names = {m["name"] for m in cell.per_layer}
    assert "batcher.mixed_share_sat" in names
    assert set(result["metrics"]) == names - DEVICE_ONLY
    share = result["metrics"]["batcher.mixed_share_sat"]
    assert share["unit"] == "%"
    if mixed:
        assert 0 < share["value"] <= 100
    else:
        assert share["value"] == 0
    # PR 47: the blockwise prefill kernel's share of the attention layers'
    # bucket positions, read the same way — booked, and 0 off the TPU
    assert result["metrics"]["attn.kernel_share_sat"]["value"] == 0
    assert result["metrics"]["attn.kernel_share_sat"]["unit"] == "%"


def test_the_definition_is_one_file_read_under_two_names():
    base = spec.metric_definition("batcher.mixed_share")
    assert base["reader"] == "ratio"
    assert base["args"]["num"] == [{"counter": "serving.prefill.mixed"}]
    assert base["args"]["den"] == [{"counter": "serving.decode.sessions"}]
    for name in ("batcher.mixed_share_open", "batcher.mixed_share_sat"):
        assert spec.metric_definition(name) == base
    entries = {m["name"]: m for m in spec.load_benchmark()["per_layer"]
               if m["name"].startswith("batcher.mixed_share")}
    assert entries["batcher.mixed_share_open"]["workloads"] == [
        "opt1b3_chat_k80"]
    assert entries["batcher.mixed_share_open"]["moves"] == "itl_p99_ms"
    sat = entries["batcher.mixed_share_sat"]
    assert sat["moves"] == "gen_tok_per_s" and len(sat["workloads"]) == 13
    assert {m["layer"] for m in entries.values()} == {"serving batcher"}


def test_the_kernel_share_is_listed_where_the_mixed_share_is():
    """PR 47: `attn.kernel_share_*` = 100 x `attn.kernel_positions` ÷
    `attn.prefill_positions`, one definition file under two names, listed
    for the same cells as `batcher.mixed_share_*` (the cell tests pin
    differences of lists)."""
    base = spec.metric_definition("attn.kernel_share")
    assert base["reader"] == "ratio" and base["args"]["scale"] == 100.0
    assert base["args"]["num"] == [{"counter": "attn.kernel_positions"}]
    assert base["args"]["den"] == [{"counter": "attn.prefill_positions"}]
    per_layer = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}
    for tail, moves in (("_open", "itl_p99_ms"), ("_sat", "gen_tok_per_s")):
        entry = per_layer["attn.kernel_share" + tail]
        assert spec.metric_definition(entry["name"]) == base
        assert entry["workloads"] == per_layer[
            "batcher.mixed_share" + tail]["workloads"]
        assert (entry["layer"], entry["moves"], entry["source"]) == (
            "device", moves, "program_counter")
