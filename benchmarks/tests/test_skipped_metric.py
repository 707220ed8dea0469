"""`kv.skipped_share` rehearsed on the CPU: the REAL BENCHMARK.json's
entries of the four serving cells (as test_runahead_metric.py rehearses
its metric: the tiny mixes and configurations of data/rehearsal/ under
the real cells' names), so the definition file and both `per_layer`
entries are read by the same `measure` the command runs.  On the CPU the
decode program reads whole pages, and the share says so: 0."""
import argparse
import copy
import json
import os
import time

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import device, spec, window
from benchmarks.readers import ratio

REHEARSAL = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "data", "rehearsal")
CELLS = {"opt1b3_chat_k80": ("opt-1.3b", "configs/opt_tiny.json",
                             "kv.skipped_share_open", "itl_p99_ms"),
         "opt1b3_offline": ("opt-1.3b", "configs/opt_tiny.json",
                            "kv.skipped_share_sat", "gen_tok_per_s"),
         "olmoe_offline": ("olmoe-1b-7b", "configs/olmoe_tiny.json",
                           "kv.skipped_share_sat", "gen_tok_per_s")}


def _cell(name):
    bench = copy.deepcopy(spec.load_benchmark())
    bench["paths"] = ["."]
    conf, = [c for c in bench["configs"] if c["name"] == CELLS[name][0]]
    conf["file"] = CELLS[name][1]
    return spec.Cell(bench, name, REHEARSAL)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_a_traced_rehearsal_reports_the_share_of_page_positions_skipped(name):
    import jax

    cell, metric = _cell(name), CELLS[name][2]
    entry, = [m for m in cell.per_layer if m["name"] == metric]
    assert entry["moves"] == CELLS[name][3]
    assert entry["layer"] == "device" and entry["better"] == "higher"
    assert CELLS[name][3] in {m["name"] for m in cell.end_to_end}
    args = argparse.Namespace(workload=name, seed=2**31 + 11, seconds=2.0,
                              trace=1)
    result = json.loads(json.dumps(bench_run.measure(
        cell, args, jax.devices()[:1], device.CompileClock(),
        time.perf_counter())))
    assert result["correct"] is True and result["failed"] == 0
    share = result["metrics"][metric]
    # the CPU's decode program reads whole pages: steps ran, none skipped
    assert share == {"value": 0.0, "unit": "%"}


def test_the_granite_cell_lists_the_metric_too():
    bench = spec.load_benchmark()
    entry, = [m for m in bench["per_layer"]
              if m["name"] == "kv.skipped_share_sat"]
    assert entry["workloads"] == ["opt1b3_offline", "olmoe_offline",
                                  "granite4h_docs_c16"]
    entry, = [m for m in bench["per_layer"]
              if m["name"] == "kv.skipped_share_open"]
    assert entry["workloads"] == ["opt1b3_chat_k80"]


def test_both_names_share_one_definition_and_an_idle_window_reads_nothing():
    base = spec.metric_definition("kv.skipped_share")
    assert base["reader"] == "ratio"
    for name in ("kv.skipped_share_open", "kv.skipped_share_sat"):
        assert spec.metric_definition(name) == base
    w = window.Window()
    w.before = {"counters": {}, "histograms": {}}
    # a program without the counters (this PR's parent): nothing to read
    w.after = {"counters": {"kv.used_positions": 900}, "histograms": {}}
    assert ratio.read(w, **base["args"]) is None
    # whole pages read (the CPU, a ring no block divides): 0
    w.after = {"counters": {"kv.page_positions": 6144,
                            "kv.skipped_positions": 0}, "histograms": {}}
    assert ratio.read(w, **base["args"]) == 0.0
    # 8 rows of 768, 2 blocks of 128 read of each
    w.after = {"counters": {"kv.page_positions": 6144,
                            "kv.skipped_positions": 4096},
               "histograms": {}}
    assert ratio.read(w, **base["args"]) == pytest.approx(66.6667, abs=1e-3)
