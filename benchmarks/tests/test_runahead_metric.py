"""`batcher.runahead_share` rehearsed on the CPU: the REAL
BENCHMARK.json's entries of the three serving cells (as
test_olmoe_cell.py rehearses its cell: the tiny mixes and
configurations of data/rehearsal/ under the real cells' names), so the
definition file and both `per_layer` entries are read by the same
`measure` the command runs."""
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
                             "batcher.runahead_share_open", "itl_p99_ms"),
         "opt1b3_offline": ("opt-1.3b", "configs/opt_tiny.json",
                            "batcher.runahead_share_sat", "gen_tok_per_s"),
         "olmoe_offline": ("olmoe-1b-7b", "configs/olmoe_tiny.json",
                           "batcher.runahead_share_sat", "gen_tok_per_s")}


def _cell(name):
    bench = copy.deepcopy(spec.load_benchmark())
    bench["paths"] = ["."]
    conf, = [c for c in bench["configs"] if c["name"] == CELLS[name][0]]
    conf["file"] = CELLS[name][1]
    return spec.Cell(bench, name, REHEARSAL)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_a_traced_rehearsal_reports_the_share_of_steps_that_ran_ahead(name):
    import jax

    cell, metric = _cell(name), CELLS[name][2]
    entry, = [m for m in cell.per_layer if m["name"] == metric]
    assert entry["moves"] == CELLS[name][3]
    assert entry["layer"] == "serving batcher"
    assert CELLS[name][3] in {m["name"] for m in cell.end_to_end}
    args = argparse.Namespace(workload=name, seed=2**31 + 9, seconds=2.0,
                              trace=1)
    result = json.loads(json.dumps(bench_run.measure(
        cell, args, jax.devices()[:1], device.CompileClock(),
        time.perf_counter())))
    assert result["correct"] is True and result["failed"] == 0
    share = result["metrics"][metric]
    assert share["unit"] == "%"
    # every step follows a prefill or a step whose tokens are unread
    assert 0.0 < share["value"] <= 100.0
    # the five legs still read a number beside it
    suffix = metric.rsplit("_", 1)[1]
    for leg in ("pack", "dispatch", "device_wait", "d2h", "emit"):
        assert result["metrics"]["batcher.%s_ms_%s" % (leg, suffix)][
            "value"] > 0.0


def test_both_names_share_one_definition_and_an_idle_window_reads_nothing():
    base = spec.metric_definition("batcher.runahead_share")
    assert base["reader"] == "ratio"
    for name in ("batcher.runahead_share_open", "batcher.runahead_share_sat"):
        assert spec.metric_definition(name) == base
    w = window.Window()
    w.before = {"counters": {}, "histograms": {}}
    # a program without the counter (this PR's parent): no step ran ahead
    w.after = {"counters": {"serving.decode.dispatches": 40},
               "histograms": {}}
    assert ratio.read(w, **base["args"]) == 0.0
    w.after = {"counters": {"serving.decode.dispatches": 40,
                            "serving.decode.runahead_steps": 30},
               "histograms": {}}
    assert ratio.read(w, **base["args"]) == 75.0
    # no decode step in the window: nothing to read
    w.after = {"counters": {}, "histograms": {}}
    assert ratio.read(w, **base["args"]) is None
