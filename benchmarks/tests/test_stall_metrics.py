"""The four readings of the batcher's pass (`batcher.stall_share`,
`batcher.stall_wait_share`, `batcher.stalls_per_min`,
`batcher.unspanned_share`) rehearsed on the CPU, in the shape of
test_runahead_metric.py: the REAL BENCHMARK.json's entries under the tiny
mixes and configurations of data/rehearsal/, so the definition files and
both `per_layer` entries of each are read by the same `measure` the
command runs.  Every denominator is the window's `seconds`: a window
without a stall — and a program without the histograms, this PR's
parent — reads 0.0, not nothing."""
import argparse
import copy
import json
import logging
import os
import time

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import device, spec, window
from benchmarks.readers import ratio

REHEARSAL = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "data", "rehearsal")
BASES = {"batcher.stall_share": ("%", "program_span"),
         "batcher.stall_wait_share": ("%", "program_span"),
         "batcher.stalls_per_min": ("1/min", "program_counter"),
         "batcher.unspanned_share": ("%", "program_span")}
CHAT = "opt1b3_chat_k80"
CLOSED_LOOP = ["opt1b3_offline", "olmoe_offline", "granite4h_docs_c16",
               "olmohybrid_extract_c16", "trinitymini_reason_c16",
               "qwen3next_batch_c32", "mistralsmall4_reason_c32",
               "dots3note_longdoc_c8"]
# one cell is enough here: test_rehearsal.py holds EVERY cell's traced
# rehearsal to the names its `per_layer` entries list, these eight among
# them (and this file runs on tier-1's longest worker)
REHEARSED = {"opt1b3_offline": ("opt-1.3b", "configs/opt_tiny.json", "_sat")}


def _window(seconds, counters=None, hists=None):
    w = window.Window()
    w.scalars["seconds"] = seconds
    w.before = {"counters": {}, "histograms": {}}
    w.after = {"counters": counters or {}, "histograms": hists or {}}
    return w


@pytest.mark.parametrize("base", sorted(BASES))
def test_both_names_share_one_definition_and_their_entries_name_the_cells(
        base):
    bench = spec.load_benchmark()
    definition = spec.metric_definition(base)
    assert definition["reader"] == "ratio"
    assert definition["args"]["den"] == [{"scalar": "seconds"}]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for suffix, moves, cells in (("_open", "itl_p99_ms", [CHAT]),
                                 ("_sat", "gen_tok_per_s", CLOSED_LOOP)):
        assert spec.metric_definition(base + suffix) == definition
        entry = by_name[base + suffix]
        assert entry["workloads"] == cells
        assert entry["moves"] == moves and entry["better"] == "lower"
        assert entry["layer"] == "serving batcher"
        assert (entry["unit"], entry["source"]) == BASES[base]
        # each listed cell reports the end-to-end metric it moves
        moved, = [m for m in bench["end_to_end"] if m["name"] == moves]
        assert set(cells) <= set(moved["workloads"])
    # the closed-loop cells are every serving cell but the chat cell
    serving = [w["name"] for w in bench["workloads"]
               if w["name"] != "resnet50_fit_dp4"]
    assert serving == [CHAT] + CLOSED_LOOP


def test_a_window_without_a_stall_reads_zero_and_one_with_a_stall_its_share():
    share = spec.metric_definition("batcher.stall_share")["args"]
    wait = spec.metric_definition("batcher.stall_wait_share")["args"]
    rate = spec.metric_definition("batcher.stalls_per_min")["args"]
    rest = spec.metric_definition("batcher.unspanned_share")["args"]
    # this PR's parent: no such histogram, no such counter
    sound = _window(50.0, {"serving.decode.dispatches": 9000})
    for args in (share, wait, rate, rest):
        assert ratio.read(sound, **args) == 0.0
    # a sound window of this program: passes, and no stall
    sound = _window(50.0, {"serving.loop.passes": 11000},
                    {"serving.loop.unspanned_seconds": (11000, 2.2)})
    assert ratio.read(sound, **share) == 0.0
    assert ratio.read(sound, **wait) == 0.0
    assert ratio.read(sound, **rate) == 0.0
    assert ratio.read(sound, **rest) == pytest.approx(4.4)
    # one fence of 3 s and one emit of 0.5 s in a 50 s window
    held = _window(50.0, {"serving.stalls": 2}, {
        "serving.stall_seconds": (2, 3.5),
        "serving.stall_seconds.device_wait": (1, 3.0),
        "serving.stall_seconds.emit": (1, 0.5)})
    assert ratio.read(held, **share) == pytest.approx(7.0)
    assert ratio.read(held, **wait) == pytest.approx(6.0)
    assert ratio.read(held, **rate) == pytest.approx(2.4)
    # the growth in the window, not the lifetime's sums
    held.before = {"counters": {"serving.stalls": 1},
                   "histograms": {"serving.stall_seconds": (1, 3.0)}}
    assert ratio.read(held, **share) == pytest.approx(1.0)
    assert ratio.read(held, **rate) == pytest.approx(1.2)
    # a job that reported no window: nothing to divide by
    assert ratio.read(window.Window(), **share) is None


def _cell(name):
    bench = copy.deepcopy(spec.load_benchmark())
    bench["paths"] = ["."]
    conf, = [c for c in bench["configs"] if c["name"] == REHEARSED[name][0]]
    conf["file"] = REHEARSED[name][1]
    return spec.Cell(bench, name, REHEARSAL)


@pytest.mark.parametrize("name", sorted(REHEARSED))
def test_a_traced_rehearsal_reports_the_four_and_a_sound_window_reads_zero(
        name, caplog):
    import jax

    cell, suffix = _cell(name), REHEARSED[name][2]
    args = argparse.Namespace(workload=name, seed=2**31 + 50, seconds=2.0,
                              trace=1)
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.serving.decode"):
        result = json.loads(json.dumps(bench_run.measure(
            cell, args, jax.devices()[:1], device.CompileClock(),
            time.perf_counter())))
    assert result["correct"] is True and result["failed"] == 0
    for base, (unit, _) in BASES.items():
        assert result["metrics"][base + suffix]["unit"] == unit
    # exactly 0.0 — unless a loaded CPU held a leg for 0.25 s somewhere
    # in the run, and then the log says so
    stalled = any(r.getMessage().startswith("mx.stall ")
                  for r in caplog.records)
    for base in ("batcher.stall_share", "batcher.stall_wait_share",
                 "batcher.stalls_per_min"):
        value = result["metrics"][base + suffix]["value"]
        assert value >= 0.0 if stalled else value == 0.0
    # the batcher's thread is under no span for some of every pass, and
    # for less than all of it
    assert 0.0 < result["metrics"]["batcher.unspanned_share" + suffix][
        "value"] < 100.0
    # the outside-timed twins stay beside them
    for leg in ("pack", "dispatch", "device_wait", "d2h", "emit"):
        assert result["metrics"]["batcher.%s_ms%s" % (leg, suffix)][
            "value"] > 0.0
