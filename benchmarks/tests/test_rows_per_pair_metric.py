"""`moe.rows_per_pair` (PR 55): the rows a held range's expert layers
gathered a pair they were owed — `moe.pair_rows` over `moe.pairs`.  The
definition file, the REAL BENCHMARK.json's entry and the rehearsal datum
that brings the same entry to `data/rehearsal/cells.json`, read on made-up
windows; `test_rehearsal.py` holds the six cells' traced rehearsals to the
name, as it does every listed metric."""
import json
import os

import pytest

from benchmarks.harness import spec, window
from benchmarks.readers import ratio

REHEARSAL = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "data", "rehearsal")
HELD_CELLS = ["trinitymini_reason_c16", "qwen3next_batch_c32",
              "mistralsmall4_reason_c32", "dots3note_longdoc_c8",
              "glm5_mtp_reason_c8", "granite4hsmall_chat_c16"]


def _window(counters):
    w = window.Window()
    w.before = {"counters": {}, "histograms": {}}
    w.after = {"counters": counters, "histograms": {}}
    return w


def test_the_entry_lists_the_cells_that_hold_a_range_of_their_experts():
    bench = spec.load_benchmark()
    entry, = [m for m in bench["per_layer"]
              if m["name"] == "moe.rows_per_pair"]
    assert entry == {"name": "moe.rows_per_pair", "unit": "rows",
                     "better": "lower", "source": "program_counter",
                     "layer": "expert layer", "moves": "gen_tok_per_s",
                     "workloads": HELD_CELLS}
    assert bench["per_layer"][-1] == entry      # appended, nothing moved
    moved, = [m for m in bench["end_to_end"] if m["name"] == "gen_tok_per_s"]
    assert set(HELD_CELLS) <= set(moved["workloads"])
    # the cells whose configuration states a held range, and no other
    held = []
    for row in bench["workloads"]:
        conf, = [c for c in bench["configs"] if c["name"] == row["config"]]
        with open(os.path.join(spec.ROOT, conf["file"])) as f:
            if "held_experts" in json.load(f):
                held.append(row["name"])
    assert held == HELD_CELLS


def test_the_rehearsal_datum_brings_the_same_entry():
    with open(os.path.join(REHEARSAL, "cells.d",
                           "moe.rows_per_pair.json")) as f:
        datum = json.load(f)
    real, = [m for m in spec.load_benchmark()["per_layer"]
             if m["name"] == "moe.rows_per_pair"]
    assert datum == {"per_layer": [real]}
    tiny = spec.load_benchmark(REHEARSAL, "cells.json")
    assert real in tiny["per_layer"]
    assert set(HELD_CELLS) <= {w["name"] for w in tiny["workloads"]}


def test_rows_over_pairs_and_a_program_without_the_counter_reads_zero():
    definition = spec.metric_definition("moe.rows_per_pair")
    assert definition == {"reader": "ratio", "args": {
        "num": [{"counter": "moe.pair_rows"}],
        "den": [{"counter": "moe.pairs"}]}}
    args = definition["args"]
    # nine of 72 experts held, every pair's row gathered: 1 / held share
    assert ratio.read(_window({"moe.pair_rows": 5120, "moe.pairs": 640}),
                      **args) == pytest.approx(8.0)
    # the held pairs alone, one pass of 1,024 sorted rows
    assert ratio.read(_window({"moe.pair_rows": 1024, "moe.pairs": 640}),
                      **args) == pytest.approx(1.6)
    # this PR's parent books no rows: 0, not nothing
    assert ratio.read(_window({"moe.pairs": 640}), **args) == 0.0
    # no call of a routed program in the window: nothing to divide by
    assert ratio.read(_window({}), **args) is None
