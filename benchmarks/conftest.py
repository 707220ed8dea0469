"""Rehearsal entries that a cell brings as a file of its own.

`tests/test_rehearsal.py` and `tests/test_rehearsal_spans.py` run every
cell of the real BENCHMARK.json at a tiny size, and look the tiny
configuration and mix up in `tests/data/rehearsal/cells.json`.  That file
is the benchmark's own: a PR that adds a cell may add files, not edit it.
So such a PR puts its `configs` and `workloads` rows in
`tests/data/rehearsal/cells.d/<cell>.json`, and for a file `<name>.json`
that has a directory `<name>.d` beside it `spec.load_benchmark` returns
the rows of every file in there appended, list by list.  A `benchmark` PR
folds them into `cells.json` and deletes them.
"""
import json
import os

from benchmarks.harness import spec

_load_benchmark = spec.load_benchmark


def load_benchmark(root=spec.ROOT, name="BENCHMARK.json"):
    bench = _load_benchmark(root, name)
    extra = os.path.join(root, os.path.splitext(name)[0] + ".d")
    if os.path.isdir(extra):
        for part in sorted(os.listdir(extra)):
            with open(os.path.join(extra, part)) as f:
                for key, rows in json.load(f).items():
                    bench[key] = bench[key] + rows
    return bench


spec.load_benchmark = load_benchmark
