"""BENCHMARK.json against the contract's letter, and against the files
it names."""
import importlib
import json
import os
import re

import pytest

from benchmarks.harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCH = spec.load_benchmark()
METRICS = ([("end_to_end", m) for m in BENCH["end_to_end"]]
           + [("per_layer", m) for m in BENCH["per_layer"]])


def test_top_level_keys_are_exactly_the_contracts():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) < 65536


@pytest.mark.parametrize("kind,metric", METRICS,
                         ids=[m["name"] for _, m in METRICS])
def test_metric_entry_and_its_definition(kind, metric):
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"bound"} if kind == "end_to_end" else {"layer", "moves"}
    assert set(metric) <= allowed
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    if kind == "end_to_end":
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    definition = spec.metric_definition(metric["name"])
    reader = importlib.import_module(
        "benchmarks.readers." + definition["reader"])
    assert callable(reader.read)


def test_names_are_unique_and_well_formed():
    for key in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[key]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    names = [m["name"] for _, m in METRICS]
    assert len(set(names)) == len(names)
    for w in BENCH["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("cell_name",
                         [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_and_reports_what_the_contract_asks(cell_name):
    cell = spec.Cell(BENCH, cell_name)
    importlib.import_module("benchmarks.jobs." + cell.traffic["job"])
    importlib.import_module("benchmarks.families." + cell.config["family"])
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    # a per-layer metric is reported only where the metric it moves is
    assert all(m["moves"] in e2e for m in cell.per_layer)
    # the configuration file holds what `reduced` says it holds
    conf = [c for c in BENCH["configs"] if c["name"] == cell.config_name][0]
    assert conf["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    assert conf["reduced"] == cell.config["reduced"]


def test_every_config_is_used_and_every_file_sits_under_paths():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for f in files:
        with open(os.path.join(spec.ROOT, f)) as fh:
            json.load(fh)


def test_an_open_loop_sends_whole_blocks_of_requests():
    """The generator makes lengths in stratified blocks of BLOCK: only a
    count that is a multiple of it offers every seed the same work
    (test_loadgen.py), so an open-loop mix's rate is chosen to give one."""
    from benchmarks.harness import loadgen

    open_loops = 0
    for w in BENCH["workloads"]:
        arrivals = spec.Cell(BENCH, w["name"]).traffic.get("arrivals", {})
        if arrivals.get("process") == "open":
            open_loops += 1
            n = len(loadgen.arrival_times(arrivals["rate_per_s"],
                                          BENCH["run_seconds"], seed=0))
            assert n % loadgen.BLOCK == 0, (w["traffic"], n)
    assert open_loops
