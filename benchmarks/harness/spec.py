"""BENCHMARK.json and the data files it names.

Everything that belongs to one cell, configuration, traffic mix or
metric is a file found by its name here; nothing in the harness
branches on a name.
"""
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


class SpecError(RuntimeError):
    pass


def _load(path):
    with open(path) as f:
        return json.load(f)


def load_benchmark(root=ROOT, name="BENCHMARK.json"):
    return _load(os.path.join(root, name))


class Cell:
    """One entry of `workloads`, with its configuration, traffic mix and
    the metric entries that apply to it."""

    def __init__(self, bench, name, root=ROOT):
        rows = [w for w in bench["workloads"] if w["name"] == name]
        if not rows:
            raise SpecError("no workload %r in BENCHMARK.json (have: %s)" % (
                name, ", ".join(w["name"] for w in bench["workloads"])))
        row = rows[0]
        self.name = name
        self.chips = int(row["chips"])
        conf = [c for c in bench["configs"] if c["name"] == row["config"]]
        if not conf:
            raise SpecError("workload %r names config %r, which is not in "
                            "`configs`" % (name, row["config"]))
        self.config_name = row["config"]
        self.config = _load(os.path.join(root, conf[0]["file"]))
        self.traffic_name = row["traffic"]
        self.traffic = _load(os.path.join(
            root, bench["paths"][0], "traffic", row["traffic"] + ".json"))
        self.end_to_end = _applies(bench["end_to_end"], name)
        self.per_layer = _applies(bench["per_layer"], name)


def _applies(entries, cell_name):
    return [m for m in entries
            if "workloads" not in m or cell_name in m["workloads"]]


def metric_definition(name, directory=os.path.join(HERE, "metrics")):
    """The reader and its arguments for one metric: ``<name>.json``, or
    the file that lists the name under `also` (one reading that two
    cells report under two names, because a per-layer metric belongs to
    the end-to-end metric it moves)."""
    path = os.path.join(directory, name + ".json")
    if os.path.exists(path):
        return _load(path)
    for other in sorted(os.listdir(directory)):
        definition = _load(os.path.join(directory, other))
        if name in definition.get("also", ()):
            return definition
    raise SpecError("metric %r has no definition under %s" % (name, directory))
