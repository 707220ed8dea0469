"""Tier-1 runs the benchmark's own tests (`benchmarks/tests/`), a case
per file.

Each file runs in a subprocess: the two suites force different virtual
device counts in their `conftest.py` (8 here, 4 there) before jax is
imported, so they cannot share an interpreter.  A case per file so that
a break names its file.

The files run one after the other, and all of them on one worker were
the longest chain of tier-1 (twelve minutes alone, while the other
workers had finished): this file keeps `test_rehearsal.py` — every cell
twice —, the files that rehearse nothing and five cells' (`HERE`), `test_benchmarks_guard_more.py`
runs the others through `run_file`.  Two traced rehearsals at once would
share one trace directory; `tests/trace_dir_plugin.py` gives each
subprocess its own.

`test_rehearsal.py` alone took 359 s of the 420 a file has with fifteen
cells (PR 64, two workers on an idle machine; 342 with fourteen), so it
runs as TWO cases, half the cells each by `-k` — the earlier cells and
the tests of no cell here, the `LATER` cells on the other chain — no
rehearsal dropped, no limit raised.
"""
import glob
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ALL = sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(ROOT, "benchmarks", "tests", "test_*.py")))
# the files of `test_benchmarks_guard_more.py`: a cell's own rehearsal, or
# every cell's spans — but for three that stay here to level the two chains
# (about nine minutes each, alone: `--durations`)
HERE = ("olmo_hybrid", "trinity_mini", "glm5_mtp", "qwen3_next",
        "dots3_note")
MORE = [p for p in ALL if p.endswith(("_cell.py", "_spans.py"))
        and not any(name in p for name in HERE)]
FILES = [p for p in ALL if p not in MORE]
# `test_rehearsal.py` runs every cell twice and grows by ~25 s a cell: 285 s
# alone with twelve cells, more beside five other workers
LIMIT_S = 420
REHEARSAL = os.path.join("benchmarks", "tests", "test_rehearsal.py")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    _CELLS = [w["name"] for w in json.load(_f)["workloads"]]
# the second half of the cells, as a `-k` expression of their names
LATER = " or ".join(_CELLS[len(_CELLS) // 2:])


def test_the_glob_finds_the_benchmark_tests():
    """An empty list would parametrise the guard away in silence."""
    assert FILES and MORE and sorted(FILES + MORE) == ALL


def ids(files):
    return [os.path.splitext(os.path.basename(p))[0] for p in files]


def run_file(path, keyword=None):
    """`path` through pytest in a subprocess; `keyword`: its `-k`."""
    env = dict(os.environ)
    # this suite's conftest forced 8 devices; benchmarks/tests/conftest.py
    # appends its own count, and only one such flag may stand
    env["XLA_FLAGS"] = re.sub(
        r"\s*--xla_force_host_platform_device_count=\d+", "",
        env.get("XLA_FLAGS", ""))
    try:
        r = subprocess.run(
            [sys.executable, "-m", "pytest", path, "-q",
             "-p", "no:cacheprovider", "-p", "tests.trace_dir_plugin"]
            + (["-k", keyword] if keyword else []),
            cwd=ROOT, env=env, capture_output=True, text=True,
            timeout=LIMIT_S)
    except subprocess.TimeoutExpired as e:
        out = e.stdout or ""
        if isinstance(out, bytes):
            out = out.decode(errors="replace")
        pytest.fail("%s did not finish in %d s:\n%s"
                    % (path, LIMIT_S, out[-6000:]))
    assert r.returncode == 0, \
        "%s: exit %d\n%s" % (path, r.returncode,
                             (r.stdout + r.stderr)[-6000:])


@pytest.mark.parametrize("path", FILES, ids=ids(FILES))
def test_benchmark_test_file_passes(path):
    # (`test_rehearsal`: the earlier cells and the tests of no cell)
    run_file(path, "not (%s)" % LATER if path == REHEARSAL else None)
