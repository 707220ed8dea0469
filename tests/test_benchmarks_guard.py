"""Tier-1 runs the benchmark's own tests (`benchmarks/tests/`), a case
per file.

Each file runs in a subprocess: the two suites force different virtual
device counts in their `conftest.py` (8 here, 4 there) before jax is
imported, so they cannot share an interpreter.  A case per file so that
a break names its file.
"""
import glob
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILES = sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(ROOT, "benchmarks", "tests", "test_*.py")))
LIMIT_S = 300


def test_the_glob_finds_the_benchmark_tests():
    """An empty list would parametrise the guard away in silence."""
    assert FILES


@pytest.mark.parametrize("path", FILES, ids=[
    os.path.splitext(os.path.basename(p))[0] for p in FILES])
def test_benchmark_test_file_passes(path):
    env = dict(os.environ)
    # this suite's conftest forced 8 devices; benchmarks/tests/conftest.py
    # appends its own count, and only one such flag may stand
    env["XLA_FLAGS"] = re.sub(
        r"\s*--xla_force_host_platform_device_count=\d+", "",
        env.get("XLA_FLAGS", ""))
    try:
        r = subprocess.run(
            [sys.executable, "-m", "pytest", path, "-q",
             "-p", "no:cacheprovider"],
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
