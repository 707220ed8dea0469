"""A pytest plugin for the subprocesses of `test_benchmarks_guard*.py`:
each gets a profiler trace directory of its own.

`benchmarks/harness/common.py` traces into ONE directory of the checkout
(`TRACE_DIR`, `benchmarks/.run/trace`), which it empties before a trace
and reads back after it, so two traced rehearsals at once lose each
other's `.xplane.pb`.  With this plugin loaded (`-p tests.trace_dir_plugin`)
a process traces into a temporary directory, and the guard's two files
can run on two workers.  The benchmark's own command is not touched."""
import shutil
import tempfile


def pytest_configure(config):
    from benchmarks.harness import common

    common.TRACE_DIR = tempfile.mkdtemp(prefix="mx-bench-trace-")


def pytest_unconfigure(config):
    from benchmarks.harness import common

    shutil.rmtree(common.TRACE_DIR, ignore_errors=True)
