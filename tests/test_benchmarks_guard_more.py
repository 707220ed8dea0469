"""The second half of `test_benchmarks_guard.py` (which says why there
are two files): the cells' own rehearsals and every cell's spans, a case
per file, on another worker."""
import pytest

from test_benchmarks_guard import MORE, ids, run_file


@pytest.mark.parametrize("path", MORE, ids=ids(MORE))
def test_benchmark_test_file_passes(path):
    run_file(path)
