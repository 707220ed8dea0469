"""The second half of `test_benchmarks_guard.py` (which says why there
are two files): the cells' own rehearsals and every cell's spans, a case
per file, on another worker."""
import pytest

from test_benchmarks_guard import LATER, MORE, REHEARSAL, ids, run_file


@pytest.mark.parametrize("path", MORE, ids=ids(MORE))
def test_benchmark_test_file_passes(path):
    run_file(path)


def test_the_later_cells_rehearsals_pass():
    """The second half of `test_rehearsal.py`'s cells (`LATER`): the first
    half runs on `test_benchmarks_guard.py`'s chain."""
    run_file(REHEARSAL, LATER)
