"""The `q` quantile of one of the job's client-side series; nothing
below `min_samples` (a tail over a handful of samples is a maximum)."""
from ..harness import loadgen


def read(window, series, q, min_samples=1):
    values = window.series.get(series)
    if not values or len(values) < min_samples:
        return None
    return loadgen.quantile(values, q)
