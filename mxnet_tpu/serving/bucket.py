"""Shape bucketing: the ladder of batch sizes the batcher compiles for.

XLA compiles one executable per input shape, so serving every observed
batch size verbatim would compile O(max_batch) programs per tenant and
pay a multi-second compile on the first request of each new size — the
classic shape-churn failure.  The ladder (vLLM-style bucketing, the
serving analog of rnn.BucketSentenceIter's sequence buckets) rounds
every fill UP to the nearest bucket, pads the tail slots with zeros,
and masks the padding back out of the returned outputs, trading
``(bucket - n) / bucket`` wasted device work for an O(len(ladder))
bound on compiled programs that are each reused forever after.
"""
from __future__ import annotations

import numpy as _np

from ..base import MXNetError

__all__ = ["bucket_ladder", "choose_bucket", "pad_rows"]


def bucket_ladder(max_batch, spec=""):
    """The sorted batch-bucket ladder: `spec` is the comma-separated
    ladder ``ModelServer(buckets=)`` was given; empty means powers of
    two up to (and always including) `max_batch`.  Buckets above
    `max_batch` are rejected rather than clamped — a silent clamp would
    hide a config contradiction."""
    max_batch = int(max_batch)
    if max_batch < 1:
        raise MXNetError("max_batch must be >= 1, got %d" % max_batch)
    if spec:
        try:
            buckets = sorted({int(tok) for tok in spec.split(",") if tok.strip()})
        except ValueError:
            raise MXNetError("bucket ladder %r is not a comma-"
                             "separated int list" % spec)
        if not buckets or buckets[0] < 1:
            raise MXNetError("bucket ladder %r must be positive ints" % spec)
        if buckets[-1] > max_batch:
            raise MXNetError("bucket %d exceeds max_batch=%d"
                             % (buckets[-1], max_batch))
        if buckets[-1] != max_batch:
            buckets.append(max_batch)
        return buckets
    buckets, b = [], 1
    while b < max_batch:
        buckets.append(b)
        b *= 2
    buckets.append(max_batch)
    return buckets


def choose_bucket(ladder, n):
    """Smallest bucket holding `n` requests (callers cap n at the top
    bucket before packing)."""
    for b in ladder:
        if n <= b:
            return b
    return ladder[-1]


def pad_rows(rows, bucket, sample_shape, dtype):
    """Stack `rows` (sample-shaped arrays) into a (bucket, *sample)
    batch, zero-padding the unfilled tail slots.  Shape mismatches
    raise per-row so the failing REQUEST is identifiable, not just the
    failing fill."""
    out = _np.zeros((bucket,) + tuple(sample_shape), dtype=dtype)
    for i, row in enumerate(rows):
        arr = _np.asarray(row, dtype=dtype)
        if tuple(arr.shape) != tuple(sample_shape):
            raise MXNetError(
                "request row %d has shape %s, expected the tenant's "
                "sample shape %s (submit() takes UNBATCHED samples; the "
                "batcher owns the batch axis)"
                % (i, tuple(arr.shape), tuple(sample_shape)))
        out[i] = arr
    return out
