"""Telemetry — the framework-wide metrics registry.

The quantitative counterpart of the profiler's span lanes: where
profiler.py answers "when did this op run", telemetry answers "how much
— ops, bytes, seconds, occupancy — per component, per step".  The
reference brackets every engine op with SetOprStart/SetOprEnd
(reference src/engine/profiler.cc) and aggregates per-op rows in
Profiler::DumpProfile; this module generalizes those rows to counters,
gauges, and fixed-bucket histograms wired through every layer: engine
queue depth and worker busy time, io buffer occupancy and consumer
wait, executor dispatch latency / compile-cache traffic / H2D-D2H
bytes, kvstore push/pull, and per-step MFU at the module level.

Three sinks:

  * :func:`snapshot` — nested plain-dict view for tests and the benchmark;
  * a JSONL writer (:func:`flush`, path from ``MXTPU_TELEMETRY_FILE``)
    emitting one record per flush with monotonic step stamps, which
    ``tools/parse_log.py --telemetry`` renders as a table;
  * chrome-trace counter lanes: every :func:`set_gauge` while the
    profiler is running appends a ``"ph": "C"`` event, so queue depth
    and MFU render as counter lanes alongside the span lanes in
    ``profiler.dump_profile()`` output.

Cost discipline (the profiler's ``spans_active()`` contract): every
recording helper returns immediately when disabled, and HOT paths must
additionally guard the call itself behind :func:`enabled` so no
timestamping, formatting, or argument construction happens when
telemetry is off — mxlint check E004 enforces exactly that.  Telemetry
is ON by default (``MXTPU_TELEMETRY=0`` disables); unlike profiling it
is cheap enough to leave on, and the always-on registry is what the
benchmark's readers (benchmarks/readers/), Speedometer and the obs
plane report through.
"""
from __future__ import annotations

import json
import os as _os
import threading
import time
from bisect import bisect_left

__all__ = [
    "enabled", "set_enabled", "inc", "set_gauge", "observe",
    "observe_values", "attach_value_histogram", "ValueHistogram",
    "counter_value", "gauge_value", "histogram_moments",
    "histogram_quantile", "snapshot", "reset", "flush",
    "rank_suffixed", "note_retrace", "PEAK_FLOPS", "peak_flops",
    "flops_of_jaxpr",
    "TIME_BUCKETS", "BYTE_BUCKETS", "COUNT_BUCKETS",
]

# fixed bucket boundaries (seconds): half-decade exponential ladder from
# 10 us to 100 s — wide enough for one engine op and a whole K-block
TIME_BUCKETS = (1e-5, 3.16e-5, 1e-4, 3.16e-4, 1e-3, 3.16e-3, 1e-2,
                3.16e-2, 1e-1, 3.16e-1, 1.0, 3.16, 10.0, 31.6, 100.0)
# fixed bucket boundaries (bytes): decades from 1 KiB to 10 GiB
BYTE_BUCKETS = (2.0 ** 10, 2.0 ** 13, 2.0 ** 16, 2.0 ** 20, 2.0 ** 23,
                2.0 ** 26, 2.0 ** 30, 10.0 * 2.0 ** 30)
# fixed bucket boundaries (counts): powers of two from 1 to 1024 — sized
# for small integer distributions like lazy fused-chain lengths
COUNT_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0,
                 512.0, 1024.0)

_ENABLED = _os.environ.get("MXTPU_TELEMETRY", "1") not in ("0", "")
# RLock, as obs/memory._CENSUS_LOCK and for its reason: what runs under
# it allocates (snapshot's dicts), an allocation can trigger GC, and a
# collected NDArray's __del__ unbooks through set_gauge on this thread
_LOCK = threading.RLock()
_COUNTERS = {}
_GAUGES = {}
_HISTOGRAMS = {}
_FLUSH_SEQ = 0


def enabled():
    """Cheap hot-path check: is the registry recording?  Callers on hot
    paths (engine worker loop, per-step training code) must skip metric
    construction entirely when this is False — the profiler
    ``spans_active()`` discipline, enforced by mxlint E004."""
    return _ENABLED


def set_enabled(flag):
    """Turn recording on/off; returns the previous state (so tests can
    restore).  ``MXTPU_TELEMETRY=0`` sets the import-time default."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = bool(flag)
    return prev


class _Histogram:
    """Fixed-boundary histogram: PER-BUCKET (non-cumulative) counts
    keyed Prometheus-style (``le_<bound>`` … ``le_inf``, in boundary
    order) plus count/sum/min/max.  Unlike real Prometheus ``le``
    buckets the counts do NOT accumulate — ``sum(buckets) == count``
    (tools/parse_log.py's quantile math relies on this)."""

    __slots__ = ("boundaries", "bucket_counts", "count", "sum", "min", "max")

    def __init__(self, boundaries):
        self.boundaries = tuple(boundaries)
        self.bucket_counts = [0] * (len(self.boundaries) + 1)
        self.count = 0
        self.sum = 0.0
        self.min = None
        self.max = None

    def observe(self, value):
        value = float(value)
        # the first boundary the value does not exceed (le_inf past all)
        self.bucket_counts[bisect_left(self.boundaries, value)] += 1
        self.count += 1
        self.sum += value
        if self.min is None or value < self.min:
            self.min = value
        if self.max is None or value > self.max:
            self.max = value

    def as_dict(self):
        return {
            "count": self.count, "sum": self.sum,
            "min": self.min, "max": self.max,
            "buckets": {
                ("le_%g" % b): c
                for b, c in zip(self.boundaries, self.bucket_counts)
            } | {"le_inf": self.bucket_counts[-1]},
        }


def inc(name, n=1):
    """Increment counter `name` by `n` (monotonic; floats allowed for
    byte totals)."""
    if not _ENABLED:
        return
    with _LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + n


def set_gauge(name, value):
    """Set gauge `name`; while the profiler is running the sample is
    also appended to the trace as a chrome counter event, so every
    gauge doubles as a counter lane in the dumped profile."""
    if not _ENABLED:
        return
    value = float(value)
    with _LOCK:
        _GAUGES[name] = value
    from . import profiler

    if profiler.spans_active():
        profiler.record_counter(name, value)


def observe(name, value, buckets=TIME_BUCKETS):
    """Record `value` into histogram `name` (created on first use with
    the given fixed `buckets`; later calls reuse the existing
    boundaries)."""
    if not _ENABLED:
        return
    with _LOCK:
        h = _HISTOGRAMS.get(name)
        if h is None:
            h = _HISTOGRAMS[name] = _Histogram(buckets)
        h.observe(value)


class ValueHistogram:
    """VALUE-RANGE histogram — the distribution recorder the fixed
    TIME/BYTE/COUNT ladders cannot be: those ladders are tuned for
    latencies and byte totals, while activation magnitudes (the int8
    calibration use, mxnet_tpu/quant/calib.py) span unknown,
    model-dependent ranges.

    Two bucket modes:

      * **caller-supplied** — pass explicit ``boundaries`` (any sorted
        upper edges); behaves like the fixed ladders plus an overflow
        bucket, but over the caller's range.
      * **auto-ranging** (default) — ``n_buckets`` equal-width buckets
        over ``[0, hi]`` where ``hi`` starts at the first batch's max
        and DOUBLES (merging adjacent bucket pairs, counts preserved)
        whenever a later value exceeds it, so one pass over data of
        unknown magnitude still yields a usable distribution.  Auto
        mode records magnitudes: negative values clip to 0 (record
        ``abs(x)`` for signed data).

    Bulk ingestion (:meth:`observe_array`) bins a whole numpy array per
    call — a calibration pass feeds multi-megabyte activation tensors,
    so per-element Python dispatch is off the table.  ``as_dict()``
    emits the same count/sum/min/max/buckets schema as the fixed-bucket
    histograms (non-cumulative ``le_*`` counts summing to ``count``),
    so snapshot/flush/parse_log render it unchanged; :meth:`quantile`
    adds within-bucket linear interpolation for the percentile
    calibration mode."""

    __slots__ = ("n", "hi", "counts", "boundaries", "count", "sum",
                 "min", "max", "_lock")

    def __init__(self, n_buckets=64, boundaries=None):
        # per-histogram lock: binning is O(array) and must NOT ride the
        # registry-wide _LOCK (a multi-MB calibration observe would
        # stall every serving thread's telemetry.inc for its duration)
        self._lock = threading.Lock()
        if boundaries is not None:
            bs = tuple(float(b) for b in boundaries)
            if not bs or list(bs) != sorted(bs):
                raise ValueError("boundaries must be a non-empty sorted "
                                 "sequence, got %r" % (boundaries,))
            self.boundaries = bs
            self.counts = [0] * (len(bs) + 1)   # + overflow
            self.n = None
            self.hi = None
        else:
            n = int(n_buckets)
            if n < 2 or n % 2:
                raise ValueError("n_buckets must be an even int >= 2 "
                                 "(pair-merge range doubling), got %r"
                                 % (n_buckets,))
            self.boundaries = None
            self.n = n
            self.hi = 0.0
            self.counts = [0] * n
        self.count = 0
        self.sum = 0.0
        self.min = None
        self.max = None

    def observe(self, value):
        self.observe_array((value,))

    def observe_array(self, values):
        import numpy as _np

        a = _np.asarray(values, dtype=_np.float64).reshape(-1)
        if a.size == 0:
            return
        with self._lock:
            self._observe_locked(a, _np)

    def _observe_locked(self, a, _np):
        lo, hi = float(a.min()), float(a.max())
        self.count += int(a.size)
        self.sum += float(a.sum())
        self.min = lo if self.min is None else min(self.min, lo)
        self.max = hi if self.max is None else max(self.max, hi)
        if self.boundaries is not None:
            idx = _np.searchsorted(_np.asarray(self.boundaries), a,
                                   side="left")
            for i, c in enumerate(_np.bincount(idx,
                                               minlength=len(self.counts))):
                self.counts[i] += int(c)
            return
        a = _np.maximum(a, 0.0)
        m = float(a.max())
        if self.hi <= 0.0:
            self.hi = m if m > 0.0 else 1.0
        while m > self.hi:
            # double the range: bucket k of the new width covers exactly
            # old buckets 2k and 2k+1, so the merge loses no counts and
            # keeps the widths equal
            c = self.counts
            half = [c[2 * i] + c[2 * i + 1] for i in range(self.n // 2)]
            self.counts = half + [0] * (self.n - self.n // 2)
            self.hi *= 2.0
        width = self.hi / self.n
        idx = _np.clip(_np.ceil(a / width).astype(_np.int64) - 1, 0,
                       self.n - 1)
        for i, c in enumerate(_np.bincount(idx, minlength=self.n)):
            self.counts[i] += int(c)

    def _edges(self):
        if self.boundaries is not None:
            return self.boundaries
        width = (self.hi or 1.0) / self.n
        return tuple(width * (i + 1) for i in range(self.n))

    def quantile(self, q):
        """Value at quantile ``q`` (0..1), linearly interpolated inside
        the containing bucket; None when empty.  Clamped to the
        observed max so a sparse top bucket cannot report a value no
        observation reached."""
        with self._lock:
            return self._quantile_locked(q)

    def _quantile_locked(self, q):
        if not self.count:
            return None
        target = q * self.count
        edges = self._edges()
        seen = 0.0
        prev = 0.0
        for i, c in enumerate(self.counts):
            if i >= len(edges):      # explicit-mode overflow bucket
                return self.max
            if c and seen + c >= target:
                frac = (target - seen) / c
                val = prev + frac * (edges[i] - prev)
                return min(val, self.max) if self.max is not None else val
            seen += c
            prev = edges[i]
        return self.max

    def fraction_above(self, value):
        """Approximate fraction of observations strictly above `value`
        (linear interpolation inside the containing bucket) — the
        clip-rate readout for a percentile-capped calibration."""
        with self._lock:
            return self._fraction_above_locked(value)

    def _fraction_above_locked(self, value):
        if not self.count:
            return 0.0
        value = float(value)
        edges = self._edges()
        above = 0.0
        prev = 0.0
        for i, c in enumerate(self.counts):
            if i >= len(edges):          # explicit-mode overflow bucket
                above += c
                break
            hi = edges[i]
            if value <= prev:
                above += c
            elif value < hi:
                above += c * (hi - value) / (hi - prev)
            prev = hi
        return above / self.count

    def as_dict(self):
        with self._lock:
            edges = self._edges()
            buckets = {("le_%g" % b): c
                       for b, c in zip(edges, self.counts)}
            buckets["le_inf"] = (self.counts[len(edges)]
                                 if self.boundaries is not None else 0)
            return {
                "count": self.count, "sum": self.sum,
                "min": self.min, "max": self.max,
                "buckets": buckets,
            }


def observe_values(name, values, n_buckets=64, boundaries=None):
    """Bulk-record a numpy array (or scalar) into the VALUE-RANGE
    histogram `name` (created on first use as a :class:`ValueHistogram`
    with the given ``n_buckets`` / explicit ``boundaries``; later calls
    reuse the existing instance and ignore the creation arguments).
    The E004 hot-path contract applies exactly as for :func:`observe`:
    guard the call (and the array construction feeding it) behind
    :func:`enabled`.  The registry lock covers only the lookup; the
    O(array) binning runs under the histogram's OWN lock, so a bulk
    observe never stalls unrelated telemetry calls."""
    if not _ENABLED:
        return
    with _LOCK:
        h = _HISTOGRAMS.get(name)
        if h is None:
            h = _HISTOGRAMS[name] = ValueHistogram(n_buckets=n_buckets,
                                                   boundaries=boundaries)
        elif not isinstance(h, ValueHistogram):
            raise ValueError(
                "histogram %r already exists with fixed ladder buckets; "
                "observe_values needs a ValueHistogram (pick a distinct "
                "metric name)" % name)
    h.observe_array(values)


def attach_value_histogram(name, hist):
    """Expose a caller-OWNED :class:`ValueHistogram` under `name` in the
    registry (shared object, nothing copied), so snapshots and flushes
    see the same distribution the caller keeps binning into — the int8
    calibrator owns its histograms for the percentile/cap math and
    attaches them rather than binning every activation tensor twice.
    No-op when disabled (the registry stays untouched); replacing an
    existing fixed-ladder name is refused like :func:`observe_values`.
    Same E004 guard contract as every recording call."""
    if not _ENABLED:
        return
    if not isinstance(hist, ValueHistogram):
        raise ValueError("attach_value_histogram needs a ValueHistogram, "
                         "got %r" % type(hist).__name__)
    with _LOCK:
        h = _HISTOGRAMS.get(name)
        if h is not None and not isinstance(h, ValueHistogram):
            raise ValueError(
                "histogram %r already exists with fixed ladder buckets; "
                "pick a distinct metric name" % name)
        _HISTOGRAMS[name] = hist


# ----------------------------------------------------------------------
# retrace monitor — the runtime half of mxlint W104.  Every compiled-
# program cache in the framework (the executor's jit caches, the lazy
# fusion cache) calls note_retrace on a cache MISS with the signature
# it is about to compile; a site that keeps compiling NEW signatures
# is a retrace storm — steps look slow, nothing errors.  The monitor
# counts churn per cache site (``trace.retraces`` total +
# ``trace.retraces.<site>``) and, past ``MXTPU_RETRACE_WARN=N``
# distinct signatures at one site, logs the offending signature delta
# (previous vs new) so the unstable static arg is named, not guessed.
# ----------------------------------------------------------------------

_RETRACE_SEEN = {}    # (site, scope) -> set of signature reprs (bounded)
_RETRACE_LAST = {}    # (site, scope) -> last signature repr
_RETRACE_SEEN_CAP = 64    # signatures retained per site
_RETRACE_KEYS_CAP = 512   # (site, scope) keys retained process-wide: a
# server rebinding executors forever must not grow monitor state
# without bound — a wholesale clear (a burst of uncounted churn) beats
# leaking; the counters themselves are never cleared
_SIG_REPR_MAX = 400


def _retrace_warn_threshold():
    raw = _os.environ.get("MXTPU_RETRACE_WARN", "")
    try:
        return int(raw) if raw else 0
    except ValueError:
        return 0


def note_retrace(site, signature, scope=None):
    """Record one compile-cache miss at `site` (cold path — called
    only when a compile is about to happen, never per dispatch).

    The FIRST signature a (site, scope) compiles is not a retrace;
    every later distinct signature counts one.  `scope` separates
    same-named sites with independent caches (the executor passes
    ``id(self)``: each bound executor owns its jit caches, so churn is
    judged within one binding, not across models).  Returns True when
    the miss was a retrace."""
    if not _ENABLED:
        return False
    sig = repr(signature)
    if len(sig) > _SIG_REPR_MAX:
        sig = sig[:_SIG_REPR_MAX] + "...<truncated>"
    key = (site, scope)
    with _LOCK:
        seen = _RETRACE_SEEN.get(key)
        if seen is None:
            if len(_RETRACE_SEEN) >= _RETRACE_KEYS_CAP:
                _RETRACE_SEEN.clear()
                _RETRACE_LAST.clear()
            seen = _RETRACE_SEEN[key] = set()
        first = not seen
        known = sig in seen
        prev = _RETRACE_LAST.get(key)
        if len(seen) < _RETRACE_SEEN_CAP:
            seen.add(sig)
        _RETRACE_LAST[key] = sig
        n_distinct = len(seen)
    if first or known:
        return False
    inc("trace.retraces")
    inc("trace.retraces.%s" % site)
    warn_at = _retrace_warn_threshold()
    if warn_at > 0 and n_distinct > warn_at:
        import logging

        logging.getLogger("mxnet_tpu.telemetry").warning(
            "retrace storm at cache site %r: %d distinct signatures "
            "(MXTPU_RETRACE_WARN=%d); signature delta:\n  was: %s\n  "
            "now: %s\nA churning signature usually means a float/"
            "unstable static arg that should be a traced operand "
            "(mxlint W104)", site, n_distinct, warn_at, prev, sig)
    return True


def counter_value(name, default=0):
    with _LOCK:
        return _COUNTERS.get(name, default)


def gauge_value(name, default=None):
    with _LOCK:
        return _GAUGES.get(name, default)


def histogram_moments(name):
    """Cheap ``(count, sum)`` point read of one histogram — probe
    paths (the router agent's per-HEALTH serving extract) read two
    moments without the full-registry deep copy snapshot() takes."""
    with _LOCK:
        h = _HISTOGRAMS.get(name)
        return (0, 0.0) if h is None else (h.count, h.sum)


def histogram_quantile(name, q):
    """Point-read quantile of one histogram without a full snapshot —
    upper-bucket-boundary convention, the SAME math as
    ``tools/parse_log.py`` (the probe and the rendered table must
    never disagree on what p99 means).  None when the histogram does
    not exist or is empty.  Value-range histograms answer through
    their own interpolated :meth:`ValueHistogram.quantile`."""
    with _LOCK:
        h = _HISTOGRAMS.get(name)
        if h is None:
            return None
        if isinstance(h, ValueHistogram):
            # per-histogram lock is a leaf under the registry lock (the
            # observe path takes them in the same order)
            return h.quantile(q)
        if not h.count:
            return None
        target = q * h.count
        seen = 0
        for b, c in zip(h.boundaries, h.bucket_counts):
            seen += c
            if seen >= target:
                return float(b)
        return h.max


def snapshot():
    """Nested plain-dict view of the whole registry — the test and
    benchmark sink.  Stable schema: top-level ``counters`` / ``gauges`` /
    ``histograms``; histogram values carry count/sum/min/max/buckets."""
    with _LOCK:
        return {
            "counters": dict(_COUNTERS),
            "gauges": dict(_GAUGES),
            "histograms": {k: h.as_dict() for k, h in _HISTOGRAMS.items()},
        }


def reset():
    """Clear every metric (tests; a long-lived server would flush+reset
    per reporting window)."""
    global _FLUSH_SEQ
    with _LOCK:
        _COUNTERS.clear()
        _GAUGES.clear()
        _HISTOGRAMS.clear()
        _RETRACE_SEEN.clear()
        _RETRACE_LAST.clear()
        _FLUSH_SEQ = 0


def rank_suffixed(path):
    """Per-rank sink path: ``path`` + ``.r<rank>`` when the launcher
    exported ``MXTPU_PROCESS_ID`` (tools/launch.py --local-spmd),
    unchanged otherwise.

    N ranks of a multi-process job inherit the SAME
    ``MXTPU_TELEMETRY_FILE`` / profiler filename from the launcher
    environment; N processes appending to one file interleave partial
    lines into a corrupt sink.  Every file sink (telemetry.flush,
    profiler.dump_profile) routes its path through this helper, and
    the downstream tools glob the suffix back up
    (``tools/obs_stitch.py`` merges ``trace.json.r*``)."""
    if not path:
        return path
    rank = _os.environ.get("MXTPU_PROCESS_ID", "")
    if rank == "":
        return path
    return "%s.r%s" % (path, rank)


def flush(path=None, extra=None):
    """Append ONE JSONL record of the current registry state to `path`
    (default ``MXTPU_TELEMETRY_FILE``; no-op when neither is set).

    Each record carries a monotonic flush sequence number, a monotonic
    clock stamp, and the global training-step counter
    (``module.steps``), so downstream tooling can order and diff
    records without trusting wall clocks.  ``tools/parse_log.py
    --telemetry`` reads this format back.  In a multi-process launch
    the path is auto-suffixed per rank (:func:`rank_suffixed`).
    Returns the record dict (or None when no sink is configured)."""
    global _FLUSH_SEQ
    if not _ENABLED:
        return None
    path = rank_suffixed(path or _os.environ.get("MXTPU_TELEMETRY_FILE", ""))
    if not path:
        return None
    with _LOCK:
        _FLUSH_SEQ += 1
        record = {
            "flush_seq": _FLUSH_SEQ,
            "monotonic_s": time.monotonic(),
            "step": _COUNTERS.get("module.steps", 0),
            "counters": dict(_COUNTERS),
            "gauges": dict(_GAUGES),
            "histograms": {k: h.as_dict() for k, h in _HISTOGRAMS.items()},
        }
        if extra:
            record.update(extra)
        # write under the lock: concurrent flushes (epoch-end + a user
        # reporter thread) must not interleave partial lines or land
        # flush_seq N+1 before N in the file
        with open(path, "a") as f:
            f.write(json.dumps(record) + "\n")
    return record


# ----------------------------------------------------------------------
# MFU support: hardware peak + an analytic FLOP counter over jaxprs
# ----------------------------------------------------------------------

# Published dense bf16 peak FLOP/s per chip (MAC=2 convention), keyed by
# jax ``device_kind`` — the ONE table MFU math divides by.  A device that
# is not here has no peak: nothing substitutes another chip's.
#   "TPU v5 lite": Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16
PEAK_FLOPS = {"TPU v5 lite": 197e12}


def peak_flops(device=None):
    """Peak FLOP/s for MFU math on `device` (default: JAX's default
    device) — ``MXTPU_PEAK_FLOPS`` when set to a positive number, else
    the :data:`PEAK_FLOPS` entry for its ``device_kind``, else None: an
    unknown device has no MFU.  A malformed override is warned about
    ONCE and ignored — a typo'd env var must not kill the training loop
    from a telemetry call."""
    raw = _os.environ.get("MXTPU_PEAK_FLOPS", "")
    if raw:
        try:
            val = float(raw)
            if val > 0:
                return val
        except ValueError:
            if raw not in _BAD_PEAK_WARNED:
                _BAD_PEAK_WARNED.add(raw)
                import warnings

                warnings.warn("MXTPU_PEAK_FLOPS=%r is not a number; "
                              "ignored" % raw)
    if device is None:
        from .context import default_device

        device = default_device()
    return PEAK_FLOPS.get(device.device_kind)


_BAD_PEAK_WARNED = set()


def _prod(xs):
    out = 1
    for x in xs:
        out *= int(x)
    return out


def _dot_flops(eqn):
    """2 * batch * M * N * K from the operand shapes and the contraction
    spec (MAC=2 convention, matching tools/tpu_constants.py)."""
    (lc, rc), (lb, _rb) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    batch = _prod(lhs[d] for d in lb)
    contract = _prod(lhs[d] for d in lc)
    lhs_free = _prod(lhs[d] for d in range(len(lhs)) if d not in set(lc) | set(lb))
    rhs_free = _prod(rhs[d] for d in range(len(rhs)) if d not in set(rc) | set(_rb))
    return 2.0 * batch * contract * lhs_free * rhs_free


def _conv_flops(eqn):
    """2 * |output| * kernel_spatial * in_channels_per_group."""
    out = eqn.outvars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    dn = eqn.params["dimension_numbers"]
    rhs_spec = dn.rhs_spec  # (out_ch, in_ch/group, *spatial)
    kernel_spatial = _prod(rhs[d] for d in rhs_spec[2:])
    in_per_group = rhs[rhs_spec[1]]
    return 2.0 * _prod(out) * kernel_spatial * in_per_group


def flops_of_jaxpr(jaxpr):
    """Analytic FLOP count of a (closed or open) jaxpr: MXU work only
    (dot_general + conv_general_dilated — the terms that dominate MFU;
    elementwise ops are bandwidth-bound and excluded by convention,
    same as XLA's cost analysis headline number).  Recurses into call
    primitives; a scan body is multiplied by its trip count, cond
    branches contribute their max.  Pure tracing arithmetic — never
    runs device code."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    total = 0.0
    for eqn in inner.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            total += _dot_flops(eqn)
        elif name == "conv_general_dilated":
            total += _conv_flops(eqn)
        elif name == "cond":
            branches = eqn.params.get("branches", ())
            if branches:
                total += max(flops_of_jaxpr(b) for b in branches)
        else:
            mult = eqn.params.get("length", 1) if name == "scan" else 1
            for v in eqn.params.values():
                total += mult * _flops_of_param(v)
    return total


def _flops_of_param(v):
    """FLOPs of any jaxpr(s) hiding in one eqn param value."""
    if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
        return flops_of_jaxpr(v)
    if isinstance(v, (tuple, list)):
        return sum(_flops_of_param(x) for x in v)
    return 0.0
