"""Request-scoped distributed tracing — one trace per serving request.

The flight recorder (obs/recorder.py) and the cluster aggregator
answer "which *rank* is slow"; this module answers "where did *this
request* spend its time".  A :class:`TraceContext` — a
``(trace_id, span_id, sampled)`` triple — is minted at
``Router.submit`` (and at ``ModelServer.submit`` for direct callers),
rides the SUBMIT/RESULT/RERROR wire frames as plain meta
(:func:`to_meta` / :func:`from_meta`), attaches to the serving
``Request``, and links into the fill span the batcher creates, so one
sampled request decomposes into named, contiguous segments::

    router_queue -> wire -> replica_queue -> batch_fill -> h2d
                 -> compute -> readback -> reply

(the router-side spans live in the router process's trace, the
replica-side spans in the replica's; ``tools/obs_stitch.py`` merges
them onto one clock-offset-aligned timeline — the offset is measured
NTP-style at the ReplicaAgent HELLO handshake, the obs/aggregate.py
recipe).

**Sampling is head-based**: ``MXTPU_TRACE_SAMPLE`` is the sampled
fraction (0 = tracing entirely off — the fast path books *nothing*,
not even a context object).  When tracing is armed, requests that end
in timeout/redispatch/error are recorded ALWAYS — an unsampled
request's failure still gets a ``request`` outcome span
(:func:`record_outcome` with ``force=True`` semantics), so every
failure is explained even at a 1e-4 sample rate.

**Cost discipline** is the telemetry/recorder contract: every helper
early-returns when off, and hot call sites must guard the call itself
behind :func:`enabled` (mxlint E004 covers ``tracing.record`` /
``record_outcome`` / ``record_event`` / ``flow`` exactly as it covers
``telemetry.inc``).

Two sinks:

  * a bounded in-process span buffer (``_CAP`` slots;
    :func:`spans` / :func:`reset`) — what tests and in-process
    consumers read;
  * the profiler chrome trace: while profiling is running every span
    also lands as a ``cat="trace"`` X event (args carry
    trace/span/parent ids) on a synthetic "requests (traced)" lane,
    plus chrome flow events (``ph: s/f``) binding the router-side and
    replica-side spans causally across the stitched processes.
"""
from __future__ import annotations

import os as _os
import random as _random
import threading
import time
from .. import locks

__all__ = ["TraceContext", "enabled", "sample_fraction", "set_sample",
           "new_trace", "to_meta", "from_meta", "record", "record_event",
           "record_outcome", "flow", "flow_id", "wall", "spans", "reset"]


def _env_fraction():
    raw = _os.environ.get("MXTPU_TRACE_SAMPLE", "")
    try:
        f = float(raw) if raw else 0.0
    except ValueError:
        f = 0.0
    return min(1.0, max(0.0, f))


_SAMPLE = _env_fraction()
_CAP = 4096
_LOCK = locks.lock("obs.tracing")
_SPANS = []          # bounded: the oldest _CAP spans are kept, then drop
_DROPPED = 0
# span ids: a per-process random base keeps ids unique across the
# router and N replica processes without coordination
_NEXT_ID = _random.getrandbits(46) << 16
# one conversion epoch per process: monotonic + _EPOCH = wall seconds.
# Captured once so every span's conversion is exactly consistent
# in-process (segments recorded from shared monotonic boundary stamps
# stay contiguous to the microsecond); cross-process alignment is the
# stitch tool's clock-offset job.
_EPOCH = time.time() - time.monotonic()
# synthetic chrome lane for request spans (outside the real-thread-id
# space, the data-service worker-lane recipe)
_TRACE_TID = 0x7A11
_LANE_NAMED = False


class TraceContext:
    """One request's identity on the wire: trace id (shared by every
    span of the request, across processes), this hop's span id (the
    parent of the segments recorded under it), and the head-based
    sampling verdict."""

    __slots__ = ("trace_id", "span_id", "sampled")

    def __init__(self, trace_id, span_id, sampled):
        self.trace_id = trace_id
        self.span_id = int(span_id)
        self.sampled = bool(sampled)

    def __repr__(self):
        return ("TraceContext(trace_id=%r, span_id=%d, sampled=%r)"
                % (self.trace_id, self.span_id, self.sampled))


def enabled():
    """Cheap hot-path check: is tracing armed at all?  Callers must
    skip context minting and every record call — including argument
    construction — entirely when this is False (the telemetry
    ``enabled()`` discipline, mxlint E004)."""
    return _SAMPLE > 0.0


def sample_fraction():
    return _SAMPLE


def set_sample(fraction):
    """Set the sampled fraction (tests); returns the
    previous value.  ``MXTPU_TRACE_SAMPLE`` sets the import-time
    default."""
    global _SAMPLE
    prev = _SAMPLE
    _SAMPLE = min(1.0, max(0.0, float(fraction)))
    return prev


def _next_span_id():
    global _NEXT_ID
    with _LOCK:
        _NEXT_ID += 1
        return _NEXT_ID


def new_trace(sampled=None):
    """Mint a root context for one request (head-based sampling unless
    `sampled` forces the verdict).  Books the sampling decision
    counters so ``parse_log --telemetry``'s ``trace_sampled`` column
    can state the sampled volume."""
    if sampled is None:
        sampled = _random.random() < _SAMPLE
    ctx = TraceContext("%016x" % _random.getrandbits(64),
                       _next_span_id(), sampled)
    from .. import telemetry

    if telemetry.enabled():
        telemetry.inc("trace.requests_sampled" if ctx.sampled
                      else "trace.requests_unsampled")
    return ctx


def to_meta(ctx):
    """Wire encoding (plain scalars — the repr/literal_eval meta
    contract of router/wire.py)."""
    return {"tid": ctx.trace_id, "sid": ctx.span_id,
            "sampled": 1 if ctx.sampled else 0}


def from_meta(meta):
    """Rebuild a context from wire meta (None-tolerant: a pre-trace
    router sends no ``trace`` key and the replica serves untraced)."""
    if not meta or "tid" not in meta:
        return None
    return TraceContext(meta["tid"], meta.get("sid", 0),
                        meta.get("sampled", 0))


def wall(t_mono):
    """This process's wall-clock seconds for a ``time.monotonic()``
    stamp (one shared epoch, so in-process conversions are exactly
    consistent)."""
    return t_mono + _EPOCH


def _book(rec):
    """Append one span record to the buffer + the profiler mirror."""
    global _DROPPED
    with _LOCK:
        if len(_SPANS) < _CAP:
            _SPANS.append(rec)
            dropped = False
        else:
            _DROPPED += 1
            dropped = True
    from .. import profiler, telemetry

    if telemetry.enabled():
        telemetry.inc("trace.spans")
        if dropped:
            telemetry.inc("trace.spans_dropped")
    if profiler.spans_active():
        global _LANE_NAMED
        if not _LANE_NAMED:
            _LANE_NAMED = True
            profiler.register_thread_name(_TRACE_TID, "requests (traced)")
        args = {"trace": rec["trace"], "span": rec["span"],
                "parent": rec["parent"]}
        if rec.get("attrs"):
            args.update(rec["attrs"])
        profiler.record_span(rec["name"], rec["t0_us"], rec["dur_us"],
                             cat="trace", tid=_TRACE_TID, args=args)


def record(ctx, name, t0, t1, parent=None, wall_time=False, **attrs):
    """Record one named segment of a sampled request.

    `t0`/`t1` are ``time.monotonic()`` seconds (converted through the
    shared epoch), or wall seconds when ``wall_time=True`` (the
    router's cross-process segments, computed from replica wall stamps
    plus the HELLO clock offset).  Returns the new span id (the fill
    span's id is passed back as a ``fill=`` attr by its request
    segments) or None when the context is unsampled."""
    if ctx is None or not ctx.sampled:
        return None
    if not wall_time:
        t0, t1 = t0 + _EPOCH, t1 + _EPOCH
    sid = _next_span_id()
    rec = {"trace": ctx.trace_id, "span": sid,
           "parent": ctx.span_id if parent is None else parent,
           "name": name, "t0_us": int(t0 * 1e6),
           "dur_us": max(0, int((t1 - t0) * 1e6))}
    if attrs:
        rec["attrs"] = dict(attrs)
    _book(rec)
    return sid


def record_event(ctx, name, t=None, force=False, **attrs):
    """Record a zero-duration marker (e.g. ``redispatch``).  With
    ``force=True`` the event is recorded even for an UNSAMPLED context
    — the always-on failure discipline: a request that was redispatched
    or failed must be explainable regardless of the head verdict."""
    if ctx is None or not (ctx.sampled or force):
        return None
    t = time.monotonic() if t is None else t
    sid = _next_span_id()
    rec = {"trace": ctx.trace_id, "span": sid, "parent": ctx.span_id,
           "name": name, "t0_us": int((t + _EPOCH) * 1e6), "dur_us": 0}
    if attrs:
        rec["attrs"] = dict(attrs)
    _book(rec)
    return sid


def record_outcome(ctx, outcome, t0, t1, force=False, **attrs):
    """Record the request's ROOT span (span id = the context's own id)
    with an outcome label.  ``outcome != "ok"`` — and ``force=True``
    (a redispatched request that eventually succeeded) — record even
    when the head verdict was unsampled, so every failure is
    explained; a plain unsampled "ok" books nothing."""
    if ctx is None:
        return None
    if not ctx.sampled and outcome == "ok" and not force:
        return None
    from .. import telemetry

    if telemetry.enabled():
        telemetry.inc("trace.outcomes.%s" % outcome)
        if not ctx.sampled:
            telemetry.inc("trace.forced")
    rec = {"trace": ctx.trace_id, "span": ctx.span_id, "parent": None,
           "name": "request", "t0_us": int((t0 + _EPOCH) * 1e6),
           "dur_us": max(0, int((t1 - t0) * 1e6)),
           "attrs": dict(attrs, outcome=outcome)}
    _book(rec)
    return ctx.span_id


def flow_id(ctx, direction):
    """Deterministic chrome flow-event id for one trace + direction
    (``"submit"`` = router→replica, ``"reply"`` = replica→router) —
    both processes derive the SAME id from the shared trace id, which
    is what makes the arrows bind after stitching."""
    base = int(ctx.trace_id, 16) & 0x3FFFFFFF
    return base * 2 + (1 if direction == "reply" else 0)


def flow(ctx, direction, phase, t_wall):
    """Emit one chrome flow endpoint (``phase`` ``"s"`` start /
    ``"f"`` finish) at wall second `t_wall`, when profiling is
    running — the causal link between the router-side and replica-side
    span chains in the stitched trace."""
    if ctx is None or not ctx.sampled:
        return
    from .. import profiler

    if profiler.spans_active():
        profiler.record_flow("req", flow_id(ctx, direction), phase,
                             int(t_wall * 1e6), tid=_TRACE_TID)


def spans(trace_id=None):
    """Buffered span records, oldest first (optionally one trace's)."""
    with _LOCK:
        out = list(_SPANS)
    if trace_id is not None:
        out = [s for s in out if s["trace"] == trace_id]
    return out


def dropped():
    with _LOCK:
        return _DROPPED


def reset():
    """Clear the span buffer (tests)."""
    global _DROPPED
    with _LOCK:
        del _SPANS[:]
        _DROPPED = 0
