"""Flight recorder — a bounded, always-on ring of dispatch/collective
edge events.

The reference's profiler brackets every engine op with
SetOprStart/SetOprEnd *while profiling*; a hung collective needs that
bracketing ALWAYS, because the interesting window is the one nobody
was profiling.  This module keeps a fixed-slot ring buffer of the last
N host-observable edge events — fused-dispatch enter/exit
(executor.py), allgather/barrier enter/exit (parallel/multihost.py),
PS barriers (parallel/dist.py), serving fills (serving/session.py) —
each stamped with a per-kind sequence number, detail string, byte
count, and a monotonic timestamp.  The PyTorch NCCL flight recorder is
the shape: cheap enough to leave on, complete enough that a post-mortem
(obs/watchdog.py) can say *which* collective seq a rank is stuck in
and whether its peers ever entered it.

Cost discipline matches telemetry: every helper early-returns when
disabled, and HOT call sites must guard the call itself behind
:func:`enabled` so no formatting/timestamping happens when the
recorder is off (:func:`set_enabled`) — mxlint E004 enforces the
guard for ``recorder.record`` exactly as it does for
``telemetry.inc``.

Alongside the ring, the recorder keeps O(1) aggregates the watchdog
and the cluster aggregator consume without scanning events:

  * :func:`progress` — per-kind entered/exited counts and last seqs
    (the "rank R never entered seq S" attribution input);
  * :func:`open_spans` — events whose exit has not arrived;
  * a compile bracket (kind ``"compile"``): while a compile span is
    open the stall watchdog suppresses itself, so a minutes-long
    legitimate first compile on real hardware is never reported as a
    hang (:func:`compiling`, :func:`last_compile_exit`).
"""
from __future__ import annotations

import os as _os
import threading
import time
from .. import locks

__all__ = ["enabled", "set_enabled", "record", "events", "open_spans",
           "progress", "compiling", "last_compile_exit", "reset",
           "ring_slots", "own_rank", "set_schedule_hook"]

_ENABLED = True
_RING_SLOTS = 512

_LOCK = locks.lock("obs.recorder")
# collective-schedule hook (parallel/schedule_check.py installs it when
# MXTPU_COLLECTIVE_CHECK=1): called OUTSIDE _LOCK with every enter
# event's (kind, seq, nbytes, detail) so the cross-rank schedule
# verifier folds the same stream the ring retains.  None when the
# check is off — one predicate per record(), nothing else.
_SCHED_HOOK = None
_RING = [None] * _RING_SLOTS  # fixed slots, preallocated — no growth
_NEXT = 0  # total events ever recorded; slot = _NEXT % len(_RING)
_KIND_SEQ = {}  # kind -> last auto-assigned sequence number
_OPEN = {}  # (kind, seq) -> (t_enter, detail, nbytes)
_PROGRESS = {}  # kind -> [entered, exited, last_entered_seq, last_exited_seq]
_LAST_COMPILE_EXIT = 0.0


def enabled():
    """Cheap hot-path check (the telemetry.enabled() discipline):
    callers must skip :func:`record` — including its argument
    construction — entirely when this is False."""
    return _ENABLED


def set_enabled(flag):
    """Turn recording on/off; returns the previous state (tests).

    Disabling clears the open-span table: exit events are not recorded
    while off (record() early-returns), so an enter that was in flight
    at the flip would otherwise look permanently open and the watchdog
    would report — or abort on — a phantom stall."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = bool(flag)
    if not _ENABLED:
        with _LOCK:
            _OPEN.clear()
    return prev


def own_rank():
    """This process's rank in a multi-process launch (the launcher's
    MXTPU_PROCESS_ID / DMLC_WORKER_ID export; 0 standalone) — the ONE
    rank resolution the watchdog's artifact name and the aggregator's
    snapshot rank must agree on."""
    return int(_os.environ.get("MXTPU_PROCESS_ID",
                               _os.environ.get("DMLC_WORKER_ID", "0")) or 0)


def ring_slots():
    return len(_RING)


def record(kind, phase, seq=None, detail="", nbytes=0):
    """Record one edge event; returns the event's sequence number.

    ``phase`` is ``"enter"`` or ``"exit"``.  ``seq=None`` on enter
    draws the next per-kind sequence number (call sites with a natural
    counter — the executor's dispatch count — pass their own); on exit
    it resolves to the most recently entered still-open seq of `kind`,
    so bracketing call sites can write
    ``seq = recorder.record(k, "enter")`` … ``recorder.record(k,
    "exit", seq)`` without bookkeeping."""
    global _NEXT, _LAST_COMPILE_EXIT
    if not _ENABLED:
        return seq
    t = time.monotonic()
    with _LOCK:
        prog = _PROGRESS.get(kind)
        if prog is None:
            prog = _PROGRESS[kind] = [0, 0, None, None]
        if phase == "enter":
            if seq is None:
                seq = _KIND_SEQ.get(kind, 0) + 1
            _KIND_SEQ[kind] = seq
            _OPEN[(kind, seq)] = (t, detail, nbytes)
            prog[0] += 1
            prog[2] = seq
        else:
            if seq is None:
                open_seqs = [s for (k, s) in _OPEN if k == kind]
                seq = max(open_seqs) if open_seqs else _KIND_SEQ.get(kind)
            _OPEN.pop((kind, seq), None)
            prog[1] += 1
            prog[3] = seq
            if kind == "compile":
                _LAST_COMPILE_EXIT = t
        _RING[_NEXT % len(_RING)] = (_NEXT, t, kind, phase, seq, detail,
                                     int(nbytes))
        _NEXT += 1
    if _SCHED_HOOK is not None and phase == "enter":
        _SCHED_HOOK(kind, seq, nbytes=nbytes, detail=detail)
    return seq


def set_schedule_hook(fn):
    """Install/remove the collective-schedule hook (module comment at
    _SCHED_HOOK); returns the previous hook."""
    global _SCHED_HOOK
    prev = _SCHED_HOOK
    _SCHED_HOOK = fn
    return prev


def events(last_k=None):
    """The last `last_k` (default: all retained) events, oldest first,
    as dicts — the post-mortem/artifact view."""
    with _LOCK:
        n = min(_NEXT, len(_RING))
        start = _NEXT - n
        raw = [_RING[i % len(_RING)] for i in range(start, _NEXT)]
    if last_k is not None:
        raw = raw[-int(last_k):]
    return [{"index": i, "t_mono": t, "kind": k, "phase": p, "seq": s,
             "detail": d, "nbytes": b} for (i, t, k, p, s, d, b) in raw]


def open_spans(now=None):
    """Entered-but-not-exited events, oldest first: what every thread
    of this rank is currently *inside* — the watchdog's subject."""
    now = time.monotonic() if now is None else now
    with _LOCK:
        items = sorted(_OPEN.items(), key=lambda kv: kv[1][0])
    return [{"kind": k, "seq": s, "t_enter": t, "age_s": now - t,
             "detail": d, "nbytes": b}
            for (k, s), (t, d, b) in items]


def progress():
    """Per-kind counters: ``{kind: {entered, exited, last_entered_seq,
    last_exited_seq}}``.  Shipped to rank 0 by the aggregation reporter;
    comparing a stalled rank's seq against every peer's
    ``last_entered_seq`` is the straggler-vs-hang attribution."""
    with _LOCK:
        return {k: {"entered": v[0], "exited": v[1],
                    "last_entered_seq": v[2], "last_exited_seq": v[3]}
                for k, v in _PROGRESS.items()}


def compiling():
    """True while any compile bracket is open — the watchdog suppresses
    stall reports for the duration (a first XLA compile legitimately
    takes minutes on real hardware)."""
    with _LOCK:
        return any(k == "compile" for (k, _s) in _OPEN)


def last_compile_exit():
    """Monotonic time the most recent compile bracket closed (0.0 if
    never).  The watchdog ages open spans from ``max(enter, this)`` so
    time a dispatch spent *waiting behind a compile* never counts
    toward its stall budget."""
    with _LOCK:
        return _LAST_COMPILE_EXIT


def reset(slots=None):
    """Clear the ring and all aggregates (tests); `slots` resizes."""
    global _RING, _NEXT, _LAST_COMPILE_EXIT
    with _LOCK:
        _RING = [None] * (max(8, int(slots)) if slots else len(_RING))
        _NEXT = 0
        _KIND_SEQ.clear()
        _OPEN.clear()
        _PROGRESS.clear()
        _LAST_COMPILE_EXIT = 0.0
