"""Cross-rank collective-schedule verifier — E007's runtime teeth.

A multi-process SPMD job deadlocks the moment two ranks disagree about
the SEQUENCE of collectives: rank 0 enters all-reduce #7 while rank 1
— having skipped a batch or raced a rebind — is entering a different
#7 (or none at all).  The stall watchdog (obs/watchdog.py) diagnoses
that hang POST-MORTEM, after ``MXTPU_OBS_STALL_SECONDS`` of silence;
this module catches the divergence the moment it becomes observable,
usually BEFORE the hang:

  * every rank folds its flight-recorder stream of collective-ish
    enter events — ``(kind, seq, nbytes, detail)``; detail carries K
    on the fused-dispatch path — into a rolling structural hash
    (:class:`ScheduleLog`), keeping a bounded ring of recent per-event
    prefix hashes so any common prefix length within the window is
    comparable;
  * the per-rank digest rides the EXISTING obs snapshot
    (obs/aggregate.py Reporter -> rank-0 Aggregator, every
    ``MXTPU_OBS_INTERVAL_SECONDS``) — no new control plane;
  * a :class:`ScheduleVerifier` thread on every rank queries the peer
    digests back (``aggregate.query_peers``) and compares prefix
    hashes at the longest common event count.  A mismatch binary-
    searches the rings for the FIRST diverging event and raises a
    :class:`ScheduleDivergence` naming it — kind, per-kind seq, byte
    count, detail — and both ranks, dumps a ``sched_divergence.r<rank>
    .json`` artifact (write-then-rename, like the watchdog's), and
    with ``MXTPU_OBS_STALL_ACTION=abort`` hard-exits with
    :data:`DIVERGENCE_EXIT_CODE` so the launcher observes a failure
    well inside the watchdog window instead of a forever-hang.

Armed by ``MXTPU_COLLECTIVE_CHECK=1`` (config-registered); the
recorder hook and verifier cost nothing when off.  The static half is
mxlint E007 (tools/analysis/spmd_checks.py): rank-dependent collective
control flow it can prove is rejected before the job ever runs; this
verifier catches the dynamically-divergent remainder.
"""
from __future__ import annotations

import hashlib
import json
import os
import sys
import threading
import time
from .. import locks

__all__ = ["enabled", "set_enabled", "ScheduleLog", "ScheduleDivergence",
           "ScheduleVerifier", "digest", "note_event", "first_divergence",
           "log", "reset", "maybe_start_from_env", "stop",
           "DIVERGENCE_EXIT_CODE", "SCHEDULE_KINDS"]

# distinctive exit code (watchdog aborts use 17) so launchers/tests can
# tell "schedule verifier killed a divergent job" from ordinary crashes
DIVERGENCE_EXIT_CODE = 18

# recorder kinds that are collective-shaped: every rank of the mesh
# must produce an IDENTICAL ordered stream of these.  Rank-local kinds
# (serve fills, compile brackets — timing-dependent, legitimately
# divergent) are excluded.
SCHEDULE_KINDS = frozenset(
    {"dispatch", "allreduce", "allgather", "reduce_scatter",
     "alltoall", "barrier", "psum"})

_ENABLED = os.environ.get("MXTPU_COLLECTIVE_CHECK", "0") not in ("0", "")

_RING_SLOTS = 1024      # per-event prefix hashes retained locally
_SNAPSHOT_RECENT = 256  # ring entries shipped in each obs snapshot


def enabled():
    """Is the schedule check armed?  (``MXTPU_COLLECTIVE_CHECK=1``)"""
    return _ENABLED


def set_enabled(flag):
    """Toggle at runtime (tests); returns the previous state and
    (re)installs/removes the recorder hook to match."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = bool(flag)
    _sync_recorder_hook()
    return prev


class ScheduleDivergence(RuntimeError):
    """Raised/reported when two ranks' collective schedules diverge.
    Carries the structured report in ``.report``."""

    def __init__(self, report):
        self.report = report
        ev = report.get("event_here") or report.get("event_peer") or {}
        super().__init__(
            "collective schedule divergence between rank %s and rank %s "
            "at event index %s: first diverging collective is kind=%r "
            "seq=%s (detail=%r, nbytes=%s)"
            % (report.get("rank_here"), report.get("rank_peer"),
               report.get("index"), ev.get("kind"), ev.get("seq"),
               ev.get("detail"), ev.get("nbytes")))


class ScheduleLog:
    """Rolling structural hash + bounded ring of one rank's collective
    schedule (module docstring).  Thread-safe; one module-level
    instance feeds production, tests build their own."""

    def __init__(self, ring_slots=_RING_SLOTS):
        self._lock = locks.lock("dist.schedule_hash")
        self._ring_slots = int(ring_slots)
        self.reset()

    def reset(self):
        with self._lock:
            self._count = 0
            self._hash = hashlib.sha1(b"mxtpu-sched-v1").hexdigest()
            self._ring = []  # dicts: index/kind/seq/nbytes/detail/prefix

    def note(self, kind, seq, nbytes=0, detail=""):
        """Fold one collective enter event into the schedule."""
        with self._lock:
            fp = "%s|%s|%d|%s" % (kind, seq, int(nbytes or 0), detail)
            h = hashlib.sha1(
                (self._hash + "\x00" + fp).encode()).hexdigest()
            self._hash = h
            entry = {"index": self._count, "kind": kind, "seq": seq,
                     "nbytes": int(nbytes or 0), "detail": str(detail),
                     "prefix": h}
            self._count += 1
            self._ring.append(entry)
            if len(self._ring) > self._ring_slots:
                del self._ring[: len(self._ring) - self._ring_slots]

    def digest(self, recent=_SNAPSHOT_RECENT):
        """The shippable view: total count, rolling hash, and the last
        `recent` ring entries (each with its prefix hash)."""
        with self._lock:
            return {"count": self._count, "hash": self._hash,
                    "recent": [dict(e) for e in self._ring[-recent:]]}


def _hash_at(dig, count):
    """Prefix hash of a digest's schedule after `count` events, or
    None when `count` predates the retained ring."""
    if count <= 0:
        return None
    if count == dig.get("count"):
        return dig.get("hash")
    for e in dig.get("recent", ()):
        if e.get("index") == count - 1:
            return e.get("prefix")
    return None


def _entry_at(dig, index):
    for e in dig.get("recent", ()):
        if e.get("index") == index:
            return e
    return None


def first_divergence(here, peer):
    """Compare two schedule digests over their longest common prefix.

    Returns None when consistent (or not yet comparable: no common
    prefix hash inside both retained rings); otherwise a report dict
    naming the first diverging event from each side —
    ``{"index", "event_here", "event_peer", "count_here",
    "count_peer"}``.  When the true first divergence predates both
    rings, ``index`` is the earliest comparable mismatch and
    ``truncated`` is True.
    """
    common = min(here.get("count", 0), peer.get("count", 0))
    if common <= 0:
        return None
    ha, hb = _hash_at(here, common), _hash_at(peer, common)
    if ha is None or hb is None:
        return None  # skew beyond the ring window: compare next round
    if ha == hb:
        return None
    # prefix mismatch: find the earliest comparable diverging index
    idx_here = {e["index"]: e for e in here.get("recent", ())
                if e["index"] < common}
    idx_peer = {e["index"]: e for e in peer.get("recent", ())
                if e["index"] < common}
    shared = sorted(set(idx_here) & set(idx_peer))
    first = None
    for i in shared:
        if idx_here[i]["prefix"] != idx_peer[i]["prefix"]:
            first = i
            break
    if first is None:
        # every shared ring index agrees (or rings don't overlap): the
        # divergence predates the retained window
        return {"index": min(shared) if shared else common,
                "truncated": True, "event_here": None, "event_peer": None,
                "count_here": here.get("count"),
                "count_peer": peer.get("count")}
    return {"index": first, "truncated": False,
            "event_here": {k: idx_here[first].get(k)
                           for k in ("kind", "seq", "nbytes", "detail")},
            "event_peer": {k: idx_peer[first].get(k)
                           for k in ("kind", "seq", "nbytes", "detail")},
            "count_here": here.get("count"),
            "count_peer": peer.get("count")}


# ----------------------------------------------------------------------
# module-level log + recorder hook
# ----------------------------------------------------------------------

_LOG = ScheduleLog()


def log():
    """The process-wide ScheduleLog."""
    return _LOG


def note_event(kind, seq, nbytes=0, detail=""):
    """Recorder hook target: fold one enter event if it is schedule-
    relevant (installed into obs.recorder when the check is armed)."""
    if kind in SCHEDULE_KINDS:
        _LOG.note(kind, seq, nbytes=nbytes, detail=detail)


def digest(recent=_SNAPSHOT_RECENT):
    """This rank's schedule digest (the obs snapshot field)."""
    return _LOG.digest(recent=recent)


def reset():
    """Clear the process-wide log (tests)."""
    _LOG.reset()


def _sync_recorder_hook():
    from ..obs import recorder

    recorder.set_schedule_hook(note_event if _ENABLED else None)


# ----------------------------------------------------------------------
# the verifier thread
# ----------------------------------------------------------------------

def _own_rank():
    from ..obs.recorder import own_rank

    return own_rank()


class ScheduleVerifier(threading.Thread):
    """Per-rank daemon comparing this rank's schedule digest against
    every peer's (shipped through the obs aggregator) each interval.

    On divergence: dumps ``sched_divergence.r<rank>.json`` (write-then-
    rename), counts ``schedule.divergences`` in telemetry, and either
    hard-exits with DIVERGENCE_EXIT_CODE (action='abort') or keeps
    running without re-reporting the same divergence (action='dump').
    Peer digests are CACHED across polls, so a peer that already
    aborted (taking the rank-0 aggregator with it) stays comparable —
    both sides of a divergence terminate even when they detect it one
    poll apart."""

    def __init__(self, interval_s=5.0, action="dump", artifact_dir="",
                 query_fn=None, digest_fn=None, rank=None,
                 abort_fn=None):
        super().__init__(name="sched_verifier", daemon=True)
        self.interval_s = float(interval_s)
        if action not in ("dump", "abort"):
            raise ValueError("schedule-check action must be 'dump' or "
                             "'abort', got %r" % (action,))
        self.action = action
        self.artifact_dir = artifact_dir or "."
        self.rank = _own_rank() if rank is None else int(rank)
        self._query_fn = query_fn
        self._digest_fn = digest_fn or digest
        self._abort_fn = abort_fn or (
            lambda code: os._exit(code))  # noqa: E731 — test seam
        self._stop_evt = threading.Event()
        self._peer_cache = {}  # rank -> last seen sched digest
        self._reported = set()  # peer ranks already reported
        self.artifact_path = None

    def stop(self):
        self._stop_evt.set()

    def _peers(self):
        if self._query_fn is not None:
            return self._query_fn()
        from ..obs import aggregate

        return aggregate.query_peers()

    def run(self):
        while not self._stop_evt.wait(self.interval_s):
            try:
                self.check()
            except ScheduleDivergence:
                # action='dump': reported once, keep watching
                pass
            except Exception:  # pragma: no cover — the verifier must
                pass           # never kill the job it watches

    def check(self):
        """One comparison round.  Returns the divergence report (after
        dumping/aborting) or None; raises ScheduleDivergence under
        action='dump' so synchronous callers see it too."""
        for rank, snap in (self._peers() or {}).items():
            sched = (snap or {}).get("sched")
            if sched is not None and int(rank) != self.rank:
                self._peer_cache[int(rank)] = sched
        here = self._digest_fn()
        for rank, sched in sorted(self._peer_cache.items()):
            if rank in self._reported:
                continue
            div = first_divergence(here, sched)
            if div is None:
                continue
            self._reported.add(rank)
            report = dict(div, rank_here=self.rank, rank_peer=rank,
                          ranks=sorted({self.rank, rank}))
            exc = ScheduleDivergence(report)
            self._dump(report, str(exc))
            from .. import telemetry

            if telemetry.enabled():
                telemetry.inc("schedule.divergences")
            sys.stderr.write(
                "mxnet_tpu.parallel.schedule_check: %s; artifact at %s\n"
                % (exc, self.artifact_path))
            sys.stderr.flush()
            if self.action == "abort":
                self._abort_fn(DIVERGENCE_EXIT_CODE)
                return report  # only reachable with a test abort_fn
            raise exc
        return None

    def _dump(self, report, message):
        """Write the divergence artifact atomically (the watchdog's
        write-then-rename discipline); a failed write must not cancel
        the report/abort."""
        artifact = {
            "schema": "mxtpu-sched-divergence-v1",
            "wall_time": time.time(),
            "message": message,
            "report": report,
            "digest_here": self._digest_fn(),
        }
        try:
            os.makedirs(self.artifact_dir, exist_ok=True)
            path = os.path.join(self.artifact_dir,
                                "sched_divergence.r%d.json" % self.rank)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(artifact, f, indent=1, default=str)
            os.replace(tmp, path)
            self.artifact_path = path
        except OSError as e:
            sys.stderr.write("mxnet_tpu.parallel.schedule_check: "
                             "artifact dump FAILED (%s)\n" % e)


_VERIFIER = None
_VERIFIER_LOCK = locks.lock("dist.schedule_verifier")


def maybe_start_from_env():
    """Arm from the environment: ``MXTPU_COLLECTIVE_CHECK=1`` installs
    the recorder hook and — when the obs aggregation plane is armed
    (``MXTPU_OBS_PORT``) — starts the verifier at
    ``MXTPU_OBS_INTERVAL_SECONDS`` with ``MXTPU_OBS_STALL_ACTION`` /
    ``MXTPU_OBS_DIR``.  Idempotent; returns the verifier or None."""
    global _VERIFIER
    if not _ENABLED:
        return None
    from ..obs import recorder

    if not recorder.enabled():
        # the verifier folds the RECORDER's event stream: with the
        # recorder off every digest stays empty and the check would be
        # silently inert — say so instead of pretending to protect
        import warnings

        warnings.warn(
            "MXTPU_COLLECTIVE_CHECK=1 requires the flight recorder "
            "(recorder.set_enabled(False) turned it off): the schedule "
            "verifier will see no events and detect nothing")
        return None
    _sync_recorder_hook()
    if not os.environ.get("MXTPU_OBS_PORT", ""):
        return None  # hook-only: digests still accumulate for tests
    raw = os.environ.get("MXTPU_OBS_INTERVAL_SECONDS", "")
    try:
        interval = float(raw) if raw else 5.0
    except ValueError:
        interval = 5.0
    with _VERIFIER_LOCK:
        if _VERIFIER is not None and _VERIFIER.is_alive():
            return _VERIFIER
        _VERIFIER = ScheduleVerifier(
            interval_s=interval,
            action=os.environ.get("MXTPU_OBS_STALL_ACTION", "dump")
            or "dump",
            artifact_dir=os.environ.get("MXTPU_OBS_DIR", ""))
        _VERIFIER.start()
        return _VERIFIER


def stop():
    """Stop the module verifier and remove the recorder hook (tests)."""
    global _VERIFIER
    with _VERIFIER_LOCK:
        if _VERIFIER is not None:
            _VERIFIER.stop()
            _VERIFIER = None
    from ..obs import recorder

    recorder.set_schedule_hook(None)
