"""Stall watchdog — turn a silent distributed hang into a post-mortem.

A desynced or wedged collective inside the fused K-step scan hangs the
whole job with zero diagnostics: every healthy rank blocks in a psum
(or in the allgather readback behind it) waiting for a peer that will
never arrive.  This thread watches the flight recorder
(obs/recorder.py) for an entered-but-never-exited span older than
``MXTPU_OBS_STALL_SECONDS``, and when one appears it dumps a
post-mortem artifact (write-then-rename) and — with
``MXTPU_OBS_STALL_ACTION=abort`` — hard-exits the process so the
launcher observes a failure instead of a forever-hang.

The artifact (``MXTPU_OBS_DIR``/``postmortem.r<rank>.json``,
schema ``mxtpu-obs-postmortem-v1``) carries:

  * the stalled span(s): kind, seq, detail, age;
  * the last-K flight-recorder events and per-kind progress counters;
  * every peer rank's last-known progress counters (queried from the
    rank-0 aggregator, obs/aggregate.py) and the straggler-vs-hang
    attribution computed from them (:func:`attribute_stall`):
    "rank R never entered seq S" vs "all ranks entered, none exited";
  * a Python stack per live thread (``sys._current_frames``) — where
    exactly this rank is blocked;
  * a small telemetry digest (steps, dispatches).

False-positive guard: while a compile bracket is open
(``recorder.compiling()``) the watchdog is suppressed entirely, and
span ages are measured from ``max(enter, last_compile_exit)`` — a
minutes-long legitimate first compile neither trips the watchdog nor
bills its duration to the dispatch that waited behind it.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
import traceback

from . import recorder
from .. import locks

__all__ = ["StallWatchdog", "start", "stop", "maybe_start_from_env",
           "attribute_stall", "ABORT_EXIT_CODE"]

# distinctive code so launchers/tests can tell "watchdog aborted a
# wedged rank" from ordinary crashes
ABORT_EXIT_CODE = 17

_WD = None
_WD_LOCK = locks.lock("obs.watchdog")


_own_rank = recorder.own_rank


def attribute_stall(kind, seq, peers):
    """Straggler-vs-hang attribution for a span of `kind` stuck at
    `seq`, given ``{rank: progress_dict}`` peer snapshots (the
    aggregator's view of every rank's ``recorder.progress()``).

    Returns ``{"verdict", "detail", "ranks_behind"}``:

      * ``straggler`` — some rank's ``last_entered_seq`` for `kind` is
        behind `seq` (or it never recorded the kind): that rank never
        entered the collective the others are blocked in — desync /
        dead / slow peer, and the artifact names it;
      * ``hang`` — every known rank entered `seq` but none exited:
        the collective itself is wedged (transport, deadlock);
      * ``unknown`` — no peer snapshots to compare against (single
        rank, or the aggregator is not armed/reachable).
    """
    if not peers:
        return {"verdict": "unknown", "ranks_behind": [],
                "detail": "no peer snapshots (aggregator not armed or "
                          "unreachable); cannot attribute the stall"}
    behind, entered, exited = [], [], []
    for rank, prog in sorted(peers.items()):
        p = (prog or {}).get(kind) or {}
        last_in = p.get("last_entered_seq")
        if last_in is None or last_in < seq:
            behind.append(int(rank))
        else:
            entered.append(int(rank))
            if (p.get("last_exited_seq") or -1) >= seq:
                exited.append(int(rank))
    if behind:
        return {"verdict": "straggler", "ranks_behind": behind,
                "detail": "rank(s) %s never entered %s seq %s (last "
                          "known progress is behind); the blocked ranks "
                          "are waiting on them" % (behind, kind, seq)}
    if entered and not exited:
        return {"verdict": "hang", "ranks_behind": [],
                "detail": "all known ranks entered %s seq %s and none "
                          "exited: the collective itself is wedged"
                          % (kind, seq)}
    return {"verdict": "unknown", "ranks_behind": [],
            "detail": "peer progress for %s seq %s is inconclusive "
                      "(some peers already past it)" % (kind, seq)}


def _thread_stacks():
    """One formatted Python stack per live thread — where this rank is
    actually blocked.  sys._current_frames is a CPython implementation
    detail but the standard post-mortem tool (faulthandler uses it)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    stacks = {}
    for tid, frame in sys._current_frames().items():
        label = "%d %s" % (tid, names.get(tid, "?"))
        stacks[label] = "".join(traceback.format_stack(frame))
    return stacks


class StallWatchdog(threading.Thread):
    """Daemon polling the recorder for stalled open spans (module doc).

    Constructed explicitly in tests; production arms it from the
    environment via :func:`maybe_start_from_env`."""

    def __init__(self, stall_seconds, action="dump", artifact_dir="",
                 poll_seconds=None, last_k=64):
        super().__init__(name="obs_watchdog", daemon=True)
        self.stall_seconds = float(stall_seconds)
        if action not in ("dump", "abort"):
            raise ValueError("watchdog action must be 'dump' or 'abort', "
                             "got %r" % (action,))
        self.action = action
        self.artifact_dir = artifact_dir or "."
        self.poll_seconds = (poll_seconds if poll_seconds is not None
                             else max(0.05, self.stall_seconds / 4.0))
        self.last_k = int(last_k)
        self.artifact_path = None  # last artifact written
        self._stop_evt = threading.Event()
        self._dumped = set()  # (kind, seq) already reported

    def stop(self):
        self._stop_evt.set()

    def run(self):
        while not self._stop_evt.wait(self.poll_seconds):
            try:
                self.check()
            except Exception:  # pragma: no cover — a watchdog bug must
                pass           # never kill the job it watches

    def stalled_spans(self, now=None):
        """Open spans whose age — measured from max(enter,
        last_compile_exit) — exceeds the threshold.  Empty while a
        compile bracket is open (suppression)."""
        if recorder.compiling():
            return []
        now = time.monotonic() if now is None else now
        floor = recorder.last_compile_exit()
        out = []
        for s in recorder.open_spans(now=now):
            if s["kind"] == "compile":
                continue
            effective_age = now - max(s["t_enter"], floor)
            if effective_age > self.stall_seconds:
                s = dict(s, age_s=effective_age)
                out.append(s)
        return out

    def check(self):
        """One poll: dump (once per span) if anything stalled; abort
        the process afterwards when configured to."""
        stalled = [s for s in self.stalled_spans()
                   if (s["kind"], s["seq"]) not in self._dumped]
        if not stalled:
            return None
        for s in stalled:
            self._dumped.add((s["kind"], s["seq"]))
        # the abort must NOT depend on the artifact write succeeding: a
        # read-only MXTPU_OBS_DIR losing the post-mortem is bad, but a
        # wedged rank silently hanging forever because of it would be
        # exactly the failure mode this watchdog exists to prevent
        try:
            path = self.dump(stalled)
        except Exception as e:
            path = None
            sys.stderr.write("mxnet_tpu.obs.watchdog: post-mortem dump "
                             "FAILED (%s)\n" % e)
        if self.action == "abort":
            sys.stderr.write(
                "mxnet_tpu.obs.watchdog: collective/dispatch stall "
                "detected (%s); post-mortem at %s; aborting rank %d\n"
                % (", ".join("%s seq %s age %.1fs"
                             % (s["kind"], s["seq"], s["age_s"])
                             for s in stalled), path, _own_rank()))
            sys.stderr.flush()
            os._exit(ABORT_EXIT_CODE)
        return path

    def dump(self, stalled):
        """Write the post-mortem artifact atomically (temp + rename —
        a monitoring process tailing the directory never sees a
        partial JSON) and return its path."""
        from . import aggregate
        from .. import telemetry

        rank = _own_rank()
        peers = aggregate.query_peers()
        peer_progress = {r: (p or {}).get("recorder_progress")
                         for r, p in peers.items()}
        worst = max(stalled, key=lambda s: s["age_s"])
        artifact = {
            "schema": "mxtpu-obs-postmortem-v1",
            "rank": rank,
            "wall_time": time.time(),
            "monotonic_s": time.monotonic(),
            "stall_seconds": self.stall_seconds,
            "stalled": stalled,
            "attribution": attribute_stall(worst["kind"], worst["seq"],
                                           peer_progress),
            "events": recorder.events(last_k=self.last_k),
            "progress": recorder.progress(),
            "peers": {str(r): p for r, p in peers.items()},
            "stacks": _thread_stacks(),
            "telemetry": {
                "module.steps": telemetry.counter_value("module.steps"),
                "executor.train_dispatches":
                    telemetry.counter_value("executor.train_dispatches"),
            },
        }
        os.makedirs(self.artifact_dir, exist_ok=True)
        path = os.path.join(self.artifact_dir, "postmortem.r%d.json" % rank)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(artifact, f, indent=1, default=str)
        os.replace(tmp, path)
        self.artifact_path = path
        return path


def start(stall_seconds, action="dump", artifact_dir="", poll_seconds=None):
    """Start (or return the already-running) module watchdog."""
    global _WD
    with _WD_LOCK:
        if _WD is not None and _WD.is_alive():
            return _WD
        _WD = StallWatchdog(stall_seconds, action=action,
                            artifact_dir=artifact_dir,
                            poll_seconds=poll_seconds)
        _WD.start()
        return _WD


def stop():
    global _WD
    with _WD_LOCK:
        if _WD is not None:
            _WD.stop()
            _WD = None


def maybe_start_from_env():
    """Arm from the environment: ``MXTPU_OBS_STALL_SECONDS`` > 0 starts
    the watchdog with ``MXTPU_OBS_STALL_ACTION`` / ``MXTPU_OBS_DIR``.
    Returns the watchdog or None."""
    raw = os.environ.get("MXTPU_OBS_STALL_SECONDS", "")
    try:
        stall = float(raw) if raw else 0.0
    except ValueError:
        stall = 0.0
    if stall <= 0:
        return None
    return start(stall,
                 action=os.environ.get("MXTPU_OBS_STALL_ACTION", "dump")
                 or "dump",
                 artifact_dir=os.environ.get("MXTPU_OBS_DIR", ""))
