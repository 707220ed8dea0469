"""Profiler — chrome://tracing output + XLA profile bridge.

Parity: reference src/engine/profiler.{h,cc} + python/mxnet/profiler.py.
The reference brackets every engine op with SetOprStart/SetOprEnd; here the
unit of execution is a jitted XLA executable, so we record per-call spans
(compile vs run) and can additionally capture a device-level XLA trace via
`jax.profiler` when requested.
"""
from __future__ import annotations

import itertools
import json
import threading
import time

from jax.profiler import TraceAnnotation as _TraceAnnotation

from . import telemetry

__all__ = ["profiler_set_config", "profiler_set_state", "dump_profile",
           "record_span", "record_counter", "record_flow",
           "register_thread_name", "set_trace_meta"]

import os as _os

# chrome-trace pids: host-side spans/counters vs the joined XLA device
# trace — named via process_name metadata at dump time so traces show
# "host" / "device (XLA)" lanes instead of bare 0/1
PID_HOST = 0
PID_DEVICE = 1

_STATE = {
    # MXNET_PROFILER_MODE honored at import (reference env_var.md:101-108)
    "mode": _os.environ.get("MXNET_PROFILER_MODE", "symbolic"),
    "filename": _os.environ.get("MXNET_PROFILER_FILENAME", "profile.json"),
    "running": False,
}
_EVENTS = []
# RLock: telemetry.set_gauge reaches record_counter from NDArray.__del__,
# which GC can run on a thread that already holds this lock
_LOCK = threading.RLock()
_JAX_TRACE_DIR = None
# tid -> human thread name, harvested as spans are recorded; dumped as
# thread_name metadata so engine-worker lanes are labeled in the UI
_TID_NAMES = {}
# stitch metadata stamped into the dumped trace's otherData: this
# rank's id and its measured wall-clock offset vs rank 0 (seconds*1e6;
# obs/aggregate.py's clock handshake sets it) — what tools/obs_stitch.py
# uses to merge N per-rank traces onto one aligned timeline
_TRACE_META = {"rank": None, "clock_offset_us": 0.0}


def set_trace_meta(rank=None, clock_offset_us=None):
    """Stamp per-rank stitch metadata into subsequent dump_profile()
    outputs (obs/aggregate.py calls this after its clock handshake)."""
    if rank is not None:
        _TRACE_META["rank"] = int(rank)
    if clock_offset_us is not None:
        _TRACE_META["clock_offset_us"] = float(clock_offset_us)


def profiler_set_config(mode="symbolic", filename="profile.json"):
    """Configure profiler (parity: python/mxnet/profiler.py profiler_set_config)."""
    if mode not in ("symbolic", "all", "xla"):
        raise ValueError("mode must be 'symbolic', 'all' or 'xla'")
    _STATE["mode"] = mode
    _STATE["filename"] = filename


def profiler_set_state(state="stop"):
    """Start/stop profiling (parity: profiler.py profiler_set_state)."""
    global _JAX_TRACE_DIR, _WALL_MINUS_PERF_NS
    if state not in ("run", "stop"):
        raise ValueError("state must be 'run' or 'stop'")
    if state == "run" and not _STATE["running"]:
        _WALL_MINUS_PERF_NS = time.time_ns() - time.perf_counter_ns()
        _STATE["running"] = True
        if _STATE["mode"] == "xla":
            import jax
            import shutil

            _JAX_TRACE_DIR = _STATE["filename"] + ".xla"
            # fresh dir per session: start_trace writes a new timestamped
            # subdir and never cleans old ones, so stale sessions would be
            # re-aggregated into this profile's per-op rows
            shutil.rmtree(_JAX_TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(_JAX_TRACE_DIR)
    elif state == "stop" and _STATE["running"]:
        _STATE["running"] = False
        if _STATE["mode"] == "xla" and _JAX_TRACE_DIR is not None:
            import jax

            jax.profiler.stop_trace()
            _join_xla_trace(_JAX_TRACE_DIR)


def _join_xla_trace(trace_dir):
    """Fold the XLA device trace back into the chrome-JSON event list as
    per-op rows (reference Profiler::DumpProfile per-op rows,
    src/engine/profiler.cc:134-190).  Executor._run_graph wraps every node
    in jax.named_scope(node.name), so device events carry the graph-node
    name in their `tf_op` metadata; events are aggregated per scope path."""
    import glob
    import gzip

    files = glob.glob(trace_dir + "/**/*.trace.json.gz", recursive=True)
    if not files:
        return
    rows = {}
    for path in sorted(files):
        try:
            with gzip.open(path) as f:
                trace = json.load(f)
        except Exception:
            continue
        for e in trace.get("traceEvents", []):
            if e.get("ph") != "X" or not isinstance(e.get("args"), dict):
                continue
            # TPU device events carry the named-scope path in tf_op;
            # XLA:CPU thunk events carry only the HLO instruction (hlo_op)
            op = e["args"].get("tf_op")
            if not op and "hlo_op" in e["args"]:
                op = e["name"]
            if not op:
                continue
            dur = e.get("dur", 0)
            r = rows.setdefault(op, {"dur": 0, "count": 0, "ts": e.get("ts", 0)})
            r["dur"] += dur
            r["count"] += 1
    with _LOCK:
        for op, r in sorted(rows.items(), key=lambda kv: -kv[1]["dur"]):
            _EVENTS.append({
                "name": op, "cat": "xla_op", "ph": "X", "ts": r["ts"],
                "dur": r["dur"], "pid": PID_DEVICE, "tid": 0,
                "args": {"calls": r["count"]},
            })


def spans_active():
    """Cheap hot-path check: is span recording on?  Callers (the engine
    worker loop) skip timestamping and span-name formatting entirely
    when profiling is off."""
    return _STATE["running"]


def record_span(name, start_us, dur_us, cat="operator", tid=None, args=None):
    """Record one span; called by executors and engine workers when
    profiling is on.  `tid` defaults to the REAL calling thread id so
    engine worker lanes render as separate rows in chrome://tracing
    (reference SetOprStart/SetOprEnd record per-thread ProfileStat).
    `args` (a plain dict) lands in the event's chrome ``args`` — the
    request tracer (obs/tracing.py) carries trace/span/parent ids
    there so stitched traces stay groupable per request."""
    if not _STATE["running"]:
        return
    own_thread = tid is None
    if own_thread:
        tid = threading.get_ident()
    with _LOCK:
        if own_thread and tid not in _TID_NAMES:
            _TID_NAMES[tid] = threading.current_thread().name
        ev = {"name": name, "cat": cat, "ph": "X", "ts": start_us,
              "dur": dur_us, "pid": PID_HOST, "tid": tid}
        if args:
            ev["args"] = dict(args)
        _EVENTS.append(ev)


def record_flow(name, fid, phase, ts_us, tid=0, cat="trace"):
    """Append one chrome FLOW endpoint (``phase`` ``"s"`` start /
    ``"f"`` finish, bound by `fid` + `cat` + `name`): the causal
    arrows the request tracer draws between a router-side span and the
    replica-side span chain it triggered (obs/tracing.py; the two ends
    live in different processes' traces and bind after
    tools/obs_stitch.py merges them)."""
    if not _STATE["running"]:
        return
    ev = {"name": name, "cat": cat, "ph": phase, "id": int(fid),
          "ts": int(ts_us), "pid": PID_HOST, "tid": int(tid)}
    if phase == "f":
        ev["bp"] = "e"  # bind to the enclosing slice (chrome flow spec)
    with _LOCK:
        _EVENTS.append(ev)


def register_thread_name(tid, name):
    """Label a SYNTHETIC trace lane: spans recorded on behalf of another
    process (e.g. data-service worker decode, mxnet_tpu/data) carry a
    caller-chosen tid outside the real-thread-id space; this maps it to
    a human name in the dumped trace's thread_name metadata.  First
    registration wins (matching the span-side harvest)."""
    with _LOCK:
        _TID_NAMES.setdefault(int(tid), str(name))


# per-series floor between counter samples: engine gauges update on
# EVERY op push/complete — unthrottled they would dwarf the span lanes
# (4+ events per engine op); 1 ms keeps lanes step-chart-smooth while
# bounding trace growth
_COUNTER_MIN_INTERVAL_US = 1000
_COUNTER_LAST_TS = {}


def record_counter(name, value, ts_us=None):
    """Append one chrome counter sample (``"ph": "C"``): `name` becomes
    a counter LANE in the dumped trace, rendered as a step chart next
    to the span lanes.  telemetry.set_gauge calls this for every gauge
    while profiling is on, so queue depth / buffer occupancy / MFU are
    visible against the dispatch timeline.  Samples landing within
    _COUNTER_MIN_INTERVAL_US of the previous one for the same series
    are dropped (the gauge itself keeps the latest value regardless)."""
    if not _STATE["running"]:
        return
    if ts_us is None:
        ts_us = int(time.time() * 1e6)
    with _LOCK:
        last = _COUNTER_LAST_TS.get(name)
        if last is not None and ts_us - last < _COUNTER_MIN_INTERVAL_US:
            return
        _COUNTER_LAST_TS[name] = ts_us
        _EVENTS.append({"name": name, "cat": "telemetry", "ph": "C",
                        "ts": ts_us, "pid": PID_HOST, "tid": 0,
                        "args": {"value": float(value)}})


# wall clock minus perf_counter, in ns.  A span reads perf_counter_ns
# once at each end; adding this constant puts its start on the wall
# clock the other chrome events (engine ops, counter lanes) are stamped
# with.  Re-taken whenever the chrome profiler starts, so a long-lived
# process does not carry hours of slew between the two clocks.
_WALL_MINUS_PERF_NS = time.time_ns() - time.perf_counter_ns()
_SPAN_IDS = itertools.count(1)
_SPAN_TLS = threading.local()  # .stack: ids of the spans open on this thread
_tracing = _TraceAnnotation.is_enabled  # a profiler session wants TraceMes


class span:
    """Context manager around one host step: the ONE span primitive.

    One clock read on entry and one on exit (``time.perf_counter_ns``)
    feed three sinks:

    * the JAX profiler — the body runs under
      ``jax.profiler.TraceAnnotation("mx:" + name, **attrs)``, so under
      any running profiler session the span is an event of the
      ``/host:CPU`` plane of the same ``.xplane.pb`` as the device's
      ``XLA Ops``.  That file's timestamps count from the session's
      start, so a span with no parent on its thread also carries
      ``wall_ns``, its start on the wall clock: one such event aligns
      the chrome events below with the xplane.  With no session this
      sink costs one level check;
    * telemetry — with ``hist`` given and the registry on, the duration
      in seconds goes into that histogram (not when the body raised);
    * the chrome event list while ``profiler_set_state("run")`` — as
      ``record_span`` does, with ``args`` holding ``id``, ``parent`` (the
      id of the span open on this thread when this one started, 0 for
      none) and the ``attrs``.

    ``name`` is a static string and ``attrs`` are values the caller
    already holds (an int, a bucket, a tenant's name): the guards live
    in here, so a call site builds nothing that is thrown away when
    every sink is off.  After exit ``seconds`` holds the duration and
    ``end_ns`` the span's end on the ``perf_counter_ns`` clock, so a
    caller that needs WHEN the step ended reads no clock of its own."""

    __slots__ = ("name", "cat", "hist", "attrs", "id", "parent", "seconds",
                 "end_ns", "_t0", "_ann")

    def __init__(self, name, cat="operator", hist=None, **attrs):
        self.name = name
        self.cat = cat
        self.hist = hist
        self.attrs = attrs
        self.seconds = self.end_ns = None

    def __enter__(self):
        try:
            stack = _SPAN_TLS.stack
        except AttributeError:
            stack = _SPAN_TLS.stack = []
        self.parent = parent = stack[-1] if stack else 0
        self.id = next(_SPAN_IDS)
        stack.append(self.id)
        self._t0 = t0 = time.perf_counter_ns()
        # constructing the annotation starts it; with no session of the
        # JAX profiler at host level >= 1 it is not even built
        if not _tracing():
            self._ann = None
        elif parent:
            self._ann = _TraceAnnotation("mx:" + self.name, **self.attrs)
        else:
            self._ann = _TraceAnnotation("mx:" + self.name,
                                         wall_ns=t0 + _WALL_MINUS_PERF_NS,
                                         **self.attrs)
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        dur_ns = time.perf_counter_ns() - self._t0
        _SPAN_TLS.stack.pop()
        self.end_ns = self._t0 + dur_ns
        self.seconds = seconds = dur_ns * 1e-9
        if (self.hist is not None and exc_type is None
                and telemetry.enabled()):
            telemetry.observe(self.hist, seconds)
        if _STATE["running"]:
            record_span(self.name,
                        (self._t0 + _WALL_MINUS_PERF_NS) // 1000,
                        dur_ns // 1000, self.cat,
                        args=dict(self.attrs, id=self.id,
                                  parent=self.parent))


def _metadata_events():
    """Chrome ``"ph": "M"`` rows naming the trace's processes/threads:
    pid 0 = host-side spans and counter lanes, pid 1 = the joined XLA
    device trace, plus one thread_name row per host thread that
    recorded spans (engine workers carry their real thread names)."""
    meta = [
        {"name": "process_name", "ph": "M", "pid": PID_HOST, "tid": 0,
         "args": {"name": "host"}},
        {"name": "process_sort_index", "ph": "M", "pid": PID_HOST, "tid": 0,
         "args": {"sort_index": 0}},
        {"name": "process_name", "ph": "M", "pid": PID_DEVICE, "tid": 0,
         "args": {"name": "device (XLA)"}},
        {"name": "process_sort_index", "ph": "M", "pid": PID_DEVICE, "tid": 0,
         "args": {"sort_index": 1}},
    ]
    for tid, tname in sorted(_TID_NAMES.items()):
        meta.append({"name": "thread_name", "ph": "M", "pid": PID_HOST,
                     "tid": tid, "args": {"name": tname}})
    return meta


def dump_profile():
    """Write chrome-tracing JSON (parity: reference Profiler::DumpProfile
    src/engine/profiler.cc:134-190): process/thread naming metadata,
    span lanes, and the telemetry counter lanes.  In a multi-process
    launch (MXTPU_PROCESS_ID exported) the output path is auto-suffixed
    ``.r<rank>`` so N ranks never write over one file, and the payload's
    ``otherData`` carries the rank + measured clock offset vs rank 0 —
    exactly what ``tools/obs_stitch.py`` consumes to merge the per-rank
    traces onto one aligned timeline.  Returns the path written."""
    rank_env = _os.environ.get("MXTPU_PROCESS_ID", "")
    rank = _TRACE_META["rank"]
    if rank is None and rank_env != "":
        rank = int(rank_env)
    path = telemetry.rank_suffixed(_STATE["filename"])
    with _LOCK:
        payload = {"traceEvents": _metadata_events() + list(_EVENTS),
                   "displayTimeUnit": "ms",
                   "otherData": {
                       "rank": 0 if rank is None else rank,
                       "clock_offset_us": _TRACE_META["clock_offset_us"],
                   }}
        with open(path, "w") as f:
            json.dump(payload, f)
        _EVENTS.clear()
    return path


# env-driven bootstrap (reference docs/how_to/env_var.md:97-108)
if _STATE["mode"] not in ("symbolic", "all", "xla"):
    _STATE["mode"] = "symbolic"
if int(_os.environ.get("MXNET_PROFILER_AUTOSTART", "0") or "0"):
    profiler_set_state("run")
