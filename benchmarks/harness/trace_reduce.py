"""From a profiler trace (.xplane.pb) to the device numbers of a run.

Reads the trace with `jax.profiler.ProfileData` and nothing else.  The
reduction is kept here, with the benchmark, so that every PR computes
the same number in the same way; it is checked on a small recorded trace
(benchmarks/tests/data/).

What a TPU trace holds (looked at by hand, PR 22): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per executed
HLO op (start, duration); host threads are lines of the plane
``/host:CPU``, and the benchmark's own `TraceAnnotation`s (names that
start with ``bench:``) are events there.  All planes share one clock.
"""
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"  # one event per executed program
COLLECTIVE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast)", re.I)
ANNOTATION_PREFIX = "bench:"
WINDOW_LABEL = "window"  # the span around the whole traced stretch
TOP = 10
NAME_LEN = 160
_OPERAND = re.compile(r"%[\w.\-]+")


def op_kind(hlo_text):
    """A device event's name is its whole HLO instruction.  Dropping
    the instruction and operand names leaves what kind of op it is
    (opcode, shapes, layouts), so that the 24 copies of one layer's
    fusion add up under one entry."""
    head, sep, rest = hlo_text.partition(" = ")
    text = rest if sep else head
    return _OPERAND.sub("%", text)[:NAME_LEN]


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return paths[-1]


def _merge(intervals):
    """Union of [start, end) intervals as a sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _total(merged):
    return sum(e - s for s, e in merged)


def _subtract(merged_a, merged_b):
    """Total length of the part of `merged_a` that `merged_b` leaves
    uncovered (both sorted and disjoint)."""
    left, j = 0.0, 0
    for s, e in merged_a:
        cur = s
        while j < len(merged_b) and merged_b[j][1] <= cur:
            j += 1
        k = j
        while k < len(merged_b) and merged_b[k][0] < e:
            bs, be = merged_b[k]
            if bs > cur:
                left += bs - cur
            cur = max(cur, be)
            k += 1
        if cur < e:
            left += e - cur
    return left


def _self_times(events):
    """name -> seconds of self time: an op that contains others (a
    while loop, a call) is charged only what its children leave."""
    totals = {}
    stack = []  # (end, name, self_ns as a one-element list)
    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            end, n, own = stack.pop()
            totals[n] = totals.get(n, 0.0) + own[0]
        if stack:
            stack[-1][2][0] -= min(e, stack[-1][0]) - s
        stack.append((e, name, [e - s]))
    for end, n, own in stack:
        totals[n] = totals.get(n, 0.0) + own[0]
    return {n: max(t, 0.0) / 1e9 for n, t in totals.items()}


def load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _leaves(events):
    """The events that contain no other event.  A `while` or `call` op
    spans its whole body (a K-step training block is one `while`), so
    counting it would call every gap inside the loop busy and hide every
    collective under "another op was running"."""
    out, stack = [], []  # stack of [end, has_child, event]
    for ev in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= ev[0]:
            end, has_child, done = stack.pop()
            if not has_child:
                out.append(done)
        if stack:
            stack[-1][1] = True
        stack.append([ev[1], False, ev])
    out.extend(done for _end, has_child, done in stack if not has_child)
    return out


def inventory(data):
    """(plane, line, events, first start s, last end s) per line of a
    loaded trace: printed by a traced run on lines before the result,
    for whoever reads a new device's trace for the first time."""
    rows = []
    for plane in data.planes:
        for line in plane.lines:
            n, lo, hi = 0, float("inf"), 0.0
            for ev in line.events:
                n += 1
                lo = min(lo, ev.start_ns)
                hi = max(hi, ev.start_ns + ev.duration_ns)
            if n:
                rows.append((plane.name, line.name, n, lo / 1e9, hi / 1e9))
    return rows


def reduce_trace(data, chips):
    """The device numbers of one traced window; `data` is a loaded
    trace or the path of one.

    Returns a dict: `busy_s` (seconds in which an op ran on a chip, the
    union of the intervals of ops that contain no other op, mean over
    the chips used), `window_s` (the
    traced window: first to last program start), `idle_share`,
    `exposed_collective_s`
    (time in collective ops during which no other op ran on that chip,
    mean over chips), `device_ops` / `idle_gaps` (the breakdown lists).
    Raises ValueError when the trace has no device plane: a traced run
    in which nothing ran on the device has no device numbers."""
    if isinstance(data, (str, os.PathLike)):
        data = load(data)
    devices, modules, annotations = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules[int(m.group(1))] = [ev.start_ns
                                                for ev in line.events]
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    if ev.duration_ns > 0:
                        evs.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                    ev.name))
            devices[int(m.group(1))] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(ANNOTATION_PREFIX):
                        annotations.append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns,
                             ev.name[len(ANNOTATION_PREFIX):]))
    used = [devices[k] for k in sorted(devices)[:chips] if devices[k]]
    if not used:
        raise ValueError("the trace has no device op events (planes: %s)"
                         % [p.name for p in data.planes])
    # The window is made of what the device tracer caught, not of the
    # host's `bench:window` span: the device tracer starts with the next
    # program that is enqueued, so under second-long programs (a K-step
    # training block, two of them in flight) its events begin where the
    # host's span ends (seen on the v5e, PR 22).  It runs from the first
    # program's start to the LAST program's start, a whole number of
    # program periods: first op to last op would count one busy stretch
    # more than idle gaps, and call a host-bound job that runs one
    # 0.4 s block every 4 s busy.
    lo = min(ev[0] for evs in used for ev in evs)
    hi = max(ev[1] for evs in used for ev in evs)
    starts = sorted(modules.get(sorted(devices)[0], []))
    if len(starts) >= 3:
        lo, hi = starts[0], starts[-1]
    labels = [a for a in annotations if a[2] != WINDOW_LABEL]

    busy, exposed, ops, gaps = [], [], {}, {}
    for evs in used:
        evs = [(max(s, lo), min(e, hi), n) for s, e, n in evs
               if min(e, hi) > max(s, lo)]
        leaves = _leaves(evs)
        merged = _merge((s, e) for s, e, _ in leaves)
        busy.append(_total(merged) / 1e9)
        coll = _merge((s, e) for s, e, n in leaves if COLLECTIVE.search(n))
        rest = _merge((s, e) for s, e, n in leaves
                      if not COLLECTIVE.search(n))
        exposed.append(_subtract(coll, rest) / 1e9)
        for name, sec in _self_times(evs).items():
            kind = op_kind(name)
            ops[kind] = ops.get(kind, 0.0) + sec / len(used)
    # idle gaps of the first chip, by what the benchmark's host thread
    # was doing at the gap's middle
    first = [(max(s, lo), min(e, hi)) for s, e, _ in _leaves(used[0])
             if min(e, hi) > max(s, lo)]
    merged = _merge(first)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        mid = (s + e) / 2
        inside = [a for a in labels if a[0] <= mid < a[1]]
        # the innermost annotation names what was going on
        name = (min(inside, key=lambda a: a[1] - a[0])[2] if inside
                else "unannotated")
        gaps[name] = gaps.get(name, 0.0) + (e - s) / 1e9
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy) / len(busy)

    def top(d):
        return [[n, v] for n, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:TOP]]

    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s,
            "exposed_collective_s": sum(exposed) / len(exposed),
            "chips_traced": len(used),
            "device_ops": top(ops), "idle_gaps": top(gaps)}
