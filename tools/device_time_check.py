#!/usr/bin/env python3
"""Hold the batcher's device-time estimator against the device trace.

    python tools/device_time_check.py --workload <serving cell> --seed <n>
        [--seconds 50] [--out chiprun_out/device_time_<cell>.json]
        [--ops <kind>.<bucket> ...]

runs one traced run of a serving cell of BENCHMARK.json through the
benchmark's own `measure` (so the per-layer metrics, the new `device.*`
ones among them, are printed as a traced run prints them), KEEPS the
xplane the benchmark deletes, and joins the program's spans to the
device's `XLA Modules` events in it.  Chip only: a CPU trace has no
device plane.

The join (docs/observability.md "Device time without a profiler"):

* `mx:decode.dispatch{seq}` contains, on the profiler's one clock, the
  runtime's `DoEnqueueProgram{run_id}` of the call it launched, and the
  `XLA Modules` event with that `run_id` is the flight's run on the
  device (name `<program>(<fingerprint>)`: `program` is the spans'
  attribute, the fingerprint is the runtime's own);
* `mx:decode.device_wait{seq}` is the flight's fence: its end is
  `ready(k)`, the dispatch span's end `enqueued(k)`;
* which program a flight ran: `mx:decode.dispatch{kind, bucket}` says it
  (`decode` and its rows, `prefill` and its positions, or `mixed` — a
  prefill bucket's program that is the mixed step, PR 46 — and its
  positions); in a trace from before those attributes,
  `mx:serve.decode_step{seq, bucket}` names the step it dispatched and
  `mx:serve.prefill{seq, bucket}` the prefill it read;
* where a trace has no `DoEnqueueProgram` (host level 0), the flight's
  run is the `XLA Modules` event of its program that ended last before
  the fence returned — right for every flight whose fence blocked.

`--ops prefill.256` / `--ops mixed.256` / `--ops decode.8` (repeatable)
prints the device ops of that program summed by name — each run's `XLA Ops` events inside its `XLA Modules`
interval, self times, as ms a run — and writes the program's optimised
HLO beside the report (`device_time_<cell>.<kind>.<bucket>.hlo`): which
op of a bucket's program takes its time, and what the compiler made of
it, in one command.  Below it the same ops summed by the innermost ``mx:``
scope (`jax.named_scope`) of each instruction's `op_name` in that HLO
(`scope_of`): the device time under `mx:dsa.read`, `mx:mla.expand`, ….

Per kind it reports the estimate (`serving.decode.device_interval` on
the spans' times, the rule `GenerativeSession._book_device` books by)
against the module's duration for every SEEN flight, the completion's
way to the host (fence exit - module end), the share of flights seen,
and the gaps in which the chip had nothing queued against its idle time.
"""
import argparse
import bisect
import json
import os
import re
import shutil
import statistics
import sys
import threading
import time

PROCESS_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SPANS = ("mx:decode.dispatch", "mx:decode.device_wait",
         "mx:serve.decode_step", "mx:serve.prefill")
ENQUEUE = "DoEnqueueProgram"
OPS_SHOWN = 12  # lines of an --ops table on the terminal; the file has all


def read_trace(path):
    """(spans, enqueues, modules, ops) of one xplane: `spans[name]` =
    [(start_ns, end_ns, seq, program, bucket, kind)], `enqueues` = [(start_ns,
    run_id)], `modules` = [(start_ns, end_ns, name, run_id)] of the
    first chip, `ops` = [(start_ns, end_ns, name)] of its `XLA Ops`, by
    start."""
    from benchmarks.harness import trace_reduce

    data = trace_reduce.load(path)
    spans = {name: [] for name in SPANS}
    enqueues, modules, ops = [], [], []
    for plane in data.planes:
        first_chip = plane.name == "/device:TPU:0"
        if not (first_chip or plane.name == "/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                start, end = ev.start_ns, ev.start_ns + ev.duration_ns
                if first_chip:
                    if line.name == trace_reduce.MODULES_LINE:
                        stats = dict(ev.stats)
                        modules.append((start, end, ev.name,
                                        stats.get("run_id")))
                    elif line.name == trace_reduce.OPS_LINE:
                        ops.append((start, end, ev.name))
                elif ev.name in spans:
                    stats = dict(ev.stats)
                    spans[ev.name].append((start, end, int(stats["seq"]),
                                           str(stats.get("program", "")),
                                           int(stats.get("bucket", 0)),
                                           str(stats.get("kind", ""))))
                elif ev.name == ENQUEUE:
                    enqueues.append((start, dict(ev.stats).get("run_id")))
    enqueues.sort(key=lambda e: e[0])
    modules.sort(key=lambda m: m[:2])
    ops.sort()
    return spans, enqueues, modules, ops


_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = .*op_name=\"([^\"]*)\"", re.M)
_SCOPE = re.compile(r"mx:[\w.]+")


def scope_of(hlo_text):
    """A key for `ops_by_name` that sums a program's device ops by the
    innermost ``mx:`` scope (`jax.named_scope`) of each instruction's
    `op_name` in the program's optimised HLO (a fusion carries its
    root's); "-" for an instruction outside every scope or without
    metadata (a `while`, a copy the compiler added)."""
    scopes = {name: (_SCOPE.findall(op_name) or ["-"])[-1]
              for name, op_name in _INSTRUCTION.findall(hlo_text)}

    def key(event_name):
        return scopes.get(event_name.partition(" = ")[0].lstrip("%"), "-")

    return key


def ops_by_name(rows, ops, kind, bucket, key=None):
    """The device ops of the `(kind, bucket)` program's runs in the
    trace, summed by what kind of op each is (`trace_reduce.op_kind`:
    opcode, shapes, layouts and tiles, names dropped; or by `key` of the
    event's name, `scope_of`'s): {runs, module_ms, ops: [[name, ms a run,
    events a run]]} by falling time, self times (a `while` is charged
    what its body leaves)."""
    from benchmarks.harness import trace_reduce

    key = key or trace_reduce.op_kind
    runs = [r["module"] for r in rows
            if r["module"] and (r["kind"], r["bucket"]) == (kind, bucket)]
    if not runs:
        return {"runs": 0, "module_ms": None, "ops": []}
    starts = [op[0] for op in ops]  # sorted, as `ops` is
    seconds, counts = {}, {}
    for m_start, m_end, _name in runs:
        inside = [(s, e, key(name)) for s, e, name in ops[
            bisect.bisect_left(starts, m_start):
            bisect.bisect_right(starts, m_end)] if e <= m_end]
        for name, t in trace_reduce._self_times(inside).items():
            seconds[name] = seconds.get(name, 0.0) + t
        for _s, _e, name in inside:
            counts[name] = counts.get(name, 0) + 1
    n = len(runs)
    table = sorted(([name, 1e3 * t / n, counts[name] / n]
                    for name, t in seconds.items()), key=lambda r: -r[1])
    return {"runs": n,
            "module_ms": statistics.fmean((e - s) * 1e-6
                                          for s, e, _name in runs),
            "ops": table}


def join(spans, enqueues, modules):
    """One row a flight whose dispatch and fence are both in the trace,
    in `seq` order: {seq, kind, bucket, program, sent, ready, waited,
    module: (start, end, name) or None, by: "run_id" | "nearest" |
    None}."""
    by_run = {m[3]: m for m in modules if m[3] is not None}
    launched = [at for at, _rid in enqueues]  # sorted, as `enqueues` is
    fences = {sp[2]: (sp[0], sp[1], sp[3])
              for sp in spans["mx:decode.device_wait"]}
    ran = {sp[2]: ("decode", sp[4])
           for sp in spans["mx:serve.decode_step"] if sp[2]}
    ran.update({sp[2]: ("prefill", sp[4]) for sp in spans["mx:serve.prefill"]})
    # a dispatch span that says its program's kind says it best
    ran.update({sp[2]: (sp[5], sp[4])
                for sp in spans["mx:decode.dispatch"] if sp[5:] and sp[5]})
    rows = []
    for start, end, seq, *_ in sorted(
            spans["mx:decode.dispatch"], key=lambda sp: sp[2]):
        if seq not in fences:
            continue
        f_start, f_end, program = fences[seq]
        run_ids = [rid for _at, rid in enqueues[
            bisect.bisect_left(launched, start):
            bisect.bisect_right(launched, end)]]
        module, how = None, None
        if len(run_ids) == 1 and run_ids[0] in by_run:
            module, how = by_run[run_ids[0]], "run_id"
        else:
            ended = [m for m in modules
                     if m[1] <= f_end and m[2].split("(")[0] == program]
            if ended:
                module, how = ended[-1], "nearest"
        kind, bucket = ran.get(seq, ("?", 0))
        rows.append({"seq": seq, "kind": kind, "bucket": bucket,
                     "program": program, "sent": end,
                     "ready": f_end, "waited": f_end - f_start,
                     "module": module and module[:3], "by": how})
    return rows


def _spread(values):
    values = sorted(values)
    if not values:
        return None
    at = lambda q: values[min(len(values) - 1, int(q * len(values)))]  # noqa: E731
    return {"n": len(values), "mean": statistics.fmean(values),
            "p5": at(0.05), "p50": at(0.5), "p95": at(0.95)}


def compare(rows, floor_s):
    """Per kind: the estimate against the module for every seen flight
    (ms), the completion's way to the host, and the shares."""
    from mxnet_tpu.serving.decode import device_interval

    out, per, last, gaps = {}, {}, None, 0
    for prev, row in zip([None] + rows[:-1], rows):
        if prev is None or prev["seq"] != row["seq"] - 1:
            last = None  # the flight before it is not in the trace
        blocked = row["waited"] * 1e-9 > floor_s
        start, seen, gap = device_interval(last, row["sent"], row["ready"],
                                           blocked)
        last = (row["ready"], blocked)
        gaps += gap
        acc = per.setdefault(row["kind"], {
            "flights": 0, "seen": 0, "own_fence": 0, "fence_before": 0,
            "by_run_id": 0, "diff_ms": [], "module_ms": [],
            "estimate_ms": [], "latency_ms": []})
        acc["flights"] += 1
        acc["seen"] += seen
        # why a flight was not seen: its own fence returned at once, or
        # (it blocked, but) the fence of the flight it was queued behind
        acc["own_fence"] += not blocked
        acc["fence_before"] += blocked and not seen
        acc["by_run_id"] += row["by"] == "run_id"
        if row["module"] is None:
            continue
        m_start, m_end, _name = row["module"]
        if blocked:
            acc["latency_ms"].append((row["ready"] - m_end) * 1e-6)
        if seen:
            acc["estimate_ms"].append((row["ready"] - start) * 1e-6)
            acc["module_ms"].append((m_end - m_start) * 1e-6)
            acc["diff_ms"].append((row["ready"] - start - (m_end - m_start))
                                  * 1e-6)
    for kind, acc in per.items():
        est, mod = acc["estimate_ms"], acc["module_ms"]
        out[kind] = {
            "flights": acc["flights"], "seen": acc["seen"],
            "unseen_own_fence": acc["own_fence"],
            "unseen_fence_before": acc["fence_before"],
            "joined_by_run_id": acc["by_run_id"],
            "estimate_ms": _spread(est), "module_ms": _spread(mod),
            "estimate_minus_module_ms": _spread(acc["diff_ms"]),
            "mean_diff_pct": (100.0 * (statistics.fmean(est)
                                       / statistics.fmean(mod) - 1.0)
                              if est else None),
            "completion_latency_ms": _spread(acc["latency_ms"])}
    return out, gaps * 1e-9


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--ops", action="append", default=[],
                    metavar="KIND.BUCKET",
                    help="print that program's device ops summed by name "
                         "and keep its optimised HLO (e.g. prefill.256)")
    args = ap.parse_args(argv)
    wanted = [(k, int(b)) for k, _dot, b in
              (tag.rpartition(".") for tag in args.ops)]
    args.trace = 1

    from benchmarks import run as bench_run
    from benchmarks.harness import common, device, spec, trace_reduce

    bench = spec.load_benchmark()
    cell = spec.Cell(bench, args.workload)
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    import jax

    import mxnet_tpu  # noqa: F401 — places the compile cache
    from mxnet_tpu import telemetry
    from mxnet_tpu.obs import memory
    from mxnet_tpu.serving import decode

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = device.require_accelerator(cell.chips)
    kept = os.path.join(ROOT, "chiprun_out",
                        "device_time_%s.xplane.pb" % cell.name)
    os.makedirs(os.path.dirname(kept), exist_ok=True)
    finish = common.MidWindowTrace.finish

    def keep_then_finish(self, chips, on_chip):
        self._thread.join(600)
        shutil.copy(trace_reduce.find_xplane(common.TRACE_DIR), kept)
        return finish(self, chips, on_chip)

    common.MidWindowTrace.finish = keep_then_finish
    # flights landed and seen, second by second: whether what is not
    # seen comes evenly, in bursts, or with the profiler's four seconds
    seconds, stop = [], threading.Event()

    def sample():
        while not stop.wait(1.0):
            seconds.append((
                telemetry.counter_value("serving.device.flights"),
                telemetry.counter_value("serving.device.seen_flights")))

    threading.Thread(target=sample, daemon=True).start()
    # what reading a program's name off its compiled object costs set-up
    named, module_name = [], memory.Program.module_name

    def timed_module_name(self):
        t0 = time.perf_counter()
        try:
            return module_name(self)
        finally:
            named.append(time.perf_counter() - t0)

    memory.Program.module_name = timed_module_name
    # the compiled programs by (kind, bucket), for --ops' HLO
    programs, program_of = {}, decode.GenerativeSession._program

    def remembered_program(self, pred, batch, seq, prefill):
        exe, fn = program_of(self, pred, batch, seq, prefill)
        kind = "decode" if not prefill else (
            "mixed" if self._mixed else "prefill")
        programs[kind, seq if prefill else batch] = fn
        return exe, fn

    decode.GenerativeSession._program = remembered_program
    result = bench_run.measure(cell, args, devices, device.CompileClock(),
                               PROCESS_START)
    stop.set()
    print(json.dumps(result), flush=True)

    spans, enqueues, modules, ops = read_trace(kept)
    busy = trace_reduce._merge(op[:2] for op in ops)
    rows = join(spans, enqueues, modules)
    report, gaps_s = compare(rows, decode._FENCE_FLOOR_S)
    names, by_program = {}, {}
    for row in rows:
        if row["module"]:
            tag = "%s.%d" % (row["kind"], row["bucket"])
            names.setdefault(row["module"][2], set()).add(tag)
            by_program.setdefault(tag, []).append(
                (row["module"][1] - row["module"][0]) * 1e-6)
    lo = min((r["sent"] for r in rows), default=0)
    hi = max((r["ready"] for r in rows), default=0)
    idle_s = (hi - lo - sum(min(e, hi) - max(s, lo) for s, e in busy
                            if e > lo and s < hi)) * 1e-9
    hists = telemetry.snapshot()["histograms"]
    summary = {
        "cell": cell.name, "seed": args.seed,
        "flights_in_trace": len(rows), "traced_s": (hi - lo) * 1e-9,
        "kinds": report,
        "module_ms_by_program": {k: _spread(v)
                                 for k, v in sorted(by_program.items())},
        "booked_ms_by_program": {
            k[len("serving.device."):]: {"n": h["count"],
                                         "mean": 1e3 * h["sum"] / h["count"]}
            for k, h in sorted(hists.items())
            if k.startswith("serving.device.") and h.get("count")},
        "module_names": {k: sorted(v) for k, v in sorted(names.items())},
        # a step's fence by how long it lasted: le_0.0001 and below did
        # not block (whole process, the warm-up's few steps included)
        "step_fence_buckets": hists.get(
            "serving.decode.device_wait_seconds", {}).get("buckets"),
        "gaps_nothing_queued_s": gaps_s, "chip_idle_s": idle_s,
        "module_name_calls_s": [len(named), sum(named)],
        "landed_seen_by_second": [
            (b[0] - a[0], b[1] - a[1])
            for a, b in zip(seconds, seconds[1:]) if b[0] > a[0]],
        "rows": [[r["seq"], r["kind"], r["bucket"], r["sent"], r["ready"],
                  r["waited"]] + list((r["module"] or (None, None))[:2])
                 for r in rows],
        "metrics": {k: v["value"] for k, v in result["metrics"].items()
                    if k.startswith(("device.", "batcher.device_wait",
                                     "batcher.decode_step",
                                     "batcher.prefill"))}}
    print("[device_time] " + json.dumps(dict(summary, rows=len(rows))),
          flush=True)
    out = args.out or os.path.join(ROOT, "chiprun_out",
                                   "device_time_%s.json" % cell.name)
    summary["ops_by_program"], summary["scopes_by_program"] = {}, {}
    for kind, bucket in wanted:
        tag = "%s.%d" % (kind, bucket)
        table = summary["ops_by_program"][tag] = ops_by_name(
            rows, ops, kind, bucket)
        print("[device_time] ops of %s: %d runs, %s ms a run" % (
            tag, table["runs"], table["module_ms"]))
        for name, ms, n in table["ops"][:OPS_SHOWN]:
            print("  %8.4f ms  x%-5.4g %s" % (ms, n, name))
        if (kind, bucket) in programs:
            hlo = programs[kind, bucket].hlo_text() or ""
            with open("%s.%s.hlo" % (os.path.splitext(out)[0], tag),
                      "w") as f:
                f.write(hlo)
            scopes = summary["scopes_by_program"][tag] = ops_by_name(
                rows, ops, kind, bucket, key=scope_of(hlo))["ops"]
            print("[device_time] scopes of %s:" % tag)
            for name, ms, n in scopes:
                print("  %8.4f ms  x%-5.4g %s" % (ms, n, name))
    with open(out, "w") as f:
        json.dump(summary, f, indent=1)
    os.remove(kept)


if __name__ == "__main__":
    main()
