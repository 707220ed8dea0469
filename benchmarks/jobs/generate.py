"""Job kind `generate`: token generation through `ModelServer`.

The tenant is registered with `add_generative_tenant`, requests go
through `submit_generate`, and every token's arrival is timed on the
client's side from `on_token` — the program's own latency histograms
have half-decade buckets and are not read for tails.  A traffic file
says how requests arrive (`arrivals`: an open loop at a fixed rate, or a
closed loop of clients) and what they look like (`requests`), see
benchmarks/harness/loadgen.py.
"""
import importlib
import queue
import time

import numpy as np

from ..harness import common, loadgen
from ..harness.window import Window, telemetry_snapshot

TENANT = "lm"
QUEUE_TIMEOUT_MS = 3600e3   # far beyond any window: nothing times out
FIRST_TOKEN_GRACE_S = 60.0  # after the window, for requests sent in it
WATCHED_COUNTERS = ("executor.compile_cache_misses", "mem.program_fallbacks",
                    "serving.dispatch_errors")


class _Rec:
    __slots__ = ("due", "sent", "times", "budget", "future")

    def __init__(self, due, budget):
        self.due, self.budget = due, budget
        self.sent, self.future, self.times = None, None, []


def _submit(server, rec, req):
    times = rec.times
    with common.annotate("send"):
        rec.sent = time.perf_counter()
        rec.future = server.submit_generate(
            TENANT, req.prompt, max_new_tokens=req.budget,
            timeout_ms=QUEUE_TIMEOUT_MS,
            on_token=lambda _tok: times.append(time.perf_counter()))


def open_loop(server, requests, due, t0, seconds):
    """Send request i at t0 + due[i] whatever the server is doing; return
    the records once the window is over and every request sent in it has
    its first token (or the grace has run out)."""
    recs = []
    for i, at in enumerate(due):
        with common.annotate("wait_due"):
            wait = t0 + at - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
        rec = _Rec(t0 + at, requests[i].budget)
        _submit(server, rec, requests[i])
        recs.append(rec)
    with common.annotate("wait_reply"):
        time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
        limit = time.perf_counter() + FIRST_TOKEN_GRACE_S
        while (any(not r.times and not r.future.done() for r in recs)
               and time.perf_counter() < limit):
            time.sleep(0.005)
    return recs


def closed_loop(server, requests, clients, t0, seconds):
    """`clients` callers, each sending its next request when its last
    one completes, until the window is over."""
    done, recs = queue.Queue(), []

    def send():
        rec = _Rec(None, requests[len(recs)].budget)
        _submit(server, rec, requests[len(recs)])
        rec.due = rec.sent
        rec.future.add_done_callback(done.put)
        recs.append(rec)

    for _ in range(clients):
        send()
    while True:
        with common.annotate("wait_reply"):
            left = t0 + seconds - time.perf_counter()
            if left <= 0:
                return recs
            try:
                done.get(timeout=left)
            except queue.Empty:
                return recs
        send()


def _verdicts(recs, t_end, waiting_counts):
    """(attempted, failed, completed), judged when the window is over
    and before the server is closed.  A request fails when its future
    raised, or a finished reply does not hold exactly its budget of
    tokens, or — in an open loop, where `waiting_counts` — it still has
    no first token.  A closed loop's queued requests, by design as many
    as there are slots, are simply not attempted yet."""
    attempted = failed = completed = 0
    for r in recs:
        if r.future.done():
            attempted += 1
            if r.future.exception() is not None:
                failed += 1
                continue
            res = r.future.result()
            ok = len(res.tokens) == r.budget and res.finish_reason == "length"
            failed += not ok
            completed += ok and r.times[-1] <= t_end
        elif r.times or waiting_counts:
            attempted += 1
            failed += not r.times
    return attempted, failed, completed


def summarize(recs, t0, seconds, waiting_counts):
    """Series and scalars of one window, from the client-side records.
    Gaps and tokens after the window's end are left out: once arrivals
    stop, the batcher runs unloaded."""
    t_end = t0 + seconds
    ttft, itl, late = [], [], []
    tokens = 0
    for r in recs:
        late.append((r.sent - r.due) * 1e3)
        ts = [t for t in r.times if t <= t_end]
        tokens += len(ts)
        if r.times:
            ttft.append((r.times[0] - r.due) * 1e3)
        itl.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:]))
    attempted, failed, completed = _verdicts(recs, t_end, waiting_counts)
    return ({"ttft_ms": ttft, "itl_ms": itl, "late_ms": late},
            {"seconds": seconds, "tokens": tokens, "requests": attempted,
             "requests_completed": completed,
             "tokens_per_s": tokens / seconds},
            attempted, failed)


def left_waiting(recs, t_end, limits):
    """Requests that had the TTFT limit's time inside the window and
    still had no first token at its end: the backlog an open loop above
    its knee leaves (benchmarks/README.md, "Finding the knee")."""
    allowed = limits.get("ttft_ms", 0.0) / 1e3
    return sum(1 for r in recs if r.due + allowed <= t_end
               and (not r.times or r.times[0] > t_end))


def _warm_up(server, tenant, vocab, seed):
    """Compile every program the window can use by sending real requests:
    one prompt per prefill bucket, and budgets staggered so that the
    active-session count passes through every value up to the slots.
    (The program's own `warm()` threads a second, throwaway set of KV
    rings, which a chip filled as a deployment is cannot hold.)  Whether
    it reached every program is the window's to say: a compile or a
    compile-cache miss inside it makes the run not `correct`."""
    rng = np.random.default_rng([seed, 7])
    buckets = tenant["seq_buckets"]
    futures = []
    for i in range(max(tenant["max_sessions"], len(buckets))):
        n = buckets[i % len(buckets)]
        budget = min(2 + 2 * i, tenant["max_len"] - n)
        futures.append(server.submit_generate(
            TENANT, rng.integers(0, vocab, n), max_new_tokens=budget,
            timeout_ms=QUEUE_TIMEOUT_MS))
    for f in futures:
        f.result(timeout=1800)


class Served:
    """The tenant, warm and checked, ready for windows."""

    def __init__(self, cell, seed, devices):
        import mxnet_tpu as mx
        from mxnet_tpu import telemetry

        self.cell, self.seed = cell, seed
        config, traffic = cell.config, cell.traffic
        self.family = importlib.import_module(
            "benchmarks.families." + config["family"])
        telemetry.set_enabled(True)
        ctx = common.contexts(mx, devices)[0]
        # weights are made on the device; both of the tenant's predictors
        # bind these same arrays
        self.params = self.family.make_params(config, seed, devices[0])
        held = {k: mx.nd.NDArray(v, ctx) for k, v in self.params.items()}
        self.server = mx.serving.ModelServer({})
        session = self.server.add_generative_tenant(
            TENANT, self.family.model(config), held, ctx=ctx,
            **traffic["tenant"])
        _warm_up(self.server, traffic["tenant"], config["vocab_size"], seed)
        self.correct, self.check = self.family.check_against_reference(
            config, session, self.params, seed,
            min(traffic["tenant"]["seq_buckets"]))
        print("[bench] reference check: %s %s" % (self.correct, self.check),
              flush=True)
        self.requests = loadgen.RequestList(
            traffic["requests"], config["vocab_size"], seed)

    def window(self, seconds, rate=None, drain=False, trace_s=0.0):
        """One measured window, summarized before anything is closed;
        `rate` overrides an open loop's rate (the sweep) and `drain`
        then waits for every reply, so that the next step starts on an
        empty server.  Returns (summary, t0, before, after, tracer,
        records)."""
        arrivals = self.cell.traffic["arrivals"]
        is_open = arrivals["process"] == "open"
        if is_open:
            due = loadgen.arrival_times(
                rate or arrivals["rate_per_s"], seconds, self.seed)
            reqs = [self.requests[i] for i in range(len(due))]
        before = telemetry_snapshot()
        t0 = time.perf_counter()
        tracer = (common.MidWindowTrace(
                      t0, seconds, trace_s,
                      self.cell.traffic.get("trace_host_level", 1))
                  if trace_s else None)
        if is_open:
            recs = open_loop(self.server, reqs, due, t0, seconds)
        else:
            recs = closed_loop(self.server, self.requests,
                               arrivals["clients"], t0, seconds)
        after = telemetry_snapshot()
        summary = summarize(recs, t0, seconds, waiting_counts=is_open)
        if drain:
            for r in recs:
                r.future.exception(timeout=600)
        return summary, t0, before, after, tracer, recs

    def close(self):
        self.server.close(drain=False)


def run(cell, args, devices, clock, process_start):
    served = Served(cell, args.seed, devices)
    try:
        compiled = clock.read()
        trace_s = cell.traffic.get("trace_seconds", 4.0) if args.trace else 0.0
        summary, t0, before, after, tracer, recs = served.window(
            args.seconds, trace_s=trace_s)
        served.close()
    except BaseException:
        served.server.close(drain=False)
        raise
    w = Window()
    w.series, w.scalars, w.attempted, w.failed = summary
    w.scalars["setup_s"] = t0 - process_start
    w.scalars["slots"] = cell.traffic["tenant"]["max_sessions"]
    w.before, w.after, w.compile = before, after, compiled
    quiet = all(w.counter_delta(c) == 0 for c in WATCHED_COUNTERS)
    compiled_in_window = clock.read()[1] - compiled[1]
    q = loadgen.quantile
    w.notes = {"check": served.check,
               "ttft_ms": {str(p): q(w.series["ttft_ms"], p)
                           for p in (0.5, 0.75, 0.9, 0.95)},
               "itl_ms": {str(p): q(w.series["itl_ms"], p)
                          for p in (0.5, 0.9, 0.95, 0.98, 0.99)},
               "late_p99_ms": q(w.series["late_ms"], 0.99),
               "compiles_in_window": compiled_in_window,
               "watched": {c: w.counter_delta(c) for c in WATCHED_COUNTERS}}
    if "limits" in cell.traffic:   # an open loop's: a closed one queues by design
        w.notes["left_waiting"] = left_waiting(recs, t0 + args.seconds,
                                               cell.traffic["limits"])
    w.correct = bool(served.correct and quiet and compiled_in_window == 0
                     and w.failed == 0 and w.attempted > 0)
    if tracer is not None:
        w.trace = tracer.finish(cell.chips, devices[0].platform != "cpu")
    return w


def sweep(cell, args, devices, rates):
    """Not a cell: step an open loop's rate on one warm tenant and print
    what each rate did, to find the knee (benchmarks/README.md)."""
    served = Served(cell, args.seed, devices)
    limits = cell.traffic.get("limits", {})
    try:
        for rate in rates:
            (series, scalars, attempted, failed), t0, _b, _a, _t, recs = \
                served.window(args.seconds, rate=rate, drain=True)
            t_end = t0 + args.seconds
            met = sum(
                1 for r in recs
                if r.times and (r.times[0] - r.due) * 1e3
                <= limits.get("ttft_ms", float("inf"))
                and (len(r.times) < 2
                     or (r.times[-1] - r.times[0]) * 1e3 / (len(r.times) - 1)
                     <= limits.get("itl_mean_ms", float("inf"))))
            q = loadgen.quantile
            print("[sweep] " + " ".join("%s=%s" % kv for kv in [
                ("rate_per_s", rate), ("sent", attempted), ("failed", failed),
                ("attained_pct", round(100.0 * met / max(1, attempted), 1)),
                ("ttft_p50_ms", round(q(series["ttft_ms"], 0.5), 1)),
                ("ttft_p90_ms", round(q(series["ttft_ms"], 0.9), 1)),
                ("itl_p50_ms", round(q(series["itl_ms"], 0.5), 2)),
                ("itl_p99_ms", round(q(series["itl_ms"], 0.99), 2)),
                ("tokens_per_s", round(scalars["tokens_per_s"], 1)),
                ("late_p99_ms", round(q(series["late_ms"], 0.99), 2)),
                ("left_waiting", left_waiting(recs, t_end, limits)),
                ("drain_s", round(max(r.times[-1] for r in recs if r.times)
                                  - t_end, 2))]), flush=True)
    finally:
        served.close()
