"""Job kind `fit`: `Module.fit`, the way its users are told to run it.

One call to `fit` over an iterator that cycles a few seeded host batches
and ends the epoch when the clock runs out.  `fit` calls the benchmark
back once per dispatch, after it has read the training metric from that
dispatch's outputs — a fence — so the window runs from one such fence
to another and the rate is items between them over seconds between them.
The first `warm_dispatches` of the call compile and settle and belong to
set-up.
"""
import importlib
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from ..harness import common
from ..harness.window import Window, telemetry_snapshot

WATCHED_COUNTERS = ("executor.compile_cache_misses", "mem.program_fallbacks")
CHUNK = 1 << 22


def seeded_normal(shape, seed):
    """Standard normal float32 of `shape` from `seed`, filled in fixed
    chunks by a few threads (NumPy's generators release the GIL); the
    result does not depend on the number of threads."""
    out = np.empty(int(np.prod(shape)), np.float32)
    starts = range(0, out.size, CHUNK)
    seeds = np.random.SeedSequence(int(seed)).spawn(len(starts))

    def fill(job):
        start, child = job
        np.random.default_rng(child).standard_normal(
            out=out[start:start + CHUNK], dtype=np.float32)

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(fill, zip(starts, seeds)))
    return out.reshape(shape)


def clocked_iter(mx, inner, k):
    """A DataIter that cycles `inner` for ever and ends the epoch, on a
    boundary of `k` batches, once `deadline` (perf_counter) has passed;
    the deadline is set when the window starts."""

    class ClockedIter(mx.io.DataIter):
        def __init__(self):
            super().__init__()
            self.batch_size = inner.batch_size
            self.provide_data = inner.provide_data
            self.provide_label = inner.provide_label
            self.deadline = None
            self.served = 0

        def reset(self):
            inner.reset()

        def next(self):
            with common.annotate("next_batch"):
                if (self.served % k == 0 and self.deadline is not None
                        and time.perf_counter() >= self.deadline):
                    raise StopIteration
                try:
                    batch = inner.next()
                except StopIteration:
                    inner.reset()
                    batch = inner.next()
                self.served += 1
                return batch

    return ClockedIter()


def run(cell, args, devices, clock, process_start):
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    config, traffic = cell.config, cell.traffic
    family = importlib.import_module("benchmarks.families." + config["family"])
    telemetry.set_enabled(True)
    ctxs = common.contexts(mx, devices)
    ctxs = ctxs if len(ctxs) > 1 else ctxs[0]
    batch, k = traffic["batch_size"], traffic["steps_per_dispatch"]
    warm = traffic["warm_dispatches"]

    correct_ref, check = family.check_against_reference(
        mx, config, ctxs, args.seed)
    print("[bench] reference check: %s %s" % (correct_ref, check), flush=True)

    n = batch * traffic["distinct_batches"]
    images = seeded_normal((n,) + family.item_shape(config), args.seed)
    labels = np.random.default_rng([args.seed, 1]).integers(
        0, config["num_classes"], n).astype(np.float32)
    it = clocked_iter(mx, mx.io.NDArrayIter(images, labels, batch_size=batch),
                      k)
    mx.random.seed(args.seed)
    mod = mx.mod.Module(family.symbol(config), context=ctxs,
                        compute_dtype=config.get("compute_dtype"))
    metric = mx.metric.CrossEntropy()
    marks = []   # (time, batches done, metric sum, metric count) per dispatch
    state = {"tracer": None}
    trace_s = traffic.get("trace_seconds", 4.0) if args.trace else 0.0

    def batch_end(param):
        with common.annotate("batch_end"):
            now = time.perf_counter()
            marks.append((now, param.nbatch + 1, metric.sum_metric,
                          metric.num_inst))
            if len(marks) == warm:
                # the window opens on this fence
                state["before"] = telemetry_snapshot()
                state["compile"] = clock.read()
                it.deadline = now + args.seconds
                if trace_s:
                    state["tracer"] = common.MidWindowTrace(
                        now, args.seconds, trace_s,
                        traffic.get("trace_host_level", 1))
            elif it.deadline is not None and now >= it.deadline:
                state["after"] = telemetry_snapshot()
                state["compile_end"] = clock.read()

    with common.annotate("fit"):
        mod.fit(it, eval_metric=metric, kvstore=None,
                optimizer=traffic["optimizer"],
                optimizer_params=traffic["optimizer_params"],
                initializer=family.initializer(mx), num_epoch=1,
                steps_per_dispatch=k, batch_end_callback=batch_end)

    if len(marks) <= warm or "after" not in state:
        raise RuntimeError("fit made %d dispatches, not past the %d of "
                           "warm-up: no window" % (len(marks), warm))
    t_open, n_open = marks[warm - 1][:2]
    t_close, n_close = marks[-1][:2]
    steps = n_close - n_open

    def loss_of(i):  # mean cross-entropy of dispatch i alone
        return float((marks[i][2] - marks[i - 1][2])
                     / max(1, marks[i][3] - marks[i - 1][3]))

    first, last = loss_of(warm), loss_of(len(marks) - 1)
    w = Window()
    w.scalars = {"seconds": t_close - t_open, "items": steps * batch,
                 "steps": steps, "dispatches": len(marks) - warm,
                 "items_per_s": steps * batch / (t_close - t_open),
                 "setup_s": t_open - process_start,
                 "flops_per_item": family.train_flops_per_item(config),
                 "loss_first": first, "loss_last": last}
    w.series = {"dispatch_ms": [(b[0] - a[0]) * 1e3 for a, b in
                                zip(marks[warm - 1:], marks[warm:])]}
    w.before, w.after, w.compile = (state["before"], state["after"],
                                    state["compile"])
    w.attempted = len(marks) - warm
    learned = bool(np.isfinite(first) and np.isfinite(last) and last < first)
    w.failed = 0 if learned else w.attempted
    quiet = all(w.counter_delta(c) == 0 for c in WATCHED_COUNTERS)
    compiled_in_window = state["compile_end"][1] - state["compile"][1]
    w.notes = {"check": check, "loss_first": first, "loss_last": last,
               "compiles_in_window": compiled_in_window,
               "watched": {c: w.counter_delta(c) for c in WATCHED_COUNTERS}}
    w.correct = bool(correct_ref and learned and quiet
                     and compiled_in_window == 0)
    if state["tracer"] is not None:
        w.trace = state["tracer"].finish(cell.chips,
                                         devices[0].platform != "cpu")
    return w
