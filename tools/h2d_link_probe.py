#!/usr/bin/env python3
"""How fast do host batches reach the chips of one host?

The probe that decided how `NDArrayIter` batches reach the chips (PR 39:
they stay in host memory and staging sends each chip its own rows; the
numbers are in PERF.md section 6).  One float32 batch of the fit cell's shape (1024 x 3 x 224 x 224, 616 MB) is
cut into one row-view a chip and `jax.device_put` to its chip,

  (a) all views enqueued from ONE thread, then awaited,
  (b) one thread a chip, each enqueues and awaits its own view,

against (c) the whole batch to chip 0, which is what `nd.array` does
(and every `NDArrayIter` batch did until PR 39).  Each form is also run as a block of K batches with at most two
steps in flight, the rule `DeviceStagedIter._fetch_block` keeps, and
from a source that is a host-resident `jax.Array` (the CPU backend's
device beside the TPU's) instead of numpy: what `place_step_input`'s
host branch is handed.

    chiprun --chips 4 -- python3 tools/h2d_link_probe.py

Prints one JSON object and writes it to chiprun_out/h2d_link_probe.json.
On the CPU backend (`--rows 64`) it rehearses the control flow only: a
"GB/s" from there is no link's.
"""
import argparse
import json
import os
import statistics
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np


def gbps(nbytes, seconds):
    return nbytes / seconds / 1e9


def timed(fn, reps):
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        out.append(time.perf_counter() - t0)
    return out


def summary(nbytes, seconds):
    return {"gbps_median": gbps(nbytes, statistics.median(seconds)),
            "gbps_best": gbps(nbytes, min(seconds)),
            "seconds": seconds}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()

    devs = jax.local_devices()
    n = len(devs)
    shape = (args.rows, 3, 224, 224)
    rng = np.random.default_rng(0)
    batches = [rng.standard_normal(shape, dtype=np.float32)
               for _ in range(args.steps)]
    nbytes = batches[0].nbytes
    piece = args.rows // n
    pool = ThreadPoolExecutor(max_workers=n)

    def views(b):
        return [b[i * piece:(i + 1) * piece] for i in range(n)]

    def whole(b):                      # (c) everything to chip 0
        return jax.device_put(b, devs[0])

    def one_thread(b):                 # (a)
        return [jax.device_put(v, d) for v, d in zip(views(b), devs)]

    def thread_a_chip(b):              # (b)
        return list(pool.map(
            lambda vd: jax.block_until_ready(jax.device_put(*vd)),
            zip(views(b), devs)))

    def block(put):
        """K steps, at most two in flight: as the staging thread does."""
        in_flight, kept = None, []
        for b in batches:
            step = put(b)
            jax.block_until_ready(in_flight)
            in_flight = step
            kept.append(step)
        return kept

    report = {"device": {"platform": devs[0].platform,
                         "kind": devs[0].device_kind, "count": n},
              "batch_bytes": nbytes, "steps": args.steps,
              "numpy_aligned_64": [b.ctypes.data % 64 == 0 for b in batches],
              "numpy_aligned_16": [b.ctypes.data % 16 == 0 for b in batches]}
    # warm every path once (allocators, transfer buffers, thread pool)
    for put in (whole, one_thread, thread_a_chip):
        jax.block_until_ready(put(batches[0]))

    def record(key, size, fn, reps=args.reps):
        report[key] = summary(size, timed(fn, reps))

    b0, blk = batches[0], nbytes * args.steps
    record("c_whole_to_chip0", nbytes, lambda: whole(b0))
    record("a_one_thread", nbytes, lambda: one_thread(b0))
    record("b_thread_a_chip", nbytes, lambda: thread_a_chip(b0))
    # the same again at the end: a later form must not owe its number to
    # a warmer machine
    record("c_whole_to_chip0_again", nbytes, lambda: whole(b0))
    record("block_c_whole", blk, lambda: block(whole), 3)
    record("block_a_one_thread", blk, lambda: block(one_thread), 3)
    record("block_b_thread_a_chip", blk, lambda: block(thread_a_chip), 3)
    # one chip's link alone: a quarter of the batch to chip 0
    record("one_piece_to_chip0", nbytes // n,
           lambda: jax.device_put(views(b0)[0], devs[0]))

    # a host-resident jax.Array as the source
    try:
        cpu = jax.local_devices(backend="cpu")[0]
    except RuntimeError:
        cpu = None
    if cpu is not None:
        t0 = time.perf_counter()
        host = jax.block_until_ready(jax.device_put(b0, cpu))
        t_put = time.perf_counter() - t0
        t0 = time.perf_counter()
        back = np.asarray(host)
        t_view = time.perf_counter() - t0
        report["host_array"] = {
            "device_put_to_cpu_s": t_put,
            "aliases_numpy": bool(np.shares_memory(back, b0)),
            "asarray_s": t_view,
            "asarray_view_of_buffer": bool(
                back.ctypes.data == host.unsafe_buffer_pointer())}

        record("a_one_thread_from_host_array", nbytes,
               lambda: one_thread(np.asarray(host)))
        record("b_thread_a_chip_from_host_array", nbytes,
               lambda: thread_a_chip(np.asarray(host)))

    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/h2d_link_probe.json", "w") as f:
        json.dump(report, f, indent=1)
    brief = {k: (round(v["gbps_median"], 3) if isinstance(v, dict)
                 and "gbps_median" in v else v) for k, v in report.items()}
    print(json.dumps(brief))


if __name__ == "__main__":
    main()
