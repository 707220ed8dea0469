#!/usr/bin/env python
"""Parse training logs into a table (parity: reference tools/parse_log.py —
the nightly accuracy gates grep their thresholds out of these logs,
reference tests/nightly/test_all.sh:43-50).

Reads fit() output lines:
    Epoch[3] Train-accuracy=0.94
    Epoch[3] Time cost=12.2
    Epoch[3] Validation-accuracy=0.95
and prints one row per epoch: epoch, train metric, valid metric, time.

With ``--telemetry`` the input is a telemetry JSONL file instead
(mxnet_tpu/telemetry.py flush records, one JSON object per line — the
``MXTPU_TELEMETRY_FILE`` sink): one row per flush with the step stamp,
step-time percentiles from the histogram, MFU, dispatch and
compile-cache counters, plus the lazy-fusion columns (flush count,
mean fused-chain length, fusion-cache hit %) when the run recorded
the ``lazy`` namespace and the serving columns (queue depth, exact
batch-fill %, request p99) when it recorded the ``serving`` namespace
(docs/serving.md), and the data-service columns (``data_qdepth`` ring
backlog, ``decode_mbps`` compressed MB/s through the worker decoders)
when it recorded the ``data`` namespace (docs/data.md), and the
trace-contract columns (``retraces``
compiled-signature churn from the retrace monitor, ``sched_div``
cross-rank collective-schedule divergences from
``MXTPU_COLLECTIVE_CHECK=1``; docs/static_analysis.md), and the int8-
quantization columns (``quant_clip_pct`` mean calibration clip rate,
``tenant_bits`` per-tenant serving numerics as ``name:8`` int8 /
``name:16`` bf16 / ``name:32`` f32; docs/perf.md "Int8 serving"), and
the multi-replica router columns (``replicas_healthy`` live replica
count, ``redispatches`` drain-on-death replays, ``route_p99``
submit-to-result p99 through the tier; docs/serving.md "Multi-replica
tier"), and the request-tracing + SLO columns (``trace_sampled``
head-sampled request count, ``slo_burn`` the worst per-tenant
error-budget burn rate, ``queue_p99``/``service_p99`` the queue-wait
vs fill-to-resolution latency split that localizes a p99 move;
docs/observability.md "Request tracing & SLOs"), and the KV-cache
decode columns (``tokens_s`` mean decoded tokens/s, ``active_sessions``
live decode sessions, ``kv_slot_occupancy`` KV-ring slot fill fraction)
when the run recorded the ``serving.decode`` namespace (docs/serving.md
"Decode sessions & continuous batching"), and the memory-census columns
(``live_mb`` booked live bytes at flush, ``peak_mb`` the process
high-watermark, ``mem_headroom_pct`` % headroom under the byte budget)
when it recorded the ``mem`` namespace (docs/observability.md "Memory
observability").
Older logs render '-' in columns they predate.

With ``--cluster`` the input is the rank-0 CLUSTER JSONL
(``MXTPU_OBS_CLUSTER_FILE``, written by the obs aggregator —
mxnet_tpu/obs/aggregate.py): one row per record with per-rank steps
and step times and the max/median step-time skew ratio with the slowest
rank named (straggler attribution).
Plain single-rank telemetry records fed to --cluster render '-' in
every cluster column.  See docs/observability.md.
"""
from __future__ import annotations

import argparse
import json
import re
import sys


def parse(lines, metric="accuracy"):
    rows = {}
    num = r"([-+]?(?:[\d.]+(?:e[-+]?\d+)?|nan|inf))"
    res = [
        re.compile(r"Epoch\[(\d+)\] Train-%s=%s" % (re.escape(metric), num), re.I),
        re.compile(r"Epoch\[(\d+)\] Validation-%s=%s" % (re.escape(metric), num), re.I),
        re.compile(r"Epoch\[(\d+)\] Time cost=([\d.]+)"),
    ]
    for line in lines:
        for col, rx in enumerate(res):
            m = rx.search(line)
            if m:
                epoch = int(m.group(1))
                rows.setdefault(epoch, [None, None, None])[col] = float(m.group(2))
    return [(e,) + tuple(v) for e, v in sorted(rows.items())]


def _hist_quantile(hist, q):
    """Approximate quantile from a telemetry fixed-bucket histogram
    record (upper bucket boundary containing the q-th observation)."""
    count = hist.get("count", 0)
    if not count:
        return None
    target = q * count
    seen = 0
    for key, c in hist.get("buckets", {}).items():
        # keys are "le_<bound>" / "le_inf" in boundary order (dicts
        # preserve insertion order end-to-end through json)
        seen += c
        if seen >= target:
            if key == "le_inf":
                return hist.get("max")
            return float(key[3:])
    return hist.get("max")


def parse_telemetry(lines):
    """Telemetry JSONL (telemetry.flush records) -> one summary row per
    record: [{flush_seq, step, epoch?, step_p50, step_max, mfu,
    dispatches, cache_hits, cache_misses, io_wait_p50, h2d_bytes}]."""
    rows = []
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            # a truncated tail (killed run) or a line mid-append must
            # not hide the valid records before it
            print("warning: skipping malformed telemetry line",
                  file=sys.stderr)
            continue
        hist = rec.get("histograms", {})
        step_h = hist.get("module.step_seconds", {})
        io_h = hist.get("io.consumer_wait_seconds", {})
        counters = rec.get("counters", {})
        gauges = rec.get("gauges", {})
        # lazy-fusion columns (mxnet_tpu/lazy.py): None-out when the run
        # recorded no lazy namespace at all, so pre-lazy logs render '-'
        has_lazy = any(k.startswith("lazy.") for k in counters)
        lazy_flushes = sum(v for k, v in counters.items()
                           if k.startswith("lazy.flushes.")
                           and k != "lazy.flushes.fallback")
        chain_h = hist.get("lazy.chain_length", {})
        chain_mean = (chain_h["sum"] / chain_h["count"]
                      if chain_h.get("count") else None)
        f_hits = counters.get("lazy.fusion_cache_hits", 0)
        f_misses = counters.get("lazy.fusion_cache_misses", 0)
        fusion_hit_pct = (100.0 * f_hits / (f_hits + f_misses)
                          if (f_hits + f_misses) else None)
        slots_used = counters.get("serving.batch_slots_used", 0)
        slots_padded = counters.get("serving.batch_slots_padded", 0)
        # data-service columns (mxnet_tpu/data, docs/data.md): ring
        # backlog and compressed MB/s through the worker decoders —
        # '-' for logs that predate the service
        data_bytes = sum(v for k, v in counters.items()
                         if k.startswith("data.worker_bytes."))
        dec_h = hist.get("data.decode_seconds", {})
        has_ckpt = any(k.startswith("ckpt.")
                       for k in list(counters) + list(gauges) + list(hist))
        has_locks = any(k.startswith("locks.")
                        for k in list(counters) + list(hist))
        has_decode = any(k.startswith("serving.decode.")
                         for k in list(counters) + list(gauges)
                         + list(hist))
        dec_step_h = hist.get("serving.decode.step_seconds", {})
        has_mem = any(k.startswith("mem.")
                      for k in list(counters) + list(gauges))
        rows.append({
            "flush_seq": rec.get("flush_seq"),
            "step": rec.get("step"),
            "epoch": rec.get("epoch"),
            "step_p50": _hist_quantile(step_h, 0.5),
            "step_max": step_h.get("max"),
            "mfu": gauges.get("module.mfu"),
            "dispatches": counters.get("executor.train_dispatches"),
            "cache_hits": counters.get("executor.compile_cache_hits"),
            "cache_misses": counters.get("executor.compile_cache_misses"),
            "io_wait_p50": _hist_quantile(io_h, 0.5),
            "h2d_bytes": counters.get("executor.h2d_bytes"),
            "lazy_flushes": lazy_flushes if has_lazy else None,
            "chain_mean": chain_mean,
            "fusion_hit_pct": fusion_hit_pct,
            # mode gauges (docs/perf.md "MFU sinks"): which grad/BN
            # numerics the run used — '-' for records that predate them
            "wgrad_bf16": gauges.get("ops.wgrad_bf16"),
            "frozen_bn": gauges.get("module.frozen_bn"),
            # serving columns (docs/serving.md): backlog, exact mean
            # batch-fill %, and request p99 — '-' for pre-serving logs
            "serve_qdepth": gauges.get("serving.queue_depth"),
            "fill_pct": (100.0 * slots_used / (slots_used + slots_padded)
                         if (slots_used + slots_padded) else None),
            "req_p99": _hist_quantile(
                hist.get("serving.request_seconds", {}), 0.99),
            "data_qdepth": gauges.get("data.ring_occupancy"),
            "decode_mbps": (data_bytes / dec_h["sum"] / 1e6
                            if dec_h.get("sum") else None),
            # trace-contract columns (ISSUE 12, docs/static_analysis.md):
            # compiled-signature churn per run (telemetry.note_retrace,
            # the runtime half of mxlint W104) and cross-rank collective-
            # schedule divergences (parallel/schedule_check.py, the
            # runtime half of E007) — '-' for logs that predate them
            "retraces": (counters.get("trace.retraces", 0)
                         if any(k == "trace.retraces"
                                or k.startswith("trace.retraces.")
                                for k in counters) else None),
            "sched_div": (counters.get("schedule.divergences")
                          if "schedule.divergences" in counters else None),
            # int8-quantization columns (mxnet_tpu/quant, docs/perf.md
            # "Int8 serving"): mean calibration clip rate and the
            # per-tenant serving numerics (name:bits, 8 = int8,
            # 16 = bf16, 32 = f32) — '-' for logs that predate the
            # quant pipeline
            "quant_clip_pct": gauges.get("quant.clip_pct"),
            "tenant_bits": (";".join(
                "%s:%d" % (k[len("quant.tenant_bits."):], int(v))
                for k, v in sorted(gauges.items())
                if k.startswith("quant.tenant_bits."))
                or None),
            # multi-replica router columns (mxnet_tpu/router,
            # docs/serving.md "Multi-replica tier"): live healthy-
            # replica count, drain-on-death replays, and the
            # submit-to-result p99 through the tier — '-' for logs
            # that predate the router
            "replicas_healthy": gauges.get("router.replicas_healthy"),
            "redispatches": (counters.get("router.redispatches", 0)
                             if any(k.startswith("router.")
                                    for k in list(counters)
                                    + list(gauges)) else None),
            "route_p99": _hist_quantile(
                hist.get("router.route_seconds", {}), 0.99),
            # request-tracing + SLO columns (mxnet_tpu/obs/tracing.py,
            # docs/observability.md "Request tracing & SLOs"):
            # head-sampled request count, the worst per-tenant SLO
            # burn rate, and the queue/service latency split that
            # localizes a p99 move — '-' for logs that predate the
            # tracing plane
            "trace_sampled": (counters.get("trace.requests_sampled", 0)
                              if any(k.startswith("trace.requests_")
                                     for k in counters) else None),
            "slo_burn": (max(v for k, v in gauges.items()
                             if k.startswith("slo.burn."))
                         if any(k.startswith("slo.burn.")
                                for k in gauges) else None),
            "queue_p99": _hist_quantile(
                hist.get("serving.queue_seconds", {}), 0.99)
            if "serving.queue_seconds" in hist else None,
            "service_p99": _hist_quantile(
                hist.get("serving.service_seconds", {}), 0.99)
            if "serving.service_seconds" in hist else None,
            # checkpoint columns (mxnet_tpu/ckpt, docs/checkpoint.md):
            # cumulative background shard-write seconds, bytes written,
            # and how many times this run resumed from a manifest — '-'
            # for logs that predate the checkpoint subsystem
            "ckpt_secs": (hist.get("ckpt.write_seconds", {}).get("sum", 0.0)
                          if has_ckpt else None),
            "ckpt_bytes": counters.get("ckpt.bytes", 0) if has_ckpt else None,
            "resumes": counters.get("ckpt.resumes", 0) if has_ckpt else None,
            # lock-sentinel columns (mxnet_tpu/locks.py, docs/
            # observability.md "Observing lock contention"): total ms
            # threads spent blocked on RecordingLocks this flush and the
            # contended-acquire count — '-' for runs without
            # MXTPU_LOCK_CHECK=1 (no locks.* namespace at all)
            "lock_wait_ms": (1e3 * sum(
                h.get("sum", 0.0) for k, h in hist.items()
                if k.startswith("locks.wait_seconds."))
                if has_locks else None),
            "contended": (counters.get("locks.contended", 0)
                          if has_locks else None),
            # KV-cache decode columns (mxnet_tpu/serving/decode.py,
            # docs/serving.md "Decode sessions & continuous batching"):
            # mean decoded tokens/s over the flush (cumulative tokens /
            # cumulative step seconds), live packed-session count, and
            # KV-ring slot occupancy — '-' for logs that predate the
            # decode engine (no serving.decode.* namespace)
            "tokens_s": (counters.get("serving.decode.tokens", 0)
                         / dec_step_h["sum"]
                         if has_decode and dec_step_h.get("sum")
                         else (0.0 if has_decode else None)),
            "active_sessions": (gauges.get(
                "serving.decode.active_sessions", 0)
                if has_decode else None),
            "kv_slot_occupancy": (gauges.get("kv.slot_occupancy", 0.0)
                                  if has_decode else None),
            # memory-census columns (mxnet_tpu/obs/memory.py,
            # docs/observability.md "Memory observability"): live booked
            # MB at flush, the process-lifetime peak, and % headroom
            # under the byte budget (only present when a budget is
            # resolvable) — '-' for logs that predate the census (no
            # mem.* namespace)
            "live_mb": (gauges.get("mem.live_bytes", 0) / 1e6
                        if has_mem else None),
            "peak_mb": (gauges.get("mem.peak_bytes", 0) / 1e6
                        if has_mem else None),
            "mem_headroom_pct": (gauges.get("mem.headroom_pct")
                                 if has_mem else None),
        })
    return rows


def parse_cluster(lines):
    """Cluster JSONL (obs/aggregate.py Aggregator records) -> one
    summary row per record.  Records without the cluster shape (plain
    per-rank telemetry flushes, pre-obs logs) yield all-None rows so
    older logs render '-' instead of crashing the table."""
    rows = []
    for idx, line in enumerate(lines):
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            print("warning: skipping malformed cluster line",
                  file=sys.stderr)
            continue
        ranks = rec.get("ranks")
        skew = rec.get("skew") or {}
        if not isinstance(ranks, dict) or not ranks:
            rows.append({c: (idx if c == "seq" else None)
                         for c in _CLUSTER_COLS})
            continue
        order = sorted(ranks, key=int)

        def col(key, scale=1.0, _r=ranks, _o=order):
            vals = []
            for r in _o:
                v = _r[r].get(key)
                vals.append("-" if v is None else "%.4g" % (v * scale))
            return ";".join("r%s:%s" % (r, v) for r, v in zip(_o, vals))

        rows.append({
            "seq": idx,
            "nranks": rec.get("nranks", len(ranks)),
            "steps": ";".join("r%s:%s" % (r, ranks[r].get("steps", "-"))
                              for r in order),
            "step_ms": col("step_mean_s", scale=1e3),
            "skew": skew.get("max_over_median"),
            "slowest": skew.get("slowest_rank"),
        })
    return rows


_CLUSTER_COLS = ["seq", "nranks", "steps", "step_ms", "skew", "slowest"]


_TELEMETRY_COLS = ["flush_seq", "step", "epoch", "step_p50", "step_max",
                   "mfu", "dispatches", "cache_hits", "cache_misses",
                   "io_wait_p50", "h2d_bytes", "lazy_flushes", "chain_mean",
                   "fusion_hit_pct", "wgrad_bf16", "frozen_bn",
                   "serve_qdepth", "fill_pct", "req_p99", "data_qdepth",
                   "decode_mbps", "retraces",
                   "sched_div", "quant_clip_pct", "tenant_bits",
                   "replicas_healthy", "redispatches", "route_p99",
                   "trace_sampled", "slo_burn", "queue_p99", "service_p99",
                   "ckpt_secs", "ckpt_bytes", "resumes", "lock_wait_ms",
                   "contended", "tokens_s", "active_sessions",
                   "kv_slot_occupancy", "live_mb", "peak_mb",
                   "mem_headroom_pct"]


def _print_rows(rows, cols, fmt):
    def cell(v):
        if v is None:
            return "-"
        if isinstance(v, float):
            return "%.6g" % v
        return str(v)

    if fmt == "markdown":
        print("| " + " | ".join(cols) + " |")
        print("|" + " --- |" * len(cols))
    for r in rows:
        cells = [cell(r[c]) for c in cols]
        if fmt == "markdown":
            print("| " + " | ".join(cells) + " |")
        else:
            print(*cells)


def _print_telemetry(rows, fmt):
    _print_rows(rows, _TELEMETRY_COLS, fmt)


def _print_cluster(rows, fmt):
    _print_rows(rows, _CLUSTER_COLS, fmt)


def main():
    parser = argparse.ArgumentParser(description="parse training logs")
    parser.add_argument("logfile", nargs="?", help="log file (default stdin)")
    parser.add_argument("--format", choices=["markdown", "none"],
                        default="markdown")
    parser.add_argument("--metric", type=str, default="accuracy")
    parser.add_argument("--telemetry", action="store_true",
                        help="input is a telemetry JSONL file "
                             "(MXTPU_TELEMETRY_FILE sink) instead of a "
                             "fit() text log")
    parser.add_argument("--cluster", action="store_true",
                        help="input is a rank-0 cluster JSONL "
                             "(MXTPU_OBS_CLUSTER_FILE, obs aggregator): "
                             "per-rank step/step-time columns + the "
                             "max/median skew straggler attribution")
    args = parser.parse_args()
    lines = open(args.logfile).readlines() if args.logfile else sys.stdin.readlines()
    if args.cluster:
        _print_cluster(parse_cluster(lines), args.format)
        return
    if args.telemetry:
        _print_telemetry(parse_telemetry(lines), args.format)
        return
    rows = parse(lines, metric=args.metric)
    if args.format == "markdown":
        print("| epoch | train-%s | valid-%s | time |" % (args.metric, args.metric))
        print("| --- | --- | --- | --- |")
    for e, tr, va, t in rows:
        fmt = lambda v: ("%.6f" % v) if v is not None else "-"  # noqa: E731
        if args.format == "markdown":
            print("| %d | %s | %s | %s |" % (e, fmt(tr), fmt(va), fmt(t)))
        else:
            print(e, fmt(tr), fmt(va), fmt(t))


if __name__ == "__main__":
    main()
