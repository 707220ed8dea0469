"""Runtime configuration registry — the documented env-var surface.

Parity: the reference documents ~25 runtime env vars read via
`dmlc::GetEnv` (reference docs/how_to/env_var.md); this module is the
equivalent single source of truth.  Each variable is declared once with
type, default, and description; `describe()` renders the table and
`get(name)` is the typed accessor the rest of the framework uses (or can
migrate to — modules that read os.environ at import time list their
variable here for documentation even when they read it directly).

Many reference knobs (engine thread pools, GPU memory pool, bulk-exec
segment sizes) have no analog because XLA/PJRT owns those resources —
they are listed as `absorbed` so users migrating scripts get an answer
instead of silence.  A variable this registry once held and nothing
reads any more is listed as `retired`, with what holds its value now:
the registry says only what someone sets.
"""
from __future__ import annotations

import os
from collections import namedtuple

__all__ = ["EnvVar", "REGISTRY", "ABSORBED", "RETIRED", "get", "spec",
           "describe", "warn_retired"]

EnvVar = namedtuple("EnvVar", ["name", "type", "default", "desc"])


REGISTRY = [
    # ---- distributed kvstore (parallel/dist.py) ----
    EnvVar("MXNET_KVSTORE_BIGARRAY_BOUND", int, 1 << 20,
           "Arrays above this many elements shard over ALL servers "
           "(reference kvstore_dist.h EncodeKey)"),
    EnvVar("MXNET_KVSTORE_HEARTBEAT_INTERVAL", float, 2.0,
           "Seconds between node heartbeats to the scheduler"),
    EnvVar("MXNET_KVSTORE_DEAD_TIMEOUT", float, 60.0,
           "Seconds without a heartbeat before a node is reported dead "
           "(reference ps-lite CheckDeadNodes)"),
    EnvVar("MXNET_KVSTORE_BARRIER_TIMEOUT", float, 300.0,
           "Barrier wait limit; the barrier raises instead of hanging"),
    # ---- topology (set by tools/launch.py, reference dmlc tracker) ----
    EnvVar("DMLC_ROLE", str, "worker", "Node role: worker/server/scheduler"),
    EnvVar("DMLC_PS_ROOT_URI", str, "127.0.0.1", "Scheduler host"),
    EnvVar("DMLC_PS_ROOT_PORT", int, 9091, "Scheduler port"),
    EnvVar("DMLC_NUM_WORKER", int, 1, "Worker count"),
    EnvVar("DMLC_NUM_SERVER", int, 1, "Server count"),
    EnvVar("DMLC_WORKER_ID", int, 0,
           "This worker's rank, assigned by the tracker (launch.py); "
           "multihost.initialize falls back to it for the process id"),
    EnvVar("MXTPU_DIST_URI", str, "",
           "Non-empty enables the dist kvstore backends without the full "
           "DMLC_* launcher environment (kvstore.create dist_* gate)"),
    EnvVar("MXTPU_RECOVER_RANK", int, -1,
           "Rejoin a running dist_async job under this previous rank "
           "after a worker death (parallel/dist.py elastic recovery); "
           "-1 = fresh start"),
    EnvVar("MXTPU_COORDINATOR", str, "",
           "host:port of the jax.distributed coordinator for multi-host "
           "meshes (parallel/multihost.py); defaults to "
           "DMLC_PS_ROOT_URI:port+1 when a tracker env is present"),
    EnvVar("MXTPU_PROCESS_ID", int, 0,
           "This host's process index in the multi-host mesh "
           "(parallel/multihost.py; falls back to DMLC_WORKER_ID)"),
    EnvVar("MXTPU_MPIRUN", str, "mpirun",
           "Binary tools/launch.py --launcher mpi invokes (tests shim it "
           "without an MPI install)"),
    EnvVar("MXTPU_QSUB", str, "qsub",
           "Binary tools/launch.py --launcher sge submits array jobs "
           "with (tests shim it without a grid engine)"),
    EnvVar("MXTPU_QDEL", str, "qdel",
           "Binary tools/launch.py --launcher sge cancels jobs with on "
           "failure"),
    EnvVar("MXTPU_LOCAL_DEVICES", int, 0,
           "Per-process CPU device count for multi-process SPMD testing "
           "(exported by tools/launch.py --local-spmd --local-devices; "
           "multihost.initialize forces "
           "--xla_force_host_platform_device_count to it).  0 = leave "
           "the platform's own device discovery alone"),
    # ---- dependency engine (engine/) ----
    EnvVar("MXNET_ENGINE_TYPE", str, "ThreadedEnginePerDevice",
           "Execution engine backend (engine/): ThreadedEnginePerDevice "
           "(default; ThreadedEngine accepted) schedules host-side ops "
           "on a worker pool with read/write-var dependency ordering; "
           "NaiveEngine executes every push inline for debugging/"
           "determinism; SanitizerEngine is the threaded backend plus "
           "runtime detection of chunk accesses an op did not declare "
           "(engine/sanitizer.py; docs/engine.md). Unknown values warn "
           "listing the valid names and fall back to the default "
           "(reference src/engine/engine.cc CreateEngine)"),
    EnvVar("MXNET_SANITIZER_STRICT", int, 0,
           "With MXNET_ENGINE_TYPE=SanitizerEngine: 1 turns undeclared-"
           "access reports into deferred RaceErrors raised at the next "
           "sync point (wait_for_var/waitall/value read) instead of "
           "warnings-only"),
    EnvVar("MXNET_CPU_WORKER_NTHREADS", int, 0,
           "Engine worker threads (engine/threaded.py); 0 = auto, "
           "min(4, max(2, n_cpus)). The reference defaults to 1; here "
           "auto keeps >=2 workers so host compute, IO decode, and "
           "kvstore traffic overlap out of the box"),
    # ---- training dispatch / input staging (executor.py, io.py) ----
    EnvVar("MXTPU_STEPS_PER_DISPATCH", int, 1,
           "Fused training block size K: Module.fit runs K full "
           "fwd+bwd+update steps per XLA dispatch — one jitted lax.scan "
           "carrying (params, optimizer state, aux) with donated buffers "
           "— so the fixed per-dispatch host cost (magnitude on the TPU "
           "host: not measured) is paid once per K steps.  1 = one "
           "dispatch per "
           "step (the pre-block behavior); see docs/perf.md"),
    # ---- multi-process data service (data/; docs/data.md) ----
    EnvVar("MXTPU_DATA_SLOT_BYTES", int, 0,
           "Bytes per data-service shared-memory slot; 0 = auto (one "
           "batch exactly: batch_size x data_shape float32 + labels). "
           "An explicit value smaller than one batch raises at "
           "DataService construction instead of corrupting slots"),
    EnvVar("MXTPU_DATA_HOST_INDEX", int, 0,
           "This host's shard of the data service's RecordIO file — "
           "composed ON TOP of worker sharding: hosts stride-shard "
           "records exactly like ImageRecordIter part_index/num_parts "
           "(image_io.shard_offsets), then each host's workers split "
           "the surviving batches.  The per-host input story of the "
           "multi-process mesh (docs/data.md)"),
    EnvVar("MXTPU_DATA_NUM_HOSTS", int, 1,
           "Total hosts sharding the data service's RecordIO file "
           "(MXTPU_DATA_HOST_INDEX selects this host's stride)"),
    # ---- lazy imperative evaluation (lazy.py; docs/perf.md) ----
    EnvVar("MXTPU_LAZY", int, 1,
           "Lazy imperative evaluation (lazy.py): NDArray ops defer "
           "into a per-context pending graph and each chain runs as "
           "ONE jitted XLA dispatch at the next sync point "
           "(.data/asnumpy/wait_to_read/waitall, mutation, autograd "
           "recording, or the MXTPU_LAZY_MAX_OPS cap), behind a "
           "structural fusion cache with scalar-family float attrs "
           "lifted to traced operands.  1 = on (default); 0 = eager "
           "per-op engine dispatch (the pre-lazy behavior); see "
           "docs/perf.md"),
    EnvVar("MXTPU_LAZY_MAX_OPS", int, 64,
           "Cap on a pending lazy chain: recording the Nth op flushes "
           "the graph even without a sync point, bounding host memory "
           "held by deferred operands and compile time of the fused "
           "program (lazy.py)"),
    # ---- inference serving (serving/; docs/serving.md) ----
    EnvVar("MXTPU_SERVE_MAX_QUEUE", int, 1024,
           "Admission control: submit() raises instead of enqueueing "
           "when this many requests are already pending across all "
           "tenants (bounds queue memory and tail latency; rejected "
           "requests count in serving.rejected)"),
    EnvVar("MXTPU_SERVE_WAIT_MS", float, 2.0,
           "Continuous-batcher batching window: a tenant's queue head "
           "may wait this many ms for more requests to arrive before "
           "the batcher dispatches a partial fill (a full "
           "max_batch dispatches immediately). Larger = "
           "better fill ratio, worse p99 under light load"),
    # ---- multi-replica serving tier (router/; docs/serving.md
    #      "Multi-replica tier") ----
    EnvVar("MXTPU_ROUTER_PORT", int, 0,
           "router.ReplicaAgent bind port (one ModelServer behind a "
           "socket); 0 = ephemeral, read back from agent.port. "
           "tools/launch.py --serve-replicas exports a free one per "
           "replica process"),
    EnvVar("MXTPU_ROUTER_REPLICAS", str, "",
           "Comma-separated host:port replica list Router() connects "
           "to by default — launch.py --serve-replicas prints and "
           "exports it for the fleet it spawned"),
    EnvVar("MXTPU_REPLICA_ID", int, 0,
           "This replica's index in the serving fleet (exported per "
           "process by launch.py --serve-replicas; names the replica "
           "in Router.health() and the chaos-test dead list)"),
    # ---- request-scoped tracing (obs/tracing.py;
    #      docs/observability.md "Request tracing & SLOs") ----
    EnvVar("MXTPU_TRACE_SAMPLE", float, 0.0,
           "Head-based request-trace sampling fraction for the serving "
           "tier: each Router.submit / ModelServer.submit mints a "
           "(trace_id, span_id, sampled) context, and a sampled "
           "request decomposes into router_queue/wire/replica_queue/"
           "batch_fill/h2d/compute/readback/reply segments across the "
           "router and replica traces (stitch with tools/obs_stitch.py"
           ").  Requests that end in timeout/redispatch/error are "
           "recorded regardless of the head verdict so every failure "
           "is explained.  0 (default) = tracing entirely off — the "
           "fast path books nothing"),
    # ---- telemetry (telemetry.py; docs/observability.md) ----
    EnvVar("MXTPU_TELEMETRY", int, 1,
           "Metrics registry (telemetry.py): counters/gauges/histograms "
           "across engine, io, executor, kvstore, and module layers, "
           "read via telemetry.snapshot() and reported by the benchmark's "
           "readers and callback.Speedometer.  0 disables recording "
           "entirely — every instrumentation site fast-paths out behind "
           "telemetry.enabled() (mxlint E004 enforces the guard)"),
    EnvVar("MXTPU_TELEMETRY_FILE", str, "",
           "Non-empty: telemetry.flush() appends one JSONL record of "
           "the registry (monotonic flush_seq + step stamps) here — "
           "fit() flushes per epoch, Speedometer per report interval; "
           "render with `python tools/parse_log.py --telemetry FILE`"),
    EnvVar("MXTPU_PEAK_FLOPS", float, 0.0,
           "Hardware peak FLOP/s per chip for the telemetry MFU gauge "
           "(module.mfu); <=0 or unset = the telemetry.PEAK_FLOPS entry "
           "for the device's device_kind (TPU v5e: 197e12 bf16 MAC=2). "
           "On a device with no entry and no override the gauge is not "
           "published"),
    # ---- distributed observability (obs/; docs/observability.md) ----
    EnvVar("MXTPU_OBS_STALL_SECONDS", float, 0.0,
           "Stall watchdog (obs/watchdog.py): a collective/dispatch "
           "edge event whose exit has not arrived after this many "
           "seconds triggers a post-mortem artifact (last-K recorder "
           "events, per-rank progress, Python stacks, straggler-vs-"
           "hang attribution; write-then-rename to "
           "MXTPU_OBS_DIR/postmortem.r<rank>.json).  Suppressed while "
           "a compile bracket is open, so a minutes-long first XLA "
           "compile never trips it.  0 (default) = watchdog off"),
    EnvVar("MXTPU_OBS_STALL_ACTION", str, "dump",
           "What the stall watchdog does after writing the artifact: "
           "'dump' keeps the process alive (it may yet recover), "
           "'abort' hard-exits with code 17 so the launcher observes "
           "a failure instead of an indefinite hang"),
    EnvVar("MXTPU_OBS_DIR", str, "",
           "Directory for watchdog post-mortem artifacts (empty = "
           "current directory).  The memory plane's OOM artifact "
           "(obs/memory.py, memory_postmortem.r<rank>.json) lands in "
           "the same directory"),
    EnvVar("MXTPU_MEM_BUDGET_MB", int, 0,
           "Byte-budget for tenant admission (obs/memory.py, docs/"
           "observability.md 'Memory observability'): add_tenant/"
           "add_generative_tenant preflight their predicted footprint "
           "(params + KV ring) against this many MB plus the live "
           "census and refuse with the numbers instead of OOMing "
           "mid-traffic.  0 (default) = the platform-queried device "
           "memory (memory_stats bytes_limit), or unlimited where the "
           "platform reports none (XLA:CPU)"),
    EnvVar("MXTPU_MEM_PROGRAMS", int, 1,
           "Per-program footprint accounting (obs/memory.py): compile-"
           "cache sites compile ahead-of-time and harvest XLA's "
           "compiled memory analysis into the ProgramFootprint table "
           "and mem.program_bytes.<site> gauges.  0 = plain jax.jit "
           "dispatch, no footprints (the escape hatch)"),
    EnvVar("MXTPU_OBS_PORT", int, 0,
           "TCP port of the rank-0 observability aggregator "
           "(obs/aggregate.py; host side comes from MXTPU_COORDINATOR). "
           "When set — tools/launch.py --local-spmd --obs exports a "
           "free one — every rank ships periodic telemetry/recorder "
           "snapshots to rank 0, measures its wall-clock offset for "
           "trace stitching (tools/obs_stitch.py), and the watchdog "
           "can attribute stalls across ranks.  0 = aggregation off"),
    EnvVar("MXTPU_OBS_INTERVAL_SECONDS", float, 5.0,
           "Cadence of per-rank snapshot shipping AND of rank 0's "
           "cluster JSONL records"),
    EnvVar("MXTPU_OBS_CLUSTER_FILE", str, "",
           "Non-empty: rank 0's aggregator appends one cluster-level "
           "JSONL record per interval (per-rank steps/step-time "
           "columns + max/median step-skew straggler attribution) — "
           "render with `python tools/parse_log.py --cluster FILE`"),
    EnvVar("MXTPU_COLLECTIVE_CHECK", int, 0,
           "Cross-rank collective-schedule verifier (parallel/"
           "schedule_check.py, the runtime half of mxlint E007): every "
           "rank folds its flight-recorder stream of collective enter "
           "events (kind, seq, bytes, detail) into a "
           "rolling structural hash, ships the digest in the obs "
           "snapshot every MXTPU_OBS_INTERVAL_SECONDS, and compares "
           "against every peer.  A divergent schedule is reported as a "
           "ScheduleDivergence naming the first diverging event and "
           "both ranks (sched_divergence.r<rank>.json artifact; with "
           "MXTPU_OBS_STALL_ACTION=abort the rank exits code 18) — "
           "catching the desync BEFORE the stall watchdog's timeout "
           "would fire.  0 (default) = off"),
    EnvVar("MXTPU_LOCK_CHECK", int, 0,
           "Runtime lock-contract verifier (mxnet_tpu/locks.py, the "
           "runtime half of mxlint E008/E009): 1 makes the declared "
           "lock factories (locks.lock/rlock/condition) hand out "
           "RecordingLocks that keep per-thread held-sets, fold every "
           "acquisition into a global lock ORDER graph, raise a "
           "DeadlockError postmortem naming both conflicting "
           "acquisition sites when an acquisition would close a cycle "
           "(BEFORE blocking on the deadlock), and book "
           "locks.wait_seconds.<name>/locks.hold_seconds.<name> "
           "histograms + a locks.contended counter into telemetry "
           "(lock_wait.<name> spans while profiling).  0 (default) = "
           "plain threading primitives, zero overhead"),
    EnvVar("MXTPU_LOCK_CHECK_ACTION", str, "raise",
           "What MXTPU_LOCK_CHECK=1 does on an order-graph cycle: "
           "'raise' (default) raises the DeadlockError at the "
           "offending acquisition; 'dump' records it (locks."
           "violations(), locks.order_violations counter) and prints "
           "the postmortem to stderr, letting the run continue — the "
           "soak-test mode"),
    # ---- checkpoint / elastic training (mxnet_tpu/ckpt) ----
    EnvVar("MXTPU_CKPT_DIR", str, "",
           "Where Module.fit(checkpoint_every_steps=N > 0) writes its "
           "periodic async distributed checkpoints: every rank writes "
           "write-then-rename shard files here, rank 0 commits the "
           "mxtpu-ckpt-v1 manifest (docs/checkpoint.md).  Empty = "
           "checkpointing off"),
    EnvVar("MXTPU_CKPT_KEEP", int, 2,
           "Committed checkpoints retained; older manifests are pruned "
           "manifest-first (an interrupted prune leaves orphan shards, "
           "never a manifest naming missing shards)"),
    EnvVar("MXTPU_CKPT_RESUME", str, "",
           "Resume source consumed by Module.fit when resume_from is "
           "not passed explicitly: a checkpoint directory (newest "
           "committed manifest wins) or one manifest file.  A directory "
           "with no committed checkpoint starts fresh instead of "
           "failing — the elastic supervisor (tools/launch.py "
           "--elastic) sets this unconditionally and generation 0 has "
           "nothing to resume yet"),
    EnvVar("MXTPU_ELASTIC_GENERATION", int, 0,
           "This process's elastic generation, bumped by the "
           "tools/launch.py --elastic supervisor on every relaunch "
           "(shrink after a rank death, regrow at an epoch boundary); "
           "0 = the original launch.  Read via ckpt.elastic.generation "
           "— set it only if you are standing in for the supervisor"),
    EnvVar("MXTPU_RETRACE_WARN", int, 0,
           "Retrace-storm warning threshold (telemetry.note_retrace, "
           "the runtime half of mxlint W104): every compiled-program "
           "cache site counts signature churn in trace.retraces[.site]"
           "; past this many DISTINCT signatures at one site a warning "
           "logs the signature delta (previous vs new) naming the "
           "unstable static arg.  0 (default) = count only, never "
           "warn"),
    # ---- memory (executor.py) ----
    EnvVar("MXNET_BACKWARD_DO_MIRROR", int, 0,
           "Memory mirroring: recompute cheap activations (BN/ReLU/elemwise) "
           "in the backward pass instead of storing them — jax.checkpoint "
           "with a save-only-matmul/conv-outputs remat policy (reference "
           "src/executor/graph_executor.cc:225-239)"),
    EnvVar("MXNET_PROFILER_MODE", str, "symbolic",
           "Profiler mode at import: symbolic/all/xla (profiler.py)"),
    EnvVar("MXNET_PROFILER_AUTOSTART", int, 0,
           "Start profiling at import; dump via mx.profiler.dump_profile()"),
    EnvVar("MXNET_PROFILER_FILENAME", str, "profile.json",
           "Profiler output path (profiler.py)"),
    EnvVar("MXNET_TPU_S2D_STEM", int, 0,
           "EXACT space-to-depth rewrite of 2-D stride-2 stem "
           "convolutions (C_in<=4, any kernel/pad, odd sizes "
           "zero-padded): factor-2 fold to an equivalent stride-1 conv "
           "on 4x the channels (ops/nn.py space_to_depth_stem). "
           "Model-dependent: measured SLOWER on ResNet-50's 224^2 7x7 "
           "stem (11456 vs 11759 img/s inference — the fold's relayout "
           "copies outweigh the MXU fill, README Per-model MFU item 5) "
           "but FASTER on Inception-v3's 3x-larger 299^2 3x3 stem "
           "(README Roofline item 8, July 2026; not re-measured, "
           "ROADMAP.md D4). Default OFF"),
    EnvVar("MXTPU_BF16_WGRAD", int, 0,
           "bf16-accumulated WEIGHT gradients for small-kernel (max dim "
           "<=7) convolutions (ops/nn.py _conv_call custom-vjp): the "
           "weight-grad conv runs with bf16 operands and "
           "preferred_element_type=bf16, cast to the fp32 master dtype "
           "after — keeps the fast bf16 grad kernels reachable instead "
           "of the f32-output kernels that cost Inception-v3 27% of "
           "device time (README Roofline item 8, July 2026; not "
           "re-measured, ROADMAP.md D4). Activation gradients keep exact f32 "
           "accumulation. Changes gradient numerics (tolerance-pinned "
           "in tests/test_mfu_sinks.py); default OFF"),
    # ---- JAX/XLA passthrough the test/dev flows rely on ----
    EnvVar("JAX_PLATFORMS", str, "", "Force a JAX backend, e.g. 'cpu'"),
    EnvVar("XLA_FLAGS", str, "",
           "XLA options; --xla_force_host_platform_device_count=8 gives a "
           "virtual multi-chip CPU mesh for testing"),
]

# reference env vars whose role XLA/PJRT absorbed — accepted, ignored,
# documented (reference docs/how_to/env_var.md)
# NOTE: MXNET_ENGINE_TYPE and MXNET_CPU_WORKER_NTHREADS graduated from
# this table to the registry above when the dependency engine (engine/)
# landed — the host-side scheduler is ours again; XLA keeps only the
# device-side knobs.
ABSORBED = {
    "MXNET_GPU_WORKER_NTHREADS": "PJRT device streams",
    "MXNET_CPU_PRIORITY_NTHREADS": "XLA scheduling",
    "MXNET_EXEC_ENABLE_INPLACE": "XLA buffer assignment",
    "NNVM_EXEC_MATCH_RANGE": "XLA memory planner",
    "MXNET_EXEC_NUM_TEMP": "XLA temp allocation",
    "MXNET_GPU_MEM_POOL_RESERVE": "PJRT allocator",
    "MXNET_EXEC_BULK_EXEC_INFERENCE": "whole-graph jit (always bulk)",
    "MXNET_EXEC_BULK_EXEC_TRAIN": "whole-graph jit (always bulk)",
    "MXNET_KVSTORE_REDUCTION_NTHREADS": "XLA collectives",
    "MXNET_ENABLE_GPU_P2P": "ICI collectives",
}

# variables this registry once held and no code reads any more: name ->
# what holds the value now.  Accepted and without effect; importing the
# package with one of them set says so once (warn_retired), and
# docs/how_to/env_var.md lists them
RETIRED = {
    "MXTPU_COMM_BUCKETED":
        "deleted: a data-parallel fit syncs gradients one way, by the "
        "all-reduce XLA's partitioner puts into the step",
    "MXTPU_COMM_BUCKET_MB": "deleted with MXTPU_COMM_BUCKETED",
    "MXNET_TPU_PALLAS_BN":
        "deleted: the chip measured the Pallas BatchNorm statistics "
        "kernel 27% slower end to end (1,826 vs 2,487 img/s, v5e)",
    "MXNET_BN_STATS_SAMPLE":
        "deleted: the chip measured no change (2,474 vs 2,480 img/s, v5e)",
    "MXTPU_FROZEN_BN": "Module.fit(frozen_bn=False)",
    "MXTPU_SERVE_MAX_BATCH": "serving.ModelServer(max_batch=32)",
    "MXTPU_SERVE_BUCKETS":
        "serving.ModelServer(buckets=None): powers of two up to max_batch",
    "MXTPU_SERVE_TIMEOUT_MS":
        "serving.ModelServer(timeout_ms=5000.0), submit(timeout_ms=)",
    "MXTPU_SERVE_MAX_SESSIONS":
        "ModelServer.add_generative_tenant(max_sessions=8)",
    "MXTPU_SERVE_MAX_DECODE_TOKENS":
        "ModelServer.add_generative_tenant(max_decode_tokens=64)",
    "MXTPU_SERVE_DECODE_WINDOW_MS":
        "the constant serving.server.DECODE_WINDOW_MS = 2.0",
    "MXTPU_SERVE_KV_MAX_LEN":
        "ModelServer.add_generative_tenant(max_len=256)",
    "MXTPU_STAGE_BUFFERS": "io.DeviceStagedIter(buffers=2)",
    "MXTPU_DATA_WORKERS":
        "data.DataService / io.ShardedImageRecordIter(num_workers=2)",
    "MXTPU_DATA_RING_SLOTS":
        "data.DataService / io.ShardedImageRecordIter(ring_slots=4)",
    "MXTPU_ROUTER_POLL_MS": "router.Router(poll_ms=200.0)",
    "MXTPU_ROUTER_REDISPATCH": "router.Router(redispatch_cap=2)",
    "MXTPU_ROUTER_ADAPT_WINDOW_S": "router.Router(adapt_window_s=10.0)",
    "MXTPU_QUANT_CALIB_MODE": "quant.calibrate(mode='minmax')",
    "MXTPU_QUANT_PERCENTILE": "quant.calibrate(percentile=99.99)",
    "MXTPU_QUANT_HIST_BINS": "quant.calibrate(hist_bins=2048)",
    "MXTPU_QUANT_SKIP_FIRST_LAST":
        "quant.quantize_symbol(skip_first_last=True)",
    "MXTPU_TRACE_BUFFER": "the constant obs.tracing._CAP = 4096",
    "MXTPU_OBS_RECORDER":
        "always on (obs.recorder.set_enabled(False) turns it off in-process)",
    "MXTPU_OBS_RING_SLOTS": "the constant obs.recorder._RING_SLOTS = 512",
    "MXTPU_MEM_CENSUS":
        "always on (obs.memory.set_census(False) turns it off in-process)",
    "MXTPU_CKPT_EVERY_STEPS": "Module.fit(checkpoint_every_steps=0)",
    "MXTPU_CKPT_ASYNC": "ckpt.CheckpointManager(async_write=True)",
    "MXNET_KVSTORE_PULL_TIMEOUT":
        "the constant parallel.dist.PULL_TIMEOUT = 60.0",
    "MXNET_KVSTORE_REGISTER_TIMEOUT":
        "the constant parallel.dist.REGISTER_TIMEOUT = 600.0",
}

_BY_NAME = {v.name: v for v in REGISTRY}


def warn_retired(environ):
    """One warning for each RETIRED variable `environ` sets, naming what
    holds its value now.  Reads `environ`, never writes it."""
    import warnings

    for name in sorted(set(RETIRED).intersection(environ)):
        warnings.warn("%s is set and has no effect: %s"
                      % (name, RETIRED[name]), stacklevel=2)


warn_retired(os.environ)


def spec(name):
    """The EnvVar registration for `name` (KeyError on unknown names)."""
    s = _BY_NAME.get(name)
    if s is None:
        if name in RETIRED:
            raise KeyError("config variable %s was retired: %s"
                           % (name, RETIRED[name]))
        raise KeyError("unknown config variable %s (see config.REGISTRY; "
                       "absorbed-by-XLA vars: %s)" % (name, sorted(ABSORBED)))
    return s


def get(name, default=None):
    """Typed read of a registered variable (reference dmlc::GetEnv)."""
    s = spec(name)
    raw = os.environ.get(name)
    if raw is None:
        return s.default if default is None else default
    return s.type(raw)


def describe():
    """Render the env-var table (the docs/how_to/env_var.md analog)."""
    lines = ["%-36s %-8s %-12s %s" % ("variable", "type", "default", "description")]
    for v in REGISTRY:
        lines.append("%-36s %-8s %-12s %s"
                     % (v.name, v.type.__name__, v.default, v.desc))
    lines.append("")
    lines.append("absorbed by XLA/PJRT (accepted, ignored):")
    for k, why in sorted(ABSORBED.items()):
        lines.append("  %-34s -> %s" % (k, why))
    lines.append("")
    lines.append("retired (accepted, no effect):")
    for k, now in sorted(RETIRED.items()):
        lines.append("  %-34s -> %s" % (k, now))
    return "\n".join(lines)
