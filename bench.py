#!/usr/bin/env python
"""Headline benchmark: ResNet-50 ImageNet-shape training throughput, 1 chip.

Measures the FULL training step through the public API — Module.forward_
backward + update (fused XLA dispatch: fwd+bwd+SGD with donated
buffers) — matching how the reference's 181.53 img/s baseline was measured
(train_imagenet.py full steps on 1x P100, reference docs/how_to/perf.md:
181-190).

Config: bf16 compute with fp32 master weights (Module compute_dtype —
the multi-precision recipe) at batch 512 in NHWC layout (the TPU-native
channel-minor layout; measured equal to NCHW on v5e since XLA relayouts
convs internally — see README "Roofline" for the full layout A/B and
profile).  BatchNorm uses the one-pass fp32-accumulated E[x]/E[x^2] stats
(ops/nn.py batch_norm), worth ~17% step time on this model.

Dispatch amortization (docs/perf.md): with --steps-per-dispatch K > 1
(or MXTPU_STEPS_PER_DISPATCH), each dispatch is ONE jitted lax.scan
executing K full fwd+bwd+update steps, with input blocks double-buffered
to the device by a background engine op (io.DeviceStagedIter) — the
fixed per-dispatch host cost is paid once per K steps.
The JSON line reports `dispatches` (= ceil(steps/K)) and
`steps_per_dispatch` either way.

`--smoke` runs a tiny model on CPU (JAX_PLATFORMS=cpu) through the REAL
K-step path end-to-end — fit -> DeviceStagedIter -> fused_update_block —
with the profiler on, and reports the io.stage / fit.dispatch lanes;
tests/test_bench_smoke.py pins it so this harness cannot silently rot.

Methodology note: the timed loop runs several steps per fence and is
fenced by `block_until_ready` on an updated weight (a real completion
fence on the TPU — chip_smoke.py's fence phase checks it against the
chip's peak).  Every non-smoke row names the device it ran on
(platform / device_kind / device_count), and MFU divides by that
device's entry in mxnet_tpu.telemetry.PEAK_FLOPS: a device with no
entry (and no MXTPU_PEAK_FLOPS) is an error, not a v5e default.
"""
import argparse
import contextlib
import json
import os
import time

BASELINE_IMG_S = 181.53  # 1x P100, reference docs/how_to/perf.md:181-190


def parse_args():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--smoke", action="store_true",
                   help="tiny model on CPU through the real K-step path; "
                        "prints a JSON line with dispatch/lane checks")
    p.add_argument("--imperative", action="store_true",
                   help="imperative microbench: a --chain-ops-long "
                        "elementwise NDArray chain, lazy fusion vs "
                        "MXTPU_LAZY=0 eager — reports ops/s, dispatch "
                        "counts, and fusion-cache hit rate")
    p.add_argument("--serve", action="store_true",
                   help="serving load driver (docs/serving.md): a mixed "
                        "ResNet-50/152 two-tenant ModelServer on one "
                        "device, closed- or open-loop clients, reporting "
                        "img/s + p50/p99 latency + batch-fill ratio at "
                        "the stated offered load.  With --smoke: tiny "
                        "CPU tenants through the identical path "
                        "(tests/test_bench_smoke.py)")
    p.add_argument("--replicas", type=str, default="",
                   help="--serve: comma-separated replica counts (e.g. "
                        "'1,2,4') — for each N, launch N ReplicaAgent "
                        "processes via tools/launch.py --serve-replicas "
                        "and drive the SAME load through a Router "
                        "(docs/serving.md 'Multi-replica tier'); one "
                        "JSON row reports img/s + route p50/p99 per "
                        "count and the 1->max scaling.  Empty = the "
                        "single in-process ModelServer path")
    p.add_argument("--serve-agent", action="store_true",
                   help=argparse.SUPPRESS)  # internal: one replica of --replicas
    p.add_argument("--generate", action="store_true",
                   help="--serve: generative-serving load driver "
                        "(docs/serving.md 'Decode sessions & continuous "
                        "batching') — a transformer-LM tenant behind a "
                        "ReplicaAgent + Router, closed-loop clients "
                        "submitting varied-length prompts so prefills "
                        "and token-level decode steps interleave; one "
                        "JSON row with decoded tokens/s, request "
                        "p50/p99, decode batch-fill, and KV-slot "
                        "occupancy.  With --smoke: tiny CPU LM "
                        "(tests/test_bench_smoke.py)")
    p.add_argument("--trace-ab", action="store_true",
                   help="--serve: measure request-tracing overhead "
                        "(docs/observability.md 'Request tracing & "
                        "SLOs') — the SAME load driven back-to-back "
                        "with MXTPU_TRACE_SAMPLE=0 vs 0.01, 3 timed "
                        "chunks per side (the --ab stdev machinery), "
                        "one JSON row with both sides + the overhead "
                        "delta.  With --smoke the row asserts the "
                        "delta is within noise and <=1%")
    p.add_argument("--lock-ab", action="store_true",
                   help="--serve: measure the MXTPU_LOCK_CHECK=1 "
                        "RecordingLock sentinel overhead (docs/"
                        "observability.md 'Observing lock contention') "
                        "— the SAME load driven against a plain server, "
                        "then a fresh server built with the sentinel "
                        "armed, 3 timed chunks per side.  With --smoke "
                        "the row asserts the armed side saw ZERO "
                        "order-graph cycles and the overhead is <5% "
                        "(within noise)")
    p.add_argument("--mem-ab", action="store_true",
                   help="--serve: measure the live-buffer census "
                        "overhead (docs/observability.md 'Memory "
                        "observability') — the SAME load driven "
                        "back-to-back with the census disarmed "
                        "(MXTPU_MEM_CENSUS=0 equivalent) vs armed, 3 "
                        "timed chunks per side (the --ab stdev "
                        "machinery).  With --smoke the row asserts the "
                        "armed side really booked buffers and the "
                        "overhead is <=1% (within noise)")
    p.add_argument("--trace-sample", type=float, default=0.01,
                   help="--trace-ab: the sampled fraction of the ON "
                        "side (default 0.01)")
    p.add_argument("--clients", type=int, default=4,
                   help="--serve closed loop: concurrent clients per "
                        "tenant (default 4)")
    p.add_argument("--offered-load", type=float, default=0.0,
                   help="--serve: target aggregate request rate in "
                        "req/s (open loop); 0 = closed loop driven by "
                        "--clients")
    p.add_argument("--requests", type=int, default=None,
                   help="--serve: total timed requests across tenants "
                        "(default: 96 smoke / 512 full)")
    p.add_argument("--decode", action="store_true",
                   help="decode-throughput bench (docs/data.md): pack a "
                        "synthetic JPEG RecordIO file and drive the "
                        "multi-process DataService at --decode-workers "
                        "worker counts, reporting MEASURED img/s + MB/s "
                        "per count and the 1->max scaling — the row "
                        "that replaces the old extrapolated input-bound "
                        "artifact.  With --smoke: tiny dataset "
                        "(tests/test_bench_smoke.py)")
    p.add_argument("--decode-workers", type=str, default="1,2,4",
                   help="--decode: comma-separated worker-process "
                        "counts to measure (default 1,2,4)")
    p.add_argument("--ab", choices=sorted(AB_SINKS),
                   help="matched A/B of one attributed MFU sink "
                        "(docs/perf.md 'MFU sinks'): runs the before/"
                        "after pair back-to-back IN ONE PROCESS and "
                        "emits a single JSON row with both sides, "
                        "stdev, and the delta.  With --smoke: tiny "
                        "models on CPU (tests/test_bench_smoke.py)")
    p.add_argument("--knobs-a", type=str, default="",
                   help="--ab knobs: side-A knob vector 'K=V,K=V' of "
                        "registered tunables (empty = registered "
                        "defaults); each entry is validated against the "
                        "config tunable annotation")
    p.add_argument("--knobs-b", type=str, default="",
                   help="--ab knobs: side-B knob vector (the candidate)")
    p.add_argument("--workload", choices=("train", "serve"),
                   default="train",
                   help="--ab knobs: which workload body the knob "
                        "vectors drive — the K-step fused training path "
                        "or the ModelServer closed-loop path")
    p.add_argument("--comm-ab", action="store_true",
                   help="--spmd-procs: after the comm probe, run an "
                        "interleaved matched A/B of the auto-derived "
                        "comm bucket target vs the registered default "
                        "(MXTPU_COMM_BUCKET_MB), adding a comm_auto "
                        "section to the SPMDROW")
    p.add_argument("--spmd-procs", type=int, default=0,
                   help="multi-process SPMD row (docs/distributed.md): "
                        "relaunch this bench as N jax.distributed "
                        "processes via tools/launch.py --local-spmd, "
                        "train through the K-step fused dispatch with "
                        "bucketed hierarchical gradient collectives, and "
                        "report MEASURED img/s + comm telemetry (bucket "
                        "bytes, measured collective GB/s, overlap "
                        "fraction).  With --smoke: tiny CPU model "
                        "(tests/test_spmd_runtime.py pins the row)")
    p.add_argument("--spmd-local-devices", type=int, default=2,
                   help="--spmd-procs: devices per process (CPU mesh)")
    p.add_argument("--spmd-worker", action="store_true",
                   help=argparse.SUPPRESS)  # internal: one rank of --spmd-procs
    p.add_argument("--ckpt-dir", type=str, default="",
                   help=argparse.SUPPRESS)  # internal: --spmd-worker A/B dir
    p.add_argument("--chain-ops", type=int, default=64,
                   help="ops per imperative chain (default 64)")
    p.add_argument("--steps-per-dispatch", type=int, default=None,
                   help="fused block size K (default: "
                        "MXTPU_STEPS_PER_DISPATCH, i.e. 1)")
    p.add_argument("--batch", type=int, default=None,
                   help="batch size (default: 512 headline; per-sink "
                        "defaults under --ab)")
    p.add_argument("--steps", type=int, default=30,
                   help="total timed steps (with K>1: rounded up to 3 "
                        "fenced chunks of whole K-blocks)")
    return p.parse_args()


def _resolve_k(args):
    if args.steps_per_dispatch is not None:
        return max(1, args.steps_per_dispatch)
    from mxnet_tpu import config  # registered default, single source

    return max(1, config.get("MXTPU_STEPS_PER_DISPATCH"))


def _endless_iter(mx, rng, batch, shape, classes, nbatches=4):
    """Endless in-memory iterator cycling over `nbatches` synthetic
    batches (ResizeIter rewinds the source on exhaustion), so ONE
    staging pipeline can stream the whole timed run and the H2D of
    block N+1 genuinely overlaps block N's compute."""
    import numpy as np

    n = batch * nbatches
    X = rng.randn(n, *shape).astype("float32")
    y = rng.randint(0, classes, n).astype("float32")
    return mx.io.ResizeIter(mx.io.NDArrayIter(X, y, batch_size=batch),
                            size=1 << 30)


def _fence(mod, name):
    mod._exec_group.execs[0].arg_dict[name].data.block_until_ready()


def _device_row():
    """The device fields every non-smoke row carries, as JAX reports
    them.  Initialises the backend: never call from a parent that only
    launches the processes which need the chip (serve_replicas)."""
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "device_kind": d.device_kind,
            "device_count": len(jax.devices())}


def _emit(row, device=True):
    """Print one result row as a JSON line, naming the device it ran on.
    `device=False` is for rows whose process stays off JAX (the decode
    benchmark; parents whose children hold the chips and report it)."""
    if device:
        row = dict(row, **_device_row())
    print(json.dumps(row))


def _peak_or_die():
    """Per-chip peak FLOP/s of the default device, or exit: an MFU
    against another chip's peak is not a number."""
    import jax

    from mxnet_tpu import telemetry

    peak = telemetry.peak_flops()
    if not peak:
        raise SystemExit(
            "bench.py: no peak FLOP/s known for device_kind %r (platform "
            "%s) — add it to mxnet_tpu.telemetry.PEAK_FLOPS with its "
            "source or set MXTPU_PEAK_FLOPS; use --smoke for the CPU "
            "correctness run" % (jax.devices()[0].device_kind,
                                 jax.default_backend()))
    return peak


def main():
    args = parse_args()
    if args.spmd_worker:
        return spmd_worker(args)
    if args.spmd_procs:
        return spmd(args)
    if args.decode:
        return decode(args)
    if args.serve_agent:
        return serve_agent(args)
    if args.serve:
        if args.generate:
            return serve_generate(args)
        if args.replicas:
            return serve_replicas(args)
        return serve(args)
    if args.ab:
        return ab(args)
    if args.smoke:
        return smoke(args)
    if args.imperative:
        return imperative(args)

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.models.resnet import resnet

    BATCH = args.batch or 512
    K = _resolve_k(args)
    peak = _peak_or_die()  # before any compile: fail fast off the chip

    mx.random.seed(0)
    net = resnet(50, layout="NHWC")
    mod = mx.mod.Module(net, context=mx.tpu(), compute_dtype="bfloat16")
    mod.bind(data_shapes=[("data", (BATCH, 224, 224, 3))],
             label_shapes=[("softmax_label", (BATCH,))])
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", factor_type="in", magnitude=2))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05, "momentum": 0.9})
    rng = np.random.RandomState(0)
    exe = mod._exec_group.execs[0]
    # dispatch accounting comes from the telemetry registry (the public
    # counter surface).  MXTPU_TELEMETRY=0 is respected — a user timing
    # the instrumentation's own overhead gets a registry-free run, and
    # dispatch counts fall back to the executor's internal attribute.
    from mxnet_tpu import telemetry

    def _dispatches():
        if telemetry.enabled():
            return telemetry.counter_value("executor.train_dispatches")
        return exe._train_dispatches

    if K > 1:
        # K-step fused block path: --steps rounded up to whole K-blocks
        # and 3 equal fenced chunks; ONE DeviceStagedIter stays alive
        # across the whole timed run so staging overlaps compute like it
        # does in training (a fresh pipeline per chunk would serialize
        # the first H2D into every chunk)
        blocks_per_chunk = max(1, -(-args.steps // K // 3))
        it = _endless_iter(mx, rng, BATCH, (224, 224, 3), 1000)
        staged = mx.io.DeviceStagedIter(it, steps_per_dispatch=K,
                                        place_fn=exe.place_step_input,
                                        stack_fn=exe.stack_block_input)
        rates, steps_done = [], 0
        try:
            block = next(staged)  # compile + settle
            mod.forward_backward(block)
            mod.update()
            _fence(mod, "fc1_weight")
            d0 = _dispatches()
            for _ in range(3):
                t0 = time.time()
                n = 0
                for _ in range(blocks_per_chunk):
                    block = next(staged)
                    mod.forward_backward(block)
                    mod.update()
                    n += block.count
                _fence(mod, "fc1_weight")
                rates.append(BATCH * n / (time.time() - t0))
                steps_done += n
        finally:
            staged.close()
        dispatches = _dispatches() - d0
        img_s = float(np.mean(rates))
        spread = float(np.std(rates))
        dt = BATCH / img_s
    else:
        batch = mx.io.DataBatch(
            data=[mx.nd.array(rng.randn(BATCH, 224, 224, 3).astype("float32"))],
            label=[mx.nd.array(rng.randint(0, 1000, BATCH).astype("float32"))],
        )
        for _ in range(4):  # compile + settle
            mod.forward_backward(batch)
            mod.update()
        _fence(mod, "fc1_weight")

        # 3 fenced chunks -> mean + spread, so the headline number carries a
        # variance estimate (perf.md-style methodology, not a single sample)
        chunk = max(1, args.steps // 3)
        rates = []
        d0 = _dispatches()
        for _ in range(3):
            t0 = time.time()
            for _ in range(chunk):
                mod.forward_backward(batch)
                mod.update()
            _fence(mod, "fc1_weight")
            rates.append(BATCH * chunk / (time.time() - t0))
        dispatches = _dispatches() - d0
        steps_done = 3 * chunk
        img_s = float(np.mean(rates))
        spread = float(np.std(rates))
        dt = BATCH / img_s

    # analytic model FLOPs of one step (Executor.flops_per_step: MXU terms
    # of the traced forward, x3 for fwd+bwd) over measured step time and
    # the device's published peak
    mfu = round(exe.flops_per_step(is_train=True) / dt / peak, 4)

    _emit({
        "metric": "ResNet-50 full train step img/s/chip (bf16+fp32 master, "
                  "batch %d, NHWC, fwd+bwd+SGD)" % BATCH,
        "value": round(img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(img_s / BASELINE_IMG_S, 3),
        "mfu": mfu,
        "stdev": round(spread, 2),
        "steps_per_dispatch": K,
        "steps": steps_done,
        "dispatches": dispatches,
    })


# ----------------------------------------------------------------------
# --ab: matched back-to-back A/B of one attributed MFU sink.  Both sides
# run IN ONE PROCESS (same host state, same machine, back to back — the
# methodology for deltas smaller than the run-to-run spread),
# each as warmup + 3 fenced chunks so the row carries its own stdev.
# Roofline entries are reproducible with exactly one command:
#     python bench.py --ab s2d_stem        (v5e)
#     python bench.py --ab frozen_bn --smoke   (CPU, tiny — the CI pin)
# ----------------------------------------------------------------------


def _tiny_bn_net(mx, layout="NCHW"):
    """--smoke model for the conv sinks: a stride-2 odd-input stem conv
    (exercises the s2d parity pad) + BN + a 3x3 body conv, so every
    toggled code path (fold, bf16 wgrad, frozen BN) is actually on the
    traced graph."""
    ax = -1 if layout.endswith("C") else 1
    d = mx.sym.Variable("data")
    c = mx.sym.Convolution(d, num_filter=16, kernel=(3, 3), stride=(2, 2),
                           no_bias=True, layout=layout, name="stem_conv")
    b = mx.sym.BatchNorm(c, fix_gamma=False, axis=ax, name="stem_bn")
    a = mx.sym.Activation(b, act_type="relu")
    c2 = mx.sym.Convolution(a, num_filter=32, kernel=(3, 3), pad=(1, 1),
                            no_bias=True, layout=layout, name="body_conv")
    b2 = mx.sym.BatchNorm(c2, fix_gamma=False, axis=ax, name="body_bn")
    a2 = mx.sym.Activation(b2, act_type="relu")
    f = mx.sym.FullyConnected(a2, num_hidden=8, name="fc1")
    return mx.sym.SoftmaxOutput(f, name="softmax")


def _train_rates(mod, batch_obj, batch_size, steps):
    """Warmup (compile + settle) then 3 fenced chunks; returns img-or-
    sample/s per chunk."""
    for _ in range(2):
        mod.forward_backward(batch_obj)
        mod.update()
    _fence(mod, "fc1_weight")
    chunk = max(1, steps // 3)
    rates = []
    for _ in range(3):
        t0 = time.time()
        for _ in range(chunk):
            mod.forward_backward(batch_obj)
            mod.update()
        _fence(mod, "fc1_weight")
        rates.append(batch_size * chunk / (time.time() - t0))
    return rates


@contextlib.contextmanager
def _env_overlay(overrides):
    """Apply one A/B side's env overrides, restore-and-reraise.

    `overrides` maps name -> string value (None = unset for this side).
    Previous values are captured for EVERY name before anything is
    applied and restored in a finally — including when application
    itself raises partway through a multi-knob vector, or when the side
    body raises — so a failing side can never leak knob state into the
    other side's measurement (pinned in tests/test_autotune.py)."""
    prev = {name: os.environ.get(name) for name in overrides}
    try:
        for name, val in overrides.items():
            if val is None:
                os.environ.pop(name, None)
            else:
                os.environ[name] = str(val)
        yield
    finally:
        for name, old in prev.items():
            if old is None:
                os.environ.pop(name, None)
            else:
                os.environ[name] = old


def _conv_ab_side(args, smoke, env_name, flag, frozen=False):
    """One side of a conv-model A/B: build a FRESH Module (fresh jit
    caches — config flags are read at trace time) under `env_name`=flag
    and measure the full fwd+bwd+SGD step."""
    import numpy as np

    import mxnet_tpu as mx

    overlay = {} if env_name is None else {env_name: "1" if flag else "0"}
    with _env_overlay(overlay):
        mx.random.seed(0)
        if smoke:
            net = _tiny_bn_net(mx)
            shape, batch, classes, steps = (3, 17, 17), 16, 8, 9
            ctx, dtype = mx.cpu(), None
        elif frozen or env_name is None:
            # frozen-BN targets the ResNet-50 headline config
            from mxnet_tpu.models.resnet import resnet

            net = resnet(50, layout="NHWC")
            shape, batch = (224, 224, 3), args.batch or 512
            classes, steps = 1000, args.steps
            ctx, dtype = mx.tpu(), "bfloat16"
        else:
            # stem/wgrad sinks target Inception-v3 (the attribution rows)
            from mxnet_tpu.models.inception_v3 import get_inception_v3

            net = get_inception_v3(layout="NHWC")
            shape, batch = (299, 299, 3), args.batch or 128
            classes, steps = 1000, args.steps
            ctx, dtype = mx.tpu(), "bfloat16"
        fixed = None
        if frozen and flag:
            from mxnet_tpu.symbol import (batchnorm_param_names,
                                          freeze_batchnorm)

            fixed = batchnorm_param_names(net)
            net = freeze_batchnorm(net)
        mod = mx.mod.Module(net, context=ctx, compute_dtype=dtype,
                            fixed_param_names=fixed)
        mod.bind(data_shapes=[("data", (batch,) + shape)],
                 label_shapes=[("softmax_label", (batch,))])
        mod.init_params(mx.init.Xavier(rnd_type="gaussian",
                                       factor_type="in", magnitude=2))
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.05,
                                             "momentum": 0.9})
        rng = np.random.RandomState(0)
        b = mx.io.DataBatch(
            data=[mx.nd.array(rng.randn(batch, *shape).astype("float32"))],
            label=[mx.nd.array(rng.randint(0, classes, batch)
                               .astype("float32"))])
        return _train_rates(mod, b, batch, steps)


def _lstm_ab_side(args, smoke, packed):
    """One side of the bucketed-LSTM A/B: a full BucketingModule training
    epoch over BucketSentenceIter, batch_growth off vs on.  tokens/s
    counts every (padded) sequence slot — identical work per epoch on
    both sides, only the batch packing differs."""
    import random as _random

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import rnn

    if smoke:
        # short buckets keep the unrolled-graph compiles (the dominant
        # CPU cost) cheap; the packing mechanics are identical
        V, H, E, B, layers = 50, 32, 16, 8, 1
        buckets, n_sent = [4, 8], 128
        ctx = mx.cpu()
    else:
        # BASELINE config 3 shape: 2x200 LSTM, batch 32 (bptt via buckets)
        V, H, E, B, layers = 10000, 200, 200, 32, 2
        buckets, n_sent = [10, 20, 30, 35], 4096
        ctx = mx.tpu()
    _random.seed(0)
    np.random.seed(0)
    rng = np.random.RandomState(0)
    sents = []
    for _ in range(n_sent):
        n = rng.randint(3, max(buckets) + 1)
        sents.append([int(v) for v in rng.randint(2, V, n)])
    it = rnn.BucketSentenceIter(sents, B, buckets=list(buckets),
                                invalid_label=0, batch_growth=packed)
    cell = rnn.FusedRNNCell(H, num_layers=layers, mode="lstm",
                            prefix="lstm_")

    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        label = mx.sym.Variable("softmax_label")
        embed = mx.sym.Embedding(data, input_dim=V, output_dim=E,
                                 name="embed")
        output, _ = cell.unroll(seq_len, inputs=embed, layout="NTC",
                                merge_outputs=True)
        pred = mx.sym.Reshape(output, shape=(-1, H))
        pred = mx.sym.FullyConnected(pred, num_hidden=V, name="pred")
        label = mx.sym.Reshape(label, shape=(-1,))
        pred = mx.sym.SoftmaxOutput(pred, label, name="softmax")
        return pred, ("data",), ("softmax_label",)

    mod = mx.mod.BucketingModule(sym_gen=sym_gen,
                                 default_bucket_key=it.default_bucket_key,
                                 context=ctx)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Xavier(factor_type="in", magnitude=2.34))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})

    def epoch():
        it.reset()
        tokens = 0
        for batch in it:
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()
            tokens += batch.data[0].size
        return tokens

    epoch()  # compile every bucket + settle
    rates = []
    for _ in range(3):
        t0 = time.time()
        tokens = epoch()
        rates.append(tokens / (time.time() - t0))
    return rates


def _int8_tiny_net(mx):
    """Tiny conv+FC classifier for the int8_serve CPU smoke: enough
    eligible layers that the first/last skip policy still leaves int8
    nodes in the middle."""
    d = mx.sym.Variable("data")
    c1 = mx.sym.Activation(mx.sym.Convolution(
        d, kernel=(3, 3), num_filter=8, pad=(1, 1), name="conv1",
        layout="NHWC"), act_type="relu")
    c2 = mx.sym.Activation(mx.sym.Convolution(
        c1, kernel=(3, 3), num_filter=8, pad=(1, 1), name="conv2",
        layout="NHWC"), act_type="relu")
    f1 = mx.sym.Activation(mx.sym.FullyConnected(
        c2, num_hidden=32, name="fc1"), act_type="relu")
    return mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        f1, num_hidden=7, name="fc2"), name="softmax")


def _int8_serve_ab(args):
    """--ab int8_serve: matched bf16-vs-int8 INFERENCE A/B through the
    real serving fill path (docs/serving.md "Int8 serving").

    Per model, ONE ModelServer hosts the same symbol+params twice — a
    ``dtype_mode='bf16'`` tenant and a calibrated ``dtype_mode='int8'``
    tenant (the mixed-tenant serving this PR ships) — warmed so the
    timed windows are compile-free, then each side serves the SAME eval
    requests closed-loop.  The row reports per-side img/s and
    request p50/p99 plus the top-1 disagreement between the sides on
    the eval batch.  Top-1 here is argmax agreement against the bf16
    side (the params are a fresh random init — there is no ImageNet in
    this environment); the trained-accuracy bound (≤1% absolute top-1
    delta on the LeNet real-data gate path) is pinned in
    tests/test_quant.py."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import quant, telemetry

    telemetry.set_enabled(True)
    telemetry.reset()
    if args.smoke:
        models = [("tiny", _int8_tiny_net(mx), (8, 8, 3),
                   args.batch or 4, args.requests or 24)]
    else:
        from mxnet_tpu.models.inception_v3 import get_inception_v3
        from mxnet_tpu.models.resnet import resnet

        bucket = args.batch or 2
        n_req = args.requests or 8
        models = [
            ("resnet50", resnet(50, layout="NHWC"), (224, 224, 3),
             bucket, n_req),
            ("inception_v3", get_inception_v3(layout="NHWC"),
             (299, 299, 3), bucket, n_req),
        ]
    ctx = mx.cpu() if args.smoke else mx.tpu()
    rows = {}
    for name, net, sample, bucket, n_req in models:
        mx.random.seed(0)
        mod = mx.mod.Module(net, context=ctx)
        mod.bind(data_shapes=[("data", (bucket,) + sample)],
                 label_shapes=None, for_training=False)
        mod.init_params(mx.init.Xavier(rnd_type="gaussian",
                                       factor_type="in", magnitude=2))
        arg, aux = mod.get_params()
        params = {"arg:%s" % k: v for k, v in arg.items()}
        params.update({"aux:%s" % k: v for k, v in aux.items()})
        rng = np.random.RandomState(0)
        calib = [{"data": rng.randn(bucket, *sample).astype("float32")}
                 for _ in range(3)]
        table = quant.calibrate(net, arg, aux, calib, ctx=ctx)
        shapes = {"data": (bucket,) + sample}
        server = mx.serving.ModelServer(
            {"bf16": mx.Predictor(net, dict(params), shapes, ctx=ctx,
                                  dtype_mode="bf16"),
             "int8": mx.Predictor(net, dict(params), shapes, ctx=ctx,
                                  dtype_mode="int8", calib_table=table)},
            max_batch=bucket, buckets=str(bucket),
            # the A/B is a matched-throughput measurement, not an SLO
            # run: a whole side's requests queue at once, so the
            # deadline must cover the full side on a slow host (the
            # int8 side on XLA:CPU runs the generic int8 conv path)
            timeout_ms=3600e3)
        server.warmup()
        miss0 = telemetry.counter_value("executor.compile_cache_misses")
        erng = np.random.RandomState(1)
        xs = [erng.randn(*sample).astype("float32") for _ in range(n_req)]
        top1 = {}
        side = {}
        for tenant in ("bf16", "int8"):
            t0 = time.time()
            futs = [server.submit(tenant, {"data": x}) for x in xs]
            outs = [f.result(timeout=3600) for f in futs]
            elapsed = time.time() - t0
            top1[tenant] = np.array([o[0].argmax() for o in outs])
            lat = telemetry.snapshot()["histograms"].get(
                "serving.request_seconds.%s" % tenant, {})
            side[tenant] = {
                "img_s": round(n_req / elapsed, 3),
                "p50_ms": round(_hist_q(lat, 0.5) * 1e3, 3)
                if lat.get("count") else None,
                "p99_ms": round(_hist_q(lat, 0.99) * 1e3, 3)
                if lat.get("count") else None,
            }
        compile_misses = (telemetry.counter_value(
            "executor.compile_cache_misses") - miss0)
        server.close()
        disagree = float((top1["int8"] != top1["bf16"]).mean() * 100.0)
        rows[name] = {
            "bf16": side["bf16"], "int8": side["int8"],
            "delta_pct": round((side["int8"]["img_s"]
                                - side["bf16"]["img_s"])
                               / side["bf16"]["img_s"] * 100.0, 2),
            "top1_disagree_pct": round(disagree, 2),
            "bucket": bucket, "requests": n_req,
            "compile_misses_timed": compile_misses,
            "quantized_nodes": int(telemetry.gauge_value(
                "quant.nodes_quantized", 0)),
        }
    # headline a/b: the first model's sides (per-model detail in rows)
    first = rows[models[0][0]]
    row = {
        "metric": "A/B int8_serve: bf16 vs int8 post-training-quantized "
                  "inference through the serving fill path (%s)"
                  % ("tiny CPU smoke" if args.smoke
                     else "ResNet-50 + Inception-v3"),
        "sink": "int8_serve",
        "unit": "img/s",
        "a": {"value": first["bf16"]["img_s"], "mode": "bf16"},
        "b": {"value": first["int8"]["img_s"], "mode": "int8"},
        "delta_pct": first["delta_pct"],
        "top1_ref": "bf16-argmax agreement on the eval batch (random "
                    "init; trained real-data bound in tests/test_quant.py)",
        "models": rows,
        "smoke": bool(args.smoke),
    }
    if args.smoke:
        # CI pins (tests/test_bench_smoke.py) start here
        assert first["compile_misses_timed"] == 0, "timed window recompiled"
        assert first["quantized_nodes"] > 0, "no int8 nodes served"
        assert first["top1_disagree_pct"] <= 50.0, rows
    _emit(row)


def _lm_spec(args, mx):
    """(lm, params, decode-length targets, prompt_len, ctx) for the
    generative benches — a randomly-initialized TransformerLM checkpoint
    (throughput does not care about the weights; numerics parity vs the
    trained model is tests/test_transformer_lm.py's job)."""
    from mxnet_tpu.models import TransformerLM

    if args.smoke:
        lm = TransformerLM(vocab=32, num_layers=2, num_heads=2,
                           d_model=32, max_len=48)
        targets, prompt_len, ctx = [16, 32], 4, mx.cpu()
    else:
        lm = TransformerLM(vocab=8192, num_layers=4, num_heads=8,
                           d_model=512, max_len=320)
        targets, prompt_len, ctx = [64, 256], 8, mx.tpu()
    mx.random.seed(0)
    mod = mx.mod.Module(lm.training_symbol(), data_names=("data",),
                        label_names=("softmax_label",), context=ctx)
    mod.bind(data_shapes=[("data", (2, 8))],
             label_shapes=[("softmax_label", (2, 8))])
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                   magnitude=2))
    arg, aux = mod.get_params()
    params = dict(arg)
    params.update(aux)
    return lm, params, targets, prompt_len, ctx


def _kv_decode_ab(args):
    """--ab kv_decode: KV-cache decode vs full-recompute, matched
    greedy generation (docs/perf.md "KV-cache decode").

    Side A regenerates every token by re-running the FULL prefix
    through the score forward (padded to a power-of-two sequence
    bucket — the honest recompute baseline: it gets the same
    compile-once bucketing the cache side gets).  Side B prefills once
    and decodes one token per step through the KV ring
    (serving/decode.py's engine, driven directly — no server thread in
    the measurement).  Both sides are warmed first and the timed
    windows assert compile-free; greedy argmax makes the token
    sequences bit-comparable, asserted identical under --smoke."""
    if args.smoke:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.serving.bucket import bucket_ladder, choose_bucket
    from mxnet_tpu.serving.decode import GenerateRequest, GenerativeSession

    telemetry.set_enabled(True)
    telemetry.reset()
    lm, params, targets, prompt_len, ctx = _lm_spec(args, mx)
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, lm.vocab, size=prompt_len).tolist()
    seq_bucket = 1
    while seq_bucket < prompt_len:
        seq_bucket *= 2

    # ---- side A: full recompute through the score forward ----
    ladder = [b for b in bucket_ladder(lm.max_len, "")
              if b >= prompt_len] or [lm.max_len]
    score = mx.Predictor(lm.score_symbol(), dict(params),
                         {"data": (1, ladder[0])}, ctx=ctx)
    for b in ladder:  # warm every sequence bucket
        score.reshape({"data": (1, b)})
        score.forward(data=np.zeros((1, b), np.float32))
        score.get_output(0)

    def recompute(max_new):
        toks = list(prompt)
        t0 = time.time()
        for _ in range(max_new):
            t = len(toks)
            b = choose_bucket(ladder, t)
            data = np.zeros((1, b), np.float32)
            data[0, :t] = toks
            score.reshape({"data": (1, b)})
            score.forward(data=data)
            logits = score.get_output(0).reshape(1, b, lm.vocab)
            toks.append(int(np.argmax(logits[0, t - 1])))
        return toks[prompt_len:], time.time() - t0

    # ---- side B: prefill once + token-level KV decode ----
    def kv_decode(gs, max_new):
        req = GenerateRequest("kv_bench", prompt, 3600.0, max_new)
        t0 = time.time()
        leftovers = gs.admit([req])
        assert not leftovers, "bench session was not admitted"
        while gs.active():
            gs.decode_step()
        dt = time.time() - t0
        return list(req.future.result(timeout=0).tokens), dt

    rows = {}
    for T in targets:
        max_new = T - prompt_len
        gs = GenerativeSession("kv_bench", lm, params, ctx=ctx,
                               max_sessions=1, max_len=lm.max_len,
                               max_decode_tokens=max_new,
                               seq_buckets=[seq_bucket])
        gs.warm()  # compile prefill + decode buckets OUTSIDE the timed window
        miss0 = telemetry.counter_value("executor.compile_cache_misses")
        a_toks, a_dt = recompute(max_new)
        b_toks, b_dt = kv_decode(gs, max_new)
        misses = (telemetry.counter_value("executor.compile_cache_misses")
                  - miss0)
        rows[str(T)] = {
            "recompute_tok_s": round(max_new / a_dt, 2),
            "kv_tok_s": round(max_new / b_dt, 2),
            "delta_pct": round((max_new / b_dt - max_new / a_dt)
                               / (max_new / a_dt) * 100.0, 2),
            "tokens": max_new,
            "match": a_toks == b_toks,
            "compile_misses_timed": misses,
        }
    first, last = rows[str(targets[0])], rows[str(targets[-1])]
    row = {
        "metric": "A/B kv_decode: greedy decode to T tokens, full-"
                  "recompute forward vs KV-cache decode sessions (%s)"
                  % ("tiny CPU smoke" if args.smoke
                     else "512d 4-layer LM, 1 chip"),
        "sink": "kv_decode",
        "unit": "tokens/s",
        "a": {"value": last["recompute_tok_s"], "mode": "recompute"},
        "b": {"value": last["kv_tok_s"], "mode": "kv_cache"},
        "delta_pct": last["delta_pct"],
        "targets": rows,
        "prompt_len": prompt_len,
        "smoke": bool(args.smoke),
    }
    if args.smoke:
        # CI pins (tests/test_bench_smoke.py) start here: greedy
        # sequences must agree token-for-token (the numerics parity the
        # speedup is not allowed to buy back) and the timed windows
        # must be compile-free
        for T, r in rows.items():
            assert r["match"], "kv decode diverged from recompute at T=%s" % T
            assert r["compile_misses_timed"] == 0, "timed window recompiled"
            assert r["kv_tok_s"] > 0 and r["recompute_tok_s"] > 0, rows
    _emit(row)


# ----------------------------------------------------------------------
# --ab knobs: the GENERIC knob-vector A/B (docs/perf.md "Autotuning").
# Any combination of registered tunable knobs (config.tunables) can be
# matched side-A vs side-B in one process: each side applies its vector
# via _env_overlay, builds a FRESH workload body (fresh jit caches —
# knobs are read at trace/construction time), and measures warmup + 3
# fenced chunks.  tools/autotune.py drives exactly this path in-process.
# ----------------------------------------------------------------------


def _parse_knobs(spec):
    """'K=V,K=V' -> {name: value string}, each entry validated against
    the registered tunable annotation (unknown names and out-of-range
    values raise MXNetError naming the offender)."""
    from mxnet_tpu import config as _config

    out = {}
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise SystemExit("--knobs: '%s' is not K=V" % part)
        k, v = (s.strip() for s in part.split("=", 1))
        _config.validate_knob(k, v, where="--knobs")
        out[k] = v
    return out


def _knobs_train_side(args, smoke, knobs):
    """One knob-A/B side, train workload: fresh Module through the
    K-step fused dispatch + staged input path — the consumer of
    MXTPU_STEPS_PER_DISPATCH / MXTPU_STAGE_BUFFERS / comm knobs — so a
    knob vector changes the thing actually being timed.  Returns
    sample/s per fenced chunk (3 chunks)."""
    import numpy as np

    import mxnet_tpu as mx

    with _env_overlay(knobs):
        from mxnet_tpu import config as _config

        K = max(1, int(_config.get("MXTPU_STEPS_PER_DISPATCH")))
        mx.random.seed(0)
        rng = np.random.RandomState(0)
        if smoke:
            batch, shape, classes = 32, (64,), 8
            steps = max(12, args.steps)
            net = mx.sym.Variable("data")
            net = mx.sym.FullyConnected(net, num_hidden=64, name="fc1")
            net = mx.sym.Activation(net, act_type="relu")
            net = mx.sym.FullyConnected(net, num_hidden=classes, name="fc2")
            net = mx.sym.SoftmaxOutput(net, name="softmax")
            ctx, dtype = mx.cpu(), None
        else:
            from mxnet_tpu.models.resnet import resnet

            net = resnet(50, layout="NHWC")
            batch, shape, classes = args.batch or 256, (224, 224, 3), 1000
            steps = args.steps
            ctx, dtype = mx.tpu(), "bfloat16"
        it = _endless_iter(mx, rng, batch, shape, classes)
        mod = mx.mod.Module(net, context=ctx, compute_dtype=dtype)
        mod.bind(data_shapes=[("data", (batch,) + shape)],
                 label_shapes=[("softmax_label", (batch,))])
        mod.init_params(mx.init.Xavier(rnd_type="gaussian",
                                       factor_type="in", magnitude=2))
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.05,
                                             "momentum": 0.9})
        exe = mod._exec_group.execs[0]
        staged = mx.io.DeviceStagedIter(it, steps_per_dispatch=K,
                                        place_fn=exe.place_step_input,
                                        stack_fn=exe.stack_block_input)
        blocks_per_chunk = max(1, -(-steps // K // 3))
        rates = []
        try:
            block = next(staged)  # compile + settle
            mod.forward_backward(block)
            mod.update()
            _fence(mod, "fc1_weight")
            for _ in range(3):
                t0 = time.time()
                n = 0
                for _ in range(blocks_per_chunk):
                    block = next(staged)
                    mod.forward_backward(block)
                    mod.update()
                    n += block.count
                _fence(mod, "fc1_weight")
                rates.append(batch * n / (time.time() - t0))
        finally:
            staged.close()
        return rates


def _knobs_serve_side(args, smoke, knobs):
    """One knob-A/B side, serve workload: fresh ModelServer built with
    every ctor default left to the env-backed config reads (so the knob
    vector governs max_batch/wait_ms/decode window), warmed compile-
    free, then 3 closed-loop chunks.  Returns req/s per chunk."""
    import numpy as np

    import mxnet_tpu as mx

    with _env_overlay(knobs):
        preds, sample, _mb, _wait, total = _serve_models(args, mx)
        server = mx.serving.ModelServer(preds)
        tenants = server.tenants
        rng = np.random.RandomState(0)
        xs = [rng.randn(*sample).astype("float32") for _ in range(16)]
        try:
            server.warmup()
            rates = []
            per_chunk = max(len(tenants), total // 3)
            for _ in range(3):
                elapsed, failed, driven = _drive_load(
                    server.submit, tenants, xs, args, per_chunk)
                if failed:
                    raise SystemExit(
                        "--ab knobs serve side dropped %d requests — the "
                        "row would mislabel an overloaded run" % failed)
                rates.append(driven / elapsed)
        finally:
            server.close()
        return rates


def _knobs_ab(args):
    """--ab knobs: matched A/B of two validated knob vectors over the
    selected workload body; one JSON row with both vectors, per-side
    stdev, and the delta."""
    import numpy as np

    side = (_knobs_serve_side if args.workload == "serve"
            else _knobs_train_side)
    knobs_a = _parse_knobs(args.knobs_a)
    knobs_b = _parse_knobs(args.knobs_b)
    a_rates = side(args, args.smoke, knobs_a)
    b_rates = side(args, args.smoke, knobs_b)
    a, b = float(np.mean(a_rates)), float(np.mean(b_rates))
    unit = "req/s" if args.workload == "serve" else "sample/s"
    _emit({
        "metric": "A/B knobs [%s]: %s vs %s"
                  % (args.workload,
                     args.knobs_a or "defaults", args.knobs_b or "defaults"),
        "sink": "knobs",
        "workload": args.workload,
        "unit": unit,
        "knobs_a": knobs_a,
        "knobs_b": knobs_b,
        "a": {"value": round(a, 2),
              "stdev": round(float(np.std(a_rates)), 2)},
        "b": {"value": round(b, 2),
              "stdev": round(float(np.std(b_rates)), 2)},
        "delta_pct": round((b - a) / a * 100.0, 2),
        "smoke": bool(args.smoke),
    })


AB_SINKS = {
    "s2d_stem": {
        "unit": "img/s",
        "desc": "Inception-v3 train step, MXNET_TPU_S2D_STEM 0 vs 1 "
                "(space-to-depth fold of the 299^2 3x3/s2 stem)",
        "side": lambda args, smoke, flag: _conv_ab_side(
            args, smoke, "MXNET_TPU_S2D_STEM", flag),
    },
    "bf16_wgrad": {
        "unit": "img/s",
        "desc": "Inception-v3 train step, MXTPU_BF16_WGRAD 0 vs 1 "
                "(bf16-accumulated small-kernel weight grads)",
        "side": lambda args, smoke, flag: _conv_ab_side(
            args, smoke, "MXTPU_BF16_WGRAD", flag),
    },
    "lstm_pack": {
        "unit": "tokens/s",
        "desc": "bucketed LSTM epoch, BucketSentenceIter batch_growth "
                "off vs on (short buckets trade length for batch rows)",
        "side": lambda args, smoke, flag: _lstm_ab_side(args, smoke, flag),
    },
    "frozen_bn": {
        "unit": "img/s",
        "desc": "ResNet-50 train step, trainable BN vs "
                "fit(frozen_bn=True) (use_global_stats + fixed "
                "gamma/beta)",
        "side": lambda args, smoke, flag: _conv_ab_side(
            args, smoke, None, flag, frozen=True),
    },
    "kv_decode": {
        "unit": "tokens/s",
        "desc": "greedy transformer decode, full-recompute forward vs "
                "KV-cache decode sessions (compile-once bucketed both "
                "sides)",
        "run": _kv_decode_ab,
    },
    # inference-side sink: declares a whole-run body ("run") instead of
    # the training-shaped off/on "side" pair — the A/B here is two
    # NUMERICS MODES of the same serving path, not an env toggle, and
    # the row carries latency percentiles + top-1 agreement beside the
    # throughput delta
    "int8_serve": {
        "unit": "img/s",
        "desc": "bf16 vs int8 post-training-quantized inference through "
                "the ModelServer fill path (mixed-tenant, one device)",
        "run": _int8_serve_ab,
    },
    # the generic knob-vector sink: --knobs-a/--knobs-b pick ANY
    # registered tunable combination per side, --workload picks the
    # body (train = K-step fused dispatch, serve = ModelServer closed
    # loop) — the harness tools/autotune.py searches through
    "knobs": {
        "unit": "sample/s",
        "desc": "generic registered-knob vector A/B "
                "(--knobs-a vs --knobs-b over --workload)",
        "run": _knobs_ab,
    },
}


def ab(args):
    """Run one sink's matched A/B (see AB_SINKS) and print ONE JSON row.

    Training sinks declare a ``side(args, smoke, flag)`` body run twice
    (flag off/on); inference sinks declare a ``run(args)`` body that
    owns both sides (and its extra columns) itself."""
    if args.smoke:
        # like smoke(): must win over any site TPU default BEFORE jax
        # is first imported
        os.environ["JAX_PLATFORMS"] = "cpu"
    sink = AB_SINKS[args.ab]
    if "run" in sink:
        sink["run"](args)
        return
    import numpy as np
    a_rates = sink["side"](args, args.smoke, False)
    b_rates = sink["side"](args, args.smoke, True)
    a, b = float(np.mean(a_rates)), float(np.mean(b_rates))
    desc = ("tiny-model CPU smoke of: " + sink["desc"] if args.smoke
            else sink["desc"])
    _emit({
        "metric": "A/B %s: %s" % (args.ab, desc),
        "sink": args.ab,
        "unit": sink["unit"],
        "a": {"value": round(a, 2),
              "stdev": round(float(np.std(a_rates)), 2)},
        "b": {"value": round(b, 2),
              "stdev": round(float(np.std(b_rates)), 2)},
        "delta_pct": round((b - a) / a * 100.0, 2),
        "smoke": bool(args.smoke),
    })


# ----------------------------------------------------------------------
# --decode: measured host decode throughput through the multi-process
# data service (docs/data.md).  Drives DataService DIRECTLY — no device
# in the loop — so the row isolates the host pipeline (read -> native
# JPEG decode -> augment -> batch-assemble -> shm hand-off) and the
# scaling across worker PROCESSES is the thing being measured, not
# H2D or compute.  Replaces the extrapolated input-bound artifact row:
# every number here is a wall-clock measurement on this host.
# ----------------------------------------------------------------------


def decode(args):
    import tempfile

    import numpy as np

    from mxnet_tpu import telemetry
    from mxnet_tpu.data import DataService
    from mxnet_tpu.recordio import MXIndexedRecordIO, pack_img

    # like --smoke, this harness asserts its own instrumentation
    telemetry.set_enabled(True)
    telemetry.reset()

    if args.smoke:
        n, px, shape, batch, epochs = 96, 56, (3, 48, 48), 8, 3
    else:
        n, px, shape, batch, epochs = 2048, 256, (3, 224, 224), 64, 3
    rng = np.random.RandomState(0)
    # TemporaryDirectory: the packed dataset is tens of MB in full mode
    # and must not accumulate in /tmp across runs
    tmpdir = tempfile.TemporaryDirectory(prefix="mxtpu_decode_bench_")
    prefix = os.path.join(tmpdir.name, "decode_bench")
    rec = MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    for i in range(n):
        # random noise compresses badly: every JPEG carries real
        # entropy, so huffman+IDCT work per image is at the high end
        img = rng.randint(0, 255, (px, px, 3)).astype("uint8")
        rec.write_idx(i, pack_img((0, float(i % 10), i, 0), img,
                                  quality=90, img_fmt=".jpg"))
    rec.close()

    workers = [int(w) for w in args.decode_workers.split(",")]
    rows = {}
    for w in workers:
        svc = DataService(prefix + ".rec", shape, batch, num_workers=w,
                          preprocess_threads=1, shuffle=False)
        try:
            svc.begin_epoch(0)  # warmup: page cache, pools, first slots
            for _ in range(svc.num_batches):
                svc.next_batch()
            imgs, nbytes, t0 = 0, 0, time.time()
            for e in range(1, epochs + 1):
                svc.begin_epoch(e)
                for _ in range(svc.num_batches):
                    _, _, pad, meta = svc.next_batch()
                    imgs += batch - pad
                    nbytes += meta["bytes"]
            dt = time.time() - t0
        finally:
            svc.close()
        rows[str(w)] = {"img_s": round(imgs / dt, 1),
                        "mb_s": round(nbytes / dt / 1e6, 2),
                        "epochs": epochs}
    tmpdir.cleanup()
    assert telemetry.counter_value("data.batches_produced") > 0
    first, last = str(workers[0]), str(workers[-1])
    best = max(rows, key=lambda k: rows[k]["img_s"])
    _emit({
        "metric": "RecordIO decode+augment throughput, multi-process "
                  "DataService (%dpx JPEG -> %s f32, batch %d; MEASURED "
                  "per worker count)" % (px, "x".join(map(str, shape)),
                                         batch),
        "value": rows[best]["img_s"],
        "unit": "img/s",
        "measured": True,
        "workers": rows,
        "best_workers": int(best),
        # scaling saturates at the host's physical cores: worker counts
        # past them oversubscribe and the rows show it honestly
        "scaling_1_to_max": round(rows[last]["img_s"]
                                  / rows[first]["img_s"], 2),
        "scaling_1_to_best": round(rows[best]["img_s"]
                                   / rows[first]["img_s"], 2),
        "records": n,
        "batch": batch,
        "host_cores": os.cpu_count(),
        "smoke": bool(args.smoke),
    }, device=False)


def imperative(args):
    """Imperative dispatch microbench (docs/perf.md "Lazy imperative
    fusion"): run a `--chain-ops`-long elementwise NDArray chain twice
    under MXTPU_LAZY=0 eager (one engine op + one un-jitted XLA dispatch
    per primitive) and twice under lazy fusion (the whole chain deferred
    and flushed as ONE jitted call), reporting ops/s, per-iteration XLA
    dispatch counts from the telemetry registry, and the fusion-cache
    hit rate — the second lazy iteration must hit the cache compiled by
    the first.  Prints ONE JSON line in the headline bench's shape;
    tests/test_bench_smoke.py pins it."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import lazy, telemetry

    # like --smoke, this harness asserts its own instrumentation: the
    # registry is the dispatch counter, so it must be on
    telemetry.set_enabled(True)
    telemetry.reset()
    lazy.reset_cache()

    chain_ops = max(2, args.chain_ops // 2 * 2)  # whole mul+add pairs
    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.randn(256, 256).astype("float32"))
    a = mx.nd.array(rng.rand(256, 256).astype("float32") + 0.5)
    b = mx.nd.array(rng.randn(256, 256).astype("float32"))

    def chain():
        y = x
        for _ in range(chain_ops // 2):
            y = y * a
            y = y + b
        return y

    def timed(iters):
        d0 = telemetry.counter_value("ndarray.imperative_dispatches")
        t0 = time.time()
        for _ in range(iters):
            chain().wait_to_read()
        dt = time.time() - t0
        d = telemetry.counter_value("ndarray.imperative_dispatches") - d0
        return dt, d / iters

    iters = 4
    prev = lazy.set_enabled(False)
    try:
        chain().wait_to_read()  # settle per-primitive compile caches
        t_eager, eager_dispatches = timed(iters)

        lazy.set_enabled(True)
        chain().wait_to_read()  # compile the fused executable
        h0 = telemetry.counter_value("lazy.fusion_cache_hits")
        m0 = telemetry.counter_value("lazy.fusion_cache_misses")
        t_lazy, lazy_dispatches = timed(iters)
        hits = telemetry.counter_value("lazy.fusion_cache_hits") - h0
        misses = telemetry.counter_value("lazy.fusion_cache_misses") - m0
    finally:
        lazy.set_enabled(prev)

    snap = telemetry.snapshot()
    chain_h = snap["histograms"].get("lazy.chain_length", {})
    _emit({
        "metric": "imperative %d-op elementwise chain ops/s "
                  "(lazy fusion, 256x256 f32)" % chain_ops,
        "value": round(chain_ops * iters / t_lazy, 1),
        "unit": "ops/s",
        "eager_ops_s": round(chain_ops * iters / t_eager, 1),
        "speedup": round(t_eager / t_lazy, 3),
        "chain_ops": chain_ops,
        "dispatches_lazy": lazy_dispatches,
        "dispatches_eager": eager_dispatches,
        "fusion_cache_hit_rate": round(hits / (hits + misses), 3)
        if (hits + misses) else None,
        "flushes": {k.split(".")[-1]: v for k, v in snap["counters"].items()
                    if k.startswith("lazy.flushes.")},
        "mean_chain_len": round(chain_h["sum"] / chain_h["count"], 2)
        if chain_h.get("count") else None,
    })


def smoke(args):
    """Tiny-model CPU run of the REAL K-step path end-to-end: fit ->
    DeviceStagedIter (background h2d_stage engine op, io.stage span) ->
    Executor.fused_update_block (lax.scan dispatch).  Prints ONE JSON
    line with the dispatch count (= ceil(steps/K)) and the profiler-lane
    evidence that staging ran asynchronously."""
    # must win over any site TPU default BEFORE jax is first imported
    os.environ["JAX_PLATFORMS"] = "cpu"

    import tempfile

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import profiler, telemetry

    # --smoke IS the telemetry acceptance harness: it force-enables the
    # registry (overriding MXTPU_TELEMETRY=0) because its job is to
    # assert the instrumentation works; use the headline bench for
    # telemetry-free timing
    telemetry.set_enabled(True)
    telemetry.reset()

    K = args.steps_per_dispatch or 4
    BATCH = 16
    NBATCH = 24  # 6 blocks at K=4: enough for staging to run ahead
    mx.random.seed(0)
    rng = np.random.RandomState(0)
    X = rng.randn(BATCH * NBATCH, 32).astype("float32")
    y = rng.randint(0, 4, BATCH * NBATCH).astype("float32")
    it = mx.io.NDArrayIter(X, y, batch_size=BATCH)

    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=128, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())

    fname = os.path.join(tempfile.mkdtemp(), "smoke_profile.json")
    profiler.profiler_set_config(mode="all", filename=fname)
    profiler.profiler_set_state("run")
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            steps_per_dispatch=K)
    mx.waitall()
    profiler.profiler_set_state("stop")
    profiler.dump_profile()

    with open(fname) as f:
        events = json.load(f)["traceEvents"]
    h2d = [e for e in events if e["name"] == "io.stage"]
    fused = [e for e in events if e["name"] == "fit.dispatch"]

    def overlaps(a, b):
        return a["ts"] < b["ts"] + b["dur"] and b["ts"] < a["ts"] + a["dur"]

    h2d_overlap = any(overlaps(a, b) for a in h2d for b in fused)
    fused_tids = {e["tid"] for e in fused}
    # staging ops run on engine workers (record_span keeps real thread
    # ids), so an h2d span off the dispatching thread proves the H2D ran
    # asynchronously even when the tiny CPU spans are too short to overlap
    h2d_async = any(e["tid"] not in fused_tids for e in h2d)

    # telemetry snapshot asserts: the registry saw the run — dispatches
    # counted, input bytes staged to device, and the staging pipeline's
    # buffer occupancy observed at least once (docs/observability.md)
    snap = telemetry.snapshot()
    tel_dispatches = snap["counters"].get("executor.train_dispatches", 0)
    tel_h2d = snap["counters"].get("executor.h2d_bytes", 0)
    stage_seen = "io.buffer.h2d_stage" in snap["gauges"]
    assert tel_dispatches == -(-NBATCH // K), snap["counters"]
    assert tel_h2d > 0, snap["counters"]
    assert stage_seen, snap["gauges"]
    assert snap["histograms"]["module.step_seconds"]["count"] == tel_dispatches

    exe = mod._exec_group.execs[0]
    _emit({
        "metric": "bench smoke (K-step fused dispatch + async staging, CPU)",
        "steps": NBATCH,
        "steps_per_dispatch": K,
        "dispatches": exe._train_dispatches,
        "expected_dispatches": -(-NBATCH // K),
        "h2d_stage_spans": len(h2d),
        "fused_dispatch_spans": len(fused),
        "h2d_overlap": bool(h2d_overlap),
        "h2d_async": bool(h2d_async),
        "telemetry_dispatches": tel_dispatches,
        "telemetry_h2d_bytes": tel_h2d,
        "telemetry_stage_occupancy_seen": stage_seen,
        "telemetry_mfu": snap["gauges"].get("module.mfu"),
    })


# ----------------------------------------------------------------------
# --spmd-procs: the multi-process distributed-runtime row
# (docs/distributed.md).  The parent relaunches this bench as N ranks
# through tools/launch.py --local-spmd; every rank joins ONE
# jax.distributed mesh, trains the same deterministic problem through
# the K-step fused dispatch (explicit bucketed hierarchical gradient
# collectives — executor._comm_mode arms automatically at
# process_count > 1), runs the collective measure_comm probe, and
# rank 0 prints the row with the comm telemetry snapshot.
# ----------------------------------------------------------------------


def spmd(args):
    import subprocess
    import sys

    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    if args.smoke:
        # CPU smoke: a clean virtual-mesh runtime per rank (ranks size
        # their own device count via MXTPU_LOCAL_DEVICES; the launcher
        # makes --local-devices ranks CPU processes either way)
        env.pop("XLA_FLAGS", None)
        for k in list(env):
            if k.startswith("TPU_"):
                env.pop(k)
        env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    import shutil
    import tempfile

    ckpt_dir = tempfile.mkdtemp(prefix="mxtpu_bench_ckpt_")
    cmd = [sys.executable, os.path.join(repo, "tools", "launch.py"),
           "--local-spmd", "-n", str(args.spmd_procs), "-s", "0",
           "--local-devices", str(args.spmd_local_devices),
           sys.executable, os.path.join(repo, "bench.py"),
           "--spmd-worker", "--spmd-procs", str(args.spmd_procs),
           "--steps", str(args.steps), "--ckpt-dir", ckpt_dir]
    if args.smoke:
        cmd.append("--smoke")
    if args.comm_ab:
        cmd.append("--comm-ab")
    if args.batch:
        cmd += ["--batch", str(args.batch)]
    if args.steps_per_dispatch:
        cmd += ["--steps-per-dispatch", str(args.steps_per_dispatch)]
    try:
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                              timeout=1200)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    rows = [l[len("SPMDROW "):] for l in proc.stdout.splitlines()
            if l.startswith("SPMDROW ")]
    if proc.returncode != 0 or not rows:
        raise SystemExit("spmd bench failed (rc=%d):\n%s\n%s"
                         % (proc.returncode, proc.stdout, proc.stderr))
    print(rows[0])


def spmd_worker(args):
    """One rank of --spmd-procs (launched under --local-spmd env)."""
    import numpy as np

    from mxnet_tpu.parallel import multihost

    multihost.initialize()

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    telemetry.set_enabled(True)
    telemetry.reset()
    if args.comm_ab:
        # the auto-vs-default bucket A/B: the run itself trains under
        # the derived target (set BEFORE the module binds)
        os.environ["MXTPU_COMM_BUCKET_MB"] = "auto"
    rank = jax.process_index()
    mesh = multihost.global_mesh(hierarchical=True)
    n_dev = jax.device_count()
    K = args.steps_per_dispatch or 2
    BATCH = args.batch or (16 * n_dev if args.smoke else 32 * n_dev)
    mx.random.seed(0)
    rng = np.random.RandomState(0)

    if args.smoke:
        # under --comm-ab the smoke net is a chain of MEDIUM ~590KB
        # params: bucket packing moves whole arrays, so the two probe
        # bucket sizes only yield DIFFERENT bucket counts (the
        # two-point model's requirement, tune.fit_comm_model) when the
        # sweep is many packable arrays — one dominant weight packs
        # into one bucket at every size and the derivation keeps
        if args.comm_ab:
            in_dim, hidden, depth = 384, 384, 6
        else:
            in_dim, hidden, depth = 64, 256, 1
        X = rng.randn(BATCH * 4, in_dim).astype("float32")
        y = rng.randint(0, 8, BATCH * 4).astype("float32")
        it = mx.io.ResizeIter(mx.io.NDArrayIter(X, y, batch_size=BATCH),
                              size=1 << 30)
        net = mx.sym.Variable("data")
        for i in range(depth):
            net = mx.sym.FullyConnected(net, num_hidden=hidden,
                                        name="fc%d" % (i + 1))
            net = mx.sym.Activation(net, act_type="relu")
        net = mx.sym.FullyConnected(net, num_hidden=8, name="fc_out")
        net = mx.sym.SoftmaxOutput(net, name="softmax")
        fence_arg = "fc1_weight"
    else:
        from mxnet_tpu.models.resnet import resnet

        it = _endless_iter(mx, rng, BATCH, (224, 224, 3), 1000)
        net = resnet(50, layout="NHWC")
        fence_arg = "fc1_weight"
    mod = mx.mod.Module(net, context=mx.cpu() if args.smoke else mx.tpu(),
                        mesh=mesh)
    data_shape = it.provide_data[0][1]
    label_shape = it.provide_label[0][1]
    mod.bind(data_shapes=[("data", tuple(data_shape))],
             label_shapes=[(it.provide_label[0][0], tuple(label_shape))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    exe = mod._exec_group.execs[0]
    if exe._comm_mode() is None:
        # a bare assert would vanish under python -O and let a row
        # labelled "bucketed collectives" report an unarmed run
        raise SystemExit("--spmd-procs: the bucketed collective path "
                         "did not arm on this mesh (see "
                         "executor._comm_mode) — the row would be "
                         "mislabelled")
    staged = mx.io.DeviceStagedIter(it, steps_per_dispatch=K,
                                    place_fn=exe.place_step_input,
                                    stack_fn=exe.stack_block_input)
    blocks_per_chunk = max(1, -(-args.steps // K // 3))
    rates, steps_done = [], 0
    try:
        block = next(staged)  # compile + settle
        mod.forward_backward(block)
        mod.update()
        _fence(mod, fence_arg)
        for _ in range(3):
            t0 = time.time()
            n = 0
            for _ in range(blocks_per_chunk):
                block = next(staged)
                mod.forward_backward(block)
                mod.update()
                n += block.count
            _fence(mod, fence_arg)
            rates.append(BATCH * n / (time.time() - t0))
            steps_done += n
    finally:
        staged.close()
    # checkpoint-overhead A/B (docs/checkpoint.md): INTERLEAVED chunks —
    # plain, ckpt-armed, plain, ... over one warm staged iterator, so
    # host drift can't masquerade as checkpoint cost.  Armed chunks cut
    # one async snapshot at their last dispatch (the D2H capture is a
    # sync point; the shard write overlaps the following dispatches) and
    # drain the commit inside their own timed window, so every cost of
    # checkpointing — and nothing else — lands on the B side
    ckpt_rates = []
    ckpt_stats = None
    if args.ckpt_dir:
        from mxnet_tpu.ckpt import CheckpointManager

        mod._steps_per_dispatch = K  # manifest knob record
        mgr = CheckpointManager(directory=args.ckpt_dir,
                                every_steps=K * blocks_per_chunk)
        staged = mx.io.DeviceStagedIter(it, steps_per_dispatch=K,
                                        place_fn=exe.place_step_input,
                                        stack_fn=exe.stack_block_input)
        ab_plain = []
        armed_secs = blocked_secs = 0.0
        nb = 0
        try:
            for chunk in range(10):
                armed = chunk % 2 == 1
                t0 = time.time()
                tb = 0.0
                n = 0
                for _ in range(blocks_per_chunk):
                    block = next(staged)
                    mod.forward_backward(block)
                    mod.update()
                    n += block.count
                    if armed:
                        nb += block.count
                        tm = time.time()
                        mgr.note_dispatch(mod, 0, nb, steps=block.count)
                        tb += time.time() - tm
                # the pending write is deliberately NOT drained here: the
                # commit drains at the NEXT armed chunk's trigger (inside
                # its timed window, via note_dispatch -> snapshot), a full
                # cadence later — the production pattern, by which point
                # the shard write has overlapped the interleaved chunks
                _fence(mod, fence_arg)
                if chunk >= 2:  # first pair re-warms the staging pipeline
                    (ckpt_rates if armed else ab_plain).append(
                        BATCH * n / (time.time() - t0))
                    if armed:
                        armed_secs += time.time() - t0
                        blocked_secs += tb
        finally:
            staged.close()
            mgr.finalize()
        csnap = telemetry.snapshot()
        wh = csnap["histograms"].get("ckpt.write_seconds", {})
        ckpt_stats = {
            "every_steps": K * blocks_per_chunk,
            "snapshots": csnap["counters"].get("ckpt.snapshots", 0),
            "bytes": csnap["counters"].get("ckpt.bytes", 0),
            "write_secs": round(wh.get("sum", 0.0), 4),
            "ab_plain_rates": ab_plain,
            "armed_secs": armed_secs,
            "blocked_secs": blocked_secs,
        }
    # the probe is COLLECTIVE: every rank calls it here, in step
    probe = exe.measure_comm(iters=2)
    # auto-vs-default comm-bucket A/B (docs/perf.md "Autotuning"):
    # INTERLEAVED chunks over one warm staged iterator — auto (the
    # derived target), default, auto, ... — flipping only the bucket
    # env + the comm cache per chunk, so both block variants stay
    # jit-cached after the discarded first pair and host drift cannot
    # masquerade as a bucket-size effect.  Every rank flips in step
    # (same chunk schedule), so bucket plans never diverge across ranks
    comm_decision = getattr(exe, "_comm_auto_decision", None)
    comm_ab = None
    if args.comm_ab:
        from mxnet_tpu import config as _config

        default_mb = float(_config.spec("MXTPU_COMM_BUCKET_MB").default)
        auto_rates, dflt_rates = [], []
        staged = mx.io.DeviceStagedIter(it, steps_per_dispatch=K,
                                        place_fn=exe.place_step_input,
                                        stack_fn=exe.stack_block_input)
        try:
            for chunk in range(10):
                auto_side = chunk % 2 == 0
                os.environ["MXTPU_COMM_BUCKET_MB"] = (
                    "auto" if auto_side else repr(default_mb))
                exe._comm_mode_cache = "unset"
                t0 = time.time()
                n = 0
                for _ in range(blocks_per_chunk):
                    block = next(staged)
                    mod.forward_backward(block)
                    mod.update()
                    n += block.count
                _fence(mod, fence_arg)
                if chunk >= 2:  # first pair pays both sides' compiles
                    (auto_rates if auto_side else dflt_rates).append(
                        BATCH * n / (time.time() - t0))
        finally:
            staged.close()
            os.environ["MXTPU_COMM_BUCKET_MB"] = "auto"
            exe._comm_mode_cache = "unset"
        a = float(np.mean(dflt_rates))
        b = float(np.mean(auto_rates))
        comm_ab = {
            "a_default": {"value": round(a, 2),
                          "stdev": round(float(np.std(dflt_rates)), 2),
                          "bucket_mb": default_mb},
            "b_auto": {"value": round(b, 2),
                       "stdev": round(float(np.std(auto_rates)), 2),
                       "bucket_mb": round((comm_decision or {}).get(
                           "applied_bytes", 0) / 1e6, 3)},
            "delta_pct": round((b - a) / a * 100.0, 2),
        }
    snap = telemetry.snapshot()
    # per-rank skew column (docs/observability.md "Distributed
    # observability"): allgather every rank's mean step seconds — a
    # COLLECTIVE, so all ranks call it — and attribute the straggler
    # with the same max/median ratio the obs aggregator uses
    from jax.experimental import multihost_utils

    from mxnet_tpu.obs import aggregate as obs_aggregate

    # dispatch-latency histograms, not module.step_seconds: this driver
    # calls forward_backward/update directly, so the module-level step
    # books never fill here
    d_sum = d_count = 0.0
    for kind in ("block", "step"):
        h = snap["histograms"].get("executor.dispatch_seconds.%s" % kind, {})
        d_sum += h.get("sum", 0.0)
        d_count += h.get("count", 0)
    mean_step = (d_sum / d_count) if d_count else 0.0
    per_rank_step = np.asarray(multihost_utils.process_allgather(
        np.float64(mean_step))).reshape(-1)
    if rank == 0:
        import numpy as _np

        skew = obs_aggregate.step_skew(
            {i: float(v) for i, v in enumerate(per_rank_step)})
        comm_counters = {k: v for k, v in snap["counters"].items()
                         if k.startswith("comm.")}
        print("SPMDROW " + json.dumps({
            "metric": "multi-process SPMD train img/s (%d procs x %d "
                      "devices, K=%d, bucketed hierarchical collectives)"
                      % (jax.process_count(),
                         n_dev // jax.process_count(), K),
            "value": round(float(_np.mean(rates)), 2),
            "unit": "img/s",
            "stdev": round(float(_np.std(rates)), 2),
            "batch": BATCH,
            "steps": steps_done,
            "mesh_axes": list(mesh.axis_names),
            "rank_skew": {
                "per_rank_step_s": [round(float(v), 6)
                                    for v in per_rank_step],
                "max_over_median": (None
                                    if skew["max_over_median"] is None
                                    else round(skew["max_over_median"], 4)),
                "slowest_rank": skew["slowest_rank"],
            },
            "comm": {
                "buckets": probe["buckets"],
                "bucket_bytes": probe["bucket_bytes"],
                "bytes_reduced": comm_counters.get("comm.bytes_reduced"),
                "dispatches": comm_counters.get("comm.dispatches"),
                "gbps": round(probe["comm_gbps"], 4),
                "overlap_frac": round(probe["overlap_frac"], 4),
                # the MXTPU_COMM_BUCKET_MB=auto decision record, when
                # the run derived one (measured basis included)
                "auto": comm_decision,
            },
            # matched interleaved auto-vs-default bucket A/B (--comm-ab)
            "comm_ab": comm_ab,
            # matched interleaved A/B: plain chunks and ckpt-armed chunks
            # alternate over one warm iterator.  overhead_pct is the
            # DIRECTLY measured critical-path cost — host time blocked
            # inside the manager (D2H capture + commit drain + barrier)
            # as a fraction of armed training time with that cost
            # removed; the async shard write itself overlaps the next
            # dispatches and never blocks.  The A/B throughputs ride
            # along as context (ab_deficit_pct; chunk-level timing on a
            # shared host is noisy, which is why the headline number is
            # the measured one)
            "ckpt": (None if ckpt_stats is None else {
                "every_steps": ckpt_stats["every_steps"],
                "snapshots": ckpt_stats["snapshots"],
                "bytes": ckpt_stats["bytes"],
                "write_secs": ckpt_stats["write_secs"],
                "overhead_pct": round(
                    100.0 * ckpt_stats["blocked_secs"]
                    / max(1e-9, ckpt_stats["armed_secs"]
                          - ckpt_stats["blocked_secs"]), 2),
                "ab_deficit_pct": round(100.0 * float(_np.median(
                    [1.0 - b / a for a, b in
                     zip(ckpt_stats["ab_plain_rates"], ckpt_rates)])), 2),
                "ckpt_imgs_per_s": round(float(_np.mean(ckpt_rates)), 2),
            }),
        }))
    multihost.sync_global_devices("bench_spmd_done")


# ----------------------------------------------------------------------
# --serve: the serving load driver (docs/serving.md).  Two tenants share
# one device behind serving.ModelServer; clients drive it closed-loop
# (each submits its next request when the previous completes — the
# throughput-seeking shape) or open-loop (--offered-load R: requests
# arrive on a fixed schedule regardless of completions — the tail-
# latency-honest shape, since a slow server cannot slow its own arrival
# process).  Every ladder bucket is compiled during warmup, telemetry is
# reset, and the timed window must run compile-free — the row reports
# img/s, p50/p99 from the serving.request_seconds histogram, and the
# exact batch-fill ratio from the slots-used/padded counters.
# ----------------------------------------------------------------------


def _hist_q(hist, q):
    """Quantile from a telemetry fixed-bucket histogram snapshot — THE
    parse_log math (one implementation; the bench row and the rendered
    telemetry table must never disagree on what p99 means)."""
    import sys

    repo = os.path.dirname(os.path.abspath(__file__))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from tools.parse_log import _hist_quantile

    return _hist_quantile(hist, q)


def _serve_predictor(mx, net, sample_shape, ctx):
    """Predictor from a fresh randomly-initialized checkpoint of `net`
    (bound at batch 1; the server rebinds per bucket through the
    predictor's signature cache)."""
    mod = mx.mod.Module(net, context=ctx)
    mod.bind(data_shapes=[("data", (1,) + sample_shape)], label_shapes=None,
             for_training=False)
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                   magnitude=2))
    arg, aux = mod.get_params()
    params = {"arg:%s" % k: v for k, v in arg.items()}
    params.update({"aux:%s" % k: v for k, v in aux.items()})
    return mx.Predictor(net, params, {"data": (1,) + sample_shape}, ctx=ctx)


# the one statement of the --serve tenant contract, importable without
# building predictors: the agent subprocess builds tenants from
# _serve_models while the --replicas driver only needs the sample shape
# and request floor — sharing the constants keeps the two processes in
# lockstep by construction
SERVE_SMOKE_SAMPLE, SERVE_SMOKE_REQUESTS = (12,), 96
SERVE_FULL_SAMPLE, SERVE_FULL_REQUESTS = (224, 224, 3), 512


def _serve_models(args, mx):
    """(tenant predictors, sample shape, max_batch, wait_ms, total) —
    shared by the in-process ModelServer path, the --serve-agent
    replica process, and so the --replicas router path: every mode
    serves the IDENTICAL tenant set."""
    if args.smoke:
        def tiny(hidden, classes, seed):
            mx.random.seed(seed)
            d = mx.sym.Variable("data")
            h = mx.sym.Activation(
                mx.sym.FullyConnected(d, num_hidden=hidden, name="fc1"),
                act_type="relu")
            return mx.sym.SoftmaxOutput(
                mx.sym.FullyConnected(h, num_hidden=classes, name="fc2"),
                name="softmax")

        sample, ctx = SERVE_SMOKE_SAMPLE, mx.cpu()
        nets = {"small": tiny(16, 5, 0), "big": tiny(32, 7, 1)}
        max_batch, wait_ms = 8, 5.0
        total = args.requests or SERVE_SMOKE_REQUESTS
    else:
        from mxnet_tpu.models.resnet import resnet

        sample, ctx = SERVE_FULL_SAMPLE, mx.tpu()
        nets = {"resnet50": resnet(50, layout="NHWC"),
                "resnet152": resnet(152, layout="NHWC")}
        max_batch = args.batch or 32
        wait_ms = None  # registered default
        total = args.requests or SERVE_FULL_REQUESTS
    preds = {name: _serve_predictor(mx, net, sample, ctx)
             for name, net in nets.items()}
    return preds, sample, max_batch, wait_ms, total


def _drive_load(submit, tenants, xs, args, total):
    """Drive `total` requests through `submit(tenant, inputs)` —
    closed loop (--clients concurrent clients per tenant) or open loop
    (--offered-load req/s fixed arrival schedule).  Failures (timeouts
    past deadline, admission rejections under overload) are the
    MEASUREMENT in an overload run, not a crash: counted and returned.
    Returns (elapsed seconds, failed count, requests driven) — driven
    can exceed `total` because the closed loop rounds the per-client
    share UP (--requests is a floor, never silently cut)."""
    import threading

    failed = [0]
    fail_lock = threading.Lock()

    def _await(f):
        try:
            f.result(timeout=600)
        except Exception:
            with fail_lock:
                failed[0] += 1

    # ceil BOTH splits (tenant and per-client) so --requests is a true
    # floor — an odd total must never drive fewer requests than asked
    per_tenant = -(-total // len(tenants))
    driven = per_tenant * len(tenants)
    futs, t0 = [], time.time()
    if args.offered_load > 0:
        # open loop: fixed arrival schedule, round-robin over tenants —
        # arrivals never slow down because the server is slow, which is
        # exactly why overload must surface as counted failures here
        interval = 1.0 / args.offered_load
        for i in range(per_tenant * len(tenants)):
            at = t0 + i * interval
            delay = at - time.time()
            if delay > 0:
                time.sleep(delay)
            try:
                futs.append(submit(tenants[i % len(tenants)],
                                   {"data": xs[i % len(xs)]}))
            except Exception:
                with fail_lock:
                    failed[0] += 1
        for f in futs:
            _await(f)
    else:
        # closed loop: --clients concurrent clients per tenant
        def client(tenant, n):
            for i in range(n):
                try:
                    _await(submit(tenant, {"data": xs[i % len(xs)]}))
                except Exception:
                    with fail_lock:
                        failed[0] += 1

        threads = []
        # ceil: round UP so --requests is a floor, never silently cut
        n_per_client = max(1, -(-per_tenant // args.clients))
        driven = n_per_client * args.clients * len(tenants)
        for t in tenants:
            for _ in range(args.clients):
                th = threading.Thread(target=client, args=(t, n_per_client))
                th.start()
                threads.append(th)
        for th in threads:
            th.join()
    return time.time() - t0, failed[0], driven


def serve(args):
    if args.smoke:
        # must win over any site TPU default BEFORE jax is first imported
        os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    # like --smoke, this harness asserts its own instrumentation
    telemetry.set_enabled(True)
    telemetry.reset()

    preds, sample, max_batch, wait_ms, total = _serve_models(args, mx)
    server = mx.serving.ModelServer(preds, max_batch=max_batch,
                                    wait_ms=wait_ms)
    tenants = server.tenants
    rng = np.random.RandomState(0)
    xs = [rng.randn(*sample).astype("float32") for _ in range(16)]

    # warmup: compile every (tenant, bucket) program deterministically
    # (one synchronous dummy fill each — not via submit(), whose fill
    # grouping depends on batching-window timing) so the timed window
    # below is provably compile-free
    server.warmup()
    if args.trace_ab:
        return _serve_trace_ab(args, server, tenants, xs, total, telemetry)
    if args.mem_ab:
        return _serve_mem_ab(args, server, tenants, xs, total, telemetry)
    if args.lock_ab:
        return _serve_lock_ab(args, server, preds, max_batch, wait_ms,
                              xs, total, telemetry)
    telemetry.reset()
    miss0 = telemetry.counter_value("executor.compile_cache_misses")

    elapsed, failed, _driven = _drive_load(server.submit, tenants, xs,
                                           args, total)
    server.close()

    snap = telemetry.snapshot()
    counters, gauges = snap["counters"], snap["gauges"]
    used = counters.get("serving.batch_slots_used", 0)
    padded = counters.get("serving.batch_slots_padded", 0)
    fill_pct = 100.0 * used / (used + padded) if (used + padded) else None
    lat = snap["histograms"].get("serving.request_seconds", {})
    compile_misses = (telemetry.counter_value("executor.compile_cache_misses")
                      - miss0)
    completed = counters.get("serving.requests", 0)
    mode = "open" if args.offered_load > 0 else "closed"
    row = {
        "metric": "serving img/s, %d-tenant %s-loop continuous batching "
                  "(%s)" % (len(tenants), mode,
                            "tiny CPU smoke" if args.smoke
                            else "ResNet-50+152, 1 chip"),
        "value": round(completed / elapsed, 2),
        "unit": "img/s",
        "mode": mode,
        "offered_load": round(args.offered_load
                              or completed / elapsed, 2),
        "p50_ms": round(_hist_q(lat, 0.5) * 1e3, 3) if lat.get("count") else None,
        "p99_ms": round(_hist_q(lat, 0.99) * 1e3, 3) if lat.get("count") else None,
        "fill_pct": round(fill_pct, 2) if fill_pct is not None else None,
        "dispatches": counters.get("serving.dispatches", 0),
        "requests": completed,
        "failed": failed,
        "timeouts": counters.get("serving.timeouts", 0),
        "compile_misses_timed": compile_misses,
        "queue_depth_seen": gauges.get("serving.queue_depth") is not None,
        "max_batch": max_batch,
        "ladder": list(server.ladder),
        "tenants": {
            t: {"requests": counters.get("serving.requests.%s" % t, 0),
                "p99_ms": round(_hist_q(
                    snap["histograms"].get(
                        "serving.request_seconds.%s" % t, {}), 0.99) * 1e3, 3)
                if snap["histograms"].get(
                    "serving.request_seconds.%s" % t, {}).get("count")
                else None}
            for t in tenants},
        "smoke": bool(args.smoke),
    }
    if args.smoke:
        # the CI pins (tests/test_bench_smoke.py) start here: the
        # instrumentation must have seen the run, the timed window must
        # be compile-free, and nobody may have timed out
        assert row["fill_pct"] and row["fill_pct"] > 0, counters
        assert row["p99_ms"] and row["p99_ms"] > 0, snap["histograms"]
        assert row["timeouts"] == 0, counters
        assert row["failed"] == 0, "smoke run dropped requests"
        assert compile_misses == 0, "timed window recompiled"
        assert row["queue_depth_seen"], gauges
    _emit(row)


def _serve_trace_ab(args, server, tenants, xs, total, telemetry):
    """--serve --trace-ab: the request-tracing overhead pin.  Both
    sides run in ONE process against the SAME warm server — side A
    with sampling OFF (0.0), side B at --trace-sample (default 0.01,
    the always-on production setting) — as 3 timed chunks each, so the
    row carries per-side stdev exactly like `--ab` (the acceptance
    criterion: overhead <=1% at MXTPU_TRACE_SAMPLE=0.01, asserted
    within noise under --smoke)."""
    import numpy as np

    from mxnet_tpu.obs import tracing

    on_frac = max(0.0, float(args.trace_sample))
    per_chunk = max(24, -(-total // 3))
    miss0 = telemetry.counter_value("executor.compile_cache_misses")

    def side(fraction, chunks=3):
        rates = []
        prev = tracing.set_sample(fraction)
        try:
            for _ in range(chunks):
                elapsed, failed, driven = _drive_load(
                    server.submit, tenants, xs, args, per_chunk)
                assert failed == 0, "trace A/B dropped requests"
                rates.append(driven / elapsed)
        finally:
            tracing.set_sample(prev)
        return rates

    side(0.0, chunks=1)  # settle: one untimed chunk after warmup
    a_rates = side(0.0)       # tracing off
    b_rates = side(on_frac)   # tracing armed at the production fraction
    server.close()
    compile_misses = (telemetry.counter_value(
        "executor.compile_cache_misses") - miss0)
    a, b = float(np.mean(a_rates)), float(np.mean(b_rates))
    overhead_pct = (a - b) / a * 100.0
    noise_pct = 100.0 * (float(np.std(a_rates))
                         + float(np.std(b_rates))) / a
    row = {
        "metric": "request-tracing overhead, %d-tenant serving load "
                  "(%s), MXTPU_TRACE_SAMPLE=0 vs %g"
                  % (len(tenants), "tiny CPU smoke" if args.smoke
                     else "ResNet-50+152, 1 chip", on_frac),
        "value": round(overhead_pct, 3),
        "unit": "% img/s overhead",
        "sink": "trace_overhead",
        "a": {"label": "MXTPU_TRACE_SAMPLE=0",
              "img_s": round(a, 2),
              "stdev": round(float(np.std(a_rates)), 2)},
        "b": {"label": "MXTPU_TRACE_SAMPLE=%g" % on_frac,
              "img_s": round(b, 2),
              "stdev": round(float(np.std(b_rates)), 2)},
        "overhead_pct": round(overhead_pct, 3),
        "noise_pct": round(noise_pct, 3),
        "requests_per_chunk": per_chunk,
        "trace_spans": telemetry.counter_value("trace.spans"),
        "sampled_requests": telemetry.counter_value(
            "trace.requests_sampled"),
        # every armed-side submit mints a sampling decision; 0 here
        # means the B side never actually armed (the CI pin's check)
        "sampling_decisions": (
            telemetry.counter_value("trace.requests_sampled")
            + telemetry.counter_value("trace.requests_unsampled")),
        "compile_misses_timed": compile_misses,
        "smoke": bool(args.smoke),
    }
    if args.smoke:
        # the CI pin (tests/test_bench_smoke.py): the timed windows
        # never recompiled, the armed side really sampled the minted
        # contexts' sampling decisions, and the overhead is within
        # noise of the <=1% acceptance bar
        assert compile_misses == 0, "trace A/B window recompiled"
        assert row["sampling_decisions"] > 0, row
        assert overhead_pct <= max(1.0, 2.0 * noise_pct), row
    _emit(row)


def _serve_mem_ab(args, server, tenants, xs, total, telemetry):
    """--serve --mem-ab: the live-buffer census overhead pin.  Both
    sides run in ONE process against the SAME warm server — side A
    with the census disarmed (memory.set_census(False), the runtime
    equivalent of MXTPU_MEM_CENSUS=0: book/unbook return before
    touching the lock), side B with it armed (the default) — as 3
    timed chunks each, so the row carries per-side stdev exactly like
    `--ab`.  The acceptance bar (docs/observability.md "Memory
    observability"): census cost <=1% of serving throughput, asserted
    within noise under --smoke."""
    import numpy as np

    from mxnet_tpu.obs import memory

    per_chunk = max(24, -(-total // 3))
    miss0 = telemetry.counter_value("executor.compile_cache_misses")

    def side(armed, chunks=3):
        rates = []
        prev = memory.set_census(armed)
        try:
            for _ in range(chunks):
                elapsed, failed, driven = _drive_load(
                    server.submit, tenants, xs, args, per_chunk)
                assert failed == 0, "mem A/B dropped requests"
                rates.append(driven / elapsed)
        finally:
            memory.set_census(prev)
        return rates

    side(False, chunks=1)  # settle: one untimed chunk after warmup
    a_rates = side(False)  # census disarmed
    books0 = memory.census_stats()["books"]
    b_rates = side(True)   # census armed (the production default)
    books = memory.census_stats()["books"] - books0
    server.close()
    compile_misses = (telemetry.counter_value(
        "executor.compile_cache_misses") - miss0)
    a, b = float(np.mean(a_rates)), float(np.mean(b_rates))
    overhead_pct = (a - b) / a * 100.0
    noise_pct = 100.0 * (float(np.std(a_rates))
                         + float(np.std(b_rates))) / a
    row = {
        "metric": "live-buffer census overhead, %d-tenant serving load "
                  "(%s), MXTPU_MEM_CENSUS=0 vs 1"
                  % (len(tenants), "tiny CPU smoke" if args.smoke
                     else "ResNet-50+152, 1 chip"),
        "value": round(overhead_pct, 3),
        "unit": "% img/s overhead",
        "sink": "mem_overhead",
        "a": {"label": "MXTPU_MEM_CENSUS=0",
              "img_s": round(a, 2),
              "stdev": round(float(np.std(a_rates)), 2)},
        "b": {"label": "MXTPU_MEM_CENSUS=1",
              "img_s": round(b, 2),
              "stdev": round(float(np.std(b_rates)), 2)},
        "overhead_pct": round(overhead_pct, 3),
        "noise_pct": round(noise_pct, 3),
        "requests_per_chunk": per_chunk,
        # census ops during the armed side; 0 means the B side never
        # actually booked anything (the CI pin's "really armed" check)
        "census_books": books,
        "live_bytes": memory.live_bytes(),
        "peak_bytes": memory.peak()["bytes"],
        "compile_misses_timed": compile_misses,
        "smoke": bool(args.smoke),
    }
    if args.smoke:
        # the CI pin (tests/test_bench_smoke.py): the timed windows
        # never recompiled, the armed side really booked buffers, and
        # the overhead is within noise of the <=1% acceptance bar
        assert compile_misses == 0, "mem A/B window recompiled"
        assert row["census_books"] > 0, row
        assert overhead_pct <= max(1.0, 2.0 * noise_pct), row
    _emit(row)


def _serve_lock_ab(args, server, preds, max_batch, wait_ms, xs, total,
                   telemetry):
    """--serve --lock-ab: the MXTPU_LOCK_CHECK sentinel overhead pin.
    Side A drives the plain warm server (sentinel off — its locks are
    raw threading primitives, bound at construction).  Side B sets
    MXTPU_LOCK_CHECK=1 and builds a FRESH server over the same
    predictors — the locks.lock/condition factories read the env at
    construction, so only the new server's locks are RecordingLocks —
    then drives the identical load.  3 timed chunks per side (the --ab
    stdev machinery).  Under --smoke the row asserts the armed side's
    lock-order graph has ZERO cycles and the throughput overhead is
    under the 5% acceptance bar (within noise)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import locks

    per_chunk = max(24, -(-total // 3))
    miss0 = telemetry.counter_value("executor.compile_cache_misses")

    def side(srv, chunks=3):
        rates = []
        for _ in range(chunks):
            elapsed, failed, driven = _drive_load(
                srv.submit, srv.tenants, xs, args, per_chunk)
            assert failed == 0, "lock A/B dropped requests"
            rates.append(driven / elapsed)
        return rates

    side(server, chunks=1)  # settle: one untimed chunk after warmup
    a_rates = side(server)  # sentinel off
    server.close()

    prev = os.environ.get("MXTPU_LOCK_CHECK")
    os.environ["MXTPU_LOCK_CHECK"] = "1"
    try:
        locks.reset()
        armed = mx.serving.ModelServer(preds, max_batch=max_batch,
                                       wait_ms=wait_ms)
        armed.warmup()
        side(armed, chunks=1)  # settle the armed side too
        b_rates = side(armed)
        armed.close()
        cycle_list = locks.cycles()
        graph_edges = sum(len(v) for v in locks.order_graph().values())
        snap = telemetry.snapshot()
        # hold_seconds books on every release; wait_seconds only on
        # contended acquires (a clean smoke run may legitimately be
        # contention-free), so the presence pin is on hold hists
        lock_hists = sorted(k for k in snap["histograms"]
                            if k.startswith("locks.hold_seconds."))
    finally:
        if prev is None:
            os.environ.pop("MXTPU_LOCK_CHECK", None)
        else:
            os.environ["MXTPU_LOCK_CHECK"] = prev

    compile_misses = (telemetry.counter_value(
        "executor.compile_cache_misses") - miss0)
    a, b = float(np.mean(a_rates)), float(np.mean(b_rates))
    overhead_pct = (a - b) / a * 100.0
    noise_pct = 100.0 * (float(np.std(a_rates))
                         + float(np.std(b_rates))) / a
    row = {
        "metric": "lock-sentinel overhead, %d-tenant serving load "
                  "(%s), MXTPU_LOCK_CHECK=0 vs 1"
                  % (len(preds), "tiny CPU smoke" if args.smoke
                     else "ResNet-50+152, 1 chip"),
        "value": round(overhead_pct, 3),
        "unit": "% img/s overhead",
        "sink": "lock_overhead",
        "a": {"label": "MXTPU_LOCK_CHECK=0",
              "img_s": round(a, 2),
              "stdev": round(float(np.std(a_rates)), 2)},
        "b": {"label": "MXTPU_LOCK_CHECK=1",
              "img_s": round(b, 2),
              "stdev": round(float(np.std(b_rates)), 2)},
        "overhead_pct": round(overhead_pct, 3),
        "noise_pct": round(noise_pct, 3),
        "requests_per_chunk": per_chunk,
        "order_cycles": len(cycle_list),
        "order_edges": graph_edges,
        "lock_hists": lock_hists,
        "contended": telemetry.counter_value("locks.contended"),
        "compile_misses_timed": compile_misses,
        "smoke": bool(args.smoke),
    }
    if args.smoke:
        # the CI pin (tests/test_bench_smoke.py): the timed windows
        # never recompiled, the armed side really recorded (edges +
        # wait histograms prove RecordingLocks were live), its order
        # graph is acyclic, and the overhead is within noise of the
        # <5% acceptance bar
        assert compile_misses == 0, "lock A/B window recompiled"
        assert graph_edges > 0, "armed side recorded no lock edges"
        assert lock_hists, "armed side booked no lock histograms"
        assert cycle_list == [], cycle_list
        assert overhead_pct <= max(5.0, 2.0 * noise_pct), row
    _emit(row)


# ----------------------------------------------------------------------
# --serve --replicas N: the multi-replica tier (docs/serving.md
# "Multi-replica tier").  For each requested count, a fleet of N
# ReplicaAgent processes (each the SAME tenants as --serve, launched by
# tools/launch.py --serve-replicas) takes the SAME offered load through
# one Router — the measured composition row for ROADMAP item 1.
# ----------------------------------------------------------------------


def serve_agent(args):
    """One replica of --serve --replicas: build the --serve tenant set,
    warm every bucket, and serve it on MXTPU_ROUTER_PORT until the
    router sends CLOSE (internal; spawned via tools/launch.py)."""
    if args.smoke:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.router import ReplicaAgent

    # the replica's health replies carry the serving.* fill extract the
    # router's ladder adaptation (and the bench row) feeds on — force
    # it on like serve() does, regardless of an inherited
    # MXTPU_TELEMETRY=0
    telemetry.set_enabled(True)
    preds, _sample, max_batch, wait_ms, _total = _serve_models(args, mx)
    agent = ReplicaAgent(preds, max_batch=max_batch, wait_ms=wait_ms)
    agent.warmup()
    print("AGENT_READY replica=%d port=%d" % (agent.replica_id, agent.port),
          flush=True)
    agent.serve_forever()


def _launch_fleet(n, args):
    """Spawn the N-replica fleet via the real launcher; returns
    (launcher process, replica address list)."""
    import subprocess
    import sys
    import threading

    repo = os.path.dirname(os.path.abspath(__file__))
    cmd = [sys.executable, os.path.join(repo, "tools", "launch.py"),
           "--serve-replicas", str(n),
           sys.executable, os.path.join(repo, "bench.py"), "--serve-agent"]
    if args.smoke:
        cmd.append("--smoke")
    if args.batch:
        cmd += ["--batch", str(args.batch)]
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True, cwd=repo)
    addrs = None
    for line in proc.stdout:
        if line.startswith("MXTPU_ROUTER_REPLICAS="):
            addrs = line.strip().split("=", 1)[1].split(",")
            break
    if not addrs:
        proc.terminate()
        raise RuntimeError("launch.py --serve-replicas printed no "
                           "MXTPU_ROUTER_REPLICAS line")
    # keep draining the shared pipe (replica AGENT_READY lines) so a
    # chatty fleet can never block on a full pipe
    threading.Thread(target=proc.stdout.read, daemon=True).start()
    return proc, addrs


def serve_replicas(args):
    if args.smoke:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np

    from mxnet_tpu import telemetry
    from mxnet_tpu.router import Router

    telemetry.set_enabled(True)
    counts = sorted({int(c) for c in args.replicas.split(",") if c.strip()})
    sample = SERVE_SMOKE_SAMPLE if args.smoke else SERVE_FULL_SAMPLE
    total = args.requests or (SERVE_SMOKE_REQUESTS if args.smoke
                              else SERVE_FULL_REQUESTS)
    rng = np.random.RandomState(0)
    xs = [rng.randn(*sample).astype("float32") for _ in range(16)]
    poll_ms = 100.0 if args.smoke else None
    per_count = {}
    for n in counts:
        proc, addrs = _launch_fleet(n, args)
        router = None
        try:
            # adaptation off for the bench: every count must serve the
            # same ladder, or the rows measure ladder drift instead of
            # scaling.  connect_timeout must cover the fleet's warmup:
            # each agent binds its socket, then compiles EVERY
            # (tenant, bucket) program before serve_forever() accepts —
            # minutes for the full-mode ResNet pair, so the Router's
            # default 60s HELLO bound would give up mid-compile
            router = Router(addrs, poll_ms=poll_ms, adapt_window_s=0,
                            connect_timeout=120.0 if args.smoke
                            else 1800.0)
            router.warmup()
            telemetry.reset()
            elapsed, failed, driven = _drive_load(
                router.submit, router.tenants, xs, args, total)
            # let the final health poll land so the per-replica fill
            # accounting below reflects the whole run
            time.sleep(3 * (poll_ms or 200.0) / 1e3)
            snap = telemetry.snapshot()
            counters, gauges = snap["counters"], snap["gauges"]
            lat = snap["histograms"].get("router.route_seconds", {})
            health = router.health()
            per_replica, used, padded, device = {}, 0, 0, None
            for name, rep in sorted(health["replicas"].items()):
                serving = ((rep.get("health") or {}).get("serving")) or {}
                device = (rep.get("health") or {}).get("device") or device
                per_replica[name] = {
                    "dispatches": serving.get("dispatches", 0),
                    "requests": serving.get("requests", 0),
                }
                used += serving.get("slots_used", 0)
                padded += serving.get("slots_padded", 0)
            completed = counters.get("router.requests", 0)
            router.close(shutdown_replicas=True)
            rc = proc.wait(timeout=300)
        except BaseException:
            # never orphan the fleet: a bring-up or drive failure must
            # still CLOSE the replicas (or kill the launcher) before
            # the error propagates
            if router is not None:
                try:
                    router.close(drain=False, shutdown_replicas=True,
                                 timeout=30)
                except Exception:
                    pass
            if proc.poll() is None:
                proc.terminate()
            proc.wait(timeout=60)
            raise
        per_count[str(n)] = {
            "img_s": round(completed / elapsed, 2),
            "p50_ms": (round(_hist_q(lat, 0.5) * 1e3, 3)
                       if lat.get("count") else None),
            "p99_ms": (round(_hist_q(lat, 0.99) * 1e3, 3)
                       if lat.get("count") else None),
            "requests": completed,
            "driven": driven,
            "failed": failed,
            "redispatches": counters.get("router.redispatches", 0),
            "replicas_healthy": gauges.get("router.replicas_healthy"),
            "fill_pct": (round(100.0 * used / (used + padded), 2)
                         if (used + padded) else None),
            "per_replica": per_replica,
            # as the replicas report it (this parent stays off JAX)
            "device": device,
            "launcher_rc": rc,
        }
    top = per_count[str(counts[-1])]
    mode = "open" if args.offered_load > 0 else "closed"
    row = {
        "metric": "multi-replica serving img/s through the router, "
                  "N in %s, %s loop (%s)"
                  % (counts, mode,
                     "tiny CPU smoke" if args.smoke
                     else "ResNet-50+152 per replica"),
        "value": top["img_s"],
        "unit": "img/s",
        "mode": mode,
        "replica_counts": per_count,
        "scaling_1_to_max": (round(top["img_s"]
                                   / per_count["1"]["img_s"], 3)
                             if "1" in per_count and counts[-1] != 1
                             and per_count["1"]["img_s"] else None),
        "host_cores": os.cpu_count(),
        "requests_per_count": total,
        "smoke": bool(args.smoke),
    }
    if args.smoke:
        # the CI pins (tests/test_bench_smoke.py) start here
        for n in counts:
            sub = per_count[str(n)]
            assert sub["failed"] == 0, per_count
            # every DRIVEN request completed (driven >= the --requests
            # floor: the closed loop rounds per-client shares up)
            assert sub["requests"] == sub["driven"] >= total, per_count
            assert sub["redispatches"] == 0, per_count
            assert sub["launcher_rc"] == 0, per_count
            assert sub["p99_ms"] and sub["p99_ms"] >= sub["p50_ms"] > 0
            served = [r for r in sub["per_replica"].values()
                      if r["dispatches"] > 0]
            # the router genuinely SPREAD traffic: with >1 replica at
            # least two served fills
            assert len(served) >= min(n, 2), per_count
    # one process per chip: this parent only routes — the replicas it
    # launched hold the chips, so it must never have initialised a JAX
    # backend (on a TPU host that would have taken a chip from them)
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise SystemExit("bench.py --serve --replicas: the router parent "
                         "initialised a JAX backend; it must stay off "
                         "the device its replicas need")
    _emit(row, device=False)


def serve_generate(args):
    """--serve --generate: mixed prefill/decode generative serving
    through the Router (docs/serving.md "Decode sessions & continuous
    batching").

    One in-process ReplicaAgent hosts a generative TransformerLM
    tenant; closed-loop clients stream generations with VARIED prompt
    lengths and token budgets through Router.submit_generate, so new
    prompts prefill while earlier sessions are mid-decode — the
    token-level continuous-batching path is what gets timed, not a
    lockstep batch.  The row reports end-to-end generated tokens/s,
    request latency quantiles from the server's own histogram, and the
    decode-loop health gauges (batch fill, KV-slot occupancy)."""
    import threading

    if args.smoke:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.router import ReplicaAgent, Router

    telemetry.set_enabled(True)
    telemetry.reset()

    lm, params, _targets, _plen, ctx = _lm_spec(args, mx)
    if args.smoke:
        max_sessions, max_len, seq_buckets = 4, 48, [8, 16]
        total = args.requests or 24
        prompt_lens, budgets = (2, 13), (4, 17)
    else:
        max_sessions, max_len, seq_buckets = 16, lm.max_len, None
        total = args.requests or 256
        prompt_lens, budgets = (8, 65), (16, 129)

    agent = ReplicaAgent(
        {}, port=0, replica_id=0, wait_ms=1.0,
        generative={"lm": dict(model=lm, params=params, ctx=ctx,
                               max_sessions=max_sessions, max_len=max_len,
                               max_decode_tokens=budgets[1],
                               seq_buckets=seq_buckets)})
    agent_thread = threading.Thread(target=agent.serve_forever, daemon=True)
    agent_thread.start()
    router = Router(replicas=["127.0.0.1:%d" % agent.port],
                    connect_timeout=120.0 if args.smoke else 1800.0)
    try:
        router.warmup()  # compiles every prefill/decode bucket program
        telemetry.reset()
        miss0 = telemetry.counter_value("executor.compile_cache_misses")

        rng = np.random.RandomState(0)
        jobs = [(rng.randint(0, lm.vocab,
                             size=rng.randint(*prompt_lens)).tolist(),
                 int(rng.randint(*budgets)))
                for _ in range(total)]
        tokens_out, failed = [0], [0]
        lock = threading.Lock()
        n_clients = max(1, args.clients)
        shares = [jobs[i::n_clients] for i in range(n_clients)]

        def client(share):
            for prompt, max_new in share:
                try:
                    r = router.submit_generate(
                        "lm", prompt, max_new_tokens=max_new,
                        timeout_ms=600000).result(timeout=600)
                    with lock:
                        tokens_out[0] += len(r.tokens)
                except Exception:
                    with lock:
                        failed[0] += 1

        t0 = time.time()
        threads = [threading.Thread(target=client, args=(s,), daemon=True)
                   for s in shares if s]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        elapsed = time.time() - t0
        compile_misses = (telemetry.counter_value(
            "executor.compile_cache_misses") - miss0)
        snap = telemetry.snapshot()
        counters, gauges = snap["counters"], snap["gauges"]
        lat = snap["histograms"].get("serving.request_seconds", {})
    finally:
        router.close(shutdown_replicas=True)
        agent_thread.join(timeout=30)

    retired = counters.get("serving.decode.retired", 0)
    row = {
        "metric": "generative serving tokens/s, mixed prefill/decode "
                  "through the router, %d clients (%s)"
                  % (n_clients, "tiny CPU smoke" if args.smoke
                     else "512d 4-layer LM, 1 chip"),
        "value": round(tokens_out[0] / elapsed, 2),
        "unit": "tokens/s",
        "tokens": tokens_out[0],
        "requests": total,
        "failed": failed[0],
        "p50_ms": (round(_hist_q(lat, 0.5) * 1e3, 3)
                   if lat.get("count") else None),
        "p99_ms": (round(_hist_q(lat, 0.99) * 1e3, 3)
                   if lat.get("count") else None),
        "decode_dispatches": counters.get("serving.decode.dispatches", 0),
        "decode_tokens": counters.get("serving.decode.tokens", 0),
        "retired": {
            "total": retired,
            "eos": counters.get("serving.decode.retired.eos", 0),
            "length": counters.get("serving.decode.retired.length", 0),
        },
        "batch_fill_ratio": gauges.get("serving.decode.batch_fill_ratio"),
        "kv_slot_occupancy": gauges.get("kv.slot_occupancy"),
        "bucket_programs": counters.get("serving.decode.bucket_programs", 0),
        "compile_misses_timed": compile_misses,
        "max_sessions": max_sessions,
        "smoke": bool(args.smoke),
    }
    if args.smoke:
        # CI pins (tests/test_bench_smoke.py) start here: every
        # generation completed, the decode loop genuinely ran
        # token-level batches, and the timed window never compiled
        assert row["failed"] == 0, "smoke run dropped generations"
        assert row["requests"] == retired, row["retired"]
        # each session emits its FIRST token at prefill, the rest
        # through decode steps — so the end-to-end token count must
        # reconcile exactly against the decode counter (zero lost or
        # double-counted tokens across retirement)
        assert row["tokens"] > 0, row
        assert row["tokens"] == row["decode_tokens"] + retired, row
        assert row["decode_dispatches"] > 0, counters
        assert row["compile_misses_timed"] == 0, "timed window recompiled"
        assert row["p99_ms"] and row["p99_ms"] >= row["p50_ms"] > 0, lat
        assert row["kv_slot_occupancy"] is not None, gauges
    _emit(row)


if __name__ == "__main__":
    main()
