#!/usr/bin/env python
"""Benchmark table: one measured row per BASELINE.md entry, on one chip.

Parity: reference example/image-classification/benchmark_score.py
(inference img/s) + docs/how_to/perf.md training tables + the LSTM/SSD
example configs.  Prints one JSON line per row and writes BENCH_TABLE.json.

vs_baseline compares against the reference's best published single-GPU
number (1x P100) for that config where one exists; rows the reference
never published a number for carry vs_baseline: null.

Methodology (CHIP-limited, not harness-limited): every row runs K
batches per dispatch inside ONE compiled program — a `lax.scan` over a
device-resident batch stack (inference: forward per tick; training:
fwd+bwd+SGD with params/momentum/aux as the scan carry — exactly how a
real TPU training loop amortizes host dispatch).  The fixed per-dispatch
host cost is therefore paid once per K batches and the per-model
numbers are FLOP-consistent instead of clamped at a dispatch floor.
Each row reports `mfu` = XLA-counted FLOPs / time / 197 TFLOP/s (v5e
bf16 peak, MAC=2 both sides).

Quotable numbers: the per-row `value` here IS the quotable number for
its config (chip-limited, batch as stated).  The repo headline remains
`bench.py`'s batch-512 fused-Module step — the deployment-shaped config;
batch-32 rows exist for reference-table parity (see README Benchmarks).
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from tpu_constants import V5E_PEAK_FLOPS  # noqa: E402

ROWS = []


def _row(metric, value, unit, baseline, config, mfu=None):
    r = {"metric": metric, "value": round(value, 2), "unit": unit,
         "vs_baseline": round(value / baseline, 3) if baseline else None,
         "mfu": round(mfu, 4) if mfu else None,
         "config": config}
    ROWS.append(r)
    print(json.dumps(r), flush=True)


def _flops(compiled, trip_count=1):
    """XLA cost analysis counts a while/scan body ONCE — multiply by the
    scan trip count to get whole-program FLOPs (verified against
    hand-computed model FLOPs: ResNet-50 fwd 7.8 GFLOP/img MAC=2)."""
    try:
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, list) else ca
        return float(ca.get("flops", 0.0)) * trip_count
    except Exception:
        return 0.0


def _bind_module(net, data_shape, label_shape=None, data_names=("data",),
                 label_names=("softmax_label",), for_training=True):
    import mxnet_tpu as mx

    mx.random.seed(0)
    mod = mx.mod.Module(net, context=mx.tpu(), compute_dtype="bfloat16",
                        data_names=list(data_names),
                        label_names=list(label_names))
    mod.bind(data_shapes=[(data_names[0], data_shape)],
             label_shapes=[(label_names[0], label_shape)] if label_shape else None,
             for_training=for_training)
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                   magnitude=2))
    return mod


def _scan_forward(mod, data_stack):
    """One jitted program: forward over K device-resident batches."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.executor import _run_graph

    exe = mod._exec_group.execs[0]
    an, xn = exe._arg_names, exe._aux_names
    entries, order = exe._entries, exe._order
    cast = exe._cast()
    didx = an.index("data")

    def run(args, aux, stack):
        def tick(carry, xk):
            vals = list(args)
            vals[didx] = xk
            outs, _ = _run_graph(entries, order, an, xn, tuple(vals), aux,
                                 False, None, cast=cast)
            return carry, outs[0].reshape(-1)[0]

        _, ys = lax.scan(tick, jnp.float32(0), stack)
        return ys

    args = exe._place(exe._gather_args())
    aux = exe._gather_aux()
    jf = jax.jit(run)
    compiled = jf.lower(args, aux, data_stack).compile()
    return compiled, args, aux


def _scan_train(mod, data_stack, label_stack, lr=0.05, momentum=0.9):
    """One jitted program: K full train steps (fwd+bwd+SGD momentum),
    params/momentum/aux carried through the scan — the compiled-loop
    training pattern."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.executor import _run_graph

    exe = mod._exec_group.execs[0]
    an, xn = exe._arg_names, exe._aux_names
    entries, order = exe._entries, exe._order
    cast = exe._cast()
    input_names = set(mod._data_names) | set(mod._label_names)
    diff_idx = [i for i, n in enumerate(an) if n not in input_names]
    didx = an.index(mod._data_names[0])
    lidx = an.index(mod._label_names[0]) if mod._label_names else None

    def run(dv, mom, aux, xs, ys, seed):
        rng0 = jax.random.key(seed)

        def tick(carry, xy):
            dv, mom, aux, i = carry
            xk, yk = xy

            def fwd(d):
                vals = [None] * len(an)
                for j, v in zip(diff_idx, d):
                    vals[j] = v
                vals[didx] = xk
                if lidx is not None:
                    vals[lidx] = yk
                return _run_graph(entries, order, an, xn, tuple(vals), aux,
                                  True, jax.random.fold_in(rng0, i),
                                  cast=cast)

            (outs, aux_upd), vjp_fn = jax.vjp(fwd, dv)
            cots = tuple(jnp.ones_like(o) for o in outs)
            (grads,) = vjp_fn((cots, tuple(jnp.zeros_like(a) for a in aux_upd)))
            mom = tuple(momentum * m - lr * g for m, g in zip(mom, grads))
            dv = tuple(w + m for w, m in zip(dv, mom))
            return (dv, mom, aux_upd, i + 1), outs[0].reshape(-1)[0]

        (dv, mom, aux, _), outs = lax.scan(
            tick, (dv, mom, aux, jnp.uint32(0)), (xs, ys))
        return dv, mom, aux, outs

    args = exe._place(exe._gather_args())
    dv = tuple(args[i] for i in diff_idx)
    mom = tuple(jnp.zeros_like(v) for v in dv)
    aux = exe._gather_aux()
    jf = jax.jit(run, donate_argnums=(0, 1, 2))
    compiled = jf.lower(dv, mom, aux, data_stack, label_stack,
                        np.uint32(0)).compile()
    return compiled, (dv, mom, aux)


def _time_compiled(call, fence_of_result, repeats=6, warmup=2):
    for _ in range(warmup):
        r = call()
    fence_of_result(r)
    t0 = time.time()
    for _ in range(repeats):
        r = call()
    fence_of_result(r)
    return (time.time() - t0) / repeats


def _stack(rng, k, shape, dtype="float32", hi=None):
    import jax

    if hi is None:
        a = rng.randn(k, *shape).astype(dtype)
    else:
        a = rng.randint(0, hi, (k,) + shape).astype(dtype)
    return jax.device_put(a)


def bench_inference(name, sym_fn, image_shape, baseline, batch=32, k=64,
                    note=""):
    # k=64: a fast model at batch 32 finishes 16 batches in ~20-40 ms of
    # device time, so the fixed per-dispatch host cost is amortized over
    # 64 batches (its share at k=64 on the TPU host: not measured)
    net = sym_fn()
    mod = _bind_module(net, (batch,) + image_shape, None, for_training=False)
    rng = np.random.RandomState(0)
    stack = _stack(rng, k, (batch,) + image_shape)
    compiled, args, aux = _scan_forward(mod, stack)
    dt = _time_compiled(lambda: compiled(args, aux, stack),
                        lambda r: np.asarray(r[0]))
    per_s = k * batch / dt
    _row("Inference %s img/s" % name, per_s, "img/s", baseline,
         "batch %d bf16, %d batches/dispatch (lax.scan), 1 chip vs 1x P100 "
         "fp32%s" % (batch, k, (". MFU: " + note) if note else ""),
         mfu=_flops(compiled, k) / dt / V5E_PEAK_FLOPS)


def bench_train(name, sym_fn, image_shape, baseline, batch=32, k=16,
                classes=1000, note=""):
    net = sym_fn()
    mod = _bind_module(net, (batch,) + image_shape, (batch,))
    rng = np.random.RandomState(0)
    xs = _stack(rng, k, (batch,) + image_shape)
    ys = _stack(rng, k, (batch,), hi=classes)
    compiled, state = _scan_train(mod, xs, ys)

    def call():
        # donated args: re-feed the previous call's outputs (steady-state
        # training: params/momentum/aux flow call to call)
        call.state = compiled(*call.state, xs, ys, np.uint32(0))[:3]
        return call.state

    call.state = state
    dt = _time_compiled(call, lambda r: np.asarray(r[0][0].reshape(-1)[0]))
    per_s = k * batch / dt
    _row("Training %s img/s" % name, per_s, "img/s", baseline,
         "batch %d bf16+fp32 master, fwd+bwd+SGD, %d steps/dispatch "
         "(lax.scan carry), 1 chip vs 1x P100 fp32%s"
         % (batch, k, (". MFU: " + note) if note else ""),
         mfu=_flops(compiled, k) / dt / V5E_PEAK_FLOPS)


def _lstm_row(row_name, vocab, embed, hidden, layers, seq, batch, k, note=""):
    import mxnet_tpu as mx
    cell = mx.rnn.FusedRNNCell(hidden, num_layers=layers, mode="lstm",
                               prefix="lstm_")
    data = mx.sym.Variable("data")
    label = mx.sym.Variable("softmax_label")
    emb = mx.sym.Embedding(data, input_dim=vocab, output_dim=embed,
                           name="embed")
    output, _ = cell.unroll(seq, inputs=emb, layout="NTC", merge_outputs=True)
    pred = mx.sym.Reshape(output, shape=(-1, hidden))
    pred = mx.sym.FullyConnected(pred, num_hidden=vocab, name="pred")
    lab = mx.sym.Reshape(label, shape=(-1,))
    net = mx.sym.SoftmaxOutput(pred, lab, name="softmax")
    mod = _bind_module(net, (batch, seq), (batch, seq))
    rng = np.random.RandomState(0)
    xs = _stack(rng, k, (batch, seq), hi=vocab)
    ys = _stack(rng, k, (batch, seq), hi=vocab)
    compiled, state = _scan_train(mod, xs, ys, lr=0.1, momentum=0.0)

    def call():
        call.state = compiled(*call.state, xs, ys, np.uint32(0))[:3]
        return call.state

    call.state = state
    dt = _time_compiled(call, lambda r: np.asarray(r[0][0].reshape(-1)[0]))
    _row("Training %s tokens/s" % row_name, k * batch * seq / dt, "tokens/s",
         None,
         "%dx%d LSTM (lax.scan fused), bptt %d, batch %d, bf16, %d "
         "steps/dispatch%s" % (layers, hidden, seq, batch, k,
                               (". MFU: " + note) if note else ""),
         mfu=_flops(compiled, k) / dt / V5E_PEAK_FLOPS)


def bench_lstm_ptb(k=8):
    """LSTM language model, PTB config (reference example/rnn/lstm_bucketing.py
    defaults: 2x200 LSTM, embed 200, vocab 10k, bptt 35, batch 32)."""
    _lstm_row("LSTM-PTB", 10000, 200, 200, 2, 35, 32, k,
              note="latency-bound by design: per scan tick each layer's "
                   "gate matmul is [32,400]x[400,800] (20 MFLOP) — M=32 "
                   "rows underfill the MXU and 70 sequential tick-layers "
                   "serialize; the MXU-shaped row below is the same code "
                   "at a modern size. Reference "
                   "example/rnn/lstm_bucketing.py config (no published "
                   "reference number)")


def bench_lstm_large(k=8):
    """MXU-shaped LSTM: 4x1024, batch 512 — the same fused-RNN code path
    at a size whose gate matmuls ([512,2048]x[2048,4096]) fill the MXU."""
    _lstm_row("LSTM-4x1024", 10000, 1024, 1024, 4, 35, 512, k,
              note="same fused-RNN kernel as LSTM-PTB at MXU-filling size; residual vs conv models is the sequential scan dependency (140 tick-layers serialize per step)")


def bench_ssd(k=6):
    """SSD-300 VGG16-reduced training step (reference example/ssd)."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.models.ssd import get_ssd_vgg16

    batch = 32
    net = get_ssd_vgg16(num_classes=20, mode="train")
    mod = _bind_module(net, (batch, 3, 300, 300), (batch, 3, 6),
                       label_names=("label",))
    rng = np.random.RandomState(0)
    xs = _stack(rng, k, (batch, 3, 300, 300))
    label = np.full((k, batch, 3, 6), -1, np.float32)
    label[:, :, 0] = [0, 0.1, 0.1, 0.5, 0.5, 0]
    ys = jax.device_put(label)
    compiled, state = _scan_train(mod, xs, ys, lr=0.001)

    def call():
        call.state = compiled(*call.state, xs, ys, np.uint32(0))[:3]
        return call.state

    call.state = state
    dt = _time_compiled(call, lambda r: np.asarray(r[0][0].reshape(-1)[0]))
    _row("Training SSD-300 VGG16 img/s", k * batch / dt, "img/s", None,
         "batch 32 bf16, MultiBoxTarget in-graph, %d steps/dispatch; "
         "reference example/ssd config (no published reference number)" % k,
         mfu=_flops(compiled, k) / dt / V5E_PEAK_FLOPS)


def bench_input_pipeline(n_images=768, image=224, batch=64, epochs=2):
    """End-to-end real-format path: JPEGs -> im2rec .rec -> ImageRecordIter
    (native C++ decode + prefetch) -> Module.fit on the chip, steady-state.

    Reports e2e img/s plus the two sides separately (decode-only and
    compute-only) so the binding side and the overlap are explicit —
    the reference's iter_image_recordio_2.cc + train pipeline, measured
    (reference tests/nightly/test_all.sh gates through this stack)."""
    import shutil
    import subprocess
    import tempfile

    from PIL import Image

    import mxnet_tpu as mx
    from mxnet_tpu.models.resnet import resnet

    tmp = tempfile.mkdtemp(prefix="benchrec_")
    try:
        _bench_input_pipeline(tmp, n_images, image, batch, epochs)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _bench_input_pipeline(tmp, n_images, image, batch, epochs):
    import subprocess

    from PIL import Image

    import mxnet_tpu as mx
    from mxnet_tpu.models.resnet import resnet

    rng = np.random.RandomState(0)
    for label in range(8):
        d = os.path.join(tmp, "c%d" % label)
        os.makedirs(d)
        for i in range(n_images // 8):
            img = rng.randint(0, 255, (256, 256, 3), dtype=np.uint8)
            Image.fromarray(img).save(
                os.path.join(d, "i%04d.jpg" % i), "JPEG", quality=90)
    prefix = os.path.join(tmp, "bench")
    subprocess.run([sys.executable,
                    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "im2rec.py"), prefix, tmp],
                   check=True, capture_output=True, timeout=600)

    def make_iter():
        return mx.io.ImageRecordIter(
            path_imgrec=prefix + ".rec", data_shape=(image, image, 3),
            batch_size=batch, shuffle=True, rand_crop=True, rand_mirror=True,
            scale=1.0 / 255, preprocess_threads=int(os.environ.get(
                "MXNET_CPU_WORKER_NTHREADS", os.cpu_count() or 1)),
            prefetch_buffer=4)

    # decode-only rate (iterator drained, nothing consumed on device)
    it = make_iter()
    n = 0
    for b in it:  # warm one epoch: page cache + thread pool spin-up
        n += batch
    t0 = time.time()
    it.reset()
    for b in it:
        pass
    d_rate = n / (time.time() - t0)

    # e2e: fit on the chip, timing the steady-state epoch
    net = resnet(18, num_classes=8, image_shape=(image, image, 3),
                 layout="NHWC")
    mod = mx.mod.Module(net, context=mx.tpu(), compute_dtype="bfloat16")
    it = make_iter()
    times = []
    mod.fit(it, num_epoch=epochs, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            initializer=mx.init.Xavier(rnd_type="gaussian",
                                       factor_type="in", magnitude=2),
            epoch_end_callback=lambda *a: times.append(time.time()),
            batch_end_callback=None)
    e2e_rate = n / (times[-1] - times[-2])

    # compute-only rate for the same graph (device-resident batch)
    b0 = mx.io.DataBatch(
        data=[mx.nd.array(rng.randn(batch, image, image, 3)
                          .astype("float32"))],
        label=[mx.nd.array(rng.randint(0, 8, batch).astype("float32"))])
    for _ in range(3):
        mod.forward_backward(b0)
        mod.update()
    w = mod._exec_group.execs[0].arg_dict["fc1_weight"].data
    np.asarray(w[(0,) * w.ndim])
    t0 = time.time()
    for _ in range(20):
        mod.forward_backward(b0)
        mod.update()
    w = mod._exec_group.execs[0].arg_dict["fc1_weight"].data
    np.asarray(w[(0,) * w.ndim])
    c_rate = 20 * batch / (time.time() - t0)

    # host->device transfer rate for one batch (jax.device_put + a
    # one-element read back)
    import jax

    xb = rng.randn(batch, image, image, 3).astype("float32")
    a = jax.device_put(xb)
    np.asarray(a.reshape(-1)[0])
    t0 = time.time()
    for _ in range(3):
        a = jax.device_put(xb)
        np.asarray(a.reshape(-1)[0])
    x_rate = 3 * batch / (time.time() - t0)

    floor = min(d_rate, c_rate, x_rate)
    bound = {d_rate: "host-decode", c_rate: "chip",
             x_rate: "host->device transfer"}[floor]
    _row("Input pipeline JPEG->rec->fit img/s", e2e_rate, "img/s", None,
         "ResNet-18 %dpx NHWC bf16 train via ImageRecordIter (native "
         "decode, %s threads, prefetch 4); decode-only %.0f img/s, "
         "compute-only %.0f img/s, host->device transfer %.0f img/s -> "
         "%s-bound; e2e/bound=%.2f (>=1 means the other stages fully "
         "overlap the binding one); decode scales with host cores (this "
         "host: %d)"
         % (image, os.environ.get("MXNET_CPU_WORKER_NTHREADS",
                                  os.cpu_count() or 1),
            d_rate, c_rate, x_rate, bound, e2e_rate / floor,
            os.cpu_count() or 1))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--out", default="BENCH_TABLE.json")
    p.add_argument("--only", default=None, help="substring filter")
    args = p.parse_args()

    from mxnet_tpu.models.alexnet import get_alexnet
    from mxnet_tpu.models.inception_v3 import get_inception_v3
    from mxnet_tpu.models.resnet import resnet

    # MFU notes: measured per-stage device-trace attribution
    # (tools/mfu_decompose.py, round-5 audit) — see README "Per-model MFU"
    jobs = [
        ("inference resnet-50", lambda: bench_inference(
            "ResNet-50", lambda: resnet(50), (3, 224, 224), 713.17,
            note="resolution mix — 34% of device time is the 3-block "
                 "56x56/C=64 stage (~25% stage MFU: 64-wide channels fill "
                 "half the 128-lane MXU on both contraction and output) "
                 "plus stem conv C_in=3 at ~12%; the 14x14/C=1024 blocks "
                 "run near peak")),
        ("inference resnet-152", lambda: bench_inference(
            "ResNet-152", lambda: resnet(152), (3, 224, 224), 294.17,
            note="its 30 extra blocks over RN-50 are all 14x14/C=1024 "
                 "near-peak stages (53% of device time), diluting the "
                 "same fixed stem/56x56 cost RN-50 pays")),
        ("inference inception-v3", lambda: bench_inference(
            "Inception-v3", get_inception_v3, (3, 299, 299), 493.72,
            note="stem-bound — 46% of device time is the 147x147/71x71 "
                 "C=32..192 stem convs (tiny channel counts at huge "
                 "resolution), a structural property of the v3 stem")),
        ("inference alexnet", lambda: bench_inference(
            "AlexNet", get_alexnet, (3, 224, 224), 4883.77,
            note="was LRN-bound (53% of device time in cross-channel "
                 "reduce_window, now 5 shifted adds — round-5 fix "
                 "halved device time); remainder is 54x54/C=96 convs "
                 "and the grouped-conv split")),
        ("training resnet-50 b32", lambda: bench_train(
            "ResNet-50 (batch 32)", lambda: resnet(50), (3, 224, 224),
            181.53,
            note="same 56x56/C=64 + stem fractions as inference, plus "
                 "exact-BN backward reductions (README Roofline item 6: "
                 "frozen-BN +17.9%)")),
        ("training inception-v3 b32", lambda: bench_train(
            "Inception-v3 (batch 32)", get_inception_v3, (3, 299, 299),
            129.98,
            note="fragmentation — 27% of device time is small-kernel "
                 "weight-grad convs (f32 [C,C,3,3] outputs, C<=384) and "
                 "~40% per-branch BN/bias backward reductions at "
                 "C=32..192: hundreds of tiny ops that underfill the "
                 "MXU, vs ResNet's uniform large blocks")),
        ("lstm ptb", bench_lstm_ptb),
        ("lstm large", bench_lstm_large),
        ("ssd", bench_ssd),
        ("input pipeline", bench_input_pipeline),
    ]
    for name, fn in jobs:
        if args.only and args.only not in name:
            continue
        try:
            fn()
        except Exception as e:  # keep the table going; record the failure
            ROWS.append({"metric": name, "error": "%s: %s" % (type(e).__name__, e)})
            print(json.dumps(ROWS[-1]), flush=True)
    with open(args.out, "w") as f:
        json.dump(ROWS, f, indent=1)


if __name__ == "__main__":
    main()
