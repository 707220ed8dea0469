#!/usr/bin/env python
"""Per-category device-time decomposition of benchmark_score rows.

Answers "where does each model's MFU go": compiles the exact same
scan-program a benchmark row times (tools/benchmark_score.py), runs it
under `jax.profiler.trace`, and buckets TPU device events into op
categories (MXU convs/dots, reductions, pool backward, copies/converts,
other fusions).  Prints one ms/step table per requested row — the same
methodology the round-3 roofline audit used for ResNet-50 train
(README "Roofline" item 4), extended to every row.

Usage:  python tools/mfu_decompose.py [row ...]
  rows: inf-resnet50 inf-resnet152 inf-inception inf-alexnet
        train-resnet50 train-inception lstm [default: the MFU outliers]
"""
import argparse
import glob
import gzip
import json
import os
import re
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from tpu_constants import V5E_PEAK_FLOPS  # noqa: E402

# event-name -> category, first match wins (names are XLA fusion/op
# names as they appear in the device trace)
CATEGORIES = [
    ("conv", re.compile(r"conv|dot|gemm", re.I)),
    ("reduce", re.compile(r"reduce", re.I)),
    ("pool_bwd", re.compile(r"select_and_scatter|select-and-scatter", re.I)),
    ("scatter_gather", re.compile(r"scatter|gather|dynamic", re.I)),
    ("copy_convert", re.compile(r"copy|convert|transpose|bitcast", re.I)),
]

# container spans that PARENT the op events (whole program, scan loop) —
# counting them would double every child
CONTAINERS = re.compile(r"^jit_|^while|^condition|^body|^tuple|^parameter",
                        re.I)


def _bucket(name):
    for cat, rx in CATEGORIES:
        if rx.search(name):
            return cat
    return "other_fusion"


def _device_events(trace_dir):
    """All complete ('ph':'X') events from device-side tracks."""
    files = glob.glob(trace_dir + "/**/*.trace.json.gz", recursive=True)
    events, pids = [], {}
    for f in files:
        with gzip.open(f, "rt") as fh:
            data = json.load(fh)
        for ev in data.get("traceEvents", []):
            if ev.get("ph") == "M" and ev.get("name") == "process_name":
                pids[ev.get("pid")] = ev.get("args", {}).get("name", "")
            elif ev.get("ph") == "X":
                events.append(ev)
    dev_pids = {p for p, n in pids.items()
                if "TPU" in n or "/device" in n.lower() or "xla" in n.lower()}
    return [e for e in events if e.get("pid") in dev_pids], pids


def _explain_fusion(hlo_text, fusion_name):
    """One line: what this fusion computes (def shape + body op mix)."""
    m = re.search(r"%%?%s = (\S+)[^\n]*?calls=%%?([\w.\-]+)"
                  % re.escape(fusion_name), hlo_text)
    if not m:
        m2 = re.search(r"%%?%s = (\S+)" % re.escape(fusion_name), hlo_text)
        return m2.group(1) if m2 else "?"
    shape, comp = m.group(1), m.group(2)
    body = re.search(r"%%?%s [^\{]*\{(.*?)\n\}" % re.escape(comp),
                     hlo_text, re.S)
    ops = {}
    if body:
        for op in re.findall(r"= \S+ ([\w\-]+)\(", body.group(1)):
            if op not in ("parameter", "constant", "tuple",
                          "get-tuple-element"):
                ops[op] = ops.get(op, 0) + 1
    mix = ",".join("%s x%d" % kv for kv in
                   sorted(ops.items(), key=lambda kv: -kv[1])[:4])
    return "%s  [%s]" % (shape, mix)


def decompose(compiled_call, steps, label, total_flops_per_step,
              hlo_text=None):
    """Run `compiled_call` `steps` times under the profiler; print the
    per-category device-ms table normalized per step."""
    import jax

    tmp = tempfile.mkdtemp(prefix="mfu_decomp_")
    with jax.profiler.trace(tmp):
        for _ in range(steps):
            compiled_call()
    events, pids = _device_events(tmp)
    if not events:  # fall back: any pid with XLA-looking op names
        allev, pids = [], {}
        for f in glob.glob(tmp + "/**/*.trace.json.gz", recursive=True):
            with gzip.open(f, "rt") as fh:
                data = json.load(fh)
            allev += [e for e in data.get("traceEvents", [])
                      if e.get("ph") == "X"]
        events = [e for e in allev
                  if re.search(r"fusion|conv|reduce|copy|while",
                               e.get("name", ""))]
    cats, names = {}, {}
    total = 0.0
    for ev in events:
        if CONTAINERS.search(ev.get("name", "")):
            continue
        dur = float(ev.get("dur", 0.0)) / 1000.0  # us -> ms
        cat = _bucket(ev.get("name", ""))
        cats[cat] = cats.get(cat, 0.0) + dur
        key = (cat, ev.get("name", "")[:60])
        names[key] = names.get(key, 0.0) + dur
        total += dur
    per_step = {k: v / steps for k, v in cats.items()}
    step_ms = total / steps
    mfu = (total_flops_per_step / (step_ms / 1e3) / V5E_PEAK_FLOPS
           if step_ms else 0.0)
    print("\n== %s ==  device %.2f ms/step, device-time MFU %.1f%%"
          % (label, step_ms, 100 * mfu))
    for cat, ms in sorted(per_step.items(), key=lambda kv: -kv[1]):
        print("  %-16s %8.3f ms  %5.1f%%" % (cat, ms,
                                             100 * ms / step_ms))
    top = sorted(names.items(), key=lambda kv: -kv[1])[:10]
    print("  top ops:")
    for (cat, nm), ms in top:
        detail = ""
        if hlo_text and ("fusion" in nm or "convolution" in nm):
            detail = "  <- " + _explain_fusion(hlo_text, nm)
        print("    %-14s %7.3f ms  %s%s" % (cat, ms / steps, nm, detail))
    stages = None
    if hlo_text:
        # bucket device time by the producing op's output SPATIAL
        # resolution (from its HLO result shape) — the per-stage view
        # that explains resolution-mix MFU differences between models
        stages = {}
        for (cat, nm), ms in names.items():
            m = re.search(r"%%?%s = (?:\(?)(\w+)\[([\d,]+)\]"
                          % re.escape(nm.split(" ")[0]), hlo_text)
            key = "no-shape"
            if m:
                dims = [int(d) for d in m.group(2).split(",")]
                spatial = [d for d in dims[1:] if d > 1]
                key = "x".join(str(d) for d in sorted(dims, reverse=True)[:2])
                # prefer HxW-looking pair when 4D
                if len(dims) == 4:
                    hs = sorted(dims[2:] if dims[1] <= dims[2] else
                                dims[1:3])
                    key = "%dx%d" % (max(dims[2], dims[3]),
                                     max(dims[2], dims[3])) \
                        if dims[2] == dims[3] else "%dx%d" % (dims[2],
                                                              dims[3])
            stages[key] = stages.get(key, 0.0) + ms / steps
        print("  by output resolution:")
        for key, ms in sorted(stages.items(), key=lambda kv: -kv[1])[:10]:
            print("    %-12s %8.3f ms  %5.1f%%" % (key, ms,
                                                   100 * ms / step_ms))
    return {"label": label, "device_ms_per_step": step_ms,
            "per_category_ms": per_step,
            "device_time_mfu": mfu, "by_resolution": stages,
            "top_ops": [{"cat": c, "name": n, "ms": ms / steps}
                        for (c, n), ms in top]}


def _build_row(row):
    """Compile the exact scan program a bench row times; return
    (call, flops_per_step, label)."""
    import jax

    import benchmark_score as bs
    from mxnet_tpu.models.alexnet import get_alexnet
    from mxnet_tpu.models.inception_v3 import get_inception_v3
    from mxnet_tpu.models.resnet import resnet

    rng = np.random.RandomState(0)

    def inference(name, sym_fn, shape, batch=32, k=16):
        net = sym_fn()
        mod = bs._bind_module(net, (batch,) + shape, for_training=False)
        stack = bs._stack(rng, k, (batch,) + shape)
        compiled, args, aux = bs._scan_forward(mod, stack)
        flops = bs._flops(compiled, trip_count=k) / k
        return (lambda: compiled(args, aux, stack).block_until_ready(),
                flops, "inference %s batch %d (k=%d)" % (name, batch, k),
                compiled)

    def train(name, sym_fn, shape, batch=32, k=8):
        net = sym_fn()
        mod = bs._bind_module(net, (batch,) + shape,
                              label_shape=(batch,), for_training=True)
        xs = bs._stack(rng, k, (batch,) + shape)
        ys = bs._stack(rng, k, (batch,), hi=10)
        compiled, state = bs._scan_train(mod, xs, ys)
        flops = bs._flops(compiled, trip_count=k) / k
        st = {"v": state}

        def call():
            # donated buffers: thread the returned state back in, fence
            out = compiled(*st["v"], xs, ys, np.uint32(0))
            st["v"] = out[:3]
            jax.block_until_ready(out)
        return (call, flops, "train %s batch %d (k=%d)" % (name, batch, k),
                compiled)

    def lstm(label, vocab, embed, hidden, layers, seq, batch, k=8):
        import mxnet_tpu as mx
        cell = mx.rnn.FusedRNNCell(hidden, num_layers=layers, mode="lstm",
                                   prefix="lstm_")
        data = mx.sym.Variable("data")
        lab_v = mx.sym.Variable("softmax_label")
        emb = mx.sym.Embedding(data, input_dim=vocab, output_dim=embed,
                               name="embed")
        output, _ = cell.unroll(seq, inputs=emb, layout="NTC",
                                merge_outputs=True)
        pred = mx.sym.Reshape(output, shape=(-1, hidden))
        pred = mx.sym.FullyConnected(pred, num_hidden=vocab, name="pred")
        lab = mx.sym.Reshape(lab_v, shape=(-1,))
        net = mx.sym.SoftmaxOutput(pred, lab, name="softmax")
        mod = bs._bind_module(net, (batch, seq), (batch, seq))
        xs = bs._stack(rng, k, (batch, seq), hi=vocab)
        ys = bs._stack(rng, k, (batch, seq), hi=vocab)
        compiled, state = bs._scan_train(mod, xs, ys, lr=0.1, momentum=0.0)
        flops = bs._flops(compiled, trip_count=k) / k
        st = {"v": state}

        def call():
            out = compiled(*st["v"], xs, ys, np.uint32(0))
            st["v"] = out[:3]
            np.asarray(out[0][0].reshape(-1)[0])
        return call, flops, label, compiled

    if row == "lstm":
        return lstm("train lstm-ptb 2x200 b32", 10000, 200, 200, 2, 35, 32)
    if row == "lstm-large":
        return lstm("train lstm 4x1024 b128", 10000, 1024, 1024, 4, 35, 128)

    # EXACT model constructors + shapes the bench rows use (main())
    hw = (3, 224, 224)
    if row == "inf-resnet50":
        return inference("resnet50", lambda: resnet(50), hw)
    if row == "inf-resnet152":
        return inference("resnet152", lambda: resnet(152), hw)
    if row == "inf-inception":
        return inference("inception-v3", get_inception_v3, (3, 299, 299))
    if row == "inf-alexnet":
        return inference("alexnet", get_alexnet, hw)
    if row == "train-resnet50":
        return train("resnet50", lambda: resnet(50), hw)
    if row == "train-inception":
        return train("inception-v3", get_inception_v3, (3, 299, 299))
    raise SystemExit("unknown row %r" % row)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("rows", nargs="*",
                   default=["inf-resnet50", "inf-resnet152",
                            "train-inception"])
    p.add_argument("--steps", type=int, default=3)
    p.add_argument("--out", default=None)
    args = p.parse_args()
    results = []
    for row in args.rows:
        call, flops, label, compiled = _build_row(row)
        try:
            hlo = compiled.as_text()
        except Exception:
            hlo = None
        call()  # warm the executable before tracing
        results.append(decompose(call, args.steps, label, flops,
                                 hlo_text=hlo))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2)


if __name__ == "__main__":
    main()
