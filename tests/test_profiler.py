"""Profiler produces a non-empty chrome trace for the real training path
(round-1 review: record_span had zero call sites — dump was always empty)."""
import json
import os

import numpy as np

import mxnet_tpu as mx
import mxnet_tpu.io as mio
from mxnet_tpu import profiler


def test_profile_training_path(tmp_path):
    fname = str(tmp_path / "profile.json")
    profiler.profiler_set_config(mode="all", filename=fname)

    rng = np.random.RandomState(0)
    X = rng.rand(64, 10).astype(np.float32)
    y = rng.randint(0, 3, 64).astype(np.float32)
    it = mio.NDArrayIter(X, y, batch_size=32)
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=3),
        name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    mod.init_optimizer(optimizer="sgd")

    profiler.profiler_set_state("run")
    for batch in it:
        mod.forward(batch, is_train=True)
        mod.backward()
        mod.update()
    mod.forward(batch, is_train=False)
    mod.get_outputs()[0].asnumpy()
    profiler.profiler_set_state("stop")
    profiler.dump_profile()

    with open(fname) as f:
        trace = json.load(f)
    events = trace["traceEvents"]
    assert len(events) > 0
    names = {e["name"] for e in events}
    # the fused single-dispatch step and the eval forward both show up
    assert "fit.dispatch" in names, names
    assert "executor.forward" in names, names
    # spans have sane timing fields (metadata "M" and telemetry counter
    # "C" rows ride alongside the span lanes)
    spans = [e for e in events if e["ph"] == "X"]
    assert spans
    for e in spans:
        assert e["dur"] >= 0
    assert os.path.exists(fname)
    # pid naming metadata: chrome shows "host" / "device (XLA)" lanes
    # instead of bare pids 0/1, and span-recording threads are labeled
    meta = {(e["name"], e["pid"]): e["args"] for e in events
            if e["ph"] == "M"}
    assert meta[("process_name", 0)]["name"] == "host"
    assert meta[("process_name", 1)]["name"] == "device (XLA)"
    span_tids = {e["tid"] for e in spans if e["pid"] == 0}
    named_tids = {e["tid"] for e in events
                  if e["ph"] == "M" and e["name"] == "thread_name"}
    assert span_tids & named_tids


def test_xla_mode_emits_per_op_rows(tmp_path):
    """Per-op rows through the fused step (reference profiler.cc:134-190
    per-op dump).  On TPU rows carry graph-node names via named_scope
    (verified on-chip: jit(step)/jvp(stage1_unit1_conv1)/...); XLA:CPU
    traces expose per-HLO thunk events, which must still be joined."""
    import json

    import numpy as np

    fn = str(tmp_path / "prof.json")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Activation(mx.sym.FullyConnected(mx.sym.Variable("data"),
        num_hidden=64, name="fc1"), act_type="relu", name="relu1"),
        num_hidden=8, name="fc2"), name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (16, 32))],
             label_shapes=[("softmax_label", (16,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd", optimizer_params={"learning_rate": 0.1})
    b = mx.io.DataBatch(
        data=[mx.nd.array(np.random.randn(16, 32).astype("f4"))],
        label=[mx.nd.array(np.random.randint(0, 8, 16).astype("f4"))])
    mod.forward_backward(b)
    mod.update()  # compile outside the trace
    profiler.profiler_set_config(mode="xla", filename=fn)
    profiler.profiler_set_state("run")
    for _ in range(3):
        mod.forward_backward(b)
        mod.update()
    np.asarray(mod._exec_group.execs[0].arg_dict["fc1_weight"].data[0, 0])
    profiler.profiler_set_state("stop")
    profiler.dump_profile()
    d = json.load(open(fn))
    ops = [e for e in d["traceEvents"] if e.get("cat") == "xla_op"]
    assert len(ops) >= 3, "no per-op rows joined from the XLA trace"
    assert any("dot" in e["name"] or "fusion" in e["name"] or "convert" in e["name"]
               for e in ops), [e["name"] for e in ops][:10]
