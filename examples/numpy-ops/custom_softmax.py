#!/usr/bin/env python
"""Custom numpy-implemented operator (reference example/numpy-ops/
custom_softmax.py): a Softmax written against the CustomOp host API,
trained end-to-end inside an otherwise-compiled graph.

The op's forward/backward run as host callbacks around the XLA program —
where the reference ran numpy ops outside its engine."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np


def main():
    import mxnet_tpu as mx

    class Softmax(mx.operator.CustomOp):
        def forward(self, is_train, req, in_data, out_data, aux):
            x = in_data[0].asnumpy()
            e = np.exp(x - x.max(axis=1, keepdims=True))
            self.assign(out_data[0], req[0],
                        mx.nd.array(e / e.sum(axis=1, keepdims=True)))

        def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
            label = in_data[1].asnumpy().ravel().astype(np.int64)
            y = out_data[0].asnumpy().copy()
            y[np.arange(label.shape[0]), label] -= 1.0
            self.assign(in_grad[0], req[0], mx.nd.array(y))

    @mx.operator.register("custom_softmax_demo")
    class SoftmaxProp(mx.operator.CustomOpProp):
        def __init__(self):
            super().__init__(need_top_grad=False)

        def list_arguments(self):
            return ["data", "label"]

        def list_outputs(self):
            return ["output"]

        def infer_shape(self, in_shape):
            data_shape = in_shape[0]
            label_shape = (in_shape[0][0],)
            return [data_shape, label_shape], [data_shape], []

        def create_operator(self, ctx, shapes, dtypes):
            return Softmax()

    rng = np.random.RandomState(0)
    X = rng.randn(256, 8).astype(np.float32)
    y = (X @ rng.randn(8, 3)).argmax(1).astype(np.float32)

    data = mx.sym.Variable("data")
    label = mx.sym.Variable("softmax_label")
    fc = mx.sym.FullyConnected(data, num_hidden=3, name="fc")
    net = mx.sym.Custom(fc, label, op_type="custom_softmax_demo",
                        name="softmax")

    # numpy op bodies are HOST code run through a host callback; this
    # demo pins the CPU so it runs anywhere — on a TPU host, mx.tpu()
    # works too.
    mod = mx.mod.Module(net, context=mx.cpu())
    it = mx.io.NDArrayIter(X, y, batch_size=32, shuffle=True)
    mod.fit(it, num_epoch=8, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5})
    score = mod.score(mx.io.NDArrayIter(X, y, batch_size=32), "acc")
    print("accuracy with numpy-implemented softmax:", score)
    assert score[0][1] > 0.9
    print("custom numpy op OK")


if __name__ == "__main__":
    main()
