"""Mixed precision: infer_type propagation + bf16 compute with fp32 masters.

Reference analogs: tests/python/train/test_dtype.py (fp16 training) and the
multi-precision SGD path (reference python/mxnet/optimizer.py:311+).
"""
import numpy as np

import mxnet_tpu as mx
import mxnet_tpu.io as mio


def _mlp():
    x = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(x, num_hidden=32, name="fc1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=3, name="fc2")
    return mx.sym.SoftmaxOutput(h, name="softmax")


def test_infer_type_propagation():
    net = _mlp()
    arg_types, out_types, _ = net.infer_type(data="float16")
    types = dict(zip(net.list_arguments(), arg_types))
    assert str(types["fc1_weight"]) == "float16"
    assert str(types["fc2_bias"]) == "float16"
    assert str(out_types[0]) == "float16"
    # Cast overrides propagation
    c = mx.sym.Cast(mx.sym.Variable("x"), dtype="float64")
    _, ot, _ = c.infer_type(x="float32")
    assert str(ot[0]) == "float64"


def test_simple_bind_type_dict():
    net = _mlp()
    ex = net.simple_bind(mx.cpu(), data=(4, 10), type_dict={"data": "float16"})
    assert all(str(a.dtype) == "float16" for a in ex.arg_dict.values())
    ex.forward(is_train=False, data=mx.nd.array(
        np.zeros((4, 10), np.float16)))
    assert str(ex.outputs[0].dtype) == "float16"


def test_bf16_compute_trains_with_fp32_masters():
    rng = np.random.RandomState(0)
    X = rng.randn(256, 10).astype("float32")
    y = np.argmax(X @ rng.randn(10, 3), 1).astype("float32")
    it = mio.NDArrayIter(X, y, batch_size=32, shuffle=True)
    mod = mx.mod.Module(_mlp(), context=mx.cpu(), compute_dtype="bfloat16")
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    for _ in range(8):
        it.reset()
        for batch in it:
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()
    params, _ = mod.get_params()
    # master weights stay fp32 (multi-precision recipe)
    assert all(str(v.dtype) == "float32" for v in params.values())
    acc = mod.score(mio.NDArrayIter(X, y, batch_size=32), "acc")[0][1]
    assert acc > 0.9, acc


def test_bf16_keeps_index_args_fp32():
    # review finding: token ids > 256 are not bf16-exact; args feeding
    # index slots (Embedding data etc.) must stay fp32 under compute_dtype
    V, E = 2000, 8
    data = mx.sym.Variable("data")
    net = mx.sym.Embedding(data, input_dim=V, output_dim=E, name="emb")
    ex = mx.executor.Executor.simple_bind(net, mx.cpu(), grad_req="null",
                                          compute_dtype="bfloat16",
                                          data=(4,))
    assert "data" in ex._fp32_names
    ids = np.array([0, 257, 1001, 1999], np.float32)  # not bf16-exact
    w = np.random.RandomState(0).randn(V, E).astype(np.float32)
    ex.arg_dict["data"][:] = ids
    ex.arg_dict["emb_weight"][:] = w
    ex.forward(is_train=False)
    # rows must come from the EXACT ids (a bf16 cast would fetch 1000/1002)
    exp = w[ids.astype(int)]
    got = ex.outputs[0].asnumpy()
    np.testing.assert_allclose(got, exp, rtol=1e-2, atol=1e-2)  # bf16 values
    # and specifically row identity, not just proximity
    for r in range(4):
        best = np.argmin(np.abs(w - got[r]).sum(axis=1))
        assert best == int(ids[r]), (r, best, ids[r])


def test_bf16_outputs_are_fp32_and_close_to_fp32_run():
    rng = np.random.RandomState(1)
    X = rng.randn(8, 10).astype("float32")

    def run(cd):
        mx.random.seed(3)
        net = _mlp()
        mod = mx.mod.Module(net, context=mx.cpu(), compute_dtype=cd)
        mod.bind(data_shapes=[("data", (8, 10))], for_training=False,
                 label_shapes=None)
        mod.init_params(mx.init.Xavier(), force_init=True)
        mod.forward(mio.DataBatch(data=[mx.nd.array(X)], label=None),
                    is_train=False)
        return mod.get_outputs()[0].asnumpy()

    ref = run(None)
    bf = run("bfloat16")
    assert bf.dtype == np.float32  # outputs cast back on exit
    np.testing.assert_allclose(bf, ref, atol=0.05)


def test_bf16_survives_reshape():
    # round-2 review: Executor.reshape rebuilt without compute_dtype —
    # any reshape after Module(compute_dtype=...) silently reverted to fp32
    net = _mlp()
    ex = mx.executor.Executor.simple_bind(
        net, mx.cpu(), grad_req="write", compute_dtype="bfloat16",
        data=(4, 10), softmax_label=(4,))
    ex2 = ex.reshape(data=(8, 10), softmax_label=(8,))
    assert ex2._compute_dtype == ex._compute_dtype
    assert ex2._fp32_names == ex._fp32_names


def test_bind_accepts_compute_dtype():
    net = _mlp()
    args = {n: mx.nd.zeros(s) for n, s in zip(
        net.list_arguments(),
        net.infer_shape(data=(4, 10), softmax_label=(4,))[0])}
    ex = mx.executor.Executor.bind(net, mx.cpu(), args, args_grad=None,
                                   compute_dtype="bfloat16")
    assert ex._compute_dtype is not None


def test_bf16_index_protection_is_transitive():
    # an index routed through an intermediate op (slice before take) must
    # also keep its source variable fp32
    idx = mx.sym.Variable("idx")
    src = mx.sym.Variable("src")
    sliced = mx.sym.slice(idx, begin=(0,), end=(2,))
    net = mx.sym.take(src, sliced)
    ex = mx.executor.Executor.simple_bind(net, mx.cpu(), grad_req="null",
                                          compute_dtype="bfloat16",
                                          src=(2000, 4), idx=(4,))
    assert "idx" in ex._fp32_names
    w = np.random.RandomState(0).randn(2000, 4).astype(np.float32)
    ex.arg_dict["src"][:] = w
    ex.arg_dict["idx"][:] = np.array([1001, 1999, 3, 5], np.float32)
    ex.forward(is_train=False)
    got = ex.outputs[0].asnumpy()
    exp = w[[1001, 1999]]
    np.testing.assert_allclose(got, exp, rtol=1e-2, atol=1e-2)


def test_bf16_keeps_bn_aux_fp32():
    # advisor finding: casting BN moving stats to bf16 on entry re-quantizes
    # the carried fp32 statistics every step; they must stay fp32
    x = mx.sym.Variable("data")
    net = mx.sym.BatchNorm(x, name="bn", fix_gamma=False, momentum=0.9)
    ex = mx.executor.Executor.simple_bind(net, mx.cpu(), grad_req="null",
                                          compute_dtype="bfloat16",
                                          data=(8, 4))
    # a moving mean NOT representable in bf16 (needs >8 mantissa bits);
    # zero data => batch mean 0, so new_mm = momentum * mm EXACTLY
    mm = np.full((4,), 1.0 + 2 ** -12, np.float32)
    ex.aux_dict["bn_moving_mean"][:] = mm
    ex.arg_dict["data"][:] = np.zeros((8, 4), np.float32)
    ex.forward(is_train=True)
    _ = ex.outputs[0].asnumpy()
    new_mm = ex.aux_dict["bn_moving_mean"].asnumpy()
    assert str(new_mm.dtype) == "float32"
    # old bf16 round-trip collapsed 1+2^-12 to 1.0 (error ~2.2e-4)
    np.testing.assert_allclose(new_mm, 0.9 * mm, rtol=0, atol=1e-6)
