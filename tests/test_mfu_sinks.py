"""Numerics-parity pins for the four attributed MFU sinks (docs/perf.md
"MFU sinks", README Roofline item 8): every toggle must be off-by-default
safe, and ON must either be exact (s2d fold, frozen-BN stat carrying,
LSTM batch growth) or within declared tolerance (bf16 weight grads)."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx


@pytest.fixture
def clean_knobs():
    """Snapshot/restore the sink env knobs around a test."""
    names = ("MXNET_TPU_S2D_STEM", "MXTPU_BF16_WGRAD")
    prior = {n: os.environ.get(n) for n in names}
    yield
    for n, v in prior.items():
        if v is None:
            os.environ.pop(n, None)
        else:
            os.environ[n] = v


# ----------------------------------------------------------------------
# (a) generalized space-to-depth stem rewrite
# ----------------------------------------------------------------------


def _conv_fwd_bwd(layout, kernel, stride, pad, dshape):
    rng = np.random.RandomState(0)
    nf = 8
    if layout == "NCHW":
        wshape = (nf, dshape[1]) + kernel
    else:
        wshape = kernel + (dshape[3], nf)
    x = mx.sym.Variable("data")
    c = mx.sym.Convolution(x, num_filter=nf, kernel=kernel, stride=stride,
                           pad=pad, no_bias=True, layout=layout, name="stem")
    loss = mx.sym.MakeLoss(mx.sym.sum(c * c))
    gx = mx.nd.zeros(dshape)
    gw = mx.nd.zeros(wshape)
    exe = loss.bind(
        mx.cpu(),
        {"data": mx.nd.array(rng.randn(*dshape).astype(np.float32)),
         "stem_weight": mx.nd.array(
             (rng.randn(*wshape) * 0.1).astype(np.float32))},
        args_grad={"data": gx, "stem_weight": gw},
        grad_req={"data": "write", "stem_weight": "write"})
    exe.forward(is_train=True)
    out = exe.outputs[0].asnumpy().copy()
    exe.backward()
    return out, gx.asnumpy().copy(), gw.asnumpy().copy()


@pytest.mark.parametrize("layout,kernel,pad,hw", [
    # the Inception-v3 stem shape family: odd input, no pad
    ("NCHW", (3, 3), (0, 0), (29, 29)),
    ("NHWC", (3, 3), (0, 0), (29, 29)),
    ("NCHW", (5, 5), (2, 2), (17, 16)),   # mixed odd/even input
    ("NHWC", (4, 4), (1, 1), (15, 17)),   # even kernel
])
def test_s2d_generalized_fold_exact(clean_knobs, layout, kernel, pad, hw):
    """The parameterized fold (any 2-D stride-2 conv, odd inputs padded)
    reproduces the direct conv exactly — forward and both grads.  The
    classic 7x7/s2/p3 even-input case stays pinned in test_operator.py."""
    h, w = hw
    dshape = (2, 3, h, w) if layout == "NCHW" else (2, h, w, 3)
    os.environ["MXNET_TPU_S2D_STEM"] = "0"
    o0, gx0, gw0 = _conv_fwd_bwd(layout, kernel, (2, 2), pad, dshape)
    os.environ["MXNET_TPU_S2D_STEM"] = "1"
    o1, gx1, gw1 = _conv_fwd_bwd(layout, kernel, (2, 2), pad, dshape)
    np.testing.assert_allclose(o1, o0, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(gx1, gx0, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(gw1, gw0, rtol=2e-4, atol=2e-4)


def test_s2d_unsupported_configs_raise():
    """space_to_depth_stem errors CLEARLY on shapes the fold cannot
    express (the old helper silently claimed 7x7-only generality —
    config.py and the docstring now match the code)."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.nn import space_to_depth_stem

    x = jnp.zeros((1, 3, 8, 8))
    w = jnp.zeros((4, 3, 3, 3))
    with pytest.raises(ValueError, match="stride"):
        space_to_depth_stem(x, w, (3, 3), (1, 1), (0, 0))
    with pytest.raises(ValueError, match="dilation"):
        space_to_depth_stem(x, w, (3, 3), (2, 2), (0, 0), dilate=(2, 2))
    with pytest.raises(ValueError, match="grouped"):
        space_to_depth_stem(x, w, (3, 3), (2, 2), (0, 0), groups=3)
    with pytest.raises(ValueError, match="2-D"):
        space_to_depth_stem(x, w, (3,), (2,), (0,))


def test_s2d_inception_v3_forward_backward_parity(clean_knobs):
    """The tentpole pin: s2d stem vs direct stem on the REAL Inception-v3
    graph, forward+backward.  BN runs frozen (use_global_stats via
    symbol.freeze_batchnorm) so the comparison is conditioned — with
    batch statistics, ~95 BN layers chaotically amplify benign
    float-reordering deltas (~1e-6 at the stem) into percent-level
    output noise, which would pin nothing."""
    from mxnet_tpu.models.inception_v3 import get_inception_v3
    from mxnet_tpu.symbol import freeze_batchnorm

    def run(flag):
        os.environ["MXNET_TPU_S2D_STEM"] = "1" if flag else "0"
        rng = np.random.RandomState(0)
        net = freeze_batchnorm(get_inception_v3(num_classes=10))
        exe = net.simple_bind(mx.cpu(), data=(2, 3, 75, 75),
                              softmax_label=(2,))
        for name, arr in sorted(exe.arg_dict.items()):
            if name in ("data", "softmax_label"):
                continue
            arr[:] = mx.nd.array(
                (rng.randn(*arr.shape) * 0.05).astype(np.float32))
        for name, arr in sorted(exe.aux_dict.items()):
            arr[:] = mx.nd.array(
                np.ones(arr.shape, np.float32)
                if name.endswith("_moving_var")
                else np.zeros(arr.shape, np.float32))
        exe.forward(
            is_train=True,
            data=mx.nd.array(rng.randn(2, 3, 75, 75).astype(np.float32)),
            softmax_label=mx.nd.array(
                rng.randint(0, 10, 2).astype(np.float32)))
        exe.backward()
        out = exe.outputs[0].asnumpy().copy()
        grads = {k: exe.grad_dict[k].asnumpy().copy()
                 for k in ("conv_conv2d_weight", "conv_1_conv2d_weight",
                           "fc1_weight")}
        return out, grads

    o0, g0 = run(False)
    o1, g1 = run(True)
    np.testing.assert_allclose(o1, o0, rtol=1e-5, atol=1e-6)
    for k in g0:
        np.testing.assert_allclose(g1[k], g0[k], rtol=1e-4, atol=1e-5,
                                   err_msg=k)


# ----------------------------------------------------------------------
# (b) bf16 weight-grad accumulation
# ----------------------------------------------------------------------


def _convnet_grads(dshape):
    rng = np.random.RandomState(0)
    x = mx.sym.Variable("data")
    c1 = mx.sym.Convolution(x, num_filter=8, kernel=(3, 3), pad=(1, 1),
                            no_bias=True, name="c1")
    a1 = mx.sym.Activation(c1, act_type="relu")
    c2 = mx.sym.Convolution(a1, num_filter=8, kernel=(3, 3), pad=(1, 1),
                            no_bias=True, name="c2")
    loss = mx.sym.MakeLoss(mx.sym.sum(mx.sym.sin(c2)))
    exe = loss.simple_bind(mx.cpu(), data=dshape)
    for name, arr in sorted(exe.arg_dict.items()):
        if name != "data":
            arr[:] = mx.nd.array(
                (rng.randn(*arr.shape) * 0.1).astype(np.float32))
    exe.forward(is_train=True,
                data=mx.nd.array(rng.randn(*dshape).astype(np.float32)))
    out = exe.outputs[0].asnumpy().copy()
    exe.backward()
    return out, {k: v.asnumpy().copy() for k, v in exe.grad_dict.items()}


def test_bf16_wgrad_tolerance_bounds(clean_knobs):
    """MXTPU_BF16_WGRAD=1: forward values and the DATA grad (an exact
    path by construction) are unchanged; weight grads deviate, but stay
    inside bf16-accumulation bounds relative to the f32 grads."""
    from mxnet_tpu import telemetry

    dshape = (2, 4, 12, 12)
    os.environ["MXTPU_BF16_WGRAD"] = "0"
    o0, g0 = _convnet_grads(dshape)
    os.environ["MXTPU_BF16_WGRAD"] = "1"
    o1, g1 = _convnet_grads(dshape)
    np.testing.assert_array_equal(o1, o0)
    np.testing.assert_array_equal(g1["data"], g0["data"])
    for k in ("c1_weight", "c2_weight"):
        scale = np.max(np.abs(g0[k]))
        np.testing.assert_allclose(g1[k], g0[k], rtol=5e-2,
                                   atol=2e-2 * scale, err_msg=k)
        assert g1[k].dtype == np.float32  # master dtype preserved
    # the mode gauge was set at trace time (parse_log --telemetry column)
    assert telemetry.gauge_value("ops.wgrad_bf16") == 1


def test_bf16_wgrad_gate_skips_large_kernels(clean_knobs):
    """Kernels above the small-kernel bound keep exact f32 accumulation
    even with the flag on (bit-identical grads)."""
    def grads():
        rng = np.random.RandomState(0)
        x = mx.sym.Variable("data")
        c = mx.sym.Convolution(x, num_filter=4, kernel=(9, 9), pad=(4, 4),
                               no_bias=True, name="big")
        loss = mx.sym.MakeLoss(mx.sym.sum(c * c))
        exe = loss.simple_bind(mx.cpu(), data=(1, 2, 16, 16))
        exe.arg_dict["big_weight"][:] = mx.nd.array(
            (np.arange(4 * 2 * 81).reshape(4, 2, 9, 9) % 7 * 0.1)
            .astype(np.float32))
        exe.forward(is_train=True,
                    data=mx.nd.array(rng.randn(1, 2, 16, 16)
                                     .astype(np.float32)))
        exe.backward()
        return exe.grad_dict["big_weight"].asnumpy().copy()

    os.environ["MXTPU_BF16_WGRAD"] = "0"
    g0 = grads()
    os.environ["MXTPU_BF16_WGRAD"] = "1"
    g1 = grads()
    np.testing.assert_array_equal(g1, g0)


# ----------------------------------------------------------------------
# (c) batch-growth packed bucketing
# ----------------------------------------------------------------------


def _bucket_sentences(rng, count, low, high):
    return [[int(v) for v in rng.randint(2, 20, rng.randint(low, high))]
            for _ in range(count)]


def test_batch_growth_iter_shapes():
    """Short buckets emit grown batches; the default (longest) bucket —
    and therefore provide_data and the default-bucket executor — keeps
    the plain batch size."""
    from mxnet_tpu import rnn

    rng = np.random.RandomState(0)
    sents = ([[1] * 4 for _ in range(64)] + [[1] * 8 for _ in range(16)])
    it = rnn.BucketSentenceIter(sents, 4, buckets=[4, 8], invalid_label=0,
                                batch_growth=True)
    assert it.bucket_batch == [8, 4]  # growth 8//4=2 for the short bucket
    assert it.provide_data[0].shape == (4, 8)
    seen = {}
    for batch in it:
        seen.setdefault(batch.bucket_key, set()).add(batch.data[0].shape)
    assert seen[4] == {(8, 4)}
    assert seen[8] == {(4, 8)}
    # max_growth caps the multiplier
    it2 = rnn.BucketSentenceIter(sents, 4, buckets=[4, 8], invalid_label=0,
                                 batch_growth=True, max_growth=1)
    assert it2.bucket_batch == [4, 4]
    # off by default: unchanged behavior
    it3 = rnn.BucketSentenceIter(sents, 4, buckets=[4, 8], invalid_label=0)
    assert it3.bucket_batch == [4, 4]


def test_batch_growth_clamps_to_bucket_population():
    """A sparsely-populated short bucket must not be starved: growth is
    clamped to the number of full plain batches the bucket holds, so
    every sequence the unpacked iterator would emit is still emitted."""
    from mxnet_tpu import rnn

    # short bucket holds 6 sequences: unpacked (batch 4) emits one batch;
    # naive growth 2 would need 8 sequences and emit NOTHING
    sents = ([[1] * 4 for _ in range(6)] + [[1] * 8 for _ in range(8)])
    it = rnn.BucketSentenceIter(sents, 4, buckets=[4, 8], invalid_label=0,
                                batch_growth=True)
    assert it.bucket_batch == [4, 4]  # growth clamped 2 -> 1
    seen = sorted(b.bucket_key for b in it)
    assert seen == [4, 8, 8]
    # population supports a partial clamp: 11 sequences, batch 4,
    # headroom growth 4 -> clamped to 11//4 = 2
    sents2 = ([[1] * 2 for _ in range(11)] + [[1] * 8 for _ in range(8)])
    it2 = rnn.BucketSentenceIter(sents2, 4, buckets=[2, 8], invalid_label=0,
                                 batch_growth=True)
    assert it2.bucket_batch == [8, 4]
    # the tail past the last full grown batch is emitted at the plain
    # batch size: 20 seqs at grown batch 8 -> two (8,) batches plus one
    # (4,) tail, same 20-sequence coverage as five unpacked batches
    sents3 = ([[1] * 4 for _ in range(20)] + [[1] * 8 for _ in range(8)])
    it3 = rnn.BucketSentenceIter(sents3, 4, buckets=[4, 8], invalid_label=0,
                                 batch_growth=True)
    short = sorted(b.data[0].shape[0] for b in it3 if b.bucket_key == 4)
    assert short == [4, 8, 8]
    assert sum(short) == 20


def test_packed_bucket_lstm_loss_parity():
    """Packed vs unpacked epochs see the same sequences, so the
    aggregate per-token loss (Perplexity over the epoch) matches —
    batch rows are independent in an RNN; only float summation order
    differs."""
    import random

    from mxnet_tpu import rnn

    V, H, E, B = 20, 16, 8, 4
    rng = np.random.RandomState(3)
    # counts NOT divisible by the grown batch: the short bucket (20 seqs,
    # grown batch 8) emits 2 grown batches plus a plain-batch-size TAIL
    # batch, and the long bucket drops the same 1-sequence remainder both
    # ways — packed epochs cover exactly the sequences unpacked ones do
    sents = ([[int(v) for v in rng.randint(2, V, 3)] for _ in range(20)]
             + [[int(v) for v in rng.randint(2, V, 7)] for _ in range(9)])

    def sym_gen_factory(cell):
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
        return sym_gen

    def epoch_metric(packed):
        random.seed(7)
        np.random.seed(7)
        it = rnn.BucketSentenceIter(list(sents), B, buckets=[4, 8],
                                    invalid_label=0, batch_growth=packed)
        cell = rnn.FusedRNNCell(H, num_layers=1, mode="lstm",
                                prefix="lstm_")
        mod = mx.mod.BucketingModule(
            sym_gen=sym_gen_factory(cell),
            default_bucket_key=it.default_bucket_key, context=mx.cpu())
        mod.bind(data_shapes=it.provide_data,
                 label_shapes=it.provide_label)
        mx.random.seed(11)
        mod.init_params(mx.init.Xavier(factor_type="in", magnitude=2.34))
        metric = mx.metric.Perplexity(0)
        nbatches = 0
        for batch in it:
            mod.forward(batch, is_train=False)
            mod.update_metric(metric, batch.label)
            nbatches += 1
        return metric.get()[1], nbatches

    ppl_unpacked, n_unpacked = epoch_metric(False)
    ppl_packed, n_packed = epoch_metric(True)
    assert n_packed < n_unpacked  # fewer, larger dispatches
    assert np.isfinite(ppl_packed)
    np.testing.assert_allclose(ppl_packed, ppl_unpacked, rtol=1e-4)


def test_packed_bucket_training_arms_fused_update():
    """Every (bucket, batch-shape) executor — grown batches AND the
    plain-batch-size tail — arms the fused single-dispatch update (the
    borrowed updater is name-keyed, so bind arms it right after
    borrow_optimizer); none silently falls back to multi-dispatch
    _update_params."""
    import random

    from mxnet_tpu import rnn

    V, H, E, B = 20, 16, 8, 4
    rng = np.random.RandomState(3)
    sents = ([[int(v) for v in rng.randint(2, V, 3)] for _ in range(20)]
             + [[int(v) for v in rng.randint(2, V, 7)] for _ in range(8)])
    cell = rnn.FusedRNNCell(H, num_layers=1, mode="lstm", prefix="lstm_")

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

    random.seed(7)
    np.random.seed(7)
    it = rnn.BucketSentenceIter(sents, B, buckets=[4, 8], invalid_label=0,
                                batch_growth=True)
    mod = mx.mod.BucketingModule(sym_gen=sym_gen,
                                 default_bucket_key=it.default_bucket_key,
                                 context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05})
    batch_shapes = {k[1][0] for k in mod._buckets}
    assert (B, 4) in batch_shapes and (2 * B, 4) in batch_shapes  # tail + grown
    for key, m in mod._buckets.items():
        assert m._exec_group.execs[0]._fused_updater is not None, key


# ----------------------------------------------------------------------
# (d) first-class frozen-BN fine-tuning
# ----------------------------------------------------------------------


def _bn_net():
    d = mx.sym.Variable("data")
    c = mx.sym.Convolution(d, num_filter=8, kernel=(3, 3), pad=(1, 1),
                           no_bias=True, name="c1")
    b = mx.sym.BatchNorm(c, fix_gamma=False, name="bn1")
    a = mx.sym.Activation(b, act_type="relu")
    f = mx.sym.FullyConnected(a, num_hidden=4, name="fc1")
    return mx.sym.SoftmaxOutput(f, name="softmax")


def _bn_fit_inputs():
    rng = np.random.RandomState(0)
    X = rng.randn(64, 1, 8, 8).astype("float32")
    y = rng.randint(0, 4, 64).astype("float32")
    aux = {"bn1_moving_mean": mx.nd.array(rng.randn(8).astype("float32")),
           "bn1_moving_var": mx.nd.array(
               (rng.rand(8) + 0.5).astype("float32"))}
    return mx.io.NDArrayIter(X, y, batch_size=16), aux


def test_freeze_batchnorm_symbol_transform():
    from mxnet_tpu.symbol import batchnorm_param_names, freeze_batchnorm

    net = _bn_net()
    assert batchnorm_param_names(net) == ["bn1_gamma", "bn1_beta"]
    frozen = freeze_batchnorm(net)
    assert frozen.attr_dict()["bn1"]["use_global_stats"] == "True"
    # the input symbol is NOT mutated, and names survive the copy
    assert "use_global_stats" not in net.attr_dict().get("bn1", {})
    assert frozen.list_arguments() == net.list_arguments()
    assert frozen.list_auxiliary_states() == net.list_auxiliary_states()


@pytest.mark.parametrize("k", [1, 2])
def test_frozen_bn_fit_bit_identical(clean_knobs, k):
    """fit(frozen_bn=True): across both the per-step and the K-step
    fused dispatch paths, BN gamma/beta and the running stats come out
    BIT-identical while the rest of the net trains."""
    from mxnet_tpu import telemetry

    it, aux0 = _bn_fit_inputs()
    mod = mx.mod.Module(_bn_net(), context=mx.cpu())
    telemetry.reset()
    mod.fit(it, num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            aux_params={n: v.copy() for n, v in aux0.items()},
            allow_missing=True, frozen_bn=True, steps_per_dispatch=k)
    args, auxs = mod.get_params()
    for n, v in aux0.items():
        np.testing.assert_array_equal(auxs[n].asnumpy(), v.asnumpy())
    np.testing.assert_array_equal(args["bn1_gamma"].asnumpy(),
                                  np.ones(8, np.float32))
    np.testing.assert_array_equal(args["bn1_beta"].asnumpy(),
                                  np.zeros(8, np.float32))
    assert np.any(args["fc1_weight"].asnumpy() != 0)
    assert telemetry.gauge_value("module.frozen_bn") == 1
    if k > 1:
        # the mode must RIDE the fused block path, not fall back:
        # fixed BN params are static args of the scan (module.py
        # _maybe_install_fused_update)
        snap = telemetry.snapshot()
        assert snap["histograms"]["module.step_seconds"]["count"] == \
            2 * -(-4 // k)


def test_trainable_bn_updates_stats_by_default():
    it, aux0 = _bn_fit_inputs()
    from mxnet_tpu import telemetry

    mod = mx.mod.Module(_bn_net(), context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            aux_params={n: v.copy() for n, v in aux0.items()},
            allow_missing=True)
    _, auxs = mod.get_params()
    assert not np.array_equal(auxs["bn1_moving_mean"].asnumpy(),
                              aux0["bn1_moving_mean"].asnumpy())
    assert telemetry.gauge_value("module.frozen_bn") == 0


def test_frozen_bn_already_bound_needs_force_rebind():
    from mxnet_tpu.base import MXNetError

    it, aux0 = _bn_fit_inputs()
    mod = mx.mod.Module(_bn_net(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Xavier(),
                    aux_params={n: v.copy() for n, v in aux0.items()},
                    allow_missing=True)
    with pytest.raises(MXNetError, match="force_rebind"):
        mod.fit(it, num_epoch=1, frozen_bn=True)
    # with force_rebind the same call goes through
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            aux_params={n: v.copy() for n, v in aux0.items()},
            allow_missing=True, frozen_bn=True, force_rebind=True)
    _, auxs = mod.get_params()
    for n, v in aux0.items():
        np.testing.assert_array_equal(auxs[n].asnumpy(), v.asnumpy())


def test_force_rebind_carries_device_trained_params():
    """bind(force_rebind=True) on a Module trained outside fit (update()
    leaves the host params stale) must sync device values down before
    discarding the executor — the fresh executor seeds from the host
    copy.  This is the flow every frozen-BN force_rebind message
    recommends, so losing the training there would be silent."""
    it, aux0 = _bn_fit_inputs()
    mod = mx.mod.Module(_bn_net(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Uniform(0.1),
                    aux_params={n: v.copy() for n, v in aux0.items()},
                    allow_missing=True)
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    for batch in it:
        mod.forward(batch, is_train=True)
        mod.backward()
        mod.update()
    trained = mod._exec_group.execs[0].arg_dict["fc1_weight"].asnumpy().copy()
    mod._apply_frozen_bn(force_rebind=True)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             force_rebind=True)
    np.testing.assert_array_equal(
        mod._exec_group.execs[0].arg_dict["fc1_weight"].asnumpy(), trained)


def test_frozen_bn_unfreezes_on_next_fit():
    """frozen_bn is a per-fit mode, not a one-way latch: a later
    fit(frozen_bn=False) restores the trainable-BN graph and un-pins the
    BN params, so running stats move again."""
    from mxnet_tpu.base import MXNetError

    it, aux0 = _bn_fit_inputs()
    mod = mx.mod.Module(_bn_net(), context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            aux_params={n: v.copy() for n, v in aux0.items()},
            allow_missing=True, frozen_bn=True)
    _, auxs = mod.get_params()
    np.testing.assert_array_equal(auxs["bn1_moving_mean"].asnumpy(),
                                  aux0["bn1_moving_mean"].asnumpy())
    # unfreezing recompiles the executor, so it needs force_rebind too
    with pytest.raises(MXNetError, match="force_rebind"):
        mod.fit(it, num_epoch=1, frozen_bn=False)
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            allow_missing=True, frozen_bn=False, force_rebind=True)
    assert not mod._fixed_param_names
    assert "use_global_stats" not in mod._symbol.attr_dict().get("bn1", {})
    # a force_rebind with a live optimizer must re-arm the fused
    # single-dispatch update on the NEW executor (init_optimizer
    # early-returns, so bind does it)
    assert mod._exec_group.execs[0]._fused_updater is not None
    _, auxs = mod.get_params()
    assert not np.array_equal(auxs["bn1_moving_mean"].asnumpy(),
                              aux0["bn1_moving_mean"].asnumpy())


def test_frozen_bn_unsupported_module_errors():
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.module.base_module import BaseModule

    class Dummy(BaseModule):
        pass

    with pytest.raises(MXNetError, match="freeze_batchnorm"):
        Dummy()._apply_frozen_bn()


# ----------------------------------------------------------------------
# tooling: the mode columns in parse_log --telemetry
# ----------------------------------------------------------------------


def test_parse_log_renders_mode_gauges():
    import json

    from tools.parse_log import _TELEMETRY_COLS, parse_telemetry

    assert "wgrad_bf16" in _TELEMETRY_COLS
    assert "frozen_bn" in _TELEMETRY_COLS
    rec = {"flush_seq": 0, "step": 4, "counters": {}, "histograms": {},
           "gauges": {"ops.wgrad_bf16": 1, "module.frozen_bn": 1}}
    rows = parse_telemetry([json.dumps(rec)])
    assert rows[0]["wgrad_bf16"] == 1 and rows[0]["frozen_bn"] == 1
    # pre-sink records render '-' (None), not a crash
    old = dict(rec, gauges={})
    rows = parse_telemetry([json.dumps(old)])
    assert rows[0]["wgrad_bf16"] is None and rows[0]["frozen_bn"] is None
