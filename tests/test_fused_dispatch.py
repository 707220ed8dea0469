"""K-step fused training dispatch (Executor.fused_update_block): the
parity pin from docs/perf.md — training K steps with steps_per_dispatch=K
must equal K sequential single-step dispatches (same rng, same batches)
in params AND optimizer state, with dispatch count = ceil(steps/K)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.test_utils import assert_almost_equal


def _toy_data(n=256, d=10, k=3, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype("float32")
    w = rng.randn(d, k)
    y = np.argmax(X @ w, axis=1).astype("float32")
    return X, y


def _mlp(num_classes=3, dropout=False):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    if dropout:
        net = mx.sym.Dropout(net, p=0.5, name="drop")
    net = mx.sym.FullyConnected(net, num_hidden=num_classes, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _bn_net(num_classes=3):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.BatchNorm(net, name="bn1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=num_classes, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _fit(sym, k, n=256, batch=32, seed=11, epochs=1, metric=None, **opt_kw):
    """Train `epochs` epochs at block size k; returns (params, opt states,
    executor)."""
    X, y = _toy_data(n=n)
    mx.random.seed(seed)
    it = mx.io.NDArrayIter(X, y, batch_size=batch)
    mod = mx.mod.Module(sym, context=mx.cpu())
    kw = dict(optimizer="sgd",
              optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    kw.update(opt_kw)
    mod.fit(it, num_epoch=epochs, initializer=mx.init.Xavier(),
            steps_per_dispatch=k, eval_metric=metric or "acc", **kw)
    args, _ = mod.get_params()
    states = dict(mod._updater.states)
    return ({n_: v.asnumpy() for n_, v in args.items()}, states,
            mod._exec_group.execs[0])


def _assert_state_close(sa, sb):
    from mxnet_tpu.optimizer import _state_leaves

    assert sa.keys() == sb.keys()
    for key in sa:
        la, lb = _state_leaves(sa[key]), _state_leaves(sb[key])
        assert len(la) == len(lb)
        for a, b in zip(la, lb):
            assert_almost_equal(a.asnumpy(), b.asnumpy(), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("k", [1, 4])
def test_block_matches_sequential_single_steps(k):
    """The acceptance pin: params and optimizer state after an epoch at
    steps_per_dispatch=K allclose to the same epoch run one dispatch per
    step (the K=1 baseline runs the classic per-step fused path)."""
    ref_args, ref_states, ref_exe = _fit(_mlp(), 1)
    blk_args, blk_states, blk_exe = _fit(_mlp(), k)
    for name in ref_args:
        assert_almost_equal(ref_args[name], blk_args[name],
                            rtol=1e-5, atol=1e-6)
    _assert_state_close(ref_states, blk_states)
    # 256 samples / batch 32 = 8 steps -> ceil(8/k) dispatches
    assert ref_exe._train_dispatches == 8
    assert blk_exe._train_dispatches == -(-8 // k)


def test_block_tail_shorter_than_k():
    """An epoch length not divisible by K ends with a short block; parity
    and dispatch count = ceil(steps/K) must still hold."""
    # 192 samples / batch 32 = 6 steps, K=4 -> blocks of 4 and 2
    ref_args, _, _ = _fit(_mlp(), 1, n=192)
    blk_args, _, exe = _fit(_mlp(), 4, n=192)
    for name in ref_args:
        assert_almost_equal(ref_args[name], blk_args[name],
                            rtol=1e-5, atol=1e-6)
    assert exe._train_dispatches == 2


def test_block_parity_with_dropout_rng():
    """Per-step seeds are drawn from the host RNG in the same order on
    both paths, so dropout masks — and therefore params — agree."""
    ref_args, _, _ = _fit(_mlp(dropout=True), 1, seed=5)
    blk_args, _, _ = _fit(_mlp(dropout=True), 2, seed=5)
    for name in ref_args:
        assert_almost_equal(ref_args[name], blk_args[name],
                            rtol=1e-5, atol=1e-6)


def test_block_parity_with_lr_scheduler_and_adam():
    """The host-computed (K, n, 3) schedule prefix must advance
    num_update exactly as K sequential updates (FactorScheduler decays
    mid-block) — and Adam's t-dependent bias correction must see the
    same per-step t."""
    def sched():
        # a FRESH scheduler per run: FactorScheduler mutates count/base_lr
        return dict(optimizer="adam",
                    optimizer_params={
                        "learning_rate": 0.05,
                        "lr_scheduler": mx.lr_scheduler.FactorScheduler(
                            step=3, factor=0.5)})

    ref_args, ref_states, _ = _fit(_mlp(), 1, **sched())
    blk_args, blk_states, _ = _fit(_mlp(), 4, **sched())
    for name in ref_args:
        assert_almost_equal(ref_args[name], blk_args[name],
                            rtol=1e-5, atol=1e-6)
    _assert_state_close(ref_states, blk_states)


def test_block_carries_batchnorm_aux():
    """BN moving stats are scan-carried: after a blocked epoch they match
    the per-step path (aux chaining across steps inside one dispatch)."""
    X, y = _toy_data()
    auxs = []
    for k in (1, 4):
        mx.random.seed(3)
        it = mx.io.NDArrayIter(X, y, batch_size=32)
        mod = mx.mod.Module(_bn_net(), context=mx.cpu())
        mod.fit(it, num_epoch=1, initializer=mx.init.Xavier(),
                optimizer="sgd", optimizer_params={"learning_rate": 0.05},
                steps_per_dispatch=k)
        _, aux = mod.get_params()
        auxs.append({n: v.asnumpy() for n, v in aux.items()})
    assert auxs[0], "BN net must expose aux states"
    for name in auxs[0]:
        assert_almost_equal(auxs[0][name], auxs[1][name],
                            rtol=1e-5, atol=1e-6)


def test_block_metric_matches_per_step():
    """update_metric consumes the stacked block (one readback per
    dispatch) and must accumulate exactly what per-step updates did."""
    metrics = []
    for k in (1, 4):
        m = mx.metric.Accuracy()
        _fit(_mlp(), k, metric=m)
        metrics.append(m.get())
    assert metrics[0][1] == pytest.approx(metrics[1][1], abs=1e-12)
    assert metrics[0][0] == metrics[1][0]


def test_block_outputs_are_stacked_and_fit_converges():
    """End-to-end: blocked fit converges like per-step fit, and the
    executor reports the stacked output shape of the last block."""
    X, y = _toy_data(n=512)
    mx.random.seed(7)
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    val = mx.io.NDArrayIter(X, y, batch_size=32)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(it, num_epoch=5, initializer=mx.init.Xavier(),
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            steps_per_dispatch=4)
    exe = mod._exec_group.execs[0]
    assert exe._last_block_count == 4
    assert mod.get_outputs()[0].shape == (4, 32, 3)
    score = mod.score(val, "acc")
    assert score[0][1] > 0.95, score
    # score() ran plain forwards: the block flag must have cleared
    assert exe._last_block_count == 0


def test_block_spmd_matches_single_device():
    """The K-step block under a 4-device 'data' mesh (stacked inputs
    sharded P(None, 'data'), XLA inserting the per-step grad all-reduce
    inside the scan) matches single-device per-step training."""
    X, y = _toy_data()
    results, dispatches = {}, {}
    for name, ctxs, k in [("single", [mx.cpu(0)], 1),
                          ("spmd", [mx.cpu(i) for i in range(4)], 2)]:
        mx.random.seed(3)
        it = mx.io.NDArrayIter(X, y, batch_size=64)
        mod = mx.mod.Module(_mlp(), context=ctxs)
        # kvstore=None: the kvstore-side update path disarms the fused
        # dispatch (single- and K-step alike) on multi-device
        mod.fit(it, num_epoch=2, initializer=mx.init.Xavier(), kvstore=None,
                optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                steps_per_dispatch=k)
        assert (mod._exec_group.mesh is not None) == (name == "spmd")
        dispatches[name] = mod._exec_group.execs[0]._train_dispatches
        a, _ = mod.get_params()
        results[name] = {n_: v.asnumpy() for n_, v in a.items()}
    assert dispatches == {"single": 8, "spmd": 4}
    for name in results["single"]:
        assert_almost_equal(results["single"][name], results["spmd"][name],
                            rtol=1e-4, atol=1e-5)


def test_non_fused_optimizer_falls_back_per_step():
    """Optimizers without a fused kernel can't scan-carry their update;
    fit must fall back to one dispatch per step and still train."""
    blk_args, _, exe = _fit(_mlp(), 4, optimizer="nadam",
                            optimizer_params={"learning_rate": 0.01})
    ref_args, _, _ = _fit(_mlp(), 1, optimizer="nadam",
                          optimizer_params={"learning_rate": 0.01})
    assert exe._train_dispatches == 8  # per-step, not ceil(8/4)
    for name in ref_args:
        assert_almost_equal(ref_args[name], blk_args[name],
                            rtol=1e-5, atol=1e-6)


def test_fresh_forward_supersedes_stale_staged_block():
    """A staged block whose update() never ran (e.g. an exception between
    forward_backward and update) must NOT hijack the next per-step
    update: a fresh forward clears the pending block."""
    from mxnet_tpu.io import DeviceStagedIter

    X, y = _toy_data(n=64)
    mx.random.seed(2)
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    exe = mod._exec_group.execs[0]
    staged = DeviceStagedIter(it, steps_per_dispatch=2,
                              place_fn=exe.place_step_input,
                              stack_fn=exe.stack_block_input)
    mod.forward_backward(next(staged))  # staged; update() skipped
    staged.close()
    assert exe._pending_fused_block
    batch = mx.io.DataBatch(data=[mx.nd.array(X[:32])],
                            label=[mx.nd.array(y[:32])])
    mod.forward_backward(batch)
    assert not exe._pending_fused_block and exe._staged_block is None
    d0 = exe._train_dispatches
    mod.update()
    # ONE single-step dispatch ran, not the 2-step stale block
    assert exe._train_dispatches == d0 + 1
    assert exe._last_block_count == 0
    assert mod.get_outputs()[0].shape == (32, 3)
    # ... and the mirror direction: a staged block supersedes a deferred
    # single step (backward deferred, update skipped, then a block)
    mod.forward_backward(batch)  # defers: _pending_fused set
    assert exe._pending_fused
    staged2 = DeviceStagedIter(mx.io.NDArrayIter(X, y, batch_size=32),
                               steps_per_dispatch=2,
                               place_fn=exe.place_step_input,
                               stack_fn=exe.stack_block_input)
    mod.forward_backward(next(staged2))
    staged2.close()
    assert exe._pending_fused_block and not exe._pending_fused
    d1 = exe._train_dispatches
    mod.update()
    assert exe._train_dispatches == d1 + 1 and exe._last_block_count == 2


def test_env_default_steps_per_dispatch(monkeypatch):
    """MXTPU_STEPS_PER_DISPATCH is the fit default (config-registered)."""
    monkeypatch.setenv("MXTPU_STEPS_PER_DISPATCH", "4")
    _, _, exe = _fit(_mlp(), None)
    assert exe._train_dispatches == 2


def test_schedule_prefix_matches_eager_updates():
    """optimizer.schedule_prefix advances counts exactly like sequential
    eager updates: same lr/wd/t rows, same final num_update."""
    from mxnet_tpu.optimizer import schedule_prefix

    def make():
        return mx.optimizer.SGD(
            learning_rate=1.0,
            lr_scheduler=mx.lr_scheduler.FactorScheduler(step=2, factor=0.5))

    keys = ["w0", "w1"]
    a = make()
    pref = schedule_prefix(a, keys, 3)
    b = make()
    rows = np.empty_like(pref)
    for s in range(3):
        for r, key in enumerate(keys):
            rows[s, r, 0] = b._get_lr(key)
            rows[s, r, 1] = b._get_wd(key)
            b._update_count(key)
            rows[s, r, 2] = b._index_update_count[key]
    np.testing.assert_array_equal(pref, rows)
    assert a.num_update == b.num_update == 3
