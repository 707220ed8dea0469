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


# ----------------------------------------------------------------------
# the K-step loop runs one block ahead of what it reads (PR 42)
# ----------------------------------------------------------------------
def _recorded_fit(k, n=256, batch=32, callback=None, metric=None, epochs=1):
    """One fit at block size k with a recording `update()` and a
    recording callback: (events, module).  An event is ("dispatch", i) —
    the i-th update() of the run — or ("callback", epoch, nbatch,
    sum_metric, num_inst)."""
    from mxnet_tpu import telemetry

    telemetry.reset()
    X, y = _toy_data(n=n)
    mx.random.seed(11)
    it = mx.io.NDArrayIter(X, y, batch_size=batch)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    events = []
    metric = metric or mx.metric.CrossEntropy()
    update = mod.update

    def recording_update():
        update()
        events.append(("dispatch",
                       sum(e[0] == "dispatch" for e in events)))

    def on_batch(param):
        events.append(("callback", param.epoch, param.nbatch,
                       param.eval_metric.sum_metric,
                       param.eval_metric.num_inst))
        if callback is not None:
            callback(param)

    mod.update = recording_update
    mod.fit(it, num_epoch=epochs, initializer=mx.init.Xavier(),
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            steps_per_dispatch=k, eval_metric=metric,
            batch_end_callback=on_batch)
    return events, mod


def test_callback_n_fires_after_dispatch_n_plus_1_and_before_n_plus_2():
    """Block n+1 is dispatched before block n's outputs are read, and
    block n+2 only after block n's callback: depth exactly one."""
    events, _ = _recorded_fit(2)  # 8 steps, 4 blocks
    kinds = [(e[0], e[2] if e[0] == "callback" else e[1]) for e in events]
    assert kinds == [("dispatch", 0), ("dispatch", 1), ("callback", 1),
                     ("dispatch", 2), ("callback", 3),
                     ("dispatch", 3), ("callback", 5),
                     ("callback", 7)]


@pytest.mark.parametrize("n,k", [(256, 4), (192, 4), (32, 4), (256, 8)],
                         ids=["whole_blocks", "short_last_block",
                              "one_short_block", "one_whole_block"])
def test_callbacks_see_the_metric_of_the_single_step_order(n, k):
    """`nbatch`, `sum_metric` and `num_inst` at every callback of a K-step
    fit are those a fit of single-step dispatches shows after the same
    step: once a dispatch, in order, the last block and a short last
    block included — drained before the epoch ends."""
    steps, _ = _recorded_fit(1, n=n)
    blocks, mod = _recorded_fit(k, n=n)
    steps = {e[2]: e for e in steps if e[0] == "callback"}
    got = [e for e in blocks if e[0] == "callback"]
    total = n // 32
    assert [e[2] for e in got] == [min(s + k, total) - 1
                                   for s in range(0, total, k)]
    assert blocks[-1] is got[-1]
    for e in got:
        want = steps[e[2]]
        assert e[4] == want[4] == (e[2] + 1) * 32
        assert e[3] == pytest.approx(want[3], rel=1e-5)
    assert mod._exec_group.execs[0]._train_dispatches == len(got)


def test_every_epoch_is_drained_before_it_ends():
    """Each epoch's last block is read and called back before the next
    epoch's first dispatch: the metric fit logs at the epoch's end, and
    resets, holds every block."""
    events, _ = _recorded_fit(4, epochs=2)  # 2 blocks an epoch
    kinds = [(e[0], e[1:3] if e[0] == "callback" else e[1]) for e in events]
    assert kinds == [("dispatch", 0), ("dispatch", 1), ("callback", (0, 3)),
                     ("callback", (0, 7)),
                     ("dispatch", 2), ("dispatch", 3), ("callback", (1, 3)),
                     ("callback", (1, 7))]
    # the metric was reset between the epochs, after the drain
    assert [e[4] for e in events if e[0] == "callback"] == [128, 256] * 2


def test_runahead_blocks_count_the_dispatches_behind_an_unread_block():
    """`module.runahead_blocks`: every dispatch of an epoch but its
    first follows a block whose outputs are still unread."""
    from mxnet_tpu import telemetry

    _recorded_fit(2, epochs=3)  # 4 dispatches an epoch
    assert telemetry.counter_value("executor.train_dispatches") == 12
    assert telemetry.counter_value("module.runahead_blocks") == 12 - 3


def test_a_raising_callback_still_closes_the_staging_iterator(monkeypatch):
    """An exception out of block n's callback, with block n+1 in flight,
    leaves no staging op running on the source iterator."""
    from mxnet_tpu import io as mxio

    made = []
    cls = mxio.DeviceStagedIter

    class Recorded(cls):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            made.append(self)

    monkeypatch.setattr(mxio, "DeviceStagedIter", Recorded)

    def boom(param):
        raise KeyError("callback %d" % param.nbatch)

    with pytest.raises(KeyError, match="callback 1"):
        _recorded_fit(2, callback=boom)
    (staged,) = made
    assert staged._bg is None
    with pytest.raises(mx.MXNetError, match="closed"):
        staged.next()


def test_a_fit_without_labels_is_never_fenced_and_calls_back_in_order():
    """No label, no metric read: the loop has no fence, and its order —
    dispatch n+1, then block n's callback — is the same."""
    X, _ = _toy_data(n=128)
    data = mx.sym.Variable("data")
    net = mx.sym.MakeLoss(mx.sym.sum(mx.sym.square(
        mx.sym.FullyConnected(data, num_hidden=4, name="fc1"))))
    mod = mx.mod.Module(net, label_names=None, context=mx.cpu())
    events = []
    update = mod.update

    def recording_update():
        update()
        events.append("dispatch")

    mod.update = recording_update
    mod.fit(mx.io.NDArrayIter(X, None, batch_size=32), num_epoch=1,
            initializer=mx.init.Xavier(), optimizer="sgd",
            optimizer_params={"learning_rate": 0.01}, steps_per_dispatch=2,
            batch_end_callback=lambda p: events.append(p.nbatch))
    assert events == ["dispatch", "dispatch", 1, 3]


def test_the_wait_for_a_block_behind_an_unread_one_is_in_the_flight_recorder():
    """Staging may queue device work behind the block in flight, so a
    hang there can hold the loop in `next()` before it reaches the read:
    every wait for a staged block with a dispatch unread is a
    `stage_wait` span numbered like that dispatch — an epoch's first
    wait has nothing in flight and is none — and all are closed."""
    from mxnet_tpu.obs import recorder

    prev = recorder.set_enabled(True)
    recorder.reset()
    try:
        _, mod = _recorded_fit(2, epochs=2)  # 4 dispatches an epoch
        waits = [(e["phase"], e["seq"]) for e in recorder.events()
                 if e["kind"] == "stage_wait"]
        prog = recorder.progress()["stage_wait"]
    finally:
        recorder.reset()
        recorder.set_enabled(prev)
    # behind dispatches 1-3 a block follows, behind the 4th the epoch's end
    assert waits == [(ph, seq) for seq in range(1, 9)
                     for ph in ("enter", "exit")]
    assert prog["entered"] == prog["exited"] == 8
    assert recorder.open_spans() == []
