"""DeviceStagedIter assembles a K-step block on the device(s): the block
equals `np.stack` of the batches bit for bit and carries the executor's
block_input_sharding(), whichever way the step arrays came, and no data
array crosses to the host on the way."""
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.io import DataBatch, DataDesc, DataIter, DeviceStagedIter

BATCH, DIM, K = 8, 6, 4


def _executor(mesh):
    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(data, num_hidden=3),
                               name="softmax")
    ctx = [mx.cpu(i) for i in range(4)] if mesh else mx.cpu()
    mod = mx.mod.Module(net, context=ctx)
    mod.bind(data_shapes=[("data", (BATCH, DIM))],
             label_shapes=[("softmax_label", (BATCH,))])
    return mod._exec_group.execs[0]


def _arrays(steps):
    rng = np.random.RandomState(steps)
    return (rng.randn(steps * BATCH, DIM).astype("float32"),
            rng.randint(0, 3, steps * BATCH).astype("float32"))


class _ReusedBufferIter(DataIter):
    """Numpy batches out of a ring of K buffers, each overwritten K
    next() calls later: what a host-producing source is allowed to do
    once the staging op that fetched a block has returned."""

    def __init__(self, X, y):
        super().__init__()
        self.batch_size = BATCH
        self.provide_data = [DataDesc("data", (BATCH, DIM))]
        self.provide_label = [DataDesc("softmax_label", (BATCH,))]
        self._X, self._y, self._at = X, y, 0
        self._ring = [(np.empty((BATCH, DIM), "float32"),
                       np.empty((BATCH,), "float32")) for _ in range(K)]

    def reset(self):
        self._at = 0

    def next(self):
        if self._at >= len(self._X):
            raise StopIteration
        data, label = self._ring[(self._at // BATCH) % K]
        data[:] = self._X[self._at:self._at + BATCH]
        label[:] = self._y[self._at:self._at + BATCH]
        self._at += BATCH
        return DataBatch(data=[data], label=[label], pad=0)


def _source(kind, X, y):
    if kind == "numpy":
        return _ReusedBufferIter(X, y)
    return mx.io.NDArrayIter(X, y, batch_size=BATCH)


def _buffers(arr):
    return [s.data.unsafe_buffer_pointer() for s in arr.addressable_shards]


@pytest.fixture
def fresh_telemetry():
    prev = telemetry.set_enabled(True)
    telemetry.reset()
    yield
    telemetry.reset()
    telemetry.set_enabled(prev)


@pytest.mark.parametrize("mesh", [False, True], ids=["one_device", "mesh4"])
@pytest.mark.parametrize("kind,steps", [("ndarray", 8), ("numpy", 8),
                                        ("ndarray", 6)],
                         ids=["ndarray", "numpy", "short_last_block"])
def test_block_is_np_stack_of_the_batches_with_the_block_sharding(
        fresh_telemetry, kind, steps, mesh):
    exe = _executor(mesh)
    X, y = _arrays(steps)
    staged = DeviceStagedIter(_source(kind, X, y), steps_per_dispatch=K,
                              place_fn=exe.place_step_input,
                              stack_fn=exe.stack_block_input)
    blocks = list(staged)
    staged.close()
    assert [b.count for b in blocks] == [4, steps - 4]
    sh = exe.block_input_sharding()
    assert (sh is not None) == mesh
    at = 0
    for b in blocks:
        rows = slice(at * BATCH, (at + b.count) * BATCH)
        want = {"data": X[rows].reshape(b.count, BATCH, DIM),
                "label": y[rows].reshape(b.count, BATCH)}
        for got, ref in ((b.data[0], want["data"]), (b.label[0],
                                                     want["label"])):
            assert got.dtype == ref.dtype and got.shape == ref.shape
            assert np.asarray(got).tobytes() == ref.tobytes()
            if mesh:
                assert got.sharding == sh
                assert len(got.sharding.device_set) == 4
            else:
                assert got.devices() == {mx.cpu().jax_device()}
            # the dispatch path's re-placement moves nothing
            assert _buffers(exe.place_block_input("data", got)) == \
                _buffers(got)
        assert np.array_equal(np.stack([l[0] for l in b.label_host]),
                              want["label"])
        at += b.count
    counters = telemetry.snapshot()["counters"]
    came = ("io.stage.host_parts" if kind == "numpy"
            else "io.stage.device_parts")
    other = ({"io.stage.host_parts", "io.stage.device_parts"} - {came}).pop()
    assert counters[came] == 2 * steps and counters.get(other, 0) == 0
    # a host array crosses the link once, a device array never again
    # (NDArrayIter's own per-batch creation is the H2D that is left)
    assert counters["executor.h2d_bytes"] == X.nbytes + y.nbytes
    assert counters["io.stage_bytes"] == X.nbytes + y.nbytes


@pytest.mark.parametrize("mesh", [False, True], ids=["one_device", "mesh4"])
def test_no_data_array_crosses_to_the_host_during_staging(monkeypatch, mesh):
    """Every host read of a device array on a thread that is inside a
    staging op is counted — `NDArray.asnumpy`, and what jax itself
    reads through `jax.Array._value` (where device_put of a
    single-device array to a mesh sharding ends): the labels, and
    nothing else."""
    from jax._src.array import ArrayImpl

    inside = threading.local()
    read = []
    value, asnumpy = ArrayImpl._value, mx.nd.NDArray.asnumpy
    fetch_block = DeviceStagedIter._fetch_block

    def counted_value(self):
        if getattr(inside, "staging", False):
            read.append(self.nbytes)
        return value.fget(self)

    def counted_asnumpy(self):
        # np.asarray may itself go through `_value`: count once
        counting, inside.staging = getattr(inside, "staging", False), False
        try:
            out = asnumpy(self)
        finally:
            inside.staging = counting
        if counting:
            read.append(out.nbytes)
        return out

    def flagged_fetch(self):
        inside.staging = True
        try:
            return fetch_block(self)
        finally:
            inside.staging = False

    monkeypatch.setattr(ArrayImpl, "_value", property(counted_value))
    monkeypatch.setattr(mx.nd.NDArray, "asnumpy", counted_asnumpy)
    monkeypatch.setattr(DeviceStagedIter, "_fetch_block", flagged_fetch)
    exe = _executor(mesh)
    X, y = _arrays(8)
    staged = DeviceStagedIter(_source("ndarray", X, y), steps_per_dispatch=K,
                              place_fn=exe.place_step_input,
                              stack_fn=exe.stack_block_input)
    blocks = list(staged)
    staged.close()
    assert len(blocks) == 2
    assert sum(read) == y.nbytes and max(read) == BATCH * 4
