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


def _contexts(mesh, first=0):
    return ([mx.cpu(first + i) for i in range(4)] if mesh
            else mx.cpu(first))


def _module(mesh, first=0):
    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(data, num_hidden=3),
                               name="softmax")
    mod = mx.mod.Module(net, context=_contexts(mesh, first))
    mod.bind(data_shapes=[("data", (BATCH, DIM))],
             label_shapes=[("softmax_label", (BATCH,))])
    return mod


def _executor(mesh):
    return _module(mesh)._exec_group.execs[0]


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


# ----------------------------------------------------------------------
# A batch in host memory goes to each device as that device's own rows
# (PR 39).  Tier-1 has one platform, so host memory has a STAND-IN here:
# the CPU backend's first device, which no block below computes on.
# `nd.off_platform` — the one rule that tells host memory from the
# block's devices — is made to say so, and everything that follows from
# it runs as it does beside an accelerator.
# ----------------------------------------------------------------------

def _host_device():
    import jax

    return jax.local_devices(backend="cpu")[0]


def _block_devices(mesh):
    ctx = _contexts(mesh, first=1)
    return [c.jax_device() for c in (ctx if mesh else [ctx])]


@pytest.fixture
def host_standin(monkeypatch):
    import jax

    host = {_host_device()}
    monkeypatch.setattr(
        mx.nd, "off_platform",
        lambda value, platform: isinstance(value, jax.Array)
        and value.devices() == host)


@pytest.fixture
def puts(monkeypatch):
    """(shape, target device) of every piece jax puts on a device:
    pxla.batched_device_put is where device_put, jnp.asarray and the
    shards of a sharded put all end."""
    from jax._src.interpreters import pxla

    made = []
    put = pxla.batched_device_put

    def recorded(aval, sharding, xs, devices, *args, **kwargs):
        made.extend((tuple(x.shape), d) for x, d in zip(xs, devices))
        return put(aval, sharding, xs, devices, *args, **kwargs)

    monkeypatch.setattr(pxla, "batched_device_put", recorded)
    return made


def _pieces(devices):
    """(shape, device) of one batch's data and label rows a device."""
    rows = BATCH // len(devices)
    return sorted([(shape, d) for d in devices
                   for shape in ((rows, DIM), (rows,))], key=str)


def _batches_of(X, y):
    """What NDArrayIter(last_batch_handle="pad") yields, in numpy."""
    n = len(X)
    for at in range(0, n, BATCH):
        rows = [i % n for i in range(at, at + BATCH)]
        yield X[rows], y[rows]


@pytest.mark.parametrize("mesh", [False, True], ids=["one_device", "mesh4"])
@pytest.mark.parametrize("rows", [8 * BATCH, 6 * BATCH + 3],
                         ids=["whole", "short_block_padded_batch"])
def test_ndarrayiter_block_goes_to_each_device_from_host_memory(
        fresh_telemetry, host_standin, puts, rows, mesh):
    exe = _module(mesh, first=1)._exec_group.execs[0]
    rng = np.random.RandomState(rows)
    X = rng.randn(rows, DIM).astype("float32")
    y = rng.randint(0, 3, rows).astype("float32")
    want = list(_batches_of(X, y))
    telemetry.reset()
    del puts[:]
    it = mx.io.NDArrayIter(X, y, batch_size=BATCH)
    staged = DeviceStagedIter(it, steps_per_dispatch=K,
                              place_fn=exe.place_step_input,
                              stack_fn=exe.stack_block_input)
    blocks = list(staged)
    staged.close()
    assert [b.count for b in blocks] == [K, len(want) - K]
    assert blocks[-1].pad == (-rows) % BATCH
    sh = exe.block_input_sharding()
    devices = _block_devices(mesh)
    at = 0
    for b in blocks:
        ref_x = np.stack([x for x, _ in want[at:at + b.count]])
        ref_y = np.stack([l for _, l in want[at:at + b.count]])
        for got, ref in ((b.data[0], ref_x), (b.label[0], ref_y)):
            assert got.dtype == ref.dtype and got.shape == ref.shape
            assert np.asarray(got).tobytes() == ref.tobytes()
            assert got.sharding.device_set == set(devices)
            if mesh:
                assert got.sharding == sh
        assert np.array_equal(np.stack([l[0] for l in b.label_host]), ref_y)
        at += b.count
    counters = telemetry.snapshot()["counters"]
    # every step array came from host memory, each counted once
    assert counters["io.stage.host_parts"] == 2 * len(want)
    assert counters.get("io.stage.device_parts", 0) == 0
    staged_bytes = sum(x.nbytes + l.nbytes for x, l in want)
    assert counters["executor.h2d_bytes"] == staged_bytes
    assert counters.get("executor.d2h_bytes", 0) == 0
    # what crossed: a device's own rows to that device, and nothing else;
    # no whole batch on the first device of a block of four
    crossed = [(shape, to) for shape, to in puts if to != _host_device()]
    assert sorted(set(crossed), key=str) == _pieces(devices)
    assert len(crossed) == 2 * len(want) * len(devices)


@pytest.mark.parametrize("source", ["aligned", "unaligned", "shuffled"])
def test_ndarrayiter_next_makes_views_of_its_store_and_no_transfer(
        fresh_telemetry, puts, source):
    """`next()` on its own: every array of a full batch is the
    iterator's own memory (an aligned store, laid once at construction)
    on the host device, uncommitted, and nothing is put anywhere else."""
    from mxnet_tpu.io import HOST_ALIGN, aligned_empty

    rng = np.random.RandomState(7)
    X = aligned_empty((4 * BATCH + 1, DIM), "float32")
    X[:] = rng.randn(*X.shape)
    if source == "unaligned":
        X = X[1:]            # starts 24 bytes past a boundary
    else:
        X = X[:-1]
    y = np.arange(len(X), dtype="float32")
    np.random.seed(3)
    it = mx.io.NDArrayIter(X, y, batch_size=BATCH,
                           shuffle=source == "shuffled")
    store = it.data[0][1]
    assert store.ctypes.data % HOST_ALIGN == 0
    assert (store is X) == (source == "aligned")
    order = it.idx if source == "shuffled" else np.arange(len(X))
    del puts[:]
    for at, batch in zip(range(0, len(X), BATCH), it):
        data, label = batch.data[0], batch.label[0]
        assert data.context == mx.cpu() and label.context == mx.cpu()
        for nd_arr, src in ((data, store), (label, it.label[0][1])):
            payload = nd_arr.data
            assert payload.devices() == {_host_device()}
            assert not payload.committed
            # a view of the store wherever the batch starts at a
            # boundary (the data always; every second label batch)
            start = src[at:].ctypes.data
            assert (payload.unsafe_buffer_pointer() == start) == \
                (start % HOST_ALIGN == 0)
            assert src is not store or start % HOST_ALIGN == 0
        assert np.array_equal(data.asnumpy(), X[order[at:at + BATCH]])
        assert np.array_equal(label.asnumpy(), y[order[at:at + BATCH]])
    assert puts and all(to == _host_device() for _, to in puts)


@pytest.mark.parametrize("mesh", [False, True], ids=["one_device", "mesh4"])
def test_forward_places_a_host_batch_on_its_own_devices(
        fresh_telemetry, host_standin, puts, mesh):
    """K=1 (`Module.forward`, `score`, `predict`): the executor places a
    host-resident batch on its device(s) itself — each device its rows,
    counted once — and computes what it computes from a device batch."""
    mod = _module(mesh, first=1)
    mod.init_params()
    exe = mod._exec_group.execs[0]
    X, y = _arrays(1)
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(X)],
                                label=[mx.nd.array(y)]), is_train=False)
    out = mod.get_outputs()[0].asnumpy()  # the parameters are placed now
    batch = next(iter(mx.io.NDArrayIter(X, y, batch_size=BATCH)))
    telemetry.reset()
    del puts[:]
    mod.forward(batch, is_train=False)
    assert np.array_equal(mod.get_outputs()[0].asnumpy(), out)
    devices = _block_devices(mesh)
    assert exe.arg_dict["data"].data.sharding.device_set == set(devices)
    assert telemetry.counter_value("executor.h2d_bytes") == \
        X.nbytes + y.nbytes
    rows = (BATCH // len(devices),)  # the parameters are put anew, too
    assert sorted([p for p in puts if p[0][:1] == rows], key=str) == \
        _pieces(devices)


# ----------------------------------------------------------------------
# what the staging thread waits for (PR 42)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("device,host,fence", [
    ("tpu", "cpu", "pieces"), ("gpu", "cpu", "pieces"),
    ("cpu", "cpu", "stack")])
def test_the_fence_of_a_host_step_follows_from_the_two_platforms(
        device, host, fence):
    """Host memory beside a chip: the buffer is needed until the pieces
    have arrived, and the stack — a program in the chips' compute queue —
    is not waited for.  One platform (the CPU backend may alias the
    buffer): until the stack has run."""
    from mxnet_tpu.executor import _host_step_fence

    assert _host_step_fence(device, host) == fence
    assert _host_step_fence(device) == fence  # host memory is the CPU's


@pytest.mark.parametrize("mesh", [False, True], ids=["one_device", "mesh4"])
@pytest.mark.parametrize("platform,kind,waited", [
    ("cpu", "numpy", "stacks"), ("tpu", "numpy", "pieces"),
    ("cpu", "ndarray", None)],
    ids=["one_platform", "beside_a_chip", "device_source"])
def test_stack_block_input_waits_for_what_the_fence_names(
        monkeypatch, mesh, platform, kind, waited):
    """`stack_block_input` hands `jax.block_until_ready` the stacks where
    host and device are one platform, every host step's pieces where the
    executor computes on another one, and nothing for steps that were on
    the device already."""
    import jax

    exe = _executor(mesh)
    X, _ = _arrays(K)
    steps = [exe.place_step_input(
        "data", X[s * BATCH:(s + 1) * BATCH] if kind == "numpy"
        else mx.nd.array(X[s * BATCH:(s + 1) * BATCH])) for s in range(K)]
    assert [host for host, _ in steps] == [kind == "numpy"] * K
    seen = []
    wait = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: seen.append(x) or wait(x))
    monkeypatch.setattr(exe, "_platform", platform)
    block = exe.stack_block_input("data", steps)
    assert np.asarray(block).tobytes() == X.tobytes()
    if waited is None:
        assert seen == []
        return
    (arrays,) = seen
    devices = 4 if mesh else 1
    if waited == "pieces":
        assert [len(p) for p in arrays] == [devices] * K
        assert all(a is b for got, (_, want) in zip(arrays, steps)
                   for a, b in zip(got, want))
    else:
        assert len(arrays) == devices
        assert all(a.shape[0] == K for a in arrays)
