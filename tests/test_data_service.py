"""mxnet_tpu.data — parity + failure pins for the sharded multi-process
input pipeline (docs/data.md).

The load-bearing claims: a multi-process sharded epoch covers exactly
the records a single-process ImageRecordIter epoch does (same seed →
same sample multiset), the batch SEQUENCE is identical for any worker
count (so Module.fit loss trajectories match the single-process path),
worker crashes surface as clear errors instead of hangs, teardown
leaks neither processes nor shared memory, and the consumer-side
pipeline declares everything it touches (SanitizerEngine-clean)."""
import os
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine
from mxnet_tpu.data import (DataService, DataWorkerError,
                            ShardedImageRecordIter, epoch_order)
from mxnet_tpu.engine.sanitizer import RaceWarning

PIL = pytest.importorskip("PIL.Image")


# ----------------------------------------------------------------------
# one packed dataset per module: 72 tiny JPEGs in 3 classes
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def rec_prefix(tmp_path_factory):
    from conftest import pack_jpeg_rec

    return pack_jpeg_rec(tmp_path_factory.mktemp("data_service"),
                         n_per_class=24, classes=3, size=24)


def _epoch_arrays(it):
    """[(data, label, pad)] numpy triples of one epoch of a DataIter."""
    out = []
    for b in it:
        out.append((np.asarray(b.data[0].asnumpy()),
                    np.asarray(b.label[0].asnumpy()), b.pad or 0))
    return out


# ----------------------------------------------------------------------
# epoch order / coverage
# ----------------------------------------------------------------------

def test_epoch_order_is_pure_in_seed_and_epoch():
    a = epoch_order(100, seed=3, epoch=5, shuffle=True)
    b = epoch_order(100, seed=3, epoch=5, shuffle=True)
    assert (a == b).all()
    assert sorted(a.tolist()) == list(range(100))  # a permutation
    assert not (a == epoch_order(100, 3, 6, True)).all()   # epochs differ
    assert not (a == epoch_order(100, 4, 5, True)).all()   # seeds differ
    assert (epoch_order(10, 0, 0, False) == np.arange(10)).all()


def test_sharded_epoch_matches_single_process_multiset(rec_prefix):
    """The acceptance pin: a 2-worker shuffled epoch covers exactly the
    sample multiset a single-process ImageRecordIter epoch covers."""
    kw = dict(path_imgrec=rec_prefix + ".rec", data_shape=(3, 20, 20),
              batch_size=8, shuffle=True, seed=11)
    ref = mx.io.ImageRecordIter(preprocess_threads=2, **kw)
    ref_epoch = _epoch_arrays(ref)
    ref.close()
    it = ShardedImageRecordIter(num_workers=2, **kw)
    got_epoch = _epoch_arrays(it)
    it.close()

    def multiset(epoch):
        rows = []
        for data, label, pad in epoch:
            n = data.shape[0] - pad
            for j in range(n):
                rows.append(data[j].tobytes() + label[j].tobytes())
        return sorted(rows)

    assert len(ref_epoch) == len(got_epoch) == 9  # ceil(72/8)
    assert multiset(ref_epoch) == multiset(got_epoch)


def test_batch_sequence_identical_across_worker_counts(rec_prefix):
    """Round-robin reassembly in global batch-index order + per-(seed,
    epoch, batch) augmentation streams make the batch SEQUENCE a
    function of (seed, epoch) only — any worker count produces
    byte-identical epochs EVEN WITH augmentation on, and epochs
    reshuffle."""
    kw = dict(path_imgrec=rec_prefix + ".rec", data_shape=(3, 20, 20),
              batch_size=8, shuffle=True, seed=5, rand_crop=True,
              rand_mirror=True)
    epochs = {}
    for w in (1, 2):
        it = ShardedImageRecordIter(num_workers=w, **kw)
        first = _epoch_arrays(it)
        it.reset()
        second = _epoch_arrays(it)
        it.close()
        epochs[w] = (first, second)
    for (d1, l1, p1), (d2, l2, p2) in zip(*[epochs[w][0] for w in (1, 2)]):
        assert (d1 == d2).all() and (l1 == l2).all() and p1 == p2
    for (d1, l1, p1), (d2, l2, p2) in zip(*[epochs[w][1] for w in (1, 2)]):
        assert (d1 == d2).all() and (l1 == l2).all() and p1 == p2
    # epoch 1 reshuffles relative to epoch 0
    assert any((l1 != l2).any() for (_, l1, _), (_, l2, _)
               in zip(epochs[1][0], epochs[1][1]))


def test_unshuffled_matches_image_record_iter_bytewise(rec_prefix):
    """With augmentation off and shuffle off the 2-worker service is
    byte-identical to the single-process iterator, batch for batch
    (same decode core, same order, same pad semantics)."""
    kw = dict(path_imgrec=rec_prefix + ".rec", data_shape=(3, 20, 20),
              batch_size=16, shuffle=False)
    ref = mx.io.ImageRecordIter(preprocess_threads=2, **kw)
    it = ShardedImageRecordIter(num_workers=2, **kw)
    ref_epoch, got_epoch = _epoch_arrays(ref), _epoch_arrays(it)
    ref.close()
    it.close()
    assert len(ref_epoch) == len(got_epoch) == 5  # ceil(72/16), tail pad 8
    for (rd, rl, rp), (gd, gl, gp) in zip(ref_epoch, got_epoch):
        assert rp == gp
        assert (rd == gd).all()
        assert (rl == gl).all()
    assert ref_epoch[-1][2] == 8


def test_part_index_maps_to_host_shard(rec_prefix):
    """Drop-in migration: ImageRecordIter's part_index/num_parts args
    ARE the per-host stride shard — mapped, not silently swallowed (a
    rank passing them must not iterate the full dataset), and mixing
    the two spellings raises."""
    it = ShardedImageRecordIter(path_imgrec=rec_prefix + ".rec",
                                data_shape=(3, 20, 20), batch_size=6,
                                num_workers=2, part_index=1, num_parts=2)
    assert it._service.num_records == 36
    assert it._service.host_index == 1 and it._service.num_hosts == 2
    it.close()
    with pytest.raises(mx.base.MXNetError, match="not both"):
        ShardedImageRecordIter(path_imgrec=rec_prefix + ".rec",
                               data_shape=(3, 20, 20), batch_size=6,
                               part_index=0, num_parts=2, num_hosts=2)
    with pytest.warns(UserWarning, match="ignoring unsupported"):
        ShardedImageRecordIter(path_imgrec=rec_prefix + ".rec",
                               data_shape=(3, 20, 20), batch_size=6,
                               no_such_option=True).close()


def test_host_sharding_composes_on_top_of_workers(rec_prefix):
    """host_index/num_hosts shards the record set BEFORE worker
    sharding: two 2-worker hosts cover disjoint halves whose union is
    the full dataset."""
    kw = dict(path_imgrec=rec_prefix + ".rec", data_shape=(3, 20, 20),
              batch_size=6, shuffle=True, seed=2)
    seen = []
    for host in range(2):
        it = ShardedImageRecordIter(num_workers=2, host_index=host,
                                    num_hosts=2, **kw)
        assert it._service.num_records == 36
        for data, label, pad in _epoch_arrays(it):
            seen.extend(label[:len(label) - pad].tolist())
        it.close()
    assert len(seen) == 72
    assert sorted(set(seen)) == [0.0, 1.0, 2.0]


# ----------------------------------------------------------------------
# training-path parity
# ----------------------------------------------------------------------

def _convnet(classes=3):
    x = mx.sym.Variable("data")
    x = mx.sym.Convolution(x, num_filter=8, kernel=(3, 3), stride=(2, 2),
                           name="c1")
    x = mx.sym.Activation(x, act_type="relu")
    x = mx.sym.FullyConnected(x, num_hidden=classes, name="fc")
    return mx.sym.SoftmaxOutput(x, name="softmax")


def _fit_trajectory(it, steps_per_dispatch=1):
    """Train 2 epochs; returns (per-epoch train metric values, params)."""
    mx.random.seed(0)
    mod = mx.mod.Module(_convnet(), context=mx.cpu())
    metrics = []
    mod.fit(it, num_epoch=2, optimizer="sgd", initializer=mx.init.Xavier(),
            optimizer_params={"learning_rate": 0.05},
            eval_metric="ce",
            epoch_end_callback=lambda *a: None,
            batch_end_callback=lambda p: metrics.append(
                p.eval_metric.get()[1]),
            steps_per_dispatch=steps_per_dispatch)
    arg, _ = mod.get_params()
    return metrics, {k: v.asnumpy() for k, v in arg.items()}


def test_fit_matches_single_process_loss_trajectory(rec_prefix):
    """Module.fit through ShardedImageRecordIter + DeviceStagedIter
    (steps_per_dispatch=2 rides the staged path) matches the
    single-process ImageRecordIter run batch for batch."""
    kw = dict(path_imgrec=rec_prefix + ".rec", data_shape=(3, 20, 20),
              batch_size=12, shuffle=False, scale=1.0 / 255)
    ref = mx.io.ImageRecordIter(preprocess_threads=2, **kw)
    m_ref, p_ref = _fit_trajectory(ref, steps_per_dispatch=2)
    ref.close()
    it = ShardedImageRecordIter(num_workers=2, **kw)
    m_got, p_got = _fit_trajectory(it, steps_per_dispatch=2)
    it.close()
    assert len(m_ref) == len(m_got) > 0
    np.testing.assert_allclose(m_got, m_ref, rtol=1e-6)
    for k in p_ref:
        np.testing.assert_allclose(p_got[k], p_ref[k], rtol=1e-6, atol=1e-7)


# ----------------------------------------------------------------------
# failure + lifecycle
# ----------------------------------------------------------------------

def test_worker_crash_surfaces_clear_error(rec_prefix):
    svc = DataService(rec_prefix + ".rec", (3, 20, 20), 8, num_workers=2,
                      ring_slots=2)
    try:
        svc.begin_epoch(0)
        svc.next_batch()  # pipeline is live
        victim = svc._procs[1]
        victim.terminate()
        victim.join(timeout=10)
        with pytest.raises(DataWorkerError, match="worker 1 died"):
            for _ in range(svc.num_batches):
                svc.next_batch()
    finally:
        svc.close()


def test_close_is_bounded_after_worker_kill(rec_prefix):
    """The shutdown path survives a worker killed MID-RUN: the stop
    channel is a lock-free RawValue (a killed worker can die holding
    any lock it touches — a lock-protected Value/Event would poison
    the consumer's own close()), so close() returns promptly instead
    of hanging on a lock the dead worker can never release."""
    import time

    svc = DataService(rec_prefix + ".rec", (3, 20, 20), 8, num_workers=2,
                      ring_slots=2)
    svc.begin_epoch(0)
    svc.next_batch()
    svc._procs[0].kill()  # SIGKILL: no cleanup, locks die held
    t0 = time.time()
    svc.close()
    assert time.time() - t0 < 20.0
    assert svc.workers_alive() == 0


def test_worker_exception_forwards_traceback(tmp_path):
    """A poisoned record (undecodable payload) raises in the WORKER;
    the consumer gets the worker's own traceback in the error instead
    of a timeout."""
    from mxnet_tpu.recordio import MXIndexedRecordIO, pack

    bad = str(tmp_path / "poison")
    rec = MXIndexedRecordIO(bad + ".idx", bad + ".rec", "w")
    for i in range(4):
        rec.write_idx(i, pack((0, float(i), i, 0), b"this is not an image"))
    rec.close()
    svc = DataService(bad + ".rec", (3, 20, 20), 4, num_workers=1,
                      ring_slots=2)
    try:
        svc.begin_epoch(0)
        with pytest.raises(DataWorkerError, match="worker 0 raised"):
            for _ in range(svc.num_batches):
                svc.next_batch()
    finally:
        svc.close()


def test_service_close_idempotent_and_unlinks(rec_prefix):
    svc = DataService(rec_prefix + ".rec", (3, 20, 20), 8, num_workers=2,
                      ring_slots=2)
    names = [r.name for r in svc._rings]
    svc.begin_epoch(0)
    svc.next_batch()
    svc.close()
    svc.close()  # idempotent
    assert svc.workers_alive() == 0
    for name in names:
        assert not os.path.exists("/dev/shm/%s" % name.lstrip("/"))
    with pytest.raises(mx.base.MXNetError, match="closed"):
        svc.next_batch()


def test_slot_bytes_too_small_raises_clearly(rec_prefix):
    with pytest.raises(mx.base.MXNetError, match="MXTPU_DATA_SLOT_BYTES"):
        DataService(rec_prefix + ".rec", (3, 20, 20), 8, num_workers=1,
                    slot_bytes=64)


def test_iter_telemetry_books_the_namespace(rec_prefix):
    from mxnet_tpu import telemetry

    prev = telemetry.set_enabled(True)
    snap0 = telemetry.counter_value("data.batches_produced")
    try:
        it = ShardedImageRecordIter(path_imgrec=rec_prefix + ".rec",
                                    data_shape=(3, 20, 20), batch_size=8,
                                    num_workers=2)
        n = sum(1 for _ in it)
        it.close()
        snap = telemetry.snapshot()
        assert (telemetry.counter_value("data.batches_produced") - snap0
                == n == 9)
        h = snap["histograms"]["data.decode_seconds"]
        assert h["count"] >= 9 and h["sum"] > 0
        per_worker = [k for k in snap["counters"]
                      if k.startswith("data.worker_bytes.")]
        assert len(per_worker) == 2
        assert all(snap["counters"][k] > 0 for k in per_worker)
        assert snap["gauges"].get("data.workers_alive") == 0  # post-close
        assert "data.ring_occupancy" in snap["gauges"]
    finally:
        telemetry.set_enabled(prev)


def test_sanitizer_clean_epoch(rec_prefix):
    """The consumer-side pipeline (ThreadedIter fetch ops over the
    service) declares everything it touches: a full epoch under
    SanitizerEngine reports zero violations."""
    prev = engine.get().kind
    try:
        eng = engine.set_engine_type("SanitizerEngine", num_workers=2)
        with warnings.catch_warnings():
            warnings.simplefilter("error", RaceWarning)
            it = ShardedImageRecordIter(path_imgrec=rec_prefix + ".rec",
                                        data_shape=(3, 20, 20),
                                        batch_size=8, num_workers=2,
                                        shuffle=True, seed=1)
            total = 0
            for b in it:
                total += b.data[0].asnumpy().shape[0]
            it.close()
            mx.waitall()
        assert total == 72  # 9 batches x 8 (tail pad included)
        assert not getattr(eng, "violations", [])
    finally:
        engine.set_engine_type(prev)


def test_profiler_renders_per_worker_decode_lanes(rec_prefix, tmp_path):
    """Worker decode is visible in the trace: one data_decode(w<i>)
    lane per worker PROCESS (spans recorded consumer-side on the
    worker's behalf), named via thread metadata — so decode / io.stage
    / fit.dispatch overlap can be read off one timeline."""
    import json

    from mxnet_tpu import profiler

    fname = str(tmp_path / "data_profile.json")
    profiler.profiler_set_config(mode="all", filename=fname)
    profiler.profiler_set_state("run")
    it = ShardedImageRecordIter(path_imgrec=rec_prefix + ".rec",
                                data_shape=(3, 20, 20), batch_size=8,
                                num_workers=2)
    for _ in it:
        pass
    it.close()
    mx.waitall()
    profiler.profiler_set_state("stop")
    profiler.dump_profile()
    with open(fname) as f:
        events = json.load(f)["traceEvents"]
    decode = [e for e in events if e["name"].startswith("data_decode(w")]
    assert {e["name"] for e in decode} == {"data_decode(w0)",
                                           "data_decode(w1)"}
    lanes = {e["tid"] for e in decode}
    assert len(lanes) == 2  # one lane per worker, off every real thread
    names = {e["args"]["name"] for e in events
             if e.get("name") == "thread_name" and e["tid"] in lanes}
    # lane names carry the service instance, so two live services
    # (train + val iterators) never merge into one mislabeled lane
    assert len(names) == 2
    assert {n.split(" (service")[0] for n in names} == {"data worker 0",
                                                        "data worker 1"}
    # the consumer-side fetch pipeline shows as its own buffer gauge too
    assert any(e["name"] == "io.buffer.data_service" for e in events
               if e.get("ph") == "C")


# ----------------------------------------------------------------------
# satellite: the IN-PROCESS decode pool at N>1, for real
# ----------------------------------------------------------------------

def test_preprocess_threads_4_is_batch_identical_to_1(rec_prefix):
    """ImageRecordIter(preprocess_threads=4) produces batch-identical
    output to preprocess_threads=1 — through BOTH decode paths (native
    C++ pool and the Python fallback pool)."""
    for force_py in (False, True):
        epochs = []
        for nthreads in (1, 4):
            it = mx.io.ImageRecordIter(
                path_imgrec=rec_prefix + ".rec", data_shape=(3, 20, 20),
                batch_size=8, preprocess_threads=nthreads,
                force_python_decode=force_py)
            epochs.append(_epoch_arrays(it))
            it.close()
        for (d1, l1, p1), (d4, l4, p4) in zip(*epochs):
            assert (d1 == d4).all() and (l1 == l4).all() and p1 == p4


def test_python_decode_pool_has_4_live_workers(rec_prefix):
    """The pool is not decorative: with preprocess_threads=4 the
    iterator's executor really runs 4 concurrent workers (a barrier
    only 4 simultaneously-live threads can pass)."""
    import threading

    it = mx.io.ImageRecordIter(
        path_imgrec=rec_prefix + ".rec", data_shape=(3, 20, 20),
        batch_size=8, preprocess_threads=4, force_python_decode=True)
    next(it)  # decode traffic has flowed through the pool
    barrier = threading.Barrier(5, timeout=30)
    futs = [it._pool.submit(barrier.wait) for _ in range(4)]
    barrier.wait()  # passes only if all 4 workers are live concurrently
    for f in futs:
        f.result(timeout=30)
    assert len(it._pool._threads) >= 4
    it.close()


def test_native_decode_pool_at_4_threads_matches_1(rec_prefix):
    """The native imdecode pool (src/imdecode.cc) exercised at N>1 for
    real: the same batch decoded with a forced 4-thread pool is
    bit-identical to the 1-thread decode.  (The constructor caps
    nthreads at the host's cores — overridden here deliberately so the
    multi-thread path runs even on small CI hosts.)"""
    from mxnet_tpu.native import NativeImageDecoder, NativeRecordReader, \
        native_index
    from mxnet_tpu.recordio import unpack

    try:
        dec = NativeImageDecoder(1)
    except RuntimeError:
        pytest.skip("native imdecode unavailable (no toolchain/libjpeg)")
    offsets = native_index(rec_prefix + ".rec")[:16]
    reader = NativeRecordReader(rec_prefix + ".rec")
    payloads = []
    for off in offsets:
        _, payload = unpack(reader.read_at(off))
        payloads.append(bytes(payload))
    n = len(payloads)
    cu = cv = np.full((n,), 0.5, np.float32)
    mir = np.zeros((n,), np.uint8)
    mean = np.zeros((3,), np.float32)
    outs = []
    for nthreads in (1, 4):
        dec.nthreads = nthreads  # bypass the cpu-count cap: pool at N>1
        out = np.empty((n, 3, 20, 20), np.float32)
        status = dec.decode_batch(payloads, out, cu, cv, mir, mean)
        assert (status == 0).all()
        outs.append(out)
    assert (outs[0] == outs[1]).all()
    reader.close()
