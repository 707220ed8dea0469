"""Data iterators (parity: reference python/mxnet/io.py + src/io/).

Host-side pipeline feeding the device: the reference's C++ chain
(record parser → BatchLoader → Normalize → PrefetcherIter double-buffering,
reference src/io/iter_prefetcher.h:28-130) maps to python iterators with a
background prefetch thread; the heavy RecordIO/image path has a native C++
backend (src/recordio.cc via recordio.py ctypes bindings).
"""
from __future__ import annotations

import gzip
import os
import struct
from collections import namedtuple

import jax
import numpy as _np

from .base import MXNetError
from . import ndarray as nd
from .engine.threaded_iter import ThreadedIter
from .ndarray import NDArray, host_array

__all__ = [
    "DataDesc", "DataBatch", "DataIter", "NDArrayIter", "ResizeIter",
    "PrefetchingIter", "DeviceStagedIter", "StagedBlock", "MNISTIter",
    "CSVIter", "ImageRecordIter", "ImageDetRecordIter",
    "ShardedImageRecordIter", "stage_put",
]


class DataDesc(namedtuple("DataDesc", ["name", "shape"])):
    """Data description incl. dtype/layout (parity: io.py DataDesc)."""

    def __new__(cls, name, shape, dtype=_np.float32, layout="NCHW"):
        ret = super().__new__(cls, name, shape)
        ret.dtype = dtype
        ret.layout = layout
        return ret

    def __repr__(self):
        return "DataDesc[%s,%s,%s,%s]" % (self.name, self.shape, self.dtype, self.layout)

    @staticmethod
    def get_batch_axis(layout):
        if layout is None:
            return 0
        return layout.find("N")

    @staticmethod
    def get_list(shapes, types):
        if types is not None:
            type_dict = dict(types)
            return [DataDesc(x[0], x[1], type_dict[x[0]]) for x in shapes]
        return [DataDesc(x[0], x[1]) for x in shapes]


# XLA's CPU client takes a numpy buffer WITHOUT a copy only where it starts
# at this boundary (cpu_function_runtime::MinAlign); numpy's own
# allocations give 16
HOST_ALIGN = 64


def aligned_empty(shape, dtype):
    """Uninitialised C-ordered numpy memory that a host-resident NDArray
    can alias (ndarray.host_array): it starts at a HOST_ALIGN boundary."""
    dtype = _np.dtype(dtype)
    nbytes = int(_np.prod(shape, dtype=_np.int64)) * dtype.itemsize
    raw = _np.empty(nbytes + HOST_ALIGN, _np.uint8)
    at = -raw.ctypes.data % HOST_ALIGN
    return raw[at:at + nbytes].view(dtype).reshape(shape)


def _aligned(v, order=None):
    """The rows of `v` (in `order`, when given) where a batch cut from
    them can be aliased: `v` itself if it lies so, else one copy."""
    if order is not None:
        return _np.take(v, order, axis=0, mode="clip",
                        out=aligned_empty(v.shape, v.dtype))
    if v.flags.c_contiguous and v.ctypes.data % HOST_ALIGN == 0:
        return v
    out = aligned_empty(v.shape, v.dtype)
    out[...] = v
    return out


def desc_shape(desc):
    """Shape of a bind-style shape spec: DataDesc or plain (name, shape)."""
    return tuple(desc.shape) if hasattr(desc, "shape") else tuple(desc[1])


def redesc(desc, shape):
    """A DataDesc like `desc` (DataDesc or (name, shape) tuple) with a
    new shape — dtype/layout carried over when present."""
    if hasattr(desc, "shape"):
        return DataDesc(desc.name, shape, desc.dtype, desc.layout)
    return DataDesc(desc[0], shape)


class DataBatch:
    """One batch: data/label NDArray lists + pad/index (parity: io.py DataBatch)."""

    def __init__(self, data, label=None, pad=None, index=None, bucket_key=None,
                 provide_data=None, provide_label=None):
        self.data = data
        self.label = label
        self.pad = pad
        self.index = index
        self.bucket_key = bucket_key
        self.provide_data = provide_data
        self.provide_label = provide_label


class DataIter:
    """Base iterator (parity: io.py DataIter)."""

    def __init__(self, batch_size=0):
        self.batch_size = batch_size

    def __iter__(self):
        return self

    def reset(self):
        pass

    def next(self):
        if self.iter_next():
            return DataBatch(
                data=self.getdata(), label=self.getlabel(), pad=self.getpad(), index=self.getindex()
            )
        raise StopIteration

    def __next__(self):
        return self.next()

    def iter_next(self):
        pass

    def getdata(self):
        pass

    def getlabel(self):
        pass

    def getindex(self):
        return None

    def getpad(self):
        pass


class NDArrayIter(DataIter):
    """Iterate over in-memory arrays (parity: io.py NDArrayIter).

    The arrays are kept in host memory and a batch is a HOST-RESIDENT
    NDArray (`cpu()` context, as the reference's batches are;
    ndarray.host_array): `next()` copies nothing and touches no
    accelerator — a full batch is a view of the store, which is why the
    store is laid at a 64-byte boundary once, here (one copy of a source
    that does not start at one, or that is shuffled).  Whoever consumes
    the batch decides where it goes: DeviceStagedIter sends each device
    its own rows over that device's own host link, an executor places a
    whole batch on its device(s)."""

    def __init__(self, data, label=None, batch_size=1, shuffle=False,
                 last_batch_handle="pad", data_name="data", label_name="softmax_label"):
        super().__init__(batch_size)
        self.data = _init_data(data, allow_empty=False, default_name=data_name)
        self.label = _init_data(label, allow_empty=True, default_name=label_name)
        self.idx = _np.arange(self.data[0][1].shape[0])
        if shuffle:
            _np.random.shuffle(self.idx)
        order = self.idx if shuffle else None
        self.data = [(k, _aligned(v, order)) for k, v in self.data]
        self.label = [(k, _aligned(v, order)) for k, v in self.label]
        if last_batch_handle == "discard":
            new_n = self.data[0][1].shape[0] - self.data[0][1].shape[0] % batch_size
            self.idx = self.idx[:new_n]
        self.data_list = [x[1] for x in self.data] + [x[1] for x in self.label]
        self.num_source = len(self.data_list)
        self.num_data = self.idx.shape[0]
        assert self.num_data >= batch_size, "batch_size needs to be smaller than data size."
        self.cursor = -batch_size
        self.batch_size = batch_size
        self.last_batch_handle = last_batch_handle

    @property
    def provide_data(self):
        return [
            DataDesc(k, tuple([self.batch_size] + list(v.shape[1:])), v.dtype)
            for k, v in self.data
        ]

    @property
    def provide_label(self):
        return [
            DataDesc(k, tuple([self.batch_size] + list(v.shape[1:])), v.dtype)
            for k, v in self.label
        ]

    def hard_reset(self):
        self.cursor = -self.batch_size

    def reset(self):
        if self.last_batch_handle == "roll_over" and self.cursor > self.num_data:
            self.cursor = -self.batch_size + (self.cursor % self.num_data) % self.batch_size
        else:
            self.cursor = -self.batch_size

    def iter_next(self):
        self.cursor += self.batch_size
        return self.cursor < self.num_data

    def next(self):
        if self.iter_next():
            return DataBatch(
                data=self.getdata(), label=self.getlabel(), pad=self.getpad(), index=None
            )
        raise StopIteration

    def _getdata(self, data_source):
        assert self.cursor < self.num_data, "DataIter needs reset."
        if self.cursor + self.batch_size <= self.num_data:
            return [host_array(x[1][self.cursor : self.cursor + self.batch_size])
                    for x in data_source]
        pad = self.batch_size - self.num_data + self.cursor
        return [
            host_array(_np.concatenate(
                (x[1][self.cursor :], x[1][:pad]), axis=0,
                out=aligned_empty((self.batch_size,) + x[1].shape[1:], x[1].dtype)))
            for x in data_source
        ]

    def getdata(self):
        return self._getdata(self.data)

    def getlabel(self):
        return self._getdata(self.label)

    def getpad(self):
        if self.last_batch_handle == "pad" and self.cursor + self.batch_size > self.num_data:
            return self.cursor + self.batch_size - self.num_data
        return 0


def _init_data(data, allow_empty, default_name):
    """Normalize input data (parity: io.py _init_data)."""
    assert data is not None or allow_empty
    if data is None:
        data = []
    if isinstance(data, (_np.ndarray, NDArray)):
        data = [data]
    if isinstance(data, list):
        if not allow_empty:
            assert len(data) > 0
        if len(data) == 1:
            data = {default_name: data[0]}
        else:
            data = {"_%d_%s" % (i, default_name): d for i, d in enumerate(data)}
    if not isinstance(data, dict):
        raise TypeError("Input must be NDArray, numpy.ndarray, a list of them or dict with them as values")
    for k, v in data.items():
        if isinstance(v, NDArray):
            data[k] = v.asnumpy()
    return list(sorted(data.items()))


class ResizeIter(DataIter):
    """Resize another iterator to `size` batches per epoch (parity: io.py ResizeIter)."""

    def __init__(self, data_iter, size, reset_internal=True):
        super().__init__()
        self.data_iter = data_iter
        self.size = size
        self.reset_internal = reset_internal
        self.cur = 0
        self.current_batch = None
        self.provide_data = data_iter.provide_data
        self.provide_label = data_iter.provide_label
        self.batch_size = data_iter.batch_size

    def reset(self):
        self.cur = 0
        if self.reset_internal:
            self.data_iter.reset()

    def iter_next(self):
        if self.cur == self.size:
            return False
        try:
            self.current_batch = self.data_iter.next()
        except StopIteration:
            self.data_iter.reset()
            self.current_batch = self.data_iter.next()
        self.cur += 1
        return True

    def next(self):
        if self.iter_next():
            return self.current_batch
        raise StopIteration

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


class PrefetchingIter(DataIter):
    """Engine-backed prefetch over one or more iterators (parity: io.py
    PrefetchingIter; reference double-buffering iter_prefetcher.h:96-118
    over dmlc threadediter — here each batch fetch is one engine op, so
    decode overlaps with device compute on the engine's worker pool and
    `mx.waitall()` fences IO along with everything else)."""

    def __init__(self, iters, rename_data=None, rename_label=None):
        super().__init__()
        if not isinstance(iters, list):
            iters = [iters]
        self.n_iter = len(iters)
        assert self.n_iter > 0
        self.iters = iters
        self.rename_data = rename_data
        self.rename_label = rename_label
        self.batch_size = self.provide_data[0][1][0]
        self._bg_iters = None
        self.current_batch = [None for _ in range(self.n_iter)]
        self._start_prefetch()

    def _start_prefetch(self):
        self._bg_iters = [
            ThreadedIter(it.next, max_prefetch=2, name="prefetch_%d" % i)
            for i, it in enumerate(self.iters)
        ]

    def _stop_prefetch(self):
        """Stop background fetching and DRAIN it: after this returns no
        engine op is still calling into the wrapped iterators, so the
        caller may safely reset or destroy them.  Idempotent — reset()
        cycles and repeated close() calls must not double-release (or
        leak one fetch pipeline per epoch)."""
        if self._bg_iters is not None:
            for bg in self._bg_iters:
                bg.close()
        self._bg_iters = None

    def close(self):
        """Final teardown: drain this iterator's prefetch ops AND close the
        wrapped iterators (joining any worker threads they own, e.g.
        ImageRecordIter's decode pool).  Idempotent; the iterator is not
        usable afterwards (unlike reset(), which restarts prefetch)."""
        self._stop_prefetch()
        for it in self.iters:
            inner_close = getattr(it, "close", None)
            if callable(inner_close):
                inner_close()

    def __del__(self):
        if self._bg_iters is not None:
            for bg in self._bg_iters:
                bg.cancel()

    @property
    def provide_data(self):
        if self.rename_data is None:
            return sum([i.provide_data for i in self.iters], [])
        return sum(
            [
                [DataDesc(r[x.name], x.shape, x.dtype) if isinstance(x, DataDesc) else DataDesc(r[x[0]], x[1])
                 for x in i.provide_data]
                for r, i in zip(self.rename_data, self.iters)
            ],
            [],
        )

    @property
    def provide_label(self):
        if self.rename_label is None:
            return sum([i.provide_label for i in self.iters], [])
        return sum(
            [
                [DataDesc(r[x.name], x.shape, x.dtype) if isinstance(x, DataDesc) else DataDesc(r[x[0]], x[1])
                 for x in i.provide_label]
                for r, i in zip(self.rename_label, self.iters)
            ],
            [],
        )

    def reset(self):
        self._stop_prefetch()
        for it in self.iters:
            it.reset()
        self._start_prefetch()

    def iter_next(self):
        batches = []
        for bg in self._bg_iters:
            try:
                batches.append(next(bg))
            except StopIteration:
                batches.append(None)
        if any(b is None for b in batches):
            return False
        self.current_batch = batches
        return True

    def next(self):
        if self.iter_next():
            if self.n_iter == 1:
                return self.current_batch[0]
            return DataBatch(
                data=sum([b.data for b in self.current_batch], []),
                label=sum([b.label for b in self.current_batch], []),
                pad=self.current_batch[0].pad,
                index=self.current_batch[0].index,
            )
        raise StopIteration

    def getdata(self):
        return sum([b.data for b in self.current_batch], [])

    def getlabel(self):
        return sum([b.label for b in self.current_batch], [])

    def getindex(self):
        return self.current_batch[0].index

    def getpad(self):
        return self.current_batch[0].pad


class StagedBlock:
    """K training batches stacked on a new leading axis, resident on
    device: the unit of work of the K-step fused dispatch
    (Executor.fused_update_block).  DeviceStagedIter stacks it on the
    device from the K steps' arrays; the batches' data never returns to
    the host.

    * ``data`` / ``label`` — lists of (K, ...) device arrays aligned with
      ``provide_data`` / ``provide_label``;
    * ``label_host`` — per-step numpy labels ([[arr, ...] per step]): the
      one thing staging reads to the host, so update_metric never reads
      the device block back;
    * ``count`` — number of real steps K (the last block of an epoch may
      be short);
    * ``pad`` — pad rows of the FINAL step (earlier steps are full);
    * ``seq`` — the block's number within its DeviceStagedIter (0 when
      built by hand): the ``block`` attribute of the spans that staged
      it and of the one that dispatches it.
    """

    __slots__ = ("data", "label", "label_host", "count", "pad", "seq",
                 "_mem_booked")

    def __init__(self, data, label, label_host, count, pad=0, seq=0):
        self.seq = seq
        self.data = data
        self.label = label
        self.label_host = label_host
        self.count = count
        self.pad = pad
        # live-buffer census: a staged block pins device memory from
        # H2D until the fused dispatch consumes (donates) it — book it
        # so "what is holding bytes right now" can name staging depth
        self._mem_booked = 0
        from . import telemetry

        if telemetry.enabled():
            from .obs import memory

            self._mem_booked = sum(
                int(getattr(a, "nbytes", 0) or 0)
                for a in list(self.data) + list(self.label))
            memory.book("staged_blocks", self._mem_booked)

    def __del__(self):
        try:
            booked, self._mem_booked = self._mem_booked, 0
            if booked:
                from .obs import memory

                memory.unbook("staged_blocks", booked)
        except Exception:  # pragma: no cover — interpreter teardown
            pass


def _book_staged(nbytes):
    """One staged input in the `io.stage_bytes` / `io.stage_block_bytes`
    books."""
    from . import telemetry

    if telemetry.enabled():
        telemetry.inc("io.stage_bytes", int(nbytes))
        # size DISTRIBUTION too: whether transfers are big enough to
        # amortize the per-transfer overhead is a bucket question
        telemetry.observe("io.stage_block_bytes", int(nbytes),
                          buckets=telemetry.BYTE_BUCKETS)


def stage_put(name, arr, place_fn=None):
    """Count and place ONE stacked host input: the H2D of the serving
    continuous batcher's request batches (serving/session.py).  The
    bytes land in the books DeviceStagedIter keeps for training blocks
    (`io.stage_bytes` / `io.stage_block_bytes`), so "is the host feeding
    the device in big-enough transfers" has one answer across both
    pipelines.  `place_fn(name, arr)` does the actual device placement;
    None keeps the array host-side."""
    _book_staged(arr.nbytes)
    return place_fn(name, arr) if place_fn is not None else arr


def _in_host_memory(a):
    """Is the batch array `a` in host memory — numpy, or a host-resident
    NDArray (nd.host_array) of a process that computes elsewhere?"""
    return (not isinstance(a, NDArray)
            or nd.off_platform(a.data, jax.default_backend()))


def _to_host(a):
    """One array of a batch as numpy that stays what it is: a host
    array is copied (its source may refill the buffer), the read-back
    of an NDArray from a device is counted in `executor.d2h_bytes`, so
    the transfer books balance, and a host-resident NDArray is read
    where it lies: no device is waited for and nothing is counted."""
    if not isinstance(a, NDArray):
        return _np.array(a)
    out = a.asnumpy()
    from . import telemetry

    if telemetry.enabled() and not _in_host_memory(a):
        telemetry.inc("executor.d2h_bytes", int(out.nbytes))
    return out


class DeviceStagedIter(DataIter):
    """Async device staging: groups K batches from `data_iter` into one
    StagedBlock, assembled ON THE DEVICE(S) from a BACKGROUND engine op,
    so the host decode + H2D of later blocks overlap block N's device
    compute — the tf.data prefetch-to-device recipe layered on the
    reference's double-buffered PrefetcherIter (src/io/iter_prefetcher.h).
    Module.fit takes block N+1 and dispatches it while block N runs, so
    the staging op works two and three blocks ahead of the devices; it
    is held back by its buffers alone (below) and by the links, never by
    the devices' compute queue: the one device program it enqueues, the
    stack, it does not wait for beside a chip
    (Executor.stack_block_input).

    The fetch rides engine.ThreadedIter (one engine op per block on the
    shared worker pool, its iterator var declared as the op's write set,
    so SanitizerEngine sees a fully-declared pipeline and `mx.waitall()`
    fences staging along with everything else).  `buffers` blocks are
    kept in flight (default 2 = classic double buffering).
    Each staging op records an ``io.stage`` profiler span, so overlap
    with the ``fit.dispatch`` lane is visible in the trace.

    The host neither stacks nor reads a batch's data back.
    `place_fn(name, array)` lays ONE step's array out over the devices,
    from where the source left it — a batch in host memory (numpy, or
    the host-resident NDArray of an NDArrayIter) goes to each device as
    that device's own rows over that device's own host link, once; the
    NDArray of a device-resident batch (an iterator that ends in
    `nd.array`) moves chip to chip — and `stack_fn(name, steps)` stacks
    the K results where they lie.  Module.fit passes
    Executor.place_step_input and Executor.stack_block_input, so a block
    carries the executor's block_input_sharding().
    `io.stage.host_parts` / `io.stage.device_parts` count which way the
    step arrays came, as place_fn says.
    Without the pair, blocks are stacked and stay on the host and the
    executor places them at dispatch (no overlap, same results).
    """

    def __init__(self, data_iter, steps_per_dispatch=None, place_fn=None,
                 buffers=2, stack_fn=None):
        super().__init__()
        from . import config

        self._inner = data_iter
        k = (steps_per_dispatch if steps_per_dispatch is not None
             else config.get("MXTPU_STEPS_PER_DISPATCH"))
        self._k = max(1, int(k))
        if (place_fn is None) != (stack_fn is None):
            raise MXNetError("DeviceStagedIter: place_fn and stack_fn come "
                             "as a pair (Executor.place_step_input, "
                             "Executor.stack_block_input)")
        # either place_fn answers (came from host memory?, placed)
        self._place_fn = place_fn or (
            lambda name, a: (_in_host_memory(a), _to_host(a)))
        self._stack_fn = stack_fn or (
            lambda name, steps: _np.stack([s for _, s in steps]))
        self._buffers = max(1, int(buffers))
        self.batch_size = getattr(data_iter, "batch_size", 0)
        self._bg = None
        self._seq = 0  # blocks staged so far: the `block` of their spans
        self._in_flight = None  # what place_fn made of the last step
        self._start()

    @property
    def steps_per_dispatch(self):
        return self._k

    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label

    def _start(self):
        self._bg = ThreadedIter(self._fetch_block, max_prefetch=self._buffers,
                                name="h2d_stage")

    def _names(self, descs):
        return [d.name if isinstance(d, DataDesc) else d[0] for d in descs]

    def _fetch_block(self):
        """One staging op: pull up to K batches, placing each step's
        arrays as soon as it is fetched (a full batch is let go before
        the next one is asked for, and at most two steps are in flight,
        so a device holds few of them at a time), then stack them on
        the device.  Runs on an engine worker
        while the consumer's previous block computes on device.  The
        whole op is one `io.stage` span and its legs are
        `io.stage.fetch` / `.put` (one of each per step) and `.stack` /
        `.readback` (labels only); all carry the block's number, as
        does the `fit.dispatch` span that consumes the block."""
        from . import profiler, telemetry

        seq = self._seq = self._seq + 1
        names = self._names(self.provide_data) \
            + self._names(self.provide_label)
        rows, labels = [], []  # per step: what place_fn made; the labels
        parts = {True: 0, False: 0}  # arrived on the device? -> count
        pad = 0
        with profiler.span("io.stage", cat="io", hist="io.h2d_stage_seconds",
                           block=seq):
            while len(rows) < self._k:
                with profiler.span("io.stage.fetch", cat="io",
                                   hist="io.stage.fetch_seconds", block=seq):
                    try:
                        batch = self._inner.next()
                    except StopIteration:
                        break
                # the ENQUEUE of this step's split and chip-to-chip
                # copies (device arrays) or H2D (host arrays), then the
                # wait for the step BEFORE it to have arrived: a device
                # allocates its buffers when work is enqueued, so a
                # staging thread that ran ahead of the link would have
                # a whole block's full batches and pieces allocated at
                # once (+3 GB on the chip that feeds four, PERF.md PR 24)
                with profiler.span("io.stage.put", cat="io",
                                   hist="io.stage.put_seconds", block=seq):
                    rows.append(self._place_step(batch, names, parts))
                    jax.block_until_ready(self._in_flight)
                    self._in_flight = rows[-1]
                labels.append(batch.label)
                pad = batch.pad or 0
                del batch  # the data, before the next fetch
            if not rows:
                raise StopIteration
            block = self._assemble(names, rows, labels, pad, seq)
        if telemetry.enabled():
            telemetry.inc("io.blocks_staged")
            telemetry.inc("io.stage.device_parts", parts[True])
            telemetry.inc("io.stage.host_parts", parts[False])
        return block

    def _place_step(self, batch, names, parts):
        arrays = list(batch.data) + list(batch.label or [])
        placed = [self._place_fn(name, a) for name, a in zip(names, arrays)]
        for from_host, _ in placed:
            parts[not from_host] += 1
        return placed

    def _assemble(self, names, rows, labels, pad, seq):
        """The StagedBlock of the placed steps `rows`.  No data array
        comes to the host in staging: only the labels are read, for
        label_host."""
        from . import profiler

        blocks = []
        for name, steps in zip(names, zip(*rows)):
            # the enqueue of the device stack (host arrays: and the
            # wait that frees the source's buffers, see
            # Executor.stack_block_input)
            with profiler.span("io.stage.stack", cat="io",
                               hist="io.stage.stack_seconds", block=seq):
                blocks.append(self._stack_fn(name, steps))
            _book_staged(blocks[-1].nbytes)
        label_host = None
        if labels[0]:
            # a label waits here for its batch's own H2D, which is
            # queued behind the data's
            with profiler.span("io.stage.readback", cat="io",
                               hist="io.stage.readback_seconds", block=seq):
                label_host = [[_to_host(a) for a in row] for row in labels]
        n_data = len(self.provide_data)
        return StagedBlock(blocks[:n_data], blocks[n_data:], label_host,
                           len(rows), pad=pad, seq=seq)

    def next(self):
        if self._bg is None:
            raise MXNetError("DeviceStagedIter is closed (reset() restarts "
                             "a live iterator; a closed one is done)")
        return next(self._bg)

    def iter_next(self):
        raise NotImplementedError("DeviceStagedIter yields StagedBlocks; "
                                  "iterate with next()")

    def reset(self):
        """Drain in-flight staging ops, rewind the source, restart the
        lookahead.  Idempotent per cycle — no staging pipeline survives
        from the previous epoch."""
        self.close()
        self._inner.reset()
        self._start()

    def close(self):
        """Stop staging and drain outstanding ops (after this returns the
        source iterator is no longer being read, so the owner may reset
        or destroy it).  Idempotent.  Does NOT close the source — the
        training loop owns its lifetime."""
        if self._bg is not None:
            self._bg.close()
        self._bg = None
        self._in_flight = None

    def __del__(self):
        if getattr(self, "_bg", None) is not None:
            self._bg.cancel()


class MNISTIter(NDArrayIter):
    """MNIST raw-ubyte reader (parity: reference src/io/iter_mnist.cc:61-241).

    Reads idx-format image/label files (optionally .gz); `flat` controls
    (B,784) vs (B,1,28,28).
    """

    def __init__(self, image="train-images-idx3-ubyte", label="train-labels-idx1-ubyte",
                 batch_size=128, shuffle=True, flat=False, silent=False, seed=0,
                 input_shape=None, **kwargs):
        images = _read_idx_images(image)
        labels = _read_idx_labels(label)
        images = images.astype(_np.float32) / 255.0
        if flat:
            images = images.reshape(images.shape[0], -1)
        else:
            images = images.reshape(images.shape[0], 1, images.shape[1], images.shape[2])
        super().__init__(
            images, labels.astype(_np.float32), batch_size=batch_size,
            shuffle=bool(shuffle), last_batch_handle="discard",
            data_name="data", label_name="softmax_label",
        )


def _open_maybe_gz(path):
    if not os.path.exists(path) and os.path.exists(path + ".gz"):
        path = path + ".gz"
    if path.endswith(".gz"):
        return gzip.open(path, "rb")
    return open(path, "rb")


def _read_idx_images(path):
    with _open_maybe_gz(path) as f:
        magic, num, rows, cols = struct.unpack(">IIII", f.read(16))
        if magic != 2051:
            raise MXNetError("Invalid MNIST image file %s" % path)
        data = _np.frombuffer(f.read(num * rows * cols), dtype=_np.uint8)
        return data.reshape(num, rows, cols)


def _read_idx_labels(path):
    with _open_maybe_gz(path) as f:
        magic, num = struct.unpack(">II", f.read(8))
        if magic != 2049:
            raise MXNetError("Invalid MNIST label file %s" % path)
        return _np.frombuffer(f.read(num), dtype=_np.uint8)


class CSVIter(NDArrayIter):
    """CSV reader (parity: reference src/io/iter_csv.cc)."""

    def __init__(self, data_csv, data_shape, label_csv=None, label_shape=(1,),
                 batch_size=128, round_batch=True, **kwargs):
        data = _np.loadtxt(data_csv, delimiter=",", dtype=_np.float32)
        data = data.reshape((-1,) + tuple(data_shape))
        if label_csv is not None:
            label = _np.loadtxt(label_csv, delimiter=",", dtype=_np.float32)
            label = label.reshape((-1,) + tuple(label_shape))
            if label_shape == (1,):
                label = label.reshape(-1)
        else:
            label = _np.zeros((data.shape[0],), dtype=_np.float32)
        super().__init__(
            data, label, batch_size=batch_size,
            last_batch_handle="pad" if round_batch else "discard",
        )


def ImageRecordIter(**kwargs):
    """RecordIO-packed image iterator (reference src/io/iter_image_recordio_2.cc).

    Implemented over the native C++ RecordIO reader — see image_io.py.
    """
    from .image_io import ImageRecordIterImpl

    return ImageRecordIterImpl(**kwargs)


def ImageDetRecordIter(**kwargs):
    """Detection record iterator with bbox-aware augmentation
    (reference src/io/iter_image_det_recordio.cc) — see det_io.py."""
    from .det_io import ImageDetRecordIterImpl

    return ImageDetRecordIterImpl(**kwargs)


def ShardedImageRecordIter(**kwargs):
    """Multi-process sharded RecordIO image iterator (mxnet_tpu.data):
    ``num_workers`` decode PROCESSES (default 2) feed batches through
    shared-memory rings, with deterministic ``(seed, epoch)`` coverage,
    per-host sharding composed on top (``host_index``/``num_hosts``),
    and worker-crash detection.  Same
    decode/augment surface as ``ImageRecordIter``; plugs into
    ``DeviceStagedIter``/``Module.fit`` like any DataIter.  See
    docs/data.md."""
    from .data.iter import ShardedImageRecordIter as _Impl

    return _Impl(**kwargs)
