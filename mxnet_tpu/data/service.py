"""DataService — sharded multi-process input pipeline.

The host side of the reference's whole I/O story (dmlc threadediter +
RecordIO + the imdecode engine, PAPER ⚙18) scaled out across
PROCESSES: N worker processes each own the batches ``b ≡ w (mod N)``
of one RecordIO file's epoch order and run read → native JPEG decode
(src/imdecode.cc pool) → augment → batch-assemble, handing finished
batches to the trainer over shared-memory rings (data/shm.py —
pickle-free for the hot ndarray payload) with backpressure from a
bounded free-slot queue.

Determinism is the design center: the epoch order is a pure function
of ``(seed, epoch)`` (worker.epoch_order) and the consumer reassembles
batches in GLOBAL BATCH-INDEX order (round-robin over workers), so the
batch sequence is identical for ANY worker count — a 4-worker epoch is
byte-identical to a 1-worker epoch, which (augmentation off) is
byte-identical to a single-process ``ImageRecordIter`` epoch.  Every
shard record appears exactly once per epoch across all workers.

Per-host sharding composes ON TOP of worker sharding: ``host_index /
num_hosts`` stride-shards the record set first (the same arithmetic
``ImageRecordIter(part_index=, num_parts=)`` uses — image_io.py
shard_offsets), then the host's workers split the surviving batches —
the input story the multi-process SPMD mesh needs, for free.

Worker death is detected, not hung on: a crashed worker (OOM kill, bad
record, import error) surfaces as a ``DataWorkerError`` at the
consumer with the worker's exit code or forwarded traceback.
"""
from __future__ import annotations

import itertools as _itertools
import multiprocessing as _mp
import queue as _queue
import time

import numpy as _np

from ..base import MXNetError
from .worker import STOP_EPOCH, worker_main

__all__ = ["DataService", "DataWorkerError"]

# synthetic chrome-trace lane ids for worker-process decode spans (real
# thread ids are process-local, so consumer-side recording needs its own
# namespace well above any plausible kernel tid); each service instance
# gets its own lane block so two live services (train + val iterators)
# never merge their workers into one mislabeled lane
_WORKER_TID_BASE = 0x7D000000
_SERVICE_SEQ = _itertools.count()


class DataWorkerError(MXNetError):
    """A data-service worker process died or raised; the consumer gets
    the worker id plus its exit code or forwarded traceback."""


def _mp_context():
    """fork where the platform has it (workers inherit the already-built
    native libs and skip re-importing the framework), spawn otherwise."""
    methods = _mp.get_all_start_methods()
    return _mp.get_context("fork" if "fork" in methods else "spawn")


class DataService:
    """Spawn ``num_workers`` decode processes over one RecordIO file and
    consume their batches in deterministic epoch order.

    Protocol: :meth:`begin_epoch` starts (or restarts) an epoch;
    :meth:`next_batch` returns ``(data, label, pad, meta)`` numpy copies
    until the epoch's ``num_batches`` are consumed, then raises
    StopIteration; :meth:`close` joins the workers and unlinks the
    shared-memory rings (idempotent).  ``ShardedImageRecordIter``
    (data/iter.py) wraps this in the standard DataIter contract.
    """

    def __init__(self, path_imgrec, data_shape, batch_size, num_workers=2,
                 label_width=1, shuffle=False, seed=0, host_index=None,
                 num_hosts=None, ring_slots=4, slot_bytes=None,
                 rand_crop=False, rand_mirror=False, mean_r=0.0, mean_g=0.0,
                 mean_b=0.0, scale=1.0, resize=0, preprocess_threads=1,
                 force_python_decode=False):
        from .. import config
        from ..image_io import shard_offsets
        from ..native import native_index
        from .shm import ShmRing, slot_bytes_needed

        if path_imgrec is None or data_shape is None:
            raise MXNetError("path_imgrec and data_shape are required")
        self.path = path_imgrec
        self.data_shape = tuple(int(d) for d in data_shape)
        self.batch_size = int(batch_size)
        self.label_width = int(label_width)
        self.num_workers = int(num_workers)
        if self.num_workers < 1:
            raise MXNetError("num_workers must be >= 1 (got %d)"
                             % self.num_workers)
        self.host_index = int(host_index if host_index is not None
                              else config.get("MXTPU_DATA_HOST_INDEX"))
        self.num_hosts = int(num_hosts if num_hosts is not None
                             else config.get("MXTPU_DATA_NUM_HOSTS"))
        ring_slots = int(ring_slots)
        if ring_slots < 1:
            raise MXNetError("ring_slots must be >= 1 (got %d)" % ring_slots)
        need = slot_bytes_needed(self.batch_size, self.data_shape,
                                 self.label_width)
        slot_bytes = int(slot_bytes if slot_bytes is not None
                         else config.get("MXTPU_DATA_SLOT_BYTES"))
        if slot_bytes <= 0:
            slot_bytes = need
        elif slot_bytes < need:
            raise MXNetError(
                "MXTPU_DATA_SLOT_BYTES=%d is smaller than one batch "
                "(batch %d x %s float32 + label = %d bytes); raise it or "
                "leave it 0 for auto sizing"
                % (slot_bytes, self.batch_size, self.data_shape, need))
        self._ring_slots = ring_slots
        self._slot_bytes = slot_bytes

        # the host shard, resolved consumer-side too: num_batches (and so
        # epoch length) must be known without waiting on any worker
        offsets = shard_offsets(native_index(path_imgrec), self.host_index,
                                self.num_hosts)
        if not offsets:
            raise MXNetError("no records in host shard %d/%d of %s"
                             % (self.host_index, self.num_hosts, path_imgrec))
        self.num_records = len(offsets)
        self.num_batches = -(-self.num_records // self.batch_size)

        self._seed = int(seed)
        self._shuffle = bool(shuffle)
        self._svc_seq = next(_SERVICE_SEQ)  # profiler lane block
        spec = {
            "path": path_imgrec, "batch_size": self.batch_size,
            "data_shape": self.data_shape, "label_width": self.label_width,
            "num_workers": self.num_workers, "seed": self._seed,
            "shuffle": self._shuffle, "host_index": self.host_index,
            "num_hosts": self.num_hosts, "ring_slots": ring_slots,
            "slot_bytes": slot_bytes, "rand_crop": bool(rand_crop),
            "rand_mirror": bool(rand_mirror),
            "mean": [float(mean_r), float(mean_g), float(mean_b)],
            "scale": float(scale), "resize": int(resize),
            "preprocess_threads": int(preprocess_threads),
            "force_python_decode": bool(force_python_decode),
        }

        ctx = _mp_context()
        # the abort/stop channel: workers bail out of any epoch that is
        # no longer the latest (STOP_EPOCH = shut down).  LOCK-FREE
        # (RawValue) on purpose — a worker killed mid-run can die
        # holding any lock it touches, and a lock-protected Value/Event
        # shared by every process would then hang the consumer's own
        # close(); a raw aligned word with a single writer (this
        # process) cannot be left locked (data/worker.py)
        self._latest = ctx.Value("l", -1, lock=False)
        self._rings, self._free_qs, self._full_qs, self._cmd_qs = [], [], [], []
        self._procs = []
        self._closed = False
        self._epoch = None
        self._cursor = 0
        self._done = [True] * self.num_workers  # nothing to drain yet
        try:
            for w in range(self.num_workers):
                ring = ShmRing(ring_slots, slot_bytes)
                free_q, full_q, cmd_q = ctx.Queue(), ctx.Queue(), ctx.Queue()
                for s in range(ring_slots):
                    free_q.put(s)
                self._rings.append(ring)
                self._free_qs.append(free_q)
                self._full_qs.append(full_q)
                self._cmd_qs.append(cmd_q)
            import warnings

            for w in range(self.num_workers):
                p = ctx.Process(
                    target=worker_main,
                    args=(spec, w, self._rings[w].name, self._free_qs[w],
                          self._full_qs[w], self._cmd_qs[w], self._latest),
                    name="mxtpu-data-worker-%d" % w, daemon=True)
                with warnings.catch_warnings():
                    # JAX warns about fork-with-threads at every fork;
                    # the worker never touches JAX/XLA (numpy + ctypes
                    # decode only), so the caution does not apply here
                    warnings.filterwarnings(
                        "ignore", message=".*fork.*",
                        category=RuntimeWarning)
                    p.start()
                self._procs.append(p)
        except Exception:
            self.close()
            raise

    # ------------------------------------------------------------------
    def workers_alive(self):
        """How many worker processes are currently alive."""
        return sum(1 for p in self._procs if p.is_alive())

    def _check(self):
        if self._closed:
            raise MXNetError("DataService is closed")

    def _get(self, w):
        """Next message from worker `w`'s full queue, with crash
        detection: a dead worker raises DataWorkerError instead of
        hanging the trainer."""
        q = self._full_qs[w]
        while True:
            try:
                return q.get(timeout=0.2)
            except _queue.Empty:
                p = self._procs[w]
                if not p.is_alive():
                    # final sweep: messages can outlive their producer
                    try:
                        return q.get_nowait()
                    except _queue.Empty:
                        from .. import telemetry

                        if telemetry.enabled():
                            telemetry.set_gauge("data.workers_alive",
                                                self.workers_alive())
                        raise DataWorkerError(
                            "data worker %d died (exit code %s) while the "
                            "consumer waited for batch %d of epoch %s — "
                            "check the worker's stderr; a poisoned record "
                            "or host OOM kill are the usual causes"
                            % (w, p.exitcode, self._cursor, self._epoch))

    def _next_msg(self, w):
        """Next CURRENT-epoch message from worker `w`, recycling any
        stale leftovers from an aborted epoch and re-raising forwarded
        worker errors."""
        while True:
            msg = self._get(w)
            kind = msg[0]
            if kind == "error":
                raise DataWorkerError(
                    "data worker %d raised:\n%s" % (msg[1], msg[2]))
            if msg[1] != self._epoch:  # aborted-epoch leftovers
                if kind == "batch":
                    self._free_qs[w].put(msg[3])
                continue
            return msg

    def begin_epoch(self, epoch, start_batch=0):
        """Start epoch `epoch`: abort + drain whatever the workers were
        doing, then command every worker into the new epoch.  The batch
        sequence that follows depends only on ``(seed, epoch)``.

        ``start_batch`` > 0 is the exact-resume fast-forward
        (ckpt/resume.py): workers recompute the pure epoch order and
        jump straight to their first batch index >= start_batch — no
        record is read or decoded for the skipped prefix — and the
        consumer cursor starts there too, so delivery continues in
        global order exactly where the interrupted run stopped."""
        self._check()
        epoch = int(epoch)
        start_batch = int(start_batch)
        self._latest.value = epoch  # workers bail out of older epochs
        self._drain()
        for q in self._cmd_qs:
            q.put(("epoch", epoch, start_batch))
        self._epoch = epoch
        self._cursor = start_batch
        self._done = [False] * self.num_workers

    def _drain(self):
        """Consume until every worker has closed its current epoch (the
        ``done`` marker), recycling slots — after this no worker holds a
        slot and no stale message is in flight."""
        if self._epoch is None:
            return
        for w in range(self.num_workers):
            while not self._done[w]:
                msg = self._next_msg(w)
                if msg[0] == "batch":
                    self._free_qs[w].put(msg[3])
                elif msg[0] == "done":
                    self._done[w] = True

    def next_batch(self):
        """The next batch of the running epoch, in global batch-index
        order: ``(data, label, pad, meta)`` where data/label are fresh
        numpy arrays (the shm slot is recycled immediately), ``pad`` is
        the wrapped-row count of a tail batch, and ``meta`` carries the
        producing worker's stats (decode seconds, bytes, timestamps).
        Raises StopIteration once the epoch's batches are consumed."""
        self._check()
        if self._epoch is None:
            raise MXNetError("no epoch started: call begin_epoch() first")
        if self._cursor >= self.num_batches:
            self._drain()  # collect the done markers, recycle stragglers
            raise StopIteration
        w = self._cursor % self.num_workers
        msg = self._next_msg(w)
        if msg[0] == "done":
            self._done[w] = True
            raise DataWorkerError(
                "data worker %d finished epoch %d after producing only "
                "part of its batches (consumer expected batch %d) — the "
                "worker and consumer disagree about the shard size"
                % (w, self._epoch, self._cursor))
        _, _, seq, slot, pad, meta = msg
        if seq != self._cursor:
            # never deliver out of global order: the determinism
            # guarantee (docs/data.md) is worthless if a protocol
            # desync slips through silently (and `assert` would vanish
            # under python -O)
            raise DataWorkerError(
                "data worker %d delivered batch %d of epoch %s where the "
                "consumer expected batch %d — worker/consumer protocol "
                "desynchronized" % (w, seq, self._epoch, self._cursor))
        from .shm import batch_views

        buf = self._rings[w].slot_buffer(slot)
        data_v, label_v = batch_views(buf, self.batch_size, self.data_shape,
                                      self.label_width)
        data = data_v.copy()
        label = label_v.copy()
        del data_v, label_v, buf  # release the shm views before recycling
        self._free_qs[w].put(slot)
        self._cursor += 1
        self._book(meta)
        return data, label, pad, meta

    def _book(self, meta):
        """Consumer-side telemetry/profiler booking from worker stats —
        worker processes cannot reach this process's registry, so the
        consumer books on their behalf (docs/observability.md)."""
        from .. import profiler, telemetry

        if telemetry.enabled():
            telemetry.inc("data.batches_produced")
            telemetry.observe("data.decode_seconds", meta["decode_s"])
            telemetry.inc("data.worker_bytes.w%d" % meta["w"], meta["bytes"])
            telemetry.set_gauge("data.ring_occupancy", self._occupancy())
            telemetry.set_gauge("data.workers_alive", self.workers_alive())
        if profiler.spans_active():
            tid = (_WORKER_TID_BASE + ((self._svc_seq & 0x3FFF) << 8)
                   + meta["w"])
            profiler.register_thread_name(
                tid, "data worker %d (service %d)"
                % (meta["w"], self._svc_seq))
            profiler.record_span("data_decode(w%d)" % meta["w"],
                                 meta["t0_us"],
                                 int(meta["decode_s"] * 1e6),
                                 cat="data", tid=tid)

    def _occupancy(self):
        """Decoded batches currently waiting in the rings (approximate:
        Queue.qsize is advisory on some platforms)."""
        total = 0
        for q in self._full_qs:
            try:
                total += q.qsize()
            except NotImplementedError:  # macOS qsize
                return -1
        return total

    # ------------------------------------------------------------------
    def close(self):
        """Stop and join the workers, then unlink every shared-memory
        ring.  Idempotent; the service is unusable afterwards."""
        if self._closed:
            return
        self._closed = True
        # lock-free stop: no epoch matches STOP_EPOCH, so every worker
        # wait loop falls through and exits (this store cannot block
        # even when a killed worker died holding queue internals)
        self._latest.value = STOP_EPOCH
        for q in self._cmd_qs:
            try:
                q.put_nowait(("stop",))
            except Exception:
                pass
        deadline = time.time() + 10.0
        for p in self._procs:
            p.join(timeout=max(0.1, deadline - time.time()))
        for p in self._procs:
            if p.is_alive():
                p.terminate()
                p.join(timeout=5.0)
        for p in self._procs:
            if p.is_alive():  # SIGTERM-proof (e.g. wedged in native code)
                p.kill()
                p.join(timeout=2.0)
        # release queue feeder threads/fds; buffered items are garbage now
        for q in self._free_qs + self._full_qs + self._cmd_qs:
            try:
                q.cancel_join_thread()
                q.close()
            except Exception:
                pass
        for ring in self._rings:
            ring.unlink()
        from .. import telemetry

        if telemetry.enabled():
            telemetry.set_gauge("data.workers_alive", 0)

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
