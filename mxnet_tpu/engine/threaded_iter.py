"""ThreadedIter — engine-backed prefetching iterator.

The native replacement for dmlc-core's `threadediter.h` (the producer
thread under the reference's PrefetcherIter, src/io/iter_prefetcher.h).
Instead of owning a dedicated thread, each batch fetch is one engine op:

  * fetches are serialized by a WAW chain on one iterator variable, so
    `next_fn` is never called concurrently and order is preserved;
  * demand-driven credit flow replaces the bounded queue — at most
    `max_prefetch` fetches are outstanding, and consuming one item
    schedules the next, so an op never blocks a worker on a full buffer
    (a blocked worker could starve the shared pool);
  * under NaiveEngine every push runs inline and the iterator degrades
    to synchronous lookahead — same results, no threads.

Producer errors are delivered in-band and re-raised at the consumer's
`next()` (deferred-error parity with the engine itself).
"""
from __future__ import annotations

import queue as _queue

__all__ = ["ThreadedIter"]

_END = object()


class ThreadedIter:
    """Iterate `next_fn()` with up to `max_prefetch` results computed ahead
    on engine workers.  `next_fn` signals exhaustion with StopIteration."""

    def __init__(self, next_fn, max_prefetch=2, name="threaded_iter",
                 priority=0):
        from . import get as _get_engine

        self._next_fn = next_fn
        self._name = name
        self._gauge = "io.buffer.%s" % name
        self._priority = priority
        self._queue = _queue.Queue()       # unbounded; credits bound it
        self._var = _get_engine().new_variable()  # WAW chain serializes fetches
        self._closed = False
        self._producer_done = False
        for _ in range(max(1, int(max_prefetch))):
            self._schedule()

    def _schedule(self):
        # the engine is re-resolved per push: set_engine_type() must not
        # strand a live iterator on a stopped backend
        from . import get as _get_engine

        if self._closed or self._producer_done:
            return
        # atomic=False: next_fn is arbitrary user iterator code whose
        # NDArray reads are not covered by this op's declared vars — it
        # must keep normal engine sync semantics
        _get_engine().push(self._fetch_one, write_vars=(self._var,),
                           priority=self._priority, name=self._name,
                           atomic=False)

    def _fetch_one(self):
        # runs on an engine worker; must never block on the consumer.
        # _producer_done: an earlier fetch in the WAW chain already hit
        # StopIteration or an error — do not touch the source again
        if self._closed or self._producer_done:
            self._queue.put((_END, None))
            return
        try:
            item = self._next_fn()
        except StopIteration:
            self._producer_done = True
            self._queue.put((_END, None))
        except BaseException as e:
            self._producer_done = True
            self._queue.put((None, e))
        else:
            self._queue.put((item, None))

    def __iter__(self):
        return self

    def __next__(self):
        from . import get as _get_engine
        from .. import profiler, telemetry

        # how long the consumer stalled waiting for this pipeline (≈0
        # when lookahead keeps up).  Never hard-block: when the queue is
        # empty, help the engine run ready ops instead — the consumer may
        # itself be inside an engine op (nested engine-backed iterators,
        # e.g. PrefetchingIter over ImageRecordIter), and a blind get()
        # would pin a worker while the fetch that must fill this queue
        # starves in the ready heap
        with profiler.span("io.consumer_wait", cat="io",
                           hist="io.consumer_wait_seconds", pipe=self._name):
            while True:
                try:
                    item, err = self._queue.get_nowait()
                    break
                except _queue.Empty:
                    if not _get_engine().help_one():
                        try:
                            item, err = self._queue.get(timeout=0.05)
                            break
                        except _queue.Empty:
                            continue
        if telemetry.enabled():
            # how full this pipeline's buffer ran
            telemetry.set_gauge(self._gauge, self._queue.qsize())
        if err is not None:
            self._queue.put((_END, None))  # subsequent next() stops cleanly
            raise err
        if item is _END:
            self._queue.put((_END, None))  # keep raising on repeated next()
            raise StopIteration
        self._schedule()
        return item

    next = __next__

    def cancel(self):
        """Flag-only cancellation: outstanding fetches drain as no-ops,
        nothing blocks.  The one safe call from GC/interpreter-shutdown
        context (__del__ must never wait on the engine)."""
        self._closed = True

    def close(self):
        """Cancel outstanding fetches and drain them: after close()
        returns, `next_fn` is no longer being called, so the caller may
        safely reset/destroy the underlying source.  Safe to call
        repeatedly."""
        from . import get as _get_engine

        self._closed = True
        _get_engine().wait_for_var(self._var, wait_reads=True)
