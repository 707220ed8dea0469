"""Request plumbing for the serving engine: futures, deadlines,
admission control, and the fairness-aware pending queue.

One :class:`Request` is one caller-visible unit of work — a dict of
sample-shaped inputs plus a `concurrent.futures.Future` the caller
waits on.  The :class:`RequestQueue` holds pending requests per tenant
behind one condition variable and answers the continuous batcher's only
scheduling question — *which tenant should the next fill serve, and
when* — with the oldest-deadline-first policy: among tenants whose
queue head is "ripe" (a full batch is waiting, the batching window
expired, the head's deadline passed, or the server is draining), pick
the one whose head request must finish soonest.  With equal per-tenant
timeouts this degrades to oldest-arrival-first, i.e. global FIFO
across tenants — no tenant can starve another by flooding.

Deadlines are enforced at dequeue time: a request still queued past its
deadline fails with :class:`RequestTimeout` instead of wasting a batch
slot on an answer nobody is waiting for (the Orca/vLLM admission
discipline).  Admission control bounds the queue itself — beyond
``MXTPU_SERVE_MAX_QUEUE`` pending requests, ``submit()`` raises
:class:`AdmissionError` immediately so overload surfaces as fast
rejections, not unbounded tail latency.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future, InvalidStateError

import numpy as _np

from ..base import MXNetError
from .. import locks

__all__ = ["Request", "RequestQueue", "RequestTimeout", "AdmissionError",
           "ServerClosed"]


class RequestTimeout(MXNetError):
    """The request sat in the queue past its deadline and was dropped
    before dispatch (serving.timeouts counts these)."""


class AdmissionError(MXNetError):
    """The server's pending queue is full; the request was rejected at
    submit() (serving.rejected counts these)."""


class ServerClosed(MXNetError):
    """The server was closed: either this submit() arrived after
    close(), or close(drain=False) failed the still-queued request."""


class Request:
    """One pending inference request.

    ``taken_at`` (stamped by :meth:`RequestQueue.take`) and
    ``service_at`` (stamped at the top of the fill that serves it)
    split the request's life into queue-wait and service; resolution —
    :meth:`fulfil` OR :meth:`fail` — books BOTH halves plus the
    combined latency with an outcome label, so the p99 histograms
    include the worst requests (timeouts, failed fills) instead of
    silently excluding them.  ``trace`` is the request's
    :class:`~mxnet_tpu.obs.tracing.TraceContext` (None when tracing is
    off); ``slo`` an optional ``(budget_s, target)`` pair declared at
    ``add_tenant`` feeding the ``slo.*`` burn/availability gauges."""

    __slots__ = ("tenant", "inputs", "future", "arrival", "deadline",
                 "trace", "slo", "taken_at", "service_at", "_booked")

    def __init__(self, tenant, inputs, timeout_s, trace=None, slo=None):
        self.tenant = tenant
        # SNAPSHOT the inputs (the engine-op operand discipline,
        # ndarray._snapshot): the caller may refill its buffer the
        # moment submit() returns, while the batcher reads these up to
        # a full batching window later
        self.inputs = {k: _np.array(v) for k, v in inputs.items()}
        self.future = Future()
        self.arrival = time.monotonic()
        self.deadline = self.arrival + float(timeout_s)
        self.trace = trace
        self.slo = slo
        self.taken_at = None
        self.service_at = None
        self._booked = False

    def _book(self, outcome):
        """Book resolution telemetry ONCE: combined + queue/service
        split latency histograms (outcome-labeled counters beside
        them), the per-tenant SLO ledger, and — when tracing is armed —
        the request's outcome span (forced for failures, so an
        unsampled timeout is still explained)."""
        if self._booked:
            return
        self._booked = True
        now = time.monotonic()
        from .. import telemetry

        tenant = self.tenant
        total = now - self.arrival
        q_end = self.taken_at if self.taken_at is not None else now
        if telemetry.enabled():
            telemetry.inc("serving.outcomes.%s" % outcome)
            telemetry.observe("serving.request_seconds", total)
            telemetry.observe("serving.request_seconds.%s" % tenant, total)
            telemetry.observe("serving.queue_seconds", q_end - self.arrival)
            telemetry.observe("serving.queue_seconds.%s" % tenant,
                              q_end - self.arrival)
            if self.service_at is not None:
                telemetry.observe("serving.service_seconds",
                                  now - self.service_at)
                telemetry.observe("serving.service_seconds.%s" % tenant,
                                  now - self.service_at)
            if outcome == "ok":
                telemetry.inc("serving.requests")
                telemetry.inc("serving.requests.%s" % tenant)
            if self.slo is not None:
                budget_s, target = self.slo
                good = outcome == "ok" and total <= budget_s
                telemetry.inc("slo.good.%s" % tenant if good
                              else "slo.bad.%s" % tenant)
                g = telemetry.counter_value("slo.good.%s" % tenant)
                b = telemetry.counter_value("slo.bad.%s" % tenant)
                n = g + b
                telemetry.set_gauge("slo.availability.%s" % tenant, g / n)
                telemetry.set_gauge(
                    "slo.burn.%s" % tenant,
                    (b / n) / max(1e-9, 1.0 - target))
        from ..obs import tracing

        if tracing.enabled() and self.trace is not None:
            tracing.record_outcome(self.trace, outcome, self.arrival, now,
                                   side="server", tenant=tenant)

    def fail(self, exc):
        """set_exception that tolerates caller-cancelled futures — a
        cancelled request must never kill the batcher thread.  Books
        the resolution latency with its outcome label (timeout vs
        error) — the satellite fix: p99 used to silently exclude
        exactly the requests that blew it."""
        if not self.future.done():
            try:
                self.future.set_exception(exc)
            except InvalidStateError:  # cancelled in the check window
                return
            self._book("timeout" if isinstance(exc, RequestTimeout)
                       else "error")

    def fulfil(self, result):
        """set_result with the same cancellation tolerance."""
        if not self.future.done():
            try:
                self.future.set_result(result)
            except InvalidStateError:
                return
            self._book("ok")


class RequestQueue:
    """Thread-safe per-tenant pending queues + the batcher's scheduler.

    Producers (any thread) call :meth:`put`; the single batcher thread
    alternates :meth:`next_work` / :meth:`take`.  Every mutation updates
    the ``serving.queue_depth`` gauges so the backlog renders as a
    chrome counter lane beside the dispatch spans."""

    def __init__(self, max_queue):
        self._cv = locks.condition("serving.queue")
        self._queues = {}
        self._depth = 0
        self._max_queue = int(max_queue)

    def register(self, tenant):
        with self._cv:
            self._queues.setdefault(tenant, deque())

    def depth(self, tenant=None):
        with self._cv:
            if tenant is None:
                return self._depth
            return len(self._queues.get(tenant, ()))

    def headroom(self):
        """Admission slots left before submit() starts rejecting
        (MXTPU_SERVE_MAX_QUEUE bound) — owned here so health() never
        reaches into this queue's bookkeeping."""
        with self._cv:
            return max(0, self._max_queue - self._depth)

    def oldest_deadline(self):
        """Earliest deadline among the queue heads (monotonic seconds),
        or None when nothing is pending — the urgency half of the
        ModelServer.health() probe: how long before the most pressed
        queued request starts timing out."""
        with self._cv:
            heads = [dq[0].deadline for dq in self._queues.values() if dq]
        return min(heads) if heads else None

    def probe(self):
        """One ATOMIC health snapshot — total depth, per-tenant depths,
        admission headroom, and the oldest head deadline read under a
        single lock acquisition, so ModelServer.health() can never
        report a torn view (a depth from before a concurrent put and a
        headroom from after it)."""
        with self._cv:
            heads = [dq[0].deadline for dq in self._queues.values() if dq]
            return {
                "queue_depth": self._depth,
                "per_tenant_depth": {t: len(dq)
                                     for t, dq in self._queues.items()},
                "queue_headroom": max(0, self._max_queue - self._depth),
                "oldest_deadline": min(heads) if heads else None,
            }

    def _note_depth(self, tenant):
        # called under self._cv; telemetry's lock is a leaf lock
        from .. import telemetry

        if telemetry.enabled():
            telemetry.set_gauge("serving.queue_depth", self._depth)
            telemetry.set_gauge("serving.queue_depth.%s" % tenant,
                                len(self._queues[tenant]))

    def put(self, req):
        """Enqueue or reject (admission control).  Raises KeyError-free
        errors for unknown tenants so a typo'd tenant name is a clear
        client bug, not a silent new queue."""
        from .. import telemetry

        with self._cv:
            if req.tenant not in self._queues:
                raise MXNetError("unknown tenant %r (tenants: %s)"
                                 % (req.tenant, sorted(self._queues)))
            if self._depth >= self._max_queue:
                if telemetry.enabled():
                    telemetry.inc("serving.rejected")
                raise AdmissionError(
                    "serving queue is full (%d pending >= "
                    "MXTPU_SERVE_MAX_QUEUE=%d); retry later or raise the "
                    "bound" % (self._depth, self._max_queue))
            self._queues[req.tenant].append(req)
            self._depth += 1
            self._note_depth(req.tenant)
            self._cv.notify_all()

    def put_front(self, req):
        """Re-queue an ALREADY-ADMITTED request at the head of its
        tenant queue (no admission check — its depth slot was released
        by the take() that popped it, and re-counting it here keeps
        the gauge honest).  The generative batcher uses this for
        prompts that found no free KV slot: they keep their arrival
        order and deadline, and are re-offered next decode window."""
        with self._cv:
            if req.tenant not in self._queues:
                raise MXNetError("unknown tenant %r (tenants: %s)"
                                 % (req.tenant, sorted(self._queues)))
            self._queues[req.tenant].appendleft(req)
            self._depth += 1
            self._note_depth(req.tenant)
            self._cv.notify_all()

    def kick(self):
        """Wake the batcher (close() flips its stop flag, then kicks)."""
        with self._cv:
            self._cv.notify_all()

    def next_work(self, wait_s, max_batch, stopping, until=None):
        """Block until some tenant deserves a dispatch; return its name.

        A tenant is *ripe* when its head request has waited out the
        batching window, a full ``max_batch`` is already pending, the
        head's deadline passed (so the timeout fires promptly), or
        `stopping()` is true (drain mode dispatches everything).  Among
        ripe tenants the one with the OLDEST head deadline wins.
        Returns None when stopping and fully drained, or — with
        `until` set (a monotonic instant) — when that instant passes
        with nothing ripe: the generative batcher's decode-window tick,
        which must run decode steps on schedule even while the queue
        is quiet."""
        with self._cv:
            while True:
                now = time.monotonic()
                if until is not None and now >= until:
                    return None
                best, best_deadline = None, None
                next_event = None
                draining = stopping()
                for tenant, dq in self._queues.items():
                    if not dq:
                        continue
                    head = dq[0]
                    ripe = (draining or len(dq) >= max_batch
                            or now - head.arrival >= wait_s
                            or now >= head.deadline)
                    if ripe:
                        if best is None or head.deadline < best_deadline:
                            best, best_deadline = tenant, head.deadline
                    else:
                        at = min(head.arrival + wait_s, head.deadline)
                        if next_event is None or at < next_event:
                            next_event = at
                if best is not None:
                    return best
                if draining and self._depth == 0:
                    return None
                # fully idle: block until a put()/kick() notifies (close()
                # always kicks after flipping its stop flag, so an
                # indefinite wait cannot strand the batcher); an `until`
                # tick bounds the wait either way
                if until is not None:
                    next_event = (until if next_event is None
                                  else min(next_event, until))
                self._cv.wait(max(1e-4, next_event - now)
                              if next_event is not None else None)

    def take(self, tenant, limit):
        """Pop up to `limit` live requests for `tenant`, failing expired
        ones with RequestTimeout on the way (their callers stopped
        waiting; a batch slot spent on them is pure waste)."""
        from .. import telemetry

        out, expired = [], []
        with self._cv:
            dq = self._queues[tenant]
            now = time.monotonic()
            while dq and len(out) < limit:
                req = dq.popleft()
                self._depth -= 1
                if now >= req.deadline:
                    expired.append(req)
                else:
                    # dequeue-side queue-wait stamp: everything before
                    # this instant books as serving.queue_seconds,
                    # everything after as service (an expired request
                    # never dequeued — its whole life was queue)
                    req.taken_at = now
                    out.append(req)
            self._note_depth(tenant)
        for req in expired:
            if telemetry.enabled():
                telemetry.inc("serving.timeouts")
                telemetry.inc("serving.timeouts.%s" % tenant)
            req.fail(RequestTimeout(
                "request to tenant %r spent %.1f ms queued, past its "
                "%.1f ms deadline (ModelServer(timeout_ms=) or the "
                "submit() override)" % (
                    tenant, (now - req.arrival) * 1e3,
                    (req.deadline - req.arrival) * 1e3)))
        return out

    def fail_all(self, make_exc):
        """Drain every queue, failing each request with `make_exc(req)`
        (the close(drain=False) path)."""
        with self._cv:
            pending = []
            for dq in self._queues.values():
                pending.extend(dq)
                dq.clear()
            self._depth = 0
            for tenant in self._queues:
                self._note_depth(tenant)
            self._cv.notify_all()
        for req in pending:
            req.fail(make_exc(req))
        return len(pending)
