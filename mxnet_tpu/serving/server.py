"""ModelServer — the client-facing continuous-batching inference engine.

``submit(tenant, inputs) -> Future`` is the whole client API: any
thread may submit; one batcher thread turns the pending queue into
shape-bucketed fills (Orca-style iteration-level scheduling — every
fill is re-packed from whatever is pending NOW, so late requests join
the next fill instead of waiting behind a fixed batch), dispatching
each through the tenant's cached bucket program while the next fill's
H2D stages in the background (session.py).  N tenants share one device;
the oldest-deadline-first policy (request.py) arbitrates between them.

Shutdown is explicit: :meth:`close` stops admission, then either drains
(every queued request dispatched, every future resolved) or fails the
queue with :class:`~.request.ServerClosed`.  Either way in-flight fills
complete — no future is ever left unresolved.

::

    server = mx.serving.ModelServer({"resnet50": pred50, "resnet152": pred152})
    fut = server.submit("resnet50", {"data": image})   # sample-shaped, no batch axis
    probs = fut.result()[0]                            # one array per model output
    server.close()
"""
from __future__ import annotations

import threading
import time

from ..base import MXNetError
from ..context import current_context
from .bucket import bucket_ladder
from .decode import GenerateRequest, GenerativeSession, Pass
from .request import Request, RequestQueue, ServerClosed
from .session import TenantSession
from .. import locks

__all__ = ["ModelServer"]

# default per-request deadline (queue time); the router resolves a
# request that names none to the same number before it goes on the wire
DEFAULT_TIMEOUT_MS = 5000.0

# token-level continuous-batching window: with decode sessions active the
# batcher waits at most this long for admissions between two steps
DECODE_WINDOW_MS = 2.0


def _memory_section(tenants):
    """health()'s ``memory`` key — defensive: a census problem must
    never fail the health probe a router is steering traffic by."""
    from ..obs import memory

    try:
        return memory.health_section(tenants)
    except Exception:  # pragma: no cover — defensive
        return None


class ModelServer:
    """Continuous-batching server over N Predictor-backed tenants.

    `max_batch` is the top of the bucket ladder, `buckets` the ladder
    itself (a list, or a comma-separated string; None = powers of two
    up to `max_batch`), `timeout_ms` the default per-request deadline.
    `max_queue` / `wait_ms` default to ``MXTPU_SERVE_MAX_QUEUE`` /
    ``MXTPU_SERVE_WAIT_MS`` (docs/how_to/env_var.md)."""

    def __init__(self, tenants=None, max_batch=32, buckets=None,
                 timeout_ms=DEFAULT_TIMEOUT_MS, max_queue=None, wait_ms=None):
        from .. import config, obs

        # arm what the environment asks for (idempotent, never raises):
        # the stall watchdog then finds a decode flight that stands open
        # on a serving replica too (docs/observability.md)
        obs.bootstrap()
        self._max_batch = int(max_batch)
        spec = buckets or ""
        if isinstance(spec, (list, tuple)):
            spec = ",".join(str(int(b)) for b in spec)
        self.ladder = bucket_ladder(self._max_batch, spec)
        self._timeout_s = float(timeout_ms) / 1e3
        self._wait_s = float(wait_ms if wait_ms is not None
                             else config.get("MXTPU_SERVE_WAIT_MS")) / 1e3
        self._window_s = DECODE_WINDOW_MS / 1e3
        self._queue = RequestQueue(max_queue if max_queue is not None
                                   else config.get("MXTPU_SERVE_MAX_QUEUE"))
        self._slo = {}  # tenant -> (budget_s, target) declared at add_tenant
        self._sessions = {}
        self._lock = locks.lock("serving.server")
        self._stopping = False
        self._closed = False
        self._abandon = False  # close(drain=False): cut sessions short
        # per-server liveness counters for health() — instance-scoped on
        # purpose (the telemetry serving.* counters are process-wide and
        # a host may run several servers)
        self._dispatches = 0
        self._dispatch_errors = 0
        # the batcher's pass: each generative session adds its legs
        self._pass = Pass()
        for name, pred in (tenants or {}).items():
            self.add_tenant(name, pred)
        self._thread = threading.Thread(target=self._loop,
                                        name="serve_batcher", daemon=True)
        self._thread.start()

    # ------------------------------------------------------------------
    # client surface
    # ------------------------------------------------------------------
    def add_tenant(self, name, predictor, dtype_mode=None, slo_ms=None,
                   slo_target=0.999):
        """Register one model under `name`.  Allowed while serving — a
        new tenant starts empty and simply joins the fairness policy.

        The tenant's numerics are the PREDICTOR's ``dtype_mode`` (an
        int8 tenant is a ``Predictor(..., dtype_mode='int8',
        calib_table=...)``; the mode rides the predictor's executor-
        signature cache, so mixed bf16/int8 tenants compile one program
        per (tenant, bucket, mode)).  `dtype_mode` here is an assertion
        only: pass it to fail FAST when the wired predictor serves a
        different mode than the deployment intended.

        ``slo_ms`` declares the tenant's per-request latency budget:
        every resolution then updates the ``slo.availability.<tenant>``
        gauge (fraction of requests that resolved OK within the
        budget) and ``slo.burn.<tenant>`` — the error-budget burn rate
        ``bad_fraction / (1 - slo_target)``, 1.0 = burning exactly the
        declared budget.  Shipped to the router in every HEALTH reply
        (docs/observability.md "Request tracing & SLOs")."""
        mode = getattr(predictor, "dtype_mode", "f32")
        if dtype_mode is not None and dtype_mode != mode:
            raise MXNetError(
                "tenant %r: requested dtype_mode=%r but the predictor "
                "was built with %r — the mode is fixed at Predictor "
                "construction (build it with dtype_mode=%r and, for "
                "int8, a calib_table)" % (name, dtype_mode, mode,
                                          dtype_mode))
        slo = None
        if slo_ms is not None:
            target = float(slo_target)
            if not 0.0 < target < 1.0:
                raise MXNetError(
                    "tenant %r: slo_target must be a fraction in (0, 1) "
                    "(the share of requests that must meet the %s ms "
                    "budget), got %r" % (name, slo_ms, slo_target))
            slo = (float(slo_ms) / 1e3, target)
        # byte-budget admission (docs/observability.md "Memory
        # observability"): refuse with numbers BEFORE the tenant takes
        # a queue lane or compiles anything
        from ..obs import memory

        memory.admit("tenant %r" % name, predictor.footprint_bytes(),
                     device=predictor._ctx.jax_device())
        with self._lock:
            if self._closed:
                raise ServerClosed("cannot add tenant %r: server is closed"
                                   % name)
            if name in self._sessions:
                raise MXNetError("tenant %r already registered" % name)
            self._sessions[name] = TenantSession(name, predictor, self.ladder)
            if slo is not None:
                self._slo[name] = slo
            self._queue.register(name)
        from .. import telemetry

        if telemetry.enabled():
            # per-tenant numerics gauge, rendered by parse_log
            # --telemetry's tenant_bits column: 8 = int8, 16 = bf16,
            # 32 = f32 (docs/observability.md)
            telemetry.set_gauge("quant.tenant_bits.%s" % name,
                                {"int8": 8, "bf16": 16}.get(mode, 32))
            if slo is not None:
                telemetry.set_gauge("slo.budget_ms.%s" % name, slo[0] * 1e3)
                telemetry.set_gauge("slo.target.%s" % name, slo[1])

    def add_generative_tenant(self, name, model, params, ctx=None,
                              slo_ms=None, slo_target=0.999,
                              max_sessions=8, max_len=256,
                              max_decode_tokens=64, eos_id=None,
                              seq_buckets=None):
        """Register one autoregressive LM for token generation
        (docs/serving.md "Decode sessions & continuous batching").

        `model` is a zoo LM exposing prefill/decode symbols
        (models/transformer_lm.py TransformerLM); `params` its trained
        parameters by plain name.  Requests go through
        :meth:`submit_generate` — plain :meth:`submit` is rejected for
        generative tenants.  The tenant owns ``max_sessions`` KV-cache
        slots of ``max_len`` tokens (clamped to the model's positional
        table) and a request that names no budget gets
        ``max_decode_tokens``; classic tenants on the same server
        interleave with its decode steps under the usual fairness
        policy."""
        slo = None
        if slo_ms is not None:
            target = float(slo_target)
            if not 0.0 < target < 1.0:
                raise MXNetError(
                    "tenant %r: slo_target must be a fraction in (0, 1), "
                    "got %r" % (name, slo_target))
            slo = (float(slo_ms) / 1e3, target)
        # byte-budget admission: predict the footprint ANALYTICALLY —
        # the parameters as often as the device will hold them, plus every
        # entry of the cache spec GenerativeSession will allocate (rings
        # and recurrent state alike, each by its own bytes) — so refusal
        # happens before any compile or ring allocation.  The prefill and
        # the decode predictor bind the arrays they are given: NDArrays
        # already on the tenant's device are held once — and what the live
        # census has booked of them is in the bytes `admit` adds to the
        # prediction, so it is not predicted a second time — anything else
        # is placed by each predictor for itself
        from ..ndarray import NDArray
        from ..obs import memory

        ctx = ctx or current_context()
        param_bytes = sum(memory.nbytes_of(v) for v in params.values())
        shared = all(isinstance(v, NDArray) and v.context == ctx
                     for v in params.values())
        ring_len = min(int(max_len), int(model.max_len))
        cache_bytes = sum(
            entry.nbytes for entry in
            model.cache_spec(int(max_sessions) + 1, ring_len).values())
        live = sum(v._mem_booked for v in params.values()) if shared else 0
        memory.admit("generative tenant %r" % name,
                     (1 if shared else 2) * param_bytes - live + cache_bytes,
                     device=ctx.jax_device())
        # build outside the lock — Predictor construction compiles the
        # smallest prefill/decode buckets and must not stall submits
        session = GenerativeSession(
            name, model, params, ctx=ctx, max_sessions=max_sessions,
            max_len=max_len, max_decode_tokens=max_decode_tokens,
            eos_id=eos_id, seq_buckets=seq_buckets)
        session._pass = self._pass
        with self._lock:
            if self._closed:
                raise ServerClosed("cannot add tenant %r: server is closed"
                                   % name)
            if name in self._sessions:
                raise MXNetError("tenant %r already registered" % name)
            self._sessions[name] = session
            if slo is not None:
                self._slo[name] = slo
            self._queue.register(name)
        self._queue.kick()  # the batcher may now have decode work
        return session

    def submit_generate(self, tenant, tokens, max_new_tokens=None,
                        eos_id=None, timeout_ms=None, on_token=None,
                        trace=None):
        """Enqueue one generation request; returns a Future resolving
        to a :class:`~.decode.GenerateResult` (generated token ids +
        finish reason).  `tokens` is the 1-D int prompt;
        `max_new_tokens` / `eos_id` override the tenant defaults
        (``add_generative_tenant``'s ``max_decode_tokens`` / ``eos_id``).
        `on_token` — optional callable streamed each sampled token id
        from the batcher thread (must be cheap and never block; the
        router agent uses it to push TOKEN frames).  The deadline
        covers QUEUE TIME only: once a session is admitted to a KV slot
        it runs to completion."""
        from ..obs import tracing

        if trace is None and tracing.enabled():
            trace = tracing.new_trace()
        timeout_s = (float(timeout_ms) / 1e3 if timeout_ms is not None
                     else self._timeout_s)
        with self._lock:
            if self._closed:
                raise ServerClosed("ModelServer is closed; no new requests")
            session = self._sessions.get(tenant)
            if session is None or not getattr(session, "is_generative",
                                              False):
                raise MXNetError(
                    "tenant %r is not generative (tenants: %s) — "
                    "register the model with add_generative_tenant() "
                    "or use submit() for classic tenants"
                    % (tenant, sorted(self._sessions)))
            budget = session.budget_for(max_new_tokens)
            session.validate_generate(tokens, budget)
            req = GenerateRequest(tenant, tokens, timeout_s, budget,
                                  eos_id=eos_id, on_token=on_token,
                                  trace=trace, slo=self._slo.get(tenant))
            self._queue.put(req)
        return req.future

    @property
    def tenants(self):
        return sorted(self._sessions)

    def submit(self, tenant, inputs, timeout_ms=None, trace=None):
        """Enqueue one request; returns a `concurrent.futures.Future`
        resolving to [one numpy array per model output], each
        sample-shaped (the batcher owns the batch axis end to end).
        Raises AdmissionError when the queue is full, ServerClosed
        after close(), and a clear error for unknown tenants or
        malformed inputs (validated HERE so a bad request fails its own
        caller immediately instead of poisoning the fill it would have
        been co-batched into).

        `trace` propagates an upstream request trace (the router's
        agent passes the context that rode the SUBMIT frame); when
        tracing is armed and none is given, a head-sampled context is
        minted here — ModelServer.submit is the trace root for direct
        callers."""
        from ..obs import tracing

        if trace is None and tracing.enabled():
            trace = tracing.new_trace()
        timeout_s = (float(timeout_ms) / 1e3 if timeout_ms is not None
                     else self._timeout_s)
        # build (and SNAPSHOT) the request before taking the lock —
        # concurrent submitters must not serialize on each other's
        # input copies
        req = Request(tenant, inputs, timeout_s, trace=trace,
                      slo=self._slo.get(tenant))
        # closed check, tenant lookup + validation, and enqueue share
        # the close()/add_tenant() lock: a request that passes is
        # enqueued before close() can drain/fail the queue (no future
        # left unresolved), and a submit racing add_tenant can never
        # slip an UNVALIDATED request past a just-registered tenant
        # (validation is cheap shape checks — the copies stayed outside)
        with self._lock:
            if self._closed:
                raise ServerClosed("ModelServer is closed; no new requests")
            session = self._sessions.get(tenant)
            if session is not None:
                session.validate(req.inputs)
            self._queue.put(req)
        return req.future

    def warmup(self, buckets=None):
        """Pre-compile every (tenant, bucket) program with one dummy
        fill each, synchronously, bypassing the queue — call BEFORE
        taking traffic so no real request ever pays an XLA compile
        (tests/test_serving.py pins that traffic after it compiles
        nothing).  Returns the number of programs visited."""
        buckets = list(buckets) if buckets is not None else list(self.ladder)
        with self._lock:  # consistent view vs concurrent add_tenant
            sessions = list(self._sessions.values())
        return sum(session.warm(buckets) for session in sessions)

    def stats(self):
        """Cheap live view for load shedding / dashboards (the full
        story is the telemetry registry, docs/observability.md)."""
        with self._lock:
            sessions = dict(self._sessions)
        return {
            "queue_depth": self._queue.depth(),
            "per_tenant_depth": {t: self._queue.depth(t) for t in sessions},
            "tenant_modes": {t: getattr(s._predictor, "dtype_mode", "f32")
                             for t, s in sessions.items()
                             if not getattr(s, "is_generative", False)},
            "generative": {t: s.stats() for t, s in sessions.items()
                           if getattr(s, "is_generative", False)},
            "ladder": list(self.ladder),
            "closed": self._closed,
        }

    def health(self):
        """Structured health probe for a router/load balancer — the
        surface the ROADMAP multi-replica tier polls before spreading
        traffic to this replica (docs/observability.md "Distributed
        observability").  Cheap by contract: lock + counter reads, never
        touches the device or waits on the batcher.

        Keys: ``healthy`` (batcher alive and accepting), ``closed``,
        ``batcher_alive``, ``queue_depth`` / ``per_tenant_depth``
        (backpressure), ``queue_headroom`` (admission slots left),
        ``oldest_deadline_in_s`` (seconds until the most pressed queued
        request times out; None when idle — negative means requests are
        already expiring), ``dispatches`` / ``dispatch_errors`` (this
        server's fill counts), ``stalls`` (legs of the batcher's pass
        that stood still so far, over the generative tenants; their
        records are ``stats()["generative"][tenant]["stalls"]``),
        ``tenants``, ``ladder``, and ``memory``
        — the live-byte census / budget headroom / per-tenant KV-ring
        bytes section from :func:`mxnet_tpu.obs.memory.health_section`
        (docs/observability.md "Memory observability")."""
        # the queue probe is taken WHILE holding the server lock (the
        # queue's cv already nests under it on the submit path), so a
        # concurrent add_tenant/close cannot produce a torn probe —
        # per_tenant_depth, headroom, and the tenant list are one
        # consistent view
        with self._lock:
            tenants = list(self._sessions)
            stalls = sum(getattr(s, "_stall_count", 0)
                         for s in self._sessions.values())
            closed = self._closed
            dispatches = self._dispatches
            errors = self._dispatch_errors
            probe = self._queue.probe()
        thread = self._thread
        alive = bool(thread is not None and thread.is_alive())
        oldest = probe["oldest_deadline"]
        return {
            "healthy": alive and not closed,
            "closed": closed,
            "batcher_alive": alive,
            "queue_depth": probe["queue_depth"],
            "per_tenant_depth": {t: probe["per_tenant_depth"].get(t, 0)
                                 for t in tenants},
            "queue_headroom": probe["queue_headroom"],
            "oldest_deadline_in_s": (None if oldest is None
                                     else oldest - time.monotonic()),
            "dispatches": dispatches,
            "dispatch_errors": errors,
            "stalls": stalls,
            "tenants": sorted(tenants),
            "ladder": list(self.ladder),
            "memory": _memory_section(tenants),
        }

    def close(self, drain=True, timeout=None):
        """Stop the server.  ``drain=True`` (default) serves every
        already-queued request before returning — generative sessions
        keep decoding until they retire naturally; ``drain=False``
        fails still-queued requests with ServerClosed and resolves
        active decode sessions with their PARTIAL tokens
        (``finish_reason='closed'``).  In-flight fills complete either
        way, so every future this server ever returned is resolved when
        close() returns.  Idempotent."""
        with self._lock:
            already = self._closed
            self._closed = True
        if already and self._thread is None:
            return
        if not drain:
            self._abandon = True
            self._queue.fail_all(lambda req: ServerClosed(
                "ModelServer.close(drain=False) dropped the queued "
                "request to tenant %r" % req.tenant))
        self._stopping = True
        self._queue.kick()
        thread = self._thread
        if thread is not None:
            thread.join(timeout)
            if thread.is_alive():
                # the contract is "every future resolved when close()
                # returns" — a timed-out join must not fake it
                raise MXNetError(
                    "ModelServer.close(timeout=%s) expired before the "
                    "queue drained; fills are still running — call "
                    "close() again to keep waiting, or "
                    "close(drain=False) to drop the backlog" % timeout)
            self._thread = None
        for session in self._sessions.values():
            session.close()

    # ------------------------------------------------------------------
    # the batcher thread
    # ------------------------------------------------------------------
    def _generative(self):
        with self._lock:
            return [s for s in self._sessions.values()
                    if getattr(s, "is_generative", False)]

    def _loop(self):
        """Classic fills and decode steps interleave on this one
        thread.  Each iteration: (1) wait for ripe queue work, bounded
        by the decode window whenever sessions are mid-generation;
        (2) serve the ripe tenant — a classic fill, or prompt
        admissions into free KV slots; (3) run ONE decode step per
        generative tenant with active sessions (the Orca iteration:
        re-packed from whoever is active NOW, so sessions admitted in
        (2) join and sessions that hit EOS leave, all without
        recompiling).  Exit only when stopping, the queue is drained,
        and every decode session has retired — the zero-lost-futures
        contract."""
        from .. import profiler, telemetry

        pas = self._pass
        while True:
            gens = self._generative()
            ticking = any(s.active() for s in gens)
            until = (time.monotonic() + self._window_s) if ticking else None
            with profiler.span("serve.wait_work", cat="serving",
                               hist="serving.loop.wait_seconds") as wait:
                tenant = self._queue.next_work(self._wait_s, self._max_batch,
                                               lambda: self._stopping,
                                               until=until)
            # one PASS ends where this wait began and the next begins
            # with it (serving/decode.py "A pass and its legs")
            pas.turn(wait, ticking)
            if tenant is not None:
                session = self._sessions[tenant]
                if getattr(session, "is_generative", False):
                    self._admit(tenant, session)
                else:
                    self._fill(tenant, session)
            for session in gens:
                if session.active():
                    try:
                        if session.decode_step():
                            self._dispatches += 1
                    except BaseException as e:
                        # a failed decode step poisons that tenant's KV
                        # state: fail ITS active sessions, keep serving
                        # the others
                        self._dispatch_errors += 1
                        if telemetry.enabled():
                            telemetry.inc("serving.dispatch_errors")
                        session.fail_active(e)
            if tenant is None and self._stopping and self._queue.depth() == 0:
                gens = self._generative()
                if self._abandon:
                    for session in gens:
                        session.finish_all("closed")
                if not any(s.active() for s in gens):
                    return

    def _admit(self, tenant, session):
        """Move queued prompts into free KV slots (prefill).  With no
        free slot the head requests stay queued — put_front preserves
        arrival order — and are re-offered after the decode steps
        below retire sessions."""
        from .. import telemetry

        limit = min(self._max_batch, session.free_slots())
        if limit <= 0:
            return
        reqs = self._queue.take(tenant, limit)
        if not reqs:
            return
        try:
            leftovers = session.admit(reqs)
            self._dispatches += 1
        except BaseException as e:
            self._dispatch_errors += 1
            if telemetry.enabled():
                telemetry.inc("serving.dispatch_errors")
            for r in reqs:
                r.fail(e)
            return
        for r in reversed(leftovers):
            self._queue.put_front(r)

    def _fill(self, tenant, session):
        from .. import telemetry

        reqs = self._queue.take(tenant, self._max_batch)
        if not reqs:
            return
        # a classic fill's program is under no leg of the pass
        self._pass.unjudged = True
        try:
            session.dispatch(reqs)
            self._dispatches += 1
        except BaseException as e:
            # a failed fill fails ITS requests, never the server: the
            # loop survives to serve the other tenants
            self._dispatch_errors += 1
            if telemetry.enabled():
                telemetry.inc("serving.dispatch_errors")
            for r in reqs:
                r.fail(e)
