"""Router — one ``submit()`` surface over N ModelServer replicas.

The fleet layer (ROADMAP item 1): N single-chip continuous batchers
(each behind a :class:`~mxnet_tpu.router.agent.ReplicaAgent`) become
one service.  The router exposes the exact :class:`ModelServer`
client contract — ``submit(tenant, inputs) -> Future`` resolving to
[one sample-shaped array per output] — and owns three fleet problems:

* **health-gated least-loaded dispatch** — a poll thread probes every
  replica's ``health()`` (queue depth / admission headroom / deadline
  pressure) on the ``poll_ms`` cadence; ``submit()``
  routes whole requests to the least-loaded replica that can take
  traffic (policy.py), never sharding one request across replicas —
  each replica runs a complete program (the pjit multi-device
  dispatch lesson: route programs, don't scatter operands).
* **drain-on-death re-dispatch** — requests are snapshotted at submit
  time (the PR 7 Request discipline), so when a replica dies — its
  socket drops, or its health stamp ages past the liveness timeout
  (``parallel.dist.LivenessBook``, the CheckDeadNodes machinery) —
  every in-flight submission it held is replayed to a healthy peer
  from the snapshot.  No caller future is ever lost or resolved
  twice: the flight table is popped under one lock, so exactly one
  of {replica result, replay result, terminal failure} lands in each
  future.  Inference is read-only, so the at-least-once execution a
  replay implies is safe.
* **traffic-adaptive bucket ladders** — health replies carry the
  cumulative fill accounting (``serving.batch_slots_used`` /
  ``_padded`` / ``dispatches``); every ``adapt_window_s``
  the router derives the mean fill per replica and, when the offered
  mix pads away more than a quarter of each bucket
  (policy.derive_ladder), pushes a WARMUP carrying a better ladder.
  The replica drains, rebinds, and recompiles; the router suppresses
  its staleness verdict for the duration (the obs watchdog's
  compile-bracket discipline) and prefers peers while it warms.
"""
from __future__ import annotations

import queue as _queue
import socket as _socket
import threading
import time

import numpy as _np

from ..base import MXNetError
from ..parallel.dist import LivenessBook, _connect_retry
from ..serving.request import AdmissionError, RequestTimeout, ServerClosed
from . import wire
from .policy import NoHealthyReplica, derive_ladder, pick_replica
from .. import locks

__all__ = ["Router", "ReplicaDead", "RouterClosed", "NoHealthyReplica"]


class ReplicaDead(MXNetError):
    """The replica holding this request died and the re-dispatch budget
    (Router(redispatch_cap=)) ran out before a healthy peer answered."""


class RouterClosed(MXNetError):
    """submit() after Router.close()."""


_ERROR_KINDS = {
    "AdmissionError": AdmissionError,
    "RequestTimeout": RequestTimeout,
    "ServerClosed": ServerClosed,
}

# error kinds that indicate the REPLICA's state, not the request's —
# worth replaying to a peer instead of failing the caller
_REPLAYABLE_KINDS = ("AdmissionError", "ServerClosed")


class _Flight:
    """One in-flight submission: the caller's future plus the
    submit-time snapshot a replay is served from.  ``trace`` is the
    request's trace context (obs/tracing.py, None when tracing is
    off); ``t_sent`` the monotonic stamp of the last wire send — the
    ``router_queue`` / ``wire`` segment boundary."""

    __slots__ = ("req_id", "tenant", "inputs", "names", "future",
                 "t_submit", "timeout_ms", "replica", "redispatches",
                 "trace", "t_sent", "generate", "policy", "on_token")

    def __init__(self, req_id, tenant, inputs, timeout_ms):
        from concurrent.futures import Future

        self.req_id = req_id
        self.tenant = tenant
        # SNAPSHOT now (the serving Request discipline): the caller may
        # refill its buffer the moment submit() returns, and a replica
        # death hours of queueing later replays from THESE bytes
        self.names = sorted(inputs)
        self.inputs = [_np.array(inputs[k]) for k in self.names]
        self.timeout_ms = timeout_ms
        self.future = Future()
        self.t_submit = time.monotonic()
        self.replica = None
        self.redispatches = 0
        self.trace = None
        self.t_sent = None
        self.generate = False  # GENERATE flight: never replayed (the
        self.policy = None     # replica-resident KV state is the request)
        self.on_token = None

    def fulfil(self, result):
        if not self.future.done():
            try:
                self.future.set_result(result)
            except Exception:  # cancelled in the check window
                pass

    def fail(self, exc):
        if not self.future.done():
            try:
                self.future.set_exception(exc)
            except Exception:
                pass


class _Replica:
    """Router-side state for one agent connection."""

    __slots__ = ("addr", "name", "sock", "send_lock", "reader", "alive",
                 "health", "health_at", "inflight", "ladder", "tenants",
                 "rebucketing", "ctl_pending", "acks", "adapt_base",
                 "adapt_at", "offset_s")

    def __init__(self, addr):
        self.addr = addr
        self.name = None
        self.sock = None
        self.send_lock = locks.lock("router.replica_send")
        self.reader = None
        self.alive = True
        self.health = None
        self.health_at = None
        self.inflight = set()
        self.ladder = []
        self.tenants = []
        self.rebucketing = False
        self.ctl_pending = 0  # sync control ops awaiting their ack
        self.acks = _queue.Queue()
        self.adapt_base = None
        self.adapt_at = None
        # router wall-clock minus replica wall-clock, measured at the
        # HELLO handshake (3-ping NTP fold, min-RTT sample — the
        # obs/aggregate.py recipe): replica_wall + offset_s lands on
        # the router's timeline.  The router's trace segments and
        # tools/obs_stitch.py both key off it.
        self.offset_s = 0.0


class Router:
    """Spread tenant traffic across N ReplicaAgents (module docstring).

    `replicas`: list of ``host:port`` strings (default: the
    ``MXTPU_ROUTER_REPLICAS`` list ``launch.py --serve-replicas``
    prints/exports).  Construction connects, handshakes, and blocks
    until every replica answered its first health probe — a router
    that would route blind instead raises within `connect_timeout`."""

    def __init__(self, replicas=None, poll_ms=200.0, redispatch_cap=2,
                 adapt_window_s=10.0, connect_timeout=60.0):
        from .. import config
        from ..serving.server import DEFAULT_TIMEOUT_MS

        if replicas is None:
            spec = config.get("MXTPU_ROUTER_REPLICAS")
            replicas = [a for a in spec.split(",") if a.strip()]
        if not replicas:
            raise MXNetError(
                "Router needs at least one replica address (pass "
                "replicas=['host:port', ...] or export "
                "MXTPU_ROUTER_REPLICAS — tools/launch.py "
                "--serve-replicas prints the list)")
        self._poll_s = float(poll_ms) / 1e3
        self._redispatch_cap = int(redispatch_cap)
        self._adapt_window_s = float(adapt_window_s)
        # resolved HERE, not left as None on the wire: a None deadline
        # would let each replay hop apply a fresh replica-side default,
        # multiplying the caller's effective deadline by the redispatch
        # count — the remaining-budget math needs a concrete number
        self._default_timeout_ms = DEFAULT_TIMEOUT_MS
        # a replica is stale-dead after 5 silent poll intervals (floored
        # so a very tight test cadence doesn't flap on scheduler jitter)
        self._dead_after = max(5 * self._poll_s, 2.0)
        self._lock = locks.condition("router.flights")
        self._book = LivenessBook(timeout=self._dead_after)
        self._flights = {}
        self._pending_replays = 0  # flights between pop and re-place
        self._req_seq = 0
        self._closed = False
        self._replicas = {}
        self._stop = threading.Event()
        self._poller = None
        try:
            deadline = time.monotonic() + connect_timeout
            for spec in replicas:
                addr = self._parse_addr(spec)
                rep = _Replica(addr)
                rep.sock = _connect_retry(
                    addr, timeout=max(0.1, deadline - time.monotonic()))
                self._replicas["%s:%d" % addr] = rep  # keyed early for cleanup
                self._handshake(rep, max(0.1, deadline - time.monotonic()))
                del self._replicas["%s:%d" % addr]
                self._replicas[rep.name] = rep
            with self._lock:
                for rep in self._replicas.values():
                    self._book.beat(rep.name)
            for rep in self._replicas.values():
                rep.reader = threading.Thread(
                    target=self._read_loop, args=(rep,),
                    name="router_read[%s]" % rep.name, daemon=True)
                rep.reader.start()
            self._poller = threading.Thread(target=self._poll_loop,
                                            name="router_poll", daemon=True)
            self._poller.start()
            self._wait_first_health(connect_timeout)
        except BaseException:
            # a failed constructor must not leak its fleet connections
            # or leave the poll thread spamming HEALTH forever
            self._stop.set()
            with self._lock:
                self._closed = True
                for rep in self._replicas.values():
                    rep.alive = False
            for rep in self._replicas.values():
                try:
                    rep.sock.close()
                except OSError:
                    pass
            raise

    @staticmethod
    def _parse_addr(spec):
        if isinstance(spec, (tuple, list)):
            return (spec[0], int(spec[1]))
        host, _, port = spec.rpartition(":")
        return (host or "127.0.0.1", int(port))

    def _handshake(self, rep, timeout=None):
        """Inline HELLO before the reader starts: identity, tenant set,
        and current ladder arrive synchronously — bounded by `timeout`.
        An agent binds+listens in its constructor but only accepts in
        serve_forever(), so a wedged agent (stuck compile, SIGSTOP)
        accepts the TCP connect off its listen backlog and then never
        answers: without the bound, construction would hang forever
        instead of raising within connect_timeout as promised.  The
        bound is a hard abort timer, not a socket timeout: the shared
        framing layer deliberately rides out mid-frame timeouts (it
        must never desync a long-lived PS stream), but THIS socket is
        discarded on failure, so shutdown() — which reliably wakes a
        blocked recv — is the right tool."""
        aborted = threading.Event()

        def _abort():
            aborted.set()
            try:
                rep.sock.shutdown(_socket.SHUT_RDWR)
            except OSError:
                pass

        timer = None
        if timeout is not None:
            timer = threading.Timer(timeout, _abort)
            timer.daemon = True
            timer.start()
        try:
            wire.send(rep.sock, wire.HELLO, lock=rep.send_lock)
            cmd, info, _ = wire.recv(rep.sock)
            # clock offset vs this replica, measured INSIDE the bounded
            # handshake (frames on the connection are handled in order,
            # so the pings are synchronous): three NTP folds, keep the
            # minimum-RTT sample — obs/aggregate.py's recipe, now also
            # taken at ReplicaAgent HELLO so serving-fleet traces
            # stitch like SPMD ranks do
            best = None
            for _ in range(3):
                t0 = time.time()
                wire.send(rep.sock, wire.CLOCK, lock=rep.send_lock, t0=t0)
                ccmd, cinfo, _arr = wire.recv(rep.sock)
                t1 = time.time()
                if ccmd != wire.CLOCK_R:
                    continue
                rtt = t1 - t0
                # sample = replica wall minus router wall
                sample = float(cinfo["t_server"]) - 0.5 * (t0 + t1)
                if best is None or rtt < best[0]:
                    best = (rtt, sample)
            if best is not None:
                rep.offset_s = -best[1]  # router minus replica
                # hand the replica its stitch metadata: its dumped
                # trace carries clock_offset_us so obs_stitch can
                # shift it onto the router's timeline
                wire.send(rep.sock, wire.TRACEMETA, lock=rep.send_lock,
                          offset_us=rep.offset_s * 1e6)
        except (ConnectionError, OSError):
            if not aborted.is_set():
                raise
            raise MXNetError(
                "replica %s:%d accepted the connection but never "
                "answered HELLO within %.0fs (agent bound but not "
                "serving yet?)" % (rep.addr[0], rep.addr[1], timeout))
        finally:
            if timer is not None:
                timer.cancel()
        if cmd != wire.HELLO:
            raise MXNetError("replica %s:%d answered HELLO with frame %d"
                             % (rep.addr[0], rep.addr[1], cmd))
        # unique per fleet even when two agents share a replica id
        # (hand-launched without MXTPU_REPLICA_ID)
        rep.name = "%s@%s:%d" % (info.get("name", "replica"),
                                 rep.addr[0], rep.addr[1])
        rep.ladder = list(info.get("ladder", []))
        rep.tenants = list(info.get("tenants", []))

    def _wait_first_health(self, timeout):
        deadline = time.monotonic() + timeout
        with self._lock:
            while True:
                missing = [r.name for r in self._replicas.values()
                           if r.alive and r.health is None]
                if not missing:
                    return
                if not any(r.alive for r in self._replicas.values()):
                    raise NoHealthyReplica(
                        "every replica died during router startup")
                if time.monotonic() > deadline:
                    raise MXNetError(
                        "router startup: no health reply from %s within "
                        "%.0fs" % (missing, timeout))
                self._lock.wait(0.05)

    # ------------------------------------------------------------------
    # client surface — the ModelServer contract
    # ------------------------------------------------------------------
    @property
    def tenants(self):
        with self._lock:
            names = set()
            for rep in self._replicas.values():
                names.update(rep.tenants)
        return sorted(names)

    def submit(self, tenant, inputs, timeout_ms=None, trace=None):
        """Enqueue one request on the least-loaded healthy replica;
        returns a Future resolving to [one array per model output].
        Raises NoHealthyReplica when the whole fleet is unroutable and
        RouterClosed after close() — per-request failures (timeouts,
        validation) arrive on the future, exactly like ModelServer.

        `trace` propagates an upstream trace context; when tracing is
        armed (``MXTPU_TRACE_SAMPLE`` > 0) and none is given, a
        head-sampled context is minted HERE — Router.submit is the
        trace root, and the context rides the SUBMIT frame so the
        replica's segments join the same trace
        (docs/observability.md "Request tracing & SLOs")."""
        from ..obs import tracing

        flight = _Flight(self._next_req(), tenant, inputs,
                         self._default_timeout_ms if timeout_ms is None
                         else timeout_ms)
        if trace is None and tracing.enabled():
            trace = tracing.new_trace()
        flight.trace = trace
        self._place(flight)
        return flight.future

    def submit_generate(self, tenant, tokens, max_new_tokens=None,
                        eos_id=None, timeout_ms=None, on_token=None):
        """Route one generation request to a healthy replica serving
        the generative tenant; returns a Future resolving to a
        :class:`~mxnet_tpu.serving.GenerateResult`.  `on_token` streams
        each sampled token id as it is decoded (called on the reader
        thread — keep it cheap).

        Unlike classic submissions, generative flights are NOT
        replayed when their replica dies: the session's KV cache — the
        request's real state — died with it, and silently re-decoding
        from the prompt could double-stream tokens the caller already
        consumed.  The flight fails with :class:`ReplicaDead` and the
        CALLER owns the resubmit decision (docs/serving.md)."""
        prompt = _np.asarray(tokens, dtype=_np.int32).reshape(-1)
        flight = _Flight(self._next_req(), tenant, {"data": prompt},
                         self._default_timeout_ms if timeout_ms is None
                         else timeout_ms)
        flight.generate = True
        flight.policy = {"max_new_tokens": max_new_tokens,
                         "eos_id": eos_id}
        flight.on_token = on_token
        self._place(flight)
        return flight.future

    def _next_req(self):
        with self._lock:
            self._req_seq += 1
            return self._req_seq

    def _candidates(self, tenant=None, exclude=()):
        """Placeable replicas for `tenant` — heterogeneous fleets are
        legal (hand-launched agents may serve different tenant sets),
        so a replica that does not serve the tenant is not a
        candidate, however idle it is."""
        return [(rep.name, rep.health, len(rep.inflight), rep.rebucketing)
                for rep in self._replicas.values()
                if rep.alive and rep.name not in exclude
                and (tenant is None or not rep.tenants
                     or tenant in rep.tenants)]

    def _place(self, flight, exclude=(), replay=False, fallback_exc=None):
        """Register the flight on a chosen replica and send it.  The
        registration happens under the lock; the send happens outside
        (a stalled peer must not pin the router) — a send failure
        funnels into the death path, which re-collects the flight.

        `fallback_exc` (replays only) is the replica-state error that
        triggered the replay (AdmissionError/ServerClosed): when no
        peer can take it, the caller gets THAT error — the fleet is
        merely overloaded, not dead, so it is not booked in
        ``router.lost`` either."""
        from .. import telemetry

        # failures resolve OUTSIDE the lock: flight.fail runs caller
        # done-callbacks inline, and a callback that re-enters the
        # router (retry pipelines, health logging) would deadlock the
        # reader thread on this non-reentrant lock
        fail_with = None
        book_lost = False
        # a replay does NOT restart the caller's deadline: the wire
        # carries the budget REMAINING since submit() (the ModelServer
        # contract — timeout_ms bounds time since submit, however many
        # replicas the request visits), and an already-expired flight
        # fails with the timeout it earned instead of re-dispatching
        wire_timeout = flight.timeout_ms
        if replay and wire_timeout is not None:
            wire_timeout = (float(wire_timeout)
                            - (time.monotonic() - flight.t_submit) * 1e3)
        with self._lock:
            if self._closed:
                if not replay:
                    raise RouterClosed("Router is closed; no new requests")
                # a replay landing mid-close must still RESOLVE its
                # future (the drain contract), just not re-enter
                fail_with = RouterClosed(
                    "router closed while replaying the request to "
                    "tenant %r" % flight.tenant)
                book_lost = fallback_exc is None
            elif replay and wire_timeout is not None and wire_timeout <= 0:
                fail_with = RequestTimeout(
                    "request to tenant %r: deadline (timeout_ms=%s) "
                    "expired before the replay could reach a peer"
                    % (flight.tenant, flight.timeout_ms))
            else:
                try:
                    name = pick_replica(self._candidates(flight.tenant,
                                                         exclude))
                except NoHealthyReplica:
                    served = set()
                    for r in self._replicas.values():
                        if r.alive:
                            served.update(r.tenants)
                    if (not replay and served
                            and flight.tenant not in served):
                        # the fleet is routable, it just has no replica
                        # SERVING this tenant: that is the ModelServer
                        # unknown-tenant validation error, and like every
                        # per-request failure it lands on the caller's
                        # OWN future, not the fleet verdict
                        fail_with = MXNetError(
                            "unknown tenant %r (tenants: %s)"
                            % (flight.tenant, ", ".join(sorted(served))))
                    elif not replay:
                        raise
                    else:
                        fail_with = fallback_exc or NoHealthyReplica(
                            "request to tenant %r lost its replica and no "
                            "healthy peer remains to replay it"
                            % flight.tenant)
                        book_lost = fallback_exc is None
                else:
                    rep = self._replicas[name]
                    flight.replica = name
                    self._flights[flight.req_id] = flight
                    rep.inflight.add(flight.req_id)
        from ..obs import tracing

        if fail_with is not None:
            if book_lost and telemetry.enabled():
                # a failed DEATH replay is a lost caller future (the
                # observability contract: router.lost counts futures
                # the drain-on-death machinery could not save — an
                # overload bounce or an expired deadline is not a
                # loss, the request got the answer it had coming)
                telemetry.inc("router.lost")
            flight.fail(fail_with)
            if tracing.enabled() and flight.trace is not None:
                # failures are always explained, sampled or not
                tracing.record_outcome(
                    flight.trace,
                    "timeout" if isinstance(fail_with, RequestTimeout)
                    else "error",
                    flight.t_submit, time.monotonic(), side="router",
                    tenant=flight.tenant, error=type(fail_with).__name__)
            return
        if replay and telemetry.enabled():
            telemetry.inc("router.redispatches")
        trace_meta = None
        if tracing.enabled() and flight.trace is not None:
            trace_meta = tracing.to_meta(flight.trace)
        flight.t_sent = time.monotonic()
        try:
            if flight.generate:
                wire.send(rep.sock, wire.GENERATE, lock=rep.send_lock,
                          arrays=flight.inputs, req=flight.req_id,
                          tenant=flight.tenant,
                          timeout_ms=wire_timeout,
                          stream=flight.on_token is not None,
                          **flight.policy)
            else:
                wire.send(rep.sock, wire.SUBMIT, lock=rep.send_lock,
                          arrays=flight.inputs, req=flight.req_id,
                          tenant=flight.tenant, names=flight.names,
                          timeout_ms=wire_timeout, trace=trace_meta)
        except (ConnectionError, OSError) as e:
            self._on_death(rep, e)
            return
        if tracing.enabled() and flight.trace is not None:
            # open the router->replica causal flow arrow at the send
            tracing.flow(flight.trace, "submit", "s",
                         tracing.wall(flight.t_sent))

    def warmup(self, timeout=600.0):
        """Broadcast WARMUP so every replica compiles every (tenant,
        bucket) program before traffic; returns total programs visited.
        Blocks until each replica ACKs (one XLA compile per cold
        program — hence the generous default)."""
        # phase 1 — send WARMUP to every replica first: the compiles run
        # CONCURRENTLY across the fleet (independent processes), so
        # bring-up costs one sweep, not N.  ctl_pending suppresses the
        # staleness verdict while each agent compiles (the WARMUP
        # stalls its connection — frames are handled in order — so no
        # HEALTH answers arrive; on a cold real-model fleet the sweep
        # runs for tens of seconds and must not read as a death).
        armed = []
        for rep in list(self._replicas.values()):
            # a rebucketing replica already has a warmup-scoped control
            # op outstanding (the ladder push IS a re-warm): issuing a
            # second would make its acks ambiguous — skip it
            if not rep.alive or rep.rebucketing:
                continue
            with self._lock:
                rep.ctl_pending += 1
            try:
                wire.send(rep.sock, wire.WARMUP, lock=rep.send_lock)
            except (ConnectionError, OSError) as e:
                with self._lock:
                    rep.ctl_pending -= 1
                self._on_death(rep, e)
                continue
            armed.append(rep)
        # phase 2 — collect every ack (death sentinels arrive here too),
        # decrementing ctl_pending for ALL armed replicas before any
        # raise so a partial failure cannot leave staleness suppressed
        total, errors = 0, []
        for rep in armed:
            try:
                ack = rep.acks.get(timeout=timeout)
            except _queue.Empty:
                ack = {"error": "no warmup ACK within %.0fs" % timeout}
            with self._lock:
                rep.ctl_pending -= 1
            if "error" in ack:
                errors.append("%s: %s" % (rep.name, ack["error"]))
            else:
                total += int(ack.get("programs", 0))
        if errors:
            raise MXNetError("router warmup failed: %s"
                             % "; ".join(errors))
        return total

    def health(self):
        """The fleet verdict: per-replica liveness + last health
        snapshot age, the dead list (by name — the chaos-test
        attribution surface), and the router's own flight count.
        Each replica row surfaces its ``memory`` headroom section
        (live/budget/headroom bytes + per-tenant KV rings) lifted out
        of the HEALTH snapshot so placement logic does not have to dig
        through the raw health dict."""
        now = time.monotonic()
        with self._lock:
            dead = self._book.dead()
            reps = {}
            for rep in self._replicas.values():
                reps[rep.name] = {
                    "alive": rep.alive,
                    "usable": rep.alive and bool(
                        rep.health and rep.health.get("healthy")),
                    "inflight": len(rep.inflight),
                    "ladder": list(rep.ladder),
                    "rebucketing": rep.rebucketing,
                    "health_age_s": (None if rep.health_at is None
                                     else now - rep.health_at),
                    "memory": (rep.health or {}).get("memory"),
                    "health": rep.health,
                }
            return {
                "replicas": reps,
                "dead": dead,
                "replicas_alive": sum(r.alive
                                      for r in self._replicas.values()),
                "inflight": len(self._flights),
                "closed": self._closed,
            }

    def close(self, drain=True, shutdown_replicas=False, timeout=600.0):
        """Stop the router.  ``drain=True`` waits for every in-flight
        future to resolve first; ``drain=False`` fails them with
        RouterClosed.  ``shutdown_replicas=True`` additionally sends
        CLOSE so the agent processes drain and exit (the launcher
        fleet teardown).  Idempotent."""
        with self._lock:
            if self._closed and self._stop.is_set():
                return
            self._closed = True
            if drain:
                deadline = time.monotonic() + timeout
                # pending replays count too: a flight popped by a death
                # handler but not yet re-placed is still owed a result
                while self._flights or self._pending_replays:
                    if not any(r.alive for r in self._replicas.values()):
                        break  # death path fails the rest
                    if time.monotonic() > deadline:
                        raise MXNetError(
                            "Router.close(timeout=%.0f) expired with %d "
                            "futures still in flight — call close() "
                            "again to keep waiting, or close(drain="
                            "False) to fail them" % (timeout,
                                                     len(self._flights)))
                    self._lock.wait(0.1)
            doomed = list(self._flights.values())
            self._flights.clear()
            for rep in self._replicas.values():
                rep.inflight.clear()
        from ..obs import tracing

        for flight in doomed:
            flight.fail(RouterClosed(
                "Router.close(drain=False) dropped the in-flight request "
                "to tenant %r" % flight.tenant))
            if tracing.enabled() and flight.trace is not None:
                tracing.record_outcome(
                    flight.trace, "error", flight.t_submit,
                    time.monotonic(), side="router",
                    tenant=flight.tenant, error="RouterClosed")
        self._stop.set()
        self._poller.join(timeout=5.0)
        for rep in list(self._replicas.values()):
            if shutdown_replicas and rep.alive:
                with self._lock:
                    rep.ctl_pending += 1  # a long drain is not a death
                try:
                    wire.send(rep.sock, wire.CLOSE, lock=rep.send_lock,
                              drain=drain)
                    rep.acks.get(timeout=timeout)
                except (ConnectionError, OSError, _queue.Empty):
                    pass  # agent already gone: teardown is best-effort
                finally:
                    with self._lock:
                        rep.ctl_pending -= 1
            with self._lock:
                if rep.alive:
                    # clean deregistration: a replica that was alive at
                    # close() must never age into the dead list (the
                    # chaos-test attribution surface) just because the
                    # poll loop stopped stamping beats
                    self._book.finalize(rep.name)
                rep.alive = False
            try:
                rep.sock.close()
            except OSError:
                pass
        for rep in self._replicas.values():
            if rep.reader is not None:
                rep.reader.join(timeout=5.0)

    # ------------------------------------------------------------------
    # per-replica reader — results, errors, health, control acks
    # ------------------------------------------------------------------
    def _read_loop(self, rep):
        while True:
            # the WHOLE body is the funnel, not just the recv:
            # connection drops, decode garbage, and malformed-but-
            # parseable frames (a version-skewed agent sending RESULT
            # without a req id) must all land in the death path — a
            # handler exception that killed only this thread would
            # leave a silently dead reader behind an alive=True
            # replica, its futures hanging until the staleness verdict
            try:
                cmd, info, arrays = wire.recv(rep.sock)
                with self._lock:
                    self._book.beat(rep.name)
                if cmd == wire.RESULT:
                    self._resolve(rep, info, arrays)
                elif cmd == wire.TOKEN:
                    self._note_token(rep, info)
                elif cmd == wire.RERROR:
                    self._resolve_error(rep, info)
                elif cmd == wire.HEALTH_R:
                    self._note_health(rep, info)
                elif cmd == wire.ACK:
                    self._note_ack(rep, info)
            except Exception as e:
                self._on_death(rep, e)
                return

    def _pop_flight(self, rep, req_id):
        with self._lock:
            flight = self._flights.pop(req_id, None)
            if flight is not None:
                self._replicas[flight.replica].inflight.discard(req_id)
            self._lock.notify_all()
        return flight

    def _resolve(self, rep, info, arrays):
        from .. import telemetry
        from ..obs import tracing

        flight = self._pop_flight(rep, info["req"])
        if flight is None:
            return  # late duplicate of a replayed request: already owned
        now = time.monotonic()
        if flight.generate:
            from ..serving.decode import GenerateResult

            toks = (arrays or [_np.zeros((0,), _np.int32)])[0]
            flight.fulfil(GenerateResult(
                toks, info.get("finish_reason", "length"),
                int(info.get("prompt_len", 0))))
        else:
            flight.fulfil(list(arrays or []))
        if telemetry.enabled():
            telemetry.inc("router.requests")
            telemetry.observe("router.route_seconds", now - flight.t_submit)
        if tracing.enabled() and flight.trace is not None:
            tr = flight.trace
            if tr.sampled:
                t_sent = (flight.t_sent if flight.t_sent is not None
                          else flight.t_submit)
                # router-side segments: submit -> wire send is
                # router_queue; the cross-process gaps are named too,
                # from the replica's boundary stamps mapped onto this
                # clock with the HELLO offset — so the whole chain
                # tiles [submit, resolve] with no unattributed gap
                tracing.record(tr, "router_queue", flight.t_submit,
                               t_sent, replica=rep.name)
                reply = info.get("trace_reply") or {}
                if reply:
                    t_recv_w = float(reply["t_recv"]) + rep.offset_s
                    t_done_w = float(reply["t_done"]) + rep.offset_s
                    tracing.record(tr, "wire", tracing.wall(t_sent),
                                   t_recv_w, wall_time=True,
                                   replica=rep.name)
                    tracing.record(tr, "reply", t_done_w,
                                   tracing.wall(now), wall_time=True,
                                   replica=rep.name)
                tracing.flow(tr, "reply", "f", tracing.wall(now))
            # a redispatched request that SUCCEEDED still records its
            # root span (force) — "ended in redispatch" is one of the
            # always-explained outcomes
            tracing.record_outcome(tr, "ok", flight.t_submit, now,
                                   force=flight.redispatches > 0,
                                   side="router", tenant=flight.tenant,
                                   redispatches=flight.redispatches)

    def _note_token(self, rep, info):
        """One streamed TOKEN for an in-flight GENERATE: look the
        flight up WITHOUT popping (the final RESULT closes it) and
        forward to the caller's on_token.  A token for a finished or
        unknown flight is silently dropped — frames on the connection
        are ordered, so this only happens after a local failure
        already resolved the future."""
        with self._lock:
            flight = self._flights.get(info.get("req"))
        if flight is None or flight.on_token is None:
            return
        try:
            flight.on_token(int(info["token"]))
        except BaseException:  # noqa: BLE001 — foreign callback
            pass  # a client callback must never kill the reader

    def _resolve_error(self, rep, info):
        req_id = info.get("req")
        if req_id is None:
            # a failed CONTROL op (warmup): unwedge whoever waits on it
            self._note_ack(rep, {"error": info.get("msg", "control error")})
            return
        kind, msg = info.get("kind", ""), info.get("msg", "")
        # pop AND book the pending replay under ONE lock acquisition:
        # with two, close(drain=True) could observe the gap (_flights
        # already empty, _pending_replays not yet bumped), return, and
        # the replay would bounce off _closed — failing a future that
        # had budget and a healthy peer AFTER close() reported drained
        will_replay = False
        with self._lock:
            flight = self._flights.pop(req_id, None)
            if flight is not None:
                self._replicas[flight.replica].inflight.discard(req_id)
                # generative flights never replay (submit_generate
                # docstring): the error could arrive after tokens
                # streamed, and a replay would re-decode them
                will_replay = (kind in _REPLAYABLE_KINDS
                               and not flight.generate
                               and flight.redispatches
                               < self._redispatch_cap)
                if will_replay:
                    flight.redispatches += 1
                    self._pending_replays += 1
            self._lock.notify_all()
        if flight is None:
            return
        from ..obs import tracing

        mapped = _ERROR_KINDS.get(kind, MXNetError)(
            "replica %s: %s" % (rep.name, msg))
        if will_replay:
            # the REPLICA is full/draining, the request is fine: replay
            # to a peer — and if none can take it, surface the ORIGINAL
            # overload error (the ModelServer contract), not a death
            if tracing.enabled() and flight.trace is not None:
                # forced marker: a redispatched request is explained
                # end-to-end even when head-unsampled
                tracing.record_event(flight.trace, "redispatch",
                                     force=True, reason=kind,
                                     replica=rep.name)
            try:
                self._place(flight, exclude=(rep.name,), replay=True,
                            fallback_exc=mapped)
            finally:
                with self._lock:
                    self._pending_replays -= 1
                    self._lock.notify_all()
            return
        flight.fail(mapped)
        if tracing.enabled() and flight.trace is not None:
            tracing.record_outcome(
                flight.trace,
                "timeout" if kind == "RequestTimeout" else "error",
                flight.t_submit, time.monotonic(), side="router",
                tenant=flight.tenant, error=kind, replica=rep.name)

    def _note_health(self, rep, info):
        now = time.monotonic()
        fire_adapt = None
        with self._lock:
            rep.health = info
            rep.health_at = now
            if "ladder" in info and not rep.rebucketing:
                rep.ladder = list(info["ladder"])
            serving = info.get("serving") or {}
            if serving and self._adapt_window_s > 0 and not rep.rebucketing:
                if rep.adapt_base is None:
                    rep.adapt_base, rep.adapt_at = serving, now
                elif now - rep.adapt_at >= self._adapt_window_s:
                    fire_adapt = (dict(rep.adapt_base), dict(serving))
                    rep.adapt_base, rep.adapt_at = serving, now
            self._lock.notify_all()
        if fire_adapt is not None:
            self._maybe_adapt(rep, *fire_adapt)

    def _note_ack(self, rep, info):
        from .. import telemetry

        # correlate by the ack's op tag: only a WARMUP-scoped ack (an
        # explicit op="warmup", or a warmup RERROR — the one control op
        # that errors without a req id) may close an async ladder push.
        # A CLOSE ack always reaches the waiting close() call — without
        # the tag, a ladder push racing shutdown would swallow it and
        # close() would block its full timeout
        warmup_scoped = info.get("op") == "warmup" or "error" in info
        closes_push = False
        with self._lock:
            if rep.rebucketing and warmup_scoped:
                rep.rebucketing = False
                closes_push = True
                if "error" not in info and "ladder" in info:
                    rep.ladder = list(info["ladder"])
        if closes_push:
            if "error" not in info and telemetry.enabled():
                telemetry.inc("router.ladder_pushes")
            return
        rep.acks.put(info)

    # ------------------------------------------------------------------
    # drain-on-death re-dispatch
    # ------------------------------------------------------------------
    def _on_death(self, rep, exc):
        """A replica vanished: mark it dead, collect every flight it
        held, replay each to a healthy peer from its submit-time
        snapshot (bounded by the redispatch cap)."""
        from .. import telemetry

        with self._lock:
            if not rep.alive:
                return  # reader and a failed send both funnel here
            rep.alive = False
            rep.rebucketing = False
            self._book.left(rep.name)
            doomed = [self._flights.pop(rid)
                      for rid in sorted(rep.inflight)
                      if rid in self._flights]
            rep.inflight.clear()
            # these flights are out of the table but still owed a
            # resolution: close(drain=True) must wait for them
            self._pending_replays += len(doomed)
            healthy_now = sum(
                1 for r in self._replicas.values()
                if r.alive and r.health and r.health.get("healthy"))
            self._lock.notify_all()
        try:
            rep.sock.close()
        except OSError:
            pass
        if telemetry.enabled():
            telemetry.set_gauge("router.replicas_healthy", healthy_now)
            telemetry.inc("router.replica_deaths")
        # unblock any control waiter (warmup()/close()) parked on this
        # replica's ack queue — the death is known NOW; without the
        # sentinel they would sit out their full timeout
        rep.acks.put({"error": "replica %s died: %s" % (rep.name, exc)})
        from ..obs import tracing

        for flight in doomed:
            try:
                if flight.generate:
                    # the session's KV cache died with the replica; a
                    # silent replay could double-stream tokens the
                    # caller already consumed — fail, caller resubmits
                    flight.fail(ReplicaDead(
                        "generation on tenant %r: replica %s died (%s) "
                        "mid-session; generative flights are not "
                        "replayed (the KV-cache state died with the "
                        "replica) — resubmit the prompt"
                        % (flight.tenant, rep.name, exc)))
                    if telemetry.enabled():
                        telemetry.inc("router.lost")
                    if tracing.enabled() and flight.trace is not None:
                        tracing.record_outcome(
                            flight.trace, "error", flight.t_submit,
                            time.monotonic(), side="router",
                            tenant=flight.tenant, error="ReplicaDead",
                            replica=rep.name)
                    continue
                if flight.redispatches >= self._redispatch_cap:
                    flight.fail(ReplicaDead(
                        "request to tenant %r: replica %s died (%s) and "
                        "the re-dispatch budget (redispatch_cap"
                        "=%d) is spent" % (flight.tenant, rep.name, exc,
                                           self._redispatch_cap)))
                    if telemetry.enabled():
                        telemetry.inc("router.lost")
                    if tracing.enabled() and flight.trace is not None:
                        tracing.record_outcome(
                            flight.trace, "error", flight.t_submit,
                            time.monotonic(), side="router",
                            tenant=flight.tenant, error="ReplicaDead",
                            replica=rep.name)
                    continue
                flight.redispatches += 1
                if tracing.enabled() and flight.trace is not None:
                    tracing.record_event(flight.trace, "redispatch",
                                         force=True, reason="replica_death",
                                         replica=rep.name)
                self._place(flight, exclude=(rep.name,), replay=True)
            finally:
                with self._lock:
                    self._pending_replays -= 1
                    self._lock.notify_all()

    # ------------------------------------------------------------------
    # the poll loop — heartbeat, staleness, gauges, ladder adaptation
    # ------------------------------------------------------------------
    def _poll_loop(self):
        from .. import telemetry

        while not self._stop.wait(self._poll_s):
            stale = []
            with self._lock:
                for rep in self._replicas.values():
                    if rep.alive and (rep.rebucketing or rep.ctl_pending):
                        # an outstanding re-warm / control op stalls
                        # the conn on purpose (frames are handled in
                        # order behind it): suppress staleness like
                        # the watchdog's compile bracket
                        self._book.beat(rep.name)
                dead_names = set(self._book.dead())
                for rep in self._replicas.values():
                    if rep.alive and rep.name in dead_names:
                        stale.append(rep)
            for rep in stale:
                self._on_death(rep, "no health reply for %.1fs"
                               % self._dead_after)
            # gauge AFTER the stale pass: counting before it would
            # overwrite _on_death's corrected value and report a dead
            # replica healthy for a whole poll interval
            with self._lock:
                healthy = sum(
                    1 for r in self._replicas.values()
                    if r.alive and r.health and r.health.get("healthy"))
            if telemetry.enabled():
                telemetry.set_gauge("router.replicas_healthy", healthy)
                telemetry.set_gauge("router.inflight", len(self._flights))
            for rep in list(self._replicas.values()):
                if not rep.alive:
                    continue
                try:
                    wire.send(rep.sock, wire.HEALTH, lock=rep.send_lock)
                except (ConnectionError, OSError) as e:
                    self._on_death(rep, e)

    def _maybe_adapt(self, rep, base, cur):
        """One adaptation window closed for `rep`: derive the mean fill
        from the counter deltas and push a better ladder if one exists."""
        d_used = cur.get("slots_used", 0) - base.get("slots_used", 0)
        d_disp = cur.get("dispatches", 0) - base.get("dispatches", 0)
        if d_disp < 5:
            return  # too little traffic to call a drift
        mean_fill = d_used / float(d_disp)
        with self._lock:
            ladder = list(rep.ladder)
        if not ladder:
            return
        new = derive_ladder(mean_fill, ladder, ladder[-1])
        if new is None:
            return
        with self._lock:
            # never push into a closing fleet, and never overlap a
            # synchronous control op (ctl_pending): two outstanding
            # WARMUPs on one connection would make their acks ambiguous
            if (self._closed or not rep.alive or rep.rebucketing
                    or rep.ctl_pending):
                return
            rep.rebucketing = True
        try:
            wire.send(rep.sock, wire.WARMUP, lock=rep.send_lock,
                      buckets=new)
        except (ConnectionError, OSError) as e:
            self._on_death(rep, e)
