"""ReplicaAgent — one ModelServer behind a socket.

One agent process wraps ONE :class:`~mxnet_tpu.serving.ModelServer`
(one device's continuous batcher) and speaks the serve wire protocol
(wire.py) so a :class:`~mxnet_tpu.router.Router` in another process
can drive it: SUBMIT enqueues into the server and streams RESULT /
RERROR frames back as futures resolve (out of order — the batcher,
not the wire, owns scheduling), HEALTH answers the
``ModelServer.health()`` probe plus the ``serving.*`` telemetry
extract the router's ladder adaptation feeds on, WARMUP (re)compiles
bucket programs — with a NEW ladder when the router pushes one — and
CLOSE drains and exits.

Fleets are launched by ``tools/launch.py --serve-replicas N``: each
replica process gets ``MXTPU_REPLICA_ID`` and its own
``MXTPU_ROUTER_PORT``, builds its tenants, and calls
``ReplicaAgent(tenants).serve_forever()``.

Rebucketing (the traffic-adaptive ladder): the ladder is fixed at
ModelServer construction, so a WARMUP carrying a different bucket
list drains the current server (every outstanding future resolves —
the snapshot/drain semantics PR 7 guarantees) and stands up a fresh
one over the SAME predictors with the new ladder.  Frames on a
connection are handled in order, so submissions behind the WARMUP
simply queue in the socket until the re-warm finishes; the router
suppresses its staleness verdict for the duration (the same
discipline as the obs watchdog's compile bracket).
"""
from __future__ import annotations

import os
import socket
import threading
import time

from ..base import MXNetError
from ..serving.server import ModelServer
from . import wire
from .. import locks

__all__ = ["ReplicaAgent"]


def _serving_extract(tenants=()):
    """The ladder-adaptation + SLO slice of the telemetry registry:
    exact cumulative fill accounting, the request-latency histogram
    moments, the queue/service split p99s (WHICH segment moved when a
    tenant's p99 burns), and the per-tenant SLO ledger declared at
    ``add_tenant(slo_ms=)``.  Counters are process-wide, which is
    exactly right here — one agent process serves one ModelServer."""
    from .. import telemetry

    if not telemetry.enabled():
        return {}
    # point reads, not snapshot(): the probe answers every
    # Router(poll_ms=) per connected router, and a full-registry
    # deep copy (every histogram ladder) on that cadence is real work
    lat_count, lat_sum = telemetry.histogram_moments(
        "serving.request_seconds")
    slo = {}
    for t in tenants:
        budget = telemetry.gauge_value("slo.budget_ms.%s" % t)
        if budget is None:
            continue
        slo[t] = {
            "budget_ms": budget,
            "target": telemetry.gauge_value("slo.target.%s" % t),
            "burn": telemetry.gauge_value("slo.burn.%s" % t),
            "availability": telemetry.gauge_value(
                "slo.availability.%s" % t),
        }
    return {
        "slots_used": telemetry.counter_value("serving.batch_slots_used"),
        "slots_padded": telemetry.counter_value(
            "serving.batch_slots_padded"),
        "dispatches": telemetry.counter_value("serving.dispatches"),
        "requests": telemetry.counter_value("serving.requests"),
        "batch_fill_ratio": telemetry.gauge_value(
            "serving.batch_fill_ratio"),
        "request_seconds_count": lat_count,
        "request_seconds_sum": lat_sum,
        # the latency-localization split (docs/observability.md
        # "Request tracing & SLOs"): queue-wait vs fill-to-resolution
        "queue_p99": telemetry.histogram_quantile(
            "serving.queue_seconds", 0.99),
        "service_p99": telemetry.histogram_quantile(
            "serving.service_seconds", 0.99),
        "slo": slo,
    }


def _check_one_chip():
    """A replica that ``launch.py --serve-replicas`` bound to one chip
    (TPU_CHIPS_PER_PROCESS_BOUNDS=1,1,1) must see exactly that chip:
    seeing more means the binding did not take and this process holds
    chips its sibling replicas need."""
    import jax

    if (os.environ.get("TPU_CHIPS_PER_PROCESS_BOUNDS") == "1,1,1"
            and jax.default_backend() == "tpu"
            and jax.local_device_count() != 1):
        raise MXNetError(
            "replica was launched on one chip (TPU_VISIBLE_CHIPS=%s) but "
            "sees %d: %s" % (os.environ.get("TPU_VISIBLE_CHIPS"),
                             jax.local_device_count(), jax.local_devices()))


class ReplicaAgent:
    """Serve one ModelServer to remote routers (module docstring).

    `tenants` maps name -> Predictor, exactly as ModelServer takes
    them; the ModelServer knobs pass through.  `port` 0 binds an
    ephemeral port (read back from :attr:`port` — the test/driver
    pattern); None takes ``MXTPU_ROUTER_PORT`` (what
    ``launch.py --serve-replicas`` exports per replica)."""

    def __init__(self, tenants, port=None, replica_id=None, max_batch=None,
                 buckets=None, timeout_ms=None, max_queue=None, wait_ms=None,
                 generative=None):
        from .. import config

        _check_one_chip()
        self._tenants = dict(tenants)
        # what this replica's tenants compute on, as JAX reports it —
        # rides every HEALTH reply so a router-side report can name the
        # device without the router process touching JAX
        import jax

        from ..context import default_device

        dev = next((p._ctx.jax_device() for p in self._tenants.values()),
                   None) or default_device()
        self._device = {"platform": dev.platform,
                        "device_kind": dev.device_kind,
                        "device_count": jax.local_device_count()}
        # generative tenants: name -> {"model": lm, "params": {...},
        # **add_generative_tenant kwargs}; re-registered on every server
        # (re)construction (the rebucket swap included)
        self._generative = {k: dict(v) for k, v in (generative or {}).items()}
        # None = ModelServer's own default
        self._server_kw = {
            k: v for k, v in dict(
                max_batch=max_batch, timeout_ms=timeout_ms,
                max_queue=max_queue, wait_ms=wait_ms).items()
            if v is not None}
        self.replica_id = (int(replica_id) if replica_id is not None
                           else config.get("MXTPU_REPLICA_ID"))
        self.name = "replica:%d" % self.replica_id
        if port is None:
            port = config.get("MXTPU_ROUTER_PORT")
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind(("", int(port)))
        self._sock.listen(16)
        self.port = self._sock.getsockname()[1]
        # serializes SUBMIT's server grab against WARMUP's server swap
        # (rebucketing) and CLOSE
        self._server_lock = locks.rlock("router.agent_server")
        self._server = self._make_server(buckets)
        self._stop = threading.Event()

    def _make_server(self, buckets):
        server = ModelServer(self._tenants, buckets=buckets,
                             **self._server_kw)
        for name, spec in self._generative.items():
            spec = dict(spec)
            server.add_generative_tenant(name, spec.pop("model"),
                                         spec.pop("params"), **spec)
        return server

    @property
    def ladder(self):
        with self._server_lock:
            return list(self._server.ladder)

    def warmup(self, buckets=None):
        """Compile every (tenant, bucket) program now — call before
        serve_forever() so the fleet comes up warm (the router's
        warmup() broadcast re-runs this remotely; re-warming an
        already-warm ladder is a cheap jit-cache sweep)."""
        with self._server_lock:
            return self._server.warmup(buckets)

    def close(self, drain=True):
        """Stop serving: drain (or fail) the queue, resolve every
        future, stop the accept loop.  Idempotent."""
        self._stop.set()
        try:
            self._sock.close()
        except OSError:
            pass
        with self._server_lock:
            self._server.close(drain=drain)

    # ------------------------------------------------------------------
    # the serve loop
    # ------------------------------------------------------------------
    def serve_forever(self):
        """Accept router connections until CLOSE (or close()).  Each
        connection gets its own handler thread; agents typically serve
        exactly one router, but a second connection (a probing
        dashboard, a draining predecessor router) is legal."""
        self._sock.settimeout(0.5)
        threads = []
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except socket.timeout:
                continue
            except OSError:
                break  # close() pulled the listening socket
            t = threading.Thread(target=self._serve_conn, args=(conn,),
                                 name="replica_conn", daemon=True)
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=5.0)

    def _serve_conn(self, conn):
        send_lock = locks.lock("router.conn_send")
        try:
            while True:
                cmd, info, arrays = wire.recv(conn)
                if cmd == wire.HELLO:
                    wire.send(conn, wire.HELLO, lock=send_lock,
                              replica=self.replica_id, name=self.name,
                              tenants=sorted(set(self._tenants)
                                             | set(self._generative)),
                              generative=sorted(self._generative),
                              ladder=self.ladder)
                elif cmd == wire.SUBMIT:
                    self._handle_submit(conn, send_lock, info, arrays)
                elif cmd == wire.GENERATE:
                    self._handle_generate(conn, send_lock, info, arrays)
                elif cmd == wire.CLOCK:
                    # NTP-style clock leg (the obs/aggregate.py recipe):
                    # echo the router's t0 plus our wall clock; the
                    # router folds the pair into the stitch offset
                    wire.send(conn, wire.CLOCK_R, lock=send_lock,
                              t0=info.get("t0", 0.0),
                              t_server=time.time())
                elif cmd == wire.TRACEMETA:
                    # the router's measured offset (router wall minus
                    # ours): stamped into our profiler trace so
                    # tools/obs_stitch.py can shift this replica's
                    # spans onto the router's timeline
                    from .. import profiler

                    profiler.set_trace_meta(
                        clock_offset_us=float(info.get("offset_us", 0.0)))
                elif cmd == wire.HEALTH:
                    self._handle_health(conn, send_lock)
                elif cmd == wire.WARMUP:
                    self._handle_warmup(conn, send_lock, info)
                elif cmd == wire.CLOSE:
                    self.close(drain=bool(info.get("drain", True)))
                    wire.send(conn, wire.ACK, lock=send_lock, op="close")
                    return
                else:
                    raise MXNetError("replica agent: unknown frame "
                                     "command %d" % cmd)
        except (ConnectionError, OSError):
            # the router went away: keep serving — in-flight fills
            # complete and resolve locally; a successor router
            # reconnects (drain-on-death is the ROUTER's job for its
            # callers, the agent's job is to never wedge)
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def _handle_submit(self, conn, send_lock, info, arrays):
        from ..obs import tracing

        t_recv = time.time()
        req_id = info["req"]
        inputs = dict(zip(info["names"], arrays or []))
        ctx = tracing.from_meta(info.get("trace"))
        if tracing.enabled() and ctx is not None:
            # close the router->replica causal flow arrow at receipt
            tracing.flow(ctx, "submit", "f", t_recv)
        with self._server_lock:
            server = self._server
        try:
            fut = server.submit(info["tenant"], inputs,
                                timeout_ms=info.get("timeout_ms"),
                                trace=ctx)
        except BaseException as e:  # noqa: BLE001 — travels the wire
            self._send_error(conn, send_lock, req_id, e)
            return

        def _reply(f, _req=req_id, _conn=conn, _lock=send_lock,
                   _ctx=ctx, _t_recv=t_recv):
            exc = f.exception()
            extra = {}
            if tracing.enabled() and _ctx is not None and _ctx.sampled:
                t_done = time.time()
                # replica wall boundary stamps: the router maps them
                # onto its own timeline with the HELLO clock offset and
                # records the cross-process `wire`/`reply` segments
                extra["trace_reply"] = {"t_recv": _t_recv,
                                        "t_done": t_done}
                tracing.flow(_ctx, "reply", "s", t_done)
            try:
                if exc is not None:
                    self._send_error(_conn, _lock, _req, exc)
                else:
                    wire.send(_conn, wire.RESULT, lock=_lock, req=_req,
                              arrays=f.result(), **extra)
            except (ConnectionError, OSError):
                pass  # router died mid-reply: its successor replays

        fut.add_done_callback(_reply)

    def _handle_generate(self, conn, send_lock, info, arrays):
        """One GENERATE flight: enqueue into the server's generative
        tenant, stream a TOKEN frame per sampled token (when the router
        asked to — ``stream``), close with RESULT carrying the full
        generated-token array + finish metadata.  TOKEN frames are sent
        from the batcher thread under the connection's send lock, so
        they interleave whole-frame with concurrent RESULT callbacks."""
        req_id = info["req"]
        prompt = (arrays or [None])[0]
        on_token = None
        if info.get("stream"):
            counter = iter(range(1 << 62))

            def on_token(token, _req=req_id, _conn=conn, _lock=send_lock,
                         _seq=counter):
                try:
                    wire.send(_conn, wire.TOKEN, lock=_lock, req=_req,
                              token=int(token), seq=next(_seq))
                except (ConnectionError, OSError):
                    pass  # router died: generation still resolves locally

        with self._server_lock:
            server = self._server
        try:
            fut = server.submit_generate(
                info["tenant"], prompt,
                max_new_tokens=info.get("max_new_tokens"),
                eos_id=info.get("eos_id"),
                timeout_ms=info.get("timeout_ms"), on_token=on_token)
        except BaseException as e:  # noqa: BLE001 — travels the wire
            self._send_error(conn, send_lock, req_id, e)
            return

        def _reply(f, _req=req_id, _conn=conn, _lock=send_lock):
            exc = f.exception()
            try:
                if exc is not None:
                    self._send_error(_conn, _lock, _req, exc)
                else:
                    r = f.result()
                    wire.send(_conn, wire.RESULT, lock=_lock, req=_req,
                              arrays=[r.tokens], generate=True,
                              finish_reason=r.finish_reason,
                              prompt_len=r.prompt_len)
            except (ConnectionError, OSError):
                pass  # router died mid-reply; generative flights are
                #       not replayed (the KV state died with us)

        fut.add_done_callback(_reply)

    def _send_error(self, conn, send_lock, req_id, exc):
        try:
            wire.send(conn, wire.RERROR, lock=send_lock, req=req_id,
                      kind=type(exc).__name__, msg=str(exc))
        except (ConnectionError, OSError):
            pass

    def _handle_health(self, conn, send_lock):
        with self._server_lock:
            health = self._server.health()
        health["replica"] = self.replica_id
        health["name"] = self.name
        health["device"] = self._device
        health["serving"] = _serving_extract(health.get("tenants", ()))
        wire.send(conn, wire.HEALTH_R, lock=send_lock, **health)

    def _handle_warmup(self, conn, send_lock, info):
        buckets = info.get("buckets")
        try:
            with self._server_lock:
                if buckets and list(buckets) != list(self._server.ladder):
                    # rebucket: drain the old server (every future
                    # resolves), stand up the new ladder on the same
                    # predictors, compile it before answering
                    self._server.close(drain=True)
                    self._server = self._make_server(list(buckets))
                programs = self._server.warmup()
                ladder = list(self._server.ladder)
        except BaseException as e:  # noqa: BLE001 — travels the wire
            self._send_error(conn, send_lock, None, e)
            return
        wire.send(conn, wire.ACK, lock=send_lock, op="warmup",
                  programs=programs, ladder=ladder)
