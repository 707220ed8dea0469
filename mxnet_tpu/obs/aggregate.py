"""Cluster aggregation — per-rank telemetry shipped to rank 0.

Every rank runs its own telemetry registry and flight recorder; no
single file answers "which rank is slow".  This module closes that
gap with the smallest possible control plane, reusing the
length-prefixed framing `parallel/dist.py` already ships (the same
transport the PS scheduler's heartbeat/dead-node machinery rides):

  * rank 0 runs an :class:`Aggregator` listening on ``MXTPU_OBS_PORT``
    (``tools/launch.py --local-spmd --obs`` exports a free one);
  * every rank runs a :class:`Reporter` thread that ships a small
    snapshot — steps, mean/p50 step seconds, flight-
    recorder progress counters — every ``MXTPU_OBS_INTERVAL_SECONDS``;
  * the aggregator folds the latest per-rank snapshots into one
    cluster-level JSONL record (``MXTPU_OBS_CLUSTER_FILE``) carrying
    per-rank step-time skew and straggler attribution
    (:func:`step_skew`: max/median step-time ratio + slowest rank),
    rendered by ``tools/parse_log.py --cluster``;
  * the reporter's connect handshake measures this rank's wall-clock
    offset against rank 0 (NTP-style: three pings, keep the
    minimum-RTT sample) and stamps it into the profiler's trace
    metadata, which is what lets ``tools/obs_stitch.py`` merge N
    per-rank chrome traces onto one aligned timeline;
  * the stall watchdog queries the same server (:func:`query_peers`)
    for every rank's last-known progress — the input to its
    straggler-vs-hang attribution.

Snapshots are advisory monitoring data: a dead aggregator degrades to
per-rank-only observability, never to a training failure (every send
path swallows connection errors and retries)."""
from __future__ import annotations

import json
import os
import socket
import threading
import time

from .. import locks
from ..parallel.dist import (_connect_retry, _meta, _parse_meta,
                             _recv_frame, _send_frame)

__all__ = ["Aggregator", "Reporter", "query_peers", "step_skew",
           "clock_offset_s", "bootstrap_from_env", "shutdown"]

# frame commands — disjoint from parallel/dist.py's 1-17 range so a
# frame misdirected between the two planes fails loudly
_SNAP = 41
_PING = 42
_PONG = 43
_PEERS = 44
_PEERS_R = 45

_STATE = {"aggregator": None, "reporter": None, "offset_s": 0.0}


from .recorder import own_rank as _own_rank


def _obs_endpoint():
    """(host, port) of the rank-0 aggregator from the environment, or
    None when the plane is not armed.  The host is the coordinator's
    (rank 0 runs both); port is ``MXTPU_OBS_PORT``."""
    raw = os.environ.get("MXTPU_OBS_PORT", "")
    try:
        port = int(raw) if raw else 0
    except ValueError:
        port = 0
    if port <= 0:
        return None
    coord = os.environ.get("MXTPU_COORDINATOR", "")
    host = coord.rsplit(":", 1)[0] if ":" in coord else "127.0.0.1"
    return host, port


def _hist_quantile(hist, q):
    """Upper-boundary quantile over a telemetry fixed-bucket histogram
    dict (per-bucket counts, tools/parse_log.py convention)."""
    count = hist.get("count", 0)
    if not count:
        return None
    target = q * count
    seen = 0
    for key, c in hist.get("buckets", {}).items():
        seen += c
        if seen >= target:
            return hist.get("max") if key == "le_inf" else float(key[3:])
    return hist.get("max")


def step_skew(per_rank_mean_s):
    """Straggler attribution over ``{rank: mean step seconds}``:
    ``max_over_median`` (1.0 = perfectly even; 2.0 = the slowest rank
    takes twice the median step) and which rank is slowest.  Feeds
    the aggregator's cluster records."""
    vals = {r: float(v) for r, v in (per_rank_mean_s or {}).items()
            if v is not None and float(v) > 0}
    if not vals:
        return {"max_over_median": None, "slowest_rank": None}
    ordered = sorted(vals.values())
    n = len(ordered)
    median = (ordered[n // 2] if n % 2
              else 0.5 * (ordered[n // 2 - 1] + ordered[n // 2]))
    slowest = max(vals, key=lambda r: vals[r])
    return {"max_over_median": (vals[slowest] / median) if median else None,
            "slowest_rank": slowest}


def build_snapshot(rank=None):
    """One rank's shippable digest of telemetry + flight recorder."""
    from . import recorder
    from .. import telemetry

    snap = telemetry.snapshot()
    counters, gauges = snap["counters"], snap["gauges"]
    step_h = snap["histograms"].get("module.step_seconds", {})
    count = step_h.get("count", 0)
    # collective-schedule digest (parallel/schedule_check.py): rides
    # the snapshot only when MXTPU_COLLECTIVE_CHECK=1 — the verifier's
    # cross-rank exchange reuses this exact framing, no new plane
    sched = None
    from ..parallel import schedule_check

    if schedule_check.enabled():
        sched = schedule_check.digest()
    return {
        "sched": sched,
        "rank": _own_rank() if rank is None else int(rank),
        "t_wall": time.time(),
        "steps": counters.get("module.steps", 0),
        "dispatches": counters.get("executor.train_dispatches", 0),
        "step_count": count,
        "step_mean_s": (step_h.get("sum", 0.0) / count) if count else None,
        "step_p50_s": _hist_quantile(step_h, 0.5),
        "mfu": gauges.get("module.mfu"),
        "recorder_progress": recorder.progress(),
        "clock_offset_s": _STATE["offset_s"],
    }


class Aggregator:
    """Rank 0's snapshot sink + peer directory (module docstring)."""

    def __init__(self, port, cluster_file="", interval_s=5.0):
        self.cluster_file = cluster_file
        self.interval_s = float(interval_s)
        self._latest = {}  # rank -> (t_recv_mono, snapshot)
        self._lock = locks.lock("obs.aggregate")
        self._last_write = 0.0
        self._stopped = False
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("", int(port)))
        self.port = self.sock.getsockname()[1]
        self.sock.listen(64)
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="obs_aggregator", daemon=True)
        self._accept_thread.start()

    def _accept_loop(self):
        while not self._stopped:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return  # listening socket closed
            threading.Thread(target=self._serve_conn, args=(conn,),
                             daemon=True).start()

    def _serve_conn(self, conn):
        try:
            while True:
                cmd, meta, payload = _recv_frame(conn)
                if cmd == _PING:
                    # clock handshake: echo the caller's t0 plus our
                    # wall clock; the caller NTP-folds the pair
                    info = _parse_meta(meta)
                    _send_frame(conn, _PONG,
                                _meta(t0=info.get("t0", 0.0),
                                      t_server=time.time()))
                elif cmd == _SNAP:
                    snap = json.loads(payload.decode())
                    with self._lock:
                        self._latest[int(snap["rank"])] = (time.monotonic(),
                                                           snap)
                    self._maybe_write_cluster_record()
                elif cmd == _PEERS:
                    _send_frame(conn, _PEERS_R,
                                payload=json.dumps(
                                    self.peers_view()).encode())
        except (ConnectionError, OSError):
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def peers_view(self):
        """{rank: snapshot + age_s} — the watchdog's attribution input."""
        now = time.monotonic()
        with self._lock:
            return {str(r): dict(snap, age_s=now - t)
                    for r, (t, snap) in self._latest.items()}

    def cluster_record(self):
        """Fold the latest per-rank snapshots into ONE cluster record:
        per-rank step/step-time columns + the skew attribution."""
        now = time.monotonic()
        with self._lock:
            latest = {r: (t, dict(snap)) for r, (t, snap)
                      in self._latest.items()}
        ranks = {}
        for r, (t, snap) in sorted(latest.items()):
            ranks[str(r)] = {
                "steps": snap.get("steps"),
                "dispatches": snap.get("dispatches"),
                "step_mean_s": snap.get("step_mean_s"),
                "step_p50_s": snap.get("step_p50_s"),
                "mfu": snap.get("mfu"),
                "clock_offset_s": snap.get("clock_offset_s"),
                "age_s": now - t,
            }
        skew = step_skew({r: v[1].get("step_mean_s")
                          for r, v in latest.items()})
        return {"schema": "mxtpu-obs-cluster-v1", "t_wall": time.time(),
                "monotonic_s": now, "nranks": len(ranks), "ranks": ranks,
                "skew": skew}

    def _maybe_write_cluster_record(self, force=False):
        if not self.cluster_file:
            return
        now = time.monotonic()
        with self._lock:
            if not force and now - self._last_write < self.interval_s:
                return
            self._last_write = now
        rec = self.cluster_record()
        # append under no lock beyond the throttle: one writer thread
        # per snapshot frame, and JSONL lines are single writes
        with open(self.cluster_file, "a") as f:
            f.write(json.dumps(rec) + "\n")

    def force_write(self):
        """Write one cluster record NOW, bypassing the interval throttle
        — the shutdown path, so short runs still end on a record that
        reflects their final state."""
        self._maybe_write_cluster_record(force=True)

    def seen_since(self, rank, t):
        """Has `rank`'s latest snapshot been PROCESSED at/after monotonic
        `t`?  The exit-flush ordering check: a reporter's final _SNAP is
        fire-and-forget, so rank 0's atexit must wait for the serve
        thread to stamp it before force_write — or the JSONL would end
        on a stale mid-run record whenever the write wins the race."""
        with self._lock:
            return self._latest.get(int(rank), (0.0,))[0] >= t

    def close(self):
        self._stopped = True
        try:
            self.sock.close()
        except OSError:
            pass


class Reporter(threading.Thread):
    """Per-rank snapshot shipper + clock-offset handshake."""

    def __init__(self, host, port, interval_s=5.0, rank=None,
                 snapshot_fn=None):
        super().__init__(name="obs_reporter", daemon=True)
        self.addr = (host, int(port))
        self.interval_s = float(interval_s)
        self.rank = _own_rank() if rank is None else int(rank)
        self._snapshot_fn = snapshot_fn or (
            lambda: build_snapshot(self.rank))
        self._stop_evt = threading.Event()
        self.offset_s = None  # rank-0 wall time minus local wall time
        self.final_sent_at = None  # monotonic stamp of the exit flush

    def stop(self):
        self._stop_evt.set()

    def _handshake(self, sock):
        """Three-ping NTP fold; keep the minimum-RTT sample.  Offset is
        rank-0 time MINUS local time, so local_ts + offset lands on the
        rank-0 timeline (the stitch convention)."""
        best = None
        for _ in range(3):
            t0 = time.time()
            _send_frame(sock, _PING, _meta(t0=t0))
            cmd, meta, _ = _recv_frame(sock)
            t1 = time.time()
            if cmd != _PONG:
                continue
            info = _parse_meta(meta)
            rtt = t1 - t0
            offset = float(info["t_server"]) - 0.5 * (t0 + t1)
            if best is None or rtt < best[0]:
                best = (rtt, offset)
        if best is not None:
            self.offset_s = best[1]
            _STATE["offset_s"] = best[1]
            from .. import profiler

            profiler.set_trace_meta(rank=self.rank,
                                    clock_offset_us=best[1] * 1e6)

    def run(self):
        sock = None
        while not self._stop_evt.is_set():
            try:
                if sock is None:
                    sock = _connect_retry(self.addr, timeout=30.0)
                    self._handshake(sock)
                snap = self._snapshot_fn()
                _send_frame(sock, _SNAP,
                            payload=json.dumps(snap, default=str).encode())
            except (ConnectionError, OSError, ValueError):
                # monitoring only: drop the sample, reconnect next tick
                try:
                    if sock is not None:
                        sock.close()
                except OSError:
                    pass
                sock = None
            if self._stop_evt.wait(self.interval_s):
                break
        # final flush: a short run's last interval tick can precede the
        # training steps entirely — one exit snapshot makes the cluster
        # record end on the run's real final state.  Best effort with a
        # bounded connect; never blocks shutdown on a dead aggregator.
        try:
            if sock is None:
                sock = socket.create_connection(self.addr, timeout=2.0)
                self._handshake(sock)
            _send_frame(sock, _SNAP,
                        payload=json.dumps(self._snapshot_fn(),
                                           default=str).encode())
            # the aggregator PROCESSES this strictly after the last byte
            # is delivered, i.e. after sendall returned — so a stamp
            # taken now lower-bounds the processing stamp (_atexit_flush
            # waits on it before force_write)
            self.final_sent_at = time.monotonic()
        except (ConnectionError, OSError, ValueError):
            pass
        try:
            if sock is not None:
                sock.close()
        except OSError:
            pass


def clock_offset_s():
    """This rank's measured wall-clock offset vs rank 0 (0.0 before the
    handshake / on rank 0)."""
    return _STATE["offset_s"]


def query_peers(endpoint=None, timeout=5.0):
    """One-shot peer-progress query against the aggregator: ``{rank:
    snapshot}`` (each carrying ``recorder_progress``), or ``{}`` when
    the plane is not armed or unreachable — callers (the watchdog)
    degrade to per-rank-only attribution."""
    endpoint = endpoint or _obs_endpoint()
    if endpoint is None:
        return {}
    try:
        sock = socket.create_connection(endpoint, timeout=timeout)
    except OSError:
        return {}
    try:
        sock.settimeout(timeout)
        _send_frame(sock, _PEERS)
        cmd, _meta_b, payload = _recv_frame(sock)
        if cmd != _PEERS_R:
            return {}
        raw = json.loads(payload.decode())
        return {int(r): snap for r, snap in raw.items()}
    except (OSError, ValueError):
        return {}
    finally:
        try:
            sock.close()
        except OSError:
            pass


def bootstrap_from_env():
    """Arm aggregation from the launcher environment (idempotent): when
    ``MXTPU_OBS_PORT`` is set, rank 0 starts the :class:`Aggregator`
    (cluster JSONL to ``MXTPU_OBS_CLUSTER_FILE`` if set) and EVERY rank
    starts a :class:`Reporter` at ``MXTPU_OBS_INTERVAL_SECONDS``."""
    endpoint = _obs_endpoint()
    if endpoint is None:
        return None
    raw = os.environ.get("MXTPU_OBS_INTERVAL_SECONDS", "")
    try:
        interval = float(raw) if raw else 5.0
    except ValueError:
        interval = 5.0
    if _own_rank() == 0 and _STATE["aggregator"] is None:
        _STATE["aggregator"] = Aggregator(
            endpoint[1],
            cluster_file=os.environ.get("MXTPU_OBS_CLUSTER_FILE", ""),
            interval_s=interval)
    if _STATE["reporter"] is None:
        _STATE["reporter"] = Reporter(endpoint[0], endpoint[1],
                                      interval_s=interval)
        _STATE["reporter"].start()
        import atexit

        atexit.register(_atexit_flush)
    return _STATE["reporter"]


def _atexit_flush():
    """Process-exit hook: ship one final snapshot (Reporter.run's
    final-flush path) and, on rank 0, force one last cluster record so
    the JSONL ends on the run's final state."""
    rep = _STATE["reporter"]
    if rep is not None:
        rep.stop()
        rep.join(timeout=5.0)
    agg = _STATE["aggregator"]
    if agg is not None:
        try:
            if rep is not None and rep.final_sent_at is not None:
                # bounded wait for the final snapshot to be PROCESSED
                # (frames on the reporter connection land in order, so
                # a stamp at/after the send means it — or something
                # even fresher — is in): on an idle host this is one
                # loop iteration; under load it is the difference
                # between the JSONL ending on the run's final state
                # and ending on a stale mid-run record
                deadline = time.monotonic() + 2.0
                while (not agg.seen_since(rep.rank, rep.final_sent_at)
                       and time.monotonic() < deadline):
                    time.sleep(0.02)
            agg.force_write()
        except Exception:  # pragma: no cover — shutdown best effort
            pass
        agg.close()


def shutdown():
    """Stop the module-level reporter/aggregator (tests)."""
    if _STATE["reporter"] is not None:
        _STATE["reporter"].stop()
        _STATE["reporter"] = None
    if _STATE["aggregator"] is not None:
        _STATE["aggregator"].close()
        _STATE["aggregator"] = None
