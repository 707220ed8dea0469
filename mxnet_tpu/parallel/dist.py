"""Distributed parameter-server backend.

TPU-native replacement for the reference's ps-lite stack (SURVEY.md §2 ⚙9):
  * Scheduler  ≙ ps::Postoffice + dmlc tracker — rank assignment, address
    book, barriers, liveness (reference kvstore_dist.h:144-170).
  * Server     ≙ KVStoreDistServer (reference kvstore_dist_server.h:136-228)
    — per-key stores, sync-mode aggregation applying the optimizer once all
    workers contributed, async-mode immediate updates, command channel
    (kStopServer / kSyncMode / optimizer shipping).
  * Worker     ≙ KVStoreDist — key sharding over servers: arrays above
    MXNET_KVSTORE_BIGARRAY_BOUND elements are split evenly over ALL servers,
    small keys go to hash(key) % num_servers (reference kvstore_dist.h:
    276-320 EncodeKey).

Topology comes from the reference's env contract: DMLC_ROLE,
DMLC_PS_ROOT_URI, DMLC_PS_ROOT_PORT, DMLC_NUM_WORKER, DMLC_NUM_SERVER.

Transport is length-prefixed binary frames over TCP (numpy raw payloads —
no pickling of tensor data).  The optimizer object shipped by
`set_optimizer` IS pickled, mirroring the reference's python-pickled
optimizer (python/mxnet/kvstore.py set_optimizer); this assumes the
cluster is the user's own, as in the reference.

On TPU pods the gradient path for `dist_sync` data-parallelism should
normally be XLA collectives over ICI/DCN (one SPMD executable — see
executor.py); this process-based PS exists for full capability parity:
`dist_async` (Hogwild semantics have no collective mapping) and
parameter-server-style topologies.
"""
from __future__ import annotations

import os
import pickle
import socket
import struct
import threading
import time

import numpy as np

from ..base import MXNetError
from .. import locks

__all__ = ["LivenessBook", "Scheduler", "Server", "DistKVStore",
           "run_scheduler", "run_server"]

# frame commands
_REGISTER = 1
_ADDRS = 2
_BARRIER = 3
_BARRIER_DONE = 4
_INIT = 5
_PUSH = 6
_PULL = 7
_VALUE = 8
_COMMAND = 9
_STOP = 10
_ACK = 11
_SETSYNC = 12
_HEARTBEAT = 13
_DEADNODES = 14
_DEADNODES_R = 15
_ERROR = 16
_FINALIZE = 17

BIGARRAY_BOUND = int(os.environ.get("MXNET_KVSTORE_BIGARRAY_BOUND", 1 << 20))
# liveness knobs (reference analog: ps-lite heartbeats + CheckDeadNodes,
# kvstore_dist.h:158-170)
HEARTBEAT_INTERVAL = float(os.environ.get("MXNET_KVSTORE_HEARTBEAT_INTERVAL", "2"))
DEAD_NODE_TIMEOUT = float(os.environ.get("MXNET_KVSTORE_DEAD_TIMEOUT", "60"))
BARRIER_TIMEOUT = float(os.environ.get("MXNET_KVSTORE_BARRIER_TIMEOUT", "300"))
# version-gated pull wait limit: past it a server replies with an error
# instead of serving a stale value
PULL_TIMEOUT = 60.0
# scheduler wait limit for every role to register at start-up
REGISTER_TIMEOUT = 600.0


# ----------------------------------------------------------------------
# framing: [u32 total_len][u8 cmd][u32 meta_len][meta bytes][payload bytes]
# ----------------------------------------------------------------------


def _send_frame(sock, cmd, meta=b"", payload=b""):
    header = struct.pack("<IBI", 1 + 4 + len(meta) + len(payload), cmd, len(meta))
    sock.sendall(header + meta + payload)


def _recv_exact(sock, n, started=False):
    buf = bytearray()
    while len(buf) < n:
        try:
            chunk = sock.recv(n - len(buf))
        except socket.timeout:
            if not buf and not started:
                raise  # clean timeout between frames
            continue  # mid-frame: keep reading, never desync the stream
        if not chunk:
            raise ConnectionError("peer closed")
        buf.extend(chunk)
    return bytes(buf)


def _recv_frame(sock):
    (total,) = struct.unpack("<I", _recv_exact(sock, 4))
    body = _recv_exact(sock, total, started=True)
    cmd = body[0]
    (meta_len,) = struct.unpack("<I", body[1:5])
    meta = body[5 : 5 + meta_len]
    payload = body[5 + meta_len :]
    return cmd, meta, payload


def _connect_retry(addr, timeout=60.0):
    """Connect with retry — roles race at startup (slow jax imports).

    The returned socket BLOCKS: create_connection's timeout would
    otherwise persist as a 60 s recv deadline on every RPC, and on an
    oversubscribed host a healthy server can be starved past that
    (observed during multi-process test compile storms).  Liveness is the
    scheduler's job (heartbeats + dead-node detection), matching ps-lite's
    blocking vans; callers that need a bounded wait set their own
    deadline (barrier, dead-node polls)."""
    deadline = time.time() + timeout
    while True:
        try:
            sock = socket.create_connection(addr, timeout=60)
            sock.settimeout(None)
            return sock
        except (ConnectionRefusedError, OSError):
            if time.time() > deadline:
                raise
            time.sleep(0.2)


def _meta(**kwargs):
    return repr(kwargs).encode()


def _parse_meta(meta):
    import ast

    return ast.literal_eval(meta.decode()) if meta else {}


# ----------------------------------------------------------------------
# Liveness bookkeeping — shared by the PS scheduler and the serving
# router (mxnet_tpu/router): who is alive, who deregistered cleanly,
# who vanished
# ----------------------------------------------------------------------


class LivenessBook:
    """Per-node liveness ledger: last-seen stamps, clean deregistrations
    (ps-lite Finalize), and vanished connections.  ``dead()`` is the
    CheckDeadNodes answer — nodes that left WITHOUT finalizing, plus
    nodes whose last stamp is older than `timeout`.

    NOT internally synchronized: the owner (Scheduler under its
    condition lock, Router under its own lock) brackets every call —
    one lock discipline instead of two nested ones."""

    def __init__(self, timeout=None):
        self.timeout = DEAD_NODE_TIMEOUT if timeout is None else float(timeout)
        self._last_seen = {}  # node -> monotonic timestamp
        self._left = set()  # nodes whose connection closed
        self._finalized = set()  # clean deregistrations

    def beat(self, node):
        self._last_seen[node] = time.monotonic()

    def left(self, node):
        """The node's connection dropped (dead unless it finalized)."""
        self._left.add(node)

    def finalize(self, node):
        """Clean deregistration: never reported dead afterwards."""
        self._finalized.add(node)

    def revive(self, node):
        """A recovered node rejoins under its old identity: clear every
        verdict and restamp."""
        self._left.discard(node)
        self._finalized.discard(node)
        self.beat(node)

    def dead(self):
        """Sorted dead-node list: left-without-finalize first, then
        silent nodes past the heartbeat timeout."""
        now = time.monotonic()
        dead = sorted(self._left - self._finalized)
        for node, seen in self._last_seen.items():
            if node in self._left or node in self._finalized:
                continue
            if now - seen > self.timeout:
                dead.append(node)
        return dead

    def unclean(self):
        """Nodes that vanished without finalizing (exit-code accounting:
        run_scheduler propagates these as failure)."""
        return set(self._left) - self._finalized


# ----------------------------------------------------------------------
# Scheduler — rank assignment + address book + barrier (Postoffice analog)
# ----------------------------------------------------------------------


class Scheduler:
    def __init__(self, port, num_workers, num_servers):
        self.num_workers = num_workers
        self.num_servers = num_servers
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("", port))
        self.sock.listen(128)
        self._lock = locks.condition("dist.scheduler")
        self._server_addrs = {}
        self._ranks = {"worker": 0, "server": 0}
        self._barrier_waiters = []
        self._book = LivenessBook()  # guarded by self._lock
        self._send_locks = {}  # id(conn) -> Lock serializing frame sends
        self._current_conn = {}  # node -> id(conn) of its LIVE connection
        self._worker_threads = []
        self._stopped = False

    def _send(self, conn, cmd, meta=b""):
        """Serialize sends per connection — a dead-node wakeup and a
        barrier reply racing on one socket would interleave mid-frame."""
        lock = self._send_locks.setdefault(id(conn),
                                           locks.lock("dist.conn_send"))
        with lock:
            _send_frame(conn, cmd, meta)

    def _dead_nodes(self):
        """Nodes that vanished WITHOUT a _FINALIZE deregistration.  A clean
        exit (FINALIZE then close) is never reported dead — matching ps-lite,
        where Finalize() removes the node before the connection drops."""
        return self._book.dead()

    def serve_forever(self):
        """Register num_workers+num_servers nodes, then service barriers,
        heartbeats, dead-node queries — and late RECOVERY registrations
        (ps-lite is_recovery(): a restarted role rejoins under its old
        rank, servers retain state; reference kvstore_dist.h:39-44) —
        until all workers disconnect."""
        conns = []
        pending_recovery = []
        # a role that dies BEFORE registering would otherwise hang this
        # loop (and any launcher waiting on the scheduler) forever
        deadline = time.monotonic() + REGISTER_TIMEOUT
        self.sock.settimeout(1.0)
        while len(conns) < self.num_workers + self.num_servers:
            try:
                conn, _ = self.sock.accept()
            except socket.timeout:
                if time.monotonic() > deadline:
                    raise MXNetError(
                        "scheduler: only %d/%d nodes registered within "
                        "%.0fs (dist.REGISTER_TIMEOUT)"
                        % (len(conns), self.num_workers + self.num_servers,
                           REGISTER_TIMEOUT))
                continue
            cmd, meta, _ = _recv_frame(conn)
            assert cmd == _REGISTER
            info = _parse_meta(meta)
            if int(info.get("recover", -1)) >= 0:
                # a rejoining WORKER racing the startup window must NOT be
                # assigned a fresh rank (it would inflate the member count
                # and desync barrier accounting): park it until the
                # original membership is fully registered.  Same guard as
                # _accept_recovery: only workers recover.
                if info.get("role") == "worker":
                    pending_recovery.append((conn, info))
                else:
                    conn.close()
                continue
            role = info["role"]
            with self._lock:
                rank = self._ranks[role]
                self._ranks[role] += 1
                if role == "server":
                    self._server_addrs[rank] = (info["host"], info["port"])
                node = "%s:%d" % (role, rank)
                self._book.beat(node)
                self._current_conn[node] = conn
            conns.append((conn, role, rank))
        self.sock.settimeout(None)
        # everyone registered: broadcast address book + ranks
        addrs = [self._server_addrs[r] for r in sorted(self._server_addrs)]
        for conn, role, rank in conns:
            self._send(conn, _ADDRS, _meta(rank=rank, servers=addrs))
        # serve every node's connection (workers barrier, all heartbeat)
        with self._lock:
            for conn, role, rank in conns:
                t = threading.Thread(target=self._serve_conn,
                                     args=(conn, role, rank), daemon=True)
                t.start()
                if role == "worker":
                    self._worker_threads.append(t)
        # recoveries parked during the startup window rejoin first
        for conn, info in pending_recovery:
            self._handle_recovery(conn, info)
        # recovery registrations arrive on the listening socket after start
        accept_t = threading.Thread(target=self._accept_recovery, daemon=True)
        accept_t.start()
        while True:
            with self._lock:
                threads = list(self._worker_threads)
            if not any(t.is_alive() for t in threads):
                # re-check under the lock: a recovery may have just landed
                with self._lock:
                    if not any(t.is_alive() for t in self._worker_threads):
                        return
            for t in threads:
                t.join(timeout=0.5)

    def _accept_recovery(self):
        """Accept post-startup _REGISTER frames carrying recover=rank: the
        WORKER resumes its old identity; liveness bookkeeping is reset so
        peers stop seeing it dead.  (Server recovery is not a capability:
        a restarted Server has an empty store and workers hold connections
        to the old address — sync-mode jobs resume from checkpoint.)"""
        while True:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return  # listening socket closed: scheduler shutting down
            try:
                cmd, meta, _ = _recv_frame(conn)
            except (ConnectionError, OSError):
                conn.close()  # stray probe died mid-register: keep serving
                continue
            if cmd != _REGISTER:
                conn.close()
                continue
            info = _parse_meta(meta)
            if int(info.get("recover", -1)) < 0 or info.get("role") != "worker":
                conn.close()  # late non-recovery register: not a member
                continue
            self._handle_recovery(conn, info)

    def _handle_recovery(self, conn, info):
        """Rejoin a recovering WORKER under its old rank: reset liveness
        bookkeeping, supersede its stale socket, replay the address book."""
        role, rank = info["role"], int(info["recover"])
        node = "%s:%d" % (role, rank)
        with self._lock:
            self._book.revive(node)
            old = self._current_conn.get(node)
            self._current_conn[node] = conn
            addrs = [self._server_addrs[r]
                     for r in sorted(self._server_addrs)]
        if old is not None:
            # close the superseded socket: unblocks the stale
            # _serve_conn thread (else a half-open connection from a
            # power-failed host pins it, and serve_forever never exits)
            try:
                old.close()
            except OSError:
                pass
        try:
            self._send(conn, _ADDRS,
                       _meta(rank=rank, servers=addrs, recovery=1))
        except (ConnectionError, OSError):
            # the rejoiner died mid-handshake: drop it — with no serve
            # thread its last_seen simply ages back into dead via the
            # timeout, and this must never crash serve_forever (which
            # calls here inline for startup-window recoveries)
            try:
                conn.close()
            except OSError:
                pass
            return
        t = threading.Thread(target=self._serve_conn,
                             args=(conn, role, rank), daemon=True)
        t.start()
        with self._lock:
            self._worker_threads.append(t)

    def _serve_conn(self, conn, role, rank):
        node = "%s:%d" % (role, rank)
        try:
            while True:
                cmd, meta, _ = _recv_frame(conn)
                with self._lock:
                    self._book.beat(node)
                if cmd == _BARRIER:
                    done = None
                    with self._lock:
                        self._barrier_waiters.append(conn)
                        if len(self._barrier_waiters) == self.num_workers:
                            done = self._barrier_waiters
                            self._barrier_waiters = []
                            self._lock.notify_all()
                    if done is not None:
                        # send AFTER releasing the lock: sockets are
                        # blocking, so one stalled peer with a full recv
                        # buffer would otherwise pin the global lock and
                        # freeze heartbeats/dead-node queries cluster-wide
                        for c in done:
                            try:
                                self._send(c, _BARRIER_DONE)
                            except Exception:
                                pass  # dead waiter: its serve thread reports it
                elif cmd == _DEADNODES:
                    with self._lock:
                        dead = self._dead_nodes()
                    self._send(conn, _DEADNODES_R, _meta(dead=dead))
                elif cmd == _FINALIZE:
                    with self._lock:
                        self._book.finalize(node)
                    self._send(conn, _ACK)
                # _HEARTBEAT: timestamp already refreshed above
        except (ConnectionError, OSError):
            with self._lock:
                if self._current_conn.get(node) is not conn:
                    return  # stale socket of an already-recovered node
                # a closed connection counts as dead unless the job is done
                self._book.left(node)
                # a worker that died INSIDE a barrier must not keep
                # occupying a waiter slot: the next rendezvous would
                # "complete" against its dead socket and skip the live
                # replacement
                self._barrier_waiters = [c for c in self._barrier_waiters
                                         if c is not conn]
                waiters = list(self._barrier_waiters)
                dead = self._dead_nodes()
            # wake any barrier waiters so they can observe the dead node
            for c in waiters:
                try:
                    self._send(c, _DEADNODES_R, _meta(dead=dead))
                except Exception:
                    pass


# ----------------------------------------------------------------------
# Server — sharded key-value store with sync/async update application
# ----------------------------------------------------------------------


class _KeyState:
    __slots__ = ("key", "value", "version", "merge", "count", "cond")

    def __init__(self, key, value):
        self.key = key
        self.value = value
        self.version = 0
        self.merge = None
        self.count = 0
        self.cond = locks.condition("dist.entry")


class Server:
    """One parameter-server shard (reference KVStoreDistServer)."""

    def __init__(self, port, num_workers):
        self.num_workers = num_workers
        self.sync_mode = False
        self.updater = None  # (key:str, recv np, stored np) -> None
        self.command_hook = None  # (head:int, body:bytes) -> None
        self.store = {}
        self._store_lock = locks.lock("dist.server_store")
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("", port))
        self.port = self.sock.getsockname()[1]
        self.sock.listen(128)
        self._stop = threading.Event()

    def serve_forever(self):
        threads = []
        while not self._stop.is_set():
            try:
                self.sock.settimeout(0.5)
                conn, _ = self.sock.accept()
            except socket.timeout:
                continue
            t = threading.Thread(target=self._serve_conn, args=(conn,), daemon=True)
            t.start()
            threads.append(t)

    def _get_state(self, key, value=None):
        with self._store_lock:
            if key not in self.store:
                self.store[key] = _KeyState(key, value)
            return self.store[key]

    def _apply(self, st, recved):
        """Apply an aggregated gradient / pushed value to the stored weight
        (reference kvstore_dist_server.h:164-228 ApplyUpdates)."""
        if self.updater is not None:
            self.updater(st, recved)
        else:
            st.value = recved.copy()
        st.version += 1

    def _handle_command(self, head, payload):
        """One worker command.  A user controller (MXKVStoreRunServer)
        OWNS command semantics — every head goes to it and the default
        handling is skipped (reference KVStoreDistServer::set_controller
        replaces the built-in controller).  Without one, head 0 carries
        the pickled optimizer (set_optimizer) and other heads are
        acknowledged no-ops."""
        if self.command_hook is not None:
            self.command_hook(head, payload)
            return
        if head != 0:
            return
        optimizer = pickle.loads(payload)
        from .. import optimizer as opt_mod
        from ..ndarray import array

        updater = opt_mod.get_updater(optimizer)

        def apply_update(st_, recved, _updater=updater):
            w = array(st_.value)
            g = array(recved)
            _updater(st_.key, g, w)
            st_.value = np.asarray(w.asnumpy())

        self.updater = apply_update

    def _serve_conn(self, conn):
        try:
            while True:
                cmd, meta, payload = _recv_frame(conn)
                info = _parse_meta(meta)
                if cmd == _INIT:
                    key = info["key"]
                    arr = np.frombuffer(payload, dtype=info["dtype"]).reshape(info["shape"]).copy()
                    st = self._get_state(key)
                    with st.cond:
                        if st.value is None:  # re-Init of existing key ignored
                            st.value = arr
                            st.version = 0
                    _send_frame(conn, _ACK)
                elif cmd == _PUSH:
                    key = info["key"]
                    arr = np.frombuffer(payload, dtype=info["dtype"]).reshape(info["shape"])
                    st = self._get_state(key, np.zeros_like(arr))
                    with st.cond:
                        if self.sync_mode:
                            if st.merge is None:
                                st.merge = arr.copy()
                                st.count = 1
                            else:
                                st.merge += arr
                                st.count += 1
                            if st.count == self.num_workers:
                                self._apply(st, st.merge)
                                st.merge = None
                                st.count = 0
                                st.cond.notify_all()
                        else:
                            self._apply(st, arr)
                            st.cond.notify_all()
                    _send_frame(conn, _ACK)
                elif cmd == _PULL:
                    key = info["key"]
                    min_version = info.get("min_version", 0)
                    st = self._get_state(key)
                    deadline = time.monotonic() + PULL_TIMEOUT
                    timed_out = False
                    with st.cond:
                        while st.value is None or st.version < min_version:
                            remaining = deadline - time.monotonic()
                            if remaining <= 0:
                                timed_out = True
                                break
                            st.cond.wait(timeout=remaining)
                        value = st.value
                        version = st.version
                    if timed_out:
                        # never serve a stale value silently (round-1 review:
                        # dist.py:280 proceeded with possibly-stale data)
                        _send_frame(conn, _ERROR, _meta(
                            msg="pull timeout for key %r: version %d < required %d "
                                "after %.0fs (a worker likely died)"
                                % (key, version, min_version, PULL_TIMEOUT)))
                    else:
                        _send_frame(conn, _VALUE,
                                    _meta(shape=list(value.shape), dtype=str(value.dtype),
                                          version=version),
                                    value.tobytes())
                elif cmd == _SETSYNC:
                    self.sync_mode = bool(info["sync"])
                    _send_frame(conn, _ACK)
                elif cmd == _COMMAND:
                    # a bad command (unpicklable head-0 body, raising user
                    # controller) must answer _ERROR, not kill this
                    # connection thread and strand the worker's RPC
                    try:
                        self._handle_command(info.get("head", 0), payload)
                    except Exception as e:  # noqa: BLE001
                        _send_frame(conn, _ERROR,
                                    _meta(msg="command failed: %s" % e))
                    else:
                        _send_frame(conn, _ACK)
                elif cmd == _STOP:
                    _send_frame(conn, _ACK)
                    self._stop.set()
                    return
        except (ConnectionError, OSError):
            pass


# ----------------------------------------------------------------------
# Worker client
# ----------------------------------------------------------------------


class DistKVStore:
    """Distributed kvstore client (parity: reference KVStoreDist +
    python/mxnet/kvstore.py for dist types)."""

    def __init__(self, kv_type="dist_sync"):
        from ..kvstore import KVStore  # local aggregation façade

        self.type = kv_type
        self._local = KVStore("local")
        root = os.environ.get("DMLC_PS_ROOT_URI", "127.0.0.1")
        port = int(os.environ.get("DMLC_PS_ROOT_PORT", "9091"))
        self._num_workers = int(os.environ.get("DMLC_NUM_WORKER", "1"))
        self._num_servers = int(os.environ.get("DMLC_NUM_SERVER", "1"))
        self._sched = _connect_retry((root, port))
        self._sched_send_lock = locks.lock("dist.sched_send")
        self._sched_recv_lock = locks.lock("dist.sched_recv")
        # MXTPU_RECOVER_RANK: rejoin a running job under the old rank after
        # a crash (ps-lite is_recovery; reference kvstore_dist.h:39-44,77-80).
        # Servers retained state, so re-Init is ignored and the worker
        # resumes by pulling; the startup barrier and sync-mode flip are
        # skipped — the cluster is already past them.
        recover = int(os.environ.get("MXTPU_RECOVER_RANK", "-1"))
        self.is_recovery = recover >= 0
        if self.is_recovery and "async" not in self.type:
            # sync aggregation cannot absorb a mid-round rejoin: the dead
            # worker's partial merge contribution is still counted on the
            # servers, so the round would apply with a double rank-r /
            # missing-peer gradient.  Sync jobs resume from checkpoint
            # (reference practice: example/image-classification --load-epoch)
            raise MXNetError(
                "MXTPU_RECOVER_RANK is only supported for dist_async; "
                "restart %s jobs from a checkpoint instead" % self.type)
        if self.is_recovery:
            _send_frame(self._sched, _REGISTER,
                        _meta(role="worker", host="", port=0, recover=recover))
        else:
            _send_frame(self._sched, _REGISTER,
                        _meta(role="worker", host="", port=0))
        cmd, meta, _ = _recv_frame(self._sched)
        assert cmd == _ADDRS
        info = _parse_meta(meta)
        self._rank = info["rank"]
        self._server_addrs = info["servers"]
        _start_heartbeat(self._sched, self._sched_send_lock)
        self._servers = [_connect_retry(tuple(a)) for a in self._server_addrs]
        self._server_locks = [locks.lock("dist.server_conn")
                              for _ in self._servers]
        self._push_round = {}
        self._updater = None
        if self.is_recovery:
            return
        # NOTE: substring matching would be wrong here — "sync" is a
        # substring of "async", so test the async marker
        if "async" not in self.type and self._rank == 0:
            # rank-0 flips servers to sync mode (reference kvstore.cc:30-34)
            for i in range(len(self._servers)):
                self._rpc(i, _SETSYNC, _meta(sync=True))
        self.barrier()

    # -- plumbing ------------------------------------------------------
    def _rpc(self, server_i, cmd, meta=b"", payload=b"", want=(_ACK,)):
        with self._server_locks[server_i]:
            _send_frame(self._servers[server_i], cmd, meta, payload)
            rcmd, rmeta, rpayload = _recv_frame(self._servers[server_i])
        if rcmd == _ERROR:
            raise MXNetError("server %d: %s"
                             % (server_i, _parse_meta(rmeta).get("msg", "error")))
        assert rcmd in want, (rcmd, want)
        return rmeta, rpayload

    def _shards(self, key, arr):
        """Key→server placement (reference EncodeKey kvstore_dist.h:276-320):
        big arrays split evenly over all servers, small ones hashed."""
        flat = arr.reshape(-1)
        n = len(self._servers)
        if flat.size > BIGARRAY_BOUND and n > 1:
            bounds = [(i * flat.size) // n for i in range(n + 1)]
            return [(i, "%s#%d" % (key, i), flat[bounds[i]:bounds[i + 1]])
                    for i in range(n) if bounds[i + 1] > bounds[i]]
        # deterministic across processes — python's str hash is randomized
        # per process, which would scatter the same key to different servers
        import zlib

        return [(zlib.crc32(str(key).encode()) % n, str(key), flat)]

    # -- public api (parity: kvstore.py) --------------------------------
    @property
    def rank(self):
        return self._rank

    @property
    def num_workers(self):
        return self._num_workers

    def check_dead_nodes(self):
        """Nodes the scheduler considers dead (reference CheckDeadNodes via
        ps::Postoffice::GetDeadNodes, kvstore_dist.h:161-162)."""
        with self._sched_recv_lock:
            with self._sched_send_lock:
                _send_frame(self._sched, _DEADNODES)
            while True:
                # _sched_recv_lock exists to serialize request/reply
                # turns on the ONE scheduler socket; replies are
                # immediate and the heartbeat never takes this lock
                # mxlint: disable=E009 -- intentional: the lock serializes turns on the scheduler socket
                cmd, meta, _ = _recv_frame(self._sched)
                if cmd == _DEADNODES_R:
                    return _parse_meta(meta).get("dead", [])

    def barrier(self, timeout=None):
        """Global worker barrier.  Raises (instead of hanging forever) when
        the scheduler reports dead nodes or `timeout` elapses.  Bracketed
        in the flight recorder (obs/recorder.py): a rendezvous this worker
        is stuck in shows up as an open ``ps_barrier`` event in the
        watchdog post-mortem, with the per-rank progress counters saying
        which peer never arrived."""
        from ..obs import recorder

        rec_seq = None
        if recorder.enabled():
            rec_seq = recorder.record("ps_barrier", "enter",
                                      detail="rank=%d" % self._rank)
        try:
            self._barrier_impl(timeout)
        finally:
            if recorder.enabled() and rec_seq is not None:
                recorder.record("ps_barrier", "exit", rec_seq)

    def _barrier_impl(self, timeout=None):
        timeout = BARRIER_TIMEOUT if timeout is None else timeout
        deadline = time.monotonic() + timeout
        with self._sched_recv_lock:
            with self._sched_send_lock:
                _send_frame(self._sched, _BARRIER)
            self._sched.settimeout(max(HEARTBEAT_INTERVAL * 2, 1.0))
            try:
                while True:
                    try:
                        # mxlint: disable=E009 -- barrier turn on the serialized scheduler socket, bounded by settimeout + deadline
                        cmd, meta, _ = _recv_frame(self._sched)
                    except socket.timeout:
                        if time.monotonic() > deadline:
                            raise MXNetError(
                                "barrier timed out after %.0fs" % timeout)
                        with self._sched_send_lock:
                            _send_frame(self._sched, _DEADNODES)
                        continue
                    if cmd == _BARRIER_DONE:
                        return
                    if cmd == _DEADNODES_R:
                        # the barrier is a WORKER-group rendezvous (ps-lite
                        # Barrier(kWorkerGroup)): only a dead worker can
                        # leave it stuck — a flapping server heartbeat
                        # must not abort it
                        dead = [n for n in _parse_meta(meta).get("dead", [])
                                if n.startswith("worker:")]
                        if dead:
                            raise MXNetError(
                                "barrier aborted: dead nodes %s" % (dead,))
            finally:
                self._sched.settimeout(None)

    def init(self, key, value):
        keys, vals = ([key], [value]) if not isinstance(key, (list, tuple)) else (list(key), list(value))
        for k, v in zip(keys, vals):
            arr = v.asnumpy() if hasattr(v, "asnumpy") else np.asarray(v)
            if self._rank == 0:
                for si, skey, shard in self._shards(k, arr):
                    self._rpc(si, _INIT,
                              _meta(key=skey, shape=list(shard.shape), dtype=str(shard.dtype)),
                              np.ascontiguousarray(shard).tobytes())
            self._push_round[k] = 0
        # a RECOVERED worker re-declares keys without the rendezvous: the
        # cluster is mid-job and its barrier counts must stay aligned with
        # the survivors (ps-lite is_recovery skips the init barrier,
        # reference kvstore_dist.h:77-80)
        if not self.is_recovery:
            self.barrier()

    def push(self, key, value, priority=0):
        keys, vals = ([key], [value]) if not isinstance(key, (list, tuple)) else (list(key), list(value))
        for k, v in zip(keys, vals):
            if isinstance(v, (list, tuple)):
                merged = v[0].copy()
                for o in v[1:]:
                    merged += o
                arr = merged.asnumpy()
            else:
                arr = v.asnumpy() if hasattr(v, "asnumpy") else np.asarray(v)
            for si, skey, shard in self._shards(k, arr):
                self._rpc(si, _PUSH,
                          _meta(key=skey, shape=list(shard.shape), dtype=str(shard.dtype)),
                          np.ascontiguousarray(shard).tobytes())
            self._push_round[k] = self._push_round.get(k, 0) + 1

    def pull(self, key, out=None, priority=0):
        keys, outs = ([key], [out]) if not isinstance(key, (list, tuple)) else (list(key), list(out))
        for k, o in zip(keys, outs):
            first = o[0] if isinstance(o, (list, tuple)) else o
            shape = first.shape
            total = int(np.prod(shape))
            flat = np.empty((total,), dtype=np.float32)
            min_version = self._push_round.get(k, 0) \
                if "async" not in self.type else 0
            pieces = self._shards(k, flat)
            for si, skey, shard in pieces:
                meta, payload = self._rpc(
                    si, _PULL, _meta(key=skey, min_version=min_version), want=(_VALUE,)
                )
                info = _parse_meta(meta)
                got = np.frombuffer(payload, dtype=info["dtype"])
                shard[:] = got
            value = flat.reshape(shape)
            if isinstance(o, (list, tuple)):
                for oo in o:
                    oo[:] = value
            else:
                o[:] = value

    def set_optimizer(self, optimizer):
        if self._rank == 0:
            self._send_command_to_servers(0, pickle.dumps(optimizer, 0))
        self.barrier()

    def _send_command_to_servers(self, head, body):
        """Reference MXKVStoreSendCommmandToServers: (head, body) to every
        server; head 0 carries the pickled optimizer (set_optimizer)."""
        if isinstance(body, str):
            body = body.encode()
        for i in range(len(self._servers)):
            self._rpc(i, _COMMAND, _meta(head=int(head)), bytes(body))

    def _set_updater(self, updater):
        self._updater = updater

    def _barrier_before_exit(self):
        self.barrier()

    def close(self):
        """Graceful exit: barrier, rank-0 stops servers, then deregister
        from the scheduler so peers never see this node as dead (reference
        ps-lite Finalize(); kStopServer on finalize)."""
        self.barrier()
        if self._rank == 0:
            for i in range(len(self._servers)):
                try:
                    self._rpc(i, _STOP)
                except Exception:
                    pass
        try:
            with self._sched_recv_lock:
                # bounded handshake: a dead-but-not-RST scheduler must not
                # hang worker shutdown waiting for the ACK forever
                self._sched.settimeout(10.0)
                with self._sched_send_lock:
                    _send_frame(self._sched, _FINALIZE)
                while True:
                    # mxlint: disable=E009 -- finalize handshake on the serialized scheduler socket, bounded by the 10 s settimeout
                    cmd, _, _ = _recv_frame(self._sched)
                    if cmd == _ACK:
                        break
        except Exception:
            pass

    def save_optimizer_states(self, fname):
        raise MXNetError(
            "save_optimizer_states on a %r store: the optimizer runs on "
            "the server processes (set_optimizer shipped it there), so "
            "workers hold no state to save.  Checkpoint params from "
            "rank 0 only (kv.rank == 0) via Module.save_checkpoint and "
            "resume with a fresh optimizer" % self.type)

    def load_optimizer_states(self, fname):
        raise MXNetError(
            "load_optimizer_states on a %r store: the optimizer state "
            "lives on the server processes.  Resume from a rank-0 "
            "params checkpoint (Module.load + fit(begin_epoch=...)) "
            "with a fresh optimizer instead" % self.type)


# ----------------------------------------------------------------------
# role entry points (used by kvstore_server bootstrap + launcher)
# ----------------------------------------------------------------------


def run_scheduler():
    """Returns 0 when every worker deregistered cleanly (_FINALIZE), 1 if
    any vanished — launchers that cannot see worker exit codes directly
    (qsub array jobs) propagate failure through this."""
    port = int(os.environ.get("DMLC_PS_ROOT_PORT", "9091"))
    sched = Scheduler(port, int(os.environ["DMLC_NUM_WORKER"]), int(os.environ["DMLC_NUM_SERVER"]))
    try:
        sched.serve_forever()
    except MXNetError as e:
        import sys as _sys

        print("scheduler: %s" % e, file=_sys.stderr)
        return 1
    with sched._lock:
        # a server never FINALIZEs: it is stopped once the workers have, and
        # its socket may close before this thread looks (1 run in 8, PR 43)
        unclean = {n for n in sched._book.unclean()
                   if n.startswith("worker:")}
    return 1 if unclean else 0


def _start_heartbeat(sock, send_lock, stop_event=None):
    """Send-only heartbeat loop on a scheduler connection."""

    def beat():
        while stop_event is None or not stop_event.is_set():
            time.sleep(HEARTBEAT_INTERVAL)
            try:
                with send_lock:
                    _send_frame(sock, _HEARTBEAT)
            except socket.timeout:
                # transient: barrier() puts a short timeout on this shared
                # socket — a timed-out beat must not kill the loop (the node
                # would then be declared dead after DEAD_NODE_TIMEOUT)
                continue
            except (OSError, ConnectionError):
                return

    t = threading.Thread(target=beat, daemon=True)
    t.start()
    return t


def run_server(command_hook=None):
    root = os.environ.get("DMLC_PS_ROOT_URI", "127.0.0.1")
    port = int(os.environ.get("DMLC_PS_ROOT_PORT", "9091"))
    server = Server(0, int(os.environ["DMLC_NUM_WORKER"]))
    server.command_hook = command_hook
    sched = _connect_retry((root, port))
    # advertise the address workers can actually REACH: the local address
    # of the route to the scheduler (a literal 127.0.0.1 would break any
    # cross-host launch — workers would dial their own loopback)
    my_host = sched.getsockname()[0]
    _send_frame(sched, _REGISTER, _meta(role="server", host=my_host, port=server.port))
    cmd, meta, _ = _recv_frame(sched)
    assert cmd == _ADDRS
    _start_heartbeat(sched, locks.lock("dist.heartbeat_send"), server._stop)
    server.serve_forever()
