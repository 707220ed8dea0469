"""Async distributed snapshots — the write half of ``mxnet_tpu.ckpt``.

Design (docs/checkpoint.md): at a dispatch boundary each rank captures
the full training state D2H — params/aux off the executor (a read of the
post-update arrays, never the donated inputs), the name-keyed optimizer
state (``Updater.states``), the lr-scheduler counters, both host RNG
streams, and the data cursor ``(epoch, batch_index)`` — then hands the
serialized payload to a BACKGROUND engine op (the ``serve_stage``
pattern, serving/session.py: ``atomic=False`` push with an in-band
error queue) that writes the shard file tmp-then-rename.  The file I/O
overlaps the next K-step dispatches; the training thread only ever
blocks on the PREVIOUS write, at the next trigger, by which point it has
almost always finished.

Commit is deferred by one trigger: once every rank's shard for step S is
drained (and, multi-process, a ``sync_global_devices`` barrier proves
it cluster-wide), rank 0 renames ``manifest-sS.json.tmp`` into place —
the checkpoint exists from that instant and never before.  A kill at
ANY point leaves either the previous committed checkpoint or the new
one, never a torn restore (ckpt/atomic.py).

State identity across ranks: on the data-parallel mesh every process
holds the full (replicated) param/optimizer host copy and — by the SPMD
seed contract (every rank seeds ``HOST_RNG`` identically and draws one
seed per dispatch in lockstep, executor._next_seed) — the identical RNG
stream.  Every rank therefore writes a complete shard, and ANY subset
of survivors can restore from any one of them: the redundancy the
elastic shrink path (ckpt/elastic.py) rides.
"""
from __future__ import annotations

import os
import pickle
import queue as _queue
import time

from ..base import MXNetError
from . import atomic

__all__ = ["CheckpointManager", "capture_state"]


def _rank_count():
    """(process_index, process_count) — (0, 1) for a single-process run
    (jax.process_index works unconditionally once a backend exists, and
    by first-snapshot time the training stack has long initialized it)."""
    import jax

    return jax.process_index(), jax.process_count()


def capture_state(module, epoch, batch_index, step):
    """One rank's complete resume state as a host-side dict (all numpy /
    plain python — nothing in the payload keeps a device buffer alive).

    The D2H read happens here, synchronously, OFF the donated-buffer
    path: ``get_params`` reads the executor's post-update arrays (the
    dispatch outputs, not its donated inputs) and the Updater's state
    leaves were written back host-side by the same dispatch."""
    import numpy as np

    from ..ops.random_ops import GLOBAL_RNG, HOST_RNG

    if not (module.binded and module.params_initialized):
        raise MXNetError("cannot snapshot an unbound/uninitialized module")
    args, auxs = module.get_params()
    updater = getattr(module, "_updater", None)
    if module.optimizer_initialized and updater is None:
        raise MXNetError(
            "checkpointing the kvstore-side update path is not supported: "
            "optimizer state lives on the servers (use kvstore=None, the "
            "fused-dispatch path, for elastic training)")
    opt = getattr(module, "_optimizer", None)
    payload = {
        "format": atomic.MANIFEST_FORMAT,
        "step": int(step),
        "epoch": int(epoch),
        "batch_index": int(batch_index),
        "args": {k: np.asarray(v.asnumpy()) for k, v in args.items()},
        "auxs": {k: np.asarray(v.asnumpy()) for k, v in auxs.items()},
        "updater": updater.get_states() if updater is not None else None,
        "opt": None if opt is None else {
            "num_update": int(opt.num_update),
            "begin_num_update": int(opt.begin_num_update),
            "index_update_count": dict(opt._index_update_count),
        },
        "host_rng": HOST_RNG.get_state(),
        "global_rng": GLOBAL_RNG.get_state(),
    }
    return payload


def _mesh_desc(module):
    mesh = getattr(module, "_mesh", None)
    if mesh is None:
        return None
    return {"axes": list(mesh.axis_names),
            "shape": [int(mesh.shape[a]) for a in mesh.axis_names]}


class CheckpointManager:
    """Arm periodic async snapshots on a training loop.

    ``Module.fit`` drives it: :meth:`note_dispatch` after every device
    dispatch (snapshot when the step budget is due), :meth:`epoch_end`
    at each epoch boundary (commit + elastic regrow-yield check),
    :meth:`finalize` when the loop exits.  All ranks of an SPMD job must
    drive the SAME manager schedule — triggers align by determinism of
    the dispatch sequence, and the commit barrier assumes it.

    `directory` / `keep` default to ``MXTPU_CKPT_DIR`` /
    ``MXTPU_CKPT_KEEP`` (config.py).  `every_steps` is the cadence in
    training steps (0 = off); `async_write=False` writes shards
    synchronously, for debugging.
    """

    def __init__(self, directory=None, every_steps=0, keep=None,
                 async_write=True, data_seed=0, knobs=None):
        from .. import config

        self.directory = (directory if directory is not None
                          else config.get("MXTPU_CKPT_DIR"))
        self.every_steps = int(every_steps)
        self.keep = int(keep if keep is not None
                        else config.get("MXTPU_CKPT_KEEP"))
        self.async_write = bool(async_write)
        self.enabled = bool(self.directory) and self.every_steps > 0
        self.data_seed = int(data_seed)
        self.knobs = dict(knobs or {})
        self.yielded = False
        self._global_step = 0
        self._last_snap = 0
        self._var = None          # engine var serializing the write ops
        self._pending = None      # (step, handoff queue) of the in-flight write
        self._commit_step = None  # step whose manifest awaits rename
        if self.enabled:
            os.makedirs(self.directory, exist_ok=True)

    # ------------------------------------------------------------------
    # trigger plumbing
    # ------------------------------------------------------------------
    def set_global_step(self, step):
        """Seed the step counter after a resume so snapshot cadence (and
        shard/manifest names) continue the interrupted run's sequence."""
        self._global_step = int(step)
        self._last_snap = int(step)

    def note_dispatch(self, module, epoch, batch_index, steps=1):
        """Called once per device dispatch; `batch_index` is the count
        of batches CONSUMED so far this epoch (the resume cursor)."""
        self._global_step += int(steps)
        if not self.enabled:
            return
        if self._global_step - self._last_snap >= self.every_steps:
            self.snapshot(module, epoch, batch_index)

    def snapshot(self, module, epoch, batch_index):
        """Take one snapshot now: drain+commit the previous write, then
        schedule this step's shard write in the background."""
        if not self.enabled:
            return
        self._drain_commit()
        self._last_snap = self._global_step
        self._write(module, epoch, batch_index, self._global_step)

    def epoch_end(self, module, next_epoch):
        """Epoch-boundary service: commit any pending snapshot, then —
        if an elastic regrow was requested (ckpt/elastic.py) — cut a
        boundary checkpoint at ``(next_epoch, 0)`` and mark the manager
        yielded so the caller can exit for the full-width relaunch."""
        if not self.enabled:
            return
        self._drain_commit()
        from . import elastic

        if elastic.regrow_requested(self.directory):
            if self._global_step > self._last_snap or not atomic.list_manifests(self.directory):
                self._last_snap = self._global_step
                self._write(module, next_epoch, 0, self._global_step)
            self._drain_commit()
            self.yielded = True

    def finalize(self):
        """Commit whatever write is still in flight (fit exit path)."""
        if self.enabled:
            self._drain_commit()

    # ------------------------------------------------------------------
    # the async write + deferred commit
    # ------------------------------------------------------------------
    def _write(self, module, epoch, batch_index, step):
        from .. import engine, telemetry
        from ..obs import recorder

        rank, nranks = _rank_count()
        t0 = time.time()
        payload = capture_state(module, epoch, batch_index, step)
        blob = pickle.dumps(payload, protocol=pickle.HIGHEST_PROTOCOL)
        # census: the D2H blob rides host RAM until the async write
        # retires it — book here, unbook in _io's finally below
        blob_booked = 0
        if telemetry.enabled():
            from ..obs import memory

            blob_booked = len(blob)
            memory.book("ckpt_blobs", blob_booked)
        if telemetry.enabled():
            telemetry.inc("ckpt.snapshots")
            telemetry.observe("ckpt.d2h_seconds", time.time() - t0)
            telemetry.set_gauge("ckpt.last_step", step)
        if recorder.enabled():
            # post-mortem attribution: "which snapshot was in flight";
            # the exit lands at commit time (_drain_commit)
            recorder.record("ckpt", "enter", step,
                            detail="snapshot(e%d,b%d)" % (epoch, batch_index),
                            nbytes=len(blob))
        spath = atomic.shard_path(self.directory, rank, step)
        manifest = None
        if rank == 0:
            manifest = {
                "format": atomic.MANIFEST_FORMAT,
                "step": step, "epoch": int(epoch),
                "batch_index": int(batch_index),
                "seed": self.data_seed,
                "nranks": nranks,
                "mesh_shape": _mesh_desc(module),
                "knobs": dict(self.knobs,
                              steps_per_dispatch=getattr(
                                  module, "_steps_per_dispatch", 1),
                              every_steps=self.every_steps),
                "shards": [os.path.basename(
                    atomic.shard_path(self.directory, r, step))
                    for r in range(nranks)],
                "wall_time": time.time(),
            }
        handoff = _queue.Queue(1)
        mpath = atomic.manifest_path(self.directory, step)

        def _io(_blob=blob, _spath=spath, _manifest=manifest, _mpath=mpath,
                _q=handoff, _booked=blob_booked):
            # errors travel in-band (serve_stage convention): a deferred
            # engine error would leave the trainer blocked on the
            # handoff at the next drain forever
            try:
                import json as _json

                t0 = time.time()
                n = atomic.write_bytes(_spath, _blob)
                if _manifest is not None:
                    # the manifest is STAGED (tmp file), not committed:
                    # the rename is the host thread's commit act, after
                    # the cluster-wide barrier proves every shard landed
                    with open(_mpath + ".tmp", "w") as f:
                        _json.dump(_manifest, f, indent=2, sort_keys=True)
                        f.flush()
                        os.fsync(f.fileno())
                if telemetry.enabled():
                    telemetry.inc("ckpt.bytes", n)
                    telemetry.observe("ckpt.write_seconds",
                                      time.time() - t0)
                _q.put(None)
            except BaseException as e:  # pragma: no cover - error path
                _q.put(e)
            finally:
                if _booked:
                    from ..obs import memory

                    memory.unbook("ckpt_blobs", _booked)

        if self.async_write:
            if self._var is None:
                self._var = engine.new_variable()
            engine.push(_io, write_vars=(self._var,), atomic=False,
                        name="ckpt_write")
        else:
            _io()
        self._pending = (step, handoff)
        self._commit_step = step
        if not self.async_write:
            self._drain_commit()

    def _drain_commit(self):
        """Block on the in-flight shard write (usually long done — it
        overlapped the dispatches since), then commit its manifest:
        barrier so every rank's shard is durable, rank-0 renames."""
        if self._pending is not None:
            step, handoff = self._pending
            err = handoff.get()
            self._pending = None
            if err is not None:
                raise MXNetError("checkpoint shard write for step %d "
                                 "failed: %s" % (step, err))
        if self._commit_step is None:
            return
        step, self._commit_step = self._commit_step, None
        rank, nranks = _rank_count()
        if nranks > 1:
            from ..parallel import multihost

            # every rank reaches here with its shard durable; after the
            # barrier rank 0 knows ALL shards are, and may commit.  A
            # COORDINATION-SERVICE barrier, deliberately: the next
            # dispatch's gradient all-reduce is usually still in flight
            # on the gloo pairs, and a device-collective barrier would
            # interleave with it (multihost.coordination_barrier)
            multihost.coordination_barrier("ckpt_commit_s%d" % step)
        if rank == 0:
            mpath = atomic.manifest_path(self.directory, step)
            os.replace(mpath + ".tmp", mpath)
            atomic.prune(self.directory, self.keep)
        from .. import telemetry
        from ..obs import recorder

        if telemetry.enabled():
            telemetry.inc("ckpt.commits")
        if recorder.enabled():
            recorder.record("ckpt", "exit", step)
