"""Multi-host SPMD runtime — the DCN-scale story.

Parity target: the reference scales past one host with ps-lite over
TCP/RDMA (`parallel/dist.py` reimplements that control plane).  The
TPU-native data plane is different: every host runs the SAME SPMD
program, JAX's distributed runtime stitches the per-host PJRT clients
into one global device list, and XLA lowers collectives so intra-slice
traffic rides ICI while cross-host hops ride DCN — no parameter server
in the gradient path at all (the "How to Scale Your Model" recipe).

This module packages that: `initialize()` bootstraps from the same
DMLC_* / MXTPU_* environment `tools/launch.py` already exports (so the
reference launcher workflow starts multi-host SPMD jobs unchanged),
`global_mesh()` builds a mesh over ALL hosts' devices, and
`host_local_batch()` carves out this host's slice of the global batch
(per-host input pipelines, the standard multi-host data-loading
pattern).

Verified by real multi-process tests: `tests/test_multihost.py` spawns
N OS processes that each initialize the distributed runtime over a CPU
"DCN" and jit one global-psum training step.
"""
from __future__ import annotations

import os

import jax

__all__ = ["initialize", "is_initialized", "global_mesh",
           "host_local_batch", "make_global_array", "sync_global_devices",
           "fetch"]

_STATE = {"initialized": False}


def initialize(coordinator=None, num_processes=None, process_id=None,
               local_device_count=None):
    """Join (or create) a multi-host SPMD job.

    Defaults come from the launcher environment: MXTPU_COORDINATOR or
    DMLC_PS_ROOT_URI:PORT+1 for the coordinator address, DMLC_NUM_WORKER
    for world size, MXTPU_PROCESS_ID / DMLC_WORKER_ID for the rank.  On
    real TPU pods jax.distributed discovers these from the TPU metadata
    instead — then all arguments may be None.

    local_device_count forces per-process CPU device count (testing);
    it defaults to MXTPU_LOCAL_DEVICES when the launcher exported one
    (tools/launch.py --local-spmd --local-devices)."""
    if _STATE["initialized"]:
        return
    if local_device_count is None:
        env_n = int(os.environ.get("MXTPU_LOCAL_DEVICES", "0"))
        local_device_count = env_n if env_n > 0 else None
    if local_device_count is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        flags = " ".join(f for f in flags.split() if not f.startswith(
            "--xla_force_host_platform_device_count"))
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=%d"
            % local_device_count).strip()
    if coordinator is None:
        coordinator = os.environ.get("MXTPU_COORDINATOR")
    if coordinator is None and os.environ.get("DMLC_PS_ROOT_URI"):
        # launcher env: scheduler host, one port above the PS port
        coordinator = "%s:%d" % (os.environ["DMLC_PS_ROOT_URI"],
                                 int(os.environ.get("DMLC_PS_ROOT_PORT",
                                                    "9091")) + 1)
    if num_processes is None:
        num_processes = int(os.environ.get("DMLC_NUM_WORKER", "1"))
    if process_id is None:
        process_id = int(os.environ.get(
            "MXTPU_PROCESS_ID", os.environ.get("DMLC_WORKER_ID", "0")))
    if num_processes > 1 or coordinator is not None:
        # a localhost "DCN" of CPU processes all-reduces over gloo, the
        # default jax_cpu_collectives_implementation (TPU jobs ignore it)
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)
    _STATE["initialized"] = True
    # arm the distributed observability plane from the same launcher
    # environment (obs/: rank-0 aggregation + clock-offset handshake
    # when MXTPU_OBS_PORT is set, stall watchdog when
    # MXTPU_OBS_STALL_SECONDS > 0).  Monitoring must never be able to
    # fail mesh bring-up, so problems degrade to a warning.
    try:
        from ..obs import bootstrap as _obs_bootstrap

        _obs_bootstrap()
    except Exception as e:  # pragma: no cover — defensive
        import warnings

        warnings.warn("observability bootstrap failed: %s" % e)


def is_initialized():
    return _STATE["initialized"]


def global_mesh(axes=None, hierarchical=False):
    """Mesh over ALL processes' devices from {'axis': size} (-1 inferred).

    Device order is jax.devices() — process-major, so a leading 'data'
    axis puts whole hosts in distinct data shards and cross-host traffic
    is the gradient all-reduce on DCN, the efficient layout.

    ``hierarchical=True`` (with axes=None) names the topology instead of
    flattening it: {'data_dcn': process_count, 'data_ici': local_devices}
    — the same device order, with the two levels named so a sharding
    (or a shard_map'd collective) can tell the intra-host ICI axis from
    the cross-host DCN one.  Degenerates to a flat
    {'data': -1} mesh when only one of the two levels has size > 1."""
    from .mesh import make_mesh

    if hierarchical:
        assert axes is None, "hierarchical=True builds its own axes"
        n_proc = jax.process_count()
        n_local = jax.device_count() // max(1, n_proc)
        if n_proc > 1 and n_local > 1:
            axes = {"data_dcn": n_proc, "data_ici": n_local}
        else:
            axes = {"data": -1}
    elif axes is None:
        axes = {"data": -1}
    return make_mesh(axes, devices=jax.devices())


def host_local_batch(global_batch_size):
    """(start, stop) row range of the global batch this host must load —
    per-host input pipelines feed disjoint slices (the multi-host data
    pattern; replaces the reference's per-worker `part_index`/`num_parts`
    RecordIO splitting at DCN scale)."""
    n = jax.process_count()
    i = jax.process_index()
    per = global_batch_size // n
    assert global_batch_size % n == 0, \
        "global batch %d not divisible by %d hosts" % (global_batch_size, n)
    return i * per, (i + 1) * per


def make_global_array(mesh, spec, host_data, batch_axis=0):
    """Assemble a globally-sharded array from this host's local rows
    (jax.make_array_from_process_local_data) — the device_put analog that
    works when no single host holds the full batch."""
    from jax.sharding import NamedSharding

    return jax.make_array_from_process_local_data(
        NamedSharding(mesh, spec), host_data)


def sync_global_devices(tag="barrier"):
    """Cross-host barrier (useful around checkpoint writes).  Bracketed
    in the flight recorder: a peer that never arrives leaves this
    rank's enter event open, which is exactly what the stall watchdog
    (obs/watchdog.py) reports with the barrier tag."""
    from jax.experimental import multihost_utils

    from ..obs import recorder

    seq = None
    if recorder.enabled():
        seq = recorder.record("barrier", "enter", detail=str(tag))
    try:
        multihost_utils.sync_global_devices(tag)
    finally:
        if recorder.enabled() and seq is not None:
            recorder.record("barrier", "exit", seq)


def coordination_barrier(tag="barrier", timeout_ms=600000):
    """Cross-host barrier over the jax.distributed COORDINATION SERVICE
    (gRPC), not a device collective.  Unlike :func:`sync_global_devices`
    this is safe to call while device collectives are still in flight:
    the checkpoint commit (ckpt/snapshot.py) runs on the host thread
    concurrently with the next dispatch's gradient all-reduce, and a
    gloo barrier there would interleave with it on the same socket
    pairs.  Bracketed in the flight recorder like every other barrier
    so a no-show peer is attributed by tag."""
    from ..obs import recorder

    try:
        from jax._src import distributed as _jdist

        client = _jdist.global_state.client
    except Exception:  # pragma: no cover - jax internals moved
        client = None
    if client is None:
        # single-process (nothing to wait for) or a jax without the
        # coordination client exposed — the collective barrier is the
        # only fallback there
        if jax.process_count() > 1:
            sync_global_devices(tag)
        return
    seq = None
    if recorder.enabled():
        seq = recorder.record("barrier", "enter", detail=str(tag))
    try:
        client.wait_at_barrier(str(tag), timeout_in_ms=int(timeout_ms))
    finally:
        if recorder.enabled() and seq is not None:
            recorder.record("barrier", "exit", seq)


def fetch(x):
    """Global jax.Array -> full host numpy on EVERY process.

    Replicated arrays read their local copy; batch-sharded arrays
    (e.g. stacked per-step outputs) allgather the remote shards first
    (multihost_utils.process_allgather) — a COLLECTIVE: all processes
    must call it in the same order, which SPMD training loops do by
    construction.  Single-process/addressable arrays take the plain
    numpy path."""
    import numpy as np

    if not isinstance(x, jax.Array) or x.is_fully_addressable \
            or x.is_fully_replicated:
        return np.asarray(x)
    from jax.experimental import multihost_utils

    from ..obs import recorder

    # flight-recorder bracket: the allgather is the readback-side
    # collective a healthy rank actually BLOCKS in when a peer stops
    # dispatching — an open enter here is the watchdog's stall subject
    seq = None
    if recorder.enabled():
        seq = recorder.record("allgather", "enter",
                              nbytes=getattr(x, "nbytes", 0))
    try:
        return np.asarray(multihost_utils.process_allgather(x, tiled=True))
    finally:
        if recorder.enabled() and seq is not None:
            recorder.record("allgather", "exit", seq)
