"""Collective primitives — the comm layer.

TPU-native replacement for the reference's CommCPU/CommDevice reductions
and ps-lite ZPush/ZPull (reference src/kvstore/comm.h:216-300,
kvstore_dist.h:105-133): inside `shard_map`-ped functions these lower to
XLA collective HLOs riding ICI (all-reduce / all-gather / reduce-scatter /
all-to-all / ppermute).
"""
from __future__ import annotations

import functools

import jax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map


def shard_map_unchecked(f, mesh, in_specs, out_specs):
    """shard_map with the replication (varying-manual-axes) check off."""
    return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


axis_size = lax.axis_size  # static size of a mapped mesh axis


__all__ = ["allreduce", "allgather", "reduce_scatter", "alltoall", "ring_permute",
           "shard_map", "shard_map_unchecked", "axis_size"]


def allreduce(x, axis_name):
    """Sum-all-reduce over a mesh axis (≙ KVStore device-mode Reduce+Broadcast)."""
    return lax.psum(x, axis_name)


def allgather(x, axis_name, axis=0, tiled=True):
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name, scatter_dimension=0):
    return lax.psum_scatter(x, axis_name, scatter_dimension=scatter_dimension, tiled=True)


def alltoall(x, axis_name, split_axis, concat_axis):
    return lax.all_to_all(x, axis_name, split_axis=split_axis, concat_axis=concat_axis, tiled=True)


def ring_permute(x, axis_name, shift=1):
    """Rotate shards around the ring — the building block of ring attention."""
    n = axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def mesh_allreduce(mesh, arrays, axis="data"):
    """Host-level helper: all-reduce a list of replicated arrays over `axis`
    by one fused shard_map call (used by KVStore device mode on a mesh).
    Bracketed in the flight recorder (obs/recorder.py) — this is a host
    entry point into a real collective, so a wedged reduction leaves an
    open enter event for the stall watchdog to attribute."""
    from ..obs import recorder

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=tuple(P(axis) for _ in arrays),
        out_specs=tuple(P() for _ in arrays),
    )
    def _reduce(*xs):
        return tuple(lax.psum(x, axis) for x in xs)

    seq = None
    if recorder.enabled():
        seq = recorder.record(
            "allreduce", "enter", detail=str(axis),
            nbytes=sum(int(getattr(a, "nbytes", 0)) for a in arrays))
    try:
        return _reduce(*arrays)
    finally:
        if recorder.enabled() and seq is not None:
            recorder.record("allreduce", "exit", seq)
