"""Collective primitives — the comm layer.

TPU-native replacement for the reference's CommCPU/CommDevice reductions
and ps-lite ZPush/ZPull (reference src/kvstore/comm.h:216-300,
kvstore_dist.h:105-133): inside `shard_map`-ped functions these lower to
XLA collective HLOs riding ICI (all-reduce / all-gather / reduce-scatter /
all-to-all / ppermute).

The gradient-sync layer on top (docs/distributed.md):

  * `hierarchical_psum` reduces over a SEQUENCE of mesh axes innermost
    (ICI) first, so on a hierarchical mesh (multihost.global_mesh
    hierarchical=True: {'data_dcn': hosts, 'data_ici': local}) the
    cross-host DCN hop moves one already-ICI-reduced value per host —
    the 1/n_pod payload decomposition SCALING.md's cross-pod section
    models.
  * `plan_buckets` / `pack_bucket` / `unpack_bucket` implement
    size-targeted gradient bucketing (MXTPU_COMM_BUCKET_MB): many small
    per-parameter all-reduces become a few fused transfers big enough
    to reach wire bandwidth, and — because each bucket's reduction
    depends ONLY on its member gradients — the compiled HLO lets bucket
    k's all-reduce start while earlier layers' backward is still
    computing (structural comm/compute overlap, not scheduling luck).
  * `bucketed_psum` composes the two: the executor's fused K-step scan
    calls it on the raw vjp gradients (executor.py fused_update_block).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map


def shard_map_unchecked(f, mesh, in_specs, out_specs):
    """shard_map with the replication (varying-manual-axes) check off."""
    return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


axis_size = lax.axis_size  # static size of a mapped mesh axis


__all__ = ["allreduce", "allgather", "reduce_scatter", "alltoall", "ring_permute",
           "shard_map", "shard_map_unchecked",
           "hierarchical_psum", "hierarchical_pmean",
           "axis_size", "plan_buckets", "bucket_plan", "pack_bucket",
           "unpack_bucket", "bucketed_psum"]


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


def hierarchical_psum(x, axis_names):
    """Sum-reduce over mesh axes IN ORDER — callers pass the innermost
    (ICI) axis first so the cross-host (DCN) exchange moves one
    already-reduced value per host instead of one per chip.  A plain
    1-D 'data' mesh degenerates to a single psum."""
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    for name in axis_names:
        x = lax.psum(x, name)
    return x


def hierarchical_pmean(x, axis_names):
    """Mean over the product of the given axes, reduced ICI-first."""
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    n = 1
    for name in axis_names:
        n *= axis_size(name)
    return hierarchical_psum(x, axis_names) / n


def plan_buckets(sizes_bytes, bucket_bytes):
    """Greedy size-targeted bucket assignment: consecutive gradients
    (vjp output order = reverse graph order, so bucket 0 holds the
    LAST layers' grads — the first ones backward produces) fill a
    bucket until it reaches `bucket_bytes`.  Returns a list of index
    lists covering range(len(sizes_bytes)) in order.  An oversized
    single gradient gets its own bucket rather than splitting."""
    buckets, cur, cur_bytes = [], [], 0
    for i, nb in enumerate(sizes_bytes):
        if cur and cur_bytes + nb > bucket_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nb
    if cur:
        buckets.append(cur)
    return buckets


def pack_bucket(arrays):
    """Flatten-and-concat one bucket's gradients into a single 1-D
    transfer buffer (all leaves share a dtype — plan callers group by
    dtype before packing)."""
    return jnp.concatenate([jnp.ravel(a) for a in arrays])


def unpack_bucket(flat, shapes):
    """Inverse of pack_bucket."""
    out, off = [], 0
    for s in shapes:
        n = 1
        for d in s:
            n *= int(d)
        out.append(jnp.reshape(lax.dynamic_slice_in_dim(flat, off, n), s))
        off += n
    return tuple(out)


def bucket_plan(avals, bucket_bytes):
    """Full bucket assignment for a sequence of array-likes (.shape /
    .dtype / .size suffice — jax arrays, NDArray payloads, or
    ShapeDtypeStructs): leaves grouped by dtype (a bucket packs one
    dtype), then greedily filled to `bucket_bytes`.  Returns
    [(member_index_list, bucket_nbytes)].  Shared by the traced
    reduction (bucketed_psum) and the host-side telemetry/probe mirror
    (executor._comm_plan_bytes) so the books always match the HLO."""
    by_dtype = {}
    for i, a in enumerate(avals):
        by_dtype.setdefault(jnp.dtype(a.dtype), []).append(i)
    plan = []
    for dt, idxs in by_dtype.items():
        sizes = []
        for i in idxs:
            n = 1
            for d in avals[i].shape:
                n *= int(d)
            sizes.append(n * dt.itemsize)
        for bucket in plan_buckets(sizes, bucket_bytes):
            members = [idxs[j] for j in bucket]
            plan.append((members, sum(sizes[j] for j in bucket)))
    return plan


def bucketed_psum(grads, axis_names, bucket_bytes):
    """All-reduce a gradient tuple as size-targeted packed buckets over
    `axis_names` (ICI-first).  Must run inside shard_map over the mesh
    that owns the axes.  Each bucket's psum depends only on its member
    grads, so XLA's scheduler overlaps bucket k's reduction with the
    backward compute still producing later buckets — the overlap is in
    the dependency structure of the emitted HLO.  Returns (reduced
    grads tuple in input order, per-bucket byte list)."""
    grads = tuple(grads)
    if not grads:
        return grads, []
    out = [None] * len(grads)
    bucket_sizes = []
    for members, nbytes in bucket_plan(grads, bucket_bytes):
        flat = pack_bucket([grads[i] for i in members])
        bucket_sizes.append(nbytes)
        red = hierarchical_psum(flat, axis_names)
        for i, r in zip(members,
                        unpack_bucket(red, [grads[i].shape
                                            for i in members])):
            out[i] = r
    return tuple(out), bucket_sizes


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
