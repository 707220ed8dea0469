"""Routing + ladder policy — the router's pure decision functions.

Separated from the socket machinery so the decisions are unit-testable
without a fleet: :func:`replica_usable` is the health gate (which
replicas may take traffic NOW), :func:`pick_replica` the health-gated
least-loaded dispatch, and :func:`derive_ladder` the traffic-adaptive
bucket math that turns the fill-ratio telemetry shipped in health
snapshots into a better bucket ladder.
"""
from __future__ import annotations

import math

from ..base import MXNetError
from ..serving.bucket import bucket_ladder, choose_bucket

__all__ = ["NoHealthyReplica", "replica_usable", "pick_replica",
           "derive_ladder"]


class NoHealthyReplica(MXNetError):
    """Every replica is dead, closed, or out of admission headroom —
    the submit cannot be placed anywhere."""


def replica_usable(health):
    """May this replica take NEW traffic?  Gates on the
    ``ModelServer.health()`` contract: the batcher must be alive and
    accepting, and admission control must have headroom (routing into
    a full queue converts a routable request into a guaranteed
    AdmissionError round trip)."""
    if not health:
        return False  # never heard from it: don't route blind
    return bool(health.get("healthy")) and health.get("queue_headroom", 0) > 0


def pick_replica(candidates):
    """Health-gated least-loaded dispatch.

    `candidates`: iterable of ``(name, health, inflight, rebucketing)``
    — `health` the latest HEALTH_R snapshot (may be None before the
    first poll answers), `inflight` the router's LIVE count of
    unresolved submissions on that replica, `rebucketing` whether a
    ladder re-warm is outstanding (its programs are recompiling, so
    prefer peers — but fall back to it over failing).

    Load is ranked on the live inflight count first — the health
    snapshot's ``queue_depth`` is a poll interval stale and only
    breaks ties — then name for determinism.  Raises
    :class:`NoHealthyReplica` when nothing is usable."""
    usable = [c for c in candidates if replica_usable(c[1])]
    if not usable:
        raise NoHealthyReplica(
            "no replica can take traffic: every one is dead, closed, or "
            "out of queue headroom (see Router.health() for the verdict "
            "per replica)")
    warm = [c for c in usable if not c[3]]
    pool = warm or usable
    return min(pool, key=lambda c: (c[2],
                                    (c[1] or {}).get("queue_depth", 0),
                                    c[0]))[0]


def derive_ladder(mean_fill, ladder, max_batch,
                  waste_threshold=0.25, max_extra=4):
    """Propose a better bucket ladder for an observed mean fill size,
    or None when the current ladder already serves the mix.

    The drift this corrects: the ladder is sized at deploy time, but
    the offered shape mix moves — when the typical fill lands far
    below its bucket, every dispatch pads ``(bucket - fill)/bucket``
    of the device work away.  When that waste exceeds
    `waste_threshold`, the smallest bucket holding the mean fill is
    added, so the common case packs tight while the rest of the
    ladder (and its already-compiled programs) keeps serving the
    tails.  Growth is bounded: at most `max_extra` buckets beyond the
    default power-of-two ladder, and never a bucket at/above
    `max_batch` (the top is pinned).  Shrinking is deliberately not
    attempted — an extra compiled program is cheap, a recompile storm
    from ladder flapping is not."""
    if not mean_fill or mean_fill <= 0:
        return None
    target = int(math.ceil(mean_fill))
    if target >= max_batch:
        return None
    bucket = choose_bucket(ladder, target)
    waste = (bucket - mean_fill) / float(bucket)
    if waste <= waste_threshold:
        return None
    if target in ladder:
        return None
    if len(ladder) >= len(bucket_ladder(max_batch)) + max_extra:
        return None
    return sorted(set(ladder) | {target})
