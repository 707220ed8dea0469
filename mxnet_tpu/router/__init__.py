"""mxnet_tpu.router — the multi-replica serving tier.

One :class:`Router` in front of N :class:`ReplicaAgent` processes
(each wrapping one :class:`~mxnet_tpu.serving.ModelServer`) turns N
single-chip continuous batchers into one service with the SAME client
surface — ``submit(tenant, inputs) -> Future``:

* health-gated least-loaded dispatch over the ``ModelServer.health()``
  probe (policy.py), routing whole requests to whole replicas;
* drain-on-death re-dispatch — a dead replica's in-flight requests
  replay to healthy peers from their submit-time snapshots, so no
  caller future is ever lost (router.py);
* traffic-adaptive bucket ladders — the fill-ratio telemetry shipped
  in health snapshots re-derives each replica's bucket
  ladder and pushes a re-warm when the offered shape mix drifts.

Fleets launch with ``tools/launch.py --serve-replicas N``; the wire
protocol (wire.py) rides the ``parallel/dist.py`` framing.  See
docs/serving.md "Multi-replica tier" and the ``router.*`` rows of the
docs/observability.md catalog.
"""
from __future__ import annotations

from .agent import ReplicaAgent
from .policy import NoHealthyReplica, derive_ladder, pick_replica
from .router import ReplicaDead, Router, RouterClosed

__all__ = ["Router", "ReplicaAgent", "ReplicaDead", "RouterClosed",
           "NoHealthyReplica", "pick_replica", "derive_ladder"]
