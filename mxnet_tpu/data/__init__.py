"""mxnet_tpu.data — sharded multi-process input pipeline.

The host side of training scaled out across PROCESSES, not just
threads (the reference keeps its accelerators fed with dmlc
threadediter + RecordIO + the OMP imdecode engine; one Python process
tops out long before one TPU chip does):

  * :class:`~mxnet_tpu.data.service.DataService` — N worker processes,
    each owning a deterministic slice of one RecordIO file's epoch
    order, decoding straight into shared-memory rings with
    backpressure, crash detection, and exactly-once epoch coverage
    reproducible from ``(seed, epoch)``;
  * :class:`~mxnet_tpu.data.iter.ShardedImageRecordIter` — the
    standard DataIter face on top, plugging into
    ``io.DeviceStagedIter`` / ``Module.fit`` so worker decode overlaps
    H2D staging overlaps device compute;
  * per-host sharding (``host_index``/``num_hosts``) composed on top
    of worker sharding — the multi-process SPMD mesh's input story.

Knobs: the ``num_workers`` / ``ring_slots`` arguments, and
``MXTPU_DATA_SLOT_BYTES`` / ``MXTPU_DATA_HOST_INDEX`` /
``MXTPU_DATA_NUM_HOSTS`` (config.py).  Metrics: the ``data.*``
namespace (docs/observability.md).  Decode throughput on the chip's
host: not measured (no cell reads through a ``DataService``).
See docs/data.md.
"""
from __future__ import annotations

from . import shm
from .iter import ShardedImageRecordIter
from .service import DataService, DataWorkerError
from .worker import epoch_order

__all__ = ["DataService", "DataWorkerError", "ShardedImageRecordIter",
           "epoch_order", "shm"]
