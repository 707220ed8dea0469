"""ShardedImageRecordIter — the DataIter face of the data service.

Wraps :class:`~mxnet_tpu.data.service.DataService` in the standard
iterator contract (``provide_data``/``provide_label``/``reset``/
``next``), so it plugs directly into ``io.DeviceStagedIter`` and
``Module.fit`` — decode+augment in worker processes overlaps H2D
staging overlaps device compute, each stage on its own profiler lane
(``data_decode(w<i>)`` per worker, the ``data_service`` buffer gauge,
``io.stage``, ``fit.dispatch``).

The consumer-side fetch rides engine.ThreadedIter like every other
pipeline stage (one engine op per batch, `mx.waitall()` fences it),
and ``reset()`` advances the epoch — each epoch's shuffle is a pure
function of ``(seed, epoch)``, so runs are reproducible and any worker
count yields the same batch sequence.
"""
from __future__ import annotations

import numpy as _np

from ..base import MXNetError
from ..engine.threaded_iter import ThreadedIter
from ..io import DataBatch, DataDesc, DataIter
from ..ndarray import array
from .service import DataService

__all__ = ["ShardedImageRecordIter"]


class ShardedImageRecordIter(DataIter):
    """Multi-process sharded drop-in for ``ImageRecordIter``.

    Accepts the same decode/augment surface (``data_shape``,
    ``rand_crop``/``rand_mirror``, ``mean_*``/``scale``/``resize``,
    ``label_width``, ``shuffle``/``seed``) plus the service knobs:
    ``num_workers`` decode processes, ``ring_slots`` /
    ``slot_bytes`` (shm ring geometry), and ``host_index``/``num_hosts``
    for per-host sharding composed on top of worker sharding.
    """

    def __init__(self, path_imgrec=None, data_shape=None, batch_size=1,
                 num_workers=2, label_width=1, shuffle=False, seed=0,
                 rand_crop=False, rand_mirror=False, mean_r=0.0, mean_g=0.0,
                 mean_b=0.0, scale=1.0, resize=0, preprocess_threads=1,
                 prefetch_buffer=2, host_index=None, num_hosts=None,
                 ring_slots=4, slot_bytes=None, data_name="data",
                 label_name="softmax_label", force_python_decode=False,
                 **kwargs):
        super().__init__(batch_size)
        # drop-in migration from ImageRecordIter: its part_index/
        # num_parts sharding args ARE the per-host stride shard here —
        # map them instead of silently iterating the full dataset on
        # every rank
        if "part_index" in kwargs or "num_parts" in kwargs:
            if host_index is not None or num_hosts is not None:
                raise MXNetError(
                    "pass either part_index/num_parts (the "
                    "ImageRecordIter spelling) or host_index/num_hosts, "
                    "not both")
            host_index = kwargs.pop("part_index", 0)
            num_hosts = kwargs.pop("num_parts", 1)
        if kwargs:
            import warnings

            warnings.warn("ShardedImageRecordIter ignoring unsupported "
                          "arguments: %s" % sorted(kwargs))
        self._service = DataService(
            path_imgrec, data_shape, batch_size, num_workers=num_workers,
            label_width=label_width, shuffle=shuffle, seed=seed,
            host_index=host_index, num_hosts=num_hosts,
            ring_slots=ring_slots, slot_bytes=slot_bytes,
            rand_crop=rand_crop, rand_mirror=rand_mirror, mean_r=mean_r,
            mean_g=mean_g, mean_b=mean_b, scale=scale, resize=resize,
            preprocess_threads=preprocess_threads,
            force_python_decode=force_python_decode)
        self.data_shape = self._service.data_shape
        self.label_width = label_width
        self.provide_data = [DataDesc(data_name,
                                      (batch_size,) + self.data_shape)]
        self.provide_label = [DataDesc(
            label_name,
            (batch_size,) if label_width == 1 else (batch_size, label_width))]
        self._prefetch = max(1, int(prefetch_buffer))
        self._bg = None
        self._epoch = -1
        self.reset()

    @property
    def num_workers(self):
        return self._service.num_workers

    @property
    def epoch(self):
        """The running epoch number (drives the (seed, epoch) shuffle)."""
        return self._epoch

    def _fetch(self):
        """One consumer fetch as an engine op: pull the next batch out of
        the shm rings and wrap it as a DataBatch."""
        data, label, pad, _meta = self._service.next_batch()
        return DataBatch(data=[array(data)], label=[array(label)], pad=pad,
                         index=None)

    def reset(self):
        """Advance to the next epoch: drain in-flight fetches, abort+
        re-command the workers, restart the lookahead."""
        if self._service is None:
            raise MXNetError("ShardedImageRecordIter is closed")
        if self._bg is not None:
            self._bg.close()
        self._epoch += 1
        self._service.begin_epoch(self._epoch)
        self._bg = ThreadedIter(self._fetch, max_prefetch=self._prefetch,
                                name="data_service")

    def seek_epoch(self, epoch, start_batch=0):
        """Jump to batch `start_batch` of `epoch` without decoding the
        skipped prefix — the exact-resume fast-forward hook
        (ckpt/resume.py): workers recompute the pure ``(seed, epoch)``
        order and start at their first index >= start_batch."""
        if self._service is None:
            raise MXNetError("ShardedImageRecordIter is closed")
        if self._bg is not None:
            self._bg.close()
        self._epoch = int(epoch)
        self._service.begin_epoch(self._epoch, start_batch=start_batch)
        self._bg = ThreadedIter(self._fetch, max_prefetch=self._prefetch,
                                name="data_service")

    def next(self):
        if self._bg is None:
            raise MXNetError("ShardedImageRecordIter is closed")
        return next(self._bg)

    def close(self):
        """Join the worker processes and unlink the shared-memory rings.
        Idempotent; the iterator is not usable afterwards."""
        if self._bg is not None:
            self._bg.close()
            self._bg = None
        if self._service is not None:
            self._service.close()
            self._service = None

    def __del__(self):
        if getattr(self, "_bg", None) is not None:
            self._bg.cancel()
        svc = getattr(self, "_service", None)
        if svc is not None:
            try:
                svc.close()
            except Exception:
                pass
