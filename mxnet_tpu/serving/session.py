"""Per-tenant serving session: bucketed compiled programs + the
stage / compute / readback pipeline.

One :class:`TenantSession` wraps one :class:`~mxnet_tpu.predict.Predictor`
(one model's symbol + params, bound forward-only) and owns everything
shape-shaped about serving it:

  * **program cache** — each batch bucket binds through the predictor's
    signature cache (`Predictor.executor_for`) and compiles ONE
    forward-only program (`Executor.serve_program`) whose batch inputs
    are a separate, donated argument tuple.  A bucket therefore
    compiles exactly once; every later fill of any size in that bucket
    is a jit-cache hit (`executor.compile_cache_hits`).
  * **ping-pong staging** — the H2D of fill N+1 rides a background
    engine op (the `io.DeviceStagedIter` recipe generalized from
    training blocks to request batches, sharing `io.stage_put` so the
    staged bytes land in the same books) while fill N computes.  Two
    slot vars alternate; WAW ordering on a slot var queues the stage of
    fill N+2 behind the readback of fill N, which bounds the pipeline
    at classic double buffering without any explicit wait.
  * **async readback** — output D2H + future resolution run as another
    engine op, off the batcher thread, so packing the next fill never
    waits on `np.asarray` of the previous one.  Partial-fill padding is
    sliced back out here: request i gets row i of each output, the
    `bucket - n` padded rows are never seen by a caller.

Engine ops are pushed ``atomic=False`` (the ThreadedIter convention for
callbacks running arbitrary foreign code with normal sync semantics);
`mx.waitall()` and :meth:`drain` fence the pipeline via the slot vars.
"""
from __future__ import annotations

import queue as _queue
import threading as _threading
import time

import numpy as _np

from .. import engine
from .. import io as _io
from ..base import MXNetError
from .bucket import choose_bucket, pad_rows
from .. import locks

__all__ = ["TenantSession"]


class TenantSession:
    """One model serving under one tenant name (see module docstring)."""

    def __init__(self, name, predictor, ladder):
        self.name = name
        self._predictor = predictor
        self._ladder = list(ladder)
        predictor._check_open()
        exe = predictor._exec
        self._input_names = list(predictor._input_names)
        # the tenant's per-request contract: the bound predictor's input
        # shapes minus the leading batch axis
        self._samples = {n: tuple(exe.arg_dict[n].shape[1:])
                         for n in self._input_names}
        self._dtypes = {n: _np.dtype(exe.arg_dict[n].data.dtype)
                        for n in self._input_names}
        self._device = exe._first_ctx.jax_device()
        self._programs = {}
        # serializes program build/lookup: warm() runs on a caller
        # thread and may overlap the batcher's dispatch of the same
        # bucket (add_tenant while serving) — without this, both sides
        # could compile the same program and double-count
        # serving.bucket_programs
        self._prog_lock = locks.lock("serving.session_progs")
        self._slot_vars = (engine.new_variable(), engine.new_variable())
        self._fills = 0
        # buckets whose program has RUN at least once (warm() or a
        # fill): a first run pays the XLA compile, so dispatch brackets
        # it in the flight recorder's compile bracket and the stall
        # watchdog stays suppressed across it (obs/watchdog.py)
        self._ran_buckets = set()

    @property
    def sample_shapes(self):
        return dict(self._samples)

    def validate(self, inputs):
        """Shape-check one request against the tenant contract — called
        at submit() time so a malformed request fails ITS caller
        immediately and never reaches a fill where its error would fail
        every co-batched request."""
        for name in self._input_names:
            if name not in inputs:
                raise MXNetError(
                    "request for tenant %r is missing input %r "
                    "(expected inputs: %s)"
                    % (self.name, name, self._input_names))
            shape = tuple(_np.shape(inputs[name]))
            if shape != self._samples[name]:
                raise MXNetError(
                    "request input %r for tenant %r has shape %s, "
                    "expected the sample shape %s (submit() takes "
                    "UNBATCHED samples; the batcher owns the batch axis)"
                    % (name, self.name, shape, self._samples[name]))

    def _program(self, bucket):
        """(executor, jitted fn) for one bucket.  The session PINS the
        bucket's executor itself — the ladder is small and bounded, and
        pinning makes compile-once-per-bucket immune to eviction from
        the predictor's (capped) signature cache — while each fill still
        goes through the executor's jit cache, so the telemetry counters
        state the property directly: `serving.bucket_programs` and
        `executor.compile_cache_misses` move only on a bucket's FIRST
        fill; every later fill is a `executor.compile_cache_hits`
        increment (the steady-state pin in tests/test_serving.py)."""
        from .. import telemetry

        with self._prog_lock:
            exe = self._programs.get(bucket)
            if exe is None:
                exe = self._programs[bucket] = self._predictor.executor_for(
                    {n: (bucket,) + self._samples[n]
                     for n in self._input_names})
                if telemetry.enabled():
                    telemetry.inc("serving.bucket_programs")
            fn = exe.serve_program(self._input_names)
        return exe, fn

    def warm(self, buckets):
        """Compile-and-run this tenant's program for each bucket with a
        zero-filled dummy batch, synchronously on the calling thread (no
        queue, no engine ops) — ModelServer.warmup() calls this before
        traffic so no real request ever pays an XLA compile."""
        for b in buckets:
            exe, fn = self._program(b)
            dummy = tuple(_np.zeros((b,) + self._samples[n], self._dtypes[n])
                          for n in self._input_names)
            other_vals, aux_vals = exe.serve_args(self._input_names)
            outs = fn(dummy, other_vals, aux_vals, _np.uint32(0))
            _np.asarray(outs[0])  # block: compile + run complete
            self._ran_buckets.add(b)
        return len(buckets)

    def dispatch(self, reqs):
        """Run one fill: pack `reqs` into the smallest bucket that holds
        them, stage, dispatch, and hand the readback to the engine.
        Returns after the compute is DISPATCHED (not complete); the
        requests' futures resolve from the readback op.

        Tracing (docs/observability.md "Request tracing & SLOs"): the
        fill opens ONE `fill` span; every head-sampled request in it
        records contiguous `replica_queue` / `batch_fill` / `h2d` /
        `compute` segments here (sharing boundary timestamps, so the
        segments tile the request's life gap-free) and a `readback`
        segment from the readback op — each linked to the fill span by
        its id."""
        import jax

        from .. import profiler, telemetry
        from ..obs import memory, tracing

        t_fill0 = time.monotonic()
        for r in reqs:
            # service starts NOW: everything before this fill was
            # queue-wait (serving.queue_seconds), everything after is
            # service (serving.service_seconds) — Request._book reads
            # both stamps at resolution
            r.service_at = t_fill0
        traced = ()
        if tracing.enabled():
            traced = tuple(r for r in reqs
                           if r.trace is not None and r.trace.sampled)
        n = len(reqs)
        bucket = choose_bucket(self._ladder, n)
        exe, fn = self._program(bucket)
        host = {
            name: pad_rows([r.inputs[name] for r in reqs], bucket,
                           self._samples[name], self._dtypes[name])
            for name in self._input_names
        }
        slot_var = self._slot_vars[self._fills % 2]
        handoff = _queue.Queue(1)
        dev = self._device

        def _stage(_host=host, _names=tuple(self._input_names), _dev=dev,
                   _q=handoff):
            # errors travel in-band: a deferred engine error would leave
            # the batcher blocked on the handoff forever
            try:
                placed = tuple(
                    _io.stage_put(nm, _host[nm],
                                  lambda _n, a: jax.device_put(a, _dev))
                    for nm in _names)
            except BaseException as e:
                _q.put((None, e))
                return
            _q.put((placed, None))

        t_stage0 = time.monotonic()
        engine.push(_stage, write_vars=(slot_var,), atomic=False,
                    name="serve_stage")
        staged, err = handoff.get()
        if err is not None:
            raise err
        t_staged = time.monotonic()
        other_vals, aux_vals = exe.serve_args(self._input_names)
        # live-buffer census (obs/memory.py, tag serve_slots): the
        # staged request batch is resident from here until the fill's
        # compute consumes it (donated on device backends) — book the
        # window so the mem.live_bytes.serve_slots lane pulses with
        # every fill; the recorded amount keeps the books balanced
        slot_bytes = 0
        if telemetry.enabled():
            slot_bytes = sum(int(getattr(a, "nbytes", 0) or 0)
                             for a in staged)
            memory.book("serve_slots", slot_bytes)
        from ..obs import recorder

        # flight-recorder bracket: a serving fill wedged in the device
        # dispatch is attributable post-mortem like a training
        # collective.  An unwarmed bucket's first fill pays the XLA
        # compile inside fn, so it also opens the compile bracket —
        # without it, a long first compile on a cold tenant would trip
        # the stall watchdog on a perfectly healthy server.
        first_run = bucket not in self._ran_buckets
        rec_seq = None
        if recorder.enabled():
            rec_seq = recorder.record(
                "serve", "enter", seq=self._fills + 1,
                detail="%s,b=%d" % (self.name, bucket))
            if first_run:
                recorder.record("compile", "enter", rec_seq,
                                detail="serve:%s,b=%d" % (self.name, bucket))
        try:
            with profiler.span("serve.dispatch", cat="serving",
                               tenant=self.name, bucket=bucket):
                outs = tuple(fn(staged, other_vals, aux_vals, _np.uint32(0)))
        finally:
            self._ran_buckets.add(bucket)
            if slot_bytes:
                memory.unbook("serve_slots", slot_bytes)
            if recorder.enabled() and rec_seq is not None:
                if first_run:
                    recorder.record("compile", "exit", rec_seq)
                recorder.record("serve", "exit", rec_seq)
        t_done = time.monotonic()
        tenant = self.name
        fill_sid = None
        if tracing.enabled() and traced:
            # ONE fill span per fill; each sampled request's segments
            # share the fill's boundary timestamps so the chain tiles
            # [arrival, resolution] without gaps — the acceptance test
            # sums exactly these
            fill_sid = tracing.record(traced[0].trace, "fill", t_fill0,
                                      t_done, tenant=tenant,
                                      bucket=bucket, n=n)
            for r in traced:
                taken = r.taken_at if r.taken_at is not None else t_fill0
                tracing.record(r.trace, "replica_queue", r.arrival, taken,
                               tenant=tenant)
                tracing.record(r.trace, "batch_fill", taken, t_stage0,
                               fill=fill_sid)
                tracing.record(r.trace, "h2d", t_stage0, t_staged,
                               fill=fill_sid)
                tracing.record(r.trace, "compute", t_staged, t_done,
                               fill=fill_sid)

        def _readback(_outs=outs, _reqs=reqs, _bucket=bucket,
                      _traced=traced, _fill=fill_sid, _t0=t_done):
            try:
                host_outs = [_np.asarray(o) for o in _outs]
                for ho in host_outs:
                    if ho.ndim < 1 or ho.shape[0] != _bucket:
                        raise MXNetError(
                            "serving requires batch-major outputs: got "
                            "output shape %s from a bucket-%d fill (a "
                            "batch-reducing head cannot be unbatched per "
                            "request)" % (tuple(ho.shape), _bucket))
                if telemetry.enabled():
                    telemetry.inc("executor.d2h_bytes",
                                  sum(int(ho.nbytes) for ho in host_outs))
                for i, r in enumerate(_reqs):
                    if r.future.cancelled():
                        continue
                    # fulfil books the request/queue/service latency
                    # histograms + outcome counters (Request._book)
                    r.fulfil([ho[i] for ho in host_outs])
                t_end = time.monotonic()
                if telemetry.enabled():
                    telemetry.observe("serving.readback_seconds",
                                      t_end - _t0)
                if tracing.enabled():
                    for r in _traced:
                        tracing.record(r.trace, "readback", _t0, t_end,
                                       fill=_fill)
            except BaseException as e:
                for r in _reqs:
                    r.fail(e)

        engine.push(_readback, write_vars=(slot_var,), atomic=False,
                    name="serve_readback")
        self._fills += 1
        if telemetry.enabled():
            telemetry.inc("serving.dispatches")
            telemetry.inc("serving.batch_slots_used", n)
            telemetry.inc("serving.batch_slots_padded", bucket - n)
            telemetry.set_gauge("serving.batch_fill_ratio", n / bucket)
            # per-segment fill histograms: with the queue/service split
            # these are what let parse_log/health say WHICH segment
            # moved when a tenant's p99 burns
            telemetry.observe("serving.h2d_seconds", t_staged - t_stage0)
            telemetry.observe("serving.compute_seconds", t_done - t_staged)
        return bucket

    def drain(self):
        """Fence the pipeline: returns once every in-flight stage and
        readback op has completed (all dispatched futures resolved)."""
        for var in self._slot_vars:
            engine.wait_for_var(var, wait_reads=True)

    def close(self):
        """Drain and drop the bucket programs.  Does NOT close the
        predictor — the caller owns its lifetime (it may serve
        elsewhere, or be retired with Predictor.close())."""
        self.drain()
        self._programs.clear()
