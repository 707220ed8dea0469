"""Monitor — periodic statistics over executor values while training
(parity: reference python/mxnet/monitor.py:16-126).

The reference taps every op output through an engine callback; here the
step is one fused XLA dispatch, so the callback fires on the fetchable
values (outputs at the executor boundary) and `toc` additionally sweeps
parameters and auxiliary states by name.  The tic/toc rhythm, the
name-pattern filter, and the queue-of-(step, name, stat) records keep
the reference's debugging workflow intact: activate every `interval`
batches, collect, print.

Cost note: with the default statistic, a window's worth of values is
reduced ON DEVICE and fetched in ONE batched transfer at `toc` — a
sweep over N watched values costs one D2H round-trip, not N blocking
`asscalar()` syncs.  A custom `stat_func` falls back to per-value
evaluation at `toc` (still deferred off the forward path).  Sweep
duration lands in the `monitor.sweep_seconds` telemetry histogram.
"""
from __future__ import annotations

import logging
import re
import time

from .ndarray import NDArray

__all__ = ["Monitor"]


def _mean_abs(x):
    """Default statistic: mean |x| — cheap, scale-revealing, and the
    first thing one checks for vanishing/exploding values."""
    return float(x.abs().sum().asscalar()) / x.size


class Monitor:
    """Watch value statistics every `interval` batches.

    Parameters
    ----------
    interval : activate once per this many `tic` calls.
    stat_func : NDArray -> value; defaults to mean |x|.
    pattern : regex; only matching value names are recorded.
    sort : sort each report by value name before returning.

    Workflow (identical to the reference):
        mon = Monitor(10)
        mod.install_monitor(mon)        # or mon.install(exe)
        ... mon.tic(); train a batch; mon.toc_print()
    """

    def __init__(self, interval, stat_func=None, pattern=".*", sort=False):
        self.stat_func = stat_func or _mean_abs
        self.interval = interval
        self.sort = sort
        self.re_prog = re.compile(pattern)
        self.activated = False
        self.queue = []     # (step, name, ARRAY) records; stats resolve at toc
        self.step = 0
        self.exes = []
        # executors call back with (name, array) per fetchable value;
        # exposed as an attribute for reference-shape compatibility
        self.stat_helper = self._record

    def _record(self, name, arr):
        """Queue a value for this window; the statistic is NOT computed
        here — a blocking reduction per recorded value would serialize
        the forward path — but in one batched fetch at `toc`."""
        if self.activated and self.re_prog.match(name):
            self.queue.append((self.step, name, arr))

    def install(self, exe):
        """Attach to an executor (reference `install`)."""
        exe.set_monitor_callback(self.stat_helper)
        self.exes.append(exe)

    def _fence(self, arrays):
        for a in arrays:
            a.wait_to_read()

    def _sweep(self, names, arrays):
        for name, arr in zip(names, arrays):
            self._record(name, arr)

    def _resolve_stats(self, records):
        """[(step, name, arr)] -> [(step, name, stat)].

        Default-statistic path: build every |x|.sum() as a lazy device
        scalar, stack, and fetch the whole window in ONE host transfer
        (the reference's per-value `asscalar()` costs one blocking
        device sync per watched value — a host round-trip per parameter
        per window)."""
        if self.stat_func is _mean_abs and records:
            import jax.numpy as jnp
            import numpy as _np

            sums = jnp.stack([jnp.abs(a.data).sum()
                              for (_, _, a) in records])
            host = _np.asarray(sums)  # the ONE batched fetch
            return [(step, name, float(host[i]) / a.size)
                    for i, (step, name, a) in enumerate(records)]
        return [(step, name, self.stat_func(a))
                for (step, name, a) in records]

    def tic(self):
        """Start a window if this step is on the interval."""
        if self.step % self.interval == 0:
            for exe in self.exes:
                self._fence(exe.arg_arrays)
            self.queue = []
            self.activated = True
        self.step += 1

    def toc(self):
        """Close the window: fence, sweep params + aux states, resolve
        all queued statistics in one batched fetch, and return this
        window's [(step, name, stat-as-str)] records."""
        if not self.activated:
            return []
        from . import telemetry

        tel = telemetry.enabled()
        t0 = time.perf_counter() if tel else 0.0
        for exe in self.exes:
            self._fence(exe.arg_arrays)
            self._fence(exe.aux_arrays)
        for exe in self.exes:
            sym = exe._symbol
            self._sweep(sym.list_arguments(), exe.arg_arrays)
            # running statistics (BN moving mean/var) are the values one
            # actually watches while debugging training
            self._sweep(sym.list_auxiliary_states(), exe.aux_arrays)
        self.activated = False
        records = self._resolve_stats(self.queue)
        self.queue = []
        if self.sort:
            records.sort(key=lambda r: r[1])
        if tel:
            telemetry.observe("monitor.sweep_seconds",
                              time.perf_counter() - t0)
        return [(step, name, str(stat)) for step, name, stat in records]

    def toc_print(self):
        """toc + log one line per record (the reference's formatting)."""
        for step, name, stat in self.toc():
            logging.info("Batch: %7d %30s %s", step, name, stat)
