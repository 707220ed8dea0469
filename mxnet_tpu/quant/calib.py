"""Post-training calibration: record activation ranges, emit a CalibTable.

The calibration pass is a plain forward warmup over representative
batches — but through a TAP symbol: the internal entries feeding each
eligible conv/FC node become the outputs of a forward-only
:class:`~mxnet_tpu.predict.Predictor`, so one bound program per batch
shape yields every activation the quantizer needs in one dispatch (no
per-layer hooks, no graph stepping).  Per tapped activation it records:

  * the running **per-channel |x| max** along the consumer's channel
    axis (``transform.channel_spec`` — the same spec the int8 kernel
    applies the scale along, so calibrator and kernel cannot disagree);
  * in ``percentile`` mode, the **|x| distribution** through the
    auto-ranging :class:`~mxnet_tpu.telemetry.ValueHistogram` — the
    value-range histogram machinery PR 4's fixed TIME/BYTE ladders
    could not provide.  The percentile cap clips outlier-driven ranges
    (one hot activation otherwise wastes the whole int8 grid on values
    that almost never occur), and the mass it clips is recorded as the
    per-node ``clip_pct``.

The result is a :class:`CalibTable` — a serializable
``{node_name: {amax, clip_pct, channels, count}}`` mapping keyed by
op name, the currency between calibration and
:func:`~mxnet_tpu.quant.transform.quantize_symbol`.

Calibration telemetry (``docs/observability.md``): per-node
``quant.calib.act.<node>`` value histograms, ``quant.calib.batches``,
``quant.calib.coverage`` / ``quant.clip_pct`` / ``quant.calib.nodes``
gauges.
"""
from __future__ import annotations

import json

import numpy as _np

from ..base import MXNetError
from ..symbol import Symbol
from .transform import eligible_nodes

__all__ = ["CalibTable", "calibrate"]


class CalibTable:
    """Serializable per-node activation ranges (module docstring).

    ``entries``: ``{node_name: {"amax": [per-channel floats],
    "clip_pct": float, "channels": int, "count": int}}``; ``mode`` /
    ``percentile`` record how the ranges were derived, ``eligible``
    how many nodes the source graph offered (the coverage
    denominator)."""

    def __init__(self, entries=None, mode="minmax", percentile=None,
                 eligible=0):
        self.entries = dict(entries or {})
        self.mode = str(mode)
        self.percentile = percentile
        self.eligible = int(eligible)

    def get(self, name):
        return self.entries.get(name)

    def __len__(self):
        return len(self.entries)

    def __contains__(self, name):
        return name in self.entries

    def coverage(self):
        """Calibrated fraction of the graph's eligible nodes (0..1)."""
        return len(self.entries) / self.eligible if self.eligible else 0.0

    def to_json(self):
        return json.dumps({
            "version": 1, "mode": self.mode, "percentile": self.percentile,
            "eligible": self.eligible, "entries": self.entries,
        }, sort_keys=True)

    @classmethod
    def from_json(cls, s):
        rec = json.loads(s)
        if rec.get("version") != 1:
            raise MXNetError("unsupported CalibTable version %r "
                             "(this build reads version 1)"
                             % rec.get("version"))
        return cls(entries=rec.get("entries"), mode=rec.get("mode"),
                   percentile=rec.get("percentile"),
                   eligible=rec.get("eligible", 0))

    def save(self, path):
        with open(path, "w") as f:
            f.write(self.to_json() + "\n")

    @classmethod
    def load(cls, path):
        with open(path) as f:
            return cls.from_json(f.read())


def _channel_amax(act, spec):
    """Per-channel |act| max under a transform.channel_spec."""
    kind, axis = spec
    a = _np.abs(_np.asarray(act, dtype=_np.float32))
    if kind == "fc_flatten":
        return a.reshape(a.shape[0], -1).max(axis=0)
    ax = axis % a.ndim
    other = tuple(i for i in range(a.ndim) if i != ax)
    return a.max(axis=other) if other else a


def calibrate(symbol, arg_params, aux_params, batches, ctx=None,
              mode="minmax", percentile=99.99, hist_bins=2048,
              max_batches=None):
    """Run `batches` through `symbol` bound with the given params and
    return a :class:`CalibTable` of per-channel activation ranges for
    every eligible conv/FC node.

    `batches` — iterable of ``{input_name: batched ndarray}`` (the
    representative set; a handful of real batches is the point, random
    data calibrates random ranges).  `mode` — ``minmax`` (default)
    keeps the observed per-channel max;
    ``percentile`` additionally caps every channel at the
    `percentile`-th percentile of the node's |x|
    distribution (a `hist_bins`-bucket value-range
    histogram), recording the clipped mass as ``clip_pct``.
    Calibration runs in the executor's default f32; the bf16 serving
    executors see ranges within bf16 rounding of these."""
    from .. import ndarray as _nd
    from .. import telemetry
    from ..predict import Predictor

    mode = str(mode)
    if mode not in ("minmax", "percentile"):
        raise MXNetError("calibrate: mode must be 'minmax' or "
                         "'percentile', got %r" % mode)
    pct = float(percentile)
    if not 0.0 < pct <= 100.0:
        raise MXNetError("calibrate: percentile must be in (0, 100], "
                         "got %r" % pct)
    bins = int(hist_bins)
    nodes = eligible_nodes(symbol)
    if not nodes:
        raise MXNetError(
            "calibrate: %r has no quantizable conv/FC nodes" % symbol)
    # tap the activation ENTERING each eligible node (its data input);
    # distinct nodes may share one tap (a residual block fan-out)
    taps, tap_index = [], {}
    consumers = []  # [(node, spec, tap position)]
    for node, spec in nodes:
        src, idx = node.inputs[0]
        key = (id(src), idx)
        if key not in tap_index:
            tap_index[key] = len(taps)
            taps.append((src, idx))
        consumers.append((node, spec, tap_index[key]))
    params = {}
    for k, v in (arg_params or {}).items():
        params["arg:%s" % k] = v if isinstance(v, _nd.NDArray) else _nd.array(v)
    for k, v in (aux_params or {}).items():
        params["aux:%s" % k] = v if isinstance(v, _nd.NDArray) else _nd.array(v)

    amax = [None] * len(consumers)
    hists = [None] * len(consumers)
    counts = [0] * len(consumers)
    pred = None
    bound_shapes = None
    n_batches = 0
    tel = telemetry.enabled()
    try:
        for batch in batches:
            if max_batches is not None and n_batches >= max_batches:
                break
            feed = {k: _np.asarray(v) for k, v in batch.items()}
            shapes = {k: v.shape for k, v in feed.items()}
            if pred is None:
                pred = Predictor(Symbol(list(taps)), params, shapes,
                                 ctx=ctx)
            elif shapes != bound_shapes:
                # a different batch shape — the ubiquitous ragged last
                # batch — rebinds through the predictor's signature
                # cache: one bound program per batch shape, revisits hit
                pred.reshape(shapes)
            bound_shapes = shapes
            pred.forward(**feed)
            outs = [pred.get_output(i) for i in range(len(taps))]
            for ci, (node, spec, ti) in enumerate(consumers):
                act = outs[ti]
                vec = _channel_amax(act, spec)
                amax[ci] = vec if amax[ci] is None \
                    else _np.maximum(amax[ci], vec)
                counts[ci] += act.size
                if mode == "percentile":
                    if hists[ci] is None:
                        hists[ci] = telemetry.ValueHistogram(n_buckets=bins)
                        if tel:
                            # SHARED object: the registry snapshots the
                            # very histogram the cap math reads, so the
                            # activation tensor is binned exactly once
                            telemetry.attach_value_histogram(
                                "quant.calib.act.%s" % node.name,
                                hists[ci])
                    hists[ci].observe_array(_np.abs(act).reshape(-1))
            n_batches += 1
            if tel:
                telemetry.inc("quant.calib.batches")
    finally:
        if pred is not None:
            pred.close()
    if n_batches == 0:
        raise MXNetError("calibrate: `batches` yielded nothing — pass at "
                         "least one representative batch")
    entries = {}
    clip_pcts = []
    for ci, (node, spec, _ti) in enumerate(consumers):
        vec = amax[ci]
        clip_pct = 0.0
        if mode == "percentile":
            cap = hists[ci].quantile(pct / 100.0)
            if cap is not None and cap > 0:
                clip_pct = 100.0 * hists[ci].fraction_above(cap)
                vec = _np.minimum(vec, cap)
        entries[node.name] = {
            "amax": [float(x) for x in vec.reshape(-1)],
            "clip_pct": float(clip_pct),
            "channels": int(vec.size),
            "count": int(counts[ci]),
        }
        clip_pcts.append(clip_pct)
    table = CalibTable(entries=entries, mode=mode,
                       percentile=pct if mode == "percentile" else None,
                       eligible=len(nodes))
    if tel:
        telemetry.set_gauge("quant.calib.nodes", len(entries))
        telemetry.set_gauge("quant.calib.coverage", table.coverage())
        telemetry.set_gauge("quant.clip_pct",
                            float(_np.mean(clip_pcts)) if clip_pcts else 0.0)
    return table
