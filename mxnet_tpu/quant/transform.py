"""quantize_symbol — the int8 forward-emission graph transform.

In the spirit of :func:`mxnet_tpu.symbol.freeze_batchnorm`: a deep-copy
rewrite that swaps eligible ``Convolution`` / ``FullyConnected`` nodes
onto the int8 kernels (``ops/quant_ops.py``), leaving everything else
(BatchNorm statistics, softmax, pooling, activations — and, by policy,
the first and last eligible layer) on the float ops, where the
surrounding mixed-precision executor runs them in bf16.  Each rewritten
node gains ONE new argument, ``<node>_act_amax``: the calibrated
per-input-channel |activation| range from ``quant/calib.py``, returned
as a params dict the caller merges into ``arg_params`` (the Predictor's
``dtype_mode='int8'`` does both steps).

The transform is the POLICY layer: eligibility is decided here with
recorded reasons (``quant.nodes_quantized`` / ``quant.nodes_skipped``
telemetry), and anything the int8 kernels cannot express — grouped or
non-2-D convolutions — is skipped with its reason rather than failing
at bind time.
"""
from __future__ import annotations

import numpy as _np

from ..base import MXNetError
from ..ops.registry import get_op
from ..ops.tensor import _bool, _lit, _shape
from ..symbol import _Node, _topo_order, load_json

__all__ = ["quantize_symbol", "eligible_nodes", "QUANT_OP_MAP"]

# float op -> int8 kernel it rewrites onto (ops/quant_ops.py)
QUANT_OP_MAP = {
    "Convolution": "_quantized_conv2d",
    "FullyConnected": "_quantized_fully_connected",
}


def _eligibility(node):
    """(ok, reason): can this node run on an int8 kernel?"""
    op = node.op
    if op is None or op.name not in QUANT_OP_MAP:
        return False, "not a quantizable op"
    if op.name == "Convolution":
        kernel = _shape(node.attrs.get("kernel"))
        if kernel is None or len(kernel) != 2:
            return False, "non-2-D kernel %s" % (kernel,)
        if int(_lit(node.attrs.get("num_group", 1))) != 1:
            return False, "grouped convolution"
    return True, None


def channel_spec(node):
    """How to reduce this node's INPUT activation to a per-channel amax
    vector — ``(kind, axis)`` where kind is ``conv`` (reduce every axis
    but the channel axis), ``fc_flatten`` (reshape to (batch, -1), reduce
    axis 0) or ``fc_last`` (reduce every axis but the last).  The int8
    kernel applies the scale along the same axis (quant_ops.py)."""
    if node.op.name == "Convolution":
        from ..ops.nn import _channel_last

        return ("conv", -1 if _channel_last(node.attrs.get("layout")) else 1)
    if _bool(node.attrs.get("flatten", True)):
        return ("fc_flatten", -1)
    return ("fc_last", -1)


def eligible_nodes(symbol):
    """Topo-ordered eligible nodes of `symbol` as
    ``[(node, (kind, axis))]`` — shared by the calibrator (what to
    record, and along which axis) and the transform (what to rewrite),
    so the two can never disagree on the quantization surface."""
    out = []
    for node in _topo_order(symbol._entries):
        ok, _ = _eligibility(node)
        if ok:
            out.append((node, channel_spec(node)))
    return out


def quantize_symbol(symbol, calib_table, skip_names=(), skip_first_last=True):
    """Rewrite `symbol`'s calibrated conv/FC nodes onto the int8 kernels.

    Returns ``(qsym, scale_args)``: a NEW symbol (the input is never
    mutated; argument/aux names are preserved, so pretrained params load
    unchanged) plus the ``{<node>_act_amax: NDArray}`` params dict its
    new arguments bind to.

    `calib_table` is a :class:`~mxnet_tpu.quant.calib.CalibTable` (or a
    plain ``{node_name: amax_vector}`` mapping).  A node is LEFT IN
    FLOAT when it is ineligible (grouped/non-2-D conv), named in
    `skip_names`, excluded by the first/last policy
    (`skip_first_last`, default on — the input stem and
    the classifier head are the classic accuracy-critical layers), or
    missing from the table (a calibration coverage hole: it is counted,
    not fatal).  Quantizing NOTHING is fatal — an "int8" symbol with
    zero int8 nodes would silently serve float."""
    from .. import telemetry

    qsym = load_json(symbol.tojson())
    arg_names = set(qsym.list_arguments())
    eligible = eligible_nodes(qsym)
    skip = {str(n) for n in skip_names}
    if skip_first_last and eligible:
        skip.add(eligible[0][0].name)
        skip.add(eligible[-1][0].name)
    quantized, skipped = [], []
    scale_args = {}
    for node, _spec in eligible:
        if node.name in skip:
            skipped.append((node.name, "policy (first/last or skip_names)"))
            continue
        entry = calib_table.get(node.name) if hasattr(calib_table, "get") \
            else None
        amax = entry.get("amax") if isinstance(entry, dict) else entry
        if amax is None:
            skipped.append((node.name, "no calibration entry"))
            continue
        sname = "%s_act_amax" % node.name
        if sname in arg_names:
            raise MXNetError(
                "quantize_symbol: scale argument name %r collides with an "
                "existing argument; rename the layer" % sname)
        svar = _Node(None, sname)
        node.op = get_op(QUANT_OP_MAP[node.op.name])
        node.inputs = list(node.inputs[:2]) + [(svar, 0)] \
            + list(node.inputs[2:])
        vec = _np.asarray(amax, dtype=_np.float32).reshape(-1)
        from .. import ndarray as _nd

        scale_args[sname] = _nd.array(vec)
        quantized.append(node.name)
    if not quantized:
        raise MXNetError(
            "quantize_symbol produced no int8 nodes (%d eligible, all "
            "skipped: %s) — calibrate over the layers you want quantized "
            "or relax the skip policy; an 'int8' graph with zero int8 "
            "nodes would silently serve float"
            % (len(eligible), skipped or "graph has no conv/FC nodes"))
    if telemetry.enabled():
        telemetry.set_gauge("quant.nodes_quantized", len(quantized))
        telemetry.set_gauge("quant.nodes_skipped", len(skipped))
    return qsym, scale_args
