"""Neural-network layer operators.

TPU-native equivalents of the reference's legacy layer ops
(reference src/operator/*-inl.h, SURVEY.md §2 ⚙10) and nn primitives
(src/operator/nn/).  Where the reference hand-writes im2col/cuDNN calls,
here each layer is a pure JAX function: XLA lowers convolutions and
matmuls onto the MXU, fuses the elementwise epilogues, and plans memory —
the roles of mshadow + cuDNN + PlanMemory collapse into the compiler.

Loss-style ops (SoftmaxOutput, *RegressionOutput, MakeLoss, SVMOutput)
reproduce the reference semantics of *ignoring the incoming head gradient*
(reference src/operator/softmax_output-inl.h backward writes (p - label)
directly) via `jax.custom_vjp`.

Layout: NCHW / OIHW, matching the reference default so model code ports
unmodified.  XLA relayouts internally for the TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register
from . import params as P
from .tensor import _axis, _bool, _dtype, _lit, _shape

# ----------------------------------------------------------------------
# helpers
# ----------------------------------------------------------------------


def _pair(v, n=2):
    v = _shape(v)
    if v is None or v == ():
        return (1,) * n if n else ()
    if len(v) == 1:
        return v * n
    return v


def _loss_vjp(fwd_fn, grad_fn):
    """Build a loss op whose backward ignores head gradients.

    Parity: reference loss layers write their gradient directly into
    in_grad regardless of out_grad (e.g. src/operator/softmax_output-inl.h).
    """

    def op_fn(data, label, **attrs):
        @jax.custom_vjp
        def f(d, l):
            return fwd_fn(d, l, attrs)

        def f_fwd(d, l):
            out = fwd_fn(d, l, attrs)
            return out, (d, l, out)

        def f_bwd(res, g):
            d, l, out = res
            return grad_fn(d, l, out, attrs), jnp.zeros_like(l)

        f.defvjp(f_fwd, f_bwd)
        return f(data, label)

    return op_fn


# ----------------------------------------------------------------------
# FullyConnected (reference src/operator/fully_connected-inl.h:55-87:
# out = dot(data, W.T) + bias — one MXU matmul + fused bias add)
# ----------------------------------------------------------------------


def _infer_fc(in_shapes, attrs):
    data = in_shapes[0]
    num_hidden = int(_lit(attrs["num_hidden"]))
    no_bias = _bool(attrs.get("no_bias", False))
    flatten = _bool(attrs.get("flatten", True))
    if flatten:
        in_dim = 1
        for d in data[1:]:
            in_dim *= d
        out = (data[0], num_hidden)
    else:
        in_dim = data[-1]
        out = tuple(data[:-1]) + (num_hidden,)
    shapes = [data, (num_hidden, in_dim)]
    if not no_bias:
        shapes.append((num_hidden,))
    return shapes, [out]


@register(
    "FullyConnected",
    inputs=("data", "weight", "bias"),
    infer_shape=_infer_fc,
    params={"num_hidden": P.Int(required=True, low=1, desc="output dimension"),
            "no_bias": P.Bool(), "flatten": P.Bool()},
)
def fully_connected(data, weight, bias=None, num_hidden=None, no_bias=False, flatten=True, **kw):
    """``data @ weight.T + bias`` over the last axis (`flatten`: over all
    but the first).  The product and the bias are taken on the ``(rows,
    channels)`` view of `data` and the leading axes put back behind them:
    one plain 2-D matmul whatever the batch's rank.  On the 3-D
    ``(1, T, d)`` operand XLA's TPU compiler kept a 3-D and a 2-D copy of
    each product's result, and at T = 256 of OPT-1.3B's widths that left
    four layers' FFN fusions (both matmuls, the residual add and the next
    norm's channel sum in one) with their operands in HBM and 512 tiles
    that recomputed the first matmul: 4.3 ms each where the same fusion
    takes 0.1 ms (PERF.md section 6, PR 36; `chip_smoke.py` kv_ring holds
    every prefill bucket's program against its neighbour's)."""
    if _bool(flatten):
        data = data.reshape((data.shape[0], -1))
    lead = data.shape[:-1]
    out = jnp.dot(data.reshape((-1, data.shape[-1])), weight.T)
    if bias is not None and not _bool(no_bias):
        out = out + bias
    return out.reshape(lead + (out.shape[-1],))


# ----------------------------------------------------------------------
# Convolution / Deconvolution (reference src/operator/convolution-inl.h)
# ----------------------------------------------------------------------


def _conv_out_dim(x, k, s, p, d):
    return (x + 2 * p - (d * (k - 1) + 1)) // s + 1


def _channel_last(layout):
    """True for NWC/NHWC/NDHWC layouts (reference ConvolutionParam.layout,
    convolution-inl.h).  Channel-last is the TPU-native layout: C rides the
    128-lane minor dimension, so convs tile directly onto the MXU instead
    of relayouting (measured 4.8x on v5e bottleneck blocks vs NCHW)."""
    return layout is not None and str(layout) not in ("None", "") \
        and str(layout).endswith("C")


def _conv_dn(layout, n):
    """lax dimension_numbers for an n-d conv in the given layout.

    Channel-last uses spatial+IO weights (HWIO): keeping OIHW weights with
    NHWC activations makes XLA emit a hostile-layout weight-grad conv
    (measured 5.7x slower) — the weight layout must follow the data layout."""
    spatial = "".join("DHW"[3 - n + i] for i in range(n))
    if _channel_last(layout):
        return ("N" + spatial + "C", spatial + "IO", "N" + spatial + "C")
    return ("NC" + spatial, "OI" + spatial, "NC" + spatial)


def _infer_conv(in_shapes, attrs):
    data = in_shapes[0]
    kernel = _shape(attrs["kernel"])
    n = len(kernel)
    nf = int(_lit(attrs["num_filter"]))
    stride = _pair(attrs.get("stride"), n)
    pad = _pair(attrs.get("pad", (0,) * n), n)
    if _shape(attrs.get("pad")) is None:
        pad = (0,) * n
    dilate = _pair(attrs.get("dilate"), n)
    groups = int(_lit(attrs.get("num_group", 1)))
    no_bias = _bool(attrs.get("no_bias", False))
    cl = _channel_last(attrs.get("layout"))
    c_in = data[-1] if cl else data[1]
    in_spatial = data[1:1 + n] if cl else data[2:2 + n]
    spatial = tuple(
        _conv_out_dim(in_spatial[i], kernel[i], stride[i], pad[i], dilate[i]) for i in range(n)
    )
    if cl:
        wshape = kernel + (c_in // groups, nf)
        out = (data[0],) + spatial + (nf,)
    else:
        wshape = (nf, c_in // groups) + kernel
        out = (data[0], nf) + spatial
    shapes = [data, wshape]
    if not no_bias:
        shapes.append((nf,))
    return shapes, [out]


def _bf16_wgrad_active(kernel, data, weight):
    """Whether the bf16 weight-grad accumulation path applies (opt-in:
    MXTPU_BF16_WGRAD=1, small spatial kernels, floating inputs).

    The Inception-v3 training trace spends 27% of device time in f32
    [C,C,k,k] weight-grad convolutions (BENCH_TABLE attribution): the
    weight cotangent's cast back to the fp32 master dtype fuses into the
    grad conv, forcing the slow f32-output MXU kernel.  Accumulating the
    weight grad in bf16 (cast to master dtype AFTER the conv) keeps the
    fast bf16 kernels reachable — README Roofline item 2 proved the HWIO
    layouts keep them reachable; this flag actually takes them.  Gated to
    small kernels (max dim <= 7: the 1x1/3x3/5x5/1x7/7x1 family the
    attribution names) — large-kernel grads keep exact f32 accumulation.
    Changes gradient NUMERICS (bf16 mantissa in the reduction): default
    OFF, tolerance-pinned in tests/test_mfu_sinks.py."""
    from ..config import get as _cfg_get

    from .. import telemetry

    if not _cfg_get("MXTPU_BF16_WGRAD"):
        if telemetry.enabled():
            # unlatch: a conv traced with the flag OFF records the mode,
            # so a run after an earlier bf16-wgrad run in the same
            # process doesn't keep reporting wgrad_bf16=1
            telemetry.set_gauge("ops.wgrad_bf16", 0)
        return False
    if max(kernel) > 7:
        return False
    if not (jnp.issubdtype(data.dtype, jnp.floating)
            and jnp.issubdtype(weight.dtype, jnp.floating)):
        return False
    if telemetry.enabled():
        # mode gauge (trace-time, once per compile): parse_log --telemetry
        # renders it so a run's record says which grad numerics it used
        telemetry.set_gauge("ops.wgrad_bf16", 1)
    return True


def _conv_call(data, weight, strides, padding, dilate, dn, groups, kernel):
    """The one lax conv call both the direct and the space-to-depth paths
    share: f32 inputs accumulate in f32 (preferred_element_type), and the
    opt-in MXTPU_BF16_WGRAD path wraps the conv in a custom_vjp whose
    WEIGHT gradient accumulates in bf16 (see _bf16_wgrad_active)."""
    pet = jnp.float32 if data.dtype == jnp.float32 else None

    def raw(d, w, p):
        return lax.conv_general_dilated(
            d, w, window_strides=strides, padding=padding,
            rhs_dilation=dilate, dimension_numbers=dn,
            feature_group_count=groups, preferred_element_type=p)

    if not _bf16_wgrad_active(kernel, data, weight):
        return raw(data, weight, pet)

    @jax.custom_vjp
    def conv(d, w):
        return raw(d, w, pet)

    def conv_fwd(d, w):
        return raw(d, w, pet), (d, w)

    def conv_bwd(res, g):
        d, w = res
        # data grad: EXACT same numerics as the uncustomized conv (the
        # activation grad feeds the rest of the backward chain — only the
        # weight grad, a leaf, tolerates the cheaper accumulation)
        _, vjp_d = jax.vjp(lambda dd: raw(dd, w, pet), d)
        (dd,) = vjp_d(g)
        # weight grad: bf16 inputs + preferred_element_type=bf16 so JAX's
        # conv transpose emits a bf16-accumulating grad kernel; cast to
        # the master dtype AFTER the conv (not fused into it)
        d16 = d.astype(jnp.bfloat16)
        _, vjp_w = jax.vjp(lambda ww: raw(d16, ww, jnp.bfloat16),
                           w.astype(jnp.bfloat16))
        (dw,) = vjp_w(g.astype(jnp.bfloat16))
        return dd, dw.astype(w.dtype)

    conv.defvjp(conv_fwd, conv_bwd)
    return conv(data, weight)


def _s2d_fold_dim(k, p, size, out):
    """Per-dimension tap bijection of the factor-2 fold of a stride-2
    conv: original tap ky at pad p maps to parity py = (ky - p) % 2 and
    folded tap KY = floor((ky - p) / 2) — injective, since (KY, py)
    recovers ky = 2*KY + py + p.  Returns (py[k], shifted KY[k], folded
    kernel size, folded (lo, hi) padding, folded input size)."""
    import numpy as _onp

    ks = _onp.arange(k)
    py = (ks - p) % 2
    KY = (ks - p - py) // 2
    kmin, kmax = int(KY.min()), int(KY.max())
    kf = kmax - kmin + 1
    lo = -kmin
    folded = (size + 1) // 2
    hi = out - 1 + kf - lo - folded
    return py, KY - kmin, kf, (lo, hi), folded


def space_to_depth_stem(data, weight, kernel, stride, pad, dilate=(1, 1),
                        groups=1, layout=None):
    """EXACT factor-2 space-to-depth rewrite of a 2-D stride-2 conv.

    A C_in<=4 stem conv fills 3/128 of the MXU's contraction lanes (its
    MFU on the chip: not measured since the round-5 audit's tool went).
    Folding factor-2 space-to-depth turns a [H, W, C] x (ky, kx)/s2 conv
    into an equivalent stride-1 conv on [ceil(H/2), ceil(W/2), 4*C]:
    input row 2Y+py folds into channel c*4 + py*2 + px, and each tap ky
    maps to (KY, py) per _s2d_fold_dim — a bijection over the taps, so
    the rewritten weights reproduce the original conv EXACTLY (slots no
    tap maps to stay zero).  Odd H/W zero-pad up to even first; any
    folded tap that could read the parity row carries a zero weight, so
    exactness holds for odd inputs too (e.g. Inception-v3's 299x299
    3x3/s2/p0 stem, not just ResNet's even 224x224 7x7/s2/p3).

    Raises ValueError on configurations the fold cannot express (not
    2-D, stride != 2, dilation != 1, or grouped) — callers that merely
    probe eligibility use _maybe_s2d_stem, which gates instead of
    raising."""
    kernel = tuple(int(x) for x in kernel)
    if len(kernel) != 2:
        raise ValueError(
            "space_to_depth_stem: only 2-D convolutions fold (kernel %s)"
            % (kernel,))
    if tuple(int(s) for s in stride) != (2, 2):
        raise ValueError(
            "space_to_depth_stem: the factor-2 fold requires stride "
            "(2, 2), got %s" % (tuple(stride),))
    if tuple(int(d) for d in dilate) != (1, 1):
        raise ValueError(
            "space_to_depth_stem: dilation is not supported (got %s)"
            % (tuple(dilate),))
    if int(groups) != 1:
        raise ValueError(
            "space_to_depth_stem: grouped convolutions do not fold "
            "(num_group=%d)" % int(groups))
    import numpy as _onp

    last = _channel_last(layout)
    N = data.shape[0]
    if last:
        H, W, C = data.shape[1], data.shape[2], data.shape[3]
    else:
        C, H, W = data.shape[1], data.shape[2], data.shape[3]
    (ky, kx), (py_, px_) = kernel, (int(pad[0]), int(pad[1]))
    oy = _conv_out_dim(H, ky, 2, py_, 1)
    ox = _conv_out_dim(W, kx, 2, px_, 1)
    pyv, KYs, kfy, pady, Y = _s2d_fold_dim(ky, py_, H, oy)
    pxv, KXs, kfx, padx, X = _s2d_fold_dim(kx, px_, W, ox)
    if H % 2 or W % 2:
        spatial_pad = ((0, H % 2), (0, W % 2))
        widths = ((0, 0),) + (spatial_pad + ((0, 0),) if last
                              else ((0, 0),) + spatial_pad)
        data = jnp.pad(data, widths)
    iky, ikx = _onp.meshgrid(_onp.arange(ky), _onp.arange(kx),
                             indexing="ij")
    KYa = KYs[iky].reshape(-1)
    KXa = KXs[ikx].reshape(-1)
    pypx = (pyv[iky] * 2 + pxv[ikx]).reshape(-1)         # [ky*kx]
    ch = (_onp.arange(C)[None, :] * 4 + pypx[:, None])   # [ky*kx, C]
    if last:
        # x: [N,H,W,C] -> [N,Y,X,C*4] with channel c*4 + py*2 + px
        x2 = data.reshape(N, Y, 2, X, 2, C)
        x2 = x2.transpose(0, 1, 3, 5, 2, 4).reshape(N, Y, X, C * 4)
        O = weight.shape[3]                               # HWIO
        taps = weight[iky.reshape(-1), ikx.reshape(-1)]   # [ky*kx, C, O]
        w2 = jnp.zeros((kfy, kfx, C * 4, O), weight.dtype)
        w2 = w2.at[KYa[:, None], KXa[:, None], ch].set(taps)
    else:
        # x: [N,C,H,W] -> [N,C*4,Y,X]
        x2 = data.reshape(N, C, Y, 2, X, 2)
        x2 = x2.transpose(0, 1, 3, 5, 2, 4).reshape(N, C * 4, Y, X)
        O = weight.shape[0]                               # OIHW
        taps = weight[:, :, iky.reshape(-1), ikx.reshape(-1)]  # [O,C,n]
        taps = taps.transpose(2, 1, 0)                    # [n, C, O]
        w2 = jnp.zeros((kfy, kfx, C * 4, O), weight.dtype)
        w2 = w2.at[KYa[:, None], KXa[:, None], ch].set(taps)
        w2 = w2.transpose(3, 2, 0, 1)                     # -> OIHW
    return _conv_call(x2, w2, strides=(1, 1), padding=(pady, padx),
                      dilate=(1, 1), dn=_conv_dn(layout, 2), groups=1,
                      kernel=(kfy, kfx))


def _maybe_s2d_stem(data, weight, kernel, stride, pad, dilate, groups,
                    layout):
    """Eligibility gate for the opt-in stem rewrite (MXNET_TPU_S2D_STEM=1):
    folds any 2-D stride-2 C_in<=4 undilated ungrouped conv via
    space_to_depth_stem; returns None (caller runs the direct conv) for
    everything else or when the flag is off."""
    from ..config import get as _cfg_get

    if not _cfg_get("MXNET_TPU_S2D_STEM"):
        return None
    if (len(kernel) != 2 or tuple(stride) != (2, 2)
            or tuple(dilate) != (1, 1) or groups != 1):
        return None
    c_in = data.shape[3] if _channel_last(layout) else data.shape[1]
    if c_in > 4:
        return None
    return space_to_depth_stem(data, weight, kernel, stride, pad,
                               dilate=dilate, groups=groups, layout=layout)


@register("Convolution", inputs=("data", "weight", "bias"), infer_shape=_infer_conv,
          aliases=("Convolution_v1",),
          params={"kernel": P.Shape(required=True, low=1, desc="conv kernel (h, w)"),
                  "num_filter": P.Int(required=True, low=1, desc="number of output filters"),
                  "stride": P.Shape(low=1), "pad": P.Shape(low=0),
                  "dilate": P.Shape(low=1), "num_group": P.Int(default=1, low=1),
                  "no_bias": P.Bool(),
                  "layout": P.Enum(("NCHW", "NHWC", "NCW", "NWC", "NCDHW",
                                    "NDHWC", "None"))})
def convolution(
    data,
    weight,
    bias=None,
    kernel=None,
    num_filter=None,
    stride=None,
    pad=None,
    dilate=None,
    num_group=1,
    no_bias=False,
    layout=None,
    **kw,
):
    """N-d convolution on the MXU (reference src/operator/convolution-inl.h).

    The reference lowers to im2col+gemm or cuDNN; here a single
    `lax.conv_general_dilated` lets XLA tile directly onto the systolic array.
    `layout` follows the reference ConvolutionParam: NCHW (default, weights
    OIHW) or the TPU-preferred NHWC (weights HWIO — C on the 128-lane minor
    dim, no relayout between layers).
    """
    kernel = _shape(kernel)
    n = len(kernel)
    stride = _pair(stride, n)
    dilate = _pair(dilate, n)
    p = _shape(pad) or (0,) * n
    pairs = [(int(x), int(x)) for x in p]
    dn = _conv_dn(layout, n)
    out = _maybe_s2d_stem(data, weight, kernel, stride, p, dilate,
                          int(_lit(num_group)), layout)
    if out is None:
        out = _conv_call(data, weight, strides=stride, padding=pairs,
                         dilate=dilate, dn=dn,
                         groups=int(_lit(num_group)), kernel=kernel)
    if bias is not None and not _bool(no_bias):
        if _channel_last(layout):
            out = out + bias  # C is minormost: plain broadcast
        else:
            out = out + bias.reshape((1, -1) + (1,) * n)
    return out


def _infer_deconv(in_shapes, attrs):
    data = in_shapes[0]
    kernel = _shape(attrs["kernel"])
    nf = int(_lit(attrs["num_filter"]))
    n = len(kernel)
    stride = _pair(attrs.get("stride"), n)
    pad, adj = _deconv_pad_adj(
        data[2:], kernel, stride,
        _shape(attrs.get("pad")) or (0,) * n,
        _shape(attrs.get("adj")) or (0,) * n,
        _shape(attrs.get("target_shape")) or None,
    )
    no_bias = _bool(attrs.get("no_bias", True))
    groups = int(_lit(attrs.get("num_group", 1)))
    wshape = (data[1], nf // groups) + kernel
    spatial = tuple(
        stride[i] * (data[2 + i] - 1) + kernel[i] - 2 * pad[i] + adj[i] for i in range(n)
    )
    out = (data[0], nf) + spatial
    shapes = [data, wshape]
    if not no_bias:
        shapes.append((nf,))
    return shapes, [out]


def _deconv_pad_adj(in_spatial, kernel, stride, pad, adj, target_shape):
    """Resolve effective (pad, adj): `target_shape` overrides both
    (reference DeconvolutionParam::InferPad, deconvolution-inl.h:94-116)."""
    n = len(kernel)
    if not target_shape:
        return tuple(pad), tuple(adj)
    o_pad, o_adj = [], []
    for i in range(n):
        total = stride[i] * (in_spatial[i] - 1) + kernel[i]
        if total < target_shape[i]:
            raise ValueError("Deconvolution: too big target shape %s" % (target_shape,))
        total -= target_shape[i]
        o_adj.append(total % 2)
        o_pad.append((total + 1) // 2)
    return tuple(o_pad), tuple(o_adj)


@register("Deconvolution", inputs=("data", "weight", "bias"), infer_shape=_infer_deconv)
def deconvolution(
    data, weight, bias=None, kernel=None, num_filter=None, stride=None, pad=None, adj=None,
    target_shape=None, num_group=1, no_bias=True, **kw
):
    """Transposed convolution (reference src/operator/deconvolution-inl.h)."""
    kernel = _shape(kernel)
    n = len(kernel)
    stride = _pair(stride, n)
    p, a = _deconv_pad_adj(
        data.shape[2:], kernel, stride,
        _shape(pad) or (0,) * n,
        _shape(adj) or (0,) * n,
        _shape(target_shape) or None,
    )
    spatial = "".join("DHW"[3 - n + i] for i in range(n))
    dn = ("NC" + spatial, "IO" + spatial, "NC" + spatial)
    # adj extends the high-side padding, matching the shape rule
    # out = stride*(in-1) + kernel - 2*pad + adj
    pairs = [(kernel[i] - 1 - p[i], kernel[i] - 1 - p[i] + a[i]) for i in range(n)]
    # transposed conv = input-dilated CONVOLUTION: the kernel must be
    # spatially mirrored since conv_general_dilated computes correlation
    weight = jnp.flip(weight, axis=tuple(range(2, 2 + n)))
    out = lax.conv_general_dilated(
        data,
        weight,
        window_strides=(1,) * n,
        padding=pairs,
        lhs_dilation=stride,
        dimension_numbers=dn,
        feature_group_count=int(_lit(num_group)),
    )
    if bias is not None and not _bool(no_bias):
        out = out + bias.reshape((1, -1) + (1,) * n)
    return out


# ----------------------------------------------------------------------
# Pooling (reference src/operator/pooling-inl.h + src/operator/nn/pool.h)
# ----------------------------------------------------------------------


def _pool_out_dim(x, k, s, p, convention):
    if convention == "full":
        return -((x + 2 * p - k) // -s) + 1  # ceil
    return (x + 2 * p - k) // s + 1


def _infer_pool(in_shapes, attrs):
    data = in_shapes[0]
    cl = _channel_last(attrs.get("layout"))
    n = len(data) - 2
    if _bool(attrs.get("global_pool", False)):
        one = (1,) * n
        return [data], [(data[0],) + one + (data[-1],) if cl
                        else tuple(data[:2]) + one]
    kernel = _shape(attrs["kernel"])
    n = len(kernel)
    stride = _pair(attrs.get("stride"), n)
    pad = _shape(attrs.get("pad")) or (0,) * n
    conv = str(attrs.get("pooling_convention", "valid"))
    in_spatial = data[1:1 + n] if cl else data[2:2 + n]
    spatial = tuple(_pool_out_dim(in_spatial[i], kernel[i], stride[i], pad[i], conv) for i in range(n))
    out = (data[0],) + spatial + (data[-1],) if cl else tuple(data[:2]) + spatial
    return [data], [out]


@register("Pooling", infer_shape=_infer_pool, aliases=("Pooling_v1",),
          params={"kernel": P.Shape(low=1), "stride": P.Shape(low=1),
                  "pad": P.Shape(low=0), "global_pool": P.Bool(),
                  "pool_type": P.Enum(("max", "avg", "sum")),
                  "pooling_convention": P.Enum(("valid", "full")),
                  "layout": P.Enum(("NCHW", "NHWC", "NCW", "NWC", "NCDHW",
                                    "NDHWC", "None"))})
def pooling(
    data, kernel=None, pool_type="max", stride=None, pad=None, global_pool=False,
    pooling_convention="valid", layout=None, **kw
):
    """Max/avg/sum pooling via XLA reduce_window (reference src/operator/nn/pool.h).
    `layout` as in Convolution: NCHW default, NHWC for the TPU-native path."""
    nd = data.ndim - 2
    cl = _channel_last(layout)
    if _bool(global_pool):
        kernel = data.shape[1:-1] if cl else data.shape[2:]
        stride = (1,) * nd
        pad = (0,) * nd
    else:
        kernel = _shape(kernel)
        stride = _pair(stride, nd)
        pad = _shape(pad) or (0,) * nd
    if cl:
        window = (1,) + tuple(kernel) + (1,)
        strides = (1,) + tuple(stride) + (1,)
        pads = ((0, 0),) + tuple((p, p) for p in pad) + ((0, 0),)
    else:
        window = (1, 1) + tuple(kernel)
        strides = (1, 1) + tuple(stride)
        pads = ((0, 0), (0, 0)) + tuple((p, p) for p in pad)
    pt = str(pool_type)
    if pt == "max":
        init = -jnp.inf
        out = lax.reduce_window(data, init, lax.max, window, strides, pads)
    elif pt in ("avg", "sum"):
        out = lax.reduce_window(data, 0.0, lax.add, window, strides, pads)
        if pt == "avg":
            denom = 1.0
            for k in kernel:
                denom *= k
            out = out / denom
    else:
        raise ValueError("unsupported pool_type %s" % pt)
    return out


# ----------------------------------------------------------------------
# BatchNorm (reference src/operator/batch_norm-inl.h) — aux moving stats
# returned as extra outputs and threaded back by the executor.
# ----------------------------------------------------------------------


def _infer_bn(in_shapes, attrs):
    data = in_shapes[0]
    axis = int(_lit(attrs.get("axis", 1)))
    c = (data[axis],)
    return [data, c, c], [data], [c, c]


@register(
    "BatchNorm",
    inputs=("data", "gamma", "beta"),
    aux=("moving_mean", "moving_var"),
    infer_shape=_infer_bn,
    need_is_train=True,
    num_aux_out=2,
    aliases=("BatchNorm_v1", "CuDNNBatchNorm"),
    params={"eps": P.Float(default=1e-3, low=0.0),
            "momentum": P.Float(default=0.9, low=0.0, high=1.0),
            "fix_gamma": P.Bool(), "use_global_stats": P.Bool()},
)
def batch_norm(
    data, gamma, beta, moving_mean, moving_var, eps=1e-3, momentum=0.9, fix_gamma=True,
    use_global_stats=False, axis=1, is_train=False, **kw
):
    """Batch normalization (reference src/operator/batch_norm-inl.h).

    Training: normalize with batch stats, update moving stats; returns
    (out, new_moving_mean, new_moving_var).  fix_gamma pins gamma to 1
    (reference batch_norm-inl.h fix_gamma handling).
    """
    eps = float(_lit(eps))
    momentum = float(_lit(momentum))
    ax = int(_lit(axis)) % data.ndim  # axis=-1 / axis=3 for NHWC graphs
    reduce_axes = tuple(i for i in range(data.ndim) if i != ax)
    bshape = [1] * data.ndim
    bshape[ax] = data.shape[ax]
    bshape = tuple(bshape)
    if _bool(fix_gamma):
        gamma = jnp.ones_like(gamma)
    # batch statistics accumulate in fp32 even under bf16 compute (the
    # cuDNN-BN multi-precision recipe); moving stats stay in their storage
    # dtype (fp32) — see executor._run_graph, which no longer casts aux.
    # fp32-ACCUMULATED reductions (dtype=) rather than an fp32 cast of the
    # activation: a materialized fp32 copy would be saved as an AD residual,
    # doubling activation HBM traffic (measured +70 GB/step on ResNet-50
    # batch 512)
    if is_train and not _bool(use_global_stats):
        # ONE-pass stats: E[x] and E[x^2] reduce side by side, so XLA's
        # multi-output fusion reads the activation once (a centered two-pass
        # var costs a second full HBM sweep — measured ~25 ms/step on
        # ResNet-50 batch 512).  Cancellation is benign post-conv (mean~0)
        # and both accumulators are fp32.
        mean = jnp.mean(data, axis=reduce_axes, dtype=jnp.float32)
        mean_sq = jnp.mean(jnp.square(data), axis=reduce_axes,
                           dtype=jnp.float32)
        var = jnp.maximum(mean_sq - jnp.square(mean), 0.0)
        new_mm = moving_mean * momentum + lax.stop_gradient(mean).astype(moving_mean.dtype) * (1 - momentum)
        new_mv = moving_var * momentum + lax.stop_gradient(var).astype(moving_var.dtype) * (1 - momentum)
    else:
        mean, var = moving_mean.astype(jnp.float32), moving_var.astype(jnp.float32)
        new_mm, new_mv = moving_mean, moving_var
    # fold normalization into ONE per-channel affine: out = data*w + b.
    # Halves the elementwise HBM traffic vs sub/mul/mul/add and keeps the
    # output in data.dtype (bf16 end-to-end under mixed precision)
    inv = lax.rsqrt(var + eps)
    g32 = gamma.astype(jnp.float32)
    w = (g32 * inv).astype(data.dtype)
    b = (beta.astype(jnp.float32) - mean * inv * g32).astype(data.dtype)
    out = data * w.reshape(bshape) + b.reshape(bshape)
    return out, new_mm, new_mv


def _infer_in(in_shapes, attrs):
    data = in_shapes[0]
    c = (data[1],)
    return [data, c, c], [data]


@register("InstanceNorm", inputs=("data", "gamma", "beta"), infer_shape=_infer_in)
def instance_norm(data, gamma, beta, eps=1e-3, **kw):
    """Instance norm (reference src/operator/instance_norm-inl.h)."""
    eps = float(_lit(eps))
    axes = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=axes, keepdims=True)
    var = jnp.var(data, axis=axes, keepdims=True)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    return (data - mean) * lax.rsqrt(var + eps) * gamma.reshape(bshape) + beta.reshape(bshape)


@register("L2Normalization")
def l2_normalization(data, eps=1e-10, mode="instance", **kw):
    """L2 normalization (reference src/operator/l2_normalization-inl.h)."""
    eps = float(_lit(eps))
    mode = str(mode)
    if mode == "instance":
        axes = tuple(range(1, data.ndim))
        norm = jnp.sqrt(jnp.sum(jnp.square(data), axis=axes, keepdims=True) + eps)
    elif mode == "channel":
        norm = jnp.sqrt(jnp.sum(jnp.square(data), axis=1, keepdims=True) + eps)
    else:  # spatial
        axes = tuple(range(2, data.ndim))
        norm = jnp.sqrt(jnp.sum(jnp.square(data), axis=axes, keepdims=True) + eps)
    return data / norm


@register("LRN")
def lrn(data, nsize=5, alpha=1e-4, beta=0.75, knorm=2.0, **kw):
    """Local response norm across channels (reference src/operator/lrn-inl.h).

    The window sum is nsize explicitly-shifted adds, NOT a
    `lax.reduce_window` over the channel axis: channels are the tiled
    minor dim on TPU, and a cross-lane windowed reduce there dominated
    the whole AlexNet inference step (19.4 of 36.4 device ms in the
    round-5 MFU audit; no cell measures it).  Shifted slices of a
    zero-padded copy fuse into plain elementwise adds instead."""
    nsize = int(_lit(nsize))
    alpha, beta, knorm = float(_lit(alpha)), float(_lit(beta)), float(_lit(knorm))
    sq = jnp.square(data)
    half = nsize // 2
    c = data.shape[1]
    padded = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    summed = padded[:, 0:c]
    for k in range(1, nsize):
        summed = summed + padded[:, k:k + c]
    return data * jnp.power(knorm + alpha / nsize * summed, -beta)


# ----------------------------------------------------------------------
# activations
# ----------------------------------------------------------------------


@register("Activation")
def activation(data, act_type="relu", **kw):
    """Activation (reference src/operator/activation-inl.h)."""
    act = str(act_type)
    if act == "relu":
        return jax.nn.relu(data)
    if act == "sigmoid":
        return jax.nn.sigmoid(data)
    if act == "tanh":
        return jnp.tanh(data)
    if act == "softrelu":
        return jax.nn.softplus(data)
    if act == "softsign":
        return jax.nn.soft_sign(data)
    if act == "silu":
        return jax.nn.silu(data)
    raise ValueError("unknown act_type %s" % act)


def _infer_leaky(in_shapes, attrs):
    data = in_shapes[0]
    if str(attrs.get("act_type", "leaky")) == "prelu":
        return [data, (data[1],)], [data]
    return [data], [data]


@register("LeakyReLU", inputs=("data", "gamma"), infer_shape=_infer_leaky,
          params={"act_type": P.Enum(("leaky", "elu", "prelu", "rrelu")),
                  "slope": P.Float(default=0.25, low=0.0)})
def leaky_relu(data, gamma=None, act_type="leaky", slope=0.25, lower_bound=0.125, upper_bound=0.334, **kw):
    """Leaky family (reference src/operator/leaky_relu-inl.h)."""
    act = str(act_type)
    if act == "leaky":
        return jnp.where(data > 0, data, float(_lit(slope)) * data)
    if act == "elu":
        s = float(_lit(slope))
        return jnp.where(data > 0, data, s * (jnp.exp(data) - 1.0))
    if act == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2))
        return jnp.where(data > 0, data, g * data)
    if act == "rrelu":
        s = (float(_lit(lower_bound)) + float(_lit(upper_bound))) / 2.0
        return jnp.where(data > 0, data, s * data)
    raise ValueError("unknown act_type %s" % act)


@register("softmax")
def softmax(data, axis=-1, temperature=None, **kw):
    t = _lit(temperature)
    if t:
        data = data / float(t)
    return jax.nn.softmax(data, axis=_axis(axis, -1))


@register("log_softmax")
def log_softmax(data, axis=-1, **kw):
    return jax.nn.log_softmax(data, axis=_axis(axis, -1))


@register("SoftmaxActivation")
def softmax_activation(data, mode="instance", **kw):
    if str(mode) == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape((data.shape[0], -1)), axis=-1).reshape(data.shape)


# ----------------------------------------------------------------------
# Dropout (reference src/operator/dropout-inl.h) — rng threaded by executor
# ----------------------------------------------------------------------


@register("Dropout", need_is_train=True, need_rng=True,
          params={"p": P.Float(default=0.5, low=0.0, high=1.0,
                               desc="fraction zeroed"),
                  "mode": P.Enum(("training", "always"))})
def dropout(data, p=0.5, mode="training", is_train=False, rng=None, **kw):
    p = float(_lit(p))
    if (not is_train and str(mode) != "always") or p <= 0.0 or rng is None:
        return data
    keep = 1.0 - p
    mask = jax.random.bernoulli(rng, keep, data.shape)
    return jnp.where(mask, data / keep, 0.0).astype(data.dtype)


# ----------------------------------------------------------------------
# Embedding (reference src/operator/tensor/indexing_op.h Embedding)
# ----------------------------------------------------------------------


def _infer_embed(in_shapes, attrs):
    data = in_shapes[0]
    idim = int(_lit(attrs["input_dim"]))
    odim = int(_lit(attrs["output_dim"]))
    return [data, (idim, odim)], [tuple(data) + (odim,)]


@register("Embedding", inputs=("data", "weight"), infer_shape=_infer_embed,
          params={"input_dim": P.Int(required=True, low=1, desc="vocab size"),
                  "output_dim": P.Int(required=True, low=1, desc="embed dim")})
def embedding(data, weight, input_dim=None, output_dim=None, dtype="float32", **kw):
    return jnp.take(weight, data.astype(jnp.int32), axis=0)


# ----------------------------------------------------------------------
# loss output layers — backward ignores head gradients (reference
# src/operator/softmax_output-inl.h, regression_output-inl.h,
# svm_output-inl.h, make_loss-inl.h)
# ----------------------------------------------------------------------


def _softmax_fwd(data, label, attrs):
    if _bool(attrs.get("multi_output", False)):
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data, axis=-1)


def _softmax_bwd(data, label, out, attrs):
    grad_scale = float(_lit(attrs.get("grad_scale", 1.0)))
    use_ignore = _bool(attrs.get("use_ignore", False))
    ignore_label = float(_lit(attrs.get("ignore_label", -1)))
    normalization = str(attrs.get("normalization", "null"))
    multi_output = _bool(attrs.get("multi_output", False))
    cls_axis = 1 if multi_output else -1
    num_cls = data.shape[cls_axis]
    if multi_output and label.ndim != out.ndim:
        # the reference accepts a FLAT label (batch, spatial...) for the
        # channel-softmax form (e.g. Faster R-CNN rpn_label (1, A*H*W)
        # against scores (1, 2, A*H, W)); align it to the spatial dims
        expect = data.shape[:1] + data.shape[2:]
        import math
        if tuple(label.shape) != tuple(expect) and \
                label.size == math.prod(expect):
            label = label.reshape(expect)
    if label.ndim == out.ndim:
        onehot = label
        valid = jnp.ones(label.shape[:1], dtype=data.dtype)
    else:
        lab = label.astype(jnp.int32)
        onehot = jax.nn.one_hot(lab, num_cls, dtype=data.dtype, axis=cls_axis)
        valid = jnp.ones_like(label, dtype=data.dtype)
        if use_ignore:
            keep = (label != ignore_label).astype(data.dtype)
            onehot = onehot * jnp.expand_dims(keep, cls_axis)
            gmask = jnp.expand_dims(keep, cls_axis)
            valid = keep
        else:
            gmask = 1.0
    grad = out - onehot
    if use_ignore and label.ndim != out.ndim:
        grad = grad * gmask
    if normalization == "batch":
        grad = grad / data.shape[0]
    elif normalization == "valid":
        grad = grad / jnp.maximum(jnp.sum(valid), 1.0)
    return grad * grad_scale


def _infer_softmax_out(in_shapes, attrs):
    data = in_shapes[0]
    if data is None:
        return in_shapes, None
    if _bool(attrs.get("multi_output", False)):
        label = (data[0],) + tuple(data[2:])
    else:
        label = tuple(data[:-1])
    return [data, label], [data]


def _infer_reg_out(in_shapes, attrs):
    data = in_shapes[0]
    if data is None:
        return in_shapes, None
    return [data, data], [data]


def _infer_svm_out(in_shapes, attrs):
    data = in_shapes[0]
    if data is None:
        return in_shapes, None
    return [data, tuple(data[:-1])], [data]


@register("SoftmaxOutput", inputs=("data", "label"), aliases=("Softmax",),
          infer_shape=_infer_softmax_out)
def softmax_output(data, label, **attrs):
    """Softmax with integrated CE gradient (reference src/operator/softmax_output-inl.h)."""
    return _loss_vjp(_softmax_fwd, _softmax_bwd)(data, label, **attrs)


def _reg_grad_scale(out, attrs):
    # reference regression_output-inl.h:70-77: grad_scale / num_output,
    # num_output = label.Size()/batch (outputs per sample)
    num_output = 1
    for d in out.shape[1:]:
        num_output *= d
    return float(_lit(attrs.get("grad_scale", 1.0))) / float(num_output)


@register("LinearRegressionOutput", inputs=("data", "label"), infer_shape=_infer_reg_out)
def linear_regression_output(data, label, **attrs):
    return _loss_vjp(
        lambda d, l, a: d,
        lambda d, l, out, a: (out - l.reshape(out.shape)) * _reg_grad_scale(out, a),
    )(data, label, **attrs)


@register("LogisticRegressionOutput", inputs=("data", "label"), infer_shape=_infer_reg_out)
def logistic_regression_output(data, label, **attrs):
    return _loss_vjp(
        lambda d, l, a: jax.nn.sigmoid(d),
        lambda d, l, out, a: (out - l.reshape(out.shape)) * _reg_grad_scale(out, a),
    )(data, label, **attrs)


@register("MAERegressionOutput", inputs=("data", "label"), infer_shape=_infer_reg_out)
def mae_regression_output(data, label, **attrs):
    return _loss_vjp(
        lambda d, l, a: d,
        lambda d, l, out, a: jnp.sign(out - l.reshape(out.shape)) * _reg_grad_scale(out, a),
    )(data, label, **attrs)


@register("SVMOutput", inputs=("data", "label"), infer_shape=_infer_svm_out)
def svm_output(data, label, **attrs):
    def bwd(d, l, out, a):
        margin = float(_lit(a.get("margin", 1.0)))
        reg = float(_lit(a.get("regularization_coefficient", 1.0)))
        use_linear = _bool(a.get("use_linear", False))
        lab = l.astype(jnp.int32)
        onehot = jax.nn.one_hot(lab, d.shape[-1], dtype=d.dtype)
        score_true = jnp.sum(d * onehot, axis=-1, keepdims=True)
        viol = (margin - (score_true - d)) > 0
        viol = jnp.where(onehot > 0, False, viol)
        if use_linear:
            g = viol.astype(d.dtype)
        else:
            g = 2.0 * (margin - (score_true - d)) * viol.astype(d.dtype)
        g = g - onehot * jnp.sum(g, axis=-1, keepdims=True)
        return g * reg

    return _loss_vjp(lambda d, l, a: d, bwd)(data, label, **attrs)


@register("MakeLoss")
def make_loss(data, grad_scale=1.0, normalization="null", valid_thresh=0.0, **attrs):
    """Turn any symbol into a loss (reference src/operator/make_loss-inl.h)."""
    gs = float(_lit(grad_scale))
    norm = str(normalization)

    @jax.custom_vjp
    def f(d):
        return d

    def f_fwd(d):
        return d, d

    def f_bwd(d, g):
        grad = jnp.full_like(d, gs)
        if norm == "batch":
            grad = grad / d.shape[0]
        elif norm == "valid":
            grad = grad / jnp.maximum(jnp.sum((d > float(_lit(valid_thresh))).astype(d.dtype)), 1.0)
        return (grad,)

    f.defvjp(f_fwd, f_bwd)
    return f(data)


# ----------------------------------------------------------------------
# sequence ops (reference src/operator/sequence_{mask,last,reverse}-inl.h)
# layout: (seq_len, batch, ...) as in the reference
# ----------------------------------------------------------------------


def _seq_len_mask(data, sequence_length, use_sequence_length):
    T = data.shape[0]
    if _bool(use_sequence_length) and sequence_length is not None:
        return sequence_length
    return None


def _infer_seq(in_shapes, attrs):
    data = in_shapes[0]
    if _bool(attrs.get("use_sequence_length", False)):
        return [data, (data[1],)], [data]
    return [data], [data]


@register("SequenceMask", inputs=("data", "sequence_length"), infer_shape=_infer_seq)
def sequence_mask(data, sequence_length=None, use_sequence_length=False, value=0.0, axis=0, **kw):
    if not _bool(use_sequence_length) or sequence_length is None:
        return data
    ax = int(_lit(axis))
    T = data.shape[ax]
    steps = jnp.arange(T)
    if ax == 0:
        mask = steps[:, None] < sequence_length[None, :]
        mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    else:
        mask = steps[None, :] < sequence_length[:, None]
        mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    return jnp.where(mask, data, float(_lit(value)))


def _infer_seq_last(in_shapes, attrs):
    data = in_shapes[0]
    out = tuple(data[1:])
    if _bool(attrs.get("use_sequence_length", False)):
        return [data, (data[1],)], [out]
    return [data], [out]


@register("SequenceLast", inputs=("data", "sequence_length"), infer_shape=_infer_seq_last)
def sequence_last(data, sequence_length=None, use_sequence_length=False, axis=0, **kw):
    if not _bool(use_sequence_length) or sequence_length is None:
        return data[-1]
    idx = (sequence_length - 1).astype(jnp.int32)
    return jnp.take_along_axis(
        data, idx.reshape((1, -1) + (1,) * (data.ndim - 2)), axis=0
    )[0]


@register("SequenceReverse", inputs=("data", "sequence_length"), infer_shape=_infer_seq)
def sequence_reverse(data, sequence_length=None, use_sequence_length=False, **kw):
    if not _bool(use_sequence_length) or sequence_length is None:
        return jnp.flip(data, 0)
    T = data.shape[0]
    steps = jnp.arange(T)
    rev_idx = sequence_length[None, :] - 1 - steps[:, None]
    rev_idx = jnp.where(rev_idx >= 0, rev_idx, steps[:, None]).astype(jnp.int32)
    return jnp.take_along_axis(data, rev_idx.reshape(rev_idx.shape + (1,) * (data.ndim - 2)), axis=0)


# ----------------------------------------------------------------------
# spatial ops
# ----------------------------------------------------------------------


def _infer_upsampling(in_shapes, attrs):
    data = in_shapes[0]
    s = int(_lit(attrs.get("scale", 1)))
    return [data], [tuple(data[:2]) + tuple(d * s for d in data[2:])]


@register("UpSampling", variadic=True, infer_shape=_infer_upsampling)
def upsampling(*args, scale=1, sample_type="nearest", num_args=1, **kw):
    """Nearest upsampling (reference src/operator/upsampling-inl.h)."""
    data = args[0]
    s = int(_lit(scale))
    out = jnp.repeat(jnp.repeat(data, s, axis=2), s, axis=3)
    return out


def _infer_crop(in_shapes, attrs):
    data = in_shapes[0]
    if len(in_shapes) > 1 and in_shapes[1] is not None:
        ref = in_shapes[1]
        return list(in_shapes), [tuple(data[:2]) + tuple(ref[2:])]
    hw = _shape(attrs.get("h_w"))
    return [data], [tuple(data[:2]) + tuple(hw)]


@register("Crop", variadic=True, infer_shape=_infer_crop)
def crop(*args, offset=(0, 0), h_w=(0, 0), num_args=1, center_crop=False, **kw):
    """Crop to size (reference src/operator/crop-inl.h)."""
    data = args[0]
    if len(args) > 1:
        th, tw = args[1].shape[2], args[1].shape[3]
    else:
        th, tw = _shape(h_w)
    if _bool(center_crop):
        oy = (data.shape[2] - th) // 2
        ox = (data.shape[3] - tw) // 2
    else:
        oy, ox = _shape(offset)
    return data[:, :, oy : oy + th, ox : ox + tw]
