"""Hand-tiled Pallas TPU kernels for hot-path ops.

Everything here has a jnp fallback and strict shape gating, so graphs
never fail for want of alignment — they just take the XLA path.

  * bn_stats — per-channel one-pass E[x]/E[x^2] over channel-minor
    activations (the BN stats sweeps are the biggest non-conv cost of the
    ResNet-50 step; README "Roofline" item 3).  fp32 accumulation from
    bf16 input; custom_vjp keeps the backward elementwise (d/dx of the
    sums is a broadcast), so AD never differentiates through the kernel.

    MEASURED RESULT (README Roofline item 5): 27% slower END-TO-END than
    XLA's own convert+reduce fusion on ResNet-50 batch 512 (1826 vs 2487
    img/s, 30-step A/B) even though the isolated kernel matches XLA on
    bandwidth — the pallas_call is a fusion barrier (the stats no longer
    fuse with the producing convert) and the custom_vjp residual pins the
    [M, C]-reshaped activation.  Hence default OFF
    (MXNET_TPU_PALLAS_BN=0, config.py); kept as runnable infrastructure
    and as the recorded experiment.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["bn_stats_supported", "bn_stats"]

_LANE = 128

# tests flip this to run the kernel in Pallas interpret mode on CPU
_INTERPRET = False


def _pick_bm(m):
    """Largest power-of-two block <= 4096 dividing m (sublane-aligned)."""
    for bm in (4096, 2048, 1024, 512, 256, 128, 64, 32, 16, 8):
        if m % bm == 0:
            return bm
    return None


def _fold(c):
    """Fold factor packing a narrow channel dim up to the 128-lane width."""
    if c >= _LANE:
        return 1 if c % _LANE == 0 else None
    return _LANE // c if _LANE % c == 0 else None


def bn_stats_supported(shape, channel_axis):
    """True if the Pallas kernel can take (shape, channel_axis).  Called
    only with MXNET_TPU_PALLAS_BN=1 (ops/nn.py): off the TPU, outside
    the tests' interpret mode, it says that the switch has no kernel to
    select here and the jnp reduction runs (once — the warnings
    module's per-location registry drops the repeats of a 50-BN trace)."""
    if jax.default_backend() != "tpu" and not _INTERPRET:
        import warnings

        warnings.warn(
            "MXNET_TPU_PALLAS_BN=1 on the %r backend: the Pallas "
            "bn_stats kernel compiles for the TPU only, so BatchNorm "
            "takes the jnp reduction" % jax.default_backend())
        return False
    ndim = len(shape)
    if channel_axis % ndim != ndim - 1:
        return False  # channel-minor layouts only (NHWC/NWC/NC)
    c = shape[-1]
    fold = _fold(c)
    if fold is None:
        return False
    m = 1
    for d in shape[:-1]:
        m *= d
    if m % fold != 0:
        return False
    return _pick_bm(m // fold) is not None


def _stats_kernel(x_ref, s1_ref, s2_ref):
    from jax.experimental import pallas as pl

    # the M (reduction) dim is the INNERMOST grid dim, so its iterations
    # over one output block are consecutive — the accumulator block stays
    # resident in VMEM; init it on the first visit
    @pl.when(pl.program_id(1) == 0)
    def _init():
        s1_ref[...] = jnp.zeros_like(s1_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    x = x_ref[...].astype(jnp.float32)
    s1_ref[...] += jnp.sum(x, axis=0, keepdims=True)
    s2_ref[...] += jnp.sum(x * x, axis=0, keepdims=True)


def _stats_fwd_impl(x2, bm, bc):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, c = x2.shape
    s1, s2 = pl.pallas_call(
        _stats_kernel,
        grid=(c // bc, m // bm),
        in_specs=[pl.BlockSpec((bm, bc), lambda ci, mi: (mi, ci))],
        out_specs=[pl.BlockSpec((1, bc), lambda ci, mi: (0, ci)),
                   pl.BlockSpec((1, bc), lambda ci, mi: (0, ci))],
        out_shape=[jax.ShapeDtypeStruct((1, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_INTERPRET,
    )(x2)
    return s1[0], s2[0]


@jax.custom_vjp
def _bn_stats_flat(x2):
    """(sum, sum_sq) per channel of [M, C]."""
    bm = _pick_bm(x2.shape[0])
    bc = 256 if x2.shape[1] % 256 == 0 else _LANE
    return _stats_fwd_impl(x2, bm, bc)


def _bn_stats_flat_fwd(x2):
    return _bn_stats_flat(x2), x2


def _bn_stats_flat_bwd(x2, gs):
    g1, g2 = gs
    # d(sum)/dx = 1, d(sum_sq)/dx = 2x — elementwise, XLA fuses it into
    # the surrounding backward traffic
    return ((g1[None, :] + 2.0 * x2.astype(jnp.float32) * g2[None, :])
            .astype(x2.dtype),)


_bn_stats_flat.defvjp(_bn_stats_flat_fwd, _bn_stats_flat_bwd)


def bn_stats(x, channel_axis):
    """Per-channel (mean, mean_sq) in fp32 over all non-channel axes.

    Caller must have checked `bn_stats_supported`.  Narrow channel dims
    (C < 128) are folded lane-wise: [M, C] viewed as [M/f, f*C] — the f
    channel groups land in distinct lanes and are summed after the sweep."""
    c = x.shape[-1]
    fold = _fold(c)
    m = x.size // c
    x2 = x.reshape(m // fold, fold * c)
    s1, s2 = _bn_stats_flat(x2)
    if fold > 1:
        s1 = s1.reshape(fold, c).sum(0)
        s2 = s2.reshape(fold, c).sum(0)
    return s1 / m, s2 / m
