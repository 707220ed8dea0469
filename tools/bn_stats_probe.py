#!/usr/bin/env python
"""Microbench: per-channel E[x], E[x^2] over NHWC bf16 activations —
XLA reduce vs a Pallas accumulation kernel.  The BN stats passes are the
biggest non-conv cost in the ResNet step (README roofline item 3); this
probe measures whether a hand-tiled kernel beats XLA's reduce on the
isolated pattern before wiring it into ops/nn.py."""
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def xla_stats(x):
    m = x.shape[0] * x.shape[1] * x.shape[2]
    xf = x.astype(jnp.float32)
    s1 = jnp.sum(xf, axis=(0, 1, 2))
    s2 = jnp.sum(xf * xf, axis=(0, 1, 2))
    return s1 / m, s2 / m


def _kernel(x_ref, s1_ref, s2_ref):
    mi = pl.program_id(1)

    @pl.when(mi == 0)
    def _init():
        s1_ref[...] = jnp.zeros_like(s1_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    x = x_ref[...].astype(jnp.float32)
    s1_ref[...] += jnp.sum(x, axis=0, keepdims=True)
    s2_ref[...] += jnp.sum(x * x, axis=0, keepdims=True)


def pallas_stats(x, bm=2048, bc=256):
    n, h, w, c = x.shape
    m = n * h * w
    x2 = x.reshape(m, c)
    bm = min(bm, m)
    bc = min(bc, c)
    grid = (c // bc, m // bm)
    s1, s2 = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bc), lambda ci, mi: (mi, ci))],
        out_specs=[pl.BlockSpec((1, bc), lambda ci, mi: (0, ci)),
                   pl.BlockSpec((1, bc), lambda ci, mi: (0, ci))],
        out_shape=[jax.ShapeDtypeStruct((1, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(x2)
    return s1[0] / m, s2[0] / m


def bench(fn, x, steps=20):
    f = jax.jit(fn)
    r = f(x)
    jax.block_until_ready(r)
    t0 = time.time()
    for _ in range(steps):
        r = f(x)
    np.asarray(r[0][0])
    return (time.time() - t0) / steps


def main():
    shapes = [(512, 56, 56, 256), (512, 28, 28, 512), (512, 112, 112, 64)]
    for shape in shapes:
        x = jax.random.normal(jax.random.PRNGKey(0), shape,
                              dtype=jnp.bfloat16)
        gb = np.prod(shape) * 2 / 1e9
        r_x = xla_stats(x)
        r_p = pallas_stats(x)
        err = max(float(jnp.abs(r_x[0] - r_p[0]).max()),
                  float(jnp.abs(r_x[1] - r_p[1]).max()))
        t_x = bench(xla_stats, x)
        t_p = bench(pallas_stats, x)
        print("%s  %.0f MB  xla %.3f ms (%.0f GB/s)  pallas %.3f ms "
              "(%.0f GB/s)  maxerr %.2e"
              % (shape, gb * 1e3, t_x * 1e3, gb / t_x, t_p * 1e3, gb / t_p,
                 err))


if __name__ == "__main__":
    main()
