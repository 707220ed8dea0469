"""The delta rule's decode step as a Pallas TPU kernel
(mxnet_tpu/ops/gdn_step_kernel.py), run by Pallas's interpreter on the CPU
against its oracle, the ``jax.numpy`` body `ops.gdn._step_body`, through
`_gdn_step` as a decode program calls it and through the shape function
that says where the kernel runs (`ops.gdn.step_heads`): live rows at
scattered distinct slots and padded rows on the scratch slot, ``y``, the
live pages and windows to float32 rounding and every other slot bit for
bit.  What Mosaic makes of the kernel at the benchmark's widths is in
tests/test_tpu_compile.py.  The file costs about 50 s."""
import contextlib
from unittest import mock

import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import gdn

TOL = 2e-5   # of the largest entry of what is compared
K = 4
# name -> (key heads, value heads, d_k, d_v, the most bytes a block may
# have — None: the module's own —, heads a grid step or None for the body)
SHAPES = {
    # a head is one lane tile, and a row's page goes in two blocks
    "key_heads_under_value_heads": (16, 32, 8, 128, 1 << 16, 16),
    # four heads share a lane tile
    "equal_head_counts": (4, 4, 16, 32, None, 4),
    # a key axis that is no whole number of 8-row tiles
    "a_shape_the_function_refuses": (2, 4, 12, 32, None, None),
}


@contextlib.contextmanager
def _tpu_kernel_interpreted(block_bytes=None):
    """Inside, `_gdn_step` takes the branch a lowering for the TPU keeps
    — the Pallas kernel — run by Pallas's interpreter.  Yields the list
    of kernel branches taken."""
    calls = []

    def take_tpu(*operands, tpu, default):
        calls.append(tpu)
        return tpu(*operands)

    # a trace made under an earlier patch would be served from the cache
    gdn._state_step.clear_cache()
    with mock.patch.object(gdn.lax, "platform_dependent", take_tpu), \
            mock.patch.object(gdn, "_INTERPRET", True), \
            mock.patch.object(gdn, "_STEP_BLOCK_BYTES",
                              block_bytes or gdn._STEP_BLOCK_BYTES):
        yield calls
    gdn._state_step.clear_cache()


def _step(rows, slots, hk, h, dk, dv, seed):
    """`_gdn_step` of `rows` packed rows at `slots`; its three outputs as
    numpy, and the state buffers it was given."""
    conv_dim = gdn.conv_channels(h, dk, dv, hk)
    rng = np.random.RandomState(seed)
    data = rng.randn(rows, 1, conv_dim + h * dv + 2 * h).astype(np.float32)
    data[..., conv_dim + h * dv:conv_dim + h * dv + h] *= 3.0       # b
    small = [rng.uniform(-0.5, 0.5, (K, conv_dim)), rng.randn(h),
             np.log(rng.uniform(0.01, 16.0, h)), 1 + 0.1 * rng.randn(dv)]
    n_slots = 2 * rows + 2
    conv0 = rng.randn(n_slots, K - 1, conv_dim).astype(np.float32)
    gdn0 = rng.randn(n_slots, dk, h * dv).astype(np.float32)
    nd = [mx.nd.array(np.asarray(a, np.float32))
          for a in [data, *small, conv0, gdn0, slots]]
    attrs = dict(num_heads=h, key_dim=dk, value_dim=dv, conv_kernel=K,
                 chunk_size=16, neg_eigval=hk == h, eps=1e-6)
    if hk != h:
        attrs["num_key_heads"] = hk
    return [o.asnumpy() for o in mx.nd._gdn_step(*nd, **attrs)], conv0, gdn0


def _close(got, want):
    assert got.shape == want.shape
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= TOL * np.abs(want).max()


@pytest.mark.parametrize("rows", [1, 2, 4, 8, 16])
@pytest.mark.parametrize("name", sorted(SHAPES))
def test_a_step_with_the_kernel_writes_what_the_body_writes(name, rows):
    """`_gdn_step` through the interpreted kernel: ``y`` of the live rows,
    their windows and their pages are the body's, the padded rows leave
    finite values on the scratch slot, and no other slot is touched.  A
    shape the kernel does not tile gives a lowering for the TPU no kernel
    branch to take, and the op's outputs equal the body's exactly."""
    hk, h, dk, dv, block_bytes, heads = SHAPES[name]
    live = rows - rows // 2
    scratch = 2 * rows + 1
    rng = np.random.RandomState(rows)
    slots = np.concatenate([rng.permutation(scratch)[:live],
                            np.full(rows - live, scratch)])
    seed = 7 * rows + len(name)
    want, conv0, gdn0 = _step(rows, slots, hk, h, dk, dv, seed)
    with _tpu_kernel_interpreted(block_bytes) as calls:
        assert gdn.step_heads(gdn0.shape, dv, "tpu") == heads
        got, _, _ = _step(rows, slots, hk, h, dk, dv, seed)
    assert len(calls) == (heads is not None)
    if heads is None:
        for a, b in zip(got, want):
            assert np.array_equal(a, b)
    _close(got[0][:live], want[0][:live])
    assert np.array_equal(got[1], want[1])      # the windows: copied rows
    _close(got[2][slots[:live]], want[2][slots[:live]])
    assert np.isfinite(got[2][scratch]).all()
    # the pages moved, and nothing else did
    assert not np.array_equal(got[2][slots[0]], gdn0[slots[0]])
    others = [i for i in range(scratch) if i not in slots]
    assert np.array_equal(got[2][others], gdn0[others])
    assert np.array_equal(got[1][others], conv0[others])


def test_the_body_matches_the_rule_position_by_position():
    """`_step_body` against the rule written out a row and a head in
    float64: ``S <- alpha S + beta (v - alpha S k) k^T``, ``o = S q``."""
    rows, h, dk, dv = 3, 4, 16, 32
    rng = np.random.RandomState(0)
    k, q = (rng.randn(rows, h, dk).astype(np.float32) for _ in range(2))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.randn(rows, h, dv).astype(np.float32)
    alpha = rng.uniform(0.5, 1.0, (rows, h)).astype(np.float32)
    beta = rng.uniform(0.0, 2.0, (rows, h)).astype(np.float32)
    state = rng.randn(6, dk, h * dv).astype(np.float32)
    slot = np.array([4, 0, 2], np.int32)
    o, new = (np.asarray(x) for x in gdn._step_body(*(
        jnp.asarray(x) for x in (k, q, v, alpha, beta, state, slot))))
    for b in range(rows):
        for n in range(h):
            s = alpha[b, n] * state[slot[b], :, n * dv:(n + 1) * dv].astype(
                np.float64).T                                  # (d_v, d_k)
            s = s + beta[b, n] * np.outer(v[b, n] - s @ k[b, n], k[b, n])
            _close(new[slot[b], :, n * dv:(n + 1) * dv], s.T)
            _close(o[b, n], s @ q[b, n])
    others = [1, 3, 5]
    assert np.array_equal(new[others], state[others])


def test_the_shape_function_says_where_the_kernel_runs():
    """The two published shapes on the TPU — Qwen3-Next's 32 heads of 128
    x 128: sixteen heads, half a page, a grid step; Olmo-Hybrid's 30 heads
    of 96 x 192, a head a tile and a half: ten, a third of a page — and
    where the body runs: off the TPU, a key axis that is no whole number
    of 8-row tiles, heads of which no group fills whole lane tiles, a
    smallest group beyond 4 MiB."""
    assert gdn.step_heads((17, 128, 4096), 128, "tpu") == 16
    assert gdn.step_heads((9, 96, 5760), 192, "tpu") == 10
    assert gdn.step_heads((17, 128, 4096), 128, "cpu") is None
    assert gdn.step_heads((17, 128, 4096), 128, None) is None
    assert gdn.step_heads((9, 100, 5760), 192, "tpu") is None
    assert gdn.step_heads((9, 96, 3 * 192), 192, "tpu") is None
    assert gdn.step_heads((3, 8192, 512), 256, "tpu") is None
    # the fewest heads that fill whole tiles where no group is within 1 MiB
    assert gdn.step_heads((3, 2048, 6 * 192), 192, "tpu") == 2
    # the rehearsal's tiny model: eight heads of 16 x 16, one lane tile
    assert gdn.step_heads((5, 16, 128), 16, "tpu") == 8
