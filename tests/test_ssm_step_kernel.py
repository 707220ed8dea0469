"""The Mamba-2 decode step's state update as a Pallas TPU kernel
(mxnet_tpu/ops/ssm_step_kernel.py), run by Pallas's interpreter on the CPU
against its oracle, the ``jax.numpy`` body `ops.ssm._step_body`, through
`_ssm_step` as a decode program calls it and through the shape function
that says where the kernel runs (`ops.ssm.step_heads`): live rows at
scattered distinct slots and padded rows on the scratch slot, ``y`` and the
live pages to float32 rounding, the windows and every other slot bit for
bit.  What Mosaic makes of the kernel at the benchmark's widths is in
tests/test_tpu_compile.py.  The file costs about 40 s."""
import contextlib
import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.models import TransformerLM
from mxnet_tpu.ops import ssm

TOL = 2e-5   # of the largest entry of what is compared
K = 4
# name -> (heads H, head_dim P, states S, groups G, the most bytes a block
# may have — None: the module's own —, heads a grid step or None for the
# body)
SHAPES = {
    # granite-4.0-h-small's page, 128 heads of 64 x 128: four blocks a row
    "granite_h_small": (128, 64, 128, 1, None, 32),
    # granite-4.0-h-micro's, 64 heads: two blocks a row
    "granite_h_micro": (64, 64, 128, 1, None, 32),
    # Nemotron-H's (PR 64), 64 heads in EIGHT groups: two blocks a row of
    # 32 heads, four whole groups each
    "nemotron_h": (64, 64, 128, 8, None, 32),
    # a block of two whole groups of two heads
    "whole_groups_a_block": (8, 8, 128, 4, 1 << 14, 4),
    # two blocks inside each group of four heads
    "a_block_inside_a_group": (8, 16, 128, 2, 1 << 14, 2),
    # a head_dim that is no whole number of 8-row tiles
    "a_shape_the_function_refuses": (4, 12, 128, 1, None, None),
}


@contextlib.contextmanager
def _tpu_kernel_interpreted(block_bytes=None):
    """Inside, `_ssm_step` takes the branch a lowering for the TPU keeps
    — the Pallas kernel — run by Pallas's interpreter.  Yields the list
    of kernel branches taken."""
    calls = []

    def take_tpu(*operands, tpu, default):
        calls.append(tpu)
        return tpu(*operands)

    # a trace made under an earlier patch would be served from the cache
    ssm._state_step.clear_cache()
    with mock.patch.object(ssm.lax, "platform_dependent", take_tpu), \
            mock.patch.object(ssm, "_INTERPRET", True), \
            mock.patch.object(ssm, "_STEP_BLOCK_BYTES",
                              block_bytes or ssm._STEP_BLOCK_BYTES):
        yield calls
    ssm._state_step.clear_cache()


def _operands(rows, slots, n_slots, h, p, s, g, seed):
    """`_ssm_step`'s operands for `rows` packed rows at `slots` — the
    projection's output, the mixer's small parameters, the two state
    buffers, the slots — and its attributes."""
    conv_dim = h * p + 2 * g * s
    rng = np.random.RandomState(seed)
    data = rng.randn(rows, 1, h * p + conv_dim + h)
    small = [rng.uniform(-0.5, 0.5, (K, conv_dim)), 0.1 * rng.randn(conv_dim),
             rng.randn(h), np.log(rng.uniform(1.0, 16.0, h)), rng.randn(h),
             1 + 0.1 * rng.randn(h * p)]
    conv0 = rng.randn(n_slots, K - 1, conv_dim)
    ssm0 = rng.randn(n_slots, h, p, s)
    return [np.asarray(a, np.float32)
            for a in [data, *small, conv0, ssm0, slots]], dict(
        num_heads=h, head_dim=p, state_size=s, n_groups=g, conv_kernel=K,
        chunk_size=16, eps=1e-5)


def _step(*sizes):
    """`_ssm_step` of `_operands(*sizes)` traced into a program, as a
    decode graph's node is (called on arrays the op runs its body: the
    test below); its three outputs as numpy, and the state buffers it was
    given."""
    arrays, attrs = _operands(*sizes)
    step = jax.jit(functools.partial(ssm.ssm_step, **attrs))
    return ([np.asarray(o) for o in step(*map(jnp.asarray, arrays))],
            arrays[-3], arrays[-2])


def _close(got, want):
    assert got.shape == want.shape
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= TOL * np.abs(want).max()


# (rows, of them live): the padded rows name the scratch slot
ROWS = [(1, 1), (2, 1), (4, 2), (8, 8), (8, 5)]


@pytest.mark.parametrize("rows,live", ROWS)
@pytest.mark.parametrize("name", sorted(SHAPES))
def test_a_step_with_the_kernel_writes_what_the_body_writes(name, rows, live):
    """`_ssm_step` through the interpreted kernel: ``y`` of the live rows
    and their pages are the body's, their windows the same copied rows,
    the padded rows leave finite values on the scratch slot, and no other
    slot is touched.  A shape the kernel does not tile gives a lowering
    for the TPU no kernel branch to take, and the op's outputs equal the
    body's exactly."""
    h, p, s, g, block_bytes, heads = SHAPES[name]
    scratch = rows + 2           # few slots: a page is up to 4 MiB
    rng = np.random.RandomState(rows + live)
    slots = np.concatenate([rng.permutation(scratch)[:live],
                            np.full(rows - live, scratch)])
    seed = 7 * rows + len(name)
    want, conv0, ssm0 = _step(rows, slots, scratch + 1, h, p, s, g, seed)
    with _tpu_kernel_interpreted(block_bytes) as calls:
        assert ssm.step_heads(ssm0.shape, "tpu", g) == heads
        got, _, _ = _step(rows, slots, scratch + 1, h, p, s, g, seed)
    assert len(calls) == (heads is not None)
    if heads is None:
        for a, b in zip(got, want):
            assert np.array_equal(a, b)
    _close(got[0][:live], want[0][:live])
    assert np.array_equal(got[1], want[1])      # the windows: copied rows
    _close(got[2][slots[:live]], want[2][slots[:live]])
    assert np.isfinite(got[2][scratch]).all()
    # the pages moved, and nothing else did
    assert not np.array_equal(got[2][slots[0]], ssm0[slots[0]])
    others = [i for i in range(scratch) if i not in slots]
    assert np.array_equal(got[2][others], ssm0[others])
    assert np.array_equal(got[1][others], conv0[others])


def test_an_eager_call_of_the_op_runs_the_body():
    """``mx.nd._ssm_step`` on arrays is a program of its own that donates
    nothing: it takes no kernel branch on any platform (a lone, undonated
    call of the pinned kernel aborts the TPU's compiler), and gives what
    the traced op gives."""
    h, p, s = 8, 8, 128
    arrays, attrs = _operands(2, np.array([2, 0]), 3, h, p, s, 1, 11)
    nd = [mx.nd.array(a) for a in arrays]
    with _tpu_kernel_interpreted() as calls:
        assert ssm.step_heads((3, h, p, s), "tpu") == 8
        eager = [o.asnumpy() for o in mx.nd._ssm_step(*nd, **attrs)]
        assert calls == []
        traced = jax.jit(functools.partial(ssm.ssm_step, **attrs))(
            *map(jnp.asarray, arrays))
        assert len(calls) == 1
    for a, b in zip(eager, traced):
        _close(a, np.asarray(b))


def test_the_body_matches_the_recurrence_position_by_position():
    """`_step_body` against the recurrence written out a row and a head in
    float64: ``S <- decay S + dtx (x) B``, ``y = S C``, a group's ``B``
    and ``C`` read by its heads."""
    rows, h, p, s, g = 3, 4, 8, 16, 2
    rng = np.random.RandomState(0)
    dtx = rng.randn(rows, h, p).astype(np.float32)
    decay = rng.uniform(0.5, 1.0, (rows, h)).astype(np.float32)
    b, c = (rng.randn(rows, g, s).astype(np.float32) for _ in range(2))
    state = rng.randn(6, h, p, s).astype(np.float32)
    slot = np.array([4, 0, 2], np.int32)
    y, new = (np.asarray(x) for x in ssm._step_body(*(
        jnp.asarray(x) for x in (dtx, decay, b, c, state, slot))))
    for i in range(rows):
        for n in range(h):
            page = (decay[i, n] * state[slot[i], n].astype(np.float64)
                    + np.outer(dtx[i, n], b[i, n // 2]))
            _close(new[slot[i], n], page)
            _close(y[i, n], page @ c[i, n // 2])
    others = [1, 3, 5]
    assert np.array_equal(new[others], state[others])


def test_a_gradient_through_a_step_the_kernel_tiles_is_the_bodys():
    """The kernel has no backward: where `step_heads` gives the step a
    kernel branch, a gradient through `_state_step` — of ``y`` and of the
    new state, to every float operand — is `_step_body`'s."""
    rows, h, p, s = 2, 32, 8, 128
    rng = np.random.RandomState(3)
    operands = [jnp.asarray(a, jnp.float32) for a in (
        rng.randn(rows, h, p), rng.uniform(0.5, 1.0, (rows, h)),
        rng.randn(rows, 1, s), rng.randn(rows, 1, s),
        rng.randn(3, h, p, s))]
    slot = jnp.asarray([1, 0], jnp.int32)
    heads = ssm.step_heads((3, h, p, s), "tpu")
    assert heads == 32

    def loss(step):
        def of(*floats):
            y, state = step(*floats)
            return (y * y).sum() + (state * state).sum()
        return of

    got = jax.grad(loss(lambda *f: ssm._state_step(
        *f, slot, heads=heads, interpret=False)), argnums=range(5))(*operands)
    want = jax.grad(loss(lambda *f: ssm._step_body(*f, slot)),
                    argnums=range(5))(*operands)
    for a, b in zip(got, want):
        _close(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("state,platform,groups,heads", [
    # the two published shapes on the TPU, heads of 32 KiB: 32, a quarter
    # of granite-4.0-h-small's page and half of granite-4.0-h-micro's
    ((9, 128, 64, 128), "tpu", 1, 32),
    ((9, 64, 64, 128), "tpu", 1, 32),
    # off the TPU
    ((9, 128, 64, 128), "cpu", 1, None),
    ((9, 128, 64, 128), None, 1, None),
    # a P that is no whole number of 8-row tiles, an S that is no whole
    # number of 128-lane tiles (the rehearsals' tiny models: 16 states)
    ((9, 128, 60, 128), "tpu", 1, None),
    ((9, 128, 64, 96), "tpu", 1, None),
    ((3, 4, 16, 16), "tpu", 1, None),
    # a head beyond 4 MiB
    ((3, 4, 1024, 2048), "tpu", 1, None),
    # one head where a head alone is beyond 1 MiB
    ((3, 4, 512, 1024), "tpu", 1, 1),
    # groups: a block is whole groups (of 3 heads: 30, not 32) or lies in
    # one (of 48 heads: 24)
    ((3, 96, 64, 128), "tpu", 32, 24),
    ((3, 96, 64, 128), "tpu", 2, 24),
    ((3, 90, 64, 128), "tpu", 30, 30),
    # Nemotron-H's eight groups of eight heads: four whole groups
    ((9, 64, 64, 128), "tpu", 8, 32),
])
def test_the_shape_function_says_where_the_kernel_runs(state, platform,
                                                       groups, heads):
    assert ssm.step_heads(state, platform, groups) == heads
    if groups == 1:
        assert ssm.step_heads(state, platform) == heads


@pytest.mark.parametrize("heads", [128, 64])
def test_the_mixer_kind_books_what_the_step_kernel_moves(heads):
    """`ssm.step_kernel_bytes` beside `ssm.state_bytes`: the same bytes
    where the decode program is lowered for the TPU and `step_heads` gives
    the kernel the Granite state, 0 for the CPU's programs and for a
    state the kernel does not tile."""
    sizes = dict(vocab=32, num_layers=2, d_model=64, num_heads=2, d_ff=64,
                 layer_types=["mamba", "attention"], mamba_heads=heads,
                 mamba_head_dim=64, mamba_state=128)
    lm = TransformerLM(**sizes)
    page = sum(e.nbytes for e in lm.cache_spec(1).values()
               if e.kind == "state")
    assert page == 4 * (3 * (heads * 64 + 2 * 128) + heads * 64 * 128)
    on_tpu = lm.call_counters(rows=8, platform="tpu")
    assert on_tpu["ssm.step_kernel_bytes"] == on_tpu[
        "ssm.state_bytes"] == 2 * 8 * page
    for platform in ("cpu", None):
        booked = lm.call_counters(rows=8, platform=platform)
        assert booked["ssm.state_bytes"] == 2 * 8 * page
        assert booked["ssm.step_kernel_bytes"] == 0
    tiny = TransformerLM(**dict(sizes, mamba_state=16))
    assert tiny.call_counters(rows=8, platform="tpu")[
        "ssm.step_kernel_bytes"] == 0
