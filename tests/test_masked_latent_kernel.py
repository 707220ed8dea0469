"""PR 49: the whole-sequence attention under the learned selection as the
TPU's blockwise kernel (`ops/masked_latent_kernel.py`), run by Pallas's
interpreter on the CPU and held to the ``jax.numpy`` body
(`ops.sparse_latent._attend_masked`): every way the selection's mask can
meet the blocks, the shape rule (`masked_block`), the op with the kernel
chosen, and the gradient through it.  The interpreter multiplies in
float32, as the CPU's body does: what Mosaic makes of the kernel is
`tests/test_tpu_compile.py`'s, what the chip makes of bfloat16 operands the
cell's reference check's."""
import contextlib
from unittest import mock

import numpy as np
import pytest

from mxnet_tpu.ops import attention, sparse_latent

NOPE, ROPE, VALUE = 64, 64, 128
SCALE = (NOPE + ROPE) ** -0.5


def _operands(tq, t, g, seed):
    """(q_n, q_r, k_n, k_r, v) as ``_attend_masked`` takes them."""
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(*shape), jnp.float32) for shape in (
        (tq, g, NOPE), (tq, g, ROPE), (t, g, NOPE), (t, ROPE), (t, g, VALUE)))


def _selected(tq, t, top_k, seed):
    """A selection's mask ``(tq, t)``: row r keeps its `top_k` best of
    ``s <= r`` by a random score (all while there are fewer), rows from
    `t` on — a pad's — nothing."""
    import jax.numpy as jnp

    score = jnp.asarray(np.random.RandomState(seed).randn(tq, t), jnp.float32)
    causal = jnp.arange(t)[None, :] <= jnp.arange(tq)[:, None]
    keys, edge = sparse_latent._kth_largest(
        jnp.where(causal, score, -jnp.inf), min(top_k, t))
    return ((keys >= edge[:, None]) & causal).at[t:].set(False)


@contextlib.contextmanager
def _tpu_kernel_interpreted():
    """Inside, `_sparse_latent_attention` takes the branch a lowering for
    the TPU keeps — the Pallas kernel — run by Pallas's interpreter.
    Yields the list of kernel branches taken."""
    calls = []

    def take_tpu(*operands, tpu, default):
        calls.append(tpu)
        return tpu(*operands)

    # a trace made under an earlier patch would be served from the cache
    sparse_latent._masked_read.clear_cache()
    with mock.patch.object(sparse_latent.lax, "platform_dependent",
                           take_tpu), \
            mock.patch.object(attention, "_INTERPRET", True):
        yield calls
    sparse_latent._masked_read.clear_cache()


def _kernel(operands, keep, rows, keys, heads):
    """The kernel's context ``(tq, g, value)`` of ``_attend_masked``'s
    operands, laid out as `_masked_read` lays them out and run by the
    interpreter (which leaves the operands' dtype as it comes)."""
    with _tpu_kernel_interpreted():
        ctx = sparse_latent._masked_read(
            *operands, keep, scale=SCALE, block=rows,
            tiled=(rows, keys, heads), interpret=True)
    assert ctx.dtype == np.float32
    return ctx


def _close(got, want, tol=1e-5):
    got, want = np.asarray(got), np.asarray(want)
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


@pytest.mark.parametrize("why,t,top_k,block,rows,keys,heads", [
    ("rows before top_k: all kept", 256, 512, 128, 128, 128, 2),
    ("a selection sparser than a block", 512, 16, 128, 128, 128, 2),
    ("a key block wider than the rows", 768, 64, 128, 128, 384, 1),
    ("rows wider than a key block", 768, 64, 256, 256, 128, 2),
    ("four heads a step share the mask's block", 512, 100, 256, 256, 256, 4),
    ("a T of several runs", 2048, 300, 128, 128, 512, 2)])
def test_the_kernel_computes_the_bodys_context(why, t, top_k, block, rows,
                                               keys, heads):
    operands = _operands(t, t, 4, seed=t + top_k)
    keep = _selected(t, t, top_k, seed=rows + keys)
    if t == 2048:
        assert len(sparse_latent._runs(t // block)) == 4
    want = sparse_latent._attend_masked(*operands, keep, SCALE, block)
    _close(_kernel(operands, keep, rows, keys, heads), want)


def test_a_block_that_keeps_nothing_is_wiped_by_the_next():
    """Rows whose FIRST visited blocks hold no kept key (the selection
    keeps only recent positions) carry garbage through them and end
    exact; a row that keeps nothing INSIDE a later block is not moved by
    it."""
    import jax.numpy as jnp

    t = 512
    operands = _operands(t, t, 2, seed=11)
    row, col = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    recent = (col <= row) & (row - col < 40)          # nothing in early blocks
    early = (col <= row) & (col < 100)                # nothing in later blocks
    for keep in (recent, early, early | jnp.eye(t, dtype=bool)):
        want = sparse_latent._attend_masked(*operands, keep, SCALE, 128)
        _close(_kernel(operands, keep, 128, 128, 2), want)


def test_a_pads_rows_are_finite():
    """Queries in whole blocks beyond a T that is none (`_pad_rows`): the
    pad's rows keep nothing, come out finite — no NaN — and the real rows
    are the body's."""
    t, tq = 640, 1024
    operands = _operands(tq, t, 2, seed=5)
    keep = _selected(tq, t, 50, seed=6)
    assert not np.asarray(keep[t:]).any()
    want = sparse_latent._attend_masked(*operands, keep, SCALE, 512)
    got = _kernel(operands, keep, 512, 128, 2)
    assert got.shape == (tq, 2, VALUE)
    assert np.isfinite(np.asarray(got)).all()
    _close(got[:t], want[:t])


def test_bfloat16_operands_take_both_products():
    """What the TPU's branch hands the kernel: bfloat16 `q`, `k`, `v`, the
    probabilities rounded to it for their product, float32 out."""
    import jax.numpy as jnp

    operands = _operands(512, 512, 2, seed=3)
    keep = _selected(512, 512, 64, seed=4)
    want = sparse_latent._attend_masked(*operands, keep, SCALE, 256)
    got = _kernel([x.astype(jnp.bfloat16) for x in operands], keep, 256, 256,
                  2)
    _close(got, want, tol=3e-2)


# four heads of 32 + 32 beside a value of 128, a query rank of 48, a latent
# of 40 + 32, two indexer heads of 32 that keep 256
HEADS, RANK, Q_RANK, INDEX_HEADS, INDEX_DIM, TOP_K = 4, 40, 48, 2, 32, 256
ATTRS = dict(num_heads=HEADS, nope_dim=32, rope_dim=32, value_dim=VALUE,
             theta=10000.0, index_heads=INDEX_HEADS, top_k=TOP_K, gated=True)


def _node_operands(t, seed):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    shapes = ((1, t, Q_RANK), (HEADS * 64, Q_RANK), (1, t, RANK + 32),
              (HEADS * (32 + VALUE), RANK), (1, t, HEADS),
              (1, t, INDEX_HEADS * INDEX_DIM), (1, t, INDEX_DIM),
              (1, t, INDEX_HEADS))
    operands = [jnp.asarray(0.5 * rng.randn(*s), jnp.float32) for s in shapes]
    operands[4] = 1.0 / (1.0 + jnp.exp(-operands[4]))   # the gate's sigmoid
    return operands


# four heads' float32 scores pass 96 MiB from 2,560 positions on
T_TILED = 2560


def test_the_op_with_the_kernel_chosen_is_the_op():
    """`_sparse_latent_attention` on the TPU's branch — the indexer's
    selection as it is, the group's up-projections, the kernel, the gate —
    to 1e-5 of the op's largest entry."""
    assert sparse_latent.masked_block(T_TILED, HEADS, 64, VALUE, "tpu") == (
        512, 640)
    operands = _node_operands(T_TILED, seed=9)
    want = sparse_latent.sparse_latent_attention(*operands, **ATTRS)
    with _tpu_kernel_interpreted() as calls:
        got = sparse_latent.sparse_latent_attention(*operands, **ATTRS)
    assert len(calls) == 1 and got.shape == (1, T_TILED, HEADS * VALUE)
    _close(got, want)


def test_without_a_block_no_branch_is_taken():
    """A sequence the rule keeps on XLA's form, and the window kind at
    any length: no kernel branch to take, the outputs the body's
    exactly."""
    operands = _node_operands(256, seed=1)
    want = sparse_latent.sparse_latent_attention(*operands, **ATTRS)
    window = dict(ATTRS, window=100)
    del window["index_heads"], window["top_k"]
    with _tpu_kernel_interpreted() as calls:
        got = sparse_latent.sparse_latent_attention(*operands, **ATTRS)
        slid = sparse_latent.window_latent_attention(
            *_node_operands(T_TILED, seed=2)[:5], **window)
    assert calls == [] and slid.shape == (1, T_TILED, HEADS * VALUE)
    assert np.array_equal(got, want)


def test_the_gradient_with_the_kernel_chosen_is_the_bodys():
    """Under `jax.grad` the kernel branch is not differentiated: the
    backward pass is the body's, recomputed; the mask takes none."""
    import jax
    import jax.numpy as jnp

    t = 512
    operands = _operands(t, t, 2, seed=5)
    keep = _selected(t, t, 64, seed=6)
    weight = jnp.asarray(np.random.RandomState(7).randn(t, 2, VALUE),
                         jnp.float32)

    def loss(attend, *operands):
        return jnp.sum(attend(*operands, keep) * weight)

    body = lambda *ops: sparse_latent._attend_masked(*ops, SCALE, 128)
    chosen = lambda *ops: sparse_latent._masked_read(
        *ops, scale=SCALE, block=128, tiled=(128, 256, 2), interpret=True)
    want = jax.grad(loss, argnums=(1, 2, 3, 4, 5))(body, *operands)
    with _tpu_kernel_interpreted() as calls:
        got = jax.grad(loss, argnums=(1, 2, 3, 4, 5))(chosen, *operands)
    assert calls     # traced as the primal and as the forward rule
    for a, b in zip(got, want):
        assert np.abs(np.asarray(a - b)).max() <= 1e-5 * np.abs(b).max()


@pytest.mark.parametrize("why,t,heads,key_dim,value_dim,platform", [
    ("off the TPU", 15360, 128, 192, 128, "cpu"),
    ("no platform said", 15360, 128, 192, 128, None),
    ("a T that is no multiple of 128", 15400, 128, 192, 128, "tpu"),
    ("the tiny rehearsal size", 64, 4, 24, 16, "tpu"),
    ("a short scoring call: scores of 128 heads that stay on the chip",
     384, 128, 192, 128, "tpu"),
    ("scores of four heads that stay on the chip", 2432, 4, 64, 128, "tpu"),
    ("a key width the tiling refuses", 15360, 128, 160, 128, "tpu"),
    ("a value width the tiling refuses", 15360, 128, 192, 64, "tpu"),
    ("a value width the lanes do not divide", 15360, 128, 192, 192, "tpu")])
def test_the_shape_function_says_where_the_body_runs(why, t, heads, key_dim,
                                                     value_dim, platform):
    assert sparse_latent.masked_block(t, heads, key_dim, value_dim,
                                      platform) is None, why


@pytest.mark.parametrize("t,heads,want", [
    (15360, 128, (512, 1024)),     # the cell's one bucket
    (512, 128, (512, 512)),        # the first T of 128 heads it takes
    (640, 128, (512, 640)),        # queries padded to 1,024, keys not
    (1152, 64, (512, 384)),
    (16384, 128, (512, 1024)),
    (2560, 4, (512, 640))])
def test_the_shape_function_gives_the_blocks_of_a_step(t, heads, want):
    """The body's block of queries, the largest multiple of 128 that
    divides T within 1,024 positions a key block."""
    assert sparse_latent.masked_block(t, heads, 192, 128, "tpu") == want
    assert t % want[1] == 0


def test_the_group_of_a_tiled_layer_is_bounded_by_its_rows():
    """With no score array of a run in HBM the group is what 512 MiB of
    float32 Q, K and V hold: 16 of the cell's 128 heads where the body's
    64 MiB of scores allow 2."""
    head = 4 * 15360 * (2 * 128 + 64 + 128)
    assert sparse_latent._head_group(128, 512, 15360, head) == 2
    assert sparse_latent._head_group(128, 512, None, head) == 16
    assert sparse_latent._head_group(4, 64, None, 1 << 40) == 1
