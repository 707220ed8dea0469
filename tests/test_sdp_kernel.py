"""PR 47: a prompt's causal attention as the TPU's blockwise kernel
(`ops/sdp_kernel.py`), run by Pallas's interpreter on the CPU and held to
the ``jax.numpy`` body (`ops.attention._masked_attention`) at the shapes
of every caller of `_sdp_attention` in a cell; the shape rule
(`prefill_block`); the gradient through the chosen kernel; the counters a
prefill books.  The interpreter multiplies in float32, as the CPU's body
does: what Mosaic makes of the kernel is `tests/test_tpu_compile.py`'s,
what the chip makes of bfloat16 operands the cells' reference checks'."""
import contextlib
from unittest import mock

import numpy as np
import pytest

from mxnet_tpu.models import TransformerLM
from mxnet_tpu.ops import attention, latent, sdp_kernel

# (query heads, K/V heads, d_head, window, scale): every caller of
# `_sdp_attention` in a cell
SHAPES = {"opt": (32, 32, 64, None, None),
          "olmoe": (16, 16, 128, None, None),
          "olmo_hybrid": (30, 30, 128, None, None),
          "granite": (32, 8, 64, None, 1 / 64),
          "trinity_full": (32, 4, 128, None, None),
          "trinity_window": (32, 4, 128, 2048, None),
          "qwen3_next": (16, 2, 256, None, None),
          "mistral4_latent": (32, 32, 128, None, 0.195)}


def _operands(n, t, h, kv, dh, seed):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(n, kv, h // kv, t, dh), jnp.float32),
            jnp.asarray(rng.randn(n, kv, t, dh), jnp.float32),
            jnp.asarray(rng.randn(n, kv, t, dh), jnp.float32))


def _tiling(t, group):
    """`prefill_block`'s rows and keys, also for a T the rule keeps on
    XLA's form."""
    fit = lambda cap: max(b for b in range(128, max(cap, 128) + 1, 128)
                          if t % b == 0)
    return fit(1024 // group), fit(1024)


def _close(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


@pytest.mark.parametrize("t", [256, 768, 2048])
@pytest.mark.parametrize("name", sorted(SHAPES))
def test_the_kernel_computes_the_bodys_context(name, t):
    import jax

    h, kv, dh, window, scale = SHAPES[name]
    rows, keys = _tiling(t, h // kv)
    if t == 2048:
        assert attention.prefill_block((1, t, h * dh), h, kv, "tpu") == (
            rows, keys)
    q, k, v = _operands(1, t, h, kv, dh, seed=t + h)
    want = attention._masked_attention(q, k, v, scale=scale, window=window)
    got, = jax.jit(lambda *ops: sdp_kernel.causal_attention(
        *ops, rows=rows, keys=keys, window=window, interpret=True,
        scale=dh ** -0.5 if scale is None else scale))(q, k, v)
    assert got.shape == q.shape and got.dtype == np.float32
    _close(got, want)


def test_a_group_of_sixteen_heads_goes_by_a_shorter_key_block():
    """Nemotron-H's attention (PR 64): 32 query heads over 2 K/V heads of
    128, the tiling `prefill_block` gives a group of sixteen — 128 rows,
    512 keys at 2,048 positions — interpreted against the body."""
    import jax

    t = 2048
    rows, keys = attention.prefill_block((1, t, 32 * 128), 32, 2, "tpu")
    assert (rows, keys) == (128, 512)
    q, k, v = _operands(1, t, 32, 2, 128, seed=64)
    want = attention._masked_attention(q, k, v, scale=None, window=None)
    got, = jax.jit(lambda *ops: sdp_kernel.causal_attention(
        *ops, rows=rows, keys=keys, scale=128 ** -0.5, interpret=True))(
            q, k, v)
    _close(got, want)


@pytest.mark.parametrize("why,window,rows,keys", [
    ("a window shorter than a key block", 100, 128, 256),
    ("a window that ends inside a block", 300, 256, 128),
    ("a window of one block and one position", 129, 128, 128),
    ("a window of whole blocks", 256, 128, 128),
    ("a window longer than the sequence", 4096, 256, 256),
    ("rows wider than a key block", None, 384, 128),
    ("a key block wider than the rows", None, 128, 384)])
def test_blocks_outside_the_mask_are_not_missed(why, window, rows, keys):
    """Two sequences a batch, grouped heads, every way the diagonal and
    the window's edge can cross the blocks."""
    q, k, v = _operands(2, 768, 4, 2, 64, seed=rows + keys)
    want = attention._masked_attention(q, k, v, scale=0.2, window=window)
    got, = sdp_kernel.causal_attention(q, k, v, rows=rows, keys=keys,
                                       scale=0.2, window=window,
                                       interpret=True)
    _close(got, want)


def test_bfloat16_operands_take_both_products():
    """What the TPU's branch hands the kernel: bfloat16 `q`, `k`, `v`, the
    probabilities rounded to it for their product, float32 out."""
    import jax.numpy as jnp

    q, k, v = _operands(1, 512, 4, 2, 128, seed=3)
    want = attention._masked_attention(q, k, v, scale=None, window=None)
    got, = sdp_kernel.causal_attention(
        *(x.astype(jnp.bfloat16) for x in (q, k, v)), rows=256, keys=256,
        scale=128 ** -0.5, interpret=True)
    assert got.dtype == np.float32
    assert np.abs(np.asarray(got - want)).max() <= 3e-2 * np.abs(want).max()


@contextlib.contextmanager
def _tpu_kernel_interpreted():
    """Inside, `_sdp_attention` takes the branch a lowering for the TPU
    keeps — the Pallas kernel — run by Pallas's interpreter.  Yields the
    list of kernel branches taken."""
    calls = []

    def take_tpu(*operands, tpu, default):
        calls.append(tpu)
        return tpu(*operands)

    # a trace made under an earlier patch would be served from the cache
    attention._prefill_attention.clear_cache()
    with mock.patch.object(attention.lax, "platform_dependent", take_tpu), \
            mock.patch.object(attention, "_INTERPRET", True):
        yield calls
    attention._prefill_attention.clear_cache()


def _projected(n, t, h, kv, dh, seed):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(n, t, c * dh), jnp.float32)
            for c in (h, kv, kv)]


@pytest.mark.parametrize("name,t,n", [
    ("opt", 1024, 1), ("granite", 768, 2), ("trinity_window", 1024, 1),
    ("qwen3_next", 1024, 2)])
def test_the_op_with_the_kernel_chosen_is_the_op(name, t, n):
    """`_sdp_attention` on the TPU's branch: the context to 1e-5 of its
    largest entry, outputs 1 / 2 (what `_kv_cache_write` puts into the
    rings) bit-equal."""
    h, kv, dh, window, scale = SHAPES[name]
    attrs = dict(num_heads=h, scale=scale, window=window and t // 2)
    if kv != h:
        attrs["num_kv_heads"] = kv
    operands = _projected(n, t, h, kv, dh, seed=t)
    want = attention.sdp_attention(*operands, **attrs)
    with _tpu_kernel_interpreted() as calls:
        got = attention.sdp_attention(*operands, **attrs)
    assert len(calls) == 1
    _close(got[0], want[0])
    assert np.array_equal(got[1], want[1])
    assert np.array_equal(got[2], want[2])


def test_the_latent_prefill_goes_through_the_kernel():
    """`_latent_attention` up-projects and calls `sdp_attention` with the
    model's own `scale`: 32 heads of 64 + 64."""
    import jax.numpy as jnp

    h, nope, rope, value, rank, t = 32, 64, 64, 128, 256, 1024
    rng = np.random.RandomState(7)
    operands = [jnp.asarray(0.3 * rng.randn(*s), jnp.float32) for s in (
        (1, t, h * nope), (1, t, h * rope), (1, t, rank + rope),
        (h * (nope + value), rank))]
    attrs = dict(num_heads=h, rope_dim=rope, value_dim=value, scale=0.195)
    want = latent.latent_attention(*operands, **attrs)
    with _tpu_kernel_interpreted() as calls:
        got = latent.latent_attention(*operands, **attrs)
    assert len(calls) == 1
    _close(got, want)


@pytest.mark.parametrize("why,shape,heads,kv_heads,platform,causal", [
    ("off the TPU", (1, 2048, 2048), 16, 16, "cpu", True),
    ("no causal mask", (1, 2048, 2048), 16, 16, "tpu", False),
    ("a T the blocks do not divide", (1, 1000, 2048), 16, 16, "tpu", True),
    ("a d_head the tiling refuses", (1, 2048, 1536), 16, 16, "tpu", True),
    ("a tiny model's d_head", (1, 2048, 128), 16, 16, "tpu", True),
    ("scores of 32 heads that stay on the chip", (1, 768, 2048), 32, 32,
     "tpu", True),
    ("scores of 16 heads that stay on the chip", (1, 1024, 2048), 16, 16,
     "tpu", True),
    ("more than VMEM holds", (1, 16384, 8192), 32, 32, "tpu", True)])
def test_the_shape_function_says_where_the_body_runs(why, shape, heads,
                                                     kv_heads, platform,
                                                     causal):
    assert attention.prefill_block(shape, heads, kv_heads, platform,
                                   causal=causal) is None, why


@pytest.mark.parametrize("t,group,want", [
    (1024, 1, (1024, 1024)), (1536, 1, (768, 768)), (2048, 1, (1024, 1024)),
    (1280, 4, (256, 640)), (2048, 4, (256, 1024)), (1536, 8, (128, 768)),
    (2048, 8, (128, 1024)), (4096, 8, (128, 1024)),
    (2048, 16, (128, 512)), (5120, 16, (128, 640)), (8192, 16, (128, 512))])
def test_the_shape_function_gives_the_rows_and_keys_of_a_step(t, group, want):
    """The largest multiples of 128 that divide T, within 1,024 rows of
    all the heads of a group and 1,024 positions of a key block — a
    shorter key block where sixteen heads' 128 rows would not fit beside
    1,024 keys (PR 64: Nemotron-H's 32 query heads over 2)."""
    assert attention.prefill_block((1, t, 32 * 128), 32, 32 // group,
                                   "tpu") == want


@pytest.mark.parametrize("heads,first", [(30, 1024), (32, 896), (16, 1280),
                                         (64, 640)])
def test_the_kernel_takes_over_where_the_scores_leave_the_chip(heads, first):
    """The shortest bucket of whole 128s the rule sends through the
    kernel: the first whose float32 scores of all heads pass 96 MiB."""
    taken = [t for t in range(128, 4097, 128) if attention.prefill_block(
        (1, t, heads * 128), heads, heads, "tpu") is not None]
    assert taken[0] == first and taken == list(range(first, 4097, 128))
    assert attention.prefill_block((2, 768, 32 * 64), 32, 8, "tpu") == (
        256, 768)       # two sequences a batch: twice the scores


def test_without_a_block_no_branch_is_taken():
    """A sequence the rule keeps on XLA's form: no kernel branch to take,
    and the op's outputs equal the body's exactly."""
    operands = _projected(1, 256, 4, 4, 64, seed=1)
    want = attention.sdp_attention(*operands, num_heads=4)
    with _tpu_kernel_interpreted() as calls:
        got = attention.sdp_attention(*operands, num_heads=4)
        full = attention.sdp_attention(*_projected(1, 2560, 4, 4, 64, 2),
                                       num_heads=4, causal=False)
    assert calls == [] and full[0].shape == (1, 2560, 256)
    for a, b in zip(got, want):
        assert np.array_equal(a, b)


def test_the_gradient_with_the_kernel_chosen_is_the_bodys():
    """Under `jax.grad` the kernel branch is not differentiated: the
    backward pass is the body's, recomputed."""
    import jax
    import jax.numpy as jnp

    t = 2560   # four heads' scores pass 96 MiB
    operands = _projected(1, t, 4, 2, 64, seed=5)
    weight = jnp.asarray(np.random.RandomState(6).randn(1, t, 256),
                         jnp.float32)

    def loss(q, k, v, attend):
        ctx, kh, vh = attend(q, k, v)
        return jnp.sum(ctx * weight) + jnp.sum(kh) + 2.0 * jnp.sum(vh)

    def body(q, k, v):
        heads = lambda x, c: x.reshape(1, t, c, 64).transpose(0, 2, 1, 3)
        kh, vh = heads(k, 2), heads(v, 2)
        ctx = attention._masked_attention(
            heads(q, 4).reshape(1, 2, 2, t, 64), kh, vh, scale=None,
            window=None)
        return (ctx.reshape(1, 4, t, 64).transpose(0, 2, 1, 3).reshape(
            1, t, 256), kh, vh)

    op = lambda q, k, v: attention.sdp_attention(q, k, v, num_heads=4,
                                                 num_kv_heads=2)
    want = jax.grad(loss, argnums=(0, 1, 2))(*operands, body)
    with _tpu_kernel_interpreted() as calls:
        got = jax.grad(loss, argnums=(0, 1, 2))(*operands, op)
    assert calls     # traced as the primal and as the forward rule
    for a, b in zip(got, want):
        assert np.abs(np.asarray(a - b)).max() <= 1e-5 * np.abs(b).max()


@pytest.mark.parametrize("kind", ["attention", "window", "latent"])
def test_a_prefill_books_what_the_shape_function_says(kind):
    """`call_counters(positions=..., platform=...)`: the bucket's
    positions an attention layer, and of them those `prefill_block` sends
    through the kernel — all on a TPU for a bucket the rule takes, none
    for a shorter one or on the CPU; a decode step adds none."""
    shape = dict(vocab=64, num_layers=2, num_heads=4, d_model=256, d_ff=64,
                 max_len=8192)
    if kind == "window":
        shape.update(layer_types=["window_attention", "attention"],
                     sliding_window=512, num_kv_heads=2, norm="rms",
                     positions={"window_attention": "rotary"}, bias=False)
    elif kind == "latent":
        shape.update(layer_types=["latent_attention"] * 2, latent_q_rank=32,
                     latent_kv_rank=16, latent_nope_dim=32,
                     latent_rope_dim=32, latent_value_dim=64, norm="rms",
                     positions="none", bias=False)
    lm = TransformerLM(**shape)

    def booked(**call):
        counters = lm.call_counters(**call)
        return (counters["attn.prefill_positions"],
                counters["attn.kernel_positions"])

    assert attention.prefill_block((1, 4096, 256), 4, 4, "tpu") is not None
    assert booked(positions=4096, platform="tpu") == (8192, 8192)
    assert booked(positions=4096, platform="cpu") == (8192, 0)
    assert booked(positions=2048, platform="tpu") == (4096, 0)
    assert booked(positions=4096, rows=3, lengths=[5, 6, 7], computed=4,
                  pages=5, max_len=8192, platform="tpu") == (8192, 8192)
    assert booked(rows=3, lengths=[5, 6, 7], computed=4, pages=5,
                  max_len=8192, platform="tpu") == (0, 0)
