"""Long-context attention: ring (seq-parallel over the mesh) and blockwise
kernels vs full-softmax attention (SURVEY §5 mandated capability)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.parallel.mesh import make_mesh
from mxnet_tpu.parallel.ring_attention import (blockwise_attention,
                                               ring_attention_sharded)


def _full_attention(q, k, v, causal=False):
    d = q.shape[-1]
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    if causal:
        t = q.shape[1]
        mask = np.tril(np.ones((t, t), bool))
        s = np.where(mask[None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


def _qkv(seed, b, t, h, d):
    rng = np.random.RandomState(seed)
    return [rng.uniform(-1, 1, (b, t, h, d)).astype(np.float32)
            for _ in range(3)]


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    B, T, H, D = 2, 32, 2, 8
    q, k, v = _qkv(0, B, T, H, D)
    mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
    out = np.asarray(ring_attention_sharded(mesh, q, k, v, causal=causal))
    ref = _full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


def test_ring_attention_with_data_axis():
    B, T, H, D = 4, 16, 2, 4
    q, k, v = _qkv(1, B, T, H, D)
    mesh = make_mesh({"data": 2, "seq": 4})
    out = np.asarray(ring_attention_sharded(mesh, q, k, v, batch_axis="data"))
    np.testing.assert_allclose(out, _full_attention(q, k, v),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_attention_matches_full(causal):
    B, T, H, D = 2, 64, 2, 8
    q, k, v = _qkv(2, B, T, H, D)
    out = np.asarray(blockwise_attention(jnp.asarray(q), jnp.asarray(k),
                                         jnp.asarray(v), block_size=16,
                                         causal=causal))
    np.testing.assert_allclose(out, _full_attention(q, k, v, causal=causal),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_gradients():
    B, T, H, D = 1, 16, 1, 4
    q, k, v = _qkv(3, B, T, H, D)
    mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])

    def ring_loss(args):
        return jnp.sum(ring_attention_sharded(mesh, *args) ** 2)

    def full_loss(args):
        qq, kk, vv = args
        d = qq.shape[-1]
        s = jnp.einsum("bqhd,bkhd->bhqk", qq, kk) / jnp.sqrt(jnp.float32(d))
        p = jax.nn.softmax(s, axis=-1)
        return jnp.sum(jnp.einsum("bhqk,bkhd->bqhd", p, vv) ** 2)

    g_ring = jax.grad(ring_loss)((jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
    g_full = jax.grad(full_loss)((jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
    for gr, gf, name in zip(g_ring, g_full, "qkv"):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf),
                                   rtol=5e-3, atol=1e-4, err_msg=name)


def test_contrib_blockwise_attention_op():
    B, T, H, D = 2, 32, 2, 4
    q, k, v = _qkv(4, B, T, H, D)
    out = mx.contrib.ndarray.BlockwiseAttention(
        mx.nd.array(q), mx.nd.array(k), mx.nd.array(v), block_size=8,
        causal=True).asnumpy()
    np.testing.assert_allclose(out, _full_attention(q, k, v, causal=True),
                               rtol=2e-4, atol=2e-5)
    # symbolic + gradient path
    sym = mx.contrib.symbol.BlockwiseAttention(
        mx.sym.Variable("q"), mx.sym.Variable("k"), mx.sym.Variable("v"),
        block_size=8)
    loss = mx.sym.MakeLoss(mx.sym.sum(sym))
    args = {"q": mx.nd.array(q), "k": mx.nd.array(k), "v": mx.nd.array(v)}
    grads = {n: mx.nd.zeros(a.shape) for n, a in args.items()}
    ex = loss.bind(mx.cpu(), args, args_grad=grads)
    ex.forward(is_train=True)
    ex.backward()
    for n, g in ex.grad_dict.items():
        assert np.isfinite(g.asnumpy()).all() and np.abs(g.asnumpy()).max() > 0, n


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_full(causal):
    from mxnet_tpu.parallel.ring_attention import ulysses_attention_sharded
    B, T, H, D = 2, 32, 4, 8  # H=4 divisible by seq axis 4
    q, k, v = _qkv(6, B, T, H, D)
    mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
    out = np.asarray(ulysses_attention_sharded(mesh, q, k, v, causal=causal))
    ref = _full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


def test_ulysses_matches_ring():
    from mxnet_tpu.parallel.ring_attention import ulysses_attention_sharded
    B, T, H, D = 1, 16, 4, 4
    q, k, v = _qkv(7, B, T, H, D)
    mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
    ring = np.asarray(ring_attention_sharded(mesh, q, k, v, causal=True))
    uly = np.asarray(ulysses_attention_sharded(mesh, q, k, v, causal=True))
    np.testing.assert_allclose(uly, ring, rtol=2e-4, atol=2e-5)


# sha1 of the jaxpr — the Pallas call with the kernel's body inside — that
# `ops.kv_ring_kernel.ring_attention` traces for a per-head K and V ring,
# as the parent of PR 46 (335689c) traces it — the same file, to the byte,
# that the parent of PR 44 had: (rows, query heads, K/V heads, d_head, ring
# length, wraps).  (Until PR 46 the pin was on `_decode_attention`'s own
# jaxpr, which held this call inline; it now calls the kernel EXPORTED,
# lowered once a shape — `ops/exported.py` — and what is exported is this.)
PARENT_RING_PROGRAMS = {
    "olmoe": ((8, 16, 16, 128, 768, False),
              "31e07feaa7be48044af11f5847624ca7e2d8a535"),
    "trinity_window": ((8, 32, 4, 128, 2048, True),
                       "fbe1114ee3134eac4d5c3bae9dcb75168d238005"),
}


@pytest.mark.parametrize("which", sorted(PARENT_RING_PROGRAMS))
def test_a_per_head_rings_kernel_program_is_the_parents(which):
    """The latent ring (PR 44) has a kernel of its own beside
    `kv_ring_kernel.py`, and `decode_block` / `decode_heads` answer for it
    only when asked `latent=True`: for rings that are per-head K and V the
    decode step traces the parent's program, op for op (PR 38's lesson:
    there the kernel's source moved and `olmoe_offline` read 1% lower until
    it emitted the parent's program for rings that do not wrap)."""
    import functools
    import hashlib

    from mxnet_tpu.ops import attention, kv_ring_kernel

    (rows, h_q, h_kv, d_head, ring_len, wraps), want = \
        PARENT_RING_PROGRAMS[which]
    ring = (9, h_kv, d_head, ring_len)
    block = attention.decode_block(ring, "tpu")
    heads = attention.decode_heads(ring)
    step = functools.partial(kv_ring_kernel.ring_attention, block=block,
                             heads=heads, scale=None, interpret=False,
                             wraps=wraps)

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    text = str(jax.make_jaxpr(step)(
        arg((rows, h_q, d_head)), arg((rows, h_kv, d_head)),
        arg((rows, h_kv, d_head)), arg(ring), arg(ring),
        arg((rows,), jnp.int32), arg((rows,), jnp.int32)))
    assert "pallas_call" in text
    assert hashlib.sha1(text.encode()).hexdigest() == want
