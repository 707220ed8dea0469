"""PR 36: `FullyConnected` takes its product on the ``(rows, channels)``
view of its operand, so that a prefill of one prompt hands XLA plain 2-D
matmuls (at ``(1, 256, 2048)`` the TPU compiler's FFN fusion ran 21 ms a
program where its neighbours' run 4 to 6: PERF.md section 6, PR 36).  The
form may not move a number: at OPT-1.3B's published widths (hidden 2048,
32 heads, FFN 8192; one layer and a small vocabulary, which are no
widths) and at each of the cell's prefill buckets, the op equals the
parent's ``jnp.dot(data, weight.T) + bias`` on the 3-D operand, a prefill
through the tenant's own program leaves the rings and the next-token
logits the parent's op leaves, and those logits are the full forward's
(`score_symbol`) at the prompt's tail."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.models import TransformerLM
from mxnet_tpu.ops import nn
from mxnet_tpu.ops.registry import OP_REGISTRY
from mxnet_tpu.serving import GenerativeSession

BUCKETS = [64, 128, 256, 512]  # benchmarks/traffic/gen_closed_c16.json
WIDTHS = dict(d_model=2048, num_heads=32, d_ff=8192)
VOCAB, MAX_LEN = 256, 768


def parent_fully_connected(data, weight, bias=None, num_hidden=None,
                           no_bias=False, flatten=True, **kw):
    """`ops.nn.fully_connected` as commit f3ad487 had it."""
    import jax.numpy as jnp

    if nn._bool(flatten):
        data = data.reshape((data.shape[0], -1))
    out = jnp.dot(data, weight.T)
    if bias is not None and not nn._bool(no_bias):
        out = out + bias
    return out


def _close(got, want, rel):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= rel * np.abs(want).max()


@pytest.mark.parametrize("t", BUCKETS)
def test_the_product_on_the_2d_view_is_the_parents(t):
    """Each projection of an OPT block — QKV, FFN in, FFN out, with
    their biases — on a ``(1, t, d)`` operand, and the flattening form
    on a 4-D one."""
    rng = np.random.RandomState(t)
    d, ff = WIDTHS["d_model"], WIDTHS["d_ff"]
    for rows, cols in ((3 * d, d), (ff, d), (d, ff)):
        data = rng.randn(1, t, cols).astype(np.float32)
        weight = (0.02 * rng.randn(rows, cols)).astype(np.float32)
        bias = (0.02 * rng.randn(rows)).astype(np.float32)
        got = nn.fully_connected(data, weight, bias, num_hidden=rows,
                                 flatten=False)
        assert got.shape == (1, t, rows)
        _close(got, parent_fully_connected(data, weight, bias,
                                           flatten=False), 1e-6)
        _close(nn.fully_connected(data, weight, num_hidden=rows,
                                  no_bias=True, flatten=False),
               parent_fully_connected(data, weight, no_bias=True,
                                      flatten=False), 1e-6)
    data = rng.randn(3, t // 16, 4, 4).astype(np.float32)
    weight = (0.02 * rng.randn(10, t)).astype(np.float32)
    _close(nn.fully_connected(data, weight, None, num_hidden=10,
                              no_bias=True),
           parent_fully_connected(data, weight, no_bias=True), 1e-6)


@pytest.fixture(scope="module")
def opt_block():
    """One OPT-1.3B block at its published widths, weights N(0, 0.02),
    LayerNorm gains about 1."""
    lm = TransformerLM(vocab=VOCAB, num_layers=1, max_len=2048, **WIDTHS)
    graph = lm.prefill_symbol()
    spec = lm.cache_spec(2, MAX_LEN)
    inputs = dict(data=(1, 64), slot=(1,), length=(1,), last_token=(2,),
                  **{n: e.shape for n, e in spec.items()})
    shapes, _, _ = graph.infer_shape(**inputs)
    rng = np.random.RandomState(36)
    params = {}
    for name, shape in zip(graph.list_arguments(), shapes):
        if name in inputs:
            continue
        value = 0.02 * rng.randn(*shape)
        if name.endswith("_gamma"):
            value = 1.0 + 5.0 * value
        params[name] = mx.nd.array(value.astype(np.float32), ctx=mx.cpu())
    return lm, params


def _prefill(lm, params, tokens, bucket):
    """(next-token logits, the rings) after one prompt's prefill in the
    `bucket` program of a fresh session."""
    gs = GenerativeSession("lm", lm, params, ctx=mx.cpu(), max_sessions=1,
                           max_len=MAX_LEN, seq_buckets=[bucket])
    try:
        exe, fn = gs._program(gs._prefill_pred, 1, bucket, True)
        data = np.zeros((1, bucket), np.float32)
        data[0, :len(tokens)] = tokens
        logits = gs._run(exe, fn, data, np.zeros((1,), np.float32),
                         np.full((1,), len(tokens), np.float32))
        return logits[0], [np.asarray(a) for a in gs._state[:-1]]
    finally:
        gs.close()


@pytest.mark.parametrize("t", BUCKETS)
def test_a_prefill_at_opts_widths_is_the_parents_and_the_full_forwards(
        t, opt_block, monkeypatch):
    lm, params = opt_block
    n = t - 5  # a padded tail, as every prompt but a bucket-long one has
    tokens = np.random.RandomState(t).randint(0, VOCAB, n)
    logits, rings = _prefill(lm, params, tokens, t)
    assert len(rings) == 2 and rings[0].shape == (2, 32, 64, MAX_LEN)
    pred = mx.Predictor(lm.score_symbol(), dict(params), {"data": (1, n)})
    pred.forward(data=tokens[None].astype(np.float32))
    _close(logits, pred.get_output(0).reshape(n, VOCAB)[-1], 1e-4)
    pred.close()
    monkeypatch.setattr(OP_REGISTRY["FullyConnected"], "fn",
                        parent_fully_connected)
    parent_logits, parent_rings = _prefill(lm, params, tokens, t)
    _close(logits, parent_logits, 1e-6)
    for got, want in zip(rings, parent_rings):
        assert np.abs(want[0, :, :, :n]).max() > 0
        _close(got, want, 1e-6)
