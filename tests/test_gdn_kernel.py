"""The delta rule's chunked prefill as a Pallas TPU kernel
(mxnet_tpu/ops/gdn_kernel.py), run by Pallas's interpreter on the CPU
against its oracle, the ``jax.numpy`` body `ops.gdn._chunked`: the same
operands, ``o`` and the final state to float32 rounding — the order of the
benchmark's limit 2 (benchmarks/families/olmo_hybrid.py
`PREFILL_STATE_RTOL` 4e-4 holds a prefill's state to the reference;
1e-4 of the largest entry here, measured 1e-6) — through the shape
function that says where the kernel runs (`ops.gdn.chunk_heads`) and
through `_gdn_prefill` as a serving program calls it.  What Mosaic makes
of the kernel at the benchmark's widths is in tests/test_tpu_compile.py.
The file costs about 50 s."""
import contextlib
from unittest import mock

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import gdn, gdn_kernel

RTOL = 1e-4   # of the largest entry of what is compared
H, DK, DV, K, CHUNK = 4, 16, 32, 4, 16


def _operands(n, t, seed, heads=H, dk=DK, dv=DV, length=None, beta=None,
              repeat=False, decay=(1e-3, 5.0)):
    """``q``, ``k``, ``v``, ``beta``, ``g`` as `_mix` hands them to the
    rule: keys of unit length, queries of length ``d_k ** -0.5``, `beta`
    in (0, 2) or all `beta`, the log decay a position log-uniform in
    ``-decay`` (from nearly kept to forgotten within a position); with
    `repeat` every key is the one before it or its negative, so that the
    chunk's system couples every pair of positions as strongly as it
    can; positions at and beyond ``length[n]`` masked to ``beta = 0, g =
    0`` as `_mix` masks a bucket's pad."""
    rng = np.random.RandomState(seed)
    q, k = (rng.randn(n, t, heads, d).astype(np.float32) for d in (dk, dk))
    if repeat:
        sign = rng.choice([-1.0, 1.0], (n, t, heads, 1)).astype(np.float32)
        k = k[:, :1] * np.cumprod(sign, axis=1)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * dk ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.randn(n, t, heads, dv).astype(np.float32)
    b = (rng.uniform(0, 2, (n, t, heads)) if beta is None
         else np.full((n, t, heads), beta)).astype(np.float32)
    g = -np.exp(rng.uniform(*np.log(decay), (n, t, heads))).astype(np.float32)
    if length is not None:
        live = np.arange(t)[None, :, None] < np.asarray(length)[:, None, None]
        b, g = np.where(live, b, 0.0), np.where(live, g, 0.0)
    return q, k, v, b.astype(np.float32), g.astype(np.float32)


def _both(operands, chunk, heads):
    """(o, stored state) of the body and of the interpreted kernel."""
    want = gdn._stored_chunked(*operands, chunk)
    got = gdn_kernel.chunked_delta_rule(*operands, chunk=chunk, heads=heads,
                                        interpret=True)
    return [np.asarray(x) for x in want], [np.asarray(x) for x in got]


def _close(got, want):
    assert got.shape == want.shape
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= RTOL * np.abs(want).max()


# name -> (sequences, chunks, kwargs of `_operands`, heads a step)
CASES = {
    "one_chunk": (1, 1, {}, 2),
    "three_chunks": (1, 3, {}, 4),
    "twelve_chunks": (1, 12, {}, 1),
    "a_length_inside_the_first_chunk": (1, 3, dict(length=[5]), 2),
    "a_length_inside_the_last_chunk": (1, 3, dict(length=[2 * CHUNK + 7]), 2),
    "a_length_equal_to_the_bucket": (1, 3, dict(length=[3 * CHUNK]), 2),
    "the_two_token_prompt": (1, 3, dict(length=[2]), 1),
    "beta_at_its_ceiling_and_keys_repeated": (
        1, 2, dict(beta=2.0, repeat=True, decay=(1e-3, 1e-2)), 2),
    "hardly_any_decay": (1, 3, dict(decay=(1e-3, 1.001e-3)), 2),
    "a_decay_of_five_a_position": (1, 3, dict(decay=(4.999, 5.0)), 2),
    "two_sequences_a_batch": (2, 3, dict(length=[40, 9]), 4),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_kernel_matches_the_body(name):
    """``o`` and the final state as the session stores it, the kernel
    interpreted against `_chunked`."""
    n, chunks, kw, heads = CASES[name]
    operands = _operands(n, chunks * CHUNK, seed=len(name), **kw)
    (want_o, want_s), (got_o, got_s) = _both(operands, CHUNK, heads)
    assert got_s.shape == (n, DK, H * DV)
    _close(got_o, want_o)
    _close(got_s, want_s)


def test_the_kernel_matches_the_body_at_the_benchmarks_widths():
    """Heads of 96 x 192 and chunks of 64, the Olmo-Hybrid widths: a
    head's lanes begin where no tile does (six heads: one whole span of
    four keys' heads and two left over, three spans of two values'
    heads), and the chunk's inverse is built in five eliminations; `beta`
    at 2 and every key the one before it or its negative — the solve's
    hardest case, its system's entries all 2 and its inverse's too."""
    operands = _operands(1, 128, seed=3, heads=6, dk=96, dv=192, beta=2.0,
                         repeat=True, decay=(1e-3, 1e-2))
    (want_o, want_s), (got_o, got_s) = _both(operands, 64, 6)
    _close(got_o, want_o)
    _close(got_s, want_s)


@contextlib.contextmanager
def _tpu_kernel_interpreted():
    """Inside, `_gdn_prefill` takes the branch a lowering for the TPU
    keeps — the Pallas kernel — run by Pallas's interpreter.  Yields the
    list of kernel branches taken."""
    calls = []

    def take_tpu(*operands, tpu, default):
        calls.append(tpu)
        return tpu(*operands)

    # a trace made under an earlier patch would be served from the cache
    gdn._delta_rule.clear_cache()
    with mock.patch.object(gdn.lax, "platform_dependent", take_tpu), \
            mock.patch.object(gdn, "_INTERPRET", True):
        yield calls
    gdn._delta_rule.clear_cache()


def _prefill(bucket, lengths, slots, seed, chunk=CHUNK):
    """`_gdn_prefill` of a bucket over random slots; returns its three
    outputs as numpy."""
    n = len(lengths)
    conv_dim = H * (2 * DK + DV)
    rng = np.random.RandomState(seed)
    data = rng.randn(n, bucket, conv_dim + H * DV + 2 * H).astype(np.float32)
    data[..., conv_dim + H * DV:conv_dim + H * DV + H] *= 3.0       # b
    small = [rng.uniform(-0.5, 0.5, (K, conv_dim)), rng.randn(H),
             np.log(rng.uniform(0.01, 16.0, H)), 1 + 0.1 * rng.randn(DV)]
    conv0 = rng.randn(max(slots) + 2, K - 1, conv_dim)
    gdn0 = rng.randn(max(slots) + 2, DK, H * DV)
    nd = [mx.nd.array(np.asarray(a, np.float32))
          for a in [data, *small, conv0, gdn0, slots, lengths]]
    attrs = dict(num_heads=H, key_dim=DK, value_dim=DV, conv_kernel=K,
                 chunk_size=chunk, neg_eigval=True, eps=1e-6)
    return [o.asnumpy() for o in mx.nd._gdn_prefill(*nd, **attrs)]


@pytest.mark.parametrize("lengths,slots", [([37], [2]), ([2], [0]),
                                           ([48, 19], [3, 1])])
def test_prefill_with_the_kernel_writes_what_the_body_writes(lengths, slots):
    """`_gdn_prefill` through the interpreted kernel: ``y``, and the conv
    window and the state at `slot` — every other slot untouched — are the
    body's."""
    want = _prefill(3 * CHUNK, lengths, slots, seed=sum(lengths))
    with _tpu_kernel_interpreted() as calls:
        got = _prefill(3 * CHUNK, lengths, slots, seed=sum(lengths))
    assert len(calls) == 1
    _close(got[0], want[0])
    assert np.array_equal(got[1], want[1])      # the window: copied rows
    _close(got[2], want[2])
    others = [i for i in range(len(got[2])) if i not in slots]
    assert np.array_equal(got[2][others], want[2][others])


@pytest.mark.parametrize("why,shape,chunk,platform", [
    ("no whole number of chunks", (1, 3 * CHUNK + 8, H, DK), CHUNK, "tpu"),
    ("a chunk that is no whole number of tiles", (1, 36, H, DK), 12, "tpu"),
    ("a short bucket that is no whole number of tiles", (1, 12, H, DK),
     CHUNK, "tpu"),
    ("off the TPU", (1, 3 * CHUNK, H, DK), CHUNK, "cpu"),
    ("more than VMEM holds", (1, 4096, 512, 128), 64, "tpu"),
])
def test_the_shape_function_says_where_the_body_runs(why, shape, chunk,
                                                     platform):
    assert gdn.chunk_heads(shape, DV, chunk, platform) is None, why


def test_the_shape_function_gives_the_heads_of_a_step():
    """Whole chunks on the TPU: the most heads that divide ``H`` and fit
    the walk's 4 MiB, an even number where one does — six of
    Olmo-Hybrid's thirty, in each of the cell's four buckets; a bucket
    shorter than a chunk is one chunk."""
    for bucket in (768, 1024, 1536, 2048):
        assert gdn.chunk_heads((1, bucket, 30, 96), 192, 64, "tpu") == 6
    assert gdn.chunk_heads((1, 3 * CHUNK, H, DK), DV, CHUNK, "tpu") == H
    assert gdn.chunk_heads((2, 8, H, DK), DV, CHUNK, "tpu") == H
    assert gdn.chunk_heads((1, 64, 7, 96), 192, 64, "tpu") == 1
    assert gdn.chunk_heads((1, 64, 14, 96), 192, 64, "tpu") == 2
    assert gdn.chunk_heads((1, 64, 15, 96), 192, 64, "tpu") == 5


@pytest.mark.parametrize("bucket,chunk", [(3 * CHUNK + 8, CHUNK), (36, 12)])
def test_where_the_function_says_none_the_op_is_todays(bucket, chunk):
    """A shape the kernel does not tile: a lowering for the TPU is given
    no kernel branch to take, and the op's three outputs equal the
    body's exactly."""
    want = _prefill(bucket, [bucket - 5], [1], seed=bucket, chunk=chunk)
    with _tpu_kernel_interpreted() as calls:
        got = _prefill(bucket, [bucket - 5], [1], seed=bucket, chunk=chunk)
    assert calls == []
    for a, b in zip(got, want):
        assert np.array_equal(a, b)


def test_the_kernel_is_exported_once_a_shape_and_found_again(tmp_path):
    """`ops.exported` keeps the lowered kernel beside JAX's compiled
    programs: made and written on the first call, and a later process —
    here the same one with its memory of it cleared and the kernel's
    module made unusable — reads it back instead of tracing the kernel;
    a file that is not a whole export is made anew."""
    import jax

    from mxnet_tpu.ops import exported

    shapes = ((1, 2 * CHUNK, H, DK), (1, 2 * CHUNK, H, DK),
              (1, 2 * CHUNK, H, DV), (1, 2 * CHUNK, H), (1, 2 * CHUNK, H))
    operands = [jax.ShapeDtypeStruct(s, np.float32) for s in shapes]

    def made(heads):
        key = ("gdn_kernel", "chunked_delta_rule",
               tuple((s, "float32") for s in shapes),
               (("chunk", CHUNK), ("heads", heads)))
        return exported._exported(key, operands,
                                  dict(chunk=CHUNK, heads=heads))

    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    try:
        first = made(2)
        kept, = tmp_path.iterdir()
        assert kept.name.startswith("mx-gdn-kernel-")
        assert first.platforms == ("tpu",)
        assert [tuple(a.shape) for a in first.in_avals] == list(shapes)
        with mock.patch.object(gdn_kernel, "chunked_delta_rule",
                               side_effect=AssertionError("traced again")):
            again = made(2)
            assert again.mlir_module_serialized == first.mlir_module_serialized
            # another shape is another kernel
            with pytest.raises(AssertionError, match="traced again"):
                made(4)
        kept.write_bytes(kept.read_bytes()[:100])
        mended = made(2)
        assert [tuple(a.shape) for a in mended.out_avals] == [
            (1, 2 * CHUNK, H, DV), (1, DK, H * DV)]
        assert len(kept.read_bytes()) > 100
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
