"""The delta rule's chunked prefill as a Pallas TPU kernel
(mxnet_tpu/ops/gdn_kernel.py) — everything between the conv's SiLU and the
output projection: the L2 norms of q and k, the repeat to value heads, the
chunked rule and the gated norm —, run by Pallas's interpreter on the CPU
against its oracle, the ``jax.numpy`` composition `ops.gdn._normed_rule`
(`_heads` -> `_stored_chunked` -> `_gated_norm`): the same operands, ``y``
and the final state to float32 rounding — the order of the benchmark's
limit 2 (benchmarks/families/olmo_hybrid.py `PREFILL_STATE_RTOL` 4e-4 holds
a prefill's state to the reference; 1e-4 of the largest entry here,
measured 2e-6) — through the shape function that says where the kernel
runs (`ops.gdn.chunk_heads`) and through `_gdn_prefill` as a serving
program calls it.  What Mosaic makes of the kernel at the benchmark's
widths is in tests/test_tpu_compile.py.  The file costs about 50 s."""
import contextlib
from unittest import mock

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import gdn, gdn_kernel

RTOL = 1e-4   # of the largest entry of what is compared
H, DK, DV, K, CHUNK = 4, 16, 32, 4, 16
EPS = 1e-6


def _operands(n, t, seed, heads=H, dk=DK, dv=DV, key_heads=None, length=None,
              beta=None, repeat=False, decay=(1e-3, 5.0), zero_rows=()):
    """``qkv``, ``z``, ``beta``, ``g``, ``gamma`` as `_mix` hands them to
    the rule: the conv's output ``[q | k | v]`` with q and k of `key_heads`
    heads (`heads` where not given) and of no particular length — all
    zeros at `zero_rows`, where only the eps inside the root keeps the
    norm finite —, the gate ``z``, `beta` in (0, 2) or all `beta`, the log
    decay a position log-uniform in ``-decay`` (from nearly kept to
    forgotten within a position), a ``gamma`` that is not all ones; with
    `repeat` every key is the one before it or its negative, so that the
    chunk's system couples every pair of positions as strongly as it
    can; positions at and beyond ``length[n]`` masked to ``beta = 0, g =
    0`` as `_mix` masks a bucket's pad."""
    rng = np.random.RandomState(seed)
    hk = heads if key_heads is None else key_heads
    q, k = (3.0 * rng.randn(n, t, hk, dk).astype(np.float32) for _ in "qk")
    if repeat:
        sign = rng.choice([-1.0, 1.0], (n, t, hk, 1)).astype(np.float32)
        k = k[:, :1] * np.cumprod(sign, axis=1)
    for row in zero_rows:
        q[:, row] = k[:, row] = 0.0
    v = rng.randn(n, t, heads * dv).astype(np.float32)
    z = 2.0 * rng.randn(n, t, heads * dv).astype(np.float32)
    b = (rng.uniform(0, 2, (n, t, heads)) if beta is None
         else np.full((n, t, heads), beta)).astype(np.float32)
    g = -np.exp(rng.uniform(*np.log(decay), (n, t, heads))).astype(np.float32)
    gamma = (1.0 + 0.3 * rng.randn(dv)).astype(np.float32)
    if length is not None:
        live = np.arange(t)[None, :, None] < np.asarray(length)[:, None, None]
        b, g = np.where(live, b, 0.0), np.where(live, g, 0.0)
    qkv = np.concatenate([q.reshape(n, t, -1), k.reshape(n, t, -1), v], -1)
    return qkv, z, b.astype(np.float32), g.astype(np.float32), gamma


def _both(operands, chunk, heads, key_heads):
    """(y, stored state) of the composition and of the interpreted
    kernel."""
    rule = dict(key_heads=key_heads, eps=EPS, chunk=chunk)
    want = gdn._normed_rule(*operands, **rule)
    got = gdn_kernel.chunked_delta_rule(*operands, heads=heads,
                                        interpret=True, **rule)
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
    "rows_of_zero_q_and_k": (1, 2, dict(zero_rows=(0, 3, CHUNK + 1)), 2),
    "two_value_heads_a_key_head": (1, 2, dict(key_heads=2), 4),
    "a_step_within_one_key_head": (1, 2, dict(key_heads=2, length=[21]), 1),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_kernel_matches_the_body(name):
    """``y`` — the L2 norms, the rule, the gated norm with a ``gamma``
    that is not all ones — and the final state as the session stores it,
    the kernel interpreted against `_normed_rule`; the rows of a bucket's
    pad come out finite (`_close`)."""
    n, chunks, kw, heads = CASES[name]
    operands = _operands(n, chunks * CHUNK, seed=len(name), **kw)
    (want_y, want_s), (got_y, got_s) = _both(operands, CHUNK, heads,
                                             kw.get("key_heads", H))
    assert got_y.shape == (n, chunks * CHUNK, H * DV)
    assert got_s.shape == (n, DK, H * DV)
    _close(got_y, want_y)
    _close(got_s, want_s)


def test_the_kernel_matches_the_body_at_the_benchmarks_widths():
    """Heads of 96 x 192 and chunks of 64, the Olmo-Hybrid widths: a
    head's lanes begin where no tile does (six heads: three whole spans
    of four of ``[q | k]``'s twelve, three spans of two values' heads),
    and the chunk's inverse is built in five eliminations; `beta` at 2
    and every key the one before it or its negative — the solve's hardest
    case, its system's entries all 2 and its inverse's too."""
    operands = _operands(1, 128, seed=3, heads=6, dk=96, dv=192, beta=2.0,
                         repeat=True, decay=(1e-3, 1e-2))
    (want_y, want_s), (got_y, got_s) = _both(operands, 64, 6, 6)
    _close(got_y, want_y)
    _close(got_s, want_s)


# name -> (key heads, value heads, d_k, d_v): the two configurations that
# run the kernel, every head of them
WIDTHS = {"olmo_hybrid": (30, 30, 96, 192), "qwen3_next": (16, 32, 128, 128)}


@pytest.mark.parametrize("name", sorted(WIDTHS))
def test_the_fused_form_at_a_configurations_widths(name):
    """One chunk of 64 of ALL heads of Olmo-Hybrid (30 of 96 x 192:
    ``[q | k]`` fifteen whole spans of four heads, ``v`` fifteen of two)
    and of Qwen3-Next (16 key heads under 32 value heads of 128 x 128:
    value head n reads key head ``n // 2``), walked as `chunk_heads` says,
    a true length inside the chunk and a row of zeros."""
    hk, h, dk, dv = WIDTHS[name]
    heads = gdn.chunk_heads((1, 64, h, dk), dv, 64, "tpu", hk)
    operands = _operands(1, 64, seed=h, heads=h, dk=dk, dv=dv, key_heads=hk,
                         length=[41], zero_rows=(7,))
    (want_y, want_s), (got_y, got_s) = _both(operands, 64, heads, hk)
    _close(got_y, want_y)
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
    Olmo-Hybrid's thirty and eight of Qwen3-Next's thirty-two, in each of
    the cells' four buckets (the gate's block beside the others within the
    24 MiB a chunk of all heads may take); a bucket shorter than a chunk is
    one chunk."""
    for bucket in (768, 1024, 1536, 2048):
        assert gdn.chunk_heads((1, bucket, 30, 96), 192, 64, "tpu") == 6
        assert gdn.chunk_heads((1, bucket, 32, 128), 128, 64, "tpu", 16) == 8
    assert gdn.chunk_heads((1, 3 * CHUNK, H, DK), DV, CHUNK, "tpu") == H
    assert gdn.chunk_heads((2, 8, H, DK), DV, CHUNK, "tpu") == H
    assert gdn.chunk_heads((1, 64, 7, 96), 192, 64, "tpu") == 1
    assert gdn.chunk_heads((1, 64, 14, 96), 192, 64, "tpu") == 2
    assert gdn.chunk_heads((1, 64, 15, 96), 192, 64, "tpu") == 5
    # q and k at their own width: 36 heads of 96 x 192 are beyond what
    # VMEM holds under as many key heads, and within it under eighteen
    assert gdn.chunk_heads((1, 64, 36, 96), 192, 64, "tpu") is None
    assert gdn.chunk_heads((1, 64, 36, 96), 192, 64, "tpu", 18) == 6


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

    shapes = ((1, 2 * CHUNK, H * (2 * DK + DV)), (1, 2 * CHUNK, H * DV),
              (1, 2 * CHUNK, H), (1, 2 * CHUNK, H), (DV,))
    operands = [jax.ShapeDtypeStruct(s, np.float32) for s in shapes]

    def made(heads):
        static = dict(chunk=CHUNK, eps=EPS, heads=heads, key_heads=H)
        key = ("gdn_kernel", "chunked_delta_rule",
               tuple((s, "float32") for s in shapes),
               tuple(sorted(static.items())))
        return exported._exported(key, operands, static)

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
            (1, 2 * CHUNK, H * DV), (1, DK, H * DV)]
        assert len(kept.read_bytes()) > 100
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
