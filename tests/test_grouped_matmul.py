"""A routed FFN's segment matmul as a Pallas TPU kernel
(mxnet_tpu/ops/grouped_matmul_kernel.py), run by Pallas's interpreter on
the CPU against `lax.ragged_dot`: alone at every way a call's segments can
lie over the row tiles, and inside `parallel.moe.dropless_experts` with
the kernel forced against the parent's form — outputs, `load` and the
gradients — and through the shape function that says where the kernel
runs (`parallel.moe.kernel_tiles`).  The two calls that fetch and place
their own rows and the sum of what they place (PR 61: `gate_up`, `down`,
`slab_sum`, chosen by `parallel.moe.fused_tile`) against the gather,
`lax.ragged_dot` and the un-sort they replace, alone and inside the
layer.  A held range's return to token order (PR 63:
mxnet_tpu/ops/row_return_kernel.py, chosen by
`parallel.moe.return_tiles`) against the scatter-add it replaces, pass
by pass at the seven held cells' routings cut down, and inside the layer.
What Mosaic makes of
the kernels at the benchmark's widths is in tests/test_tpu_compile.py.
The file costs about 110 s."""
import contextlib
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mxnet_tpu.ops.grouped_matmul_kernel import (down, gate_up,
                                                  grouped_matmul, items,
                                                  slab_sum)
from mxnet_tpu.ops.row_return_kernel import row_return
from mxnet_tpu.parallel import moe

# name -> (rows M, K, N, row tile, columns a strip, each expert's rows)
CASES = {
    # three segments over four tiles of 16, two edges inside a tile
    "segments that cross row tiles": (64, 128, 128, 16, 128, [20, 25, 19]),
    "segments that end on a tile's edge": (64, 128, 128, 16, 128,
                                           [16, 32, 16]),
    "several segments inside one tile": (32, 128, 128, 32, 128,
                                         [3, 5, 0, 7, 17]),
    "an expert with no rows": (48, 128, 128, 16, 128, [20, 0, 0, 28, 0]),
    "the first experts with no rows": (48, 128, 128, 16, 128, [0, 0, 48]),
    "a last tile that is partial": (44, 128, 128, 16, 128, [30, 14]),
    "rows that are no whole 8-row tile": (39, 128, 128, 16, 128, [9, 30]),
    "rows past the last segment": (64, 128, 128, 16, 128, [10, 11, 0]),
    # a held range's pass: a window of a longer walk, filled to two thirds
    "a held range's pass": (96, 128, 256, 32, 256, [0, 23, 41, 0]),
    "no row in any segment": (32, 128, 128, 16, 128, [0, 0]),
    "one row tile larger than the rows": (24, 128, 128, 128, 128, [10, 14]),
    # SmallThinker's 2,560 x 768 and dots3's 5,120 x 1,536 (10 : 3), up
    # and down, and OLMoE's 2,048 x 1,024, cut down to whole lane tiles
    "smallthinker's widths cut down": (80, 1280, 384, 32, 384,
                                       [31, 0, 40, 9]),
    "smallthinker's down projection": (80, 384, 1280, 32, 1280,
                                       [31, 0, 40, 9]),
    "olmoe's widths cut down": (72, 512, 256, 16, 256, [9, 8, 30, 25]),
    # a matrix walked in strips of its columns: the rows read once a strip
    "dots3's widths in two strips": (80, 1280, 384, 32, 128, [50, 30]),
    "dots3's down projection in strips": (80, 384, 1280, 32, 256,
                                          [50, 0, 25]),
}


def _rounded(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_kernel_multiplies_each_experts_rows_by_its_matrix(name):
    """Both operands rounded to bfloat16, the products exact in float32:
    the kernel against `lax.ragged_dot` of the rounded operands at
    `highest` differs by the order of a float32 sum alone; a row past the
    last segment is 0 inside a tile that holds a segment's rows and
    untouched (the interpreter's NaN) in one that holds none; and the walk
    visits no item of an expert without rows."""
    m, k, n, tm, tn, sizes = CASES[name]
    rng = np.random.default_rng(len(name))
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((len(sizes), k, n)), jnp.float32)
    sizes = jnp.asarray(sizes, jnp.int32)
    live = int(sizes.sum())
    # rows past the last segment hold what a TPU leaves there
    x = x.at[live:].set(jnp.nan)
    got, = grouped_matmul(x, w, sizes, tm=tm, tn=tn, interpret=True)
    assert got.shape == (m, n) and got.dtype == x.dtype
    want = lax.ragged_dot(_rounded(x[:live]), _rounded(w), sizes,
                          precision=lax.Precision.HIGHEST)
    got = np.asarray(got)
    scale = max(float(jnp.abs(want).max()), 1.0) if live else 1.0
    assert np.abs(got[:live] - np.asarray(want)).max(initial=0) < 1e-5 * scale
    # against the one bfloat16 pass of XLA's default on a TPU, not the
    # CPU's float32 product: within a rounding of both operands
    exact = lax.ragged_dot(x[:live], w, sizes,
                           precision=lax.Precision.HIGHEST)
    assert np.abs(got[:live] - np.asarray(exact)).max(initial=0) \
        < 2e-2 * scale
    visited = -(-live // tm) * tm if live else 0
    assert not got[live:visited].any()
    assert np.isnan(got[visited:]).all()
    expert, tile, _, following, offsets, count = (
        np.asarray(a) for a in items(sizes, m, tm))
    count = int(count[0])
    hit = [e for e, size in enumerate(np.asarray(sizes)) if size]
    assert sorted(set(expert[:count])) == (hit if count else [])
    assert list(expert[:count]) == sorted(expert[:count])
    assert len(expert) == -(-m // tm) + len(sizes) - 1 >= count
    # an item a tile a segment touches, no more
    assert count == sum(
        (offsets[e + 1] - 1) // tm - offsets[e] // tm + 1 for e in hit)
    assert set(following[:count]) <= set(hit[1:]) | {-1}
    # the items past the last repeat it: no block moves for them
    assert count == 0 or (set(expert[count:]) <= {expert[count - 1]}
                          and set(tile[count:]) <= {tile[count - 1]})


# name -> (tokens, experts a token, D, H, row tile, each expert's rows,
# activation, gated)
FUSED = {
    # 39 rows over tiles of 16: row 9 an edge inside a tile, an expert
    # with no row, a last tile of 7 rows
    "an edge inside a tile, an expert with no row, a partial last tile": (
        13, 3, 128, 256, 16, [9, 0, 30], "silu", True),
    "un-gated, segments that cross tiles": (
        11, 4, 256, 128, 16, [30, 14], "relu", False),
    "several segments inside one tile larger than the rows": (
        13, 3, 128, 128, 128, [3, 5, 0, 7, 17, 7], "relu", True),
}


@pytest.mark.parametrize("name", sorted(FUSED))
def test_the_two_calls_fetch_and_place_their_own_rows(name):
    """Operands of small whole numbers, so that every product and every
    float32 sum is exact whatever its order: `gate_up` — the rows of `x`
    fetched by `token` — against gather + `lax.ragged_dot` x 2 + product
    rounded to bfloat16, TO THE LAST BIT.  `down` against `lax.ragged_dot`
    of that `h` (no whole numbers any more: the two differ by the order
    of a float32 sum), each row times its float32 weight, at ``dest`` —
    every row of the k slabs written; and `slab_sum` — slot 0 first, one
    add a slab, to the last bit; 13 and 11 tokens over steps of 8 —
    against the parent's ``(pairs * top_w).sum(1)`` — XLA's reduction
    over k, in the order it chooses — within float32 rounding of a k-term
    sum.  That `h` stored
    bfloat16 changes no bit of `down`'s result is the layer test's: the
    three-call form rounds the same float32 `h` in the same place."""
    tokens, k, d, h, tm, sizes, act, gated = FUSED[name]
    m = tokens * k
    assert sum(sizes) == m
    rng = np.random.default_rng(len(name))

    def whole(*shape):
        return jnp.asarray(rng.integers(-2, 3, shape), jnp.float32)

    sizes = jnp.asarray(sizes, jnp.int32)
    x, w1, w3 = whole(tokens, d), whole(len(sizes), d, h), whole(len(sizes), d, h)
    w2 = whole(len(sizes), h, d)
    order = jnp.asarray(rng.permutation(m), jnp.int32)   # sorted row -> pair
    token = order // k
    top_w = jnp.asarray(rng.random((tokens, k)), jnp.float32)
    got, = gate_up(x, token, sizes, w1, *([w3] if gated else []), tm=tm,
                   act=act, interpret=True)
    assert got.shape == (m, h) and got.dtype == jnp.bfloat16

    def dot(rows, w):
        return lax.ragged_dot(rows, w, sizes, precision=lax.Precision.HIGHEST)

    want = getattr(jax.nn, act)(dot(x[token], w1))
    if gated:
        want = want * dot(x[token], w3)
    np.testing.assert_array_equal(
        np.asarray(got.astype(jnp.float32)), np.asarray(_rounded(want)))
    slabs, = down(got, w2, sizes, order % k * tokens + token,
                  top_w.reshape(-1)[order], tm=tm, dtype="float32",
                  interpret=True)
    assert slabs.shape == (m, 1, d) and slabs.dtype == jnp.float32
    pairs = dot(_rounded(want), w2)[jnp.argsort(order)].reshape(tokens, k, d)
    weighed = pairs * top_w[:, :, None]
    # (the interpreter hands out NaNs: a row nobody wrote would show)
    scale = float(jnp.abs(weighed).max())
    assert np.abs(np.asarray(slabs[:, 0]) - np.asarray(
        weighed.transpose(1, 0, 2).reshape(m, d))).max() < 1e-6 * scale
    out, = slab_sum(slabs, k=k, tb=8, interpret=True)
    assert out.shape == (tokens, d) and out.dtype == jnp.float32
    assert np.abs(np.asarray(out) - np.asarray(weighed.sum(1))).max() \
        < k * 1e-6 * scale
    by_slot = slabs.reshape(k, tokens, d)
    want = by_slot[0]
    for slot in range(1, k):
        want = want + by_slot[slot]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@contextlib.contextmanager
def _tpu_kernel_interpreted(rows=1, tile=16):
    """Inside, `segment_matmul` takes the branch a lowering for the TPU
    keeps — the Pallas kernel — run by Pallas's interpreter, from `rows`
    rows an expert on and by a row tile of `tile`.  Yields the list of
    kernel branches taken."""
    calls = []

    def take_tpu(*operands, tpu, default):
        calls.append(tpu)
        return tpu(*operands)

    with mock.patch.object(moe.lax, "platform_dependent", take_tpu), \
            mock.patch.object(moe, "_INTERPRET", True), \
            mock.patch.object(moe, "_KERNEL_ROWS", rows), \
            mock.patch.object(moe, "_KERNEL_TILE", tile):
        yield calls


# name -> (tokens, experts a token, experts scored, held range, what is
# patched of `parallel.moe`)
LAYERS = {
    "every expert held": (48, 2, 4, None, {}),
    "a held range, every pair's row gathered": (48, 2, 8, (2, 4), {}),
    "a held range walked in passes": (
        48, 4, 16, (2, 4), {"_ROW_TILE": 8, "_COMPACT_PAIRS": 16}),
    "the tokens in pieces": (48, 2, 4, None, {"_PAIR_BYTES": 32 * 512}),
}


@pytest.mark.parametrize("biased", [False, True])
@pytest.mark.parametrize("name", sorted(LAYERS))
def test_the_layer_through_the_kernel_is_the_parents(name, biased):
    """`dropless_experts` with its three segment matmuls in the kernel
    (forced: the TPU's branch, interpreted, from one row an expert on)
    against the same layer through `lax.ragged_dot`: `load` bit for bit,
    the output and the gradients in `x`, the logits, the three matrices
    and the biases within the one bfloat16 pass the kernel makes where
    the CPU's dot is float32 — the backward IS `lax.ragged_dot`'s, at the
    kernel's forward values.  With no held range and no bias (PR 61) the
    layer is the TWO calls that fetch and place their own rows, a piece:
    one branch a trace, its backward `_every_pair`'s through
    `lax.ragged_dot`, and within float32 rounding of the three-call
    form it replaces (`h` rounded to bfloat16 where `down` would round
    it; a token's k rows summed in the same order)."""
    tokens, k, scored, held, patched = LAYERS[name]
    rng = np.random.default_rng(7)
    d_model, d_expert = 128, 256
    count = scored if held is None else held[1]
    x = jnp.asarray(rng.standard_normal((tokens, d_model)), jnp.float32)
    logits = jnp.asarray(rng.standard_normal((tokens, scored)), jnp.float32)
    weights = tuple(
        jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[1]),
                    jnp.float32)
        for shape in ((count, d_model, d_expert), (count, d_expert, d_model),
                      (count, d_model, d_expert)))
    biases = tuple(
        jnp.asarray(rng.standard_normal(shape), jnp.float32)
        for shape in ((count, d_expert), (count, d_model),
                      (count, d_expert))) if biased else None

    def layer(x, logits, weights, biases):
        return moe.dropless_experts(x, logits, k, weights, biases,
                                    act="silu", gated=True, held=held)

    def both():
        return layer(x, logits, weights, biases), jax.grad(
            lambda *args: (layer(*args)[0] ** 2).sum(), (0, 1, 2, 3))(
                x, logits, weights, biases)

    fused = held is None and not biased
    with contextlib.ExitStack() as stack:
        for attr, value in patched.items():
            stack.enter_context(mock.patch.object(moe, attr, value))
        parent = both()
        with _tpu_kernel_interpreted() as calls:
            kernel = both()
            if fused:
                with mock.patch.object(moe, "_FUSED_ROWS", 0):
                    three = layer(x, logits, weights, biases)[0]
    if fused:
        # the two calls are ONE branch a trace, forward and forward again
        # under the gradient: no segment matmul of its own is left
        assert len(calls) >= 2 and {
            call.func for call in calls[:-3]} == {moe._two_calls}
        assert np.abs(np.asarray(kernel[0][0] - three)).max() \
            < 1e-6 * np.abs(np.asarray(three)).max()
    else:
        # a layer's three matmuls, forward and forward again under the
        # gradient (a loop's body is traced more than once) — and, where
        # a range is walked in passes, each pass's return to token order
        # (PR 63: one more branch a pass's three)
        returns = [call for call in calls
                   if call.__qualname__.startswith("_placed_rows")]
        matmuls = len(calls) - len(returns)
        assert matmuls >= 6 and matmuls % 3 == 0
        assert bool(returns) == ("passes" in name)
    np.testing.assert_array_equal(np.asarray(kernel[0][1]),
                                  np.asarray(parent[0][1]))
    assert 0 < float(parent[0][1].sum()) <= tokens * k
    for a, b in zip(jax.tree_util.tree_leaves((kernel[0][0], kernel[1])),
                    jax.tree_util.tree_leaves((parent[0][0], parent[1]))):
        a, b = np.asarray(a), np.asarray(b)
        assert np.isfinite(a).all()
        assert np.abs(a - b).max() < 2e-2 * np.abs(b).max()


# name -> ((rows, experts, K, N), the tiles or None): the eight routed
# cells' programs (benchmarks/traffic, `pass_plan`)
TILES = {
    "smallthinker 8200, a piece": ((24600, 64, 2560, 768), (128, 768)),
    "smallthinker 9224, down": ((13836, 64, 768, 2560), (128, 2560)),
    "smallthinker step": ((48, 64, 2560, 768), None),
    "dots3 15360, a pass": ((6144, 8, 5120, 1536), (128, 768)),
    "dots3 15360, down": ((6144, 8, 1536, 5120), (128, 2560)),
    "trinity 2056, a pass": ((12800, 64, 2048, 1024), (128, 1024)),
    "granite-h-small 1024, a pass": ((2048, 9, 4096, 768), (128, 768)),
    "granite-h-small 512, a pass": ((1024, 9, 4096, 768), (128, 768)),
    "granite-h-small 256, a pass": ((512, 9, 4096, 768), (128, 768)),
    "granite-h-small step": ((80, 9, 4096, 768), None),
    "mistral-small-4 2048, a pass": ((1536, 16, 4096, 2048), (128, 1024)),
    "mistral-small-4 step": ((64, 16, 4096, 2048), None),
    "qwen3-next 2064, a pass": ((8192, 128, 2048, 512), (128, 512)),
    "glm-5 1032, a pass": ((512, 8, 6144, 2048), (128, 512)),
    "glm-5 1032, down": ((512, 8, 2048, 6144), (128, 2048)),
    "glm-5 step": ((64, 8, 6144, 2048), None),
    "olmoe 520": ((4160, 64, 2048, 1024), (128, 1024)),
    "olmoe 136": ((1088, 64, 2048, 1024), (128, 1024)),
    "olmoe 72": ((576, 64, 2048, 1024), None),
    "olmoe step": ((64, 64, 2048, 1024), None),
    "widths that are no whole lane tile": ((4096, 4, 96, 128), None),
}


# name -> ((rows, experts, D, H, gated), the row tile or None): where the
# layer's calls fetch and place their own rows — `_dropless` asks for a
# call with no held range alone
FUSED_TILES = {
    "fused: smallthinker 8200, a piece": ((24600, 64, 2560, 768, True), 128),
    "fused: smallthinker 10248, a piece": ((20496, 64, 2560, 768, True),
                                           128),
    "fused: smallthinker step": ((48, 64, 2560, 768, True), None),
    "fused: olmoe 520": ((4160, 64, 2048, 1024, True), 128),
    "fused: olmoe 136": ((1088, 64, 2048, 1024, True), 128),
    "fused: olmoe 72": ((576, 64, 2048, 1024, True), None),
    # both matrices of an expert whole, twice over, or the three calls
    "fused: dots3's matrices, too large": ((6144, 8, 5120, 1536, True),
                                           None),
    "fused: dots3's matrices, un-gated": ((6144, 8, 5120, 768, False), 128),
    "fused: more rows than the scalar memory holds": (
        (1 << 17, 64, 128, 128, True), None),
}


@pytest.mark.parametrize("name", sorted({**TILES, **FUSED_TILES}))
def test_the_rule_reads_a_calls_static_shape(name):
    if name in FUSED_TILES:
        shape, tile = FUSED_TILES[name]
        assert moe.fused_tile(*shape) == tile
        return
    shape, tiles = TILES[name]
    assert moe.kernel_tiles(*shape) == tiles


def test_off_the_tpu_the_layer_is_ragged_dot_forward_and_backward():
    """A call the rule takes, traced for the CPU: the platform's branch is
    `lax.ragged_dot`, so output, load and gradients are the parent's bit
    for bit — and a call under the rule is traced with no trace of the
    choice.  The call here is one `fused_tile` takes too (PR 61): what is
    lowered for the CPU is the parent's program — the same operations the
    same number of times, no kernel."""
    rng = np.random.default_rng(11)
    tokens, k, experts, d_model, d_expert = 256, 2, 4, 128, 128
    assert moe.kernel_tiles(tokens * k, experts, d_model, d_expert)
    x = jnp.asarray(rng.standard_normal((tokens, d_model)), jnp.float32)
    logits = jnp.asarray(rng.standard_normal((tokens, experts)), jnp.float32)
    weights = tuple(
        jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[1]),
                    jnp.float32)
        for shape in ((experts, d_model, d_expert),
                      (experts, d_expert, d_model),
                      (experts, d_model, d_expert)))

    def layer(x, logits, weights):
        return moe.dropless_experts(x, logits, k, weights, act="silu",
                                    gated=True)

    def both():
        return layer(x, logits, weights), jax.grad(
            lambda *args: (layer(*args)[0] ** 2).sum(), (0, 1, 2))(
                x, logits, weights)

    def traced():       # (a trace is kept by the function's identity)
        return str(jax.make_jaxpr(lambda *args: layer(*args))(
            x, logits, weights))

    def lowered():
        import collections
        import re

        text = jax.jit(lambda *args: layer(*args)).lower(
            x, logits, weights).as_text()
        assert "custom_call" not in text
        # but for the choice itself: a `case` of one branch, functions
        ops = collections.Counter(re.findall(r'= "?(\w+\.\w+)', text))
        return {op: n for op, n in ops.items() if op not in (
            "func.call", "stablehlo.case", "stablehlo.constant")}

    assert moe.fused_tile(tokens * k, experts, d_model, d_expert, True)
    chosen, program = both(), lowered()
    assert "platform_index" in traced()
    assert program["stablehlo.dot_general"] == 3 \
        and program["stablehlo.gather"] == 2
    with mock.patch.object(moe, "_KERNEL_ROWS", 1 << 30):
        parent = both()
        assert "platform_index" not in traced()
        assert lowered() == program
    for a, b in zip(jax.tree_util.tree_leaves(chosen),
                    jax.tree_util.tree_leaves(parent)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# A held range's return to token order (PR 63), at the seven held cells'
# routings cut to what a CPU runs.  name -> (tokens T, experts a token k,
# experts scored, experts held, D, a pass's rows, tokens a tile, rows a
# chunk, held pairs): the router's draw is adjusted pair by pair until
# exactly `held pairs` of its T x k pairs chose a held expert
RETURNS = {
    # dots3-note: 8 of 256 at 8 a token, 6,144 rows x 5,120 into 15,360;
    # a quarter of the tokens have a live row, a tile of tokens a few
    "dots3: a live row for a quarter of the tokens, D 5,120 cut to 640": (
        240, 8, 64, 2, 640, 96, 16, 16, 61),
    # LongCat-Flash: 8 of 768 at 12 a token, 512 rows x 6,144; the live
    # rows end inside a chunk and inside a tile of tokens
    "longcat-flash: live rows that end inside a chunk, D 6,144 cut to 768": (
        96, 12, 96, 2, 768, 40, 16, 8, 27),
    # GLM-5: 8 of 256 at 8 a token, 512 rows x 6,144; the second pass of
    # two holds ONE live row
    "glm-5: a second pass of one live row, D 6,144 cut to 384": (
        64, 8, 32, 2, 384, 48, 16, 16, 49),
    # Qwen3-Next: 128 of 512 at 10 a token, 7,680 rows x 2,048: a token
    # holds two and three live rows of ONE pass
    "qwen3-next: two and three live rows a token, D 2,048 cut to 256": (
        64, 10, 64, 16, 256, 240, 16, 16, 170),
    # Granite-H-Small: 9 of 72 at 10 a token, 512 rows x 4,096 into the
    # 256 bucket: more rows than tokens, the pass filled to its last row
    "granite-h-small: a pass filled to its last row, D 4,096 cut to 512": (
        32, 10, 24, 3, 512, 64, 8, 16, 64),
    # Trinity-Mini: 64 of 128 at 8 a token; the mixed step's 2,048 + 8
    # tokens are no whole tile
    "trinity-mini: T = 2,056, D 2,048 cut to 128": (
        2056, 2, 64, 2, 128, 192, 128, 32, 150),
    # Mistral-Small-4: 16 of 128 at 4 a token, 1,536 rows x 4,096; a
    # skewed router fills a pass and a third of the next, which adds to
    # tokens the first pass wrote, and rows past the held pairs
    "mistral-small-4: two passes under a skewed router, D 4,096 cut to 512": (
        96, 4, 32, 4, 512, 72, 16, 8, 96),
}


def _routing(rng, tokens, k, scored, held, held_pairs):
    """``[T, k]`` distinct experts a token, exactly `held_pairs` of the
    pairs on an expert below `held`."""
    top_e = np.argsort(rng.random((tokens, scored)), axis=1)[:, :k]
    while (top_e < held).sum() != held_pairs:
        more = (top_e < held).sum() < held_pairs
        t = rng.integers(tokens)
        mine = top_e[t] < held
        free = np.setdiff1d(np.arange(held) if more
                            else np.arange(held, scored), top_e[t])
        if len(free) and (~mine if more else mine).any():
            top_e[t, rng.choice(np.flatnonzero(~mine if more else mine))] \
                = rng.choice(free)
    return top_e


@pytest.mark.parametrize("name", sorted(RETURNS))
def test_a_passs_rows_return_to_their_tokens(name):
    """`row_return` under Pallas's interpreter, pass after pass as
    `_held_passes` calls it — the sorted pairs of a routing, a window of
    `rows` of them a pass, the rows past the held pairs token `T`'s —
    against ``out.at[token].add(ys, mode="drop")``: within a float32
    rounding of a token's few terms (XLA's order is its own), and TO THE
    LAST BIT against the order the kernel states — a token's rows
    ascending by sorted row, which is ascending expert, after what `out`
    held.  `out` starts as noise, not zeros: a tile no row names must come
    back as it went in.  The rows past the held pairs hold NaN here (zeros
    in the layer): they are added nowhere."""
    tokens, k, scored, held, d, rows, tb, tm, held_pairs = RETURNS[name]
    rng = np.random.default_rng(len(name))
    top_e = _routing(rng, tokens, k, scored, held, held_pairs)
    flat_e = np.where(top_e < held, top_e, held).reshape(-1)
    order = np.argsort(flat_e, kind="stable")
    passes = -(-held_pairs // rows)
    order = np.pad(order, (0, max(passes * rows - len(order), 0)))
    out = rng.standard_normal((tokens, d)).astype(np.float32)
    got, want, exact = jnp.asarray(out), jnp.asarray(out), out.copy()
    seen = set()
    for first in range(0, passes * rows, rows):
        live = first + np.arange(rows) < held_pairs
        token = np.where(live, order[first:first + rows] // k,
                         tokens).astype(np.int32)
        ys = rng.standard_normal((rows, d)).astype(np.float32)
        want = want.at[token].add(np.where(live[:, None], ys, 0),
                                  mode="drop")
        for r in np.flatnonzero(live):
            exact[token[r]] += ys[r]
        ys[~live] = np.nan
        got, = row_return(got, jnp.asarray(ys), jnp.asarray(token),
                          jnp.int32(held_pairs - first), tb=tb, tm=tm,
                          interpret=True)
        counts = np.bincount(token[live], minlength=tokens)
        seen |= set(counts)
        # an expert's rows of a pass are of distinct tokens, in token order
        expert = flat_e[order[first:first + rows]][live]
        for e in set(expert):
            mine = token[live][expert == e]
            assert (np.diff(mine) > 0).all()
        if "ends inside" in name:
            assert live.sum() % tm and 0 < counts[-tb:].sum() < live.sum()
    assert got.shape == out.shape and got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), exact)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-6 * max(
        np.abs(exact).max(), 1.0)
    # what the case says it holds
    assert passes == (2 if "pass of" in name or "two passes" in name else 1)
    if "one live row" in name:
        assert held_pairs - rows == 1
    if "two and three" in name:
        assert {2, 3} <= seen
    if "last row" in name:
        assert held_pairs == rows > tokens
    if "2,056" in name:
        assert tokens % tb == 8
    # tokens no pass named came back as they went in
    named = np.zeros(tokens, bool)
    named[order[:held_pairs] // k] = True
    assert (~named).any()
    if "a quarter" in name:
        assert 0.15 < named.mean() < 0.35
    np.testing.assert_array_equal(np.asarray(got)[~named], out[~named])


@pytest.mark.parametrize("shape, tiles", [
    ((15360, 6144, 5120, "float32"), (128, 128)),       # dots3
    ((2056, 512, 6144, "float32"), (128, 128)),         # LongCat, GLM-5
    ((256, 512, 4096, "float32"), (128, 128)),          # Granite-H-Small
    ((2064, 8192, 2048, "float32"), (128, 128)),        # Qwen3-Next
    ((2048, 1536, 4096, "bfloat16"), None),
    ((2048, 1536, 4000, "float32"), None),              # no whole lane tile
    ((64, 1 << 17, 128, "float32"), None),              # the scalar memory
    ((2048, 1536, 16384, "float32"), None),             # the VMEM
])
def test_the_returns_rule_reads_a_passs_static_shape(shape, tiles):
    assert moe.return_tiles(*shape) == tiles


@pytest.mark.parametrize("biased", [False, True])
def test_the_layer_through_the_return_kernel_is_the_parents(biased):
    """`dropless_experts` over a held range in passes, a router skewed
    toward the held experts so that a layer needs TWO passes, with each
    pass's return in the kernel (forced: the TPU's branch of
    `_placed_rows` alone, interpreted; the segment matmuls stay
    `lax.ragged_dot`) against the parent's scatter-add: `load` bit for
    bit; the output within a float32 rounding of a token's few terms (the
    same rows, added ascending expert); the gradients in `x`, the logits,
    the three matrices and the biases within that rounding carried
    through — the backward IS the scatter-add's."""
    tokens, k, scored, held = 48, 4, 16, (2, 4)
    rng = np.random.default_rng(63)
    d_model, d_expert = 128, 256
    x = jnp.asarray(rng.standard_normal((tokens, d_model)), jnp.float32)
    logits = rng.standard_normal((tokens, scored))
    logits[:, held[0]:held[0] + held[1]] += 1.5
    logits = jnp.asarray(logits, jnp.float32)
    weights = tuple(
        jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[1]),
                    jnp.float32)
        for shape in ((held[1], d_model, d_expert),
                      (held[1], d_expert, d_model),
                      (held[1], d_model, d_expert)))
    biases = tuple(
        jnp.asarray(rng.standard_normal(shape), jnp.float32)
        for shape in ((held[1], d_expert), (held[1], d_model),
                      (held[1], d_expert))) if biased else None

    def layer(x, logits, weights, biases):
        return moe.dropless_experts(x, logits, k, weights, biases,
                                    act="silu", gated=True, held=held)

    def both():
        return layer(x, logits, weights, biases), jax.grad(
            lambda *args: (layer(*args)[0] ** 2).sum(), (0, 1, 2, 3))(
                x, logits, weights, biases)

    calls = []

    def the_returns_alone(*operands, tpu, default):
        ours = tpu.__qualname__.startswith("_placed_rows")
        calls.append(ours)
        return (tpu if ours else default)(*operands)

    with mock.patch.object(moe, "_ROW_TILE", 8), \
            mock.patch.object(moe, "_COMPACT_PAIRS", 16):
        rows = moe._pass_rows(tokens * k, held, scored)
        parent = both()
        with mock.patch.object(moe.lax, "platform_dependent",
                               the_returns_alone), \
                mock.patch.object(moe, "_INTERPRET", True), \
                mock.patch.object(moe, "_KERNEL_ROWS", 1), \
                mock.patch.object(moe, "_KERNEL_TILE", 16):
            kernel = both()
    assert any(calls) and not all(calls)
    np.testing.assert_array_equal(np.asarray(kernel[0][1]),
                                  np.asarray(parent[0][1]))
    # two passes a layer: the second adds to what the first wrote
    assert rows < float(parent[0][1].sum()) <= 2 * rows
    for a, b in zip(jax.tree_util.tree_leaves((kernel[0][0], kernel[1])),
                    jax.tree_util.tree_leaves((parent[0][0], parent[1]))):
        a, b = np.asarray(a), np.asarray(b)
        assert np.isfinite(a).all()
        assert np.abs(a - b).max() < 1e-5 * np.abs(b).max()
