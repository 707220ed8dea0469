"""The decode-attention kernel, the prefill's blockwise attention kernel,
the delta rule's prefill and step kernels, the Mamba-2 step kernel and the
expert layer's grouped matmul
compiled for a TPU v5e that is described, not attached (the TPU's
compiler is installed where the tests run): what Pallas's interpreter
cannot see — Mosaic refusing a slice, a layout or the fast memory a
kernel asks for — at the real widths of the benchmark's decoders.
Nothing runs; a compile that passes is not a chip run.  All such compiles live in this ONE file: only one process a
host may hold the TPU's library, and the topology is described inside a
fixture so that every xdist worker collects the same tests."""
import os

import numpy as np
import pytest

import chip_smoke
from mxnet_tpu.ops import attention, gdn, latent, ssm

SLOTS = 9
# (rows, query heads, K/V heads, d_head, ring length, scale[, wraps]);
# Trinity's two: a full layer's ring of the session's 6,144 positions and
# a window layer's of 2,048, which wraps; Qwen3-Next's: 16 rows of 16 query
# heads of 256 — a head over two 128-line tiles — on 2 K/V heads
SHAPES = {"opt": (8, 32, 32, 64, 768, None),
          "olmoe": (8, 16, 16, 128, 768, None),
          "granite": (8, 32, 8, 64, 2304, 1 / 64),
          "olmo_hybrid": (8, 30, 30, 128, 2304, None),
          "one_row": (1, 32, 32, 64, 768, None),
          "trinity_full": (8, 32, 4, 128, 6144, None),
          "trinity_window": (8, 32, 4, 128, 2048, None, True),
          "qwen3_next": (16, 16, 2, 256, 4096, None)}
# positions and K/V heads a block, by the ring's bytes alone
BLOCKS = {"opt": (128, 32), "olmoe": (128, 16), "granite": (384, 8),
          "olmo_hybrid": (128, 15), "one_row": (128, 32),
          "trinity_full": (512, 4), "trinity_window": (512, 4),
          "qwen3_next": (512, 2)}


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no log files
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    # a compile for a described chip can be written to the persistent
    # cache but not read back: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_the_decode_attention_compiles_for_a_v5e(name, one_chip):
    """Lowered for the TPU, `_decode_attention` is the kernel: ONE
    `tpu_custom_call`, both rings aliased to its outputs, no copy of a
    ring, and the rings in the layout `cache_spec`'s shape has by
    default."""
    import jax
    import jax.numpy as jnp

    rows, h_q, h_kv, d_head, max_len, scale, *wraps = SHAPES[name]
    ring = (SLOTS, h_kv, d_head, max_len)
    block = attention.decode_block(ring, "tpu")
    heads = attention.decode_heads(ring)
    assert (block, heads) == BLOCKS[name]

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(*operands):
        return attention._decode_attention(*operands, block=block,
                                           heads=heads, scale=scale,
                                           interpret=False, wraps=bool(wraps))

    compiled = jax.jit(step, donate_argnums=(3, 4)).lower(
        arg((rows, h_q, d_head)), arg((rows, h_kv, d_head)),
        arg((rows, h_kv, d_head)), arg(ring), arg(ring),
        arg((rows,), jnp.int32), arg((rows,), jnp.int32)).compile()
    facts = chip_smoke.ring_hlo_facts(compiled.as_text(), ring)
    assert facts["kernel_calls"] == 1
    assert facts["ring_params"] == facts["aliased"] == 2
    assert facts["copies"] == []
    assert facts["layouts"] == ["{3,2,1,0:T(8,128)}"]
    # nothing but the rings and the small operands: no ring-sized scratch
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes < np.prod(ring[1:]) * 4


# (rows, ring length): Mistral-Small-4's cell and its stated fallback
LATENT = {"mistral4": (16, 6144, 768), "mistral4_fallback": (16, 4096, 512),
          "one_row": (1, 6144, 768)}


@pytest.mark.parametrize("name", sorted(LATENT))
def test_the_latent_decode_compiles_for_a_v5e(name, one_chip):
    """Lowered for the TPU, `_latent_decode` is the latent kernel — 32
    heads against ONE page of 320 lines by two matrix products, the
    contraction 320 wide, not a multiple of 128 —: ONE `tpu_custom_call`,
    the ring aliased to its output, no copy of it, and the ring in the
    layout `cache_spec`'s shape has by default (a change of layout would
    be a copy of 33 MB a row-step)."""
    import jax
    import jax.numpy as jnp

    rows, ring_len, want = LATENT[name]
    ring = (17, 1, 320, ring_len)
    block = attention.decode_block(ring, "tpu", latent=True)
    assert block == want

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(*operands):
        return latent._latent_decode(*operands, rank=256, scale=0.195,
                                     block=block, interpret=False)

    compiled = jax.jit(step, donate_argnums=(2,)).lower(
        arg((rows, 32, 320)), arg((rows, 320)), arg(ring),
        arg((rows,), jnp.int32), arg((rows,), jnp.int32)).compile()
    facts = chip_smoke.ring_hlo_facts(compiled.as_text(), ring)
    assert facts["kernel_calls"] == 1
    assert facts["ring_params"] == facts["aliased"] == 1
    assert facts["copies"] == []
    assert facts["layouts"] == ["{3,2,1,0:T(8,128)}"]
    # nothing but the ring and the small operands: no page-sized scratch
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes < np.prod(ring[1:]) * 4


@pytest.mark.parametrize("rows, on_the_mxu", [(1, 0), (16, 1)])
def test_a_one_row_product_is_not_the_mxus(rows, on_the_mxu, one_chip):
    """Why a check of a decoder's precision goes through the decode
    program of as many rows as the window runs (PR 40, second session):
    XLA compiles a float32 product of ONE row as a multiply-and-reduce
    fusion in float32, and from two rows on as a convolution at the
    default precision, one bfloat16 pass — the one-row program reads a
    third of the 16-row one's error against a float32 reference."""
    import re

    import jax
    import jax.numpy as jnp

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    text = jax.jit(lambda x, w: jnp.tanh(x @ w.T)).lower(
        arg((rows, 2048)), arg((8192, 2048))).compile().as_text()
    assert len(re.findall(r"= \S+ convolution\(", text)) == on_the_mxu


# (tokens, k, experts scored, experts held, d_model, d_expert) -> the row
# tile the grouped matmul should walk: a mixed step's bucket + rows tokens
# at the cells' widths (OLMoE's four programs; Qwen3-Next's and Trinity's
# longest, a quarter and a half of their experts held; Granite-H-Small's 512
# bucket and its 8-row decode step, nine of 72 held)
PAIR_TILES = {"olmoe 64": ((72, 8, 64, 64, 2048, 1024), 64),
              "olmoe 128": ((136, 8, 64, 64, 2048, 1024), 64),
              "olmoe 256": ((264, 8, 64, 64, 2048, 1024), 512),
              "olmoe 512": ((520, 8, 64, 64, 2048, 1024), 512),
              "qwen3-next 2048": ((2064, 10, 512, 128, 2048, 512), 512),
              "trinity 2048": ((2056, 8, 128, 64, 2048, 1024), 512),
              "granite-h-small 512": ((512, 10, 72, 9, 4096, 768), 512),
              "granite-h-small step": ((8, 10, 72, 9, 4096, 768), 16),
              "smallthinker 8192": ((8200, 6, 64, 64, 2560, 768), 512),
              "smallthinker step": ((8, 6, 64, 64, 2560, 768), 16)}
# the rows a pass of the held pairs walks (PR 55); 0: every pair's row
PASS_ROWS = {"qwen3-next 2048": 8192, "trinity 2048": 12800,
             "granite-h-small 512": 1024}
# the cases whose segment matmuls are the TPU's kernel (PR 60,
# `moe.kernel_tiles`: from `_KERNEL_ROWS` sorted rows an expert held)
KERNEL_CASES = {"olmoe 128", "olmoe 256", "olmoe 512", "qwen3-next 2048",
                "trinity 2048", "granite-h-small 512", "smallthinker 8192"}
# of them, the cases whose kernel calls fetch and place their own rows (PR
# 61, `moe.fused_tile`: no held range, an expert's matrices held whole)
FUSED_CASES = {"olmoe 128", "olmoe 256", "olmoe 512", "smallthinker 8192"}


def _ragged_dots(text):
    """(rows, row tile) of every compiled `ragged-dot` in `text`."""
    import re

    return re.findall(
        r"= f32\[(\d+),\d+\]\S* custom-call\([^)]*\)[^\n]*?"
        r'ragged_dot_tiling="(\d+),', text)


@pytest.mark.parametrize("name", sorted(PAIR_TILES))
def test_the_grouped_matmul_walks_the_tile_the_pairs_were_gathered_for(
        name, one_chip):
    """Under `moe._KERNEL_ROWS` rows an expert (PR 60) the layer is the
    parent's: `parallel/moe.py _spare_rows` leans on a choice XLA documents
    nowhere: the TPU's `ragged-dot` walks its rows by the largest power of
    two, up to 512, that divides their count.  Read back from the compiled
    program — the dot's `ragged_dot_tiling` and its metadata operand of
    tiles + groups - 1 entries — so that a compiler that chooses otherwise
    fails here and not silently on the chip: two dozen pairs an expert or
    more walk whole 512-row tiles, fewer keep the parent's count and its
    small tile.  From `_KERNEL_ROWS` on the three segment matmuls of a
    call are OUR kernel (`ops/grouped_matmul_kernel.py`: three
    `tpu_custom_call`s, no `ragged-dot`, the rows there ARE — no pad to a
    multiple of 512 — and the fast memory the kernel asks for granted).  A
    held range's long call (PR 55) walks passes of its HELD
    pairs — whole 512-row tiles of them, inside the one loop of
    `_held_passes` — and no array of every pair's row is left; without a
    held range, and in a decode step, the layer is the straight line it
    was: no loop, no conditional — but the one loop over the pieces of a
    bucket whose pair rows pass `_PAIR_BYTES` (SmallThinker's 8,192: two
    pieces of 24,600 rows).  With no held range (PR 61, `moe.fused_tile`)
    the kernel's calls are TWO a piece — `grouped_gate_up_kernel`, which
    fetches its rows of `x`, and `grouped_down_kernel`, which puts each
    weighted row where its token's sum reads it — and no array of every
    pair's row is left in the program at all: no gather, scatter or copy
    of ``[pairs, D]``; the k slabs the second call writes are rows of ONE
    sublane, ``[pairs, 1, D]``, which a third, small call
    (`grouped_slab_sum_kernel`) adds slab on slab into whole tiles, so
    that the loop over the pieces carries its result in the layout the
    parent's does.  A decode step and a held range's call hold none of
    the three.  A held range's pass (PR 63, `moe.return_tiles`) returns
    its rows to their tokens in ONE more call, `row_return_kernel`, inside
    the loop, the result aliased to the loop's carry: no `scatter(` over
    ``[T, D]`` is left, and no call without a pass holds the kernel."""
    import re

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_matmul_kernel
    from mxnet_tpu.parallel import moe

    (tokens, k, scored, held, d_model, d_expert), tile = PAIR_TILES[name]
    held_range = None if held == scored else (0, held)

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def layer(x, logits, *weights):
        return moe.dropless_experts(
            x, logits, k, weights, act="silu", gated=True, held=held_range)

    text = jax.jit(layer).lower(
        arg(tokens, d_model), arg(tokens, scored),
        arg(held, d_model, d_expert), arg(held, d_expert, d_model),
        arg(held, d_model, d_expert)).compile().as_text()
    pieces, passed = moe.pass_plan(tokens, k, 4 * d_model, held_range,
                                   scored)
    assert passed == PASS_ROWS.get(name, 0)
    pairs = tokens // pieces * k
    loops = len(re.findall(r" (?:while|conditional)\(", text))
    every_pair = re.findall(r"f32\[%d,%d\]" % (pairs, d_model), text)
    fused = held_range is None and moe.fused_tile(
        pairs, held, d_model, d_expert, True) is not None
    assert fused == (name in FUSED_CASES)
    if passed or fused:
        assert loops == (2 if passed else pieces > 1) and every_pair == []
    else:
        assert loops == (pieces > 1) and every_pair
    dots = _ragged_dots(text)
    tiles = moe.kernel_tiles(passed or pairs, held, d_model, d_expert)
    assert (tiles is not None) == (name in KERNEL_CASES)
    calls = [chip_smoke.named_kernel_calls(text, kernel) for kernel in (
        "grouped_matmul_kernel", "grouped_gate_up_kernel",
        "grouped_down_kernel", "grouped_slab_sum_kernel")]
    assert calls == ([0, 1, 1, 1] if fused else [3, 0, 0, 0] if tiles
                     else [0, 0, 0, 0])
    placed = bool(passed) and moe.return_tiles(
        tokens // pieces, passed, d_model, "float32") is not None
    assert placed == bool(passed)       # every pass of every cell's
    assert chip_smoke.named_kernel_calls(text, "row_return_kernel") == placed
    assert not re.search(r"f32\[%d,%d\]\S* scatter\(" % (
        tokens // pieces, d_model), text) or not passed
    if placed:
        call, = [line for line in text.splitlines()
                 if "row_return_kernel" in line and " custom-call(" in line]
        assert "output_to_operand_aliasing={{}: (6, {})}" in call
        # a row a DMA can slice: `ys` one sublane a row
        assert re.search(r"f32\[%d,1,%d\]\{2,1,0:T\(1,128\)" % (
            passed, d_model), text)
    if fused:
        assert dots == [] and "ragged" not in text
        # the rows are fetched from `x` and written into the slabs where
        # they lie: both one sublane a row
        assert re.search(r"f32\[%d,1,%d\]\S* custom-call\(" % (pairs, d_model),
                         text)
        assert re.search(r"bf16\[%d,%d\]\S* custom-call\(" % (pairs, d_expert),
                         text)
        assert not re.search(r" (?:gather|scatter)\([^\n]*f32\[%d," % pairs,
                             text)
        return
    if tiles:
        assert dots == [] and "ragged" not in text
        # the rows there are, not a whole number of 512-row tiles
        spare = moe._spare_rows(pairs, scored)
        assert passed or not spare or not re.search(
            r"f32\[%d,\d+\]" % (pairs + spare), text)
        # both of a matrix's slots, its rounded copy and the pipeline's
        # tiles within what a v5e's 128 MiB of fast memory can give
        for n_in, n_out in ((d_model, d_expert), (d_expert, d_model)):
            tm, tn = moe.kernel_tiles(passed or pairs, held, n_in, n_out)
            assert grouped_matmul_kernel._vmem(tm, tn, n_in, 4) < 96 << 20
        return
    rows = passed or pairs + moe._spare_rows(pairs, scored)
    assert rows % tile == 0 and (tile == 512 or rows == tokens * k)
    assert len(dots) == 3, name
    assert set(dots) == {(str(rows), str(tile))}
    entries = set(re.findall(r"%ragged-dot-metadata = \(s32\[\d+\]\S*, "
                             r"s32\[(\d+)\]", text))
    assert entries == {str(rows // tile + held - 1)}


# (key heads, value heads, d_k, d_v, heads a step of the kernel's walk)
GDN_WIDTHS = {"olmo_hybrid": (30, 30, 96, 192, 6),
              "qwen3_next": (16, 32, 128, 128, 8)}


@pytest.mark.parametrize("bucket", [768, 2048])
@pytest.mark.parametrize("widths", sorted(GDN_WIDTHS))
def test_the_delta_rule_prefill_compiles_for_a_v5e(widths, bucket, one_chip):
    """`_gdn_prefill` at Olmo-Hybrid's widths (30 heads of 96 x 192, chunks
    of 64, the cell's shortest and longest bucket) and at Qwen3-Next's (16
    q/k heads under 32 value heads of 128 x 128, q and k at their 16 heads)
    lowered for the TPU: ONE `tpu_custom_call` — the kernel, walking six
    or eight heads at a time — no triangular solve, no array split into
    heads between the conv and the kernel's ``y`` (XLA's L2 norms over
    ``(…, H, 96)``, the repeat of q and k to ``(N, T, H, d_k)``, the gated
    norm over ``(…, H, 192)``: the kernel's since PR 51), the window and
    the state aliased to their outputs, and nothing the size of the
    operands kept beside them."""
    import jax
    import jax.numpy as jnp

    hk, h, dk, dv, walk = GDN_WIDTHS[widths]
    taps, slots = 4, 9
    conv_dim = gdn.conv_channels(h, dk, dv, hk)
    attrs = dict(num_heads=h, key_dim=dk, value_dim=dv, conv_kernel=taps,
                 chunk_size=64, neg_eigval=hk == h, eps=1e-6)
    if hk != h:
        attrs["num_key_heads"] = hk
    assert gdn.chunk_heads((1, bucket, h, dk), dv, 64, "tpu", hk) == walk

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def prefill(*operands):
        return gdn.gdn_prefill(*operands, **attrs)

    gdn._delta_rule.clear_cache()
    compiled = jax.jit(prefill, donate_argnums=(5, 6)).lower(
        arg(1, bucket, conv_dim + h * dv + 2 * h), arg(taps, conv_dim),
        arg(h), arg(h), arg(dv), arg(slots, taps - 1, conv_dim),
        arg(slots, dk, h * dv), arg(1), arg(1)).compile()
    text = compiled.as_text()
    assert chip_smoke.delta_rule_hlo_facts(
        text, [(hk, dk), (h, dk), (h, dv)]) == {
            "solves": 0, "kernel_calls": 1, "head_arrays": []}
    state = chip_smoke.ring_hlo_facts(text, (slots, dk, h * dv))
    assert state["ring_params"] == state["aliased"] == 1
    assert state["copies"] == []


def _serving_program(graph, wire, one_chip):
    """The serving `graph` compiled for the described chip as a session
    compiles it: `wire` ({name: shape}: the call's inputs and the cache
    entries) donated AS A TUPLE IN ITS ORDER — JAX gives a donated buffer
    to the first output of its shape, so the entries have to come in the
    order the graph returns them, as `GenerativeSession._launch` passes
    them; a dict would be sorted by name, and of two layers' rings of one
    shape each would be given the other's output and copied — every
    other argument a weight."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.executor import _run_graph
    from mxnet_tpu.symbol import _topo_order

    order = _topo_order(graph._entries)
    names = graph.list_arguments()
    wired = [n for n in wire if n in names]

    def program(state, weights):
        vals = {**dict(zip(wired, state)), **weights}
        outs, _ = _run_graph(graph._entries, order, names, [],
                             tuple(vals[n] for n in names), (), False,
                             jax.random.key(0))
        return outs

    shapes, _, _ = graph.infer_shape(**wire)
    args = {n: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for n, s in zip(names, shapes)}
    return jax.jit(program, donate_argnums=(0,)).lower(
        tuple(args[n] for n in wired),
        {n: a for n, a in args.items() if n not in wire}).compile()


def _wire(spec, rows, prompt=None):
    """The inputs of a serving graph in a session's wire order: a decode
    step of `rows` rows, or — `prompt` positions — the mixed step of that
    bucket with `rows` riders."""
    slots = next(iter(spec.values())).shape[0]
    if prompt is None:
        small = dict(data=(rows, 1), slot=(rows,), length=(rows,))
    else:
        small = dict(data=(1, prompt), slot=(1,), length=(1,),
                     row_data=(rows, 1), row_slot=(rows,),
                     row_length=(rows,))
    return dict(small, **{n: e.shape for n, e in spec.items()},
                last_token=(slots,))


def _smoke_model(index):
    """`chip_smoke.py`'s kv_ring model `index` at its full size."""
    from mxnet_tpu.models import TransformerLM

    sizes = chip_smoke.FULL["kv_ring"]
    shape = {k: v for k, v in sizes["shapes"][index].items()
             if k not in ("seq_buckets", "max_sessions")}
    return TransformerLM(**{**{k: sizes[k] for k in (
        "vocab", "num_layers", "d_model", "d_ff")}, **shape})


# name -> (chip_smoke.py's kv_ring model, rows, heads a grid step)
STEP_MODELS = {"olmo_hybrid": (3, 8, 10), "qwen3_next": (5, 16, 16)}


@pytest.mark.parametrize("widths", sorted(STEP_MODELS))
def test_the_delta_rule_step_compiles_for_a_v5e(widths, one_chip):
    """The decode program of `chip_smoke.py`'s two delta-rule models — a
    delta-rule layer at Olmo-Hybrid's widths (30 heads of 96 x 192, 8
    rows) and at Qwen3-Next's (16 q/k heads under 32 value heads of 128 x
    128, 16 rows), each beside an attention layer — lowered for the TPU:
    ONE step-kernel call beside the ring's, no array of ``rows x d_k x H
    d_v`` elements (the keys spread out to a page's size for all rows,
    PR 40's 33.5 MB an operand a layer; or the rows' pages gathered), and
    the state aliased to its output and never copied.  The whole program,
    not the op alone: a program with nothing else in it has the chip's
    128 MiB of VMEM to spare, and XLA stages the whole 36 MB state there
    and back around the kernel."""
    import warnings

    index, rows, heads = STEP_MODELS[widths]
    lm = _smoke_model(index)
    spec = lm.cache_spec(rows + 1)
    state = spec["gdn_state_0"].shape
    assert gdn.step_heads(
        state, lm.kind_specs["linear_attention"]["value_dim"], "tpu") == heads
    gdn._state_step.clear_cache()
    with warnings.catch_warnings():   # the small inputs are not donated
        warnings.simplefilter("ignore")
        text = _serving_program(lm.decode_symbol(), _wire(spec, rows),
                                one_chip).as_text()
    assert chip_smoke.delta_step_hlo_facts(text, rows, state) == {
        "kernel_calls": 1, "row_pages": []}
    facts = chip_smoke.ring_hlo_facts(text, state)
    assert facts["kernel_calls"] == 2      # the ring's and the step's
    assert facts["ring_params"] == facts["aliased"] == 1
    assert facts["copies"] == []


# name -> (chip_smoke.py's kv_ring model, rows, heads a grid step)
MAMBA_STEP_MODELS = {"granite_h_small": (8, 8, 32),
                     "granite_h_micro": (9, 8, 32)}


@pytest.mark.parametrize("widths", sorted(MAMBA_STEP_MODELS))
def test_the_mamba2_step_compiles_for_a_v5e(widths, one_chip):
    """The 8-row decode program of `chip_smoke.py`'s two Mamba-2 models —
    a Mamba-2 layer at granite-4.0-h-small's state (128 heads of 64 x 128)
    and at granite-4.0-h-micro's (64 heads), each beside an attention
    layer — lowered for the TPU: ONE step-kernel call beside the ring's,
    no array of ``rows x H x P x S`` elements (the rows' pages gathered),
    and the state aliased to its output and never copied — neither within
    HBM nor staged whole through the compiler's fast memory, as XLA did
    with four of nine layers' buffers around the ``jax.numpy`` form (PR
    54).  The whole program, not the op alone (PR 41: XLA stages a whole
    state around a lone kernel)."""
    import warnings

    index, rows, heads = MAMBA_STEP_MODELS[widths]
    lm = _smoke_model(index)
    spec = lm.cache_spec(rows + 1)
    state = spec["ssm_state_0"].shape
    assert ssm.step_heads(state, "tpu") == heads
    ssm._state_step.clear_cache()
    with warnings.catch_warnings():   # the small inputs are not donated
        warnings.simplefilter("ignore")
        text = _serving_program(lm.decode_symbol(), _wire(spec, rows),
                                one_chip).as_text()
    assert chip_smoke.ssm_step_hlo_facts(text, rows, state) == {
        "kernel_calls": 1, "row_pages": [], "copies": []}
    facts = chip_smoke.ring_hlo_facts(text, state)
    assert facts["kernel_calls"] == 2      # the ring's and the step's
    assert facts["ring_params"] == facts["aliased"] == 1


def test_the_latent_decode_program_compiles_for_a_v5e(one_chip):
    """The whole 16-row decode program of `chip_smoke.py`'s seventh model
    — two latent-attention layers at Mistral-Small-4's widths, 32 heads
    over ONE ring of 320 x 6,144 each — lowered for the TPU: ONE kernel
    call a layer, both latent rings aliased to their outputs in the layout
    `cache_spec` states, no copy or change of layout of a ring, and no
    per-head K or V of the page's length anywhere (`f32[...,32,...,6144]`
    would be the absorbed form undone)."""
    import re
    import warnings

    rows = 16
    lm = _smoke_model(6)
    spec = lm.cache_spec(rows + 1)
    ring = spec["latent_cache_0"].shape
    assert ring == (17, 1, 320, 6144) and len(spec) == 2
    assert lm.mixed_symbol(rows) is None   # it keeps its two programs
    with warnings.catch_warnings():   # the small inputs are not donated
        warnings.simplefilter("ignore")
        text = _serving_program(lm.decode_symbol(), _wire(spec, rows),
                                one_chip).as_text()
    facts = chip_smoke.ring_hlo_facts(text, ring)
    assert facts["kernel_calls"] == 2
    assert facts["ring_params"] == facts["aliased"] == 2
    assert facts["copies"] == []
    assert facts["layouts"] == ["{3,2,1,0:T(8,128)}"]
    assert not re.search(r"f32\[[\d,]*\b32,[\d,]*6144\]", text)


# name -> (chip_smoke.py's kv_ring model, riders, the prompt's bucket, the
# kernel calls a mixed step of it holds: the riders' ring kernel an
# attention layer — and, at a bucket `prefill_block` takes, the prompt's
# blockwise kernel —, and a delta-rule layer's chunked AND step kernel)
MIXED_MODELS = {"opt": (0, 8, 64, 2), "olmo_hybrid": (3, 8, 2048, 2 + 2),
                "trinity": (4, 8, 2048, 4), "qwen3_next": (5, 16, 2048, 2 + 2)}


@pytest.mark.parametrize("name", sorted(MIXED_MODELS))
def test_a_mixed_step_compiles_for_a_v5e(name, one_chip):
    """The mixed step of `chip_smoke.py`'s models — a prompt's bucket AND
    the slots' rows in one program — lowered for the TPU: the riders' ring
    kernel once an attention layer and a delta-rule layer's two kernels,
    every ring and state a parameter aliased to its output and never
    copied, and of ring-shaped `dynamic-update-slice`s only the prompt's
    block into K and into V (a rider's row is written inside the kernel)."""
    import re
    import warnings

    index, rows, bucket, kernels = MIXED_MODELS[name]
    lm = _smoke_model(index)
    spec = lm.cache_spec(rows + 1)
    gdn._delta_rule.clear_cache()
    gdn._state_step.clear_cache()
    with warnings.catch_warnings():   # the small inputs are not donated
        warnings.simplefilter("ignore")
        text = _serving_program(lm.mixed_symbol(rows),
                                _wire(spec, rows, prompt=bucket),
                                one_chip).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == kernels
    if "linear_attention" in lm.layer_types:
        # nor anything of the prompt's scan split into heads: the norms
        # and the repeat are the chunked kernel's (PR 51)
        assert chip_smoke.delta_rule_hlo_facts(
            text, chip_smoke.delta_head_shapes(lm))["head_arrays"] == []
    floor = sum(e.nbytes for e in spec.values()) // 100
    entry = text[text.index("ENTRY"):]
    for shape in sorted({e.shape for e in spec.values()
                         if e.nbytes >= floor}):
        count = sum(e.shape == shape for e in spec.values())
        facts = chip_smoke.ring_hlo_facts(text, shape)
        assert facts["ring_params"] == facts["aliased"] == count, shape
        assert facts["copies"] == [], shape
        dims = re.escape(",".join(str(d) for d in shape))
        writes = len(re.findall(
            r"= f32\[%s\]\S* dynamic-update-slice\(" % dims, entry))
        rings = sum(e.shape == shape and e.kind == "ring"
                    for e in spec.values())
        assert writes <= rings, (shape, writes)


# (query heads, K/V heads, d_head, window, scale): every caller of
# `_sdp_attention` in a cell
PREFILL_SHAPES = {"opt": (32, 32, 64, None, None),
                  "olmoe": (16, 16, 128, None, None),
                  "olmo_hybrid": (30, 30, 128, None, None),
                  "granite": (32, 8, 64, None, 1 / 64),
                  "trinity_full": (32, 4, 128, None, None),
                  "trinity_window": (32, 4, 128, 2048, None),
                  "qwen3_next": (16, 2, 256, None, None),
                  "mistral4_latent": (32, 32, 128, None, 0.195)}


@pytest.mark.parametrize("name", sorted(PREFILL_SHAPES))
def test_the_prefill_attention_compiles_for_a_v5e(name, one_chip):
    """`_sdp_attention` of a 2,048-bucket lowered for the TPU is the
    blockwise kernel: ONE `tpu_custom_call` under the scope
    `mx:attn.prefill`, bfloat16 operands, and no array of bucket x bucket
    anywhere — what Mosaic makes of a head of 64, of a group of eight
    heads a step, of a head of 256 and of a window."""
    import jax
    import jax.numpy as jnp

    h, kv, dh, window, scale = PREFILL_SHAPES[name]
    t = 2048
    attrs = dict(num_heads=h, scale=scale, window=window)
    if kv != h:
        attrs["num_kv_heads"] = kv
    assert attention.prefill_block((1, t, h * dh), h, kv, "tpu") is not None

    def arg(width):
        return jax.ShapeDtypeStruct((1, t, width), jnp.float32,
                                    sharding=one_chip)

    attention._prefill_attention.clear_cache()
    text = jax.jit(
        lambda q, k, v: attention.sdp_attention(q, k, v, **attrs)).lower(
            arg(h * dh), arg(kv * dh), arg(kv * dh)).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and "sdp_causal_attention" in calls[0]
    assert "mx:attn.prefill" in calls[0]
    assert "bf16[1,%d,%d,%d,%d]" % (kv, h // kv, t, dh) in calls[0]
    assert chip_smoke.score_arrays(text, t) == []


# name -> (chip_smoke.py's kv_ring model, riders, attention layers):
# a delta-rule layer beside 30 heads of 128 (Olmo-Hybrid's), and two
# layers of 32 query heads on 8 K/V heads of 64 (Granite's attention)
PREFILL_MODELS = {"olmo_hybrid": (3, 8, 1), "granite": (2, 8, 2)}


@pytest.mark.parametrize("form", ["prefill", "mixed"])
@pytest.mark.parametrize("name", sorted(PREFILL_MODELS))
def test_a_long_prefill_holds_no_scores(name, form, one_chip):
    """The 2,048-bucket prefill program and the mixed step in its place,
    lowered for the TPU: the blockwise kernel once an attention layer and
    NO array whose last two dimensions are both the bucket — the `(H, T,
    T)` scores, 503 MB a layer of 30 heads, that XLA's form writes and
    reads about six times."""
    import warnings

    index, rows, layers = PREFILL_MODELS[name]
    bucket = 2048
    lm = _smoke_model(index)
    spec = lm.cache_spec(rows + 1)
    booked = lm.call_counters(positions=bucket, platform="tpu")
    assert booked["attn.kernel_positions"] == layers * bucket
    assert booked["attn.prefill_positions"] == layers * bucket
    gdn._delta_rule.clear_cache()
    gdn._state_step.clear_cache()
    attention._prefill_attention.clear_cache()
    wire = _wire(spec, rows, prompt=bucket)
    if form == "mixed":
        graph = lm.mixed_symbol(rows)
    else:
        graph = lm.prefill_symbol()
        wire = {n: s for n, s in wire.items() if not n.startswith("row_")}
    with warnings.catch_warnings():   # the small inputs are not donated
        warnings.simplefilter("ignore")
        text = _serving_program(graph, wire, one_chip).as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sum("sdp_causal_attention" in c for c in calls) == layers
    assert chip_smoke.score_arrays(text, bucket) == []


OPT_BUCKETS = [64, 128, 256, 512]  # benchmarks/traffic/gen_closed_c16.json


@pytest.fixture(scope="module", params=["prefill", "mixed"])
def opt_prefill_cycles(request, one_chip):
    """OPT-1.3B's prefill graph, and the mixed step that takes its place
    in a session (the benchmark's configuration: twelve layers at the
    published widths, nine pages of 768, eight riders) jitted for the
    described v5e at each of the cell's buckets: {bucket: (the sum of
    the compiler's own `estimated_cycles` over the program's ops, the
    largest single one)}."""
    import json
    import re
    import warnings

    from benchmarks.families import opt

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "opt-1.3b.json")) as f:
        lm = opt.model(json.load(f))
    mixed = request.param == "mixed"
    graph = lm.mixed_symbol(SLOTS - 1) if mixed else lm.prefill_symbol()
    spec = lm.cache_spec(SLOTS, 768)
    cycles = {}
    for t in OPT_BUCKETS:
        wire = _wire(spec, SLOTS - 1, prompt=t)
        with warnings.catch_warnings():   # the small inputs are not donated
            warnings.simplefilter("ignore")
            text = _serving_program(graph, wire, one_chip).as_text()
        found = [int(c) for c in re.findall(
            r'"estimated_cycles":"(\d+)"', text[text.index("ENTRY"):])]
        cycles[t] = (sum(found), max(found))
    return cycles


@pytest.mark.parametrize("bucket,larger", list(zip(OPT_BUCKETS,
                                                   OPT_BUCKETS[1:])))
def test_opts_prefill_programs_grow_with_their_bucket(bucket, larger,
                                                      opt_prefill_cycles):
    """By the TPU compiler's own estimate no prefill bucket of OPT-1.3B
    costs more than 1.5 times the next larger one, and no single fusion
    of it more than the larger program's costliest: at the parent of
    PR 36 the 256 bucket read 30.2 M cycles beside 8.7 M and 13.7 M —
    three FFN fusions of 7.5 M each, tiled 512 ways with their first
    matmul recomputed — and ran 21.2 ms on the chip beside 3.9 and 5.7
    (PERF.md section 6, PR 36).  An estimate is not a time:
    `chip_smoke.py` kv_ring holds the same rule on the chip."""
    total, worst = opt_prefill_cycles[bucket]
    total_larger, worst_larger = opt_prefill_cycles[larger]
    assert total <= 1.5 * total_larger, opt_prefill_cycles
    assert worst <= 1.5 * worst_larger, opt_prefill_cycles


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_the_dots3_programs_compile_for_a_v5e(program, one_chip):
    """dots3-note-prev's two serving programs at the cell's sizes — the
    published widths, five layers, eight held experts, four slots of
    16,384 positions, the ONE bucket of 15,360 — lowered for the TPU:
    every cache entry (latent rings, index keys, window rings) aliased to
    its output and never copied; the decode step gathers 2,048 rows and
    keeps no array of a page's positions by heads; the prefill's
    temporaries fit beside the weights and five bound cache sets (its Q,
    K and V exist a group of heads at a time, its experts' pairs a piece
    of the tokens at a time, and no ``(heads, T, T)`` score at all), and
    its two full layers' masked attention is the blockwise kernel
    (``ops/masked_latent_kernel.py``): no array of a run's scores."""
    import json
    import re
    import warnings

    from benchmarks.families import dots3 as family

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "configs",
            "dots3-note-prev.json")) as f:
        config = json.load(f)
    lm = family.model(config)
    rows, bucket = 4, 15360
    spec = lm.cache_spec(rows + 1, 16384)
    wire = _wire(spec, rows)
    if program == "prefill":
        wire = dict(wire, data=(1, bucket), slot=(1,), length=(1,))
    graph = (lm.decode_symbol() if program == "decode"
             else lm.prefill_symbol())
    with warnings.catch_warnings():   # the small inputs are not donated
        warnings.simplefilter("ignore")
        compiled = _serving_program(graph, wire, one_chip)
    text, stats = compiled.as_text(), compiled.memory_analysis()
    for shape in sorted({e.shape for e in spec.values()}):
        count = sum(e.shape == shape for e in spec.values())
        facts = chip_smoke.ring_hlo_facts(text, shape)
        assert facts["ring_params"] == facts["aliased"] == count, shape
        assert facts["copies"] == [], shape
    # XLA's own ragged-dot calls and, in the prefill, ONE masked kernel a
    # full layer (PR 49: its call sits in the scan over the groups of
    # heads); no other Pallas kernel of this repo
    masked = chip_smoke.named_kernel_calls(text, "masked_latent_attention")
    assert masked == (2 if program == "prefill" else 0)
    # and, since PR 60, the prefill's segment matmuls (a pass of 6,144
    # sorted rows over eight experts), three a routed layer
    grouped = chip_smoke.named_kernel_calls(text, "grouped_matmul_kernel")
    assert grouped == (3 * sum(
        kind == "routed" for kind in lm.ffn_types)
        if program == "prefill" else 0)
    # and (PR 63) the ONE pass a routed layer returns its 6,144 rows to
    # their tokens by the kernel's row copies: no scatter over the 315 MB
    # ``[15360, 5120]``, XLA's 28 ms a layer
    placed = chip_smoke.named_kernel_calls(text, "row_return_kernel")
    assert placed == grouped // 3
    assert not re.search(r"f32\[15360,5120\]\S* scatter\(", text)
    assert text.count('custom_call_target="tpu_custom_call"') \
        == text.count('op_name="ragged-dot') + masked + grouped + placed
    sets = sum(e.nbytes for e in spec.values())
    assert stats.alias_size_in_bytes >= sets
    weights = stats.argument_size_in_bytes - sets
    assert 7.2e9 < weights < 7.4e9
    if program == "decode":
        assert stats.temp_size_in_bytes < 0.3e9
        # the selected rows, not the page, meet the heads
        assert re.search(r"f32\[4,576,2048\]", text)
        assert not re.search(r"f32\[(4,)?128,16384\]", text)
    else:
        # a v5e's 16.9e9 bytes hold the weights, five sets, the program
        assert weights + 5 * sets + stats.temp_size_in_bytes < 16.5e9
        assert not re.search(r"f32\[(\d+,)?(128|64|16|8),15360,15360\]", text)
        # nor a group of heads' scores of a block of queries against a
        # run's keys, which XLA's form wrote to HBM and read back
        assert chip_smoke.run_score_arrays(text, bucket) == []
        assert not re.search(r"f32\[122880,5120\]", text)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_the_glm5_drafting_programs_compile_for_a_v5e(program, one_chip):
    """GLM-5's two serving programs WITH ITS DRAFT MODULE at the cell's
    sizes — the published widths, five layers and the MTP block, eight
    held experts, four slots of 3,200 positions, the ONE bucket of 1,024 —
    lowered for the TPU: the drafting decode step runs two positions a
    session (eight rows), and every cache entry — the module's own latent
    ring and index keys among them — is aliased to its output and never
    copied, as is ``last_token (3, slots + 1)``; the weights are the
    13.17 GB the configuration's `reduced_why` reckons, and weights, five
    bound cache sets and the larger program's temporaries fit a v5e.  The
    prefill's routed layers — the module's own among them — walk ONE pass
    of 512 sorted rows each and return it by the kernel's row copies
    (PR 63: the program whose split scatter-add hung the chip in PR 57
    holds no scatter over ``f32[1024, 6144]``)."""
    import json
    import re
    import warnings

    from benchmarks.families import glm5 as family

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "configs",
            "glm-5.json")) as f:
        config = json.load(f)
    lm = family.model(config)
    rows, bucket, max_len = 4, 1024, 3200
    spec = lm.cache_spec(rows + 1, max_len)
    assert len(spec) == 2 * (config["num_hidden_layers"] + 1)
    wire = dict(_wire(spec, rows), last_token=lm.token_state(rows + 1))
    if program == "prefill":
        wire = dict(wire, data=(1, bucket), slot=(1,), length=(1,))
    graph = (lm.decode_symbol() if program == "decode"
             else lm.prefill_symbol())
    with warnings.catch_warnings():   # the small inputs are not donated
        warnings.simplefilter("ignore")
        compiled = _serving_program(graph, wire, one_chip)
    text, stats = compiled.as_text(), compiled.memory_analysis()
    for shape in sorted({e.shape for e in spec.values()}):
        count = sum(e.shape == shape for e in spec.values())
        facts = chip_smoke.ring_hlo_facts(text, shape)
        assert facts["ring_params"] == facts["aliased"] == count, shape
        assert facts["copies"] == [], shape
    sets = sum(e.nbytes for e in spec.values())
    assert stats.alias_size_in_bytes >= sets
    weights = stats.argument_size_in_bytes - sets
    assert 13.1e9 < weights < 13.25e9
    placed = chip_smoke.named_kernel_calls(text, "row_return_kernel")
    grouped = chip_smoke.named_kernel_calls(text, "grouped_matmul_kernel")
    assert placed == grouped // 3 and bool(placed) == (program == "prefill")
    assert not re.search(r"f32\[%d,6144\]\S* scatter\(" % bucket, text)
    assert lm.expert_plan(bucket)[2:] == (512, True, False, True)
    # a v5e's 16.9e9 bytes hold the weights, five sets, the program
    assert weights + 5 * sets + stats.temp_size_in_bytes < 16.5e9, (
        weights, sets, stats.temp_size_in_bytes)
    if program == "decode":
        assert stats.temp_size_in_bytes < 0.3e9


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_the_granite_h_small_programs_compile_for_a_v5e(program, one_chip):
    """granite-4.0-h-small's two serving programs at the cell's sizes —
    the published widths, ten layers (nine Mamba-2 of 128 heads x 64 with
    128 states, one attention), nine held experts of 72 under every mixer,
    eight slots of 1,536 positions, the 8-row step and the 1,024 bucket —
    lowered for the TPU: every cache entry (conv windows, Mamba states,
    the attention layer's two rings) is aliased to its output and none is
    copied within HBM; the step advances each Mamba-2 layer's state in ONE
    kernel call (``ops/ssm_step_kernel.py``, PR 58) and stages no state
    buffer in the compiler's fast memory (XLA did, four of nine layers'
    37.7 MB around the ``jax.numpy`` form, one write-out each: 75 MB a
    layer where the eight rows' pages read and written in place are 67,
    PERF.md section 6, PR 54), at most the 0.9 MB windows; the weights
    are the 8.22 GB the configuration's `reduced_why` reckons; weights,
    the tenant's nine bound cache sets and the larger program's
    temporaries fit a v5e.  The expert layers (PR 55): the step gathers
    its 80 pairs' rows as it did; the 1,024 bucket walks ONE pass's 2,048
    sorted rows of its held pairs by the 512-row tile and keeps no array
    of all 10,240 pairs' rows of 4,096, and (PR 63) returns the pass's
    rows to their tokens in ONE `row_return_kernel` call a routed layer:
    no `scatter(` over ``f32[1024, 4096]``."""
    import json
    import re
    import warnings

    from benchmarks.families import granite_moe_hybrid as family

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "configs",
            "granite-4.0-h-small.json")) as f:
        config = json.load(f)
    lm = family.model(config)
    assert lm.mixed_symbol(8) is None      # two programs: Mamba-2
    rows, bucket, max_len = 8, 1024, 1536
    spec = lm.cache_spec(rows + 1, max_len)
    assert len(spec) == 2 * config["num_hidden_layers"]
    wire = _wire(spec, rows)
    if program == "prefill":
        wire = dict(wire, data=(1, bucket), slot=(1,), length=(1,))
    graph = (lm.decode_symbol() if program == "decode"
             else lm.prefill_symbol())
    with warnings.catch_warnings():   # the small inputs are not donated
        warnings.simplefilter("ignore")
        compiled = _serving_program(graph, wire, one_chip)
    text, stats = compiled.as_text(), compiled.memory_analysis()
    windows = {e.shape for n, e in spec.items() if n.startswith("conv_")}
    state, = {e.shape for n, e in spec.items() if n.startswith("ssm_state")}
    assert len(windows) == 1
    for shape in sorted({e.shape for e in spec.values()}):
        count = sum(e.shape == shape for e in spec.values())
        facts = chip_smoke.ring_hlo_facts(text, shape)
        assert facts["ring_params"] == facts["aliased"] == count, shape
        if shape in windows and program == "decode":
            # the step's compiler may advance a layer's window (0.9 MB,
            # XLA's on every platform) in its fast memory, S(1), and write
            # it out once; no buffer is copied within HBM
            assert all("S(1)" in line for line in facts["copies"]), shape
            assert len(facts["copies"]) <= count, shape
        else:
            assert facts["copies"] == [], shape
    layers = config["layer_types"].count("mamba")
    stepped = chip_smoke.ssm_step_hlo_facts(text, rows, state)
    assert stepped == {"kernel_calls": layers * (program == "decode"),
                       "row_pages": [], "copies": []}
    sets = sum(e.nbytes for e in spec.values())
    assert 0.46e9 < sets < 0.4625e9        # nine pages of 51.2 MB
    assert stats.alias_size_in_bytes >= sets
    weights = stats.argument_size_in_bytes - sets
    assert 8.21e9 < weights < 8.23e9
    # a v5e's 16.9e9 bytes hold the weights, nine sets, the program
    assert weights + 9 * sets + stats.temp_size_in_bytes < 16.5e9, (
        weights, sets, stats.temp_size_in_bytes)
    dots = set(_ragged_dots(text))
    routed = config["num_hidden_layers"]
    grouped = chip_smoke.named_kernel_calls(text, "grouped_matmul_kernel")
    if program == "decode":
        assert stats.temp_size_in_bytes < 0.3e9
        assert dots == {("80", "16")} and grouped == 0
    else:
        # a pass of 2,048 sorted rows over nine experts: ours (PR 60)
        assert dots == set() and grouped == 3 * routed
        assert not re.search(r"f32\[10240,4096\]", text)
    placed = chip_smoke.named_kernel_calls(text, "row_return_kernel")
    assert placed == routed * (program == "prefill")
    assert not re.search(r"f32\[%d,4096\]\S* scatter\(" % bucket, text)
    assert lm.expert_plan(bucket)[5] and not lm.expert_plan(rows)[5]


SMALLTHINKER_BUCKETS = (7168, 8192, 9216, 10240)


@pytest.mark.parametrize("program", ["decode", "mixed"])
def test_the_smallthinker_programs_compile_for_a_v5e(program, one_chip):
    """SmallThinker-21BA3B's two serving programs at the cell's sizes — the
    published widths, four layers (a full layer and three window layers of
    4,096), 64 experts held of 64 under a router on the mixer's input,
    eight slots of 10,752 positions, the 8-row step and the mixed step of
    the 10,240 bucket (a prompt 2.5 windows long beside eight riders) —
    lowered for the TPU: every ring — the full layer's of 10,752 positions,
    the window layers' of 4,096, SHORTER than the bucket — is aliased to
    its output and none is copied within HBM; the prompt's attention is one
    blockwise kernel call a layer at groups of SEVEN query heads, and
    `attn.kernel_positions` equals `attn.prefill_positions` at every bucket
    of the cell (`prefill_block` takes them all); the weights are the
    9.49 GB the configuration's `reduced_why` reckons; weights, the
    tenant's two bound cache sets (the live one and the placeholder set its
    bucket programs share since PR 59: with a set a program, nine, the
    tenant died binding on the chip) and the larger program's temporaries
    fit a v5e."""
    import json
    import re
    import warnings

    from benchmarks.families import smallthinker as family

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "configs",
            "smallthinker-21b-a3b.json")) as f:
        config = json.load(f)
    lm = family.model(config)
    rows, bucket, max_len = 8, SMALLTHINKER_BUCKETS[-1], 10752
    spec = lm.cache_spec(rows + 1, max_len)
    shapes = sorted({e.shape for e in spec.values()})
    assert shapes == [(9, 4, 128, 4096), (9, 4, 128, 10752)]
    for t in SMALLTHINKER_BUCKETS:
        booked = lm.call_counters(positions=t, platform="tpu")
        assert booked["attn.kernel_positions"] == booked[
            "attn.prefill_positions"] == 4 * t
    if program == "decode":
        graph, wire = lm.decode_symbol(), _wire(spec, rows)
    else:
        graph, wire = lm.mixed_symbol(rows), _wire(spec, rows, bucket)
    with warnings.catch_warnings():   # the small inputs are not donated
        warnings.simplefilter("ignore")
        compiled = _serving_program(graph, wire, one_chip)
    text, stats = compiled.as_text(), compiled.memory_analysis()
    for shape in shapes:
        count = sum(e.shape == shape for e in spec.values())
        facts = chip_smoke.ring_hlo_facts(text, shape)
        assert facts["ring_params"] == facts["aliased"] == count, shape
        assert facts["copies"] == [], shape
    # the rows' ring kernel a layer and, in the mixed step, the prompt's
    # blockwise kernel a layer
    assert chip_smoke.named_kernel_calls(text, "kv_ring_attention") == 4
    assert chip_smoke.named_kernel_calls(text, "sdp_causal_attention") == (
        4 * (program == "mixed"))
    # and the mixed step's expert layers are OUR grouped matmul (PR 60:
    # three pieces of 20,496 sorted rows over 64 experts), since PR 61 as
    # the two calls that fetch and place their own rows and the slabs'
    # sum — and no array of a piece's every pair's row, gathered,
    # scattered or copied —, the step's 48 pairs a layer `lax.ragged_dot`
    assert [chip_smoke.named_kernel_calls(text, kernel) for kernel in (
        "grouped_matmul_kernel", "grouped_gate_up_kernel",
        "grouped_down_kernel", "grouped_slab_sum_kernel")] == [
            0, *[4 * (program == "mixed")] * 3]
    # no range is held: no pass, and no call of the passes' return (PR 63)
    assert chip_smoke.named_kernel_calls(text, "row_return_kernel") == 0
    assert text.count('custom_call_target="tpu_custom_call"') == (
        4 + 4 * 4 if program == "mixed"
        else 4 + text.count('op_name="ragged-dot'))
    assert ("ragged" in text) == (program == "decode")
    assert not re.search(r"f32\[20496,2560\]", text)
    sets = sum(e.nbytes for e in spec.values())
    assert 0.849e9 < sets < 0.850e9        # nine pages of 94.4 MB
    assert stats.alias_size_in_bytes >= sets
    weights = stats.argument_size_in_bytes - sets
    assert 9.48e9 < weights < 9.50e9
    # a v5e's 16.9e9 bytes hold the weights, two sets, the program; the
    # nine sets of a set a bucket program (four prefill buckets, four
    # decode buckets, the live one) would not fit beside the weights alone
    assert weights + 2 * sets + stats.temp_size_in_bytes < 13.5e9, (
        weights, sets, stats.temp_size_in_bytes)
    assert weights + 9 * sets > 16.9e9
    if program == "decode":
        assert stats.temp_size_in_bytes < 0.3e9
    else:
        assert not chip_smoke.score_arrays(text, bucket)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_the_longcat_flash_programs_compile_for_a_v5e(program, one_chip):
    """LongCat-Flash-Omni's two serving programs at the cell's sizes — the
    published widths, four published layers (eight here: two latent
    sublayers, two dense FFNs of 12,288 and the carried routed layer a
    published one), eight held heads and eight held experts of a 768-wide
    router, eight slots of 2,304 positions, the 8-row step and the 2,048
    bucket — lowered for the TPU: all eight latent rings (two a published
    layer) are aliased to their outputs and none is copied; the step reads
    each ring through ONE latent-ring kernel call; the 2,048 bucket — the
    one `ops.attention.prefill_block` sends through the blockwise kernel,
    a head 192 wide with the value carried at that width — holds ONE
    kernel call a sublayer and no ``(heads, 2048, 2048)`` score; its
    experts walk ONE pass of 512 sorted rows of the 24,576 pairs (the
    zero-compute experts' pairs gather no row); the weights are the 13.69
    GB the configuration's `reduced_why` reckons, and weights, two bound
    cache sets and the larger program's temporaries fit a v5e."""
    import json
    import re
    import warnings

    from benchmarks.families import longcat_flash as family
    from mxnet_tpu.ops import attention

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "configs",
            "longcat-flash-omni.json")) as f:
        config = json.load(f)
    lm = family.model(config)
    rows, bucket, max_len = 8, 2048, 2304
    spec = lm.cache_spec(rows + 1, max_len)
    assert list(spec) == ["latent_cache_%d" % i for i in range(8)]
    assert {e.shape for e in spec.values()} == {(9, 1, 576, 2304)}
    assert attention.prefill_block((1, bucket, 8 * 192), 8, 8, "tpu")
    assert lm.expert_plan(bucket)[1:4] == (1, 512, True)
    assert lm.expert_plan(rows)[1:4] == (1, 0, False)
    wire = _wire(spec, rows)
    if program == "prefill":
        wire = dict(wire, data=(1, bucket), slot=(1,), length=(1,))
    graph = (lm.decode_symbol() if program == "decode"
             else lm.prefill_symbol())
    with warnings.catch_warnings():   # the small inputs are not donated
        warnings.simplefilter("ignore")
        compiled = _serving_program(graph, wire, one_chip)
    text, stats = compiled.as_text(), compiled.memory_analysis()
    facts = chip_smoke.ring_hlo_facts(text, (9, 1, 576, 2304))
    assert facts["ring_params"] == facts["aliased"] == 8
    assert facts["copies"] == []
    ring = chip_smoke.named_kernel_calls(text, "latent_ring_attention")
    sdp = chip_smoke.named_kernel_calls(text, "causal_attention")
    grouped = chip_smoke.named_kernel_calls(text, "grouped_matmul_kernel")
    assert (ring, sdp) == ((8, 0) if program == "decode" else (0, 8))
    assert grouped == (0 if program == "decode" else 3 * 4)
    # a pass of 512 rows a carried routed layer, returned by the kernel
    assert chip_smoke.named_kernel_calls(text, "row_return_kernel") == (
        0 if program == "decode" else 4)
    assert lm.expert_plan(bucket)[5] and not lm.expert_plan(rows)[5]
    assert "mx:moe.shortcut" in text and "mx:moe.zero" in text
    sets = sum(e.nbytes for e in spec.values())
    assert stats.alias_size_in_bytes >= sets
    weights = stats.argument_size_in_bytes - sets
    assert 13.6e9 < weights < 13.8e9
    assert weights + 2 * sets + stats.temp_size_in_bytes < 16.2e9, (
        weights, sets, stats.temp_size_in_bytes)
    if program == "decode":
        assert stats.temp_size_in_bytes < 0.3e9
    else:
        assert not re.search(r"f32\[(1,)?8,(1,)?2048,2048\]", text)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_the_nemotron_h_programs_compile_for_a_v5e(program, one_chip):
    """NVIDIA-Nemotron-3-Nano-30B-A3B's two serving programs at the cell's
    sizes (PR 64) — the published widths, thirteen layers of ONE sublayer
    each (`MEMEM*EMEMEM*`: six Mamba-2 of 64 heads x 64 with 128 states in
    EIGHT groups, five of 32 held of 128 ungated squared-ReLU experts of
    1,856 beside a shared 3,712, two NoPE attention layers of 32 heads over
    2), eight slots of 8,704 positions, the 8-row step and the 8,192 bucket
    — lowered for the TPU: a layer books a cache entry only for the half it
    has (six windows, six states, four rings) and each is aliased to its
    output, none copied within HBM; the step advances each Mamba-2 layer's
    state in ONE kernel call at 32 heads — four whole groups — a grid step
    (`ssm.step_heads`) and each attention layer's rings in one; the
    two-matrix experts run under ``mx:moe.ungated``; the model is built at
    the PUBLISHED 1,856 and the layer stores its stacks 1,920 wide
    (`transformer_lm.stored_width`: as published the step COPIES each
    layer's 639 MB `up` stack, 0.68 GB of temporaries — no copy of a stack
    is in either program): TWO segment matmuls a routed layer, not
    three — XLA's ragged-dot over the step's 48 pairs, `grouped_matmul_kernel`
    over the bucket's ONE pass of 18,432 sorted rows, whose rows of 2,688
    return through `row_return_kernel`; the weights are the 8.83 GB the
    configuration's `reduced_why` reckons and fit a v5e with the tenant's
    bound sets and the prefill's temporaries."""
    import json
    import re
    import warnings

    from benchmarks.families import nemotron_h as family

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "configs",
            "nemotron-3-nano-30b-a3b.json")) as f:
        config = json.load(f)
    lm = family.model(config)
    assert lm.mixed_symbol(8) is None      # two programs: Mamba-2
    pattern = config["hybrid_override_pattern"]
    rows, bucket, max_len = 8, 8192, 8704
    spec = lm.cache_spec(rows + 1, max_len)
    assert len(spec) == 2 * (pattern.count("M") + pattern.count("*"))
    wire = _wire(spec, rows)
    if program == "prefill":
        wire = dict(wire, data=(1, bucket), slot=(1,), length=(1,))
    graph = (lm.decode_symbol() if program == "decode"
             else lm.prefill_symbol())
    ssm._state_step.clear_cache()
    with warnings.catch_warnings():   # the small inputs are not donated
        warnings.simplefilter("ignore")
        compiled = _serving_program(graph, wire, one_chip)
    text, stats = compiled.as_text(), compiled.memory_analysis()
    windows = {e.shape for n, e in spec.items() if n.startswith("conv_")}
    state, = {e.shape for n, e in spec.items() if n.startswith("ssm_state")}
    assert state == (9, 64, 64, 128) and windows == {(9, 3, 6144)}
    assert ssm.step_heads(state, "tpu", config["n_groups"]) == 32
    for shape in sorted({e.shape for e in spec.values()}):
        count = sum(e.shape == shape for e in spec.values())
        facts = chip_smoke.ring_hlo_facts(text, shape)
        assert facts["ring_params"] == facts["aliased"] == count, shape
        if shape in windows and program == "decode":
            assert all("S(1)" in line for line in facts["copies"]), shape
        else:
            assert facts["copies"] == [], shape
    stepped = chip_smoke.ssm_step_hlo_facts(text, rows, state)
    assert stepped == {
        "kernel_calls": pattern.count("M") * (program == "decode"),
        "row_pages": [], "copies": []}
    assert "mx:moe.ungated/mx:moe.experts" in text
    # built at the published width, stored in whole lane tiles: no program
    # copies an expert stack (at 1,856 as it is the step copies `up`)
    assert (lm.expert_d_ff, config["moe_intermediate_size"]) == (1856, 1856)
    stacks = {s for n, s in zip(graph.list_arguments(), graph.infer_shape(
        **wire)[0]) if n.endswith(("_up_weight", "_down_weight"))
        and "shared" not in n}
    assert stacks == {(32, 2688, 1920), (32, 1920, 2688)}
    assert not re.search(r"f32\[32,(2688,\d+|\d+,2688)\]\S* copy\(", text)
    sets = sum(e.nbytes for e in spec.values())
    assert 0.437e9 < sets < 0.439e9        # nine pages of 48.7 MB
    assert stats.alias_size_in_bytes >= sets
    weights = stats.argument_size_in_bytes - sets
    assert 8.82e9 < weights < 8.85e9
    routed = pattern.count("E")
    dots = _ragged_dots(text)
    grouped = chip_smoke.named_kernel_calls(text, "grouped_matmul_kernel")
    placed = chip_smoke.named_kernel_calls(text, "row_return_kernel")
    assert lm.expert_plan(bucket) == (49152, 1, 18432, True, False, True)
    if program == "decode":
        assert stats.temp_size_in_bytes < 0.3e9
        assert set(dots) == {("48", "16")} and len(dots) == 2 * routed
        assert placed == grouped == 0
    else:
        assert dots == [] and grouped == 2 * routed
        assert placed == routed
        assert not re.search(r"f32\[49152,2688\]", text)
        assert not re.search(r"f32\[%d,2688\]\S* scatter\(" % bucket, text)
    print("nemotron_h %s: weights %.3f GB, a set %.3f GB, temporaries "
          "%.3f GB" % (program, weights / 1e9, sets / 1e9,
                       stats.temp_size_in_bytes / 1e9))
    # a v5e's 16.9e9 bytes hold the weights, the tenant's bound sets (the
    # live one and one a bucket program) and the program
    assert weights + 6 * sets + stats.temp_size_in_bytes < 15.75e9, (
        weights, sets, stats.temp_size_in_bytes)
