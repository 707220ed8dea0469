"""The cell `smallthinker_longctx_c16` rehearsed on the CPU at tiny widths
through the same `measure` the command runs: the REAL BENCHMARK.json's
entries for the cell (so every metric definition it reports is read), the
tiny traffic mix of data/rehearsal/ and a tiny `smallthinker` configuration
— a NoPE full layer over three window layers of 16, groups of seven query
heads, a router on the mixer's input keeping three of eight ReLU-gated
experts; every prompt is past the window, as the real mix's are.  It pins
this cell's own entries, traffic and configuration — nothing about any
other cell; the family's check is held to wrong models at a tiny size in
`tests/test_smallthinker.py`."""
import argparse
import copy
import importlib
import json
import math
import os
import time

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import device, loadgen, spec

REHEARSAL = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "data", "rehearsal")
CELL = "smallthinker_longctx_c16"
CONFIG = "smallthinker-21b-a3b"
DEVICE_ONLY = {"device.idle_share_sat", "device.peak_mem_gb"}
# a tail is read from 300 intervals or not at all (metrics/itl_p99_ms.json)
NEEDS_SAMPLES = {"batcher.itl_p99_ms_sat"}
NEW = {"attn.band_visit_share", "kv.wrapped_share_s", "cache.window_share_s",
       "kv.kernel_share_s"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "rope_layout", "sliding_window_layout"]


def _cell():
    bench = copy.deepcopy(spec.load_benchmark())
    bench["paths"] = ["."]
    conf, = [c for c in bench["configs"] if c["name"] == CONFIG]
    conf["file"] = "configs/smallthinker_tiny.json"
    return spec.Cell(bench, CELL, REHEARSAL)


@pytest.fixture(scope="module")
def results():
    import jax

    # the traced run alone: `test_rehearsal.py` holds every cell's
    # untraced rehearsal, this one's among them, to its end-to-end names
    cell, clock = _cell(), device.CompileClock()
    args = argparse.Namespace(workload=CELL, seed=2**31 + 59, seconds=2.0,
                              trace=1)
    return cell, json.loads(json.dumps(bench_run.measure(
        cell, args, jax.devices()[:1], clock, time.perf_counter())))


def test_the_cell_and_its_traffic_are_the_issues():
    """ISSUE 59's cell, letter for letter."""
    bench = spec.load_benchmark()
    real = spec.Cell(bench, CELL)
    row, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert row["config"] == CONFIG and row["chips"] == 1
    assert row["traffic"] == "longctx_closed_c16" and len(row["why"]) <= 200
    conf, = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert len(conf["why"]) <= 200
    assert conf["file"] == "benchmarks/configs/smallthinker-21b-a3b.json"
    assert conf["source"] == real.config["source"]
    assert conf["reduced"] == REDUCED
    assert real.config["family"] == "smallthinker"
    assert ({m["name"] for m in real.end_to_end}
            == {"gen_tok_per_s", "setup_s"})
    names = {m["name"] for m in real.per_layer}
    assert NEW | {"moe.experts_hit_share", "moe.pairs_per_hit_expert",
                  "prefill.pad_share", "kv.skipped_share_wide",
                  "batcher.fill_sat", "batcher.prefill_share",
                  "batcher.prefill_ms_sat", "batcher.decode_step_ms_sat",
                  "batcher.mixed_share_sat", "attn.kernel_share_sat",
                  "batcher.runahead_share_sat", "batcher.stall_share_sat",
                  "kv.reserved_over_used", "device.decode_ms_sat",
                  "device.prefill_us_per_pos_sat", "device.seen_share_sat",
                  "device.idle_share_sat", "device.peak_mem_gb",
                  "startup.compile_s"} <= names
    # no held range, no recurrent state, no latent ring
    assert not {"moe.rows_per_pair", "moe.held_share", "cache.state_share",
                "kv.wrapped_share", "cache.window_share"} & names
    mine = [m for m in real.per_layer if m["name"] in NEW]
    assert len(mine) == len(NEW) and all(m in bench["per_layer"]
                                         for m in mine)
    for m in real.per_layer:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "gen_tok_per_s"
            assert m["source"] == "program_counter" and m["unit"] == "%"
            assert spec.metric_definition(m["name"])["reader"] == "ratio"
    # the accepted readers under a name of this cell's: one reading each
    assert (spec.metric_definition("kv.wrapped_share_s")
            == spec.metric_definition("kv.wrapped_share"))
    assert (spec.metric_definition("cache.window_share_s")
            == spec.metric_definition("cache.window_share"))
    # whether the step's ring reads took `kv_ring_kernel` (28 query heads
    # along the lanes, seven a K/V head) or the `jax.numpy` body
    assert (spec.metric_definition("kv.kernel_share_s")
            == spec.metric_definition("kv.kernel_share"))
    band = spec.metric_definition("attn.band_visit_share")["args"]
    assert band == {"num": [{"counter": "attn.band_blocks"}],
                    "den": [{"counter": "attn.causal_blocks"}],
                    "scale": 100.0}
    traffic = real.traffic
    assert traffic["job"] == "generate"
    assert traffic["tenant"] == {"max_sessions": 8, "max_len": 10752,
                                 "max_decode_tokens": 512,
                                 "seq_buckets": [7168, 8192, 9216, 10240]}
    assert traffic["arrivals"] == {"process": "closed", "clients": 16}
    assert traffic["requests"]["prompt_len"] == {
        "median": 8192, "sigma": 0.12, "min": 6144, "max": 10240}
    # equal budgets: the issue's fallback (eight seeds of 128-512 spread
    # 2.0% by quartiles, over its 1%), said in the file's `why`
    assert traffic["requests"]["output_len"] == {
        "median": 320, "sigma": 0.3, "min": 320, "max": 320}
    assert "fallback" in traffic["why"] and "2.0%" in traffic["why"]
    assert traffic["trace_seconds"] == 6.0
    # the longest prompt and the longest answer fit a ring; every prompt
    # of a run is longer than the window and no longer than a bucket
    assert 10240 + 512 <= 10752
    window = real.config["sliding_window_size"]
    requests = loadgen.RequestList(traffic["requests"],
                                   real.config["vocab_size"], 2**31 + 59)
    lengths = [len(requests[i].prompt) for i in range(256)]
    assert window < 6144 <= min(lengths) and max(lengths) <= 10240
    assert 1.5 * window <= min(lengths) and max(lengths) <= 2.5 * window


def test_the_configuration_keeps_every_published_number_outside_reduced():
    config = spec.Cell(spec.load_benchmark(), CELL).config
    assert config["reduced"] == REDUCED
    # one whole period: a full layer, then three window layers
    assert config["num_hidden_layers"] == 4
    assert config["rope_layout"] == config["sliding_window_layout"] == [
        0, 1, 1, 1]
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"]) == (
                2560, 28, 4, 128)
    assert (config["moe_num_primary_experts"], config["moe_ffn_hidden_size"],
            config["moe_num_active_primary_experts"]) == (64, 768, 6)
    assert config["vocab_size"] == 151936
    assert config["sliding_window_size"] == 4096
    assert config["rope_theta"] == 1500000
    assert "held_experts" not in config          # 64 held of 64
    assert config["deployment"]["chips_per_layer"] == 1
    assert config["published"]["num_hidden_layers"] == 52
    assert config["published"]["sliding_window_layout"] == [0, 1, 1, 1] * 13
    assert config["published"]["rope_layout"] == [0, 1, 1, 1] * 13
    assert {"depth", "experts", "vocabulary", "not_here"} <= set(
        config["deployment"])
    assert {"dtype", "router_tap", "experts", "attention", "rotary", "norms",
            "layouts", "weights", "not_run"} <= set(config["assumed"])
    assert "ReLU" in config["assumed"]["experts"]
    assert "before the attention runs" in config["assumed"]["router_tap"]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f)
                if r["name"] == "SmallThinker-21BA3B-Instruct"]
    assert config["source"] == row["source_url"]
    changed = [k for k, v in row["config"].items() if config.get(k) != v]
    assert sorted(changed) == sorted(REDUCED)
    for key in ("rope_layout", "sliding_window_layout"):
        assert config[key] == row["config"][key][:4]
        assert config["published"][key] == row["config"][key]


def test_the_cuts_arithmetic_at_the_published_sizes():
    """Parameters a layer, bytes a page and a set (`reduced_why`, PERF.md
    section 4), a step's bytes and a prefill's operations (PERF.md section
    5), pinned."""
    from benchmarks.families import smallthinker as family

    real = spec.Cell(spec.load_benchmark(), CELL)
    config, tenant = real.config, real.traffic["tenant"]
    shapes = family.param_shapes(config)
    count = lambda p, *tails: sum(  # noqa: E731
        math.prod(s) for n, s in shapes.items()
        if n.startswith(p) and n.endswith(tails or ("",)))
    d = 2560
    attention = 4608 * d + d * 3584
    assert attention == 20_971_520
    expert, router = 3 * d * 768, d * 64
    assert (expert, 64 * expert, router) == (5_898_240, 377_487_360, 163_840)
    assert count("l0_") == count("l3_") == (
        attention + 64 * expert + router + 2 * d)
    assert count("l0_") == 398_627_840             # 1.59 GB a layer
    assert count("embed_") == count("head_") == 151936 * d
    total = sum(math.prod(s) for s in shapes.values())
    assert 9.48e9 < 4 * total < 9.50e9             # 9.49 GB of weights
    lm = family.model(config)
    spec_ = lm.cache_spec(1, tenant["max_len"])
    full = 2 * 4 * 4 * 128 * 10752
    window = 2 * 4 * 4 * 128 * 4096
    assert (full, window) == (44_040_192, 16_777_216)
    page = sum(e.nbytes for e in spec_.values())
    assert page == full + 3 * window               # 94.4 MB a slot
    one_set = (tenant["max_sessions"] + 1) * page
    assert 0.849e9 < one_set < 0.850e9
    # the live set and the ONE placeholder set the bucket programs share
    assert 1.69e9 < 2 * one_set < 1.70e9
    # a set a bucket program (four prefill, four decode, the live one:
    # what the tenant bound until PR 59) does not fit beside the weights
    programs = len(tenant["seq_buckets"]) + 4
    assert 4 * total + (1 + programs) * one_set > 16.9e9
    assert round(100.0 * 3 * window / page, 1) == 53.3
    hit = 64 * (1 - (58 / 64) ** 8)
    assert 34.8 < hit < 34.9                       # ~54% of 64 a layer
    step = family.step_bytes(config, 8, [8500] * 8, hit)
    assert step["attention"] == 4 * 4 * attention
    assert step["head"] == 4 * 151936 * d
    assert 3.28e9 < step["experts"] < 3.30e9
    # a full page by blocks of 512 to 8,704, three window rings whole
    assert step["kv"] == 8 * 2 * 4 * 4 * 128 * (8704 + 3 * 4096)
    assert 5.8e9 < sum(step.values()) < 5.95e9     # ~7.2 ms at 819 GB/s
    flops = family.prefill_flops(config, 8192)
    assert 5.2e12 < sum(flops.values()) < 5.35e12
    assert 0.29 < flops["attention"] / sum(flops.values()) < 0.31
    assert family.band_pairs(8192, 4096) == 4096 * 4097 // 2 + 4096 * 4096
    assert family.band_flops(8192, None, 28, 128) == (
        2 * 2 * 28 * 128 * 8192 * 8193 // 2)
    # the kernel's walk at this model's tiling: 128 rows by 1,024 keys
    assert family.band_blocks(8192, 4096, 128, 1024) == 240
    assert family.band_blocks(8192, None, 128, 1024) == 288
    assert family.band_blocks(10240, 4096, 128, 1024) == 320
    assert family.band_blocks(10240, None, 128, 1024) == 440


def test_traced_rehearsal_reports_the_new_metrics(results):
    cell, out = results
    assert out["correct"] is True and out["failed"] == 0
    metrics = out["metrics"]
    listed = {m["name"] for m in cell.per_layer} - DEVICE_ONLY
    assert listed - NEEDS_SAMPLES <= set(metrics) <= listed
    m = {k: v["value"] for k, v in metrics.items()}
    # every prompt is past the window: every row-step reads wrapped rings
    assert m["kv.wrapped_share_s"] == 100.0
    # three window rings of 16 beside a full ring of 52: the spec's split
    assert m["cache.window_share_s"] == pytest.approx(
        100.0 * 3 * 16 / (3 * 16 + 52))
    # the CPU's body computes the whole square under either mask
    assert m["attn.band_visit_share"] == 100.0
    assert m["attn.kernel_share_sat"] == 0
    # no ring goes through the TPU's kernel on the CPU: 0, not nothing
    assert m["kv.kernel_share_s"] == 0.0
    assert 0 < m["moe.experts_hit_share"] <= 100
    assert m["moe.pairs_per_hit_expert"] >= 1
    assert 0.0 <= m["prefill.pad_share"] < 40.0
    assert m["batcher.runahead_share_sat"] > 50
    assert m["batcher.mixed_share_sat"] > 0      # prompts ride the steps
    assert m["kv.reserved_over_used"] > 1.0


def test_the_new_metrics_read_nothing_from_a_program_without_the_counters():
    """A program without this PR's counters (the parent, under any cell's
    traced run): `ratio` finds `attn.band_blocks` nowhere and, with no
    `attn.causal_blocks` either, leaves the metric out; it does not
    raise.  The three `_s` names read counters the parent has."""
    from benchmarks.harness.window import Window

    w = Window()
    w.before = {"counters": {"serving.decode.dispatches": 1}, "histograms": {}}
    w.after = {"counters": {"serving.decode.dispatches": 9,
                            "kv.window_rows": 24, "kv.wrapped_rows": 24,
                            "kv.page_positions": 4096,
                            "kv.kernel_positions": 3072,
                            "cache.reserved_bytes": 4096,
                            "cache.window_bytes": 1024},
               "histograms": {}}

    def read(name):
        definition = spec.metric_definition(name)
        reader = importlib.import_module(
            "benchmarks.readers." + definition["reader"])
        return reader.read(w, **definition["args"])

    assert read("attn.band_visit_share") is None
    assert read("kv.wrapped_share_s") == 100.0
    assert read("cache.window_share_s") == 25.0
    assert read("kv.kernel_share_s") == 75.0
    w.after["counters"]["attn.causal_blocks"] = 8
    assert read("attn.band_visit_share") == 0.0


def test_the_model_is_built_before_a_weight_is_drawn():
    """What the driver's first try of the cell on the parent meets: the
    family builds the model before it draws a weight, so a program that
    lacks `router_input` / `expert_act` fails at once with a TypeError
    that names the argument; the arguments are the configuration's, and
    name no model."""
    import inspect

    from benchmarks.families import smallthinker as family

    config = spec.Cell(spec.load_benchmark(), CELL).config
    args = family.model_args(config)
    assert args["layer_types"] == ["attention"] + ["window_attention"] * 3
    assert args["positions"] == {"window_attention": "rotary"}
    assert (args["num_heads"], args["num_kv_heads"], args["head_dim"],
            args["d_model"]) == (28, 4, 128, 2560)
    assert (args["num_experts"], args["experts_per_token"],
            args["expert_d_ff"]) == (64, 6, 768)
    assert args["route_norm"] is True and "held_experts" not in args
    assert (args["router_input"], args["expert_act"]) == ("mixer", "relu")
    assert (args["sliding_window"], args["rope_theta"]) == (4096, 1500000)
    assert args["tied_head"] is False and args["bias"] is False
    lm = family.model(config)
    assert lm.mixed_symbol(8) is not None
    source = inspect.getsource(family.make_params)
    assert source.index("model(config)") < source.index("jax.random.key")
    assert "fall" not in inspect.getsource(family.model_args)
