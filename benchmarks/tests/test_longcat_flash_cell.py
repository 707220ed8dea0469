"""The cell `longcatflash_turns_c16` rehearsed on the CPU at tiny widths
through the same `measure` the command runs: the REAL BENCHMARK.json's
entries for the cell (so every metric definition it reports is read), the
tiny traffic mix of data/rehearsal/ and a tiny `longcat_flash`
configuration — two published layers as four, a carried routed layer of
four held of 16 real experts and 8 zero-compute ones, two latent rings a
published layer.  It pins this cell's own entries, traffic, configuration
and arithmetic — nothing about any other cell; the family's check is held
to wrong models at a tiny size in `tests/test_longcat_flash.py`."""
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
CELL = "longcatflash_turns_c16"
CONFIG = "longcat-flash-omni"
DEVICE_ONLY = {"device.idle_share_sat", "device.peak_mem_gb"}
# a tail is read from 300 intervals or not at all (metrics/itl_p99_ms.json)
NEEDS_SAMPLES = {"batcher.itl_p99_ms_sat"}
NEW = {"moe.zero_share": "%", "moe.held_share_l": "%",
       "cache.latent_share_l": "%", "mla.ring_mb_step_l": "MB",
       "mla.kernel_share_l": "%"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_layers", "num_attention_heads", "n_routed_experts",
           "vocab_size"]


def _cell():
    bench = copy.deepcopy(spec.load_benchmark())
    bench["paths"] = ["."]
    conf, = [c for c in bench["configs"] if c["name"] == CONFIG]
    conf["file"] = "configs/longcat_flash_tiny.json"
    return spec.Cell(bench, CELL, REHEARSAL)


@pytest.fixture(scope="module")
def results():
    import jax

    # the traced run alone: `test_rehearsal.py` holds every cell's
    # untraced rehearsal, this one's among them, to its end-to-end names
    cell, clock = _cell(), device.CompileClock()
    args = argparse.Namespace(workload=CELL, seed=2**31 + 62, seconds=2.0,
                              trace=1)
    return cell, json.loads(json.dumps(bench_run.measure(
        cell, args, jax.devices()[:1], clock, time.perf_counter())))


def test_the_cell_and_its_traffic_are_the_issues():
    """ISSUE 62's cell, letter for letter."""
    bench = spec.load_benchmark()
    real = spec.Cell(bench, CELL)
    row, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert row["config"] == CONFIG and row["chips"] == 1
    assert row["traffic"] == "turns_closed_c16" and len(row["why"]) <= 200
    assert "not expert load" in row["why"] and "8x share" in row["why"]
    assert bench["workloads"][-1] is row and len(bench["workloads"]) == 14
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    conf, = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert len(conf["why"]) <= 200
    assert conf["file"] == "benchmarks/configs/longcat-flash-omni.json"
    assert conf["source"] == real.config["source"]
    assert conf["reduced"] == REDUCED
    assert real.config["family"] == "longcat_flash"
    assert ({m["name"] for m in real.end_to_end}
            == {"gen_tok_per_s", "setup_s"})
    names = {m["name"] for m in real.per_layer}
    assert set(NEW) | {
        "moe.experts_hit_share", "moe.pairs_per_hit_expert",
        "moe.rows_per_pair", "moe.kernel_share", "prefill.pad_share",
        "kv.skipped_share_wide", "batcher.fill_sat", "batcher.prefill_share",
        "batcher.prefill_ms_sat", "batcher.decode_step_ms_sat",
        "batcher.mixed_share_sat", "attn.kernel_share_sat",
        "batcher.runahead_share_sat", "batcher.stall_share_sat",
        "kv.reserved_over_used", "device.decode_ms_sat",
        "device.prefill_us_per_pos_sat", "device.seen_share_sat",
        "device.idle_share_sat", "device.peak_mem_gb",
        "startup.compile_s"} <= names
    # no other configuration's share under its suffix, no window, no
    # recurrent state, no draft
    assert not {n for n in names if n.startswith(("moe.", "mla.", "cache."))
                and n.endswith(("_m", "_g", "_d", "_s", "_q", "_h"))}
    assert not {"cache.state_share", "kv.wrapped_share",
                "mtp.accept_share", "moe.fused_share"} & names
    mine = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert mine == bench["per_layer"][-len(NEW):]     # appended, at the end
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "gen_tok_per_s"
        assert m["source"] == "program_counter"
        assert m["unit"] == NEW[m["name"]]
        assert spec.metric_definition(m["name"])["reader"] == "ratio"
    assert {m["name"]: m["layer"] for m in mine} == {
        "moe.zero_share": "expert layer", "moe.held_share_l": "expert layer",
        "cache.latent_share_l": "session state",
        "mla.ring_mb_step_l": "session state",
        "mla.kernel_share_l": "device"}
    # the accepted readers under a name of this cell's: one reading each
    for name, accepted in (("moe.held_share_l", "moe.held_share_g"),
                           ("cache.latent_share_l", "cache.latent_share_g"),
                           ("mla.ring_mb_step_l", "mla.ring_mb_step_g"),
                           ("mla.kernel_share_l", "mla.kernel_share_d")):
        assert (spec.metric_definition(name)
                == spec.metric_definition(accepted))
    assert spec.metric_definition("moe.zero_share")["args"] == {
        "num": [{"counter": "moe.zero_pairs"}],
        "den": [{"counter": "moe.routed_pairs"}], "scale": 100.0}
    traffic = real.traffic
    assert traffic["job"] == "generate"
    assert traffic["tenant"] == {"max_sessions": 8, "max_len": 2304,
                                 "max_decode_tokens": 256,
                                 "seq_buckets": [512, 768, 1024, 1536, 2048]}
    assert traffic["arrivals"] == {"process": "closed", "clients": 16}
    assert traffic["requests"]["prompt_len"] == {
        "median": 1024, "sigma": 0.4, "min": 512, "max": 2048}
    out = traffic["requests"]["output_len"]
    assert out["min"] == out["max"] == out["median"] == 192
    assert traffic["trace_seconds"] == 4.0
    assert "audio" in traffic["why"] and "192" in traffic["why"]
    # the longest prompt and its answer fit a ring; every prompt a bucket
    assert 2048 + 192 <= 2304
    requests = loadgen.RequestList(traffic["requests"],
                                   real.config["vocab_size"], 2**31 + 62)
    lengths = [len(requests[i].prompt) for i in range(256)]
    assert 512 <= min(lengths) and max(lengths) <= 2048
    assert all(requests[i].budget == 192 for i in range(64))
    assert max(max(requests[i].prompt) for i in range(64)) < 16384


def test_the_configuration_keeps_every_published_number_outside_reduced():
    config = spec.Cell(spec.load_benchmark(), CELL).config
    assert config["reduced"] == REDUCED
    assert (config["num_layers"], config["num_attention_heads"],
            config["n_routed_experts"], config["vocab_size"]) == (
                4, 8, 8, 16384)
    assert config["held_heads"] == [0, 8] == config["held_experts"]
    assert (config["router_experts"], config["zero_expert_num"],
            config["moe_topk"]) == (768, 256, 12)
    assert config["published"] == dict(
        config["published"], num_layers=28, num_attention_heads=64,
        n_routed_experts=512, vocab_size=131072)
    assert config["deployment"]["chips_per_layer"] == 64
    assert {"experts", "heads", "dense_ffn", "vocabulary", "depth",
            "not_here"} <= set(config["deployment"])
    assert "8 times" in config["deployment"]["dense_ffn"]
    assert {"norm_topk_prob", "router_bias", "tie_word_embeddings",
            "softmax_scale", "rotary", "lora_rescale", "block",
            "parallel_layout", "dtype", "weights"} <= set(config["assumed"])
    assert "encoders" in config["not_run"]
    assert config["param_dtype"] == config["state_dtype"] == "float32"
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f)
                if r["name"] == "LongCat-Flash-Omni"]
    assert config["source"] == row["source_url"]
    changed = [k for k, v in row["config"].items() if config.get(k) != v]
    assert sorted(changed) == sorted(REDUCED)
    for key in REDUCED:
        assert config["published"][key] == row["config"][key]
    # an eighth of the heads, of the vocabulary; a 64th of the experts
    assert 8 * config["num_attention_heads"] == row["config"][
        "num_attention_heads"]
    assert 8 * config["vocab_size"] == row["config"]["vocab_size"]
    assert 64 * config["n_routed_experts"] == row["config"][
        "n_routed_experts"]


def test_the_cuts_arithmetic_at_the_published_sizes():
    """Parameters a layer, bytes a page and a set (`reduced_why`, PERF.md
    section 4), a step's bytes and a prefill's operations (PERF.md section
    5), pinned."""
    from benchmarks.families import longcat_flash as family

    real = spec.Cell(spec.load_benchmark(), CELL)
    config, tenant = real.config, real.traffic["tenant"]
    shapes = family.param_shapes(config)
    count = lambda p, *tails: sum(  # noqa: E731
        math.prod(s) for n, s in shapes.items()
        if n.startswith(p) and n.endswith(tails or ("",)))
    d = 6144
    attention = (d * 1536 + 1536 * 8 * 192 + d * 576 + 512 * 8 * 256
                 + 8 * 128 * d)
    assert attention == family._mla_params(config) == 22_675_456
    whole = d * 1536 + 1536 * 64 * 192 + d * 576 + 512 * 64 * 256 + 8192 * d
    assert whole == 90_570_752                     # all 64 heads
    dense, expert, router = 3 * d * 12288, 3 * d * 2048, d * 768
    assert (dense, expert, router) == (226_492_416, 37_748_736, 4_718_592)
    gains = 1536 + 512 + 2 * d
    assert count("l1_") == attention + dense + gains
    assert count("l0_") == count("l1_") + router + 768 + 8 * expert
    assert 804.9e6 < count("l0_") + count("l1_") < 805.2e6   # 3.22 GB a layer
    assert count("embed_") == count("head_") == 16384 * d
    total = sum(math.prod(s) for s in shapes.values())
    assert 13.68e9 < 4 * total < 13.70e9           # 13.69 GB of weights
    # everything whole at the guide's floors leaves no room for a session
    floors = 4 * (2 * whole + 2 * dense + router + 8 * expert) + 2 * 16384 * d
    assert 15.85e9 < 4 * floors < 15.87e9
    lm = family.model(config)
    spec_ = lm.cache_spec(1, tenant["max_len"])
    assert len(spec_) == 8                         # two rings a layer
    page = sum(e.nbytes for e in spec_.values())
    assert page == 8 * 4 * 576 * 2304              # 18.4 kB a position
    one_set = (tenant["max_sessions"] + 1) * page
    assert 0.38e9 < one_set < 0.39e9
    assert 4 * total + 2 * one_set < 14.5e9
    hit = family.expected_experts_hit(config, 8)
    assert 0.94 < hit < 0.96                       # ~1 held expert a step
    step = family.step_bytes(config, 8, [1200] * 8, hit)
    assert step["mla"] == 4 * 4 * 2 * attention
    assert step["dense_ffn"] == 4 * 4 * 2 * dense
    assert 8.0e9 < step["mla"] + step["dense_ffn"] + step["router"] < 8.1e9
    assert step["head"] == 4 * 16384 * d
    assert step["ring"] == 8 * 8 * 4 * 576 * 1536  # 4 blocks of 384
    assert 9.1e9 < sum(step.values()) < 9.3e9      # ~11.3 ms at 819 GB/s
    flops = family.prefill_flops(config, 1024)
    assert 4.0e12 < sum(flops.values()) < 4.3e12
    assert flops["attention"] / sum(flops.values()) < 0.02
    # 12 x 8 / 768 pairs a token land here: 1.04% of the routed pairs
    assert config["moe_topk"] * 8 / 768 / config["moe_topk"] == 8 / 768


def test_traced_rehearsal_reports_the_new_metrics(results):
    cell, out = results
    assert out["correct"] is True and out["failed"] == 0
    metrics = out["metrics"]
    listed = {m["name"] for m in cell.per_layer} - DEVICE_ONLY
    assert listed - NEEDS_SAMPLES <= set(metrics) <= listed
    m = {k: v["value"] for k, v in metrics.items()}
    # 8 of the tiny router's 24 columns are zero-compute, 4 held
    assert 15.0 < m["moe.zero_share"] < 55.0
    assert 5.0 < m["moe.held_share_l"] < 35.0
    # the session's state is latent rings and nothing else
    assert m["cache.latent_share_l"] == 100.0
    assert m["mla.ring_mb_step_l"] > 0
    # no ring goes through the TPU's kernel on the CPU: 0, not nothing
    assert m["mla.kernel_share_l"] == 0.0
    assert m["attn.kernel_share_sat"] == 0
    assert m["batcher.mixed_share_sat"] == 0     # a latent kind has none
    assert 0 < m["moe.experts_hit_share"] <= 100
    assert m["moe.pairs_per_hit_expert"] >= 1
    assert m["moe.rows_per_pair"] > 1            # absent pairs' rows too
    assert m["moe.kernel_share"] == 0
    assert 0.0 <= m["prefill.pad_share"] < 70.0
    assert m["kv.reserved_over_used"] > 1.0


def test_the_new_metrics_read_nothing_from_a_program_without_the_counters():
    """A program without this PR's counter (the parent, under any cell's
    traced run): `ratio` finds `moe.zero_pairs` nowhere and reads 0 beside
    a routed model's `moe.routed_pairs` — nothing at all beside none —; it
    does not raise.  The four `_l` names read counters the parent has."""
    from benchmarks.harness.window import Window

    w = Window()
    w.before = {"counters": {"serving.decode.dispatches": 1}, "histograms": {}}
    w.after = {"counters": {"serving.decode.dispatches": 9,
                            "moe.routed_pairs": 9600, "moe.pairs": 100,
                            "mla.layer_steps": 64, "mla.kernel_steps": 48,
                            "mla.ring_bytes": 16e6,
                            "cache.reserved_bytes": 4096,
                            "cache.latent_bytes": 1024},
               "histograms": {}}

    def read(name):
        definition = spec.metric_definition(name)
        reader = importlib.import_module(
            "benchmarks.readers." + definition["reader"])
        return reader.read(w, **definition["args"])

    assert read("moe.zero_share") == 0.0
    assert read("moe.held_share_l") == pytest.approx(100 / 96)
    assert read("cache.latent_share_l") == 25.0
    assert read("mla.kernel_share_l") == 75.0
    assert read("mla.ring_mb_step_l") == 2.0
    w.after["counters"]["moe.zero_pairs"] = 3200
    assert read("moe.zero_share") == pytest.approx(100 / 3)
    del w.after["counters"]["moe.zero_pairs"]
    del w.after["counters"]["moe.routed_pairs"]
    assert read("moe.zero_share") is None


def test_the_model_is_built_before_a_weight_is_drawn():
    """What the driver's first try of the cell on the parent meets: the
    family builds the model before it draws a weight, so a program that
    lacks `zero_experts` / the ``"shortcut"`` kind / `latent_lora_rescale`
    fails at once; the arguments are the configuration's, and name no
    model."""
    import inspect

    from benchmarks.families import longcat_flash as family

    config = spec.Cell(spec.load_benchmark(), CELL).config
    args = family.model_args(config)
    assert args["layer_types"] == ["latent_attention"] * 8
    assert args["ffn_types"] == ["shortcut", "dense"] * 4
    assert (args["num_heads"], args["d_model"], args["d_ff"]) == (
        8, 6144, 12288)
    assert (args["num_experts"], args["zero_experts"],
            args["experts_per_token"], args["expert_d_ff"]) == (
                512, 256, 12, 2048)
    assert args["held_experts"] == (0, 8)
    assert args["route_norm"] is False and args["route_scale"] == 6.0
    assert args["router_bias"] is True and args["router_score"] == "softmax"
    assert (args["latent_nope_dim"], args["latent_rope_dim"],
            args["latent_value_dim"], args["latent_lora_rescale"]) == (
                128, 64, 128, True)
    assert args["tied_head"] is False and args["bias"] is False
    lm = family.model(config)
    assert lm.mixed_symbol(8) is None
    source = inspect.getsource(family.make_params)
    assert source.index("model(config)") < source.index("jax.random.key")
