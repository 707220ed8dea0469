"""The cell `glm5_mtp_reason_c8` rehearsed on the CPU at tiny widths
through the same `measure` the command runs: the REAL BENCHMARK.json's
entries for the cell (so every metric definition it reports is read), the
tiny traffic mix of data/rehearsal/ and a tiny `glm5` configuration whose
multi-token-prediction module drafts.  It pins this cell's own entries,
traffic and configuration — nothing about any other cell."""
import argparse
import copy
import importlib
import json
import math
import os
import time

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import device, spec

REHEARSAL = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "data", "rehearsal")
CELL = "glm5_mtp_reason_c8"
CONFIG = "glm-5"
DEVICE_ONLY = {"device.idle_share_sat", "device.peak_mem_gb"}
# a tail is read from 300 intervals or not at all (metrics/itl_p99_ms.json)
NEEDS_SAMPLES = {"batcher.itl_p99_ms_sat"}
NEW = {"mtp.accept_share", "mtp.tokens_per_row_step", "mtp.dropped_row_share",
       "mtp.draft_bytes_share", "sparse.read_share_g", "cache.index_share_g",
       "cache.latent_share_g", "moe.held_share_g", "mla.ring_mb_step_g",
       "batcher.fill_g", "prefill.pad_share_g"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size"]


def _cell():
    bench = copy.deepcopy(spec.load_benchmark())
    bench["paths"] = ["."]
    conf, = [c for c in bench["configs"] if c["name"] == CONFIG]
    conf["file"] = "configs/glm5_tiny.json"
    return spec.Cell(bench, CELL, REHEARSAL)


@pytest.fixture(scope="module")
def results():
    import jax

    # the traced run alone: `test_rehearsal.py` holds every cell's
    # untraced rehearsal, this one's among them, to its end-to-end names
    cell, clock = _cell(), device.CompileClock()
    args = argparse.Namespace(workload=CELL, seed=2**31 + 52, seconds=2.0,
                              trace=1)
    return cell, {1: json.loads(json.dumps(bench_run.measure(
        cell, args, jax.devices()[:1], clock, time.perf_counter())))}


def test_the_cell_and_its_traffic_are_the_issues():
    """ISSUE 52's cell, letter for letter."""
    bench = spec.load_benchmark()
    real = spec.Cell(bench, CELL)
    row, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert row["config"] == CONFIG and row["chips"] == 1
    assert row["traffic"] == "mtp_reason_closed_c8" and len(row["why"]) <= 200
    conf, = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert len(conf["why"]) <= 200
    assert conf["file"] == "benchmarks/configs/glm-5.json"
    assert conf["source"] == real.config["source"]
    assert conf["reduced"] == REDUCED
    assert real.config["family"] == "glm5"
    assert ({m["name"] for m in real.end_to_end}
            == {"gen_tok_per_s", "setup_s"})
    names = {m["name"] for m in real.per_layer}
    assert NEW | {"moe.experts_hit_share", "moe.pairs_per_hit_expert",
                  "batcher.prefill_ms_sat", "kv.reserved_over_used",
                  "device.decode_ms_sat", "device.seen_share_sat",
                  "device.idle_share_sat", "device.peak_mem_gb",
                  "batcher.runahead_share_sat", "batcher.mixed_share_sat",
                  "attn.kernel_share_sat", "batcher.stall_share_sat"} <= names
    # tokens over row slots reads up to 200 where a row emits two: the
    # cell reports its fill by ROWS, under a name of its own
    assert "batcher.fill_sat" not in names
    for m in real.per_layer:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "gen_tok_per_s"
    traffic = real.traffic
    assert traffic["job"] == "generate"
    assert traffic["tenant"] == {"max_sessions": 4, "max_len": 3200,
                                 "max_decode_tokens": 2048,
                                 "seq_buckets": [1024]}
    assert traffic["arrivals"] == {"process": "closed", "clients": 8}
    assert traffic["requests"]["prompt_len"] == {
        "median": 512, "sigma": 0.4, "min": 256, "max": 1024}
    assert traffic["requests"]["output_len"] == {
        "median": 2048, "sigma": 0.0, "min": 2048, "max": 2048}
    assert traffic["trace_seconds"] == 4.0
    # the longest prompt, its answer and the draft's overshoot fit a ring
    assert 1024 + 2048 + 1 <= 3200 and 3200 % 128 == 0


def test_the_configuration_keeps_every_published_number_outside_reduced():
    config = spec.Cell(spec.load_benchmark(), CELL).config
    assert config["reduced"] == REDUCED
    assert config["num_hidden_layers"] == 5
    assert config["first_k_dense_replace"] == 1
    assert config["num_nextn_predict_layers"] == 1
    assert config["n_routed_experts"] == 8
    assert config["held_experts"] == [0, 8]
    assert config["router_experts"] == 256 and config["vocab_size"] == 19360
    assert config["deployment"]["chips_per_layer"] == 32
    assert config["published"] == dict(
        config["published"], num_hidden_layers=78, first_k_dense_replace=3,
        n_routed_experts=256, vocab_size=154880)
    assert {"mtp_module", "draw", "indexer", "softmax_scale", "rotary",
            "router", "dtype", "gains", "block", "weights"} <= set(
                config["assumed"])
    assert all("why" in config["assumed"][k]
               for k in ("mtp_module", "draw", "indexer"))
    assert "never an input" in config["assumed"]["draw"]["why"]
    assert {"experts", "vocabulary", "depth", "not_here"} <= set(
        config["deployment"])
    for word in ("more than one draft", "temperature", "bias UPDATE"):
        assert word in config["not_run"]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f) if r["name"] == "GLM-5"]
    assert config["source"] == row["source_url"]
    changed = [k for k, v in row["config"].items() if config.get(k) != v]
    assert sorted(changed) == sorted(REDUCED)


def test_the_cuts_arithmetic_at_the_published_sizes():
    """Parameters a layer, bytes a position and a set (`reduced_why`,
    PERF.md section 4), and a step's bytes (PERF.md section 5), pinned."""
    from benchmarks.families import glm5 as family

    real = spec.Cell(spec.load_benchmark(), CELL)
    config, tenant = real.config, real.traffic["tenant"]
    shapes = family.param_shapes(config)
    count = lambda p: sum(math.prod(s) for n, s in shapes.items()  # noqa: E731
                          if n.startswith(p))
    d = 6144
    mixer = (d * 2048 + 2048 * 64 * 256 + d * 576 + 512 * 64 * 448
             + 64 * 256 * d + 2048 * 32 * 128 + d * 128 + d * 32)
    assert mixer == 174_391_296 == family.mixer_params(config)
    dense, expert = 3 * d * 12288, 3 * d * 2048
    assert (dense, expert) == (226_492_416, 37_748_736)
    routed = 9 * expert + d * 256 + 256
    gains = 2 * d + 2048 + 512 + 2 * 128
    assert count("l0_") == mixer + dense + gains
    assert count("l1_") == count("l5_") == mixer + routed + gains
    assert count("embed_") == count("head_") == 19360 * d
    assert count("mtp_") == 2 * d * d + 3 * d
    total = sum(math.prod(s) for s in shapes.values())
    assert 13.16e9 < 4 * total < 13.18e9           # 13.17 GB of weights
    lm = family.model(config)
    page = sum(e.nbytes for e in lm.cache_spec(1, tenant["max_len"]).values())
    assert page == 4 * 6 * (576 + 128) * 3200      # 54 MB
    one_set = (tenant["max_sessions"] + 1) * page
    assert 0.27e9 < one_set < 0.271e9
    assert 1.35e9 < 5 * one_set < 1.36e9           # five bound sets
    # a step of four sessions, two of the eight held experts hit a layer
    step = family.step_bytes(config, rows=4, lengths=[1500] * 4,
                             experts_hit=2, ring_len=3200)
    assert step["mixers"] == 4 * 5 * mixer and step["dense_mlp"] == 4 * dense
    assert step["experts"] == 4 * 4 * 2 * expert
    assert step["head"] == 4 * 19360 * d
    assert step["module"] == 4 * (mixer + routed - 6 * expert + 2 * d * d
                                  + 19360 * d)
    assert step["index"] == 6 * 8 * 4 * 128 * 3200
    assert step["rows"] == 6 * 4 * 576 * 4 * (1501 + 1502)
    assert 8.6e9 < sum(step.values()) < 8.9e9      # ~10.7 ms at 819 GB/s
    module, whole = family.weight_bytes(config, 2)
    assert 0.21 < module / whole < 0.24


def test_traced_rehearsal_reports_the_new_metrics(results):
    cell, out = results
    assert out[1]["correct"] is True and out[1]["failed"] == 0
    metrics = out[1]["metrics"]
    listed = {m["name"] for m in cell.per_layer} - DEVICE_ONLY
    assert listed - NEEDS_SAMPLES <= set(metrics) <= listed
    m = {k: v["value"] for k, v in metrics.items()}
    # both branches of the verify rule in a window of a few hundred steps
    assert 0 < m["mtp.accept_share"] < 100
    assert 1 < m["mtp.tokens_per_row_step"] < 2
    # every rejected or trimmed second position is a dropped row of two
    assert m["mtp.dropped_row_share"] == pytest.approx(
        (100 - m["mtp.accept_share"]) / 2)
    # the module: one routed block, the join and a head's read of a step
    # of two layers, the module and two heads' reads
    assert 30 < m["mtp.draft_bytes_share"] < 70
    # a quarter of the experts held; a third of the cache is the module's
    assert 10 < m["moe.held_share_g"] < 45
    assert m["cache.latent_share_g"] + m["cache.index_share_g"] == \
        pytest.approx(100)
    assert m["cache.index_share_g"] == pytest.approx(100 * 16 / 48)
    # the selection of 8 binds from the ninth position on
    assert m["sparse.read_share_g"] < 50
    assert m["mla.ring_mb_step_g"] > 0
    # rows over row slots, whatever a row emits; the one bucket's pad
    assert 0 < m["batcher.fill_g"] <= 100
    assert 0 <= m["prefill.pad_share_g"] < 100
    # the batcher still runs a step ahead, and nothing was mixed
    assert m["batcher.runahead_share_sat"] > 90
    assert m["batcher.mixed_share_sat"] == 0


def test_the_new_metrics_read_nothing_from_a_program_without_the_counters():
    """A program without this PR's counters (the parent, under any cell's
    traced run): `ratio` finds `serving.mtp.*`, `mtp.*` and
    `serving.decode.row_steps` nowhere and gives 0 over what it does find,
    or — with neither — leaves the metric out; it does not raise."""
    from benchmarks.harness.window import Window

    w = Window()
    w.before = {"counters": {"serving.decode.dispatches": 1}, "histograms": {}}
    w.after = {"counters": {"serving.decode.dispatches": 9,
                            "serving.decode.tokens": 32,
                            "cache.reserved_bytes": 4096},
               "histograms": {}}
    for name in sorted(NEW):
        definition = spec.metric_definition(name)
        reader = importlib.import_module(
            "benchmarks.readers." + definition["reader"])
        assert reader.read(w, **definition["args"]) in (None, 0.0), name


def test_the_parent_fails_at_once_on_the_new_configuration():
    """What the driver's first try of the cell on the parent meets: the
    family builds the model before it draws a weight, and a
    `TransformerLM` without `nextn` raises there."""
    import inspect

    from benchmarks.families import glm5 as family
    from mxnet_tpu.models import TransformerLM

    config = spec.Cell(spec.load_benchmark(), CELL).config
    args = family.model_args(config)
    assert args["nextn"] == 1
    assert "nextn" in inspect.signature(TransformerLM.__init__).parameters
    assert args["layer_types"] == ["sparse_latent_attention"] * 5
    assert args["ffn_types"] == ["dense"] + ["routed"] * 4
    assert args["route_scale"] == 2.5
    assert args["kind_specs"]["sparse_latent_attention"] == dict(
        num_heads=64, q_rank=2048, kv_rank=512, nope_dim=192, rope_dim=64,
        value_dim=256, rope_theta=1e6, index_heads=32, index_dim=128,
        index_topk=2048)
    source = inspect.getsource(family.make_params)
    assert source.index("model(config)") < source.index("jax.random.key")
