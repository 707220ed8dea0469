"""The cell `granite4hsmall_chat_c16` rehearsed on the CPU at tiny widths
through the same `measure` the command runs: the REAL BENCHMARK.json's
entries for the cell (so every metric definition it reports is read), the
tiny traffic mix of data/rehearsal/ and a tiny `granite_moe_hybrid`
configuration — three held of twelve experts under a Mamba-2 and an
attention mixer, as small as it can be: tier-1 runs this directory file
after file on one worker.  It pins this cell's own entries, traffic and
configuration — nothing about any other cell; the family's check is held
to wrong models at a tiny size in `tests/test_granite_h_small.py`."""
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
CELL = "granite4hsmall_chat_c16"
CONFIG = "granite-4.0-h-small"
DEVICE_ONLY = {"device.idle_share_sat", "device.peak_mem_gb"}
# a tail is read from 300 intervals or not at all (metrics/itl_p99_ms.json)
NEEDS_SAMPLES = {"batcher.itl_p99_ms_sat"}
NEW = {"ssm.state_mb_step", "moe.held_share_h"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "layer_types", "num_local_experts",
           "vocab_size"]


def _cell():
    bench = copy.deepcopy(spec.load_benchmark())
    bench["paths"] = ["."]
    conf, = [c for c in bench["configs"] if c["name"] == CONFIG]
    conf["file"] = "configs/granite_moe_hybrid_tiny.json"
    return spec.Cell(bench, CELL, REHEARSAL)


@pytest.fixture(scope="module")
def results():
    import jax

    # the traced run alone: `test_rehearsal.py` holds every cell's
    # untraced rehearsal, this one's among them, to its end-to-end names
    cell, clock = _cell(), device.CompileClock()
    args = argparse.Namespace(workload=CELL, seed=2**31 + 54, seconds=2.0,
                              trace=1)
    return cell, json.loads(json.dumps(bench_run.measure(
        cell, args, jax.devices()[:1], clock, time.perf_counter())))


def test_the_cell_and_its_traffic_are_the_issues():
    """ISSUE 54's cell, letter for letter."""
    bench = spec.load_benchmark()
    real = spec.Cell(bench, CELL)
    row, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert row["config"] == CONFIG and row["chips"] == 1
    assert row["traffic"] == "chat_closed_c16" and len(row["why"]) <= 200
    conf, = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert len(conf["why"]) <= 200
    assert conf["file"] == "benchmarks/configs/granite-4.0-h-small.json"
    assert conf["source"] == real.config["source"]
    assert conf["reduced"] == REDUCED
    assert real.config["family"] == "granite_moe_hybrid"
    assert ({m["name"] for m in real.end_to_end}
            == {"gen_tok_per_s", "setup_s"})
    names = {m["name"] for m in real.per_layer}
    assert NEW | {"moe.experts_hit_share", "moe.pairs_per_hit_expert",
                  "cache.state_share", "prefill.pad_share",
                  "batcher.fill_sat", "batcher.prefill_share",
                  "batcher.prefill_ms_sat", "batcher.decode_step_ms_sat",
                  "batcher.mixed_share_sat", "batcher.runahead_share_sat",
                  "batcher.stall_share_sat", "kv.reserved_over_used",
                  "device.decode_ms_sat", "device.seen_share_sat",
                  "device.idle_share_sat", "device.peak_mem_gb",
                  "startup.compile_s"} <= names
    for m in real.per_layer:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "gen_tok_per_s"
            assert m["source"] == "program_counter"
            assert spec.metric_definition(m["name"])["reader"] == "ratio"
    # the held share under a name of this cell's: one reading
    assert (spec.metric_definition("moe.held_share_h")
            == spec.metric_definition("moe.held_share"))
    state = spec.metric_definition("ssm.state_mb_step")["args"]
    assert state["num"] == [{"counter": "ssm.state_bytes"}]
    assert state["den"] == [{"counter": "serving.decode.dispatches"}]
    assert state["scale"] == 1e-6
    traffic = real.traffic
    assert traffic["job"] == "generate"
    assert traffic["tenant"] == {"max_sessions": 8, "max_len": 1536,
                                 "max_decode_tokens": 512,
                                 "seq_buckets": [128, 256, 512, 1024]}
    assert traffic["arrivals"] == {"process": "closed", "clients": 16}
    assert traffic["requests"]["prompt_len"] == {
        "median": 256, "sigma": 0.8, "min": 32, "max": 1024}
    assert traffic["requests"]["output_len"]["median"] == 192
    assert traffic["requests"]["output_len"]["max"] == 512
    assert traffic["trace_seconds"] == 4.0
    # the longest prompt and the longest answer fit a ring
    assert 1024 + 512 <= 1536


def test_the_configuration_keeps_every_published_number_outside_reduced():
    config = spec.Cell(spec.load_benchmark(), CELL).config
    assert config["reduced"] == REDUCED
    assert config["num_hidden_layers"] == len(config["layer_types"]) == 10
    # one whole period: nine mamba to one attention, attention at offset 5
    assert [i for i, k in enumerate(config["layer_types"])
            if k == "attention"] == [5]
    assert config["num_local_experts"] == 9
    assert config["held_experts"] == [0, 9]
    assert config["router_experts"] == 72 and config["vocab_size"] == 12544
    assert config["num_experts_per_tok"] == 10
    assert config["deployment"]["chips_per_layer"] == 8
    assert config["published"] == dict(
        config["published"], num_hidden_layers=40, num_local_experts=72,
        vocab_size=100352)
    assert {"experts", "vocabulary", "depth", "not_here"} <= set(
        config["deployment"])
    assert {"dtype", "block", "mamba", "attention", "experts", "layouts",
            "weights", "not_run"} <= set(config["assumed"])
    assert "renormalises" in config["assumed"]["experts"]
    # the guide's floors: a whole period and four layers, eight experts,
    # an eighth of the vocabulary
    assert config["num_local_experts"] >= 8
    assert 8 * config["vocab_size"] >= 100352
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f) if r["name"] == CONFIG]
    assert config["source"] == row["source_url"]
    assert config["layer_types"] == row["config"]["layer_types"][:10]
    changed = [k for k, v in row["config"].items() if config.get(k) != v]
    assert sorted(changed) == sorted(REDUCED)


def test_the_cuts_arithmetic_at_the_published_sizes():
    """Parameters a layer, bytes a page and a set (`reduced_why`, PERF.md
    section 4), and a step's bytes (PERF.md section 5), pinned."""
    from benchmarks.families import granite_moe_hybrid as family

    real = spec.Cell(spec.load_benchmark(), CELL)
    config, tenant = real.config, real.traffic["tenant"]
    shapes = family.param_shapes(config)
    count = lambda p, *tails: sum(  # noqa: E731
        math.prod(s) for n, s in shapes.items()
        if n.startswith(p) and n.endswith(tails or ("",)))
    d = 4096
    mamba = (16768 * d + d * 8192 + 4 * 8448 + 8448 + 3 * 128 + 8192)
    assert mamba == 102_286_976
    attention = 6144 * d + d * d
    assert attention == 41_943_040
    expert, shared, router = 3 * d * 768, 3 * d * 1536, d * 72
    assert (expert, shared, router) == (9_437_184, 18_874_368, 294_912)
    assert count("l0_") == mamba + 9 * expert + shared + router + 2 * d
    assert count("l0_") == 206_399_104
    assert count("l5_") == attention + 9 * expert + shared + router + 2 * d
    assert count("l5_") == 146_055_168
    assert count("embed_") == 12544 * d
    total = sum(math.prod(s) for s in shapes.values())
    assert 8.21e9 < 4 * total < 8.23e9             # 8.22 GB of weights
    lm = family.model(config)
    spec_ = lm.cache_spec(1, tenant["max_len"])
    page = sum(e.nbytes for e in spec_.values())
    state = sum(e.nbytes for e in spec_.values() if e.kind == "state")
    assert state == 9 * 4 * (128 * 64 * 128 + 3 * 8448)      # 38.7 MB
    assert page - state == 2 * 4 * 8 * 128 * 1536           # 12.6 MB
    assert 51.2e6 < page < 51.3e6
    one_set = (tenant["max_sessions"] + 1) * page
    assert 0.46e9 < one_set < 0.4625e9
    assert 4.14e9 < 9 * one_set < 4.16e9           # nine bound sets
    # what one 8-row step books: 2 x rows x the Mamba layers' pages
    assert lm.call_counters(rows=8, lengths=(300,) * 8, computed=8,
                            pages=81, max_len=1536)[
        "ssm.state_bytes"] == 2 * 8 * state
    assert 618e6 < 2 * 8 * state < 619e6
    assert state == 9 * family.step_bytes(config, 1) // 2
    hit = family.expected_experts_hit(config, 8)
    assert 6.2 < hit < 6.4                         # about two thirds of 9
    step = family.decode_bytes(config, rows=8, lengths=[400] * 8,
                               experts_hit=hit)
    assert step["mamba"] == 4 * 9 * (16768 * d + d * 8192 + 4 * 8448)
    assert 3.67e9 < step["mamba"] < 3.69e9
    assert step["shared_and_router"] == 4 * 10 * (shared + router)
    assert 2.3e9 < step["experts"] < 2.45e9
    assert step["state"] == 2 * 8 * state
    assert step["head"] == 4 * 12544 * d
    assert 7.5e9 < sum(step.values()) < 7.9e9      # ~9.5 ms at 819 GB/s


def test_traced_rehearsal_reports_the_new_metrics(results):
    cell, out = results
    assert out["correct"] is True and out["failed"] == 0
    metrics = out["metrics"]
    listed = {m["name"] for m in cell.per_layer} - DEVICE_ONLY
    assert listed - NEEDS_SAMPLES <= set(metrics) <= listed
    m = {k: v["value"] for k, v in metrics.items()}
    from benchmarks.families import granite_moe_hybrid as family

    tenant = cell.traffic["tenant"]
    spec_ = family.model(cell.config).cache_spec(1, tenant["max_len"])
    state = sum(e.nbytes for e in spec_.values() if e.kind == "state")
    total = sum(e.nbytes for e in spec_.values())
    # every bound set has the same split, so the share is the spec's
    assert m["cache.state_share"] == pytest.approx(100.0 * state / total)
    # a step moves 2 x its real rows x the Mamba layers' pages: between
    # one row's and both slots'
    slots = tenant["max_sessions"]
    assert 2 * state * 1e-6 <= m["ssm.state_mb_step"] <= (
        2 * slots * state * 1e-6)
    assert m["ssm.state_mb_step"] == pytest.approx(
        2 * state * 1e-6 * slots * m["batcher.fill_sat"] / 100, rel=0.05)
    # three of twelve experts held: a quarter of the pairs, by the draw
    assert 10 < m["moe.held_share_h"] < 45
    assert 0 < m["moe.experts_hit_share"] <= 100
    assert m["moe.pairs_per_hit_expert"] >= 1
    assert 0.0 <= m["prefill.pad_share"] < 75.0
    # the batcher runs a step ahead; a Mamba-2 model mixes nothing
    assert m["batcher.runahead_share_sat"] > 50
    assert m["batcher.mixed_share_sat"] == 0
    assert m["kv.reserved_over_used"] > 1.0


def test_the_new_metrics_read_nothing_from_a_program_without_the_counters():
    """A program without this PR's counters (the parent, under any cell's
    traced run): `ratio` finds `ssm.state_bytes` nowhere and gives 0 over
    the dispatches it does find, or — with neither — leaves the metric
    out; it does not raise."""
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
    w.after["counters"].pop("serving.decode.dispatches")
    w.before["counters"].clear()
    definition = spec.metric_definition("ssm.state_mb_step")
    reader = importlib.import_module(
        "benchmarks.readers." + definition["reader"])
    assert reader.read(w, **definition["args"]) is None


def test_the_model_is_built_before_a_weight_is_drawn():
    """What the driver's first try of the cell on the parent meets: the
    family builds the model before it draws a weight, so a program that
    cannot build this block fails at once; the arguments are the
    configuration's, and name no model."""
    import inspect

    from benchmarks.families import granite_moe_hybrid as family

    config = spec.Cell(spec.load_benchmark(), CELL).config
    args = family.model_args(config)
    assert args["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert args["ffn_types"] == ["routed"] * 10
    assert (args["num_experts"], args["experts_per_token"],
            args["held_experts"]) == (72, 10, (0, 9))
    assert (args["expert_d_ff"], args["shared_d_ff"]) == (768, 1536)
    assert args["route_norm"] is True and args["router_score"] == "softmax"
    assert (args["embedding_multiplier"], args["residual_multiplier"],
            args["attention_multiplier"], args["logits_scaling"]) == (
                12, 0.22, 0.0078125, 16)
    assert (args["mamba_heads"], args["mamba_head_dim"],
            args["mamba_state"], args["mamba_chunk"]) == (128, 64, 128, 256)
    lm = family.model(config)
    assert lm.mixed_symbol(8) is None
    source = inspect.getsource(family.make_params)
    assert source.index("model(config)") < source.index("jax.random.key")
