"""The cell `olmohybrid_extract_c16` rehearsed on the CPU at tiny widths
through the same `measure` the command runs: the REAL BENCHMARK.json's
entries for the cell (so every metric definition it reports is read), the
tiny traffic mix of data/rehearsal/ and a tiny `olmo_hybrid`
configuration."""
import argparse
import copy
import json
import os
import time

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import device, spec

REHEARSAL = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "data", "rehearsal")
CELL = "olmohybrid_extract_c16"
DEVICE_ONLY = {"device.idle_share_sat", "device.peak_mem_gb"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _cell():
    bench = copy.deepcopy(spec.load_benchmark())
    bench["paths"] = ["."]
    conf, = [c for c in bench["configs"] if c["name"] == "olmo-hybrid-7b"]
    conf["file"] = "configs/olmo_hybrid_tiny.json"
    return spec.Cell(bench, CELL, REHEARSAL)


@pytest.fixture(scope="module")
def results():
    import jax

    cell, clock, out = _cell(), device.CompileClock(), {}
    for trace in (0, 1):
        args = argparse.Namespace(workload=CELL, seed=2**31 + 33, seconds=2.0,
                                  trace=trace)
        out[trace] = json.loads(json.dumps(bench_run.measure(
            cell, args, jax.devices()[:1], clock, time.perf_counter())))
    return cell, out


def test_the_cell_is_the_granite_cell_of_another_configuration_and_mix():
    bench = spec.load_benchmark()
    real, granite = spec.Cell(bench, CELL), spec.Cell(bench,
                                                      "granite4h_docs_c16")
    assert real.chips == 1 and real.config["family"] == "olmo_hybrid"
    assert ({m["name"] for m in real.per_layer}
            - {m["name"] for m in granite.per_layer}
            == {"batcher.prefill_ms_sat", "gdn.state_mb_step",
                "kv.skipped_share_wide"})
    # `kv.skipped_share_sat`'s list of cells is pinned by a test this PR
    # may not edit (test_skipped_metric.py): the same reading is reported
    # here under a name of its own, from a definition file of its own
    assert ({m["name"] for m in granite.per_layer}
            - {m["name"] for m in real.per_layer}
            == {"kv.skipped_share_sat"})
    assert (spec.metric_definition("kv.skipped_share_wide")["args"]
            == spec.metric_definition("kv.skipped_share_sat")["args"])
    assert ({m["name"] for m in real.end_to_end}
            == {m["name"] for m in granite.end_to_end})
    assert len(bench["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    # the traffic ISSUE 33 gives, letter for letter
    assert real.traffic["job"] == "generate"
    assert real.traffic["tenant"] == {
        "max_sessions": 8, "max_len": 2304, "max_decode_tokens": 64,
        "seq_buckets": [768, 1024, 1536, 2048]}
    assert real.traffic["arrivals"] == {"process": "closed", "clients": 16}
    assert real.traffic["requests"] == {
        "prompt_len": {"median": 1536, "sigma": 0.4, "min": 512, "max": 2048},
        "output_len": {"median": 24, "sigma": 0.6, "min": 8, "max": 64}}
    assert real.traffic["trace_seconds"] == 4.0


def test_the_configuration_keeps_every_published_number_but_the_depth():
    config = spec.Cell(spec.load_benchmark(), CELL).config
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    assert config["num_hidden_layers"] == len(config["layer_types"]) == 4
    # one whole period: three linear layers, then full attention
    assert config["layer_types"] == ["linear_attention"] * 3 + [
        "full_attention"]
    assert {"block", "attention", "linear_attention", "linear_chunk_size",
            "layouts", "dtype", "weights"} <= set(config["assumed"])
    assert len(config["source"]) <= 200 and "bytes" not in config["source"]
    assert "config.json" in config["source_detail"]
    assert "first period" in config["source_detail"]
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f)
                if r["name"] == "Olmo-Hybrid-7B"]
    assert config["source"] == row["source_url"]
    assert config["layer_types"] == row["config"]["layer_types"][:4]
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key


def test_untraced_rehearsal_is_correct_and_reports_tokens_per_second(results):
    cell, out = results
    result = out[0]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert result["metrics"]["gen_tok_per_s"]["value"] > 0


def test_traced_rehearsal_reports_the_new_metrics(results):
    cell, out = results
    assert out[1]["correct"] is True
    metrics = out[1]["metrics"]
    assert set(metrics) == {m["name"] for m in cell.per_layer} - DEVICE_ONLY
    from benchmarks.families import olmo_hybrid as family

    tenant = cell.traffic["tenant"]
    lm = family.model(cell.config)
    spec_ = lm.cache_spec(tenant["max_sessions"] + 1, tenant["max_len"])
    state = sum(e.nbytes for e in spec_.values() if e.kind == "state")
    total = sum(e.nbytes for e in spec_.values())
    assert metrics["cache.state_share"]["value"] == pytest.approx(
        100.0 * state / total)
    # a step's real rows, each linear layer's window and state once in and
    # once out: at most all four slots' worth, and not far under it
    full = lm.call_counters(rows=tenant["max_sessions"])["gdn.state_bytes"]
    assert 0.5 * full < metrics["gdn.state_mb_step"]["value"] * 1e6 <= full
    assert metrics["batcher.prefill_ms_sat"]["value"] > 0
    # prompts of 6-32 in buckets of 16 and 32: some pad, never all of it
    assert 0.0 < metrics["prefill.pad_share"]["value"] < 75.0
    assert metrics["batcher.runahead_share_sat"]["value"] > 50.0
    assert metrics["kv.reserved_over_used"]["value"] > 1.0
    # the CPU's program reads whole pages
    assert metrics["kv.skipped_share_wide"]["value"] == 0.0


def test_the_new_metrics_do_not_raise_on_a_program_without_the_counters():
    """A program without the `gdn.*` counters (the parent — which cannot
    run this cell at all) or without a prefill in the window: the readers
    the two new metric files name do not raise.  `hist_mean` finds
    nothing and the metric is left out; `ratio` reads a counter the
    program lacks as 0 (PERF.md section 7)."""
    import importlib

    from benchmarks.harness.window import Window

    w = Window()
    w.before = {"counters": {"serving.decode.dispatches": 1}, "histograms": {}}
    w.after = {"counters": {"serving.decode.dispatches": 9}, "histograms": {}}
    for name, want in (("gdn.state_mb_step", 0.0),
                       ("batcher.prefill_ms_sat", None)):
        definition = spec.metric_definition(name)
        reader = importlib.import_module(
            "benchmarks.readers." + definition["reader"])
        assert reader.read(w, **definition.get("args", {})) == want, name


def test_the_reference_check_covers_the_ladder_and_refuses_a_wrong_model():
    """On the CPU both sides multiply in float32, so every prompt of the
    check agrees to rounding; the same weights under a model that norms
    its branches' inputs are refused."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from benchmarks.families import olmo_hybrid as family
    from mxnet_tpu.models import TransformerLM

    cell = _cell()
    params = family.make_params(cell.config, 3, jax.devices()[0])
    params = {k: 5.0 * v if k.endswith("_weight") and "conv" not in k else v
              for k, v in params.items()}
    held = {k: mx.nd.array(np.asarray(v)) for k, v in params.items()}
    session = mx.serving.GenerativeSession(
        "lm", family.model(cell.config), held, **cell.traffic["tenant"])
    ok, facts = family.check_against_reference(cell.config, session, params,
                                               3, 16)
    assert ok and facts["logit_rel_err"] < 1e-4
    assert set(facts["by_prompt"]) == {"23_in_32", "2_in_16", "10_in_16"}
    assert facts["steps"] == [41, 8, 8]
    assert facts["prefill_state_rel_err"] < 1e-5 and not facts["not_as_stated"]
    assert facts["decode_state_rel_err"] < 1e-5
    # the same model with the block's norms where every other block has them
    right = family.model(cell.config)
    wrong = TransformerLM(**dict(
        {n: getattr(right, n) for n in (
            "vocab", "num_layers", "num_heads", "d_model", "d_ff", "max_len",
            "norm", "norm_eps", "positions", "qk_norm", "bias", "tied_head",
            "layer_types", "ffn", "linear_heads", "linear_key_dim",
            "linear_value_dim", "linear_conv", "linear_chunk",
            "linear_neg_eigval")}, block_norm="input"))
    session = mx.serving.GenerativeSession("lm", wrong, held,
                                           **cell.traffic["tenant"])
    ok, facts = family.check_against_reference(cell.config, session, params,
                                               3, 16)
    assert not ok and facts["logit_rel_err"] > family.LOGIT_RTOL


def test_the_operation_and_byte_counts_at_the_published_sizes():
    """The hand roofline's inputs (PERF.md section 5), pinned: a 2,048
    position scan is 10.9 GFLOP a layer and a decode step of 8 rows reads
    and writes 8 x 2.35 MB of state."""
    import math

    from benchmarks.families import olmo_hybrid as family

    config = spec.Cell(spec.load_benchmark(), CELL).config
    per_chunk_head = (2 * 2 * 64 * 64 * 96 + 64 * 64 * (192 + 96)
                      + 3 * 2 * 64 * 96 * 192 + 2 * 64 * 64 * 192)
    assert family.scan_flops(config, 2048) == 32 * 30 * per_chunk_head
    assert 10.9e9 < family.scan_flops(config, 2048) < 11.0e9
    assert family.step_bytes(config, 8) == 8 * 2 * 4 * (30 * 96 * 192
                                                        + 3 * 11520)
    assert family.step_flops(config, 8) == 8 * (7 * 30 * 96 * 192
                                                + 2 * 4 * 11520)
    assert family.scan_bytes(config, 768) == 4 * (768 * (17340 + 5760)
                                                  + 3 * 11520 + 30 * 96 * 192)
    # the weights the file's `reduced_why` reckons with
    shapes = family.param_shapes(config)
    count = lambda p: sum(math.prod(s) for n, s in shapes.items()  # noqa: E731
                          if n.startswith(p))
    assert count("l0_") == 215_570_172 and count("l3_") == 185_809_920
    assert count("embed_") == count("head_") == 100352 * 3840
