"""The cell `granite4h_docs_c16` rehearsed on the CPU at tiny widths
through the same `measure` the command runs: the REAL BENCHMARK.json's
entries for the cell (so every metric definition it reports is read), the
tiny traffic mix of data/rehearsal/ and a tiny `granite_hybrid`
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
CELL = "granite4h_docs_c16"
DEVICE_ONLY = {"device.idle_share_sat", "device.peak_mem_gb"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _cell():
    bench = copy.deepcopy(spec.load_benchmark())
    bench["paths"] = ["."]
    conf, = [c for c in bench["configs"] if c["name"] == "granite-4.0-h-micro"]
    conf["file"] = "configs/granite_hybrid_tiny.json"
    return spec.Cell(bench, CELL, REHEARSAL)


@pytest.fixture(scope="module")
def results():
    import jax

    cell, clock, out = _cell(), device.CompileClock(), {}
    for trace in (0, 1):
        args = argparse.Namespace(workload=CELL, seed=2**31 + 7, seconds=2.0,
                                  trace=trace)
        out[trace] = json.loads(json.dumps(bench_run.measure(
            cell, args, jax.devices()[:1], clock, time.perf_counter())))
    return cell, out


def test_the_cell_is_the_offline_cell_of_another_configuration_and_mix():
    real = spec.Cell(spec.load_benchmark(), CELL)
    olmoe = spec.Cell(spec.load_benchmark(), "olmoe_offline")
    assert real.chips == 1 and real.config["family"] == "granite_hybrid"
    assert ({m["name"] for m in real.per_layer}
            - {m["name"] for m in olmoe.per_layer}
            == {"cache.state_share", "prefill.pad_share"})
    assert ({m["name"] for m in olmoe.per_layer}
            - {m["name"] for m in real.per_layer}
            == {"moe.experts_hit_share", "moe.pairs_per_hit_expert"})
    assert ({m["name"] for m in real.end_to_end}
            == {m["name"] for m in olmoe.end_to_end})
    # the traffic ISSUE 31 gives, letter for letter
    assert real.traffic["tenant"] == {
        "max_sessions": 8, "max_len": 2304, "max_decode_tokens": 256,
        "seq_buckets": [256, 512, 1024, 2048]}
    assert real.traffic["arrivals"] == {"process": "closed", "clients": 16}
    assert real.traffic["requests"] == {
        "prompt_len": {"median": 768, "sigma": 0.7, "min": 128, "max": 2048},
        "output_len": {"median": 96, "sigma": 0.7, "min": 16, "max": 256}}
    assert real.traffic["trace_seconds"] == 4.0


def test_the_configuration_keeps_every_published_number_but_the_depth():
    config = spec.Cell(spec.load_benchmark(), CELL).config
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    assert config["num_hidden_layers"] == len(config["layer_types"]) == 20
    # two whole periods: 9 mamba to 1 attention, attention at offset 5
    assert [i for i, k in enumerate(config["layer_types"])
            if k == "attention"] == [5, 15]
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f)
                if r["name"] == "granite-4.0-h-micro"]
    assert config["source"] == row["source_url"]
    assert config["layer_types"] == row["config"]["layer_types"][:20]
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


def test_traced_rehearsal_reports_the_state_and_pad_shares(results):
    cell, out = results
    metrics = out[1]["metrics"]
    assert set(metrics) == {m["name"] for m in cell.per_layer} - DEVICE_ONLY
    from benchmarks.families import granite_hybrid as family

    tenant = cell.traffic["tenant"]
    spec_ = family.model(cell.config).cache_spec(
        tenant["max_sessions"] + 1, tenant["max_len"])
    state = sum(e.nbytes for e in spec_.values() if e.kind == "state")
    total = sum(e.nbytes for e in spec_.values())
    # every bound set has the same split, so the share is the spec's
    assert metrics["cache.state_share"]["value"] == pytest.approx(
        100.0 * state / total)
    assert 0 < state < total
    # prompts of 4-32 in buckets of 16 and 32: some pad, never all of it
    assert 0.0 < metrics["prefill.pad_share"]["value"] < 75.0
    assert metrics["batcher.runahead_share_sat"]["value"] > 50.0
    assert metrics["kv.reserved_over_used"]["value"] > 1.0


def test_the_reference_check_covers_the_ladder_and_refuses_a_wrong_model():
    """On the CPU both sides multiply in float32, so every prompt of the
    check agrees to rounding; the same weights under a model with
    another residual multiplier are refused."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from benchmarks.families import granite_hybrid as family

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
    # the long prompt decodes until its ring of 64 is full; layer 0's
    # state agrees with the reference's; the tenant holds what it was given
    assert facts["steps"] == [41, 8, 8]
    assert facts["prefill_state_rel_err"] < 1e-5 and not facts["not_as_stated"]
    assert facts["decode_state_rel_err"] < 1e-5
    wrong = family.model(dict(cell.config, residual_multiplier=0.5))
    session = mx.serving.GenerativeSession("lm", wrong, held,
                                           **cell.traffic["tenant"])
    ok, facts = family.check_against_reference(cell.config, session, params,
                                               3, 16)
    assert not ok and facts["logit_rel_err"] > family.LOGIT_RTOL


def test_the_operation_and_byte_counts_at_the_published_sizes():
    """The hand roofline's inputs (PERF.md section 5), pinned: a 2,048
    position scan is 8.7 GFLOP a layer and a decode step of 8 rows reads
    and writes 8 x 2.1 MB of state."""
    from benchmarks.families import granite_hybrid as family

    config = spec.Cell(spec.load_benchmark(), CELL).config
    assert family.scan_flops(config, 2048) == 2 * 8 * 256 * 256 * (
        128 + 4096) + 4 * 2048 * 4096 * 128
    assert family.step_bytes(config, 8) == 8 * 2 * 4 * (64 * 64 * 128
                                                        + 3 * 4352)
    assert family.step_flops(config, 8) == 8 * (5 * 64 * 64 * 128
                                                + 2 * 4 * 4352)
    assert family.scan_bytes(config, 256) == 4 * (256 * (8512 + 4096)
                                                  + 3 * 4352 + 64 * 64 * 128)
