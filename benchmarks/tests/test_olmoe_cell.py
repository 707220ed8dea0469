"""The cell `olmoe_offline` rehearsed on the CPU at tiny widths through
the same `measure` the command runs: the REAL BENCHMARK.json's entries
for the cell (so every metric definition it reports is read), the tiny
traffic mix of data/rehearsal/ and a tiny `olmoe` configuration."""
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
CELL = "olmoe_offline"
DEVICE_ONLY = {"device.idle_share_sat", "device.peak_mem_gb"}


def _cell():
    bench = copy.deepcopy(spec.load_benchmark())
    bench["paths"] = ["."]
    conf, = [c for c in bench["configs"] if c["name"] == "olmoe-1b-7b"]
    conf["file"] = "configs/olmoe_tiny.json"
    return spec.Cell(bench, CELL, REHEARSAL)


@pytest.fixture(scope="module")
def results():
    import jax

    cell, clock, out = _cell(), device.CompileClock(), {}
    for trace in (0, 1):
        args = argparse.Namespace(workload=CELL, seed=2**31 + 5, seconds=2.0,
                                  trace=trace)
        out[trace] = json.loads(json.dumps(bench_run.measure(
            cell, args, jax.devices()[:1], clock, time.perf_counter())))
    return cell, out


def test_the_cell_is_the_opt_offline_cell_with_another_configuration():
    real = spec.Cell(spec.load_benchmark(), CELL)
    opt = spec.Cell(spec.load_benchmark(), "opt1b3_offline")
    assert real.traffic == opt.traffic and real.chips == opt.chips == 1
    assert real.config["family"] == "olmoe"
    assert ({m["name"] for m in real.per_layer}
            - {m["name"] for m in opt.per_layer}
            == {"moe.experts_hit_share", "moe.pairs_per_hit_expert"})
    assert ({m["name"] for m in real.end_to_end}
            == {m["name"] for m in opt.end_to_end})


def test_untraced_rehearsal_is_correct_and_reports_tokens_per_second(results):
    cell, out = results
    result = out[0]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert result["metrics"]["gen_tok_per_s"]["value"] > 0


def test_traced_rehearsal_reports_the_expert_layers_counters(results):
    cell, out = results
    metrics = out[1]["metrics"]
    assert set(metrics) == {m["name"] for m in cell.per_layer} - DEVICE_ONLY
    layers = cell.config["num_hidden_layers"]
    experts, k = cell.config["num_experts"], cell.config["num_experts_per_tok"]
    assert 100.0 * k / experts <= metrics["moe.experts_hit_share"]["value"] <= 100.0
    # a hit expert sees at least one row, at most a whole prefill bucket
    rows = metrics["moe.pairs_per_hit_expert"]["value"]
    assert 1.0 <= rows <= max(cell.traffic["tenant"]["seq_buckets"])
    assert layers == 2


def test_the_reference_check_skips_near_ties_and_holds_the_rest():
    """On the CPU both sides multiply in float32, so every compared row
    agrees to rounding; a router near tie is skipped, not failed, and a
    check that skipped more than half would fail."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from benchmarks.families import olmoe

    cell = _cell()
    params = olmoe.make_params(cell.config, 3, jax.devices()[0])
    held = {k: mx.nd.array(np.asarray(v)) for k, v in params.items()}
    session = mx.serving.GenerativeSession(
        "lm", olmoe.model(cell.config), held, **cell.traffic["tenant"])
    ok, facts = olmoe.check_against_reference(cell.config, session, params,
                                              3, 16)
    rows = olmoe.CHECK_PROMPTS * (olmoe.CHECK_STEPS + 1)
    assert ok and facts["compared"] + facts["skipped"] == rows
    assert facts["compared"] >= rows / 2 and facts["logit_rel_err"] < 1e-4
    # another rotary base is not the model: the same check refuses it
    wrong = olmoe.model(dict(cell.config, rope_theta=100))
    session = mx.serving.GenerativeSession("lm", wrong, held,
                                           **cell.traffic["tenant"])
    ok, facts = olmoe.check_against_reference(cell.config, session, params,
                                              3, 16)
    assert not ok and facts["logit_rel_err"] > olmoe.LOGIT_RTOL
