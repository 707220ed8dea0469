"""bench.py --smoke: the benchmark harness runs the REAL K-step fused
dispatch + async staging path end-to-end on CPU, so the bench cannot
silently rot while the code underneath it changes (satellite of the
dispatch-amortization work, docs/perf.md)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_bench_smoke_runs_k_step_path():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MXTPU_STEPS_PER_DISPATCH", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--smoke"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    line = proc.stdout.strip().splitlines()[-1]
    out = json.loads(line)
    # the acceptance pin: dispatch count = ceil(steps / K)
    assert out["steps"] == 24 and out["steps_per_dispatch"] == 4
    assert out["dispatches"] == out["expected_dispatches"] == 6
    # both profiler lanes exist: one io.stage span per staged block and
    # one fit.dispatch span per dispatch
    assert out["fused_dispatch_spans"] == 6
    assert out["h2d_stage_spans"] >= 6
    # staging ran asynchronously: off the dispatching thread, or
    # wall-clock-overlapping a fused dispatch (both hold on real runs;
    # either alone proves the H2D was not inline with dispatch)
    assert out["h2d_async"] or out["h2d_overlap"], out
    # the telemetry registry saw the same run (bench asserts the
    # snapshot itself; these pins keep the reported fields honest)
    assert out["telemetry_dispatches"] == 6
    assert out["telemetry_h2d_bytes"] > 0
    assert out["telemetry_stage_occupancy_seen"] is True
    # a CPU run has no MFU: no peak is known for the device, so the
    # gauge is not published (never a ratio against another chip's peak)
    assert out["telemetry_mfu"] is None
    assert out["platform"] == "cpu"


@pytest.mark.slow
def test_bench_imperative_fuses_the_chain():
    """bench.py --imperative: the acceptance pin for lazy imperative
    fusion (docs/perf.md) — the 64-op chain executes in ≤ 4 XLA
    dispatches per iteration under lazy mode vs 64 eager, and the
    second lazy iteration hits the fusion cache."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MXTPU_LAZY", None)
    env.pop("MXTPU_LAZY_MAX_OPS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--imperative"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["chain_ops"] == 64
    assert out["dispatches_eager"] == 64  # one dispatch per primitive
    assert out["dispatches_lazy"] <= 4    # the whole chain fused
    assert out["fusion_cache_hit_rate"] > 0
    assert out["mean_chain_len"] and out["mean_chain_len"] > 8
    assert out["value"] > 0 and out["unit"] == "ops/s"


@pytest.mark.slow
@pytest.mark.parametrize("sink", ["s2d_stem", "bf16_wgrad", "lstm_pack",
                                  "frozen_bn"])
def test_bench_ab_smoke_runs_both_sides(sink):
    """bench.py --ab <sink> --smoke: the matched A/B harness for the four
    attributed MFU sinks (docs/perf.md "MFU sinks") runs both sides
    back-to-back in one process on CPU and emits one JSON row with both
    values, per-side stdev, and the delta — so every README Roofline
    item-8 entry stays reproducible with one command."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for knob in ("MXNET_TPU_S2D_STEM", "MXTPU_BF16_WGRAD",
                 "MXTPU_FROZEN_BN"):
        env.pop(knob, None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--ab", sink,
         "--smoke"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sink"] == sink and out["smoke"] is True
    assert out["unit"] == ("tokens/s" if sink == "lstm_pack" else "img/s")
    for side in ("a", "b"):
        assert out[side]["value"] > 0
        assert out[side]["stdev"] >= 0
    # the delta is computed from the sides it reports
    expect = round((out["b"]["value"] - out["a"]["value"])
                   / out["a"]["value"] * 100.0, 2)
    assert abs(out["delta_pct"] - expect) < 0.05


@pytest.mark.slow
def test_bench_serve_smoke_reports_load_row():
    """bench.py --serve --smoke: the serving load driver (docs/serving.md)
    runs two tiny CPU tenants through the REAL ModelServer path —
    continuous batching, bucketed programs, ping-pong staging — and
    emits ONE JSON row with img/s, p50/p99 latency, and the exact
    batch-fill ratio at the stated offered load.  The same driver with
    ResNet-50/152 tenants produces the chip row."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for knob in ("MXTPU_SERVE_MAX_BATCH", "MXTPU_SERVE_BUCKETS",
                 "MXTPU_SERVE_TIMEOUT_MS", "MXTPU_SERVE_MAX_QUEUE",
                 "MXTPU_SERVE_WAIT_MS"):
        env.pop(knob, None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--serve",
         "--smoke"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["smoke"] is True and out["unit"] == "img/s"
    assert out["value"] > 0 and out["offered_load"] > 0
    # the batch_fill_ratio was observed and the p99 is reported — the
    # acceptance criteria of the serving PR
    assert out["fill_pct"] is not None and 0 < out["fill_pct"] <= 100
    assert out["p50_ms"] is not None and out["p99_ms"] >= out["p50_ms"]
    assert out["requests"] == sum(t["requests"]
                                  for t in out["tenants"].values())
    assert out["timeouts"] == 0 and out["failed"] == 0
    # both tenants actually shared the device in this run
    assert len(out["tenants"]) == 2
    assert all(t["requests"] > 0 for t in out["tenants"].values())
    # the timed window never recompiled: every bucket program was built
    # in warmup and reused (compile-once-per-bucket, ladder reported)
    assert out["compile_misses_timed"] == 0
    assert out["ladder"][-1] == out["max_batch"]


@pytest.mark.slow
def test_bench_serve_smoke_trace_overhead_within_noise():
    """bench.py --serve --smoke --trace-ab: the request-tracing
    overhead pin (ISSUE 15 acceptance — overhead <=1% at
    MXTPU_TRACE_SAMPLE=0.01).  The same serving load runs back-to-back
    with sampling off vs armed, 3 timed chunks per side (the --ab
    stdev machinery), and the row must report the delta within noise —
    bench.py asserts it internally under --smoke, this pin keeps the
    harness from silently rotting."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MXTPU_TRACE_SAMPLE", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--serve",
         "--smoke", "--trace-ab"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sink"] == "trace_overhead" and out["smoke"] is True
    assert out["a"]["img_s"] > 0 and out["b"]["img_s"] > 0
    # both sides carry their own stdev and the delta is computed from
    # the sides it reports (the --ab row contract)
    expect = round((out["a"]["img_s"] - out["b"]["img_s"])
                   / out["a"]["img_s"] * 100.0, 3)
    assert abs(out["overhead_pct"] - expect) < 0.05
    # the armed side really minted sampling decisions (every B-side
    # submit draws one — 0 would mean tracing never armed), and the
    # timed windows were compile-free
    assert out["sampling_decisions"] > 0
    assert out["compile_misses_timed"] == 0
    assert out["overhead_pct"] <= max(1.0, 2.0 * out["noise_pct"])


@pytest.mark.slow
def test_bench_serve_smoke_mem_census_overhead_within_noise():
    """bench.py --serve --smoke --mem-ab: the live-buffer census
    overhead pin (docs/observability.md "Memory observability" —
    census cost <=1% of serving throughput).  The same load runs
    back-to-back with the census disarmed vs armed, 3 timed chunks per
    side; bench.py asserts the bar internally under --smoke, this pin
    keeps the harness from silently rotting."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MXTPU_MEM_CENSUS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--serve",
         "--smoke", "--mem-ab"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sink"] == "mem_overhead" and out["smoke"] is True
    assert out["a"]["img_s"] > 0 and out["b"]["img_s"] > 0
    expect = round((out["a"]["img_s"] - out["b"]["img_s"])
                   / out["a"]["img_s"] * 100.0, 3)
    assert abs(out["overhead_pct"] - expect) < 0.05
    # the armed side really booked buffers (0 = census never armed),
    # and the timed windows were compile-free
    assert out["census_books"] > 0
    assert out["compile_misses_timed"] == 0
    assert out["overhead_pct"] <= max(1.0, 2.0 * out["noise_pct"])


@pytest.mark.slow
def test_bench_serve_replicas_smoke_scaling_row():
    """bench.py --serve --replicas 1,2 --smoke: the multi-replica tier
    row (docs/serving.md "Multi-replica tier") launches each fleet via
    the REAL tools/launch.py --serve-replicas path, drives the same
    offered load through a Router per replica count, and emits ONE
    JSON row with img/s + route p50/p99 per count and the 1->max
    scaling.  The same driver at --replicas 1,2,4 with ResNet tenants
    produces the BENCH_TABLE row."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for knob in ("MXTPU_SERVE_MAX_BATCH", "MXTPU_SERVE_BUCKETS",
                 "MXTPU_ROUTER_POLL_MS", "MXTPU_ROUTER_REDISPATCH",
                 "MXTPU_ROUTER_ADAPT_WINDOW_S", "MXTPU_ROUTER_REPLICAS"):
        env.pop(knob, None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--serve",
         "--smoke", "--replicas", "1,2"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["smoke"] is True and out["unit"] == "img/s"
    assert set(out["replica_counts"]) == {"1", "2"}
    for n, sub in out["replica_counts"].items():
        # zero lost futures, every driven request completed (driven is
        # >= the --requests floor: closed loop rounds per-client shares
        # up), the fleet came up and tore down via the launcher (rc 0)
        assert sub["requests"] == sub["driven"] >= out["requests_per_count"]
        assert sub["failed"] == 0 and sub["redispatches"] == 0
        assert sub["launcher_rc"] == 0
        assert sub["p99_ms"] >= sub["p50_ms"] > 0
        assert sub["replicas_healthy"] == float(n)
        assert len(sub["per_replica"]) == int(n)
    # the router genuinely spread the N=2 load over both replicas
    n2 = out["replica_counts"]["2"]["per_replica"]
    assert sum(1 for r in n2.values() if r["dispatches"] > 0) == 2, n2
    assert out["value"] == out["replica_counts"]["2"]["img_s"]
    assert out["scaling_1_to_max"] is not None
    assert out["host_cores"] >= 1


@pytest.mark.slow
def test_bench_decode_reports_measured_rows():
    """bench.py --decode --smoke: the decode-throughput harness
    (docs/data.md) packs a synthetic JPEG RecordIO file and drives the
    REAL multi-process DataService at 1/2/4 workers, emitting ONE JSON
    row of MEASURED img/s + MB/s per worker count — the row that
    retires the old extrapolated input-bound artifact.  Worker-process
    scaling is pinned where the host can actually show it (it
    saturates at the physical core count)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for knob in ("MXTPU_DATA_WORKERS", "MXTPU_DATA_RING_SLOTS",
                 "MXTPU_DATA_SLOT_BYTES", "MXTPU_DATA_HOST_INDEX",
                 "MXTPU_DATA_NUM_HOSTS"):
        env.pop(knob, None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--decode",
         "--smoke"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["smoke"] is True and out["unit"] == "img/s"
    assert out["measured"] is True
    assert set(out["workers"]) == {"1", "2", "4"}
    for row in out["workers"].values():
        assert row["img_s"] > 0 and row["mb_s"] > 0 and row["epochs"] >= 2
    assert out["value"] == out["workers"][str(out["best_workers"])]["img_s"]
    cores = os.cpu_count() or 1
    if cores >= 4:
        # the acceptance bar: >1.5x from 1 to 4 workers on a multi-core
        # host (decode is CPU-bound; 4 processes get >=4 real cores)
        assert out["scaling_1_to_max"] > 1.5, out
    elif cores >= 2:
        # oversubscribed hosts still must not collapse: the best count
        # beats a single worker
        assert out["scaling_1_to_best"] > 1.0, out


@pytest.mark.slow
def test_bench_smoke_honors_k_flag():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--smoke",
         "--steps-per-dispatch", "8"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["steps_per_dispatch"] == 8
    assert out["dispatches"] == out["expected_dispatches"] == 3  # ceil(24/8)


@pytest.mark.slow
def test_bench_ab_int8_serve_smoke():
    """bench.py --ab int8_serve --smoke: the inference-side A/B body
    (docs/perf.md "Int8 serving") runs a tiny bf16+int8 TENANT PAIR of
    one model through the real ModelServer fill path — calibration,
    quantize_symbol, mixed-tenant warmup, compile-free timed windows —
    and emits one JSON row with both sides' img/s, p50/p99, and the
    top-1 agreement column.  The same driver with ResNet-50 /
    Inception-v3 produces the README Roofline row."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for knob in ("MXTPU_QUANT_CALIB_MODE", "MXTPU_QUANT_PERCENTILE",
                 "MXTPU_QUANT_SKIP_FIRST_LAST", "MXTPU_SERVE_BUCKETS",
                 "MXTPU_SERVE_MAX_BATCH"):
        env.pop(knob, None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--ab",
         "int8_serve", "--smoke"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sink"] == "int8_serve" and out["smoke"] is True
    assert out["unit"] == "img/s"
    assert out["a"]["mode"] == "bf16" and out["b"]["mode"] == "int8"
    assert out["a"]["value"] > 0 and out["b"]["value"] > 0
    row = out["models"]["tiny"]
    assert row["compile_misses_timed"] == 0   # warmup owned every compile
    assert row["quantized_nodes"] > 0         # int8 nodes actually served
    assert row["requests"] > 0 and row["bucket"] > 0
    for side in ("bf16", "int8"):
        assert row[side]["img_s"] > 0
        assert row[side]["p99_ms"] >= row[side]["p50_ms"] > 0
    assert 0 <= row["top1_disagree_pct"] <= 50.0
    expect = round((out["b"]["value"] - out["a"]["value"])
                   / out["a"]["value"] * 100.0, 2)
    assert abs(out["delta_pct"] - expect) < 0.05


@pytest.mark.slow
def test_bench_ab_kv_decode_smoke():
    """bench.py --ab kv_decode --smoke: the KV-cache decode A/B body
    (docs/perf.md "KV-cache decode") runs matched greedy generation of
    a tiny TransformerLM — side A re-running the FULL prefix through
    the bucketed score forward per token, side B prefill + one
    KV-decode step per token — and emits one JSON row with both sides'
    tokens/s per decode target.  The same driver with the 512d 4-layer
    LM at T in {64, 256} produces the BENCH_TABLE row."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for knob in ("MXTPU_SERVE_MAX_SESSIONS", "MXTPU_SERVE_KV_MAX_LEN",
                 "MXTPU_SERVE_MAX_DECODE_TOKENS", "MXTPU_SERVE_BUCKETS"):
        env.pop(knob, None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--ab",
         "kv_decode", "--smoke"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sink"] == "kv_decode" and out["smoke"] is True
    assert out["unit"] == "tokens/s"
    assert out["a"]["mode"] == "recompute" and out["b"]["mode"] == "kv_cache"
    assert out["a"]["value"] > 0 and out["b"]["value"] > 0
    for T, sub in out["targets"].items():
        # the numerics pin the speedup may not buy back: greedy token
        # sequences agree EXACTLY, and the timed windows never compiled
        assert sub["match"] is True, (T, sub)
        assert sub["compile_misses_timed"] == 0, (T, sub)
        assert sub["tokens"] == int(T) - out["prompt_len"]
        assert sub["kv_tok_s"] > 0 and sub["recompute_tok_s"] > 0
    expect = round((out["b"]["value"] - out["a"]["value"])
                   / out["a"]["value"] * 100.0, 2)
    assert abs(out["delta_pct"] - expect) < 0.05


@pytest.mark.slow
def test_bench_serve_generate_smoke_reports_token_row():
    """bench.py --serve --generate --smoke: the mixed prefill/decode
    generative serving driver (docs/serving.md "Decode sessions &
    continuous batching") streams varied-length generations through a
    real Router -> ReplicaAgent -> GenerativeSession stack and emits
    ONE JSON row with tokens/s, request p50/p99, and the decode-loop
    health gauges.  The same driver with the 512d LM produces the
    BENCH_TABLE serving row."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for knob in ("MXTPU_SERVE_MAX_SESSIONS", "MXTPU_SERVE_KV_MAX_LEN",
                 "MXTPU_SERVE_MAX_DECODE_TOKENS",
                 "MXTPU_SERVE_DECODE_WINDOW_MS", "MXTPU_SERVE_BUCKETS"):
        env.pop(knob, None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--serve",
         "--generate", "--smoke"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["smoke"] is True and out["unit"] == "tokens/s"
    assert out["value"] > 0 and out["failed"] == 0
    # zero lost futures: every submitted generation retired, and the
    # end-to-end token count reconciles exactly against the decode
    # counter (+1 prefill-emitted token per session)
    assert out["retired"]["total"] == out["requests"]
    assert out["tokens"] == out["decode_tokens"] + out["retired"]["total"]
    assert out["decode_dispatches"] > 0
    assert out["p99_ms"] >= out["p50_ms"] > 0
    assert out["compile_misses_timed"] == 0
    assert out["batch_fill_ratio"] is not None
    assert out["kv_slot_occupancy"] is not None


@pytest.mark.slow
def test_bench_serve_smoke_lock_overhead_and_acyclic_graph():
    """bench.py --serve --smoke --lock-ab: the MXTPU_LOCK_CHECK
    sentinel pin (ISSUE 17 acceptance — zero order-graph cycles over
    the serving load and <5% throughput overhead).  Side A drives a
    plain server, side B a fresh one built with the sentinel armed;
    bench.py asserts the bars internally under --smoke, this pin keeps
    the harness from silently rotting."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MXTPU_LOCK_CHECK", None)
    env.pop("MXTPU_LOCK_CHECK_ACTION", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--serve",
         "--smoke", "--lock-ab"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sink"] == "lock_overhead" and out["smoke"] is True
    assert out["a"]["img_s"] > 0 and out["b"]["img_s"] > 0
    expect = round((out["a"]["img_s"] - out["b"]["img_s"])
                   / out["a"]["img_s"] * 100.0, 3)
    assert abs(out["overhead_pct"] - expect) < 0.05
    # the armed side really recorded: the order graph saw edges, the
    # hold histograms were booked, and no cycle exists over the load
    assert out["order_edges"] > 0
    assert out["lock_hists"], out
    assert out["order_cycles"] == 0
    assert out["compile_misses_timed"] == 0
    assert out["overhead_pct"] <= max(5.0, 2.0 * out["noise_pct"])


@pytest.mark.slow
def test_bench_ab_knobs_train_smoke():
    """bench.py --ab knobs --smoke: the generic knob-vector A/B
    (docs/perf.md "Autotuning") drives the REAL K-step fused dispatch
    path per side under validated env overlays and emits one JSON row
    with both vectors, per-side stdev, and the delta.  K=1 vs K=4 on
    the fused path is the canonical pair: the same driver produces the
    tuner's trial rows."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for knob in ("MXTPU_STEPS_PER_DISPATCH", "MXTPU_STAGE_BUFFERS"):
        env.pop(knob, None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--ab", "knobs",
         "--smoke", "--workload", "train",
         "--knobs-a", "MXTPU_STEPS_PER_DISPATCH=1",
         "--knobs-b", "MXTPU_STEPS_PER_DISPATCH=4"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sink"] == "knobs" and out["workload"] == "train"
    assert out["unit"] == "sample/s" and out["smoke"] is True
    assert out["knobs_a"] == {"MXTPU_STEPS_PER_DISPATCH": "1"}
    assert out["knobs_b"] == {"MXTPU_STEPS_PER_DISPATCH": "4"}
    for side in ("a", "b"):
        assert out[side]["value"] > 0 and out[side]["stdev"] >= 0
    assert isinstance(out["delta_pct"], float)
    # the overlays leaked nothing into the parent bench process's row
    assert "MXTPU_STEPS_PER_DISPATCH" not in env


@pytest.mark.slow
def test_bench_ab_knobs_serve_smoke():
    """bench.py --ab knobs --workload serve --smoke: the same generic
    A/B over the ModelServer fill path — the serve-side knob vector
    (batch ceiling + fill wait) governs the row."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for knob in ("MXTPU_SERVE_MAX_BATCH", "MXTPU_SERVE_WAIT_MS",
                 "MXTPU_SERVE_BUCKETS"):
        env.pop(knob, None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--ab", "knobs",
         "--smoke", "--workload", "serve",
         "--knobs-a", "",
         "--knobs-b", "MXTPU_SERVE_MAX_BATCH=64,MXTPU_SERVE_WAIT_MS=0.5"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sink"] == "knobs" and out["workload"] == "serve"
    assert out["unit"] == "req/s" and out["smoke"] is True
    assert out["knobs_a"] == {}
    assert out["knobs_b"] == {"MXTPU_SERVE_MAX_BATCH": "64",
                              "MXTPU_SERVE_WAIT_MS": "0.5"}
    for side in ("a", "b"):
        assert out[side]["value"] > 0 and out[side]["stdev"] >= 0
