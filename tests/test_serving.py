"""mxnet_tpu.serving — the continuous-batching inference engine.

The acceptance pins (ISSUE 7 / ROADMAP open item 1): batched outputs
are allclose to per-request Predictor.forward for EVERY bucket and
partial-fill size, a (tenant, bucket) program compiles exactly once
across repeated fills (telemetry-verified), deadlines/admission/drain
behave, the oldest-deadline-first policy keeps tenants fair, the
pipeline is SanitizerEngine-clean under concurrent submitters, and the
serving telemetry renders through parse_log and the chrome trace.
Everything runs on CPU with tiny MLP tenants.
"""
import json
import os
import sys
import threading
import time
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine, serving, telemetry
from mxnet_tpu.serving import (AdmissionError, RequestTimeout, ServerClosed,
                               bucket_ladder, choose_bucket, pad_rows)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _mlp(hidden, classes, seed):
    mx.random.seed(seed)
    data = mx.sym.Variable("data")
    h = mx.sym.Activation(
        mx.sym.FullyConnected(data, num_hidden=hidden, name="fc1"),
        act_type="relu")
    return mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(h, num_hidden=classes, name="fc2"),
        name="softmax")


def _predictor(net, sample=(12,), ctx=None, output_names=None):
    """Predictor from a randomly-initialized checkpoint of `net`,
    bound at batch 1 (serving rebinds per bucket)."""
    ctx = ctx or mx.cpu()
    mod = mx.mod.Module(net, context=ctx)
    mod.bind(data_shapes=[("data", (1,) + sample)], label_shapes=None,
             for_training=False)
    mod.init_params(mx.init.Xavier())
    arg, aux = mod.get_params()
    params = {"arg:%s" % k: v for k, v in arg.items()}
    params.update({"aux:%s" % k: v for k, v in aux.items()})
    return mx.Predictor(net, params, {"data": (1,) + sample}, ctx=ctx,
                        output_names=output_names)


def _rows(n, dim=12, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randn(dim).astype("float32") for _ in range(n)]


# ----------------------------------------------------------------------
# bucket ladder math
# ----------------------------------------------------------------------

def test_bucket_ladder_and_choice():
    assert bucket_ladder(8) == [1, 2, 4, 8]
    assert bucket_ladder(12) == [1, 2, 4, 8, 12]  # top always included
    assert bucket_ladder(1) == [1]
    assert bucket_ladder(16, "2,8") == [2, 8, 16]
    ladder = bucket_ladder(8)
    assert choose_bucket(ladder, 1) == 1
    assert choose_bucket(ladder, 3) == 4
    assert choose_bucket(ladder, 8) == 8
    assert choose_bucket(ladder, 99) == 8  # caller caps at max_batch
    with pytest.raises(mx.MXNetError, match="exceeds"):
        bucket_ladder(8, "4,16")
    with pytest.raises(mx.MXNetError, match="comma"):
        bucket_ladder(8, "4,banana")


def test_pad_rows_rejects_batched_samples():
    out = pad_rows(_rows(3), 4, (12,), np.float32)
    assert out.shape == (4, 12) and not out[3].any()
    with pytest.raises(mx.MXNetError, match="sample shape"):
        pad_rows([np.zeros((1, 12), "f")], 2, (12,), np.float32)


# ----------------------------------------------------------------------
# result parity: every bucket, every partial-fill size
# ----------------------------------------------------------------------

def test_parity_every_bucket_and_partial_fill():
    """The acceptance pin: for every fill size 1..max_batch (hitting
    every ladder bucket full AND partial), each request's result is
    allclose to a direct per-request Predictor.forward — padding rows
    never leak into a caller's answer."""
    pred = _predictor(_mlp(16, 5, 0))
    ref = _predictor(_mlp(16, 5, 0))  # same seed -> identical params
    server = serving.ModelServer({"m": pred}, max_batch=8, wait_ms=60,
                                 timeout_ms=60000)
    try:
        for n in (1, 2, 3, 4, 5, 7, 8):
            xs = _rows(n, seed=n)
            futs = [server.submit("m", {"data": x}) for x in xs]
            for x, f in zip(xs, futs):
                out = f.result(timeout=120)
                expect = ref.forward(data=x[None]).get_output(0)[0]
                assert isinstance(out, list) and len(out) == 1
                assert out[0].shape == expect.shape
                assert np.allclose(out[0], expect, atol=1e-5), n
    finally:
        server.close()


def test_multi_output_tenant_returns_one_array_per_output():
    outs = ["fc2_output", "softmax_output"]
    pred = _predictor(_mlp(16, 5, 3), output_names=outs)
    ref = _predictor(_mlp(16, 5, 3), output_names=outs)
    server = serving.ModelServer({"m": pred}, max_batch=4, wait_ms=20)
    try:
        x = _rows(1, seed=9)[0]
        out = server.submit("m", {"data": x}).result(timeout=120)
        assert len(out) == 2
        ref.forward(data=x[None])
        for i in range(2):
            assert np.allclose(out[i], ref.get_output(i)[0], atol=1e-5)
    finally:
        server.close()


# ----------------------------------------------------------------------
# compile-once-per-bucket (telemetry-verified)
# ----------------------------------------------------------------------

def test_bucket_program_compiles_once_across_fills():
    pred = _predictor(_mlp(16, 5, 0))
    server = serving.ModelServer({"m": pred}, max_batch=8, wait_ms=40,
                                 timeout_ms=60000)
    try:
        def round_trip(n, seed):
            futs = [server.submit("m", {"data": x})
                    for x in _rows(n, seed=seed)]
            for f in futs:
                f.result(timeout=120)

        round_trip(3, 0)  # first bucket-4 fill: binds + compiles
        programs0 = telemetry.counter_value("serving.bucket_programs")
        misses0 = telemetry.counter_value("executor.compile_cache_misses")
        hits0 = telemetry.counter_value("executor.compile_cache_hits")
        for seed in range(1, 4):  # three more bucket-4 fills (sizes 3, 4)
            round_trip(3, seed)
        round_trip(4, 9)
        assert telemetry.counter_value("serving.bucket_programs") == programs0
        assert telemetry.counter_value("executor.compile_cache_misses") == misses0
        assert telemetry.counter_value("executor.compile_cache_hits") >= hits0 + 4
    finally:
        server.close()


# ----------------------------------------------------------------------
# deadlines, admission, drain
# ----------------------------------------------------------------------

def test_queued_request_past_deadline_times_out():
    pred = _predictor(_mlp(8, 3, 1))
    # a LONG batching window: the lone request cannot fill a batch, so
    # only its deadline can ripen it — the timeout path, not a dispatch
    server = serving.ModelServer({"m": pred}, max_batch=8, wait_ms=5000)
    try:
        t0 = telemetry.counter_value("serving.timeouts")
        fut = server.submit("m", {"data": _rows(1)[0]}, timeout_ms=40)
        with pytest.raises(RequestTimeout, match="deadline"):
            fut.result(timeout=60)
        assert telemetry.counter_value("serving.timeouts") == t0 + 1
    finally:
        server.close(drain=False)


def test_admission_control_rejects_when_full():
    pred = _predictor(_mlp(8, 3, 1))
    server = serving.ModelServer({"m": pred}, max_batch=8, wait_ms=5000,
                                 max_queue=2, timeout_ms=60000)
    try:
        r0 = telemetry.counter_value("serving.rejected")
        x = _rows(1)[0]
        server.submit("m", {"data": x})
        server.submit("m", {"data": x})
        with pytest.raises(AdmissionError, match="MXTPU_SERVE_MAX_QUEUE"):
            server.submit("m", {"data": x})
        assert telemetry.counter_value("serving.rejected") == r0 + 1
        with pytest.raises(mx.MXNetError, match="unknown tenant"):
            server.submit("nope", {"data": x})
    finally:
        server.close(drain=False)


def test_warmup_precompiles_every_bucket():
    """ModelServer.warmup() visits every (tenant, bucket) program, so
    traffic after it never compiles (what a cell's `correct` demands
    of its timed window)."""
    pred = _predictor(_mlp(16, 5, 0))
    server = serving.ModelServer({"m": pred}, max_batch=8, wait_ms=20,
                                 timeout_ms=60000)
    try:
        assert server.warmup() == len(server.ladder)
        misses0 = telemetry.counter_value("executor.compile_cache_misses")
        futs = [server.submit("m", {"data": x}) for x in _rows(5, seed=8)]
        for f in futs:
            f.result(timeout=120)
        assert telemetry.counter_value(
            "executor.compile_cache_misses") == misses0
    finally:
        server.close()


def test_cancelled_request_does_not_kill_the_batcher():
    """A caller-cancelled future whose deadline then expires must not
    raise InvalidStateError inside the batcher — later requests are
    still served."""
    pred = _predictor(_mlp(8, 3, 1))
    server = serving.ModelServer({"m": pred}, max_batch=8, wait_ms=40)
    try:
        fut = server.submit("m", {"data": _rows(1)[0]}, timeout_ms=30)
        assert fut.cancel()  # still queued: cancellable
        out = server.submit("m", {"data": _rows(1)[0]},
                            timeout_ms=60000).result(timeout=120)
        assert out[0].shape == (3,)
    finally:
        server.close()


def test_inputs_are_snapshotted_at_submit():
    """submit() snapshots the request arrays (the engine-operand
    discipline): a caller refilling its buffer right after submit()
    must not corrupt the in-flight request."""
    pred = _predictor(_mlp(16, 5, 0))
    ref = _predictor(_mlp(16, 5, 0))
    server = serving.ModelServer({"m": pred}, max_batch=8, wait_ms=50,
                                 timeout_ms=60000)
    try:
        x = _rows(1, seed=11)[0]
        keep = x.copy()
        fut = server.submit("m", {"data": x})
        x[:] = 0.0  # caller reuses its buffer inside the batching window
        out = fut.result(timeout=120)
        expect = ref.forward(data=keep[None]).get_output(0)[0]
        assert np.allclose(out[0], expect, atol=1e-5)
    finally:
        server.close()


def test_malformed_request_fails_at_submit_not_the_fill():
    """Validation runs at submit() time: a bad request fails ITS caller
    immediately and never reaches a fill where its error would fail
    every co-batched request."""
    pred = _predictor(_mlp(16, 5, 0))
    server = serving.ModelServer({"m": pred}, max_batch=4, wait_ms=30,
                                 timeout_ms=60000)
    try:
        with pytest.raises(mx.MXNetError, match="sample shape"):
            server.submit("m", {"data": np.zeros((1, 12), "f")})  # batched
        with pytest.raises(mx.MXNetError, match="missing input"):
            server.submit("m", {"wrong": np.zeros(12, "f")})
        # a well-formed request in the same window is unaffected
        out = server.submit("m", {"data": _rows(1)[0]}).result(timeout=120)
        assert out[0].shape == (5,)
    finally:
        server.close()


def test_close_drains_pending_futures():
    pred = _predictor(_mlp(16, 5, 0))
    ref = _predictor(_mlp(16, 5, 0))
    # window long enough that requests are still QUEUED when close() runs
    server = serving.ModelServer({"m": pred}, max_batch=8, wait_ms=5000,
                                 timeout_ms=60000)
    xs = _rows(5, seed=2)
    futs = [server.submit("m", {"data": x}) for x in xs]
    server.close()  # drain=True: queued work completes
    for x, f in zip(xs, futs):
        out = f.result(timeout=1)  # already resolved by close()
        assert np.allclose(out[0],
                           ref.forward(data=x[None]).get_output(0)[0],
                           atol=1e-5)
    with pytest.raises(ServerClosed):
        server.submit("m", {"data": xs[0]})
    server.close()  # idempotent


def test_close_without_drain_fails_queued_requests():
    pred = _predictor(_mlp(8, 3, 1))
    server = serving.ModelServer({"m": pred}, max_batch=8, wait_ms=5000,
                                 timeout_ms=60000)
    futs = [server.submit("m", {"data": x}) for x in _rows(3)]
    server.close(drain=False)
    for f in futs:
        with pytest.raises(ServerClosed, match="drain=False"):
            f.result(timeout=10)


# ----------------------------------------------------------------------
# fairness: oldest-deadline-first across tenants
# ----------------------------------------------------------------------

def test_next_work_picks_oldest_deadline_head():
    """Unit pin on the policy itself (no threads): among ripe tenants
    the head with the earliest deadline wins; empty queues and the
    drain path behave."""
    from mxnet_tpu.serving.request import Request, RequestQueue

    q = RequestQueue(100)
    q.register("a")
    q.register("b")
    ra = Request("a", {}, timeout_s=60.0)
    rb = Request("b", {}, timeout_s=0.5)  # later arrival, EARLIER deadline
    q.put(ra)
    q.put(rb)
    assert q.next_work(wait_s=0.0, max_batch=8, stopping=lambda: False) == "b"
    assert [r is rb for r in q.take("b", 8)] == [True]
    assert q.next_work(wait_s=0.0, max_batch=8, stopping=lambda: False) == "a"
    q.take("a", 8)
    assert q.next_work(wait_s=0.0, max_batch=8, stopping=lambda: True) is None


def test_flooding_tenant_cannot_starve_another():
    """Integration: tenant A floods 24 requests; B submits ONE with a
    tighter deadline after the flood.  Oldest-deadline-first must serve
    B before A's tail drains."""
    pa = _predictor(_mlp(16, 5, 0))
    pb = _predictor(_mlp(8, 3, 1))
    server = serving.ModelServer({"a": pa, "b": pb}, max_batch=4,
                                 wait_ms=0, timeout_ms=120000)
    try:
        done = []

        def note(tag):
            return lambda f: done.append((tag, time.monotonic()))

        a_futs = [server.submit("a", {"data": x})
                  for x in _rows(24, seed=0)]
        for f in a_futs:
            f.add_done_callback(note("a"))
        b_fut = server.submit("b", {"data": _rows(1, seed=1)[0]},
                              timeout_ms=1000)
        b_fut.add_done_callback(note("b"))
        b_fut.result(timeout=120)
        for f in a_futs:
            f.result(timeout=120)
        b_time = next(t for tag, t in done if tag == "b")
        a_times = [t for tag, t in done if tag == "a"]
        # B (earliest outstanding deadline) finished before A's backlog
        assert b_time < max(a_times)
    finally:
        server.close()


# ----------------------------------------------------------------------
# concurrency: SanitizerEngine-clean under parallel submitters
# ----------------------------------------------------------------------

def test_concurrent_submitters_sanitizer_clean():
    """4 client threads hammer 2 tenants while the SanitizerEngine
    watches every chunk access: the staging/readback pipeline must
    declare everything it touches (zero violations) AND every result
    must still be exact."""
    from mxnet_tpu.engine.sanitizer import RaceWarning

    prev = engine.get().kind
    try:
        eng = engine.set_engine_type("SanitizerEngine", num_workers=4)
        with warnings.catch_warnings():
            warnings.simplefilter("error", RaceWarning)
            pa = _predictor(_mlp(16, 5, 0))
            ref = _predictor(_mlp(16, 5, 0))
            pb = _predictor(_mlp(8, 3, 1))
            server = serving.ModelServer({"a": pa, "b": pb}, max_batch=4,
                                         wait_ms=2, timeout_ms=120000)
            try:
                errors = []
                # the REFERENCE predictor is a single-caller API (that
                # is the point of this PR): serialize the ref checks
                ref_lock = threading.Lock()

                def client(tenant, seed):
                    xs = _rows(8, seed=seed)
                    for x in xs:
                        out = server.submit(tenant, {"data": x}) \
                            .result(timeout=120)
                        if tenant == "a":
                            with ref_lock:
                                expect = ref.forward(
                                    data=x[None]).get_output(0)[0]
                            if not np.allclose(out[0], expect, atol=1e-5):
                                errors.append("parity")

                threads = [threading.Thread(target=client,
                                            args=("a" if i % 2 else "b", i))
                           for i in range(4)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                assert not errors
            finally:
                server.close()
            mx.waitall()
        assert eng.violations == []
    finally:
        engine.set_engine_type(prev)


# ----------------------------------------------------------------------
# telemetry: books balance, lanes render, parse_log columns
# ----------------------------------------------------------------------

def test_serving_telemetry_books_balance():
    telemetry.reset()
    pred = _predictor(_mlp(16, 5, 0))
    server = serving.ModelServer({"m": pred}, max_batch=8, wait_ms=30,
                                 timeout_ms=60000)
    try:
        futs = [server.submit("m", {"data": x}) for x in _rows(5, seed=4)]
        for f in futs:
            f.result(timeout=120)
    finally:
        server.close()
    snap = telemetry.snapshot()
    c, g, h = snap["counters"], snap["gauges"], snap["histograms"]
    assert c["serving.requests"] == 5
    assert c["serving.requests.m"] == 5
    assert c["serving.batch_slots_used"] == 5
    # used + padded = sum of dispatched bucket sizes (every slot accounted)
    assert (c["serving.batch_slots_used"]
            + c.get("serving.batch_slots_padded", 0)) >= 5
    assert c["serving.dispatches"] >= 1
    assert c["serving.bucket_programs"] >= 1
    assert 0 < g["serving.batch_fill_ratio"] <= 1
    assert g["serving.queue_depth"] == 0  # drained
    assert h["serving.request_seconds"]["count"] == 5
    assert h["serving.request_seconds.m"]["count"] == 5
    # the staging leg rode the shared io books (io.stage_put)
    assert c["io.stage_bytes"] > 0


def test_serving_lanes_render_in_trace(tmp_path):
    from mxnet_tpu import profiler

    pred = _predictor(_mlp(16, 5, 0))
    fname = str(tmp_path / "serve_profile.json")
    profiler.profiler_set_config(mode="all", filename=fname)
    profiler.profiler_set_state("run")
    server = serving.ModelServer({"m": pred}, max_batch=4, wait_ms=10,
                                 timeout_ms=60000)
    try:
        futs = [server.submit("m", {"data": x}) for x in _rows(6, seed=5)]
        for f in futs:
            f.result(timeout=120)
    finally:
        server.close()
        profiler.profiler_set_state("stop")
    profiler.dump_profile()
    with open(fname) as f:
        events = json.load(f)["traceEvents"]
    spans = {e["name"] for e in events if e.get("ph") == "X"}
    counters = {e["name"] for e in events if e.get("ph") == "C"}
    assert "serve.dispatch" in spans, spans
    assert "engine::serve_stage" in spans
    assert "engine::serve_readback" in spans
    # the per-tenant backlog and fill ratio render as counter lanes
    # beside the dispatch spans (docs/observability.md)
    assert "serving.queue_depth" in counters
    assert "serving.batch_fill_ratio" in counters


def test_parse_log_renders_serving_columns():
    from tools.parse_log import parse_telemetry

    serving_rec = {
        "flush_seq": 1, "step": 0,
        "counters": {"serving.batch_slots_used": 30,
                     "serving.batch_slots_padded": 10},
        "gauges": {"serving.queue_depth": 3.0},
        "histograms": {"serving.request_seconds": {
            "count": 4, "sum": 0.2, "min": 0.01, "max": 0.09,
            "buckets": {"le_0.01": 1, "le_0.1": 3, "le_inf": 0}}},
    }
    legacy_rec = {"flush_seq": 2, "step": 5, "counters": {},
                  "gauges": {}, "histograms": {}}
    rows = parse_telemetry([json.dumps(serving_rec), json.dumps(legacy_rec)])
    assert rows[0]["serve_qdepth"] == 3.0
    assert abs(rows[0]["fill_pct"] - 75.0) < 1e-9
    assert rows[0]["req_p99"] == pytest.approx(0.1)
    # pre-serving records render '-' (None) in the new columns
    assert rows[1]["serve_qdepth"] is None
    assert rows[1]["fill_pct"] is None
    assert rows[1]["req_p99"] is None


def test_parse_log_renders_decode_columns():
    """`parse_log --telemetry` renders the generative decode lane:
    tokens_s is cumulative decode tokens over summed step time,
    active_sessions / kv_slot_occupancy are the loop gauges — and
    pre-decode logs (no serving.decode.* namespace) render '-' (None)
    in all three columns."""
    from tools.parse_log import _TELEMETRY_COLS, parse_telemetry

    decode_rec = {
        "flush_seq": 1, "step": 0,
        "counters": {"serving.decode.tokens": 120,
                     "serving.decode.dispatches": 40},
        "gauges": {"serving.decode.active_sessions": 3.0,
                   "kv.slot_occupancy": 0.75},
        "histograms": {"serving.decode.step_seconds": {
            "count": 40, "sum": 0.5, "min": 0.01, "max": 0.02,
            "buckets": {"le_0.1": 40, "le_inf": 0}}},
    }
    legacy_rec = {"flush_seq": 2, "step": 5, "counters": {},
                  "gauges": {}, "histograms": {}}
    rows = parse_telemetry([json.dumps(decode_rec), json.dumps(legacy_rec)])
    assert rows[0]["tokens_s"] == pytest.approx(240.0)
    assert rows[0]["active_sessions"] == 3.0
    assert rows[0]["kv_slot_occupancy"] == 0.75
    assert rows[1]["tokens_s"] is None
    assert rows[1]["active_sessions"] is None
    assert rows[1]["kv_slot_occupancy"] is None
    for col in ("tokens_s", "active_sessions", "kv_slot_occupancy"):
        assert col in _TELEMETRY_COLS


# ----------------------------------------------------------------------
# Predictor hygiene (the serving sessions depend on both)
# ----------------------------------------------------------------------

def test_predictor_close_is_idempotent_and_final():
    pred = _predictor(_mlp(16, 5, 0))
    x = _rows(1)[0]
    pred.forward(data=x[None])
    pred.close()
    pred.close()  # idempotent
    for call in (lambda: pred.forward(data=x[None]),
                 lambda: pred.get_output(0),
                 lambda: pred.get_output_shape(0),
                 lambda: pred.reshape({"data": (2, 12)}),
                 lambda: pred.num_outputs):
        with pytest.raises(mx.MXNetError, match="closed"):
            call()


def test_predictor_reshape_reuses_cached_executor():
    pred = _predictor(_mlp(16, 5, 0))
    x = _rows(4, seed=6)
    first = pred._exec
    out1 = pred.forward(data=x[0][None]).get_output(0)
    pred.reshape({"data": (2, 12)})
    assert pred._exec is not first
    misses0 = telemetry.counter_value("predict.bind_cache_misses")
    hits0 = telemetry.counter_value("predict.bind_cache_hits")
    pred.reshape({"data": (1, 12)})  # seen signature: cache hit
    assert pred._exec is first
    assert telemetry.counter_value("predict.bind_cache_misses") == misses0
    assert telemetry.counter_value("predict.bind_cache_hits") == hits0 + 1
    # the cached executor still answers (and kept its jit cache warm)
    out2 = pred.forward(data=x[0][None]).get_output(0)
    assert np.allclose(out1, out2)
