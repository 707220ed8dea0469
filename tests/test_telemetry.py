"""mxnet_tpu.telemetry — framework-wide metrics registry.

Pins the observability contracts: zero registry mutation when disabled
(the enabled() fast-path promise), snapshot schema stability, the
acceptance run (10-step CPU fit reports step-time histogram,
compile-cache traffic, io wait, and an MFU gauge), JSONL round-trip
through tools/parse_log.py, and counter lanes ("ph": "C") in the
dumped chrome trace.
"""
import json
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler, telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture(autouse=True)
def _fresh_registry():
    """Each test starts from an empty, enabled registry and leaves the
    process-wide state the way it found it."""
    prev = telemetry.set_enabled(True)
    telemetry.reset()
    yield
    telemetry.reset()
    telemetry.set_enabled(prev)


def _mlp_fit(nsteps=10, batch=16, steps_per_dispatch=None, prefetch=False):
    """10-step (by default) CPU Module.fit through the real training
    path; returns the module."""
    rng = np.random.RandomState(0)
    X = rng.rand(batch * nsteps, 10).astype(np.float32)
    y = rng.randint(0, 3, batch * nsteps).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=batch)
    if prefetch:
        it = mx.io.PrefetchingIter(it)
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=8),
        name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    kwargs = {}
    if steps_per_dispatch is not None:
        kwargs["steps_per_dispatch"] = steps_per_dispatch
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05}, **kwargs)
    mx.waitall()
    if prefetch:
        it.close()
    return mod


# ----------------------------------------------------------------------
# the acceptance run — ONE 10-step fit drives all three sinks (snapshot,
# JSONL file, chrome counter lanes), keeping tier-1 wall time down
# ----------------------------------------------------------------------

def test_fit_populates_registry_and_all_sinks(tmp_path, monkeypatch):
    """10-step CPU fit: step-time histogram with count == steps,
    compile-cache hit/miss counters, io wait-time, MFU gauge — plus the
    JSONL epoch record and ≥2 counter lanes in the dumped trace."""
    jsonl = str(tmp_path / "fit.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_FILE", jsonl)
    # the CPU device has no entry in telemetry.PEAK_FLOPS: the MFU gauge
    # (and its trace lane) exists here only because a peak is declared
    monkeypatch.setenv("MXTPU_PEAK_FLOPS", "1e12")
    prof = str(tmp_path / "prof.json")
    profiler.profiler_set_config(mode="all", filename=prof)
    profiler.profiler_set_state("run")
    _mlp_fit(nsteps=10, prefetch=True)
    profiler.profiler_set_state("stop")
    profiler.dump_profile()
    snap = telemetry.snapshot()

    hist = snap["histograms"]["module.step_seconds"]
    assert hist["count"] == 10
    assert hist["sum"] > 0 and hist["min"] >= 0
    assert snap["counters"]["module.steps"] == 10
    assert snap["counters"]["executor.train_dispatches"] == 10

    # ONE compile for the fused step, then cache hits every step after
    assert snap["counters"]["executor.compile_cache_misses"] >= 1
    assert snap["counters"]["executor.compile_cache_hits"] >= 8

    # the engine-backed prefetch pipeline reported consumer wait and
    # buffer occupancy
    assert snap["histograms"]["io.consumer_wait_seconds"]["count"] > 0
    assert any(k.startswith("io.buffer.prefetch") for k in snap["gauges"])

    # bytes moved both ways
    assert snap["counters"]["executor.h2d_bytes"] > 0
    assert snap["counters"]["executor.d2h_bytes"] > 0

    mfu = snap["gauges"]["module.mfu"]
    assert 0.0 < mfu <= 1.0

    # sink 2: fit flushed one JSONL record per epoch
    with open(jsonl) as f:
        recs = [json.loads(line) for line in f]
    assert len(recs) >= 1
    assert recs[-1]["step"] == 10
    assert recs[-1]["histograms"]["module.step_seconds"]["count"] == 10

    # sink 3: gauges rendered as chrome counter lanes beside the spans
    with open(prof) as f:
        events = json.load(f)["traceEvents"]
    counters = [e for e in events if e["ph"] == "C"]
    series = {e["name"] for e in counters}
    assert len(series) >= 2, series
    assert "module.mfu" in series
    for e in counters:
        assert "value" in e["args"] and e["ts"] > 0
    assert any(e["ph"] == "X" for e in events)


def test_fit_block_dispatch_histogram_counts_dispatches():
    """With steps_per_dispatch=K the step-time histogram counts
    ceil(steps/K) dispatches and the block latency lane is used."""
    _mlp_fit(nsteps=8, steps_per_dispatch=4)
    snap = telemetry.snapshot()
    assert snap["histograms"]["module.step_seconds"]["count"] == 2
    assert snap["counters"]["module.steps"] == 8
    assert snap["counters"]["executor.train_dispatches"] == 2
    assert snap["histograms"]["executor.dispatch_seconds.block"]["count"] == 2
    assert snap["counters"]["io.blocks_staged"] == 2
    # no peak is known for the CPU device and none was declared: the MFU
    # gauge is not published (never divided by another chip's peak)
    assert "module.mfu" not in snap["gauges"]
    # H2D counted where transfers happen and EXACTLY once per transfer:
    # per-batch nd.array creation in NDArrayIter (8 x (16,10)+(16,)) and
    # nothing else — a block is assembled on the device from the batches
    # that are there already, and the dispatch re-places it for free
    per_batch = 8 * (16 * 10 + 16) * 4
    assert snap["counters"]["executor.h2d_bytes"] == per_batch
    # ...and the books balance: staging reads the labels alone back (the
    # per-step label_host copies), the dispatch the stacked metric output
    label_host_readback = 8 * 16 * 4
    metric_readback = 2 * (4 * 16 * 8) * 4  # (K, batch, num_hidden) fp32
    assert snap["counters"]["executor.d2h_bytes"] == (
        label_host_readback + metric_readback)
    # every step array (8 steps x data + label) arrived on the device
    assert snap["counters"]["io.stage.device_parts"] == 16
    assert snap["counters"].get("io.stage.host_parts", 0) == 0
    # block-size distribution landed in the BYTE_BUCKETS histogram
    assert snap["histograms"]["io.stage_block_bytes"]["count"] == 4


def test_gauge_set_by_a_finalizer_under_the_registry_lock_does_not_deadlock():
    """What runs under the registry lock allocates (snapshot's dicts), an
    allocation can run the GC, and a collected NDArray's __del__ unbooks
    through set_gauge on that same thread."""
    import threading

    done = []

    def body():
        with telemetry._LOCK:
            telemetry.set_gauge("mem.live_bytes", 1.0)
        done.append(True)

    t = threading.Thread(target=body, daemon=True)
    t.start()
    t.join(10)
    assert done


# ----------------------------------------------------------------------
# disabled-by-flag: zero overhead, untouched registry
# ----------------------------------------------------------------------

def test_disabled_run_leaves_registry_untouched():
    """MXTPU_TELEMETRY=0 semantics: a full hot-path run mutates NOTHING
    in the registry — the enabled() guard keeps every layer out."""
    telemetry.set_enabled(False)
    _mlp_fit(nsteps=3, prefetch=True)
    snap = telemetry.snapshot()
    assert snap == {"counters": {}, "gauges": {}, "histograms": {}}


def test_disabled_helpers_are_noops():
    telemetry.set_enabled(False)
    telemetry.inc("c")
    telemetry.set_gauge("g", 1.0)
    telemetry.observe("h", 0.5)
    assert telemetry.flush("/nonexistent/should/never/open") is None
    telemetry.set_enabled(True)
    assert telemetry.snapshot() == {"counters": {}, "gauges": {},
                                    "histograms": {}}


def test_env_var_disables_at_import():
    """MXTPU_TELEMETRY=0 in the environment turns recording off at
    import time (subprocess: import-time state is per-process; the
    module file is loaded standalone — stdlib only — so this does not
    pay a full jax import in tier-1)."""
    import subprocess

    tpath = os.path.join(ROOT, "mxnet_tpu", "telemetry.py")
    code = ("import importlib.util\n"
            "spec = importlib.util.spec_from_file_location('t', %r)\n"
            "t = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(t)\n"
            "t.inc('x')\n"
            "t.observe('h', 1.0)\n"
            "assert not t.enabled()\n"
            "assert t.snapshot() == {'counters': {}, 'gauges': {},"
            " 'histograms': {}}\n"
            "print('ok')\n" % tpath)
    env = dict(os.environ, MXTPU_TELEMETRY="0")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, env=env, cwd=ROOT)
    assert r.returncode == 0 and "ok" in r.stdout, r.stdout + r.stderr


# ----------------------------------------------------------------------
# snapshot schema stability
# ----------------------------------------------------------------------

def test_snapshot_schema():
    telemetry.inc("layer.count", 2)
    telemetry.inc("layer.count")
    telemetry.set_gauge("layer.gauge", 7.5)
    telemetry.observe("layer.hist", 0.02)
    telemetry.observe("layer.hist", 123.0)  # lands in the overflow bucket
    snap = telemetry.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    assert snap["counters"]["layer.count"] == 3
    assert snap["gauges"]["layer.gauge"] == 7.5
    h = snap["histograms"]["layer.hist"]
    assert set(h) == {"count", "sum", "min", "max", "buckets"}
    assert h["count"] == 2 and h["min"] == 0.02 and h["max"] == 123.0
    assert h["buckets"]["le_inf"] == 1
    assert sum(h["buckets"].values()) == h["count"]
    # snapshot is a copy: mutating it does not write back
    snap["counters"]["layer.count"] = 999
    assert telemetry.counter_value("layer.count") == 3


def test_histogram_fixed_boundaries():
    telemetry.observe("t", 2e-5)   # second bucket of TIME_BUCKETS
    h = telemetry.snapshot()["histograms"]["t"]
    keys = list(h["buckets"])
    assert keys[0] == "le_1e-05" and keys[-1] == "le_inf"
    assert h["buckets"]["le_3.16e-05"] == 1


# ----------------------------------------------------------------------
# JSONL sink round-trip through tools/parse_log.py
# ----------------------------------------------------------------------

def test_jsonl_roundtrip_through_parse_log(tmp_path):
    from tools.parse_log import parse_telemetry

    path = str(tmp_path / "telemetry.jsonl")
    telemetry.inc("module.steps", 4)
    telemetry.observe("module.step_seconds", 0.02)
    telemetry.set_gauge("module.mfu", 0.31)
    telemetry.inc("executor.train_dispatches", 4)
    rec1 = telemetry.flush(path)
    telemetry.inc("module.steps", 4)
    rec2 = telemetry.flush(path, extra={"epoch": 1})
    assert rec1["flush_seq"] == 1 and rec2["flush_seq"] == 2
    assert rec2["monotonic_s"] >= rec1["monotonic_s"]
    assert rec1["step"] == 4 and rec2["step"] == 8

    with open(path) as f:
        lines = f.readlines()
    assert len(lines) == 2
    rows = parse_telemetry(lines)
    assert [r["flush_seq"] for r in rows] == [1, 2]
    assert rows[0]["step"] == 4 and rows[1]["step"] == 8
    assert rows[0]["mfu"] == 0.31
    assert rows[0]["dispatches"] == 4
    assert rows[1]["epoch"] == 1
    assert rows[0]["step_p50"] is not None


def test_parse_log_telemetry_cli(tmp_path):
    import subprocess

    path = str(tmp_path / "t.jsonl")
    telemetry.inc("module.steps", 3)
    telemetry.observe("module.step_seconds", 0.01)
    telemetry.flush(path)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "parse_log.py"),
         "--telemetry", path],
        capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert r.returncode == 0, r.stderr
    assert "step_p50" in r.stdout and "| 3 |" in r.stdout.replace(" 3 ", " 3 ")


# ----------------------------------------------------------------------
# counter lanes in the chrome trace (the fit-driven lane assertions live
# in test_fit_populates_registry_and_all_sinks)
# ----------------------------------------------------------------------

def test_gauge_emits_no_counter_event_when_profiler_off(tmp_path):
    fname = str(tmp_path / "prof2.json")
    telemetry.set_gauge("g.off", 1.0)  # profiler not running
    profiler.profiler_set_config(mode="all", filename=fname)
    profiler.profiler_set_state("run")
    telemetry.set_gauge("g.on", 2.0)
    profiler.profiler_set_state("stop")
    profiler.dump_profile()
    with open(fname) as f:
        events = json.load(f)["traceEvents"]
    names = [e["name"] for e in events if e["ph"] == "C"]
    assert names == ["g.on"]


# ----------------------------------------------------------------------
# MFU machinery
# ----------------------------------------------------------------------

def test_flops_estimator_counts_matmul():
    """dot_general FLOPs from the jaxpr: (B,I)x(I,O) = 2*B*I*O."""
    import jax
    import jax.numpy as jnp

    a = jnp.zeros((4, 10))
    b = jnp.zeros((10, 3))
    jaxpr = jax.make_jaxpr(lambda x, y: x @ y)(a, b)
    assert telemetry.flops_of_jaxpr(jaxpr) == 2 * 4 * 10 * 3


def test_flops_estimator_scales_scan_by_length():
    import jax
    import jax.numpy as jnp

    def body(c, _):
        return c @ c, None

    def f(x):
        out, _ = jax.lax.scan(body, x, None, length=5)
        return out

    jaxpr = jax.make_jaxpr(f)(jnp.zeros((8, 8)))
    assert telemetry.flops_of_jaxpr(jaxpr) == 5 * 2 * 8 * 8 * 8


def test_executor_flops_per_step_positive():
    """Binding alone is enough — flops_per_step only traces (make_jaxpr),
    it never compiles or runs device code, and it must not seed the
    executable cache (the first real forward is still a compile MISS)."""
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=8),
        name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (16, 10))],
             label_shapes=[("softmax_label", (16,))])
    mod.init_params()
    exe = mod._exec_group.execs[0]
    train = exe.flops_per_step(is_train=True)
    fwd = exe.flops_per_step(is_train=False)
    assert train > 0 and fwd > 0
    # training counts fwd+bwd (3x forward by convention)
    assert train == pytest.approx(3 * fwd)
    # cached: second call returns the identical value
    assert exe.flops_per_step(is_train=True) == train
    # tracing did not populate the jit cache (review regression pin)
    assert exe._jit_fwd == {}


def test_peak_flops_env_override(monkeypatch):
    monkeypatch.setenv("MXTPU_PEAK_FLOPS", "1e12")
    assert telemetry.peak_flops() == 1e12
    # no override: the peak is the PEAK_FLOPS entry for the device_kind,
    # and a device that is not in the table has none
    monkeypatch.setenv("MXTPU_PEAK_FLOPS", "0")
    assert telemetry.peak_flops() is None  # this host's CPU device
    from tools.tpu_constants import V5E_PEAK_FLOPS

    class V5e:
        device_kind = "TPU v5 lite"

    assert telemetry.peak_flops(V5e()) == V5E_PEAK_FLOPS == 197e12


# ----------------------------------------------------------------------
# layer coverage riding the real paths
# ----------------------------------------------------------------------

def test_engine_metrics_observed():
    eng = mx.engine.get()
    v = mx.engine.new_variable()
    for _ in range(4):
        eng.push(lambda: None, write_vars=(v,), name="tick")
    eng.wait_for_all()
    snap = telemetry.snapshot()
    assert snap["counters"]["engine.ops_completed"] >= 4
    assert snap["histograms"]["engine.op_seconds"]["count"] >= 4
    if eng.num_workers:  # threaded backends expose scheduler gauges
        assert "engine.pending_ops" in snap["gauges"]


def test_kvstore_metrics_observed():
    kv = mx.kv.create("local")
    kv.init(3, mx.nd.ones((4, 4)))
    out = mx.nd.zeros((4, 4))
    kv.push(3, mx.nd.ones((4, 4)))
    kv.pull(3, out=out)
    out.wait_to_read()
    mx.waitall()
    snap = telemetry.snapshot()
    assert snap["counters"]["kvstore.push_count"] == 1
    assert snap["counters"]["kvstore.pull_count"] == 1
    assert snap["counters"]["kvstore.push_bytes"] == 4 * 4 * 4
    assert snap["histograms"]["kvstore.push_seconds"]["count"] == 1
    assert snap["histograms"]["kvstore.pull_seconds"]["count"] == 1


def test_monitor_sweep_records_duration_and_batches_stats():
    mon = mx.monitor.Monitor(interval=1, pattern=".*")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                              name="fc1"), name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    it = mx.io.NDArrayIter(np.random.rand(32, 6).astype(np.float32),
                           np.zeros(32, np.float32), batch_size=16)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    mod.install_monitor(mon)
    mon.tic()
    mod.forward(next(it), is_train=True)
    rows = mon.toc()
    assert rows
    # batched default-stat values match the per-value definition
    exe = mod._exec_group.execs[0]
    w = exe.arg_dict["fc1_weight"]
    expect = float(np.abs(np.asarray(w.data)).sum()) / w.size
    got = {name: float(stat) for (_, name, stat) in rows}
    assert got["fc1_weight"] == pytest.approx(expect)
    assert telemetry.snapshot()["histograms"][
        "monitor.sweep_seconds"]["count"] == 1


# ----------------------------------------------------------------------
# retrace monitor (ISSUE 12): the runtime half of mxlint W104
# ----------------------------------------------------------------------

def test_note_retrace_counts_signature_churn_only():
    """First signature at a site compiles for free; the same signature
    again is never a retrace; each NEW distinct signature counts one
    (total + per-site counters)."""
    assert telemetry.note_retrace("site.a", ("x", (4, 4))) is False
    assert telemetry.note_retrace("site.a", ("x", (4, 4))) is False
    assert telemetry.note_retrace("site.a", ("x", (8, 4))) is True
    assert telemetry.note_retrace("site.a", ("x", (16, 4))) is True
    assert telemetry.counter_value("trace.retraces") == 2
    assert telemetry.counter_value("trace.retraces.site.a") == 2
    # scopes separate same-named sites with independent caches (the
    # executor passes id(self)): a second binding's first compile is
    # not churn
    assert telemetry.note_retrace("site.a", ("x", (4, 4)),
                                  scope=123) is False
    assert telemetry.counter_value("trace.retraces") == 2
    # disabled registry: no counting at all
    prev = telemetry.set_enabled(False)
    try:
        assert telemetry.note_retrace("site.a", ("y",)) is False
    finally:
        telemetry.set_enabled(prev)


def test_retrace_warn_threshold_logs_signature_delta(monkeypatch, caplog):
    import logging

    monkeypatch.setenv("MXTPU_RETRACE_WARN", "2")
    telemetry.note_retrace("site.warn", "sigA")
    telemetry.note_retrace("site.warn", "sigB")
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.telemetry"):
        telemetry.note_retrace("site.warn", "sigC")
    joined = "\n".join(r.getMessage() for r in caplog.records)
    assert "retrace storm" in joined and "site.warn" in joined
    assert "sigB" in joined and "sigC" in joined  # the delta, named


def test_forced_signature_churn_counts_through_the_lazy_cache():
    """ISSUE 12 acceptance pin: a REAL signature-churn retrace is
    counted end-to-end.  `clip` embeds its float attrs statically (no
    lift_floats), so each distinct a_max keys its own fused program —
    exactly the W104 bug class — and trace.retraces.lazy.fusion climbs;
    the lifted scalar family (`x * 0.1` vs `x * 0.2`) shares ONE
    program and counts nothing."""
    import numpy as _np

    import mxnet_tpu as mx
    from mxnet_tpu import lazy

    lazy.reset_cache()
    x = mx.nd.array(_np.ones((4, 4), _np.float32))
    for i in range(3):
        y = mx.nd.clip(x, a_min=0.0, a_max=1.0 + i)
        y.asnumpy()
    churn = telemetry.counter_value("trace.retraces.lazy.fusion")
    assert churn >= 2, telemetry.snapshot()["counters"]
    assert telemetry.counter_value("trace.retraces") >= churn
    # the lifted scalar family: the STRUCTURE costs one program (one
    # fingerprint, counted once on first sight), then every distinct
    # VALUE reuses it — value churn adds nothing
    (x * 0.05).asnumpy()  # warm the _mul_scalar program fingerprint
    before = telemetry.counter_value("trace.retraces.lazy.fusion")
    for i in range(3):
        y = x * (0.1 * (i + 1))  # lifted: one program, many values
        y.asnumpy()
    assert telemetry.counter_value("trace.retraces.lazy.fusion") == before


def test_executor_forward_site_feeds_retrace_monitor():
    """The executor's jit caches report their signatures: one binding
    compiling a SECOND distinct signature at a site counts churn."""
    import mxnet_tpu as mx

    d = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(d, num_hidden=3, name="fc")
    exe = mx.Executor.simple_bind(net, ctx=mx.cpu(), grad_req="null",
                                  data=(2, 5))
    exe.forward(is_train=False, data=mx.nd.zeros((2, 5)))
    assert telemetry.counter_value("trace.retraces.executor.forward") == 0
    exe.forward(is_train=True, data=mx.nd.zeros((2, 5)))
    exe.outputs
    assert telemetry.counter_value("trace.retraces.executor.forward") == 1


def test_parse_log_telemetry_grows_retrace_and_sched_div_columns(tmp_path):
    """ISSUE 12 satellite: --telemetry renders `retraces`/`sched_div`;
    records that predate the counters render '-' (the prior column-
    addition contract)."""
    from tools.parse_log import _TELEMETRY_COLS, parse_telemetry

    # the ISSUE 12/13 columns stay one contiguous block in order (the
    # tail has since grown the ISSUE 14 router columns)
    i = _TELEMETRY_COLS.index("retraces")
    assert _TELEMETRY_COLS[i:i + 4] == ["retraces", "sched_div",
                                        "quant_clip_pct", "tenant_bits"]
    old = {"flush_seq": 1, "counters": {}, "gauges": {}, "histograms": {}}
    new = {"flush_seq": 2,
           "counters": {"trace.retraces": 3,
                        "trace.retraces.lazy.fusion": 3,
                        "schedule.divergences": 1},
           "gauges": {}, "histograms": {}}
    rows = parse_telemetry([json.dumps(old), json.dumps(new)])
    assert rows[0]["retraces"] is None and rows[0]["sched_div"] is None
    assert rows[1]["retraces"] == 3 and rows[1]["sched_div"] == 1
    # and through the CLI: '-' for the legacy record, numbers after
    f = tmp_path / "t.jsonl"
    f.write_text(json.dumps(old) + "\n" + json.dumps(new) + "\n")
    import subprocess

    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "parse_log.py"),
         "--telemetry", str(f)], capture_output=True, text=True,
        timeout=120)
    assert r.returncode == 0, r.stderr
    assert "retraces" in r.stdout and "sched_div" in r.stdout


# ----------------------------------------------------------------------
# value-range histograms (ValueHistogram / observe_values — the int8
# calibration recorder, docs/observability.md)
# ----------------------------------------------------------------------

def test_value_histogram_auto_range_doubles_preserving_counts():
    h = telemetry.ValueHistogram(n_buckets=4)
    h.observe_array([0.5, 1.0])
    assert h.count == 2 and h.hi == 1.0
    # 3.9 forces two doublings (1 -> 2 -> 4); pair-merge keeps every
    # prior observation counted
    h.observe(3.9)
    assert h.hi == 4.0
    assert h.count == 3 and sum(h.counts) == 3
    assert h.min == 0.5 and h.max == 3.9
    d = h.as_dict()
    assert d["count"] == 3 and sum(d["buckets"].values()) == 3
    assert d["buckets"]["le_inf"] == 0  # auto mode grows, never overflows


def test_value_histogram_quantile_and_fraction_above():
    h = telemetry.ValueHistogram(n_buckets=64)
    h.observe_array(np.linspace(0.0, 100.0, 10001))
    q99 = h.quantile(0.99)
    assert abs(q99 - 99.0) < 2.0
    assert abs(h.fraction_above(q99) - 0.01) < 0.005
    assert h.quantile(1.0) == 100.0  # clamped to the observed max
    assert telemetry.ValueHistogram().quantile(0.5) is None  # empty


def test_value_histogram_explicit_boundaries_and_overflow():
    h = telemetry.ValueHistogram(boundaries=(1.0, 2.0))
    h.observe_array([0.5, 1.5, 5.0])
    d = h.as_dict()
    assert d["buckets"] == {"le_1": 1, "le_2": 1, "le_inf": 1}
    assert h.fraction_above(2.0) == pytest.approx(1.0 / 3.0)


def test_value_histogram_rejects_bad_construction():
    with pytest.raises(ValueError):
        telemetry.ValueHistogram(n_buckets=3)   # odd: pair-merge breaks
    with pytest.raises(ValueError):
        telemetry.ValueHistogram(boundaries=(2.0, 1.0))  # unsorted


def test_observe_values_registry_schema_and_disabled():
    telemetry.observe_values("test.vals", np.array([1.0, 2.0, 3.0]))
    telemetry.observe_values("test.vals", 4.0)
    snap = telemetry.snapshot()["histograms"]["test.vals"]
    assert snap["count"] == 4 and snap["max"] == 4.0
    assert sum(snap["buckets"].values()) == 4
    # the snapshot schema is the one parse_log's quantile math reads
    from tools.parse_log import _hist_quantile

    assert _hist_quantile(snap, 0.5) is not None
    # disabled: zero registry mutation (the E004 fast-path promise)
    telemetry.set_enabled(False)
    telemetry.observe_values("test.off", np.array([1.0]))
    telemetry.set_enabled(True)
    assert "test.off" not in telemetry.snapshot()["histograms"]
    # a name already holding a fixed-ladder histogram is a clear error
    telemetry.observe("test.fixed", 1.0)
    with pytest.raises(ValueError, match="fixed ladder"):
        telemetry.observe_values("test.fixed", np.array([1.0]))


def test_attach_value_histogram_shares_one_object():
    """The calibration recorder owns its histograms and ATTACHES them —
    the registry snapshot sees the same distribution the caller keeps
    binning into, with every array binned exactly once."""
    h = telemetry.ValueHistogram(n_buckets=8)
    telemetry.attach_value_histogram("test.shared", h)
    h.observe_array(np.array([1.0, 2.0, 3.0]))
    snap = telemetry.snapshot()["histograms"]["test.shared"]
    assert snap["count"] == 3 and snap["max"] == 3.0
    # disabled: registry untouched (the recording-call contract)
    telemetry.set_enabled(False)
    telemetry.attach_value_histogram("test.shared.off",
                                     telemetry.ValueHistogram())
    telemetry.set_enabled(True)
    assert "test.shared.off" not in telemetry.snapshot()["histograms"]
    with pytest.raises(ValueError, match="ValueHistogram"):
        telemetry.attach_value_histogram("test.bad", object())
    telemetry.observe("test.fixed2", 1.0)
    with pytest.raises(ValueError, match="fixed ladder"):
        telemetry.attach_value_histogram("test.fixed2",
                                         telemetry.ValueHistogram())


def test_parse_log_telemetry_grows_ckpt_columns(tmp_path):
    """ISSUE 16 satellite: --telemetry renders `ckpt_secs`/`ckpt_bytes`/
    `resumes` from the ckpt.* namespace; records from runs that predate
    (or never armed) checkpointing render '-' — the same column-addition
    contract every prior telemetry growth followed."""
    from tools.parse_log import _TELEMETRY_COLS, parse_telemetry

    i = _TELEMETRY_COLS.index("ckpt_secs")
    assert _TELEMETRY_COLS[i:i + 3] == ["ckpt_secs", "ckpt_bytes", "resumes"]
    old = {"flush_seq": 1, "counters": {}, "gauges": {}, "histograms": {}}
    new = {"flush_seq": 2,
           "counters": {"ckpt.snapshots": 4, "ckpt.commits": 4,
                        "ckpt.bytes": 612352, "ckpt.resumes": 1},
           "gauges": {"ckpt.last_step": 8},
           "histograms": {"ckpt.write_seconds":
                          {"count": 4, "sum": 0.125}}}
    rows = parse_telemetry([json.dumps(old), json.dumps(new)])
    assert rows[0]["ckpt_secs"] is None and rows[0]["ckpt_bytes"] is None \
        and rows[0]["resumes"] is None
    assert rows[1]["ckpt_secs"] == 0.125
    assert rows[1]["ckpt_bytes"] == 612352
    assert rows[1]["resumes"] == 1
    f = tmp_path / "t.jsonl"
    f.write_text(json.dumps(old) + "\n" + json.dumps(new) + "\n")
    import subprocess

    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "parse_log.py"),
         "--telemetry", str(f)], capture_output=True, text=True,
        timeout=120)
    assert r.returncode == 0, r.stderr
    assert "ckpt_secs" in r.stdout and "ckpt_bytes" in r.stdout
    assert "resumes" in r.stdout
