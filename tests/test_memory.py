"""The memory observability plane (mxnet_tpu/obs/memory.py,
docs/observability.md "Memory observability"): per-program footprint
accounting harvested from XLA compiled-memory analysis, the
tag-attributed live-buffer census, byte-budget admission for serving
tenants, and OOM forensics.

The acceptance pins live here: the census balances back to its
baseline after a train + serve + close round trip, an injected
RESOURCE_EXHAUSTED produces a schema-valid postmortem whose top holder
names the planted allocation, and a live 2-replica router fleet
reports per-replica memory headroom that shrinks when a generative
tenant's KV ring is added.
"""
import gc
import json
import os
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.obs import memory


@pytest.fixture(autouse=True)
def _armed_telemetry():
    """Census booking happens only while telemetry is enabled — pin the
    state so a prior test's set_enabled(False) cannot skew balances."""
    prev = telemetry.enabled()
    telemetry.set_enabled(True)
    yield
    telemetry.set_enabled(prev)
    memory.inject_oom(None)


def _mlp(hidden=16, classes=5, seed=0):
    mx.random.seed(seed)
    data = mx.sym.Variable("data")
    h = mx.sym.Activation(
        mx.sym.FullyConnected(data, num_hidden=hidden, name="fc1"),
        act_type="relu")
    return mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(h, num_hidden=classes, name="fc2"),
        name="softmax")


def _predictor(net=None, sample=(12,)):
    mod = mx.mod.Module(net or _mlp(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (1,) + sample)], label_shapes=None,
             for_training=False)
    mod.init_params(mx.init.Xavier())
    arg, aux = mod.get_params()
    params = {"arg:%s" % k: v for k, v in arg.items()}
    params.update({"aux:%s" % k: v for k, v in aux.items()})
    return mx.Predictor(net or _mlp(), params, {"data": (1,) + sample},
                        ctx=mx.cpu())


def _settle():
    """Flush lazy chains and collect, so census assertions see only
    really-live holders (an unflushed chain pins its operands)."""
    mx.nd.waitall()
    # what an earlier test file of this worker left behind may sit in
    # cycles that free others when they go: collect until nothing does
    while gc.collect():
        pass


# ----------------------------------------------------------------------
# the live-buffer census
# ----------------------------------------------------------------------

def test_census_books_and_balances_ndarray_lifecycle():
    _settle()
    base = memory.live_bytes("ndarray.cpu")
    a = mx.nd.zeros((64, 64))
    a.asnumpy()  # materialize
    assert memory.live_bytes("ndarray.cpu") >= base + 64 * 64 * 4
    del a
    _settle()
    assert memory.live_bytes("ndarray.cpu") == base


def test_census_rebook_on_set_data_swap():
    _settle()
    base = memory.live_bytes("ndarray.cpu")
    a = mx.nd.zeros((8, 8))
    b = (a + 1.0)
    b.asnumpy()  # flush: b's payload lands
    _settle()
    after = memory.live_bytes("ndarray.cpu")
    assert after >= base + 2 * 8 * 8 * 4
    del a, b
    _settle()
    assert memory.live_bytes("ndarray.cpu") == base


def test_census_disarm_via_set_census():
    prev = memory.set_census(False)
    try:
        base = memory.live_bytes("ndarray.cpu")
        a = mx.nd.zeros((32, 32))
        a.asnumpy()
        assert memory.live_bytes("ndarray.cpu") == base  # not booked
        del a
        _settle()
        assert memory.live_bytes("ndarray.cpu") == base  # and balanced
    finally:
        memory.set_census(prev)


def test_census_balance_pin_train_serve_close():
    """ACCEPTANCE (tier-1 census-balance pin): a train round + a serving
    round, everything closed and collected, returns the census to its
    baseline — no tag leaks bytes across the lifecycle."""
    _settle()
    base = memory.census()

    # --- train: fit a small module (staged blocks book/unbook inside)
    mx.random.seed(7)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    xs = np.random.RandomState(0).randn(32, 12).astype("float32")
    ys = np.random.RandomState(1).randint(0, 5, (32,)).astype("float32")
    it = mx.io.NDArrayIter(xs, ys, batch_size=8)
    mod.fit(it, num_epoch=1,
            optimizer_params={"learning_rate": 0.05})
    del mod, it

    # --- serve: a 1-tenant server round trip
    server = mx.serving.ModelServer({"m": _predictor()})
    fut = server.submit("m", {"data": xs[0]})
    assert len(fut.result()) == 1
    server.close()
    del server, fut

    _settle()
    after = memory.census()
    for tag in ("serve_slots", "staged_blocks", "ckpt_blobs"):
        assert after.get(tag, 0) == base.get(tag, 0), (tag, base, after)
    assert after.get("ndarray.cpu", 0) == base.get("ndarray.cpu", 0), \
        (base, after)
    # (a session an earlier test file never closed keeps its booking:
    # only rings booked since the baseline are this lifecycle's)
    assert not any(t.startswith("kv_ring.") and t not in base
                   for t in after), (base, after)


def test_census_concurrent_booking_stays_consistent():
    errs = []

    def worker(seed):
        try:
            rng = np.random.RandomState(seed)
            for _ in range(50):
                a = mx.nd.array(rng.randn(17, 3).astype("float32"))
                a.asnumpy()
                del a
        except Exception as e:  # pragma: no cover
            errs.append(e)

    _settle()
    base = memory.live_bytes("ndarray.cpu")
    threads = [threading.Thread(target=worker, args=(s,)) for s in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    _settle()
    assert memory.live_bytes("ndarray.cpu") == base


# ----------------------------------------------------------------------
# per-program footprint accounting
# ----------------------------------------------------------------------

def test_program_footprint_matches_actual_arg_output_bytes():
    """Predicted-vs-actual sanity on XLA:CPU: the harvested analysis
    must report the real argument/output bytes of the program (temp
    bytes are 0 on CPU — the arg/output numbers are the honest part)."""
    import jax.numpy as jnp

    prog = memory.program(lambda x, y: (x @ y).sum(axis=1),
                          site="test.matmul")
    x = np.ones((8, 16), np.float32)
    y = np.ones((16, 4), np.float32)
    out = prog(x, y)
    assert out.shape == (8,)
    fp = prog.footprint()
    assert fp is not None and fp["site"] == "test.matmul"
    assert fp["argument_bytes"] == x.nbytes + y.nbytes
    assert fp["output_bytes"] == np.zeros(8, np.float32).nbytes
    assert fp["peak_bytes"] >= fp["argument_bytes"] + fp["output_bytes"] \
        - fp["alias_bytes"]
    # the table and the site gauge saw the row
    assert any(f["site"] == "test.matmul" for f in memory.footprints())
    assert memory.program_bytes("test.matmul") >= fp["peak_bytes"]
    prog.release()
    assert memory.program_bytes("test.matmul") == 0
    assert not any(f["site"] == "test.matmul" for f in memory.footprints())
    del jnp


def test_program_signature_drift_recompiles_not_breaks():
    prog = memory.program(lambda x: x * 2.0, site="test.drift")
    a = prog(np.ones((4,), np.float32))
    b = prog(np.ones((9,), np.float32))  # new shape: second executable
    assert a.shape == (4,) and b.shape == (9,)
    assert len(memory.footprints(site="test.drift")) == 2
    # ping-pong back: cache hit, no third row
    prog(np.ones((4,), np.float32))
    assert len(memory.footprints(site="test.drift")) == 2
    prog.release()


def test_program_escape_hatch_env(monkeypatch):
    monkeypatch.setenv("MXTPU_MEM_PROGRAMS", "0")
    prog = memory.program(lambda x: x + 1.0, site="test.hatch")
    out = prog(np.zeros((3,), np.float32))
    assert out.shape == (3,)
    assert prog.footprint() is None  # plain jit, no AOT harvest
    assert memory.footprints(site="test.hatch") == []


def test_executor_sites_register_footprints():
    """The executor's compile-cache sites land in the footprint table
    under their site names after one fit round."""
    before = {(f["site"], f["key"], f["signature"])
              for f in memory.footprints()}
    mx.random.seed(3)
    # hidden=23 keeps this compile unique: a shape any other test shares
    # would hit the executor cache and register no new rows.
    mod = mx.mod.Module(_mlp(hidden=23), context=mx.cpu())
    xs = np.random.RandomState(0).randn(16, 12).astype("float32")
    ys = np.zeros((16,), np.float32)
    it = mx.io.NDArrayIter(xs, ys, batch_size=8)
    mod.fit(it, num_epoch=1, optimizer_params={"learning_rate": 0.01})
    new = [f for f in memory.footprints()
           if (f["site"], f["key"], f["signature"]) not in before]
    sites = {f["site"] for f in new}
    assert any(s.startswith("executor.") for s in sites), sites
    fwd = [f for f in new if f["site"].startswith("executor.")]
    assert all(f["argument_bytes"] > 0 for f in fwd), fwd


def test_predictor_eviction_releases_footprints(monkeypatch):
    """Executor-signature cache eviction removes the evicted programs'
    footprints and ticks mem.programs_evicted."""
    from mxnet_tpu import predict as predict_mod

    monkeypatch.setattr(predict_mod, "_EXEC_CACHE_CAP", 1)
    pred = _predictor()
    c0 = telemetry.counter_value("mem.programs_evicted")
    rows0 = len(memory.footprints(site="executor.forward"))
    pred.forward(data=np.zeros((1, 12), np.float32))
    rows1 = len(memory.footprints(site="executor.forward"))
    assert rows1 > rows0
    # rebind at batch 2: with the cache capped at 1 this EVICTS the
    # batch-1 executor, whose programs leave the footprint table
    pred.reshape({"data": (2, 12)})
    pred.forward(data=np.zeros((2, 12), np.float32))
    assert telemetry.counter_value("mem.programs_evicted") > c0
    assert len(memory.footprints(site="executor.forward")) <= rows1
    pred.close()


# ----------------------------------------------------------------------
# byte-budget admission
# ----------------------------------------------------------------------

def test_admission_refused_under_tiny_budget(monkeypatch):
    """Registration against an exhausted 1 MB budget is refused with
    numbers, BEFORE the tenant compiles or allocates anything."""
    big = mx.nd.zeros((600, 600))  # ~1.4 MB live, booked in the census
    big.asnumpy()
    _settle()
    monkeypatch.setenv("MXTPU_MEM_BUDGET_MB", "1")
    r0 = telemetry.counter_value("mem.admission_refusals")
    server = mx.serving.ModelServer({})
    try:
        with pytest.raises(memory.MemoryBudgetError) as ei:
            server.add_tenant("t", _predictor())
        msg = str(ei.value)
        assert "predicted footprint" in msg and "MB budget" in msg
        assert "MXTPU_MEM_BUDGET_MB" in msg
        assert telemetry.counter_value("mem.admission_refusals") > r0
        assert server.tenants == []  # nothing half-registered
    finally:
        server.close()
    del big


def test_admission_headroom_api(monkeypatch):
    monkeypatch.setenv("MXTPU_MEM_BUDGET_MB", "64")
    budget = memory.budget_bytes()
    assert budget == 64 << 20
    head = memory.headroom_bytes()
    assert head is not None and head <= budget
    # fits: admit returns the predicted bytes
    assert memory.admit("small thing", 1024) == 1024


def test_health_memory_section_reports_tenants_and_headroom(monkeypatch):
    monkeypatch.setenv("MXTPU_MEM_BUDGET_MB", "256")
    server = mx.serving.ModelServer({"m": _predictor()})
    try:
        fut = server.submit("m", {"data": np.zeros(12, np.float32)})
        fut.result()
        sec = server.health()["memory"]
        assert sec["budget_bytes"] == 256 << 20
        assert sec["headroom_bytes"] == sec["budget_bytes"] - sec["live_bytes"]
        assert 0.0 <= sec["headroom_pct"] <= 100.0
        assert isinstance(sec["by_tag"], dict)
        assert sec["live_bytes"] == sum(sec["by_tag"].values())
    finally:
        server.close()


# ----------------------------------------------------------------------
# OOM forensics
# ----------------------------------------------------------------------

def test_injected_oom_writes_postmortem_naming_top_holder(
        monkeypatch, tmp_path):
    """ACCEPTANCE: an injected RESOURCE_EXHAUSTED at the serve dispatch
    produces a schema-valid memory_postmortem.r<rank>.json whose top
    holder names the planted allocation."""
    monkeypatch.setenv("MXTPU_OBS_DIR", str(tmp_path))
    _settle()
    # the planted allocation: big enough that ndarray.cpu necessarily
    # tops the census peak when the OOM fires
    planted = mx.nd.zeros((1024, 1024))
    planted.asnumpy()
    server = mx.serving.ModelServer({"m": _predictor()})
    try:
        # warm first so the injection hits a DISPATCH, not the compile
        server.warmup()
        memory.inject_oom("executor.serve")
        fut = server.submit("m", {"data": np.zeros(12, np.float32)})
        with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
            fut.result(timeout=60)
    finally:
        memory.inject_oom(None)
        server.close()
    path = tmp_path / "memory_postmortem.r0.json"
    assert path.exists()
    assert memory.last_postmortem_path() == str(path)
    pm = json.loads(path.read_text())
    assert pm["schema"] == "mxtpu-mem-postmortem-v1"
    assert pm["rank"] == 0
    assert pm["site"] == "executor.serve"
    assert "RESOURCE_EXHAUSTED" in pm["error"]
    assert pm["live_bytes"] > 0 and pm["census"]
    # the planted allocation is the top holder at the recorded peak
    top = pm["peak"]["top"]
    assert top and top[0][0] == "ndarray.cpu"
    assert top[0][1] >= 1024 * 1024 * 4
    # the footprint table rode along (the serve program compiled)
    assert any(f["site"] == "executor.serve" for f in pm["footprints"])
    del planted


def test_postmortem_write_is_atomic_no_tmp_left(monkeypatch, tmp_path):
    monkeypatch.setenv("MXTPU_OBS_DIR", str(tmp_path))
    path = memory.write_postmortem("test.site", "k", "boom")
    assert path and os.path.exists(path)
    assert not [p for p in os.listdir(tmp_path) if p.endswith(".tmp")]
    json.loads(open(path).read())  # valid JSON


# ----------------------------------------------------------------------
# ACCEPTANCE: 2-replica fleet memory headroom through the router
# ----------------------------------------------------------------------

def test_router_reports_replica_memory_headroom_shrinks_with_kv_ring(
        monkeypatch):
    """Router.health() on a live 2-replica fleet carries each replica's
    memory headroom; adding a generative tenant's KV ring shrinks it."""
    import sys
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_transformer_lm import _lm_and_params
    from mxnet_tpu.router import ReplicaAgent, Router

    monkeypatch.setenv("MXTPU_MEM_BUDGET_MB", "512")
    agents, threads = [], []
    for rid in range(2):
        ag = ReplicaAgent({"m": _predictor()}, port=0, replica_id=rid,
                          wait_ms=10)
        th = threading.Thread(target=ag.serve_forever, daemon=True)
        th.start()
        agents.append(ag)
        threads.append(th)
    router = Router(["127.0.0.1:%d" % a.port for a in agents],
                    poll_ms=100, adapt_window_s=0)

    def wait_health(cond, timeout=30.0):
        deadline = time.time() + timeout
        while time.time() < deadline:
            h = router.health()
            if cond(h):
                return h
            time.sleep(0.1)
        raise AssertionError("health condition not met: %s"
                             % json.dumps(router.health(), default=str))

    def rep1(h):
        """Replica rows are keyed 'replica:<id>@host:port'."""
        for n, r in h["replicas"].items():
            if n.startswith("replica:1"):
                return r
        return None

    try:
        h = wait_health(lambda h: all(
            r["memory"] and r["memory"]["headroom_bytes"] is not None
            for r in h["replicas"].values()) and len(h["replicas"]) == 2)
        before = {n: r["memory"]["headroom_bytes"]
                  for n, r in h["replicas"].items()}
        assert all(v > 0 for v in before.values())
        before1 = rep1(h)["memory"]["headroom_bytes"]

        # grow replica 1: a generative tenant books its KV ring
        lm, params = _lm_and_params(num_layers=1)
        # (a tenant "lm" that an earlier test file of this worker never
        # closed keeps its booking: this tenant's ring is what is added)
        booked = memory.live_bytes("kv_ring.lm")
        agents[1]._server.add_generative_tenant(
            "lm", lm, params, max_sessions=2, max_len=16, seq_buckets=[8])
        ring = memory.live_bytes("kv_ring.lm") - booked
        assert ring > 0

        h = wait_health(lambda h: "lm" in (
            (rep1(h)["memory"] or {}).get("tenants", {})))
        mem1 = rep1(h)["memory"]
        assert mem1["tenants"]["lm"]["kv_ring_bytes"] == booked + ring
        # headroom shrank by at least the ring (params booked too)
        assert mem1["headroom_bytes"] <= before1 - ring
    finally:
        try:
            router.close(drain=False, shutdown_replicas=True, timeout=30)
        except Exception:
            pass
        for ag in agents:
            try:
                ag.close(drain=False)
            except Exception:
                pass
        for th in threads:
            th.join(timeout=10)


# ----------------------------------------------------------------------
# parse_log --telemetry memory columns
# ----------------------------------------------------------------------

def test_parse_log_memory_columns():
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from tools.parse_log import parse_telemetry, _TELEMETRY_COLS

    with_mem = json.dumps({
        "flush_seq": 1, "step": 10,
        "counters": {"executor.train_dispatches": 5},
        "gauges": {"mem.live_bytes": 3_000_000,
                   "mem.peak_bytes": 5_000_000,
                   "mem.headroom_pct": 62.5},
        "histograms": {}})
    pre_mem = json.dumps({
        "flush_seq": 2, "step": 20,
        "counters": {"executor.train_dispatches": 9},
        "gauges": {}, "histograms": {}})
    rows = parse_telemetry([with_mem, pre_mem])
    assert rows[0]["live_mb"] == 3.0
    assert rows[0]["peak_mb"] == 5.0
    assert rows[0]["mem_headroom_pct"] == 62.5
    # pre-census logs render '-' (None), not 0
    assert rows[1]["live_mb"] is None
    assert rows[1]["peak_mb"] is None
    assert rows[1]["mem_headroom_pct"] is None
    for col in ("live_mb", "peak_mb", "mem_headroom_pct"):
        assert col in _TELEMETRY_COLS
