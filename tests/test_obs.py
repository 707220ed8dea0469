"""Distributed observability plane (ISSUE 11): the collective flight
recorder + stall watchdog (obs/recorder.py, obs/watchdog.py), rank-0
cluster aggregation with step-skew attribution (obs/aggregate.py,
parse_log --cluster), per-rank sink suffixes, clock-offset trace
stitching (tools/obs_stitch.py), and the ModelServer.health() probe.

The two launcher subprocess tests are the acceptance pins: a
2-process --local-spmd fit where one rank stub-stalls mid-epoch must
yield a watchdog post-mortem on the HEALTHY rank naming the stalled
rank and the stalled collective seq — and the healthy rank must abort
instead of hanging forever; and a profiled 2-process fit must stitch
into one trace with aligned per-rank lanes while parse_log --cluster
renders the per-rank skew table from the aggregator's JSONL.
"""
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler, telemetry
from mxnet_tpu.obs import aggregate, recorder, watchdog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(REPO, "tools") not in sys.path:
    sys.path.insert(0, os.path.join(REPO, "tools"))


@pytest.fixture(autouse=True)
def _fresh_recorder():
    recorder.reset()
    prev = recorder.set_enabled(True)
    yield
    recorder.set_enabled(prev)
    recorder.reset()


# ----------------------------------------------------------------------
# flight recorder
# ----------------------------------------------------------------------

def test_recorder_ring_is_bounded_and_ordered():
    slots = recorder.ring_slots()
    assert slots == 512
    recorder.reset(slots=8)
    try:
        for i in range(30):
            s = recorder.record("dispatch", "enter", detail="d%d" % i)
            recorder.record("dispatch", "exit", s)
        ev = recorder.events()
        prog = recorder.progress()["dispatch"]
        last3 = recorder.events(last_k=3)
    finally:
        # the ring is the process's: the next test file of this worker
        # reads whole epochs out of it (tests/test_fused_dispatch.py)
        recorder.reset(slots=slots)
    assert len(ev) == 8  # fixed slots: oldest 52 events overwritten
    idx = [e["index"] for e in ev]
    assert idx == sorted(idx) and idx[-1] == 59
    assert ev[-1]["phase"] == "exit" and ev[-1]["seq"] == 30
    assert prog == {"entered": 30, "exited": 30,
                    "last_entered_seq": 30, "last_exited_seq": 30}
    assert last3[0]["index"] == 57


def test_recorder_open_spans_and_auto_seq():
    s1 = recorder.record("allgather", "enter", nbytes=128)
    s2 = recorder.record("allgather", "enter")
    assert (s1, s2) == (1, 2)
    spans = recorder.open_spans()
    assert [x["seq"] for x in spans] == [1, 2]
    assert spans[0]["nbytes"] == 128 and spans[0]["age_s"] >= 0
    recorder.record("allgather", "exit")  # resolves to most recent open
    assert [x["seq"] for x in recorder.open_spans()] == [1]
    recorder.record("allgather", "exit", s1)
    assert recorder.open_spans() == []


def test_recorder_disabled_records_nothing():
    recorder.set_enabled(False)
    assert recorder.record("dispatch", "enter") is None
    assert recorder.events() == [] and recorder.progress() == {}
    assert not recorder.enabled()


def test_disable_mid_span_leaves_no_phantom_open_span():
    """Flipping the recorder off while a bracket is open must clear the
    open-span table: exits are not recorded while off, so a stale entry
    would age forever and the watchdog would abort on a phantom stall."""
    recorder.record("dispatch", "enter")
    assert recorder.open_spans()
    recorder.set_enabled(False)
    recorder.set_enabled(True)
    assert recorder.open_spans() == []


def test_recorder_compile_bracket():
    assert not recorder.compiling()
    recorder.record("compile", "enter")
    assert recorder.compiling()
    before = recorder.last_compile_exit()
    recorder.record("compile", "exit")
    assert not recorder.compiling()
    assert recorder.last_compile_exit() > before


def test_fused_dispatch_records_edge_events():
    """One real single-device fit: the executor's fused-dispatch path
    writes enter/exit pairs (and a compile bracket on the first call)
    into the flight recorder."""
    rng = np.random.RandomState(0)
    it = mx.io.NDArrayIter(rng.randn(32, 6).astype("float32"),
                           rng.randn(32, 1).astype("float32"),
                           batch_size=8, label_name="lro_label")
    net = mx.sym.LinearRegressionOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=1),
        name="lro")
    mod = mx.mod.Module(net, label_names=("lro_label",), context=mx.cpu())
    mod.fit(it, num_epoch=1, kvstore=None, optimizer="sgd",
            initializer=mx.init.Xavier(), eval_metric="mse",
            steps_per_dispatch=2)
    prog = recorder.progress()
    assert prog["dispatch"]["entered"] == prog["dispatch"]["exited"] > 0
    assert prog["compile"]["entered"] == prog["compile"]["exited"] >= 1
    assert recorder.open_spans() == []
    kinds = {(e["kind"], e["phase"]) for e in recorder.events()}
    assert ("dispatch", "enter") in kinds and ("dispatch", "exit") in kinds
    block_evs = [e for e in recorder.events()
                 if e["kind"] == "dispatch" and e["phase"] == "enter"]
    assert any("block(K=2" in e["detail"] for e in block_evs)


# ----------------------------------------------------------------------
# stall watchdog
# ----------------------------------------------------------------------

def test_watchdog_dumps_postmortem_atomically(tmp_path):
    wd = watchdog.StallWatchdog(0.15, artifact_dir=str(tmp_path),
                                poll_seconds=0.05)
    seq = recorder.record("dispatch", "enter", detail="block(K=2)",
                          nbytes=999)
    time.sleep(0.3)
    path = wd.check()
    assert path is not None and os.path.exists(path)
    assert not os.path.exists(path + ".tmp")  # write-then-rename
    art = json.load(open(path))
    assert art["schema"] == "mxtpu-obs-postmortem-v1"
    assert art["stalled"][0]["kind"] == "dispatch"
    assert art["stalled"][0]["seq"] == seq
    assert art["stalled"][0]["age_s"] > 0.15
    assert art["progress"]["dispatch"]["entered"] == 1
    assert art["events"] and art["stacks"]  # python stacks captured
    # no peer snapshots -> attribution is honest about it
    assert art["attribution"]["verdict"] == "unknown"
    # the same span is reported once, not on every poll
    assert wd.check() is None
    recorder.record("dispatch", "exit", seq)


def test_watchdog_suppressed_while_compile_open(tmp_path):
    """Satellite: a long legitimate first compile must not trip the
    watchdog — spans are ignored while a compile bracket is open, and
    their stall age restarts at the compile's exit (slow-compile
    stub)."""
    wd = watchdog.StallWatchdog(0.2, artifact_dir=str(tmp_path),
                                poll_seconds=0.05)
    cseq = recorder.record("compile", "enter", detail="slow first compile")
    dseq = recorder.record("dispatch", "enter", detail="block(K=4)")
    time.sleep(0.45)  # way past the threshold, but compiling
    assert wd.stalled_spans() == []
    assert wd.check() is None
    recorder.record("compile", "exit", cseq)
    time.sleep(0.1)  # age restarts at compile exit: still not stalled
    assert wd.stalled_spans() == []
    time.sleep(0.25)  # now genuinely stalled past the threshold
    stalled = wd.stalled_spans()
    assert [s["seq"] for s in stalled] == [dseq]
    assert wd.check() is not None
    recorder.record("dispatch", "exit", dseq)


def test_watchdog_thread_fires_without_manual_polling(tmp_path):
    wd = watchdog.StallWatchdog(0.1, artifact_dir=str(tmp_path),
                                poll_seconds=0.03)
    wd.start()
    try:
        recorder.record("barrier", "enter", detail="lost peer")
        deadline = time.time() + 5
        while wd.artifact_path is None and time.time() < deadline:
            time.sleep(0.02)
        assert wd.artifact_path and os.path.exists(wd.artifact_path)
    finally:
        wd.stop()


def test_watchdog_survives_unwritable_artifact_dir(tmp_path):
    """A failed artifact write must not crash the watchdog (and, for
    action=abort, must not cancel the abort — the dump is wrapped, the
    action is not).  Here: artifact_dir is a FILE, so makedirs raises."""
    blocker = tmp_path / "not_a_dir"
    blocker.write_text("x")
    wd = watchdog.StallWatchdog(0.05, artifact_dir=str(blocker),
                                poll_seconds=0.02)
    seq = recorder.record("dispatch", "enter")
    time.sleep(0.1)
    assert wd.check() is None  # dump failed, swallowed, span marked
    assert wd.check() is None  # and not re-reported every poll
    recorder.record("dispatch", "exit", seq)


def test_attribute_stall_verdicts():
    done = {"entered": 5, "exited": 5,
            "last_entered_seq": 5, "last_exited_seq": 5}
    behind = {"entered": 4, "exited": 4,
              "last_entered_seq": 4, "last_exited_seq": 4}
    stuck = {"entered": 5, "exited": 4,
             "last_entered_seq": 5, "last_exited_seq": 4}
    att = watchdog.attribute_stall("dispatch", 5, {0: {"dispatch": done},
                                                   1: {"dispatch": behind}})
    assert att["verdict"] == "straggler" and att["ranks_behind"] == [1]
    assert "never entered dispatch seq 5" in att["detail"]
    att = watchdog.attribute_stall("dispatch", 5, {0: {"dispatch": stuck},
                                                   1: {"dispatch": stuck}})
    assert att["verdict"] == "hang" and att["ranks_behind"] == []
    # a peer that never recorded the kind at all is also "behind"
    att = watchdog.attribute_stall("dispatch", 5, {1: {}})
    assert att["verdict"] == "straggler" and att["ranks_behind"] == [1]
    assert watchdog.attribute_stall("dispatch", 5, {})["verdict"] == "unknown"


# ----------------------------------------------------------------------
# cluster aggregation + skew
# ----------------------------------------------------------------------

def _snap(rank, step_mean, entered):
    return {"rank": rank, "t_wall": time.time(), "steps": 10,
            "dispatches": entered, "step_count": 5,
            "step_mean_s": step_mean, "step_p50_s": step_mean,
            "mfu": 0.5,
            "recorder_progress": {"dispatch": {
                "entered": entered, "exited": entered,
                "last_entered_seq": entered, "last_exited_seq": entered}},
            "clock_offset_s": 0.0}


def test_aggregator_reporter_roundtrip(tmp_path):
    cluster = str(tmp_path / "cluster.jsonl")
    agg = aggregate.Aggregator(0, cluster_file=cluster, interval_s=0.05)
    final = {"entered": 5}  # mutated below to pin the stop-time flush
    reps = [aggregate.Reporter("127.0.0.1", agg.port, interval_s=0.05,
                               rank=r,
                               snapshot_fn=lambda r=r: _snap(
                                   r, 0.1 * (1 + r),
                                   final["entered"] - r))
            for r in (0, 1)]
    try:
        for r in reps:
            r.start()
        deadline = time.time() + 10
        while time.time() < deadline:
            peers = aggregate.query_peers(("127.0.0.1", agg.port))
            if sorted(peers) == [0, 1]:
                break
            time.sleep(0.05)
        assert sorted(peers) == [0, 1], peers
        assert peers[1]["recorder_progress"]["dispatch"]["entered"] == 4
        # the handshake measured a (near-zero, same-host) clock offset
        assert reps[1].offset_s is not None
        assert abs(reps[1].offset_s) < 1.0
        rec = agg.cluster_record()
        assert rec["schema"] == "mxtpu-obs-cluster-v1"
        assert rec["nranks"] == 2
        assert rec["skew"]["slowest_rank"] == 1
        assert rec["skew"]["max_over_median"] == pytest.approx(0.2 / 0.15)
        # watchdog attribution rides the same peers view
        att = watchdog.attribute_stall(
            "dispatch", 5,
            {r: p["recorder_progress"] for r, p in peers.items()})
        assert att["verdict"] == "straggler" and att["ranks_behind"] == [1]
        # stop-time final flush: progress that advanced AFTER the last
        # interval tick still reaches the aggregator (short runs end on
        # their real final state)
        final["entered"] = 99
        for r in reps:
            r.stop()
        for r in reps:
            r.join(timeout=10)
        peers = aggregate.query_peers(("127.0.0.1", agg.port))
        assert peers[0]["recorder_progress"]["dispatch"]["entered"] == 99
        agg.force_write()
    finally:
        for r in reps:
            r.stop()
        agg.close()
    lines = [json.loads(l) for l in open(cluster).read().splitlines()]
    assert lines and lines[-1]["schema"] == "mxtpu-obs-cluster-v1"
    assert lines[-1]["ranks"]["0"]["dispatches"] == 99


def test_query_peers_degrades_to_empty():
    # unreachable endpoint and unarmed env both mean {} (per-rank-only
    # attribution), never an exception
    assert aggregate.query_peers(("127.0.0.1", 1), timeout=0.5) == {}
    assert aggregate.query_peers(endpoint=None) == {}


def test_step_skew_math():
    skew = aggregate.step_skew({0: 0.1, 1: 0.1, 2: 0.3})
    assert skew["slowest_rank"] == 2
    assert skew["max_over_median"] == pytest.approx(3.0)
    assert aggregate.step_skew({}) == {"max_over_median": None,
                                       "slowest_rank": None}
    assert aggregate.step_skew({0: None})["slowest_rank"] is None


def test_parse_log_cluster_columns(tmp_path):
    import parse_log

    rec = {"schema": "mxtpu-obs-cluster-v1", "nranks": 2,
           "ranks": {"0": {"steps": 10, "step_mean_s": 0.1},
                     "1": {"steps": 9, "step_mean_s": 0.2}},
           "skew": {"max_over_median": 4.0 / 3.0, "slowest_rank": 1}}
    old = {"flush_seq": 1, "counters": {}, "gauges": {}, "histograms": {}}
    rows = parse_log.parse_cluster([json.dumps(old), json.dumps(rec)])
    # pre-obs single-rank record renders '-' everywhere
    assert rows[0]["steps"] is None and rows[0]["skew"] is None
    assert rows[1]["steps"] == "r0:10;r1:9"
    assert rows[1]["slowest"] == 1 and rows[1]["nranks"] == 2
    f = tmp_path / "c.jsonl"
    f.write_text(json.dumps(old) + "\n" + json.dumps(rec) + "\n")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "parse_log.py"),
         "--cluster", str(f)], capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "slowest" in out.stdout and "r0:10;r1:9" in out.stdout
    assert "| - |" in out.stdout  # the legacy row


# ----------------------------------------------------------------------
# per-rank sink suffix (satellite: the multi-process sink collision)
# ----------------------------------------------------------------------

def test_telemetry_flush_suffixes_per_rank(tmp_path, monkeypatch):
    base = str(tmp_path / "telem.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_FILE", base)
    monkeypatch.setenv("MXTPU_PROCESS_ID", "1")
    telemetry.flush()
    assert os.path.exists(base + ".r1")
    assert not os.path.exists(base)  # rank 1 never writes the bare path
    rec = json.loads(open(base + ".r1").read().splitlines()[0])
    assert rec["flush_seq"] >= 1
    # single-process runs (no MXTPU_PROCESS_ID) keep the exact path
    monkeypatch.delenv("MXTPU_PROCESS_ID")
    telemetry.flush()
    assert os.path.exists(base)
    assert telemetry.rank_suffixed("") == ""


def test_profiler_dump_suffixes_per_rank_and_stamps_meta(
        tmp_path, monkeypatch):
    base = str(tmp_path / "trace.json")
    monkeypatch.setenv("MXTPU_PROCESS_ID", "3")
    profiler.set_trace_meta(rank=3, clock_offset_us=250.0)
    profiler.profiler_set_config(mode="symbolic", filename=base)
    profiler.profiler_set_state("run")
    profiler.record_span("probe", 0, 10)
    profiler.profiler_set_state("stop")
    path = profiler.dump_profile()
    try:
        assert path == base + ".r3" and os.path.exists(path)
        payload = json.load(open(path))
        assert payload["otherData"]["rank"] == 3
        assert payload["otherData"]["clock_offset_us"] == 250.0
        assert any(e.get("name") == "probe"
                   for e in payload["traceEvents"])
    finally:
        profiler.set_trace_meta(rank=0, clock_offset_us=0.0)
        profiler.profiler_set_config(mode="symbolic",
                                     filename="profile.json")


# ----------------------------------------------------------------------
# trace stitching (unit level; the launcher test below does it live)
# ----------------------------------------------------------------------

def _fake_trace(rank, offset_us):
    return {"traceEvents": [
        {"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
         "args": {"name": "host"}},
        {"name": "process_sort_index", "ph": "M", "pid": 0, "tid": 0,
         "args": {"sort_index": 0}},
        {"name": "fit.dispatch", "cat": "executor", "ph": "X",
         "ts": 1000.0, "dur": 50, "pid": 0, "tid": 7}],
        "displayTimeUnit": "ms",
        "otherData": {"rank": rank, "clock_offset_us": offset_us}}


def test_obs_stitch_aligns_and_namespaces(tmp_path):
    base = str(tmp_path / "p.json")
    for r, off in ((0, 0.0), (1, 400.0)):
        with open("%s.r%d" % (base, r), "w") as f:
            json.dump(_fake_trace(r, off), f)
    out = str(tmp_path / "merged.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_stitch.py"),
         base, "-o", out], capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    merged = json.load(open(out))
    assert merged["otherData"]["stitched_ranks"] == [0, 1]
    names = {e["args"]["name"] for e in merged["traceEvents"]
             if e.get("name") == "process_name"}
    assert names == {"rank0/host", "rank1/host"}
    spans = sorted((e["pid"], e["ts"]) for e in merged["traceEvents"]
                   if e.get("ph") == "X")
    # disjoint pid ranges per rank; rank 1 shifted onto rank 0's clock
    assert spans == [(0, 1000.0), (100, 1400.0)]


# ----------------------------------------------------------------------
# ModelServer.health() (satellite: the router probe surface)
# ----------------------------------------------------------------------

def _tiny_server(**kw):
    mx.random.seed(11)
    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=4, name="fc"),
        name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (1, 6))], label_shapes=None,
             for_training=False)
    mod.init_params(mx.init.Xavier())
    arg, aux = mod.get_params()
    params = {"arg:%s" % k: v for k, v in arg.items()}
    params.update({"aux:%s" % k: v for k, v in aux.items()})
    pred = mx.Predictor(net, params, {"data": (1, 6)}, ctx=mx.cpu())
    return mx.serving.ModelServer({"t": pred}, max_batch=4, **kw)


def test_health_flooded_then_drained():
    from mxnet_tpu.serving.session import TenantSession

    gate = threading.Event()
    orig = TenantSession.dispatch

    def slow_dispatch(self, reqs):
        gate.wait(10)
        return orig(self, reqs)

    server = _tiny_server(timeout_ms=60000, wait_ms=1.0)
    try:
        h0 = server.health()
        assert h0["healthy"] and h0["batcher_alive"] and not h0["closed"]
        assert h0["queue_depth"] == 0
        assert h0["oldest_deadline_in_s"] is None  # idle: nothing queued
        assert h0["tenants"] == ["t"] and h0["dispatch_errors"] == 0
        assert h0["queue_headroom"] > 0
        TenantSession.dispatch = slow_dispatch
        x = np.zeros((6,), "float32")
        futs = [server.submit("t", {"data": x}) for _ in range(6)]
        # flooded: the batcher is gated, so beyond one in-flight fill
        # the rest sit queued
        deadline = time.time() + 5
        while server.health()["queue_depth"] == 0 and time.time() < deadline:
            time.sleep(0.01)
        h1 = server.health()
        assert h1["queue_depth"] > 0
        assert h1["per_tenant_depth"]["t"] == h1["queue_depth"]
        assert h1["oldest_deadline_in_s"] is not None
        assert 0 < h1["oldest_deadline_in_s"] <= 60.0
        assert h1["queue_headroom"] < h0["queue_headroom"]
    finally:
        TenantSession.dispatch = orig
        gate.set()
        server.close()
    for f in futs:
        assert f.result(timeout=30)[0].shape == (4,)
    h2 = server.health()
    assert h2["closed"] and not h2["healthy"]
    assert h2["queue_depth"] == 0 and h2["oldest_deadline_in_s"] is None
    assert h2["dispatches"] > 0 and h2["dispatch_errors"] == 0


def test_cold_serving_fill_opens_compile_bracket():
    """An UNWARMED bucket's first fill pays the XLA compile inside the
    dispatch, so the session must open the recorder's compile bracket —
    the stall watchdog stays suppressed across a slow cold compile
    instead of aborting a healthy server."""
    server = _tiny_server(timeout_ms=60000, wait_ms=1.0)
    try:
        fut = server.submit("t", {"data": np.zeros((6,), "float32")})
        assert fut.result(timeout=60)[0].shape == (4,)
        prog = recorder.progress()
        assert prog["serve"]["entered"] == prog["serve"]["exited"] >= 1
        assert prog["compile"]["entered"] == prog["compile"]["exited"] >= 1
        # a second fill of the now-warm bucket adds NO compile bracket
        compiles = prog["compile"]["entered"]
        fut = server.submit("t", {"data": np.zeros((6,), "float32")})
        fut.result(timeout=60)
        assert recorder.progress()["compile"]["entered"] == compiles
    finally:
        server.close()


def test_health_counts_dispatch_errors():
    from mxnet_tpu.serving.session import TenantSession

    orig = TenantSession.dispatch

    def exploding(self, reqs):
        raise RuntimeError("boom")

    server = _tiny_server(timeout_ms=60000, wait_ms=1.0)
    try:
        TenantSession.dispatch = exploding
        fut = server.submit("t", {"data": np.zeros((6,), "float32")})
        with pytest.raises(RuntimeError):
            fut.result(timeout=30)
        deadline = time.time() + 5
        while (server.health()["dispatch_errors"] == 0
               and time.time() < deadline):
            time.sleep(0.01)
        assert server.health()["dispatch_errors"] == 1
    finally:
        TenantSession.dispatch = orig
        server.close(drain=False)


# ----------------------------------------------------------------------
# launcher acceptance: chaos watchdog + live stitch
# ----------------------------------------------------------------------

def _clean_env(extra=None):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    for k in list(env):
        if k.startswith(("TPU_", "MXTPU_OBS_")):
            env.pop(k)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra or {})
    return env


def _launch_obs(script, script_args, extra_env, timeout=420):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "--local-spmd", "-n", "2", "-s", "0", "--local-devices", "1",
         "--obs",
         sys.executable, os.path.join(REPO, "tests", script)]
        + script_args,
        env=_clean_env(extra_env), capture_output=True, text=True,
        timeout=timeout, cwd=REPO)


def test_chaos_stalled_rank_yields_postmortem_and_no_forever_hang(tmp_path):
    """ISSUE 11 acceptance: 2-process --local-spmd fit, rank 1
    stub-stalls mid-epoch -> the HEALTHY rank's watchdog writes a
    post-mortem naming the stalled rank and the stalled collective
    seq within the configured window, and aborts instead of hanging
    forever (the launcher returns nonzero well inside the test
    timeout)."""
    obs_dir = str(tmp_path)
    cluster = os.path.join(obs_dir, "cluster.jsonl")
    proc = _launch_obs("obs_chaos_script.py", [], {
        "MXTPU_OBS_STALL_SECONDS": "4",
        "MXTPU_OBS_STALL_ACTION": "abort",
        "MXTPU_OBS_DIR": obs_dir,
        "MXTPU_OBS_CLUSTER_FILE": cluster,
        "MXTPU_OBS_INTERVAL_SECONDS": "0.25",
    }, timeout=420)
    # the healthy rank ABORTED (watchdog exit code) instead of hanging;
    # the stalled rank exited quietly once the post-mortem landed
    assert proc.returncode != 0, proc.stdout + proc.stderr
    assert "stub-stall" in proc.stdout, proc.stdout + proc.stderr
    assert "CHAOS" in proc.stdout
    art_path = os.path.join(obs_dir, "postmortem.r0.json")
    assert os.path.exists(art_path), (
        os.listdir(obs_dir), proc.stdout, proc.stderr)
    art = json.load(open(art_path))
    assert art["rank"] == 0
    stalled = art["stalled"][0]
    # `stage_wait`: since the K-step loop runs one block ahead (PR 42)
    # the healthy rank may be waiting for its next staged block — whose
    # device work queues behind the dispatch that will never finish —
    # and not yet in that dispatch's readback
    assert stalled["kind"] in ("dispatch", "allgather", "barrier",
                               "stage_wait")
    assert stalled["seq"] is not None
    assert stalled["age_s"] >= 4.0
    # the artifact NAMES the stalled rank: rank 1 never entered the
    # collective seq the healthy rank is blocked in
    assert art["attribution"]["verdict"] == "straggler", art["attribution"]
    assert 1 in art["attribution"]["ranks_behind"], art["attribution"]
    assert str(stalled["seq"]) in art["attribution"]["detail"]
    # peers + stacks made it into the artifact
    assert "1" in art["peers"]
    assert any("MainThread" in k or k for k in art["stacks"])
    # the aggregator wrote cluster records covering both ranks
    recs = [json.loads(l) for l in open(cluster).read().splitlines()]
    assert any(r.get("nranks") == 2 for r in recs), recs[-1:]


def test_chaos_divergent_schedule_named_before_watchdog_window(tmp_path):
    """ISSUE 12 acceptance: 2-process --local-spmd fit with
    MXTPU_COLLECTIVE_CHECK=1; rank 1 takes a divergent bucket path
    mid-epoch (one extra collective edge event with a different
    bucket-plan fingerprint) and KEEPS TRAINING — nothing hangs.  The
    schedule verifier must name the first diverging collective (kind +
    seq) and both ranks in its artifact, and the job must terminate
    (exit 18, DIVERGENCE_EXIT_CODE) well before the far-out stall
    watchdog deadline instead of relying on a hang + timeout."""
    from mxnet_tpu.parallel.schedule_check import DIVERGENCE_EXIT_CODE

    obs_dir = str(tmp_path)
    cluster = os.path.join(obs_dir, "cluster.jsonl")
    stall_s = 150.0
    t0 = time.time()
    proc = _launch_obs("sched_div_script.py", [], {
        "MXTPU_COLLECTIVE_CHECK": "1",
        "MXTPU_OBS_STALL_SECONDS": str(stall_s),
        "MXTPU_OBS_STALL_ACTION": "abort",
        "MXTPU_OBS_DIR": obs_dir,
        "MXTPU_OBS_CLUSTER_FILE": cluster,
        "MXTPU_OBS_INTERVAL_SECONDS": "0.25",
    }, timeout=420)
    elapsed = time.time() - t0
    # the launcher returned NONZERO (verifier abort), and did so before
    # the stall-watchdog deadline — the divergence was caught from the
    # schedule streams, not from a hang
    assert proc.returncode != 0, proc.stdout + proc.stderr
    assert elapsed < stall_s, (elapsed, proc.stdout, proc.stderr)
    assert "divergent bucket path" in proc.stdout, (
        proc.stdout + proc.stderr)
    arts = [os.path.join(obs_dir, "sched_divergence.r%d.json" % r)
            for r in (0, 1)]
    arts = [a for a in arts if os.path.exists(a)]
    assert arts, (os.listdir(obs_dir), proc.stdout, proc.stderr)
    art = json.load(open(arts[0]))
    assert art["schema"] == "mxtpu-sched-divergence-v1"
    rep = art["report"]
    # both ranks named, and the first diverging event carries a kind +
    # per-kind seq from the flight-recorder stream
    assert rep["ranks"] == [0, 1], rep
    events = [rep.get("event_here"), rep.get("event_peer")]
    events = [e for e in events if e]
    assert events, rep
    assert all(e["kind"] in ("dispatch", "allreduce", "allgather",
                             "barrier") and e["seq"] is not None
               for e in events), rep
    # the divergent bucket fingerprint is visible on one side
    assert any("divergent-bucket" in (e.get("detail") or "")
               for e in events), rep
    # exit code is the verifier's, not the watchdog's (17)
    assert (DIVERGENCE_EXIT_CODE & 0xFF) == 18


def test_stitch_two_rank_profiles_and_cluster_table(tmp_path):
    """ISSUE 11 acceptance: a profiled 2-process fit leaves one trace
    per rank (.r<rank> suffix) with measured clock offsets; obs_stitch
    merges them into one timeline with rank-namespaced lanes from BOTH
    ranks, and parse_log --cluster renders the per-rank skew table
    from the run's aggregator JSONL."""
    base = str(tmp_path / "trace.json")
    cluster = str(tmp_path / "cluster.jsonl")
    proc = _launch_obs("spmd_fit_script.py", ["--profile", base], {
        "MXTPU_OBS_CLUSTER_FILE": cluster,
        "MXTPU_OBS_INTERVAL_SECONDS": "0.25",
    }, timeout=420)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for r in (0, 1):
        assert os.path.exists("%s.r%d" % (base, r)), proc.stdout
    # per-rank traces carry the stitch metadata from the obs handshake
    p1 = json.load(open(base + ".r1"))
    assert p1["otherData"]["rank"] == 1
    assert isinstance(p1["otherData"]["clock_offset_us"], float)
    out = str(tmp_path / "merged.json")
    st = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_stitch.py"),
         base, "-o", out], capture_output=True, text=True, timeout=60)
    assert st.returncode == 0, st.stdout + st.stderr
    merged = json.load(open(out))
    assert merged["otherData"]["stitched_ranks"] == [0, 1]
    names = {e["args"]["name"] for e in merged["traceEvents"]
             if e.get("name") == "process_name"}
    assert "rank0/host" in names and "rank1/host" in names, names
    # real spans from BOTH ranks, on disjoint pid ranges
    span_pids = {e["pid"] // 100 for e in merged["traceEvents"]
                 if e.get("ph") == "X"
                 and e.get("name") == "fit.dispatch"}
    assert span_pids == {0, 1}, span_pids
    # the same run's cluster JSONL renders the per-rank skew table; the
    # exit-time force_write ends it on the run's real final state
    recs = open(cluster).read().splitlines()
    assert recs
    last = json.loads(recs[-1])
    assert last["ranks"]["0"]["steps"] > 0, last
    pl = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "parse_log.py"),
         "--cluster", cluster], capture_output=True, text=True, timeout=60)
    assert pl.returncode == 0, pl.stderr
    assert "slowest" in pl.stdout
    assert any(("r0:" in l and "r1:" in l)
               for l in pl.stdout.splitlines()), pl.stdout


# ----------------------------------------------------------------------
# collective-schedule verifier (ISSUE 12): unit level — the chaos test
# above drives it live across 2 launcher processes
# ----------------------------------------------------------------------

def _lockstep_logs(n=30):
    from mxnet_tpu.parallel import schedule_check as sc

    a, b = sc.ScheduleLog(), sc.ScheduleLog()
    for i in range(1, n + 1):
        for log in (a, b):
            log.note("dispatch", i, nbytes=100, detail="block(K=2)")
    return a, b


def test_schedule_log_consistent_and_skew_tolerant():
    from mxnet_tpu.parallel import schedule_check as sc

    a, b = _lockstep_logs()
    assert sc.first_divergence(a.digest(), b.digest()) is None
    # skew (one rank ahead) is NOT divergence: common prefix agrees
    b.note("dispatch", 31, nbytes=100, detail="block(K=2)")
    b.note("dispatch", 32, nbytes=100, detail="block(K=2)")
    assert sc.first_divergence(a.digest(), b.digest()) is None
    # digests are shippable plain data
    d = a.digest()
    assert d["count"] == 30 and isinstance(d["hash"], str)
    assert d["recent"][-1]["index"] == 29


def test_schedule_divergence_names_first_event_and_both_sides():
    from mxnet_tpu.parallel import schedule_check as sc

    a, b = _lockstep_logs()
    # rank b takes a divergent bucket path at index 30
    b.note("allreduce", 7, nbytes=999, detail="divergent-bucket(b=9)")
    for i in (31, 32):
        a.note("dispatch", i, nbytes=100, detail="block(K=2)")
        b.note("dispatch", i, nbytes=100, detail="block(K=2)")
    div = sc.first_divergence(a.digest(), b.digest())
    assert div is not None and div["index"] == 30
    assert div["event_peer"] == {"kind": "allreduce", "seq": 7,
                                 "nbytes": 999,
                                 "detail": "divergent-bucket(b=9)"}
    assert div["event_here"]["kind"] == "dispatch"
    assert not div["truncated"]
    # same-count different-bytes (a diverging bucket PLAN, not an
    # extra event) also diverges — nbytes is part of the fingerprint
    c, d = _lockstep_logs(5)
    c.note("dispatch", 6, nbytes=100, detail="block(K=2,buckets=3)")
    d.note("dispatch", 6, nbytes=400, detail="block(K=2,buckets=9)")
    div = sc.first_divergence(c.digest(), d.digest())
    assert div is not None and div["index"] == 5


def test_schedule_verifier_dumps_aborts_and_caches_peers(tmp_path):
    from mxnet_tpu.parallel import schedule_check as sc

    a, b = _lockstep_logs()
    b.note("barrier", 1, detail="divergent")
    a.note("dispatch", 31, nbytes=100, detail="block(K=2)")
    codes = []
    peers = {1: {"sched": b.digest()}}
    v = sc.ScheduleVerifier(interval_s=999, action="abort",
                            artifact_dir=str(tmp_path), rank=0,
                            query_fn=lambda: peers, digest_fn=a.digest,
                            abort_fn=codes.append)
    rep = v.check()
    assert codes == [sc.DIVERGENCE_EXIT_CODE] and rep["ranks"] == [0, 1]
    art = json.load(open(v.artifact_path))
    assert art["schema"] == "mxtpu-sched-divergence-v1"
    assert not os.path.exists(v.artifact_path + ".tmp")
    assert art["report"]["event_peer"]["kind"] == "barrier"
    # peer digests are CACHED: a dead aggregator (empty query) after
    # the peer shipped once still detects — both sides of a divergence
    # terminate even if one aborts first and takes the aggregator down
    codes2 = []
    v2 = sc.ScheduleVerifier(interval_s=999, action="abort",
                             artifact_dir=str(tmp_path), rank=0,
                             query_fn=lambda: peers, digest_fn=a.digest,
                             abort_fn=codes2.append)
    v2.check()
    peers_now = {}
    v2._query_fn = lambda: peers_now
    assert codes2 == [sc.DIVERGENCE_EXIT_CODE]
    # dump action raises a ScheduleDivergence naming the event
    v3 = sc.ScheduleVerifier(interval_s=999, action="dump",
                             artifact_dir=str(tmp_path), rank=0,
                             query_fn=lambda: peers, digest_fn=a.digest)
    with pytest.raises(sc.ScheduleDivergence) as ei:
        v3.check()
    assert "rank 0 and rank 1" in str(ei.value)
    # reported once: the same divergence does not re-raise every poll
    assert v3.check() is None


def test_recorder_schedule_hook_feeds_only_collective_kinds():
    """MXTPU_COLLECTIVE_CHECK wiring: with the hook installed, enter
    events of collective-shaped kinds fold into the schedule log;
    serve fills and compile brackets (rank-local, legitimately
    divergent) do not, and exits never do."""
    from mxnet_tpu.parallel import schedule_check as sc

    sc.reset()
    prev = sc.set_enabled(True)
    try:
        s = recorder.record("dispatch", "enter", detail="block(K=2)",
                            nbytes=64)
        recorder.record("dispatch", "exit", s)
        recorder.record("serve", "enter", detail="t,b=4")
        recorder.record("compile", "enter")
        d = sc.digest()
        assert d["count"] == 1
        assert d["recent"][0]["kind"] == "dispatch"
        assert d["recent"][0]["nbytes"] == 64
    finally:
        sc.set_enabled(prev)
        sc.reset()


def test_snapshot_carries_schedule_digest_only_when_armed():
    from mxnet_tpu.parallel import schedule_check as sc

    sc.reset()
    prev = sc.set_enabled(False)
    try:
        assert aggregate.build_snapshot(rank=0)["sched"] is None
        sc.set_enabled(True)
        recorder.record("dispatch", "enter", detail="block(K=2)")
        snap = aggregate.build_snapshot(rank=0)
        assert snap["sched"]["count"] == 1
        assert snap["sched"]["recent"][0]["kind"] == "dispatch"
    finally:
        sc.set_enabled(prev)
        sc.reset()
