"""Multi-process distributed runtime (ISSUE 10): `tools/launch.py
--local-spmd` brings N OS processes into ONE jax.distributed global
mesh, `Module.fit` trains on it through the K-step fused dispatch with
EXPLICIT bucketed hierarchical gradient collectives
(executor._comm_mode + parallel/collectives), and the dist_sync kvstore
control plane rides the same launcher.  tests/spmd_fit_script.py is the
worker; the launcher subprocess tests are the tier-1 proof that the
runtime is real — not a single-process simulation."""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine, profiler, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel import collectives

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _clean_env(extra=None):
    env = dict(os.environ)
    # fresh CPU-only runtime per process: no inherited device-count flag
    # (multihost.initialize sets its own from MXTPU_LOCAL_DEVICES)
    env.pop("XLA_FLAGS", None)
    for k in list(env):
        if k.startswith("TPU_"):
            env.pop(k)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra or {})
    return env


def _launch_spmd(n, servers, script_args, extra_env=None, timeout=420,
                 local_devices=2):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "--local-spmd", "-n", str(n), "-s", str(servers),
         "--local-devices", str(local_devices),
         sys.executable, os.path.join(REPO, "tests", "spmd_fit_script.py")]
        + script_args,
        env=_clean_env(extra_env), capture_output=True, text=True,
        timeout=timeout, cwd=REPO)
    return proc


def _parse_fit_lines(out):
    # finditer with number-only character classes: even if the two
    # ranks' writes ever interleave on the shared pipe, one record can
    # never swallow the next (the class excludes the 'S' of SPMDFIT)
    recs = {}
    for m in re.finditer(r"SPMDFIT rank=(\d+) axes=([\w,]+) "
                         r"losses=([\d.;eE+-]+) digest=([\d.;eE+-]+)",
                         out):
        recs[int(m.group(1))] = {
            "axes": m.group(2).split(","),
            "losses": np.array([float(v) for v
                                in m.group(3).split(";")]),
            "digest": np.array([float(v) for v
                                in m.group(4).split(";")]),
        }
    return recs


# ----------------------------------------------------------------------
# tier-1 acceptance: 2-process CPU-mesh Module.fit parity
# ----------------------------------------------------------------------

def test_local_spmd_fit_matches_single_process():
    """`launch.py --local-spmd -n 2` (2 procs x 2 devices each,
    hierarchical data_dcn x data_ici mesh): every rank reports the SAME
    per-dispatch loss trajectory and final params, and both match the
    single-process answer — the gradient path (local vjp -> bucketed
    ICI-then-DCN hierarchical psum inside the fused scan) is
    numerically the single-chip training loop."""
    proc = _launch_spmd(2, 0, [], timeout=420)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    recs = _parse_fit_lines(proc.stdout)
    assert sorted(recs) == [0, 1], proc.stdout + proc.stderr
    # the hierarchical topology was actually built (2 procs x 2 local)
    assert recs[0]["axes"] == ["data_dcn", "data_ici"], recs[0]["axes"]
    np.testing.assert_array_equal(recs[0]["losses"], recs[1]["losses"])
    np.testing.assert_array_equal(recs[0]["digest"], recs[1]["digest"])
    # single-process reference: the same fit, no mesh, in this process
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from spmd_fit_script import run_fit

    ref_losses, ref_digest = run_fit(mx, np, None, 1)
    assert len(ref_losses) == len(recs[0]["losses"]) and ref_losses, \
        (len(ref_losses), len(recs[0]["losses"]))
    np.testing.assert_allclose(recs[0]["losses"], ref_losses,
                               rtol=5e-4, atol=1e-5)
    np.testing.assert_allclose(recs[0]["digest"], ref_digest,
                               rtol=5e-3, atol=5e-5)


def test_local_spmd_transformer_fit_matches_single_process():
    """The transformer SPMD pin (ROADMAP item 2): `launch.py
    --local-spmd -n 2` trains the TransformerLM causal-LM problem —
    attention, LayerNorm, weight-tied softmax — through the same fused
    dispatch + hierarchical gradient collectives, and every rank's
    per-dispatch perplexity trajectory and final params match the
    single-process answer."""
    proc = _launch_spmd(2, 0, ["--transformer"], timeout=420)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    recs = _parse_fit_lines(proc.stdout)
    assert sorted(recs) == [0, 1], proc.stdout + proc.stderr
    assert recs[0]["axes"] == ["data_dcn", "data_ici"], recs[0]["axes"]
    np.testing.assert_array_equal(recs[0]["losses"], recs[1]["losses"])
    np.testing.assert_array_equal(recs[0]["digest"], recs[1]["digest"])
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from spmd_fit_script import run_fit_transformer

    ref_losses, ref_digest = run_fit_transformer(mx, np, None, 1)
    assert len(ref_losses) == len(recs[0]["losses"]) and ref_losses, \
        (len(ref_losses), len(recs[0]["losses"]))
    np.testing.assert_allclose(recs[0]["losses"], ref_losses,
                               rtol=5e-4, atol=1e-5)
    np.testing.assert_allclose(recs[0]["digest"], ref_digest,
                               rtol=5e-3, atol=5e-5)


def test_local_spmd_dist_kvstore_parity():
    """The dist_sync parameter-server control plane rides the SAME
    --local-spmd launcher invocation: workers that joined the SPMD mesh
    also push/pull through scheduler+servers (reference-style
    multi-machine scripts run unmodified)."""
    proc = _launch_spmd(2, 2, ["--no-fit", "--kvstore-check"],
                        timeout=300, local_devices=1)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert proc.stdout.count("SPMDMESH") == 2, proc.stdout + proc.stderr
    kv_lines = [l for l in proc.stdout.splitlines()
                if l.startswith("KVOK")]
    assert len(kv_lines) == 2, proc.stdout + proc.stderr
    # push of (rank+1)*ones from 2 workers -> every rank pulls 3.0
    assert all("sum=3.0" in l for l in kv_lines), kv_lines


# ----------------------------------------------------------------------
# single-host bucketed-collective checks (in-process, 8-device mesh)
# ----------------------------------------------------------------------

def _tiny_fit(contexts, k, epochs=1, collect_losses=False):
    from mxnet_tpu.ops.random_ops import HOST_RNG

    mx.random.seed(0)
    HOST_RNG.seed(77)
    rng = np.random.RandomState(3)
    X = rng.randn(64, 10).astype(np.float32)
    y = rng.randint(0, 3, 64).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    d = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(d, num_hidden=8, name="fc1")
    a = mx.sym.Activation(h, act_type="relu")
    o = mx.sym.FullyConnected(a, num_hidden=3, name="fc2")
    net = mx.sym.SoftmaxOutput(o, name="softmax")
    mod = mx.mod.Module(net, context=contexts)
    losses = []

    def on_batch(param):
        losses.extend(v for _, v in param.eval_metric.get_name_value())

    mod.fit(it, num_epoch=epochs, kvstore=None, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.init.Xavier(), eval_metric="acc",
            steps_per_dispatch=k,
            batch_end_callback=on_batch if collect_losses else None)
    args, _ = mod.get_params()
    return mod, {n: v.asnumpy() for n, v in args.items()}


def test_bucketed_collectives_match_implicit_spmd(monkeypatch):
    """MXTPU_COMM_BUCKETED=1 on a single-host 4-device mesh: the
    explicit shard_map path (bucketed hierarchical psum inside the
    fused scan) trains to the same params as the implicit
    XLA-partitioner path, and the comm.* books fill."""
    ctxs = [mx.cpu(i) for i in range(4)]
    monkeypatch.setenv("MXTPU_COMM_BUCKETED", "0")
    _, base = _tiny_fit(ctxs, 2)
    monkeypatch.setenv("MXTPU_COMM_BUCKETED", "1")
    monkeypatch.setenv("MXTPU_COMM_BUCKET_MB", "0.0002")  # force >1 bucket
    d0 = telemetry.counter_value("comm.dispatches")
    mod, packed = _tiny_fit(ctxs, 2)
    for n in base:
        np.testing.assert_allclose(packed[n], base[n],
                                   rtol=2e-5, atol=2e-6, err_msg=n)
    assert telemetry.counter_value("comm.dispatches") > d0
    assert telemetry.gauge_value("comm.buckets") >= 2
    assert telemetry.counter_value("comm.bytes_reduced") > 0
    # the probe measures the collectives the run just used
    res = mod._exec_group.execs[0].measure_comm(iters=1)
    assert res["buckets"] >= 2 and res["comm_gbps"] > 0
    assert 0.0 <= res["overlap_frac"] <= 1.0
    assert telemetry.gauge_value("comm.gbps") == pytest.approx(
        res["comm_gbps"])


def test_comm_spans_render_beside_fused_dispatch(monkeypatch, tmp_path):
    """The comm probe's bucket/overlap spans land in the dumped chrome
    trace as named lanes beside the fit.dispatch span."""
    monkeypatch.setenv("MXTPU_COMM_BUCKETED", "1")
    monkeypatch.setenv("MXTPU_COMM_BUCKET_MB", "0.0002")
    fname = str(tmp_path / "trace.json")
    profiler.profiler_set_config(mode="all", filename=fname)
    profiler.profiler_set_state("run")
    try:
        mod, _ = _tiny_fit([mx.cpu(i) for i in range(2)], 2)
        mod._exec_group.execs[0].measure_comm(iters=1)
    finally:
        profiler.profiler_set_state("stop")
        profiler.dump_profile()
    events = json.load(open(fname))["traceEvents"]
    names = {e.get("name", "") for e in events}
    assert "fit.dispatch" in names, names
    assert "comm.allreduce" in names
    assert "comm.overlap_probe" in names
    # comm gauges render as chrome counter lanes while profiling
    counters = {e["name"] for e in events if e.get("ph") == "C"}
    assert any(c.startswith("comm.") for c in counters), counters


def test_sanitizer_zero_violations_with_bucketed_collectives(monkeypatch):
    """A full fit epoch with the explicit bucketed-collective dispatch
    under SanitizerEngine: every staged block / fused dispatch /
    metric readback declares what it touches — zero violations."""
    monkeypatch.setenv("MXTPU_COMM_BUCKETED", "1")
    prev = engine.get().kind
    eng = engine.set_engine_type("SanitizerEngine", num_workers=2)
    try:
        _, params = _tiny_fit([mx.cpu(i) for i in range(2)], 2)
        mx.waitall()
        assert all(np.all(np.isfinite(v)) for v in params.values())
        assert not eng.violations, eng.race_report()
    finally:
        engine.set_engine_type(prev)


def test_comm_mode_declines_batch_normalized_loss(monkeypatch):
    """SoftmaxOutput(normalization='batch') backward divides by a
    PER-SHARD count inside shard_map — psumming those would over-scale
    grads n_shards x, so the comm gate must decline and leave the
    implicit partitioner (which sees the global shape) in charge."""
    monkeypatch.setenv("MXTPU_COMM_BUCKETED", "1")
    d = mx.sym.Variable("data")
    o = mx.sym.FullyConnected(d, num_hidden=3, name="fc")

    def bind(net):
        mod = mx.mod.Module(net, context=[mx.cpu(i) for i in range(2)])
        mod.bind(data_shapes=[("data", (8, 10))],
                 label_shapes=[("softmax_label", (8,))])
        return mod._exec_group.execs[0]

    armed = bind(mx.sym.SoftmaxOutput(o, name="softmax"))
    assert armed._comm_mode() is not None
    declined = bind(mx.sym.SoftmaxOutput(o, normalization="batch",
                                         name="softmax"))
    assert declined._comm_mode() is None


def test_measure_comm_preserves_optimizer_schedule(monkeypatch):
    """The probe's schedule_prefix call must not advance the real LR
    schedule: num_update / per-key counts are identical before and
    after measure_comm()."""
    monkeypatch.setenv("MXTPU_COMM_BUCKETED", "1")
    mod, _ = _tiny_fit([mx.cpu(i) for i in range(2)], 2)
    exe = mod._exec_group.execs[0]
    opt = exe._fused_updater.optimizer
    before = (opt.num_update, dict(opt._index_update_count))
    exe.measure_comm(iters=1)
    assert opt.num_update == before[0]
    assert opt._index_update_count == before[1]


def test_comm_bucket_auto_derives_from_measured_probe(monkeypatch):
    """MXTPU_COMM_BUCKET_MB=auto (docs/perf.md "Autotuning"): the first
    fused dispatch with a comm plan runs a measured two-point comm-only
    probe and books the decision — basis (both probe timings + bucket
    counts), tune.* telemetry, and a comm mode consistent with whatever
    bucket the derivation settled on.  Whether the bucket CHANGES is
    host-dependent (a model that does not separate the two probe points
    honestly keeps the default), so only the decision record and its
    invariants are pinned."""
    monkeypatch.setenv("MXTPU_COMM_BUCKETED", "1")
    monkeypatch.setenv("MXTPU_COMM_BUCKET_MB", "auto")
    d0 = telemetry.counter_value("tune.decisions")
    mod, params = _tiny_fit([mx.cpu(i) for i in range(4)], 2)
    assert all(np.all(np.isfinite(v)) for v in params.values())
    exe = mod._exec_group.execs[0]
    dec = getattr(exe, "_comm_auto_decision", None)
    assert dec is not None and dec["mode"] == "auto"
    assert isinstance(dec["changed"], bool)
    probe = dec["probe"]
    assert probe["t_cur_s"] > 0 and probe["t_probe_s"] > 0
    assert probe["buckets_cur"] >= 1 and probe["buckets_probe"] >= 1
    assert probe["sweep_bytes"] > 0 and probe["algo_bytes"] > 0
    # the derivation ran exactly once and the adopted bucket is live:
    # the comm plan the executor now compiles with uses applied_bytes
    assert exe._comm_auto_done is True
    axes, bucket_bytes = exe._comm_mode()
    assert bucket_bytes == dec["applied_bytes"]
    if dec["changed"]:
        assert dec["applied_bytes"] != dec["prev_bytes"]
        assert dec["model"] is not None
    else:
        assert dec["applied_bytes"] == dec["prev_bytes"]
    assert telemetry.counter_value("tune.decisions") == d0 + 1
    assert telemetry.gauge_value("tune.comm_bucket_bytes") == \
        dec["applied_bytes"]
    # explicit numeric value must NOT trigger the auto path
    monkeypatch.setenv("MXTPU_COMM_BUCKET_MB", "0.5")
    mod2, _ = _tiny_fit([mx.cpu(i) for i in range(4)], 2)
    exe2 = mod2._exec_group.execs[0]
    assert getattr(exe2, "_comm_auto_decision", None) is None
    assert exe2._comm_mode()[1] == int(0.5e6)


# ----------------------------------------------------------------------
# collectives unit surface
# ----------------------------------------------------------------------

def test_plan_buckets_size_targets():
    sizes = [100, 100, 100, 500, 50, 50]
    plan = collectives.plan_buckets(sizes, 250)
    assert plan == [[0, 1], [2], [3], [4, 5]]
    # oversized grad gets its own bucket, order preserved
    flat = [i for b in plan for i in b]
    assert flat == list(range(len(sizes)))


def test_bucket_plan_groups_by_dtype():
    import jax.numpy as jnp

    avals = [jnp.zeros((4,), jnp.float32), jnp.zeros((4,), jnp.int32),
             jnp.zeros((4,), jnp.float32)]
    plan = collectives.bucket_plan(avals, 1 << 20)
    groups = [set(m) for m, _ in plan]
    assert {0, 2} in groups and {1} in groups


def test_pack_unpack_roundtrip():
    import jax.numpy as jnp

    arrs = [jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            jnp.arange(4, dtype=jnp.float32) * 2.0,
            jnp.ones((1, 1), jnp.float32)]
    flat = collectives.pack_bucket(arrs)
    back = collectives.unpack_bucket(flat, [a.shape for a in arrs])
    for a, b in zip(arrs, back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_hierarchical_psum_equals_flat_psum():
    """ICI-then-DCN sequential reduction == one flat all-reduce over
    both axes (2x4 mesh on the 8-device CPU host)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.parallel.mesh import Mesh, P

    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("data_dcn", "data_ici"))
    x = jnp.arange(8.0, dtype=jnp.float32)

    def hier(v):
        return collectives.hierarchical_psum(
            v, ("data_ici", "data_dcn"))

    def flat(v):
        return lax.psum(v, ("data_dcn", "data_ici"))

    spec = P(("data_dcn", "data_ici"))
    h = collectives.shard_map_unchecked(
        hier, mesh=mesh, in_specs=(spec,), out_specs=spec)(x)
    f = collectives.shard_map_unchecked(
        flat, mesh=mesh, in_specs=(spec,), out_specs=spec)(x)
    np.testing.assert_allclose(np.asarray(h), np.asarray(f))
    np.testing.assert_allclose(np.asarray(h), np.full((8,), x.sum()))


def test_bucketed_psum_matches_per_leaf_psum():
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.parallel.mesh import Mesh, P

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    rng = np.random.RandomState(0)
    leaves = [rng.randn(4, 3).astype(np.float32),
              rng.randn(4, 7).astype(np.float32),
              rng.randn(4, 2).astype(np.float32)]

    def bucketed(ls):
        red, sizes = collectives.bucketed_psum(ls, ("data",), 40)
        assert len(sizes) >= 2  # the tiny cap forces several buckets
        return red

    def plain(ls):
        return tuple(lax.psum(l, "data") for l in ls)

    spec = P("data")
    b = collectives.shard_map_unchecked(
        bucketed, mesh=mesh, in_specs=(spec,), out_specs=spec)(tuple(leaves))
    p = collectives.shard_map_unchecked(
        plain, mesh=mesh, in_specs=(spec,), out_specs=spec)(tuple(leaves))
    for x, y in zip(b, p):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-6)


# ----------------------------------------------------------------------
# satellites: launcher help, parse_log columns, kvstore state errors
# ----------------------------------------------------------------------

def test_launcher_help_documents_local_spmd():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "--help"], capture_output=True, text=True, timeout=60)
    assert "--local-spmd" in out.stdout
    assert "--local-devices" in out.stdout
    assert "docs/distributed.md" in out.stdout


def test_parse_log_telemetry_comm_columns(tmp_path):
    """comm_gbps / overlap_pct columns render from comm.* gauges;
    records that predate the comm namespace render '-'."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import parse_log

    new = {"flush_seq": 1, "step": 4, "counters": {"comm.dispatches": 2},
           "gauges": {"comm.gbps": 1.25, "comm.overlap_frac": 0.5},
           "histograms": {}}
    old = {"flush_seq": 0, "step": 2, "counters": {}, "gauges": {},
           "histograms": {}}
    rows = parse_log.parse_telemetry([json.dumps(old), json.dumps(new)])
    assert rows[1]["comm_gbps"] == pytest.approx(1.25)
    assert rows[1]["overlap_pct"] == pytest.approx(50.0)
    assert rows[0]["comm_gbps"] is None and rows[0]["overlap_pct"] is None
    assert "comm_gbps" in parse_log._TELEMETRY_COLS
    assert "overlap_pct" in parse_log._TELEMETRY_COLS
    f = tmp_path / "t.jsonl"
    f.write_text(json.dumps(old) + "\n" + json.dumps(new) + "\n")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "parse_log.py"),
         "--telemetry", str(f)], capture_output=True, text=True,
        timeout=60)
    assert out.returncode == 0, out.stderr
    assert "comm_gbps" in out.stdout


def test_kvstore_optimizer_states_raise_with_guidance(tmp_path):
    """ISSUE 10 bugfix: save/load_optimizer_states on a store with no
    local updater (the dist topology: the optimizer runs ON THE
    SERVERS) raises a real MXNetError with rank-0 checkpoint guidance,
    not a bare assert."""
    kv = mx.kv.create("local")  # no optimizer installed
    with pytest.raises(MXNetError) as e1:
        kv.save_optimizer_states(str(tmp_path / "s.states"))
    msg = str(e1.value)
    assert "rank 0" in msg and "server" in msg
    assert "assert" not in msg
    with pytest.raises(MXNetError) as e2:
        kv.load_optimizer_states(str(tmp_path / "s.states"))
    assert "rank 0" in str(e2.value)
    # a store WITH a local updater still round-trips
    kv2 = mx.kv.create("local")
    kv2.set_optimizer(mx.optimizer.SGD(learning_rate=0.1))
    path = str(tmp_path / "ok.states")
    kv2.save_optimizer_states(path)
    kv2.load_optimizer_states(path)
