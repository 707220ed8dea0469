"""Multi-process distributed runtime (ISSUE 10): `tools/launch.py
--local-spmd` brings N OS processes into ONE jax.distributed global
mesh, `Module.fit` trains on it, per step and through the K-step fused
dispatch, its gradient all-reduce the one XLA's partitioner inserts over
the data_dcn x data_ici mesh, and the dist_sync kvstore control plane
rides the same launcher.  tests/spmd_fit_script.py is the
worker; the launcher subprocess tests are the tier-1 proof that the
runtime is real — not a single-process simulation."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine
from mxnet_tpu.base import MXNetError

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


def _fit_records(tmp_path, script_args):
    """Launch the two-rank fit and read each rank's record from the file
    it wrote: the ranks' stdout is one pipe, which they share with the
    launcher and with Gloo's own messages, and under tier-1's load a
    record on it came back cut in two (PR 43: 2 runs of 4)."""
    proc = _launch_spmd(
        2, 0, script_args + ["--record-dir", str(tmp_path)], timeout=420)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    recs = {}
    for path in sorted(tmp_path.glob("spmd_fit.r*.json")):
        rec = json.loads(path.read_text())
        recs[rec["rank"]] = {
            "axes": rec["axes"],
            "losses": np.array([float(v) for v in rec["losses"]]),
            "digest": np.array([float(v) for v in rec["digest"]])}
    assert sorted(recs) == [0, 1], proc.stdout + proc.stderr
    # the hierarchical topology was actually built (2 procs x 2 local)
    assert recs[0]["axes"] == ["data_dcn", "data_ici"], recs[0]["axes"]
    np.testing.assert_array_equal(recs[0]["losses"], recs[1]["losses"])
    np.testing.assert_array_equal(recs[0]["digest"], recs[1]["digest"])
    return recs[0]


# ----------------------------------------------------------------------
# tier-1 acceptance: 2-process CPU-mesh Module.fit parity
# ----------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 2])
def test_local_spmd_fit_matches_single_process(tmp_path, k):
    """`launch.py --local-spmd -n 2` (2 procs x 2 devices each,
    hierarchical data_dcn x data_ici mesh): every rank reports the SAME
    per-dispatch loss trajectory and final params, and both match the
    single-process answer — with the batch sharded over both axes and
    the all-reduce the partitioner inserts, the per-step loop (k=1) and
    the K-step fused block (k=2) are numerically the single-chip
    training loop."""
    rec = _fit_records(tmp_path, ["--steps-per-dispatch", str(k)])
    # single-process reference: the same fit, no mesh, in this process
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from spmd_fit_script import run_fit

    ref_losses, ref_digest = run_fit(mx, np, None, k)
    assert len(ref_losses) == len(rec["losses"]) and ref_losses, \
        (len(ref_losses), len(rec["losses"]))
    np.testing.assert_allclose(rec["losses"], ref_losses,
                               rtol=5e-4, atol=1e-5)
    np.testing.assert_allclose(rec["digest"], ref_digest,
                               rtol=5e-3, atol=5e-5)


def test_local_spmd_transformer_fit_matches_single_process(tmp_path):
    """The transformer SPMD pin (ROADMAP item 2): `launch.py
    --local-spmd -n 2` trains the TransformerLM causal-LM problem —
    attention, LayerNorm, weight-tied softmax — on the same
    two-process mesh, and every rank's per-dispatch perplexity
    trajectory and final params match the single-process answer."""
    rec = _fit_records(tmp_path, ["--transformer"])
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from spmd_fit_script import run_fit_transformer

    ref_losses, ref_digest = run_fit_transformer(mx, np, None, 1)
    assert len(ref_losses) == len(rec["losses"]) and ref_losses, \
        (len(ref_losses), len(rec["losses"]))
    np.testing.assert_allclose(rec["losses"], ref_losses,
                               rtol=5e-4, atol=1e-5)
    np.testing.assert_allclose(rec["digest"], ref_digest,
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
# single-host mesh check (in-process, 8-device mesh)
# ----------------------------------------------------------------------

def _tiny_fit(contexts, k):
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
    mod.fit(it, num_epoch=1, kvstore=None, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.init.Xavier(), eval_metric="acc",
            steps_per_dispatch=k)
    args, _ = mod.get_params()
    return {n: v.asnumpy() for n, v in args.items()}


def test_sanitizer_zero_violations_on_a_mesh_block_fit():
    """A full fit epoch of K-step block dispatches on a 2-device mesh
    under SanitizerEngine: every staged block / fused dispatch /
    metric readback declares what it touches — zero violations."""
    prev = engine.get().kind
    eng = engine.set_engine_type("SanitizerEngine", num_workers=2)
    try:
        params = _tiny_fit([mx.cpu(i) for i in range(2)], 2)
        mx.waitall()
        assert all(np.all(np.isfinite(v)) for v in params.values())
        assert not eng.violations, eng.race_report()
    finally:
        engine.set_engine_type(prev)


# ----------------------------------------------------------------------
# satellites: launcher help, kvstore state errors
# ----------------------------------------------------------------------

def test_launcher_help_documents_local_spmd():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "--help"], capture_output=True, text=True, timeout=60)
    assert "--local-spmd" in out.stdout
    assert "--local-devices" in out.stdout
    assert "docs/distributed.md" in out.stdout


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
