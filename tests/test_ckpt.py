"""mxnet_tpu.ckpt (ISSUE 16): async distributed checkpoints with
exact-resume.

Three layers of proof:

* unit pins on the atomic-commit surface (ckpt/atomic.py): write-then-
  rename, the manifest as the unit of validity, prune ordering, and the
  diagnose-don't-traceback error contract of the readers (including the
  legacy ``model.load_checkpoint`` satellite);
* in-process fit round-trips: arming checkpoints does not perturb the
  loss trajectory, resuming from a committed manifest replays the
  reference tail BIT-EXACTLY, and the elastic regrow request yields fit
  at the epoch boundary;
* fresh-process subprocess pins — the acceptance gates: the legacy
  ``save_checkpoint(save_optimizer_states=True)`` round-trip and the
  kill-at-batch-k / fresh-process-resume bit-parity pin, each on BOTH
  the per-step (K=1) and fused (K=2) dispatch paths.

Loss comparisons here are string-equal on ``%.10e`` renderings: not
"close", identical.
"""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ckpt import atomic, elastic
from mxnet_tpu.ckpt import resume as ckpt_resume

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ----------------------------------------------------------------------
# atomic commit surface
# ----------------------------------------------------------------------


def test_replace_into_commits_and_aborts(tmp_path):
    target = str(tmp_path / "artifact.json")
    with atomic.replace_into(target) as tmp:
        with open(tmp, "w") as f:
            f.write("v1")
    assert open(target).read() == "v1"
    # a failed writer leaves the previous artifact intact and no .tmp
    with pytest.raises(RuntimeError):
        with atomic.replace_into(target) as tmp:
            with open(tmp, "w") as f:
                f.write("half-written v2")
            raise RuntimeError("boom")
    assert open(target).read() == "v1"
    assert os.listdir(str(tmp_path)) == ["artifact.json"]


def test_manifest_is_the_unit_of_validity(tmp_path):
    d = str(tmp_path)
    # shard files and a staged .tmp manifest alone = NOT a checkpoint
    atomic.write_bytes(atomic.shard_path(d, 0, 3), b"payload")
    with open(atomic.manifest_path(d, 3) + ".tmp", "w") as f:
        f.write("{}")
    assert atomic.list_manifests(d) == []
    assert atomic.latest_manifest(d) is None
    assert ckpt_resume.load(d, required=False) is None
    with pytest.raises(MXNetError, match="no committed checkpoint"):
        ckpt_resume.load(d, required=True)
    # the rename is the commit
    atomic.write_json(atomic.manifest_path(d, 3),
                      {"format": atomic.MANIFEST_FORMAT, "step": 3})
    assert [s for s, _ in atomic.list_manifests(d)] == [3]
    assert atomic.latest_manifest(d) == atomic.manifest_path(d, 3)


def test_read_manifest_error_contract(tmp_path):
    missing = str(tmp_path / "manifest-s0000000001.json")
    with pytest.raises(MXNetError, match="does not exist"):
        atomic.read_manifest(missing)
    garbled = str(tmp_path / "manifest-s0000000002.json")
    with open(garbled, "w") as f:
        f.write("{ not json")
    with pytest.raises(MXNetError, match="unreadable or corrupt"):
        atomic.read_manifest(garbled)
    foreign = str(tmp_path / "manifest-s0000000003.json")
    with open(foreign, "w") as f:
        json.dump({"format": "someone-elses-v9", "step": 3}, f)
    with pytest.raises(MXNetError, match="mxtpu-ckpt-v1"):
        atomic.read_manifest(foreign)


def test_load_names_missing_shard(tmp_path):
    d = str(tmp_path)
    atomic.write_json(atomic.manifest_path(d, 7), {
        "format": atomic.MANIFEST_FORMAT, "step": 7, "epoch": 0,
        "batch_index": 0, "shards": ["shard-r00000-s0000000007.ckpt"]})
    with pytest.raises(MXNetError) as e:
        ckpt_resume.load(d)
    assert "shard-r00000-s0000000007.ckpt" in str(e.value)
    assert "missing" in str(e.value)


def test_prune_order_and_orphan_sweep(tmp_path):
    d = str(tmp_path)
    for step in (1, 2, 3):
        atomic.write_bytes(atomic.shard_path(d, 0, step), b"x")
        atomic.write_json(atomic.manifest_path(d, step),
                          {"format": atomic.MANIFEST_FORMAT, "step": step})
    # an interrupted snapshot older than the newest commit: swept;
    # one NEWER than the newest commit: a commit in flight, protected
    atomic.write_bytes(atomic.shard_path(d, 0, 2), b"orphanish")
    atomic.write_bytes(atomic.shard_path(d, 0, 9), b"in-flight")
    atomic.prune(d, keep=2)
    names = sorted(os.listdir(d))
    assert atomic.manifest_path(d, 1) not in [os.path.join(d, n)
                                              for n in names]
    assert [s for s, _ in atomic.list_manifests(d)] == [2, 3]
    assert os.path.basename(atomic.shard_path(d, 0, 1)) not in names
    assert os.path.basename(atomic.shard_path(d, 0, 9)) in names


# ----------------------------------------------------------------------
# legacy writers/readers (satellites 1-2)
# ----------------------------------------------------------------------


def _build_problem():
    rng = np.random.RandomState(7)
    X = rng.randn(64, 12).astype(np.float32)
    w = rng.randn(12, 1).astype(np.float32)
    y = (X @ w + 0.1 * rng.randn(64, 1)).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=16, label_name="lro_label")
    d = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(d, num_hidden=8, name="fc1")
    a = mx.sym.Activation(h, act_type="tanh")
    o = mx.sym.FullyConnected(a, num_hidden=1, name="fc2")
    net = mx.sym.LinearRegressionOutput(o, name="lro")
    return it, net


def _fit(mod, it, k=1, num_epoch=2, losses=None, **kwargs):
    def on_batch(param):
        if losses is not None:
            for _, val in param.eval_metric.get_name_value():
                losses.append("%.10e" % val)
        param.eval_metric.reset()

    mod.fit(it, num_epoch=num_epoch, kvstore=None, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            initializer=mx.init.Xavier(), eval_metric="mse",
            steps_per_dispatch=k, batch_end_callback=on_batch, **kwargs)


def _seeded_module():
    from mxnet_tpu.ops.random_ops import HOST_RNG

    mx.random.seed(0)
    HOST_RNG.seed(123)
    it, net = _build_problem()
    mod = mx.mod.Module(net, label_names=("lro_label",), context=mx.cpu())
    return mod, it


def test_model_save_checkpoint_atomic(tmp_path):
    prefix = str(tmp_path / "legacy")
    arg = {"w": mx.nd.ones((2, 3))}
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=1)
    mx.model.save_checkpoint(prefix, 4, net, arg, {})
    assert not [n for n in os.listdir(str(tmp_path)) if n.endswith(".tmp")]
    sym2, arg2, aux2 = mx.model.load_checkpoint(prefix, 4)
    assert np.array_equal(arg2["w"].asnumpy(), arg["w"].asnumpy())
    # a crashed re-save must leave the committed epoch-4 file readable
    with pytest.raises(RuntimeError):
        with atomic.replace_into("%s-0004.params" % prefix) as tmp:
            with open(tmp, "w") as f:
                f.write("torn")
            raise RuntimeError("kill mid-write")
    _, arg3, _ = mx.model.load_checkpoint(prefix, 4)
    assert np.array_equal(arg3["w"].asnumpy(), arg["w"].asnumpy())


def test_load_checkpoint_names_nearest_epochs(tmp_path):
    prefix = str(tmp_path / "legacy")
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=1)
    for epoch in (1, 3):
        mx.model.save_checkpoint(prefix, epoch, net,
                                 {"w": mx.nd.ones((2, 2))}, {})
    with pytest.raises(MXNetError) as e:
        mx.model.load_checkpoint(prefix, 2)
    msg = str(e.value)
    assert "legacy-0002.params" in msg and "does not exist" in msg
    assert "epochs on disk for this prefix: 1, 3" in msg
    with pytest.raises(MXNetError, match="different prefix"):
        mx.model.load_checkpoint(str(tmp_path / "nothere"), 1)


def test_load_checkpoint_truncated_params(tmp_path):
    prefix = str(tmp_path / "legacy")
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=1)
    mx.model.save_checkpoint(prefix, 1, net, {"w": mx.nd.ones((2, 2))}, {})
    with open("%s-0001.params" % prefix, "wb") as f:
        f.write(b"\x00\x01half a file")
    with pytest.raises(MXNetError, match="truncated or corrupt"):
        mx.model.load_checkpoint(prefix, 1)


# ----------------------------------------------------------------------
# in-process fit round-trips
# ----------------------------------------------------------------------


def test_fit_resume_bit_exact_in_process(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_CKPT_KEEP", "16")
    ref = []
    mod, it = _seeded_module()
    _fit(mod, it, losses=ref)
    assert len(ref) == 8

    d = str(tmp_path / "ckpt")
    armed = []
    mod, it = _seeded_module()
    _fit(mod, it, losses=armed, checkpoint_dir=d, checkpoint_every_steps=1)
    # arming async checkpoints does not perturb the trajectory
    assert armed == ref
    steps = [s for s, _ in atomic.list_manifests(d)]
    assert steps and steps[-1] == 8

    # resume from a MID-RUN manifest (step 5 = epoch 1, batch 1): the
    # resumed dispatches replay the reference tail exactly
    res = []
    mod, it = _seeded_module()
    _fit(mod, it, losses=res, resume_from=atomic.manifest_path(d, 5))
    assert res == ref[5:]


def test_fit_regrow_yields_at_epoch_boundary(tmp_path):
    d = str(tmp_path / "ckpt")
    os.makedirs(d)
    elastic.request_regrow(d)
    part1 = []
    mod, it = _seeded_module()
    _fit(mod, it, losses=part1, checkpoint_dir=d, checkpoint_every_steps=1)
    # fit yielded after epoch 0 with a committed boundary checkpoint
    assert mod._ckpt_yielded is True
    assert len(part1) == 4
    assert atomic.latest_manifest(d) is not None
    # the relaunched full-width generation consumes the sentinel and
    # finishes the run; the combined trajectory is the reference
    elastic.clear_regrow(d)
    part2 = []
    mod, it = _seeded_module()
    _fit(mod, it, losses=part2, checkpoint_dir=d, checkpoint_every_steps=1,
         resume_from=d)
    assert mod._ckpt_yielded is False
    ref = []
    mod, it = _seeded_module()
    _fit(mod, it, losses=ref)
    assert part1 + part2 == ref


def test_snapshot_requires_bound_module():
    from mxnet_tpu.ckpt.snapshot import capture_state

    _, net = _build_problem()
    mod = mx.mod.Module(net, label_names=("lro_label",), context=mx.cpu())
    with pytest.raises(MXNetError, match="unbound"):
        capture_state(mod, 0, 0, 1)


# ----------------------------------------------------------------------
# fresh-process pins (the acceptance gates)
# ----------------------------------------------------------------------


def _clean_env(extra=None):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    for k in list(env):
        if k.startswith(("TPU_", "MXTPU_CKPT")):
            env.pop(k)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra or {})
    return env


def _run_script(script, args, timeout=240):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", script)] + args,
        env=_clean_env(), capture_output=True, text=True,
        timeout=timeout, cwd=REPO)


_STEP_RE = re.compile(
    r"CKPTSTEP tag=(\w+) k=(\d+) epoch=(\d+) batch=(\d+) loss=(\S+)")


def _parse_steps(out, tag):
    return {(int(m.group(2)), int(m.group(3)), int(m.group(4))): m.group(5)
            for m in _STEP_RE.finditer(out) if m.group(1) == tag}


def test_kill_resume_bit_parity_fresh_process(tmp_path):
    """Acceptance pin: kill at batch k, resume in a FRESH process, and
    the per-dispatch loss sequence equals the uninterrupted run's
    EXACTLY — per-step (K=1) and fused (K=2)."""
    d1, d2 = str(tmp_path / "k1"), str(tmp_path / "k2")
    ref = _run_script("ckpt_resume_script.py", ["--mode", "full",
                                                "--k", "1,2"])
    assert ref.returncode == 0, ref.stderr[-2000:]
    ref_steps = _parse_steps(ref.stdout, "full")
    assert len(ref_steps) == 8 + 4  # K=1: 8 dispatches, K=2: 4

    # kill legs die by os._exit(9) mid-epoch-1, after the commit of a
    # mid-epoch manifest
    kill1 = _run_script("ckpt_resume_script.py",
                        ["--mode", "kill", "--k", "1", "--ckpt-dir", d1,
                         "--kill-after", "6"])
    assert kill1.returncode == 9, (kill1.returncode, kill1.stderr[-2000:])
    kill2 = _run_script("ckpt_resume_script.py",
                        ["--mode", "kill", "--k", "2", "--ckpt-dir", d2,
                         "--kill-after", "4"])
    assert kill2.returncode == 9, (kill2.returncode, kill2.stderr[-2000:])
    for d in (d1, d2):
        assert atomic.latest_manifest(d) is not None

    res = _run_script("ckpt_resume_script.py",
                      ["--mode", "resume", "--k", "1,2",
                       "--ckpt-dir", "%s,%s" % (d1, d2)])
    assert res.returncode == 0, res.stderr[-2000:]
    res_steps = _parse_steps(res.stdout, "resume")
    assert res_steps, res.stdout
    # every resumed dispatch reproduces the reference byte-for-byte
    for key, loss in res_steps.items():
        assert loss == ref_steps[key], (key, loss, ref_steps[key])
    for k in (1, 2):
        keys = [key for key in res_steps if key[0] == k]
        # the resume really resumed: it skipped epoch 0 entirely and
        # still reached the final dispatch of the run
        assert keys and all(e == 1 for _, e, _ in keys)
        assert (k, 1, 3) in res_steps


_RT_RE = re.compile(r"ROUNDTRIP k=(\d+) epoch=1 batch=(\d+) loss=(\S+)")


def test_legacy_save_load_roundtrip_fresh_process(tmp_path):
    """Satellite pin: Module.save_checkpoint(save_optimizer_states=True)
    in one process, Module.load in THIS process, identical next-step
    losses for the whole following epoch (K=1 and K=2)."""
    prefix = str(tmp_path / "rt")
    saver = _run_script("ckpt_roundtrip_script.py", ["--prefix", prefix])
    assert saver.returncode == 0, saver.stderr[-2000:]
    ref = {(int(m.group(1)), int(m.group(2))): m.group(3)
           for m in _RT_RE.finditer(saver.stdout)}
    assert len(ref) == 4 + 2  # K=1: 4 dispatches, K=2: 2

    for k in (1, 2):
        mod = mx.mod.Module.load("%s_k%d" % (prefix, k), 1,
                                 load_optimizer_states=True,
                                 label_names=("lro_label",),
                                 context=mx.cpu())
        it, _ = _build_problem()
        got = []
        _fit(mod, it, k=k, num_epoch=2, losses=got, begin_epoch=1)
        want = [ref[(k, b)] for b in sorted(b for kk, b in ref if kk == k)]
        assert got == want, (k, got, want)
