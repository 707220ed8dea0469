"""Elastic chaos pin (ISSUE 16 acceptance): SIGKILL one of two
``--local-spmd`` ranks mid-epoch and the ``tools/launch.py --elastic``
supervisor re-forms the job at N-1, resumes from the last committed
manifest, and replays the IDENTICAL loss sequence — launcher exits 0,
no hang.

The worker (tests/ckpt_chaos_script.py) prints one ``CKPTSTEP`` line
per dispatch tagged with its elastic generation and world size; rank 1
kills itself (``SIGKILL`` — no cleanup, no atexit) after 6 dispatches
of generation 0.  The chaos run — generation 0 at N=2, the resumed
generation at N=1, including the replayed overlap between the last
commit and the kill — must walk the IDENTICAL global batch sequence as
the uninterrupted single-process reference (the data order is a pure
function of (seed, epoch), worker-count invariant) and converge to the
same losses.  Loss values compare under the same tight tolerance as the
existing cross-width SPMD pin (test_spmd_runtime.py): XLA compiles
different reduction shapes for different mesh widths, so bit-identity
across a WIDTH CHANGE is not a property any SPMD system has — the
bit-exact contract is pinned where it holds, on same-width resume
(tests/test_ckpt.py kill/resume parity).
"""
import os
import re
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_LINE_RE = re.compile(
    r"CKPTSTEP gen=(\d+) rank=(\d+) nranks=(\d+) epoch=(\d+) batch=(\d+) "
    r"loss=(\S+)")


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


def test_elastic_sigkill_shrink_resume_bit_exact(tmp_path):
    script = os.path.join(REPO, "tests", "ckpt_chaos_script.py")
    # uninterrupted single-process reference (checkpointing unarmed: no
    # MXTPU_CKPT_DIR in the clean env)
    ref = subprocess.run(
        [sys.executable, script, "--chaos-rank", "-1"],
        env=_clean_env({"MXTPU_LOCAL_DEVICES": "2"}), capture_output=True,
        text=True, timeout=240, cwd=REPO)
    assert ref.returncode == 0, ref.stderr[-2000:]
    ref_losses = {(int(m.group(4)), int(m.group(5))): m.group(6)
                  for m in _LINE_RE.finditer(ref.stdout)}
    assert len(ref_losses) == 8, ref.stdout

    ckpt_dir = str(tmp_path / "ckpt")
    chaos = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "--elastic", "--local-spmd", "-n", "2", "-s", "0",
         "--local-devices", "2",
         sys.executable, script, "--chaos-rank", "1", "--chaos-after", "6"],
        env=_clean_env({"MXTPU_CKPT_DIR": ckpt_dir}), capture_output=True,
        text=True, timeout=420, cwd=REPO)
    # the launcher survives the chaos and exits cleanly — no hang, no
    # propagated failure
    assert chaos.returncode == 0, (chaos.returncode, chaos.stderr[-4000:])
    assert "shrinking to 1 worker" in chaos.stderr, chaos.stderr[-4000:]

    recs = [(int(m.group(1)), int(m.group(2)), int(m.group(3)),
             int(m.group(4)), int(m.group(5)), m.group(6))
            for m in _LINE_RE.finditer(chaos.stdout)]
    assert recs, chaos.stdout
    # every dispatch any generation ever ran walks a batch the
    # reference walked, with the same loss to within the cross-width
    # tolerance of the existing SPMD parity pin
    for gen, rank, nranks, epoch, batch, loss in recs:
        assert (epoch, batch) in ref_losses, (gen, rank, epoch, batch)
        np.testing.assert_allclose(
            float(loss), float(ref_losses[(epoch, batch)]),
            rtol=5e-4, atol=1e-5,
            err_msg=str((gen, rank, nranks, epoch, batch)))
    # generation 0 really ran wide ...
    assert any(gen == 0 and nranks == 2 for gen, _, nranks, _, _, _ in recs)
    # ... the survivor generation re-formed at N-1, resumed MID-epoch 1
    # (epoch 0 was never replayed), and finished the run
    shrunk = [(epoch, batch) for gen, _, nranks, epoch, batch, _ in recs
              if gen >= 1 and nranks == 1]
    assert shrunk and all(e == 1 for e, _ in shrunk)
    assert (1, 3) in shrunk
    assert re.search(r"CKPTDONE gen=[1-9]\d* rank=0 nranks=1",
                     chaos.stdout), chaos.stdout
