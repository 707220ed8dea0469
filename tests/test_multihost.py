"""Multi-host SPMD (parallel/multihost.py): spawn 2 real OS processes,
each with 2 CPU devices, joined through jax.distributed over a localhost
'DCN'; both run the same jitted data-parallel SGD steps on per-host
input slices and must agree with each other and with the single-process
answer.  This is the XLA-native counterpart of the reference's multi-
node ps-lite path (tests/test_dist_kvstore.py covers that one)."""
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _single_process_answer():
    X_rng = np.random.RandomState(0)
    batch, dim = 16, 4
    X = X_rng.randn(batch, dim).astype(np.float32)
    w_true = X_rng.randn(dim, 1).astype(np.float32)
    y = X @ w_true
    w = np.zeros((dim, 1), np.float32)
    for _ in range(5):
        g = 2.0 / batch * X.T @ (X @ w - y)
        w = w - 0.1 * g
    return w.ravel()


def test_two_process_spmd_agrees():
    port = _free_port()
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        # fresh CPU-only runtime per process (no inherited device-count
        # flag; multihost.initialize sets its own)
        env.pop("XLA_FLAGS", None)
        for k in list(env):
            if k.startswith("TPU_"):
                env.pop(k)
        env["JAX_PLATFORMS"] = "cpu"
        env["MXTPU_COORDINATOR"] = "127.0.0.1:%d" % port
        env["DMLC_NUM_WORKER"] = "2"
        env["MXTPU_PROCESS_ID"] = str(rank)
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        procs.append(subprocess.Popen(
            [sys.executable, os.path.join(ROOT, "tests",
                                          "multihost_script.py")],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=240)
        outs.append(out)
        assert p.returncode == 0, out
    lines = [l for o in outs for l in o.splitlines() if l.startswith("MHOK")]
    assert len(lines) == 2, "\n".join(outs)
    ws = []
    for line in lines:
        w = [float(v) for v in line.split("w=")[1].split(",")]
        ws.append(np.array(w, np.float32))
    np.testing.assert_allclose(ws[0], ws[1], rtol=1e-6)
    np.testing.assert_allclose(ws[0], _single_process_answer(),
                               rtol=1e-4, atol=1e-5)
