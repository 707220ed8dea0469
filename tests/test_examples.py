"""Example scripts are product surface (the reference ships and CI-runs
its examples); smoke-run the fast synthetic-data ones end-to-end as
subprocesses on the CPU platform.  Each script asserts its own
convergence/behavior and exits nonzero on failure."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FAST_EXAMPLES = [
    "examples/numpy-ops/custom_softmax.py",
    "examples/multi-task/multitask_mnist.py",
    "examples/recommenders/matrix_fact.py",
    "examples/autoencoder/mlp_autoencoder.py",
    "examples/adversary/fgsm_mnist.py",
    "examples/nce-loss/nce_lm.py",
    "examples/stochastic-depth/sd_mlp.py",
    "examples/bi-lstm-sort/lstm_sort.py",
    "examples/neural-style/nstyle.py",
    "examples/reinforcement-learning/actor_critic_gridworld.py",
    "examples/svm_mnist/svm_mnist.py",
    "examples/fcn-xs/fcn_xs.py",
    "examples/warpctc/lstm_ocr.py",
]


@pytest.mark.parametrize("script", FAST_EXAMPLES)
def test_example_runs(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    # force CPU before any jax import (the example files don't assume a
    # conftest); examples that need multiple devices set their own flags
    env["JAX_PLATFORMS"] = "cpu"
    env["MXTPU_EXAMPLE_FAST"] = "1"
    for k in list(env):
        if k.startswith("TPU_"):
            env.pop(k)
    code = (
        "import jax; jax.config.update('jax_platforms','cpu')\n"
        "import runpy, sys\n"
        "sys.argv = [%r]\n"
        "runpy.run_path(%r, run_name='__main__')\n"
        % (os.path.basename(script), os.path.join(ROOT, script)))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=ROOT)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    assert "OK" in proc.stdout, proc.stdout[-1500:]
