"""Test configuration: run the whole suite on a virtual 8-device CPU mesh.

The reference tests multi-device paths with CPU device ids standing in for
GPUs (reference tests/python/unittest/test_multi_device_exec.py:4-33);
here XLA's host-platform device-count flag gives 8 real(ly separate) CPU
devices so sharding/collective code paths execute without TPU hardware.
"""
import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

# tier-1 runs on the virtual CPU mesh even where the environment names
# another platform first (the chip host exports JAX_PLATFORMS=tpu,cpu).
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--engine-type", default=None,
        help="Run the suite under this MXNET_ENGINE_TYPE (NaiveEngine / "
             "ThreadedEnginePerDevice / SanitizerEngine); equivalent to "
             "setting the env var.")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running stress tests, excluded from tier-1")
    engine_type = config.getoption("--engine-type")
    if engine_type:
        # before any test imports mxnet_tpu, so the lazy engine singleton
        # picks it up; plain `MXNET_ENGINE_TYPE=... pytest` works too
        os.environ["MXNET_ENGINE_TYPE"] = engine_type


def pytest_report_header(config):
    return "MXNET_ENGINE_TYPE=%s" % os.environ.get(
        "MXNET_ENGINE_TYPE", "ThreadedEnginePerDevice (default)")


@pytest.fixture(autouse=True)
def _engine_barrier():
    """Drain the dependency engine after each test so async ops cannot
    bleed across tests — and so a deferred engine error is attributed to
    the test that produced it, not a random later one."""
    yield
    import sys as _sys

    if "mxnet_tpu" in _sys.modules:
        _sys.modules["mxnet_tpu"].engine.wait_for_all()


@pytest.fixture(autouse=True)
def _fresh_name_manager():
    """Reset auto-naming counters per test so tests that reference generated
    names (fullyconnected0_weight, ...) don't depend on execution order."""
    from mxnet_tpu.name import NameManager

    NameManager._current.value = NameManager()
    yield


def pack_jpeg_rec(tmp_path, n_per_class=24, classes=3, size=24, name="pack"):
    """Write a tiny labeled JPEG dataset and pack it with tools/im2rec.py;
    returns the .rec/.idx prefix.  The ONE dataset builder shared by the
    input-pipeline suites (test_data_service, test_io_hygiene) so the
    im2rec invocation and dataset shape live in one place."""
    import subprocess
    import sys as _sys

    import numpy as np
    import pytest as _pytest

    PIL = _pytest.importorskip("PIL.Image")
    root = str(tmp_path / "imgs")
    rng = np.random.RandomState(0)
    hues = [(200, 40, 40), (40, 200, 40), (40, 40, 200), (200, 200, 40)]
    for label in range(classes):
        d = os.path.join(root, "class%d" % label)
        os.makedirs(d, exist_ok=True)
        base = hues[label % len(hues)]
        for i in range(n_per_class):
            img = np.tile(np.array(base, np.uint8), (size, size, 1))
            noise = rng.randint(0, 40, img.shape).astype(np.uint8)
            PIL.fromarray(np.clip(img.astype(int) + noise, 0, 255)
                          .astype(np.uint8)).save(
                os.path.join(d, "img%03d.jpg" % i), "JPEG", quality=90)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prefix = str(tmp_path / name)
    proc = subprocess.run(
        [_sys.executable, os.path.join(repo, "tools", "im2rec.py"),
         prefix, root], capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return prefix
