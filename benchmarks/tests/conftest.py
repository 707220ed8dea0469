"""The benchmark's own tests rehearse on the CPU: a virtual 4-device
mesh stands in for the four-chip cell.  Run them with

    python -m pytest benchmarks/tests -q

A CPU run says whether the harness is right, never how fast anything is.
"""
import os
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
