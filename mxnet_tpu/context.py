"""Device contexts.

Parity with reference python/mxnet/context.py (Context, mx.cpu(), mx.gpu(),
`with Context(...)` scoping), redesigned for TPU: `mx.tpu()` is first-class
and a Context resolves to a concrete `jax.Device`.  Device type ids match the
reference ABI values (cpu=1, gpu=2, cpu_pinned=3) with tpu=4 appended.

TPU-first notes:
  * There is no per-device stream/worker state here — XLA/PJRT owns streams.
  * `gpu()` is accepted for API compatibility and resolves to the
    accelerator of the default backend so reference scripts run unmodified
    on a TPU host (SURVEY.md §7 north star).
  * A context names a device or raises: `tpu(i)`/`gpu(i)` on a host with
    no accelerator, or any `device_id` past the end of the device list,
    is an MXNetError — never a quiet substitute device.
"""
from __future__ import annotations

import threading

import jax
from . import locks
from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_tpus",
           "num_gpus", "default_device"]


class Context:
    """Execution device context.

    Parameters
    ----------
    device_type : str or Context
        'cpu', 'gpu', 'tpu' or 'cpu_pinned'.
    device_id : int
        Device ordinal.
    """

    # parity: reference python/mxnet/context.py:24-30 devtype2str/devstr2type
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 4}

    _default_lock = locks.lock("context.default")
    _current = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return Context.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __repr__(self):
        return self.__str__()

    def __enter__(self):
        if not hasattr(Context._current, "value"):
            Context._current.value = None
        self._old_ctx = Context._current.value
        Context._current.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._current.value = self._old_ctx

    # ------------------------------------------------------------------
    # TPU-native: resolve to a concrete jax.Device.
    # ------------------------------------------------------------------
    def jax_device(self):
        """Resolve this context to a `jax.Device`, or raise.

        'tpu'/'gpu' resolve to the default-backend accelerator (on a TPU
        machine both give the TPU chip, so reference gpu scripts run as-is);
        'cpu'/'cpu_pinned' resolve to a host CPU device.  A context that
        names no device of this process — an accelerator on a CPU-only
        host, a device_id past the end — raises MXNetError with what
        `jax.local_devices()` holds.  device_id indexes THIS process's
        devices: in a multi-process mesh each rank's tpu(0) is its own
        first chip (the reference's per-host gpu(i))."""
        if self.device_type in ("cpu", "cpu_pinned"):
            try:
                devs = jax.local_devices(backend="cpu")
            except RuntimeError:  # JAX_PLATFORMS excludes the cpu backend
                devs = []
        else:
            devs = [d for d in jax.local_devices() if d.platform != "cpu"]
        if not 0 <= self.device_id < len(devs):
            raise MXNetError(
                "context %s names no device: this process has %d %s "
                "device(s); jax.local_devices() = %s"
                % (self, len(devs),
                   "cpu" if self.device_type.startswith("cpu")
                   else "accelerator", jax.local_devices()))
        return devs[self.device_id]


def default_device():
    """The device JAX computes on when nothing is committed elsewhere
    (uncommitted arrays land here): this process's first device of the
    default backend, unless jax_default_device says otherwise."""
    d = jax.config.jax_default_device
    return d if isinstance(d, jax.Device) else jax.local_devices(backend=d)[0]


# module-level default context (parity: context.py current_context)
Context._default_ctx = None


def cpu(device_id=0):
    """Return a CPU context (parity: mx.cpu())."""
    return Context("cpu", device_id)


def gpu(device_id=0):
    """Accelerator context for source compatibility (resolves to TPU here)."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    """Return a TPU context — the first-class accelerator of this framework."""
    return Context("tpu", device_id)


def num_tpus():
    return len([d for d in jax.local_devices() if d.platform != "cpu"])


def num_gpus():
    return num_tpus()


def current_context():
    """Return the current context (with-scope aware; default tpu if present else cpu)."""
    cur = getattr(Context._current, "value", None)
    if cur is not None:
        return cur
    if Context._default_ctx is None:
        Context._default_ctx = tpu(0) if num_tpus() > 0 else cpu(0)
    return Context._default_ctx
