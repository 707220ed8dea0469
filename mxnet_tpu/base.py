"""Foundation utilities for mxnet_tpu.

TPU-native re-implementation of the roles played by dmlc-core in the
reference (logging/CHECK, env-var config, registries — see reference
include/dmlc usage catalogued in SURVEY.md §2.2).  There is no C ABI
boundary here: the compute path is JAX/XLA, so "check_call"-style error
marshalling (reference python/mxnet/base.py:285) collapses into ordinary
Python exceptions.
"""
from __future__ import annotations

import os
import threading
from . import locks

__all__ = [
    "MXNetError",
    "compile_cache_dir",
    "get_env",
    "env_int",
    "env_bool",
    "string_types",
    "numeric_types",
    "classproperty",
    "build_param_doc",
]


class MXNetError(RuntimeError):
    """Error raised by mxnet_tpu (parity: reference python/mxnet/base.py MXNetError)."""


string_types = (str,)
numeric_types = (float, int)


def compile_cache_dir():
    """Place JAX's persistent compilation cache; returns the directory.

    Called ONCE, from the package's __init__, before anything can
    initialise a backend or compile.  Where ``JAX_COMPILATION_CACHE_DIR``
    is set the caller owns the placement: JAX reads the variable itself
    and this sets nothing in code.  Otherwise the cache is
    ``<checkout>/.jax_cache`` — a fixed path derived from the package's
    location (the path is part of the cache key, so a temporary or
    per-process directory would never hit), git-ignored, and the same
    for every child process that imports this checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def get_env(name, default=None):
    """Read a runtime config env var (parity: dmlc::GetEnv)."""
    return os.environ.get(name, default)


def env_int(name, default):
    try:
        return int(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


def env_bool(name, default=False):
    val = os.environ.get(name)
    if val is None:
        return default
    return val not in ("0", "false", "False", "")


class classproperty:
    def __init__(self, fget):
        self.fget = fget

    def __get__(self, obj, owner):
        return self.fget(owner)


def build_param_doc(arg_names, arg_types, arg_descs, remove_dup=True):
    """Build argument docs (parity: reference python/mxnet/base.py build_param_doc)."""
    param_keys = set()
    param_str = []
    for key, type_info, desc in zip(arg_names, arg_types, arg_descs):
        if key in param_keys and remove_dup:
            continue
        param_keys.add(key)
        ret = "%s : %s" % (key, type_info)
        if len(desc) != 0:
            ret += "\n    " + desc
        param_str.append(ret)
    doc_str = "Parameters\n----------\n%s\n" % ("\n".join(param_str))
    return doc_str


class _NameCounter:
    """Thread-safe per-prefix counter used for auto-naming."""

    def __init__(self):
        self._lock = locks.lock("base.name_counter")
        self._counts = {}

    def next(self, prefix):
        with self._lock:
            idx = self._counts.get(prefix, 0)
            self._counts[prefix] = idx + 1
        return idx


_GLOBAL_NAME_COUNTER = _NameCounter()
