"""TPU kernels lowered ONCE a shape, for every program and every process.

A Pallas kernel that a serving program calls is traced and lowered to
Mosaic again in every program that holds it — 0.3 s of Python a program —
and needs Pallas itself, 1.3 s of import: a session's set-up builds eight
programs and more (a bucket ladder of decode steps; since PR 46 a mixed
step a prefill bucket, each with the riders' kernels in it), none of which
a warm compile cache spares the lowering.  :func:`call` runs the kernel
through a ``jax.export.Exported`` instead (PR 34 did so for the delta
rule's prefill kernel alone): made once for the operands' shapes, kept in
the process and as a file beside JAX's compiled programs
(``jax_compilation_cache_dir``) under a name made of the kernel's source,
the JAX versions, the shapes and the static arguments.  A program that
calls it embeds the lowered kernel as it is — the same ``tpu_custom_call``,
aliases included — so a warm start pays for neither the import nor a
lowering, as it pays for no compile.  A file that is missing, stale or
unreadable is made anew.
"""
from __future__ import annotations

import functools
import hashlib
import importlib
import os

import jax
import jaxlib

_EXPORTED = {}   # (module, function, operand avals, static arguments) -> Exported


def kernel(module, function):
    """The kernel `function` of ``ops/<module>.py``, imported now."""
    return getattr(importlib.import_module("." + module, __package__),
                   function)


def call(module, function, operands, interpret=False, **static):
    """``kernel(module, function)(*operands, **static)`` for the TPU,
    through the exported kernel of that signature: its outputs, a tuple.
    The file is ``mx-<module>-<sha1>.export``; the module's source bytes
    are part of the stamp, so an edit of the kernel makes new files and
    leaves the old ones behind.  `interpret` runs the kernel itself in
    Pallas's interpreter instead."""
    if interpret:
        return kernel(module, function)(*operands, interpret=True, **static)
    key = (module, function,
           tuple((tuple(x.shape), str(x.dtype)) for x in operands),
           tuple(sorted(static.items())))
    exported = _EXPORTED.get(key)
    if exported is None:
        exported = _EXPORTED[key] = _exported(key, operands, static)
    return tuple(exported.call(*operands))


def _exported(key, operands, static):
    from jax import export

    module, function = key[:2]
    with open(os.path.join(os.path.dirname(__file__), module + ".py"),
              "rb") as f:
        stamp = hashlib.sha1(f.read() + repr(
            (key, jax.__version__, jaxlib.__version__)).encode()).hexdigest()
    folder = jax.config.jax_compilation_cache_dir
    path = folder and os.path.join(
        folder, "mx-%s-%s.export" % (module.replace("_", "-"), stamp))
    try:
        with open(path, "rb") as f:
            return export.deserialize(bytearray(f.read()))
    except Exception:  # no cache, no file, or not a whole one
        pass
    exported = export.export(
        jax.jit(functools.partial(kernel(module, function), **static)),
        platforms=("tpu",))(
        *(jax.ShapeDtypeStruct(x.shape, x.dtype) for x in operands))
    try:
        os.makedirs(folder, exist_ok=True)
        with open("%s.%d" % (path, os.getpid()), "wb") as f:
            f.write(exported.serialize())
        os.replace(f.name, path)
    except (OSError, TypeError):  # no folder to keep it in
        pass
    return exported
