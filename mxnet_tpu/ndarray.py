"""NDArray — the imperative tensor.

TPU-native equivalent of the reference NDArray
(reference include/mxnet/ndarray.h:59-436, src/ndarray/ndarray.cc).

Architecture mapping (SURVEY.md §7 phase 2):
  * The reference NDArray is a view over a ref-counted Chunk holding a
    Storage handle plus a dependency-engine variable; every op is pushed to
    the ThreadedEngine with declared read/write sets.  Here the payload is a
    `jax.Array`: PJRT's async dispatch + XLA's data-flow ordering provide
    exactly the engine's read-after-write guarantees, and `wait_to_read` ≙
    `block_until_ready` (reference WaitToRead, ndarray.h:297).
  * Mutation (`a[:] = x`, `a += b`) is functional underneath: the wrapped
    buffer is replaced.  Donated-buffer aliasing inside jitted executors
    recovers in-place update performance (SURVEY.md §7 hard-part 1).
  * `Slice`/`At` views (reference ndarray.h:267-311) are write-through:
    a view records (parent, index); reads slice the parent lazily, writes
    scatter into the parent — preserving the reference's aliasing semantics
    without aliased device memory.
  * Imperative op invoke (reference MXImperativeInvoke,
    src/c_api/c_api_ndarray.cc:248-430) becomes: unbox args → registered
    JAX fn (eager, per-primitive compile cache ≙ CuDNNAlgoReg) → box.
"""
from __future__ import annotations

import builtins
import functools
import struct
import sys

import numpy as _np

import jax
import jax.numpy as jnp

from .base import MXNetError
from .context import Context, cpu, current_context
from .ops.registry import OP_REGISTRY, get_op
from . import engine

__all__ = ["NDArray", "array", "empty", "zeros", "ones", "full", "arange",
           "concatenate", "moveaxis", "load", "loads", "save", "waitall",
           "imresize", "onehot_encode", "from_dlpack"]

_DTYPE_ALIASES = {None: jnp.float32}

# installed by contrib.autograd: callable(replay_fn, in_ndarrays, out_ndarrays)
# recording imperative ops onto the autograd tape when a train_section is
# active (reference AutogradRuntime::RecordImperativeFCompute,
# src/ndarray/autograd.cc:82)
_RECORD_HOOK = None


def _as_jax(value, dtype=None):
    if isinstance(value, NDArray):
        return value.data
    if isinstance(value, jax.Array):
        return value
    return jnp.asarray(value, dtype=dtype)


def _snapshot(value):
    """Freeze a raw (non-NDArray) operand to an immutable jax.Array at
    its call-site value — THE snapshot rule for every deferred use of a
    caller-owned buffer (engine dispatch args/kwargs, autograd replay
    constants, lazy-chain inputs).  copy=True is load-bearing: plain
    jnp.asarray on CPU may zero-copy ALIAS numpy memory, which is no
    snapshot at all."""
    return jnp.array(value, copy=True) if isinstance(value, _np.ndarray) \
        else _as_jax(value)


class NDArray:
    """Multi-dimensional array on a device (parity: python/mxnet/ndarray.py NDArray)."""

    # _fresh_grad backs MXNDArray{Set,Get}GradState (set lazily; unset
    # slot reads as 0 through the C API).  _var is the engine dependency
    # variable for this chunk (reference NDArray::var(), ndarray.h:350),
    # created lazily on first engine dispatch.  _lazy is the pending
    # deferred-op node producing this chunk under lazy imperative
    # evaluation (lazy.py), or None once materialized/flushed.
    __slots__ = ("_data", "_ctx", "_parent", "_index", "writable",
                 "_fresh_grad", "_var", "_lazy", "_mem_booked")

    def __init__(self, data, ctx=None, _parent=None, _index=None):
        self._parent = _parent
        self._index = _index
        self._ctx = ctx if ctx is not None else current_context()
        self._data = data
        self._var = None
        self._lazy = None
        self._mem_booked = 0
        self.writable = True
        if data is not None and _parent is None:
            self._mem_account(data)

    def _mem_account(self, value):
        """Live-buffer census (obs/memory.py, tag ``ndarray.<device>``):
        book this chunk's payload bytes at every payload swap.  The
        booked amount is recorded on the chunk so __del__ releases
        exactly what was booked — the census stays balanced even when
        telemetry toggles mid-life.  Views book nothing (the parent
        owns the payload)."""
        from . import telemetry

        if not telemetry.enabled():
            return
        from .obs import memory

        n = int(getattr(value, "nbytes", 0) or 0)
        booked = self._mem_booked
        if n != booked:
            memory.rebook("ndarray." + self._ctx.device_type, booked, n)
            self._mem_booked = n

    def __del__(self):
        booked = getattr(self, "_mem_booked", 0)
        if booked:
            try:
                from .obs import memory

                memory.unbook("ndarray." + self._ctx.device_type, booked)
            except Exception:
                pass  # interpreter teardown: books are gone anyway

    # ------------------------------------------------------------------
    # payload access
    # ------------------------------------------------------------------
    @property
    def data(self):
        """The underlying jax.Array (lazy slice of parent for views).

        This is a READ sync point: if engine ops are pending on this
        chunk's variable the read blocks until the writers complete (and
        re-raises their deferred error — reference WaitToRead semantics).
        Inside an engine op the wait is skipped: the op's declared deps
        already guarantee the value is final."""
        if self._parent is not None:
            return self._parent.data[self._index]
        if self._lazy is not None:
            # lazy sync point: push the pending fused chain through the
            # engine; the wait below then blocks on its write token
            lazy.materialize(self)
        var = self._var
        if var is not None and (var.pending_writes or var.exception is not None) \
                and not engine.in_engine_op():
            engine.get().wait_for_var(var)
        if self._data is None and var is not None:
            # the producing engine op failed and its deferred error was
            # already delivered at an earlier sync point; a clear error
            # beats an AttributeError on a None payload downstream
            raise MXNetError(
                "NDArray value is unavailable: the engine op that was to "
                "produce it failed (its error was raised at an earlier "
                "sync point)")
        engine.note_access(var, False)  # SanitizerEngine contract check
        return self._data

    def _raw(self):
        """Payload WITHOUT engine sync — only valid inside an engine op
        whose declared read/write vars cover this array (the
        SanitizerEngine verifies exactly that via note_access)."""
        if self._parent is not None:
            return self._parent._raw()[self._index]
        engine.note_access(self._var, False)
        return self._data

    def _engine_var(self):
        """This chunk's dependency variable (reference NDArray::var();
        views share their parent's var, as reference views share the
        Chunk).  Requesting the var is how a chunk enters the
        engine-visible world, so any pending fused chain touching it is
        flushed first — its tokens must exist before a foreign op's
        tokens order against them."""
        if self._parent is not None:
            return self._parent._engine_var()
        lazy.flush_for_array(self)
        if self._var is None:
            self._var = engine.Var()
        return self._var

    def _full_overwrite_base(self):
        """Current payload for a whole-array overwrite, or None when there
        is none to preserve (the producing op failed): inside an engine op
        the raw payload is authoritative; outside, pending writers are
        awaited first so a not-yet-delivered producer error still raises
        here rather than being silently papered over."""
        if self._parent is not None:
            return self.data
        if self._lazy is not None:
            return self.data  # lazy sync point: flush + wait
        if engine.in_engine_op():
            return self._raw()
        var = self._var
        if var is not None and (var.pending_writes or var.exception is not None):
            return self.data  # waits; re-raises an undelivered deferred error
        return self._data

    def _set_data(self, value):
        if self._parent is not None:
            self._parent._set_data(self._parent.data.at[self._index].set(value))
        else:
            if not engine.in_engine_op():
                # mutation sync point: pending fused chains reading (or
                # producing) this chunk must be pushed first so their
                # read tokens order BEFORE this write (lazy analog of
                # the WAR wait below); inside an engine op the flush
                # already happened at push time (_engine_var)
                lazy.flush_for_array(self)
            var = self._var
            if var is not None and (var.pending_writes or var.pending_reads) \
                    and not engine.in_engine_op():
                # in-place assignment is a WRITE on the chunk var: wait out
                # pending readers (WAR) and writers (WAW) before swapping
                engine.get().wait_for_var(var, wait_reads=True)
            engine.note_access(var, True)  # SanitizerEngine contract check
            self._data = value
            self._mem_account(value)

    # ------------------------------------------------------------------
    # basic properties
    # ------------------------------------------------------------------
    def _meta_aval(self):
        """Abstract shape/dtype of a pending lazy value, or None —
        metadata reads must not flush a fused chain (lazy.aval_for)."""
        if self._parent is None and self._lazy is not None:
            return lazy.aval_for(self)
        return None

    @property
    def shape(self):
        aval = self._meta_aval()
        if aval is not None:
            return tuple(aval.shape)
        return tuple(self.data.shape)

    @property
    def size(self):
        aval = self._meta_aval()
        if aval is not None:
            return int(_np.prod(aval.shape)) if aval.shape else 1
        return int(self.data.size)

    @property
    def ndim(self):
        aval = self._meta_aval()
        if aval is not None:
            return len(aval.shape)
        return self.data.ndim

    @property
    def dtype(self):
        aval = self._meta_aval()
        if aval is not None:
            return _np.dtype(aval.dtype)
        return _np.dtype(self.data.dtype)

    @property
    def context(self):
        return self._ctx

    @property
    def ctx(self):
        return self._ctx

    @property
    def T(self):
        return NDArray(self.data.T, self._ctx)

    def __len__(self):
        return self.shape[0]

    def __repr__(self):
        return "<NDArray %s @%s>" % ("x".join(map(str, self.shape)), self._ctx)

    def __str__(self):
        return str(self.asnumpy())

    # ------------------------------------------------------------------
    # DLPack interop (reference include/mxnet/ndarray.h:401 SetDLTensor;
    # zero-copy exchange with numpy/torch/jax ecosystems)
    # ------------------------------------------------------------------
    def __array__(self, dtype=None, copy=None):
        # numpy interop: np.asarray(nd) is one bulk transfer, not a
        # per-element __getitem__ walk
        if copy is False:
            raise ValueError(
                "NDArray->numpy always copies (device-to-host transfer); "
                "copy=False cannot be honored")
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    def __dlpack__(self, *args, **kwargs):
        return self.data.__dlpack__(*args, **kwargs)

    def __dlpack_device__(self):
        return self.data.__dlpack_device__()

    def to_dlpack_for_read(self):
        """The array itself — any DLPack consumer accepts it via
        `from_dlpack(nd)` (capsule protocol)."""
        return self

    to_dlpack_for_write = to_dlpack_for_read

    # ------------------------------------------------------------------
    # host transfer / sync (reference WaitToRead / asnumpy)
    # ------------------------------------------------------------------
    def asnumpy(self):
        d = self.data
        if isinstance(d, jax.Array) and not d.is_fully_addressable \
                and not d.is_fully_replicated:
            # a batch-sharded GLOBAL array (multi-process mesh): remote
            # shards must be allgathered before a host read — collective,
            # so every process's training loop reaches here in the same
            # order (SPMD); see parallel/multihost.fetch
            from .parallel.multihost import fetch

            return fetch(d)
        return _np.asarray(d)

    def asscalar(self):
        return self.asnumpy().reshape(()).item()

    def wait_to_read(self):
        """Block until this array's value is computed (reference WaitToRead).

        Two fences compose: the engine's `wait_for_var` drains pending
        host-side ops on this chunk's variable, then `block_until_ready`
        covers XLA's own async dispatch (a real completion fence on the
        TPU: chip_smoke.py's fence phase checks it against the chip's
        peak on every run)."""
        self._sync(wait_reads=False)

    def wait_to_write(self):
        """Block until pending readers AND writers finish (reference
        WaitToWrite): after this, an in-place mutation cannot race a
        queued engine op."""
        self._sync(wait_reads=True)

    def _sync(self, wait_reads):
        base = self
        while base._parent is not None:
            base = base._parent
        # lazy sync point (wait_to_read/wait_to_write): push the pending
        # chain producing or reading this chunk before fencing its var
        lazy.flush_for_array(base)
        if base._var is not None:
            engine.get().wait_for_var(base._var, wait_reads=wait_reads)
        d = self.data
        if hasattr(d, "block_until_ready"):
            d.block_until_ready()

    # ------------------------------------------------------------------
    # conversion / copies
    # ------------------------------------------------------------------
    def astype(self, dtype):
        return NDArray(self.data.astype(jnp.dtype(dtype)), self._ctx)

    def copy(self):
        return NDArray(self.data + 0, self._ctx)

    def copyto(self, other):
        """Copy into an NDArray or to a Context (reference ndarray.h CopyFromTo)."""
        if isinstance(other, NDArray):
            other[:] = self
            return other
        if isinstance(other, Context):
            return NDArray(jax.device_put(self.data, other.jax_device()), other)
        raise TypeError("copyto does not support type " + str(type(other)))

    def as_in_context(self, ctx):
        if ctx == self._ctx:
            return self
        return self.copyto(ctx)

    def reshape(self, shape, **kwargs):
        if isinstance(shape, int):
            shape = (shape,)
        return NDArray(jnp.reshape(self.data, tuple(shape)), self._ctx)

    def broadcast_to(self, shape):
        return NDArray(jnp.broadcast_to(self.data, tuple(shape)), self._ctx)

    # ------------------------------------------------------------------
    # views (reference Slice/At are zero-copy aliases; here write-through)
    # ------------------------------------------------------------------
    def __getitem__(self, key):
        if isinstance(key, NDArray):
            key = key.data.astype(jnp.int32)
            return NDArray(self.data[key], self._ctx)
        return NDArray(None, self._ctx, _parent=self, _index=key)

    def __setitem__(self, key, value):
        # NOTE: builtins.slice — the registry populates a module-level `slice`
        # op function in this namespace, which would shadow the builtin here.
        if isinstance(key, builtins.slice) and key == builtins.slice(None):
            base = self._full_overwrite_base()
            if base is None:
                # revival of a failed array (its producer op errored and the
                # deferred error was already delivered): a full overwrite
                # needs no prior value — this is how e.g. kv.pull restores
                # a poisoned weight, and how the engine's
                # write-clears-poison rule stays reachable
                newval = _as_jax(value)
                if getattr(newval, "ndim", 0) == 0:
                    raise MXNetError(
                        "cannot restore a failed NDArray from a scalar: its "
                        "shape was never materialized; assign a full array")
                self._set_data(newval)
                return
            val = _as_jax(value, dtype=base.dtype)
            self._set_data(jnp.broadcast_to(val, base.shape).astype(base.dtype))
            return
        val = _as_jax(value, dtype=self.dtype)
        if isinstance(key, NDArray):
            key = key.data.astype(jnp.int32)
        self._set_data(self.data.at[key].set(val))

    def slice(self, start, stop):
        return self[start:stop]

    def at(self, idx):
        return self[idx]

    # ------------------------------------------------------------------
    # arithmetic — dispatches through the op registry so imperative and
    # symbolic share one definition (SURVEY.md §7 phase 2)
    # ------------------------------------------------------------------
    def _binary(self, other, op_name, scalar_name, reverse=False):
        if isinstance(other, _np.ndarray) and other.ndim == 0:
            other = float(other)
        if isinstance(other, (NDArray, jax.Array, _np.ndarray)):
            args = (other, self) if reverse else (self, other)
            out = _engine_invoke(get_op(op_name), args, {}, self._ctx)
            if _RECORD_HOOK is not None:
                fn = get_op(op_name).fn
                if isinstance(other, NDArray):
                    ins = [other, self] if reverse else [self, other]
                    _RECORD_HOOK(fn, ins, [out])
                else:
                    # raw operand captured as a replay constant — the
                    # replay must see call-site values
                    const = _snapshot(other)
                    if reverse:
                        _RECORD_HOOK(lambda x, _c=const, _f=fn: _f(_c, x),
                                     [self], [out])
                    else:
                        _RECORD_HOOK(lambda x, _c=const, _f=fn: _f(x, _c),
                                     [self], [out])
            return out
        out = _engine_invoke(get_op(scalar_name), (self,),
                             {"scalar": float(other)}, self._ctx)
        if _RECORD_HOOK is not None:
            _RECORD_HOOK(lambda x, _f=get_op(scalar_name).fn, _s=float(other):
                         _f(x, scalar=_s), [self], [out])
        return out

    def __add__(self, o):
        return self._binary(o, "elemwise_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binary(o, "elemwise_sub", "_minus_scalar")

    def __rsub__(self, o):
        if isinstance(o, NDArray):
            return o.__sub__(self)
        return NDArray(get_op("_rminus_scalar").fn(self.data, scalar=float(o)), self._ctx)

    def __mul__(self, o):
        return self._binary(o, "elemwise_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binary(o, "elemwise_div", "_div_scalar")

    __div__ = __truediv__

    def __rtruediv__(self, o):
        if isinstance(o, NDArray):
            return o.__truediv__(self)
        return NDArray(get_op("_rdiv_scalar").fn(self.data, scalar=float(o)), self._ctx)

    __rdiv__ = __rtruediv__

    def __mod__(self, o):
        return self._binary(o, "_mod", "_mod_scalar")

    def __pow__(self, o):
        return self._binary(o, "_power", "_power_scalar")

    def __rpow__(self, o):
        return NDArray(get_op("_rpower_scalar").fn(self.data, scalar=float(o)), self._ctx)

    def __neg__(self):
        return NDArray(-self.data, self._ctx)

    def __iadd__(self, o):
        self._set_data((self + o).data.astype(self.dtype))
        return self

    def __isub__(self, o):
        self._set_data((self - o).data.astype(self.dtype))
        return self

    def __imul__(self, o):
        self._set_data((self * o).data.astype(self.dtype))
        return self

    def __itruediv__(self, o):
        self._set_data((self / o).data.astype(self.dtype))
        return self

    __idiv__ = __itruediv__

    def __eq__(self, o):
        return self._binary(o, "_equal", "_equal_scalar") if o is not None else False

    def __ne__(self, o):
        return self._binary(o, "_not_equal", "_not_equal_scalar") if o is not None else True

    def __gt__(self, o):
        return self._binary(o, "_greater", "_greater_scalar")

    def __ge__(self, o):
        return self._binary(o, "_greater_equal", "_greater_equal_scalar")

    def __lt__(self, o):
        return self._binary(o, "_lesser", "_lesser_scalar")

    def __le__(self, o):
        return self._binary(o, "_lesser_equal", "_lesser_equal_scalar")

    def __hash__(self):
        return id(self)

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise ValueError("The truth value of an NDArray with multiple elements is ambiguous.")

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __getstate__(self):
        return {"data": self.asnumpy(), "ctx": (self._ctx.device_type, self._ctx.device_id)}

    def __setstate__(self, state):
        self._parent = None
        self._index = None
        self._var = None
        self._lazy = None
        self._ctx = Context(*state["ctx"])
        self._data = jnp.asarray(state["data"])
        self._mem_booked = 0
        self.writable = True
        self._mem_account(self._data)

    # convenience reductions mirroring generated methods
    def sum(self, axis=None, keepdims=False):
        return NDArray(jnp.sum(self.data, axis=axis, keepdims=keepdims), self._ctx)

    def mean(self, axis=None, keepdims=False):
        return NDArray(jnp.mean(self.data, axis=axis, keepdims=keepdims), self._ctx)

    def max(self, axis=None, keepdims=False):
        return NDArray(jnp.max(self.data, axis=axis, keepdims=keepdims), self._ctx)

    def min(self, axis=None, keepdims=False):
        return NDArray(jnp.min(self.data, axis=axis, keepdims=keepdims), self._ctx)

    def abs(self):
        return NDArray(jnp.abs(self.data), self._ctx)

    def flatten(self):
        return NDArray(self.data.reshape((self.shape[0], -1)), self._ctx)

    def expand_dims(self, axis):
        return NDArray(jnp.expand_dims(self.data, axis), self._ctx)

    def transpose(self, axes=None):
        return NDArray(jnp.transpose(self.data, axes), self._ctx)

    def argmax(self, axis=None):
        return NDArray(jnp.argmax(self.data, axis=axis).astype(jnp.float32), self._ctx)


# lazy imperative evaluation (deferred-op fusion) — imported AFTER the
# NDArray class: lazy.py imports NDArray back from this module
from . import lazy  # noqa: E402


# ----------------------------------------------------------------------
# creation routines (parity: python/mxnet/ndarray.py module functions)
# ----------------------------------------------------------------------


# NOTE on placement: creation returns UNCOMMITTED jax arrays — XLA places
# them on the default device and freely co-locates with other operands.
# Committing every array to its Context's device (the reference model,
# where NDArray memory is physically on ctx) would poison mixed-context
# arithmetic under JAX's committed-device rules.  An NDArray's context is
# therefore a label until something binds it: explicit placement happens
# in the Executor (mesh shardings; Executor._resident for a mesh-less
# executor whose context is not the default device) and in copyto().


def array(source_array, ctx=None, dtype=None):
    ctx = ctx or current_context()
    if isinstance(source_array, NDArray):
        source_array = source_array.data
    host_source = not isinstance(source_array, jax.Array)
    if dtype is None and not isinstance(source_array, (_np.ndarray, jax.Array)):
        dtype = "float32"  # parity: python lists default to float32
    arr = jnp.asarray(source_array, dtype=jnp.dtype(dtype) if dtype else None)
    if arr.dtype == jnp.float64:
        arr = arr.astype(jnp.float32)
    if host_source:
        # the real host->device transfer point of the imperative API
        # (batch iterators, init, user numpy): telemetry counts H2D
        # bytes HERE, where the copy happens, not at forward()
        from . import telemetry

        if telemetry.enabled():
            telemetry.inc("executor.h2d_bytes", int(arr.nbytes))
    return NDArray(arr, ctx)


def off_platform(value, platform):
    """Does the jax.Array `value` live on devices of ANOTHER platform
    than `platform`?  Beside an accelerator that is what tells host
    memory (a `host_array`'s payload, on the CPU backend's device) from
    the devices that compute; where the CPU backend computes, host and
    device are one platform and nothing is off it."""
    return isinstance(value, jax.Array) and not any(
        d.platform == platform for d in value.devices())


def host_array(source_array):
    """An NDArray over numpy `source_array` whose payload STAYS IN HOST
    MEMORY, as the reference's `cpu()` arrays do: what an iterator over
    host memory yields (io.NDArrayIter).  The payload is an uncommitted
    `jax.Array` of the CPU backend's first device: beside an accelerator
    it touches none, and whoever needs the values on a device moves them
    there — staging sends each device its own rows
    (Executor.place_step_input), an executor places a whole batch
    (Executor.forward), an imperative op's jit moves an uncommitted
    operand to where it computes.  XLA's CPU client ALIASES a C-ordered
    numpy buffer that starts at a 64-byte boundary (io.aligned_empty)
    and copies any other, so the source stays as it is while the array
    lives.

    `executor.h2d_bytes` counts bytes where they cross: here only if the
    payload is already where this process computes (the CPU backend:
    nothing downstream sees a crossing), as `array` counts them."""
    try:
        dev = jax.local_devices(backend="cpu")[0]
    except RuntimeError:  # JAX_PLATFORMS leaves the cpu backend out
        return array(source_array)
    with jax.default_device(dev):
        arr = jnp.asarray(source_array)
        if arr.dtype == jnp.float64:
            arr = arr.astype(jnp.float32)
    if not off_platform(arr, jax.default_backend()):
        from . import telemetry

        if telemetry.enabled():
            telemetry.inc("executor.h2d_bytes", int(arr.nbytes))
    return NDArray(arr, cpu())


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx, dtype)


def _norm_shape(shape):
    return shape if isinstance(shape, tuple) else (shape,) if isinstance(shape, int) else tuple(shape)


def zeros(shape, ctx=None, dtype=None):
    ctx = ctx or current_context()
    return NDArray(jnp.zeros(_norm_shape(shape), dtype=jnp.dtype(dtype) if dtype else jnp.float32), ctx)


def ones(shape, ctx=None, dtype=None):
    ctx = ctx or current_context()
    return NDArray(jnp.ones(_norm_shape(shape), dtype=jnp.dtype(dtype) if dtype else jnp.float32), ctx)


def full(shape, val, ctx=None, dtype=None):
    ctx = ctx or current_context()
    return NDArray(jnp.full(_norm_shape(shape), val, dtype=jnp.dtype(dtype) if dtype else jnp.float32), ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None):
    arr = get_op("_arange").fn(start=start, stop=stop, step=step, repeat=repeat,
                               dtype=dtype or "float32")
    ctx = ctx or current_context()
    return NDArray(arr, ctx)


def moveaxis(tensor, source, destination):
    """Move `tensor`'s axis `source` to position `destination`
    (reference ndarray.py:1166)."""
    return NDArray(jnp.moveaxis(tensor.data, int(source), int(destination)),
                   tensor.ctx)


def concatenate(arrays, axis=0, always_copy=True):
    return NDArray(jnp.concatenate([a.data for a in arrays], axis=axis), arrays[0].ctx)


def onehot_encode(indices, out):
    depth = out.shape[1]
    out[:] = NDArray(jax.nn.one_hot(indices.data.astype(jnp.int32), depth), indices.ctx)
    return out


def imresize(src, w, h, interp=1):
    out = jax.image.resize(src.data, (h, w) + src.shape[2:], method="bilinear" if interp else "nearest")
    return NDArray(out, src.ctx)


def waitall():
    """Global fence (reference Engine::WaitForAll).

    Drains the dependency engine (all pushed NDArray/kvstore/io ops),
    re-raising the first deferred engine error, then fences the device:
    JAX has no global work queue to drain, so we fence a fresh
    computation, which on an in-order device stream completes after all
    prior work."""
    lazy.flush_all("sync")
    engine.get().wait_for_all()
    x = jnp.zeros(()) + 0
    x.block_until_ready()


# ----------------------------------------------------------------------
# serialization (parity: mx.nd.save/load → reference src/c_api/c_api.cc:218-271)
#
# Default on-disk layout is the REFERENCE binary NDArray-list format so
# .params files interop with upstream MXNet both directions:
#   u64 magic=0x112 (kMXAPINDArrayListMagic), u64 reserved=0,
#   u64 count, per array (NDArray::Save, src/ndarray/ndarray.cc:641-664):
#   u32 NDARRAY_V1_MAGIC, u32 ndim + i64 dims (V1 int64 TShape),
#   Context (i32 dev_type, i32 dev_id), i32 type_flag, raw bytes;
#   then u64 nkeys + (u64 len + bytes) per key.  Load also accepts the
#   pre-V1 legacy TShape layout (u32 ndim + u32 dims,
#   LegacyTShapeLoad ndarray.cc:666-682).
# Arrays whose dtype the reference ABI cannot express (bfloat16, int64, ...)
# or 0-dim arrays (reference Load treats ndim==0 as a none-NDArray and
# stops reading, ndarray.cc:688-690) fall back to the self-describing
# MXTPU001 container; load() sniffs both.
# ----------------------------------------------------------------------

_SAVE_MAGIC = b"MXTPU001"
_NDLIST_MAGIC = 0x112  # kMXAPINDArrayListMagic
_NDARRAY_V1_MAGIC = 0xF993FAC8  # per-array magic, int64 TShape
_DTYPE_TO_FLAG = {"float32": 0, "float64": 1, "float16": 2, "uint8": 3, "int32": 4}
_FLAG_TO_DTYPE = {v: k for k, v in _DTYPE_TO_FLAG.items()}


def _split_save_arg(data):
    if isinstance(data, NDArray):
        data = [data]
    if isinstance(data, dict):
        keys = list(data.keys())
        arrays = [data[k] for k in keys]
    else:
        keys = None
        arrays = list(data)
    np_arrays = [a.asnumpy() if isinstance(a, NDArray) else _np.asarray(a)
                 for a in arrays]
    return keys, np_arrays


def from_dlpack(ext_array, ctx=None):
    """Zero-copy import of any DLPack-capable array (torch/numpy/jax/...)."""
    from .context import current_context

    return NDArray(jnp.from_dlpack(ext_array), ctx or current_context())


def save(fname, data):
    """Save list or dict of NDArray (parity: python/mxnet/ndarray.py save)."""
    keys, np_arrays = _split_save_arg(data)
    if all(a.dtype.name in _DTYPE_TO_FLAG and a.ndim > 0 for a in np_arrays):
        return _save_reference_format(fname, keys, np_arrays)
    return _save_container_format(fname, keys, np_arrays)


def _save_reference_format(fname, keys, np_arrays):
    with open(fname, "wb") as f:
        f.write(struct.pack("<QQ", _NDLIST_MAGIC, 0))
        f.write(struct.pack("<Q", len(np_arrays)))
        for np_arr in np_arrays:
            f.write(struct.pack("<II", _NDARRAY_V1_MAGIC, np_arr.ndim))
            f.write(struct.pack("<%dq" % np_arr.ndim, *np_arr.shape))
            f.write(struct.pack("<ii", 1, 0))  # Context: kCPU, dev_id 0
            f.write(struct.pack("<i", _DTYPE_TO_FLAG[np_arr.dtype.name]))
            f.write(_np.ascontiguousarray(np_arr).tobytes())
        names = keys if keys is not None else []
        f.write(struct.pack("<Q", len(names)))
        for name in names:
            b = name.encode()
            f.write(struct.pack("<Q", len(b)))
            f.write(b)


def _save_container_format(fname, keys, np_arrays):
    with open(fname, "wb") as f:
        f.write(_SAVE_MAGIC)
        f.write(struct.pack("<q", len(np_arrays)))
        f.write(struct.pack("<q", 1 if keys is not None else 0))
        for i, np_arr in enumerate(np_arrays):
            name = (keys[i] if keys is not None else "").encode()
            f.write(struct.pack("<q", len(name)))
            f.write(name)
            # dtype by name ('bfloat16', 'float32', ...) — extension dtypes
            # have an opaque .str ('|V2') that can't round-trip
            dt = np_arr.dtype.name.encode()
            f.write(struct.pack("<q", len(dt)))
            f.write(dt)
            f.write(struct.pack("<q", np_arr.ndim))
            for d in np_arr.shape:
                f.write(struct.pack("<q", d))
            raw = np_arr.tobytes()
            f.write(struct.pack("<q", len(raw)))
            f.write(raw)


def load(fname):
    """Load NDArrays saved by :func:`save` or by reference MXNet's mx.nd.save."""
    with open(fname, "rb") as f:
        return _load_fileobj(f, fname)


def loads(buf):
    """Load NDArrays from raw bytes (the predict-API path: reference
    MXPredCreate takes the .params file CONTENT, c_predict_api.cc:44)."""
    import io

    return _load_fileobj(io.BytesIO(buf), "<bytes>")


def _load_fileobj(f, fname):
    magic = f.read(8)
    if magic == _SAVE_MAGIC:
        return _load_container_format(f)
    if len(magic) == 8 and struct.unpack("<Q", magic)[0] == _NDLIST_MAGIC:
        return _load_reference_format(f)
    raise MXNetError(
        "Invalid NDArray file format in %s: neither the MXNet binary "
        "NDArray-list format (magic 0x112) nor the MXTPU001 container" % fname)


def _load_reference_format(f):
    (_reserved,) = struct.unpack("<Q", f.read(8))
    (num,) = struct.unpack("<Q", f.read(8))
    arrays = []
    for _ in range(num):
        (first,) = struct.unpack("<I", f.read(4))
        if first == _NDARRAY_V1_MAGIC:
            (ndim,) = struct.unpack("<I", f.read(4))
            shape = struct.unpack("<%dq" % ndim, f.read(8 * ndim)) if ndim else ()
        else:
            # legacy TShape: `first` IS ndim, u32 dims (LegacyTShapeLoad)
            ndim = first
            shape = struct.unpack("<%dI" % ndim, f.read(4 * ndim)) if ndim else ()
        if ndim == 0:
            # reference: none-NDArray — no ctx/type/data bytes follow
            arrays.append(array(_np.zeros((0,), dtype=_np.float32)))
            continue
        _dev_type, _dev_id = struct.unpack("<ii", f.read(8))
        (type_flag,) = struct.unpack("<i", f.read(4))
        if type_flag not in _FLAG_TO_DTYPE:
            raise MXNetError("Unsupported dtype flag %d in NDArray file" % type_flag)
        dt = _np.dtype(_FLAG_TO_DTYPE[type_flag])
        count = int(_np.prod(shape))
        np_arr = _np.frombuffer(f.read(dt.itemsize * count), dtype=dt).reshape(shape)
        arrays.append(array(np_arr))
    (nkeys,) = struct.unpack("<Q", f.read(8))
    if nkeys == 0:
        return arrays
    if nkeys != num:
        # reference hard-fails here too (CHECK keys->size()==data->size(),
        # ndarray.cc:742-743) — silently dropping arrays would restore a
        # checkpoint with missing params
        raise MXNetError("Invalid NDArray file format: %d names for %d arrays"
                         % (nkeys, num))
    keys = []
    for _ in range(nkeys):
        (klen,) = struct.unpack("<Q", f.read(8))
        keys.append(f.read(klen).decode())
    return dict(zip(keys, arrays))


def _load_container_format(f):
    (num,) = struct.unpack("<q", f.read(8))
    (has_keys,) = struct.unpack("<q", f.read(8))
    keys, arrays = [], []
    for _ in range(num):
        (nlen,) = struct.unpack("<q", f.read(8))
        keys.append(f.read(nlen).decode())
        (dlen,) = struct.unpack("<q", f.read(8))
        dt_name = f.read(dlen).decode()
        try:
            dt = _np.dtype(dt_name)
        except TypeError:
            import ml_dtypes

            dt = _np.dtype(getattr(ml_dtypes, dt_name))
        (ndim,) = struct.unpack("<q", f.read(8))
        shape = tuple(struct.unpack("<q", f.read(8))[0] for _ in range(ndim))
        (rlen,) = struct.unpack("<q", f.read(8))
        np_arr = _np.frombuffer(f.read(rlen), dtype=dt).reshape(shape)
        arrays.append(array(np_arr))
    if has_keys:
        return dict(zip(keys, arrays))
    return arrays


# ----------------------------------------------------------------------
# generated op namespace (parity: reference codegen ndarray.py:2362-2514
# `_make_ndarray_function` — here generated from the registry at import)
# ----------------------------------------------------------------------


def _tracer_free(args):
    """False when any operand is (backed by) a live jax Tracer: a
    CustomOp / torch-bridge forward may run imperative ops INSIDE an
    active jax transformation, and deferring those to a worker thread
    would leak the tracer out of its trace
    (jax.errors.UnexpectedTracerError) — they must execute eagerly on
    the tracing thread."""
    for a in args:
        if isinstance(a, NDArray):
            base = a
            while base._parent is not None:
                base = base._parent
            if isinstance(base._data, jax.core.Tracer):
                return False
        elif isinstance(a, jax.core.Tracer):
            return False
    return True


def _engine_invoke(op, args, kwargs, ctx, priority=0):
    """Dispatch one single-output op through the dependency engine
    (reference Engine::PushAsync from MXImperativeInvoke,
    c_api_ndarray.cc:248-430): returns the output handle immediately;
    the value materializes on an engine worker once all input writers
    have completed.  Reads on the result synchronize via its chunk var.
    Tracer operands fall back to eager inline execution.

    Under lazy imperative evaluation (lazy.py; MXTPU_LAZY, on by
    default) the op is not executed at all: it joins the context's
    pending expression graph and the whole chain later runs as ONE
    jitted dispatch.  Deferral is skipped inside engine ops (the chain
    would escape the op's declared var footprint) and while the
    autograd tape records (the tape must observe program order)."""
    if not _tracer_free(args):
        return NDArray(op.fn(*[_as_jax(a) for a in args], **kwargs), ctx)
    # non-NDArray operands — positional AND keyword — are snapshotted
    # NOW: a numpy scratch buffer the caller mutates after this call has
    # no engine var, so only an eager copy (_snapshot) keeps the op's
    # inputs at their call-site values (jax.Arrays are immutable, so
    # they pass through untouched)
    args = tuple(a if isinstance(a, NDArray) else _snapshot(a)
                 for a in args)
    if kwargs and any(isinstance(v, _np.ndarray) for v in kwargs.values()):
        kwargs = {
            k: _snapshot(v) if isinstance(v, _np.ndarray) else v
            for k, v in kwargs.items()}
    if _RECORD_HOOK is not None:
        # autograd boundary: recorded ops must observe program order
        # against any pending fused chain, and are never deferred
        lazy.flush_all("sync")
    elif lazy.enabled() and not engine.in_engine_op():
        out = lazy.record(op, args, kwargs, ctx)
        if out is not None:
            return out
    out = NDArray(None, ctx)
    eng = engine.get()
    read_vars = [a._engine_var() for a in args if isinstance(a, NDArray)]

    def _run(_op=op, _args=args, _kw=kwargs, _out=out):
        from . import telemetry

        if telemetry.enabled():
            telemetry.inc("ndarray.imperative_dispatches")
        jax_args = [a._raw() if isinstance(a, NDArray) else a for a in _args]
        _out._set_data(_op.fn(*jax_args, **_kw))

    eng.push(_run, read_vars=read_vars, write_vars=(out._engine_var(),),
             priority=priority, name=op.name)
    return out


def _engine_dispatchable(op, args):
    """Ops the engine path covers: single fixed output, no aux-state
    mutation, no host RNG (draw order must follow program order), no
    mesh/is_train plumbing, and no variadic list arguments."""
    return (op.num_outputs == 1 and op.num_aux_out == 0
            and not op.need_rng and not op.need_mesh and not op.need_is_train
            and not any(isinstance(a, (list, tuple)) for a in args))


def _make_nd_function(op):
    def fn(*args, **kwargs):
        out = kwargs.pop("out", None)
        kwargs.pop("name", None)  # accepted for symbol-compat call sites
        ctx = kwargs.pop("ctx", None)
        res_ctx = None
        for a in args:
            if isinstance(a, NDArray):
                res_ctx = a.ctx
                break
        res_ctx = ctx or res_ctx or current_context()
        if op.params:
            from .ops.params import validate_attrs

            validate_attrs(op, kwargs)
        if _engine_dispatchable(op, args):
            boxed = _engine_invoke(op, args, kwargs, res_ctx)
        else:
            jax_args = [_as_jax(a) for a in args]
            result = op.fn(*jax_args, **kwargs)
            if isinstance(result, tuple):
                main = result[: len(result) - op.num_aux_out] if op.num_aux_out else result
                boxed = tuple(NDArray(r, res_ctx) for r in main)
                if len(boxed) == 1:
                    boxed = boxed[0]
            else:
                boxed = NDArray(result, res_ctx)
        if _RECORD_HOOK is not None:
            nd_ins = [a for a in args if isinstance(a, NDArray)]
            nd_outs = list(boxed) if isinstance(boxed, tuple) else [boxed]
            # non-NDArray args are captured as constants in the replay
            # fn (snapshotted — the replay must see call-site values)
            spec = [None if isinstance(a, NDArray) else _snapshot(a)
                    for a in args]

            # mxlint: disable=W101 -- deliberate def-time snapshot: the replay closure must see the kwargs as they were at record time; the default is never mutated
            def _replay(*xs, _f=op.fn, _kw=dict(kwargs), _spec=spec):
                it = iter(xs)
                vals = [next(it) if s is None else s for s in _spec]
                return _f(*vals, **_kw)

            _RECORD_HOOK(_replay, nd_ins, nd_outs)
        if out is not None:
            if isinstance(boxed, tuple):
                for o, b in zip(out if isinstance(out, (list, tuple)) else [out], boxed):
                    o[:] = b
            else:
                out[:] = boxed
            return out
        return boxed

    fn.__name__ = op.name
    fn.__doc__ = op.doc
    return fn


def _populate(module):
    seen = {}
    for name, op in OP_REGISTRY.items():
        if id(op) not in seen:
            seen[id(op)] = _make_nd_function(op)
        public = name
        if not hasattr(module, public):
            setattr(module, public, seen[id(op)])


_populate(sys.modules[__name__])
