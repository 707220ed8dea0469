"""Lazy imperative evaluation — fuse NDArray op chains into one dispatch.

The imperative API used to pay one XLA dispatch per primitive: every
``a + b`` pushed through :func:`ndarray._engine_invoke` called ``op.fn``
un-jitted on an engine worker, one device dispatch each — a fixed host
cost per op (magnitude on the TPU host: not measured; no cell runs an
imperative chain), for every imperative workload the K-step fused
training path (docs/perf.md) cannot reach: init, metrics, monitor
sweeps, user scripts.

This module is the LazyTensor/NNVM answer (Suhan et al. 2021; Chen et
al. 2018 — the graph-optimization role the reference's empty ``nnvm/``
submodule played): imperative ops *defer*.  Each dispatchable op appends
a node to a per-context pending graph and returns an NDArray whose
payload materializes later; the whole chain is flushed as ONE
``jax.jit``-compiled call when a sync point forces a value:

  * a payload read — ``.data`` / ``asnumpy`` / ``asscalar`` /
    ``wait_to_read`` / ``float()`` / numpy interop;
  * the chunk entering the engine-visible world — ``_engine_var()``
    from any eager push site (kvstore, io staging, non-deferrable ops);
  * a mutation — ``a[:] = v``, view write-through scatter, ``a += b``;
  * an autograd ``_RECORD_HOOK`` boundary (the tape must observe
    program order);
  * the chain reaching ``MXTPU_LAZY_MAX_OPS`` nodes (cap flush);
  * ``mx.waitall()``.

Flushed programs are keyed by a *structural fingerprint* — op names,
static attrs, dependency wiring, and input shapes/dtypes — into a
fusion cache next to the executor's jit caches.  ``float`` attrs of
ops whose kernels declared themselves tracer-safe
(``Op.lift_floats`` — the ``_reg_scalar`` family) are **lifted to
traced operands**, so ``x + 0.1`` and ``x + 0.2`` share one compiled
executable (jit abstracts scalar leaves to weak-typed ShapedArrays);
float attrs of every other op embed statically — the chain still
fuses, each value just keys its own program.  A program + input signature whose fused trace fails
(an op that concretizes a lifted value, or a genuine user error)
falls back to per-op eager execution inside the same engine op —
later well-shaped uses of the same structure still fuse; user errors
surface with their original eager-path message, deferred to the next
sync point.  Error attribution is CHAIN-granular, like the
reference's bulk-exec segments: the flush is one engine op, so its
failure poisons every output of that chain, including outputs of
earlier ops that would have succeeded had each run as its own eager
dispatch (tests pin this contract).  Similarly, every chain output is
materialized by the fused executable today — dead intermediates in a
rebinding loop are not pruned — so lazy mode wins dispatch count and
wall clock, not peak memory.

The flush itself is ONE dependency-engine op carrying the union of the
chain's read/write vars, so ThreadedEnginePerDevice ordering and the
SanitizerEngine's declared-access contract both hold: external inputs
are read via ``_raw()`` under declared read vars, chain outputs are
written under declared write vars.

ON by default; ``MXTPU_LAZY=0`` disables (config-registered).
Telemetry namespace ``lazy``: ``ops_deferred``, ``ops_bypassed``,
``flushes.{sync,cap}`` (+``flushes.fallback`` marking fused→eager
downgrades), ``chain_length`` histogram, ``fusion_cache_hits`` /
``fusion_cache_misses``.  The profiler shows a ``lazy_flush(n)`` span
per flush next to the existing dispatch lanes (docs/perf.md,
docs/observability.md).
"""
from __future__ import annotations

import threading
import time

import numpy as _np

import jax
import jax.numpy as jnp

from . import engine
from .ops.registry import get_op
from . import locks

__all__ = ["enabled", "set_enabled", "max_ops", "set_max_ops", "record",
           "materialize", "flush_for_array", "flush_all", "pending_ops",
           "reset_cache", "cache_stats"]


def _env_int(name, fallback):
    from . import config

    try:
        return int(config.get(name))
    except (ValueError, TypeError):
        return fallback


_ENABLED = bool(_env_int("MXTPU_LAZY", 1))
_MAX_OPS = max(1, _env_int("MXTPU_LAZY_MAX_OPS", 64))

_LOCK = locks.rlock("lazy.graphs")      # guards _GRAPHS + per-graph state
_GRAPHS = {}                   # (device_typeid, device_id) -> _Graph
_PENDING = 0                   # total deferred nodes (lock-free fast check)

_CACHE_LOCK = locks.lock("lazy.cache")
_FUSION_CACHE = {}             # program -> jitted runner
_SEEN_KEYS = set()             # (program, input sig): telemetry hit/miss
_SEEN_KEYS_CAP = 65536         # telemetry-only; cleared when full
# programs retained before the cache is dropped wholesale: a server-style
# workload whose chain structure varies per iteration (e.g. a Python-int
# attr embedding a new value in the fingerprint) must not accumulate
# jitted runners forever; a rare re-trace beats unbounded growth
_FUSION_CACHE_CAP = 1024
# (program, input sig) pairs whose fused trace failed: replay those
# eagerly.  Keyed WITH the input signature — a shape-mismatch user
# error on one call must not condemn every later well-shaped use of
# the same program structure to un-jitted replay
_EAGER_KEYS = set()
_EAGER_KEYS_CAP = 4096

# kwargs value types a deferred node can carry: lifted (floats, for
# ops declaring lift_floats) or embedded statically in the program
# fingerprint.  Anything else — arrays, NDArrays, arbitrary objects —
# bypasses to the eager path.  numpy scalars are simple: they embed
# (and _freeze normalizes them so np.float32(0.5) and 0.5 fingerprint
# identically).
_SIMPLE_TYPES = (bool, int, float, str, bytes, type(None),
                 _np.bool_, _np.integer, _np.floating)


def enabled():
    """Is lazy deferral active?  ``MXTPU_LAZY=0`` sets the import-time
    default; :func:`set_enabled` toggles at runtime."""
    return _ENABLED


def set_enabled(flag):
    """Toggle deferral; returns the previous state.  Disabling flushes
    every pending chain first so no recorded node is stranded."""
    global _ENABLED
    prev = _ENABLED
    if not flag:
        flush_all("sync")
    _ENABLED = bool(flag)
    return prev


def max_ops():
    return _MAX_OPS


def set_max_ops(n):
    """Set the cap-flush threshold; returns the previous value (tests)."""
    global _MAX_OPS
    prev = _MAX_OPS
    _MAX_OPS = max(1, int(n))
    return prev


def pending_ops():
    """Deferred-but-unflushed node count across all contexts."""
    return _PENDING


def reset_cache():
    """Drop the fusion cache (tests measuring compile behavior)."""
    with _CACHE_LOCK:
        _release_footprints()
        _FUSION_CACHE.clear()
        _SEEN_KEYS.clear()
        _EAGER_KEYS.clear()


def _release_footprints():
    """Dropped runners must leave the ProgramFootprint table (the
    memory plane's census-drift contract) — called under _CACHE_LOCK
    wherever the fusion cache is cleared."""
    for runner in _FUSION_CACHE.values():
        release = getattr(runner, "release", None)
        if release is not None:
            release()


def cache_stats():
    """(cached_programs, seen_structural_keys) sizes."""
    with _CACHE_LOCK:
        return len(_FUSION_CACHE), len(_SEEN_KEYS)


class _Node:
    """One deferred op: program-order position in its graph plus the
    wiring needed to rebuild the call at flush time.  ``aval`` caches
    the eval_shape-derived output ShapeDtypeStruct so metadata reads
    (.shape/.dtype/len/repr) never flush the chain."""

    __slots__ = ("op", "argspec", "static_kw", "lifted", "scalars",
                 "out", "graph", "index", "aval")


class _Graph:
    """Pending expression graph for one context."""

    __slots__ = ("key", "nodes", "inputs", "input_ids", "guard_ids")

    def __init__(self, key):
        self.key = key
        self.nodes = []       # _Node, program order
        self.inputs = []      # external operands: NDArray | jax.Array
        self.input_ids = {}   # id(operand) -> index in inputs
        # ids of the BASE arrays backing every NDArray input (views
        # resolve to their parent chunk): a mutation of any of these
        # must flush this graph first (see flush_for_array)
        self.guard_ids = set()


def _simple(v):
    if isinstance(v, _SIMPLE_TYPES):
        return True
    if isinstance(v, (tuple, list)):
        return all(_simple(x) for x in v)
    return False


def _freeze(v):
    """Canonical hashable form of a simple kwargs value: numpy scalars
    normalize to builtins so e.g. np.float32(0.5) and 0.5 share a
    fingerprint (the kernel still receives the original value)."""
    if isinstance(v, (tuple, list)):
        return tuple(_freeze(x) for x in v)
    if isinstance(v, _np.bool_):
        return bool(v)
    if isinstance(v, _np.integer):
        return int(v)
    if isinstance(v, _np.floating):
        return float(v)
    return v


def record(op, args, kwargs, ctx):
    """Defer one engine-dispatchable op: append a node to ``ctx``'s
    pending graph and return the pending output NDArray — or None when
    the op is not deferrable (caller falls back to the eager engine
    dispatch).  Non-NDArray operands are snapshotted now, exactly like
    the eager path snapshots them."""
    from . import telemetry
    from .ndarray import NDArray, _snapshot

    if not any(isinstance(a, NDArray) for a in args):
        # creation-style call with no tensor operand (e.g. _arange):
        # value-dependent shapes cannot trace — leave it eager
        if telemetry.enabled():
            telemetry.inc("lazy.ops_bypassed")
        return None
    lifted, static_kw = [], {}
    for k, v in kwargs.items():
        # float attrs lift ONLY for ops whose kernels declared
        # themselves tracer-safe (Op.lift_floats — the scalar family):
        # anything else still calls float()/int() on the attr and a
        # tracer there would concretize-error the fused trace,
        # downgrading the whole chain to un-jitted replay.  Non-lifted
        # floats embed statically — still fused, value-keyed program.
        # isinstance covers np.float64 AND np.float32 (any np.floating):
        # all spellings lift to one float()-normalized traced operand.
        if op.lift_floats and isinstance(v, (float, _np.floating)):
            lifted.append(k)
        elif _simple(v):
            static_kw[k] = v
        else:
            if telemetry.enabled():
                telemetry.inc("lazy.ops_bypassed")
            return None
    lifted = tuple(sorted(lifted))
    key = (ctx.device_typeid, ctx.device_id)
    with _LOCK:
        # pre-pass: materialize graphs this op cannot reference as node
        # wiring — a view over a pending chunk, or a chain pending on
        # another context — BEFORE binding anything to the current
        # graph.  A nested flush can detach the CURRENT graph too (it
        # shares an external input whose _engine_var guard fires), so
        # binding indices taken before these flushes would dangle.
        for a in args:
            if not isinstance(a, NDArray):
                continue
            base = a
            while base._parent is not None:
                base = base._parent
            node = base._lazy
            if node is not None \
                    and not (a is base and node.graph is _GRAPHS.get(key)):
                _flush_locked(node.graph, "sync")
        # every surviving pending operand now lives in THE live graph
        # for this context (a flush clears _lazy on all its outputs);
        # no _flush_locked runs below, so the bindings cannot go stale
        graph = _GRAPHS.get(key)
        if graph is None:
            graph = _GRAPHS[key] = _Graph(key)
        argspec = []
        for a in args:
            if isinstance(a, NDArray):
                base = a
                while base._parent is not None:
                    base = base._parent
                node = base._lazy
                if node is not None and a is base:
                    argspec.append(("n", node.index))
                    continue
                idx = graph.input_ids.get(id(a))
                if idx is None:
                    idx = len(graph.inputs)
                    graph.input_ids[id(a)] = idx
                    graph.inputs.append(a)
                    graph.guard_ids.add(id(base))
                argspec.append(("i", idx))
            else:
                # snapshot NOW, under the eager path's shared rule
                val = _snapshot(a)
                idx = len(graph.inputs)
                graph.inputs.append(val)
                argspec.append(("i", idx))
        out = NDArray(None, ctx)
        node = _Node()
        node.op = op
        node.argspec = tuple(argspec)
        node.static_kw = static_kw
        node.lifted = lifted
        # normalized to builtin float: a lifted np.float64 must trace
        # exactly like a python float or the executable would not be
        # shared across the two spellings
        node.scalars = tuple(float(kwargs[k]) for k in lifted)
        node.out = out
        node.graph = graph
        node.index = len(graph.nodes)
        node.aval = None
        graph.nodes.append(node)
        out._lazy = node
        global _PENDING
        _PENDING += 1
        if telemetry.enabled():
            telemetry.inc("lazy.ops_deferred")
        if len(graph.nodes) >= _MAX_OPS:
            _flush_locked(graph, "cap")
        return out


def aval_for(nd):
    """Shape/dtype of a PENDING array's future value WITHOUT flushing —
    metadata reads (.shape/.dtype/.size/len()/repr()) must not chop a
    fused chain the way a payload read does.  Walks the producing
    graph's prefix under ``jax.eval_shape`` (host-only abstract
    tracing), caching per-node avals.  Returns None when the shape is
    unknowable without a wait (an input whose payload is still being
    produced by an eager engine op, a view input, or an op that fails
    abstract evaluation) — the caller then falls back to the flushing
    payload read."""
    if nd._lazy is None:
        return None
    with _LOCK:
        node = nd._lazy
        if node is None:
            return None
        if node.aval is not None:
            return node.aval
        from .ndarray import NDArray

        graph = node.graph
        in_avals = []
        for a in graph.inputs:
            if isinstance(a, NDArray):
                if a._parent is not None or a._data is None:
                    return None  # view, or payload not yet materialized
                in_avals.append(
                    jax.ShapeDtypeStruct(a._data.shape, a._data.dtype))
            else:
                in_avals.append(jax.ShapeDtypeStruct(
                    getattr(a, "shape", ()), getattr(a, "dtype", None)
                    or jnp.result_type(a)))
        env = []
        try:
            for gnode in graph.nodes[: node.index + 1]:
                if gnode.aval is not None:
                    env.append(gnode.aval)
                    continue
                call_avals = [env[i] if kind == "n" else in_avals[i]
                              for kind, i in gnode.argspec]
                kw = dict(gnode.static_kw)
                for k, s in zip(gnode.lifted, gnode.scalars):
                    kw[k] = s

                def _call(*xs, _f=gnode.op.fn, _kw=kw):
                    return _f(*xs, **_kw)

                gnode.aval = jax.eval_shape(_call, *call_avals)
                env.append(gnode.aval)
        except Exception:
            return None
        return node.aval


def materialize(nd):
    """Flush the pending graph that produces ``nd`` (no-op when ``nd``
    is not pending).  Called from the NDArray read sync points; the
    caller's normal engine wait then blocks on the pushed flush op."""
    if nd._lazy is None:
        return
    with _LOCK:
        node = nd._lazy
        if node is not None:
            _flush_locked(node.graph, "sync")


def flush_for_array(nd):
    """Flush every pending graph that ``nd`` participates in — as a
    chain output OR as an external input (directly or through a view).
    Called when the chunk enters the engine-visible world (an eager
    push declares it via ``_engine_var``) or is about to be mutated
    (``_set_data``): the fused chain must be pushed first so engine
    tokens order it against the foreign access."""
    if not _PENDING:
        return
    with _LOCK:
        node = nd._lazy
        if node is not None:
            _flush_locked(node.graph, "sync")
        nid = id(nd)
        for graph in list(_GRAPHS.values()):
            if nid in graph.guard_ids:
                _flush_locked(graph, "sync")


def flush_all(reason="sync"):
    """Flush every pending graph (waitall, autograd boundaries,
    disable)."""
    if not _PENDING:
        return
    with _LOCK:
        for graph in list(_GRAPHS.values()):
            _flush_locked(graph, reason)


def _flush_locked(graph, reason):
    """Push one graph as ONE engine op.  Caller holds _LOCK.  The graph
    is detached before any var is touched, so re-entrant flushes
    triggered by ``_engine_var`` below terminate — and a graph that is
    no longer the registered one for its key has already been flushed
    by such a nested call (flush_all/flush_for_array iterate snapshot
    lists), so flushing it again must be a no-op."""
    global _PENDING
    if _GRAPHS.get(graph.key) is not graph:
        return
    nodes = graph.nodes
    if not nodes:
        _GRAPHS.pop(graph.key, None)
        return
    _GRAPHS.pop(graph.key, None)
    _PENDING -= len(nodes)
    for node in nodes:
        node.out._lazy = None
    inputs = graph.inputs
    program = tuple(
        (node.op.name, node.argspec,
         tuple(sorted((k, _freeze(v)) for k, v in node.static_kw.items())),
         node.lifted)
        for node in nodes)
    scalars = [s for node in nodes for s in node.scalars]
    n = len(nodes)

    from . import telemetry
    from .ndarray import NDArray

    if telemetry.enabled():
        telemetry.inc("lazy.flushes.%s" % reason)
        telemetry.observe("lazy.chain_length", n,
                          buckets=telemetry.COUNT_BUCKETS)
    read_vars = [a._engine_var() for a in inputs if isinstance(a, NDArray)]
    write_vars = [node.out._engine_var() for node in nodes]

    def _run(_nodes=nodes, _inputs=inputs, _program=program,
             _scalars=scalars, _n=n):
        from . import profiler, telemetry

        prof = profiler.spans_active()
        t0 = time.time() if prof else 0.0
        if telemetry.enabled():
            telemetry.inc("ndarray.imperative_dispatches")
        vals = [a._raw() if isinstance(a, NDArray) else a for a in _inputs]
        outs = _execute(_program, vals, _scalars)
        for node, val in zip(_nodes, outs):
            node.out._set_data(val)
        if prof:
            profiler.record_span("lazy_flush(%d)" % _n, int(t0 * 1e6),
                                 int((time.time() - t0) * 1e6), cat="lazy")

    engine.push(_run, read_vars=read_vars, write_vars=write_vars,
                name="lazy_flush(%d)" % n)


# ----------------------------------------------------------------------
# fused execution + the fusion cache
# ----------------------------------------------------------------------

def _interpret(program, ops, vals, scalars):
    """THE program interpreter — jitted (fused path) and un-jitted
    (fallback) execution both run this one function, so the two paths
    cannot diverge."""
    env = []
    si = 0
    for (name, argspec, static_kw, lifted), op in zip(program, ops):
        call_args = [env[i] if kind == "n" else vals[i]
                     for kind, i in argspec]
        kw = dict(static_kw)
        for k in lifted:
            kw[k] = scalars[si]
            si += 1
        env.append(op.fn(*call_args, **kw))
    return tuple(env)


def _make_runner(program):
    """One jitted interpreter per program structure.  ``vals`` (external
    operands) and ``scalars`` (lifted float attrs) are traced pytree
    leaves, so jax.jit's own signature cache handles new input shapes
    and every scalar VALUE reuses one executable."""
    ops = [get_op(name) for name, _, _, _ in program]
    from .obs import memory

    # through the memory plane (obs/memory.py): the fused program's
    # compiled footprint joins the ProgramFootprint table like the
    # executor's executables, and an allocation failure here writes
    # the OOM postmortem before the eager downgrade replays
    return memory.program(
        lambda vals, scalars: _interpret(program, ops, vals, scalars),
        site="lazy.fusion", key="lazy:%08x" % (hash(program) & 0xffffffff))


def _run_eager(program, vals, scalars):
    """Per-op fallback used when the fused trace fails: same wiring, no
    jit — a genuine user error (shape mismatch, bad dtype) re-raises
    here with the op's own message and defers like any engine error."""
    ops = [get_op(name) for name, _, _, _ in program]
    return _interpret(program, ops, vals, scalars)


def _sig_of(vals):
    """Input-signature half of a fusion-cache key: shapes + dtypes of
    the resolved external operands (mirrors jit's signature cache)."""
    return tuple((tuple(getattr(v, "shape", ())),
                  str(getattr(v, "dtype", type(v).__name__)))
                 for v in vals)


def _execute(program, vals, scalars):
    """Run one flushed program over resolved input values (engine-op
    context).  Fusion-cache lookups are structural: program fingerprint
    + input shapes/dtypes."""
    from . import telemetry

    key = (program, _sig_of(vals))
    hit = False
    with _CACHE_LOCK:
        eager = key in _EAGER_KEYS
        runner = None
        if not eager:
            runner = _FUSION_CACHE.get(program)
            if runner is None:
                if len(_FUSION_CACHE) >= _FUSION_CACHE_CAP:
                    _release_footprints()
                    _FUSION_CACHE.clear()
                    # hit/miss telemetry must track the REAL cache: a
                    # re-trace after this clear is a miss, not a hit
                    _SEEN_KEYS.clear()
                runner = _FUSION_CACHE[program] = _make_runner(program)
                # retrace monitor (the runtime half of mxlint W104):
                # every NEW program fingerprint past the first is
                # signature churn at this cache site — a float attr
                # embedding per-value (not lifted to an operand) shows
                # up here as trace.retraces.lazy.fusion climbing with
                # MXTPU_RETRACE_WARN naming the fingerprint delta
                if telemetry.enabled():
                    telemetry.note_retrace("lazy.fusion", program)
            if telemetry.enabled():
                # telemetry-only structure: bound it (a burst of
                # spurious misses after a clear beats unbounded growth
                # in a long-running process with varying input shapes)
                if len(_SEEN_KEYS) >= _SEEN_KEYS_CAP:
                    _SEEN_KEYS.clear()
                hit = key in _SEEN_KEYS
                _SEEN_KEYS.add(key)
    if eager:
        # every eager-replay flush counts, so a workload stuck on the
        # fallback path stays visible in the telemetry
        if telemetry.enabled():
            telemetry.inc("lazy.flushes.fallback")
        return _run_eager(program, vals, scalars)
    if telemetry.enabled():
        telemetry.inc("lazy.fusion_cache_hits" if hit
                      else "lazy.fusion_cache_misses")
    try:
        return runner(vals, scalars)
    except Exception:
        # the fused trace failed — an op concretized a lifted scalar, or
        # this input signature carries a real user error.  Downgrade the
        # (program, signature) pair to eager-per-op and let the replay
        # produce the value or the true error.
        with _CACHE_LOCK:
            if len(_EAGER_KEYS) >= _EAGER_KEYS_CAP:
                _EAGER_KEYS.clear()
            _EAGER_KEYS.add(key)
        if telemetry.enabled():
            telemetry.inc("lazy.flushes.fallback")
        return _run_eager(program, vals, scalars)
