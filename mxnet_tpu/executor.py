"""Executor — binds a Symbol to devices and runs it.

TPU-native equivalent of the reference GraphExecutor
(reference src/executor/graph_executor.cc, include/mxnet/executor.h).

Architecture mapping (SURVEY.md §7 phase 3):
  * The reference builds the full fwd+bwd graph, runs NNVM passes
    (PlanMemory, AttachOpExecs, DetectInplaceAddTo), then replays cached
    engine ops per node with bulk "segments".  Here the ENTIRE graph is
    lowered into ONE jitted XLA executable per (is_train, backward) mode —
    bulk-exec taken to its limit; XLA is the memory planner and fuser.
  * Gradient pass ≙ `jax.vjp` over the interpreted graph.  Loss ops carry
    `custom_vjp` so `backward()` without head gradients matches reference
    semantics (graph_executor.cc:102-175 AggregateGradient: multiple
    consumers of one variable sum naturally under AD).
  * grad_req 'write'/'add'/'null' (reference OpReqType) applied on the
    host side after the fused call; 'add' accumulates into grad arrays.
  * Multi-device: pass `mesh` — inputs are sharded over the mesh's 'data'
    axis, params replicated; XLA SPMD inserts the gradient all-reduce that
    the reference got from KVStore device-mode P2P reduction
    (src/kvstore/comm.h:204-355).  This is the TPU-idiomatic data path.
"""
from __future__ import annotations

import functools as _functools

import jax
import jax.numpy as jnp
import numpy as _np

from .base import MXNetError
from .context import Context, current_context
from . import ndarray as nd
from .ndarray import NDArray
from .symbol import _topo_order

__all__ = ["Executor"]

# monotonic retrace-monitor scope tokens: each binding's jit caches are
# judged independently (telemetry.note_retrace scope=), and a counter —
# unlike id(self) — can never alias a garbage-collected executor's
# identity onto a fresh one
import itertools as _itertools

_RETRACE_SCOPE_SEQ = _itertools.count()


def _run_graph(entries, order, arg_names, aux_names, arg_vals, aux_vals, is_train, rng,
               boundary=None, cast=None, mesh=None):
    """Interpret the graph as pure JAX ops (traced once under jit).

    `rng` is a jax PRNG key (or None); callers inside jit build it from a
    host seed so no device-side key chain is maintained between steps.
    `boundary` is (replicated NamedSharding, {id(node): ctx_group}) — when
    an edge crosses two ctx_groups a replicated sharding constraint is
    applied, the SPMD analog of the reference's _CrossDeviceCopy insertion
    at PlaceDevice boundaries (reference src/executor/graph_executor.cc:347-360).
    `cast` is (compute_dtype, keep_fp32_names): float args are cast to the
    compute dtype ON ENTRY to the executable (labels and other names in the
    keep set stay fp32) and outputs are cast back on exit.  Aux states stay
    in their STORAGE dtype end-to-end — ops cast them at point of use — so
    fp32 running statistics never round-trip through bf16.
    Because the cast sits inside the traced function, `jax.vjp` returns
    fp32 gradients for the fp32 master parameters automatically — the
    multi-precision training recipe (reference python/mxnet/optimizer.py
    multi-precision SGD) with XLA doing conv/matmul in bf16 on the MXU.
    Returns (outputs tuple, aux_updates tuple ordered like aux_names).
    """

    def _to_compute(name, v):
        if cast is None:
            return v
        cdt, keep = cast
        if name in keep or not jnp.issubdtype(v.dtype, jnp.floating):
            return v
        return v.astype(cdt)

    out_dtypes = {n: v.dtype for n, v in zip(aux_names, aux_vals)}
    arg_env = {n: _to_compute(n, v) for n, v in zip(arg_names, arg_vals)}
    # aux states (BatchNorm running stats) are NEVER cast to the compute
    # dtype: re-quantizing carried fp32 statistics through bf16 every step
    # degrades them — the reference multi-precision recipe (cuDNN BN) keeps
    # statistics fp32 under fp16 compute; ops cast at the point of use
    aux_env = dict(zip(aux_names, aux_vals))
    env = {}
    aux_updates = dict(aux_env)
    for i, node in enumerate(order):
        if node.op is None:
            if node.is_aux:
                env[id(node)] = (aux_env[node.name],)
            else:
                env[id(node)] = (arg_env[node.name],)
            continue
        op = node.op
        ins = [env[id(src)][idx] for src, idx in node.inputs]
        if boundary is not None:
            repl, groups = boundary
            my_group = groups.get(id(node))
            ins = [
                jax.lax.with_sharding_constraint(v, repl)
                if groups.get(id(src)) is not None and groups.get(id(src)) != my_group
                else v
                for v, (src, idx) in zip(ins, node.inputs)
            ]
        ins += [aux_updates[a.name] for a in node.aux_vars]
        kwargs = {k: v for k, v in node.attrs.items() if not k.startswith("__") and k != "ctx_group"}
        if op.need_is_train:
            kwargs["is_train"] = is_train
        if op.need_rng:
            kwargs["rng"] = jax.random.fold_in(rng, i) if rng is not None else None
        if getattr(op, "need_mesh", False):
            kwargs["mesh"] = mesh
        # named_scope stamps the node name into HLO op metadata (tf_op),
        # so XLA device traces attribute time per GRAPH NODE even though
        # the whole step is one fused executable — the analog of the
        # reference profiler's per-op SetOprStart/End rows
        # (src/engine/profiler.cc:134-190).  Trace-time only; free at run.
        # a node made under ``AttrScope(__scope__="mx:...")`` runs under
        # that scope too: a stretch of plain nodes that is ONE thing to a
        # reader of the trace (the draft module's block)
        scope = node.attrs.get("__scope__")
        with jax.named_scope(node.name):
            if scope is None:
                res = op.fn(*ins, **kwargs)
            else:
                with jax.named_scope(scope):
                    res = op.fn(*ins, **kwargs)
        if not isinstance(res, tuple):
            res = (res,)
        if op.num_aux_out:
            main = res[: len(res) - op.num_aux_out]
            for a, upd in zip(node.aux_vars, res[len(res) - op.num_aux_out:]):
                aux_updates[a.name] = upd
            res = main
        env[id(node)] = res
    outputs = tuple(env[id(nd)][ix] for nd, ix in entries)
    aux_out = tuple(aux_updates[n] for n in aux_names)
    if cast is not None:
        outputs = tuple(
            o.astype(jnp.float32) if jnp.issubdtype(o.dtype, jnp.floating) else o
            for o in outputs)
        aux_out = tuple(a.astype(out_dtypes[n]) for n, a in zip(aux_names, aux_out))
    return outputs, aux_out


# remat policy for memory mirroring: MXU results (matmul/conv) are the
# expensive-to-recompute outputs — save those, recompute everything else
# (BN affines, activations, adds) in the backward pass
def _MIRROR_POLICY(prim, *_, **__):
    return prim.name in ("dot_general", "conv_general_dilated")


# op → input slots whose values are indices, not magnitudes
_INDEX_ARG_SLOTS = {
    "Embedding": (0,), "take": (1,), "batch_take": (1,), "one_hot": (0,),
    "gather_nd": (1,), "scatter_nd": (1,), "pick": (1,),
    "SequenceLast": (1,), "SequenceMask": (1,), "SequenceReverse": (1,),
}


def _index_like_args(symbol):
    """Variable args whose values reach an index slot of any consumer op,
    traced TRANSITIVELY through intermediate ops (an index routed through
    e.g. `slice` before `take` must not round through bf16 either).  The
    closure over-approximates — a variable feeding both an index path and a
    magnitude path is kept fp32, trading a little speed for correctness."""
    keep = set()
    pending = []  # nodes whose producing subgraph feeds an index slot
    for node in _topo_order(symbol._entries):
        if node.op is None:
            continue
        slots = _INDEX_ARG_SLOTS.get(node.op.name)
        if not slots:
            continue
        for i in slots:
            if i < len(node.inputs):
                pending.append(node.inputs[i][0])
    seen = set()
    while pending:
        src = pending.pop()
        if id(src) in seen:
            continue
        seen.add(id(src))
        if src.op is None:
            if not src.is_aux:
                keep.add(src.name)
        else:
            pending.extend(s for s, _ in src.inputs)
    return keep


def _auto_spec(shape, mesh, axis="model"):
    """Pick a PartitionSpec sharding the largest dim divisible by the model
    axis (params of a ctx_group are sharded, not placed — the SPMD
    reinterpretation of reference PlaceDevice)."""
    from .parallel.mesh import P

    if axis not in mesh.axis_names:
        return P()
    m = mesh.shape[axis]
    dims = sorted(range(len(shape)), key=lambda d: -shape[d])
    for d in dims:
        if shape[d] % m == 0 and shape[d] >= m:
            spec = [None] * len(shape)
            spec[d] = axis
            return P(*spec)
    return P()


@_functools.partial(jax.jit, static_argnums=(1, 2))
def _split_rows(x, starts, rows):
    """`rows` rows of `x` from each row of `starts`, on the device(s) `x`
    lives on: the pieces of one step's device batch
    (Executor.place_step_input)."""
    return tuple(jax.lax.slice_in_dim(x, s, s + rows) for s in starts)


@jax.jit
def _stack_steps(*steps):
    """K steps' pieces on one device as one (K, ...) array there
    (Executor.stack_block_input).  jit's own cache keys it by K, shape
    and dtype; a committed input decides the device."""
    return jnp.stack(steps)


def _host_step_fence(device_platform, host_platform="cpu"):
    """What the staging thread waits for before the source of a step
    that came from host memory may refill its buffer
    (Executor.stack_block_input), from the two platforms alone:

    * "pieces" where the devices are of another platform than host
      memory (a chip): the buffer is needed until each device's rows
      have ARRIVED.  Those copies ride the host links beside whatever
      the devices compute; the stack is a program, queued in the
      devices' compute stream behind every training block already
      dispatched, and a thread that waited for it would stage one
      block for every block the devices run — no run-ahead (PERF.md,
      PR 42).
    * "stack" where they are one platform (the CPU backend, which may
      ALIAS a host buffer: the pieces can BE the source's buffer until
      a program has copied them out)."""
    return "stack" if device_platform == host_platform else "pieces"


def _resolve_group2ctx(symbol, group2ctx, mesh):
    """Map ctx_group annotations to mesh shardings.

    Reference semantics (src/executor/graph_executor.cc:347-360): each
    ctx_group is PLACED on the device from `group2ctx` and _CrossDeviceCopy
    nodes move activations between groups.  Whole-array placement is an
    MPMD pattern XLA SPMD does not express (and an anti-pattern on TPU);
    the TPU-first translation is: build a 'model' mesh over the union of
    group devices, SHARD each group's parameters across it, and put a
    sharding constraint at group boundaries (the copy analog).  Memory per
    device drops the way placement would drop it; numerics are identical.

    Returns (mesh, param_shardings, node_groups); degrades to
    (mesh, {}, None) with a warning when <2 distinct devices are given.
    """
    import logging as _logging

    from .symbol import _topo_order as _topo

    order = _topo(symbol._entries)
    node_groups = {}
    for node in order:
        g = node.attrs.get("ctx_group") if node.attrs else None
        if g is not None:
            node_groups[id(node)] = g
    if not node_groups:
        _logging.warning("group2ctx given but symbol has no ctx_group annotations")
        return mesh, {}, None
    # param variables inherit the group of their first consumer op
    param_groups = {}
    for node in order:
        if node.op is None:
            continue
        g = node_groups.get(id(node))
        if g is None:
            continue
        for src, _ in node.inputs:
            if src.op is None and not src.is_aux and src.name not in param_groups:
                param_groups[src.name] = g
    devices = []
    for g, ctx in group2ctx.items():
        d = ctx.jax_device()
        if d not in devices:
            devices.append(d)
    if len(devices) < 2:
        _logging.warning(
            "group2ctx maps all groups onto one physical device; "
            "running without model sharding")
        return mesh, {}, None
    if mesh is not None and "model" in mesh.axis_names:
        model_mesh = mesh
    elif mesh is not None:
        # an existing mesh without a 'model' axis means the caller already
        # chose a layout (e.g. DP over contexts); don't silently replace it
        _logging.warning(
            "group2ctx ignored: executor mesh %s has no 'model' axis — pass "
            "a mesh like make_mesh({'data': -1, 'model': k}) to combine "
            "data and model parallelism" % (mesh.axis_names,))
        return mesh, {}, None
    else:
        import numpy as _np

        from .parallel.mesh import Mesh

        model_mesh = Mesh(_np.array(devices), ("model",))
    shardings = {n: "auto" for n in param_groups}
    return model_mesh, shardings, node_groups


class Executor:
    """Bound computation graph (parity: python/mxnet/executor.py Executor)."""

    def __init__(self, symbol, ctx, arg_dict, grad_dict, grad_req, aux_dict, mesh=None,
                 param_shardings=None, node_groups=None, compute_dtype=None,
                 fp32_names=(), mirror=None):
        self._symbol = symbol
        if mirror is None:
            from . import config

            mirror = bool(config.get("MXNET_BACKWARD_DO_MIRROR"))
        self._mirror = bool(mirror)
        self._compute_dtype = jnp.dtype(compute_dtype) if compute_dtype else None
        fp32 = set(fp32_names)
        if self._compute_dtype is not None:
            # args consumed as INDICES (token ids, gather positions) must
            # not round through bf16 — ids > 256 are not bf16-exact
            fp32 |= _index_like_args(symbol)
        self._fp32_names = frozenset(fp32)
        self._ctx = ctx
        self.arg_dict = arg_dict
        self.grad_dict = grad_dict
        self.aux_dict = aux_dict
        self._grad_req = grad_req
        self._mesh = mesh
        self._arg_names = symbol.list_arguments()
        self._aux_names = symbol.list_auxiliary_states()
        self._entries = symbol._entries
        self._order = _topo_order(self._entries)
        self._outputs_cache = None
        self._last_is_train = False
        self._monitor_callback = None
        from .ops.random_ops import HOST_RNG

        self._step_seed = int(HOST_RNG.randint(0, 2 ** 31))
        self._aux_applied = False
        self._jit_fwd = {}
        self._jit_bwd = {}
        # every compile-cache entry this executor built via the memory
        # plane (obs/memory.py Program) — released on predictor
        # eviction/close so the ProgramFootprint table cannot drift
        # upward across a long-lived serving process
        self._mem_programs = []
        # training-dispatch telemetry: how many device round-trips the
        # training loop has issued (fused single steps, K-step blocks,
        # and materialized fwd+bwd calls each count 1):
        # ceil(steps / steps_per_dispatch), pinned in test_fused_dispatch.py
        self._train_dispatches = 0
        # >0 after a K-step block dispatch: outputs are stacked (K, ...)
        # and update_metric consumes the whole block; any plain forward
        # resets it
        self._last_block_count = 0
        self._data_sharding = None
        self._repl_sharding = None
        self._param_shardings = dict(param_shardings or {})
        self._node_groups = node_groups
        # a mesh-less executor computes on its context's device.  JAX's
        # default device needs no placement (uncommitted arrays already
        # land there); any other device is recorded here and every bound
        # array is moved to it at dispatch (_resident).  Resolving the
        # context at bind time also makes a context that names no device
        # fail HERE, not at the first forward.
        self._device = None
        if mesh is None:
            from .context import default_device

            dev = self._first_ctx.jax_device()
            if dev != default_device():
                self._device = dev
        # the platform this executor computes on: an input whose payload
        # lies on another one (host memory beside an accelerator,
        # nd.host_array) is placed, not passed on (forward)
        self._platform = (dev if mesh is None
                          else mesh.devices.flat[0]).platform
        if mesh is not None:
            from .parallel.mesh import NamedSharding, P, batch_pspec

            # batch_pspec covers both a flat 'data' axis and the
            # hierarchical 'data_dcn' x 'data_ici' split of a multi-host
            # mesh (parallel/multihost.global_mesh hierarchical=True)
            self._data_sharding = NamedSharding(mesh, batch_pspec(mesh))
            self._repl_sharding = NamedSharding(mesh, P())
            # ops may declare per-input mesh axes (Op.input_axes, e.g. MoE
            # experts over 'expert'): shard those params dim-0 AT REST so
            # expert memory scales 1/E across the axis — the EP analog of
            # the reference's per-device expert placement
            for node in self._order:
                if node.op is None or not getattr(node.op, "input_axes", None):
                    continue
                for (src, _), in_name in zip(
                        node.inputs, node.op.list_inputs(node.attrs)):
                    ax = node.op.input_axes.get(in_name)
                    if (ax and ax in mesh.axis_names and src.op is None
                            and not src.is_aux
                            and src.name not in self._param_shardings):
                        self._param_shardings[src.name] = P(ax)

    # ------------------------------------------------------------------
    # construction (parity: Executor::SimpleBind / Bind)
    # ------------------------------------------------------------------
    @staticmethod
    def simple_bind(symbol, ctx=None, grad_req="write", type_dict=None, mesh=None,
                    shared_exec=None, group2ctx=None, param_shardings=None,
                    compute_dtype=None, fp32_names=(), mirror=None, **kwargs):
        """Allocate all arrays from shapes and bind
        (reference GraphExecutor simple_bind overload, executor.h:76)."""
        ctx = ctx or current_context()
        node_groups = None
        if group2ctx:
            mesh, auto_shardings, node_groups = _resolve_group2ctx(symbol, group2ctx, mesh)
            auto_shardings.update(param_shardings or {})
            param_shardings = auto_shardings
        arg_shapes, out_shapes, aux_shapes = symbol.infer_shape(**kwargs)
        if arg_shapes is None:
            raise MXNetError("simple_bind: cannot infer shapes from %s" % kwargs)
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        type_dict = type_dict or {}
        if type_dict:
            # propagate the given dtypes through the graph so untyped params
            # are allocated in the inferred dtype (reference simple_bind
            # InferType, graph_executor.cc:793-806)
            arg_types, _, _ = symbol.infer_type(**type_dict)
            inferred = dict(zip(arg_names, arg_types))
            type_dict = {n: type_dict.get(n, inferred[n]) for n in arg_names}
        arg_dict, grad_dict = {}, {}
        req_dict = _norm_grad_req(grad_req, arg_names)
        shared = shared_exec.arg_dict if shared_exec is not None else {}
        shared_grad = shared_exec.grad_dict if shared_exec is not None else {}
        for name, shape in zip(arg_names, arg_shapes):
            dtype = jnp.dtype(type_dict.get(name, "float32"))
            if name in shared and tuple(shared[name].shape) == tuple(shape):
                arg_dict[name] = shared[name]
            else:
                arg_dict[name] = NDArray(jnp.zeros(shape, dtype=dtype), ctx)
            if req_dict.get(name, "null") != "null":
                if name in shared_grad and tuple(shared_grad[name].shape) == tuple(shape):
                    grad_dict[name] = shared_grad[name]
                else:
                    grad_dict[name] = NDArray(jnp.zeros(shape, dtype=dtype), ctx)
        shared_aux = shared_exec.aux_dict if shared_exec is not None else {}
        aux_dict = {}
        for name, shape in zip(aux_names, aux_shapes):
            if name in shared_aux and tuple(shared_aux[name].shape) == tuple(shape):
                aux_dict[name] = shared_aux[name]
            else:
                aux_dict[name] = NDArray(jnp.zeros(shape, dtype=jnp.float32), ctx)
        return Executor(symbol, ctx, arg_dict, grad_dict, req_dict, aux_dict, mesh=mesh,
                        param_shardings=param_shardings, node_groups=node_groups,
                        compute_dtype=compute_dtype, fp32_names=fp32_names,
                        mirror=mirror)

    @staticmethod
    def bind(symbol, ctx, args, args_grad=None, grad_req="write", aux_states=None,
             group2ctx=None, shared_exec=None, mesh=None, param_shardings=None,
             compute_dtype=None, fp32_names=(), mirror=None):
        """Bind with user-provided arrays (reference Executor::Bind).

        `group2ctx` maps ctx_group names to Contexts: groups are sharded
        over a 'model' mesh built from those devices (see _resolve_group2ctx
        for the SPMD translation of reference PlaceDevice)."""
        ctx = ctx or current_context()
        node_groups = None
        if group2ctx:
            mesh, auto_shardings, node_groups = _resolve_group2ctx(symbol, group2ctx, mesh)
            auto_shardings.update(param_shardings or {})
            param_shardings = auto_shardings
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        if isinstance(args, dict):
            arg_dict = {n: args[n] for n in arg_names if n in args}
            missing = [n for n in arg_names if n not in args]
            if missing:
                raise MXNetError("bind: missing arguments %s" % missing)
        else:
            if len(args) != len(arg_names):
                raise MXNetError("bind: expected %d args, got %d" % (len(arg_names), len(args)))
            arg_dict = dict(zip(arg_names, args))
        req_dict = _norm_grad_req(grad_req, arg_names)
        if args_grad is None:
            grad_dict = {}
            for n in arg_names:
                if req_dict.get(n, "null") != "null":
                    req_dict[n] = "null"
        elif isinstance(args_grad, dict):
            grad_dict = dict(args_grad)
            for n in arg_names:
                if n not in grad_dict:
                    req_dict[n] = "null"
        else:
            grad_dict = dict(zip(arg_names, args_grad))
        if aux_states is None:
            aux_dict = {n: NDArray(jnp.zeros(()), ctx) for n in aux_names} if aux_names else {}
            if aux_names:
                # infer aux shapes from args
                shapes = {n: arg_dict[n].shape for n in arg_names}
                _, _, aux_shapes = symbol.infer_shape(**shapes)
                aux_dict = {
                    n: NDArray(jnp.zeros(s), ctx) for n, s in zip(aux_names, aux_shapes)
                }
        elif isinstance(aux_states, dict):
            aux_dict = dict(aux_states)
        else:
            aux_dict = dict(zip(aux_names, aux_states))
        return Executor(symbol, ctx, arg_dict, grad_dict, req_dict, aux_dict, mesh=mesh,
                        param_shardings=param_shardings, node_groups=node_groups,
                        compute_dtype=compute_dtype, fp32_names=fp32_names,
                        mirror=mirror)

    # ------------------------------------------------------------------
    # data-path helpers
    # ------------------------------------------------------------------
    @property
    def _data_arg_names(self):
        # args without grads are inputs (data/label); used for sharding decisions
        return [n for n in self._arg_names if self._grad_req.get(n, "null") == "null"]

    def _put(self, v):
        """`v` on self._device (a no-op when it is already committed
        there — jax.device_put's own same-device path costs ~25 us)."""
        dev = self._device
        if isinstance(v, jax.Array) and v.committed and v.devices() == {dev}:
            return v
        return jax.device_put(v, dev)

    def _resident(self, arrays):
        """Payloads of `arrays` on this executor's device.  On a
        non-default device (see __init__) an array found elsewhere is
        moved ONCE and written back into its NDArray — Predictor bucket
        executors share their parameter NDArrays, so the move is paid per
        parameter, not per dispatch or per bucket."""
        if self._device is None:
            return tuple(a.data for a in arrays)
        vals = []
        for a in arrays:
            v = a.data
            p = self._put(v)
            if p is not v and a._parent is None:
                a._set_data(p)
            vals.append(p)
        return tuple(vals)

    def _gather_args(self):
        return self._resident(self.arg_dict[n] for n in self._arg_names)

    def _gather_aux(self):
        return self._resident(self.aux_dict[n] for n in self._aux_names)

    def _place(self, vals):
        """Apply mesh shardings: batch inputs over 'data', params per their
        sharding spec ('model'-axis TP / group2ctx shards) or replicated.
        Mesh-less: `vals` came through _gather_args and are resident."""
        if self._mesh is None:
            return vals
        from .parallel.mesh import global_put

        data_names = set(self._data_arg_names)
        # global_put = device_put that also materializes pjit/GDA-
        # style global arrays when the mesh spans other processes
        return tuple(
            global_put(v, self._arg_sharding(n, v.shape, n in data_names))
            for n, v in zip(self._arg_names, vals))

    def _arg_sharding(self, name, shape, is_input):
        """The mesh sharding of argument `name`: batch inputs over
        'data', params per their spec or replicated."""
        from .parallel.mesh import NamedSharding

        if is_input:
            return self._data_sharding
        if name in self._param_shardings:
            spec = self._param_shardings[name]
            if spec == "auto":
                spec = _auto_spec(shape, self._mesh)
            return NamedSharding(self._mesh, spec)
        return self._repl_sharding

    def _from_host(self, name, v):
        """The host-resident payload `v` of an NDArray (nd.host_array: a
        batch of an iterator over host memory) where this executor
        computes: on its one device, or laid over its mesh as argument
        `name` is sharded, every device's rows sent from the host
        buffer itself.  The H2D of such a batch, counted here."""
        v = _np.asarray(v)  # a view: the CPU backend's buffer is host memory
        self._note_bytes("executor.h2d_bytes", v.nbytes)
        if self._mesh is None:
            return jax.device_put(v, self._first_ctx.jax_device())
        from .parallel.mesh import global_put

        return global_put(v, self._arg_sharding(
            name, v.shape, name in self._data_arg_names))

    def _place_repl(self, vals):
        """Replicate aux/optimizer-state leaves over the mesh.  On a
        multi-process mesh this is REQUIRED: a committed process-local
        array cannot enter a global-mesh executable (the data/param args
        already flow through _place) — global_put materializes the
        pjit-style replicated global array from each host's copy."""
        if self._mesh is None:
            if self._device is None:
                return tuple(vals)
            # optimizer-state leaves (aux came through _gather_aux): a
            # no-op after the first step, whose outputs are committed
            return tuple(self._put(v) for v in vals)
        from .parallel.mesh import global_put

        return tuple(global_put(v, self._repl_sharding) for v in vals)

    def _boundary(self):
        """(replicated sharding, node→group) for cross-group constraints."""
        if self._node_groups and self._mesh is not None:
            return (self._repl_sharding, self._node_groups)
        return None

    def _cast(self):
        """(compute_dtype, keep-fp32 names) for mixed-precision, or None."""
        if self._compute_dtype is None:
            return None
        return (self._compute_dtype, self._fp32_names)

    # ------------------------------------------------------------------
    # forward / backward (parity: MXExecutorForward/Backward)
    # ------------------------------------------------------------------
    def forward(self, is_train=False, **kwargs):
        """Set inputs and (lazily) run forward.

        Training-mode forward DEFERS computation: if `backward()` follows
        (the fit hot path), one fused fwd+bwd executable runs exactly once —
        the analog of the reference's bulk-exec segments
        (graph_executor.cc:1094-1170).  Reading `outputs` before backward
        triggers a forward-only run with the SAME per-step RNG key, so
        dropout masks agree between reported outputs and gradients, and
        aux (BatchNorm moving stats) updates apply exactly once per step.
        """
        for name, value in kwargs.items():
            if name not in self.arg_dict:
                raise MXNetError("Unknown argument %s" % name)
            if isinstance(value, NDArray):
                v = value.data
                if nd.off_platform(v, self._platform):
                    v = self._from_host(name, v)
            else:
                # raw numpy/list input converts (and transfers) here;
                # NDArray inputs paid their H2D at creation (nd.array)
                # or, host-resident ones, just above.
                # Bytes counted AFTER conversion so list inputs (no
                # .nbytes) are measured exactly.
                host = not isinstance(value, jax.Array)
                v = jnp.asarray(value)
                if host:
                    self._note_bytes("executor.h2d_bytes", v.nbytes)
            if tuple(v.shape) != tuple(self.arg_dict[name].shape):
                raise MXNetError(
                    "Shape mismatch for argument %s: bound %s, got %s (use reshape())"
                    % (name, self.arg_dict[name].shape, tuple(v.shape))
                )
            self.arg_dict[name]._set_data(v)
        self._last_is_train = bool(is_train)
        self._last_block_count = 0
        # a fresh forward supersedes any staged-but-undispatched block:
        # without this, update() after a skipped block dispatch would
        # re-run the stale block instead of this batch's deferred step
        self._pending_fused_block = False
        self._staged_block = None
        self._outputs_cache = None
        self._next_seed()
        self._aux_applied = False
        if not is_train:
            self._compute_forward(False)
        return self.outputs if not is_train else None

    # ------------------------------------------------------------------
    # telemetry helpers (each early-returns when the registry is off,
    # so hot paths pay one predicted branch — the enabled() contract)
    # ------------------------------------------------------------------
    def _note_compile_cache(self, hit, site=None, signature=None):
        """One executable-cache lookup: a miss means an XLA (re)compile —
        steady-state training must show hits only (a miss churn here is
        the bucketing-rebind / shape-instability smell).  Misses that
        carry a `site`/`signature` also feed the retrace monitor
        (telemetry.note_retrace, the runtime half of mxlint W104):
        the second DISTINCT signature at one site counts a
        ``trace.retraces`` and, past MXTPU_RETRACE_WARN, logs the
        signature delta."""
        from . import telemetry

        if not telemetry.enabled():
            return
        telemetry.inc("executor.compile_cache_hits" if hit
                      else "executor.compile_cache_misses")
        if not hit and site is not None:
            scope = getattr(self, "_retrace_scope", None)
            if scope is None:
                scope = self._retrace_scope = next(_RETRACE_SCOPE_SEQ)
            telemetry.note_retrace(site, signature, scope=scope)

    def _mem_program(self, fn, site, signature, donate_argnums=()):
        """Build one compile-cache entry through the memory plane
        (obs/memory.py): an AOT-compiling wrapper that harvests XLA's
        compiled memory analysis into the ProgramFootprint table and
        catches RESOURCE_EXHAUSTED for the OOM postmortem.  Drop-in
        for ``jax.jit(fn, donate_argnums=...)`` — tracked per executor
        so eviction can release the footprints."""
        from .obs import memory

        p = memory.program(fn, site=site, key=signature,
                           donate_argnums=donate_argnums)
        self._mem_programs.append(p)
        return p

    def release_footprints(self, evicted=False):
        """Remove this executor's programs from the ProgramFootprint
        table (predict.py signature-cache eviction and Predictor.close
        call this); `evicted=True` additionally ticks the
        ``mem.programs_evicted`` counter — the census-drift satellite
        of the memory plane."""
        from . import telemetry

        programs, self._mem_programs = self._mem_programs, []
        for p in programs:
            p.release()
        if evicted and programs and telemetry.enabled():
            telemetry.inc("mem.programs_evicted", len(programs))

    def _note_bytes(self, name, nbytes):
        from . import telemetry

        if not telemetry.enabled():
            return
        telemetry.inc(name, int(nbytes))

    def flops_per_step(self, is_train=True):
        """Analytic FLOPs of one step of the bound symbol (fwd traced via
        jax.make_jaxpr — pure tracing, no device work; training steps
        count fwd+bwd as 3x forward, the standard accounting).  Cached;
        0.0 when the trace fails.  telemetry's per-step MFU gauge is
        this over measured step time and the device's peak
        (telemetry.PEAK_FLOPS)."""
        cache = getattr(self, "_flops_cache", None)
        if cache is None:
            cache = self._flops_cache = {}
        if is_train not in cache:
            from . import telemetry

            try:
                import numpy as _np

                # the UNJITTED forward closure: tracing it must not seed
                # _jit_fwd, or the first real forward would be counted
                # as a compile-cache hit while XLA still compiles it
                jaxpr = jax.make_jaxpr(self._build_fwd(is_train))(
                    self._gather_args(), self._gather_aux(), _np.uint32(0))
                fwd = telemetry.flops_of_jaxpr(jaxpr)
                cache[is_train] = fwd * (3.0 if is_train else 1.0)
            except Exception:
                cache[is_train] = 0.0
        return cache[is_train]

    def _build_fwd(self, is_train):
        """The raw (unjitted) forward closure — jitted+cached by _fwd_fn;
        flops_per_step traces it directly."""
        entries, order = self._entries, self._order
        an, xn = self._arg_names, self._aux_names
        boundary = self._boundary()
        cast = self._cast()

        mesh = self._mesh

        def f(arg_vals, aux_vals, seed):
            rng = jax.random.key(seed)
            return _run_graph(entries, order, an, xn, arg_vals, aux_vals, is_train,
                              rng, boundary=boundary, cast=cast, mesh=mesh)

        return f

    def _fwd_fn(self, is_train):
        if is_train not in self._jit_fwd:
            self._jit_fwd[is_train] = self._mem_program(
                self._build_fwd(is_train), "executor.forward", is_train)
        return self._jit_fwd[is_train]

    def _next_seed(self):
        # host-side step seed: splitting a device-side key would be one
        # more dispatch per step; the key is derived from this seed
        # INSIDE the jitted executable
        from .ops.random_ops import HOST_RNG

        self._step_seed = int(HOST_RNG.randint(0, 2 ** 31))
        return self._step_seed

    def _compute_forward(self, is_train):
        from . import profiler

        compiled = is_train in self._jit_fwd
        self._note_compile_cache(compiled, site="executor.forward",
                                 signature=is_train)
        fn = self._fwd_fn(is_train)
        args = self._place(self._gather_args())
        import numpy as _np

        with profiler.span("executor.forward", cat="executor",
                           is_train=is_train, compiled=compiled):
            outs, aux_upd = fn(args, self._place_repl(self._gather_aux()),
                               _np.uint32(self._step_seed))
        self._outputs_cache = [NDArray(o, self._first_ctx) for o in outs]
        if is_train and not self._aux_applied:
            self._write_aux(aux_upd)
            self._aux_applied = True
        if self._monitor_callback is not None:
            for name, o in zip(self._symbol.list_outputs(), self._outputs_cache):
                self._monitor_callback(name, o)

    @property
    def _first_ctx(self):
        return self._ctx if isinstance(self._ctx, Context) else self._ctx[0]

    def devices(self):
        """The jax devices this executor computes on."""
        if self._mesh is not None:
            return list(self._mesh.devices.flat)
        return [self._first_ctx.jax_device()]

    def _write_aux(self, aux_upd):
        for n, v in zip(self._aux_names, aux_upd):
            self.aux_dict[n]._set_data(v)

    @property
    def outputs(self):
        if self._outputs_cache is None:
            self._compute_forward(self._last_is_train)
        return self._outputs_cache

    # ------------------------------------------------------------------
    # serving dispatch: a forward-only program whose batch inputs are a
    # separate (donated) leading argument — the continuous batcher
    # (serving/) stages a padded request batch to device and calls this
    # directly, so no NDArray arg_dict mutation sits on the hot path and
    # the staged input buffer is recycled by XLA the moment the fill's
    # compute consumes it (the "ping-pong donated buffer" half of the
    # serving pipeline; docs/serving.md)
    # ------------------------------------------------------------------
    def serve_program(self, input_names):
        """Jitted inference program `fn(input_vals, other_vals, aux_vals,
        seed) -> outputs` with `input_names` gathered into the donated
        leading tuple and every remaining argument (params, dead label
        args) in `other_vals`.  Cached in the executor's jit cache under
        the input-name signature, so a (tenant, bucket) program compiles
        ONCE and every later fill is a cache hit (counted in
        executor.compile_cache_hits/_misses like the training paths)."""
        names = tuple(input_names)
        key = ("serve", names)
        self._note_compile_cache(key in self._jit_fwd,
                                 site="executor.serve", signature=names)
        if key not in self._jit_fwd:
            an = self._arg_names
            missing = [n for n in names if n not in an]
            if missing:
                raise MXNetError("serve_program: unknown inputs %s" % missing)
            in_idx = [an.index(n) for n in names]
            other_idx = [i for i in range(len(an)) if i not in set(in_idx)]
            entries, order, xn = self._entries, self._order, self._aux_names
            boundary, cast, mesh = self._boundary(), self._cast(), self._mesh

            def f(input_vals, other_vals, aux_vals, seed):
                vals = [None] * len(an)
                for i, v in zip(in_idx, input_vals):
                    vals[i] = v
                for i, v in zip(other_idx, other_vals):
                    vals[i] = v
                rng = jax.random.key(seed)
                outs, _aux = _run_graph(entries, order, an, xn, tuple(vals),
                                        aux_vals, False, rng,
                                        boundary=boundary, cast=cast,
                                        mesh=mesh)
                return outs

            # donation is a TPU/GPU memory optimization; XLA:CPU does not
            # implement it and would warn on every dispatch — gate on
            # THIS executor's device, not the process default backend
            # (a host-side predictor may serve beside a TPU trainer)
            platform = self._first_ctx.jax_device().platform
            donate = (0,) if platform != "cpu" else ()
            self._jit_fwd[key] = self._mem_program(
                f, "executor.serve", names, donate_argnums=donate)
        return self._jit_fwd[key]

    def serve_args(self, input_names):
        """(other_vals, aux_vals) companions for :meth:`serve_program` —
        parameter/aux device refs gathered at dispatch time (cheap, and
        picks up params written between fills)."""
        names = set(input_names)
        other = self._resident(self.arg_dict[n] for n in self._arg_names
                               if n not in names)
        return other, self._gather_aux()

    # ------------------------------------------------------------------
    # single-dispatch training step (fwd + bwd + optimizer update in ONE
    # XLA executable with donated param/state buffers — the reference's
    # bulk-exec + update_on_kvstore taken to its limit)
    # ------------------------------------------------------------------
    def _grad_fwd(self, diff_idx, nondiff_idx):
        """Forward closure `fwd(dv, nondiff_vals, aux_vals, rng)` used by the
        gradient core; when mirroring is armed it is wrapped in
        `jax.checkpoint` so only matmul/conv outputs are kept as residuals."""
        entries, order = self._entries, self._order
        an, xn = self._arg_names, self._aux_names
        boundary = self._boundary()
        cast = self._cast()
        mesh = self._mesh

        def fwd(dv, nondiff_vals, aux_vals, rng):
            vals = [None] * len(an)
            for i, v in zip(diff_idx, dv):
                vals[i] = v
            for i, v in zip(nondiff_idx, nondiff_vals):
                vals[i] = v
            return _run_graph(entries, order, an, xn, tuple(vals), aux_vals,
                              True, rng, boundary=boundary, cast=cast, mesh=mesh)

        if self._mirror:
            fwd = jax.checkpoint(fwd, policy=_MIRROR_POLICY)
        return fwd

    def backward_residual_bytes(self):
        """Bytes of forward activations saved for the backward pass — the
        quantity memory mirroring shrinks (reference graph_executor.cc
        mirror pass reduces exactly this set).  Backend-independent: reads
        JAX's AD residuals rather than XLA buffer assignment."""
        from jax._src.ad_checkpoint import saved_residuals

        an = self._arg_names
        diff_idx = [i for i, n in enumerate(an)
                    if self._grad_req.get(n, "null") != "null"]
        nondiff_idx = [i for i in range(len(an)) if i not in set(diff_idx)]
        fwd = self._grad_fwd(diff_idx, nondiff_idx)
        all_vals = self._gather_args()
        dv = tuple(all_vals[i] for i in diff_idx)
        ndv = tuple(all_vals[i] for i in nondiff_idx)
        res = saved_residuals(fwd, dv, ndv, self._gather_aux(),
                              jax.random.key(0))
        total = 0
        for aval, _ in res:
            if hasattr(aval, "shape") and hasattr(aval, "dtype"):
                n = 1
                for d in aval.shape:
                    n *= int(d)
                total += n * jnp.dtype(aval.dtype).itemsize
        return total

    def _grad_core(self, diff_idx, nondiff_idx):
        """Build the shared fwd+vjp core used by both backward() and the
        fused step — ONE place owns the vals scatter and aux cotangents.

        Memory mirroring (reference graph_executor.cc:225-239
        MXNET_BACKWARD_DO_MIRROR): when armed, the forward is wrapped in
        `jax.checkpoint` with a policy that saves ONLY matmul/conv outputs
        — BN, activations, and other cheap elementwise results are
        recomputed during the backward pass instead of living in HBM
        across it.  Same trade as the reference (a few % more FLOPs for a
        large cut in peak activation memory), expressed as a remat policy
        instead of graph surgery."""
        fwd4 = self._grad_fwd(diff_idx, nondiff_idx)

        def core(diff_vals, nondiff_vals, aux_vals, rng, head_grads):
            def fwd(dv):
                return fwd4(dv, nondiff_vals, aux_vals, rng)

            (outs, aux_upd), vjp_fn = jax.vjp(fwd, diff_vals)
            if head_grads is None:
                cots = tuple(jnp.ones_like(o) for o in outs)
            else:
                cots = tuple(head_grads)
            zero_aux = tuple(jnp.zeros_like(a) for a in aux_upd)
            (grads,) = vjp_fn((cots, zero_aux))
            return outs, aux_upd, grads

        return core

    def install_fused_update(self, updater, index_of_name):
        """Arm the fused-dispatch training paths.  After this, `backward()`
        with no head grads defers and `fused_update()` runs fwd+bwd+update
        in one jitted call; `stage_block()` + `fused_update_block()` run
        K steps per dispatch (each dispatch sized from its staged block,
        so a short epoch tail just runs a smaller scan).  `index_of_name`
        maps arg name -> optimizer key."""
        self._fused_updater = updater
        self._fused_index_of_name = dict(index_of_name)
        self._jit_step = None
        self._jit_block = {}
        self._pending_fused = False
        self._pending_fused_block = False
        self._staged_block = None
        # step-invariant structure, computed once (grad_req/args fixed at bind)
        an = self._arg_names
        diff_names = [n for n in an if self._grad_req.get(n, "null") != "null"]
        diff_idx = [an.index(n) for n in diff_names]
        self._fused_static = (
            diff_names,
            diff_idx,
            [i for i in range(len(an)) if i not in set(diff_idx)],
        )

    def _ensure_fused_states(self, diff_names):
        """Create any missing per-key optimizer state (host side); returns
        {name: state leaves} for the armed updater."""
        from .optimizer import _state_leaves

        updater = self._fused_updater
        opt = updater.optimizer
        leaves_by_name = {}
        for n in diff_names:
            key = self._fused_index_of_name[n]
            if key not in updater.states:
                updater.states[key] = opt.create_state(key, self.arg_dict[n])
            leaves_by_name[n] = _state_leaves(updater.states[key])
        return leaves_by_name

    def fused_update(self):
        """Run the armed single-dispatch training step (see install_fused_update)."""
        import numpy as _np

        from .optimizer import schedule_prefix

        updater = self._fused_updater
        opt = updater.optimizer
        diff_names, diff_idx, nondiff_idx = self._fused_static
        leaves_by_name = self._ensure_fused_states(diff_names)
        scalars = schedule_prefix(
            opt, [self._fused_index_of_name[n] for n in diff_names], 1)[0]
        sig = tuple((n, tuple(l.shape for l in leaves_by_name[n])) for n in diff_names)
        first_call = self._jit_step is None or self._jit_step[1] != sig
        self._note_compile_cache(not first_call,
                                 site="executor.fused_step", signature=sig)
        if first_call:
            core = self._grad_core(diff_idx, nondiff_idx)

            def step(diff_vals, nondiff_vals, aux_vals, state_tuples, seed, scalars_arr):
                rng = jax.random.key(seed)
                outs, aux_upd, grads = core(diff_vals, nondiff_vals, aux_vals, rng, None)
                new_params, new_states = [], []
                for i, (w, g, st) in enumerate(zip(diff_vals, grads, state_tuples)):
                    nw, nst = opt._fused(w, g, st, scalars_arr[i, 0], scalars_arr[i, 1],
                                         scalars_arr[i, 2])
                    new_params.append(nw)
                    new_states.append(nst)
                return outs, aux_upd, tuple(new_params), tuple(new_states)

            jitted = self._mem_program(step, "executor.fused_step", sig,
                                       donate_argnums=(0, 3))
            self._jit_step = (jitted, sig)
        fn = self._jit_step[0]
        all_vals = self._place(self._gather_args())
        diff_vals = tuple(all_vals[i] for i in diff_idx)
        nondiff_vals = tuple(all_vals[i] for i in nondiff_idx)
        state_tuples = tuple(self._place_repl(
            tuple(l.data for l in leaves_by_name[n])) for n in diff_names)
        from . import profiler, telemetry
        from .obs import recorder

        tel = telemetry.enabled()
        if tel:
            donated = (sum(v.nbytes for v in diff_vals)
                       + sum(l.nbytes for st in state_tuples for l in st))
            self._note_bytes("executor.donated_bytes", donated)
            # donated-buffer retirement rides the memory plane's books
            # too: XLA recycles these the moment the step consumes them
            self._note_bytes("mem.donated_retired_bytes", donated)
        # flight-recorder edge events (obs/recorder.py): the dispatch
        # bracket is what the stall watchdog watches, and the compile
        # bracket suppresses it across a legitimate first XLA compile
        rec = recorder.enabled()
        seq = self._train_dispatches + 1
        if rec:
            if first_call:
                recorder.record("compile", "enter", seq, detail="step")
            recorder.record("dispatch", "enter", seq, detail="step")
        try:
            with profiler.span("fit.dispatch", cat="executor",
                               hist="executor.dispatch_seconds.step", k=1):
                outs, aux_upd, new_params, new_states = fn(
                    diff_vals, nondiff_vals, self._place_repl(self._gather_aux()),
                    state_tuples, _np.uint32(self._step_seed), scalars,
                )
        finally:
            if rec:
                if first_call:
                    recorder.record("compile", "exit", seq)
                recorder.record("dispatch", "exit", seq)
        if tel:
            telemetry.inc("executor.train_dispatches")
        self._train_dispatches += 1
        self._outputs_cache = [NDArray(o, self._first_ctx) for o in outs]
        if not self._aux_applied:
            self._write_aux(aux_upd)
            self._aux_applied = True
        self._pending_fused = False
        for n, nw, nst in zip(diff_names, new_params, new_states):
            self.arg_dict[n]._set_data(nw)
            for l, v in zip(leaves_by_name[n], nst):
                l._set_data(v)

    # ------------------------------------------------------------------
    # K-step fused block: ONE dispatch = K full fwd+bwd+update steps.
    # A jitted lax.scan carries (params, optimizer state, aux) with
    # donated buffers over a stacked block of K batches — the reference's
    # bulk-exec (MXNET_EXEC_BULK_EXEC_TRAIN) extended ACROSS steps, so
    # the fixed per-dispatch host cost (jit-cache lookup, argument
    # handling, PJRT enqueue) is paid once per K steps instead of once
    # per step.  Inputs arrive pre-staged (io.DeviceStagedIter overlaps the
    # H2D of block N+1 with block N's compute); scheduler scalars ride a
    # host-computed (K, n, 3) prefix (optimizer.schedule_prefix) so no
    # per-step scalar transfer remains.
    # ------------------------------------------------------------------
    def block_input_sharding(self):
        """Sharding for stacked (K, batch, ...) input blocks: the batch
        axis moves to position 1, so the 'data' mesh axis shards dim 1
        (None on single-device executors)."""
        if self._mesh is None:
            return None
        from .parallel.mesh import NamedSharding, batch_pspec

        return NamedSharding(self._mesh, batch_pspec(self._mesh, lead_dims=1))

    def place_block_input(self, name, arr):
        """Device-put one STACKED (K, batch, ...) input block with the
        right sharding — what the dispatch path does to every block it
        is handed, and the H2D of a block that was stacked on the host
        (a hand-built StagedBlock, a DeviceStagedIter built without
        `place_fn`).  Idempotent: a block that place_step_input /
        stack_block_input assembled on the device already carries
        block_input_sharding() and comes back as it is."""
        if not isinstance(arr, jax.Array):
            # count H2D bytes only for HOST arrays: the dispatch path
            # re-places already-staged device blocks (the idempotent
            # no-op), which must not double the byte counter
            self._note_bytes("executor.h2d_bytes", arr.nbytes)
        sh = self.block_input_sharding()
        if sh is None:
            return jax.device_put(arr, self._first_ctx.jax_device())
        from .parallel.mesh import global_put

        return global_put(arr, sh)

    def _step_layout(self, name):
        """Where one step's array of input `name` goes: (devices, starts,
        rows) — this process's devices of the block, the row of the
        batch axis at which each one's piece starts, and the rows of a
        piece.  A mesh-less executor has one device and one piece, the
        whole array; devices that differ only along a non-data mesh
        axis start at the same row."""
        shape = tuple(self.arg_dict[name].shape)
        if self._mesh is None:
            return (self._first_ctx.jax_device(),), (0,), shape[0]
        idx = self._data_sharding.addressable_devices_indices_map(shape)
        spans = [i[0].indices(shape[0]) for i in idx.values()]
        return (tuple(idx), tuple(s[0] for s in spans),
                spans[0][1] - spans[0][0])

    def place_step_input(self, name, arr):
        """ONE step's array of input `name`, as the source iterator made
        it (an NDArray, or a host array), laid out over the block's
        devices: the `place_fn` of io.DeviceStagedIter.  Returns (came
        from the host?, one single-device piece per device of
        _step_layout), which stack_block_input stacks where the pieces
        lie.  Which way it goes follows from where the payload LIES:

        * In HOST memory — numpy, or an NDArray whose payload is on a
          device of another platform than the block's (nd.host_array:
          the CPU backend's device beside an accelerator, what
          NDArrayIter yields) — it crosses once: each device's rows (a
          view of a contiguous batch axis) go straight from the source's
          own buffer over that device's own host link, all links at
          once, counted in `executor.h2d_bytes`.
        * On a DEVICE of the block's platform it never comes back to
          the host: one jitted program cuts it along the batch axis on
          the device it lives on, and each piece is copied chip to
          chip, one single-device copy each.  (Not jax.device_put of
          the whole array to a NamedSharding: where no shard of the
          source has a wanted index, jax/_src/array.py
          shard_sharded_device_array_slow_path serves the request from
          `x._value`, the host round trip this replaces.)

        Only enqueues: stack_block_input waits where something must."""
        devices, starts, rows = self._step_layout(name)
        if isinstance(arr, NDArray):
            arr = arr.data
        host = (not isinstance(arr, jax.Array)
                or nd.off_platform(arr, self._platform))
        if host:
            arr = _np.asarray(arr)
            self._note_bytes("executor.h2d_bytes", arr.nbytes)
        if tuple(arr.shape) != tuple(self.arg_dict[name].shape):
            raise MXNetError(
                "Shape mismatch for argument %s: bound %s, got %s"
                % (name, self.arg_dict[name].shape, tuple(arr.shape)))
        if rows == arr.shape[0]:
            cut = {0: arr}
        elif host:
            cut = {s: arr[s:s + rows] for s in set(starts)}
        else:
            uniq = tuple(sorted(set(starts)))
            cut = dict(zip(uniq, _split_rows(arr, uniq, rows)))
        return host, [jax.device_put(cut[s], d)
                      for s, d in zip(starts, devices)]

    def stack_block_input(self, name, steps):
        """The (K, batch, ...) block of input `name` from its K steps
        (place_step_input): one jitted stack on every device of the
        block, over the pieces that device holds, and — on a mesh — one
        global array over the per-device stacks, carrying
        block_input_sharding().  The `stack_fn` of io.DeviceStagedIter.
        A short last block is a smaller K.

        Device arrays are immutable and nothing is waited for.  A step
        that came from the HOST is, for as long as its source's buffer
        is needed and no longer (the source may refill it at its next
        next()): what that takes is _host_step_fence's to say."""
        stacks = [_stack_steps(*on_dev)
                  for on_dev in zip(*(pieces for _, pieces in steps))]
        from_host = [pieces for host, pieces in steps if host]
        if from_host:
            jax.block_until_ready(
                stacks if _host_step_fence(self._platform) == "stack"
                else from_host)
        sh = self.block_input_sharding()
        if sh is None:
            return stacks[0]
        shape = (len(steps),) + tuple(self.arg_dict[name].shape)
        return jax.make_array_from_single_device_arrays(shape, sh, stacks)

    def stage_block(self, named_arrays, count, seq=0):
        """Stage a stacked block of `count` batches for the next
        `fused_update_block()`.  `named_arrays` maps input arg name ->
        (count, ...) array (host or already device-put); `seq` is the
        block's number in its staging iterator (StagedBlock.seq), the
        `block` attribute of the `fit.dispatch` span."""
        unknown = [n for n in named_arrays if n not in self.arg_dict]
        if unknown:
            raise MXNetError("stage_block: unknown arguments %s" % unknown)
        self._staged_block = (dict(named_arrays), int(count), seq)
        self._pending_fused_block = True
        # the staged block supersedes any deferred single step (mirror of
        # forward() clearing stale block state): without this, a later
        # update() could replay the abandoned step on stale inputs
        self._pending_fused = False
        self._outputs_cache = None
        self._aux_applied = False

    def _build_block_fn(self, stream_idx, static_idx):
        """The K-step scan over full fwd+bwd+update steps."""
        an = self._arg_names
        diff_names, diff_idx, nondiff_idx = self._fused_static
        opt = self._fused_updater.optimizer
        core = self._grad_core(diff_idx, nondiff_idx)
        stream_pos = {i: p for p, i in enumerate(stream_idx)}
        static_pos = {i: p for p, i in enumerate(static_idx)}

        def block(diff_vals, static_vals, aux_vals, state_tuples,
                  stream_vals, seeds_arr, scalars_arr):
            def body(carry, xs):
                dv, sts, aux = carry
                stream, seed, scal = xs
                nondiff = tuple(
                    stream[stream_pos[i]] if i in stream_pos
                    else static_vals[static_pos[i]]
                    for i in nondiff_idx)
                rng = jax.random.key(seed)
                outs, aux_upd, grads = core(dv, nondiff, aux, rng, None)
                new_params, new_states = [], []
                for j, (w, g, st) in enumerate(zip(dv, grads, sts)):
                    nw, nst = opt._fused(w, g, st, scal[j, 0],
                                         scal[j, 1], scal[j, 2])
                    new_params.append(nw)
                    new_states.append(nst)
                return ((tuple(new_params), tuple(new_states), aux_upd),
                        outs)

            carry, outs = jax.lax.scan(
                body, (diff_vals, state_tuples, aux_vals),
                (stream_vals, seeds_arr, scalars_arr))
            new_dv, new_sts, aux_out = carry
            return outs, aux_out, new_dv, new_sts

        return block

    def fused_update_block(self):
        """Run the staged K-step block: one jitted lax.scan dispatch
        executing K full fwd+bwd+update steps (see stage_block).  On a
        mesh the gradient all-reduce is the one XLA's partitioner puts
        into the scan body — docs/distributed.md."""
        import numpy as _np

        from .optimizer import schedule_prefix

        named, k, block_seq = self._staged_block
        updater = self._fused_updater
        opt = updater.optimizer
        an = self._arg_names
        diff_names, diff_idx, nondiff_idx = self._fused_static
        leaves_by_name = self._ensure_fused_states(diff_names)
        # host-computed scheduler prefix for the whole block — zero
        # per-step scalar RTTs (optimizer.py schedule_prefix)
        scalars = schedule_prefix(
            opt, [self._fused_index_of_name[n] for n in diff_names], k)
        # one host seed per step, drawn in the same order the single-step
        # path draws them (forward() -> _next_seed per step), so dropout
        # masks agree between steps_per_dispatch=K and K single dispatches
        seeds = _np.array([self._next_seed() for _ in range(k)],
                          dtype=_np.uint32)
        # streamed args (one slice per scan step) vs step-invariant args
        stream_idx = [i for i in nondiff_idx if an[i] in named]
        static_idx = [i for i in nondiff_idx if an[i] not in named]
        sig = tuple((n, tuple(l.shape for l in leaves_by_name[n]))
                    for n in diff_names)
        key = (k, tuple(an[i] for i in stream_idx), sig)
        first_call = key not in self._jit_block
        self._note_compile_cache(not first_call,
                                 site="executor.fused_block", signature=key)
        if first_call:
            self._jit_block[key] = self._mem_program(
                self._build_block_fn(stream_idx, static_idx),
                "executor.fused_block", key, donate_argnums=(0, 3))
        fn = self._jit_block[key]
        all_vals = self._place(self._gather_args())
        diff_vals = tuple(all_vals[i] for i in diff_idx)
        static_vals = tuple(all_vals[i] for i in static_idx)
        stream_vals = tuple(self.place_block_input(an[i], named[an[i]])
                            for i in stream_idx)
        state_tuples = tuple(self._place_repl(
            tuple(l.data for l in leaves_by_name[n])) for n in diff_names)
        from . import profiler, telemetry
        from .obs import recorder

        tel = telemetry.enabled()
        if tel:
            donated = (sum(v.nbytes for v in diff_vals)
                       + sum(l.nbytes for st in state_tuples for l in st))
            self._note_bytes("executor.donated_bytes", donated)
            self._note_bytes("mem.donated_retired_bytes", donated)
        # flight-recorder bracket (obs/recorder.py): seq is the dispatch
        # counter, detail carries K — the post-mortem's "which dispatch
        # was in flight" answer.  The compile bracket suppresses the
        # stall watchdog across a first XLA compile.
        rec = recorder.enabled()
        seq = self._train_dispatches + 1
        if rec:
            detail = "block(K=%d)" % k
            if first_call:
                recorder.record("compile", "enter", seq, detail=detail)
            recorder.record("dispatch", "enter", seq, detail=detail)
        try:
            with profiler.span("fit.dispatch", cat="executor",
                               hist="executor.dispatch_seconds.block", k=k,
                               block=block_seq):
                outs, aux_upd, new_params, new_states = fn(
                    diff_vals, static_vals, self._place_repl(self._gather_aux()),
                    state_tuples, stream_vals, seeds, scalars)
        finally:
            if rec:
                if first_call:
                    recorder.record("compile", "exit", seq)
                recorder.record("dispatch", "exit", seq)
        if tel:
            telemetry.inc("executor.train_dispatches")
        self._train_dispatches += 1
        self._last_block_count = k
        # outputs arrive stacked (K, ...): ONE per-dispatch host readback
        # replaces K per-step ones (update_metric consumes the block)
        self._outputs_cache = [NDArray(o, self._first_ctx) for o in outs]
        self._write_aux(aux_upd)
        self._aux_applied = True
        self._pending_fused_block = False
        self._staged_block = None
        for n, nw, nst in zip(diff_names, new_params, new_states):
            self.arg_dict[n]._set_data(nw)
            for l, v in zip(leaves_by_name[n], nst):
                l._set_data(v)

    def backward(self, out_grads=None):
        """Fused forward+backward in one XLA executable; grads land per grad_req.

        When a fused update is installed (see install_fused_update) and no
        head gradients are given, backward defers — update() completes the
        whole step in one dispatch.  grad_dict is NOT materialized on that
        path (gradients live only inside the fused executable)."""
        if getattr(self, "_fused_updater", None) is not None and out_grads is None:
            self._pending_fused = True
            return
        diff_names = [n for n in self._arg_names if self._grad_req.get(n, "null") != "null"]
        if not diff_names:
            return
        has_heads = out_grads is not None
        key = (True, has_heads)
        self._note_compile_cache(key in self._jit_bwd,
                                 site="executor.backward", signature=key)
        if key not in self._jit_bwd:
            an = self._arg_names
            diff_idx = [an.index(n) for n in diff_names]
            nondiff_idx = [i for i in range(len(an)) if i not in diff_idx]
            core = self._grad_core(diff_idx, nondiff_idx)

            def f(diff_vals, nondiff_vals, aux_vals, seed, head_grads):
                rng = jax.random.key(seed)
                return core(diff_vals, nondiff_vals, aux_vals, rng, head_grads)

            self._jit_bwd[key] = (
                self._mem_program(f, "executor.backward", key),
                diff_names, diff_idx, nondiff_idx)
        fn, diff_names, diff_idx, nondiff_idx = self._jit_bwd[key]
        all_vals = self._place(self._gather_args())
        diff_vals = tuple(all_vals[i] for i in diff_idx)
        nondiff_vals = tuple(all_vals[i] for i in nondiff_idx)
        heads = None
        if out_grads is not None:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            heads = tuple(g.data if isinstance(g, NDArray) else jnp.asarray(g) for g in out_grads)
        import numpy as _np

        from . import profiler, telemetry

        with profiler.span("executor.forward_backward", cat="executor",
                           hist="executor.dispatch_seconds.step"):
            outs, aux_upd, grads = fn(diff_vals, nondiff_vals,
                                      self._place_repl(self._gather_aux()),
                                      _np.uint32(self._step_seed), heads)
        if telemetry.enabled():
            telemetry.inc("executor.train_dispatches")
        self._train_dispatches += 1
        self._outputs_cache = [NDArray(o, self._first_ctx) for o in outs]
        if not self._aux_applied:
            self._write_aux(aux_upd)
            self._aux_applied = True
        for n, g in zip(diff_names, grads):
            req = self._grad_req.get(n, "write")
            tgt = self.grad_dict.get(n)
            if tgt is None:
                continue
            if req == "add":
                tgt._set_data(tgt.data + g)
            else:
                tgt._set_data(g)

    def forward_backward(self, out_grads=None, **kwargs):
        self.forward(is_train=True, **kwargs)
        self.backward(out_grads)
        return self.outputs

    # ------------------------------------------------------------------
    # misc (parity: python/mxnet/executor.py)
    # ------------------------------------------------------------------
    @property
    def output_dict(self):
        return dict(zip(self._symbol.list_outputs(), self.outputs))

    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self._arg_names]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) for n in self._arg_names]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self._aux_names]

    def copy_params_from(self, arg_params, aux_params=None, allow_extra_params=False):
        for name, arr in (arg_params or {}).items():
            if name in self.arg_dict:
                self.arg_dict[name][:] = arr
            elif not allow_extra_params:
                raise MXNetError("Unknown param %s" % name)
        for name, arr in (aux_params or {}).items():
            if name in self.aux_dict:
                self.aux_dict[name][:] = arr
            elif not allow_extra_params:
                raise MXNetError("Unknown aux %s" % name)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Rebind with new input shapes, sharing parameter arrays
        (parity: executor.py reshape; reference shared-pool rebinding)."""
        new_shapes = dict(kwargs)
        arg_shapes, _, _ = self._symbol.infer_shape(**new_shapes)
        arg_dict = {}
        for n, s in zip(self._arg_names, arg_shapes):
            cur = self.arg_dict[n]
            if tuple(cur.shape) == tuple(s):
                arg_dict[n] = cur
            else:
                arg_dict[n] = NDArray(jnp.zeros(s, dtype=cur.dtype), self._first_ctx)
        new_exec = Executor(
            self._symbol, self._ctx, arg_dict,
            {n: NDArray(jnp.zeros_like(arg_dict[n].data), self._first_ctx) for n in self.grad_dict},
            dict(self._grad_req), dict(self.aux_dict), mesh=self._mesh,
            param_shardings=self._param_shardings, node_groups=self._node_groups,
            compute_dtype=self._compute_dtype, fp32_names=self._fp32_names,
            mirror=self._mirror,
        )
        # a rebound executor keeps the training regime: the fused
        # single-dispatch step survives reshape (bucketing hot path)
        if getattr(self, "_fused_updater", None) is not None:
            new_exec.install_fused_update(self._fused_updater,
                                          self._fused_index_of_name)
        return new_exec

    def set_monitor_callback(self, callback):
        self._monitor_callback = callback
        if callback is not None and getattr(self, "_fused_updater", None) is not None:
            # monitors need materialized outputs/grads — the single-dispatch
            # step keeps gradients inside the executable, so disarm it
            import logging
            logging.info(
                "Monitor installed: leaving the fused fwd+bwd+update "
                "dispatch (gradients must be materialized); expect lower "
                "step throughput while monitoring")
            self._fused_updater = None

    def debug_str(self):
        lines = ["Symbol outputs: %s" % self._symbol.list_outputs()]
        for node in self._order:
            if node.op is not None:
                lines.append("%s(%s) <- %s" % (node.op.name, node.name,
                                               [s.name for s, _ in node.inputs]))
        return "\n".join(lines)


def _norm_grad_req(grad_req, arg_names):
    if isinstance(grad_req, str):
        return {n: grad_req for n in arg_names}
    if isinstance(grad_req, (list, tuple)):
        return dict(zip(arg_names, grad_req))
    out = {n: "null" for n in arg_names}
    out.update(grad_req)
    return out
