"""Symbol — declarative graph construction.

TPU-native equivalent of the reference Symbol
(reference python/mxnet/symbol.py + the nnvm Symbol/Graph submodule,
SURVEY.md §2.2).  A Symbol is a DAG of `_Node`s; binding it lowers the
WHOLE forward(+backward) graph to a single jitted XLA executable
(see executor.py) — the reference's NNVM passes (PlanMemory, fusion,
DetectInplaceAddTo) collapse into the XLA compiler (SURVEY.md §7 phase 3).

Shape/type inference: per-op `infer_shape` hooks (≙ FInferShape) give
bidirectional parameter-shape inference; ops without one are inferred
forward-only with `jax.eval_shape` (zero FLOPs, pure tracing).
"""
from __future__ import annotations

import builtins
import json

import jax
import jax.numpy as jnp
import numpy as _np

from . import attribute, name as _name_mod
from .base import MXNetError
from .ops.registry import OP_REGISTRY, get_op

__all__ = ["Symbol", "Variable", "var", "Group", "load", "load_json",
           "freeze_batchnorm", "batchnorm_param_names"]


class _Node:
    """One graph node: a registered op application or a variable."""

    __slots__ = ("op", "name", "attrs", "inputs", "aux_vars", "is_aux", "_nd_attrs")

    def __init__(self, op, name, attrs=None, inputs=(), aux_vars=(), is_aux=False):
        self.op = op  # Op instance or None for variables
        self.name = name
        self.attrs = dict(attrs or {})
        self.inputs = list(inputs)  # list of (_Node, out_index)
        self.aux_vars = list(aux_vars)  # _Node list for ops with aux state
        self.is_aux = is_aux
        self._nd_attrs = {}

    @property
    def num_outputs(self):
        if self.op is None:
            return 1
        n = self.op.num_outputs
        return n(self.attrs) if callable(n) else n


def _topo_order(entries):
    """Post-order DFS over (node, idx) output entries."""
    order, visited = [], set()
    stack = [e[0] for e in entries]
    while stack:
        node = stack[-1]
        if id(node) in visited:
            stack.pop()
            continue
        pending = [n for (n, _) in node.inputs if id(n) not in visited]
        pending += [n for n in node.aux_vars if id(n) not in visited]
        if pending:
            # push in reverse so the FIRST input is visited first — keeps
            # list_arguments() in composition order (parity: nnvm DFSVisit)
            stack.extend(reversed(pending))
        else:
            visited.add(id(node))
            order.append(node)
            stack.pop()
    return order


class Symbol:
    """Symbolic graph handle over one or more output entries."""

    __slots__ = ("_entries",)

    def __init__(self, entries):
        self._entries = list(entries)

    # ------------------------------------------------------------------
    # introspection (parity: symbol.py list_arguments/list_outputs/...)
    # ------------------------------------------------------------------
    @property
    def name(self):
        if len(self._entries) == 1:
            return self._entries[0][0].name
        return None

    def list_arguments(self):
        return [n.name for n in _topo_order(self._entries) if n.op is None and not n.is_aux]

    def list_auxiliary_states(self):
        return [n.name for n in _topo_order(self._entries) if n.op is None and n.is_aux]

    def list_outputs(self):
        out = []
        for node, idx in self._entries:
            if node.op is None:
                out.append(node.name)
            elif node.num_outputs == 1:
                out.append(node.name + "_output")
            else:
                out.append("%s_output%d" % (node.name, idx))
        return out

    def list_inputs(self):
        return [n.name for n in _topo_order(self._entries) if n.op is None]

    def get_internals(self):
        entries = []
        for node in _topo_order(self._entries):
            for i in range(node.num_outputs):
                entries.append((node, i))
        return Symbol(entries)

    def get_children(self):
        children = []
        for node, _ in self._entries:
            children.extend(node.inputs)
        return Symbol(children) if children else None

    def __getitem__(self, index):
        if isinstance(index, str):
            names = self.list_outputs()
            if index not in names:
                raise ValueError("Cannot find output %s" % index)
            index = names.index(index)
        # builtins.slice: the generated op namespace shadows `slice` here
        if isinstance(index, builtins.slice):
            return Symbol(self._entries[index])
        return Symbol([self._entries[index]])

    def __len__(self):
        return len(self._entries)

    def __iter__(self):
        return (Symbol([e]) for e in self._entries)

    def __repr__(self):
        return "<Symbol %s>" % (self.name or "grouped")

    # ------------------------------------------------------------------
    # attributes (parity: symbol.py attr/list_attr/attr_dict)
    # ------------------------------------------------------------------
    def attr(self, key):
        node = self._entries[0][0]
        return node.attrs.get(key) if node.attrs else None

    def list_attr(self):
        node = self._entries[0][0]
        return {k: str(v) for k, v in node.attrs.items()}

    def attr_dict(self):
        ret = {}
        for node in _topo_order(self._entries):
            if node.attrs:
                ret[node.name] = {k: str(v) for k, v in node.attrs.items()}
        return ret

    def _set_attr(self, **kwargs):
        self._entries[0][0].attrs.update(kwargs)

    # ------------------------------------------------------------------
    # composition arithmetic (parity: symbol.py operator overloads)
    # ------------------------------------------------------------------
    def _binary(self, other, op_name, scalar_name, reverse=False):
        if isinstance(other, Symbol):
            lhs, rhs = (other, self) if reverse else (self, other)
            return _create(op_name, [lhs, rhs], {})
        attrs = {"scalar": float(other)}
        return _create(scalar_name, [self], attrs)

    def __add__(self, o):
        return self._binary(o, "elemwise_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binary(o, "elemwise_sub", "_minus_scalar")

    def __rsub__(self, o):
        return self._binary(o, "elemwise_sub", "_rminus_scalar", reverse=True)

    def __mul__(self, o):
        return self._binary(o, "elemwise_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binary(o, "elemwise_div", "_div_scalar")

    __div__ = __truediv__

    def __rtruediv__(self, o):
        return self._binary(o, "elemwise_div", "_rdiv_scalar", reverse=True)

    __rdiv__ = __rtruediv__

    def __pow__(self, o):
        return self._binary(o, "_power", "_power_scalar")

    def __neg__(self):
        return self._binary(-1.0, "elemwise_mul", "_mul_scalar")

    def __eq__(self, o):
        return self._binary(o, "_equal", "_equal_scalar") if isinstance(o, (Symbol, int, float)) else NotImplemented

    def __ne__(self, o):
        return self._binary(o, "_not_equal", "_not_equal_scalar") if isinstance(o, (Symbol, int, float)) else NotImplemented

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

    def __copy__(self):
        return Symbol(list(self._entries))

    def __deepcopy__(self, memo):
        return load_json(self.tojson())

    # ------------------------------------------------------------------
    # shape / type inference
    # ------------------------------------------------------------------
    def infer_shape(self, *args, **kwargs):
        try:
            return self._infer_shape_impl(False, *args, **kwargs)
        except MXNetError:
            raise
        except Exception as e:  # parity: infer_shape returns Nones on failure
            raise MXNetError("infer_shape error: %s" % e)

    def infer_shape_partial(self, *args, **kwargs):
        try:
            return self._infer_shape_impl(True, *args, **kwargs)
        except Exception:
            return (None, None, None)

    def _infer_shape_impl(self, partial, *args, **kwargs):
        arg_names = self.list_arguments()
        known = {}
        if args:
            for n, s in zip(arg_names, args):
                if s is not None:
                    known[n] = tuple(s)
        known.update({k: tuple(v) for k, v in kwargs.items() if v is not None})
        shapes = _infer_graph_shapes(self._entries, known, partial=partial)
        if shapes is None:
            return (None, None, None)
        node_shapes, var_shapes = shapes
        arg_shapes = [var_shapes.get(n) for n in arg_names]
        aux_shapes = [var_shapes.get(n) for n in self.list_auxiliary_states()]
        out_shapes = [node_shapes[(id(nd), ix)] for nd, ix in self._entries]
        return arg_shapes, out_shapes, aux_shapes

    def infer_type(self, *args, **kwargs):
        """Forward dtype propagation (parity: MXImperativeInvoke FInferType;
        reference src/c_api/c_api_ndarray.cc SetShapeType).

        Unknown variables default to float32; op outputs follow numpy-style
        promotion of their inputs, with `dtype`-attr ops (Cast, init ops)
        and index-producing ops (arg*/topk-indices) overriding."""
        arg_names = self.list_arguments()
        known = {}
        if args:
            for n, t in zip(arg_names, args):
                if t is not None:
                    known[n] = jnp.dtype(t)
        known.update({k: jnp.dtype(v) for k, v in kwargs.items() if v is not None})
        order = _topo_order(self._entries)
        node_types = {}
        var_types = {}
        for node in order:
            if node.op is None:
                t = known.get(node.name)
                if t is None and "__dtype__" in node.attrs:
                    t = jnp.dtype(node.attrs["__dtype__"])
                var_types[node.name] = t  # None = not yet known
                node_types[(id(node), 0)] = t
                continue
            in_types = [node_types.get((id(src), idx)) for src, idx in node.inputs]
            known_in = [t for t in in_types if t is not None]
            if "dtype" in node.attrs and node.attrs["dtype"]:
                out_t = jnp.dtype(str(node.attrs["dtype"]))
            elif known_in:
                out_t = _np.result_type(*known_in)
            else:
                out_t = _np.dtype(_np.float32)
            # same-dtype unification: untyped variable inputs (params) adopt
            # the op's resolved dtype — the one-pass analog of nnvm's
            # bidirectional InferType (reference graph_executor.cc:793-806)
            for (src, idx), t in zip(node.inputs, in_types):
                if t is None and src.op is None:
                    node_types[(id(src), idx)] = out_t
                    var_types[src.name] = out_t
            # current kernels emit float32 for index-valued outputs
            if node.op.name in ("argmax", "argmin", "argmax_channel", "argsort"):
                out_t = _np.dtype(_np.float32)
            for a in node.aux_vars:
                var_types.setdefault(a.name, _np.dtype(_np.float32))
            for i in range(node.num_outputs):
                node_types[(id(node), i)] = out_t
        f32 = _np.dtype(_np.float32)
        arg_types = [var_types.get(n) or f32 for n in arg_names]
        out_types = [node_types[(id(nd), ix)] or f32 for nd, ix in self._entries]
        aux_types = [var_types.get(n) or f32 for n in self.list_auxiliary_states()]
        return arg_types, out_types, aux_types

    # ------------------------------------------------------------------
    # serialization — MXNet-style nodes/arg_nodes/heads JSON
    # (parity: reference nnvm SaveJSON via src/c_api/c_api_symbolic.cc)
    # ------------------------------------------------------------------
    def __getstate__(self):
        # Symbols pickle via their JSON graph form (the node DAG uses
        # __slots__); needed when a dist kvstore ships an optimizer whose
        # attrs include the bound symbol (reference pickles optimizers to
        # servers, kvstore.py set_optimizer)
        return {"json": self.tojson()}

    def __setstate__(self, state):
        self._entries = load_json(state["json"])._entries

    def tojson(self):
        order = _topo_order(self._entries)
        node_ids = {id(n): i for i, n in enumerate(order)}
        nodes = []
        for n in order:
            entry = {
                "op": "null" if n.op is None else n.op.name,
                "name": n.name,
                "inputs": [[node_ids[id(src)], idx, 0] for src, idx in n.inputs],
            }
            if n.attrs:
                entry["attrs"] = {k: str(v) for k, v in n.attrs.items()}
            if n.is_aux:
                entry.setdefault("attrs", {})["__is_aux__"] = "1"
            if n.aux_vars:
                entry["aux_inputs"] = [node_ids[id(a)] for a in n.aux_vars]
            nodes.append(entry)
        heads = [[node_ids[id(nd)], ix, 0] for nd, ix in self._entries]
        arg_nodes = [i for i, n in enumerate(order) if n.op is None]
        return json.dumps(
            {"nodes": nodes, "arg_nodes": arg_nodes, "heads": heads, "attrs": {"mxnet_tpu_version": "1"}},
            indent=2,
        )

    def save(self, fname):
        with open(fname, "w") as f:
            f.write(self.tojson())

    # ------------------------------------------------------------------
    # binding (implemented in executor.py; imported lazily to avoid cycle)
    # ------------------------------------------------------------------
    def simple_bind(self, ctx=None, grad_req="write", type_dict=None, **kwargs):
        from .executor import Executor

        return Executor.simple_bind(self, ctx, grad_req=grad_req, type_dict=type_dict, **kwargs)

    def bind(self, ctx=None, args=None, args_grad=None, grad_req="write", aux_states=None,
             group2ctx=None, shared_exec=None):
        from .executor import Executor

        return Executor.bind(self, ctx, args, args_grad, grad_req, aux_states, group2ctx, shared_exec)

    def eval(self, ctx=None, **kwargs):
        return self.bind(ctx, kwargs).forward()

    def grad(self, wrt):
        raise NotImplementedError(
            "Symbol.grad is deprecated (matching the reference). Bind with "
            "gradients enabled instead: exe = sym.bind(ctx, args, "
            "args_grad={...}, grad_req='write') or sym.simple_bind(ctx, "
            "grad_req='write'), then exe.backward(); gradients land in "
            "exe.grad_dict / exe.grad_arrays.")


# ----------------------------------------------------------------------
# graph-wide shape inference
# ----------------------------------------------------------------------


def _infer_graph_shapes(entries, known_var_shapes, partial=False):
    """Topological forward inference with per-op FInferShape hooks.

    Returns ({(node_id, out_idx): shape}, {var_name: shape}).
    """
    order = _topo_order(entries)
    node_shapes = {}
    var_shapes = dict(known_var_shapes)
    for node in order:
        if node.op is None:
            shp = var_shapes.get(node.name)
            if shp is None and "__shape__" in node.attrs:
                from .ops.tensor import _shape as _parse_shape

                shp = _parse_shape(node.attrs["__shape__"])
                var_shapes[node.name] = shp
            node_shapes[(id(node), 0)] = shp
            continue
        in_shapes = [node_shapes.get((id(src), idx)) for src, idx in node.inputs]
        aux_shapes_in = [var_shapes.get(a.name) for a in node.aux_vars]
        out_shapes = None
        if node.op.infer_shape is not None and any(s is not None for s in in_shapes):
            res = node.op.infer_shape(in_shapes, node.attrs)
            if len(res) == 3:
                full_in, out_shapes, aux_shapes = res
            else:
                full_in, out_shapes = res
                aux_shapes = []
            for (src, idx), s in zip(node.inputs, full_in):
                if s is not None:
                    node_shapes[(id(src), idx)] = tuple(s)
                    if src.op is None:
                        var_shapes[src.name] = tuple(s)
            for a, s in zip(node.aux_vars, aux_shapes):
                var_shapes[a.name] = tuple(s)
        elif all(s is not None for s in in_shapes):
            out_shapes = _eval_shape_infer(node, in_shapes, aux_shapes_in)
        if out_shapes is None:
            if partial:
                for i in range(node.num_outputs):
                    node_shapes[(id(node), i)] = None
                continue
            missing = [src.name for (src, idx), s in zip(node.inputs, in_shapes) if s is None]
            raise MXNetError(
                "Cannot infer shapes for node %s (op %s); unknown inputs: %s"
                % (node.name, node.op.name, missing)
            )
        for i, s in enumerate(out_shapes):
            node_shapes[(id(node), i)] = tuple(s)
    return node_shapes, var_shapes


def _eval_shape_infer(node, in_shapes, aux_shapes):
    op = node.op
    structs = [jax.ShapeDtypeStruct(s, jnp.float32) for s in in_shapes]
    if aux_shapes and all(s is not None for s in aux_shapes):
        structs += [jax.ShapeDtypeStruct(s, jnp.float32) for s in aux_shapes]
    # same attr filter as the executor: dunder bookkeeping attrs and
    # ctx_group placement hints never reach op kernels
    kwargs = {k: v for k, v in node.attrs.items()
              if not k.startswith("__") and k != "ctx_group"}
    if op.need_is_train:
        kwargs["is_train"] = False
    if op.need_rng:
        kwargs["rng"] = None

    def f(*xs):
        return op.fn(*xs, **kwargs)

    res = jax.eval_shape(f, *structs)
    if not isinstance(res, tuple):
        res = (res,)
    n_main = node.num_outputs
    return [r.shape for r in res[:n_main]]


# ----------------------------------------------------------------------
# construction API
# ----------------------------------------------------------------------


def Variable(name, attr=None, shape=None, lr_mult=None, wd_mult=None, dtype=None, init=None, **kwargs):
    """Create a variable symbol (parity: symbol.py Variable)."""
    attrs = attribute.current().get(attr)
    if shape is not None:
        attrs["__shape__"] = str(tuple(shape))
    if lr_mult is not None:
        attrs["__lr_mult__"] = str(lr_mult)
    if wd_mult is not None:
        attrs["__wd_mult__"] = str(wd_mult)
    if dtype is not None:
        attrs["__dtype__"] = str(dtype)
    if init is not None:
        attrs["__init__"] = init if isinstance(init, str) else init.dumps()
    attrs.update(kwargs)
    return Symbol([(_Node(None, name, attrs), 0)])


var = Variable


def Group(symbols):
    """Group symbols into one multi-output symbol (parity: symbol.py Group)."""
    entries = []
    for s in symbols:
        entries.extend(s._entries)
    return Symbol(entries)


def _create(op_name, input_syms, attrs, name=None, aux_syms=None):
    """Create an op node (parity: _symbol_creator, symbol.py codegen)."""
    op = get_op(op_name)
    hint = op.name.lower().lstrip("_")
    name = _name_mod.current().get(name, hint)
    scope_attrs = attribute.current().get(None)
    full_attrs = dict(scope_attrs)
    full_attrs.update(attrs)
    if op.params:
        from .ops.params import validate_attrs

        validate_attrs(op, full_attrs)
    inputs = []
    for s in input_syms:
        if len(s._entries) != 1:
            raise MXNetError("Cannot use grouped symbol as op input")
        inputs.append(s._entries[0])
    # auto-create missing weight/bias variables (parity: nnvm Symbol compose
    # auto-creating named variable nodes for unbound op inputs)
    if not op.variadic:
        declared = op.list_inputs(full_attrs)
        while len(inputs) < len(declared):
            in_name = "%s_%s" % (name, declared[len(inputs)])
            from .ops.tensor import _bool as _b

            # no_bias defaults True only for Deconvolution
            # (deconvolution-inl.h:72 set_default(true); conv/FC default false)
            if declared[len(inputs)] == "bias" and _b(
                full_attrs.get("no_bias", op.name == "Deconvolution")
            ):
                break
            if declared[len(inputs)] in ("sequence_length",) and not _b(
                full_attrs.get("use_sequence_length", False)
            ):
                break
            if declared[len(inputs)] == "state_cell" and str(
                full_attrs.get("mode", "lstm")
            ) != "lstm":
                break
            if declared[len(inputs)] == "gamma" and op.name == "LeakyReLU" and str(
                full_attrs.get("act_type", "leaky")
            ) != "prelu":
                break
            if declared[len(inputs)] == "label" and op.name in (
                "SoftmaxOutput", "LinearRegressionOutput", "LogisticRegressionOutput",
                "MAERegressionOutput", "SVMOutput",
            ):
                var_node = _Node(None, "%s_label" % name)
                inputs.append((var_node, 0))
                continue
            var_node = _Node(None, in_name)
            if declared[len(inputs)] == "weight" and op.name in (
                "Convolution", "Deconvolution"
            ):
                lay = str(full_attrs.get("layout", ""))
                if lay.endswith("C"):  # channel-last: kernel stored spatial+IO
                    var_node.attrs["__layout__"] = lay[1:-1] + "IO"
            inputs.append((var_node, 0))
    aux_vars = []
    if aux_syms:
        for s in aux_syms:
            aux_vars.append(s._entries[0][0])
            aux_vars[-1].is_aux = True
    else:
        for aux_name in op.aux:
            aux_vars.append(_Node(None, "%s_%s" % (name, aux_name), is_aux=True))
    node = _Node(op, name, full_attrs, inputs, aux_vars)
    n_out = node.num_outputs
    entries = [(node, i) for i in range(n_out)]
    return Symbol(entries)


def _make_sym_function(op):
    def fn(*args, **kwargs):
        name = kwargs.pop("name", None)
        attr = kwargs.pop("attr", None)
        input_syms = list(args)
        aux_syms = None
        sym_kwargs = {}
        attrs = {}
        for k, v in kwargs.items():
            if isinstance(v, Symbol):
                sym_kwargs[k] = v
            elif isinstance(v, (list, tuple)) and v and all(isinstance(x, Symbol) for x in v):
                input_syms.extend(v)
            else:
                attrs[k] = v
        if sym_kwargs:
            # map keyword symbols onto declared input slots
            if not input_syms and not op.variadic:
                ordered = []
                for in_name in op.inputs:
                    if in_name in sym_kwargs:
                        ordered.append(sym_kwargs.pop(in_name))
                    elif sym_kwargs:
                        break
                input_syms = ordered
            aux_named = []
            for aux_name in op.aux:
                if aux_name in sym_kwargs:
                    aux_named.append(sym_kwargs.pop(aux_name))
            if aux_named:
                aux_syms = aux_named
            for k, v in sym_kwargs.items():
                input_syms.append(v)
        if attr:
            cur = attribute.current().get(attr)
            merged = dict(cur)
            merged.update(attrs)
            attrs = merged
        return _create(op.name, input_syms, attrs, name=name, aux_syms=aux_syms)

    fn.__name__ = op.name
    fn.__doc__ = op.doc
    return fn


def _populate(module):
    import sys

    seen = {}
    mod = sys.modules[module]
    for reg_name, op in OP_REGISTRY.items():
        if id(op) not in seen:
            seen[id(op)] = _make_sym_function(op)
        if not hasattr(mod, reg_name):
            setattr(mod, reg_name, seen[id(op)])


_populate(__name__)


# ----------------------------------------------------------------------
# frozen-BatchNorm fine-tuning transform (the symbol-level half of
# Module.fit(frozen_bn=True); README Roofline items 6/8)
# ----------------------------------------------------------------------


def freeze_batchnorm(symbol):
    """Return a COPY of `symbol` with every BatchNorm frozen for
    fine-tuning: ``use_global_stats`` forced on, so train-mode forward
    normalizes with the carried running statistics and the moving-stat
    aux updates are identity (stats carried, never recomputed — and the
    exact-BN backward's sum(dy)/sum(dy*x_hat) reductions, ~30 ms/step on
    ResNet-50 batch 512, disappear from the grad graph).

    This is the reference's own ``use_global_stats`` fine-tuning mode
    surfaced as a graph transform; pair it with excluding the BN
    gamma/beta arguments from the update (``batchnorm_param_names`` ->
    ``fixed_param_names``), which ``Module.fit(frozen_bn=True)`` does in
    one step.  The input symbol is not mutated; argument/aux names are
    preserved, so pretrained ``arg_params``/``aux_params`` load
    unchanged."""
    frozen = load_json(symbol.tojson())
    for node in _topo_order(frozen._entries):
        if node.op is not None and node.op.name == "BatchNorm":
            node.attrs["use_global_stats"] = "True"
    return frozen


def batchnorm_param_names(symbol):
    """The gamma/beta argument names feeding BatchNorm nodes — the set a
    frozen-BN fine-tune excludes from the optimizer update (grad_req
    'null' via ``fixed_param_names``)."""
    names = []
    seen = set()
    for node in _topo_order(symbol._entries):
        if node.op is None or node.op.name != "BatchNorm":
            continue
        for (src, _), slot in zip(node.inputs, node.op.inputs):
            if (slot in ("gamma", "beta") and src.op is None
                    and not src.is_aux and src.name not in seen):
                seen.add(src.name)
                names.append(src.name)
    return names


# ----------------------------------------------------------------------
# JSON load
# ----------------------------------------------------------------------


def load_json(json_str):
    """Load a symbol from its JSON string (parity: symbol.py load_json)."""
    data = json.loads(json_str)
    raw_nodes = data["nodes"]
    built = []
    for entry in raw_nodes:
        attrs = dict(entry.get("attrs", entry.get("param", {})) or {})
        is_aux = attrs.pop("__is_aux__", None) == "1"
        if entry["op"] == "null":
            built.append(_Node(None, entry["name"], attrs, is_aux=is_aux))
        else:
            op = get_op(entry["op"])
            inputs = [(built[i], idx) for i, idx, _ in entry["inputs"]]
            aux_vars = [built[i] for i in entry.get("aux_inputs", [])]
            # legacy-style JSON keeps aux at the tail of inputs for ops with aux
            if not aux_vars and op.aux and len(inputs) == len(op.inputs) + len(op.aux):
                aux_vars = [n for n, _ in inputs[len(op.inputs):]]
                for n in aux_vars:
                    n.is_aux = True
                inputs = inputs[: len(op.inputs)]
            node = _Node(op, entry["name"], attrs, inputs, aux_vars)
            built.append(node)
    heads = data["heads"]
    entries = []
    for h in heads:
        entries.append((built[h[0]], h[1] if len(h) > 1 else 0))
    return Symbol(entries)


def load(fname):
    with open(fname) as f:
        return load_json(f.read())
