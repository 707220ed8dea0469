"""Operator registry.

TPU-native analog of the reference's NNVM op registry
(reference include/mxnet/op_attr_types.h:33-63, `NNVM_REGISTER_OP` sites in
src/operator/tensor/*) merged with the legacy `OperatorProperty` layer-op
registry (reference include/mxnet/operator.h:538).

Design: each op is registered ONCE as a pure JAX function plus metadata.
  * `fn(*inputs, **attrs)` — the FCompute analog; consumes/produces
    `jax.Array`s and is traceable, so the same definition serves the
    imperative path (`mx.nd.*`, eager JAX dispatch ≙ ThreadedEngine push)
    and the symbolic path (graph node interpreted under `jax.jit` ≙
    GraphExecutor bulk-exec, reference src/executor/graph_executor.cc:1094).
  * `FGradient` is *not* a registry attr: gradients come from JAX AD.
    Ops whose reference backward ignores head gradients (SoftmaxOutput and
    friends, reference src/operator/softmax_output-inl.h) wrap their fn in
    `jax.custom_vjp` at definition site.
  * `inputs` / `aux` name lists ≙ FListInputNames / ListAuxiliaryStates —
    used by Symbol to auto-create variable nodes.
  * `infer_shape` ≙ FInferShape: bidirectional shape inference needed to
    materialize parameter shapes from data shapes in `simple_bind`
    (reference src/executor/graph_executor.cc:793-806).  Ops without one
    are inferred forward-only via `jax.eval_shape` (XLA does the rest).
  * `num_aux_out`: ops that mutate auxiliary state during training
    (BatchNorm moving stats) return `num_aux_out` extra arrays; the
    executor threads them back (reference FMutateInputs).
"""
from __future__ import annotations

__all__ = ["Op", "register", "get_op", "list_ops", "OP_REGISTRY"]

OP_REGISTRY = {}


class Op:
    """Metadata for one registered operator."""

    __slots__ = (
        "name",
        "fn",
        "inputs",
        "inputs_for",
        "aux",
        "num_outputs",
        "infer_shape",
        "aliases",
        "need_is_train",
        "num_aux_out",
        "need_rng",
        "need_mesh",
        "input_axes",
        "variadic",
        "lift_floats",
        "doc",
        "params",
    )

    def __init__(
        self,
        name,
        fn,
        inputs=("data",),
        inputs_for=None,
        aux=(),
        num_outputs=1,
        infer_shape=None,
        aliases=(),
        need_is_train=False,
        num_aux_out=0,
        need_rng=False,
        need_mesh=False,
        input_axes=None,
        variadic=False,
        lift_floats=False,
        doc="",
        params=None,
    ):
        self.name = name
        self.fn = fn
        self.inputs = tuple(inputs)
        # inputs_for(attrs) -> the slots THIS node takes, for an op whose
        # operand list depends on its attributes beyond a trailing bias
        # (MoE: gated experts add a third matrix, no_bias drops three
        # vectors from the middle); `inputs` stays the default list
        self.inputs_for = inputs_for
        self.aux = tuple(aux)
        self.num_outputs = num_outputs
        self.infer_shape = infer_shape
        self.aliases = tuple(aliases)
        self.need_is_train = need_is_train
        self.num_aux_out = num_aux_out
        self.need_rng = need_rng
        # need_mesh: fn takes mesh= (the executor's device mesh) so the op
        # can place GSPMD sharding constraints (e.g. MoE's 'expert' axis)
        self.need_mesh = need_mesh
        # input_axes: {input_name: mesh_axis} — parameters feeding these
        # slots are sharded dim-0 over that axis AT REST when the bound
        # mesh carries it (executor picks this up; the EP memory scaling)
        self.input_axes = dict(input_axes or {})
        self.variadic = variadic
        # lift_floats: this op's kernel tolerates float attrs arriving as
        # jit TRACERS (it never calls float()/int() on them), so lazy
        # fusion (lazy.py) may lift them to traced operands and share one
        # compiled executable across scalar values.  Ops left at False
        # get float attrs embedded statically — still fused, but each
        # value keys its own program.
        self.lift_floats = lift_floats
        self.doc = doc
        # declarative parameter specs (dmlc::Parameter analog, ops/params.py)
        self.params = params


    def list_inputs(self, attrs):
        """Input slot names of a node of this op with attributes `attrs`
        (FListInputNames, which takes the attrs in the reference too)."""
        if self.inputs_for is None:
            return self.inputs
        return tuple(self.inputs_for(attrs))


def register(name, **kwargs):
    """Decorator registering `fn` as operator `name`.

    Extra keyword arguments are forwarded to :class:`Op`.
    """

    def _reg(fn):
        op = Op(name, fn, doc=fn.__doc__ or "", **kwargs)
        OP_REGISTRY[name] = op
        for alias in op.aliases:
            OP_REGISTRY[alias] = op
        return fn

    return _reg


def get_op(name):
    if name not in OP_REGISTRY:
        raise KeyError("Operator %s is not registered" % name)
    return OP_REGISTRY[name]


def list_ops():
    return sorted(OP_REGISTRY.keys())
