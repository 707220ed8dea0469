"""Mesh-aware parallel layers as first-class symbol operators.

`MoE` (expert parallelism) and `RingAttention` (sequence/context
parallelism): ordinary `mx.sym` ops that detect the bound Module's mesh
axes ('expert' / 'seq') and lower to the parallel path automatically —
the user-API surface over parallel/moe.py and parallel/ring_attention.py.

MoE — Mixture-of-Experts FFN as a first-class symbol operator.

Expert parallelism from the USER API: `mx.sym.MoE(data, num_experts=8,
hidden_size=1024, k=2)` inside an ordinary model file, trained with
`Module(mesh=make_mesh({'data': d, 'expert': e}))`.  No reference
counterpart exists (SURVEY.md §2.5 marks EP absent from the 2017
reference); the design is the GShard/GSPMD dense-einsum formulation:

  * with `capacity_factor`: capacity-bounded top-k routing (parallel/
    moe.py top_k_gating — the SAME router as the shard_map library path,
    so both lower identically)
  * dispatch/combine einsums over a static [T, E, C] routing tensor —
    shape-static, fully differentiable (gate gradients flow through the
    combine weights), one XLA program
  * `with_sharding_constraint` pins expert-major tensors to the 'expert'
    mesh axis; GSPMD inserts the all_to_all that moves token slots to
    expert owners and back — the collective the library path writes by
    hand (parallel/moe.py lax.all_to_all), here compiler-derived
  * expert parameters are sharded dim-0 over 'expert' AT REST via
    Op.input_axes (executor.py picks it up), so expert memory scales 1/E

Without a mesh (or without an 'expert' axis) the same math runs dense —
single-device numerics are identical by construction.

Without `capacity_factor` the op is the DROPLESS layer open decoders
run (OLMoE: `gated`, `no_bias`, `act_type="silu"`, `normalize=False`):
sort-and-segment (parallel/moe.py dropless_experts), no [T, E, C].  That
form also takes what later routers brought: `score_func="sigmoid"`, a
`select_bias` operand that moves the choice and not the weights,
`route_scale`, an always-on shared expert (`shared_size`) with a sigmoid
gate of its own (`shared_gate`),
`held_first` / `held_count` — WHICH of the `num_experts` the expert
operands are, one chip's share of an expert-parallel layer — and
`router_input`, one more operand that the router scores in `data`'s place.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register
from .tensor import _lit


def _moe_inputs(attrs):
    """data, the router (and its selection bias), then expert matrix i
    (and its bias): 1 = in, 2 = out, 3 = the gated branch's second
    in-projection; then the shared expert's matrices in the same order,
    and its gate's ``(D, 1)`` column; last, where the node says `router_input`,
    the rows the router scores in `data`'s place."""
    gated = _bool_attr(attrs.get("gated", False))
    no_bias = _bool_attr(attrs.get("no_bias", False))
    names = ["data", "gate_weight"]
    if _bool_attr(attrs.get("select_bias", False)):
        names.append("select_bias")
    for i in (1, 2, 3) if gated else (1, 2):
        names.append("expert%d_weight" % i)
        if not no_bias:
            names.append("expert%d_bias" % i)
    if int(_lit(attrs.get("shared_size", 0))):
        names += ["shared%d_weight" % i for i in ((1, 2, 3) if gated
                                                  else (1, 2))]
    if _bool_attr(attrs.get("shared_gate", False)):
        names.append("shared_gate_weight")
    if _bool_attr(attrs.get("router_input", False)):
        names.append("router_data")
    return names


def _held(attrs):
    """(first, count) of the experts the expert operands are: all
    `num_experts` unless the node says `held_count`."""
    total = int(_lit(attrs["num_experts"]))
    count = attrs.get("held_count")
    if count is None:
        return 0, total
    return int(_lit(attrs.get("held_first", 0))), int(_lit(count))


def _moe_outputs(attrs):
    return 2 if _bool_attr(attrs.get("return_load", False)) else 1


def _infer_moe(in_shapes, attrs):
    data = in_shapes[0]
    zero = int(_lit(attrs.get("zero_experts", 0)))
    # the router scores the zero-compute experts beside the real ones
    total = int(_lit(attrs["num_experts"])) + zero
    E = _held(attrs)[1]
    H = int(_lit(attrs["hidden_size"]))
    S = int(_lit(attrs.get("shared_size", 0)))
    D = data[-1]
    by_slot = {"data": data, "gate_weight": (D, total),
               "select_bias": (total,),
               "expert1_weight": (E, D, H), "expert1_bias": (E, H),
               "expert2_weight": (E, H, D), "expert2_bias": (E, D),
               "expert3_weight": (E, D, H), "expert3_bias": (E, H),
               "shared1_weight": (D, S), "shared2_weight": (S, D),
               "shared3_weight": (D, S), "shared_gate_weight": (D, 1),
               "router_data": data}
    outs = [tuple(data)] + [(E + bool(zero),)] * (_moe_outputs(attrs) - 1)
    return [by_slot[n] for n in _moe_inputs(attrs)], outs


def _constrain(x, mesh, spec):
    from jax.sharding import NamedSharding

    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


@register(
    "MoE",
    inputs=("data", "gate_weight", "expert1_weight", "expert1_bias",
            "expert2_weight", "expert2_bias"),
    inputs_for=_moe_inputs,
    num_outputs=_moe_outputs,
    aliases=("_contrib_MoE",),
    infer_shape=_infer_moe,
    need_mesh=True,
    input_axes={"expert%d_%s" % (i, w): "expert"
                for i in (1, 2, 3) for w in ("weight", "bias")},
)
def moe(data, gate_weight, *operands, num_experts, hidden_size, k=2,
        capacity_factor=None, act_type="relu", gated=False, no_bias=False,
        normalize=True, return_load=False, score_func="softmax",
        select_bias=False, route_scale=1.0, shared_size=0,
        shared_gate=False, router_input=False, zero_experts=0, mesh=None,
        **kw):
    """Top-k routed expert FFN: out[t] = sum_e gate[t,e] * FFN_e(x[t])
    over t's top-k experts, FFN_e = ``act(x w1 + b1) @ w2 + b2``, or with
    `gated` ``(act(x w1 + b1) * (x w3 + b3)) @ w2 + b2``; `no_bias`
    leaves the vectors out (the operands are then the matrices alone).
    `normalize` renormalises the router's softmax scores over the
    experts a token keeps; false uses them as they are.

    Without `capacity_factor` the layer is DROPLESS (parallel/moe.py
    dropless_experts: sort by expert, one segment matmul an expert).
    With it, each expert holds ``capacity_factor * k * T / E`` tokens
    and overflow tokens pass through with a zero expert term (Switch-
    Transformer semantics) — the shape-static GShard form whose
    expert-major tensors the 'expert' mesh axis shards.  The router runs
    in float32 at `highest` precision in both.  `return_load` adds a
    second output, tokens per expert [E].

    The dropless form's further options (absent = as before):
    `score_func` ``"sigmoid"`` scores each expert on its own;
    `select_bias` adds the operand ``select_bias [num_experts]`` to the
    scores for the CHOICE of the k alone; `route_scale` multiplies the
    weights; `shared_size` S adds ``shared{1,2,3}_weight`` (``[D, S]``,
    ``[S, D]``, ``[D, S]``), one more FFN of the same form that every
    token passes, unweighted — or, with `shared_gate`, times
    ``sigmoid(x w_s)``, ``w_s`` the last operand ``shared_gate_weight [D,
    1]`` (the score in float32 at `highest`, like the router's);
    `held_first` / `held_count` say that the
    expert operands are experts ``held_first .. held_first + held_count``
    of the `num_experts` the router scores — the choice and the weights
    stay over all of them, pairs of absent experts add nothing, and the
    load is over the experts held; `zero_experts` n makes the router
    ``num_experts + n`` wide, its last n columns zero-compute (identity)
    experts: chosen and weighed like any, they add ``(sum of their
    weights) * x`` and have no operand, a held range is over the
    `num_experts` real ones, and the load gains one entry, the pairs that
    chose one (``parallel.moe._dropless``)."""
    from ..parallel import moe as _moe
    from ..parallel.mesh import P

    E = int(_lit(num_experts))
    kk = int(_lit(k))
    gated, no_bias = _bool_attr(gated), _bool_attr(no_bias)
    normalize = _bool_attr(normalize)
    act = str(_lit(act_type))
    operands = list(operands)
    routed_on = operands.pop() if _bool_attr(router_input) else data
    bias = operands.pop(0) if _bool_attr(select_bias) else None
    shared, shared_score = (), None
    if _bool_attr(shared_gate):
        if not int(_lit(shared_size)):
            raise ValueError("MoE: shared_gate needs shared_size")
        shared_score = operands.pop()
    if int(_lit(shared_size)):
        n_shared = 3 if gated else 2
        operands, shared = operands[:-n_shared], operands[-n_shared:]
    step = 1 if no_bias else 2
    weights = operands[0::step]
    biases = None if no_bias else operands[1::step]
    held = _held(dict(kw, num_experts=num_experts))
    options = dict(score=str(_lit(score_func)), select_bias=bias,
                   scale=float(_lit(route_scale)),
                   held=None if held == (0, E) else held)
    zero = int(_lit(zero_experts))
    if zero:
        options["zero_experts"] = zero
    if capacity_factor is not None and (
            shared or bias is not None or options["held"]
            or zero
            or routed_on is not data
            or (options["score"], options["scale"]) != ("softmax", 1.0)):
        raise ValueError("MoE: score_func, select_bias, route_scale, "
                         "shared_size, held_count, zero_experts and "
                         "router_input are the dropless form's (no "
                         "capacity_factor)")
    lead = data.shape[:-1]
    d_model = data.shape[-1]
    x = data.reshape(-1, d_model)
    T = x.shape[0]

    with jax.named_scope("mx:moe.route"):
        logits = _moe.router_logits(routed_on.reshape(-1, d_model),
                                    gate_weight)
    if capacity_factor is None:
        with jax.named_scope("mx:moe.experts"):
            out, load = _moe.dropless_experts(
                x, logits, kk, weights, biases, act, gated, normalize,
                **options)
        if shared:
            with jax.named_scope("mx:moe.shared"):
                passed = _moe.expert_ffn(
                    lambda r, w: r @ w.astype(r.dtype), x, shared, None,
                    act, gated)
                if shared_score is not None:
                    passed = passed * jax.nn.sigmoid(_moe.router_logits(
                        x, shared_score)).astype(passed.dtype)
                out = out + passed
    else:
        capacity = max(1, int(float(_lit(capacity_factor)) * kk * T // E))
        ep = mesh is not None and "expert" in mesh.axis_names
        with jax.named_scope("mx:moe.route"):
            dispatch, combine = _moe.top_k_gating(
                logits, kk, capacity, normalize)               # [T, E, C]
        with jax.named_scope("mx:moe.experts"):
            f32 = jnp.float32
            xe = jnp.einsum("tec,td->ecd", dispatch, x.astype(f32))
            if ep:
                # expert-major tensors live on the 'expert' axis; GSPMD
                # derives the dispatch/return all_to_all from this
                # constraint pair
                xe = _constrain(xe, mesh, P("expert"))
            ye = _moe.expert_ffn(
                lambda r, w: jnp.einsum("eci,eio->eco", r, w.astype(f32)),
                xe, weights,
                None if no_bias else [b.astype(f32)[:, None, :]
                                      for b in biases],
                act, gated)
            if ep:
                ye = _constrain(ye, mesh, P("expert"))
            out = jnp.einsum("tec,ecd->td", combine, ye)
            load = dispatch.sum((0, 2))
    out = out.reshape(lead + (d_model,)).astype(data.dtype)
    return (out, load) if _bool_attr(return_load) else out


# ----------------------------------------------------------------------
# RingAttention — sequence parallelism from the symbol API
# ----------------------------------------------------------------------

def _infer_ring_attn(in_shapes, attrs):
    q = in_shapes[0]
    return [q, q, q], [tuple(q)]


@register(
    "RingAttention",
    inputs=("query", "key", "value"),
    aliases=("_contrib_RingAttention",),
    infer_shape=_infer_ring_attn,
    need_mesh=True,
)
def ring_attention_op(query, key, value, causal=False, scale=None,
                      impl="auto", mesh=None, **kw):
    """Attention over (B, T, H, D) that SHARDS THE SEQUENCE automatically:
    bound on a mesh with a 'seq' axis it runs ring attention (K/V shards
    rotating over ICI, flash-style online softmax — parallel/
    ring_attention.py), composing with 'data' batch sharding; `impl=
    'ulysses'` picks the all-to-all head/seq swap variant instead (better
    for many heads at moderate T).  Without a 'seq' axis it falls back to
    single-device blockwise attention — same numerics, O(T·block) memory.
    The long-context capability (SURVEY.md §5) as one symbol op."""
    from jax import lax as _lax

    from ..parallel import ring_attention as _ra
    from ..parallel.collectives import shard_map_unchecked
    from ..parallel.mesh import P

    causal = _bool_attr(causal)
    impl = str(_lit(impl))
    sc = float(_lit(scale)) if scale is not None else None
    b, t, h, d = query.shape

    sp = (mesh is not None and "seq" in mesh.axis_names
          and t % mesh.shape["seq"] == 0)
    if sp and impl == "ulysses" and h % mesh.shape["seq"] != 0:
        sp = False
    if not sp:
        blk = min(128, t)
        while t % blk:
            blk -= 1
        return _ra.blockwise_attention(query, key, value, blk,
                                       causal=causal, scale=sc)

    batch = "data" if "data" in mesh.axis_names else None
    spec = P(batch, "seq", None, None)
    fn = _ra.ulysses_attention if impl == "ulysses" else _ra.ring_attention

    def body(qs, ks, vs):
        return fn(qs, ks, vs, "seq", causal=causal, scale=sc)

    return shard_map_unchecked(body, mesh=mesh, in_specs=(spec, spec, spec),
                               out_specs=spec)(query, key, value)


def _bool_attr(v):
    v = _lit(v)
    if isinstance(v, str):
        return v.lower() in ("1", "true", "yes")
    return bool(v)
