#!/usr/bin/env python
"""Analytic multi-chip scaling model from the compiled SPMD program.

The reference publishes measured 1..256-GPU scaling for ResNet training
(reference example/image-classification/README.md:277-305) and BASELINE.md
gates this repo at >=70% efficiency at 64 chips.  Multi-chip hardware is
not available here, but the SPMD partitioner IS: this tool compiles the
actual DP (and DPxTP) ResNet-50 training step for mesh sizes 8/16/64 on
virtual CPU devices, COUNTS the collective traffic in the optimized HLO,
and models step time against TPU v5e interconnect bandwidth.

    python tools/scaling_model.py --mesh 8            # one mesh, JSON
    python tools/scaling_model.py --sweep 8,16,64     # table for SCALING.md

Outputs per mesh: per-chip FLOPs (XLA cost analysis), per-collective
payload bytes from the HLO (all-reduce / all-gather / reduce-scatter /
all-to-all / collective-permute), the analytic expectation (ring
all-reduce of the gradient bytes: 2(n-1)/n x params), and predicted step
time / scaling efficiency under the bandwidth model in SCALING.md.

The HLO byte-counting is validated against the analytic formula by
tests/test_scaling_model.py on the 8-device CPU mesh.
"""
import argparse
import json
import os
import re
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# ---- v5e model constants (documented in SCALING.md) ---------------------
from tpu_constants import V5E_DCN_BW, V5E_ICI_BW, V5E_PEAK_FLOPS  # noqa: E402,F401

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "u64": 8, "s64": 8,
                "u32": 4, "s32": 4, "u16": 2, "s16": 2, "u8": 1, "s8": 1,
                "pred": 1, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def collective_bytes(hlo_text):
    """Per-kind result-payload bytes of every collective in optimized HLO.

    Handles tuple-typed collectives (XLA fuses many gradient all-reduces
    into one tuple all-reduce).  Returns {kind: bytes}; bytes are the
    RESULT buffer sizes — the ring-traffic factors (2(n-1)/n for
    all-reduce etc.) are applied by the model, not here."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    # '%name = TYPE <op>(' where TYPE is 'f32[8,16]{...}' or a tuple;
    # async pairs count the -start half only (the -done carries no new
    # traffic), so TPU-style async lowering is not undercounted
    pat = re.compile(
        r"= *((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\]\S*)) +(%s)(?:-start)?\(" %
        "|".join(_COLLECTIVES))
    ty = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
    for m in pat.finditer(hlo_text):
        tystr, kind = m.group(1), m.group(2)
        total = 0
        for t in ty.finditer(tystr):
            dt, dims = t.group(1), t.group(2)
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES[dt]
        out[kind] += total
        counts[kind] += 1
    out = {k: v for k, v in out.items() if v}
    return out, {k: v for k, v in counts.items() if v}


def _compile_step(n_devices, tp, batch_per_chip=32, depth=50, image=224,
                  classes=1000):
    """Compile the DP (or DPxTP) train step on an n-device mesh; return
    (per-chip flops, collective bytes, param bytes, hlo len)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import mxnet_tpu as mx
    from mxnet_tpu.executor import _run_graph
    from mxnet_tpu.models.resnet import resnet

    devices = jax.devices()[:n_devices]
    assert len(devices) == n_devices, \
        "need %d devices, have %d" % (n_devices, len(jax.devices()))
    if tp:
        assert n_devices % 4 == 0
        mesh = Mesh(np.array(devices).reshape(n_devices // 4, 4),
                    ("data", "model"))
    else:
        mesh = Mesh(np.array(devices), ("data",))
    dp = mesh.shape["data"]
    batch = batch_per_chip * dp

    net = resnet(depth, num_classes=classes,
                 image_shape=(3, image, image))
    exe = net.simple_bind(mx.cpu(), data=(batch, 3, image, image),
                          softmax_label=(batch,),
                          compute_dtype="bfloat16")
    an, xn = exe._arg_names, exe._aux_names
    entries, order = exe._entries, exe._order
    cast = exe._cast()
    diff_names = [n for n in an if n not in ("data", "softmax_label")]
    diff_idx = [an.index(n) for n in diff_names]
    nondiff_idx = [i for i in range(len(an)) if i not in diff_idx]

    def train_step(dv, ndv, aux, lr):
        def fwd(d):
            vals = [None] * len(an)
            for i, v in zip(diff_idx, d):
                vals[i] = v
            for i, v in zip(nondiff_idx, ndv):
                vals[i] = v
            return _run_graph(entries, order, an, xn, tuple(vals), aux,
                              True, None, cast=cast)

        (outs, aux_upd), vjp_fn = jax.vjp(fwd, dv)
        cots = tuple(jnp.ones_like(o) for o in outs)
        (grads,) = vjp_fn((cots, tuple(jnp.zeros_like(a) for a in aux_upd)))
        return tuple(p - lr * g for p, g in zip(dv, grads)), aux_upd

    repl = NamedSharding(mesh, P())
    data_sh = NamedSharding(mesh, P("data"))

    def aval(arr, sh):
        return jax.ShapeDtypeStruct(arr.shape, arr.dtype, sharding=sh)

    args = exe._gather_args()
    param_bytes = 0
    dv_avals = []
    for name in diff_names:
        v = args[an.index(name)]
        sh = repl
        if tp and name in ("fc1_weight",):
            sh = NamedSharding(mesh, P("model", None))
        elif tp and name in ("fc1_bias",):
            sh = NamedSharding(mesh, P("model"))
        else:
            param_bytes += v.size * v.dtype.itemsize
        dv_avals.append(aval(v, sh))
    ndv_avals = tuple(aval(args[i], data_sh) for i in nondiff_idx)
    aux_avals = tuple(aval(a, repl) for a in exe._gather_aux())

    with mesh:
        lowered = jax.jit(train_step).lower(
            tuple(dv_avals), ndv_avals, aux_avals,
            jax.ShapeDtypeStruct((), jnp.float32))
        compiled = lowered.compile()
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    flops = float(ca.get("flops", 0.0))
    hlo = compiled.as_text()
    coll, counts = collective_bytes(hlo)
    # a DP step with no detected all-reduce means the parser missed the
    # lowering (e.g. a new async form) — fail loudly, never publish a
    # zero-traffic "perfect scaling" record
    assert coll.get("all-reduce") or coll.get("reduce-scatter"), \
        "no gradient collective found in HLO — parser out of date?"
    return {"n_devices": n_devices, "tp": tp, "dp": dp,
            "batch_per_chip": batch_per_chip, "global_batch": batch,
            "per_chip_flops": flops, "replicated_param_bytes": param_bytes,
            "collective_result_bytes": coll, "collective_counts": counts}


def load_bandwidth(path=None):
    """Measured bandwidth anchors from BANDWIDTH.json (written by
    `tools/bandwidth/measure.py --artifact`, schema-checked).  Returns
    None when the artifact is absent; raises on a torn/invalid file —
    modeling silently from garbage is worse than not modeling."""
    if path is None:
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "BANDWIDTH.json")
    if not os.path.exists(path):
        return None
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "bandwidth"))
    import measure

    return measure.load_artifact(path)


def analyze(rec, measured_1chip_img_s=2502.0, w_ici=None):
    """Apply the bandwidth model; see SCALING.md for the derivation.

    `w_ici` overrides the assumed per-chip all-reduce bandwidth with a
    MEASURED constant (bytes/s, e.g. BANDWIDTH.json's
    allreduce.gbps_per_device * 1e9) — the DP rows re-derive from
    evidence instead of the spec-sheet assumption; the record carries
    w_ici_gbps + w_source so tables state which one they used."""
    w = V5E_ICI_BW if w_ici is None else float(w_ici)
    rec["w_ici_gbps"] = round(w / 1e9, 3)
    rec["w_source"] = "assumed" if w_ici is None else "measured"
    n = rec["n_devices"]
    bpc = rec["batch_per_chip"]
    # compute time at this per-chip batch from the measured 1-chip rate
    t_comp = bpc / measured_1chip_img_s
    cb = rec["collective_result_bytes"]
    # ring traffic per chip: all-reduce moves 2(n-1)/n x payload, gather/
    # scatter (n-1)/n, all-to-all (n-1)/n, permute 1x
    ring = {"all-reduce": 2.0 * (n - 1) / n, "all-gather": (n - 1) / n,
            "reduce-scatter": (n - 1) / n, "all-to-all": (n - 1) / n,
            "collective-permute": 1.0}
    traffic = sum(v * ring[k] for k, v in cb.items())
    t_comm_ici = traffic / w
    # overlap: XLA overlaps the gradient all-reduce with remaining backward
    # compute; bound efficiency between zero and full overlap
    t_no = t_comp + t_comm_ici
    t_full = max(t_comp, t_comm_ici)
    rec.update({
        "per_chip_traffic_bytes": int(traffic),
        "t_compute_s": round(t_comp, 5),
        "t_comm_ici_s": round(t_comm_ici, 5),
        "efficiency_no_overlap": round(t_comp / t_no, 4),
        "efficiency_full_overlap": round(t_comp / t_full, 4),
        "img_s_no_overlap": round(n * bpc / t_no, 1),
        "img_s_full_overlap": round(n * bpc / t_full, 1),
    })
    return rec


def _lower_text_and_flops(jitted, *args, mesh=None):
    import contextlib

    cm = mesh or contextlib.nullcontext()
    with cm:
        compiled = jitted.lower(*args).compile()
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    return compiled.as_text(), float(ca.get("flops", 0.0))


def _compile_pp(n_devices, stages=4, microbatches=8, rows_per_replica=8,
                hidden=2048):
    """PipelineModule leg: count the schedule's ppermute ring traffic and
    combine with the simulator's bubble fraction.

    The x/g boundary rings live INSIDE the schedule's lax.scan, so the
    HLO counts each permute once — multiply by the schedule step count.
    """
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.parallel.mesh import make_mesh

    devices = jax.devices()[:n_devices]
    dp = n_devices // stages
    mesh = make_mesh({"data": dp, "pipe": stages} if dp > 1
                     else {"pipe": stages}, devices=devices)
    batch = rows_per_replica * microbatches * max(dp, 1)

    def stage(i):
        x = mx.sym.Variable("data")
        x = mx.sym.FullyConnected(x, num_hidden=hidden, name="fc%da" % i)
        x = mx.sym.Activation(x, act_type="relu")
        x = mx.sym.FullyConnected(x, num_hidden=hidden, name="fc%db" % i)
        x = mx.sym.Activation(x, act_type="relu")
        if i == stages - 1:
            x = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
                x, num_hidden=128, name="head"), name="softmax")
        return x

    mod = mx.mod.PipelineModule(stage, num_stages=stages,
                                num_microbatches=microbatches, mesh=mesh,
                                schedule="1f1b")
    mod.bind(data_shapes=[("data", (batch, hidden))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    mbs, labs = mod._split_host(
        np.zeros((batch, hidden), np.float32),
        np.zeros((batch,), np.float32))
    jf = mod._get_train_jit()
    text, flops = _lower_text_and_flops(
        jf, mod._buffer, mod._aux_buffer, mod._opt_state, mbs, labs,
        jnp.asarray([0], jnp.uint32), jnp.float32(0.1), jnp.float32(0.0),
        jnp.uint32(1))
    coll, counts = collective_bytes(text)
    st = mod.schedule_stats
    trip = int(mod._sched.num_steps)
    assert coll.get("collective-permute"), \
        "no ppermute found in the pipeline HLO — parser out of date?"
    return {"leg": "pp", "n_devices": n_devices, "stages": stages,
            "dp": dp, "microbatches": microbatches,
            "global_batch": batch, "hidden": hidden,
            "boundary_floats": int(mod._bmax),
            "per_chip_flops": flops,
            "collective_result_bytes": coll, "collective_counts": counts,
            "scan_trip_count": trip,
            "bubble_fraction": float(st["bubble_fraction"]),
            "stash_slots": int(st["max_stash_slots"])}


def _compile_ep(n_devices, experts=4, d_model=1024, hidden=2048,
                tokens_per_replica=256, capacity_factor=2.0):
    """Expert-parallel leg on the EXPLICIT all_to_all path
    (parallel/moe.py moe_sharded): count the token dispatch/combine
    all_to_all traffic of a full grad step.

    The library path is the modeling object because its collectives are
    hand-written `lax.all_to_all` — the GSPMD path (mx.sym.MoE) leaves
    the resharding strategy to the partitioner, which on the CPU backend
    lowers it as all-gather+all-reduce (observed; the analytic all_to_all
    volume is the TPU lower bound either way)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from mxnet_tpu.parallel.mesh import P, make_mesh
    from mxnet_tpu.parallel.moe import moe_sharded

    devices = jax.devices()[:n_devices]
    dp = n_devices // experts
    mesh = make_mesh({"data": dp, "expert": experts} if dp > 1
                     else {"expert": experts}, devices=devices)
    data_axis = "data" if dp > 1 else None
    tokens = tokens_per_replica * max(dp, 1)

    def expert_fn(p, x):
        h = jax.nn.relu(x @ p["w1"] + p["b1"])
        return h @ p["w2"] + p["b2"]

    params = {
        "w1": jnp.zeros((experts, d_model, hidden), jnp.float32),
        "b1": jnp.zeros((experts, hidden), jnp.float32),
        "w2": jnp.zeros((experts, hidden, d_model), jnp.float32),
        "b2": jnp.zeros((experts, d_model), jnp.float32),
    }

    def train_step(p, gate_w, x, lr):
        def loss(pp, gw):
            y = moe_sharded(mesh, expert_fn, pp, x, gw, k=2,
                            capacity_factor=capacity_factor,
                            data_axis=data_axis)
            return jnp.mean(y ** 2)

        gp, gg = jax.grad(loss, argnums=(0, 1))(p, gate_w)
        newp = jax.tree_util.tree_map(lambda w, g: w - lr * g, p, gp)
        return newp, gate_w - lr * gg

    pspec = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, P("expert"))),
        params)
    tok_axes = P((data_axis, "expert")) if data_axis else P("expert")
    x_aval = jax.ShapeDtypeStruct((tokens, d_model), jnp.float32,
                                  sharding=NamedSharding(mesh, tok_axes))
    gw_aval = jax.ShapeDtypeStruct((d_model, experts), jnp.float32,
                                   sharding=NamedSharding(mesh, P()))
    text, flops = _lower_text_and_flops(
        jax.jit(train_step), pspec, gw_aval, x_aval,
        jax.ShapeDtypeStruct((), jnp.float32), mesh=mesh)
    coll, counts = collective_bytes(text)
    assert coll.get("all-to-all"), \
        "no all_to_all found in the MoE HLO — parser out of date?"
    return {"leg": "ep", "n_devices": n_devices, "experts": experts,
            "dp": dp, "d_model": d_model, "hidden": hidden,
            "tokens_per_replica": tokens_per_replica,
            "capacity_factor": capacity_factor,
            "per_chip_flops": flops,
            "collective_result_bytes": coll,
            "collective_counts": counts, "scan_trip_count": 1}


def _compile_sp(n_devices, seq_shards=4, seq=1024, heads=8, head_dim=64,
                batch_per_replica=4):
    """mx.sym.RingAttention leg: count the ring K/V ppermute traffic (the
    ring lives inside a scan — multiply by its trip count)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    import mxnet_tpu as mx
    from mxnet_tpu.executor import _run_graph
    from mxnet_tpu.parallel.mesh import P, make_mesh

    devices = jax.devices()[:n_devices]
    dp = n_devices // seq_shards
    mesh = make_mesh({"data": dp, "seq": seq_shards} if dp > 1
                     else {"seq": seq_shards}, devices=devices)
    batch = batch_per_replica * max(dp, 1)
    D = heads * head_dim

    def net():
        x = mx.sym.Variable("data")
        qkv = mx.sym.FullyConnected(x, num_hidden=3 * D, flatten=False,
                                    name="qkv")
        qkv = mx.sym.reshape(qkv, shape=(0, seq, heads, 3 * head_dim))
        q = mx.sym.slice_axis(qkv, axis=3, begin=0, end=head_dim)
        k = mx.sym.slice_axis(qkv, axis=3, begin=head_dim,
                              end=2 * head_dim)
        v = mx.sym.slice_axis(qkv, axis=3, begin=2 * head_dim,
                              end=3 * head_dim)
        a = mx.sym.RingAttention(q, k, v, causal=True, name="attn")
        a = mx.sym.reshape(a, shape=(0, seq, D))
        # mean-pool the sequence before the head so head params stay
        # O(D) — a flattened [seq*D] head would add an unrealistic
        # multi-hundred-MB parameter whose DP all-reduce drowns the
        # ring-attention traffic this leg exists to count
        a = mx.sym.mean(a, axis=1)
        return mx.sym.SoftmaxOutput(
            mx.sym.FullyConnected(a, num_hidden=128, name="out_fc"),
            name="softmax")

    exe = net().simple_bind(mx.cpu(), mesh=mesh, data=(batch, seq, D),
                            softmax_label=(batch,))
    an, xn = exe._arg_names, exe._aux_names
    entries, order = exe._entries, exe._order
    diff_idx = [an.index(nm) for nm in an
                if nm not in ("data", "softmax_label")]
    nondiff_idx = [i for i in range(len(an)) if i not in diff_idx]

    def train_step(dv, ndv, lr):
        def fwd(d):
            vals = [None] * len(an)
            for i, v in zip(diff_idx, d):
                vals[i] = v
            for i, v in zip(nondiff_idx, ndv):
                vals[i] = v
            outs, _ = _run_graph(entries, order, an, xn, tuple(vals), (),
                                 True, None, mesh=mesh)
            return outs
        outs, vjp_fn = jax.vjp(fwd, dv)
        (grads,) = vjp_fn(tuple(jnp.ones_like(o) for o in outs))
        return tuple(p - lr * g for p, g in zip(dv, grads))

    repl = NamedSharding(mesh, P())
    data_sh = NamedSharding(
        mesh, P("data", "seq") if dp > 1 else P(None, "seq"))
    label_sh = NamedSharding(mesh, P("data") if dp > 1 else P())
    args = exe._gather_args()

    def aval(arr, sh):
        return jax.ShapeDtypeStruct(arr.shape, arr.dtype, sharding=sh)

    dv_avals = tuple(aval(args[an.index(nm)], repl) for nm in an
                     if nm not in ("data", "softmax_label"))
    ndv_avals = tuple(
        aval(args[i], data_sh if an[i] == "data" else label_sh)
        for i in nondiff_idx)
    text, flops = _lower_text_and_flops(
        jax.jit(train_step), dv_avals, ndv_avals,
        jax.ShapeDtypeStruct((), jnp.float32), mesh=mesh)
    coll, counts = collective_bytes(text)
    assert coll.get("collective-permute"), \
        "no ring permute found in the RingAttention HLO"
    return {"leg": "sp", "n_devices": n_devices, "seq_shards": seq_shards,
            "dp": dp, "seq": seq, "heads": heads, "head_dim": head_dim,
            "batch_per_replica": batch_per_replica,
            "per_chip_flops": flops,
            "collective_result_bytes": coll,
            "collective_counts": counts,
            # the K/V ring advances once per scan tick; each rank sends
            # its block seq_shards-1 times per traversal
            "scan_trip_count": seq_shards - 1}


def analyze_axis(rec, effective_flops=0.305 * V5E_PEAK_FLOPS):
    """Bandwidth model for the PP/EP/SP legs.

    Two traffic components are reported SEPARATELY:
      * axis traffic — the collectives the axis itself introduces
        (boundary ppermute for PP, token all_to_all for EP, K/V ring
        for SP); `efficiency_axis` charges only these (+ the PP bubble),
        i.e. the marginal cost of turning the axis on.
      * the data-parallel gradient all-reduce, which these toy configs
        exaggerate (tiny per-replica batch vs full param set) and which
        the DP section of SCALING.md models properly.
    XLA cost analysis counts a lax.scan body ONCE, so per-leg
    corrections apply: pp flops x microbatches (the schedule runs F+B
    once per microbatch) and permute bytes x num_steps; sp permute
    bytes x ring hops.  Each leg also reports its analytic BALANCE
    threshold — the knob value at which the axis turns compute-bound on
    v5e ICI at the sustained rate."""
    cb = rec["collective_result_bytes"]
    trip = rec.get("scan_trip_count", 1)
    axis_kind = {"pp": "collective-permute", "ep": "all-to-all",
                 "sp": "collective-permute"}[rec["leg"]]
    # ring factors use the size of the GROUP each collective spans, not
    # the whole device count: the axis collectives run over their own
    # mesh axis (experts for the MoE all_to_all; permutes move one hop
    # regardless), and the gradient all-reduce spans the 'data' axis
    g_axis = {"pp": rec.get("stages", 1), "ep": rec.get("experts", 1),
              "sp": rec.get("seq_shards", 1)}[rec["leg"]]
    dp = max(rec.get("dp", 1), 1)
    axis_factor = {"collective-permute": 1.0,
                   "all-to-all": (g_axis - 1) / g_axis}[axis_kind]
    dp_ring = {"all-reduce": 2.0 * (dp - 1) / dp,
               "all-gather": (dp - 1) / dp,
               "reduce-scatter": (dp - 1) / dp,
               "all-to-all": (dp - 1) / dp,
               "collective-permute": 1.0}
    axis_traffic = cb.get(axis_kind, 0) * axis_factor * \
        (trip if axis_kind == "collective-permute" else 1)
    other_traffic = sum(v * dp_ring[k] for k, v in cb.items()
                        if k != axis_kind)
    balance = effective_flops / V5E_ICI_BW
    flops = rec["per_chip_flops"]
    if rec["leg"] == "pp":
        flops *= rec["microbatches"]
    elif rec["leg"] == "sp":
        flops *= trip  # ring body runs once per hop (upper bound incl.
        #                the out-of-scan qkv/head, over-counted (hops-1)x)
    t_comp = flops / effective_flops
    t_axis = axis_traffic / V5E_ICI_BW
    eff_axis = t_comp / (t_comp + t_axis)
    if rec["leg"] == "pp":
        eff_axis *= (1.0 - rec["bubble_fraction"])
        rec["efficiency_bubble_only"] = round(
            1.0 - rec["bubble_fraction"], 4)
    if rec["leg"] == "sp":
        rec["balance_seq_per_shard"] = int(2 * balance)
        rec["seq_per_shard"] = rec["seq"] // rec["seq_shards"]
    if rec["leg"] == "ep":
        rec["balance_hidden"] = int(2 * balance)
    rec.update({
        "axis_traffic_bytes": int(axis_traffic),
        "dp_grad_traffic_bytes": int(other_traffic),
        "t_compute_s": round(t_comp, 6),
        "t_axis_comm_s": round(t_axis, 6),
        "efficiency_axis": round(eff_axis, 4),
        "machine_balance_flop_per_byte": int(balance),
    })
    return rec


def run_child(n, tp, batch_per_chip, depth, image, classes):
    env = dict(os.environ)
    for k in list(env):
        if k.startswith("TPU_"):
            env.pop(k)
    env["JAX_PLATFORMS"] = "cpu"
    flags = " ".join(f for f in env.get("XLA_FLAGS", "").split()
                     if not f.startswith("--xla_force_host_platform_device_count"))
    env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=%d"
                        % n).strip()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--mesh", str(n),
         "--batch-per-chip", str(batch_per_chip), "--depth", str(depth),
         "--image", str(image), "--classes", str(classes)] +
        (["--leg", tp] if isinstance(tp, str) else
         (["--tp"] if tp else [])),
        env=env, capture_output=True, text=True, timeout=3600, cwd=REPO)
    if proc.returncode != 0:
        raise RuntimeError(proc.stdout + proc.stderr)
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--mesh", type=int, default=None,
                   help="child mode: compile on THIS process's devices")
    p.add_argument("--tp", action="store_true")
    p.add_argument("--leg", default=None,
                   help="pp | ep | sp (parallelism-axis legs)")
    p.add_argument("--sweep", default=None, help="e.g. 8,16,64")
    p.add_argument("--batch-per-chip", type=int, default=32)
    p.add_argument("--depth", type=int, default=50)
    p.add_argument("--image", type=int, default=224)
    p.add_argument("--classes", type=int, default=1000)
    p.add_argument("--out", default="SCALING.json")
    p.add_argument("--use-measured", action="store_true",
                   help="anchor the DP rows to BANDWIDTH.json's measured "
                        "all-reduce GB/s (tools/bandwidth/measure.py "
                        "--artifact) instead of the assumed W_ici, and "
                        "print the assumed-vs-measured delta")
    args = p.parse_args()
    w_measured = None
    if args.use_measured:
        bw = load_bandwidth()
        if bw is None:
            p.error("--use-measured: no BANDWIDTH.json found — run "
                    "python tools/bandwidth/measure.py --artifact "
                    "BANDWIDTH.json first")
        w_measured = bw["allreduce"]["gbps_per_device"] * 1e9
        print("# measured anchor: %s all-reduce %.3f GB/s/device "
              "(x%d devices, BANDWIDTH.json) vs assumed W_ici %.1f GB/s "
              "-> delta %.1fx"
              % (bw["platform"], w_measured / 1e9,
                 bw["allreduce"]["devices"], V5E_ICI_BW / 1e9,
                 V5E_ICI_BW / w_measured), flush=True)

    if args.mesh is not None:
        import jax

        jax.config.update("jax_platforms", "cpu")
        if args.leg == "pp":
            rec = _compile_pp(args.mesh)
        elif args.leg == "ep":
            rec = _compile_ep(args.mesh)
        elif args.leg == "sp":
            rec = _compile_sp(args.mesh)
        else:
            rec = _compile_step(args.mesh, args.tp, args.batch_per_chip,
                                args.depth, args.image, args.classes)
        print(json.dumps(rec))
        return

    sizes = [int(s) for s in (args.sweep or "8,16,64").split(",")]
    recs = []
    for n in sizes:
        for tp in (False, True):
            if tp and n % 4:
                continue
            rec = analyze(run_child(n, tp, args.batch_per_chip, args.depth,
                                    args.image, args.classes),
                          w_ici=w_measured)
            recs.append(rec)
            print(json.dumps(rec), flush=True)
        for leg in ("pp", "ep", "sp"):
            if n % 4:
                continue
            rec = analyze_axis(run_child(n, leg, args.batch_per_chip,
                                         args.depth, args.image,
                                         args.classes))
            recs.append(rec)
            print(json.dumps(rec), flush=True)
    with open(args.out, "w") as f:
        json.dump(recs, f, indent=1)


if __name__ == "__main__":
    main()
