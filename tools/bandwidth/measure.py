#!/usr/bin/env python
"""Communication bandwidth benchmark (parity: reference
tools/bandwidth/measure.py — "GB/s per GPU per kvstore type", README:30-40).

Measures the gradient-aggregation path for a model-sized parameter set:

  * kv_store='device'    — ICI/XLA all-reduce over the device mesh (the
    SPMD path that replaced CommDevice P2P reduction)
  * kv_store='local'     — in-process KVStore push/pull façade
  * kv_store='dist_sync' — TCP parameter-server push+pull (needs the
    launcher env, tools/launch.py)

plus the host<->device legs (`measure_h2d_d2h`): the `device_put` and
host-readback bandwidth the input pipeline and metric path ride.

Reports per-device algorithm bandwidth 2(n-1)/n * bytes / time — the
convention the reference README uses, comparable to its ~11.1 GB/s
resnet-200 number.

Every measurement is gated against a PLATFORM-AWARE sanity floor
(an order of magnitude under credible hardware, so a broken transfer
path measuring ~0 GB/s fails loudly — the old gate was
`gbps_per_device > 0`, a tautology), and `--artifact BANDWIDTH.json`
records the numbers ATOMICALLY (temp file + rename, schema-checked) so
`tools/scaling_model.py --use-measured` and SCALING.md anchor their
projections to measured constants instead of assumptions
(docs/distributed.md "Bandwidth anchors").
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

# runnable from any cwd (the reference tool is invoked standalone)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

SCHEMA_VERSION = 1

# sanity floors in GB/s, deliberately ~10x under credible hardware for
# the platform: they catch a broken/zero measurement, not a slow run
FLOORS = {
    # platform: (h2d, d2h, collective per-device)
    "cpu": (0.05, 0.05, 0.01),
    "tpu": (0.5, 0.5, 1.0),
    "gpu": (0.5, 0.5, 1.0),
}


def _floor(platform, kind):
    h2d, d2h, coll = FLOORS.get(platform, FLOORS["cpu"])
    return {"h2d": h2d, "d2h": d2h, "collective": coll}[kind]


def _check_floor(gbps, platform, kind, check=True):
    if not check:
        return
    floor = _floor(platform, kind)
    if not gbps >= floor:
        raise RuntimeError(
            "measured %s bandwidth %.4f GB/s is under the %s sanity "
            "floor %.3f GB/s — the transfer path is broken (or pass "
            "check=False for exploratory runs)" % (kind, gbps, platform,
                                                   floor))


def _platform():
    import jax

    return jax.devices()[0].platform


def _param_sizes(network, num_layers):
    """Parameter element-counts for a named model (no compute, just shapes)."""
    import mxnet_tpu as mx
    from mxnet_tpu import models

    builders = {
        "resnet": lambda: models.resnet.resnet(num_layers or 50),
        "vgg": lambda: models.get_vgg(num_layers=num_layers or 16),
        "alexnet": models.get_alexnet,
        "inception-v3": models.get_inception_v3,
        "lenet": models.get_lenet,
        "mlp": models.get_mlp,
    }
    net = builders[network]()
    image = (3, 299, 299) if network == "inception-v3" else (
        (1, 28, 28) if network in ("lenet", "mlp") else (3, 224, 224))
    if network == "mlp":
        arg_shapes, _, _ = net.infer_shape(data=(1, 784))
    else:
        arg_shapes, _, _ = net.infer_shape(data=(1,) + image)
    names = net.list_arguments()
    return [(n, int(np.prod(s))) for n, s in zip(names, arg_shapes)
            if n not in ("data", "softmax_label")]


def measure_h2d_d2h(size_mb=64.0, num_iters=10, check=True):
    """Host->device (`device_put`) and device->host (np.asarray readback)
    bandwidth for one contiguous buffer — the staging pipeline's legs
    (io.stage_put / update_metric readback)."""
    import jax

    dev = jax.devices()[0]
    n = max(1, int(size_mb * 1e6 / 4))
    host = np.random.RandomState(0).rand(n).astype(np.float32)
    jax.block_until_ready(jax.device_put(host, dev))  # warm the path
    t0 = time.time()
    bufs = []
    for _ in range(num_iters):
        bufs.append(jax.block_until_ready(jax.device_put(host, dev)))
    t_h2d = (time.time() - t0) / num_iters
    t0 = time.time()
    for b in bufs:
        # np.array (copy) — np.asarray of a CPU-backend jax array is
        # ZERO-COPY and would report absurd teraherz "bandwidth"; the
        # copy measures the real readback the metric path pays
        np.array(b)
    t_d2h = (time.time() - t0) / num_iters
    nbytes = host.nbytes
    platform = _platform()
    res = {"bytes": nbytes, "platform": platform,
           "h2d_gbps": nbytes / t_h2d / 1e9,
           "d2h_gbps": nbytes / t_d2h / 1e9,
           "h2d_time_s": t_h2d, "d2h_time_s": t_d2h}
    _check_floor(res["h2d_gbps"], platform, "h2d", check)
    _check_floor(res["d2h_gbps"], platform, "d2h", check)
    return res


def measure_device_allreduce(sizes, num_iters=10, devices=None, check=True):
    """All-reduce bandwidth over the mesh (the kvstore='device' data path)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel.collectives import mesh_allreduce
    from mxnet_tpu.parallel.mesh import data_parallel_mesh

    devices = devices or jax.devices()
    n = len(devices)
    if n < 2:
        raise RuntimeError("need >= 2 devices for allreduce bandwidth")
    mesh = data_parallel_mesh(devices)
    arrays = [jnp.zeros((n, max(1, sz // n)), jnp.float32) for _, sz in sizes]
    total_bytes = sum(a.nbytes for a in arrays)

    def run():
        outs = mesh_allreduce(mesh, arrays)
        jax.block_until_ready(outs)

    run()  # compile
    t0 = time.time()
    for _ in range(num_iters):
        run()
    dt = (time.time() - t0) / num_iters
    algo_bytes = 2.0 * (n - 1) / n * total_bytes
    res = {"kv_store": "device", "devices": n, "bytes": total_bytes,
           "time_s": dt, "gbps_per_device": algo_bytes / dt / 1e9,
           "platform": _platform()}
    _check_floor(res["gbps_per_device"], res["platform"], "collective",
                 check)
    return res


def measure_kvstore(kv_type, sizes, num_iters=10, check=True):
    """Push+pull bandwidth through the KVStore API (local or dist_*)."""
    import mxnet_tpu as mx

    kv = mx.kv.create(kv_type)
    arrays = [mx.nd.ones((sz,)) for _, sz in sizes]
    outs = [mx.nd.zeros((sz,)) for _, sz in sizes]
    for i, a in enumerate(arrays):
        kv.init(i, a)
    total_bytes = sum(4 * sz for _, sz in sizes)

    def run():
        for i, (a, o) in enumerate(zip(arrays, outs)):
            kv.push(i, a)
            kv.pull(i, o)
        outs[0].wait_to_read()

    run()
    t0 = time.time()
    for _ in range(num_iters):
        run()
    dt = (time.time() - t0) / num_iters
    nw = getattr(kv, "num_workers", 1)
    res = {"kv_store": kv_type, "workers": nw, "bytes": total_bytes,
           "time_s": dt, "gbps_per_device": 2.0 * total_bytes / dt / 1e9,
           "platform": _platform()}
    # the kvstore façade copies through host memory: gate it with the
    # host-transfer floor, not the on-chip collective floor
    _check_floor(res["gbps_per_device"], res["platform"], "h2d", check)
    return res


# ----------------------------------------------------------------------
# BANDWIDTH.json artifact — the measured anchors SCALING.md loads
# ----------------------------------------------------------------------

_REQUIRED = {
    "schema_version": int,
    "platform": str,
    "device_count": int,
    "generated_by": str,
    "h2d_gbps": float,
    "d2h_gbps": float,
    "allreduce": dict,
}


def validate_artifact(doc):
    """Schema check for BANDWIDTH.json; raises ValueError on mismatch
    (consumers must never model from a half-written or foreign file)."""
    if not isinstance(doc, dict):
        raise ValueError("BANDWIDTH artifact must be a JSON object")
    for key, typ in _REQUIRED.items():
        if key not in doc:
            raise ValueError("BANDWIDTH artifact missing %r" % key)
        if not isinstance(doc[key], typ):
            raise ValueError("BANDWIDTH artifact %r must be %s, got %r"
                             % (key, typ.__name__, type(doc[key]).__name__))
    if doc["schema_version"] != SCHEMA_VERSION:
        raise ValueError("BANDWIDTH artifact schema_version %r != %d"
                         % (doc["schema_version"], SCHEMA_VERSION))
    ar = doc["allreduce"]
    for key in ("devices", "bytes", "time_s", "gbps_per_device"):
        if key not in ar:
            raise ValueError("BANDWIDTH allreduce record missing %r" % key)
    return doc


def write_artifact(path, doc):
    """Atomic write: temp file in the destination directory + rename, so
    a crashed run can never leave a torn/half-schema BANDWIDTH.json for
    the scaling model to load."""
    validate_artifact(doc)
    dirname = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(prefix=".bandwidth_", suffix=".json",
                               dir=dirname)
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return path


def load_artifact(path):
    """Read + schema-check an artifact; raises on any mismatch."""
    with open(path) as f:
        return validate_artifact(json.load(f))


def collect_artifact(sizes, num_iters=10, h2d_mb=64.0, check=True):
    """Run the measured legs and assemble the artifact document."""
    import jax

    host = measure_h2d_d2h(size_mb=h2d_mb, num_iters=num_iters, check=check)
    ar = measure_device_allreduce(sizes, num_iters=num_iters, check=check)
    return {
        "schema_version": SCHEMA_VERSION,
        "platform": host["platform"],
        "device_count": len(jax.devices()),
        "generated_by": "tools/bandwidth/measure.py",
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "h2d_gbps": float(host["h2d_gbps"]),
        "d2h_gbps": float(host["d2h_gbps"]),
        "h2d_bytes": int(host["bytes"]),
        "allreduce": {k: ar[k] for k in
                      ("devices", "bytes", "time_s", "gbps_per_device")},
    }


def main():
    parser = argparse.ArgumentParser(description="measure comm bandwidth")
    parser.add_argument("--network", type=str, default="resnet")
    parser.add_argument("--num-layers", type=int, default=50)
    parser.add_argument("--kv-store", type=str, default="device",
                        choices=["device", "local", "dist_sync",
                                 "dist_async", "h2d"])
    parser.add_argument("--num-iters", type=int, default=10)
    parser.add_argument("--size-mb", type=float, default=0,
                        help="override: one flat buffer of this size")
    parser.add_argument("--artifact", type=str, default=None,
                        help="ALSO measure h2d/d2h + device all-reduce "
                             "and write the schema-checked BANDWIDTH.json "
                             "here (atomic temp-file + rename); "
                             "SCALING.md's model loads it via "
                             "scaling_model.py --use-measured")
    parser.add_argument("--no-check", action="store_true",
                        help="skip the platform-aware bandwidth floors "
                             "(exploratory runs on odd hardware)")
    args = parser.parse_args()
    check = not args.no_check
    if args.size_mb > 0:
        sizes = [("flat", int(args.size_mb * 1e6 / 4))]
    else:
        sizes = _param_sizes(args.network, args.num_layers)
    if args.artifact:
        doc = collect_artifact(sizes, args.num_iters, check=check)
        write_artifact(args.artifact, doc)
        print("wrote %s: platform=%s h2d=%.2f GB/s d2h=%.2f GB/s "
              "allreduce=%.2f GB/s/device x%d"
              % (args.artifact, doc["platform"], doc["h2d_gbps"],
                 doc["d2h_gbps"], doc["allreduce"]["gbps_per_device"],
                 doc["allreduce"]["devices"]))
        return
    if args.kv_store == "h2d":
        res = measure_h2d_d2h(size_mb=args.size_mb or 64.0,
                              num_iters=args.num_iters, check=check)
        print("h2d: %.1f MB, %.2f GB/s to device, %.2f GB/s to host"
              % (res["bytes"] / 1e6, res["h2d_gbps"], res["d2h_gbps"]))
        return
    if args.kv_store == "device":
        res = measure_device_allreduce(sizes, args.num_iters, check=check)
    else:
        res = measure_kvstore(args.kv_store, sizes, args.num_iters,
                              check=check)
    print("%s: %d params, %.1f MB, %.3f ms/round, %.2f GB/s per device"
          % (res["kv_store"], len(sizes), res["bytes"] / 1e6,
             res["time_s"] * 1e3, res["gbps_per_device"]))


if __name__ == "__main__":
    main()
