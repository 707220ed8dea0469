"""Worker for tests/test_spmd_runtime.py: one rank of a multi-process
`Module.fit` job launched by `tools/launch.py --local-spmd -n 2`.

Each process joins the jax.distributed mesh (multihost.initialize reads
the launcher env), builds the hierarchical global mesh, and runs the
REAL training stack — Module.fit, per step or (--steps-per-dispatch K)
DeviceStagedIter -> K-step fused dispatch, the gradient all-reduce the
one XLA's partitioner inserts — on a shared deterministic problem.  It
records per-dispatch loss values and a final parameter digest — in a
file of its own under --record-dir, else on stdout; the test asserts
every rank agrees and matches the single-process answer.

With --kvstore-check (launcher run with PS roles, -s > 0) it ALSO runs
a dist_sync push/pull parity pin through the SAME processes: the
reference-style parameter-server control plane and the SPMD mesh ride
one launcher invocation.
"""
import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_problem(mx, np):
    rng = np.random.RandomState(7)
    X = rng.randn(64, 12).astype(np.float32)
    w = rng.randn(12, 1).astype(np.float32)
    y = (X @ w + 0.1 * rng.randn(64, 1)).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=16, label_name="lro_label")
    d = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(d, num_hidden=8, name="fc1")
    a = mx.sym.Activation(h, act_type="tanh")
    o = mx.sym.FullyConnected(a, num_hidden=1, name="fc2")
    net = mx.sym.LinearRegressionOutput(o, name="lro")
    return it, net


def build_lm_problem(mx, np):
    """Deterministic next-token LM batches for the transformer parity
    pin: tokens follow ``next = (prev * 7 + 3) % (V - 2) + 2``, labels
    are the inputs shifted left (causal LM convention)."""
    rng = np.random.RandomState(11)
    V, N, T = 24, 64, 8
    data = np.empty((N, T + 1), np.float32)
    data[:, 0] = rng.randint(2, V, size=N)
    for t in range(T):
        data[:, t + 1] = (data[:, t] * 7 + 3) % (V - 2) + 2
    it = mx.io.NDArrayIter(data[:, :T], data[:, 1:],
                           batch_size=16, label_name="softmax_label")
    from mxnet_tpu.models import TransformerLM

    lm = TransformerLM(vocab=V, num_layers=2, num_heads=2, d_model=32,
                       max_len=T)
    return it, lm.training_symbol()


def run_fit_transformer(mx, np, mesh, steps_per_dispatch):
    """The transformer flavor of run_fit: the SAME training stack,
    driven by the attention graph instead of the MLP (the SPMD pin for
    the transformer rows)."""
    from mxnet_tpu.ops.random_ops import HOST_RNG

    mx.random.seed(0)
    HOST_RNG.seed(123)
    it, net = build_lm_problem(mx, np)
    mod = mx.mod.Module(net, label_names=("softmax_label",),
                        context=mx.cpu(), mesh=mesh)
    losses = []

    def on_batch(param):
        for name, val in param.eval_metric.get_name_value():
            losses.append(val)

    mod.fit(it, num_epoch=2, kvstore=None, optimizer="adam",
            optimizer_params={"learning_rate": 3e-3},
            initializer=mx.init.Xavier(rnd_type="gaussian",
                                       factor_type="in", magnitude=2.34),
            eval_metric=mx.metric.Perplexity(None),
            steps_per_dispatch=steps_per_dispatch,
            batch_end_callback=on_batch)
    args, _ = mod.get_params()
    digest = np.concatenate([args[n].asnumpy().ravel()
                             for n in sorted(args)])
    return losses, digest


def run_fit(mx, np, mesh, steps_per_dispatch):
    from mxnet_tpu.ops.random_ops import HOST_RNG

    mx.random.seed(0)
    HOST_RNG.seed(123)
    it, net = build_problem(mx, np)
    mod = mx.mod.Module(net, label_names=("lro_label",), context=mx.cpu(),
                        mesh=mesh)
    losses = []

    def on_batch(param):
        for name, val in param.eval_metric.get_name_value():
            losses.append(val)

    mod.fit(it, num_epoch=2, kvstore=None, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            initializer=mx.init.Xavier(), eval_metric="mse",
            steps_per_dispatch=steps_per_dispatch,
            batch_end_callback=on_batch)
    args, _ = mod.get_params()
    digest = np.concatenate([args[n].asnumpy().ravel()
                             for n in sorted(args)])
    return losses, digest


def kvstore_check(mx, np, rank):
    kv = mx.kv.create("dist_sync")
    shape = (5, 7)
    kv.init("spmd_key", mx.nd.ones(shape))
    kv.push("spmd_key", mx.nd.ones(shape) * (kv.rank + 1))
    out = mx.nd.zeros(shape)
    kv.pull("spmd_key", out=out)
    expect = sum(r + 1 for r in range(kv.num_workers))
    got = out.asnumpy()
    assert np.allclose(got, expect), (got.ravel()[:4], expect)
    kv.close()
    # ONE write, line end included: the ranks share the launcher's pipe,
    # and `print` writes its text and its newline apart, between which the
    # other rank's line can land (seen under tier-1's load, PR 34)
    sys.stdout.write("KVOK rank=%d sum=%.1f\n"
                     % (rank, float(got.ravel()[0])))
    sys.stdout.flush()


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps-per-dispatch", type=int, default=1)
    parser.add_argument("--kvstore-check", action="store_true")
    parser.add_argument("--transformer", action="store_true",
                        help="train the TransformerLM problem instead of "
                             "the MLP (the transformer SPMD parity pin)")
    parser.add_argument("--no-fit", action="store_true",
                        help="skip the training run (fast control-plane-"
                             "only checks)")
    parser.add_argument("--record-dir", default="",
                        help="write this rank's fit record to "
                             "<dir>/spmd_fit.r<rank>.json instead of "
                             "stdout, which the ranks, the launcher and "
                             "Gloo's own messages share")
    parser.add_argument("--profile", default="",
                        help="profile the fit and dump a chrome trace to "
                             "this path (auto-suffixed .r<rank> per "
                             "process; stitch with tools/obs_stitch.py)")
    args = parser.parse_args()

    from mxnet_tpu.parallel import multihost

    multihost.initialize()

    import jax
    import numpy as np

    import mxnet_tpu as mx

    rank = jax.process_index()
    mesh = multihost.global_mesh(hierarchical=True)
    if args.profile:
        from mxnet_tpu import profiler

        profiler.profiler_set_config(mode="all", filename=args.profile)
        profiler.profiler_set_state("run")
    if not args.no_fit:
        fit = run_fit_transformer if args.transformer else run_fit
        losses, digest = fit(mx, np, mesh, args.steps_per_dispatch)
        record = {"rank": rank, "axes": list(mesh.axis_names),
                  "losses": ["%.6f" % l for l in losses],
                  "digest": ["%.6f" % v for v in digest]}
        if args.record_dir:
            # a file a rank, renamed into place when whole: a record (a
            # full parameter digest) is far larger than PIPE_BUF, and on
            # the shared stdout pipe the other rank's lines and Gloo's
            # "[Gloo] Rank ... connected" messages land inside it
            path = os.path.join(args.record_dir,
                                "spmd_fit.r%d.json" % rank)
            with open(path + ".tmp", "w") as f:
                json.dump(record, f)
            os.replace(path + ".tmp", path)
        else:
            sys.stdout.write("SPMDFIT %s\n" % json.dumps(record))
            sys.stdout.flush()
    else:
        sys.stdout.write("SPMDMESH rank=%d axes=%s devices=%d\n"
                         % (rank, ",".join(mesh.axis_names),
                            jax.device_count()))
        sys.stdout.flush()
    if args.profile:
        from mxnet_tpu import profiler

        profiler.profiler_set_state("stop")
        sys.stdout.write("PROFILE rank=%d path=%s\n"
                         % (rank, profiler.dump_profile()))
        sys.stdout.flush()
    if args.kvstore_check:
        kvstore_check(mx, np, rank)
    multihost.sync_global_devices("spmd_fit_done")


if __name__ == "__main__":
    main()
