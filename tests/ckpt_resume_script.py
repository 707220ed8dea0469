"""Worker for tests/test_ckpt.py bit-parity resume pins (ISSUE 16).

One process = one leg of the kill/resume experiment on a shared
deterministic regression problem:

* ``--mode full``   — the uninterrupted reference: train end to end with
  NO checkpointing and print one ``CKPTSTEP`` line per device dispatch.
* ``--mode kill``   — train WITH async checkpoints armed and die by
  ``os._exit(9)`` (no finalize, no atexit — the SIGKILL analog) after
  ``--kill-after`` dispatches.
* ``--mode resume`` — a FRESH process resumes from the kill run's
  checkpoint directory (``fit(resume_from=...)``) and prints the
  remaining dispatches.

The test asserts every resumed ``CKPTSTEP`` line is byte-identical to
the reference line for the same ``(k, epoch, batch)`` — the exact-resume
contract of docs/checkpoint.md — for both the per-step (K=1) and the
fused K=2 dispatch paths.

Per-dispatch losses use the read-then-reset idiom: the callback reads
the metric and resets it, so each value is that dispatch's OWN loss.
(An epoch-cumulative metric could never match across a mid-epoch resume
— the resumed run restarts accumulation at the resume batch.)
"""
import argparse
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


def run(mx, np, k, tag, ckpt_dir=None, resume_from=None, kill_after=0):
    from mxnet_tpu.ops.random_ops import HOST_RNG

    mx.random.seed(0)
    HOST_RNG.seed(123)
    it, net = build_problem(mx, np)
    mod = mx.mod.Module(net, label_names=("lro_label",), context=mx.cpu())
    ndisp = [0]

    def on_batch(param):
        for _, val in param.eval_metric.get_name_value():
            # ONE atomic write per dispatch, flushed immediately: the
            # kill leg dies mid-run and its earlier lines must survive
            sys.stdout.write(
                "CKPTSTEP tag=%s k=%d epoch=%d batch=%d loss=%.10e\n"
                % (tag, k, param.epoch, param.nbatch, val))
            sys.stdout.flush()
        param.eval_metric.reset()
        ndisp[0] += 1
        if kill_after and ndisp[0] >= kill_after:
            os._exit(9)

    mod.fit(it, num_epoch=2, kvstore=None, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            initializer=mx.init.Xavier(), eval_metric="mse",
            steps_per_dispatch=k, batch_end_callback=on_batch,
            checkpoint_dir=ckpt_dir,
            checkpoint_every_steps=1 if ckpt_dir else 0,
            resume_from=resume_from)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--mode", choices=("full", "kill", "resume"),
                        required=True)
    parser.add_argument("--k", default="1",
                        help="comma-separated steps_per_dispatch values")
    parser.add_argument("--ckpt-dir", default="",
                        help="comma-separated checkpoint dirs, parallel "
                             "to --k (kill/resume modes)")
    parser.add_argument("--kill-after", type=int, default=0,
                        help="die after this many dispatches (kill mode)")
    args = parser.parse_args()

    import numpy as np

    import mxnet_tpu as mx

    ks = [int(v) for v in args.k.split(",")]
    dirs = [d for d in args.ckpt_dir.split(",") if d]
    for i, k in enumerate(ks):
        if args.mode == "full":
            run(mx, np, k, "full")
        elif args.mode == "kill":
            run(mx, np, k, "kill", ckpt_dir=dirs[i],
                kill_after=args.kill_after)
        else:
            # resume re-arms checkpointing on the same directory, like
            # the real relaunch path, and restores via the strict
            # explicit-argument route
            run(mx, np, k, "resume", ckpt_dir=dirs[i], resume_from=dirs[i])
    sys.stdout.write("DONE mode=%s\n" % args.mode)
    sys.stdout.flush()


if __name__ == "__main__":
    main()
