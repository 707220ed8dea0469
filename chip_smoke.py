#!/usr/bin/env python3
"""The chip check: fit, serve and generate on the TPU, once, in one process.

    python chip_smoke.py

drives the system's main paths through the entry points a user calls, at
the full width of models the repo supports (ResNet-50 at 224^2 / 1000
classes, the 512-wide 4-layer TransformerLM), on weights and inputs made
from a seed — no network, no files, no child process (a chip belongs to
one process at a time).  Each phase checks what came out by the repo's
own means and raises on the first thing that is wrong; nothing catches a
failure and carries on.  Phases, each timed with XLA compile seconds
apart from the rest:

  fence     a chain of 8192^3 bf16 matmuls ended by block_until_ready:
            the implied TFLOP/s must not exceed the chip's published peak
            (a fence that returns early would "beat" it)
  train     ResNet-50 NHWC bf16 through Module.fit: steps at one step per
            dispatch (executor.fused_step), then blocks of K steps
            through DeviceStagedIter (executor.fused_block), each but
            the first dispatched before the one ahead of it is read
  serve     a ResNet-50 Predictor behind ModelServer, unbatched requests
            from several threads, results against Predictor.forward
  generate  TransformerLM via add_generative_tenant + submit_generate;
            one session's prefill/decode logits against the
            full-recompute score_symbol forward
  kv_ring   the decode programs of ten TransformerLMs shaped like the
            benchmark's decoders (32 heads of 64; 16 of 128; 32 query on
            8 K/V heads of 64 with rings of 2,304; a delta-rule layer of
            30 heads of 96 x 192 beside 30 heads of 128 with rings of
            2,304; a window layer's rings of 2,048 positions, which wrap,
            beside a full layer's of 6,144, 32 query on 4 K/V heads of
            128; a delta-rule layer of 16 q/k heads under 32 value heads
            of 128 x 128 beside a ring of 2 K/V heads of 256 — a head
            over two tiles of 128 lines — under 16 query heads, a quarter
            of each head rotated; two latent-attention layers of 32 heads
            over ONE ring of 320-wide rows and 6,144 positions each, the
            query of rank 1,024; a layer of 128 heads of 128 + 64 under a
            learned selection of 2,048 over a latent ring of 576-wide rows
            and 16,384 positions, with its index keys, beside a window
            layer that is latent too; 8 sessions, the sixth and seventh
            16, the eighth 4) as XLA compiled them: every cache_spec
            entry aliased to its output, no instruction that copies one,
            ONE attention kernel call an attention layer (a latent layer's
            is ops/latent_ring_kernel.py; the eighth's absorbed steps are
            jax.numpy and hold none), ONE step-kernel
            call a delta-rule layer (ops/gdn_step_kernel.py) and ONE a
            Mamba-2 layer (ops/ssm_step_kernel.py: the ninth and tenth,
            128 and 64 heads of 64 x 128 states beside an attention
            layer; the eleventh, PR 62, is LongCat-Flash's published layer
            as two — 8 heads whose value is narrower than their key, a
            routed layer with zero-compute experts carried across the
            second sublayer; the twelfth, PR 64, Nemotron-H's three kinds
            of layer of ONE sublayer each — a Mamba-2 mixer of eight
            groups, ungated squared-ReLU experts whose 2,048 bucket holds
            TWO grouped-matmul kernel calls a routed layer, NoPE attention
            of 32 heads over 2) and nothing of rows x page size beside it, and no ring
            or recurrent state fatter on the device than cache_spec states;
            prints the rings' on-device layout and the warm ms of the
            delta-rule and Mamba-2 models' decode step; and the 2,048-bucket
            prefill of the fourth and the sixth: ONE kernel call a
            delta-rule layer
            (ops/gdn_kernel.py), no triangular solve left in it and no
            array of the scan split into heads beside it (XLA's L2 norms,
            the repeat to value heads, the gated norm: the kernel's); no
            prefill program of a bucket whose attention is the blockwise
            kernel (ops/sdp_kernel.py: the 2,048 buckets) may hold an
            array of bucket x bucket scores, beside the ring copies; the
            eighth's 15,360-bucket prefill holds ONE masked kernel call
            (ops/masked_latent_kernel.py) for its selected layer and no
            array of a run's scores (heads x 512 x a run's keys); and
            every tenant's prefill bucket programs timed warm: none may
            run longer than 1.5 times the next larger bucket's (the
            first shape has OPT-1.3B's FFN of 8,192 and its four buckets)
  grouped_matmul  a routed FFN's three segment matmuls at the eight
            routed cells' prefill shapes, through lax.ragged_dot and
            through the TPU's kernel (ops/grouped_matmul_kernel.py): the
            same numbers, both timed warm — the table
            parallel.moe._KERNEL_ROWS was read from; where the rule takes
            the kernel it may not be the slower one.  Where every expert
            is held, the whole layer-piece beside it: the two calls that
            fetch and place their own rows against the three between a
            gather and an un-sort (PR 61), neither the slower one where
            parallel.moe.fused_tile takes them.  Where a held range walks
            passes, a pass's return to token order beside it: XLA's
            scatter-add against the kernel's row copies
            (ops/row_return_kernel.py, PR 63), the same sums, ms a call
            and us a row
  four_chips  (>= 4 devices) a 4-way data-parallel ResNet-50 fit and a
            Predictor bound to chip 3, in this same process

It exits non-zero, before any work, when JAX's default backend is not a
TPU.  Only when every phase ran and passed does it print, as the last
line of stdout, the result: one JSON object with exactly the keys "ok"
(true) and "device" ({"platform", "kind", "count"} as JAX reports them).
The line before it, "[chip_smoke] report {...}", carries the per-phase
seconds, compile seconds and facts.  The phase functions take
(sizes, ctx) so the tier-1 tests (tests/test_chip_smoke.py) call them
tiny on mx.cpu(); main() has no switch that skips the device check.
"""
import functools
import json
import math
import re
import sys
import threading
import time

FULL = {
    "fence": {"n": 8192, "chain": 8, "reps": 3},
    "train": {"depth": 50, "image": 224, "classes": 1000, "batch": 256,
              "k": 4, "blocks": 2, "seed": 0},
    "serve": {"depth": 50, "image": 224, "classes": 1000,
              "buckets": [8, 32], "requests": 64, "threads": 4,
              "wait_ms": 5.0, "seed": 1},
    "generate": {"vocab": 8192, "num_layers": 4, "num_heads": 8,
                 "d_model": 512, "max_len": 320, "max_sessions": 4,
                 "seq_buckets": [16, 64], "prompts": 8, "new_tokens": 32,
                 "check_steps": 8, "seed": 2},
    "kv_ring": {"vocab": 8192, "num_layers": 2, "d_model": 2048,
                "d_ff": 2048, "max_sessions": 8, "seq_buckets": [64],
                "seed": 5,
                "shapes": [dict(num_heads=32, max_len=768, d_ff=8192,
                                seq_buckets=[64, 128, 256, 512]),
                           dict(num_heads=16, max_len=768),
                           dict(num_heads=32, num_kv_heads=8,
                                max_len=2304),
                           # a delta-rule layer's 96 x (30 x 192) state
                           # beside a ring of 30 heads x 128, which the
                           # kernel reads 15 heads at a time
                           dict(d_model=3840, num_heads=30, max_len=2304,
                                seq_buckets=[64, 2048],
                                layer_types=["linear_attention",
                                             "attention"],
                                linear_heads=30, linear_key_dim=96,
                                linear_value_dim=192, norm="rms",
                                positions="none", bias=False,
                                block_norm="output"),
                           # a window layer's ring of 2,048 positions,
                           # which wraps, beside a full layer's of 6,144:
                           # 32 query heads of 128 over 4 K/V heads
                           dict(num_heads=32, num_kv_heads=4, head_dim=128,
                                max_len=6144, seq_buckets=[64, 2048],
                                layer_types=["window_attention",
                                             "attention"],
                                sliding_window=2048, norm="rms",
                                positions={"window_attention": "rotary"},
                                qk_norm="head", out_gate=True, bias=False),
                           # 16 q/k heads under 32 value heads of 128 x
                           # 128 (the kernel sees 32) beside a ring of 2
                           # K/V heads of 256, a head over two tiles
                           dict(num_heads=16, num_kv_heads=2, head_dim=256,
                                max_len=4096, seq_buckets=[64, 2048],
                                max_sessions=16,
                                layer_types=["linear_attention",
                                             "attention"],
                                linear_heads=32, linear_key_heads=16,
                                linear_key_dim=128, linear_value_dim=128,
                                linear_neg_eigval=False, norm="rms",
                                positions="rotary", rotary_dim=64,
                                qk_norm="head", out_gate=True, bias=False),
                           # latent attention: ONE ring a layer of
                           # 256 + 64 lines a position for all 32 heads,
                           # the decode step absorbed over it
                           dict(d_model=4096, num_heads=32, max_len=6144,
                                seq_buckets=[64, 2048], max_sessions=16,
                                layer_types=["latent_attention"] * 2,
                                latent_q_rank=1024, latent_kv_rank=256,
                                latent_nope_dim=64, latent_rope_dim=64,
                                latent_value_dim=128,
                                rope_scaling=dict(
                                    factor=128, beta_fast=32, beta_slow=1,
                                    original_max_position_embeddings=8192,
                                    mscale=1, mscale_all_dim=1),
                                attention_multiplier=0.195,
                                query_scale=(0.1, 8192), norm="rms",
                                positions="none", bias=False),
                           # a learned selection over a latent ring of
                           # 512 + 64 lines and 16,384 positions beside
                           # a window layer that is latent too (its ring
                           # of 512 positions whole tiles): 128 heads of
                           # 128 + 64 beside a value of 128 that keep
                           # 2,048 positions; the absorbed steps are
                           # jax.numpy, the 15,360-bucket's masked
                           # attention ONE blockwise kernel a layer
                           dict(num_heads=128, max_len=16384,
                                seq_buckets=[64, 15360], max_sessions=4,
                                layer_types=["sparse_latent_attention",
                                             "window_latent_attention"],
                                kind_specs={
                                    "sparse_latent_attention": dict(
                                        num_heads=128, q_rank=1024,
                                        kv_rank=512, nope_dim=128,
                                        rope_dim=64, value_dim=128,
                                        head_gate=True, index_heads=64,
                                        index_dim=128, index_topk=2048),
                                    "window_latent_attention": dict(
                                        num_heads=64, q_rank=1024,
                                        kv_rank=1024, nope_dim=192,
                                        rope_dim=64, value_dim=128,
                                        head_gate=True, window=512)},
                                norm="rms", positions="none",
                                bias=False),
                           # a Mamba-2 layer's state of 128 heads of 64 x
                           # 128 (granite-4.0-h-small's: a page of 4.19
                           # MB the step kernel takes 32 heads at a time)
                           # beside a ring of 8 K/V heads of 128
                           dict(d_model=4096, num_heads=32, num_kv_heads=8,
                                max_len=1536,
                                layer_types=["mamba", "attention"],
                                mamba_heads=128, mamba_head_dim=64,
                                mamba_state=128, norm="rms",
                                positions="none", bias=False),
                           # and of 64 heads (granite-4.0-h-micro's)
                           dict(num_heads=32, num_kv_heads=8, max_len=2304,
                                layer_types=["mamba", "attention"],
                                mamba_heads=64, mamba_head_dim=64,
                                mamba_state=128, norm="rms",
                                positions="none", bias=False),
                           # LongCat-Flash's published layer as two: 8
                           # held heads of 128 + 64 over a value of 128
                           # (the 2,048 bucket's blockwise kernel carries
                           # it at 192), rescaled latents, two rings of
                           # 512 + 64 lines, a routed layer of 8 held of
                           # 64 real experts and 32 zero-compute ones
                           # carried across the second sublayer
                           dict(num_heads=8, max_len=2304,
                                seq_buckets=[64, 2048],
                                layer_types=["latent_attention"] * 2,
                                latent_q_rank=1536, latent_kv_rank=512,
                                latent_nope_dim=128, latent_rope_dim=64,
                                latent_value_dim=128,
                                latent_lora_rescale=True, ffn="swiglu",
                                ffn_types=["shortcut", "dense"],
                                num_experts=64, zero_experts=32,
                                experts_per_token=12, expert_d_ff=512,
                                router_bias=True, route_scale=6.0,
                                held_experts=(0, 8), norm="rms",
                                positions="none", bias=False),
                           # Nemotron-H's layers of ONE sublayer (PR 64):
                           # a Mamba-2 mixer of 64 heads in EIGHT groups
                           # (the step kernel takes four whole groups a
                           # grid step, the gated norm goes by group), 8
                           # held of 32 UNGATED squared-ReLU experts of
                           # the published 1,856, which the layer STORES
                           # 1,920 wide, beside a shared 3,712 (two
                           # kernel calls a routed layer in the 2,048
                           # bucket, not three), NoPE attention of 32
                           # heads over 2 (a key block of 512)
                           # (rings of 4,608: the 0.66 MB conv window
                           # is then under the hundredth of the set that
                           # is judged — XLA may stage so small a buffer
                           # through its fast memory, S(1))
                           dict(num_layers=3, d_model=2688, num_heads=32,
                                num_kv_heads=2, head_dim=128, max_len=4608,
                                seq_buckets=[64, 2048],
                                layer_types=["mamba", "none", "attention"],
                                ffn_types=["none", "routed", "none"],
                                kind_specs={"mamba": dict(
                                    heads=64, head_dim=64, state=128,
                                    groups=8, chunk=128)},
                                num_experts=32, experts_per_token=6,
                                expert_d_ff=1856, shared_d_ff=3712,
                                router_score="sigmoid", router_bias=True,
                                route_norm=True, route_scale=2.5,
                                held_experts=(0, 8), expert_act="relu2",
                                expert_gated=False, norm="rms",
                                positions="none", bias=False,
                                tied_head=False)]},
    "four_chips": {"depth": 50, "image": 224, "classes": 1000,
                   "batch": 256, "steps": 3, "seed": 4},
    # the eight routed cells' expert layers at their prefill programs'
    # shapes: (tokens a call, experts a token, scored experts, held,
    # d_model, d_expert) as `tests/test_tpu_compile.py` PAIR_TILES has them
    # — a mixed step's bucket and its slots' rows, or a prefill's bucket
    "grouped_matmul": {"reps": 8, "seed": 6, "shapes": {
        "olmoe 136": (136, 8, 64, 64, 2048, 1024),
        "olmoe 264": (264, 8, 64, 64, 2048, 1024),
        "olmoe 520": (520, 8, 64, 64, 2048, 1024),
        "qwen3-next 2064": (2064, 10, 512, 128, 2048, 512),
        "glm-5 1032": (1032, 8, 256, 8, 6144, 2048),
        "longcat-flash 2056": (2056, 12, 768, 8, 6144, 2048),
        "granite-h-small 256": (256, 10, 72, 9, 4096, 768),
        "granite-h-small 512": (512, 10, 72, 9, 4096, 768),
        "granite-h-small 1024": (1024, 10, 72, 9, 4096, 768),
        "mistral-small-4 2048": (2048, 4, 128, 16, 4096, 2048),
        "trinity 2056": (2056, 8, 128, 64, 2048, 1024),
        "dots3 15360": (15360, 8, 256, 8, 5120, 1536),
        "smallthinker 9224": (9224, 6, 64, 64, 2560, 768),
        "smallthinker 10248": (10248, 6, 64, 64, 2560, 768),
        "smallthinker 8200": (8200, 6, 64, 64, 2560, 768)}},
}

# Tolerances.  On the TPU an f32 matmul/conv runs at default precision
# (bf16 passes), and two programs that compute the same thing at another
# batch or sequence shape may round differently, so outputs are compared
# by max |a - b| relative to max |reference| — never by argmax equality
# on random weights.
SERVE_RTOL = 2e-2      # served logits vs Predictor.forward at batch 1
GENERATE_RTOL = 5e-2   # KV prefill/decode logits vs full recompute


def _rel_err(got, ref):
    import numpy as np

    return float(np.abs(np.asarray(got, np.float64) - ref).max()
                 / max(float(np.abs(ref).max()), 1e-30))


def _check(cond, msg):
    # not `assert`: the checks must hold under python -O too
    if not cond:
        raise RuntimeError("chip_smoke check failed: " + msg)


class CompileClock:
    """Seconds XLA spent producing executables (a compile, or a
    persistent-cache retrieval), summed from JAX's own monitoring events.
    jit calls and the AOT wrapper's lower().compile() both report here."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == self.EVENT:
            with self._lock:
                self.seconds += duration


# ----------------------------------------------------------------------
# phases
# ----------------------------------------------------------------------

def phase_fence(sizes, ctx):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import telemetry

    n, chain = sizes["n"], sizes["chain"]
    dev = ctx.jax_device()
    # ones @ (1/n) == ones exactly (1/n is a power of two, f32 accumulate),
    # so the chain's value proves the matmuls ran
    a = jax.device_put(jnp.ones((n, n), jnp.bfloat16), dev)
    b = jax.device_put(jnp.full((n, n), 1.0 / n, jnp.bfloat16), dev)

    @jax.jit
    def run(a, b):
        x = a
        for _ in range(chain):
            x = jnp.dot(x, b)
        return x

    run(a, b).block_until_ready()  # compile
    best = float("inf")
    for _ in range(sizes["reps"]):
        t0 = time.perf_counter()
        out = run(a, b)
        out.block_until_ready()
        best = min(best, time.perf_counter() - t0)
    _check(float(out[0, 0]) == 1.0 and float(out[-1, -1]) == 1.0,
           "matmul chain value %r != 1.0" % float(out[0, 0]))
    tflops = chain * 2 * n ** 3 / best / 1e12
    peak = telemetry.peak_flops(dev)
    if dev.platform == "tpu":
        _check(peak is not None,
               "no published peak for device_kind %r in "
               "mxnet_tpu.telemetry.PEAK_FLOPS" % dev.device_kind)
    if peak is not None:
        _check(tflops * 1e12 <= peak,
               "fence implies %.1f TFLOP/s > the %.0f TFLOP/s peak of %s: "
               "block_until_ready returned before the device finished"
               % (tflops, peak / 1e12, dev.device_kind))
    return {"tflops": round(tflops, 1),
            "peak_tflops": None if peak is None else peak / 1e12,
            "seconds_per_chain": round(best, 5)}


def _resnet(sizes):
    from mxnet_tpu.models.resnet import resnet

    hw = sizes["image"]
    return resnet(sizes["depth"], num_classes=sizes["classes"],
                  image_shape=(3, hw, hw), layout="NHWC")


def _xavier(mx):
    return mx.init.Xavier(rnd_type="gaussian", factor_type="in", magnitude=2)


def _images(sizes, n):
    import numpy as np

    rng = np.random.default_rng(sizes["seed"])
    hw = sizes["image"]
    X = rng.standard_normal((n, hw, hw, 3), dtype=np.float32)
    y = rng.integers(0, sizes["classes"], n).astype("float32")
    return X, y


def _check_on_devices(exe, devices, what):
    """Every parameter and optimizer-state leaf of `exe` lives on exactly
    `devices` (one device; or the whole mesh, replicated)."""
    from mxnet_tpu.optimizer import _state_leaves

    diff_names = exe._fused_static[0]
    for name in diff_names:
        got = exe.arg_dict[name].data.devices()
        _check(got == devices, "%s: parameter %s on %s, expected %s"
               % (what, name, got, devices))
        state = exe._fused_updater.states[exe._fused_index_of_name[name]]
        for leaf in _state_leaves(state):
            got = leaf.data.devices()
            _check(got == devices, "%s: optimizer state of %s on %s, "
                   "expected %s" % (what, name, got, devices))
    return len(diff_names)


def _fit(mod, it, metric, num_epoch, k, batch_end_callback=None):
    mod.fit(it, eval_metric=metric, optimizer="sgd",
            optimizer_params={"learning_rate": 0.01, "momentum": 0.9},
            num_epoch=num_epoch, steps_per_dispatch=k,
            batch_end_callback=batch_end_callback)


def phase_train(sizes, ctx):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    B, K = sizes["batch"], sizes["k"]
    X, y = _images(sizes, B * K)  # one epoch = K steps = one K-block
    it = mx.io.NDArrayIter(X, y, batch_size=B)
    mx.random.seed(sizes["seed"])
    mod = mx.mod.Module(_resnet(sizes), context=ctx, compute_dtype="bfloat16")
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(_xavier(mx))
    exe = mod._exec_group.execs[0]
    w0 = exe.arg_dict["fc1_weight"].asnumpy()
    metric = mx.metric.CrossEntropy()

    def dispatches():
        return telemetry.counter_value("executor.train_dispatches")

    d0 = dispatches()
    _fit(mod, it, metric, num_epoch=1, k=1)
    _check(dispatches() - d0 == K, "K=1: %d dispatches for %d steps"
           % (dispatches() - d0, K))
    loss_step = float(metric.get()[1])
    _check(np.isfinite(loss_step), "K=1 loss %r" % loss_step)
    w1 = exe.arg_dict["fc1_weight"].asnumpy()
    _check(np.isfinite(w1).all() and np.abs(w1 - w0).max() > 0,
           "K=1 fit did not move fc1_weight")

    # the K-step loop: `blocks` passes over the data as ONE epoch (the
    # same batches in the same order as `blocks` epochs), each block's
    # loss read by its callback while the next is already dispatched
    losses = []

    def block_end(param):
        losses.append(float(metric.get()[1]))
        metric.reset()

    d0, ahead0 = dispatches(), telemetry.counter_value(
        "module.runahead_blocks")
    _fit(mod, mx.io.ResizeIter(it, K * sizes["blocks"]), metric, num_epoch=1,
         k=K, batch_end_callback=block_end)
    _check(dispatches() - d0 == sizes["blocks"] == len(losses),
           "K=%d: %d dispatches and %d callbacks for %d blocks"
           % (K, dispatches() - d0, len(losses), sizes["blocks"]))
    ahead = telemetry.counter_value("module.runahead_blocks") - ahead0
    _check(ahead == sizes["blocks"] - 1,
           "K=%d: %d of %d blocks were dispatched behind an unread one, "
           "expected all but the first" % (K, ahead, sizes["blocks"]))
    loss_block = losses[-1]
    _check(np.isfinite(losses).all(), "K=%d losses %r" % (K, losses))
    w2 = exe.arg_dict["fc1_weight"].asnumpy()
    _check(np.isfinite(w2).all() and np.abs(w2 - w1).max() > 0,
           "K=%d fit did not move fc1_weight" % K)
    _check(exe._jit_step is not None and len(exe._jit_block) > 0,
           "fused_step and fused_block did not both compile")
    n = _check_on_devices(exe, {ctx.jax_device()}, "train")
    return {"loss_k1": round(loss_step, 4),
            "loss_k%d" % K: round(loss_block, 4),
            "steps": K + K * sizes["blocks"], "runahead_blocks": ahead,
            "params_on_device": n,
            "mfu_gauge": telemetry.gauge_value("module.mfu")}


def _serve_predictor(mx, sizes, ctx):
    """A ResNet Predictor from a seeded random checkpoint, serving the
    logits beside the softmax (the logits are what gets compared)."""
    hw = sizes["image"]
    net = _resnet(sizes)
    mx.random.seed(sizes["seed"])
    mod = mx.mod.Module(net, context=ctx)
    mod.bind(data_shapes=[("data", (1, hw, hw, 3))], label_shapes=None,
             for_training=False)
    mod.init_params(_xavier(mx))
    arg, aux = mod.get_params()
    params = {"arg:%s" % k: v for k, v in arg.items()}
    params.update({"aux:%s" % k: v for k, v in aux.items()})
    return mx.Predictor(net, params, {"data": (1, hw, hw, 3)}, ctx=ctx,
                        output_names=["fc1_output", "softmax_output"])


def phase_serve(sizes, ctx):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    pred = _serve_predictor(mx, sizes, ctx)
    X, _ = _images(sizes, sizes["requests"])
    buckets = sizes["buckets"]
    server = mx.serving.ModelServer({"resnet": pred}, max_batch=buckets[-1],
                                    buckets=buckets,
                                    wait_ms=sizes["wait_ms"])
    try:
        server.warmup()
        programs = telemetry.counter_value("serving.bucket_programs")
        compiled = telemetry.counter_value("mem.programs_compiled")
        futures = [None] * len(X)

        def client(idx):
            for i in idx:
                futures[i] = server.submit("resnet", {"data": X[i]},
                                           timeout_ms=120000)

        # daemon: a client that did not finish fails the check below and
        # must not keep the interpreter from exiting
        threads = [threading.Thread(target=client, daemon=True,
                                    args=(range(t, len(X), sizes["threads"]),))
                   for t in range(sizes["threads"])]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
            _check(not t.is_alive(), "a client thread did not finish")
        outs = [f.result(timeout=300) for f in futures]
        _check(telemetry.counter_value("serving.bucket_programs") == programs
               and telemetry.counter_value("mem.programs_compiled")
               == compiled, "a program compiled after warm-up")
    finally:
        server.close()
    worst = 0.0
    for x, (logits, prob) in zip(X, outs):
        _check(logits.shape == (sizes["classes"],)
               and prob.shape == (sizes["classes"],), "output shapes %s %s"
               % (logits.shape, prob.shape))
        _check(np.isfinite(logits).all() and np.isfinite(prob).all()
               and abs(float(prob.sum()) - 1.0) < 1e-3,
               "served output not finite / not a distribution")
        pred.forward(data=x[None])
        worst = max(worst, _rel_err(logits, pred.get_output(0)[0]))
    _check(worst <= SERVE_RTOL, "served logits differ from Predictor."
           "forward by %.3g of max |logit| (tolerance %.3g)"
           % (worst, SERVE_RTOL))
    out_dev = pred._exec.outputs[0].data.devices()
    _check(out_dev == {ctx.jax_device()},
           "Predictor output on %s, context %s" % (out_dev, ctx))
    pred.close()
    return {"requests": len(outs), "bucket_programs": programs,
            "max_rel_err": float("%.3g" % worst)}


def phase_generate(sizes, ctx):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.models import TransformerLM
    from mxnet_tpu.obs import memory

    lm = TransformerLM(vocab=sizes["vocab"], num_layers=sizes["num_layers"],
                       num_heads=sizes["num_heads"], d_model=sizes["d_model"],
                       max_len=sizes["max_len"])
    mx.random.seed(sizes["seed"])
    mod = mx.mod.Module(lm.training_symbol(), data_names=("data",),
                        label_names=("softmax_label",), context=ctx)
    mod.bind(data_shapes=[("data", (2, 8))],
             label_shapes=[("softmax_label", (2, 8))])
    mod.init_params(_xavier(mx))
    arg, aux = mod.get_params()
    params = dict(arg)
    params.update(aux)

    dev = ctx.jax_device()
    limit = memory.budget_bytes(dev)
    if dev.platform == "tpu":
        # admission below runs against what the chip really has
        _check(limit and limit == dev.memory_stats()["bytes_limit"],
               "admission budget %r is not the device's bytes_limit" % limit)
    rng = np.random.RandomState(sizes["seed"])
    longest = sizes["seq_buckets"][-1]
    prompts = [rng.randint(0, lm.vocab, size=rng.randint(2, longest + 1))
               for _ in range(sizes["prompts"])]
    server = mx.serving.ModelServer({})
    try:
        session = server.add_generative_tenant(
            "lm", lm, params, ctx=ctx, max_sessions=sizes["max_sessions"],
            max_len=sizes["max_len"], max_decode_tokens=sizes["new_tokens"],
            seq_buckets=sizes["seq_buckets"])
        server.warmup()
        programs = telemetry.counter_value("serving.decode.bucket_programs")
        compiled = telemetry.counter_value("mem.programs_compiled")
        ring = telemetry.gauge_value("kv.ring_bytes")
        _check(ring and ring > 0, "kv.ring_bytes gauge %r" % ring)
        futures = [server.submit_generate("lm", p,
                                          max_new_tokens=sizes["new_tokens"],
                                          timeout_ms=600000)
                   for p in prompts]
        results = [f.result(timeout=600) for f in futures]
        for p, r in zip(prompts, results):
            _check(len(r.tokens) == sizes["new_tokens"]
                   and r.finish_reason == "length"
                   and r.prompt_len == len(p)
                   and ((0 <= r.tokens) & (r.tokens < lm.vocab)).all(),
                   "bad generation %r for a %d-token prompt" % (r, len(p)))
        _check(telemetry.counter_value("serving.decode.bucket_programs")
               == programs
               and telemetry.counter_value("mem.programs_compiled")
               == compiled, "a decode program compiled after warm-up")

        # one session by hand, the way tests/test_transformer_lm.py does:
        # prefill + greedy decode through the tenant's own (warm)
        # programs, every step's logits against ONE full-recompute
        # forward over the final sequence (causal: row t of it is the
        # recompute answer after t+1 tokens).  The batcher is idle — every
        # future above has resolved — so slot 0 is free.
        prompt = list(prompts[0][:sizes["seq_buckets"][0] - 1])
        n, bucket = len(prompt), sizes["seq_buckets"][0]
        exe, fn = session._program(session._prefill_pred, 1, bucket, True)
        data = np.zeros((1, bucket), np.float32)
        data[0, :n] = prompt
        kv_logits = [session._run(exe, fn, data, np.zeros((1,), np.float32),
                                  np.full((1,), n, np.float32))[0]]
        toks = list(prompt)
        exe, fn = session._program(session._decode_pred, 1, 1, False)
        for _ in range(sizes["check_steps"]):
            toks.append(int(np.argmax(kv_logits[-1])))
            kv_logits.append(session._run(
                exe, fn, np.asarray([[toks[-1]]], np.float32),
                np.zeros((1,), np.float32),
                np.full((1,), len(toks) - 1, np.float32))[0])
    finally:
        server.close()
    scorer = mx.Predictor(lm.score_symbol(), dict(params),
                          {"data": (1, len(toks))}, ctx=ctx)
    scorer.forward(data=np.asarray([toks], np.float32))
    ref = scorer.get_output(0).reshape(len(toks), lm.vocab)
    scorer.close()
    worst = max(_rel_err(kv, ref[n - 1 + i])
                for i, kv in enumerate(kv_logits))
    _check(worst <= GENERATE_RTOL, "KV prefill/decode logits differ from "
           "full recompute by %.3g of max |logit| (tolerance %.3g)"
           % (worst, GENERATE_RTOL))
    return {"prompts": len(prompts),
            "tokens": int(sum(len(r.tokens) for r in results)),
            "decode_bucket_programs": programs, "kv_ring_bytes": int(ring),
            "bytes_limit": limit, "max_rel_err": float("%.3g" % worst)}


def ring_hlo_facts(text, ring_shape):
    """What a compiled decode program does with its KV rings, read from
    its optimised HLO `text`: the entry parameters of `ring_shape` with
    their on-device layouts, which of them ``input_output_alias`` gives
    to an output, and every instruction — entry or fused — that copies
    an array of the ring's shape (``copy``, or the asynchronous
    ``copy-start``), and how many Pallas kernel calls it holds."""
    dims = re.escape(",".join(str(d) for d in ring_shape))
    entry = text[text.index("ENTRY"):]
    params = {int(n): layout for layout, n in re.findall(
        r"= f32\[%s\](\{\S*\})? parameter\((\d+)\)" % dims, entry)}
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry_computation",
                      text, re.S)
    aliased = set(int(n) for n in re.findall(
        r"\((\d+), \{[\d, ]*\}", alias.group(1))) if alias else set()
    copies = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"f32\[%s\]" % dims, line)
              and re.search(r" (copy|copy-start)\(", line)]
    return {"ring_params": len(params),
            "layouts": sorted(set(params.values())),
            "aliased": len(aliased & set(params)), "copies": copies,
            "kernel_calls": text.count('custom_call_target="tpu_custom_call"')}


def named_kernel_calls(text, name):
    """The calls of the Pallas kernel `name` (a kernel keeps its name) in
    a compiled program's optimised HLO `text` — a call's own line less its
    operands, which may be another kernel's result and carry ITS name."""
    calls = 0
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            head, _, rest = line.partition(" custom-call(")
            calls += name in head or name in rest.partition(")")[2]
    return calls


def delta_rule_hlo_facts(text, head_shapes=()):
    """What a compiled prefill program makes of the delta rule's chunks,
    read from its optimised HLO `text`: the triangular solves XLA left in
    it (the op, or the custom calls a TPU expands it to), the Pallas
    kernel calls that are the chunked rule's — a mixed step (PR 46) also
    holds its riders' step and ring kernels, which keep their names — and
    every array under the scan's scope (``mx:gdn.scan``; the riders' step
    has its own) that is split into heads: positions, then one of
    `head_shapes` (``(heads, width)`` pairs: the model's key and value
    heads) — what XLA's L2 norms reduce over, what q and k are repeated
    to and what the gated norm reduces over, none of which a program
    keeps whose kernel does all three (PR 51)."""
    split = [re.compile(r"\b\w+\[(?:\d+,)+%d,%d\]" % pair)
             for pair in sorted(set(head_shapes))]
    lines = text.splitlines()
    return {"solves": len(re.findall(
                r' triangular-solve\(|custom_call_target="[^"]*Triangular',
                text)),
            "kernel_calls": sum(
                'custom_call_target="tpu_custom_call"' in line
                and "gdn_state_step" not in line
                and "kv_ring_attention" not in line
                and "sdp_causal_attention" not in line
                for line in lines),
            "head_arrays": sorted({
                found for line in lines if "mx:gdn.scan" in line
                for shape in split for found in shape.findall(line)})}


def delta_head_shapes(lm):
    """`delta_rule_hlo_facts`' `head_shapes` of a model: its delta-rule
    layers' key heads and value heads of a key's width, and its value
    heads of a value's."""
    own = lm.kind_specs["linear_attention"]
    heads, key_dim = own["heads"], own["key_dim"]
    return [(own.get("key_heads") or heads, key_dim), (heads, key_dim),
            (heads, own["value_dim"])]


def score_arrays(text, bucket):
    """Every array in a compiled prefill program's optimised HLO `text`
    of SEVERAL matrices of `bucket` x `bucket` (its last two dimensions
    both the bucket, the ones before them more than 1 together): a
    prompt's scores, or their mask spread over the heads, made whole —
    what the blockwise kernel (``ops/sdp_kernel.py``) never writes, so in
    a program of a bucket ``ops.attention.prefill_block`` sends through
    it any such array is a fault.  (One matrix of that shape may be a
    weight, or the activations of a model as wide as the bucket.)"""
    found = re.findall(r"\b(\w+)\[((?:\d+,)+)%d,%d\]" % (bucket, bucket),
                       text)
    return sorted({"%s[%s%d,%d]" % (dtype, heads, bucket, bucket)
                   for dtype, heads in found
                   if math.prod(int(d) for d in heads.split(",")[:-1]) > 1})


def run_score_arrays(text, bucket):
    """Every float32 array in a compiled prefill program's optimised HLO
    `text` of a GROUP OF HEADS' scores of a block of queries against the
    keys of a run, ``(heads, 512, keys)`` (``ops/sparse_latent.py``: a
    `bucket`'s query blocks go in runs, each against the keys up to its
    own end): what the ``jax.numpy`` form of the masked attention wrote
    to HBM and read back, and the masked kernel
    (``ops/masked_latent_kernel.py``) never writes — so in a program of a
    bucket ``ops.sparse_latent.masked_block`` sends through it any such
    array is a fault."""
    from mxnet_tpu.ops import sparse_latent

    block = sparse_latent._block(bucket, sparse_latent._QUERY_BLOCK)
    seen = {min(bucket, (first + count) * block)
            for first, count in sparse_latent._runs(-(-bucket // block))}
    found = re.findall(r"\bf32\[(\d+),%d,(\d+)\]" % block, text)
    return sorted({"f32[%s,%d,%s]" % (heads, block, keys)
                   for heads, keys in found if int(keys) in seen})


def delta_step_hlo_facts(text, rows, state_shape):
    """What a compiled decode program of `rows` rows makes of the delta
    rule's step, read from its optimised HLO `text`: the calls of the
    step kernel (``ops/gdn_step_kernel.py``; a Pallas kernel keeps its
    name) and every array of ``rows x d_k x H d_v`` elements, the rows
    leading — a key spread out to a page's size for all rows, or the
    rows' pages gathered — for a state of `state_shape` ``(slots, d_k, H
    d_v)``."""
    return {"kernel_calls": named_kernel_calls(text, "gdn_state_step"),
            "row_pages": _row_page_arrays(text, rows, state_shape)}


def _row_page_arrays(text, rows, state_shape):
    """Every float32 array in the HLO `text` of `rows` x a page of a state
    stored `state_shape` ``(slots, ...)``, the rows leading, however the
    page's elements are split into dimensions."""
    page = math.prod(state_shape[1:])
    return sorted({"f32[%d,%s]" % (rows, dims)
                   for dims in re.findall(r"f32\[%d,([\d,]+)\]" % rows, text)
                   if math.prod(int(d) for d in dims.split(",")) == page})


def ssm_step_hlo_facts(text, rows, state_shape):
    """What a compiled decode program of `rows` rows makes of the Mamba-2
    step, read from its optimised HLO `text`: the calls of the step kernel
    (``ops/ssm_step_kernel.py``; a Pallas kernel keeps its name), every
    array of ``rows x H x P x S`` elements, the rows leading — the rows'
    pages gathered — and every copy of an array of `state_shape` ``(slots,
    H, P, S)``, within HBM or staged through the compiler's fast memory
    (PERF.md section 6, PR 54: four of nine layers' whole buffers)."""
    return {"kernel_calls": named_kernel_calls(text, "ssm_step_kernel"),
            "row_pages": _row_page_arrays(text, rows, state_shape),
            "copies": ring_hlo_facts(text, state_shape)["copies"]}


def _best_ms(session, exe, fn, calls, *operands):
    """The best of `calls` synchronous calls of a warm program of the
    session on the host's clock, in ms."""
    took = []
    for _ in range(calls):
        t0 = time.perf_counter()
        session._run(exe, fn, *operands)
        took.append(time.perf_counter() - t0)
    return 1e3 * min(took)


def decode_step_ms(session, rows, calls=5):
    """The warm decode program of `rows` rows through the session's own
    synchronous call, every row a padded one on the scratch slot: the
    best of `calls` on the host's clock, in ms."""
    import numpy as np

    exe, fn = session._program(session._decode_pred, rows, 1, False)
    return _best_ms(session, exe, fn, calls,
                    np.zeros((rows, 1), np.float32),
                    np.full((rows,), rows, np.float32),
                    np.zeros((rows,), np.float32))


BUCKET_RATIO = 1.5  # a prefill bucket's time over the next larger one's


def prefill_bucket_ms(session, buckets, calls=3):
    """Each prefill bucket's warm program through the session's own
    synchronous call, a whole bucket of tokens into slot 0: the best of
    `calls` on the host's clock, in ms."""
    import numpy as np

    best = {}
    for t in buckets:
        exe, fn = session._program(session._prefill_pred, 1, t, True)
        best[t] = _best_ms(session, exe, fn, calls,
                           np.zeros((1, t), np.float32),
                           np.zeros((1,), np.float32),
                           np.full((1,), t, np.float32))
    return best


def slow_buckets(ms, ratio=BUCKET_RATIO):
    """The buckets of `ms` ({bucket: ms}) that run longer than `ratio`
    times the next larger one: a program's time grows with its bucket,
    so such a bucket holds something the compiler made badly (PERF.md
    section 6, PR 36: OPT-1.3B's 256 bucket at 21 ms beside 3.9 and
    5.7)."""
    order = sorted(ms)
    return [t for t, larger in zip(order, order[1:])
            if ms[t] > ratio * ms[larger]]


def phase_kv_ring(sizes, ctx):
    """The decode step touches a KV ring where it lies (PERF.md section
    6, PR 26 and PR 32): for each of `sizes["shapes"]` compile the
    largest decode bucket of an LM of that shape and read what XLA made
    of the rings.  Only a device backend donates and only the TPU has
    the kernel, so only there are aliasing, copies, kernel calls and
    the rings' size on the device judged; on the CPU the phase still
    compiles, runs and parses.  Each tenant's prefill buckets — the mixed
    step of each, where the model has one — are read the same way and
    timed warm, and on a device backend held against their neighbours."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.models import TransformerLM

    platform = ctx.jax_device().platform
    total = {"ring_params": 0, "aliased": 0, "copies": 0, "kernel_calls": 0,
             "layouts": [], "rings": [], "delta_rule": [], "delta_step": [],
             "ssm_step": [], "routed": [],
             "prefill_ms": [], "mixed_steps": 0, "kernel_buckets": 0,
             "masked_buckets": 0}
    for shape in sizes["shapes"]:
        shape = dict(shape)
        buckets = shape.pop("seq_buckets", sizes["seq_buckets"])
        slots = shape.pop("max_sessions", sizes["max_sessions"])
        lm = TransformerLM(**{**dict(vocab=sizes["vocab"],
                                     num_layers=sizes["num_layers"],
                                     d_model=sizes["d_model"],
                                     d_ff=sizes["d_ff"]), **shape})
        spec = lm.cache_spec(slots + 1)
        # the model's rings, one shape a length (a window layer's is its
        # own): the first is read for its layout, the others beside it
        rings = list(dict.fromkeys(e.shape for e in spec.values()
                                   if e.kind in ("ring", "latent")))
        ring = rings[0]
        # an attention layer has two rings, a latent layer ONE: a kernel
        # call each, but for a latent layer whose absorbed step is
        # jax.numpy (a learned selection's, a window's: the kinds say)
        ring_layers = (sum(e.kind == "ring" for e in spec.values()) // 2
                       + lm.call_counters(
                           rows=slots, lengths=[0] * slots, computed=slots,
                           platform=platform).get("mla.kernel_steps", 0))
        # judged: every entry of at least a hundredth of the set's bytes
        # (a conv window of three rows lies in tiles of four, and XLA may
        # fetch so small a buffer into fast memory ahead of its use)
        floor = sum(e.nbytes for e in spec.values()) // 100
        judged = {n: e for n, e in spec.items() if e.nbytes >= floor}
        step = lm.decode_symbol()
        inputs = dict(data=(slots, 1), slot=(slots,), length=(slots,),
                      last_token=(slots + 1,),
                      **{n: e.shape for n, e in spec.items()})
        shapes, _, _ = step.infer_shape(**inputs)
        rng = np.random.RandomState(sizes["seed"])
        params = {n: mx.nd.array((rng.randn(*s) * 0.02).astype(np.float32),
                                 ctx=ctx)
                  for n, s in zip(step.list_arguments(), shapes)
                  if n not in inputs}
        server = mx.serving.ModelServer({})
        try:
            session = server.add_generative_tenant(
                "ring", lm, params, ctx=ctx, max_sessions=slots,
                max_len=shape["max_len"], seq_buckets=buckets)
            server.warmup()
            _exe, fn = session._program(session._decode_pred, slots, 1,
                                        False)
            facts = ring_hlo_facts(fn.hlo_text(), ring)
            # a recurrent state is held to what a ring is: a parameter,
            # aliased, never copied
            for other in sorted({e.shape for e in judged.values()} - {ring}):
                more = ring_hlo_facts(fn.hlo_text(), other)
                for key in ("ring_params", "aliased"):
                    facts[key] += more[key]
                facts["copies"] += more["copies"]
            # a model with delta-rule layers: its longest prefill bucket,
            # as the warm-up compiled it
            longest = max(buckets)
            booked = lm.call_counters(positions=longest, platform=platform)
            scanned = booked.get("gdn.scan_positions", 0) // longest
            mamba = booked.get("ssm.scan_positions", 0) // longest
            # what a decode step of all slots books: a recurrent kind
            # says how much of its state the step kernel moves
            stepped = lm.call_counters(rows=slots, platform=platform)
            if scanned:
                _exe, pre = session._program(session._prefill_pred, 1,
                                             longest, True)
                rule = dict(delta_rule_hlo_facts(pre.hlo_text(),
                                                 delta_head_shapes(lm)),
                            bucket=longest, layers=scanned,
                            kernel_layers=booked["gdn.kernel_positions"]
                            // longest)
                # its decode program: the step kernel where the shape
                # function gives it one, and nothing of rows x page size
                state, = {e.shape for n, e in spec.items()
                          if n.startswith("gdn_state")}
                stepped_by = dict(
                    delta_step_hlo_facts(fn.hlo_text(), slots, state),
                    rows=slots, layers=scanned,
                    kernel_layers=scanned * stepped["gdn.step_kernel_bytes"]
                    // stepped["gdn.state_bytes"],
                    ms=float("%.3g" % decode_step_ms(session, slots)))
            # a model with Mamba-2 layers: its decode program holds the
            # step kernel where the shape function gives it one, no copy
            # of a state buffer and nothing of rows x page size
            if mamba:
                state, = {e.shape for n, e in spec.items()
                          if n.startswith("ssm_state")}
                # a few tokens through the batcher: what it books of the
                # steps' state bytes, and of them for the step kernel
                names = ("ssm.state_bytes", "ssm.step_kernel_bytes")
                before = [telemetry.counter_value(n) for n in names]
                server.submit_generate(
                    "ring", [1, 2, 3], max_new_tokens=4,
                    timeout_ms=600000).result(timeout=600)
                ssm_by = dict(
                    ssm_step_hlo_facts(fn.hlo_text(), slots, state),
                    rows=slots, layers=mamba,
                    kernel_layers=mamba * stepped["ssm.step_kernel_bytes"]
                    // stepped["ssm.state_bytes"],
                    booked=[int(telemetry.counter_value(n) - was)
                            for n, was in zip(names, before)],
                    ms=float("%.3g" % decode_step_ms(session, slots)))
            # each prefill bucket's program as the warm-up compiled it —
            # the mixed step where the model has one (PR 46) —: a bucket
            # the shape rule sends through the blockwise attention kernel
            # (PR 47) holds no array of bucket x bucket scores
            mixed, scores, masked = [], {}, {}
            for t in buckets:
                _exe, pre = session._program(session._prefill_pred, 1, t,
                                             True)
                text = pre.hlo_text()
                booked = lm.call_counters(positions=t, platform=platform)
                chunked = booked.get("gdn.kernel_positions", 0) // t
                tiled = booked.get("attn.kernel_positions", 0) // t
                if tiled and tiled == booked["attn.prefill_positions"] // t:
                    scores[t] = score_arrays(text, t)
                # a bucket whose layers under a learned selection go
                # through the masked kernel (PR 49; a window layer beside
                # them books no kernel): one call a layer, its site in the
                # scan over the groups of heads, and no run's scores
                if tiled and booked.get("sparse.prefill_pairs"):
                    masked[t] = {
                        "layers": tiled, "scores": run_score_arrays(text, t),
                        "kernel_calls": named_kernel_calls(
                            text, "masked_latent_attention")}
                if not session._mixed:
                    continue
                seen = {"bucket": t, "ring_params": 0, "aliased": 0,
                        "copies": [], "kernel_calls": text.count(
                            'custom_call_target="tpu_custom_call"'),
                        # the riders' ring kernel and the prompt's
                        # blockwise kernel an attention layer, a
                        # delta-rule layer's chunked and step kernels
                        "kernels": ring_layers + tiled + chunked + (
                            stepped_by["kernel_layers"] if scanned else 0)}
                for held_shape in sorted({e.shape for e in judged.values()}):
                    more = ring_hlo_facts(text, held_shape)
                    for key in ("ring_params", "aliased", "copies"):
                        seen[key] += more[key]
                mixed.append(seen)
            # a routed model's longest prefill: the segment matmuls of a
            # layer whose call `expert_plan` gives the kernel — three of a
            # gated expert, TWO of an ungated one (PR 64)
            routed = None
            if lm.extra_outputs():
                _exe, pre = session._program(session._prefill_pred, 1,
                                             longest, True)
                routed = {"layers": sum(1 for f in lm.ffn_types
                                        if f in ("routed", "shortcut")),
                          "matrices": 3 if lm.expert_gated else 2,
                          "kernel": lm.expert_plan(longest, platform)[3],
                          "bucket": longest,
                          "kernel_calls": named_kernel_calls(
                              pre.hlo_text(), "grouped_matmul_kernel")}
            prefill_ms = prefill_bucket_ms(session, buckets)
            # the live set as the warm-up's programs left it on the device
            held = [(n, e.nbytes, getattr(a, "on_device_size_in_bytes",
                                          lambda: e.nbytes)())
                    for (n, e), a in zip(spec.items(), session._state)]
        finally:
            server.close()
        print("[chip_smoke] kv_ring: %d ring parameters f32%s, layout(s) %s;"
              " %d aliased to an output; %d ring copies; %d kernel calls"
              % (facts["ring_params"], list(ring), facts["layouts"],
                 facts["aliased"], len(facts["copies"]),
                 facts["kernel_calls"]), flush=True)
        _check(facts["ring_params"] == len(judged),
               "found %d ring parameters of %d in the decode program's HLO"
               % (facts["ring_params"], len(judged)))
        if platform != "cpu":
            _check(facts["aliased"] == facts["ring_params"],
                   "only %d of %d KV ring parameters are aliased to an "
                   "output: the rest are rewritten whole every step"
                   % (facts["aliased"], facts["ring_params"]))
            _check(not facts["copies"], "the decode program copies a KV "
                   "ring: " + "; ".join(facts["copies"][:4]))
            fat = ["%s %d > %d" % row for row in held
                   if row[2] > row[1] and row[0] in judged]
            _check(not fat, "state fatter on the device than cache_spec "
                   "states (bytes): " + "; ".join(fat[:4]))
            print("[chip_smoke] kv_ring: on-device bytes over cache_spec's: "
                  + ", ".join("%s %.3f" % (n, on / want)
                              for n, want, on in held), flush=True)
        # a mixed step: every entry aliased and none copied (a rider's
        # row is written inside the kernel), the kernels it should hold
        for seen in mixed:
            print("[chip_smoke] kv_ring: the %(bucket)d-bucket mixed step: "
                  "%(ring_params)d cache parameters, %(aliased)d aliased, "
                  "%(kernel_calls)d kernel calls" % seen, flush=True)
            _check(seen["ring_params"] == len(judged),
                   "found %d cache parameters of %d in the %d-bucket mixed "
                   "step's HLO" % (seen["ring_params"], len(judged),
                                   seen["bucket"]))
            if platform != "cpu":
                _check(seen["aliased"] == seen["ring_params"]
                       and not seen["copies"],
                       "the %d-bucket mixed step aliases %d of %d cache "
                       "entries and copies: %s"
                       % (seen["bucket"], seen["aliased"],
                          seen["ring_params"], "; ".join(seen["copies"][:4])))
            if platform == "tpu":
                _check(seen["kernel_calls"] == seen["kernels"],
                       "%(kernel_calls)d kernel calls in the %(bucket)d-"
                       "bucket mixed step, %(kernels)d expected" % seen)
        total["mixed_steps"] += len(mixed)
        for t, fat in sorted(scores.items()):
            print("[chip_smoke] kv_ring: the %d-bucket prefill's attention "
                  "is the blockwise kernel; arrays of bucket x bucket: %s"
                  % (t, fat), flush=True)
            _check(not fat, "the %d-bucket prefill program, whose attention "
                   "is the blockwise kernel, still holds its scores whole: "
                   "%s" % (t, fat))
        total["kernel_buckets"] += len(scores)
        for t, seen in sorted(masked.items()):
            print("[chip_smoke] kv_ring: the %d-bucket prefill's selected "
                  "attention: %d masked kernel call(s) for %d layer(s); "
                  "arrays of a run's scores: %s"
                  % (t, seen["kernel_calls"], seen["layers"],
                     seen["scores"]), flush=True)
            _check(seen["kernel_calls"] == seen["layers"]
                   and not seen["scores"],
                   "the %d-bucket prefill program, whose selected attention "
                   "is the masked kernel, holds %d calls of it for %d "
                   "layers and these scores of a run: %s"
                   % (t, seen["kernel_calls"], seen["layers"],
                      seen["scores"]))
        total["masked_buckets"] += len(masked)
        print("[chip_smoke] kv_ring: prefill buckets, ms a warm program: "
              + ", ".join("%d: %.2f" % row for row in sorted(
                  prefill_ms.items())), flush=True)
        total["prefill_ms"].append({str(t): float("%.3g" % ms)
                                    for t, ms in sorted(prefill_ms.items())})
        if platform != "cpu":
            slow = slow_buckets(prefill_ms)
            _check(not slow, "prefill bucket(s) %s run longer than %.1f "
                   "times the next larger bucket's program: %s"
                   % (slow, BUCKET_RATIO, prefill_ms))
        if platform == "tpu":
            # (a routed layer's segment matmuls are XLA's own custom calls)
            others = ((stepped_by["kernel_calls"] if scanned else 0)
                      + (ssm_by["kernel_calls"] if mamba else 0)
                      + fn.hlo_text().count('op_name="ragged-dot'))
            _check(facts["kernel_calls"] - others == ring_layers,
                   "%d attention kernel calls in a decode program of %d "
                   "attention layers" % (facts["kernel_calls"] - others,
                                         ring_layers))
        if routed:
            print("[chip_smoke] kv_ring: the %(bucket)d-bucket prefill of "
                  "%(layers)d routed layer(s) of %(matrices)d matrices an "
                  "expert: %(kernel_calls)d grouped-matmul kernel call(s)"
                  % routed, flush=True)
            total["routed"].append(routed)
            _check(routed["kernel_calls"] == routed["kernel"]
                   * routed["layers"] * routed["matrices"],
                   "%(kernel_calls)d grouped-matmul kernel calls in the "
                   "%(bucket)d-bucket prefill of %(layers)d routed layers "
                   "of %(matrices)d matrices an expert (kernel: %(kernel)s)"
                   % routed)
        if scanned:
            print("[chip_smoke] kv_ring: the %(bucket)d-bucket prefill of "
                  "%(layers)d delta-rule layer(s): %(kernel_calls)d kernel "
                  "call(s), %(solves)d triangular solve(s)" % rule,
                  flush=True)
            total["delta_rule"].append(rule)
        if scanned and platform == "tpu":
            _check(rule["kernel_layers"] == rule["kernel_calls"]
                   == rule["layers"], "%(kernel_calls)d kernel calls in a "
                   "prefill program of %(layers)d delta-rule layers, of "
                   "which the shape function gives the kernel "
                   "%(kernel_layers)d" % rule)
            _check(not rule["solves"], "the prefill program still holds "
                   "%(solves)d triangular solve(s)" % rule)
            _check(not rule["head_arrays"], "the prefill program still "
                   "splits the scan's arrays into heads beside the kernel: "
                   "%(head_arrays)s" % rule)
        if scanned:
            print("[chip_smoke] kv_ring: the %(rows)d-row decode step of "
                  "%(layers)d delta-rule layer(s): %(kernel_calls)d "
                  "step-kernel call(s), arrays of rows x page size: "
                  "%(row_pages)s; %(ms)s ms a warm step" % stepped_by,
                  flush=True)
            total["delta_step"].append(stepped_by)
        if scanned and platform == "tpu":
            _check(stepped_by["kernel_layers"] == stepped_by["kernel_calls"]
                   == stepped_by["layers"],
                   "%(kernel_calls)d step-kernel calls "
                   "in a decode program of %(layers)d delta-rule layers, "
                   "of which the shape function gives the kernel "
                   "%(kernel_layers)d" % stepped_by)
            _check(not stepped_by["row_pages"], "the decode program holds "
                   "arrays of rows x page size: %(row_pages)s" % stepped_by)
        if mamba:
            print("[chip_smoke] kv_ring: the %(rows)d-row decode step of "
                  "%(layers)d Mamba-2 layer(s): %(kernel_calls)d "
                  "step-kernel call(s), copies of a state buffer: "
                  "%(copies)s, arrays of rows x page size: %(row_pages)s; "
                  "state bytes booked and of them the kernel's: %(booked)s; "
                  "%(ms)s ms a warm step" % ssm_by, flush=True)
            total["ssm_step"].append(ssm_by)
            state_bytes, kernel_bytes = ssm_by["booked"]
            _check(state_bytes > 0 and kernel_bytes == state_bytes * (
                       ssm_by["kernel_layers"] == mamba),
                   "the batcher booked %d state bytes and %d of them for "
                   "the step kernel on %s" % (state_bytes, kernel_bytes,
                                              platform))
        if mamba and platform == "tpu":
            _check(ssm_by["kernel_layers"] == ssm_by["kernel_calls"]
                   == ssm_by["layers"],
                   "%(kernel_calls)d step-kernel calls in a decode program "
                   "of %(layers)d Mamba-2 layers, of which the shape "
                   "function gives the kernel %(kernel_layers)d" % ssm_by)
            _check(not ssm_by["copies"] and not ssm_by["row_pages"],
                   "the decode program copies a Mamba-2 state buffer "
                   "(%(copies)s) or holds arrays of rows x page size: "
                   "%(row_pages)s" % ssm_by)
        for key in ("ring_params", "aliased", "kernel_calls"):
            total[key] += facts[key]
        total["copies"] += len(facts["copies"])
        total["layouts"] = sorted(set(total["layouts"] + facts["layouts"]))
        total["rings"] += [list(r) for r in rings]
    return total


def phase_grouped_matmul(sizes, ctx):
    """A routed FFN's three segment matmuls (`parallel.moe.expert_ffn`,
    SiLU-gated) over ONE call's sorted pair rows, at each shape of
    `sizes["shapes"]`: through `lax.ragged_dot` over the rows
    `parallel/moe.py` gathers for it (`_spare_rows`: whole 512-row tiles)
    and through the TPU's kernel (`ops/grouped_matmul_kernel.py`, forced
    at every shape; Pallas's interpreter off the TPU) over the rows there
    are.  The routing is a uniform draw over the scored experts, of which
    the call keeps the held ones' pairs — a held range's pass is filled to
    two thirds, as `_pass_rows` means it to be.  The kernel is held to
    `lax.ragged_dot` on the rows that lie in a segment, and both are timed
    warm: `reps` layers in one program, each fed the last one's result
    (the last layer's is what is compared), the best of three calls on
    the host's clock over `reps`.  The table —
    rows an expert against the two times — is where
    `parallel.moe._KERNEL_ROWS` was read (PERF.md section 6, PR 60).

    Where every expert is held and `parallel.moe.fused_tile` takes the
    call (PR 61) the WHOLE layer-piece is timed two ways beside it, from
    `x [T, D]` and a uniform routing to the tokens' weighted sums: the
    three kernel calls between a gather of every pair's row, an un-sort
    and a weighted sum over ``[T, k, D]`` (`moe._every_pair`:
    `three_calls_ms`) and the two calls that fetch and place their own
    rows (`moe._two_calls`: `two_calls_ms`), and the two results compared
    (`fused_err`).

    Where a held range walks passes (`pass_plan`'s rows: PR 63) the pass's
    RETURN to token order is timed two ways beside it: `reps` passes' rows
    — an expert's rows of distinct tokens in token order, the rows past
    the held pairs zeros and token `T`'s — added into ``[T, D]`` by XLA's
    scatter-add (`moe._scatter_add`: `xla_return_ms`, `xla_us_a_row`) and
    by the kernel (`ops/row_return_kernel.py`, the tiles
    `moe.return_tiles` gives: `kernel_return_ms`, `kernel_us_a_row`), and
    the two sums compared (`return_err`: a token's rows are added in
    another order)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from mxnet_tpu.ops.grouped_matmul_kernel import grouped_matmul
    from mxnet_tpu.ops.row_return_kernel import row_return
    from mxnet_tpu.parallel import moe

    device = ctx.jax_device()
    on_tpu = device.platform == "tpu"
    rng = np.random.default_rng(sizes["seed"])
    reps = sizes["reps"]

    def layers(matmul, spare, x, weights, load):
        def one(_, carry):
            x, _ = carry
            rows = jnp.pad(x, ((0, spare), (0, 0))) if spare else x
            y = moe.expert_ffn(lambda r, w: matmul(r, w, load), rows,
                               weights, None, "silu", True)[:len(x)]
            y = jnp.where((jnp.arange(len(x)) < load.sum())[:, None], y, 0)
            return x + 1e-3 * y, y
        return lax.fori_loop(0, reps, one, (x, jnp.zeros_like(x)))[1]

    def timed(fn, *operands):
        out = jax.block_until_ready(fn(*operands))
        took = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*operands))
            took.append(time.perf_counter() - t0)
        return out, 1e3 * min(took) / reps

    table = []
    for name, (tokens, k, scored, held, d_model, d_expert) in \
            sizes["shapes"].items():
        held_range = None if held == scored else (0, held)
        pieces, passed = moe.pass_plan(tokens, k, 4 * d_model, held_range,
                                       scored)
        pairs = tokens // pieces * k
        rows = passed or pairs
        load = np.bincount(rng.integers(0, scored, pairs),
                           minlength=scored)[:held]
        while load.sum() > rows:          # a pass takes what it holds
            load[load.argmax()] -= 1
        taken = moe.kernel_tiles(rows, held, d_model, d_expert) is not None
        # the tiles of these widths, whatever the rule says of the rows
        enough = max(rows, moe._KERNEL_ROWS * held)
        tm, tn = moe.kernel_tiles(enough, held, d_model, d_expert)
        _, tn_down = moe.kernel_tiles(enough, held, d_expert, d_model)

        def kernel(r, w, load):
            out, = grouped_matmul(
                r, w, load, tm=tm, interpret=not on_tpu,
                tn=tn if w.shape[-1] == d_expert else tn_down)
            return out

        put = lambda a: jax.device_put(jnp.asarray(a), device)
        x = put(rng.standard_normal((rows, d_model)).astype(np.float32))
        weights = [put((rng.standard_normal(shape) / math.sqrt(shape[1]))
                       .astype(np.float32))
                   for shape in ((held, d_model, d_expert),
                                 (held, d_expert, d_model),
                                 (held, d_model, d_expert))]
        sizes_ = put(load.astype(np.int32))
        spare = 0 if passed else moe._spare_rows(pairs, scored)
        ref, xla_ms = timed(jax.jit(functools.partial(
            layers, moe._ragged_dot, spare)), x, weights, sizes_)
        got, kernel_ms = timed(jax.jit(functools.partial(
            layers, kernel, 0)), x, weights, sizes_)
        err = _rel_err(got, np.asarray(ref, np.float64))
        # one bfloat16 pass both on a TPU, in another order of the float32
        # sums; off it `lax.ragged_dot` is a float32 product
        _check(err < (1e-3 if on_tpu else 2e-2), "%s: the kernel's layer is "
               "%.2e off lax.ragged_dot's" % (name, err))
        # the three matrices of every expert with a row, once; the flops
        # of the rows that lie in a segment
        hit = int((load > 0).sum())
        table.append({
            "shape": name, "rows": rows, "experts": held,
            "rows_an_expert": round(rows / held, 1),
            "held_rows": int(load.sum()), "tiles": [tm, tn, tn_down],
            "taken": taken,
            "xla_ms": round(xla_ms, 3), "kernel_ms": round(kernel_ms, 3),
            "weights_ms_at_819": round(
                1e3 * 3 * hit * 4 * d_model * d_expert / 819e9, 3),
            "flops_ms_at_197": round(
                1e3 * 6 * int(load.sum()) * d_model * d_expert / 197e12, 3),
            "err": err})
        tiles = passed and moe.return_tiles(tokens // pieces, rows, d_model,
                                            jnp.float32)
        if tiles:
            t_len, live = tokens // pieces, int(load.sum())
            # an expert's rows are of distinct tokens, in token order
            token = np.full(rows, t_len, np.int32)
            token[:live] = np.concatenate([
                np.sort(rng.choice(t_len, n, replace=False)) for n in load])
            ys = rng.standard_normal((rows, d_model)).astype(np.float32)
            ys[live:] = 0
            operands = [put(np.zeros((t_len, d_model), np.float32)),
                        put(ys), put(token), put(np.int32(live))]

            def passes(place, out, ys, token, count):
                return lax.fori_loop(
                    0, reps, lambda _, out: place(out, ys, token, count), out)

            def placed(out, ys, token, count):
                return row_return(out, ys, token, count, tb=tiles[0],
                                  tm=tiles[1], interpret=not on_tpu)[0]

            ref, xla_ms = timed(jax.jit(functools.partial(
                passes, lambda out, ys, token, _: moe._scatter_add(
                    out, ys, token))), *operands)
            got, kernel_ms = timed(jax.jit(functools.partial(
                passes, placed)), *operands)
            err = _rel_err(got, np.asarray(ref, np.float64))
            _check(err < 1e-6, "%s: the kernel's return is %.2e off the "
                   "scatter-add's" % (name, err))
            table[-1].update(
                return_live=live, return_err=err,
                xla_return_ms=round(xla_ms, 3),
                kernel_return_ms=round(kernel_ms, 3),
                xla_us_a_row=round(1e3 * xla_ms / live, 3),
                kernel_us_a_row=round(1e3 * kernel_ms / live, 3),
                return_ms_at_819=round(
                    1e3 * 3 * live * 4 * d_model / 819e9, 3))
        tile = held_range is None and moe.fused_tile(
            pairs, held, d_model, d_expert, True)
        if tile:
            routed = rng.integers(0, held, pairs)
            operands = [put(a) for a in (
                rng.standard_normal((pairs // k, d_model)).astype(np.float32),
                np.argsort(routed, kind="stable").astype(np.int32),
                np.bincount(routed, minlength=held).astype(np.int32),
                rng.random((pairs // k, k)).astype(np.float32))] + weights

            def pieces(piece, x, *operands):
                def one(_, carry):
                    x, _ = carry
                    y = piece(x, *operands)
                    return x + 1e-3 * y, y
                return lax.fori_loop(0, reps, one, (x, jnp.zeros_like(x)))[1]

            def three_calls(x, order, load, top_w, *weights):
                return moe._every_pair(kernel, x, order, None, load, top_w,
                                       weights, None, "silu", True, 0, False)

            ref, three_ms = timed(jax.jit(functools.partial(
                pieces, three_calls)), *operands)
            got, two_ms = timed(jax.jit(functools.partial(
                pieces, functools.partial(
                    moe._two_calls, (tile, "silu", True), not on_tpu))),
                *operands)
            err = _rel_err(got, np.asarray(ref, np.float64))
            # the same products and the same roundings, `h` rounded to
            # bfloat16 where `down` would; on a TPU the kernel's SiLU and
            # XLA's may differ in a last bit before that rounding
            _check(err < (2e-3 if on_tpu else 1e-5), "%s: the two calls' "
                   "layer is %.2e off the three calls'" % (name, err))
            table[-1].update(three_calls_ms=round(three_ms, 3),
                             two_calls_ms=round(two_ms, 3), fused_err=err)
        print("[chip_smoke] grouped_matmul %s" % json.dumps(table[-1]),
              flush=True)
    # the rule stands on these readings: where it sends a call to the
    # kernel, the kernel may not be the slower one
    slower = [row["shape"] for row in table
              if on_tpu and row["taken"] and row["kernel_ms"] > row["xla_ms"]]
    _check(not slower, "the kernel is slower than lax.ragged_dot where "
           "kernel_tiles takes it: %s" % slower)
    slower = [row["shape"] for row in table if on_tpu and row.get(
        "two_calls_ms", 0) > row.get("three_calls_ms", 0)]
    _check(not slower, "the two calls that fetch and place their own rows "
           "are slower than the three where fused_tile takes them: %s"
           % slower)
    # ONE form for every pass (`moe.return_tiles` has no threshold of rows
    # or width): it stands on the kernel being the faster one at each
    slower = [row["shape"] for row in table if on_tpu and row.get(
        "kernel_return_ms", 0) > row.get("xla_return_ms", 0)]
    _check(not slower, "the kernel's return to token order is slower than "
           "XLA's scatter-add where return_tiles takes it: %s" % slower)
    return {"table": table, "rows_an_expert_from": moe._KERNEL_ROWS}


def _staged_block_facts(mx, exe, it, devices, sizes):
    """One K-step block staged from the NDArrayIter `it`, as fit's
    steps_per_dispatch > 1 stages it.  Beside an accelerator the batches
    lie in host memory and every step array must reach each chip as that
    chip's own rows (`io.stage.host_parts`, no `device_parts`), so the
    first chip's memory grows by what the others' grows by — to within
    one step's piece: no whole batch was parked there.  Where the CPU
    backend computes, host and device are one platform and the arrays
    come the device's way."""
    import jax

    from mxnet_tpu import telemetry

    def parts():
        return (telemetry.counter_value("io.stage.host_parts"),
                telemetry.counter_value("io.stage.device_parts"))

    def in_use():
        return [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]

    it.reset()
    parts0, before = parts(), in_use()
    staging = mx.io.DeviceStagedIter(
        it, steps_per_dispatch=sizes["steps"], place_fn=exe.place_step_input,
        stack_fn=exe.stack_block_input)
    block = staging.next()
    jax.block_until_ready(block.data + block.label)
    after = in_use()
    staging.close()
    host, device = (b - a for a, b in zip(parts0, parts()))
    _check(block.count == sizes["steps"], "staged %d steps" % block.count)
    arrays = 2 * block.count  # data and label of every step
    want = (arrays, 0) if devices[0].platform != "cpu" else (0, arrays)
    _check((host, device) == want,
           "a staged block's step arrays came %d from host memory, %d from "
           "a device on %s" % (host, device, devices[0].platform))
    facts = {"host_parts": host, "device_parts": device}
    if None not in before + after:
        grew = [b - a for a, b in zip(before, after)]
        piece = block.data[0].nbytes // (block.count * len(devices))
        _check(grew[0] - max(grew[1:]) <= piece,
               "staging grew %s by %d bytes and the others by at most %d: "
               "more than a step's piece (%d) apart"
               % (devices[0], grew[0], max(grew[1:]), piece))
        facts["grew_bytes"] = grew
    return facts


def phase_four_chips(sizes, ctxs):
    """`ctxs`: four contexts naming four distinct devices."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    devices = [c.jax_device() for c in ctxs]
    B = sizes["batch"]
    X, y = _images(sizes, B * sizes["steps"])
    it = mx.io.NDArrayIter(X, y, batch_size=B)
    mx.random.seed(sizes["seed"])
    mod = mx.mod.Module(_resnet(sizes), context=list(ctxs),
                        compute_dtype="bfloat16")
    metric = mx.metric.CrossEntropy()
    d0 = telemetry.counter_value("executor.train_dispatches")
    # kvstore=None: the gradient all-reduce is the one XLA inserts into
    # the single SPMD step (a kvstore would disarm the fused dispatch)
    mod.fit(it, eval_metric=metric, kvstore=None, optimizer="sgd",
            optimizer_params={"learning_rate": 0.01, "momentum": 0.9},
            initializer=_xavier(mx), num_epoch=1, steps_per_dispatch=1)
    _check(telemetry.counter_value("executor.train_dispatches") - d0
           == sizes["steps"], "dispatch count")
    _check(np.isfinite(float(metric.get()[1])), "loss %r" % (metric.get(),))
    exe = mod._exec_group.execs[0]
    _check_on_devices(exe, set(devices), "four_chips")
    # the batch input as the last step consumed it: one shard per device
    shards = exe._place(exe._gather_args())[
        exe._arg_names.index("data")].addressable_shards
    _check({s.device for s in shards} == set(devices)
           and all(s.data.shape[0] == B // len(devices) for s in shards),
           "batch shards on %s" % [s.device for s in shards])
    in_use = {}
    for d in devices:
        stats = d.memory_stats()
        if stats is not None:  # XLA:CPU reports none
            in_use[str(d)] = stats["bytes_in_use"]
            _check(stats["bytes_in_use"] > 0, "%s holds no memory" % d)
    staged = _staged_block_facts(mx, exe, it, devices, sizes)

    # a context with device_id 3 computes on device 3
    pred = _serve_predictor(mx, dict(sizes, seed=sizes["seed"] + 1),
                            ctxs[3])
    pred.forward(data=X[:1])
    out = pred._exec.outputs[0].data
    _check(out.devices() == {devices[3]} and np.isfinite(
        pred.get_output(0)).all(), "Predictor(ctx=%s) computed on %s"
        % (ctxs[3], out.devices()))
    pred.close()
    return {"devices": [str(d) for d in devices],
            "loss": round(float(metric.get()[1]), 4),
            "bytes_in_use": in_use, "staged": staged,
            "predictor_device": str(devices[3])}


# ----------------------------------------------------------------------
# driver
# ----------------------------------------------------------------------

def run_phase(name, fn, sizes, ctx, clock, report):
    """Run one phase; an exception propagates (traceback, non-zero exit)."""
    print("[chip_smoke] phase %s ..." % name, flush=True)
    c0, t0 = clock.seconds, time.perf_counter()
    facts = fn(sizes, ctx)
    seconds = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    first = ctx[0] if isinstance(ctx, list) else ctx
    report[name] = dict(facts, ok=True, seconds=round(seconds, 2),
                        compile_seconds=round(compile_s, 2),
                        device=str(first.jax_device()))
    print("[chip_smoke] phase %s ok: %.1f s (%.1f s compiling) %s"
          % (name, seconds, compile_s, json.dumps(facts)), flush=True)


def main():
    try:
        import jax

        # import BEFORE any backend touch: the package places the compile
        # cache at import (mxnet_tpu.base.compile_cache_dir)
        import mxnet_tpu as mx
    except ImportError as e:
        sys.exit("chip_smoke: cannot import the system under test: %s" % e)
    from mxnet_tpu import base, telemetry

    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit("chip_smoke: JAX's default backend is %r, not a TPU "
                 "(jax.devices() = %s); nothing was run"
                 % (backend, jax.devices()))
    import jaxlib
    import libtpu

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    libtpu_version = libtpu.__version__
    print("[chip_smoke] device %s; jax %s jaxlib %s libtpu %s; compile "
          "cache %s" % (json.dumps(device), jax.__version__,
                        jaxlib.__version__, libtpu_version,
                        base.compile_cache_dir()), flush=True)

    telemetry.set_enabled(True)
    clock = CompileClock()
    report = {}
    ctx = mx.tpu(0)
    t0 = time.perf_counter()
    run_phase("fence", phase_fence, FULL["fence"], ctx, clock, report)
    run_phase("train", phase_train, FULL["train"], ctx, clock, report)
    run_phase("serve", phase_serve, FULL["serve"], ctx, clock, report)
    run_phase("generate", phase_generate, FULL["generate"], ctx, clock,
              report)
    run_phase("kv_ring", phase_kv_ring, FULL["kv_ring"], ctx, clock, report)
    run_phase("grouped_matmul", phase_grouped_matmul,
              FULL["grouped_matmul"], ctx, clock, report)
    if jax.device_count() >= 4:
        run_phase("four_chips", phase_four_chips, FULL["four_chips"],
                  [mx.tpu(i) for i in range(4)], clock, report)
    else:
        report["four_chips"] = {"ok": None, "skipped": "%d device(s)"
                                % jax.device_count()}
    fallbacks = telemetry.counter_value("mem.program_fallbacks")
    _check(fallbacks == 0, "mem.program_fallbacks = %d: an AOT compile "
           "fell back to jax.jit" % fallbacks)
    print("[chip_smoke] report " + json.dumps({
        "phases": report,
        "seconds": round(time.perf_counter() - t0, 1),
        "compile_seconds": round(clock.seconds, 1),
        "program_fallbacks": fallbacks,
        "compile_cache": base.compile_cache_dir(),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": libtpu_version,
    }), flush=True)
    # the result line: exactly these keys, and the last thing on stdout
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
