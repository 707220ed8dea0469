"""Bring-up pins (PR 21): chip_smoke.py's phases at tiny sizes on the CPU
mesh, contexts that name a device or raise, per-context placement, the
compile-cache resolver, and the launcher's one-process-per-chip
environment.  All CPU; ~25 s together."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import base, telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

import chip_smoke  # noqa: E402
import launch  # noqa: E402

TINY = {
    "fence": {"n": 128, "chain": 2, "reps": 1},
    "train": {"depth": 18, "image": 32, "classes": 10, "batch": 8, "k": 2,
              "blocks": 2, "seed": 0},
    "serve": {"depth": 18, "image": 32, "classes": 10, "buckets": [2, 4],
              "requests": 8, "threads": 2, "wait_ms": 5.0, "seed": 1},
    "generate": {"vocab": 32, "num_layers": 2, "num_heads": 2, "d_model": 32,
                 "max_len": 48, "max_sessions": 2, "seq_buckets": [8, 16],
                 "prompts": 3, "new_tokens": 4, "check_steps": 3, "seed": 2},
    "kv_ring": {"vocab": 32, "num_layers": 2, "d_model": 32, "d_ff": 32,
                "max_sessions": 2, "seq_buckets": [8], "seed": 5,
                "shapes": [dict(num_heads=2, max_len=48, d_ff=64,
                                seq_buckets=[8, 16]),
                           dict(num_heads=4, num_kv_heads=2, max_len=48),
                           dict(num_heads=2, max_len=48,
                                layer_types=["linear_attention",
                                             "attention"],
                                linear_heads=2, linear_key_dim=8,
                                linear_value_dim=16, norm="rms",
                                positions="none", bias=False,
                                block_norm="output"),
                           dict(num_heads=4, num_kv_heads=2, head_dim=16,
                                max_len=48,
                                layer_types=["window_attention",
                                             "attention"],
                                sliding_window=16, norm="rms",
                                positions={"window_attention": "rotary"},
                                qk_norm="head", out_gate=True,
                                bias=False),
                           dict(num_heads=4, num_kv_heads=2, head_dim=32,
                                max_len=48,
                                layer_types=["linear_attention",
                                             "attention"],
                                linear_heads=4, linear_key_heads=2,
                                linear_key_dim=8, linear_value_dim=8,
                                linear_neg_eigval=False, norm="rms",
                                positions="rotary", rotary_dim=8,
                                qk_norm="head", out_gate=True,
                                bias=False),
                           dict(num_heads=4, max_len=48,
                                layer_types=["latent_attention"] * 2,
                                latent_q_rank=16, latent_kv_rank=24,
                                latent_nope_dim=8, latent_rope_dim=8,
                                latent_value_dim=16,
                                rope_scaling=dict(
                                    factor=8, beta_fast=32, beta_slow=1,
                                    original_max_position_embeddings=16),
                                attention_multiplier=0.3,
                                query_scale=(0.1, 16), norm="rms",
                                positions="none", bias=False),
                           dict(num_heads=4, max_len=48,
                                layer_types=["sparse_latent_attention",
                                             "window_latent_attention"],
                                kind_specs={
                                    "sparse_latent_attention": dict(
                                        num_heads=4, q_rank=16, kv_rank=24,
                                        nope_dim=8, rope_dim=8,
                                        value_dim=16, head_gate=True,
                                        index_heads=2, index_dim=16,
                                        index_topk=8),
                                    "window_latent_attention": dict(
                                        num_heads=2, q_rank=16, kv_rank=32,
                                        nope_dim=16, rope_dim=8,
                                        value_dim=16, head_gate=True,
                                        window=16)},
                                norm="rms", positions="none",
                                bias=False),
                           dict(num_heads=2, max_len=48,
                                layer_types=["mamba", "attention"],
                                mamba_heads=4, mamba_head_dim=8,
                                mamba_state=16, norm="rms",
                                positions="none", bias=False),
                           dict(num_heads=2, max_len=48,
                                layer_types=["latent_attention"] * 2,
                                latent_q_rank=16, latent_kv_rank=24,
                                latent_nope_dim=8, latent_rope_dim=8,
                                latent_value_dim=8,
                                latent_lora_rescale=True, ffn="swiglu",
                                ffn_types=["shortcut", "dense"],
                                num_experts=8, zero_experts=4,
                                experts_per_token=3, expert_d_ff=16,
                                router_bias=True, route_scale=6.0,
                                held_experts=(0, 2), norm="rms",
                                positions="none", bias=False),
                           dict(num_layers=3, num_heads=4, num_kv_heads=2,
                                max_len=48,
                                layer_types=["mamba", "none", "attention"],
                                ffn_types=["none", "routed", "none"],
                                kind_specs={"mamba": dict(
                                    heads=8, head_dim=4, state=16, groups=4,
                                    chunk=8)},
                                num_experts=8, experts_per_token=3,
                                expert_d_ff=16, shared_d_ff=24,
                                router_score="sigmoid", router_bias=True,
                                route_norm=True, route_scale=2.5,
                                held_experts=(0, 2), expert_act="relu2",
                                expert_gated=False, norm="rms",
                                positions="none", bias=False,
                                tied_head=False)]},
    "four_chips": {"depth": 18, "image": 32, "classes": 10, "batch": 8,
                   "steps": 2, "seed": 4},
    # four of eight experts held, every pair's row gathered; every expert
    # held: widths of one and two lane tiles
    "grouped_matmul": {"reps": 2, "seed": 6, "shapes": {
        "a held range": (64, 2, 8, 4, 128, 256),
        "every expert": (40, 2, 4, 4, 256, 128),
        "a held range in passes": (512, 2, 8, 2, 128, 128)}},
}


# ----------------------------------------------------------------------
# chip_smoke.py
# ----------------------------------------------------------------------

def test_chip_smoke_refuses_to_run_without_a_tpu():
    """On a CPU-only JAX it exits non-zero with one line, before any
    work, and prints no result."""
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=120,
                          cwd=ROOT)
    assert proc.returncode != 0
    assert proc.stdout == ""
    lines = proc.stderr.strip().splitlines()
    assert len(lines) == 1 and "not a TPU" in lines[0], proc.stderr


def test_chip_smoke_phases_pass_tiny_on_a_cpu_device():
    """Every phase function, tiny, on mx.cpu(2) — NOT the default device,
    so the phases' own placement checks (parameters, optimizer state and
    Predictor outputs on the context's device) pin per-context placement
    for fit, serve and decode at once."""
    telemetry.set_enabled(True)
    # an earlier test file of this worker may have fallen back on purpose
    fallbacks = telemetry.counter_value("mem.program_fallbacks")
    clock = chip_smoke.CompileClock()
    report = {}
    ctx = mx.cpu(2)
    for name in ("fence", "train", "serve", "generate", "kv_ring",
                 "grouped_matmul"):
        chip_smoke.run_phase(name, getattr(chip_smoke, "phase_" + name),
                             TINY[name], ctx, clock, report)
    # the expert layer's kernel (Pallas's interpreter here) against
    # `lax.ragged_dot` at both shapes, each timed: 32 and 20 rows an
    # expert, the first over the rule's count and the second too
    table = report["grouped_matmul"]["table"]
    assert [(row["shape"], row["rows"], row["experts"], row["held_rows"] <=
             row["rows"], row["taken"]) for row in table] == [
        ("a held range", 128, 4, True, True),
        ("every expert", 80, 4, True, True),
        ("a held range in passes", 512, 2, True, True)]
    assert all(row["xla_ms"] > 0 and row["kernel_ms"] > 0 and
               row["err"] < 2e-2 for row in table)
    # with every expert held the whole layer-piece is timed as the two
    # calls that fetch and place their own rows too (PR 61), and held to
    # the three between a gather and an un-sort; a held range is not
    assert "two_calls_ms" not in table[0]
    assert table[1]["two_calls_ms"] > 0 and table[1]["three_calls_ms"] > 0
    assert table[1]["fused_err"] < 1e-5
    # from 1,024 pairs a held range walks passes of its held pairs (here
    # ONE of 512 rows, filled to about half), and a pass's return to token
    # order is timed as XLA's scatter-add and as the kernel's row copies
    # (PR 63), the two sums compared; no other shape here has a pass
    assert ["kernel_return_ms" in row for row in table] == [False, False,
                                                            True]
    assert 0 < table[2]["return_live"] <= 512
    assert table[2]["xla_return_ms"] > 0 and table[2]["return_err"] < 1e-6
    # two layers' K and V rings of both attention-only shapes, then a
    # delta-rule layer's window and state beside one layer's rings, found
    # in the compiled decode programs, then a window layer's rings of 16
    # positions beside a full layer's of 48, then two key heads under four
    # value heads beside one layer's rings of heads of 32, then two latent
    # layers' ONE ring each of 24 + 8 lines, then a selected layer's ring
    # of 24 + 8 lines with its index keys beside a window layer's latent
    # ring of 16 positions, then a Mamba-2 layer's window and state beside
    # one layer's rings, then (PR 62) the two latent rings of a published
    # layer whose routed branch is carried; the CPU's programs hold no
    # kernel call
    # then (PR 64) a Mamba-2 layer's window and state and an attention
    # layer's rings in a model whose layers have ONE sublayer each
    assert report["kv_ring"]["ring_params"] == (8 + 4 + 4 + 4 + 2 + 3 + 4 + 2
                                                + 4)
    assert report["kv_ring"]["rings"] == [[3, 2, 16, 48], [3, 2, 8, 48],
                                          [3, 2, 16, 48], [3, 2, 16, 16],
                                          [3, 2, 16, 48], [3, 2, 32, 48],
                                          [3, 1, 32, 48], [3, 1, 32, 48],
                                          [3, 1, 40, 16], [3, 2, 16, 48],
                                          [3, 1, 32, 48], [3, 2, 8, 48]]
    assert report["kv_ring"]["kernel_calls"] == 0
    # nor does a shape rule send a bucket through a blockwise kernel
    assert report["kv_ring"]["kernel_buckets"] == 0
    assert report["kv_ring"]["masked_buckets"] == 0
    # the third shape's longest prefill bucket, read for the delta rule:
    # one such layer, no kernel in a program lowered for the CPU
    # (whose ``jax.numpy`` composition splits the scan's arrays into heads
    # for its norms: what a program lowered for the TPU may not)
    rules = report["kv_ring"]["delta_rule"]
    assert [{k: v for k, v in rule.items() if k != "head_arrays"}
            for rule in rules] == 2 * [
        {"solves": 0, "kernel_calls": 0, "bucket": 8, "layers": 1,
         "kernel_layers": 0}]
    assert all(rule["head_arrays"] for rule in rules)
    # and their decode programs, read for the step: one such layer, no
    # kernel in a program lowered for the CPU, whose body gathers the two
    # rows' pages (two heads of 8 x 16; four of 8 x 8), the step timed warm
    steps = report["kv_ring"]["delta_step"]
    assert [{k: v for k, v in step.items() if k not in ("ms", "row_pages")}
            for step in steps] == 2 * [
        {"kernel_calls": 0, "rows": 2, "layers": 1, "kernel_layers": 0}]
    assert "f32[2,8,2,16]" in steps[0]["row_pages"]
    assert "f32[2,8,4,8]" in steps[1]["row_pages"]
    assert all(step["ms"] > 0 for step in steps)
    # the last shape's decode program, read for the Mamba-2 step: one such
    # layer, no kernel in a program lowered for the CPU (nor on any
    # platform for a state of 16 lanes), whose body advances the rows'
    # pages one by one and gathers nothing (the CPU donates no buffer, so
    # its program copies them: judged on a device only)
    step, grouped = report["kv_ring"]["ssm_step"]
    # (the twelfth model's Mamba-2 layer of four groups: as the tenth's)
    assert {k: grouped[k] for k in ("kernel_calls", "rows", "layers")} == {
        "kernel_calls": 0, "rows": 2, "layers": 1}
    # the two routed models' longest prefill: no kernel call on the CPU,
    # three matrices a gated expert and TWO an ungated one (PR 64)
    assert [(r["matrices"], r["kernel_calls"])
            for r in report["kv_ring"]["routed"]] == [(3, 0), (2, 0)]
    assert {k: v for k, v in step.items()
            if k not in ("ms", "copies", "booked")} == {
        "kernel_calls": 0, "row_pages": [], "rows": 2, "layers": 1,
        "kernel_layers": 0}
    # three tokens after the first, one row a step: the batcher booked
    # their window-and-state bytes, and none of them for the kernel
    window, state = 3 * (4 * 8 + 2 * 16) * 4, 4 * 8 * 16 * 4
    assert step["booked"] == [2 * 3 * (window + state), 0]
    assert step["ms"] > 0
    # every tenant's prefill buckets timed warm (judged on a device only)
    assert [sorted(ms) for ms in report["kv_ring"]["prefill_ms"]] == [
        ["16", "8"], ["8"], ["8"], ["8"], ["8"], ["8"], ["8"], ["8"], ["8"],
        ["8"]]
    assert all(v > 0 for ms in report["kv_ring"]["prefill_ms"]
               for v in ms.values())
    chip_smoke.run_phase("four_chips", chip_smoke.phase_four_chips,
                         TINY["four_chips"], [mx.cpu(i) for i in range(4)],
                         clock, report)
    assert all(r["ok"] for r in report.values())
    assert report["train"]["device"] == str(jax.devices()[2])
    assert report["four_chips"]["predictor_device"] == str(jax.devices()[3])
    assert clock.seconds > 0  # the AOT wrapper's compiles are seen
    assert report["train"]["mfu_gauge"] is None  # CPU: no peak, no MFU
    # the K-step fit's second block was dispatched behind the unread first
    assert report["train"]["runahead_blocks"] == TINY["train"]["blocks"] - 1
    assert telemetry.counter_value("mem.program_fallbacks") == fallbacks


@pytest.mark.parametrize("ms,slow", [
    # OPT-1.3B's four prefill programs at the parent of PR 36 and since
    ({64: 4.02, 128: 3.94, 256: 21.19, 512: 5.67}, [256]),
    ({64: 4.02, 128: 3.94, 256: 4.4, 512: 5.67}, []),
    # a small bucket may cost what the next one costs, not half as much again
    ({64: 6.1, 2048: 4.0}, [64]),
    ({768: 13.6, 1024: 17.7, 1536: 26.8, 2048: 37.7}, []),
    ({64: 1.0}, [])])
def test_a_bucket_slower_than_its_larger_neighbour_is_named(ms, slow):
    assert chip_smoke.slow_buckets(ms) == slow


def test_the_delta_rule_facts_count_solves_and_kernel_calls():
    """`delta_rule_hlo_facts` on the three ways a chunk's solve shows in
    optimised HLO: the op, the custom calls a TPU expands it to, and the
    Pallas kernel that replaces it."""
    kernel = ('%x = (f32[1,64,384]{2,1,0}, f32[1,16,384]{2,1,0}) custom-call('
              '%q), custom_call_target="tpu_custom_call"\n')
    assert chip_smoke.delta_rule_hlo_facts(3 * kernel) == {
        "solves": 0, "kernel_calls": 3, "head_arrays": []}
    old = ('%t = f32[8,8]{1,0} triangular-solve(%a, %b), left_side=true\n'
           '%i = f32[1,32,30,1,64,64]{5,4,3,2,1,0} custom-call(%a), '
           'custom_call_target="InvertDiagBlocksLowerTriangular"\n')
    assert chip_smoke.delta_rule_hlo_facts(old + kernel) == {
        "solves": 2, "kernel_calls": 1, "head_arrays": []}


@pytest.mark.parametrize("why,line,found", [
    ("XLA's L2 norm reduces over a head's keys",
     '%r = f32[2048,30]{0,1} reduce(f32[2048,30,96]{0,2,1} %q, f32[] %c), '
     'metadata={op_name="jit(f)/l0_gdn/mx:gdn.scan/reduce_sum"}',
     ["f32[2048,30,96]"]),
    ("q repeated to the value heads",
     '%b = f32[1,2048,32,128]{3,2,1,0} broadcast(f32[1,2048,16,128]{3,2,1,0} '
     '%q), metadata={op_name="jit(f)/mx:gdn.scan/broadcast_in_dim"}',
     ["f32[1,2048,16,128]", "f32[1,2048,32,128]"]),
    ("the gated norm's over a head's values",
     '%m = f32[2048,30,192]{0,2,1} multiply(%o, %o), '
     'metadata={op_name="jit(f)/mx:gdn.scan/mul"}', ["f32[2048,30,192]"]),
    ("the riders' step norms its rows under its own scope",
     '%r = f32[8,30]{1,0} reduce(f32[8,30,96]{2,1,0} %q, f32[] %c), '
     'metadata={op_name="jit(f)/l0_gdn/mx:gdn.step/reduce_sum"}', []),
    ("heads side by side on the lanes are what the kernel reads",
     '%c = f32[1,2048,11520]{2,1,0} fusion(%raw), '
     'metadata={op_name="jit(f)/mx:gdn.scan/mul"}', []),
    ("the gates a head a position are no head's width",
     '%g = f32[1,2048,30]{2,1,0} fusion(%a), '
     'metadata={op_name="jit(f)/mx:gdn.scan/mul"}', []),
    ("an attention layer's heads are another scope's",
     '%k = f32[1,2048,30,96]{3,2,1,0} fusion(%x), '
     'metadata={op_name="jit(f)/mx:attn.prefill/mul"}', [])])
def test_the_delta_rule_facts_name_what_is_split_into_heads(why, line, found):
    """`delta_rule_hlo_facts`' third fact: arrays of positions x heads x
    a head's width under the scan's scope — XLA's norms and repeat, which
    a program whose kernel does them (PR 51) may not hold."""
    pairs = [(16, 128), (32, 128)] if "16,128" in line else [(30, 96),
                                                             (30, 192)]
    assert chip_smoke.delta_rule_hlo_facts(line, pairs)["head_arrays"] \
        == found, why


_MAMBA_ENTRY = ("ENTRY %main (p: f32[9,128,64,128]) -> f32[8,1] {\n"
                "%p = f32[9,128,64,128]{3,2,1,0:T(8,128)} parameter(0)\n")


@pytest.mark.parametrize("why,line,found", [
    ("the step kernel, its state in HBM and aliased",
     '%k = (f32[8,4,64,32]{3,2,1,0:T(8,128)S(1)}, f32[9,128,64,128]{3,2,1,0:'
     'T(8,128)}) custom-call(%slot, %decay, %cols, %rows, %p), '
     'custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/'
     'l0_ssm/mx:ssm.step/ssm_step_kernel/pallas_call"}',
     {"kernel_calls": 1, "row_pages": [], "copies": []}),
    ("a state buffer staged whole through the compiler's fast memory",
     '%c = (f32[9,128,64,128]{3,2,1,0:T(8,128)}, f32[9,128,64,128]{3,2,1,0:'
     'T(8,128)S(1)}, u32[]{:S(2)}) copy-start(%g)',
     {"kernel_calls": 0, "row_pages": [], "copies": [
         "%c = (f32[9,128,64,128]{3,2,1,0:T(8,128)}, f32[9,128,64,128]"
         "{3,2,1,0:T(8,128)S(1)}, u32[]{:S(2)}) copy-start(%g)"]}),
    ("the rows' pages gathered",
     '%g = f32[8,128,64,128]{3,2,1,0} gather(%p, %slot)',
     {"kernel_calls": 0, "row_pages": ["f32[8,128,64,128]"], "copies": []}),
    ("gathered with the heads' channels folded",
     '%g = f32[8,8192,128]{2,1,0} fusion(%p, %slot)',
     {"kernel_calls": 0, "row_pages": ["f32[8,8192,128]"], "copies": []}),
    ("another kernel's call, a row's inputs and the delta rule's state",
     '%k = (f32[8,1,4096]{2,1,0}, f32[9,128,4096]{2,1,0}) custom-call(%s), '
     'custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/'
     'mx:gdn.step/gdn_state_step/pallas_call"}\n'
     '%d = f32[8,128,64]{2,1,0} multiply(%dt, %x)',
     {"kernel_calls": 0, "row_pages": [], "copies": []})])
def test_the_mamba2_step_facts_name_copies_and_gathered_pages(why, line,
                                                              found):
    """`ssm_step_hlo_facts` on what an 8-row decode program may make of a
    Mamba-2 state of 128 heads x 64 x 128: the step kernel's call, a copy
    of the buffer (within HBM or into fast memory), the rows' pages
    gathered."""
    assert chip_smoke.ssm_step_hlo_facts(
        _MAMBA_ENTRY + line + "\n}\n", 8, (9, 128, 64, 128)) == found, why


@pytest.mark.parametrize("why,line,found", [
    ("the scores of all heads", "%s = f32[1,30,1,2048,2048]{4,3,2,1,0} "
     "fusion(%q, %k)", ["f32[1,30,1,2048,2048]"]),
    ("the probabilities, rounded", "%p = bf16[32,2048,2048]{2,1,0} convert("
     "%s)", ["bf16[32,2048,2048]"]),
    ("a weight of bucket x bucket is no score", "%w = f32[2048,2048]{1,0} "
     "parameter(3)", []),
    ("nor the activations of a model as wide", "%h = f32[1,2048,2048]{2,1,0}"
     " fusion(%x)", []),
    ("another bucket's", "%s = f32[1,30,1,1024,1024]{4,3,2,1,0} fusion(%q)",
     []),
    ("K of a head", "%k = f32[1,30,2048,128]{3,2,1,0} transpose(%x)", [])])
def test_a_prompts_scores_made_whole_are_found(why, line, found):
    assert chip_smoke.score_arrays(line + "\n", 2048) == found, why


@pytest.mark.parametrize("why,line,found", [
    ("two heads' scores against the last run's keys", "%s = f32[2,512,15360]"
     "{2,1,0} fusion(%q, %k)", ["f32[2,512,15360]"]),
    ("sixteen heads' against the first run's", "%s = (f32[16,512]{1,0}, "
     "f32[16,512,4096]{2,1,0}) fusion(%s)", ["f32[16,512,4096]"]),
    ("keys that end no run", "%s = f32[2,512,2048]{2,1,0} fusion(%q)", []),
    ("the probabilities, rounded, are another array's fault", "%p = "
     "bf16[2,512,15360]{2,1,0} convert(%s)", []),
    ("a block of the mask has no heads", "%m = pred[512,15360]{1,0} "
     "slice(%keep)", []),
    ("the kernel's context", "%c = f32[16,15360,128]{2,1,0} custom-call(%q)",
     [])])
def test_a_runs_scores_made_whole_are_found(why, line, found):
    """A 15,360 bucket's thirty blocks of 512 queries go in runs that end
    at 4,096, 8,192, 12,288 and 15,360 keys."""
    assert chip_smoke.run_score_arrays(line + "\n", 15360) == found, why
    assert chip_smoke.run_score_arrays(
        "%s = f32[2,512,2560]{2,1,0} fusion(%q)\n", 2560) == [
            "f32[2,512,2560]"]       # a short bucket is ONE run


def test_a_failing_phase_propagates():
    def boom(sizes, ctx):
        chip_smoke._check(False, "boom")

    report = {}
    with pytest.raises(RuntimeError, match="boom"):
        chip_smoke.run_phase("x", boom, {}, mx.cpu(), chip_smoke.CompileClock(),
                             report)
    assert report == {}


def test_the_result_line_has_exactly_the_contract_keys(monkeypatch, capsys):
    """main() past the device check (backend and context stubbed, phases
    empty): the LAST stdout line is {"ok", "device": {"platform", "kind",
    "count"}} and nothing else — the driver refuses any other key there;
    the per-phase report rides the line before it."""
    import json

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(mx, "tpu", lambda i=0: mx.cpu(i))
    # main() holds its PROCESS to no AOT fallback; an earlier test file of
    # this worker may have fallen back on purpose (tests/test_lazy.py)
    telemetry.reset()
    for name in ("fence", "train", "serve", "generate", "kv_ring",
                 "grouped_matmul"):
        monkeypatch.setattr(chip_smoke, "phase_" + name, lambda s, c: {})
    monkeypatch.setattr(chip_smoke, "phase_four_chips",
                        lambda s, c: {"predictor_device": "x"})
    chip_smoke.main()
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert set(result) == {"ok", "device"} and result["ok"] is True
    device = result["device"]
    assert set(device) == {"platform", "kind", "count"}
    assert device == {"platform": jax.devices()[0].platform,
                      "kind": jax.devices()[0].device_kind,
                      "count": len(jax.devices())}
    assert isinstance(device["count"], int)
    assert lines[-2].startswith("[chip_smoke] report ")
    report = json.loads(lines[-2][len("[chip_smoke] report "):])
    assert set(report["phases"]) == {"fence", "train", "serve", "generate",
                                     "kv_ring", "grouped_matmul",
                                     "four_chips"}


# ----------------------------------------------------------------------
# a context names a device or raises
# ----------------------------------------------------------------------

def test_contexts_name_a_device_or_raise():
    assert mx.cpu(7).jax_device() == jax.devices()[7]
    for ctx in (mx.tpu(0), mx.gpu(0), mx.cpu(9), mx.cpu(-1)):
        with pytest.raises(mx.MXNetError, match="names no device"):
            ctx.jax_device()


def test_make_mesh_raises_on_a_duplicated_device():
    from mxnet_tpu.module.executor_group import _make_mesh

    assert _make_mesh([mx.cpu(0)]) is None
    assert _make_mesh([mx.cpu(0), mx.cpu(1)]).devices.size == 2
    with pytest.raises(mx.MXNetError, match="distinct device"):
        _make_mesh([mx.cpu(1), mx.cpu(1)])
    with pytest.raises(mx.MXNetError, match="names no device"):
        mx.mod.Module(mx.sym.Variable("data"), context=[mx.cpu(0), mx.tpu(0)]
                      ).bind(data_shapes=[("data", (2, 2))], label_shapes=None)


def test_executor_and_predictor_compute_on_their_context():
    """Bound to mx.cpu(2): outputs, gradients and the (written-back)
    parameters live on device 2, and a Predictor's bucket executors share
    ONE placed copy of each parameter."""
    dev = jax.devices()[2]
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=3,
                                name="fc")
    exe = net.simple_bind(mx.cpu(2), data=(4, 5))
    exe.arg_dict["fc_weight"][:] = np.ones((3, 5), np.float32)
    assert exe.arg_dict["fc_weight"].data.devices() != {dev}  # a label so far
    exe.forward(is_train=True, data=np.ones((4, 5), np.float32))
    exe.backward([mx.nd.ones((4, 3))])
    assert exe.outputs[0].data.devices() == {dev}
    assert exe.grad_dict["fc_weight"].data.devices() == {dev}
    assert exe.arg_dict["fc_weight"].data.devices() == {dev}  # moved once
    np.testing.assert_allclose(exe.outputs[0].asnumpy(), 5.0)
    # the default device needs no placement and gets none
    exe0 = net.simple_bind(mx.cpu(0), data=(4, 5))
    assert exe0._device is None and exe._device == dev

    params = {"arg:fc_weight": mx.nd.ones((3, 5)), "arg:fc_bias": mx.nd.zeros((3,))}
    pred = mx.Predictor(net, params, {"data": (1, 5)}, ctx=mx.cpu(2))
    pred.forward(data=np.ones((1, 5), np.float32))
    assert pred._exec.outputs[0].data.devices() == {dev}
    other = pred.executor_for({"data": (4, 5)})
    other.forward(is_train=False, data=np.ones((4, 5), np.float32))
    assert other.outputs[0].data.devices() == {dev}
    assert (other.arg_dict["fc_weight"].data
            is pred._exec.arg_dict["fc_weight"].data)
    pred.close()


def test_memory_limit_is_read_from_the_tenants_device():
    from mxnet_tpu.obs import memory

    class Dev:
        def __init__(self, limit):
            self.limit = limit

        def memory_stats(self):
            return {"bytes_limit": self.limit}

    a, b = Dev(1 << 30), Dev(2 << 30)
    try:
        assert memory.budget_bytes(a) == 1 << 30
        assert memory.budget_bytes(b) == 2 << 30
        assert memory.budget_bytes() is None  # XLA:CPU reports no limit
        with pytest.raises(memory.MemoryBudgetError):
            memory.admit("too big", 3 << 30, device=b)
    finally:
        memory._DEVICE_LIMIT.clear()


# ----------------------------------------------------------------------
# one compile cache, placeable from outside
# ----------------------------------------------------------------------

def test_compile_cache_dir_resolver(monkeypatch):
    in_code = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert base.compile_cache_dir() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == in_code  # untouched
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert base.compile_cache_dir() == os.path.join(ROOT, ".jax_cache")
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        # what import did, before any backend existed
        assert in_code == os.path.join(ROOT, ".jax_cache")


# ----------------------------------------------------------------------
# one process per chip (tools/launch.py)
# ----------------------------------------------------------------------

def test_launcher_keeps_host_roles_off_the_chip():
    for role in ("scheduler", "server"):
        env = launch._role_env(role, {"JAX_PLATFORMS": "tpu,cpu"})
        assert env == {"DMLC_ROLE": role, "JAX_PLATFORMS": "cpu"}
    assert launch._role_env("worker", {}) == {"DMLC_ROLE": "worker"}


def test_launcher_gives_each_child_one_chip_or_refuses(monkeypatch):
    class Parser:
        def error(self, msg):
            raise SystemExit(msg)

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert launch._host_chips() == []  # a CPU job is handed no chips
    assert launch._one_chip_envs(3, Parser(), "x") == [{}, {}, {}]
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "2,3")
    assert launch._host_chips() == ["2", "3"]
    envs = launch._one_chip_envs(2, Parser(), "x")
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["2", "3"]
    assert all(e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
    assert len({e["TPU_MESH_CONTROLLER_PORT"] for e in envs}) == 2
    with pytest.raises(SystemExit, match="one TPU chip per process"):
        launch._one_chip_envs(3, Parser(), "--serve-replicas 3")

    class Args:
        local_devices, num_workers = 0, 2

    with pytest.raises(SystemExit, match="One process drives all chips"):
        launch._local_spmd_env(Args, Parser())
    Args.local_devices = 2  # forced host devices: a CPU job by definition
    assert launch._local_spmd_env(Args, Parser()) == {
        "MXTPU_LOCAL_DEVICES": "2", "JAX_PLATFORMS": "cpu"}
