"""State-space mixer operators — the Mamba-2 layer (Dao & Gu,
arXiv:2405.21060) in the three forms a hybrid decoder needs.

All three take the layer's fused input projection ``data (N, T,
d_inner + conv_dim + H)`` laid out ``[z | x | B | C | dt]`` (``conv_dim =
d_inner + 2 G S``; H heads of P channels, ``d_inner = H P``; G groups
share one ``B`` and ``C`` of S states) and the mixer's small parameters,
and return the gated, normalized ``y (N, T, d_inner)`` the output
projection consumes:

    xBC = silu(causal_depthwise_conv1d(xBC) + conv_bias)
    dt  = softplus(dt + dt_bias);  a = -exp(A_log)            per head
    S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t              (H, P, S)
    y_t = S_t C_t + D x_t
    y   = RMSNorm_g(y * silu(z)) * norm_gamma    (gate first; the `n_groups`
          equal runs of the d_inner channels — a group's heads — each times
          the rsqrt of its OWN mean square; one group: all channels at once)

* ``_ssm_scan`` — a whole sequence from an empty state: training and
  scoring.  The recurrence runs in its chunked block form (the paper's
  SSD): inside a chunk of `chunk_size` positions ``(C B^T o decay) X`` as
  matrix products, one carried state between chunks.
* ``_ssm_prefill`` — the same over a PADDED sequence bucket, for serving:
  ``length`` is the prompt's true length, positions at and beyond it get
  ``dt = 0`` (decay 1, no input), so the carried state stops at the
  prompt's tail; the layer's conv window (the last ``d_conv - 1`` raw
  ``xBC`` rows before ``length``, zeros before the sequence) and its
  final state are written WHOLE at ``slot`` of the session's state
  buffers — nothing of the slot's previous tenant survives a prefill.
* ``_ssm_step`` — one position for B packed decode rows, each at its
  slot: read the slot's window and state, advance one step, write both
  back where they lay (in place under the serve program's donation, as
  the KV ring's ``_write_rows``).  Padded rows point at the scratch slot
  and dirty only it.  The state's update — one multiply-add a state
  element, one reduction over the states — is ONE Pallas kernel a layer
  on a TPU (``ops/ssm_step_kernel.py``: grid ``(row, block of heads)``,
  the block of ``slot[b]``'s page brought to VMEM by the pipeline,
  advanced and sent back: a page is read once and written once, and the
  buffer never leaves HBM whole) wherever ``step_heads`` says the kernel
  tiles the state's shape; ``lax.platform_dependent`` chooses at
  lowering, and `_step_body` — a ``dynamic_index``, the advance and a
  ``dynamic_update_slice`` a row — is what runs everywhere else and the
  kernel's oracle (XLA made some forty fusions a layer of it and staged
  four of granite-4.0-h-small's nine 37.7 MB buffers whole through its
  fast memory: 62% of the chip's bandwidth, PERF.md section 6, PR 58).
  The kernel is lowered once a shape for all programs and processes
  (``ops/exported.py``).  The window, the conv, ``dt`` and the gated norm
  are XLA's on every platform.

Stored shapes belong to the model (``TransformerLM.cache_spec``): a conv
window is ``(slots, d_conv - 1, conv_dim)`` — the channels on the lanes —
and a state ``(slots, H, P, S)``.

Precision: everything after the projection — the conv, ``softplus``,
``exp``, the cumulative decays, the block products of the scan and the
gated norm — is float32, and the scan's matrix products run at
``highest`` (they are a few percent of a layer's operations; at one
bfloat16 pass the carried state would round like a bf16 recurrence); the
step's products are multiply-adds on the vector unit, in the kernel as in
the body.  ``_ssm_scan`` and ``_ssm_prefill`` are pure ``jax.numpy`` /
``lax``, and so is ``_ssm_step`` off the TPU; all are differentiable
(the kernel has no backward: a gradient through a step takes the
body's, `_kernel_step_bwd`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, nn as jnn

from . import exported
from .attention import _LANES, _as_index
from .registry import register
from .tensor import _lit

_HIGHEST = lax.Precision.HIGHEST
PARAMS = ("conv_weight", "conv_bias", "dt_bias", "A_log", "D", "norm_gamma")
_ATTRS = dict(num_heads=1, head_dim=1, state_size=1, n_groups=1,
              conv_kernel=4, chunk_size=256, eps=1e-5)


def _attrs(kw):
    return {k: kw.get(k, v) for k, v in _ATTRS.items()}


def _sizes(attrs):
    """(heads H, head_dim P, state S, groups G, conv taps K) of a node."""
    return tuple(int(_lit(attrs.get(k, _ATTRS[k]))) for k in (
        "num_heads", "head_dim", "state_size", "n_groups", "conv_kernel"))


def param_shapes(heads, head_dim, state, groups, kernel):
    """Shapes of the mixer's own parameters, in `PARAMS` order."""
    d_inner = heads * head_dim
    conv_dim = d_inner + 2 * groups * state
    return [(kernel, conv_dim), (conv_dim,), (heads,), (heads,), (heads,),
            (d_inner,)]


def _infer(in_shapes, attrs, n_state=0):
    h, p, s, g, k = _sizes(attrs)
    data = in_shapes[0]
    out = tuple(data[:-1]) + (h * p,)
    ins = [data] + param_shapes(h, p, s, g, k)
    states = list(in_shapes[len(ins):len(ins) + n_state])
    return ins + states + list(in_shapes[len(ins) + n_state:]), \
        [out] + states


def _split(data, h, p, s, g):
    """``[z | xBC | dt]`` of the fused projection, in float32."""
    d_inner, conv_dim = h * p, h * p + 2 * g * s
    data = data.astype(jnp.float32)
    return (data[..., :d_inner], data[..., d_inner:d_inner + conv_dim],
            data[..., d_inner + conv_dim:])


def _split_xbc(xbc, h, p, s, g):
    lead = xbc.shape[:-1]
    d_inner = h * p
    return (xbc[..., :d_inner].reshape(lead + (h, p)),
            xbc[..., d_inner:d_inner + g * s].reshape(lead + (g, s)),
            xbc[..., d_inner + g * s:].reshape(lead + (g, s)))


def _conv_full(xbc, weight, bias):
    """Causal depthwise conv over ``xbc (N, T, C)`` from an empty
    history: ``out[t] = sum_j weight[j] * xbc[t - (K-1) + j] + bias``."""
    k, t = weight.shape[0], xbc.shape[1]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    out = bias
    for j in range(k):
        out = out + padded[:, j:j + t] * weight[j]
    return jnn.silu(out)


def _gated_norm(y, z, gamma, attrs):
    """``y * silu(z)``, then each of the node's `n_groups` runs of
    channels times the rsqrt of its own mean square, times the gain."""
    eps, groups = float(_lit(attrs["eps"])), int(_lit(attrs["n_groups"]))
    y = y * jnn.silu(z)
    if groups == 1:
        return y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                             + eps) * gamma
    runs = y.reshape(y.shape[:-1] + (groups, -1))
    runs = runs * lax.rsqrt(jnp.mean(runs * runs, axis=-1, keepdims=True)
                            + eps)
    return runs.reshape(y.shape) * gamma


def _ssd(x, dt, a, b, c, chunk):
    """The chunked scan from an empty state.  ``x (N, T, H, P)``, ``dt
    (N, T, H)`` (after softplus, 0 where a position must not count), ``a
    (H,)`` negative, ``b`` / ``c (N, T, G, S)``.  Returns ``(y (N, T, H,
    P)`` without the ``D`` term, final state ``(N, H, P, S))``."""
    n, t, h, p = x.shape
    g, s = b.shape[2:]
    r = h // g
    size = min(int(chunk), t)
    pad = -t % size
    if pad:  # dt = 0 there: the state passes through
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    nc = (t + pad) // size
    xd = (x * dt[..., None]).reshape(n, nc, size, g, r, p)
    b = b.reshape(n, nc, size, g, s)
    c = c.reshape(n, nc, size, g, s)
    # inclusive cumulative log-decay inside each chunk, heads leading
    cum = jnp.cumsum((dt * a).reshape(n, nc, size, g, r), axis=2)
    cum = cum.transpose(0, 1, 3, 4, 2)                   # (N, nc, G, R, L)
    # in-chunk: y[l] += sum_{m <= l} exp(cum[l] - cum[m]) (C_l . B_m) xd[m]
    seg = cum[..., :, None] - cum[..., None, :]          # (..., L, L)
    keep = jnp.tril(jnp.ones((size, size), bool))
    decay = jnp.exp(jnp.where(keep, seg, -jnp.inf))
    cb = jnp.einsum("nclgs,ncmgs->ncglm", c, b, precision=_HIGHEST)
    y = jnp.einsum("ncgrlm,ncmgrp->nclgrp", cb[:, :, :, None] * decay, xd,
                   precision=_HIGHEST)
    # what each chunk adds to the state at its own end
    to_end = jnp.exp(cum[..., -1:] - cum)                # (N, nc, G, R, L)
    added = jnp.einsum("nclgs,ncgrl,nclgrp->ncgrps", b, to_end, xd,
                       precision=_HIGHEST)
    through = jnp.exp(cum[..., -1])                      # (N, nc, G, R)

    def carry(state, chunk_in):
        add, dec = chunk_in
        return state * dec[..., None, None] + add, state

    final, before = lax.scan(
        carry, jnp.zeros((n, g, r, p, s), jnp.float32),
        (added.transpose(1, 0, 2, 3, 4, 5), through.transpose(1, 0, 2, 3)))
    # the state a chunk started from, decayed to each of its positions
    y = y + jnp.einsum("nclgs,cngrps,ncgrl->nclgrp", c, before, jnp.exp(cum),
                       precision=_HIGHEST)
    return (y.reshape(n, nc * size, h, p)[:, :t],
            final.reshape(n, h, p, s))


def _mix(data, conv_weight, conv_bias, dt_bias, a_log, d_skip, norm_gamma,
         attrs, length=None):
    """Conv, scan and gated norm of whole sequences; positions at and
    beyond ``length (N,)`` leave the state untouched.  Returns ``(y, raw
    xBC, final state)``."""
    h, p, s, g, _ = _sizes(attrs)
    z, xbc_raw, dt = _split(data, h, p, s, g)
    x, b, c = _split_xbc(_conv_full(xbc_raw, conv_weight, conv_bias),
                         h, p, s, g)
    dt = jnn.softplus(dt + dt_bias)
    if length is not None:
        live = jnp.arange(data.shape[1])[None, :] < length[:, None]
        dt = jnp.where(live[..., None], dt, 0.0)
    y, final = _ssd(x, dt, -jnp.exp(a_log.astype(jnp.float32)), b, c,
                    _lit(attrs["chunk_size"]))
    y = (y + d_skip[:, None] * x).reshape(data.shape[:2] + (h * p,))
    y = _gated_norm(y, z, norm_gamma, attrs)
    return y.astype(data.dtype), xbc_raw, final


@register("_ssm_scan", inputs=("data",) + PARAMS, infer_shape=_infer)
def ssm_scan(data, conv_weight, conv_bias, dt_bias, A_log, D, norm_gamma,
             **kw):
    """The Mamba-2 mixer over whole sequences ``data (N, T, d_proj)``
    from an empty state (module docstring); returns ``y (N, T,
    d_inner)``."""
    with jax.named_scope("mx:ssm.scan"):
        return _mix(data, conv_weight, conv_bias, dt_bias, A_log, D,
                    norm_gamma, _attrs(kw))[0]


def _infer_stateful(in_shapes, attrs):
    return _infer(in_shapes, attrs, n_state=2)


@register("_ssm_prefill",
          inputs=("data",) + PARAMS + ("conv_state", "ssm_state", "slot",
                                       "length"),
          num_outputs=3, infer_shape=_infer_stateful)
def ssm_prefill(data, conv_weight, conv_bias, dt_bias, A_log, D, norm_gamma,
                conv_state, ssm_state, slot, length, **kw):
    """Serving prefill of the mixer: ``data (N, T, d_proj)`` padded to a
    bucket, ``length (N,)`` the true lengths.  Outputs ``y``, and the two
    state buffers with row n's conv window and final state — both as of
    position ``length[n]``, the pad not counted — written at
    ``slot[n]``."""
    attrs = _attrs(kw)
    k = _sizes(attrs)[4]
    slot_i, len_i = _as_index(slot), _as_index(length)
    with jax.named_scope("mx:ssm.scan"):
        y, xbc_raw, final = _mix(data, conv_weight, conv_bias, dt_bias, A_log,
                                 D, norm_gamma, attrs, length=len_i)
        padded = jnp.pad(xbc_raw, ((0, 0), (k - 1, 0), (0, 0)))
        for n in range(data.shape[0]):
            # padded row i is raw row i - (K-1): the window ending at length
            window = lax.dynamic_slice_in_dim(padded[n], len_i[n], k - 1, 0)
            conv_state = lax.dynamic_update_slice(
                conv_state, window[None].astype(conv_state.dtype),
                (slot_i[n], 0, 0))
            ssm_state = lax.dynamic_update_slice(
                ssm_state, final[n][None].astype(ssm_state.dtype),
                (slot_i[n], 0, 0, 0))
    return y, conv_state, ssm_state


_STEP_BLOCK_BYTES = 1 << 20
_SUBLANES = 8


def step_heads(state_shape, platform, groups=1):
    """Heads that one grid step of the TPU's decode kernel holds
    (``ops/ssm_step_kernel.py``), for a state stored `state_shape`
    ``(slots, H, P, S)`` of float32 whose heads share ``B`` and ``C`` in
    `groups`: the most heads that divide ``H``, lie in one group or are
    whole groups, and make a block ``(heads, P, S)`` of at most 1 MiB — the
    pipeline holds two of them coming and two going, and a row of several
    blocks has the next one on its way while one is advanced — or one head
    where a head alone is larger.  granite-4.0-h-small's ``(9, 128, 64,
    128)`` and granite-4.0-h-micro's ``(9, 64, 64, 128)``, heads of 32 KiB:
    32, a quarter and a half of a page.  None where `_ssm_step` runs its
    ``jax.numpy`` body: off the TPU, or for a state the kernel's tiling
    does not divide — ``P`` no whole number of 8-row tiles, ``S`` no whole
    number of 128-lane tiles, or a head beyond 4 MiB.  Whoever counts what
    a decode step runs (``TransformerLM.call_counters``) asks here."""
    _, h, p, s = state_shape
    block = lambda n: 4 * n * p * s                   # bytes, float32
    if (platform != "tpu" or p % _SUBLANES or s % _LANES
            or block(1) > 4 * _STEP_BLOCK_BYTES):
        return None
    per_group = h // int(groups)
    fit = [n for n in range(1, h + 1) if h % n == 0
           and (n % per_group == 0 or per_group % n == 0)]
    return max([n for n in fit if block(n) <= _STEP_BLOCK_BYTES] or fit[:1])


def _step_body(dtx, decay, b, c, state, slot):
    """One position of the recurrence for B rows: ``dtx (B, H, P)``,
    ``decay (B, H)``, ``b`` / ``c (B, G, S)``, row i's page at ``slot[i]``
    of ``state (slots, H, P, S)``.  The rows' pages are advanced in row
    order, each read from its slot, stepped and written back with one
    ``dynamic_update_slice`` — XLA keeps the three in one in-place fusion
    on the donated buffer (padded rows all land on the scratch slot, one
    after the other).  Returns ``(y (B, H, P)`` without the ``D`` term,
    ``state')``."""
    h = dtx.shape[1]
    b, c = (jnp.repeat(v, h // v.shape[1], axis=1) for v in (b, c))
    ys = []
    for i in range(dtx.shape[0]):
        page = lax.dynamic_index_in_dim(state, slot[i], 0, keepdims=False)
        page = (page.astype(jnp.float32) * decay[i][:, None, None]
                + dtx[i][..., None] * b[i][:, None, :])
        ys.append((page * c[i][:, None, :]).sum(axis=-1))
        state = lax.dynamic_update_slice(
            state, page[None].astype(state.dtype), (slot[i], 0, 0, 0))
    return jnp.stack(ys), state


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _kernel_step(heads, interpret, *operands):
    def kernel(*operands):
        return exported.call("ssm_step_kernel", "state_step", operands,
                             interpret=interpret, heads=heads)
    return lax.platform_dependent(*operands, tpu=kernel, default=_step_body)


def _kernel_step_fwd(heads, interpret, *operands):
    return _kernel_step(heads, interpret, *operands), operands


def _kernel_step_bwd(heads, interpret, operands, cotangents):
    # the kernel has no backward: a gradient takes the body's
    *floats, slot = operands
    _, vjp = jax.vjp(lambda *floats: _step_body(*floats, slot), *floats)
    return vjp(cotangents) + (np.zeros(slot.shape, jax.dtypes.float0),)


_kernel_step.defvjp(_kernel_step_fwd, _kernel_step_bwd)


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _state_step(dtx, decay, b, c, state, slot, *, heads, interpret):
    """`_step_body` on whatever platform the program is lowered for: the
    TPU's kernel (`heads` heads a grid step; `interpret` runs it in
    Pallas's interpreter, for tests) or the ``jax.numpy`` body, which is
    also every platform's backward.  Jitted, so that the Mamba-2 layers of
    a decode program trace and lower both once."""
    operands = (dtx, decay, b, c, state, slot)
    if heads is None:
        return _step_body(*operands)
    return _kernel_step(heads, interpret, *operands)


# Pallas's interpreter in place of the TPU's kernel (tests)
_INTERPRET = False


@register("_ssm_step",
          inputs=("data",) + PARAMS + ("conv_state", "ssm_state", "slot"),
          num_outputs=3, infer_shape=_infer_stateful)
def ssm_step(data, conv_weight, conv_bias, dt_bias, A_log, D, norm_gamma,
             conv_state, ssm_state, slot, **kw):
    """One decode step of the mixer for B packed rows: ``data (B, 1,
    d_proj)``, row b's window and state at ``slot[b]``, each advanced by
    one position and written back where it lay (the state by `_state_step`;
    padded rows all land on the scratch slot).  Outputs ``y (B, 1,
    d_inner)`` and the two updated buffers."""
    attrs = _attrs(kw)
    h, p, s, g, k = _sizes(attrs)
    rows = data.shape[0]
    slot_i = _as_index(slot)
    with jax.named_scope("mx:ssm.step"):
        z, xbc_raw, dt = _split(data[:, 0], h, p, s, g)
        window = jnp.concatenate(
            [jnp.stack([lax.dynamic_index_in_dim(conv_state, slot_i[i], 0,
                                                 keepdims=False)
                        for i in range(rows)]).astype(jnp.float32),
             xbc_raw[:, None]], axis=1)                   # (B, K, C)
        for i in range(rows):
            conv_state = lax.dynamic_update_slice(
                conv_state, window[i, 1:][None].astype(conv_state.dtype),
                (slot_i[i], 0, 0))
        x, b, c = _split_xbc(
            jnn.silu((window * conv_weight).sum(axis=1) + conv_bias),
            h, p, s, g)
        dt = jnn.softplus(dt + dt_bias)                   # (B, H)
        decay = jnp.exp(dt * -jnp.exp(A_log.astype(jnp.float32)))
        # the heads a lowering for the TPU would hold at a time; which
        # platform the program is lowered for is not known here.  Called
        # on arrays, not traced into a program (``mx.nd``'s eager path),
        # the step is a program of its own that donates nothing: the body
        # (the kernel's pinned state needs the donation a whole program's
        # caller gives it: ops/ssm_step_kernel.py)
        traced = isinstance(ssm_state, jax.core.Tracer)
        y, ssm_state = _state_step(
            dt[..., None] * x, decay, b, c, ssm_state, slot_i,
            interpret=_INTERPRET,
            heads=step_heads(ssm_state.shape, "tpu", g) if traced else None)
        y = _gated_norm((y + D[:, None] * x).reshape(rows, h * p), z,
                        norm_gamma, attrs)
    return y[:, None].astype(data.dtype), conv_state, ssm_state
