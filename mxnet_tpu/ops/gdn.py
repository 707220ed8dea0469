"""Gated DeltaNet — the delta-rule linear-attention mixer (Yang, Kautz &
Hatamizadeh, arXiv:2412.06464; the delta rule in chunks: Yang et al.,
arXiv:2406.06484) in the three forms a hybrid decoder needs.

All three take the layer's fused input projection ``data (N, T, 2 H_k
d_k + 2 H d_v + 2 H)`` laid out ``[q | k | v | z | b | a]`` (`num_heads` H
VALUE heads — v and the gate z of `value_dim` d_v, one ``b`` and one ``a``
a head, one state a head — and `num_key_heads` H_k heads of q and k of
`key_dim` d_k: as many as value heads unless the node says otherwise,
else a divisor of H, and value head n then reads q/k head ``n // (H /
H_k)`` — the conv and the L2 norms run over the H_k heads, and q and k are
repeated to H heads AFTER them) and the mixer's small parameters, and
return the gated, normalized ``y (N, T, H d_v)`` the output projection
consumes.  Per value head:

    [q | k | v] = silu(causal_depthwise_conv1d(q | k | v))      no bias
    q = q / ||q|| / sqrt(d_k);   k = k / ||k||
    beta = beta_scale * sigmoid(b)            2 admits negative eigenvalues
    alpha = exp(-exp(A_log) * softplus(a + dt_bias))
    S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T
    o_t = S_t q_t                                      S is (d_v, d_k)
    y   = RMSNorm_{d_v}(o) * norm_gamma * silu(z)      gamma shared by heads

Where Mamba-2's state only decays and accumulates, this one is multiplied
by ``alpha (I - beta k k^T)`` at every position: a write first takes out
what the state already answers for its key.

* ``_gdn_scan`` — a whole sequence from an empty state: training and
  scoring, in the chunked WY / UT form.  Inside a chunk of `chunk_size`
  positions the rule's corrections form a unit lower-triangular system
  in ``beta``, ``K K^T`` and the cumulative decays; it is SOLVED once a
  chunk (``(I + A) [U | W] = beta [V | K Gamma]``), after which a chunk's
  output is an in-chunk product plus the carried state's part, and one
  state is carried from chunk to chunk.
* ``_gdn_prefill`` — the same over a PADDED sequence bucket, for serving:
  positions at and beyond ``length`` get ``alpha = 1, beta = 0``, so the
  state passes through them; the conv window (the last ``K - 1`` raw
  ``[q | k | v]`` rows before ``length``, zeros before the sequence) and
  the final state are written WHOLE at ``slot``.  On a TPU everything
  between the conv's SiLU and the output projection — the L2 norms of q
  and k, the repeat to value heads, the chunked rule and the gated norm
  — is ONE Pallas kernel a layer (``ops/gdn_kernel.py``: it turns a
  chunk head-leading in VMEM, where both norms are a reduction along a
  row of lanes and the repeat is an index; the chunk's solve and
  products stay there, the state is carried there and comes out as it
  is stored) wherever ``chunk_heads`` says the kernel tiles the shape;
  ``lax.platform_dependent`` chooses at lowering, and `_normed_rule`
  below (`_heads`, `_chunked`, `_gated_norm`) is what runs everywhere
  else and the kernel's oracle: there is no shape on which the kernel
  runs with XLA's norms around it (they cost more than the kernel's own
  work: the reductions over a head's 96 or 192 lanes of an array laid
  out for neither made XLA turn it over twice, PERF.md section 6, PR
  51).  The kernel is lowered once a shape for all programs and processes
  (``ops/exported.py``: a ``jax.export`` kept beside JAX's compiled
  programs), so that a warm start neither imports Pallas nor traces the
  kernel for its first prefill; the step's kernel likewise.
* ``_gdn_step`` — one position for B packed decode rows, each row's page
  advanced where it lies in the donated buffer.  Both products with the
  old state — ``S k`` for the correction and ``S q`` for the output, ``o =
  alpha S q + beta (v - alpha S k)(k . q)`` — and the update ``alpha S +
  k write^T`` need ONE read of the page.  On a TPU that is ONE Pallas
  kernel a layer over the packed rows (``ops/gdn_step_kernel.py``: grid
  ``(row, head group)``, the block of ``slot[b]``'s page brought to VMEM
  by the pipeline, a head's key column spread over that head's lanes in
  registers, the block sent back: a page is read once and written once)
  wherever ``step_heads`` says the kernel tiles the state's shape;
  ``lax.platform_dependent`` chooses at lowering, and `_step_body` is
  what runs everywhere else and the kernel's oracle: the rows' pages
  gathered, advanced in one expression and scattered back, nothing
  spread out to ``rows x page`` (XLA made that of the keys and queries,
  33.5 MB an operand a layer at 16 rows of 128 x 4,096, and read every
  page three times: PERF.md section 6, PR 41).  The step's L2 norms and
  gated norm are XLA's (`_heads`, `_gated_norm`: B rows of them).

The conv window, the conv and the gates are XLA's on every platform and
in all three forms.

Stored shapes belong to the model (``TransformerLM.cache_spec``): a conv
window ``(slots, K - 1, 2 H_k d_k + H d_v)`` and a state ``(slots, d_k, H *
d_v)`` — the KEY axis leading and every head's values side by side on the
lanes, so that for heads of 96 x 192 a TPU tile pads nothing (5,760 = 45
x 128 lanes, 96 = 12 x 8 sublanes; stored ``(H, d_v, d_k)`` each line of
96 would take 128) and ``S k`` is a sum of whole vectors.

Precision: everything after the projection is float32, the chunk's
solve and products at ``highest`` (ops/ssm.py says why: at one bfloat16
pass the carried state rounds like a bf16 recurrence), the step's
products multiply-adds on the vector unit, in the kernel as in the body.
``_gdn_scan`` is pure ``jax.numpy`` / ``lax``, and so are ``_gdn_prefill``
and ``_gdn_step`` off the TPU; ``_gdn_scan`` is differentiable (the
kernels have no backward and are not on its path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, nn as jnn
from jax.scipy.linalg import solve_triangular

from . import exported
from .attention import _LANES, _as_index
from .ssm import _conv_full
from .registry import register
from .tensor import _bool, _lit

_HIGHEST = lax.Precision.HIGHEST
_L2_EPS = 1e-6        # inside the root of q's and k's norm
PARAMS = ("conv_weight", "dt_bias", "A_log", "norm_gamma")
_ATTRS = dict(num_heads=1, num_key_heads=None, key_dim=1, value_dim=1,
              conv_kernel=4, chunk_size=64, neg_eigval=True, eps=1e-6)


def _attrs(kw):
    return {k: kw.get(k, v) for k, v in _ATTRS.items()}


def _sizes(attrs):
    """(q/k heads H_k, value heads H, key width d_k, value width d_v, conv
    taps K) of a node; without `num_key_heads`, ``H_k = H``."""
    h, dk, dv, k = (int(_lit(attrs.get(k, _ATTRS[k]))) for k in (
        "num_heads", "key_dim", "value_dim", "conv_kernel"))
    hk = attrs.get("num_key_heads")
    return (h if hk is None else int(_lit(hk))), h, dk, dv, k


def conv_channels(heads, key_dim, value_dim, key_heads=None):
    """Channels of ``[q | k | v]``, what the conv runs over."""
    key_heads = heads if key_heads is None else key_heads
    return 2 * key_heads * key_dim + heads * value_dim


def param_shapes(heads, key_dim, value_dim, kernel, key_heads=None):
    """Shapes of the mixer's own parameters, in `PARAMS` order."""
    conv_dim = conv_channels(heads, key_dim, value_dim, key_heads)
    return [(kernel, conv_dim), (heads,), (heads,), (value_dim,)]


def _infer(in_shapes, attrs, n_state=0):
    hk, h, dk, dv, k = _sizes(attrs)
    data = in_shapes[0]
    out = tuple(data[:-1]) + (h * dv,)
    ins = [data] + param_shapes(h, dk, dv, k, hk)
    states = list(in_shapes[len(ins):len(ins) + n_state])
    return ins + states + list(in_shapes[len(ins) + n_state:]), \
        [out] + states


def _split(data, hk, h, dk, dv):
    """``[q k v | z | b | a]`` of the fused projection, in float32."""
    conv_dim = conv_channels(h, dk, dv, hk)
    data = data.astype(jnp.float32)
    z_end = conv_dim + h * dv
    return (data[..., :conv_dim], data[..., conv_dim:z_end],
            data[..., z_end:z_end + h], data[..., z_end + h:])


def _heads(qkv, hk, h, dk, dv):
    """The conv's output split into normalized ``q``, ``k (..., H, d_k)``
    and ``v (..., H, d_v)``: the H_k heads of q and k normed, then each
    repeated for the ``H / H_k`` value heads that read it."""
    lead = qkv.shape[:-1]
    q = qkv[..., :hk * dk].reshape(lead + (hk, dk))
    k = qkv[..., hk * dk:2 * hk * dk].reshape(lead + (hk, dk))
    v = qkv[..., 2 * hk * dk:].reshape(lead + (h, dv))
    q, k = (x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS)
            for x in (q, k))
    if hk != h:
        q, k = (jnp.repeat(x, h // hk, axis=-2) for x in (q, k))
    return q * dk ** -0.5, k, v


def _gates(b, a, dt_bias, a_log, attrs):
    """``(beta, log alpha)``, each ``(..., H)``."""
    beta = jnn.sigmoid(b) * (2.0 if _bool(attrs["neg_eigval"]) else 1.0)
    return beta, -jnp.exp(a_log.astype(jnp.float32)) * jnn.softplus(
        a + dt_bias)


def _gated_norm(o, z, gamma, eps):
    """``RMSNorm(o) * gamma * silu(z)`` over each head's `d_v`."""
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return o * gamma * jnn.silu(z)


def _chunked(q, k, v, beta, g, chunk):
    """The delta rule in chunks from an empty state.  ``q`` / ``k (N, T,
    H, d_k)`` normalized, ``v (N, T, H, d_v)``, ``beta`` / ``g (N, T, H)``
    (``g`` the log decay; 0 and ``beta`` 0 where a position must not
    count).  Returns ``(o (N, T, H, d_v), final state (N, H, d_k,
    d_v))``."""
    n, t, h, dk = q.shape
    dv = v.shape[-1]
    size = min(int(chunk), t)
    pad = -t % size
    if pad:  # beta = 0, g = 0 there: the state passes through
        q, k, v, beta, g = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, beta, g))
    nc = (t + pad) // size

    def chunks(x):  # (N, T, H, ...) -> (N, nc, H, L, ...)
        x = x.reshape((n, nc, size, h) + x.shape[3:])
        return jnp.moveaxis(x, 3, 2)

    q, k, v, beta, g = (chunks(x) for x in (q, k, v, beta, g))
    cum = jnp.cumsum(g, axis=-1)                       # inclusive, (.., L)
    seg = cum[..., :, None] - cum[..., None, :]        # (.., L, L): l - m
    lower = jnp.tril(jnp.ones((size, size), bool))
    decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))   # m <= l
    kb = k * beta[..., None]
    # (I + A)[U | W] = [beta V | beta K Gamma], A strictly lower: what each
    # position writes once the positions before it in the chunk have
    a = jnp.einsum("nchld,nchmd->nchlm", kb, k, precision=_HIGHEST) * decay
    rhs = jnp.concatenate(
        [v * beta[..., None], kb * jnp.exp(cum)[..., None]], axis=-1)
    solved = solve_triangular(a, rhs, lower=True, unit_diagonal=True)
    u, w = solved[..., :dv], solved[..., dv:]
    qk = jnp.einsum("nchld,nchmd->nchlm", q, k, precision=_HIGHEST) * decay
    q_in = q * jnp.exp(cum)[..., None]                 # reads the carried
    k_out = k * jnp.exp(cum[..., -1:] - cum)[..., None]  # decayed to the end
    through = jnp.exp(cum[..., -1])                    # (N, nc, H)

    def carry(state, xs):  # state (N, H, d_k, d_v), one chunk
        u_c, w_c, qk_c, q_c, k_c, thr = xs
        v_new = u_c - jnp.einsum("nhld,nhdv->nhlv", w_c, state,
                                 precision=_HIGHEST)
        o = (jnp.einsum("nhld,nhdv->nhlv", q_c, state, precision=_HIGHEST)
             + jnp.einsum("nhlm,nhmv->nhlv", qk_c, v_new,
                          precision=_HIGHEST))
        state = (state * thr[..., None, None]
                 + jnp.einsum("nhld,nhlv->nhdv", k_c, v_new,
                              precision=_HIGHEST))
        return state, o

    final, o = lax.scan(
        carry, jnp.zeros((n, h, dk, dv), jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (u, w, qk, q_in, k_out, through)))
    o = jnp.moveaxis(o, (0, 2), (1, 3))                # (N, nc, L, H, d_v)
    return o.reshape(n, nc * size, h, dv)[:, :t], final


def _stored(state):
    """``(N, H, d_k, d_v)`` as the session stores it, ``(N, d_k, H *
    d_v)``."""
    n, h, dk, dv = state.shape
    return state.transpose(0, 2, 1, 3).reshape(n, dk, h * dv)


def _stored_chunked(q, k, v, beta, g, chunk):
    """`_chunked` with the final state as the session stores it."""
    o, final = _chunked(q, k, v, beta, g, chunk)
    return o, _stored(final)


def _normed_rule(qkv, z, beta, g, gamma, *, key_heads, eps, chunk):
    """What lies between the conv's SiLU and the output projection, for
    whole sequences from an empty state: ``qkv (N, T, 2 H_k d_k + H d_v)``
    the conv's output, ``z (N, T, H d_v)`` the gate, ``beta`` / ``g (N, T,
    H)``, ``gamma (d_v,)``.  q and k normed and repeated to the value
    heads (`_heads`), the rule in chunks (`_stored_chunked`), the gated
    norm (`_gated_norm`).  Returns ``(y (N, T, H d_v), final state (N, d_k,
    H d_v))``.  The differentiable body of `_gdn_scan`, what `_gdn_prefill`
    runs off the TPU, and the oracle of the TPU's kernel, which takes the
    same operands (``ops/gdn_kernel.py``)."""
    h, dv = beta.shape[-1], gamma.shape[0]
    dk = (qkv.shape[-1] - h * dv) // (2 * key_heads)
    q, k, v = _heads(qkv, key_heads, h, dk, dv)
    o, final = _stored_chunked(q, k, v, beta, g, chunk)
    y = _gated_norm(o, z.reshape(o.shape), gamma, eps)
    return y.reshape(z.shape), final


_SUBLANES = 8
_WALK_BYTES = 4 << 20
_VMEM_BYTES = 24 << 20


def chunk_heads(shape, value_dim, chunk, platform, key_heads=None):
    """Value heads that one step of the TPU kernel's walk over a chunk
    takes (``ops/gdn_kernel.py``), for ``q`` / ``k`` of `shape` ``(N, T, H,
    d_k)`` once repeated to the ``H`` value heads (they come `key_heads`
    wide, ``H`` where not given), values of `value_dim` and chunks of
    `chunk` positions: the most heads that divide ``H`` and whose operands
    and products of one chunk are within 4 MiB — an even number where one
    fits (the kernel solves two heads' systems side by side).  None where
    `_gdn_prefill` runs its ``jax.numpy`` body: off the TPU, or for a
    shape the kernel's tiling does not divide — ``T`` no whole number of
    chunks, a chunk that is no whole number of 8-row tiles, or a chunk of
    all heads (the pipeline's two buffers of ``[q | k]``, ``v``, the gate
    ``z``, ``y``, the gates and the state; the head-leading copies of q,
    k and v — ``o`` takes v's place — and the state) beyond 24 MiB of the
    32 MiB of VMEM the kernel asks for (it asks for no more: what a kernel
    may use, XLA may not keep activations in across it).  Whoever counts
    what a prefill runs (``TransformerLM.call_counters``) asks here."""
    _, t, h, dk = shape
    hk = h if key_heads is None else int(key_heads)
    size = min(int(chunk), t)
    if platform != "tpu" or not size or t % size or size % _SUBLANES:
        return None
    pad = lambda width: -(-width // _LANES) * _LANES
    dv = int(value_dim)
    piped = (2 * size * (2 * hk * dk + 3 * h * dv + 2 * pad(h))
             + 2 * dk * h * dv)
    turned = (size * (2 * hk * pad(dk) + h * pad(dv)) + h * dk * pad(dv)
              + 2 * h * pad(size))
    if 4 * (piped + turned) > _VMEM_BYTES:
        return None
    a_head = 4 * (size * (3 * pad(dk) + 4 * pad(dv) + 6 * pad(size))
                  + dk * pad(dv))
    fit = [n for n in range(1, h + 1)
           if h % n == 0 and (n == 1 or n * a_head <= _WALK_BYTES)]
    return max(fit, key=lambda n: (n % 2 == 0, n))


# tests flip this to have `_gdn_prefill` run the TPU's kernel in Pallas
# interpret mode on the CPU
_INTERPRET = False


@functools.partial(jax.jit, static_argnames=("key_heads", "eps", "chunk",
                                             "heads", "interpret"))
def _delta_rule(qkv, z, beta, g, gamma, *, key_heads, eps, chunk, heads,
                interpret):
    """`_normed_rule` on whatever platform the program is lowered for —
    the TPU's kernel (walking `heads` heads at a time; `interpret` runs it
    in Pallas's interpreter, for tests) or the ``jax.numpy`` composition.
    Jitted, so that the delta-rule layers of a prefill program, whose rule
    is one and the same, trace and lower both once."""
    operands = (qkv, z, beta, g, gamma)
    body = functools.partial(_normed_rule, key_heads=key_heads, eps=eps,
                             chunk=chunk)
    if heads is None:
        return body(*operands)

    def kernel(*operands):
        # lowered once a shape for all programs and processes: a serving
        # process traces a prefill first, and the exported kernel needs
        # neither Pallas (1.3 s of import that nothing hides: PERF.md
        # section 6, PR 34) nor a lowering a bucket (ops/exported.py)
        y, final = exported.call(
            "gdn_kernel", "chunked_delta_rule", operands, interpret=interpret,
            key_heads=key_heads, eps=eps, chunk=chunk, heads=heads)
        # the state's write at `slot` stays an op of its own: fused into
        # the kernel's call — which XLA does where it reckons the call
        # within the default 16 MiB of scoped VMEM — the call is held to
        # that default and not to what the kernel asks for, and a bucket
        # of 768 fails to compile by 0.6 MiB
        return y, lax.optimization_barrier(final)
    return lax.platform_dependent(*operands, tpu=kernel, default=body)


def _mix(data, conv_weight, dt_bias, a_log, norm_gamma, attrs, length=None):
    """Conv, chunked rule and gated norm of whole sequences; positions at
    and beyond ``length (N,)`` leave the state untouched (the serving
    prefill: everything after the conv may run as the TPU's kernel,
    `_delta_rule`; without `length`, training and scoring, it is the
    differentiable body).  Returns ``(y, raw [q | k | v], final state (N,
    d_k, H * d_v) as a session stores it)``."""
    hk, h, dk, dv, _ = _sizes(attrs)
    raw, z, b, a = _split(data, hk, h, dk, dv)
    qkv = _conv_full(raw, conv_weight, 0.0)
    beta, g = _gates(b, a, dt_bias, a_log, attrs)
    rule = dict(key_heads=hk, eps=float(_lit(attrs["eps"])),
                chunk=int(_lit(attrs["chunk_size"])))
    if length is None:
        y, final = _normed_rule(qkv, z, beta, g, norm_gamma, **rule)
    else:
        live = (jnp.arange(data.shape[1])[None, :] < length[:, None])[..., None]
        beta, g = jnp.where(live, beta, 0.0), jnp.where(live, g, 0.0)
        # the heads a lowering for the TPU would walk at a time; which
        # platform the program is lowered for is not known here
        y, final = _delta_rule(
            qkv, z, beta, g, norm_gamma.astype(jnp.float32),
            interpret=_INTERPRET, heads=chunk_heads(
                data.shape[:2] + (h, dk), dv, rule["chunk"], "tpu", hk),
            **rule)
    return y.astype(data.dtype), raw, final


@register("_gdn_scan", inputs=("data",) + PARAMS, infer_shape=_infer)
def gdn_scan(data, conv_weight, dt_bias, A_log, norm_gamma, **kw):
    """The Gated DeltaNet mixer over whole sequences ``data (N, T,
    d_proj)`` from an empty state (module docstring); returns ``y (N, T,
    H d_v)``."""
    with jax.named_scope("mx:gdn.scan"):
        return _mix(data, conv_weight, dt_bias, A_log, norm_gamma,
                    _attrs(kw))[0]


def _infer_stateful(in_shapes, attrs):
    return _infer(in_shapes, attrs, n_state=2)


@register("_gdn_prefill",
          inputs=("data",) + PARAMS + ("conv_state", "gdn_state", "slot",
                                       "length"),
          num_outputs=3, infer_shape=_infer_stateful)
def gdn_prefill(data, conv_weight, dt_bias, A_log, norm_gamma, conv_state,
                gdn_state, slot, length, **kw):
    """Serving prefill of the mixer: ``data (N, T, d_proj)`` padded to a
    bucket, ``length (N,)`` the true lengths.  Outputs ``y``, and the two
    state buffers with row n's conv window and final state — both as of
    position ``length[n]``, the pad not counted — written at
    ``slot[n]``."""
    attrs = _attrs(kw)
    k = _sizes(attrs)[4]
    slot_i, len_i = _as_index(slot), _as_index(length)
    with jax.named_scope("mx:gdn.scan"):
        y, raw, final = _mix(data, conv_weight, dt_bias, A_log, norm_gamma,
                             attrs, length=len_i)
        padded = jnp.pad(raw, ((0, 0), (k - 1, 0), (0, 0)))
        for n in range(data.shape[0]):
            # padded row i is raw row i - (K-1): the window ending at length
            window = lax.dynamic_slice_in_dim(padded[n], len_i[n], k - 1, 0)
            conv_state = lax.dynamic_update_slice(
                conv_state, window[None].astype(conv_state.dtype),
                (slot_i[n], 0, 0))
            gdn_state = lax.dynamic_update_slice(
                gdn_state, final[n][None].astype(gdn_state.dtype),
                (slot_i[n], 0, 0))
    return y, conv_state, gdn_state


_STEP_BLOCK_BYTES = 1 << 20


def step_heads(state_shape, value_dim, platform):
    """Value heads that one grid step of the TPU's decode kernel holds
    (``ops/gdn_step_kernel.py``), for a state stored `state_shape`
    ``(slots, d_k, H d_v)`` of float32: the most heads that divide ``H``,
    fill whole 128-lane tiles and make a block ``(d_k, heads d_v)`` of at
    most 1 MiB — the pipeline holds two of them coming and two going, and
    a row of several blocks has the next one on its way while one is
    advanced — or the fewest that fill whole tiles where none is that
    small.  Qwen3-Next's ``(17, 128, 4096)``, 32 heads of 128 values: 16,
    half a page; Olmo-Hybrid's ``(9, 96, 5760)``, 30 heads of 192 — a head
    is a tile and a half, so an even number: 10, a third of a page.  None
    where `_gdn_step` runs its ``jax.numpy`` body: off the TPU, or for a
    state the kernel's tiling does not divide — ``d_k`` no whole number
    of 8-row tiles, no group of heads a whole number of lane tiles, or
    the smallest such group beyond 4 MiB.  Whoever counts what a decode
    step runs (``TransformerLM.call_counters``) asks here."""
    _, dk, width = state_shape
    dv = int(value_dim)
    h = width // dv
    if platform != "tpu" or dk % _SUBLANES:
        return None
    block = lambda n: 4 * dk * n * dv                 # bytes, float32
    fit = [n for n in range(1, h + 1) if h % n == 0 and n * dv % _LANES == 0]
    if not fit or block(fit[0]) > 4 * _STEP_BLOCK_BYTES:
        return None
    return max([n for n in fit if block(n) <= _STEP_BLOCK_BYTES] or fit[:1])


def _step_body(k, q, v, alpha, beta, state, slot):
    """One position of the rule for B rows: ``k`` / ``q (B, H, d_k)``, ``v
    (B, H, d_v)``, ``alpha`` / ``beta (B, H)``, row b's page at
    ``slot[b]`` of ``state (slots, d_k, H d_v)``.  The rows' pages are
    gathered, advanced in one expression — a head's key against its own
    ``(d_k, d_v)`` of the page, nothing spread out to a page's size — and
    scattered back (padded rows all name the scratch slot: one of them
    stays there).  Returns ``(o (B, H, d_v), state')``."""
    rows, h, dk = k.shape
    dv = v.shape[-1]
    pages = state[slot].astype(jnp.float32).reshape(rows, dk, h, dv)
    keyed = lambda x: x.transpose(0, 2, 1)[..., None]     # (B, d_k, H, 1)
    a = alpha[..., None]
    s_k = (pages * keyed(k)).sum(axis=1) * a              # alpha S k
    s_q = (pages * keyed(q)).sum(axis=1) * a              # alpha S q
    write = beta[..., None] * (v - s_k)
    o = s_q + write * jnp.sum(k * q, axis=-1)[..., None]
    pages = pages * a[:, None] + keyed(k) * write[:, None]
    return o, state.at[slot].set(
        pages.reshape(rows, dk, h * dv).astype(state.dtype))


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _state_step(k, q, v, alpha, beta, state, slot, *, heads, interpret):
    """`_step_body` on whatever platform the program is lowered for: the
    TPU's kernel (`heads` value heads a grid step; `interpret` runs it in
    Pallas's interpreter, for tests) or the ``jax.numpy`` body.  Jitted,
    so that the delta-rule layers of a decode program trace and lower
    both once."""
    operands = (k, q, v, alpha, beta, state, slot)
    if heads is None:
        return _step_body(*operands)

    def kernel(*operands):
        return exported.call("gdn_step_kernel", "state_step", operands,
                             interpret=interpret, heads=heads)
    return lax.platform_dependent(*operands, tpu=kernel, default=_step_body)


@register("_gdn_step",
          inputs=("data",) + PARAMS + ("conv_state", "gdn_state", "slot"),
          num_outputs=3, infer_shape=_infer_stateful)
def gdn_step(data, conv_weight, dt_bias, A_log, norm_gamma, conv_state,
             gdn_state, slot, **kw):
    """One decode step of the mixer for B packed rows: ``data (B, 1,
    d_proj)``, row b's window and state at ``slot[b]``, each advanced by
    one position and written back where it lay (`_state_step`; padded
    rows all land on the scratch slot).  Outputs ``y (B, 1, H d_v)`` and
    the two updated buffers."""
    attrs = _attrs(kw)
    hk, h, dk, dv, _ = _sizes(attrs)
    rows = data.shape[0]
    slot_i = _as_index(slot)
    with jax.named_scope("mx:gdn.step"):
        raw, z, b, a = _split(data[:, 0], hk, h, dk, dv)
        window = jnp.concatenate(
            [jnp.stack([lax.dynamic_index_in_dim(conv_state, slot_i[i], 0,
                                                 keepdims=False)
                        for i in range(rows)]).astype(jnp.float32),
             raw[:, None]], axis=1)                       # (B, K, C)
        for i in range(rows):
            conv_state = lax.dynamic_update_slice(
                conv_state, window[i, 1:][None].astype(conv_state.dtype),
                (slot_i[i], 0, 0))
        q, k, v = _heads(jnn.silu((window * conv_weight).sum(axis=1)),
                         hk, h, dk, dv)
        beta, g = _gates(b, a, dt_bias, A_log, attrs)     # (B, H)
        # the heads a lowering for the TPU would hold at a time; which
        # platform the program is lowered for is not known here
        o, gdn_state = _state_step(
            k, q, v, jnp.exp(g), beta, gdn_state, slot_i,
            interpret=_INTERPRET,
            heads=step_heads(gdn_state.shape, dv, "tpu"))
        y = _gated_norm(o, z.reshape(o.shape), norm_gamma,
                        float(_lit(attrs["eps"])))
    return (y.reshape(rows, 1, h * dv).astype(data.dtype), conv_state,
            gdn_state)
