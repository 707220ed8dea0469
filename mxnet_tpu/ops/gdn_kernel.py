"""The delta rule's chunked prefill as ONE Pallas TPU kernel a layer:
everything between the conv's SiLU and the output projection.  A chunk's
L2 norms, its triangular solve, its products and its gated norm stay in
VMEM, and the state is carried there from the first chunk to the last.

The operands are those of ``ops.gdn._normed_rule``, this kernel's oracle,
read where they lie — heads side by side on the lanes: the conv's output
``qkv (N, T, 2 H_k d_k + H d_v)``, the gate ``z (N, T, H d_v)``, the gates
``beta`` / ``g (N, T, H)`` and ``gamma (d_v,)``.  A grid step ``(n, c)``
holds chunk `c` (`size` positions) of ALL heads of sequence `n` in VMEM,
brought there by the pipeline — ``[q | k]`` and ``v`` as two blocks of the
one array where their widths allow it, else as slices —; the chunk axis
is the sequential one.  A head's 96 or 192 lanes begin wherever its number
puts them, so the step first turns ``[q | k]`` (``2 H_k`` heads of `d_k`)
and ``v`` head-leading in VMEM (``(heads, size, d)`` scratch: a span of
whole 128-lane tiles is loaded, each head in it one shifted copy), then
walks the VALUE heads `heads` at a time (``ops.gdn.chunk_heads``; batched
products, so that the matrix unit has independent work while one head's
chain waits).  In that layout a head's row is a row of lanes, so the walk
norms q and k as it loads them (``x * rsqrt(sum(x^2) + 1e-6)``, q times
``d_k ** -0.5``; value head n loads key head ``n // (H / H_k)``: the
repeat to value heads is an index), and norms ``o`` over `d_v` times
`gamma` before it puts it where ``v`` was.  The heads go back side by side
times ``silu(z)`` — elementwise, so `z` is never turned — as ``y (N, T, H
d_v)``, what the output projection reads.  The state ``(H, d_k, d_v)``
lives in a VMEM scratch across the chunks and is written once, with the
last chunk, as the session stores it: ``(N, d_k, H d_v)``.

A step, a head (``ops/gdn.py`` has the algebra; ``_chunked`` is the same in
``jax.numpy``): the cumulative log decay (one product with a triangle of
ones for all heads), ``[beta K; Q] K^T`` masked by the decays, the inverse
``T = (I + A)^-1`` of the chunk's unit lower triangular system (`_inverse`:
block elimination, ten products of ``size x size`` a chunk of 64 — five
where two heads lie side by side — each as stable as the forward
substitution it stands for), ``U = T beta V``, ``W = T beta K Gamma``,
``v_new = U - W S``, ``o = (Q Gamma) S + (Q K^T) v_new``, ``S <- S through
+ (K Gamma')^T v_new``.  (``A`` is nilpotent, and ``(I - A)(I + A^2)(I +
A^4)...`` is the same inverse in as many products, but with ``beta`` at 2
and a key repeated through a chunk its powers reach 1e27 and cancel to
entries of 2: it does not hold float32.)  Everything is float32; every
product runs at ``highest`` on the matrix unit, as the body's.

Measured on a TPU v5e (PERF.md section 6, PRs 34 and 51): three layers of
30 heads of 96 x 192, chunks of 64; what was tried and was slower is there
too (rolled copy loops, the gates turned by transposes, products merged
along their rows).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["chunked_delta_rule"]

_F32 = jnp.float32
_LANE = 128
_L2_EPS = 1e-6   # inside the root of q's and k's norm: `ops.gdn._L2_EPS`


def _mm(a, b, contract, batch=True):
    """``a . b`` over the `contract` axes (one of each), float32 at
    ``highest``; the leading axis a batch unless told otherwise."""
    dims = ((0,), (0,)) if batch else ((), ())
    return lax.dot_general(a, b, (((contract[0],), (contract[1],)), dims),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=_F32)


def _spans(h, d):
    """Heads of `d` lanes side by side begin on a 128-lane tile every
    `per` heads: ``(per, whole spans of per heads, heads left over)``."""
    per = math.lcm(d, _LANE) // d
    return per, h // per, h % per


def _turn(ref, heads, h, d):
    """``ref (1, L, H d)`` head-leading into the scratch ``heads (H, L,
    d)``: a span of whole tiles at a time, each head one shifted copy."""
    per, spans, left = _spans(h, d)

    def span(j, carry):
        wide = ref[0, :, pl.ds(pl.multiple_of(j * per * d, _LANE), per * d)]
        for r in range(per):
            # mxlint: disable=E006 -- a Pallas Ref: the store is the kernel's write to VMEM, staged into the loop body
            heads[j * per + r] = wide[:, r * d:(r + 1) * d]
        return carry
    if spans:
        lax.fori_loop(0, spans, span, 0)
    for i in range(h - left, h):
        heads[i] = ref[0, :, i * d:(i + 1) * d]


def _put(heads, ref, h, d, gate=None):
    """`_turn` the other way: ``heads (H, rows, d)`` side by side into
    ``ref (1, rows, H d)`` — times ``silu(gate)`` where a `gate` of
    ``ref``'s shape is given."""
    per, spans, left = _spans(h, d)

    def gated(x, at):
        if gate is None:
            return x
        z = gate[0, :, at]
        return x * (z * jax.nn.sigmoid(z))

    def span(j, carry):
        at = pl.ds(pl.multiple_of(j * per * d, _LANE), per * d)
        # mxlint: disable=E006 -- a Pallas Ref, as above
        ref[0, :, at] = gated(
            jnp.concatenate([heads[j * per + r] for r in range(per)], axis=1),
            at)
        return carry
    if spans:
        lax.fori_loop(0, spans, span, 0)
    for i in range(h - left, h):
        at = slice(i * d, (i + 1) * d)
        ref[0, :, at] = gated(heads[i], at)


def _inverse(a, size):
    """``(I + A)^-1`` of strictly lower triangular ``A``, for ``a (B,
    size, size)`` or — two systems side by side on the lanes, which fill
    a 128-lane tile where one of 64 fills half — ``(B, size, 2 size)``.
    By block elimination: blocks of 2 are ``I - A`` exactly, and blocks of
    ``2 m`` follow from blocks of `m` as ``T - T A_m T`` with ``A_m`` the
    part of ``A`` that couples a block's two halves.  Of two systems side
    by side the right factor of each product is made block diagonal, so
    that one product serves both."""
    width = a.shape[2]
    col = lax.broadcasted_iota(jnp.int32, (size, size), 1)
    if width > size:
        col = jnp.concatenate([col, col], axis=1)
        half = lax.broadcasted_iota(jnp.int32, (width, width), 0) >= size
        side = (half == (lax.broadcasted_iota(jnp.int32, (width, width), 1)
                         >= size)).astype(_F32)
    # rows l and m lie in one block of 2 m, and in its two halves, where
    # the highest bit in which they differ is m's
    apart = lax.broadcasted_iota(jnp.int32, (size, width), 0) ^ col

    def right(x):
        if width == size:
            return x
        return jnp.concatenate([x, x], axis=1) * side

    # (masks as factors: every entry is finite)
    inv = (apart == 0).astype(_F32) - a * (apart < 2).astype(_F32)
    m = 2
    while m < size:
        halves = ((apart >= m) & (apart < 2 * m)).astype(_F32)
        inv = inv - _mm(inv, right(_mm(a * halves, right(inv), (2, 1))),
                        (2, 1))
        m *= 2
    return inv

def _kernel(qk_ref, v_ref, z_ref, b_ref, g_ref, gamma_ref,
            y_ref, s_ref,
            qks, vs, bt, ct, state,
            *, hk, h, group, dk, dv, size, eps):
    c = pl.program_id(1)
    rep = h // hk

    @pl.when(c == 0)
    def _empty_state():
        state[...] = jnp.zeros(state.shape, _F32)

    row = lax.broadcasted_iota(jnp.int32, (size, size), 0)
    col = lax.broadcasted_iota(jnp.int32, (size, size), 1)
    lower = row >= col
    eye, below = (row == col).astype(_F32), (row > col).astype(_F32)
    # the gates a head a row: beta as it is, and the inclusive cumulative
    # log decay (a product with a triangle of ones)
    bt[...] = _mm(b_ref[0], eye, (0, 0), batch=False)               # (H, L)
    ct[...] = _mm(g_ref[0], (row <= col).astype(_F32), (0, 0), batch=False)
    _turn(qk_ref, qks, 2 * hk, dk)        # q's H_k heads, then k's
    _turn(v_ref, vs, h, dv)
    gamma = gamma_ref[...]                        # (1, d_v)

    def heads(i, carry):
        at = pl.ds(i * group, group)

        def normed(first):
            """The group's value heads' q (`first` 0) or k (`first` H_k),
            each a unit vector a row: value head n reads key head ``n //
            rep``."""
            if rep == 1:
                x = qks[pl.ds(first + i * group, group)]
            else:
                x = jnp.stack([qks[first + (i * group + j) // rep]
                               for j in range(group)])
            return x * lax.rsqrt(jnp.sum(x * x, axis=2, keepdims=True)
                                 + _L2_EPS)

        q, k, v = normed(0) * dk ** -0.5, normed(hk), vs[at]    # (G, L, d)
        along = ct[at, :]                         # (G, L): the decay to m
        # down the chunk as well, a head a column: rows turned by a
        # product with the identity, exact at `highest`
        cols = _mm(eye, jnp.concatenate([along, bt[at, :]], axis=0), (1, 1),
                   batch=False)                   # (L, 2 G)
        down = jnp.stack([cols[:, j:j + 1] for j in range(group)])
        beta = jnp.stack([cols[:, j:j + 1] for j in range(group, 2 * group)])
        along = along[:, None, :]                 # (G, 1, L)
        total = down[:, size - 1:size, :]         # (G, 1, 1)
        decay = jnp.exp(jnp.where(lower, down - along, -jnp.inf))
        grow = jnp.exp(down)                      # (G, L, 1)
        kb = k * beta
        both = _mm(jnp.concatenate([kb, q], axis=1), k, (2, 2))
        a = both[:, :size] * decay * below
        qk = both[:, size:] * decay
        if group % 2:
            inv = _inverse(a, size)
        else:  # the first half of the heads beside the second
            half = group // 2
            inv = _inverse(jnp.concatenate([a[:half], a[half:]], axis=2), size)
            inv = jnp.concatenate([inv[:, :, :size], inv[:, :, size:]], axis=0)
        u = _mm(inv, v * beta, (2, 1))
        w = _mm(inv, kb * grow, (2, 1))
        s = state[at]                             # (G, d_k, d_v)
        v_new = u - _mm(w, s, (2, 1))
        o = _mm(q * grow, s, (2, 1)) + _mm(qk, v_new, (2, 1))
        # the gated norm's RMS half, where a head's values are a row of
        # lanes; ``o`` takes the place of the ``v`` it was made from
        # mxlint: disable=E006 -- a Pallas Ref, as above
        vs[at] = o * lax.rsqrt(jnp.mean(o * o, axis=2, keepdims=True)
                               + eps) * gamma
        # (G, 1, 1) to a state's (d_k, d_v) in two moves, along the lanes
        # (the sum keeps Mosaic from folding them into one) and down
        through = jnp.exp(total + jnp.zeros((group, 1, dv), _F32))
        # mxlint: disable=E006 -- a Pallas Ref, as above
        state[at] = s * through + _mm(k * jnp.exp(total - down), v_new,
                                      (1, 1))
        return carry
    lax.fori_loop(0, h // group, heads, 0)
    _put(vs, y_ref, h, dv, gate=z_ref)

    @pl.when(c == pl.num_programs(1) - 1)
    def _store_state():
        _put(state, s_ref, h, dv)


def chunked_delta_rule(qkv, z, beta, g, gamma, *, key_heads, eps, chunk,
                       heads, interpret=False):
    """``ops.gdn._normed_rule`` on the TPU: the same operands, ``(y (N, T,
    H d_v), final state (N, d_k, H d_v))`` — the state as a session stores
    it.  ``T`` is a whole number of chunks of `chunk` positions (or one
    shorter chunk) and `heads` divides ``H`` (``ops.gdn.chunk_heads``
    says for which shapes, and how many heads a step of the walk takes);
    `interpret` runs Pallas's interpreter.  The caller jits."""
    n, t, h = beta.shape
    dv = gamma.shape[0]
    hk = int(key_heads)
    keys, values = qkv.shape[-1] - h * dv, h * dv     # [q | k], v: lanes
    dk = keys // (2 * hk)
    size = min(int(chunk), t)
    nc = t // size
    lanes = lambda width, at=0: pl.BlockSpec((1, size, width),
                                             lambda i, c: (i, c, at))
    if keys % _LANE or values % _LANE or keys % values:
        # no block of `qkv` begins where v does: XLA's slices
        qk, v, v_at = qkv[..., :keys], qkv[..., keys:], 0
    else:
        qk = v = qkv
        v_at = keys // values
    vmem = lambda *shape: pltpu.VMEM(shape, _F32)
    return pl.pallas_call(
        functools.partial(_kernel, hk=hk, h=h, group=int(heads), dk=dk,
                          dv=dv, size=size, eps=float(eps)),
        grid=(n, nc),
        in_specs=[lanes(keys), lanes(values, v_at), lanes(values), lanes(h),
                  lanes(h), pl.BlockSpec((1, dv), lambda i, c: (0, 0))],
        out_specs=[lanes(values),
                   pl.BlockSpec((1, dk, values), lambda i, c: (i, 0, 0))],
        scratch_shapes=[vmem(2 * hk, size, dk),                 # q, k
                        vmem(h, size, dv),                      # v, then o
                        vmem(h, size), vmem(h, size),           # beta, cum
                        vmem(h, dk, dv)],                       # the state
        out_shape=[jax.ShapeDtypeStruct((n, t, values), _F32),
                   jax.ShapeDtypeStruct((n, dk, values), _F32)],
        # 22 MiB of blocks and scratch at Olmo-Hybrid's widths (the gate's
        # two buffers came, o's scratch and the repeated heads went); a
        # limit of 64 MiB cost the program's OTHER fusions 2.7 ms a
        # 2,048-bucket prefill (XLA keeps activations in what VMEM a
        # kernel leaves)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=32 << 20),
        name="gdn_chunked_delta_rule",
        interpret=interpret,
    )(qk, v, z, beta, g, gamma.reshape(1, dv))
