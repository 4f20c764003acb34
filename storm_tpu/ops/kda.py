"""Gated delta-rule linear attention with a per-channel decay (Kimi Delta
Attention, the linear-attention layer of ``kimi_linear``), computed in chunks,
and the short causal convolution that feeds it.

Per head the layer keeps a state ``S`` in R^{dk x dv} and reads it token by
token:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

with ``g_t <= 0`` a log-decay per key channel and ``beta_t`` in (0, 1). Token by
token that is ``S`` dependent steps of vector work; here a sequence is cut into
chunks of ``chunk`` tokens, everything inside a chunk is matrix products, and
only the ``S / chunk`` states are a chain (Yang et al. 2024, "Parallelizing
linear transformers with the delta rule over sequence length", with the
per-channel gate of gated linear attention).

Within a chunk, with ``G_t`` the decay summed from the chunk's start to ``t``
and ``u_t`` the row that token ``t`` writes (``S_t = Diag(exp(g_t)) S_{t-1} +
k_t u_t^T``):

    (I + tril(Diag(beta) A, -1)) U = Diag(beta) (V - (K * exp(G)) S_0)
    A[t, s] = sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])            (s < t)
    O = (Q * exp(G)) S_0 + tril(A_qk) U        (A_qk with q_t for k_t, s <= t)
    S_C = Diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U

**Kept stable in float32.** ``exp(G_t - G_s)`` is at most one, but its factors
``exp(G_t)`` and ``exp(-G_s)`` are not: a channel that forgets within a few
tokens overflows ``exp(-G_s)`` inside one chunk. So ``A`` is never formed from
those two factors. A chunk is cut into sub-chunks of ``sub`` tokens; a block
of ``A`` between two different sub-chunks takes the decay at the later one's
start as the point both factors are measured from (both exponents are then at
most zero), and a block on the diagonal is summed pair by pair on the vector
unit with the exponent of the difference. The unit-triangular system is solved
exactly: each ``sub`` x ``sub`` diagonal block by forward substitution, the
blocks below by block substitution, all in float32 at ``highest`` precision.
The large products (with ``S``, ``U``) take their operands in the type of
``q`` (bfloat16 when serving) and accumulate in float32; ``S`` itself stays
float32 from chunk to chunk.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST


def short_conv_init(rng, channels: int, width: int = 4,
                    dtype=jnp.float32) -> dict:
    """A depthwise kernel ``[width, channels]``; the last tap is the current
    token's."""
    return {"w": jax.random.normal(rng, (width, channels), dtype)
            * (1.0 / width) ** 0.5}


def short_conv(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    """Causal depthwise convolution over ``(B, S, C)``: ``y_t = sum_j w[j] *
    x_{t - (width - 1) + j}``, tokens before the first read as zero."""
    w = p["w"].astype(jnp.float32)
    width, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (width - 1, 0), (0, 0)))
    y = sum(w[j] * xp[:, j:j + s] for j in range(width))
    return y.astype(x.dtype)


def l2norm(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    return (xf * lax.rsqrt(jnp.sum(xf * xf, -1, keepdims=True) + eps)
            ).astype(x.dtype)


def _unit_lower_inverse(n: jnp.ndarray, sub: int) -> jnp.ndarray:
    """``(I + N)^-1`` for strictly lower-triangular ``N`` of shape
    ``(..., C, C)``, ``C`` a multiple of ``sub``: forward substitution inside
    each diagonal ``sub`` x ``sub`` block, block substitution below."""
    c = n.shape[-1]
    nb = c // sub
    lead = n.shape[:-2]
    blocks = n.reshape(*lead, nb, sub, nb, sub)
    eye = jnp.eye(sub, dtype=n.dtype)
    # diagonal blocks, all at once: row i of the inverse is e_i less row i of
    # N times the rows above it
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(nb)], -3)
    rows = [jnp.broadcast_to(eye[0], diag.shape[:-2] + (sub,))]
    for i in range(1, sub):
        rows.append(eye[i] - jnp.einsum(
            "...j,...jk->...k", diag[..., i, :i], jnp.stack(rows, -2),
            precision=_HI))
    inv = jnp.stack(rows, -2)
    # below the diagonal: T_ij = -T_ii sum_{j <= l < i} N_il T_lj
    t = [[None] * nb for _ in range(nb)]
    for i in range(nb):
        t[i][i] = inv[..., i, :, :]
        for j in range(i):
            acc = sum(jnp.einsum("...ab,...bc->...ac",
                                 blocks[..., i, :, l, :], t[l][j],
                                 precision=_HI) for l in range(j, i))
            t[i][j] = -jnp.einsum("...ab,...bc->...ac", t[i][i], acc,
                                  precision=_HI)
    zero = jnp.zeros_like(t[0][0])
    return jnp.concatenate(
        [jnp.concatenate([t[i][j] if j <= i else zero for j in range(nb)], -1)
         for i in range(nb)], -2)


def _within_chunks(q, k, v, g, beta, sub: int):
    """Everything of a chunk that does not need the state before it, for
    ``(H, N, C, d)`` inputs (one row of the batch): the solved ``W`` and
    ``U0`` (``U = U0 - W S_0``), ``Q * exp(G)``, ``K * exp(G_C - G)``,
    ``tril(A_qk)`` and ``exp(G_C)``."""
    cd = q.dtype
    f32 = jnp.float32
    c = q.shape[-2]
    ns = c // sub
    qf, kf = q.astype(f32), k.astype(f32)
    big_g = jnp.cumsum(g.astype(f32), axis=-2)  # (H, N, C, dk), <= 0

    def dot(a, b):  # (.., t, d) x (.., s, d) -> (.., t, s)
        return jnp.einsum("...td,...sd->...ts", a.astype(cd), b.astype(cd),
                          preferred_element_type=f32)

    # blocks on the diagonal: pair by pair, the exponent of the difference
    shape = q.shape[:-2] + (ns, sub, q.shape[-1])
    gs, qs, ks = (y.reshape(shape) for y in (big_g, qf, kf))
    t_idx = jnp.arange(sub)
    later = (t_idx[:, None] >= t_idx[None, :])[..., None]  # s <= t
    pair = jnp.exp(jnp.where(later, gs[..., :, None, :] - gs[..., None, :, :],
                             -jnp.inf))
    d_kk = jnp.sum(ks[..., :, None, :] * ks[..., None, :, :] * pair, -1)
    d_qk = jnp.sum(qs[..., :, None, :] * ks[..., None, :, :] * pair, -1)
    # blocks between different sub-chunks: both factors measured from the
    # decay at the later sub-chunk's start, so neither exponent is positive.
    # A row of blocks is put together left to right, the table top to bottom
    rows_kk, rows_qk = [], []
    for i in range(ns):
        lo, hi = i * sub, (i + 1) * sub
        kk, qk = [d_kk[..., i, :, :]], [d_qk[..., i, :, :]]
        if i:
            ref = big_g[..., lo - 1:lo, :]
            right = kf[..., :lo, :] * jnp.exp(ref - big_g[..., :lo, :])
            decay = jnp.exp(big_g[..., lo:hi, :] - ref)
            kk.insert(0, dot(kf[..., lo:hi, :] * decay, right))
            qk.insert(0, dot(qf[..., lo:hi, :] * decay, right))
        if hi < c:
            kk.append(jnp.zeros(q.shape[:-2] + (sub, c - hi), f32))
            qk.append(kk[-1])
        rows_kk.append(jnp.concatenate(kk, -1))
        rows_qk.append(jnp.concatenate(qk, -1))
    a_kk = jnp.concatenate(rows_kk, -2)
    a_qk = jnp.concatenate(rows_qk, -2)
    bf = beta.astype(f32)[..., None]  # (H, N, C, 1)
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    t_inv = _unit_lower_inverse(jnp.where(strict, bf * a_kk, 0.0), sub)
    decay_in = jnp.exp(big_g)
    w = jnp.einsum("...ts,...sd->...td", t_inv, bf * kf * decay_in,
                   precision=_HI)
    u0 = jnp.einsum("...ts,...sd->...td", t_inv, bf * v.astype(f32),
                    precision=_HI)
    total = big_g[..., -1:, :]
    return (w.astype(cd), u0, (qf * decay_in).astype(cd),
            (kf * jnp.exp(total - big_g)).astype(cd),
            a_qk.astype(cd), jnp.exp(total[..., 0, :]))


def kda_chunked(q, k, v, g, beta, chunk: int = 64, sub: int = 16):
    """The layer's output ``(B, S, H, dv)`` for ``q, k: (B, S, H, dk)`` (as
    the layer reads them: normalised, ``q`` already scaled), ``v: (B, S, H,
    dv)``, log-decay ``g: (B, S, H, dk)`` (float32, at most zero) and
    ``beta: (B, S, H)``. A sequence that is no multiple of ``chunk`` is padded
    with tokens that write nothing (``beta`` 0) and forget nothing (``g``
    0)."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    sub = min(sub, chunk)
    if chunk % sub:
        raise ValueError(f"chunk {chunk} is no multiple of sub-chunk {sub}")
    n = -(-s // chunk)
    pad = n * chunk - s
    cd = q.dtype
    f32 = jnp.float32

    def chunks(y):  # (B, S, H, ...) -> (B, H, N, C, ...)
        y = jnp.pad(y, ((0, 0), (0, pad)) + ((0, 0),) * (y.ndim - 2))
        y = y.reshape(b, n, chunk, h, *y.shape[3:])
        return jnp.moveaxis(y, 3, 1)

    parts = lax.map(
        lambda row: _within_chunks(*row, sub=sub),
        tuple(chunks(y) for y in (q, k, v, g.astype(f32), beta)))
    # the chain over chunks, every row and head at once
    w, u0, q_in, k_out, a_qk, decay = (jnp.moveaxis(y, 2, 0) for y in parts)

    def step(state, xs):
        w_c, u0_c, q_c, k_c, a_c, d_c = xs
        sb = state.astype(cd)
        u = u0_c - jnp.einsum("bhtk,bhkv->bhtv", w_c, sb,
                              preferred_element_type=f32)
        ub = u.astype(cd)
        o = (jnp.einsum("bhtk,bhkv->bhtv", q_c, sb,
                        preferred_element_type=f32)
             + jnp.einsum("bhts,bhsv->bhtv", a_c, ub,
                          preferred_element_type=f32))
        state = d_c[..., None] * state + jnp.einsum(
            "bhtk,bhtv->bhkv", k_c, ub, preferred_element_type=f32)
        return state, o.astype(cd)

    _, o = lax.scan(step, jnp.zeros((b, h, dk, dv), f32),
                    (w, u0, q_in, k_out, a_qk, decay))
    # (N, B, H, C, dv) -> (B, S, H, dv)
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, n * chunk, h, dv)
    return o[:, :s]
