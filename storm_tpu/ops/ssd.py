"""The Mamba-2 state-space layer's scan (state-space duality, Dao and Gu 2024,
"Transformers are SSMs"), computed in chunks.

Per head the layer keeps a state ``S`` in R^{P x N} and reads it token by
token, with one scalar decay a head and token:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t
    y_t = S_t C_t + D x_t

``A < 0`` and ``D`` are one number a head, ``dt_t > 0`` one a head and token,
``x_t`` in R^P; ``B_t`` and ``C_t`` in R^N are shared by the heads of a group
(head ``h`` reads group ``h // (H / G)``). Token by token that is ``S``
dependent steps of vector work. Here a sequence is cut into chunks of
``chunk`` tokens; with ``L_t`` the running sum of ``dt A`` from the chunk's
start (so every exponent below is at most zero):

    Y[t]  = sum_{s <= t} exp(L_t - L_s) (C_t . B_s) dt_s x_s       within
          + exp(L_t) S_prev C_t                                    from before
    S_new = exp(L_Q) S_prev + sum_s exp(L_Q - L_s) dt_s x_s (x) B_s

``C B^T`` is formed once a group, not once a head (with one group, as
Granite 4.0-H publishes it, once for all 128 heads: ``G = 1``, ``R = H``);
the decays' table ``exp(L_t - L_s)`` is a head's, always the exponent of the
difference (its factors ``exp(L_t)`` and ``exp(-L_s)`` overflow where a head
forgets within a chunk), ``B x H x chunk x chunk`` float32 a step of the
loop: 67 MB for 8 rows of 128 heads at a chunk of 128, 268 MB at 256, where a
token's row of it is twice as long; the chunk is the caller's choice and no
part of the mathematics. The products take their operands in the type of ``x`` (bfloat16
when serving) and accumulate in float32; the state stays float32 from chunk
to chunk, the decays are float32 throughout.

**One loop in the compiled program** (a ``fori_loop`` over the chunk's index,
every row of the batch and every head inside a step): the state is the only
chain, a step holds one chunk's tables (``B x H x chunk x chunk``) and no
more, and a device trace shows the scan's whole time as that one ``while``.
The loop reads its operands where the layer left them and writes its result
where the layer reads it: ``x`` stays position-major, ``(B, S, channels)``,
and a step takes its chunk by index on the view ``(B, S / chunk, chunk,
channels)`` (a bitcast; a view that is none would have the compiler bring the
whole array into the body's layout before the loop); ``B`` and ``C`` are
columns of that chunk where the layer holds all three side by side
(``ssd_chunked_columns``), slices of their own arrays otherwise; the split of
the channels into groups and heads happens on the chunk, in fast memory. The
result is carried beside the state, ``(B, S, H P)`` in the served type, and a
step writes its chunk into it in place, the skip ``D x`` added in float32
before the one rounding: no array of a branch's size is made, moved or
widened outside the loop, nothing is scattered and no table is rewritten.
The running sums ``L`` alone are made before the loop, for every chunk at
once (``(B, S, H)`` float32, a sixty-fourth of ``x``, as a product with a
triangle of ones at ``highest``: exact products, float32 sums): the v5e
compiler's cumulative sum over a chunk's ``(B, chunk, G, R)`` took 235 us of
a step's 350 inside the loop (PERF.md section 6, PR 50).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from storm_tpu.ops import parts as P
from storm_tpu.ops.platform import note as _note


def ssd_chunked(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
                b: jnp.ndarray, c: jnp.ndarray, d: jnp.ndarray,
                chunk: int = 128) -> jnp.ndarray:
    """``x: (B, S, H, P)``, ``dt: (B, S, H)`` (after its softplus), ``a, d:
    (H,)``, ``b, c: (B, S, G, N)`` with ``H % G == 0``. Returns ``y: (B, S,
    H, P)`` in the type of ``x``. ``S`` need not be a multiple of ``chunk``:
    the tail is padded with tokens of ``dt = 0``, which neither decay the
    state nor write to it."""
    bsz, s, h, p = x.shape
    g, n = b.shape[-2:]
    y = _chunks_loop(x.reshape(bsz, s, h * p),
                     (b.reshape(bsz, s, g * n), c.reshape(bsz, s, g * n)),
                     dt, a, d, g, n, chunk)
    return y.reshape(bsz, s, h, p)


def ssd_chunked_columns(xbc: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
                        d: jnp.ndarray, groups: int, state: int,
                        chunk: int = 128) -> jnp.ndarray:
    """The same scan for ``x | B | C`` held side by side, ``xbc: (B, S, H P +
    2 G N)`` as a Mamba-2 layer's convolution writes them: the loop takes a
    chunk of all three at once and the columns apart inside its body, so no
    slice of a branch's size is made for it. Returns ``y: (B, S, H P)``."""
    return _chunks_loop(xbc, None, dt, a, d, groups, state, chunk)


def _chunks_loop(x, bc, dt, a, d, g, n, chunk):
    """``x: (B, S, H P)`` and ``bc``: ``B`` and ``C`` as ``(B, S, G N)``
    each, or None where they are the columns of ``x`` after its ``H P``."""
    bsz, s, h = dt.shape
    hp = x.shape[-1] - (0 if bc else 2 * g * n)
    r, p = h // g, hp // h
    f32 = jnp.float32
    _note("ssd_scan", "chunked")
    cd = x.dtype
    q = min(chunk, s)
    pad = -s % q
    nc = (s + pad) // q

    def padded(y):  # (B, S, channels) -> (B, S + pad, channels)
        return jnp.pad(y, ((0, 0), (0, pad), (0, 0))) if pad else y

    x, dt = padded(x), padded(dt.astype(f32))
    bc = bc and tuple(padded(y) for y in bc)
    a = a.astype(f32).reshape(g, r)
    d = d.astype(f32).reshape(g, r, 1)
    later = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]  # s <= t
    # L for every chunk at once, each from its chunk's start: (B, S, H), <= 0
    runs = jnp.einsum(
        "ts,bcsh->bcth", later.astype(f32),
        (dt * a.reshape(h)).reshape(bsz, nc, q, h),
        precision=lax.Precision.HIGHEST).reshape(bsz, s + pad, h)

    def one_chunk(i, carry):  # state (B, G, R, P, N), float32
        state, out = carry

        def chunk_of(y, *split):  # (B, S, channels) -> (B, Q, *split)
            return lax.dynamic_slice_in_dim(
                y, i * q, q, axis=1, allow_negative_indices=False).reshape(
                bsz, q, *split)

        held = lax.dynamic_index_in_dim(
            x.reshape(bsz, nc, q, x.shape[-1]), i, 1, keepdims=False)
        x_c = held[..., :hp].reshape(bsz, q, g, r, p)
        if bc:
            b_c, c_c = (chunk_of(y, g, n) for y in bc)
        else:
            b_c, c_c = (held[..., lo:lo + g * n].reshape(bsz, q, g, n)
                        for lo in (hp, hp + g * n))
        dt_c, run = chunk_of(dt, g, r), chunk_of(runs, g, r)
        run_h = jnp.moveaxis(run, 1, -1)  # (B, G, R, Q)
        # within the chunk: (C B^T) a group, the decays a head
        cb = jnp.einsum("btgn,bsgn->bgts", c_c, b_c,
                        preferred_element_type=f32)
        decay = jnp.exp(jnp.where(
            later, run_h[..., :, None] - run_h[..., None, :], -jnp.inf))
        m = cb[:, :, None] * decay * jnp.moveaxis(dt_c, 1, -1)[..., None, :]
        y = jnp.einsum("bgrts,bsgrp->btgrp", m.astype(cd), x_c,
                       preferred_element_type=f32)
        # what the state before the chunk adds
        y = y + jnp.exp(run)[..., None] * jnp.einsum(
            "bgrpn,btgn->btgrp", state.astype(cd), c_c,
            preferred_element_type=f32)
        # the state after it
        last = run[:, -1]  # (B, G, R)
        w = jnp.exp(last[:, None] - run) * dt_c  # (B, Q, G, R)
        state = jnp.exp(last)[..., None, None] * state + jnp.einsum(
            "bsgrp,bsgn->bgrpn", (x_c.astype(f32) * w[..., None]).astype(cd),
            b_c, preferred_element_type=f32)
        # the skip in float32 and one rounding, into the chunk's own place
        y = (y + d * x_c.astype(f32)).astype(cd).reshape(bsz, q, hp)
        return state, lax.dynamic_update_slice_in_dim(
            out, y, i * q, axis=1, allow_negative_indices=False)

    with jax.named_scope(P.MIX_SSD_SCAN):  # its name in a device trace
        _, y = lax.fori_loop(0, nc, one_chunk, (
            jnp.zeros((bsz, g, r, p, n), f32),
            jnp.zeros((bsz, s + pad, hp), cd)))
    return y[:, :s]
