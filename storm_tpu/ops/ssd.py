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

``C B^T`` is formed once a group, not once a head; the decays' table
``exp(L_t - L_s)`` is a head's, always the exponent of the difference (its
factors ``exp(L_t)`` and ``exp(-L_s)`` overflow where a head forgets within
a chunk). The products take their operands in the type of ``x`` (bfloat16
when serving) and accumulate in float32; the state stays float32 from chunk
to chunk, the decays are float32 throughout.

**One loop in the compiled program** (``lax.scan`` over chunks, every row of
the batch and every head inside a step): the state is the only chain, a step
holds one chunk's tables (``B x H x chunk x chunk``) and no more, and a
device trace shows the scan's whole time as that one ``while``. The chunks
are brought forward once (the two minor axes stay as the layer holds them,
tokens by channels) and the result is put back the same way: nothing is
scattered and no table is rewritten.
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
    r = h // g
    f32 = jnp.float32
    _note("ssd_scan", "chunked")
    cd = x.dtype
    q = min(chunk, s)
    pad = -s % q
    nc = (s + pad) // q

    def chunks(y):  # (B, S, ...) -> (nc, B, Q, ...)
        if pad:
            y = jnp.pad(y, ((0, 0), (0, pad)) + ((0, 0),) * (y.ndim - 2))
        return jnp.moveaxis(y.reshape(bsz, nc, q, *y.shape[2:]), 1, 0)

    xs = (chunks(x.reshape(bsz, s, g, r, p)),
          chunks(dt.astype(f32).reshape(bsz, s, g, r)), chunks(b), chunks(c))
    a = a.astype(f32).reshape(g, r)
    later = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]  # s <= t

    def one_chunk(state, xs_c):  # state (B, G, R, P, N), float32
        x_c, dt_c, b_c, c_c = xs_c
        run = jnp.cumsum(dt_c * a, axis=1)  # (B, Q, G, R), <= 0
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
        return state, y.astype(cd)

    with jax.named_scope(P.MIX_SSD_SCAN):  # its name in a device trace
        _, y = lax.scan(one_chunk, jnp.zeros((bsz, g, r, p, n), f32), xs)
    # the skip, on the chunks as the loop read and wrote them: whatever
    # handed ``x`` over need not hand it to what reads the result as well
    y = (y.astype(f32)
         + d.astype(f32).reshape(g, r, 1) * xs[0].astype(f32)).astype(cd)
    return jnp.moveaxis(y, 0, 1).reshape(bsz, s + pad, h, p)[:, :s]
