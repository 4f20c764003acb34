"""Plain EvaByte forward (``model_type`` ``evabyte``; the released
``config.json`` and Zheng et al., "Efficient Attention via Control Variates",
ICLR 2023, in the deterministic form the released code serves), float32,
``jax.numpy`` only: no kernels, no tiles, no running maximum. The yardstick's
own copy of the mathematics, so a change to the program's model code cannot
move the reference with it.

``d`` a head's channels, ``W`` ``window_size``, ``C`` ``chunk_size``, ``N``
positions (a multiple of ``C``):

    x_0 = E[id]
    x += (concat_j o^j) W_o        with u = RMSNorm(x)
    x += W_down(SiLU(W_gate u') * W_up u')     with u' = RMSNorm(x)
    logits = RMSNorm(x_(N-1)) W_head  as (num_pred_heads, vocab_size)
    p_i = softmax(logits[i]): the distribution of byte N + i

For head ``j`` with learned ``mu_j``, ``phi_j`` in ``R^d``: ``q_t, k_t, v_t``
the head's channels of ``u_t W_q, u_t W_k, u_t W_v``; ``q_t`` and ``k_t``
turned by plain rotary at position ``t`` (pair ``(i, i + d / 2)`` by ``t
theta^(-2i/d)``). **Summaries**, by a reshape to ``(N / C, C, d)``: ``kbar_c
= sum_s softmax_s(mu_j . k_s / sqrt(d)) k_s`` and ``vbar_c = sum_s
softmax_s(phi_j . k_s / sqrt(d)) v_s`` over chunk ``c``'s ``C`` positions
(the turned keys are pooled). **Attention**, a window at a time: the
queries of window ``w`` against ``[kbar_c for 16 (c + 1) <= w W ; k_s for w W
<= s < (w + 1) W]`` under **one** softmax, the summaries visible to every
query of the window, the window's own keys causally; the same weights times
``[vbar ; v]``.

Departures from the published description, each also under ``assumed`` in
the configuration's file:

- ``fp32_ln`` is false in the release (the norm's statistic in the stream's
  type); here, as in the program, the statistic is float32: the stream is
  float32 (``fp32_skip_add``), so it is the stream's type.
- ``norm_add_unit_offset``: a checkpoint's norm scale ``g`` multiplies as
  ``1 + g``; the leaves read here are the served ``1 + g`` (1 from a seed).

**Parameters in the served type**, as ``references/kimi_k2.py``: each leaf is
brought to float32 where it is used, a layer at a time. Rows of the batch
one at a time at the top (``lax.map``: eight rows of 16,384 positions at an
11,008-wide feed-forward in float32 do not fit beside the parameters); the
mixer itself has no loop over rows. Neither changes a number.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _rmsnorm(p, x, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"].astype(F32)


def _swiglu(p, x):
    p = _f32(p)
    return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def _turn(x, angle):
    """``x (..., d)`` with pair ``(i, i + d / 2)`` turned by ``angle (..., d /
    2)``."""
    a, b = jnp.split(x, 2, axis=-1)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def summaries(k, v, mu, phi, chunk):
    """``k, v: (N, H, d)``, ``mu, phi: (H, d)`` -> ``(kbar, vbar)``, each ``(N
    / chunk, H, d)``."""
    n, heads, d = k.shape
    k, v = (a.reshape(n // chunk, chunk, heads, d) for a in (k, v))
    pool_k = jax.nn.softmax(jnp.einsum("cshd,hd->csh", k, mu) * d ** -0.5, 1)
    pool_v = jax.nn.softmax(jnp.einsum("cshd,hd->csh", k, phi) * d ** -0.5, 1)
    return (pool_k[..., None] * k).sum(1), (pool_v[..., None] * v).sum(1)


def attention(q, k, v, kbar, vbar, window, chunk):
    """``q, k, v: (N, H, d)``, ``kbar, vbar: (N / chunk, H, d)`` -> ``(N, H,
    d)``: a window at a time, one masked softmax over ``[the earlier windows'
    summaries ; the window's keys]``."""
    n, _, d = q.shape
    outs = []
    for begin in range(0, n, window):
        end, seen = min(begin + window, n), begin // chunk
        keys = jnp.concatenate([kbar[:seen], k[begin:end]])
        values = jnp.concatenate([vbar[:seen], v[begin:end]])
        scores = jnp.einsum("thd,shd->hts", q[begin:end], keys) * d ** -0.5
        t = jnp.arange(end - begin)
        visible = jnp.concatenate(
            [jnp.ones((end - begin, seen), bool), t[None, :] <= t[:, None]], 1)
        weights = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), -1)
        outs.append(jnp.einsum("hts,shd->thd", weights, values))
    return jnp.concatenate(outs)


def mixer(p, u, sizes):
    """One row ``(N, D)`` through the EVA mixer."""
    heads = sizes["num_attention_heads"]
    d = sizes["hidden_size"] // heads
    p = _f32(p)
    n = u.shape[0]
    angle = jnp.arange(n, dtype=F32)[:, None, None] * jnp.asarray(
        float(sizes["rope_theta"]) ** (-2.0 * np.arange(d // 2) / d), F32)
    q = _turn((u @ p["q"]).reshape(n, heads, d), angle)
    k = _turn((u @ p["k"]).reshape(n, heads, d), angle)
    v = (u @ p["v"]).reshape(n, heads, d)
    chunk = sizes["chunk_size"]
    o = attention(q, k, v, *summaries(k, v, p["mu"], p["phi"], chunk),
                  sizes["window_size"], chunk)
    return o.reshape(n, heads * d) @ p["o"]


def forward(sizes: dict, params, state, x):
    """The next ``num_pred_heads`` bytes' probabilities, ``(B, num_pred_heads
    * vocab_size)`` (a distribution a head, laid end to end), for windows of
    byte ids ``(B, N)`` (as floats: the instance contract carries them so)."""
    eps = sizes["rms_norm_eps"]
    layers = params["layers"]
    held = sizes.get("held", {})
    if "num_hidden_layers" in held and len(layers) != held["num_hidden_layers"]:
        raise ValueError("the program's model has another depth than the "
                         "configuration file")
    vocab = params["embed"].shape[0]
    ids = jnp.clip(jnp.round(x), 0, vocab - 1).astype(jnp.int32)

    def row(ids_row):
        h = params["embed"][ids_row].astype(F32)
        for blk in layers:
            h = h + mixer(blk["mixer"], _rmsnorm(blk["norm1"], h, eps), sizes)
            h = h + _swiglu(blk["ffn"], _rmsnorm(blk["norm2"], h, eps))
        last = _rmsnorm(params["norm"], h[-1], eps)
        return (last @ params["head"].astype(F32)).reshape(-1, vocab)

    probabilities = jax.nn.softmax(lax.map(row, ids), axis=-1)
    return probabilities.reshape(len(ids), -1)
