"""Plain forward of Keye-VL-2.0's language model (``model_type`` ``KeyeVL2``:
the released ``config.json`` and, for what it does not state, the readings
the configuration's ``assumed`` lists), float32, ``jax.numpy`` only: no
kernel, no skipped key block, no bisection, no grouped product. The
yardstick's own copy of the mathematics, written from the equations, so a
change to the program's model code cannot move the reference with it.

The stream starts at ``E[id]``. Every block: ``h = h + A(RMSNorm_1(h)); h =
h + M(RMSNorm_2(h))``.

- **Attention** ``A`` (``n`` its normed input): ``q = RMSNorm_d(W_q n)`` and
  ``k = RMSNorm_d(W_k n)`` a head (one learned scale a channel of
  ``head_dim``), ``v = W_v n``; query head ``i`` reads key head ``i // (heads
  / kv_heads)``. q and k are turned by M-RoPE: three position streams ``p:
  (3, S)``; pair ``(i, i + head_dim / 2)`` by the angle ``p[stream(i), t] *
  rope_theta^(-2i / head_dim)``, ``stream(i)`` the section of
  ``mrope_section`` that frequency ``i`` lies in, in their order. For token
  ids the three streams are ``0 .. S - 1``.
- **The indexer**: ``qI(t, j) = rope(W_qI n_t)_j`` for ``j <
  indexer_num_heads``, ``kI(s) = rope(LayerNorm(W_kI n_s))`` (one key a
  position; scale and bias), both of ``indexer_head_dim`` channels turned
  whole by plain rotary (stream 0, the same theta); ``w(t) = W_w n_t``.
  ``I(t, s) = sum_j w(t, j) ReLU(qI(t, j) . kI(s))`` for ``s <= t``. The query
  at ``t`` reads ``P(t)``: every ``s <= t`` where ``t < topk``, else the
  ``topk`` largest ``I(t, s)``, the lower ``s`` where two are equal (-0 and
  +0 are equal): **the scores of a block of queries against every key,
  sorted descending a query**; the ``topk``-th of them is the threshold, and
  of the keys that score exactly that the first ones in order fill the
  count.
- The **full masked softmax** of ``q k^T / sqrt(head_dim)`` over all ``S``
  keys under ``P``, the same for every head, a head and a block of queries at
  a time. ``y = W_o concat_heads a``.
- **Expert layer** ``M`` (every layer): ``s = softmax(W_r n)`` over all
  experts; the ``num_experts_per_tok`` largest; weights ``s_e`` over the
  chosen scores' sum plus 1e-20 (``norm_topk_prob``); ``sum_e w_e
  SwiGLU_e(n)``. No shared expert, no selection bias, no token dropped.

**One departure, which changes no number** (``references/trinity.py``'s): an
expert is applied to the rows routed to it, gathered 1,024 at a time in as
many passes as its count needs (a loop bounded by the count: no row is
dropped whatever the routing), and its weighted result is added back at
those rows: 128 experts on every token is 79 TFLOP a window at ``highest``.

**Parameters in the served type.** The program's initialiser hands its
leaves over in bfloat16, as a checkpoint would; each is brought to float32
where it is used, a layer (and within the expert layer an expert) at a time.
Rows of the batch one at a time (``lax.map``). None of that changes a number.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
QUERY_BLOCK = 2048  # queries whose scores over all keys exist at once
GATHER = 1024  # rows of one expert gathered and computed at once


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _rmsnorm(p, x, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"].astype(F32)


def _layernorm(p, x, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"].astype(F32) \
        + p["bias"].astype(F32)


def _swiglu(p, x):
    p = _f32(p)
    return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def _turn(x, angle):
    """``x (S, ..., dim)`` with pair ``(i, i + dim / 2)`` turned by ``angle
    (S, dim / 2)``."""
    a, b = jnp.split(x, 2, axis=-1)
    lead = (slice(None),) + (None,) * (x.ndim - 2)
    cos, sin = jnp.cos(angle)[lead], jnp.sin(angle)[lead]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _frequencies(theta, dim):
    return jnp.asarray(float(theta) ** (
        -2 * np.arange(dim // 2, dtype=np.float64) / dim), F32)


def mrope_angle(positions, theta, dim, sections):
    """``(S, dim / 2)``: frequency ``i``'s angle at every position of the
    stream its section names. ``positions: (3, S)``."""
    positions = jnp.asarray(positions, F32)
    f = _frequencies(theta, dim)
    columns, first = [], 0
    for stream, n in enumerate(sections):
        columns.append(positions[stream][:, None] * f[None, first:first + n])
        first += n
    return jnp.concatenate(columns, -1)


def picks(index, topk):
    """``index: (T, S)`` scores, ``-inf`` where a key lies after its query
    -> bool: the ``topk`` largest a query (every scored one where there are
    no more), the lower position where two are equal."""
    index = jnp.where(index == 0, 0.0, index)
    scored = index > -jnp.inf
    if index.shape[-1] <= topk:
        return scored
    ranked = -jnp.sort(-index, axis=-1)  # descending
    threshold = ranked[:, topk - 1:topk]
    above = index > threshold
    tied = index == threshold
    room = topk - jnp.sum(above, -1, keepdims=True)
    return scored & (above | (tied & (jnp.cumsum(tied, -1) <= room)))


def indexer(p, x, sizes, positions, eps):
    """The indexer's queries ``(S, heads, dim)``, its key a position ``(S,
    dim)`` and its weights ``(S, heads)`` from one row's normed input."""
    sa = sizes["sa_config"]
    ih, idim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    s = x.shape[0]
    plain = jnp.asarray(positions, F32)[0][:, None] \
        * _frequencies(sizes["rope_theta"], idim)[None, :]
    qi = _turn((x @ p["index_q"].astype(F32)).reshape(s, ih, idim), plain)
    ki = _turn(_layernorm(p["index_k_norm"], x @ p["index_k"].astype(F32),
                          eps), plain)
    return qi, ki, x @ p["index_w"].astype(F32)


def _attention(p, x, sizes, positions, eps):
    """One row ``(S, D)`` through a layer's attention."""
    heads, kv_heads = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d = sizes["head_dim"]
    sa = sizes["sa_config"]
    ih, topk = sa["indexer_num_heads"], sa["topk"]
    p = _f32(p)
    s = x.shape[0]
    angle = mrope_angle(positions, sizes["rope_theta"], d,
                        sizes["rope_scaling"]["mrope_section"])
    q = _turn(_rmsnorm(p["q_norm"], (x @ p["q"]).reshape(s, heads, d), eps),
              angle)
    k = _turn(_rmsnorm(p["k_norm"], (x @ p["k"]).reshape(s, kv_heads, d),
                       eps), angle)
    v = (x @ p["v"]).reshape(s, kv_heads, d)
    qi, ki, w = indexer(p, x, sizes, positions, eps)
    # a query head's keys and values: its group's
    k, v = (jnp.repeat(y, heads // kv_heads, axis=1) for y in (k, v))
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    key_at = jnp.arange(s)[None, :]
    qh, kh, vh = (y.transpose(1, 0, 2) for y in (q, k, v))  # (H, S, d)

    def queries(at):
        first = at * block
        seen = key_at <= first + jnp.arange(block)[:, None]

        def cut(y, axis=0):
            return lax.dynamic_slice_in_dim(y, first, block, axis)

        index = sum(cut(w)[:, j:j + 1] * jax.nn.relu(cut(qi)[:, j] @ ki.T)
                    for j in range(ih))
        read = picks(jnp.where(seen, index, -jnp.inf), topk)

        def head(qkv):
            q_h, k_h, v_h = qkv
            scores = cut(q_h) @ k_h.T / math.sqrt(d)
            return jax.nn.softmax(jnp.where(read, scores, -jnp.inf), -1) @ v_h

        return lax.map(head, (qh, kh, vh))  # (H, block, d)

    out = lax.map(queries, jnp.arange(s // block))  # (blocks, H, block, d)
    out = out.transpose(0, 2, 1, 3).reshape(s, heads * d)
    return out @ p["o"]


def _experts(p, x, sizes):
    """One row ``(S, D)`` through the expert layer: the held experts' part
    of the routed sum."""
    n = x.shape[0]
    top_k = sizes["num_experts_per_tok"]
    first = sizes.get("held", {}).get("first_expert", 0)
    score = jax.nn.softmax(x @ p["router"].astype(F32), -1)
    weight, chosen = lax.top_k(score, top_k)
    if sizes["norm_topk_prob"]:
        weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-20)
    rows_at_once = min(n, GATHER)

    def one(routed, ew):
        e, w = ew
        w = _f32(w)
        mine = chosen == e + first
        gain = jnp.sum(jnp.where(mine, weight, 0.0), -1)
        took = jnp.any(mine, -1)
        # the expert's rows first, then ``n``: nowhere
        rows = jnp.concatenate([jnp.nonzero(took, size=n, fill_value=n)[0],
                                jnp.full((rows_at_once,), n)])

        def gather(carry):
            at, routed = carry
            idx = lax.dynamic_slice_in_dim(rows, at, rows_at_once)
            y = _swiglu(w, x.at[idx].get(mode="fill", fill_value=0.0)) \
                * gain.at[idx].get(mode="fill", fill_value=0.0)[:, None]
            return at + rows_at_once, routed.at[idx].add(y, mode="drop")

        return lax.while_loop(lambda c: c[0] < jnp.sum(took), gather,
                              (0, routed))[1], None

    held = p["experts"]["gate"].shape[0]
    routed, _ = lax.scan(one, jnp.zeros_like(x),
                         (jnp.arange(held), p["experts"]))
    return routed


def forward(sizes: dict, params, state, x):
    """Next-token probabilities over the vocabulary held, ``(B,
    vocabulary)``, for windows of token ids ``(B, S)`` (as floats: the
    instance contract carries them so)."""
    eps = sizes["rms_norm_eps"]
    layers = params["layers"]
    held = sizes.get("held", {})
    if len(layers) != len(held.get("layers", layers)):
        raise ValueError("the program's model has another depth than the "
                         "configuration file")
    vocab = params["embed"].shape[0]
    ids = jnp.clip(jnp.round(x), 0, vocab - 1).astype(jnp.int32)
    # token ids: M-RoPE's three streams are one
    positions = jnp.broadcast_to(jnp.arange(x.shape[1]), (3, x.shape[1]))

    def row(ids_row):
        h = params["embed"][ids_row].astype(F32)
        for blk in layers:
            h = h + _attention(blk["mixer"], _rmsnorm(blk["norm1"], h, eps),
                               sizes, positions, eps)
            h = h + _experts(blk["ffn"], _rmsnorm(blk["norm2"], h, eps),
                             sizes)
        return _rmsnorm(params["norm"], h[-1], eps) \
            @ params["head"].astype(F32)

    return jax.nn.softmax(lax.map(row, ids), axis=-1)
