"""Plain Trinity forward (``model_type`` ``afmoe``: the released
``config.json`` and, for what it does not state, the released modelling code
as the configuration's ``assumed`` lists it), float32, ``jax.numpy`` only: no
kernel, no skipped key block, no grouped product. The yardstick's own copy of
the mathematics, written from the equations, so a change to the program's
model code cannot move the reference with it.

The stream starts at ``sqrt(hidden_size) E[id]`` (``mup_enabled``). Every
block is a sandwich: ``h = h + RMSNorm_post1(A(RMSNorm_pre1(h))); h = h +
RMSNorm_post2(F(RMSNorm_pre2(h)))``.

- **Attention** ``A`` (every layer; ``n`` its normed input): ``q = RMSNorm_d(
  W_q n)`` and ``k = RMSNorm_d(W_k n)`` a head (one learned scale a channel
  of ``head_dim``), ``v = W_v n``; query head ``i`` reads key head ``i //
  (heads / kv_heads)``. In a ``sliding_attention`` layer q and k are turned by
  position, pair ``(i, i + head_dim / 2)`` by the angle ``t * rope_theta^(-2i
  / head_dim)``, and the query at ``t`` reads the keys ``t - sliding_window <
  s <= t``; a ``full_attention`` layer has no position code and reads every
  ``s <= t``. The **full masked softmax** of ``q k^T / sqrt(head_dim)`` over
  all ``S`` keys, a head and a block of queries at a time (32 heads' scores of
  16,384 x 16,384 would be 34 GB). ``y = W_o (sigmoid(W_gate n) *
  concat_heads a)``.
- **Dense feed-forward** (a published layer below ``num_dense_layers``):
  ``W_down (SiLU(W_gate n) * W_up n)``.
- **Expert layer** (every other): ``s = sigmoid(W_r n)``; the
  ``num_experts_per_tok`` largest of ``s + bias``; weights ``s_e`` over the
  chosen scores' sum plus 1e-20 (``route_norm``), times ``route_scale``;
  ``sum_e w_e E_e(n) + E_shared(n)``. No token is dropped.

**One departure, which changes no number**: an expert is applied to the
rows routed to it, gathered 1,024 at a time in as many passes as its count
needs (a loop bounded by the count: no row is dropped whatever the routing),
and its weighted result is added back at those rows, where
``references/kimi_k2.py`` runs every held expert on every token: 128 experts
on every token is 105 TFLOP a window at ``highest``. A row's products are
the same whichever pass holds it.

**Which layers.** The parameter tree holds the layers ``held.layers`` of the
published stack in order; a layer's kind is ``layer_types[i]`` and it is
dense where ``i < num_dense_layers``, by its *published* number ``i``.

**Parameters in the served type.** The program's initialiser hands its
leaves over in bfloat16, as a checkpoint would; each is brought to float32
where it is used, a layer (and within the expert layer an expert) at a time,
so no float32 copy of the whole tree ever stands beside it. Rows of the
batch one at a time (``lax.map``). None of that changes a number.
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


def _swiglu(p, x):
    p = _f32(p)
    return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def _turn(x, angle):
    """``x (S, heads, dim)`` with pair ``(i, i + dim / 2)`` turned by
    ``angle (S, dim / 2)``."""
    a, b = jnp.split(x, 2, axis=-1)
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(p, x, sizes, kind, eps):
    """One row ``(S, D)`` through a layer's attention."""
    heads, kv_heads = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d = sizes["head_dim"]
    p = _f32(p)
    s = x.shape[0]
    q = _rmsnorm(p["q_norm"], (x @ p["q"]).reshape(s, heads, d), eps)
    k = _rmsnorm(p["k_norm"], (x @ p["k"]).reshape(s, kv_heads, d), eps)
    v = (x @ p["v"]).reshape(s, kv_heads, d)
    sliding = kind == "sliding_attention"
    if sliding:
        inv_freq = float(sizes["rope_theta"]) ** (
            -2 * np.arange(d // 2, dtype=np.float64) / d)
        angle = jnp.arange(s, dtype=F32)[:, None] \
            * jnp.asarray(inv_freq, F32)[None, :]
        q, k = _turn(q, angle), _turn(k, angle)
    # a query head's keys and values: its group's
    k, v = (jnp.repeat(y, heads // kv_heads, axis=1) for y in (k, v))
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    key_at = jnp.arange(s)[None, :]

    def head(qkv):
        q_h, k_h, v_h = qkv  # (S, d) each

        def queries(at):
            first = at * block
            query_at = first + jnp.arange(block)[:, None]
            unseen = key_at > query_at
            if sliding:
                unseen |= key_at <= query_at - sizes["sliding_window"]
            scores = lax.dynamic_slice_in_dim(q_h, first, block) @ k_h.T \
                / math.sqrt(d)
            return jax.nn.softmax(jnp.where(unseen, -jnp.inf, scores), -1) \
                @ v_h

        return lax.map(queries, jnp.arange(s // block)).reshape(s, d)

    out = lax.map(head, tuple(y.transpose(1, 0, 2) for y in (q, k, v)))
    out = out.transpose(1, 0, 2).reshape(s, heads * d)
    return (jax.nn.sigmoid(x @ p["gate"]) * out) @ p["o"]


def _experts(p, x, sizes):
    """One row ``(S, D)`` through the expert layer: the held experts' part
    of the routed sum, and the shared expert."""
    n = x.shape[0]
    top_k = sizes["num_experts_per_tok"]
    first = sizes.get("held", {}).get("first_expert", 0)
    score = jax.nn.sigmoid(x @ p["router"].astype(F32))
    _, chosen = lax.top_k(score + p["router_bias"].astype(F32), top_k)
    weight = jnp.take_along_axis(score, chosen, -1)
    if sizes["route_norm"]:
        weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-20)
    weight = weight * sizes["route_scale"]
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
    return routed + _swiglu(p["shared"], x)


def forward(sizes: dict, params, state, x):
    """Next-token probabilities over the vocabulary held, ``(B,
    vocabulary)``, for windows of token ids ``(B, S)`` (as floats: the
    instance contract carries them so)."""
    eps = sizes["rms_norm_eps"]
    layers = params["layers"]
    held = sizes.get("held", {})
    which = held.get("layers", list(range(len(layers))))
    if len(layers) != len(which):
        raise ValueError("the program's model has another depth than the "
                         "configuration file")
    vocab, dim = params["embed"].shape
    ids = jnp.clip(jnp.round(x), 0, vocab - 1).astype(jnp.int32)

    def row(ids_row):
        h = params["embed"][ids_row].astype(F32)
        if sizes["mup_enabled"]:
            h = h * math.sqrt(dim)
        for i, blk in zip(which, layers):
            y = _attention(blk["mixer"], _rmsnorm(blk["norm1"], h, eps),
                           sizes, sizes["layer_types"][i], eps)
            h = h + _rmsnorm(blk["post1"], y, eps)
            y = _rmsnorm(blk["norm2"], h, eps)
            y = _swiglu(blk["ffn"], y) if i < sizes["num_dense_layers"] \
                else _experts(blk["ffn"], y, sizes)
            h = h + _rmsnorm(blk["post2"], y, eps)
        return _rmsnorm(params["norm"], h[-1], eps) \
            @ params["head"].astype(F32)

    return jax.nn.softmax(lax.map(row, ids), axis=-1)
