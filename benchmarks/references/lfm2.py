"""Plain forward of LFM2 (``model_type`` ``lfm2_moe``: the released
``config.json``; for what the config does not state, the readings the
configuration's ``assumed`` lists), float32, ``jax.numpy`` only: no kernel,
no tile, no grouped product, nothing imported from the program or from
another reference. The yardstick's own copy of the mathematics, written from
the equations, so a change to the program's model code cannot move the
reference with it.

The stream starts at ``E[id]``. Every layer ``l`` of the held ones:

    h = h + Operator_l(RMSNorm_op(h))
    h = h + FeedForward_l(RMSNorm_ffn(h))

- **Gated short convolution** where ``layer_types[l]`` says ``conv`` (``n``
  the normed input): ``[B | C | x] = W_in n``, three ranges of
  ``hidden_size`` columns in this order; ``u_t = B_t * x_t``; ``c_t = w_0
  u_{t-2} + w_1 u_{t-1} + w_2 u_t`` (causal, depthwise, ``conv_L_cache``
  taps, the last the current token's, tokens before the first zero: **three
  shifted sums**; no bias, no activation); ``W_out (C_t * c_t)``.
- **Attention** where it says ``full_attention``: ``q`` in
  ``num_attention_heads`` heads, ``k`` and ``v`` in ``num_key_value_heads``,
  of ``hidden_size / num_attention_heads`` channels; each head of q and of
  k through an RMS norm over its channels under one learned scale, **then**
  turned by the plain rotary code over all of its channels, pairs ``(i, i +
  d / 2)``, ``inv_freq_i = theta^(-2 i / d)``, positions ``0..S-1``; query
  head ``i`` reads key head ``i // (heads / key heads)``; causal ``softmax(q
  k^T / sqrt(d)) v``, the full masked softmax a block of queries at a time;
  ``W_o``. No gate, no bias.
- **Dense feed-forward** for ``l < num_dense_layers``: ``W_2(SiLU(W_1 m) *
  W_3 m)``.
- **Expert layer** for the others (``m`` the float32 norm): ``s =
  sigmoid(W_r m)`` over the router's whole width; the ``num_experts_per_tok``
  largest ``s + b`` (``b`` the ``expert_bias``, in the choice alone);
  weights ``s_e / (sum over the chosen of s + 1e-6)`` (``norm_topk_prob``)
  times ``routed_scaling_factor``; ``sum_e w_e SwiGLU_e(m)``. No shared
  expert, no token dropped: **every held expert is run on every token** and
  weighted by what the router gave it, zero where it was not chosen.

After the last layer ``RMSNorm(h)`` at the window's last position, ``logits =
norm E^T``: the embedding's own rows (tied). Output: the softmax of those
logits, as the engine serves it.

**The share.** The parameter tree says what this chip holds: as many layers
as it has (the published layers ``held.layers``), as many routed experts as
are stacked (the whole router here), as many rows of the tied matrix as the
embedding has.

**Parameters in the served type.** The program's initialiser hands its leaves
over in bfloat16, as a checkpoint would; each is brought to float32 where it
is used, a layer (and within the expert layer an expert) at a time. Rows of
the batch one at a time (``lax.map``), attention a block of queries at a time
against every key. None of that changes a number.
"""

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
QUERY_BLOCK = 256


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _rmsnorm(p, x, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"].astype(F32)


def _swiglu(p, x):
    p = _f32(p)
    return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def _gated_conv(p, n, sizes):
    """One row (S, D) through the gated short convolution."""
    p = _f32(p)
    d, taps = sizes["hidden_size"], sizes["conv_L_cache"]
    s = n.shape[0]
    wide = n @ p["in"]
    b, c, x = wide[:, :d], wide[:, d:2 * d], wide[:, 2 * d:]
    u = jnp.concatenate([jnp.zeros((taps - 1, d), F32), b * x])
    w = p["conv"]["w"]
    conv = sum(w[j] * u[j:j + s] for j in range(taps))
    return (c * conv) @ p["out"]


def _turn(x, cos, sin):
    """(S, H, d) turned: pairs (i, i + d / 2)."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(p, n, sizes, eps):
    """One row (S, D) through grouped-query attention with head norms and
    the rotary turn, a block of queries at a time against every key, the
    later ones masked; each key head written out for the query heads that
    read it."""
    p = _f32(p)
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = sizes["hidden_size"] // heads
    s = n.shape[0]
    theta = float(sizes["rope_parameters"]["rope_theta"])
    inv_freq = theta ** (-2.0 * jnp.arange(hd // 2, dtype=F32) / hd)
    angle = jnp.arange(s, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    q = _turn(_rmsnorm(p["q_norm"], (n @ p["q"]).reshape(s, heads, hd), eps),
              cos, sin)
    k = _turn(_rmsnorm(p["k_norm"], (n @ p["k"]).reshape(s, kv, hd), eps),
              cos, sin)
    k = jnp.repeat(k, heads // kv, axis=1)
    v = jnp.repeat((n @ p["v"]).reshape(s, kv, hd), heads // kv, axis=1)
    outs = []
    for lo in range(0, s, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, s)
        scores = jnp.einsum("shd,thd->hst", q[lo:hi], k) * hd ** -0.5
        later = jnp.arange(s)[None, :] > jnp.arange(lo, hi)[:, None]
        probs = jax.nn.softmax(jnp.where(later, -jnp.inf, scores), -1)
        outs.append(jnp.einsum("hst,thd->shd", probs, v))
    return jnp.concatenate(outs).reshape(s, heads * hd) @ p["o"]


def _experts(p, m, sizes):
    """One row (S, D) through the expert layer: every held expert on every
    token, weighted by a 0/1 mask of the chosen times its weight."""
    top_k = sizes["num_experts_per_tok"]
    score = jax.nn.sigmoid(m @ p["router"].astype(F32))
    biased = score
    if sizes.get("use_expert_bias", False):
        biased = score + p["router_bias"].astype(F32)
    _, chosen = lax.top_k(biased, top_k)
    column = jnp.arange(score.shape[-1])
    mask = (chosen[..., None] == column).any(-2).astype(F32)  # (S, E)
    weight = score * mask
    if sizes.get("norm_topk_prob", True):
        weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-6)
    weight = weight * sizes.get("routed_scaling_factor", 1)

    def one(acc, ew):
        e, w = ew
        return acc + weight[:, e][:, None] * _swiglu(w, m), None

    held = p["experts"]["down"].shape[0]
    routed, _ = lax.scan(one, jnp.zeros_like(m),
                         (jnp.arange(held), p["experts"]))
    return routed


def forward(sizes: dict, params, state, x):
    """Next-token probabilities over the vocabulary, ``(B, vocabulary)``,
    for windows of token ids ``(B, S)`` (as floats: the instance contract
    carries them so)."""
    eps = sizes["norm_eps"]
    blocks = params["layers"]
    held = sizes.get("held", {})
    layers = list(held.get("layers", range(len(blocks))))
    if len(layers) != len(blocks):
        raise ValueError("the program's model has another depth than the "
                         "configuration file")
    vocab = params["embed"].shape[0]
    ids = jnp.clip(jnp.round(x), 0, vocab - 1).astype(jnp.int32)

    def row(ids_row):
        h = params["embed"][ids_row].astype(F32)
        for layer, blk in zip(layers, blocks):
            kind = sizes["layer_types"][layer]
            n = _rmsnorm(blk["norm1"], h, eps)
            if kind == "conv":
                h = h + _gated_conv(blk["mixer"], n, sizes)
            elif kind == "full_attention":
                h = h + _attention(blk["mixer"], n, sizes, eps)
            else:
                raise ValueError(f"layer {kind!r} is of no published kind")
            m = _rmsnorm(blk["norm2"], h, eps)
            if layer < sizes["num_dense_layers"]:
                h = h + _swiglu(blk["ffn"], m)
            else:
                h = h + _experts(blk["ffn"], m, sizes)
        return _rmsnorm(params["norm"], h[-1], eps)

    # the tied matrix's own rows, brought to float32 once for all windows
    logits = lax.map(row, ids) @ params["embed"].astype(F32).T
    return jax.nn.softmax(logits, axis=-1)
