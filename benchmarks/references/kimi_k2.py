"""Plain Kimi K2 forward (``model_type`` ``kimi_k2``, which keeps the block of
DeepSeek-AI 2024, "DeepSeek-V3 technical report", section 2.1, and the
released ``config.json``), float32, ``jax.numpy`` only: no kernels, no blocks
of scores, no grouped products. The yardstick's own copy of the mathematics,
so a change to the program's model code cannot move the reference with it.

Every block is ``h = x + MLA(RMSNorm(x)); y = h + F(RMSNorm(h))``:

- **MLA** (latent attention, every layer): ``c_q = RMSNorm(x W_qa)``;
  ``q = c_q W_qb`` in heads of ``nope + rope``; ``[c_kv, k_r] = x W_kva`` with
  ``c_kv`` RMS-normed and ``k_r`` shared by all heads; ``[k_n, v] = c_kv
  W_kvb``; the ``rope`` channels of each query head and of ``k_r`` are
  turned by position, pair ``(i, i + rope / 2)`` by the angle ``t *
  inv_freq_i`` (the loaded weights' order: the checkpoint pairs ``(2i, 2i +
  1)``, and the loader brings a projection's rotary columns to ``(evens,
  odds)`` once, as the released code does a step); ``k = [k_n, k_r]``;
  the **full masked softmax** of ``q k^T * scale`` over all ``S`` keys, a
  head at a time (64 heads' scores of 4,096 x 4,096 would be 4.3 GB); ``W_o``.
- **YaRN**: ``f_i = theta^(-2i/dim)``; ``low``/``high`` the pairs at which
  ``original_max_position_embeddings`` positions make ``beta_fast`` and
  ``beta_slow`` turns (floor, ceiling, clipped to ``[0, dim - 1]``);
  ``ramp_i = clip((i - low) / (high - low), 0, 1)``; ``inv_freq_i = f_i /
  factor * ramp_i + f_i * (1 - ramp_i)``; ``scale = (nope + rope)^-0.5 *
  m^2`` with ``m = 0.1 * mscale_all_dim * ln(factor) + 1``; cos and sin carry
  ``(0.1 * mscale * ln(factor) + 1) / m``.
- **Dense feed-forward** (the first ``first_k_dense_replace`` layers):
  ``W_down (SiLU(W_gate x) * W_up x)``.
- **Expert layer**: ``s = sigmoid(W_r x)``; the ``num_experts_per_tok``
  largest of ``s + bias``; weights ``s_i`` over the sum of the chosen ``s``
  (``norm_topk_prob``), times ``routed_scaling_factor``; ``sum_i w_i E_i(x) +
  E_shared(x)``. No token is dropped: every held expert is run on every
  token and weighted by what the router gave it, zero where it was not chosen.

**The share**, as ``references/kimi_linear.py``: the parameter tree says what
this chip holds (layers, stacked experts from ``held.first_expert``, rows of
the vocabulary); what experts held elsewhere would add is left out.

**Parameters in the served type.** The program's initialiser hands its
leaves over in bfloat16, as a checkpoint would; each is brought to float32
where it is used, a layer (and within the expert layer an expert) at a time,
so no float32 copy of the whole tree ever stands beside it. Rows of the
batch one at a time (``lax.map``). None of that changes a number.

Departures from the published model, each under ``assumed`` in the
configuration's file: no vision tower, random weights.
"""

import math

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


def yarn(sizes: dict):
    """``(inv_freq, softmax_scale, attention_factor)`` of the published
    rotary settings, worked in float64."""
    dim = sizes["qk_rope_head_dim"]
    theta = float(sizes["rope_theta"])
    i = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2 * i / dim)
    plain = (sizes["qk_nope_head_dim"] + dim) ** -0.5
    rs = sizes.get("rope_scaling")
    if not rs:
        return f, plain, 1.0
    factor = float(rs["factor"])
    original = rs["original_max_position_embeddings"]

    def pair_of(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(rs["beta_fast"])), 0)
    high = min(math.ceil(pair_of(rs["beta_slow"])), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)

    def mscale(scale):
        return 0.1 * scale * math.log(factor) + 1.0 if factor > 1 else 1.0

    m = mscale(rs["mscale_all_dim"])
    return (f / factor * ramp + f * (1 - ramp), plain * m * m,
            mscale(rs["mscale"]) / m)


def _turn(x, angle, factor):
    """``x (..., dim)`` with pair ``(i, i + dim / 2)`` turned by ``angle
    (..., dim / 2)``."""
    a, b = jnp.split(x, 2, axis=-1)
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _mla(p, x, sizes, eps):
    """One row (S, D) through latent attention."""
    heads = sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    v_dim, rank = sizes["v_head_dim"], sizes["kv_lora_rank"]
    inv_freq, scale, factor = yarn(sizes)
    p = _f32(p)
    s = x.shape[0]
    angle = jnp.arange(s, dtype=F32)[:, None] \
        * jnp.asarray(inv_freq, F32)[None, :]  # (S, rope / 2)
    c_q = _rmsnorm(p["q_norm"], x @ p["q_a"], eps)
    q = (c_q @ p["q_b"]).reshape(s, heads, nope + rope)
    q = jnp.concatenate(
        [q[..., :nope], _turn(q[..., nope:], angle[:, None], factor)], -1)
    kv_a = x @ p["kv_a"]
    latent = _rmsnorm(p["kv_norm"], kv_a[:, :rank], eps)
    k_r = _turn(kv_a[:, rank:], angle, factor)  # (S, rope)
    kv = (latent @ p["kv_b"]).reshape(s, heads, nope + v_dim)
    later = jnp.arange(s)[None, :] > jnp.arange(s)[:, None]

    def head(qkv):
        q_h, k_h, v_h = qkv  # (S, nope + rope), (S, nope), (S, v_dim)
        scores = q_h @ jnp.concatenate([k_h, k_r], -1).T * scale
        return jax.nn.softmax(jnp.where(later, -jnp.inf, scores), -1) @ v_h

    out = lax.map(head, (q.transpose(1, 0, 2),
                         kv[..., :nope].transpose(1, 0, 2),
                         kv[..., nope:].transpose(1, 0, 2)))  # (H, S, v_dim)
    return out.transpose(1, 0, 2).reshape(s, heads * v_dim) @ p["o"]


def _experts(p, x, sizes):
    """One row (S, D) through the expert layer: the held experts' part of
    the routed sum, and the shared expert."""
    top_k = sizes["num_experts_per_tok"]
    first = sizes.get("held", {}).get("first_expert", 0)
    score = jax.nn.sigmoid(x @ p["router"].astype(F32))
    _, chosen = lax.top_k(score + p["router_bias"].astype(F32), top_k)
    weight = jnp.take_along_axis(score, chosen, -1)
    if sizes["norm_topk_prob"]:
        weight = weight / jnp.sum(weight, -1, keepdims=True)
    weight = weight * sizes["routed_scaling_factor"]

    def one(e, w):
        gain = jnp.sum(jnp.where(chosen == e + first, weight, 0.0), -1)
        return gain[:, None] * _swiglu(w, x)

    held = p["experts"]["gate"].shape[0]
    routed, _ = lax.scan(lambda acc, ew: (acc + one(*ew), None),
                         jnp.zeros_like(x), (jnp.arange(held), p["experts"]))
    return routed + _swiglu(p["shared"], x)


def forward(sizes: dict, params, state, x):
    """Next-token probabilities over the held slice, ``(B, vocabulary
    held)``, for windows of token ids ``(B, S)`` (as floats: the instance
    contract carries them so)."""
    eps = sizes["rms_norm_eps"]
    dense = sizes["first_k_dense_replace"]
    layers = params["layers"]
    held = sizes.get("held", {})
    if "num_hidden_layers" in held and len(layers) != held["num_hidden_layers"]:
        raise ValueError("the program's model has another depth than the "
                         "configuration file")
    vocab = params["embed"].shape[0]
    ids = jnp.clip(jnp.round(x), 0, vocab - 1).astype(jnp.int32)

    def row(ids_row):
        h = params["embed"][ids_row].astype(F32)
        for i, blk in enumerate(layers):
            h = h + _mla(blk["mixer"], _rmsnorm(blk["norm1"], h, eps), sizes,
                         eps)
            y = _rmsnorm(blk["norm2"], h, eps)
            h = h + (_swiglu(blk["ffn"], y) if i < dense
                     else _experts(blk["ffn"], y, sizes))
        return _rmsnorm(params["norm"], h[-1], eps) \
            @ params["head"].astype(F32)

    return jax.nn.softmax(lax.map(row, ids), axis=-1)
