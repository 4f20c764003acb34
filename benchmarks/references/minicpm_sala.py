"""Plain MiniCPM-SALA forward (``model_type`` ``minicpm_sala``; the released
``config.json``, the MiniCPM4 report's sparse attention, arXiv:2506.07900
section 2.2 (InfLLM v2), and Lightning Attention-2's decay, Qin et al. 2024),
float32, ``jax.numpy`` only: no kernels, no chunks, no blocks of scores. The
yardstick's own copy of the mathematics, so a change to the program's model
code cannot move the reference with it.

``u`` a branch's input, ``c = scale_depth / sqrt(num_hidden_layers)`` with the
*published* depth:

    x_0 = scale_emb E[id]
    h = x + c Mixer(RMSNorm(x));   y = h + c W_down(SiLU(W_gate h') * W_up h')
    logits = (RMSNorm(x_L) / (hidden_size / dim_model_base)) W_head

- **``lightning-attn``** (published layer ``l``): ``q, k, v = u W_q, u W_k, u
  W_v`` in ``lightning_nh`` heads; ``q, k <- RMSNorm`` over a head's channels;
  rotary on all of a head's channels of ``q`` and ``k``, pair ``(i, i + d /
  2)`` turned by ``t theta^(-2i/d)``; **token by token** ``S_t = lambda_h
  S_(t-1) + k_t^T v_t``, ``o_t = (q_t / sqrt(d)) S_t`` with ``lambda_h =
  exp(-2^(-8 (h + 1) / heads) (1 - l / (layers - 1) + 1e-5))``; ``o <-
  RMSNorm(o)`` over the merged heads, times ``sigmoid(u W_g)``; ``W_o``.
- **``minicpm4``**: ``q`` in ``num_attention_heads`` heads, ``k, v`` in
  ``num_key_value_heads`` (query head ``h`` reads group ``h // (heads /
  groups)``); ``q, k <- RMSNorm`` over a head's channels; no rotary. A window
  of ``dense_len`` positions or fewer: the full causal softmax. A longer
  one, for group ``g`` and query ``t``: pooled keys ``c_i = mean(k[stride i
  .. stride i + kernel - 1])``, visible where ``stride i + kernel - 1 <= t``;
  ``p_h = softmax_i(q_h . c_i / sqrt(d))`` over the visible ``i``; ``r = sum``
  of ``p_h`` over the group's heads; block ``j`` scores ``max r_i`` over ``i``
  in ``[ratio j - 1, ratio j + ratio - 1]``, ``ratio = block / stride``; the
  first ``init_blocks`` blocks and blocks ``t // block - window / block .. t
  // block`` score ``+inf``; ``J`` = the ``topk`` best among ``j <= t //
  block`` (the lower index first among equals), all where there are fewer;
  then **the full masked softmax** of ``q_h . k_s / sqrt(d)`` over ``{s <= t,
  s // block in J}``, a head at a time, times ``v``. Last ``o`` times
  ``sigmoid(u W_g)``, then ``W_o``.

What the catalog's row does not settle, each under ``assumed`` in the
configuration's file: the sparse attention's seven sizes (``held.sparse``);
one pooling stage (the mean, no second compression); the forced blocks
counted inside the ``topk``; the max-pool's alignment (one of padding on the
left); the decay; the output norm over the merged heads; the rotary pairing
(halves); ``mup_denominator`` read by no forward.

**Parameters in the served type**, as ``references/kimi_k2.py``: each leaf is
brought to float32 where it is used, a layer at a time. Rows of the batch
one at a time (``lax.map``), heads one at a time where the scores are a
window squared. None of that changes a number.
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


def decay(sizes: dict, layer: int) -> np.ndarray:
    """``lambda_h`` of published layer ``layer``, float64."""
    heads = sizes["lightning_nh"]
    slope = 2.0 ** (-8.0 * (np.arange(heads) + 1) / heads)
    return np.exp(-slope * (
        1 - layer / (sizes["num_hidden_layers"] - 1) + 1e-5))


def _lightning(p, u, sizes, eps, layer):
    """One row (S, D) through a lightning layer, the state read token by
    token."""
    heads, d = sizes["lightning_nh"], sizes["lightning_head_dim"]
    p = _f32(p)
    s = u.shape[0]
    angle = jnp.arange(s, dtype=F32)[:, None, None] * jnp.asarray(
        float(sizes["rope_theta"]) ** (-2.0 * np.arange(d // 2) / d), F32)
    q = _turn(_rmsnorm(p["q_norm"], (u @ p["q"]).reshape(s, heads, d), eps),
              angle) * d ** -0.5
    k = _turn(_rmsnorm(p["k_norm"], (u @ p["k"]).reshape(s, heads, d), eps),
              angle)
    v = (u @ p["v"]).reshape(s, heads, d)
    lam = jnp.asarray(decay(sizes, layer), F32)[:, None, None]

    def token(state, qkv):  # state (H, d, d): keys by values
        q_t, k_t, v_t = qkv
        state = lam * state + k_t[:, :, None] * v_t[:, None, :]
        return state, jnp.einsum("hd,hde->he", q_t, state)

    _, o = lax.scan(token, jnp.zeros((heads, d, d), F32), (q, k, v))
    o = _rmsnorm(p["norm"], o.reshape(s, heads * d), eps)
    return (o * jax.nn.sigmoid(u @ p["gate"])) @ p["o"]


def picked_blocks(q, k, sparse: dict) -> jnp.ndarray:
    """``J`` as a mask ``(groups, S, S / block)`` for one row's ``q (S, H,
    d)`` and ``k (S, G, d)``, the equations as written."""
    s, heads, d = q.shape
    groups = k.shape[1]
    kernel, stride = sparse["kernel_size"], sparse["kernel_stride"]
    block, ratio = sparse["block_size"], sparse["block_size"] // stride
    n_pool, nb = (s - kernel) // stride + 1, s // block
    t = jnp.arange(s)
    window = stride * jnp.arange(n_pool)[:, None] + jnp.arange(kernel)
    pooled = k[window].mean(1)  # (n_pool, G, d)
    visible = window[:, -1][None, :] <= t[:, None]  # (S, n_pool)

    def head(r, qc):  # one query head's probabilities onto its group's sum
        q_h, c_g, g = qc
        scores = jnp.where(visible, q_h @ c_g.T * d ** -0.5, -jnp.inf)
        p = jnp.exp(scores - jnp.max(scores, -1, keepdims=True, initial=-1e30))
        p = jnp.where(visible, p, 0.0)
        total = p.sum(-1, keepdims=True)
        return r.at[g].add(p / jnp.where(total > 0, total, 1.0)), None

    of_group = jnp.arange(heads) // (heads // groups)
    r, _ = lax.scan(head, jnp.zeros((groups, s, n_pool), F32),
                    (q.transpose(1, 0, 2),
                     pooled.transpose(1, 0, 2)[of_group], of_group))
    i = ratio * jnp.arange(nb)[:, None] - 1 + jnp.arange(ratio + 1)
    inside = (i >= 0) & (i < n_pool)
    score = jnp.max(jnp.where(inside, r[..., jnp.clip(i, 0, n_pool - 1)],
                              -jnp.inf), -1)  # (G, S, nb)
    j, own = jnp.arange(nb), (t // block)[:, None]
    forced = (j < sparse["init_blocks"]) | (
        (j >= own - sparse["window_size"] // block) & (j <= own))
    score = jnp.where(forced, jnp.inf, jnp.where(j <= own, score, -jnp.inf))
    # a block's place among the best first, the lower index among equals
    place = jnp.argsort(jnp.argsort(-score, -1, stable=True), -1, stable=True)
    return (place < sparse["topk"]) & (j <= own)


def _minicpm4(p, u, sizes, eps):
    """One row (S, D) through the sparse-attention mixer."""
    heads, groups = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d, sparse = sizes["head_dim"], sizes["held"]["sparse"]
    p = _f32(p)
    s = u.shape[0]
    q = _rmsnorm(p["q_norm"], (u @ p["q"]).reshape(s, heads, d), eps)
    k = _rmsnorm(p["k_norm"], (u @ p["k"]).reshape(s, groups, d), eps)
    v = (u @ p["v"]).reshape(s, groups, d)
    t = jnp.arange(s)
    reads = jnp.broadcast_to(t[None, :] <= t[:, None], (groups, s, s))
    if s > sparse["dense_len"]:
        picked = picked_blocks(q, k, sparse)
        reads = reads & jnp.repeat(picked, sparse["block_size"], axis=-1)
    of_group = jnp.arange(heads) // (heads // groups)

    def head(qg):
        q_h, g = qg
        scores = jnp.where(reads[g], q_h @ k[:, g].T * d ** -0.5, -jnp.inf)
        return jax.nn.softmax(scores, -1) @ v[:, g]

    o = lax.map(head, (q.transpose(1, 0, 2), of_group))  # (H, S, d)
    o = o.transpose(1, 0, 2).reshape(s, heads * d)
    return (o * jax.nn.sigmoid(u @ p["gate"])) @ p["o"]


def forward(sizes: dict, params, state, x):
    """Next-token probabilities over the vocabulary, ``(B, vocabulary)``, for
    windows of token ids ``(B, S)`` (as floats: the instance contract carries
    them so)."""
    eps = sizes["rms_norm_eps"]
    layers = params["layers"]
    held = sizes.get("held", {})
    if "num_hidden_layers" in held and len(layers) != held["num_hidden_layers"]:
        raise ValueError("the program's model has another depth than the "
                         "configuration file")
    kinds = sizes["mixer_types"][:len(layers)]
    c = sizes["scale_depth"] / sizes["num_hidden_layers"] ** 0.5
    vocab = params["embed"].shape[0]
    ids = jnp.clip(jnp.round(x), 0, vocab - 1).astype(jnp.int32)

    def row(ids_row):
        h = sizes["scale_emb"] * params["embed"][ids_row].astype(F32)
        for layer, (kind, blk) in enumerate(zip(kinds, layers)):
            u = _rmsnorm(blk["norm1"], h, eps)
            h = h + c * (_minicpm4(blk["mixer"], u, sizes, eps)
                         if kind == "minicpm4" else
                         _lightning(blk["mixer"], u, sizes, eps, layer))
            h = h + c * _swiglu(blk["ffn"], _rmsnorm(blk["norm2"], h, eps))
        last = _rmsnorm(params["norm"], h[-1], eps) \
            / (sizes["hidden_size"] / sizes["dim_model_base"])
        return last @ params["head"].astype(F32)

    return jax.nn.softmax(lax.map(row, ids), axis=-1)
