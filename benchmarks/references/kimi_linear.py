"""Plain Kimi-Linear forward (``model_type`` ``kimi_linear``: Kimi Team 2025,
"Kimi Linear: an expressive, efficient attention architecture", sections 3-4,
and the released ``config.json``), float32, ``jax.numpy`` only: no kernels, no
chunks, no grouped products. The yardstick's own copy of the mathematics, so
a change to the program's model code cannot move the reference with it.

Every block is ``x += mixer(RMSNorm(x)); x += ffn(RMSNorm(x))``:

- **KDA** (linear attention): ``q = L2Norm(SiLU(Conv4(W_q x)))``, ``k``
  likewise, ``v = SiLU(Conv4(W_v x))`` (causal depthwise convolution);
  per-channel log-decay ``g_t = -exp(A_log) softplus(W_f_up W_f_down x +
  dt_bias)``, ``beta_t = sigmoid(W_beta x)``; per head, **token by token**,
  ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T``,
  ``o_t = S_t^T q_t / sqrt(d)``; out ``W_o (sigmoid(W_g_up W_g_down x) *
  RMSNorm_head(o))``.
- **MLA** (latent attention, ``mla_use_nope``: no rotary): ``q = W_q x`` in
  heads of ``nope + rope``; ``[c, k_r] = W_kva x`` with ``c`` RMS-normed and
  ``k_r`` shared by all heads; ``[k_n, v] = W_kvb c``; ``k = [k_n, k_r]``;
  causal ``softmax(q k^T / sqrt(nope + rope)) v``; ``W_o``.
- **Dense feed-forward**: ``W_down (SiLU(W_gate x) * W_up x)``.
- **Expert layer**: ``s = sigmoid(W_r x)``; the ``top_k`` largest of ``s +
  bias``; weights ``s_i`` over the sum of the chosen ``s``, times
  ``routed_scaling_factor``; ``sum_i w_i E_i(x) + E_shared(x)``. No token is
  dropped: every held expert is run on every token and weighted by what the
  router gave it, zero where it was not chosen.

**The share.** The parameter tree says what this chip holds: as many layers
as it has, as many routed experts as are stacked (experts ``first_expert`` on
of the router's width; ``held.first_expert`` of the sizes, 0 where absent),
as many rows of the vocabulary as the embedding has. What experts held
elsewhere would add is left out, as in the program, and the logits are over
the held slice. Output: the softmax of the last position's logits, as the
engine serves it.

Blocked so that 4,096 tokens fit: rows of the batch one at a time
(``lax.map``), attention a block of queries at a time, experts one at a
time. None of that changes a number.

Departures from the published model, each under ``assumed`` in the
configuration's file: the decay's parametrisation and the gate's rank are the
released code's (the config gives neither); random weights.
"""

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 256


def _rmsnorm(p, x, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * p["scale"]


def _swiglu(p, x):
    return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def _conv(p, x):
    """Causal depthwise convolution over (S, C): the last tap is the
    current token's, tokens before the first are zero."""
    w = p["w"]
    width, s = w.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), x.dtype), x])
    return sum(w[j] * xp[j:j + s] for j in range(width))


def _l2norm(x, eps=1e-6):
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def _kda(p, x, sizes, eps):
    """One row (S, D) through the KDA mixer, the state read token by token."""
    la = sizes["linear_attn_config"]
    heads, d = la["num_heads"], la["head_dim"]
    s = x.shape[0]

    def heads_of(y):
        return y.reshape(s, heads, d)

    q = _l2norm(heads_of(jax.nn.silu(_conv(p["conv_q"], x @ p["q"]))))
    k = _l2norm(heads_of(jax.nn.silu(_conv(p["conv_k"], x @ p["k"]))))
    v = heads_of(jax.nn.silu(_conv(p["conv_v"], x @ p["v"])))
    g = -jnp.exp(p["a_log"])[:, None] * heads_of(
        jax.nn.softplus((x @ p["f_down"]) @ p["f_up"] + p["dt_bias"]))
    beta = jax.nn.sigmoid(x @ p["beta"])  # (S, H)

    def token(state, xs):  # state (H, dk, dv)
        q_t, k_t, v_t, g_t, b_t = xs
        state = jnp.exp(g_t)[:, :, None] * state
        read = jnp.einsum("hk,hkv->hv", k_t, state)
        state = state + b_t[:, None, None] * k_t[:, :, None] \
            * (v_t - read)[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t) * d ** -0.5

    _, o = lax.scan(token, jnp.zeros((heads, d, d), x.dtype),
                    (q, k, v, g, beta))
    gate = jax.nn.sigmoid((x @ p["g_down"]) @ p["g_up"])
    o = _rmsnorm(p["o_norm"], o, eps) * heads_of(gate)
    return o.reshape(s, heads * d) @ p["o"]


def _mla(p, x, sizes, eps):
    """One row (S, D) through latent attention, a block of queries at a
    time against every key, the later ones masked."""
    heads = sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    v_dim, rank = sizes["v_head_dim"], sizes["kv_lora_rank"]
    s = x.shape[0]
    q = (x @ p["q"]).reshape(s, heads, nope + rope)
    kv_a = x @ p["kv_a"]
    latent = _rmsnorm(p["kv_norm"], kv_a[:, :rank], eps)
    kv = (latent @ p["kv_b"]).reshape(s, heads, nope + v_dim)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        kv_a[:, None, rank:], (s, heads, rope))], -1)
    v = kv[..., nope:]
    outs = []
    for lo in range(0, s, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, s)
        scores = jnp.einsum("shd,thd->hst", q[lo:hi], k) \
            * (nope + rope) ** -0.5
        later = jnp.arange(s)[None, :] > jnp.arange(lo, hi)[:, None]
        probs = jax.nn.softmax(jnp.where(later, -jnp.inf, scores), -1)
        outs.append(jnp.einsum("hst,thd->shd", probs, v))
    return jnp.concatenate(outs).reshape(s, heads * v_dim) @ p["o"]


def _experts(p, x, sizes):
    """One row (S, D) through the expert layer: the held experts' part of
    the routed sum, and the shared expert."""
    top_k = sizes["num_experts_per_token"]
    first = sizes.get("held", {}).get("first_expert", 0)
    score = jax.nn.sigmoid(x @ p["router"])
    _, chosen = lax.top_k(score + p["router_bias"], top_k)
    weight = jnp.take_along_axis(score, chosen, -1)
    if sizes["moe_renormalize"]:
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
    la = sizes["linear_attn_config"]
    dense = sizes["first_k_dense_replace"]
    layers = params["layers"]
    held = sizes.get("held", {})
    if "num_hidden_layers" in held and len(layers) != held["num_hidden_layers"]:
        raise ValueError("the program's model has another depth than the "
                         "configuration file")
    vocab = params["embed"].shape[0]
    ids = jnp.clip(jnp.round(x), 0, vocab - 1).astype(jnp.int32)

    def row(ids_row):
        h = params["embed"][ids_row]
        for i, blk in enumerate(layers, start=1):
            y = _rmsnorm(blk["norm1"], h, eps)
            if i in la["full_attn_layers"]:
                h = h + _mla(blk["mixer"], y, sizes, eps)
            elif i in la["kda_layers"]:
                h = h + _kda(blk["mixer"], y, sizes, eps)
            else:
                raise ValueError(f"layer {i} is of no published kind")
            y = _rmsnorm(blk["norm2"], h, eps)
            h = h + (_swiglu(blk["ffn"], y) if i <= dense
                     else _experts(blk["ffn"], y, sizes))
        return _rmsnorm(params["norm"], h[-1], eps) @ params["head"]

    return jax.nn.softmax(lax.map(row, ids), axis=-1)
