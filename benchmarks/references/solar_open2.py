"""Plain Solar Open 2 forward (``model_type`` ``solar_open2``; the released
``config.json``: Kimi Delta Attention by its ``kda_*`` and
``linear_attn_config`` keys, Kimi Team 2025, "Kimi Linear", sections 3-4;
gated softmax attention; DeepSeek-V3's routed-plus-shared feed-forward),
float32, ``jax.numpy`` only: no kernels, no chunks, no blocks of scores, no
grouped products. The yardstick's own copy of the mathematics, so a change to
the program's model code cannot move the reference with it.

Every block is ``h = x + mixer(RMSNorm(x)); y = h + experts(RMSNorm(h))``:

- **Gated grouped-query attention** (layers in ``gqa_layers``): ``q = W_q x``
  in ``num_attention_heads`` heads of ``head_dim``, ``k = W_k x`` and ``v =
  W_v x`` in ``num_key_value_heads``; no position code (``use_rope`` false),
  no bias; query head ``i`` reads key head ``i // (heads / kv_heads)``; the
  **full masked softmax** of ``q k^T / sqrt(head_dim)`` over all ``S`` keys, a
  query head at a time; ``W_o (sigmoid(W_gate x) * attn)`` (``use_gqa_gate``).
- **KDA** (every other layer), per head of ``linear_attn_config``: ``q =
  L2Norm(SiLU(Conv4(W_q x))) / sqrt(d)``, ``k`` likewise without the scale,
  ``v = SiLU(Conv4(W_v x))`` (causal depthwise convolution); per-channel
  log-decay ``g_t = -exp(A_log) softplus(W_f_up W_f_down x + dt_bias)``;
  ``beta_t = 2 sigmoid(W_beta x)`` where ``kda_allow_neg_eigval`` (else
  ``sigmoid``); **token by token** ``S_t = (I - beta_t k_t k_t^T)
  Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``; out ``W_o
  (sigmoid(W_g_up W_g_down x) * RMSNorm_head(o))``.
- **Expert layer** (every block: ``first_k_dense_replace`` 0): ``s =
  sigmoid(W_r x)``; the ``num_experts_per_tok`` largest of ``s + bias``;
  weights ``s_i`` over the sum of the chosen ``s`` (``norm_topk_prob``),
  times ``routed_scaling_factor``; ``sum_i w_i E_i(x) + E_shared(x)``, each a
  SwiGLU. No token is dropped: every held expert is run on every token and
  weighted by what the router gave it, zero where it was not chosen.

**The share**, as ``references/kimi_linear.py``: the parameter tree says what
this chip holds (layers, stacked experts from ``held.first_expert``, rows of
the vocabulary); what experts held elsewhere would add is left out.

**Parameters in the served type.** The program's initialiser hands its
leaves over in bfloat16, as a checkpoint would; each is brought to float32
where it is used, a layer (and within the expert layer an expert) at a time,
so no float32 copy of the whole tree ever stands beside it. Rows of the
batch one at a time (``lax.map``). None of that changes a number.

Departures from the published model, each under ``assumed`` in the
configuration's file: the gate's form, the decay's parametrisation and the
ranks of its and the KDA gate's projections, a sigmoid router, random weights.
"""

import jax
import jax.numpy as jnp
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


def _conv(p, x):
    """Causal depthwise convolution over (S, C): the last tap is the
    current token's, tokens before the first are zero."""
    w = p["w"]
    width, s = w.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), x.dtype), x])
    return sum(w[j] * xp[j:j + s] for j in range(width))


def _l2norm(x, eps=1e-6):
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def _gqa(p, x, sizes):
    """One row (S, D) through gated grouped-query attention."""
    heads, kv_heads = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d = sizes["head_dim"]
    group = heads // kv_heads
    p = _f32(p)
    s = x.shape[0]
    q = (x @ p["q"]).reshape(s, heads, d).transpose(1, 0, 2)
    k = (x @ p["k"]).reshape(s, kv_heads, d).transpose(1, 0, 2)
    v = (x @ p["v"]).reshape(s, kv_heads, d).transpose(1, 0, 2)
    later = jnp.arange(s)[None, :] > jnp.arange(s)[:, None]

    def head(iq):
        i, q_h = iq
        scores = q_h @ k[i // group].T * d ** -0.5
        return jax.nn.softmax(jnp.where(later, -jnp.inf, scores), -1) \
            @ v[i // group]

    out = lax.map(head, (jnp.arange(heads), q))  # (H, S, d)
    out = out.transpose(1, 0, 2).reshape(s, heads * d)
    if sizes["use_gqa_gate"]:
        out = out * jax.nn.sigmoid(x @ p["gate"])
    return out @ p["o"]


def _kda(p, x, sizes, eps):
    """One row (S, D) through the KDA mixer, the state read token by token."""
    la = sizes["linear_attn_config"]
    heads, d = la["num_heads"], la["head_dim"]
    p = _f32(p)
    s = x.shape[0]

    def heads_of(y):
        return y.reshape(s, heads, d)

    q = _l2norm(heads_of(jax.nn.silu(_conv(p["conv_q"], x @ p["q"])))) \
        * d ** -0.5
    k = _l2norm(heads_of(jax.nn.silu(_conv(p["conv_k"], x @ p["k"]))))
    v = heads_of(jax.nn.silu(_conv(p["conv_v"], x @ p["v"])))
    g = -jnp.exp(p["a_log"])[:, None] * heads_of(
        jax.nn.softplus((x @ p["f_down"]) @ p["f_up"] + p["dt_bias"]))
    beta = jax.nn.sigmoid(x @ p["beta"])  # (S, H)
    if sizes["kda_allow_neg_eigval"]:
        beta = 2.0 * beta

    def token(state, xs):  # state (H, dk, dv)
        q_t, k_t, v_t, g_t, b_t = xs
        state = jnp.exp(g_t)[:, :, None] * state
        read = jnp.einsum("hk,hkv->hv", k_t, state)
        state = state + b_t[:, None, None] * k_t[:, :, None] \
            * (v_t - read)[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = lax.scan(token, jnp.zeros((heads, d, d), F32), (q, k, v, g, beta))
    gate = jax.nn.sigmoid((x @ p["g_down"]) @ p["g_up"])
    o = _rmsnorm(p["o_norm"], o, eps) * heads_of(gate)
    return o.reshape(s, heads * d) @ p["o"]


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
    if sizes["first_k_dense_replace"]:
        raise ValueError("a dense leading layer is none of this family's")
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
            y = _rmsnorm(blk["norm1"], h, eps)
            h = h + (_gqa(blk["mixer"], y, sizes) if i in sizes["gqa_layers"]
                     else _kda(blk["mixer"], y, sizes, eps))
            h = h + _experts(blk["ffn"], _rmsnorm(blk["norm2"], h, eps), sizes)
        return _rmsnorm(params["norm"], h[-1], eps) \
            @ params["head"].astype(F32)

    return jax.nn.softmax(lax.map(row, ids), axis=-1)
