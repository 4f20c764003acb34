"""Plain forward of Granite 4.0-H (``model_type`` ``granitemoehybrid``: the
released ``config.json``; the Mamba-2 layer of Dao and Gu 2024, "Transformers
are SSMs"; for what the config does not state, the readings the
configuration's ``assumed`` lists), float32, ``jax.numpy`` only: no kernel,
no chunk, no grouped product, nothing imported from the program or from
another reference. The yardstick's own copy of the mathematics, written from
the equations, so a change to the program's model code cannot move the
reference with it.

The stream starts at ``embedding_multiplier * E[id]``. Every block, with ``r =
residual_multiplier``:

    h = h + r mixer(RMSNorm_1(h))
    h = h + r (moe(n) + shared(n)),    n = RMSNorm_2(h)

- **Mamba-2** where ``layer_types`` says ``mamba`` (``u`` the normed input):
  ``[z | xBC | dt] = W_in u``; ``xBC = SiLU(Conv(xBC) + b)`` (causal,
  depthwise, ``mamba_d_conv`` taps), split into ``x`` (``mamba_n_heads``
  heads of ``mamba_d_head``) and ``B``, ``C`` (``mamba_n_groups`` groups of
  ``mamba_d_state``; head ``h`` reads group ``h // (heads / groups)``: with
  the published one group every head reads the same ``B_t`` and ``C_t``);
  ``dt_t = softplus(dt_t + dt_bias)``, ``A = -exp(A_log)``, one number a
  head each; per head, **token by token**, ``S_t = exp(dt_t A) S_{t-1} +
  dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``; the gate first and the norm
  after it, ``RMSNorm(y * SiLU(z))`` over each group's channels (all 8,192
  at one group) with one learned scale a channel; ``W_out``.
- **Attention** where it says ``attention``: ``q`` in ``num_attention_heads``
  heads, ``k`` and ``v`` in ``num_key_value_heads``, of ``hidden_size /
  num_attention_heads`` channels; query head ``i`` reads key head ``i //
  (heads / key heads)``; causal ``softmax(q k^T * attention_multiplier) v``,
  the full masked softmax a row; ``W_o``. No position code
  (``position_embedding_type`` ``nope``), no bias, no head norm.
- **Expert layer** (every block): ``l = W_r n`` over the router's whole
  width; the ``num_experts_per_tok`` largest ``l``; weights their softmax
  over the chosen; ``sum_e w_e SwiGLU_e(n)`` plus ``SwiGLU_shared(n)``
  unweighted. No selection bias, no token dropped: **every held expert is
  run on every token** and weighted by what the router gave it, zero where
  it was not chosen.

After the last block ``RMSNorm(h)`` at the window's last position, ``logits =
(norm / logits_scaling) E^T``: the embedding's own rows
(``tie_word_embeddings``).

**The share.** The parameter tree says what this chip holds: as many blocks
as it has (the published layers ``held.layers``), as many routed experts as
are stacked (experts ``held.first_expert`` on of the router's width; 0 where
absent), as many rows of the tied matrix as the embedding has. What experts
held elsewhere would add is left out, as in the program, and the logits are
over the held slice. Output: the softmax of the last position's logits, as
the engine serves it.

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


def _conv(p, x):
    """Causal depthwise convolution over (S, C) with a bias: the last tap is
    the current token's, tokens before the first are zero."""
    w = p["w"]
    width, s = w.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), x.dtype), x])
    return sum(w[j] * xp[j:j + s] for j in range(width)) + p["b"]


def _mamba(p, u, sizes, eps):
    """One row (S, D) through the Mamba-2 mixer, the state read token by
    token."""
    p = _f32(p)
    heads, hd = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    groups, n = sizes["mamba_n_groups"], sizes["mamba_d_state"]
    inner, gn = heads * hd, groups * n
    s = u.shape[0]
    zxbcdt = u @ p["in_proj"]
    z = zxbcdt[:, :inner]
    xbc = jax.nn.silu(_conv(p["conv"], zxbcdt[:, inner:2 * inner + 2 * gn]))
    dt = jax.nn.softplus(zxbcdt[:, 2 * inner + 2 * gn:] + p["dt_bias"])
    x = xbc[:, :inner].reshape(s, heads, hd)
    # each head reads its group's B and C
    b = jnp.repeat(xbc[:, inner:inner + gn].reshape(s, groups, n),
                   heads // groups, axis=1)
    c = jnp.repeat(xbc[:, inner + gn:].reshape(s, groups, n),
                   heads // groups, axis=1)
    a = -jnp.exp(p["a_log"])

    def token(state, xs):  # state (H, P, N)
        x_t, dt_t, b_t, c_t = xs
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    _, y = lax.scan(token, jnp.zeros((heads, hd, n), F32), (x, dt, b, c))
    y = (y + p["d"][:, None] * x).reshape(s, inner) * jax.nn.silu(z)
    # the gate first, then the norm over each group's channels
    g = y.reshape(s, groups, inner // groups)
    g = g * lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    return (g.reshape(s, inner) * p["norm"]["scale"]) @ p["out_proj"]


def _attention(p, u, sizes):
    """One row (S, D) through grouped-query attention, a block of queries at
    a time against every key, the later ones masked; each key head written
    out for the query heads that read it."""
    p = _f32(p)
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = sizes["hidden_size"] // heads
    s = u.shape[0]
    q = (u @ p["q"]).reshape(s, heads, hd)
    k = jnp.repeat((u @ p["k"]).reshape(s, kv, hd), heads // kv, axis=1)
    v = jnp.repeat((u @ p["v"]).reshape(s, kv, hd), heads // kv, axis=1)
    outs = []
    for lo in range(0, s, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, s)
        scores = jnp.einsum("shd,thd->hst", q[lo:hi], k) \
            * sizes["attention_multiplier"]
        later = jnp.arange(s)[None, :] > jnp.arange(lo, hi)[:, None]
        probs = jax.nn.softmax(jnp.where(later, -jnp.inf, scores), -1)
        outs.append(jnp.einsum("hst,thd->shd", probs, v))
    return jnp.concatenate(outs).reshape(s, heads * hd) @ p["o"]


def _experts(p, u, sizes):
    """One row (S, D) through the expert layer: the held experts' part of
    the routed sum, and the shared expert."""
    top_k = sizes["num_experts_per_tok"]
    first = sizes.get("held", {}).get("first_expert", 0)
    logits, chosen = lax.top_k(u @ p["router"].astype(F32), top_k)
    weight = jax.nn.softmax(logits, -1)

    def one(acc, ew):
        e, w = ew
        gain = jnp.sum(jnp.where(chosen == e + first, weight, 0.0), -1)
        return acc + gain[:, None] * _swiglu(w, u), None

    held = p["experts"]["down"].shape[0]
    routed, _ = lax.scan(one, jnp.zeros_like(u),
                         (jnp.arange(held), p["experts"]))
    return routed + _swiglu(p["shared"], u)


def forward(sizes: dict, params, state, x):
    """Next-token probabilities over the held slice, ``(B, vocabulary
    held)``, for windows of token ids ``(B, S)`` (as floats: the instance
    contract carries them so)."""
    eps = sizes["rms_norm_eps"]
    blocks = params["layers"]
    held = sizes.get("held", {})
    kinds = [sizes["layer_types"][i]
             for i in held.get("layers", range(len(blocks)))]
    if len(kinds) != len(blocks):
        raise ValueError("the program's model has another depth than the "
                         "configuration file")
    r = sizes["residual_multiplier"]
    vocab = params["embed"].shape[0]
    ids = jnp.clip(jnp.round(x), 0, vocab - 1).astype(jnp.int32)

    def row(ids_row):
        h = sizes["embedding_multiplier"] * params["embed"][ids_row].astype(
            F32)
        for kind, blk in zip(kinds, blocks):
            u = _rmsnorm(blk["norm1"], h, eps)
            if kind == "mamba":
                h = h + r * _mamba(blk["mixer"], u, sizes, eps)
            elif kind == "attention":
                h = h + r * _attention(blk["mixer"], u, sizes)
            else:
                raise ValueError(f"layer {kind!r} is of no published kind")
            h = h + r * _experts(blk["ffn"], _rmsnorm(blk["norm2"], h, eps),
                                 sizes)
        return _rmsnorm(params["norm"], h[-1], eps) / sizes["logits_scaling"]

    # the tied matrix's own rows, brought to float32 once for all windows
    logits = lax.map(row, ids) @ params["embed"].astype(F32).T
    return jax.nn.softmax(logits, axis=-1)
