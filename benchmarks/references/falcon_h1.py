"""Plain forward of Falcon-H1 (``model_type`` ``falcon_h1``: the released
``config.json``; the Mamba-2 layer of Dao and Gu 2024, "Transformers are
SSMs"; rotary position code of Su et al. 2021; for what the config does not
state, the readings the configuration's ``assumed`` lists), float32,
``jax.numpy`` only: no kernel, no chunk, no grouped product, nothing imported
from the program or from another reference. The yardstick's own copy of the
mathematics, written from the equations, **every published scalar where the
released code puts it** (the program folds several of them elsewhere), so a
change to the program's model code cannot move the reference with it.

The stream starts at ``embedding_multiplier * E[id]``. Every block:

    n = RMSNorm_1(h)
    h = h + ssm_out_multiplier Mamba2(ssm_in_multiplier n)
          + attention_out_multiplier Attention(attention_in_multiplier n)
    m = RMSNorm_2(h)
    h = h + mlp_multipliers[1] W_down(W_up m * SiLU(mlp_multipliers[0] W_gate m))

- **Mamba-2** (``u`` its input): ``[z | x | B | C | dt] = (W_in u) * mup``,
  ``mup`` the five ``ssm_multipliers``, one a segment (``z`` and ``x`` of
  ``mamba_d_ssm`` channels, ``B`` and ``C`` of ``mamba_n_groups *
  mamba_d_state``, ``dt`` a head); ``xBC = SiLU(Conv(x | B | C) + b)``
  (causal, depthwise, ``mamba_d_conv`` taps); ``dt_t = softplus(dt_t +
  dt_bias)``, ``A = -exp(A_log)``, one number a head each; per head, **token
  by token**, ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t
  C_t + D x_t`` (head ``h`` reads group ``h // (heads / groups)``); the gate
  first and the norm after it (``mamba_norm_before_gate`` false),
  ``RMSNorm(y * SiLU(z))`` over each group's channels with one learned scale
  a channel; ``W_out``.
- **Attention** (``u`` its input): ``q = W_q u`` in ``num_attention_heads``
  heads, ``k = key_multiplier W_k u`` and ``v = W_v u`` in
  ``num_key_value_heads``, of ``head_dim`` channels; q and k turned by
  rotary position code over all channels of a head, ``x cos + rotate_half(x)
  sin`` with ``rotate_half(x) = (-x_2, x_1)`` of a head's two halves and the
  angle of channel ``i`` and ``i + head_dim / 2`` at position ``t`` ``t *
  rope_theta^(-2i / head_dim)``; query head ``i`` reads key head ``i //
  (heads / key heads)``; causal ``softmax(q k^T / sqrt(head_dim)) v``, the
  full masked softmax a block of queries at a time; ``W_o``. No bias, no
  head norm.

After the last block ``RMSNorm(h)`` at the window's last position, ``logits =
lm_head_multiplier (norm W_head)``. Output: the softmax of the last
position's logits, as the engine serves it.

**The cut.** The parameter tree says how many blocks this chip holds
(``held.layers`` of the published, all alike).

**Parameters in the served type.** The program's initialiser hands its leaves
over in bfloat16, as a checkpoint would; each is brought to float32 where it
is used. Rows of the batch one at a time (``lax.map``), attention a block of
queries at a time against every key, the feed-forward a block of tokens at a
time, the head a block of the vocabulary at a time. None of that changes a
number.
"""

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
QUERY_BLOCK = 256
TOKEN_BLOCK = 2048
VOCAB_BLOCK = 16320


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _rmsnorm(p, x, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"].astype(F32)


def _blocks(fn, x, block):
    """``fn`` over ``x``'s rows ``block`` at a time (all at once where they
    are no whole blocks)."""
    s = x.shape[0]
    if s % block:
        return fn(x)
    out = lax.map(fn, x.reshape(s // block, block, *x.shape[1:]))
    return out.reshape(s, *out.shape[2:])


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
    of_z, of_x, of_b, of_c, of_dt = sizes["ssm_multipliers"]
    mup = jnp.concatenate([
        jnp.full((width,), m, F32) for width, m in (
            (inner, of_z), (inner, of_x), (gn, of_b), (gn, of_c),
            (heads, of_dt))])
    zxbcdt = (u @ p["in_proj"]) * mup
    z = zxbcdt[:, :inner]
    xbc = jax.nn.silu(_conv(p["conv"], zxbcdt[:, inner:2 * inner + 2 * gn]))
    dt = jax.nn.softplus(zxbcdt[:, 2 * inner + 2 * gn:] + p["dt_bias"])
    x = xbc[:, :inner].reshape(s, heads, hd)
    b = xbc[:, inner:inner + gn].reshape(s, groups, n)
    c = xbc[:, inner + gn:].reshape(s, groups, n)
    a = -jnp.exp(p["a_log"])

    def token(state, xs):  # state (H, P, N)
        x_t, dt_t, b_t, c_t = xs
        # each head reads its group's B and C
        b_t, c_t = (jnp.repeat(y, heads // groups, axis=0)
                    for y in (b_t, c_t))
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    _, y = lax.scan(token, jnp.zeros((heads, hd, n), F32), (x, dt, b, c))
    y = (y + p["d"][:, None] * x).reshape(s, inner) * jax.nn.silu(z)
    # the gate first, then the norm over each group's channels
    g = y.reshape(s, groups, inner // groups)
    g = g * lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    return (g.reshape(s, inner) * p["norm"]["scale"]) @ p["out_proj"]


def _rotate(x, cos, sin):
    """``x (S, H, D)`` turned: ``x cos + rotate_half(x) sin``, the tables
    ``(S, D)`` with each frequency twice, a half each."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos[:, None, :] + turned * sin[:, None, :]


def _attention(p, u, sizes):
    """One row (S, D) through rotary grouped-query attention, a block of
    queries at a time against every key, the later ones masked; each key
    head written out for the query heads that read it."""
    p = _f32(p)
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = sizes["head_dim"]
    s = u.shape[0]
    freq = jnp.asarray(
        [float(sizes["rope_theta"]) ** (-2.0 * i / hd)
         for i in range(hd // 2)], F32)
    angle = jnp.arange(s, dtype=F32)[:, None] * freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)
    q = _rotate((u @ p["q"]).reshape(s, heads, hd), cos, sin)
    k = _rotate((sizes["key_multiplier"] * (u @ p["k"])).reshape(s, kv, hd),
                cos, sin)
    k = jnp.repeat(k, heads // kv, axis=1)
    v = jnp.repeat((u @ p["v"]).reshape(s, kv, hd), heads // kv, axis=1)
    at = jnp.arange(s)

    def queries(qt):  # (a block of queries, their positions)
        q_b, t_b = qt
        scores = jnp.einsum("shd,thd->hst", q_b, k) * hd ** -0.5
        later = at[None, :] > t_b[:, None]
        probs = jax.nn.softmax(jnp.where(later, -jnp.inf, scores), -1)
        return jnp.einsum("hst,thd->shd", probs, v)

    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    out = lax.map(queries, (q.reshape(s // block, block, heads, hd),
                            at.reshape(s // block, block)))
    return out.reshape(s, heads * hd) @ p["o"]


def _feed_forward(p, m, sizes):
    p = _f32(p)
    of_gate, of_down = sizes["mlp_multipliers"]

    def tokens(x):
        return ((x @ p["up"]) * jax.nn.silu(of_gate * (x @ p["gate"]))
                ) @ p["down"]

    return of_down * _blocks(tokens, m, TOKEN_BLOCK)


def forward(sizes: dict, params, state, x):
    """Next-token probabilities, ``(B, vocabulary)``, for windows of token
    ids ``(B, S)`` (as floats: the instance contract carries them so)."""
    eps = sizes["rms_norm_eps"]
    blocks = params["layers"]
    held = sizes.get("held", {}).get("layers",
                                     range(sizes["num_hidden_layers"]))
    if len(held) != len(blocks):
        raise ValueError("the program's model has another depth than the "
                         "configuration file")
    vocab = params["embed"].shape[0]
    ids = jnp.clip(jnp.round(x), 0, vocab - 1).astype(jnp.int32)

    def row(ids_row):
        h = sizes["embedding_multiplier"] * params["embed"][ids_row].astype(
            F32)
        for blk in blocks:
            n = _rmsnorm(blk["norm1"], h, eps)
            h = h + sizes["ssm_out_multiplier"] * _mamba(
                blk["mixer"]["mamba"], sizes["ssm_in_multiplier"] * n, sizes,
                eps) + sizes["attention_out_multiplier"] * _attention(
                blk["mixer"]["attention"],
                sizes["attention_in_multiplier"] * n, sizes)
            h = h + _feed_forward(blk["ffn"], _rmsnorm(blk["norm2"], h, eps),
                                  sizes)
        return _rmsnorm(params["norm"], h[-1], eps)

    last = lax.map(row, ids)
    # the head a block of the vocabulary's columns at a time: in float32 it
    # is 5.3 GB at the published sizes
    width = VOCAB_BLOCK if vocab % VOCAB_BLOCK == 0 else vocab
    logits = lax.map(
        lambda lo: last @ lax.dynamic_slice_in_dim(
            params["head"], lo, width, 1).astype(F32),
        jnp.arange(0, vocab, width))
    logits = sizes["lm_head_multiplier"] * jnp.moveaxis(logits, 0, 1).reshape(
        len(ids), vocab)
    return jax.nn.softmax(logits, axis=-1)
