"""Plain forward of Ouro (``model_type`` ``ouro``: the released
``config.json``; Zhu et al. 2025, "Scaling Latent Reasoning via Looped
Language Models"; rotary position code of Su et al. 2021; for what the config
does not state, the readings the configuration's ``assumed`` lists), float32,
``jax.numpy`` only: no kernel, no loop primitive over passes or layers,
nothing imported from the program or from another reference. The yardstick's
own copy of the mathematics, written from the equations, so a change to the
program's model code cannot move the reference with it.

``N(x; g) = g x / sqrt(mean(x^2) + eps)`` over the channels.

    h = E[ids]
    for t in 1..total_ut_steps:                 the same weights every pass
      for l in 1..num_hidden_layers:
        a = N(h; g1_l)
        q, k, v = a W_q, a W_k, a W_v           heads of head_dim, no bias
        q, k = turn(q), turn(k)                 x cos + rotate_half(x) sin,
                                                rotate_half(x) = (-x_2, x_1)
                                                of a head's halves, the angle
                                                of channels i and i + D/2 at
                                                position p: p theta^(-2i/D)
        o = softmax_causal(q k^T / sqrt(D)) v   a head reads its own keys
        h = h + N(merge(o) W_o; g2_l)           the sandwich
        m = N(h; g3_l)
        h = h + N(W_down(SiLU(W_gate m) * W_up m); g4_l)
      h = N(h; g_last)                          the whole stream: the next
                                                pass starts from it
      z_t = h[S-1];  lambda_t = sigmoid(w_exit . z_t + b_exit)
    p_t = lambda_t prod_{j<t}(1 - lambda_j) for t < T,  p_T the rest
    tau = the first t with p_1 + ... + p_t >= early_exit_threshold, else T
    answer = softmax(W_head z_tau)

At the published threshold of 1 no sum before the last reaches it (a sigmoid
is under 1): the reference takes ``tau = T`` there by the rule's own words,
not by a float32 sum that a saturated sigmoid could round up to 1.

**Parameters in the served type.** The program's initialiser hands its leaves
over in bfloat16, as a checkpoint would; each is brought to float32 where it
is used. Rows of the batch one at a time (``lax.map``), attention a block of
queries at a time against every key, the feed-forward a block of tokens at a
time. None of that changes a number.
"""

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
QUERY_BLOCK = 256
TOKEN_BLOCK = 2048


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _norm(p, x, eps):
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


def _rotate(x, cos, sin):
    """``x (S, H, D)`` turned: ``x cos + rotate_half(x) sin``, the tables
    ``(S, D)`` with each frequency twice, a half each."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos[:, None, :] + turned * sin[:, None, :]


def _attention(p, u, sizes):
    """One row (S, D) through rotary attention, a block of queries at a time
    against every key, the later ones masked."""
    p = _f32(p)
    heads, hd = sizes["num_attention_heads"], sizes["head_dim"]
    if sizes["num_key_value_heads"] != heads:
        raise ValueError("this reference reads a key head a query head")
    s = u.shape[0]
    freq = jnp.asarray(
        [float(sizes["rope_theta"]) ** (-2.0 * i / hd)
         for i in range(hd // 2)], F32)
    angle = jnp.arange(s, dtype=F32)[:, None] * freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)
    q = _rotate((u @ p["q"]).reshape(s, heads, hd), cos, sin)
    k = _rotate((u @ p["k"]).reshape(s, heads, hd), cos, sin)
    v = (u @ p["v"]).reshape(s, heads, hd)
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


def _feed_forward(p, m):
    p = _f32(p)

    def tokens(x):
        return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]

    return _blocks(tokens, m, TOKEN_BLOCK)


def exits(sizes: dict, params, x):
    """``(probabilities (B, vocabulary), tau (B,), z (T, B, D))``: the
    answers, the pass (1..T) each record's is read from, and every pass's
    normed last position."""
    eps = sizes["rms_norm_eps"]
    passes = int(sizes["total_ut_steps"])
    threshold = float(sizes["early_exit_threshold"])
    blocks = params["layers"]
    if len(blocks) != sizes["num_hidden_layers"]:
        raise ValueError("the program's model has another depth than the "
                         "configuration file")
    vocab = params["embed"].shape[0]
    ids = jnp.clip(jnp.round(x), 0, vocab - 1).astype(jnp.int32)

    def row(ids_row):
        h = params["embed"][ids_row].astype(F32)
        lasts = []
        for _ in range(passes):
            for blk in blocks:
                a = _norm(blk["norm1"], h, eps)
                h = h + _norm(blk["post1"],
                              _attention(blk["mixer"], a, sizes), eps)
                m = _norm(blk["norm2"], h, eps)
                h = h + _norm(blk["post2"], _feed_forward(blk["ffn"], m), eps)
            h = _norm(params["norm"], h, eps)
            lasts.append(h[-1])
        return jnp.stack(lasts)

    z = jnp.moveaxis(lax.map(row, ids), 0, 1)  # (T, B, D)
    gate = jax.nn.sigmoid(z @ params["exit"]["w"].astype(F32)
                          + params["exit"]["b"].astype(F32))  # (T, B)
    tau = jnp.full((len(ids),), passes, jnp.int32)
    if threshold < 1:
        # from the last pass but one down, so that the first to reach it wins
        for t in range(passes - 1, 0, -1):
            so_far, stay = 0.0, 1.0
            for j in range(t):  # p_1 + ... + p_t
                so_far = so_far + gate[j] * stay
                stay = stay * (1.0 - gate[j])
            tau = jnp.where(so_far >= threshold, t, tau)
    last = jnp.take_along_axis(z, (tau - 1)[None, :, None], axis=0)[0]
    logits = last @ params["head"].astype(F32)
    return jax.nn.softmax(logits, axis=-1), tau, z


def forward(sizes: dict, params, state, x):
    """Next-token probabilities, ``(B, vocabulary)``, for windows of token
    ids ``(B, S)`` (as floats: the instance contract carries them so)."""
    return exits(sizes, params, x)[0]
