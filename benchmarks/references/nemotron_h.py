"""Plain Nemotron-H forward (``model_type`` ``nemotron_h``: NVIDIA 2025,
"Nemotron-H: a family of accurate and efficient hybrid Mamba-Transformer
models", arXiv:2504.03624; the Mamba-2 layer of Dao and Gu 2024; the released
``config.json``), float32, ``jax.numpy`` only: no kernels, no chunks, no
grouped products. The yardstick's own copy of the mathematics, so a change to
the program's model code cannot move the reference with it.

The published ``hybrid_override_pattern`` gives one letter a layer, and every
layer is ``h += mixer(RMSNorm(h))``:

- **``M``, Mamba-2**: ``[z | xBC | dt] = W_in u``; ``xBC = SiLU(Conv4(xBC) +
  b)`` (causal, depthwise), split into ``x`` (heads of ``mamba_head_dim``) and
  ``B``, ``C`` (``n_groups`` groups of ``ssm_state_size``; head ``h`` reads
  group ``h // (heads / groups)``); ``dt_t = softplus(dt_t + dt_bias)``, ``A
  = -exp(A_log)``, one number a head each; per head, **token by token**, ``S_t
  = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``; then
  the gate first and the norm after it, ``RMSNorm_groups(y * SiLU(z))`` over
  each group's channels with one learned scale a channel; ``W_out``.
- **``E``, experts**: ``s = sigmoid(W_r u)``; the ``num_experts_per_tok``
  largest of ``s + bias``; weights ``s_i`` over the sum of the chosen ``s``
  (``norm_topk_prob``), times ``routed_scaling_factor``; an expert is ``W_down
  relu(W_up u)^2``; ``sum_i w_i E_i(u) + E_shared(u)``. No token is dropped:
  every held expert is run on every token and weighted by what the router
  gave it, zero where it was not chosen.
- **``*``, attention**: ``q`` in ``num_attention_heads`` heads, ``k`` and
  ``v`` in ``num_key_value_heads``, query head ``i`` reading key head ``i //
  (heads / key heads)``; causal ``softmax(q k^T / sqrt(head_dim)) v``, the
  full masked softmax a row; ``W_o``. No position embedding is applied (the
  configuration's ``assumed.positions`` says why).

After the last layer a final RMSNorm and the untied head, at the last
position only (``num_logits_to_keep`` 1).

**The share.** The parameter tree says what this chip holds: as many layers
as it has (the first letters of the pattern), as many routed experts as are
stacked (experts ``first_expert`` on of the router's width;
``held.first_expert`` of the sizes, 0 where absent), as many rows of the
vocabulary as the embedding has. What experts held elsewhere would add is
left out, as in the program, and the logits are over the held slice. Output:
the softmax of the last position's logits, as the engine serves it.

Blocked so that 4,096 tokens fit: rows of the batch one at a time
(``lax.map``), attention a block of queries at a time against every key,
experts one at a time. None of that changes a number.
"""

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 256


def _rmsnorm(p, x, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * p["scale"]


def _relu2(p, x):
    return jnp.square(jax.nn.relu(x @ p["up"])) @ p["down"]


def _conv(p, x):
    """Causal depthwise convolution over (S, C) with a bias: the last tap is
    the current token's, tokens before the first are zero."""
    w = p["w"]
    width, s = w.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), x.dtype), x])
    return sum(w[j] * xp[j:j + s] for j in range(width)) + p["b"]


def _group_norm(p, y, groups, eps):
    """RMSNorm over each of ``groups`` equal runs of the channels of (S, C)."""
    s, c = y.shape
    g = y.reshape(s, groups, c // groups)
    g = g * lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    return g.reshape(s, c) * p["scale"]


def _mamba(p, u, sizes, eps):
    """One row (S, D) through the Mamba-2 mixer, the state read token by
    token."""
    heads, hd = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    groups, n = sizes["n_groups"], sizes["ssm_state_size"]
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

    _, y = lax.scan(token, jnp.zeros((heads, hd, n), u.dtype), (x, dt, b, c))
    y = (y + p["d"][:, None] * x).reshape(s, inner)
    return _group_norm(p["norm"], y * jax.nn.silu(z), groups, eps) \
        @ p["out_proj"]


def _attention(p, u, sizes):
    """One row (S, D) through grouped-query attention, a block of queries at
    a time against every key, the later ones masked; each key head written
    out for the query heads that read it."""
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = sizes["head_dim"]
    s = u.shape[0]
    q = (u @ p["q"]).reshape(s, heads, hd)
    k = jnp.repeat((u @ p["k"]).reshape(s, kv, hd), heads // kv, axis=1)
    v = jnp.repeat((u @ p["v"]).reshape(s, kv, hd), heads // kv, axis=1)
    outs = []
    for lo in range(0, s, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, s)
        scores = jnp.einsum("shd,thd->hst", q[lo:hi], k) * hd ** -0.5
        later = jnp.arange(s)[None, :] > jnp.arange(lo, hi)[:, None]
        probs = jax.nn.softmax(jnp.where(later, -jnp.inf, scores), -1)
        outs.append(jnp.einsum("hst,thd->shd", probs, v))
    return jnp.concatenate(outs).reshape(s, heads * hd) @ p["o"]


def _experts(p, u, sizes):
    """One row (S, D) through the expert layer: the held experts' part of
    the routed sum, and the shared expert."""
    top_k = sizes["num_experts_per_tok"]
    first = sizes.get("held", {}).get("first_expert", 0)
    score = jax.nn.sigmoid(u @ p["router"])
    _, chosen = lax.top_k(score + p["router_bias"], top_k)
    weight = jnp.take_along_axis(score, chosen, -1)
    if sizes["norm_topk_prob"]:
        weight = weight / jnp.sum(weight, -1, keepdims=True)
    weight = weight * sizes["routed_scaling_factor"]

    def one(e, w):
        gain = jnp.sum(jnp.where(chosen == e + first, weight, 0.0), -1)
        return gain[:, None] * _relu2(w, u)

    held = p["experts"]["down"].shape[0]
    routed, _ = lax.scan(lambda acc, ew: (acc + one(*ew), None),
                         jnp.zeros_like(u), (jnp.arange(held), p["experts"]))
    return routed + _relu2(p["shared"], u)


def forward(sizes: dict, params, state, x):
    """Next-token probabilities over the held slice, ``(B, vocabulary
    held)``, for windows of token ids ``(B, S)`` (as floats: the instance
    contract carries them so)."""
    eps = sizes["layer_norm_epsilon"]
    layers = params["layers"]
    held = sizes.get("held", {})
    if "num_hidden_layers" in held and len(layers) != held["num_hidden_layers"]:
        raise ValueError("the program's model has another depth than the "
                         "configuration file")
    pattern = sizes["hybrid_override_pattern"][:len(layers)]
    vocab = params["embed"].shape[0]
    ids = jnp.clip(jnp.round(x), 0, vocab - 1).astype(jnp.int32)

    def row(ids_row):
        h = params["embed"][ids_row]
        for kind, blk in zip(pattern, layers):
            u = _rmsnorm(blk["norm"], h, eps)
            if kind == "M":
                h = h + _mamba(blk["mixer"], u, sizes, eps)
            elif kind == "E":
                h = h + _experts(blk["mixer"], u, sizes)
            elif kind == "*":
                h = h + _attention(blk["mixer"], u, sizes)
            else:
                raise ValueError(f"layer {kind!r} is of no published kind")
        return _rmsnorm(params["norm"], h[-1], eps) @ params["head"]

    return jax.nn.softmax(lax.map(row, ids), axis=-1)
