"""Kimi-Linear (``model_type`` ``kimi_linear``) as a scorer of token records:
a window of token ids in, the next-token distribution at its last position
out, through the same engine and topology as every other model.

Every block is ``x += mixer(RMSNorm(x)); x += ffn(RMSNorm(x))``. Three mixers
in four are Kimi Delta Attention (:mod:`storm_tpu.ops.kda`: a gated
delta-rule state per head, computed in chunks), the fourth is multi-head
latent attention with no rotary embedding (``mla_use_nope``), causal,
query/key heads of 128 + 64 against value heads of 128
(:func:`storm_tpu.ops.attention.causal_attention`). The first
``first_dense`` blocks have a dense SwiGLU; every later one the dropless
top-k expert layer with a shared expert
(:func:`storm_tpu.parallel.moe.topk_moe_layer`).

**One chip's share.** The builder is told how many routed experts and how
many rows of the vocabulary this chip holds (``experts_held`` from
``first_expert``, ``num_classes`` rows of embedding and of head): what one of
the chips that share each layer holds under expert parallelism. The router
keeps its published width and its experts per token; what the experts held
elsewhere would add is left out, and that partial result goes on to the next
layer. Ids are taken from the held slice and the distribution is over it.

The step's auxiliaries ride ``new_state["aux"]``: per expert layer the tokens
routed to each held expert and the assignments that fell on absent ones. The
engine fetches them with the predictions (``infer/engine.py``) and
``parallel/moe.py observe_expert_counts`` reads them into the registry. The
skeleton (ids, the float32 stream, the head, the counters' way out) is
:func:`storm_tpu.models.scorer.token_scorer`'s; this file holds the mixers
and the plan.

What the published ``config.json`` does not fix is set as the released code
sets it and listed under ``assumed`` in the benchmark's configuration file:
the decay's parametrisation (``g = -exp(A_log) * softplus(W_up W_down x +
dt_bias)``, rank ``head_dim``), the output gate's rank, the initialisers.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from storm_tpu.models import scorer as S
from storm_tpu.models.registry import ModelDef, register
from storm_tpu.models.scorer import _proj, _w
from storm_tpu.ops import kda
from storm_tpu.ops import layers as L
from storm_tpu.ops import parts as P
from storm_tpu.ops.attention import causal_attention
from storm_tpu.ops.rope import rotate_halves
from storm_tpu.parallel.moe import topk_moe_init


def kda_mixer_init(rng, dim: int, heads: int, head_dim: int,
                   conv: int) -> dict:
    wide = heads * head_dim
    ks = jax.random.split(rng, 14)
    # the decay as the released code starts it: A in [1, 16], a step of
    # 0.001-0.1 through the softplus
    step = jnp.exp(jax.random.uniform(ks[12], (wide,), jnp.float32,
                                      math.log(1e-3), math.log(1e-1)))
    return {
        "q": _w(ks[0], dim, wide), "k": _w(ks[1], dim, wide),
        "v": _w(ks[2], dim, wide),
        "conv_q": kda.short_conv_init(ks[3], wide, conv),
        "conv_k": kda.short_conv_init(ks[4], wide, conv),
        "conv_v": kda.short_conv_init(ks[5], wide, conv),
        "f_down": _w(ks[6], dim, head_dim), "f_up": _w(ks[7], head_dim, wide),
        "a_log": jnp.log(jax.random.uniform(ks[8], (heads,), jnp.float32,
                                            1.0, 16.0)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "beta": _w(ks[9], dim, heads),
        "g_down": _w(ks[10], dim, head_dim),
        "g_up": _w(ks[11], head_dim, wide),
        "o_norm": L.rmsnorm_init(head_dim),
        "o": _w(ks[13], wide, dim),
    }


def kda_mixer(p: dict, x: jnp.ndarray, heads: int, head_dim: int,
              chunk: int, eps: float,
              step_range: float = 1.0) -> jnp.ndarray:
    """Between the input projections and the output's, every branch is
    ``(B, S, heads * head_dim)``, a head a block of lanes: the form ``_proj``
    yields and ``ops/kda.py``'s kernel reads. Only a norm's statistic is a
    number a head. ``step_range``: the delta rule's step is ``step_range *
    sigmoid(.)``: 1 (Kimi-Linear: a transition only shrinks along ``k``) or 2
    (``kda_allow_neg_eigval``: past 1 it reflects)."""
    f32 = jnp.float32

    def branch(name):
        return kda.conv_silu(p["conv_" + name],
                             _proj(x, p[name]))

    q = kda.l2norm_heads(branch("q"), heads) * (head_dim ** -0.5)
    k = kda.l2norm_heads(branch("k"), heads)
    v = branch("v")
    f = _proj(x, p["f_down"], p["f_up"]).astype(f32)
    g = -jnp.repeat(jnp.exp(p["a_log"].astype(f32)), head_dim) \
        * jax.nn.softplus(f + p["dt_bias"].astype(f32))
    beta = jax.nn.sigmoid(_proj(x, p["beta"]).astype(f32))
    if step_range != 1:
        beta = step_range * beta
    o = kda.kda_chunked(q.astype(x.dtype), k, v, g, beta, heads, chunk=chunk,
                        out=lambda o: L.rmsnorm(p["o_norm"], o, eps))
    gate = jax.nn.sigmoid(_proj(x, p["g_down"], p["g_up"]))
    return _proj(o * gate, p["o"])


def mla_mixer_init(rng, dim: int, heads: int, nope: int, rope: int,
                   v_dim: int, kv_rank: int, q_rank=None) -> dict:
    """``q_rank`` None: one full query projection ``q``; a number: the
    low-rank pair ``q_a`` (to ``q_rank``), an RMS norm, ``q_b``."""
    ks = jax.random.split(rng, 4)  # as ever: the full-rank draw is unmoved
    p = {
        "kv_a": _w(ks[1], dim, kv_rank + rope),
        "kv_norm": L.rmsnorm_init(kv_rank),
        "kv_b": _w(ks[2], kv_rank, heads * (nope + v_dim)),
        "o": _w(ks[3], heads * v_dim, dim),
    }
    if q_rank is None:
        p["q"] = _w(ks[0], dim, heads * (nope + rope))
    else:
        ka, kb = jax.random.split(ks[0])
        p["q_a"] = _w(ka, dim, q_rank)
        p["q_norm"] = L.rmsnorm_init(q_rank)
        p["q_b"] = _w(kb, q_rank, heads * (nope + rope))
    return p


def mla_mixer(p: dict, x: jnp.ndarray, heads: int, nope: int, rope: int,
              v_dim: int, kv_rank: int, eps: float, rotary=None,
              scale=None) -> jnp.ndarray:
    """Latent attention. The queries are one projection (``q``) or low-rank
    (``q_a``, an RMS norm, ``q_b``), as the parameters say. ``rotary`` None:
    the ``rope`` channels are plain query/key channels, the key's shared by
    every head. ``rotary = (cos, sin)`` (ops/rope.py ``rotary_tables``):
    each query head's ``rope`` channels and the one shared key's are turned
    by position, as halves: the loader has brought the checkpoint's
    interleaved pairs there on the weights' columns (``halves_first``).
    ``scale``: the softmax's (None: ``(nope + rope) ** -0.5``)."""
    b, s, _ = x.shape
    if "q_a" in p:
        q = _proj(L.rmsnorm(p["q_norm"], _proj(x, p["q_a"]), eps), p["q_b"])
    else:
        q = _proj(x, p["q"])
    q = q.reshape(b, s, heads, nope + rope)
    kv_a = _proj(x, p["kv_a"])
    latent = L.rmsnorm(p["kv_norm"], kv_a[..., :kv_rank], eps)
    k_shared = kv_a[..., kv_rank:]  # (B, S, rope)
    if rotary is not None:
        cos, sin = rotary  # (S, rope / 2)
        q = rotate_halves(q, cos[:, None], sin[:, None], first=nope)
        k_shared = rotate_halves(k_shared, cos, sin)
    kv = _proj(latent, p["kv_b"]).reshape(b, s, heads, nope + v_dim)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_shared[:, :, None, :], (b, s, heads, rope))], -1)
    out = causal_attention(*(y.transpose(0, 2, 1, 3)
                             for y in (q, k, kv[..., nope:])),
                           scale=(nope + rope) ** -0.5 if scale is None
                           else scale)
    return _proj(out.transpose(0, 2, 1, 3).reshape(b, s, heads * v_dim),
                 p["o"])


def build_kimi_linear(
    name: str,
    num_classes: int,
    input_shape: tuple,
    *,
    dim: int,
    layers: int,
    kda_heads: int,
    kda_head_dim: int,
    conv: int,
    mla_heads: int,
    nope: int,
    rope: int,
    v_dim: int,
    kv_rank: int,
    dense_width: int,
    expert_width: int,
    n_experts: int,
    top_k: int,
    experts_held: int,
    first_expert: int = 0,
    first_dense: int = 1,
    full_attention_every: int = 4,
    routed_scale: float = 2.446,
    eps: float = 1e-5,
    chunk: int = 64,
    expert_tile: Optional[int] = None,
    max_rows: int = 8,
    published_layers: int = 27,
) -> ModelDef:
    """Layers ``1..layers`` of the published stack (layer ``i`` is latent
    attention where ``i`` is a multiple of ``full_attention_every``, KDA
    otherwise; dense feed-forward up to ``first_dense``, experts after) over
    ``num_classes`` rows of the vocabulary."""
    # Every residual branch's output projection starts smaller by the root of
    # the number of branches in the whole published stack (two a layer), as
    # GPT-2 and Megatron start a deep stack: the stream then keeps the scale
    # of the embedding whatever the depth, and no one branch (nor one expert
    # a rounding sent a token to) outweighs it.
    branch = (2 * published_layers) ** -0.5
    kda_branch = S.Branch(
        "norm1", "mixer",
        lambda key: S.scaled(kda_mixer_init(
            key, dim, kda_heads, kda_head_dim, conv), {"o": branch}),
        lambda p, y, _: kda_mixer(p, y, kda_heads, kda_head_dim, chunk, eps))
    mla_branch = S.Branch(
        "norm1", "mixer",
        lambda key: S.scaled(mla_mixer_init(
            key, dim, mla_heads, nope, rope, v_dim, kv_rank), {"o": branch}),
        lambda p, y, _: mla_mixer(p, y, mla_heads, nope, rope, v_dim,
                                  kv_rank, eps))
    dense = S.Branch(
        "norm2", "ffn",
        lambda key: S.scaled(L.swiglu_init(key, dim, dense_width),
                             {"down": branch}),
        lambda p, y, _: L.swiglu(p, y), scope=P.PROJ, cast="scope")
    experts = S.experts(
        "norm2", "ffn",
        lambda key: S.scaled(topk_moe_init(
            key, dim, expert_width, n_experts, experts_held),
            {"down": branch}),
        held=experts_held, top_k=top_k, first_expert=first_expert,
        scale=routed_scale, tile=expert_tile)
    return S.token_scorer(
        name, num_classes, input_shape,
        tuple((mla_branch if i % full_attention_every == 0 else kda_branch,
               dense if i <= first_dense else experts)
              for i in range(1, layers + 1)),  # layers count from 1
        dim=dim, eps=eps, max_rows=max_rows,
        hyper={"dim": dim, "layers": layers, "kda_heads": kda_heads,
               "kda_head_dim": kda_head_dim, "mla_heads": mla_heads,
               "n_experts": n_experts, "top_k": top_k,
               "experts_held": experts_held, "first_expert": first_expert,
               "chunk": chunk})


@register("kimi_linear_48b")
def build_kimi_linear_48b(num_classes: int = 20480,
                          input_shape: tuple = (4096,)) -> ModelDef:
    """Kimi-Linear-48B-A3B at its published widths, as one chip of the eight
    that share each layer holds it: layers 1-5 (dense, then KDA, KDA, MLA,
    KDA with experts), routed experts 0-31 of 256, an eighth of the
    vocabulary; 1.28 B parameters here. The layers left out lie on further
    pipeline stages."""
    return build_kimi_linear(
        "kimi_linear_48b", num_classes, tuple(input_shape), dim=2304,
        layers=5, kda_heads=32, kda_head_dim=128, conv=4, mla_heads=32,
        nope=128, rope=64, v_dim=128, kv_rank=512, dense_width=9216,
        expert_width=1024, n_experts=256, top_k=8, experts_held=32)


@register("kimi_linear_tiny")
def build_kimi_linear_tiny(num_classes: int = 96,
                           input_shape: tuple = (40,)) -> ModelDef:
    """The same code at toy widths, all four kinds of layer: for the tests
    and the benchmark's rehearsal on the CPU."""
    return build_kimi_linear(
        "kimi_linear_tiny", num_classes, tuple(input_shape), dim=64,
        layers=5, kda_heads=2, kda_head_dim=16, conv=4, mla_heads=2,
        nope=16, rope=8, v_dim=16, kv_rank=24, dense_width=128,
        expert_width=32, n_experts=8, top_k=2, experts_held=4, chunk=16,
        expert_tile=16, max_rows=8, published_layers=8)
