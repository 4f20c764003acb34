"""LFM2 (``model_type`` ``lfm2_moe``) as a scorer of token records: a window
of token ids in, the next-token distribution at its last position out,
through the same engine and topology as every other model.

The stream starts at ``E[id]``. Every block is an operator and a
feed-forward, each behind its own RMS norm:

    h = h + operator(RMSNorm_op(h));    h = h + feed_forward(RMSNorm_ffn(h))

- The operator is a **gated short convolution** where ``layer_types`` says
  ``conv`` (three in four at the published sizes): ``[b | c | u] = W_in n``,
  three ranges of ``hidden`` columns; ``c_t * Conv(b * u)_t``, the
  convolution causal and depthwise over ``conv_L_cache`` taps, no bias, no
  activation; ``W_out``. The product, the taps and the second gate are one
  pass over the projection's result where it lies (ops/kda.py
  ``gated_conv``: the part ``mix.gated_conv``).
- Where it says ``full_attention``: causal softmax attention with grouped
  queries (``heads`` over ``kv_heads``), an RMS norm over each query and key
  head and **then** the plain rotary turn of all of a head's channels, pairs
  ``(i, i + head_dim / 2)``; no gate, no bias. q, k, v and the result stay
  ``(B, S, H * head_dim)`` from the projections to the output projection:
  the norm and the turn are one pass over q and over k where they lie
  (ops/rope.py ``norm_turn_merged``) and the kernel reads heads as blocks of
  lanes (ops/attention.py ``causal_attention_merged``); at the published
  head of 64 two heads are one lane tile to both.
- The feed-forward is SwiGLU in the ``dense`` leading layers (under its own
  part ``ffn``, a row of the batch at a time) and, after them, the dropless
  sigmoid top-k expert layer with a selection bias and **no** shared expert
  (:func:`storm_tpu.parallel.moe.topk_moe_layer`): the ``top_k`` largest of
  score + bias, weighted by the score over the chosen scores' sum plus the
  published ``1e-6``.

The embedding is the head (:func:`storm_tpu.models.scorer.token_scorer` with
``tied``): ``logits = RMSNorm(h_L) E^T``.

**The cut** is in depth alone: one pipeline stage's layers, each whole. The
builder is told which layers (their ``layer_types`` and how many leading
ones are dense); every width, every head, *every routed expert* of a layer
(``experts_held`` is the router's width: no assignment is absent) and the
whole vocabulary are here.

What the published ``config.json`` does not fix is the released modelling
code's and listed under ``assumed`` in the benchmark's configuration file:
the head's width, the order of the projection's three ranges, that the
convolution has no activation, the head norms before the turn, the
``1e-6``, the bias in the choice alone, the tied head, where the weights
start.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from storm_tpu.models import scorer as S
from storm_tpu.models.falcon_h1 import gated_ffn
from storm_tpu.models.minicpm_sala import _rows
from storm_tpu.models.nemotron_h import gqa_mixer_init
from storm_tpu.models.registry import ModelDef, register
from storm_tpu.models.scorer import _proj, _w
from storm_tpu.ops import layers as L
from storm_tpu.ops import parts as P
from storm_tpu.ops import rope as R
from storm_tpu.ops.attention import causal_attention_merged
from storm_tpu.ops.kda import gated_conv, short_conv_init
from storm_tpu.parallel.moe import topk_moe_init

KINDS = ("conv", "full_attention")


def conv_mixer_init(rng, dim: int, taps: int) -> dict:
    ki, kc, ko = jax.random.split(rng, 3)
    return {"in": _w(ki, dim, 3 * dim), "conv": short_conv_init(kc, dim, taps),
            "out": _w(ko, dim, dim)}


def conv_mixer(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    """The gated short convolution between its two projections."""
    wide = _proj(x, p["in"])  # [b | c | u]
    with jax.named_scope(P.MIX_GATED_CONV):
        y = gated_conv(p["conv"], wide)
    return _proj(y, p["out"])


def attention_mixer_init(rng, dim: int, heads: int, kv_heads: int,
                         head_dim: int) -> dict:
    return {**gqa_mixer_init(rng, dim, heads, kv_heads, head_dim),
            "q_norm": L.rmsnorm_init(head_dim),
            "k_norm": L.rmsnorm_init(head_dim)}


def attention_mixer(p: dict, x: jnp.ndarray, heads: int, kv_heads: int,
                    head_dim: int, eps: float, rotary: tuple,
                    block: int = 512) -> jnp.ndarray:
    """Grouped causal attention, each head of q and of k normed and then
    turned by ``rotary``'s tables ``(S, head_dim / 2)`` where it lies in its
    projection; no gate, no bias."""
    q = R.norm_turn_merged(p["q_norm"], _proj(x, p["q"]), heads, eps, rotary)
    k = R.norm_turn_merged(p["k_norm"], _proj(x, p["k"]), kv_heads, eps,
                           rotary)
    out = causal_attention_merged(q, k, _proj(x, p["v"]), heads, kv_heads,
                                  scale=head_dim ** -0.5, block=block)
    return _proj(out, p["o"])


def build_lfm2(
    name: str,
    num_classes: int,
    input_shape: tuple,
    *,
    layer_types: tuple,
    dense: int,
    published_layers: int,
    dim: int,
    heads: int,
    kv_heads: int,
    head_dim: int,
    taps: int,
    dense_width: int,
    expert_width: int,
    n_experts: int,
    top_k: int,
    experts_held: int,
    first_expert: int = 0,
    route_scale: float = 1.0,
    rope_theta: float = 1e6,
    eps: float = 1e-5,
    expert_tile: Optional[int] = None,
    attention_block: int = 512,
    max_rows: int = 8,
    param_dtype=jnp.bfloat16,
) -> ModelDef:
    """The layers that ``layer_types`` spells (the held ones of the
    published list, ``published_layers`` long; the first ``dense`` of them
    with a dense feed-forward, experts in the others) over ``num_classes``
    rows of the tied matrix."""
    if not layer_types or set(layer_types) - set(KINDS):
        raise ValueError(f"layer_types {layer_types!r}: the kinds are "
                         f"{KINDS!r}")
    # Where the weights start: every projection LeCun's, a branch's output
    # projection (W_out, W_o, a feed-forward's and every expert's down) over
    # the root of the published stack's branches, as models/kimi_linear.py's.
    # The tied matrix is LeCun's as a head, 1/sqrt(dim) a value: the logits
    # of a unit norm then have deviation 1, as every untied scorer's, and the
    # last position's logit of its own id (scorer.py ``tied``) is 1 over the
    # final stream's root mean square, about 2 after these ten layers: among
    # the others, so no window is answered one-hot at its last id.
    branch = 1.0 / math.sqrt(2 * published_layers)
    inv_freq = rope_theta ** (-2.0 * np.arange(head_dim // 2)
                              / head_dim)  # plain rotary, float64
    mixers = {
        "conv": S.Branch(
            "norm1", "mixer",
            lambda key: S.scaled(conv_mixer_init(key, dim, taps),
                                 {"out": branch}),
            lambda p, y, _: conv_mixer(p, y)),
        "full_attention": S.Branch(
            "norm1", "mixer",
            lambda key: S.scaled(attention_mixer_init(
                key, dim, heads, kv_heads, head_dim), {"o": branch}),
            lambda p, y, rotary: attention_mixer(
                p, y, heads, kv_heads, head_dim, eps, rotary,
                attention_block)),
    }
    ffn = S.Branch(
        "norm2", "ffn",
        lambda key: S.scaled(L.swiglu_init(key, dim, dense_width),
                             {"down": branch}),
        lambda p, y, _: _rows(lambda row: gated_ffn(p, row, 1.0), y),
        scope=P.FFN)
    # An expert's down over four more. Every expert is held and the router
    # takes four a token on sigmoids that lie 0.02 apart, so a bfloat16
    # rounding sends one token in six to another expert in each layer, and at
    # a branch's full scale one flip at a window's last token moved its row
    # 0.07-0.1 of its length where float8 reads 0.42 (PERF.md section 6, PR
    # 68; models/trinity.py met the same and moved its post-norms' scales).
    # The selection bias N(0, 0.01^2), as models/kimi_k2.py's: at
    # topk_moe_init's 0.05 the busiest expert took 2.9 times the mean.
    experts = S.experts(
        "norm2", "ffn",
        lambda key: S.scaled(topk_moe_init(
            key, dim, expert_width, n_experts, experts_held, shared=False),
            {"down": branch / 4, "router_bias": 0.2}),
        held=experts_held, top_k=top_k, first_expert=first_expert,
        scale=route_scale, tile=expert_tile, eps=1e-6)
    return S.token_scorer(
        name, num_classes, input_shape,
        tuple((mixers[kind], ffn if i < dense else experts)
              for i, kind in enumerate(layer_types)),
        dim=dim, eps=eps, max_rows=max_rows, tied=True,
        embed_std=1.0 / math.sqrt(dim),
        context=lambda seq: R.rotary_tables(seq, inv_freq),
        param_dtype=param_dtype,
        hyper={"layer_types": tuple(layer_types), "dense": dense, "dim": dim,
               "heads": heads, "kv_heads": kv_heads, "head_dim": head_dim,
               "taps": taps, "n_experts": n_experts, "top_k": top_k,
               "experts_held": experts_held, "first_expert": first_expert,
               "rope_theta": rope_theta})


@register("lfm2_24b_a2b")
def build_lfm2_24b_a2b(num_classes: int = 65536,
                       input_shape: tuple = (4096,)) -> ModelDef:
    """LFM2-24B-A2B at its published widths, as one pipeline stage of four
    holds its layers, each whole: layers 0-9 of 40 (``conv conv | attn conv
    conv conv | attn conv conv conv``: both dense layers and two whole
    periods, all 64 routed experts in each of the eight), the whole tied
    vocabulary; 5.27 B parameters here, handed over in bfloat16. The layers
    left out lie on further pipeline stages."""
    return build_lfm2(
        "lfm2_24b_a2b", num_classes, tuple(input_shape),
        layer_types=("conv", "conv")
        + ("full_attention", "conv", "conv", "conv") * 2,
        dense=2, published_layers=40, dim=2048, heads=32, kv_heads=8,
        head_dim=64, taps=3, dense_width=11776, expert_width=1536,
        n_experts=64, top_k=4, experts_held=64)


@register("lfm2_tiny")
def build_lfm2_tiny(num_classes: int = 96, input_shape: tuple = (40,),
                    param_dtype=jnp.float32) -> ModelDef:
    """The same code at toy widths, in float32: for the tests and the
    benchmark's rehearsal on the CPU. Two dense layers under a convolution,
    then a whole period; 4 query heads a key head of 8 channels; a router of
    12 columns (no power of two), top 3, all held; 40 tokens are no multiple
    of the blocked form's 16 queries."""
    return build_lfm2(
        "lfm2_tiny", num_classes, tuple(input_shape),
        layer_types=("conv", "conv", "full_attention", "conv", "conv",
                     "conv"),
        dense=2, published_layers=8, dim=64, heads=8, kv_heads=2, head_dim=8,
        taps=3, dense_width=72, expert_width=24, n_experts=12, top_k=3,
        experts_held=12, rope_theta=100.0, expert_tile=16,
        attention_block=16, max_rows=4, param_dtype=param_dtype)
