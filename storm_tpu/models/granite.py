"""Granite 4.0-H (``model_type`` ``granitemoehybrid``) as a scorer of token
records: a window of token ids in, the next-token distribution at its last
position out, through the same engine and topology as every other model.

Every block is a mixer **and** an expert layer, each behind its own RMS norm
and each added to the stream times ``residual_multiplier``:

    h = h + r mixer(RMSNorm_1(h));    h = h + r (moe(n) + shared(n)),  n = RMSNorm_2(h)

- The mixer is Mamba-2 where ``layer_types`` says ``mamba`` (nine in ten at
  the published sizes): :func:`storm_tpu.models.nemotron_h.mamba_mixer` with
  **one** group, so ``B`` and ``C`` are the same for every head, ``C B^T`` is
  formed once a chunk for all of them, and the gated norm runs over the whole
  inner width.
- Where it says ``attention``: causal attention with grouped queries, no
  position code and the published ``attention_multiplier`` on the scores in
  place of ``head_dim ** -0.5``
  (:func:`storm_tpu.models.nemotron_h.gqa_mixer`).
- The expert layer is the dropless top-k layer
  (:func:`storm_tpu.parallel.moe.topk_moe_layer`) with a softmax router, no
  selection bias, SwiGLU experts and a shared expert at a width of its own:
  the ``top_k`` largest logits, weighted by their softmax over the chosen
  (which is the softmax over the whole router renormalised over the chosen).

The embedding is the head (``tie_word_embeddings``): ``h_0 =
embedding_multiplier E[id]``, ``logits = RMSNorm(h) / logits_scaling E^T``
(:func:`storm_tpu.models.scorer.token_scorer` with ``tied``).

**One chip's share**, as ``models/nemotron_h.py`` has it: the builder is told
how many routed experts and how many rows of the tied matrix this chip holds
and which of the published ``layer_types``; the router keeps its published
width and its experts per token, and what the experts held elsewhere would
add is left out. The step's counters ride ``new_state["aux"]`` to
``parallel/moe.py observe_expert_counts``.

What the published ``config.json`` does not fix is listed under ``assumed``
in the benchmark's configuration file: the head width, which key is an
expert's width, where the weights start, the chunk and the tiles.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from storm_tpu.models import scorer as S
from storm_tpu.models.nemotron_h import (gqa_mixer, gqa_mixer_init,
                                         mamba_mixer, mamba_mixer_init)
from storm_tpu.models.registry import ModelDef, register
from storm_tpu.parallel.moe import topk_moe_init

KINDS = ("mamba", "attention")


def paired_router(p: dict) -> dict:
    """An expert layer's parameters with the router's columns in antithetic
    pairs, column ``2i + 1`` the negative of column ``2i`` (an odd width's
    last column stays as drawn). A stack of Mamba-2 layers at random weights
    gives every token's stream a common direction (SiLU's positive means
    ride through the scan), which lends each expert a standing advantage or
    handicap, ``W_e . c``: a selection bias nobody drew. A pair's two loads
    move oppositely under it, so a contiguous half of the router keeps half
    of the assignments to first order whatever the seed, as the balancing
    loss keeps a trained router's; the busiest expert is as busy as before.
    (Drawn column by column the held half of 72 took 48.4-50.8 % with the
    seed, which alone spread the benchmark's rate by 0.5-0.8 %: PERF.md
    section 6, PR 63.)"""
    router = p["router"]
    left = router[:, 0:router.shape[1] - 1:2]
    pairs = jnp.stack([left, -left], axis=-1).reshape(router.shape[0], -1)
    return {**p, "router": jnp.concatenate(
        [pairs, router[:, pairs.shape[1]:]], axis=1)}


def build_granite(
    name: str,
    num_classes: int,
    input_shape: tuple,
    *,
    layer_types: tuple,
    dim: int,
    mamba_heads: int,
    mamba_head_dim: int,
    state: int,
    conv: int,
    heads: int,
    kv_heads: int,
    head_dim: int,
    attention_multiplier: float,
    expert_width: int,
    shared_width: int,
    n_experts: int,
    top_k: int,
    experts_held: int,
    first_expert: int = 0,
    embedding_multiplier: float = 12.0,
    residual_multiplier: float = 0.22,
    logits_scaling: float = 16.0,
    embed_std: float = 1.0 / 32,
    groups: int = 1,
    eps: float = 1e-5,
    chunk: int = 128,
    expert_tile: Optional[int] = None,
    max_rows: int = 8,
    param_dtype=jnp.bfloat16,
) -> ModelDef:
    """The blocks that ``layer_types`` spells (the held ones of the published
    list, in their order) over ``num_classes`` rows of the tied matrix."""
    if not layer_types or set(layer_types) - set(KINDS):
        raise ValueError(f"layer_types {layer_types!r}: the kinds are "
                         f"{KINDS!r}")
    # Where the weights start: every projection LeCun's, the branches'
    # outputs among them. The published residual_multiplier is what keeps
    # the stream's scale through the depth (0.22^2 x 80 branches = 3.9), so
    # the draw adds no factor of its own. ``embed_std``: scorer.py says what
    # a tied matrix's scale decides.
    mixers = {
        "mamba": S.Branch(
            "norm1", "mixer",
            lambda key: mamba_mixer_init(key, dim, mamba_heads,
                                         mamba_head_dim, groups, state, conv),
            lambda p, y, _: mamba_mixer(p, y, mamba_heads, mamba_head_dim,
                                        groups, state, chunk, eps),
            cast="scope"),
        "attention": S.Branch(
            "norm1", "mixer",
            lambda key: gqa_mixer_init(key, dim, heads, kv_heads, head_dim),
            lambda p, y, _: gqa_mixer(p, y, heads, kv_heads, head_dim,
                                      scale=attention_multiplier),
            cast="scope"),
    }
    experts = S.experts(
        "norm2", "ffn",
        lambda key: paired_router(topk_moe_init(
            key, dim, expert_width, n_experts, experts_held,
            shared_hidden=shared_width, selection_bias=False)),
        held=experts_held, top_k=top_k, first_expert=first_expert,
        scale=1.0, tile=expert_tile, router="softmax")
    return S.token_scorer(
        name, num_classes, input_shape,
        tuple((mixers[kind], experts) for kind in layer_types),
        dim=dim, eps=eps, max_rows=max_rows,
        scale_emb=embedding_multiplier, residual=residual_multiplier,
        logit_scale=1.0 / logits_scaling, tied=True, embed_std=embed_std,
        param_dtype=param_dtype,
        hyper={"layer_types": tuple(layer_types), "dim": dim,
               "mamba_heads": mamba_heads, "mamba_head_dim": mamba_head_dim,
               "groups": groups, "state": state, "heads": heads,
               "kv_heads": kv_heads, "head_dim": head_dim,
               "attention_multiplier": attention_multiplier,
               "n_experts": n_experts, "top_k": top_k,
               "experts_held": experts_held, "first_expert": first_expert,
               "chunk": chunk})


@register("granite_4_h_small")
def build_granite_4_h_small(num_classes: int = 50176,
                            input_shape: tuple = (4096,)) -> ModelDef:
    """granite-4.0-h-small at its published widths, as one chip of the two
    that share each layer of a pipeline stage holds it: layers 0-9 of 40 (one
    whole period: five Mamba-2 blocks, one attention block, four Mamba-2
    blocks), routed experts 0-35 of 72 in each with the shared expert, half
    the tied matrix; 4.76 B parameters here, handed over in bfloat16. The
    layers left out lie on further pipeline stages."""
    return build_granite(
        "granite_4_h_small", num_classes, tuple(input_shape),
        layer_types=("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
        dim=4096, mamba_heads=128, mamba_head_dim=64, state=128, conv=4,
        heads=32, kv_heads=8, head_dim=128, attention_multiplier=0.0078125,
        expert_width=768, shared_width=1536, n_experts=72, top_k=10,
        experts_held=36)


@register("granite_h_tiny")
def build_granite_h_tiny(num_classes: int = 96, input_shape: tuple = (40,),
                         param_dtype=jnp.float32) -> ModelDef:
    """The same code at toy widths, in float32: for the tests and the
    benchmark's rehearsal on the CPU. Both kinds of block; 6 Mamba-2 heads of
    4 on one group; 40 tokens are no multiple of its chunk of 16; 2 query
    heads a key head under a scale that is not the root's; a router of 9
    columns (no power of two), top 3, 5 held, the shared expert at twice an
    expert's width; all four multipliers off 1."""
    return build_granite(
        "granite_h_tiny", num_classes, tuple(input_shape),
        layer_types=("mamba", "mamba", "attention", "mamba"), dim=32,
        mamba_heads=6, mamba_head_dim=4, state=8, conv=4, heads=4,
        kv_heads=2, head_dim=8, attention_multiplier=0.25, expert_width=32,
        shared_width=64, n_experts=9, top_k=3, experts_held=5,
        embedding_multiplier=6.0, residual_multiplier=0.4,
        logits_scaling=4.0, embed_std=0.125, chunk=16, expert_tile=16,
        max_rows=4, param_dtype=param_dtype)
