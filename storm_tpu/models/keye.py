"""Keye-VL-2.0's language model (``model_type`` ``KeyeVL2``) as a scorer of
long token records: a window of token ids in, the next-token distribution
at its last position out, through the same engine and topology as every
other model.

Every block is ``h += attn(RMSNorm(h)); h += experts(RMSNorm(h))``.

- The mixer is causal softmax attention with grouped queries (``heads`` over
  ``kv_heads``), an RMS norm over each query and key head, and multimodal
  rotary position code: three position streams, the frequencies cut among
  them by ``mrope_section`` (ops/rope.py ``mrope_tables``; a token record's
  streams are all ``arange``). Beside it a learned **indexer**:
  ``index_heads`` small query heads, one key a position (layer-normed), both
  turned by plain rotary code over all their channels, and a weight a query
  and head, all three read from the block's normed input. The query at ``t``
  reads the ``topk`` keys the indexer scores highest, every key before it
  where there are ``topk`` or fewer, the same keys for every head
  (ops/sparse_attention.py ``indexed_attention``: the first pass
  ``select_keys``, the second the masked kernel that ``minicpm_sala``'s
  picked blocks run).
- The feed-forward of every layer is the dropless top-k expert layer with a
  softmax router, no selection bias and no shared expert
  (:func:`storm_tpu.parallel.moe.topk_moe_layer`): the ``top_k`` largest of
  ``softmax(W_r n)``, weighted by the score over the chosen scores' sum.

**The cut** is in depth alone: one pipeline stage's layers, each whole;
every width, every head, the indexer, *every routed expert* of a layer and
the whole vocabulary are here. The image tower that stands before the
language model's first stage is not: records are token ids. The load is
``models/scorer.py``'s in ``param_dtype``, one program for every block.

What the published ``config.json`` does not fix is listed under ``assumed``
in the benchmark's configuration file: the head norms, how the sections cut
the frequencies, the indexer's input, key norm and position code, what
``q_chunk_size`` and ``kv_chunk_size`` are (the squares the counters count,
no part of the mathematics), where the weights start.

The step's counters ride ``new_state["aux"]``: ``index_blocks_picked`` and
``index_blocks_causal``, a number a layer (``ops/sparse_attention.py
observe_block_counts``), beside the expert layer's.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from storm_tpu.models import scorer as S
from storm_tpu.models.registry import ModelDef, register
from storm_tpu.models.scorer import _proj, _w
from storm_tpu.ops import layers as L
from storm_tpu.ops import rope as R
from storm_tpu.ops.sparse_attention import (indexed_attention,
                                            observe_block_counts)
from storm_tpu.parallel.moe import topk_moe_init


def keye_mixer_init(rng, dim: int, heads: int, kv_heads: int, head_dim: int,
                    index_heads: int, index_dim: int) -> dict:
    ks = jax.random.split(rng, 7)
    return {"q": _w(ks[0], dim, heads * head_dim),
            "k": _w(ks[1], dim, kv_heads * head_dim),
            "v": _w(ks[2], dim, kv_heads * head_dim),
            "o": _w(ks[3], heads * head_dim, dim),
            "q_norm": L.rmsnorm_init(head_dim),
            "k_norm": L.rmsnorm_init(head_dim),
            "index_q": _w(ks[4], dim, index_heads * index_dim),
            "index_k": _w(ks[5], dim, index_dim),
            "index_w": _w(ks[6], dim, index_heads),
            "index_k_norm": L.layernorm_init(index_dim)}


def keye_mixer(p: dict, x: jnp.ndarray, heads: int, kv_heads: int,
               head_dim: int, index_heads: int, index_dim: int, eps: float,
               tables: tuple, topk: int, count_block: int,
               block: int = 512, tile: int = 1024):
    """``(out, squares picked, squares causal)``: grouped causal attention
    over the keys the indexer picks a query, head norms on q and k, M-RoPE's
    tables ``tables[0]`` on both and plain rotary ``tables[1]`` on the
    indexer's queries and key."""
    b, s, _ = x.shape
    rotary, index_rotary = tables

    def heads_first(y, n):
        return y.reshape(b, s, n, -1).transpose(0, 2, 1, 3)

    # the norm and the turn where q and k lie in their projections
    q = R.norm_turn_merged(p["q_norm"], _proj(x, p["q"]), heads, eps, rotary)
    k = R.norm_turn_merged(p["k_norm"], _proj(x, p["k"]), kv_heads, eps,
                           rotary)
    cos, sin = index_rotary
    qi = R.rotate_halves(
        _proj(x, p["index_q"]).reshape(b, s, index_heads, index_dim),
        cos[:, None], sin[:, None])
    ki = R.rotate_halves(
        L.layernorm(p["index_k_norm"], _proj(x, p["index_k"]), eps), cos, sin)
    out, picked, causal = indexed_attention(
        heads_first(q, heads), heads_first(k, kv_heads),
        heads_first(_proj(x, p["v"]), kv_heads),
        qi.transpose(0, 2, 1, 3), ki, _proj(x, p["index_w"]),
        head_dim ** -0.5, topk=topk, count_block=count_block, block=block,
        tile=tile)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, heads * head_dim)
    return _proj(out, p["o"]), picked, causal


def build_keye(
    name: str,
    num_classes: int,
    input_shape: tuple,
    *,
    layers: int,
    published_layers: int,
    dim: int,
    heads: int,
    kv_heads: int,
    head_dim: int,
    mrope_section: tuple,
    index_heads: int,
    index_dim: int,
    topk: int,
    chunk: int,
    expert_width: int,
    n_experts: int,
    top_k: int,
    experts_held: int,
    first_expert: int = 0,
    rope_theta: float = 1e7,
    eps: float = 1e-6,
    expert_tile: Optional[int] = None,
    attention_block: int = 512,
    select_tile: int = 1024,
    max_rows: int = 4,
    param_dtype=jnp.bfloat16,
) -> ModelDef:
    """``layers`` consecutive blocks of the ``published_layers`` (all are
    alike) over ``num_classes`` rows of the vocabulary. ``chunk``: the
    published ``q_chunk_size`` = ``kv_chunk_size``, the side of the squares
    of queries and keys the counters count."""
    if sum(mrope_section) * 2 != head_dim:
        raise ValueError(f"mrope_section {tuple(mrope_section)!r} does not "
                         f"cut {head_dim // 2} frequencies")
    # a branch's output projection (an expert's ``down``) over sqrt(2 x
    # layers), as models/kimi_linear.py: the stream stays at the
    # embedding's scale whatever the depth
    branch = 1.0 / math.sqrt(2 * published_layers)

    def inv_freq(d):  # plain rotary's frequencies, float64
        return rope_theta ** (-2.0 * np.arange(d // 2) / d)

    def tables(seq):
        # a token record: the three streams are one
        positions = np.broadcast_to(np.arange(seq), (3, seq))
        return (R.mrope_tables(positions, inv_freq(head_dim), mrope_section),
                R.rotary_tables(seq, inv_freq(index_dim)))

    mixer = S.Branch(
        "norm1", "mixer",
        lambda key: S.scaled(keye_mixer_init(
            key, dim, heads, kv_heads, head_dim, index_heads, index_dim),
            {"o": branch}),
        lambda p, y, ctx: keye_mixer(
            p, y, heads, kv_heads, head_dim, index_heads, index_dim, eps,
            ctx, topk, chunk, attention_block, select_tile),
        counts=(("index_blocks_picked", ()), ("index_blocks_causal", ())),
        observe=observe_block_counts)
    experts = S.experts(
        "norm2", "ffn",
        lambda key: S.scaled(topk_moe_init(
            key, dim, expert_width, n_experts, experts_held, shared=False,
            selection_bias=False), {"down": branch}),
        held=experts_held, top_k=top_k, first_expert=first_expert,
        scale=1.0, tile=expert_tile, router="softmax")
    return S.token_scorer(
        name, num_classes, input_shape, ((mixer, experts),) * layers,
        dim=dim, eps=eps, max_rows=max_rows, context=tables,
        param_dtype=param_dtype,
        hyper={"layers": layers, "dim": dim, "heads": heads,
               "kv_heads": kv_heads, "head_dim": head_dim,
               "mrope_section": tuple(mrope_section),
               "index_heads": index_heads, "index_dim": index_dim,
               "topk": topk, "chunk": chunk, "n_experts": n_experts,
               "top_k": top_k, "experts_held": experts_held,
               "first_expert": first_expert, "rope_theta": rope_theta})


@register("keye_vl2_30b")
def build_keye_vl2_30b(num_classes: int = 151936,
                       input_shape: tuple = (16384,)) -> ModelDef:
    """Keye-VL-2.0-30B-A3B's language model at its published widths, as one
    pipeline stage of eight holds its six layers, each whole (all 48 are
    alike): 32 query heads on 4 key heads, the indexer's 16 heads of 64 and
    its top 2,048 keys a query, all 128 experts of 768 with 8 a token, the
    whole vocabulary; 4.37 B parameters here, handed over in bfloat16. The
    layers left out lie on further pipeline stages; the image tower is not
    here."""
    return build_keye(
        "keye_vl2_30b", num_classes, tuple(input_shape), layers=6,
        published_layers=48, dim=2048, heads=32, kv_heads=4, head_dim=128,
        mrope_section=(16, 24, 24), index_heads=16, index_dim=64, topk=2048,
        chunk=512, expert_width=768, n_experts=128, top_k=8,
        experts_held=128)


@register("keye_tiny")
def build_keye_tiny(num_classes: int = 96, input_shape: tuple = (40,),
                    param_dtype=jnp.float32) -> ModelDef:
    """The same code at toy widths, in float32: for the tests and the
    benchmark's rehearsal on the CPU. Three layers, so that a selection
    reads a stream the mixers have written; 4 query heads a key head; an
    indexer of 4 heads of 8 that picks 12 of up to 40 keys, neither a
    multiple of a tile (16 queries); squares of 8; a router of 20 columns
    (no power of two), top-2, all held."""
    return build_keye(
        "keye_tiny", num_classes, tuple(input_shape), layers=3,
        published_layers=8, dim=64, heads=8, kv_heads=2, head_dim=16,
        mrope_section=(2, 3, 3), index_heads=4, index_dim=8, topk=12,
        chunk=8, expert_width=32, n_experts=20, top_k=2, experts_held=20,
        rope_theta=100.0, expert_tile=16, attention_block=16,
        select_tile=16, param_dtype=param_dtype)
