"""Ouro (``model_type`` ``ouro``, a looped language model) as a scorer of
token records: a window of token ids in, the next-token distribution at its
last position out, through the same engine and topology as every other
model.

The stream starts at ``E[id]``. The model's stack of blocks runs
``total_ut_steps`` times **over one set of weights**; after every pass the
model's last RMS norm is applied to the whole stream, and that normed stream
is what the next pass starts from, what the exit gate reads and what the head
reads (:func:`storm_tpu.models.scorer.token_scorer` with ``passes``: one
``lax.fori_loop`` whose body is a pass). Every block is a sandwich of four
norms around rotary attention and a SwiGLU:

    h = h + RMSNorm_post1(Attention(RMSNorm_1(h)))
    h = h + RMSNorm_post2(W_down(SiLU(W_gate m) * W_up m)),  m = RMSNorm_2(h)

- Attention is causal softmax attention at one query head a key head
  (``heads`` of ``head_dim``, no grouping, no window), q and k turned by
  plain rotary position code over all of a head's channels, pairs ``(i, i +
  head_dim / 2)``, the same positions every pass; no bias, no head norm, no
  gate (:func:`storm_tpu.models.falcon_h1.rotary_gqa` at a group of 1: q, k,
  v and the result stay ``(B, S, H * head_dim)`` from the projections to the
  output projection).
- The feed-forward is :func:`storm_tpu.models.falcon_h1.gated_ffn` under its
  own part ``ffn`` (ops/parts.py), every row of the step at once.
- The exit gate is ``sigmoid(w . z_t + b)`` on each pass's normed last
  position; a record's answer is read from the first pass where the gate's
  weights so far reach ``early_exit_threshold`` (the last where none does:
  at the published threshold of 1, every record). All the passes run for
  every row of a step either way.

**Nothing is cut** at the published sizes: all the layers, all the passes,
the whole vocabulary in an embedding and a head of their own; one chip holds
the model whole.

What the published ``config.json`` does not fix is the released modelling
code's and the paper's (Zhu et al. 2025, "Scaling Latent Reasoning via
Looped Language Models") and listed under ``assumed`` in the benchmark's
configuration file: the sandwich, the norm between passes, the biases, the
gate and its rule, the rotary pairing, where the weights start.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from storm_tpu.models import scorer as S
from storm_tpu.models.falcon_h1 import gated_ffn, rotary_gqa
from storm_tpu.models.nemotron_h import gqa_mixer_init
from storm_tpu.models.registry import ModelDef, register
from storm_tpu.ops import layers as L
from storm_tpu.ops import parts as P
from storm_tpu.ops import rope as R


def build_ouro(
    name: str,
    num_classes: int,
    input_shape: tuple,
    *,
    layers: int,
    passes: int,
    dim: int,
    ffn_width: int,
    heads: int,
    head_dim: int,
    threshold: float = 1.0,
    rope_theta: float = 1e6,
    eps: float = 1e-6,
    attention_block: int = 512,
    max_rows: int = 4,
    param_dtype=jnp.bfloat16,
) -> ModelDef:
    """``layers`` blocks (all alike) run ``passes`` times over the
    ``num_classes`` rows of the vocabulary, the answer read by the exit
    gate's rule under ``threshold``."""
    # Where the weights start: every projection LeCun's (a branch's result
    # is normed before it meets the stream, so its own scale is no matter);
    # a post-norm's scales at 1 over the root of a pass's branches, so that
    # a pass adds to a stream of 1 a channel about as much again whatever
    # the depth, and the norm between passes brings it back to 1: the
    # embedding's share of what the head reads halves a pass and no branch
    # outweighs the others (models/trinity.py argues the same for its own
    # sandwich).
    post_scale = 1.0 / math.sqrt(2 * layers)
    inv_freq = rope_theta ** (-2.0 * np.arange(head_dim // 2)
                              / head_dim)  # plain rotary, float64
    block = (
        S.Branch("norm1", "mixer",
                 lambda key: gqa_mixer_init(key, dim, heads, heads, head_dim),
                 lambda p, y, rotary: rotary_gqa(
                     p, y, heads, heads, rotary, head_dim ** -0.5,
                     attention_block),
                 post="post1", post_scale=post_scale),
        S.Branch("norm2", "ffn",
                 lambda key: L.swiglu_init(key, dim, ffn_width),
                 lambda p, y, _: gated_ffn(p, y, 1.0),
                 scope=P.FFN, post="post2", post_scale=post_scale))
    return S.token_scorer(
        name, num_classes, input_shape, (block,) * layers,
        dim=dim, eps=eps, max_rows=max_rows,
        context=lambda seq: R.rotary_tables(seq, inv_freq),
        param_dtype=param_dtype, passes=passes, threshold=threshold,
        hyper={"layers": layers, "passes": passes, "dim": dim,
               "ffn_width": ffn_width, "heads": heads, "head_dim": head_dim,
               "threshold": threshold, "rope_theta": rope_theta})


@register("ouro_2_6b")
def build_ouro_2_6b(num_classes: int = 49152,
                    input_shape: tuple = (4096,)) -> ModelDef:
    """Ouro-2.6B as published, whole: 48 sandwich-normed blocks of rotary
    16-head attention at heads of 128 and a 5,632-wide SwiGLU, run four times
    over one set of weights, the exit threshold 1 (every record reads the
    fourth pass), the untied vocabulary of 49,152; 2.67 B parameters, handed
    over in bfloat16."""
    return build_ouro(
        "ouro_2_6b", num_classes, tuple(input_shape), layers=48, passes=4,
        dim=2048, ffn_width=5632, heads=16, head_dim=128)


@register("ouro_tiny")
def build_ouro_tiny(num_classes: int = 96, input_shape: tuple = (40,),
                    threshold: float = 1.0,
                    param_dtype=jnp.float32) -> ModelDef:
    """The same code at toy widths, in float32: for the tests and the
    benchmark's rehearsal on the CPU. Three blocks run four times; 4 heads of
    8; 40 tokens are no multiple of the blocked form's 16 queries.
    ``threshold`` under 1: records leave at the pass the gate names."""
    return build_ouro(
        "ouro_tiny", num_classes, tuple(input_shape), layers=3, passes=4,
        dim=32, ffn_width=72, heads=4, head_dim=8, threshold=threshold,
        rope_theta=100.0, attention_block=16, param_dtype=param_dtype)
