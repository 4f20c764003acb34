"""EvaByte (``model_type`` ``evabyte``) as a scorer of byte records: a window
of byte ids in, the distributions of the next ``pred_heads`` bytes at its
last position out, through the same engine and topology as every other model.

A tokenizer-free language model: the vocabulary is the 256 bytes after 64
special ids (byte ``b`` is id ``b + 64``), 320 rows. Every block is the
Llama-shaped ``x += Mixer(RMSNorm(x)); x += SwiGLU(RMSNorm(x))`` with a
float32 stream (``fp32_skip_add``); the mixer's attention is EVA
(:mod:`storm_tpu.ops.eva_attention`): ``q`` and ``k`` of ``heads`` heads
turned by plain rotary position code (:mod:`storm_tpu.ops.rope`
``turn_merged``, all of a head's channels, pair ``(i, i + d / 2)``); every
chunk of ``chunk`` keys and values pooled into one summary each by two
learned vectors a head (``mu``, ``phi``; the keys are pooled after their
turn); a query reads its window's
keys up to itself exactly and every earlier window's summaries, under one
softmax. The head is one product of ``pred_heads x vocabulary`` columns on
the last norm: ``pred_heads`` next-byte distributions a record, laid end to
end in the answer (``ModelDef.num_classes`` is their total length).

``norm_add_unit_offset``: a checkpoint stores a norm's scale ``g`` and the
model multiplies by ``1 + g``. A load serves ``1 + g`` once, as
``ops/rope.py halves_first`` reorders rotary columns once; no step changes,
and weights from a seed have scale 1 (``g = 0``).

**The cut** is in depth alone: the builder is told how many of the
published layers it holds (the first ``layers``; the period is one layer);
every width, every head, the whole vocabulary and every prediction head are
here. The load is ``models/scorer.py``'s in ``param_dtype``, a program a
layer.

**A step's temporaries.** A row is thousands of positions at an
11,008-wide feed-forward, so the feed-forward runs a row at a time (one loop
under ``proj``), as the summaries and the attention do.

The step's counters ride ``new_state["aux"]``: ``eva_pairs_exact`` and
``eva_pairs_summarised``, one number a layer, read on the host by
``ops/eva_attention.py observe_pair_counts``. The skeleton is
:func:`storm_tpu.models.scorer.token_scorer`'s; this file holds the plan.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from storm_tpu.models import scorer as S
from storm_tpu.models.minicpm_sala import _rows
from storm_tpu.models.registry import ModelDef, register
from storm_tpu.models.scorer import _proj, _w
from storm_tpu.ops import layers as L
from storm_tpu.ops import parts as P
from storm_tpu.ops import rope as R
from storm_tpu.ops.eva_attention import (chunk_summaries, eva_attention,
                                         observe_pair_counts, pair_counts)


def eva_mixer_init(rng, dim: int, heads: int, head_dim: int) -> dict:
    """``mu`` and ``phi`` as the released code draws them: a normal draw
    clipped to ``[-1, 1]``, times ``head_dim^-1/2``."""
    ks = jax.random.split(rng, 6)
    inner = heads * head_dim

    def pooling(key):
        return jnp.clip(jax.random.normal(key, (heads, head_dim)), -1.0,
                        1.0) * head_dim ** -0.5

    return {"q": _w(ks[0], dim, inner), "k": _w(ks[1], dim, inner),
            "v": _w(ks[2], dim, inner), "o": _w(ks[3], inner, dim),
            "mu": pooling(ks[4]), "phi": pooling(ks[5])}


def eva_mixer(p: dict, x: jnp.ndarray, heads: int, head_dim: int,
              window: int, chunk: int, rotary: tuple):
    """``(out, pairs read exactly, pairs reached through summaries)``."""
    b, s, _ = x.shape
    # the heads stay merged, (B, S, H * D), from the projections to the
    # output projection: ops/eva_attention.py reads a head where it lies
    q, k = R.turn_merged((_proj(x, p["q"]), _proj(x, p["k"])), *rotary,
                         heads)
    v = _proj(x, p["v"])
    out = eva_attention(q, k, v, *chunk_summaries(k, v, p["mu"], p["phi"],
                                                  chunk), heads, window,
                        chunk)
    return _proj(out, p["o"]), *pair_counts(b, s, window, chunk)


def build_evabyte(
    name: str,
    num_classes: int,
    input_shape: tuple,
    *,
    layers: int,
    published_layers: int,
    dim: int,
    ffn_width: int,
    heads: int,
    head_dim: int,
    window: int,
    chunk: int,
    pred_heads: int = 8,
    rope_theta: float = 100000.0,
    eps: float = 1e-5,
    max_rows: int = 4,
    param_dtype=jnp.bfloat16,
) -> ModelDef:
    """Published layers ``0..layers-1`` over the whole vocabulary:
    ``num_classes`` is an answer's length, ``pred_heads`` distributions over
    ``num_classes / pred_heads`` ids."""
    # as the other language models: every residual branch's output
    # projection starts smaller by the root of the branches of the published
    # stack
    branch = (2 * published_layers) ** -0.5
    inv_freq = rope_theta ** (-2.0 * np.arange(head_dim // 2)
                              / head_dim)  # plain rotary, float64
    mixer = S.Branch(
        "norm1", "mixer",
        lambda key: S.scaled(eva_mixer_init(key, dim, heads, head_dim),
                             {"o": branch}),
        lambda p, y, rotary: eva_mixer(p, y, heads, head_dim, window, chunk,
                                       rotary),
        counts=(("eva_pairs_exact", ()), ("eva_pairs_summarised", ())),
        observe=observe_pair_counts)
    ffn = S.Branch(
        "norm2", "ffn",
        lambda key: S.scaled(L.swiglu_init(key, dim, ffn_width),
                             {"down": branch}),
        lambda p, y, _: _rows(lambda row: L.swiglu(p, row), y), scope=P.PROJ)
    return S.token_scorer(
        name, num_classes, input_shape, ((mixer, ffn),) * layers,
        dim=dim, eps=eps, max_rows=max_rows, heads=pred_heads,
        context=lambda seq: R.rotary_tables(seq, inv_freq),
        param_dtype=param_dtype,
        hyper={"layers": layers, "dim": dim, "heads": heads,
               "head_dim": head_dim, "window": window, "chunk": chunk,
               "pred_heads": pred_heads, "rope_theta": rope_theta})


@register("evabyte")
def build_evabyte_6b(num_classes: int = 2560,
                     input_shape: tuple = (16384,)) -> ModelDef:
    """EvaByte at its published widths, whole vocabulary (320 ids) and all
    eight prediction heads, layers 0-10 of 32; 2.24 B parameters here,
    handed over in bfloat16. The layers left out lie on further pipeline
    stages."""
    return build_evabyte(
        "evabyte", num_classes, tuple(input_shape), layers=11,
        published_layers=32, dim=4096, ffn_width=11008, heads=32,
        head_dim=128, window=2048, chunk=16)


@register("evabyte_tiny")
def build_evabyte_tiny(num_classes: int = 320, input_shape: tuple = (96,),
                       param_dtype=jnp.float32) -> ModelDef:
    """The same code at toy widths, in float32: for the tests and the
    benchmark's rehearsal on the CPU. 96 positions are three windows of 32,
    so the second and third read 8 and 16 summaries of chunks of 4; eight
    distributions over 40 ids."""
    return build_evabyte(
        "evabyte_tiny", num_classes, tuple(input_shape), layers=2,
        published_layers=4, dim=64, ffn_width=128, heads=4, head_dim=16,
        window=32, chunk=4, rope_theta=100.0, param_dtype=param_dtype)
