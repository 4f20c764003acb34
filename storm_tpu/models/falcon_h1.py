"""Falcon-H1 (``model_type`` ``falcon_h1``) as a scorer of long token records:
a window of token ids in, the next-token distribution at its last position
out, through the same engine and topology as every other model.

Every block is a **parallel** mixer and a dense feed-forward. One RMS norm's
output is read by a Mamba-2 layer and by an attention layer side by side, and
both results are added to the stream, each under its own published scalar:

    n = RMSNorm_1(h)
    h = h + ssm_out Mamba2(ssm_in n) + attention_out Attention(attention_in n)
    h = h + mlp_down W_down(W_up m * SiLU(mlp_gate W_gate m)),  m = RMSNorm_2(h)

- The Mamba-2 layer is :func:`storm_tpu.models.nemotron_h.mamba_mixer` with
  the five ``ssm_multipliers`` on the segments ``[z | x | B | C | dt]`` of its
  projection's result (its ``scales``; ``ssm_in`` rides them, the projection
  being linear): heads of ``mamba_d_head`` on a state of ``mamba_d_state`` in
  ``mamba_n_groups`` groups, the gate before a norm a group.
- The attention layer is causal softmax attention with grouped queries
  (``heads`` over ``kv_heads``: five a key head at the published sizes, no
  power of two, ops/flash_attention.py ``causal_tiles``), q and k turned by
  plain rotary position code over all of a head's channels, pairs ``(i, i +
  head_dim / 2)`` as the released code's ``rotate_half`` pairs them
  (ops/rope.py ``turn_merged``, where they lie in their projections), no
  bias, no head norm, no gate. The keys carry ``key_multiplier``; the program
  carries it, and ``attention_in`` squared, in the scores' scale (the turn is
  linear), and ``attention_in`` once more beside ``attention_out`` (so are
  the values), which is the same mathematics with no pass of its own.
- The feed-forward is SwiGLU, a row of the batch at a time (a row's gate, up
  and product are 2 GB at the published width), under its own part ``ffn``
  (ops/parts.py): ``proj`` is then the mixers' projections alone.

The stream starts at ``embedding_multiplier E[id]`` and the head reads
``lm_head_multiplier RMSNorm(h_L)`` (:func:`storm_tpu.models.scorer
.token_scorer`'s ``scale_emb`` and ``logit_scale``); the head is a matrix of
its own (``tie_word_embeddings`` false).

**The cut** is in depth alone: the builder is told how many of the published
layers it holds (they are all alike); every width, every head, both groups
and the whole vocabulary are here.

What the published ``config.json`` does not fix is the released modelling
code's and listed under ``assumed`` in the benchmark's configuration file:
where each scalar sits, the order of the projection's segments, the rotary
convention, where the weights start (every matrix a published scalar stands
on at LeCun's scale over that scalar, the three branch outputs over the root
of three times the published depth besides).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from storm_tpu.models import scorer as S
from storm_tpu.models.minicpm_sala import _rows
from storm_tpu.models.nemotron_h import (gqa_mixer_init, mamba_mixer,
                                         mamba_mixer_init)
from storm_tpu.models.registry import ModelDef, register
from storm_tpu.models.scorer import _proj
from storm_tpu.ops import layers as L
from storm_tpu.ops import parts as P
from storm_tpu.ops import platform
from storm_tpu.ops import rope as R
from storm_tpu.ops.attention import causal_attention_merged


class Mixers(NamedTuple):
    """The two mixers' sizes and the scalars that stand on them, as
    published."""

    heads: int
    kv_heads: int
    head_dim: int
    mamba_heads: int
    mamba_head_dim: int
    groups: int
    state: int
    conv: int
    attention_in: float
    attention_out: float
    key: float
    ssm_in: float
    ssm_out: float
    ssm: tuple  # one a segment of [z | x | B | C | dt]
    chunk: int = 128
    attention_block: int = 512
    eps: float = 1e-5


def rotary_gqa(p: dict, x: jnp.ndarray, heads: int, kv_heads: int,
               rotary: tuple, scale: float, block: int = 512) -> jnp.ndarray:
    """Causal attention with grouped queries, q and k turned by ``rotary``'s
    tables ``(S, head_dim / 2)`` where they lie in their projections, the
    scores times ``scale``; no bias, no head norm, no gate. The turn is
    ``causal_attention_merged``'s: inside the causal kernel, on the tiles it
    holds in VMEM, where a head is one lane tile on one chip (this model's
    and models/ouro.py's 128), by ops/rope.py ``turn_merged`` before the
    attention elsewhere."""
    out = causal_attention_merged(
        _proj(x, p["q"]), _proj(x, p["k"]), _proj(x, p["v"]), heads,
        kv_heads, scale=scale, block=block, rotary=rotary)
    return _proj(out, p["o"])


def parallel_mixer_init(rng, dim: int, m: Mixers, branch: float) -> dict:
    """Both mixers' parameters, every matrix a scalar stands on at LeCun's
    scale over that scalar, the two output projections over ``branch``
    besides."""
    km, ka = jax.random.split(rng)
    inner, gn = m.mamba_heads * m.mamba_head_dim, m.groups * m.state
    mamba = S.scaled(mamba_mixer_init(
        km, dim, m.mamba_heads, m.mamba_head_dim, m.groups, m.state, m.conv),
        {"out_proj": 1.0 / (m.ssm_out * branch)})
    mamba["in_proj"] = mamba["in_proj"] * np.repeat(
        1.0 / (m.ssm_in * np.asarray(m.ssm, np.float64)),
        [inner, inner, gn, gn, m.mamba_heads]).astype(np.float32)
    return {"mamba": mamba, "attention": S.scaled(
        gqa_mixer_init(ka, dim, m.heads, m.kv_heads, m.head_dim),
        {"q": 1.0 / m.attention_in, "k": 1.0 / (m.attention_in * m.key),
         "v": 1.0 / m.attention_in, "o": 1.0 / (m.attention_out * branch)})}


def parallel_mixer(p: dict, y: jnp.ndarray, rotary: tuple,
                   m: Mixers) -> jnp.ndarray:
    """Both mixers on the one normed input ``y``, added in float32 under
    their own multipliers. Every scalar sits where it costs no pass:
    ``ssm_in`` on the projection's five segments' own (``mamba_mixer``'s
    ``scales``), ``key`` and ``attention_in`` squared in the scores' scale,
    ``attention_in`` once more (the values') beside ``attention_out``."""
    f32 = jnp.float32
    ssm = mamba_mixer(p["mamba"], y, m.mamba_heads, m.mamba_head_dim,
                      m.groups, m.state, m.chunk, m.eps,
                      scales=tuple(m.ssm_in * s for s in m.ssm))
    attn = rotary_gqa(p["attention"], y, m.heads, m.kv_heads, rotary,
                      m.key * m.attention_in ** 2 * m.head_dim ** -0.5,
                      m.attention_block)
    return m.ssm_out * ssm.astype(f32) \
        + m.attention_out * m.attention_in * attn.astype(f32)


def gated_ffn(p: dict, x: jnp.ndarray, gate_multiplier: float) -> jnp.ndarray:
    """``W_down(W_up x * SiLU(gate_multiplier W_gate x))``: the gate's scalar,
    the activation and the product in float32, one rounding.

    The rounded product ``h`` stands behind one ``optimization_barrier``, so
    that the chip's compiler makes it once an element, on the way out of
    whichever of the two products before it runs second, and the down
    product reads a bare operand. Left alone, the compiler holds the float32
    scalar, SiLU and product inside the down product's fusion, on the way
    *in*, where an operand is made again for every tile of the product's
    columns. Compiled for a described v5e at 16,384 x 5,120 x 21,504
    (tests/test_tpu_compile.py), in millions of estimated cycles a row:
    32.60 + 32.60 + 42.53 = 107.7 left alone, 32.60 + 36.41 + 32.34 = 101.4
    behind the barrier; on the chip 62.99 and 60.02 ms a row (PERF.md
    section 6, PR 67). This is not a barrier at every bfloat16 boundary,
    which cuts fusions the compiler has right (ROADMAP Speed 1 (a)): it is
    one, between products of 3.6 TFLOP each. The formula is the same, and
    so is every bit wherever nothing is fused (tests/test_falcon_h1.py);
    on the chip the product that carries the activation hands it float32
    sums the compiler no longer rounds to bfloat16 on the way (XLA's excess
    precision, as under every ``swiglu``). ``ops/layers.py swiglu`` needs
    no barrier: its bfloat16 activation already rides out of a product."""
    f32 = jnp.float32
    platform.note("gated_ffn", "made-once")
    gate = jax.nn.silu(L.matmul(x, p["gate"]).astype(f32) * gate_multiplier)
    h = (gate * L.matmul(x, p["up"]).astype(f32)).astype(x.dtype)
    return L.matmul(lax.optimization_barrier(h), p["down"])


def build_falcon_h1(
    name: str,
    num_classes: int,
    input_shape: tuple,
    *,
    layers: int,
    published_layers: int,
    dim: int,
    ffn_width: int,
    heads: int,
    kv_heads: int,
    head_dim: int,
    mamba_heads: int,
    mamba_head_dim: int,
    groups: int,
    state: int,
    conv: int,
    embedding_multiplier: float,
    lm_head_multiplier: float,
    attention_in_multiplier: float,
    attention_out_multiplier: float,
    key_multiplier: float,
    ssm_in_multiplier: float,
    ssm_out_multiplier: float,
    ssm_multipliers: tuple,
    mlp_multipliers: tuple,
    rope_theta: float = 1e11,
    eps: float = 1e-5,
    chunk: int = 128,
    attention_block: int = 512,
    max_rows: int = 4,
    param_dtype=jnp.bfloat16,
) -> ModelDef:
    """Published layers ``0..layers-1`` of ``published_layers`` (all alike)
    over the ``num_classes`` rows of the vocabulary. ``ssm_multipliers``:
    one a segment of ``[z | x | B | C | dt]``; ``mlp_multipliers``: the
    gate's and the down projection's."""
    if len(ssm_multipliers) != 5 or len(mlp_multipliers) != 2:
        raise ValueError(f"ssm_multipliers {tuple(ssm_multipliers)!r} are "
                         f"five and mlp_multipliers "
                         f"{tuple(mlp_multipliers)!r} two")
    # Where the weights start. A multiplier stands against weights trained
    # under it (scorer.py ``ends_init`` keeps the same rule for the two
    # ends): every matrix a published scalar stands on is drawn at LeCun's
    # scale over that scalar, so that each projection's result is what a
    # LeCun matrix gives without one, and the three branch outputs over the
    # root of three branches a published layer besides, so that the stream
    # keeps the embedding's scale whatever the depth
    # (models/nemotron_h.py argues the same).
    branch = math.sqrt(3 * published_layers)
    mixers = Mixers(
        heads=heads, kv_heads=kv_heads, head_dim=head_dim,
        mamba_heads=mamba_heads, mamba_head_dim=mamba_head_dim,
        groups=groups, state=state, conv=conv,
        attention_in=attention_in_multiplier,
        attention_out=attention_out_multiplier, key=key_multiplier,
        ssm_in=ssm_in_multiplier, ssm_out=ssm_out_multiplier,
        ssm=tuple(ssm_multipliers), chunk=chunk,
        attention_block=attention_block, eps=eps)
    gate_multiplier, down_multiplier = mlp_multipliers
    inv_freq = rope_theta ** (-2.0 * np.arange(head_dim // 2)
                              / head_dim)  # plain rotary, float64
    block = (
        S.Branch("norm1", "mixer",
                 lambda key: parallel_mixer_init(key, dim, mixers, branch),
                 lambda p, y, rotary: parallel_mixer(p, y, rotary, mixers)),
        S.Branch(
            "norm2", "ffn",
            lambda key: S.scaled(L.swiglu_init(key, dim, ffn_width), {
                "gate": 1.0 / gate_multiplier,
                "down": 1.0 / (down_multiplier * branch)}),
            lambda p, y, _: down_multiplier * _rows(
                lambda row: gated_ffn(p, row, gate_multiplier), y
            ).astype(jnp.float32),
            scope=P.FFN))
    return S.token_scorer(
        name, num_classes, input_shape, (block,) * layers,
        dim=dim, eps=eps, max_rows=max_rows,
        scale_emb=embedding_multiplier, logit_scale=lm_head_multiplier,
        context=lambda seq: R.rotary_tables(seq, inv_freq),
        param_dtype=param_dtype, pin_stream=True,
        hyper={"layers": layers, "dim": dim, "ffn_width": ffn_width,
               "heads": heads, "kv_heads": kv_heads, "head_dim": head_dim,
               "mamba_heads": mamba_heads, "mamba_head_dim": mamba_head_dim,
               "groups": groups, "state": state, "chunk": chunk,
               "rope_theta": rope_theta,
               "embedding_multiplier": embedding_multiplier,
               "lm_head_multiplier": lm_head_multiplier,
               "attention_in_multiplier": attention_in_multiplier,
               "attention_out_multiplier": attention_out_multiplier,
               "key_multiplier": key_multiplier,
               "ssm_in_multiplier": ssm_in_multiplier,
               "ssm_out_multiplier": ssm_out_multiplier,
               "ssm_multipliers": tuple(ssm_multipliers),
               "mlp_multipliers": tuple(mlp_multipliers)})


@register("falcon_h1_34b")
def build_falcon_h1_34b(num_classes: int = 261120,
                        input_shape: tuple = (16384,)) -> ModelDef:
    """Falcon-H1-34B-Instruct at its published widths, whole vocabulary,
    layers 0-3 of 72 (every one the parallel block: a 32-head Mamba-2 of
    state 256 on two groups beside a rotary GQA 20Q/4KV, then a 21,504-wide
    SwiGLU) as one pipeline stage of sixteen holds them; 4.39 B parameters
    here, handed over in bfloat16. The layers left out lie on further
    pipeline stages."""
    return build_falcon_h1(
        "falcon_h1_34b", num_classes, tuple(input_shape), layers=4,
        published_layers=72, dim=5120, ffn_width=21504, heads=20, kv_heads=4,
        head_dim=128, mamba_heads=32, mamba_head_dim=128, groups=2,
        state=256, conv=4, embedding_multiplier=5.656854249492381,
        lm_head_multiplier=0.0078125, attention_in_multiplier=1.0,
        attention_out_multiplier=0.0375,
        key_multiplier=0.011048543456039804, ssm_in_multiplier=0.25,
        ssm_out_multiplier=0.08838834764831845,
        ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                         0.3535533905932738),
        mlp_multipliers=(0.1767766952966369, 0.011160714285714284))


@register("falcon_h1_tiny")
def build_falcon_h1_tiny(num_classes: int = 96, input_shape: tuple = (40,),
                         param_dtype=jnp.float32) -> ModelDef:
    """The same code at toy widths, in float32: for the tests and the
    benchmark's rehearsal on the CPU. Three parallel blocks; 4 Mamba-2 heads
    of 8 on a state of 12 (unequal to the head) in 2 groups; 40 tokens are no
    multiple of its chunk of 16; 10 query heads on 2 key heads of 8 (five a
    key head); all fourteen scalars off 1 and unequal."""
    return build_falcon_h1(
        "falcon_h1_tiny", num_classes, tuple(input_shape), layers=3,
        published_layers=6, dim=40, ffn_width=72, heads=10, kv_heads=2,
        head_dim=8, mamba_heads=4, mamba_head_dim=8, groups=2, state=12,
        conv=4, embedding_multiplier=2.5, lm_head_multiplier=0.3,
        attention_in_multiplier=1.3, attention_out_multiplier=0.45,
        key_multiplier=0.6, ssm_in_multiplier=0.75, ssm_out_multiplier=0.55,
        ssm_multipliers=(0.8, 0.7, 0.65, 0.9, 0.85),
        mlp_multipliers=(0.35, 0.4), rope_theta=100.0, chunk=16,
        attention_block=16, param_dtype=param_dtype)
