"""Kimi K2 (``model_type`` ``kimi_k2``: DeepSeek-V3's block) as a scorer of
token records: a window of token ids in, the next-token distribution at its
last position out, through the same engine and topology as every other model.

Every block is ``x += MLA(RMSNorm(x)); x += F(RMSNorm(x))``. *Every* mixer is
multi-head latent attention (``models/kimi_linear.py mla_mixer``, shared with
Kimi-Linear) with low-rank queries (``q_a``, an RMS norm, ``q_b``) and rotary
position code on each query head's ``rope`` channels and on the one shared
key's (:mod:`storm_tpu.ops.rope`: YaRN's blended frequencies, its ``mscale^2``
on the softmax's scale). ``F`` is a dense SwiGLU in the first ``first_dense``
blocks and the dropless sigmoid top-k expert layer with a shared expert after
(:func:`storm_tpu.parallel.moe.topk_moe_layer`).

**One chip's share**, as ``models/kimi_linear.py``: ``experts_held`` routed
experts from ``first_expert`` and ``num_classes`` rows of embedding and head;
the router keeps its published width. The language model only: a vision
tower in front of it is no part of this file.

**The load** is ``models/scorer.py``'s in ``param_dtype``, a program a
layer: a float32 twin of 3.5 B parameters does not fit beside them. The
engine's cast leaves such leaves alone.

The skeleton (ids, the float32 stream, the head, the counters' way out) is
:func:`storm_tpu.models.scorer.token_scorer`'s; this file holds the plan.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from storm_tpu.models import scorer as S
from storm_tpu.models.kimi_linear import mla_mixer, mla_mixer_init
from storm_tpu.models.registry import ModelDef, register
from storm_tpu.ops import layers as L
from storm_tpu.ops import parts as P
from storm_tpu.ops import rope as R
from storm_tpu.ops.platform import note as _note
from storm_tpu.parallel.moe import topk_moe_init


def build_kimi_k2(
    name: str,
    num_classes: int,
    input_shape: tuple,
    *,
    dim: int,
    layers: int,
    heads: int,
    nope: int,
    rope: int,
    v_dim: int,
    q_rank: int,
    kv_rank: int,
    dense_width: int,
    expert_width: int,
    n_experts: int,
    top_k: int,
    experts_held: int,
    first_expert: int = 0,
    first_dense: int = 1,
    routed_scale: float = 2.827,
    eps: float = 1e-5,
    rope_theta: float = 50000.0,
    yarn_factor: float = 64.0,
    yarn_original: int = 4096,
    beta_fast: float = 32.0,
    beta_slow: float = 1.0,
    mscale: float = 1.0,
    mscale_all_dim: float = 1.0,
    expert_tile: Optional[int] = None,
    max_rows: int = 4,
    published_layers: int = 61,
    param_dtype=jnp.bfloat16,
) -> ModelDef:
    """Layers ``0..layers-1`` of the published stack (dense feed-forward in
    the first ``first_dense``, experts after) over ``num_classes`` rows of
    the vocabulary."""
    # as models/kimi_linear.py: every residual branch's output projection
    # starts smaller by the root of the branches of the published stack
    branch = (2 * published_layers) ** -0.5
    inv_freq = R.yarn_inv_freq(rope, rope_theta, yarn_factor, yarn_original,
                               beta_fast, beta_slow)
    m = R.yarn_mscale(yarn_factor, mscale_all_dim)
    softmax_scale = (nope + rope) ** -0.5 * m * m
    attention_factor = R.yarn_mscale(yarn_factor, mscale) / m

    def mixer_init(key):
        mixer = S.scaled(mla_mixer_init(key, dim, heads, nope, rope, v_dim,
                                        kv_rank, q_rank), {"o": branch})
        # the draw stands for a checkpoint, whose rotary channels lie in
        # interleaved pairs: the loader's one reorder (ops/rope.py)
        q_b = mixer["q_b"].reshape(q_rank, heads, nope + rope)
        mixer["q_b"] = R.halves_first(q_b, first=nope).reshape(q_rank, -1)
        mixer["kv_a"] = R.halves_first(mixer["kv_a"], first=kv_rank)
        return mixer

    def rotary(seq):
        _note("rotary", "yarn")
        return R.rotary_tables(seq, inv_freq, attention_factor)

    mixer = S.Branch(
        "norm1", "mixer", mixer_init,
        lambda p, y, tables: mla_mixer(p, y, heads, nope, rope, v_dim,
                                       kv_rank, eps, rotary=tables,
                                       scale=softmax_scale))
    dense = S.Branch(
        "norm2", "ffn",
        lambda key: S.scaled(L.swiglu_init(key, dim, dense_width),
                             {"down": branch}),
        lambda p, y, _: L.swiglu(p, y), scope=P.PROJ, cast="scope")
    # an untrained bias of the size of the gaps between sorted scores,
    # N(0, 0.01^2), so that it matters and the held share stays near its
    # expectation (PERF.md section 6, PR 36)
    experts = S.experts(
        "norm2", "ffn",
        lambda key: S.scaled(topk_moe_init(
            key, dim, expert_width, n_experts, experts_held),
            {"router_bias": 0.2, "down": branch}),
        held=experts_held, top_k=top_k, first_expert=first_expert,
        scale=routed_scale, tile=expert_tile)
    return S.token_scorer(
        name, num_classes, input_shape,
        tuple((mixer, dense if i < first_dense else experts)
              for i in range(layers)),
        dim=dim, eps=eps, max_rows=max_rows, context=rotary,
        param_dtype=param_dtype,
        hyper={"dim": dim, "layers": layers, "heads": heads,
               "q_rank": q_rank, "kv_rank": kv_rank, "n_experts": n_experts,
               "top_k": top_k, "experts_held": experts_held,
               "first_expert": first_expert, "rope_theta": rope_theta,
               "yarn_factor": yarn_factor})


@register("kimi_k2_6")
def build_kimi_k2_6(num_classes: int = 20480,
                    input_shape: tuple = (4096,)) -> ModelDef:
    """Kimi-K2.6's language model at its published widths, as one chip of
    the 32 that share each layer holds it: layers 0-4 (the dense one and
    four expert layers), routed experts 0-11 of 384, an eighth of the
    vocabulary; 3.50 B parameters here, handed over in bfloat16, the
    checkpoint's own type. The layers left out lie on further pipeline
    stages."""
    return build_kimi_k2(
        "kimi_k2_6", num_classes, tuple(input_shape), dim=7168, layers=5,
        heads=64, nope=128, rope=64, v_dim=128, q_rank=1536, kv_rank=512,
        dense_width=18432, expert_width=2048, n_experts=384, top_k=8,
        experts_held=12)


@register("kimi_k2_tiny")
def build_kimi_k2_tiny(num_classes: int = 96, input_shape: tuple = (40,),
                       param_dtype=jnp.float32) -> ModelDef:
    """The same code at toy widths, in float32: for the tests and the
    benchmark's rehearsal on the CPU. YaRN's original window is 32 of the 40
    positions: of the four pairs one is plain, two blended, one stretched."""
    return build_kimi_k2(
        "kimi_k2_tiny", num_classes, tuple(input_shape), dim=64, layers=3,
        heads=4, nope=16, rope=8, v_dim=16, q_rank=24, kv_rank=24,
        dense_width=128, expert_width=32, n_experts=16, top_k=2,
        experts_held=4, rope_theta=10.0, yarn_factor=4.0, yarn_original=32,
        beta_fast=4.0, beta_slow=1.0, expert_tile=16, published_layers=6,
        param_dtype=param_dtype)
