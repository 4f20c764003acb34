"""Solar Open 2 (``model_type`` ``solar_open2``) as a scorer of token records:
a window of token ids in, the next-token distribution at its last position
out, through the same engine and topology as every other model.

Every block is ``x += mixer(RMSNorm(x)); x += experts(RMSNorm(x))``. The
mixers come in periods of ``period`` layers. The first of a period
(``gqa_layers``) is causal softmax attention with grouped queries, no
position code (``use_rope`` false) and an output gate (``use_gqa_gate``):
``models/nemotron_h.py gqa_mixer`` with a ``gate`` among its parameters. The
others are Kimi Delta Attention, ``models/kimi_linear.py kda_mixer`` (shared
with Kimi-Linear), with the delta rule's step in (0, 2)
(``kda_allow_neg_eigval``: a transition may reflect along its key, not only
shrink). *Every* layer's feed-forward is the dropless sigmoid top-k expert
layer with a shared expert (:func:`storm_tpu.parallel.moe.topk_moe_layer`):
``first_k_dense_replace`` is 0, so block 0 routes.

**One chip's share**, as ``models/kimi_linear.py``: ``experts_held`` routed
experts from ``first_expert`` and ``num_classes`` rows of embedding and head;
the router keeps its published width and its experts per token.

**The load** is ``models/scorer.py``'s in ``param_dtype``, a program a kind
of block: a float32 twin of 3.3 B parameters does not fit beside them.

What the published ``config.json`` does not fix is set by the family's
convention and listed under ``assumed`` in the benchmark's configuration
file: the gate of the softmax layer elementwise and full-rank from the
block's input, no head norms on q and k, the decay's and the KDA gate's
rank-``head_dim`` pairs and the decay's parametrisation as Kimi-Linear's, a
sigmoid router, the initialisers.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from storm_tpu.models import scorer as S
from storm_tpu.models.kimi_linear import kda_mixer, kda_mixer_init
from storm_tpu.models.nemotron_h import gqa_mixer, gqa_mixer_init
from storm_tpu.models.registry import ModelDef, register
from storm_tpu.parallel.moe import topk_moe_init


def build_solar_open2(
    name: str,
    num_classes: int,
    input_shape: tuple,
    *,
    dim: int,
    layers: int,
    heads: int,
    kv_heads: int,
    head_dim: int,
    kda_heads: int,
    kda_head_dim: int,
    conv: int,
    expert_width: int,
    n_experts: int,
    top_k: int,
    experts_held: int,
    first_expert: int = 0,
    period: int = 4,
    routed_scale: float = 1.0,
    eps: float = 1e-5,
    chunk: int = 64,
    expert_tile: Optional[int] = None,
    max_rows: int = 8,
    published_layers: int = 48,
    param_dtype=jnp.bfloat16,
) -> ModelDef:
    """Layers ``0..layers-1`` of the published stack (layer ``i`` is gated
    grouped-query attention where ``i`` is a multiple of ``period``, KDA
    otherwise; experts in every one) over ``num_classes`` rows of the
    vocabulary."""
    # as models/kimi_linear.py: every residual branch's output projection
    # starts smaller by the root of the branches of the published stack
    branch = (2 * published_layers) ** -0.5
    gqa_branch = S.Branch(
        "norm1", "mixer",
        lambda key: S.scaled(gqa_mixer_init(
            key, dim, heads, kv_heads, head_dim, gate=True), {"o": branch}),
        lambda p, y, _: gqa_mixer(p, y, heads, kv_heads, head_dim))
    kda_branch = S.Branch(
        "norm1", "mixer",
        lambda key: S.scaled(kda_mixer_init(
            key, dim, kda_heads, kda_head_dim, conv), {"o": branch}),
        lambda p, y, _: kda_mixer(p, y, kda_heads, kda_head_dim, chunk, eps,
                                  step_range=2.0))
    # the selection bias N(0, 0.01^2), as models/kimi_k2.py's
    experts = S.experts(
        "norm2", "ffn",
        lambda key: S.scaled(topk_moe_init(
            key, dim, expert_width, n_experts, experts_held),
            {"router_bias": 0.2, "down": branch}),
        held=experts_held, top_k=top_k, first_expert=first_expert,
        scale=routed_scale, tile=expert_tile)
    return S.token_scorer(
        name, num_classes, input_shape,
        tuple((kda_branch if i % period else gqa_branch, experts)
              for i in range(layers)),
        dim=dim, eps=eps, max_rows=max_rows, param_dtype=param_dtype,
        hyper={"dim": dim, "layers": layers, "heads": heads,
               "kv_heads": kv_heads, "head_dim": head_dim,
               "kda_heads": kda_heads, "kda_head_dim": kda_head_dim,
               "period": period, "n_experts": n_experts, "top_k": top_k,
               "experts_held": experts_held, "first_expert": first_expert,
               "chunk": chunk})


@register("solar_open2_250b")
def build_solar_open2_250b(num_classes: int = 24576,
                           input_shape: tuple = (4096,)) -> ModelDef:
    """Solar-Open2-250B at its published widths, as one chip of the eight
    that share each layer holds it: layers 0-3 of 48 (gated GQA, then KDA,
    KDA, KDA: one period, an expert layer in each), routed experts 0-39 of
    320, an eighth of the vocabulary; 3.31 B parameters here, handed over in
    bfloat16. The layers left out lie on further pipeline stages."""
    return build_solar_open2(
        "solar_open2_250b", num_classes, tuple(input_shape), dim=4096,
        layers=4, heads=64, kv_heads=8, head_dim=128, kda_heads=64,
        kda_head_dim=128, conv=4, expert_width=1280, n_experts=320, top_k=8,
        experts_held=40)


@register("solar_open2_tiny")
def build_solar_open2_tiny(num_classes: int = 96, input_shape: tuple = (40,),
                           param_dtype=jnp.float32) -> ModelDef:
    """The same code at toy widths, in float32, a whole period: for the
    tests and the benchmark's rehearsal on the CPU. A router of 20 columns
    (no power of two) of which 5 are held, 4 query heads a key head, 40
    tokens that are no multiple of the chunk of 16."""
    return build_solar_open2(
        "solar_open2_tiny", num_classes, tuple(input_shape), dim=64,
        layers=4, heads=8, kv_heads=2, head_dim=16, kda_heads=4,
        kda_head_dim=16, conv=4, expert_width=32, n_experts=20, top_k=2,
        experts_held=5, chunk=16, expert_tile=16, max_rows=4,
        published_layers=8, param_dtype=param_dtype)
