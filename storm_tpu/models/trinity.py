"""Trinity (``model_type`` ``afmoe``) as a scorer of long token records: a
window of token ids in, the next-token distribution at its last position
out, through the same engine and topology as every other model.

The stream starts at ``sqrt(hidden) E[id]`` (``mup_enabled``). Every block is
a sandwich, four norms: ``h += RMSNorm_post1(mixer(RMSNorm_pre1(h))); h +=
RMSNorm_post2(ffn(RMSNorm_pre2(h)))`` (``models/scorer.py Branch.post``).

- The mixer is causal softmax attention with grouped queries (``heads`` over
  ``kv_heads``), an RMS norm over each query and key head, and a sigmoid
  output gate from the block's normed input, a number a channel, before the
  output projection. ``layer_types`` spells the stack, one name a layer:
  a ``sliding`` layer turns q and k by plain rotary position code (all
  ``head_dim`` channels, halves paired: :mod:`storm_tpu.ops.rope`) and a
  query reads its last ``window`` keys alone, itself among them
  (ops/attention.py ``causal_attention_merged(window=...)``: the key blocks
  before a window are never loaded); a ``full`` layer has no position code
  and reads every key before it. q, k, v and the attention's result stay
  ``(B, S, H * head_dim)`` from the projections to the output projection:
  the norm and the turn are one pass over q and over k where they lie
  (ops/rope.py ``norm_turn_merged``), and the kernel reads a head as a block
  of lanes (PERF.md section 6, PR 58).
- The feed-forward is SwiGLU in the ``dense`` leading layers and, after
  them, the dropless sigmoid top-k expert layer with a shared expert
  (:func:`storm_tpu.parallel.moe.topk_moe_layer`): the ``top_k`` largest of
  score + bias, weighted by the score over the chosen scores' sum times
  ``route_scale``.

**The cut** is in depth alone: one pipeline stage's layers, each whole. The
builder is told which layers (their ``layer_types`` and how many leading
ones are dense); every width, every head, *every routed expert* of a layer
(``experts_held`` is the router's width: no assignment is absent) and the
whole vocabulary are here. The load is ``models/scorer.py``'s in
``param_dtype``, a program a kind of block: a float32 twin of 4.2 B
parameters does not fit beside them.

What the published ``config.json`` does not fix is the released modelling
code's and listed under ``assumed`` in the benchmark's configuration file:
the head norms, the gate, rotary in sliding layers only, the two norms a
branch, the embedding's multiplier, that a window counts the query itself,
the selection bias, where the weights and the post-norms' scales start.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from storm_tpu.models import scorer as S
from storm_tpu.models.minicpm_sala import minicpm4_mixer_init
from storm_tpu.models.registry import ModelDef, register
from storm_tpu.models.scorer import _proj
from storm_tpu.ops import layers as L
from storm_tpu.ops import parts as P
from storm_tpu.ops import rope as R
from storm_tpu.ops.attention import causal_attention_merged
from storm_tpu.parallel.moe import topk_moe_init

KINDS = ("sliding", "full")


def trinity_mixer(p: dict, x: jnp.ndarray, heads: int, kv_heads: int,
                  head_dim: int, eps: float, rotary, window, block: int = 512):
    """Grouped causal attention with head norms on q and k and a sigmoid
    gate on the result. ``window`` None: a full layer, no position code; a
    number: a sliding layer, q and k turned by ``rotary``'s tables ``(S,
    head_dim / 2)`` where they lie in their projections, then each query
    over its last ``window`` keys."""
    # q, k, v and the result stay as the projections leave them, ``(B, S, H *
    # head_dim)``, a head a block of lanes to the norm, the turn and the
    # kernel: a view a head is another tiling on a TPU, each way a copy
    turn = rotary if window is not None else None
    q = R.norm_turn_merged(p["q_norm"], _proj(x, p["q"]), heads, eps, turn)
    k = R.norm_turn_merged(p["k_norm"], _proj(x, p["k"]), kv_heads, eps, turn)
    out = causal_attention_merged(
        q, k, _proj(x, p["v"]), heads, kv_heads, scale=head_dim ** -0.5,
        block=block, window=window)
    return _proj(out * jax.nn.sigmoid(_proj(x, p["gate"])), p["o"])


def build_trinity(
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
    window: int,
    dense_width: int,
    expert_width: int,
    n_experts: int,
    top_k: int,
    experts_held: int,
    first_expert: int = 0,
    route_scale: float = 2.826,
    rope_theta: float = 10000.0,
    eps: float = 1e-5,
    expert_tile: Optional[int] = None,
    attention_block: int = 512,
    max_rows: int = 4,
    param_dtype=jnp.bfloat16,
) -> ModelDef:
    """The layers that ``layer_types`` spells (the held ones of the
    published list, ``published_layers`` long; the first ``dense`` of them
    with a dense feed-forward, experts in the others) over ``num_classes``
    rows of the vocabulary."""
    if not layer_types or set(layer_types) - set(KINDS):
        raise ValueError(f"layer_types {layer_types!r}: the kinds are "
                         f"{KINDS!r}")
    # Where a post-norm's scales start: what a branch adds to the stream a
    # channel, whatever its own weights' scale (every projection is LeCun's).
    # As models/kimi_linear.py's 1/sqrt(2 x layers) on a branch's output
    # projection, it keeps the stream at the embedding's scale whatever the
    # depth, so that no one branch (nor one expert a rounding sent a token
    # to) outweighs it; but a normed branch adds its *whole* scale, where a
    # LeCun projection under that factor adds a tenth to a half of it, and
    # here every expert is held, so every flipped assignment shows. At 1 a
    # row whose last token a bfloat16 rounding sent to another expert lay
    # 0.23-0.33 from the float32 reference, at 1/sqrt(2 x 32) 0.035-0.058,
    # where the same program in float8 reads 0.052-0.106: no limit parts
    # them (PERF.md section 6, PR 57; PRs 34 and 36 met the same).
    post_scale = 1.0 / published_layers
    inv_freq = rope_theta ** (-2.0 * np.arange(head_dim // 2)
                              / head_dim)  # plain rotary, float64

    def mixer_init(key):  # one for both kinds: one program of the load
        return minicpm4_mixer_init(key, dim, heads, kv_heads, head_dim)

    def mixer(kind: str) -> S.Branch:
        reach = window if kind == "sliding" else None
        return S.Branch(
            "norm1", "mixer", mixer_init,
            lambda p, y, rotary: trinity_mixer(
                p, y, heads, kv_heads, head_dim, eps, rotary, reach,
                attention_block),
            post="post1", post_scale=post_scale)

    mixers = {kind: mixer(kind) for kind in KINDS}
    ffn = S.Branch(
        "norm2", "ffn",
        lambda key: L.swiglu_init(key, dim, dense_width),
        lambda p, y, _: L.swiglu(p, y), scope=P.PROJ, cast="scope",
        post="post2", post_scale=post_scale)
    # the selection bias N(0, 0.01^2), as models/kimi_k2.py's
    experts = S.experts(
        "norm2", "ffn",
        lambda key: S.scaled(topk_moe_init(
            key, dim, expert_width, n_experts, experts_held),
            {"router_bias": 0.2}),
        held=experts_held, top_k=top_k, first_expert=first_expert,
        scale=route_scale, tile=expert_tile, post="post2",
        post_scale=post_scale)
    return S.token_scorer(
        name, num_classes, input_shape,
        tuple((mixers[kind], ffn if i < dense else experts)
              for i, kind in enumerate(layer_types)),
        dim=dim, eps=eps, max_rows=max_rows, scale_emb=math.sqrt(dim),
        context=lambda seq: R.rotary_tables(seq, inv_freq),
        param_dtype=param_dtype,
        hyper={"layer_types": tuple(layer_types), "dense": dense, "dim": dim,
               "heads": heads, "kv_heads": kv_heads, "head_dim": head_dim,
               "window": window, "n_experts": n_experts, "top_k": top_k,
               "experts_held": experts_held, "first_expert": first_expert,
               "rope_theta": rope_theta})


@register("trinity_mini")
def build_trinity_mini(num_classes: int = 200192,
                       input_shape: tuple = (16384,)) -> ModelDef:
    """Trinity-Mini (26B-A3B) at its published widths, as one pipeline stage
    of eight holds its layers, each whole: published layer 1 (sliding, dense
    feed-forward) and layers 4-7 (sliding, sliding, sliding, full: one
    period, all 128 routed experts and the shared one in each), the whole
    vocabulary; 4.24 B parameters here, handed over in bfloat16. The layers
    left out lie on further pipeline stages."""
    return build_trinity(
        "trinity_mini", num_classes, tuple(input_shape),
        layer_types=("sliding",) * 4 + ("full",), dense=1,
        published_layers=32, dim=2048, heads=32, kv_heads=4, head_dim=128,
        window=2048, dense_width=6144, expert_width=1024, n_experts=128,
        top_k=8, experts_held=128)


@register("trinity_tiny")
def build_trinity_tiny(num_classes: int = 96, input_shape: tuple = (40,),
                       param_dtype=jnp.float32) -> ModelDef:
    """The same code at toy widths, in float32, a dense block and a whole
    period: for the tests and the benchmark's rehearsal on the CPU. A window
    of 12 keys, no multiple of the blocked form's 16 queries and shorter than
    the 40 tokens (no multiple either), 4 query heads a key head, a router of
    20 columns (no power of two), all held."""
    return build_trinity(
        "trinity_tiny", num_classes, tuple(input_shape),
        layer_types=("sliding",) * 4 + ("full",), dense=1,
        published_layers=8, dim=64, heads=8, kv_heads=2, head_dim=16,
        window=12, dense_width=128, expert_width=32, n_experts=20, top_k=2,
        experts_held=20, rope_theta=100.0, expert_tile=16,
        attention_block=16, param_dtype=param_dtype)
