"""Nemotron-H (``model_type`` ``nemotron_h``) as a scorer of token records: a
window of token ids in, the next-token distribution at its last position
out, through the same engine and topology as every other model.

The stack is described by a pattern string, one letter a layer, and every
layer is one pre-normed mixer and a residual add, ``h += mixer(RMSNorm(h))``:

- ``M``: a Mamba-2 state-space layer. One projection gives the gate ``z``,
  ``x | B | C`` and a step ``dt`` a head; ``x | B | C`` goes through a causal
  depthwise convolution with a bias and SiLU; the state of each head (``P x
  N``, one scalar decay a head, ``B`` and ``C`` shared by the heads of a
  group) is computed in chunks (:mod:`storm_tpu.ops.ssd`); the result is
  gated by ``silu(z)``, RMS-normed over each group of channels and projected
  back.
- ``E``: the dropless top-k expert layer with a shared expert
  (:func:`storm_tpu.parallel.moe.topk_moe_layer`), its experts two matrices
  with a squared ReLU between, the shared one at a width of its own.
- ``*``: causal attention with grouped queries and no position embedding
  (:func:`storm_tpu.ops.attention.causal_attention`): the family uses none,
  the state-space layers carry the order.

**One chip's share**, as ``models/kimi_linear.py`` has it: the builder is
told how many routed experts and how many rows of the vocabulary this chip
holds (``experts_held`` from ``first_expert``, ``num_classes`` rows of
embedding and of head) and which letters of the pattern; the router keeps
its published width and its experts per token, and what the experts held
elsewhere would add is left out. The step's counters ride
``new_state["aux"]`` to ``parallel/moe.py observe_expert_counts``. The
skeleton is :func:`storm_tpu.models.scorer.token_scorer`'s; this file holds
the mixers and the plan, a branch a letter.

What the published ``config.json`` does not fix is listed under ``assumed``
in the benchmark's configuration file: the weights' start (a mixer's
projections at the released code's variance, a third of LeCun's, its output
projection over the root of the published depth beside,
``rescale_prenorm_residual``; ``A``, the step's bias and ``D`` as the
released code starts them), a float32 stream, the chunk and the tile.
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
from storm_tpu.ops import kda
from storm_tpu.ops import layers as L
from storm_tpu.ops.attention import causal_attention
from storm_tpu.ops.ssd import ssd_chunked_columns
from storm_tpu.parallel.moe import topk_moe_init

KINDS = "ME*"  # Mamba-2, experts, attention


def mamba_mixer_init(rng, dim: int, heads: int, head_dim: int, groups: int,
                     state: int, conv: int, dt_min: float = 1e-3,
                     dt_max: float = 1e-1, dt_floor: float = 1e-4) -> dict:
    inner, bc = heads * head_dim, 2 * groups * state
    ks = jax.random.split(rng, 5)
    # a step of dt_min..dt_max through the softplus, A in [1, 16]
    step = jnp.maximum(jnp.exp(jax.random.uniform(
        ks[3], (heads,), jnp.float32, math.log(dt_min), math.log(dt_max))),
        dt_floor)
    return {
        "in_proj": _w(ks[0], dim, 2 * inner + bc + heads),
        "conv": kda.short_conv_init(ks[1], inner + bc, conv, bias=True),
        "a_log": jnp.log(jax.random.uniform(ks[2], (heads,), jnp.float32,
                                            1.0, 16.0)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "d": jnp.ones((heads,), jnp.float32),
        "norm": L.rmsnorm_init(inner),
        "out_proj": _w(ks[4], inner, dim),
    }


def mamba_mixer(p: dict, x: jnp.ndarray, heads: int, head_dim: int,
                groups: int, state: int, chunk: int, eps: float,
                scales: Optional[tuple] = None) -> jnp.ndarray:
    """The Mamba-2 layer of the module's header. ``scales`` (None: there are
    none, and the text is what it was): five numbers, one a segment of the
    projection's columns ``[z | x | B | C | dt]``, that the projection's
    result is multiplied by (Falcon-H1's ``ssm_multipliers``, with
    ``ssm_in_multiplier`` on the input folded in: the projection is linear).
    Each is applied in float32 where its segment is next read, so none costs
    a pass over the projection nor a rounding: ``z``'s inside the gated
    norm's gate, ``x``'s, ``B``'s and ``C``'s on the convolution's taps (a
    depthwise convolution is linear a channel, its bias is not scaled) and
    the step's before its bias and softplus."""
    f32 = jnp.float32
    inner, gn = heads * head_dim, groups * state
    # in_proj's columns are [z | x B C | dt]
    z = _proj(x, p["in_proj"][:, :inner])
    xbcdt = _proj(x, p["in_proj"][:, inner:])
    conv = p["conv"]
    if scales is not None:
        of_z, of_x, of_b, of_c, of_dt = scales
        conv = {**conv, "w": conv["w"].astype(f32) * np.repeat(
            np.asarray([of_x, of_b, of_c], np.float32), [inner, gn, gn])}
        z = z.astype(f32) * of_z
    xbc = kda.conv_silu(conv, xbcdt)
    dt = xbcdt[..., inner + 2 * gn:].astype(f32)
    if scales is not None:
        dt = dt * of_dt
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(f32))
    # x | B | C whole, as the convolution wrote them: the scan's loop takes
    # the columns apart a chunk at a time
    y = ssd_chunked_columns(xbc, dt, -jnp.exp(p["a_log"].astype(f32)),
                            p["d"], groups, state, chunk)
    y = L.gated_group_rmsnorm(p["norm"], y, z, groups, eps)
    return _proj(y, p["out_proj"])


def gqa_mixer_init(rng, dim: int, heads: int, kv_heads: int,
                   head_dim: int, gate: bool = False) -> dict:
    """``gate``: one more full projection, of the output gate."""
    ks = jax.random.split(rng, 4)  # as ever: the ungated draw is unmoved
    p = {"q": _w(ks[0], dim, heads * head_dim),
         "k": _w(ks[1], dim, kv_heads * head_dim),
         "v": _w(ks[2], dim, kv_heads * head_dim),
         "o": _w(ks[3], heads * head_dim, dim)}
    if gate:
        p["gate"] = _w(jax.random.fold_in(rng, 4), dim, heads * head_dim)
    return p


def gqa_mixer(p: dict, x: jnp.ndarray, heads: int, kv_heads: int,
              head_dim: int, scale: Optional[float] = None) -> jnp.ndarray:
    """Causal attention, ``heads`` query heads over ``kv_heads`` key/value
    heads, no bias, no position embedding; where the parameters hold a
    ``gate``, the result times ``sigmoid(W_gate x)``, a number a channel,
    before the output projection. ``scale``: what the scores are multiplied
    by before the softmax (None: ``head_dim ** -0.5``; a model that publishes
    an ``attention_multiplier`` hands it over)."""
    if scale is None:
        scale = head_dim ** -0.5
    b, s, _ = x.shape

    def split(name, n):
        return _proj(x, p[name]).reshape(b, s, n, head_dim).transpose(
            0, 2, 1, 3)

    out = causal_attention(split("q", heads), split("k", kv_heads),
                           split("v", kv_heads), scale=scale)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, heads * head_dim)
    if "gate" in p:
        out = out * jax.nn.sigmoid(_proj(x, p["gate"]))
    return _proj(out, p["o"])


def build_nemotron_h(
    name: str,
    num_classes: int,
    input_shape: tuple,
    *,
    pattern: str,
    published_layers: int,
    dim: int,
    mamba_heads: int,
    mamba_head_dim: int,
    groups: int,
    state: int,
    conv: int,
    heads: int,
    kv_heads: int,
    head_dim: int,
    expert_width: int,
    shared_width: int,
    n_experts: int,
    top_k: int,
    experts_held: int,
    first_expert: int = 0,
    routed_scale: float = 2.5,
    eps: float = 1e-5,
    chunk: int = 128,
    expert_tile: Optional[int] = None,
    max_rows: int = 8,
) -> ModelDef:
    """The layers that ``pattern`` spells (the held letters of the published
    ``hybrid_override_pattern``, ``published_layers`` long) over
    ``num_classes`` rows of the vocabulary."""
    if not pattern or set(pattern) - set(KINDS):
        raise ValueError(f"pattern {pattern!r}: its letters are {KINDS!r}")
    # Where the weights start. Every projection inside a mixer at the
    # variance the released code gives a linear layer (uniform in
    # +-1/sqrt(fan_in): a third of LeCun's), and its output projection
    # smaller again by the root of the published depth
    # (``rescale_prenorm_residual``), so that the stream keeps the
    # embedding's scale whatever the depth and no one branch (nor one
    # expert a rounding sent a token to) outweighs it: at LeCun's scale a
    # row whose last token went to another expert moved by more than
    # float8 moves most rows (PERF.md section 6, PR 36). The selection
    # bias N(0, 0.01^2), a fifth of ``topk_moe_init``'s: it still decides
    # which experts many tokens take, and a random one of 0.05 unbalances
    # the experts 3.5-fold, which a trained one is there to prevent.
    inner, branch = 3 ** -0.5, (3 * published_layers) ** -0.5
    # a mixer takes the float32 norm's cast under its own scope
    kinds = {
        "M": S.Branch(
            "norm", "mixer",
            lambda key: S.scaled(mamba_mixer_init(
                key, dim, mamba_heads, mamba_head_dim, groups, state, conv),
                {"in_proj": inner, "out_proj": branch}),
            lambda p, y, _: mamba_mixer(p, y, mamba_heads, mamba_head_dim,
                                        groups, state, chunk, eps),
            cast="scope"),
        "*": S.Branch(
            "norm", "mixer",
            lambda key: S.scaled(gqa_mixer_init(
                key, dim, heads, kv_heads, head_dim),
                {"q": inner, "k": inner, "v": inner, "o": branch}),
            lambda p, y, _: gqa_mixer(p, y, heads, kv_heads, head_dim),
            cast="scope"),
        "E": S.experts(
            "norm", "mixer",
            lambda key: S.scaled(topk_moe_init(
                key, dim, expert_width, n_experts, experts_held,
                form="relu2", shared_hidden=shared_width),
                {"router_bias": 0.2, "up": inner, "down": branch}),
            held=experts_held, top_k=top_k, first_expert=first_expert,
            scale=routed_scale, tile=expert_tile),
    }
    return S.token_scorer(
        name, num_classes, input_shape,
        tuple((kinds[letter],) for letter in pattern),
        dim=dim, eps=eps, max_rows=max_rows,
        hyper={"pattern": pattern, "dim": dim, "mamba_heads": mamba_heads,
               "mamba_head_dim": mamba_head_dim, "groups": groups,
               "state": state, "heads": heads, "kv_heads": kv_heads,
               "head_dim": head_dim, "n_experts": n_experts, "top_k": top_k,
               "experts_held": experts_held, "first_expert": first_expert,
               "chunk": chunk})


@register("nemotron_3_nano_30b")
def build_nemotron_3_nano_30b(num_classes: int = 32768,
                              input_shape: tuple = (4096,)) -> ModelDef:
    """NVIDIA-Nemotron-3-Nano-30B-A3B at its published widths, as one chip of
    the four that share each layer holds it: layers 0-8 of 52 (``MEMEM*EME``:
    four Mamba-2, four expert layers, one attention), routed experts 0-31 of
    128, a quarter of the vocabulary; 1.71 B parameters here. The layers
    left out lie on further pipeline stages."""
    return build_nemotron_h(
        "nemotron_3_nano_30b", num_classes, tuple(input_shape),
        pattern="MEMEM*EME", published_layers=52, dim=2688, mamba_heads=64,
        mamba_head_dim=64, groups=8, state=128, conv=4, heads=32, kv_heads=2,
        head_dim=128, expert_width=1856, shared_width=3712, n_experts=128,
        top_k=6, experts_held=32)


@register("nemotron_h_tiny")
def build_nemotron_h_tiny(num_classes: int = 96,
                          input_shape: tuple = (44,)) -> ModelDef:
    """The same code at toy widths, all three kinds of layer: for the tests
    and the benchmark's rehearsal on the CPU. 44 tokens are no multiple of
    its chunk of 16."""
    return build_nemotron_h(
        "nemotron_h_tiny", num_classes, tuple(input_shape),
        pattern="MEM*E", published_layers=10, dim=64, mamba_heads=4,
        mamba_head_dim=8, groups=2, state=16, conv=4, heads=4, kv_heads=2,
        head_dim=16, expert_width=32, shared_width=64, n_experts=8, top_k=2,
        experts_held=4, chunk=16, expert_tile=16)
