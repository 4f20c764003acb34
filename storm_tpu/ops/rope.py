"""Rotary position code with YaRN's blended frequencies (Peng et al. 2023,
"YaRN: efficient context window extension of large language models", as
DeepSeek-V3's released code and ``kimi_k2`` apply it).

A head's ``dim`` rotary channels are ``dim / 2`` pairs; pair ``i`` of a token
at position ``t`` is turned by the angle ``t * inv_freq[i]``, so the score
of a query and a key depends on the distance of their positions alone.

**YaRN.** The plain frequencies ``f_i = theta^(-2i/dim)`` were trained over
``original`` positions. Pairs that turn often inside that window (``beta_fast``
turns or more) keep ``f_i``; pairs that turn less than ``beta_slow`` times are
stretched by ``factor`` (``f_i / factor``: interpolation); between the two a
linear ramp blends them. The softmax's scale takes ``mscale^2`` beside it
(:func:`yarn_mscale`): longer windows flatten the scores.

**Pairing.** The released checkpoints pair channels ``(2i, 2i + 1)``
(interleaved); their code brings each head's channels to ``(evens, odds)``
and pairs ``(i, i + dim/2)`` from there. Scores are the same whichever
order the rotated channels lie in, as long as queries and keys share it:
``(x W) P = x (W P)``. So a loader reorders the output columns of the two
projections that make rotary channels once (:func:`halves_first`), and a step
only turns contiguous halves (:func:`rotate_halves`): no activation is ever
shuffled across lanes, and no weight after the load.

**Merged heads.** Where every channel of every head turns, heads of whole
lane tiles can be turned where they lie in a projection's ``(B, S, H * D)``
(:func:`turn_merged`): the view ``(B, S, H, D)`` is another tiling on a TPU
and half a head another still, each a copy of the whole array. A head's
halves change places by one rotation of its 128 lanes; heads of 64 lie two
to a lane tile, their halves its quarters, and each lane takes its partner
from one of two rotations of the tile. Where the turned q and k would go
straight to the merged causal kernel, heads of 128 are not turned here at
all: that kernel does it on the tiles it holds in VMEM
(ops/flash_attention.py ``_turning_kernel``, with :func:`_turned` and
:func:`_lane_tables` of this file).
"""

from __future__ import annotations

import math

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from storm_tpu.ops import parts as P
from storm_tpu.ops.platform import note as _note
from storm_tpu.ops.platform import one_device as _one_device
from storm_tpu.ops.platform import use_pallas as _use_pallas


def yarn_correction_range(dim: int, theta: float, original: int,
                          beta_fast: float, beta_slow: float) -> tuple:
    """``(low, high)``: the pairs at which ``original`` positions make
    ``beta_fast`` and ``beta_slow`` whole turns, floor and ceiling, clipped to
    ``[0, dim - 1]`` as the released code clips them."""
    def pair_of(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    return low, high


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float = 32, beta_slow: float = 1) -> np.ndarray:
    """The ``dim / 2`` angular frequencies, float64 (a table's builder rounds
    them): ``f / factor`` where the ramp is 1, ``f`` where it is 0."""
    i = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2 * i / dim)
    low, high = yarn_correction_range(dim, theta, original, beta_fast,
                                      beta_slow)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f / factor * ramp + f * (1 - ramp)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """``0.1 * mscale * ln(factor) + 1`` (1 where nothing is stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary_tables(seq: int, inv_freq, attention_factor: float = 1.0):
    """``(cos, sin)``, each ``(seq, dim / 2)`` float32, for positions
    ``0..seq-1``; both carry ``attention_factor`` (YaRN's ``mscale /
    mscale_all_dim``: 1 where the two are equal)."""
    with jax.named_scope(P.MIX_ROPE):
        angle = jnp.arange(seq, dtype=jnp.float32)[:, None] \
            * jnp.asarray(inv_freq, jnp.float32)[None, :]
        return (jnp.cos(angle) * attention_factor,
                jnp.sin(angle) * attention_factor)


def mrope_tables(positions, inv_freq, sections: tuple):
    """:func:`rotary_tables` from several position streams (multimodal
    rotary, M-RoPE): ``positions: (streams, seq)``, and frequency ``i`` of
    ``inv_freq`` reads the stream whose section it lies in, ``sections`` the
    frequencies a stream in their order (``(16, 24, 24)``: stream 0 the
    first 16, stream 1 the next 24, stream 2 the last 24). ``(cos, sin)``,
    each ``(seq, dim / 2)`` float32. A token record's streams are all
    ``arange(seq)`` and the tables are :func:`rotary_tables`' to the bit."""
    if sum(sections) != len(inv_freq) or len(sections) != len(positions):
        raise ValueError(f"sections {tuple(sections)!r} over "
                         f"{len(inv_freq)} frequencies of {len(positions)} "
                         "streams")
    stream = np.repeat(np.arange(len(sections)), sections)
    with jax.named_scope(P.MIX_ROPE):
        angle = jnp.asarray(positions, jnp.float32)[stream].T \
            * jnp.asarray(inv_freq, jnp.float32)[None, :]
        return jnp.cos(angle), jnp.sin(angle)


def halves_first(w: jnp.ndarray, first: int = 0) -> jnp.ndarray:
    """``w``'s last axis from ``first`` on, interleaved pairs ``(2i, 2i +
    1)``, reordered to ``(evens, odds)``; the ``first`` columns before pass.
    The loader's, once a weight: values move, none is rounded."""
    dim = w.shape[-1] - first
    order = np.concatenate([np.arange(first), first + np.arange(0, dim, 2),
                            first + np.arange(1, dim, 2)])
    return w[..., order]


def rotate_halves(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
                  first: int = 0) -> jnp.ndarray:
    """``x (..., first + dim)``: the channels from ``first`` on, which lie
    ``(evens, odds)``, turned by the tables (they broadcast against a half);
    the ``first`` channels before them pass. In float32, back in ``x``'s
    type. One operation with the pass-through beside it, so that a trace
    shows the turn's time under its own name and not the concatenation's."""
    with jax.named_scope(P.MIX_ROPE):
        half = (x.shape[-1] - first) // 2
        a = x[..., first:first + half].astype(jnp.float32)
        b = x[..., first + half:].astype(jnp.float32)
        return jnp.concatenate(
            [x[..., :first], (a * cos - b * sin).astype(x.dtype),
             (b * cos + a * sin).astype(x.dtype)], -1)


_TURN_TILE = 128  # positions a step of the kernel: 1 MB of 4,096 lanes
_LANES = 128  # a lane tile


def turn_form(s: int, d: int, heads: int = 1) -> str:
    """Which form :func:`turn_merged` is built with: ``"lanes"`` (the Pallas
    kernel) on a TPU in a process with one device (a Mosaic call has no
    partitioning rule, ops/platform.py ``one_device``), for whole tiles of
    positions and heads of one lane tile, whose halves one rotation of the
    lanes exchanges, or of half a lane tile where the ``heads`` of them (as
    the caller says: one, unsaid) are whole tiles (two heads a tile:
    :func:`_turned`); ``"halves"`` (:func:`rotate_halves` on the view a
    head) elsewhere."""
    if (_use_pallas() and _one_device() and s % _TURN_TILE == 0
            and (d == _LANES or (2 * d == _LANES and heads % 2 == 0))):
        return "lanes"
    return "halves"


def _lane_tables(cos, sin) -> tuple:
    """The kernels' tables from ``(cos, sin): (S, d / 2)``: a head's lanes
    against ``(cos, cos)`` and its exchanged halves against ``(-sin, sin)``,
    over a lane tile (once where a head is one, twice where two heads are)."""
    times = max(_LANES // (2 * cos.shape[-1]), 1)
    return (jnp.concatenate([cos, cos] * times, -1),
            jnp.concatenate([-sin, sin] * times, -1))


def _turned(y, cos, sin, d: int):
    """A lane tile ``y: (positions, 128)`` of heads of ``d`` lanes turned
    against :func:`_lane_tables`' tables, in float32. A head of 128: its
    halves change places by one rotation of the tile. Heads of 64, two a
    tile: a lane's partner lies a quarter of the tile up in a head's first
    half and a quarter down in its second, so two rotations and a choice by
    the lane."""
    if d == _LANES:
        return y * cos + pltpu.roll(y, d // 2, 1) * sin
    lane = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
    partner = jnp.where(lane % d < d // 2,
                        pltpu.roll(y, _LANES - d // 2, 1),
                        pltpu.roll(y, d // 2, 1))
    return y * cos + partner * sin


def turn_merged(xs: tuple, cos: jnp.ndarray, sin: jnp.ndarray,
                heads: int) -> tuple:
    """Each ``x: (B, S, H * D)`` of ``xs`` with all ``D`` channels of every
    head turned by the tables ``(S, D / 2)``, a head's channels lying
    ``(evens, odds)``: what :func:`rotate_halves` gives on the view ``(B, S,
    H, D)``, in float32 and back in ``x``'s type. One call for all of ``xs``
    (a mixer's queries and keys). A pass of each ``x`` through HBM, noted
    ``rotary_turn=lanes`` or ``halves``. A mixer whose q and k go straight
    to ops/attention.py ``causal_attention_merged`` hands them over
    unturned with ``rotary`` instead: for heads of one lane tile on one chip
    the causal kernel turns them where it holds them (``_turned``, to the
    same bits; ``rotary_turn=causal-kernel``), and that entry calls this
    function everywhere else."""
    b, s, merged = xs[0].shape
    d = merged // heads
    form = turn_form(s, d, heads)
    _note("rotary_turn", form)
    if form == "halves":
        return tuple(rotate_halves(x.reshape(b, s, heads, d), cos[:, None],
                                   sin[:, None]).reshape(b, s, merged)
                     for x in xs)
    with jax.named_scope(P.MIX_ROPE):
        return _turn_lanes(tuple(xs), *_lane_tables(cos, sin), heads=heads)


def _turn_kernel(cos_ref, sin_ref, *refs, d):
    ins, outs = refs[:len(refs) // 2], refs[len(refs) // 2:]
    wide = cos_ref.shape[1]
    cos, sin = cos_ref[...], sin_ref[...]
    for x_ref, o_ref in zip(ins, outs):
        for at in range(0, x_ref.shape[2], wide):
            lanes = pl.ds(at, wide)
            x = x_ref[0, :, lanes].astype(jnp.float32)
            o_ref[0, :, lanes] = _turned(x, cos, sin, d).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "tile", "interpret"))
def _turn_lanes(xs, cos2, sin2, *, heads, tile=_TURN_TILE, interpret=False):
    b, s, merged = xs[0].shape

    def table():
        return pl.BlockSpec((tile, cos2.shape[1]), lambda r, i: (i, 0))

    def wide():
        return pl.BlockSpec((1, tile, merged), lambda r, i: (r, i, 0))

    return tuple(pl.pallas_call(
        functools.partial(_turn_kernel, d=merged // heads),
        grid=(b, s // tile),
        in_specs=[table(), table()] + [wide() for _ in xs],
        out_specs=[wide() for _ in xs],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in xs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(cos2, sin2, *xs))


# ---- the head norm in the turn's pass ------------------------------------------

def norm_turn_merged(p: dict, x: jnp.ndarray, heads: int, eps: float,
                     rotary=None) -> jnp.ndarray:
    """ops/layers.py ``rmsnorm`` of each head of ``x: (B, S, H * D)`` under
    the one learned scale ``p["scale"]: (D,)`` and then, with ``rotary =
    (cos, sin)``, :func:`turn_merged`'s turn of every head, in one pass over
    ``x`` where it lies: a head's block of lanes is read once, normed and
    turned in float32 in VMEM and written once in ``x``'s type (the norm's
    result is not rounded to ``x``'s type between the two). By
    :func:`turn_form`'s rule; elsewhere ops/kda.py ``rmsnorm_heads`` (the
    statistic a product with a 0/1 matrix, two passes over ``x``) and
    :func:`turn_merged`. ``rotary`` None: the norm alone, by the same
    kernel. Noted ``head_norm=kernel`` where the kernel is picked (the other
    form says nothing new: it is ``rmsnorm``'s arithmetic, and the CPU's
    notes are held as they were by tests/benchmark/)."""
    d = x.shape[2] // heads
    if turn_form(x.shape[1], d, heads) != "lanes":
        from storm_tpu.ops.kda import rmsnorm_heads

        y = rmsnorm_heads(p, x, heads, eps)
        return y if rotary is None else turn_merged((y,), *rotary, heads)[0]
    _note("head_norm", "kernel")
    # the one scale over a lane tile: once, or twice where two heads lie in it
    scale = jnp.tile(p["scale"].astype(jnp.float32),
                     max(_LANES // d, 1)).reshape(1, -1)
    if rotary is None:
        return _norm_turn_lanes(x, scale, heads=heads, eps=eps)
    _note("rotary_turn", "lanes")
    with jax.named_scope(P.MIX_ROPE):
        return _norm_turn_lanes(x, scale, *_lane_tables(*rotary),
                                heads=heads, eps=eps)


def _head_mean_squares(x, d: int):
    """The mean of ``x * x`` over each head's ``d`` lanes of a lane tile ``x:
    (positions, 128)``, against ``x``: one number a position where the tile
    is a head, and where it is two heads of 64 each half's own, laid over its
    half."""
    xx = x * x
    if d == x.shape[1]:
        return jnp.mean(xx, -1, keepdims=True)
    first = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) < d
    return jnp.where(
        first, jnp.sum(jnp.where(first, xx, 0.0), -1, keepdims=True),
        jnp.sum(jnp.where(first, 0.0, xx), -1, keepdims=True)) / d


def _norm_turn_kernel(scale_ref, *refs, d, eps):
    *tables, x_ref, o_ref = refs
    wide = scale_ref.shape[1]
    scale = scale_ref[...]
    for at in range(0, x_ref.shape[2], wide):
        lanes = pl.ds(at, wide)
        x = x_ref[0, :, lanes].astype(jnp.float32)
        y = x * jax.lax.rsqrt(_head_mean_squares(x, d) + eps) * scale
        if tables:  # as ``_turn_kernel``
            cos_ref, sin_ref = tables
            y = _turned(y, cos_ref[...], sin_ref[...], d)
        o_ref[0, :, lanes] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "interpret"))
def _norm_turn_lanes(x, scale, *tables, heads, eps, interpret=False):
    b, s, merged = x.shape
    # two of the turn's tiles a step where the positions allow: the norm makes
    # a step of 128 positions compute longer (4.9 us) than its DMA (2.6), and
    # q's 32 heads read 2.61 ms a call at 128 positions, 1.83 at 256, the
    # turn's own 1.81 (PERF.md section 6, PR 58)
    tile = 2 * _TURN_TILE if s % (2 * _TURN_TILE) == 0 else _TURN_TILE
    wide = pl.BlockSpec((1, tile, merged), lambda r, i: (r, i, 0))
    return pl.pallas_call(
        functools.partial(_norm_turn_kernel, d=merged // heads, eps=eps),
        grid=(b, s // tile),
        in_specs=[pl.BlockSpec(scale.shape, lambda r, i: (0, 0))]
        + [pl.BlockSpec((tile, scale.shape[1]), lambda r, i: (i, 0))
           for _ in tables]
        + [wide],
        out_specs=wide,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(scale, *tables, x)
