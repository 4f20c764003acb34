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
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from storm_tpu.ops import parts as P


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
