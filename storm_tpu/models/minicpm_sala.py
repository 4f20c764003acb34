"""MiniCPM-SALA (``model_type`` ``minicpm_sala``) as a scorer of long token
records: a window of token ids in, the next-token distribution at its last
position out, through the same engine and topology as every other model.

Every block is ``h = x + c Mixer(RMSNorm(x)); y = h + c SwiGLU(RMSNorm(h))``
with muP's depth scale ``c = scale_depth / sqrt(published layers)``; the
stream starts at ``scale_emb E[id]`` and the head reads ``RMSNorm(x_L) /
(hidden / dim_model_base)``. ``mixer_types`` spells the stack, one name a
layer:

- ``minicpm4``: grouped-query attention (``heads`` over ``kv_heads``) with an
  RMS norm over each query and key head, no position code, and a sigmoid
  output gate; a window of ``dense_len`` positions or fewer is plain causal
  attention (ops/attention.py ``causal_attention``), a longer one picks
  ``topk`` blocks of keys a query from mean-pooled keys and reads those
  (:mod:`storm_tpu.ops.sparse_attention`, InfLLM v2).
- ``lightning-attn``: linear attention with one fixed decay a head. Queries
  and keys of ``lightning_heads`` heads are RMS-normed a head and turned by
  plain rotary position code (:mod:`storm_tpu.ops.rope`); the state ``S_t =
  lambda_h S_(t-1) + k_t^T v_t``, ``o_t = (q_t / sqrt(d)) S_t`` is the shared
  chunked scan (:func:`storm_tpu.ops.ssd.ssd_chunked` with ``x = v``, ``dt =
  1``, ``A = ln lambda_h``, ``B = k``, ``C = q / sqrt(d)``, ``D = 0`` and a
  group a head); the result is RMS-normed over the merged heads, gated by a
  sigmoid and projected back.

**The cut** is in depth alone: the builder is told which published layers
it holds (the first ``len(mixers)``); every width, every head and the whole
vocabulary are here. The load is ``models/scorer.py``'s in ``param_dtype``,
a program a layer.

**A step's temporaries.** A row is thousands of tokens at a 16,384-wide
feed-forward, so the feed-forward runs a row at a time (one loop under
``proj``), as the attention and its selection do.

What the published ``config.json`` does not fix is listed under ``assumed``
in the benchmark's configuration file: the sparse attention's seven sizes,
the lightning decay, where the weights start.

The step's counters ride ``new_state["aux"]``: ``sparse_keys_read`` and
``sparse_keys_skipped``, one number a ``minicpm4`` layer, read on the host by
``ops/sparse_attention.py observe_key_counts``. The skeleton is
:func:`storm_tpu.models.scorer.token_scorer`'s; this file holds the plan.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from storm_tpu.models import scorer as S
from storm_tpu.models.registry import ModelDef, register
from storm_tpu.models.scorer import _proj, _w
from storm_tpu.ops import layers as L
from storm_tpu.ops import parts as P
from storm_tpu.ops import rope as R
from storm_tpu.ops.sparse_attention import (block_sparse_attention,
                                            observe_key_counts)
from storm_tpu.ops.ssd import ssd_chunked

KINDS = ("minicpm4", "lightning-attn")


def _gated_out(p: dict, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """A mixer's result ``y`` times ``sigmoid(x W_gate)`` (in float32, one
    rounding), through ``W_o``."""
    gate = jax.nn.sigmoid(_proj(x, p["gate"]).astype(jnp.float32))
    return _proj((y.astype(jnp.float32) * gate).astype(x.dtype), p["o"])


def lightning_slopes(heads: int, layer: int, published_layers: int):
    """``-ln lambda_h`` of published layer ``layer``: Lightning Attention-2's
    slope a head, ``2^(-8 (h + 1) / heads)``, times the released code's factor
    a layer, ``1 - layer / (layers - 1) + 1e-5``. Float64."""
    slope = 2.0 ** (-8.0 * (np.arange(heads) + 1) / heads)
    return slope * (1 - layer / max(published_layers - 1, 1) + 1e-5)


def minicpm4_mixer_init(rng, dim: int, heads: int, kv_heads: int,
                        head_dim: int) -> dict:
    ks = jax.random.split(rng, 5)
    return {"q": _w(ks[0], dim, heads * head_dim),
            "k": _w(ks[1], dim, kv_heads * head_dim),
            "v": _w(ks[2], dim, kv_heads * head_dim),
            "gate": _w(ks[3], dim, heads * head_dim),
            "o": _w(ks[4], heads * head_dim, dim),
            "q_norm": L.rmsnorm_init(head_dim),
            "k_norm": L.rmsnorm_init(head_dim)}


def minicpm4_mixer(p: dict, x: jnp.ndarray, heads: int, kv_heads: int,
                   head_dim: int, eps: float, sparse: dict):
    """``(out, keys read, keys skipped)``: grouped causal attention, dense or
    over the blocks each query picks (by the window's length), head norms on
    q and k, no position code, a sigmoid gate on the result."""
    b, s, _ = x.shape

    def split(name, n):
        return _proj(x, p[name]).reshape(b, s, n, head_dim)

    # a head's channels are the last axis: one learned scale a channel
    q = L.rmsnorm(p["q_norm"], split("q", heads), eps)
    k = L.rmsnorm(p["k_norm"], split("k", kv_heads), eps)
    out, read, skipped = block_sparse_attention(
        *(y.transpose(0, 2, 1, 3) for y in (q, k, split("v", kv_heads))),
        scale=head_dim ** -0.5, **sparse)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, heads * head_dim)
    return _gated_out(p, x, out), read, skipped


def lightning_mixer_init(rng, dim: int, heads: int, head_dim: int) -> dict:
    ks = jax.random.split(rng, 5)
    inner = heads * head_dim
    return {"q": _w(ks[0], dim, inner), "k": _w(ks[1], dim, inner),
            "v": _w(ks[2], dim, inner), "gate": _w(ks[3], dim, inner),
            "o": _w(ks[4], inner, dim),
            "q_norm": L.rmsnorm_init(head_dim),
            "k_norm": L.rmsnorm_init(head_dim),
            "norm": L.rmsnorm_init(inner)}


def lightning_mixer(p: dict, x: jnp.ndarray, heads: int, head_dim: int,
                    eps: float, rotary: tuple, slopes, chunk: int):
    """Linear attention with the decay ``exp(-slopes)`` a head, through the
    shared chunked scan."""
    b, s, _ = x.shape
    cos, sin = rotary  # (S, head_dim / 2)

    def split(name):
        return _proj(x, p[name]).reshape(b, s, heads, head_dim)

    # the read scale rides the query norm's scales: one rounding, not two
    read = {"scale": p["q_norm"]["scale"].astype(jnp.float32)
            * head_dim ** -0.5}
    q = R.rotate_halves(L.rmsnorm(read, split("q"), eps), cos[:, None],
                        sin[:, None])
    k = R.rotate_halves(L.rmsnorm(p["k_norm"], split("k"), eps),
                        cos[:, None], sin[:, None])
    y = ssd_chunked(split("v"), jnp.ones((b, s, heads), jnp.float32),
                    -jnp.asarray(slopes, jnp.float32), k, q,
                    jnp.zeros((heads,), jnp.float32), chunk=chunk)
    return _gated_out(p, x, L.rmsnorm(
        p["norm"], y.reshape(b, s, heads * head_dim), eps))


def _rows(fn, x: jnp.ndarray) -> jnp.ndarray:
    """``fn`` over ``x`` a row of the batch at a time: one loop, a row's
    temporaries at once."""
    return fn(x) if x.shape[0] == 1 else lax.map(
        lambda row: fn(row[None])[0], x)


def build_minicpm_sala(
    name: str,
    num_classes: int,
    input_shape: tuple,
    *,
    mixers: tuple,
    published_layers: int,
    dim: int,
    ffn_width: int,
    heads: int,
    kv_heads: int,
    head_dim: int,
    lightning_heads: int,
    lightning_head_dim: int,
    sparse: dict,
    scale_emb: float = 12.0,
    scale_depth: float = 1.4,
    dim_model_base: int = 256,
    rope_theta: float = 10000.0,
    eps: float = 1e-6,
    chunk: int = 128,
    max_rows: int = 4,
    param_dtype=jnp.bfloat16,
) -> ModelDef:
    """Published layers ``0..len(mixers)-1`` (``mixers``: their
    ``mixer_types``) over the ``num_classes`` rows of the vocabulary.
    ``sparse``: the ``minicpm4`` mixer's sizes (``kernel_size``,
    ``kernel_stride``, ``block_size``, ``topk``, ``init_blocks``,
    ``window_size``, ``dense_len``)."""
    if not mixers or set(mixers) - set(KINDS):
        raise ValueError(f"mixer_types {mixers!r}: the kinds are {KINDS!r}")
    inv_freq = rope_theta ** (-2.0 * np.arange(lightning_head_dim // 2)
                              / lightning_head_dim)  # plain rotary, float64
    sparse_mixer = S.Branch(
        "norm1", "mixer",
        lambda key: minicpm4_mixer_init(key, dim, heads, kv_heads, head_dim),
        lambda p, y, _: minicpm4_mixer(p, y, heads, kv_heads, head_dim, eps,
                                       sparse),
        counts=(("sparse_keys_read", ()), ("sparse_keys_skipped", ())),
        observe=observe_key_counts)

    def lightning_init(key):  # one for every layer: one program of the load
        return lightning_mixer_init(key, dim, lightning_heads,
                                    lightning_head_dim)

    def lightning(layer: int) -> S.Branch:
        slopes = lightning_slopes(lightning_heads, layer, published_layers)
        return S.Branch(
            "norm1", "mixer", lightning_init,
            lambda p, y, rotary: lightning_mixer(
                p, y, lightning_heads, lightning_head_dim, eps, rotary,
                slopes, chunk))

    ffn = S.Branch(
        "norm2", "ffn", lambda key: L.swiglu_init(key, dim, ffn_width),
        lambda p, y, _: _rows(lambda row: L.swiglu(p, row), y), scope=P.PROJ)
    return S.token_scorer(
        name, num_classes, input_shape,
        tuple((sparse_mixer if kind == "minicpm4" else lightning(layer), ffn)
              for layer, kind in enumerate(mixers)),
        dim=dim, eps=eps, max_rows=max_rows,
        # muP's three multipliers
        scale_emb=scale_emb, logit_scale=dim_model_base / dim,
        residual=scale_depth / math.sqrt(published_layers),
        context=lambda seq: R.rotary_tables(seq, inv_freq),
        param_dtype=param_dtype,
        hyper={"mixers": tuple(mixers), "dim": dim, "heads": heads,
               "kv_heads": kv_heads, "head_dim": head_dim,
               "lightning_heads": lightning_heads, "chunk": chunk,
               "sparse": dict(sparse), "rope_theta": rope_theta})


# InfLLM v2's sizes as the MiniCPM4 report gives them (arXiv:2506.07900)
INFLLM_V2 = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
             "topk": 64, "init_blocks": 1, "window_size": 2048,
             "dense_len": 8192}


@register("minicpm_sala")
def build_minicpm_sala_9b(num_classes: int = 73448,
                          input_shape: tuple = (16384,)) -> ModelDef:
    """MiniCPM-SALA at its published widths, whole vocabulary, layers 0-3 of
    32 (one ``minicpm4`` and three ``lightning-attn``: one period of the
    published ratio); 1.71 B parameters here, handed over in bfloat16. The
    layers left out lie on further pipeline stages."""
    return build_minicpm_sala(
        "minicpm_sala", num_classes, tuple(input_shape),
        mixers=("minicpm4",) + ("lightning-attn",) * 3, published_layers=32,
        dim=4096, ffn_width=16384, heads=32, kv_heads=2, head_dim=128,
        lightning_heads=32, lightning_head_dim=128, sparse=INFLLM_V2)


@register("minicpm_sala_tiny")
def build_minicpm_sala_tiny(num_classes: int = 96,
                            input_shape: tuple = (96,),
                            param_dtype=jnp.float32) -> ModelDef:
    """The same code at toy widths, in float32: for the tests and the
    benchmark's rehearsal on the CPU. 96 positions are 12 blocks of 8, of
    which a query picks 6 (three of them forced); a window of 32 or fewer is
    dense. Two ``minicpm4`` layers, so that a selection reads a stream the
    mixers have written."""
    return build_minicpm_sala(
        "minicpm_sala_tiny", num_classes, tuple(input_shape),
        mixers=("minicpm4", "lightning-attn", "lightning-attn", "minicpm4"),
        published_layers=8, dim=64, ffn_width=128, heads=4, kv_heads=2,
        head_dim=16, lightning_heads=4, lightning_head_dim=16,
        sparse={"kernel_size": 4, "kernel_stride": 2, "block_size": 8,
                "topk": 6, "init_blocks": 1, "window_size": 8,
                "dense_len": 32},
        dim_model_base=16, rope_theta=100.0, chunk=16,
        param_dtype=param_dtype)
