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
vocabulary are here. The load is ``models/kimi_k2.py``'s: a layer's leaves
are made by one small program that draws, scales and casts each in one pass,
handed over in ``param_dtype``.

**A step's temporaries.** A row is thousands of tokens at a 16,384-wide
feed-forward, so the feed-forward runs a row at a time (one loop under
``proj``), as the attention and its selection do.

What the published ``config.json`` does not fix is listed under ``assumed``
in the benchmark's configuration file: the sparse attention's seven sizes,
the lightning decay, where the weights start.

The step's counters ride ``new_state["aux"]``: ``sparse_keys_read`` and
``sparse_keys_skipped``, one number a ``minicpm4`` layer.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from storm_tpu.models.registry import ModelDef, register
from storm_tpu.ops import layers as L
from storm_tpu.ops import parts as P
from storm_tpu.ops import rope as R
from storm_tpu.ops.sparse_attention import block_sparse_attention
from storm_tpu.ops.ssd import ssd_chunked

KINDS = ("minicpm4", "lightning-attn")


def _w(rng, fan_in: int, fan_out: int):
    return L.lecun_normal(rng, (fan_in, fan_out), fan_in)


def _proj(x, w):
    """A product with weights, named a projection in a device trace."""
    with jax.named_scope(P.PROJ):
        return L.matmul(x, w)


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
    (seq,) = input_shape
    vocab = num_classes
    if not mixers or set(mixers) - set(KINDS):
        raise ValueError(f"mixer_types {mixers!r}: the kinds are {KINDS!r}")
    depth = scale_depth / math.sqrt(published_layers)
    logit_scale = dim_model_base / dim
    inv_freq = rope_theta ** (-2.0 * np.arange(lightning_head_dim // 2)
                              / lightning_head_dim)  # plain rotary, float64
    n_sparse = sum(kind == "minicpm4" for kind in mixers)

    def served(tree):
        return jax.tree.map(lambda a: a.astype(param_dtype), tree)

    def block_init(kind: str, km, kf):
        mixer = (minicpm4_mixer_init(km, dim, heads, kv_heads, head_dim)
                 if kind == "minicpm4" else
                 lightning_mixer_init(km, dim, lightning_heads,
                                      lightning_head_dim))
        return served({"norm1": L.rmsnorm_init(dim), "mixer": mixer,
                       "norm2": L.rmsnorm_init(dim),
                       "ffn": L.swiglu_init(kf, dim, ffn_width)})

    def ends_init(ke, kh):
        # muP's multipliers stand against weights trained under them; a
        # draw that stands for such a checkpoint starts the stream and the
        # logits where the other language models' start (N(0, 1) a channel,
        # LeCun's head): the embedding over ``scale_emb``, the head times
        # ``hidden / dim_model_base``
        return served({
            "embed": jax.random.normal(ke, (vocab, dim), jnp.float32)
            / scale_emb,
            "norm": L.rmsnorm_init(dim),
            "head": _w(kh, dim, vocab) / logit_scale})

    def init(rng):
        # one program a layer, as models/kimi_k2.py: no float32 leaf is
        # written out, a layer's temporaries are gone before the next's
        ks = jax.random.split(rng, 2 * len(mixers) + 2)
        one_block = jax.jit(block_init, static_argnums=0)
        params = jax.jit(ends_init)(ks[0], ks[1])
        params["layers"] = [one_block(kind, ks[2 * i + 2], ks[2 * i + 3])
                            for i, kind in enumerate(mixers)]
        aux = {"sparse_keys_read": jnp.zeros((n_sparse,), jnp.int32),
               "sparse_keys_skipped": jnp.zeros((n_sparse,), jnp.int32)}
        return params, {"aux": aux} if n_sparse else {}

    def apply(params, state, x, train: bool = False):
        with jax.named_scope(P.EMBED):
            # ids ride the float32 instance contract (exact under 2^24)
            ids = jnp.clip(jnp.round(x.astype(jnp.float32)), 0,
                           vocab - 1).astype(jnp.int32)
            dtype = params["head"].dtype
            # a float32 stream beside branches in ``dtype``
            h = params["embed"][ids].astype(jnp.float32) * scale_emb
        rotary = R.rotary_tables(x.shape[1], inv_freq)
        read, skipped = [], []
        # (``mixer``, not ``kind``: the protocol lint reads ``kind == "..."``
        # in a function called ``apply`` as a journal's fold arm)
        for layer, (mixer, blk) in enumerate(zip(mixers, params["layers"])):
            with jax.named_scope(P.NORM):
                y = L.rmsnorm(blk["norm1"], h, eps).astype(dtype)
            with jax.named_scope(P.MIX_ELEMENTWISE):  # but ``_proj``, loops
                if mixer == "minicpm4":
                    y, r, s = minicpm4_mixer(blk["mixer"], y, heads,
                                             kv_heads, head_dim, eps, sparse)
                    read.append(r)
                    skipped.append(s)
                else:
                    y = lightning_mixer(
                        blk["mixer"], y, lightning_heads, lightning_head_dim,
                        eps, rotary, lightning_slopes(
                            lightning_heads, layer, published_layers), chunk)
            with jax.named_scope(P.NORM):
                h = h + depth * y.astype(jnp.float32)
                y = L.rmsnorm(blk["norm2"], h, eps).astype(dtype)
            with jax.named_scope(P.PROJ):
                y = _rows(lambda row: L.swiglu(blk["ffn"], row), y)
            with jax.named_scope(P.NORM):
                h = h + depth * y.astype(jnp.float32)
        with jax.named_scope(P.HEAD):
            last = L.rmsnorm(params["norm"], h[:, -1], eps) * logit_scale
            logits = L.matmul(last.astype(dtype), params["head"])
        if not read:
            return logits, state
        return logits, {**state, "aux": {
            "sparse_keys_read": jnp.stack(read),
            "sparse_keys_skipped": jnp.stack(skipped)}}

    return ModelDef(
        name, (seq,), vocab, init, apply, max_rows=max_rows,
        input_dtype="float32",
        hyper={"mixers": tuple(mixers), "dim": dim, "heads": heads,
               "kv_heads": kv_heads, "head_dim": head_dim,
               "lightning_heads": lightning_heads, "chunk": chunk,
               "sparse": dict(sparse), "rope_theta": rope_theta,
               "input_shape": (seq,), "num_classes": vocab})


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
