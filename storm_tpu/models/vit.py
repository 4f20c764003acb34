"""ViT-B/16 — the attention-bearing config (BASELINE.json config 4).

Standard Vision Transformer: 16x16 patch embedding (as a strided conv, MXU
friendly), learned position embeddings + CLS token, pre-LN encoder blocks,
attention via :func:`storm_tpu.ops.attention.multi_head_attention` (XLA's
attention or a Pallas kernel, by the traced shapes). Stateless (LayerNorm
only) — which also makes it the flagship for the sharded train step (no BN
cross-replica stats needed).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from storm_tpu.models.registry import ModelDef, register
from storm_tpu.ops import layers as L
from storm_tpu.ops import parts as P
from storm_tpu.ops.attention import mha_init, multi_head_attention


def _block_init(rng, dim, mlp_dim, num_heads):
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "ln1": L.layernorm_init(dim),
        "attn": mha_init(k1, dim, num_heads),
        "ln2": L.layernorm_init(dim),
        "mlp_in": L.dense_init(k2, dim, mlp_dim),
        "mlp_out": L.dense_init(k3, mlp_dim, dim),
    }


def _block(p, x, num_heads):
    # Each part under its name in a device trace (ops/parts.py); the
    # attention names its own inside (ops/attention.py).
    with jax.named_scope(P.NORM):
        y = L.layernorm(p["ln1"], x)
    y = multi_head_attention(p["attn"], y, num_heads)
    with jax.named_scope(P.NORM):
        y = x + y
    # Keep the stream (batch, tokens, dim) through both norms: XLA makes each
    # residual add and the next norm's row sum the epilogue of the projection
    # before it and folds the normalise into the projection after it; flattened
    # to (rows, dim), a program of many rows pays a float32 copy of the stream
    # a block (PERF.md §6, PR 29).
    with jax.named_scope(P.NORM):
        h = L.layernorm(p["ln2"], y)
    with jax.named_scope(P.PROJ):
        h = L.dense(p["mlp_out"], L.gelu(L.dense(p["mlp_in"], h)))
    with jax.named_scope(P.NORM):
        return y + h


def build_vit(
    name: str,
    num_classes: int,
    input_shape: tuple,
    patch: int,
    dim: int,
    depth: int,
    num_heads: int,
    mlp_dim: int,
) -> ModelDef:
    h, w, c = input_shape
    if h % patch or w % patch:
        raise ValueError(f"input {h}x{w} not divisible by patch size {patch}")
    n_patches = (h // patch) * (w // patch)
    seq = n_patches + 1  # + CLS

    def init(rng):
        ks = jax.random.split(rng, depth + 4)
        params = {
            "embed": L.conv_init(ks[0], patch, patch, c, dim),
            "cls": jnp.zeros((1, 1, dim), jnp.float32),
            "pos": L.trunc_normal(ks[1], (1, seq, dim)),
            "blocks": [
                _block_init(ks[2 + i], dim, mlp_dim, num_heads) for i in range(depth)
            ],
            "ln": L.layernorm_init(dim),
            "head": L.dense_init(ks[depth + 2], dim, num_classes),
        }
        return params, {}

    def apply(params, state, x, train: bool = False):
        b = x.shape[0]
        with jax.named_scope(P.EMBED):
            # (B, H, W, C) -> (B, S, dim) patch tokens via strided conv.
            tok = L.conv2d(params["embed"], x, stride=patch, padding="VALID")
            tok = tok.reshape(b, n_patches, dim)
            cls = jnp.broadcast_to(params["cls"].astype(tok.dtype),
                                   (b, 1, dim))
            tok = (jnp.concatenate([cls, tok], axis=1)
                   + params["pos"].astype(tok.dtype))
        for p_blk in params["blocks"]:
            tok = _block(p_blk, tok, num_heads)
        with jax.named_scope(P.HEAD):
            tok = L.layernorm(params["ln"], tok)
            return L.dense(params["head"], tok[:, 0]), state

    return ModelDef(name, input_shape, num_classes, init, apply, flagship=True,
                    hyper={"num_heads": num_heads, "dim": dim, "depth": depth,
                           "mlp_dim": mlp_dim, "patch": patch,
                           "input_shape": input_shape,
                           "num_classes": num_classes})


@register("vit_b16")
def build_vit_b16(num_classes: int = 1000, input_shape: tuple = (224, 224, 3)) -> ModelDef:
    return build_vit(
        "vit_b16", num_classes, input_shape, patch=16, dim=768, depth=12,
        num_heads=12, mlp_dim=3072,
    )


@register("vit_tiny")
def build_vit_tiny(num_classes: int = 10, input_shape: tuple = (32, 32, 3)) -> ModelDef:
    """Small ViT for the tests (same code path as vit_b16, toy size)."""
    return build_vit(
        "vit_tiny", num_classes, input_shape, patch=8, dim=64, depth=2,
        num_heads=4, mlp_dim=128,
    )
