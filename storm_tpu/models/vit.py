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
from jax import lax

from storm_tpu.models.registry import ModelDef, register
from storm_tpu.ops import layers as L
from storm_tpu.ops import parts as P
from storm_tpu.ops.attention import mha_init, multi_head_attention
from storm_tpu.ops.platform import note as _note


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
    with jax.named_scope(P.NORM):
        mean = L.row_mean(x)
    return _block_about(p, x, mean, num_heads)[0]


def _block_about(p, x, mean, num_heads):
    """One block on the stream ``x`` and its float32 row mean; returns the
    new stream and *its* row mean, which the next block's first norm needs.
    That mean is made here, beside the last residual add, so that it is the
    epilogue of ``mlp_out``'s product in a loop over the blocks as it is
    where they are unrolled: a loop cut between blocks with the stream alone
    in its carry leaves the row sum a pass of its own over the stream, forty
    times a step."""
    # Each part under its name in a device trace (ops/parts.py); the
    # attention names its own inside (ops/attention.py).
    with jax.named_scope(P.NORM):
        y = L.layernorm_about(p["ln1"], x, mean)
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
        out = y + h
        return out, L.row_mean(out)


@jax.jit
def _stack(*leaves):
    # a leaf a call: one small program a shape, where the whole tree in one
    # call was a program of 640 operands (18 s to compile, 2 s to load)
    return jnp.stack(leaves)


def stack_blocks(params):
    """The tree an engine whose largest step is long serves
    (``ModelDef.serve_params``): what ``init`` and the checkpoints hold, and
    beside the list of blocks their leaves stacked on a leading axis,
    ``[depth, ...]``, under ``"stacked"``. A program scans the stacked leaves
    with one block's code (``_scan_blocks``) or walks the list as ever
    (``_SCAN_MIN_TOKENS`` says which). The blocks' parameters are so held
    twice, 2.02 GB more at ViT-g/14's sizes in bfloat16, for programs a
    twentieth the size: forty unrolled blocks were 32-40 MB a bucket and
    2.6-3.5 s of every warm start to load (PERF.md §6, PR 62). Made once, at
    load, outside any step."""
    return {**params, "stacked": jax.tree.map(_stack, *params["blocks"])}


# Which steps run the blocks as a loop over the stacked leaves, in tokens a
# step (rows x tokens). A loop reads block i's weights at an offset only the
# running program knows, and XLA cannot bring those in ahead of time as it
# does an unrolled program's: the slices are copies at the block's start that
# nothing hides, 50.7 MB and 73 us a block at ViT-g/14's sizes on a v5e
# whatever the rows, 2.6-4.1 ms of a forty-block step. The engine's own
# programs there, list -> loop, ms a step (PERF.md §6, PR 62):
#     rows     8      16      32      64     128     256
#     list   28.07   55.27  110.91  221.07  453.54  924.97
#     loop   31.56   58.14  113.48  225.20  474.08  925.64
#            +12.4 %  +5.2 %  +2.3 %  +1.9 %  +4.5 %  +0.07 %
# (at 128 rows the stream, 92.6 MB, just fits the chip's fast memory and is
# moved out of it and back in every block). So the loop is free only in the
# longest of these, and two sizes decide. An engine stacks the leaves, and
# holds them twice, only if its *largest* step is one the loop costs
# nothing: that is the step a backlog fills, every step of a saturated
# engine. Its shorter steps are what a burst or a backlog's tail passes
# through, and they take the loop (and load in 0.15 s instead of 3.4) from
# the size at which the copies are under a fortieth of a step; below that,
# where a paced stream lives (a median answer 6 ms later at 8 rows), the
# list. The 128-row step pays its 4.5 % there: known, and left so rather
# than cut a hole for one chip's fast memory into the rule. Nothing between
# 128 and 256 rows was read: the first size stands at the smallest reading
# that supports it.
_STACK_MIN_TOKENS = 65536
_SCAN_MIN_TOKENS = 8192


def _scan_blocks(stacked, tok, num_heads):
    """The stacked blocks as one ``lax.scan`` whose body is a block's code;
    the carry is the stream and its row mean (``_block_about`` says why the
    mean). The barrier keeps a block's slices operations of their own: XLA
    then copies them out at the block's start and brings them into fast
    memory ahead of the products that read them, as it does the unrolled
    program's weights; fused into the products (what it does unasked) each
    product reads its slice from HBM at its own pace, 11.6 % of a 256-row
    step (PERF.md §6, PR 62)."""
    with jax.named_scope(P.NORM):
        mean = L.row_mean(tok)
    (tok, _), _ = lax.scan(
        lambda carry, p_blk: (_block_about(
            lax.optimization_barrier(p_blk), *carry, num_heads), None),
        (tok, mean), stacked)
    return tok


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
        stacked = params.get("stacked")  # beside the list: stack_blocks
        if stacked is not None and b * seq >= _SCAN_MIN_TOKENS:
            _note("blocks", "scan")
            tok = _scan_blocks(stacked, tok, num_heads)
        else:
            _note("blocks", "unrolled")
            for p_blk in params["blocks"]:
                tok = _block(p_blk, tok, num_heads)
        with jax.named_scope(P.HEAD):
            tok = L.layernorm(params["ln"], tok)
            return L.dense(params["head"], tok[:, 0]), state

    def serve_params(params, rows):
        # rows: the engine's largest step; under the size, the loaded tree
        if rows * seq < _STACK_MIN_TOKENS:
            return params
        return stack_blocks(params)

    return ModelDef(name, input_shape, num_classes, init, apply, flagship=True,
                    serve_params=serve_params,
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
