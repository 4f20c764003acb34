"""Long-context sequence classifier: flash attention in the SERVING path.

The reference's model zoo is image classifiers with tiny spatial extents
(SURVEY.md §2.3); nothing in it stresses attention over long sequences.
This family makes long-context a first-class *serving* workload, not just
a training/SP dryrun: instances are pre-embedded sequences ``(S, D_in)``
(e.g. audio frames, patch streams, retrieval chunks), S defaults to 2048 —
above the measured flash-attention crossover (an on-chip run of round 2,
no ledger line: Pallas flash is 1.9x XLA at S=2048) — so the engine's jitted forward runs
the Pallas kernel through the same InferenceBolt/engine path every other
model uses. For sequences too long for one chip, the same blocks serve
under ring-attention SP (`parallel/sequence.py`); params follow the zoo's
q/k/v/mlp naming, so TP sharding (`shard_params_tp`) applies unchanged.

Architecture: dense embed -> pre-LN transformer encoder blocks (the vit.py
block, reused) -> mean-pool -> linear head. Stateless (LN only).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from storm_tpu.models.registry import ModelDef, register
from storm_tpu.models.vit import _block, _block_init
from storm_tpu.ops import layers as L


def build_longseq(
    name: str,
    num_classes: int,
    input_shape: tuple,
    dim: int,
    depth: int,
    num_heads: int,
    mlp_dim: int,
) -> ModelDef:
    if len(input_shape) != 2:
        raise ValueError(
            f"{name} expects per-instance shape (seq, features); "
            f"got {input_shape}")
    seq, d_in = input_shape

    def init(rng):
        ks = jax.random.split(rng, depth + 3)
        params = {
            "embed": L.dense_init(ks[0], d_in, dim),
            "pos": jax.random.normal(ks[1], (1, seq, dim)) * 0.02,
            "blocks": [
                _block_init(ks[2 + i], dim, mlp_dim, num_heads)
                for i in range(depth)
            ],
            "ln": L.layernorm_init(dim),
            "head": L.dense_init(ks[2 + depth], dim, num_classes),
        }
        return params, {}

    def apply(params, state, x, train=False):
        h = L.dense(params["embed"], x) + params["pos"]
        for p in params["blocks"]:
            h = _block(p, h, num_heads)
        h = L.layernorm(params["ln"], h)
        h = jnp.mean(h, axis=1)  # mean-pool over the sequence
        return L.dense(params["head"], h), state

    def apply_sp(params, state, x, mesh, seq_axis="seq", train=False):
        """Sequence-parallel forward: S sharded over ``seq_axis``. Embed,
        LN, MLP, and head are per-token (local to each sequence shard);
        attention runs on the ICI ring (parallel/sequence.py) — the full
        (S, D) activation never materializes on one chip."""
        from storm_tpu.parallel.sequence import seq_parallel_encoder

        h = L.dense(params["embed"], x) + params["pos"]
        h = seq_parallel_encoder(params["blocks"], h, num_heads, mesh,
                                 seq_axis)
        h = L.layernorm(params["ln"], h)
        h = jnp.mean(h, axis=1)  # GSPMD inserts the cross-shard reduce
        return L.dense(params["head"], h), state

    return ModelDef(name=name, init=init, apply=apply, apply_sp=apply_sp,
                    input_shape=input_shape, num_classes=num_classes,
                    hyper={"num_heads": num_heads, "dim": dim,
                           "depth": depth, "mlp_dim": mlp_dim,
                           "input_shape": input_shape,
                           "num_classes": num_classes})


@register("longseq_encoder")
def longseq_encoder(num_classes: int = 10,
                    input_shape: tuple = (2048, 64),
                    dim: int = 256, depth: int = 4, num_heads: int = 2,
                    mlp_dim: int = 1024) -> ModelDef:
    """Serving-scale long-context config: S=2048 rides the Pallas flash
    kernel (past the measured crossover) on TPU.

    ``num_heads=2`` => head_dim 128 = the TPU lane width. The flash
    kernel pads head_dim to 128 lanes, so head_dim 32 (8 heads) wasted
    3/4 of every vector op — measured on-chip: 5.43 -> 1.84 ms/step
    (2.95x) at batch 8 just from this alignment (an on-chip run of round 3
    on another machine; no ledger line).
    Param count is unchanged (attention projections are dim x dim
    regardless of head count); override via ``ModelConfig.extra`` if you
    need more heads."""
    return build_longseq("longseq_encoder", num_classes, input_shape,
                         dim, depth, num_heads, mlp_dim)


@register("longseq_tiny")
def longseq_tiny(num_classes: int = 10, input_shape: tuple = (64, 16),
                 dim: int = 32, depth: int = 2, num_heads: int = 4,
                 mlp_dim: int = 64) -> ModelDef:
    """CPU-test-sized variant (same code path, interpretable shapes)."""
    return build_longseq("longseq_tiny", num_classes, input_shape,
                         dim, depth, num_heads, mlp_dim)
