"""Pallas TPU attention for short sequences in programs of many rows.

Why: once a block's ``[B, H, S, S]`` scores no longer fit fast memory, XLA's
own attention is the costliest part of a ViT block after its MLP. It
transposes q, k and v to head-major layouts and the result back (six
copies of the stream a block), writes the scores in bf16, reads them,
writes the exponentials in float32 and reads those for the value
contraction: at ViT-g/14's 256 x 257 tokens 13 ms of a 29 ms block for
0.1 TFLOP of contractions, against 3.2 ms here (PERF.md §5, PR 29).

This kernel takes q, k and v as the projections leave them, ``[B, S, H*D]``,
one grid step a batch row: the row's three ``[S, H*D]`` tiles sit in VMEM,
each head is a static lane slice of them, its ``[S, S]`` scores never leave
VMEM, and the output is written in the same ``[B, S, H*D]`` layout, so no
transpose is left for XLA to make. Softmax is float32; the contractions
take bf16 (or the input's type) with float32 accumulation, as the jnp path.

It serves sequences whose whole score tile fits VMEM (``fits``); longer
ones are the flash kernel's (ops/flash_attention.py). Dispatch is by shape
(ops/attention.py ``attention_form``). Autodiff: ``pallas_call`` has no
backward, and a backward needs the scores again, so under ``jax.grad`` both
passes are jax's own of the jnp reference: training runs what it ran before
the kernel and pays for no forward twice. A backward kernel is not written.

CPU/tests: ``interpret=True`` runs the kernel under the Pallas interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from storm_tpu.ops import parts as P

# What one grid step may hold in VMEM: q, k, v and the output tile, each
# double-buffered by the pipeline, and a few float32 score tiles of one head.
_VMEM_BUDGET = 12 * 1024 * 1024


def fits(s: int, c: int, itemsize: int) -> bool:
    """Whether one batch row's tiles and one head's scores fit a grid step."""
    return 8 * s * c * itemsize + 4 * s * s * 4 <= _VMEM_BUDGET


def _kernel(q_ref, k_ref, v_ref, o_ref, *, heads, scale):
    d = q_ref.shape[2] // heads
    for h in range(heads):
        cols = slice(h * d, (h + 1) * d)
        q, k, v = q_ref[0, :, cols], k_ref[0, :, cols], v_ref[0, :, cols]
        # The scale goes onto the [S, D] operand, not the [S, S] scores.
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        p = jnp.exp(s - s.max(axis=-1, keepdims=True))
        denom = p.sum(axis=-1, keepdims=True)
        o = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        o_ref[0, :, cols] = (o / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _forward(q, k, v, *, heads, interpret=False):
    b, s, c = q.shape
    row = pl.BlockSpec((1, s, c), lambda i: (i, 0, 0))
    with jax.named_scope(P.MIX_ATTENTION):
        return pl.pallas_call(
            functools.partial(_kernel, heads=heads,
                              scale=(c // heads) ** -0.5),
            grid=(b,),
            in_specs=[row, row, row],
            out_specs=row,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            interpret=interpret,
        )(q, k, v)


def reference(q, k, v, heads):
    """The jnp path on the same ``[B, S, H*D]`` operands."""
    from storm_tpu.ops.attention import (attention_reference, merge_heads,
                                         split_heads)

    return merge_heads(attention_reference(
        *(split_heads(y, heads) for y in (q, k, v))))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def short_attention(q, k, v, heads):
    """softmax(q k^T / sqrt(D)) v per head, for ``[B, S, H*D]`` operands."""
    return _forward(q, k, v, heads=heads)


def _fwd(q, k, v, heads):
    return jax.vjp(lambda *a: reference(*a, heads), q, k, v)


def _bwd(heads, vjp, cot):
    return vjp(cot)


short_attention.defvjp(_fwd, _bwd)
