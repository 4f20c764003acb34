"""Pallas TPU attention for short sequences in programs of many rows.

Why: once a block's ``[B, H, S, S]`` scores no longer fit fast memory, XLA's
own attention is the costliest part of a ViT block after its MLP. It
transposes q, k and v to head-major layouts and the result back (six
copies of the stream a block), writes the scores in bf16, reads them,
writes the exponentials in float32 and reads those for the value
contraction: at ViT-g/14's 256 x 257 tokens 13 ms of a 29 ms block for
0.1 TFLOP of contractions, against 1.16 ms here (PERF.md §5-6, PRs 29, 72).

This kernel takes q, k and v as the projections leave them, ``[B, S, H*D]``,
one grid step a batch row: the row's three ``[S, H*D]`` tiles sit in VMEM,
a head's ``[S, S]`` scores never leave VMEM, and the output is written in the
same ``[B, S, H*D]`` layout, so no transpose is left for XLA to make. Softmax
is float32; the contractions take bf16 (or the input's type) with float32
accumulation, as the jnp path.

Every operand is read and written in whole lane tiles where it lies
(:func:`_kernel`), by two rules on the traced shapes: no lane is rotated,
and the only cross-lane work is softmax's own two reductions.

* *A head where it lies.* A head narrower than a lane tile that lies inside
  one is read as that tile, the other heads' lanes of q zeroed (they add
  exact zeros to the scores) and its lanes of the result kept by a select
  at the write. One that straddles two tiles has its lanes of the first
  above its lanes of the second (``D <= 128``), so a select folds q, k and
  v into one tile without moving a lane, the products are an aligned
  head's, and the result goes back through the same two masks. Heads of
  whole tiles need no mask; wider ragged ones contract over their tiles.
* *The odd keys columns.* Up to eight keys past the last whole lane tile
  (ViT-g/14: 256 + 1) are float32 columns on the vector unit beside score
  tiles the matrix unit makes whole (:func:`key_split`); a program's
  dispatch note says which (``rows_keys=256+1`` or ``whole``).

What that bought and what binds it now (the v5e compiler's schedule at
``bf16[256,257,1408]``, 16 heads, bundles a row of sixteen heads unrolled,
and the chip in ``vit_g14``'s traced step; PERF.md §6, PR 72; the verify
skill has the recipe). The kernel as PR 29 wrote it sliced each head out at
lane ``88 h``: **14,747 bundles, 12.4 us on the chip**, no slot full but the
cross-lane unit busy in 85 % of them (1,770 ``vrot.lane`` a row turning q,
k, v and the result to lane 0 and back, 1,056 reductions over 384 lanes).
Heads in their tiles with keys 256 + 1: **9,738, 6.9 us** (either rule
alone: keys +9 %, heads -14 %). With straddling heads folded (this):
**6,194 bundles, 4.5 us**, vector unit 0.91 of its slots, matrix unit 0.69,
cross-lane 0.72, and the row's 2.9 MB of q, k, v and result at 819 GB/s are
3.5 us: the chip now reads it 1.10 over the schedule and **no form below
6,194 ran faster**, so the row's own bytes bind it. Forms priced and left,
do not repeat (bundles; the first group against 9,738, the second against
6,194): queries in two halves or four quarters 12,155 / 15,074; the odd
key's score from the matrix unit (a slab at row 249) 15,803; the
denominator riding the value product in spare lanes 15,929; q scaled once
for all heads 9,836; the result merged a tile and not a head 10,061; the
two key tiles as two products 9,430-9,661 (3.4 % on the chip, then
nothing once folded); folded: k and v selected as 32-bit words 6,066; a
tile's parts held in float32 until its last head 6,087; ``exp`` as
``2**x`` with log2(e) in q's scale 5,920 (rounds q elsewhere: the g14
parity case read 0.0035 for 0.0029); the division without its guards
(approximate reciprocal and the division's own refinement: the same bits
on the chip) 5,523: each of the last four within 0.7 % of this on the chip.

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


_LANES = 128
# The most keys past the last whole lane tile that are taken as columns.
_ODD_KEYS = 8


def key_split(s: int) -> tuple[int, int]:
    """``(n, r)``: a row's ``s`` keys as ``n`` on whole lane tiles from the
    matrix unit and ``r`` columns beside them, or ``(s, 0)``: the score tile
    whole."""
    r = s % _LANES
    return (s - r, r) if s >= _LANES and 0 < r <= _ODD_KEYS else (s, 0)


def keys_form(s: int) -> str:
    """The split as a program's dispatch note reads it: ``"256+1"``, or
    ``"whole"``."""
    n, r = key_split(s)
    return f"{n}+{r}" if r else "whole"


def _kernel(q_ref, k_ref, v_ref, o_ref, *, heads, scale):
    s, c = q_ref.shape[1:]
    d = c // heads
    n = key_split(s)[0]
    for h in range(heads):
        # The lane tiles that hold the head, read and written where they lie.
        lo, hi = h * d, (h + 1) * d
        a, b = lo // _LANES * _LANES, min(c, -(-hi // _LANES) * _LANES)
        fold = b - a == 2 * _LANES and d <= _LANES
        w = _LANES if fold else b - a
        lane = a + lax.broadcasted_iota(jnp.int32, (1, w), 1)
        if fold:
            # A head over two lane tiles: its lanes of the first lie above
            # its lanes of the second, so one tile holds both by a select.
            first, second = lane >= lo, lane + _LANES < hi
            mine = first | second
            read = lambda ref, rows: jnp.where(
                first, ref[0, rows, a:a + w], ref[0, rows, a + w:b])
        else:
            mine = None if (a, b) == (lo, hi) else (lane >= lo) & (lane < hi)
            read = lambda ref, rows: ref[0, rows, a:b]
        # The scale goes onto the [S, D] operand, not the [S, S] scores; the
        # other heads' lanes of q are zeroed, so the contraction over the
        # tile is the head's.
        qf = read(q_ref, slice(None)).astype(jnp.float32) * scale
        qf = qf if mine is None else jnp.where(mine, qf, 0.0)
        q = qf.astype(q_ref.dtype)
        k, v = read(k_ref, slice(0, n)), read(v_ref, slice(0, n))
        sc = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        m = sc.max(axis=-1, keepdims=True)
        # Each odd key a float32 column on the vector unit.
        odd = [(slice(j, j + 1), jnp.sum(
            qf * read(k_ref, slice(j, j + 1)).astype(jnp.float32),
            axis=-1, keepdims=True)) for j in range(n, s)]
        for _, s1 in odd:
            m = jnp.maximum(m, s1)
        p = jnp.exp(sc - m)
        denom = p.sum(axis=-1, keepdims=True)
        o = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        for row, s1 in odd:
            p1 = jnp.exp(s1 - m)
            denom = denom + p1
            o = o + p1 * read(v_ref, row).astype(jnp.float32)
        val = (o / denom).astype(o_ref.dtype)
        # Every lane belongs to one head: a tile's other lanes keep what is
        # there (an earlier head's result, or what a later one overwrites).
        for x, y, keep in (((a, a + w, first), (a + w, b, second)) if fold
                           else ((a, b, mine),)):
            o_ref[0, :, x:y] = (val if keep is None or h == 0 else
                                jnp.where(keep, val, o_ref[0, :, x:y]))


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
