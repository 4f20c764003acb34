"""Pallas TPU flash attention (fused scores/softmax/value contraction), full
or causal.

Why: the naive path materializes the (S, S) score matrix in HBM twice per
layer (XLA's blocked causal form: the float32 scores of 32 heads x 512
queries, three to four passes, 35-47 ms a step of the language cells,
PERF.md §6, PR 42); this kernel keeps the whole online-softmax accumulation
in VMEM, so HBM traffic is just q/k/v in and o out. Dispatch is shape-aware
(ops/attention.py): the full form serves ``multi_head_attention`` from 1,024
tokens (below it XLA's own fused attention is faster on-chip, e.g. ViT-B/16's
S=197; an on-chip run of round 2), the causal form serves ``causal_attention``,
one call a row of the batch. The ring-attention sequence-parallel path
computes its per-shard partials with its own online-softmax math
(parallel/ring_attention.py), not this kernel.

Layout: ``q: (B, Hq, S, Dk)`` is read as ``(B * Hkv, G, S, Dk)``, ``G = Hq /
Hkv`` query heads a key head (1 without grouping); the grid is (key head,
query tile); a program owns the ``(G, block_q, Dk)`` tile of the same
positions of a group's heads, stacked into one ``(G * block_q, Dk)`` left
operand, and loops over its key head's blocks of ``block_k`` keys (the whole
``(S, Dk)`` keys and ``(S, Dv)`` values of the head are resident in VMEM
over its query tiles, so HBM hands each over once) with the standard
online-softmax carry (running max m, denominator l, accumulator acc — all
f32). Both products accumulate in float32, the exponentials are float32, the
weights go to the value product in the values' type unnormalised and the
result is divided by their float32 sum: what ``causal_blocked`` does.

``causal`` (static) bounds that loop at the tile's diagonal: key blocks
wholly before the tile's first query run without a mask, the blocks that
hold the diagonal with one, blocks after the tile's last query are never
loaded nor multiplied. A tile no wider than a key block (and dividing it)
lies in one block, so its diagonal is one step after the loop and not a
second loop: 1.2 ms a step of either language cell less (the compiler copies
the carry from one loop to the next through VMEM; PERF.md §6, PR 42). ``Dv``
may differ from ``Dk``.

``window`` (static, with ``causal``; None: the causal form above, text for
text) also bounds the walk from below: a query at ``t`` reads the keys ``t -
window < s <= t``, itself among them, and the keys before every window of a
tile are never loaded nor multiplied. :func:`window_walk`, a rule on the
window and the tiles alone, says how a tile walks the rest:

* ``"tile-end"`` (:func:`_tile_end_walk`; a window of whole tiles and one to
  eight key blocks, the tile dividing the key block: Trinity's 2,048 keys on
  tiles of 64 x 512): the blocks are counted back from the tile's own last
  query. The diagonal's block first, whose result *is* the carry; the
  ``window // block_k - 1`` blocks before it, which lie inside every one of
  the tile's windows, without a mask and written out; one masked chunk of
  ``window % block_k + block_q`` keys from ``first - window`` for the lower
  edges: ``window + block_q`` columns a tile, 2,112 at Trinity's sizes, and
  nothing before ``first - window`` is read. A tile the window does not
  bind yet (``first < window``) is plain causal and runs the causal form.
* ``"aligned"`` (every other window): the loop over blocks at multiples of
  ``block_k`` starts at the block that holds key ``first - window + 1``; the
  block or two that hold some query's lower edge (the keys up to ``first +
  block_q - 1 - window``) run with the mask ``s > t - window``, the blocks
  between them and the diagonal without one, the diagonal's as above with
  both tests: ``window + block_k`` columns, two blocks of them masked (five
  blocks of 512 where the other walk loads 2,112 columns).

By the static schedule (compiles for the described v5e at Trinity's shapes;
bundles a bound tile, PERF.md section 6, PR 76): aligned 8,520; counted back
with the early tiles chosen by scalars and the clear blocks a loop 6,562 (a
chunk of 128 keys overlapping the lowest clear block, the overlap masked:
6,560; the chunk folded into the diagonal's step: 6,584; a ``lax.cond``
between the two kinds of tile: 7,047); static under ``pl.when`` 6,232; the
clear blocks written out besides **4,971**, the form kept (the chunk of 128:
4,967; folded into the diagonal's step: 4,940-4,960: all within the
schedule's own noise, so the plainest stays). The causal form walks 16.5
blocks in the mean where the window walks four and a chunk.

Shapes are padded: S to block multiples, a width to whole lane tiles unless
it is whole tiles and a half (``lane_width``: Kimi-Linear's 192-wide keys are
read as they lie, the block laid out as two lane tiles in VMEM by Mosaic,
which masks the half tile inside the products; no copy of q or k pads them
in HBM, inside a row's call or before it); padded key positions are masked
with a large negative before the softmax (causal: they lie after every real
query), padded query rows are sliced off on return. Masking uses -1e30 (not
-inf: a fully-masked chunk would produce exp(-inf - -inf) = NaN in the
carry).

CPU/tests: ``interpret=True`` runs the same kernel under the Pallas
interpreter — cross-checked against the jnp reference in tests/test_ops.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from storm_tpu.ops.rope import _turned

_LANE = 128
_NEG = -1e30
# What a grid step may hold (the v5e has 128 MiB): a head's keys and values
# twice over (the pipeline's two buffers) and the float32 score tile's
# temporaries.
_VMEM_LIMIT = 64 * 1024 * 1024


def lane_width(d: int) -> int:
    """The width the kernel reads a head of ``d`` channels at: ``d`` itself
    where it is whole lane tiles, or whole tiles and a half beyond the first
    (192 = 128 + 64: Mosaic lays such a block out in whole tiles in VMEM and
    masks the half tile in the products itself, so no copy of the operand is
    made in HBM to pad it); the next whole tile for any other width."""
    if d >= _LANE and d % (_LANE // 2) == 0:
        return d
    return -(-d // _LANE) * _LANE


def causal_tiles(group: int) -> tuple:
    """``(block_q, block_k)`` of the causal form for ``group`` query heads a
    key head: the positions of a query tile (its ``group`` heads are stacked
    into ``group * block_q`` rows of one left operand) and the keys of a
    block. The tile is the largest power of two of positions whose stacked
    rows are at most a key block's 512, never under 16 (a bfloat16 sublane
    tile): 512, 256, 128, 64 and 32 for 1, 2, 4, 8 and 16 heads a group
    (a group of 1, a head on its own keys: models/ouro.py through the merged
    entry, models/kimi_linear.py through the head-split one; the diagonal
    tile is then square, 512 x 512).
    A group that is no power of two takes the power of two below its quotient
    (Falcon-H1's five heads a key head: 64 positions, 320 stacked rows), so
    that the tile is whole sublane tiles and divides a key block, and with
    it every window of whole key blocks: ``512 // 5`` = 102 positions
    divide no window, and ``causal_form`` then sent such a model to XLA's
    blocked form."""
    most = max(512 // group, 16)
    return 1 << (most.bit_length() - 1), 512


def _fold(s, v, carry):
    """A block's scores ``s: (rows, keys)`` and values ``v: (keys, Dv)`` into
    the online-softmax carry (running max, denominator, accumulator: all
    float32). ``carry`` None: the block *sets* it, and no carry of ``-1e30``
    and zeros is built, spilled and rescaled for it."""
    weighted = functools.partial(
        lax.dot_general, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if carry is None:
        m_new = s.max(axis=-1, keepdims=True)
        p = jnp.exp(s - m_new)
        return m_new, p.sum(axis=-1, keepdims=True), weighted(
            p.astype(v.dtype), v)
    m, l, acc = carry
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + p.sum(axis=-1, keepdims=True)
    acc_new = acc * alpha + weighted(p.astype(v.dtype), v)
    return m_new, l_new, acc_new


def _attn_kernel(at_ref, q_ref, k_ref, v_ref, o_ref, *, scale, s_valid,
                 block_k, causal, window=None, first=None):
    """One tile of queries against its key head's keys, block by block.

    ``q_ref: (1, G, BQ, Dk)`` holds the same ``BQ`` positions of the ``G``
    query heads that read this key head (``G = 1`` without grouping); they are
    stacked into one ``(G * BQ, Dk)`` left operand, so a key block is met by
    the whole group at once. ``k_ref: (1, Sk, Dk)`` and ``v_ref: (1, Sk, Dv)``
    are the key head's whole sequence, resident in VMEM over the head's
    query tiles. ``first`` (None: read from the grid) is the tile's first
    position, handed down where this body runs under a branch."""
    if window_walk(window, q_ref.shape[2], block_k) == "tile-end":
        # a tile whose every window still reaches key 0 is plain causal;
        # the branches are handed the tile's first position (the interpreter
        # knows no ``program_id`` inside one)
        first = pl.program_id(1) * q_ref.shape[2]
        pl.when(first >= window)(lambda: _tile_end_walk(
            first, q_ref, k_ref, v_ref, o_ref, scale, block_k, window))
        pl.when(first < window)(lambda: _attn_kernel(
            at_ref, q_ref, k_ref, v_ref, o_ref, scale=scale, s_valid=s_valid,
            block_k=block_k, causal=True, first=first))
        return
    del at_ref  # read by the block specs
    g, bq, dk = q_ref.shape[1:]
    rows = g * bq
    q = q_ref[0].reshape(rows, dk)
    if first is None:
        first = pl.program_id(1) * bq  # the tile's first position
    if causal:
        # blocks wholly at or before the tile's first query need no mask;
        # blocks that begin after its last query are never loaded
        # (nor one past the padded keys, where the queries are padded
        # further than the keys: those rows are padding themselves)
        clear = first // block_k
        end = jnp.minimum(pl.cdiv(first + bq, block_k),
                          k_ref.shape[1] // block_k)
    else:
        clear, end = s_valid // block_k, k_ref.shape[1] // block_k
    if window is not None:
        # blocks wholly before the window of the tile's first query are
        # never loaded; those that hold the lower edge of some query's
        # window run masked, up to ``edge``
        start = jnp.maximum(first - window + 1, 0) // block_k
        edge = jnp.minimum(
            pl.cdiv(jnp.maximum(first + bq - window, 0), block_k), clear)

    def step(masked, i, carry):
        at = pl.multiple_of(i * block_k, block_k)
        k = k_ref[0, pl.ds(at, block_k), :]  # (BK, Dk)
        v = v_ref[0, pl.ds(at, block_k), :]  # (BK, Dv)
        # (G * BQ, BK) scores, f32 accumulation on the MXU.
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if masked:
            key = at + lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            if causal:  # padded keys lie after every real query
                query = first + lax.broadcasted_iota(
                    jnp.int32, (bq, block_k), 0)
                seen = key <= query
                if window is not None:
                    seen &= key > query - window
            else:
                seen = key < s_valid
            s = jnp.where(seen, s.reshape(g, bq, block_k), _NEG).reshape(
                rows, block_k)
        return _fold(s, v, carry)

    carry = (jnp.full((rows, 1), _NEG, jnp.float32),
             jnp.zeros((rows, 1), jnp.float32),
             jnp.zeros((rows, v_ref.shape[2]), jnp.float32))
    if window is None:
        carry = lax.fori_loop(0, clear, functools.partial(step, False), carry)
    else:
        carry = lax.fori_loop(start, edge, functools.partial(step, True),
                              carry)
        carry = lax.fori_loop(edge, clear, functools.partial(step, False),
                              carry)
    if causal and block_k % bq == 0:
        # a tile no wider than a key block lies in one block: the diagonal's
        _, l, acc = step(True, clear, carry)
    else:
        _, l, acc = lax.fori_loop(clear, end, functools.partial(step, True),
                                  carry)
    o_ref[0] = (acc / l).astype(o_ref.dtype).reshape(o_ref.shape[1:])


# The most key blocks a window may hold for its walk to be written out block by
# block (``_tile_end_walk``: a body a block in the kernel's text; Trinity's
# 2,048 keys are four).
_WALK_BLOCKS = 8


def window_walk(window: Optional[int], block_q: int, block_k: int) -> str:
    """Which walk over key blocks a call's tiles take: ``"tile-end"``
    (:func:`_tile_end_walk`) for a window of one to ``_WALK_BLOCKS`` key
    blocks that the query tile divides, as it divides the key block (every
    block then starts at a multiple of the tile, whole sublane tiles, and a
    tile the window does not bind yet is plain causal); ``"aligned"``
    (:func:`_attn_kernel`'s loops over blocks at multiples of ``block_k``)
    for every other window and without one. A function of the call's static
    arguments alone; ops/attention.py notes it."""
    if (window is not None and block_k % block_q == 0
            and window % block_q == 0
            and block_k <= window <= _WALK_BLOCKS * block_k):
        return "tile-end"
    return "aligned"


def _tile_end_walk(first, q_ref, k_ref, v_ref, o_ref, scale, block_k,
                   window):
    """:func:`_attn_kernel`'s tile where a window of ``window`` keys binds it
    (``first >= window``), its key blocks counted back from the tile's own
    last query and not from multiples of ``block_k``: the ``BQ`` windows
    together hold the keys ``first - window < s < first + BQ``, and the walk
    loads ``window + BQ`` columns for them where blocks aligned to
    ``block_k`` load ``window + block_k`` and mask two blocks of them.

    * the diagonal's block first, the ``block_k`` keys up to the tile's last
      query, under the causal mask: its result *is* the carry
      (:func:`_fold`);
    * the ``window // block_k - 1`` blocks before it, each inside every one
      of the tile's windows: no mask. They are written out, not looped over:
      a loop hands its carry of 192 registers from trip to trip through VMEM;
    * one chunk of ``window % block_k + BQ`` keys from ``first - window``,
      which holds every lower edge, under the mask ``s > t - window``.

    Every start is a multiple of ``BQ``. What a key lies ahead of a query by
    is static in each step, so the masks are constants."""
    g, bq, dk = q_ref.shape[1:]
    rows = g * bq
    q = q_ref[0].reshape(rows, dk)
    end = first + bq  # one past the tile's last query

    def step(back, size, carry, seen=None):
        """The ``size`` keys from ``end - back`` on; ``seen`` says of ``key -
        query`` which pairs are."""
        at = pl.multiple_of(end - back, bq)
        k = k_ref[0, pl.ds(at, size), :]
        v = v_ref[0, pl.ds(at, size), :]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if seen is not None:
            # key - query: the key's column less the query's row, and where
            # the step begins less where the tile does
            ahead = (lax.broadcasted_iota(jnp.int32, (bq, size), 1)
                     - lax.broadcasted_iota(jnp.int32, (bq, size), 0)
                     + (bq - back))
            s = jnp.where(seen(ahead), s.reshape(g, bq, size), _NEG).reshape(
                rows, size)
        return _fold(s, v, carry)

    blocks = window // block_k
    carry = step(block_k, block_k, None, lambda ahead: ahead <= 0)
    for i in range(1, blocks):
        carry = step((i + 1) * block_k, block_k, carry)
    _, l, acc = step(window + bq, window - blocks * block_k + bq, carry,
                     lambda ahead: ahead > -window)
    o_ref[0] = (acc / l).astype(o_ref.dtype).reshape(o_ref.shape[1:])


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _rows_read(row, b: int, hkv: int) -> tuple:
    """How many rows of the batch a call computes, and the index of their
    first key head in the ``(B * Hkv, ...)`` arrays (the scalar the block
    specs are prefetched): all ``b`` from 0, or the one row ``row``."""
    if row is None:
        return b, jnp.zeros((1,), jnp.int32)
    return 1, jnp.asarray(row, jnp.int32).reshape(1) * hkv


@functools.partial(jax.jit, static_argnames=(
    "scale", "block_q", "block_k", "interpret", "causal", "window"))
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 2048,
    interpret: bool = False,
    causal: bool = False,
    row=None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """softmax(q k^T * scale) v, fused on TPU, for ``q: (B, Hq, S, Dk)``,
    ``k: (B, Hkv, S, Dk)`` and ``v: (B, Hkv, S, Dv)``; with ``causal`` a query
    reads the keys at and before its own position, and with ``window`` beside
    it only the last ``window`` of them, itself among them (the key blocks
    before a tile's windows are never loaded). With ``row`` (an index,
    traced or not) only that row of the batch is computed, ``(1, Hq, S,
    Dv)``, read where it lies in the whole arrays: a loop over rows cuts
    nothing out of them.

    Block defaults are the measured-fastest on v5e for the non-causal form
    (an on-chip sweep of round 2, no ledger line: bq=512/bk=2048 runs S=2048 in
    0.52 ms vs 0.91 ms with the round-1 128/512 tiles — 3.25x XLA's fused
    attention); both clamp to the padded sequence so direct short-shape
    callers (tests, sweeps) never pad q 8x just to fill a tile. The causal
    form's come from :func:`causal_tiles`."""
    b, hq, sq, dk = q.shape
    hkv, sk, dv = k.shape[1], k.shape[2], v.shape[3]
    if hq % hkv:
        raise ValueError(f"{hq} query heads over {hkv} key heads")
    if causal and sq != sk:
        raise ValueError(f"causal over {sq} queries and {sk} keys")
    if window is not None and (not causal or window < 1):
        raise ValueError(f"a window of {window} keys, causal {causal}")
    g = hq // hkv
    if scale is None:
        scale = dk**-0.5

    qf = q.reshape(b * hkv, g, sq, dk)
    kf = k.reshape(b * hkv, sk, dk)
    vf = v.reshape(b * hkv, sk, dv)

    # Tile padding: widths -> whole lane tiles; Sq -> block_q; Sk -> block_k,
    # with both block sizes clamped to the (pow2-padded) sequence lengths.
    block_q = min(block_q, max(_LANE, 1 << (sq - 1).bit_length()))
    qf = _pad_to(_pad_to(qf, 3, lane_width(dk)), 2, block_q)
    bk = min(block_k, max(_LANE, 1 << (sk - 1).bit_length()))
    kf = _pad_to(_pad_to(kf, 2, lane_width(dk)), 1, bk)
    vf = _pad_to(_pad_to(vf, 2, lane_width(dv)), 1, bk)
    sq_p, dk_p = qf.shape[2], qf.shape[3]
    sk_p, dv_p = kf.shape[1], vf.shape[2]

    # the grid walks (key head, query tile) of the rows computed; ``at`` is
    # the first key head of those rows in the whole arrays
    rows, at = _rows_read(row, b, hkv)
    out = pl.pallas_call(
        functools.partial(_attn_kernel, scale=scale, s_valid=sk, block_k=bk,
                          causal=causal, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows * hkv, sq_p // block_q),
            in_specs=[
                pl.BlockSpec((1, g, block_q, dk_p),
                             lambda h, qi, at: (at[0] + h, 0, qi, 0)),
                pl.BlockSpec((1, sk_p, dk_p),
                             lambda h, qi, at: (at[0] + h, 0, 0)),
                pl.BlockSpec((1, sk_p, dv_p),
                             lambda h, qi, at: (at[0] + h, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, g, block_q, dv_p),
                                   lambda h, qi, at: (h, 0, qi, 0))),
        out_shape=jax.ShapeDtypeStruct((rows * hkv, g, sq_p, dv_p), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(at, qf, kf, vf)
    return out[:, :, :sq, :dv].reshape(rows, hq, sq, dv)


# ---- merged heads: q, k, v and the result where the projections leave them ----

def _merged_kernel(row_ref, q_ref, k_ref, v_ref, _, o_ref, qs_ref, os_ref,
                   **kw):
    """:func:`_attn_kernel` for a tile that lies ``(1, BQ, G * Dk)``, a query
    head a block of ``Dk`` lanes: the ``G`` lane blocks are stacked into the
    ``(1, G, BQ, Dk)`` tile it works on (static slices of whole lane tiles, in
    VMEM) and its result is unstacked the same way into ``(1, BQ, G * Dv)``.
    The walk over key blocks, a window's either walk and the carry are that
    kernel's: nothing of them is here. q and k come turned (or need no
    turn): :func:`_turning_kernel` is this kernel for a caller whose plain
    rotary turn is still to do."""
    g, dk, dv = qs_ref.shape[1], qs_ref.shape[3], os_ref.shape[3]
    for i in range(g):
        qs_ref[0, i] = q_ref[0, :, i * dk:(i + 1) * dk]
    _attn_kernel(row_ref, qs_ref, k_ref, v_ref, os_ref, **kw)
    for i in range(g):
        o_ref[0, :, i * dv:(i + 1) * dv] = os_ref[0, i]


def _turning_kernel(row_ref, q_ref, k_ref, v_ref, cos_ref, sin_ref, _, o_ref,
                    qs_ref, os_ref, kt_ref, *, block_k, **kw):
    """:func:`_merged_kernel` on q and k that come **unturned**, for heads of
    one lane tile: the plain rotary turn (ops/rope.py ``_turned``: the lanes
    kernel's arithmetic, float32 and one rounding, so what
    :func:`_attn_kernel` is handed is bit for bit what that kernel would
    have written to HBM) is done on operands this kernel holds in VMEM
    anyway. A query tile turns its own positions: each query head's lanes as
    they are stacked, and the same rows of the key head's resident k into
    ``kt_ref: (1, Sk, Dk)``, against one read of the tables' rows. The
    scratch lives over a head's tiles (the query-tile axis is ``arbitrary``,
    walked in order), and the form is causal: a tile reads keys at and
    before its last query alone, which it or an earlier tile of the head
    has turned; what lies after them in the diagonal's block is another
    head's or nothing's, and is masked before anything reads the score.
    ``cos_ref`` and ``sin_ref`` are ops/rope.py ``_lane_tables``' two whole
    ``(S, 128)`` float32 tables: their block never changes, so a call
    fetches them once, and they are sliced here. (All of k turned at the
    head's first tile, a key block at a time, read 8.3 ms a step slower on
    Ouro's cell: it reads the tables twice. PERF.md section 6, PR 74.)"""
    f32 = jnp.float32
    g, bq, dk = qs_ref.shape[1:]
    dv = os_ref.shape[3]

    at = pl.ds(pl.multiple_of(pl.program_id(1) * bq, bq), bq)
    cos, sin = cos_ref[at, :], sin_ref[at, :]
    kt_ref[0, at, :] = _turned(k_ref[0, at, :].astype(f32), cos, sin,
                               dk).astype(kt_ref.dtype)
    for i in range(g):
        qs_ref[0, i] = _turned(q_ref[0, :, i * dk:(i + 1) * dk].astype(f32),
                               cos, sin, dk).astype(qs_ref.dtype)
    _attn_kernel(row_ref, qs_ref, kt_ref, v_ref, os_ref, block_k=block_k,
                 **kw)
    for i in range(g):
        o_ref[0, :, i * dv:(i + 1) * dv] = os_ref[0, i]


def heads_a_lane_tile(dk: int, dv: int, kv_heads: int) -> int:
    """How many key heads one lane tile of merged k and v holds, to the
    merged entry: 2 where a head is half a tile in both widths and the key
    heads pair off (:func:`_halves_kernel`), else 1 (a head is read as its
    own block of lanes, which ops/attention.py ``merged_form`` grants whole
    tiles alone)."""
    return 2 if 2 * dk == _LANE == 2 * dv and kv_heads % 2 == 0 else 1


def _halves_kernel(row_ref, q_ref, k_ref, v_ref, _, o_ref, qs_ref, os_ref,
                   **kw):
    """:func:`_merged_kernel` for heads of half a lane tile: a block of k and
    of v is one lane tile, **two** key heads side by side, and the query tile
    ``(1, BQ, 2 G * 64)`` the ``2 G`` query heads that read them, two a lane
    tile. A query head is stacked as a whole tile with its 64 channels in
    the half where its key head lies in k's tile (moved there by one
    rotation of the lanes where it lay in the other) and zeros in the other
    half, so that the 128-deep product with k's tile is its product with its
    own key head alone: the matrix unit's rows are 128 deep whatever is in
    them, so the zeros cost no pass that 64 channels would have spared. The
    value product gives both key heads' results side by side, and a head's
    own half is rotated to where the head lies in the result's tile. The
    loop over key blocks is :func:`_attn_kernel`'s, on ``2 G`` stacked heads
    of 128. ``os_ref`` is float32: the rotations run on whole 32-bit
    tiles."""
    f32 = jnp.float32
    n, bq, lanes = qs_ref.shape[1:]
    half, g = lanes // 2, n // 2
    low = lax.broadcasted_iota(jnp.int32, (bq, lanes), 1) < half
    for i in range(n):
        tile = q_ref[0, :, i // 2 * lanes:(i // 2 + 1) * lanes].astype(f32)
        if i % 2 != i // g:  # its key head lies in the other half
            tile = pltpu.roll(tile, half, 1)
        qs_ref[0, i] = jnp.where(low == (i < g), tile, 0.0).astype(
            qs_ref.dtype)
    _attn_kernel(row_ref, qs_ref, k_ref, v_ref, os_ref, **kw)
    for t in range(g):
        first, second = os_ref[0, 2 * t], os_ref[0, 2 * t + 1]
        if 2 * t >= g:
            first = pltpu.roll(first, half, 1)
        if 2 * t + 1 < g:
            second = pltpu.roll(second, half, 1)
        o_ref[0, :, t * lanes:(t + 1) * lanes] = jnp.where(
            low, first, second).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "scale", "block_q", "block_k", "interpret", "window"))
def flash_attention_merged(
    out: jnp.ndarray,
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    row,
    *,
    heads: int,
    kv_heads: int,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
    window: Optional[int] = None,
    rotary: Optional[tuple] = None,
) -> jnp.ndarray:
    """The causal form of :func:`flash_attention` (with ``window``, over a
    query's last ``window`` keys alone) for heads that lie merged, as a
    projection leaves them: row ``row`` (an index, traced or not) of ``q: (B,
    S, Hq * Dk)``, ``k: (B, S, Hkv * Dk)`` and ``v: (B, S, Hkv * Dv)``, read
    where it lies, written into ``out: (B, S, Hq * Dv)``, which is returned
    with its other rows as they were (the result is its buffer: a loop over
    rows stacks nothing and zeroes nothing, ``lax.empty`` will do). No view a
    head ``(B, S, H, D)`` nor ``(B, H, S, D)`` is made: on a TPU each is
    another tiling and a copy of the whole array. The block specs pick a key
    head's ``G * Dk`` lanes of a query tile, that head's ``Dk`` lanes of the
    whole sequence of k and v (rows of ``Dk`` values ``Hkv * Dk`` apart: the
    DMA's stride, no copy), and the tile's ``G * Dv`` lanes of the result; the
    grid is (key head, query tile) and the kernel the head-split entry's
    (:func:`_merged_kernel`). Positions are padded to whole blocks; a head's
    width is read as it lies, so on a TPU it is whole lane tiles
    (ops/attention.py ``merged_form`` sends other widths elsewhere), or half
    of one with the key heads paired off (:func:`heads_a_lane_tile`): a
    block is then a lane tile's two key heads with the ``2 G`` query heads
    that read them, the grid (pair of key heads, query tile), the kernel
    :func:`_halves_kernel`, and nothing is padded or copied in HBM.

    ``rotary`` (None: q and k are read as they come): ops/rope.py
    ``_lane_tables``' two float32 tables ``(S, 128)``, for heads of one lane
    tile. q and k then come **unturned** and the kernel turns them where it
    holds them in VMEM (:func:`_turning_kernel`): the result is bit for bit
    that of ops/rope.py ``turn_merged`` on both and then this function
    without the argument, less a pass of q and of k through HBM."""
    s = q.shape[1]
    dk, dv = k.shape[2] // kv_heads, v.shape[2] // kv_heads
    if heads % kv_heads or q.shape[2] != heads * dk:
        raise ValueError(f"{heads} query heads over {kv_heads} key heads of "
                         f"{dk}: q has {q.shape[2]} lanes")
    if window is not None and window < 1:
        raise ValueError(f"a window of {window} keys")
    g = heads // kv_heads
    if scale is None:
        scale = dk**-0.5
    # a grid step reads the ``per`` key heads of one block of lanes and the
    # ``per * g`` query heads that read them
    per = heads_a_lane_tile(dk, dv, kv_heads)
    if rotary is not None and (per != 1 or dk != _LANE):
        raise ValueError(f"the kernel turns heads of {_LANE}, not of {dk}")
    kernel = _halves_kernel if per > 1 else \
        _merged_kernel if rotary is None else _turning_kernel
    block_q = min(block_q, max(_LANE, 1 << (s - 1).bit_length()))
    bk = min(block_k, max(_LANE, 1 << (s - 1).bit_length()))
    qp, buf = _pad_to(q, 1, block_q), _pad_to(out, 1, block_q)
    # keys at every query's position (a tile that turns k's rows of its own
    # positions finds them there; a tile is no wider than a key block but
    # where a caller says so)
    kp, vp = (_pad_to(y, 1, max(block_q, bk)) for y in (k, v))
    # the tables whole, over those positions: one block whose index never
    # changes is fetched once a call and needs one buffer
    tables = [_pad_to(t, 0, max(block_q, bk)) for t in rotary or ()]
    whole = [pl.BlockSpec(t.shape, lambda h, qi, r: (0, 0),
                          pipeline_mode=pl.Buffered(1)) for t in tables]

    def tile(width):  # of block ``h``'s lanes, in the row read
        return pl.BlockSpec((1, block_q, width),
                            lambda h, qi, r: (r[0], qi, h))

    return pl.pallas_call(
        functools.partial(kernel, scale=scale, s_valid=s, block_k=bk,
                          causal=True, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(kv_heads // per, qp.shape[1] // block_q),
            in_specs=[tile(per * g * dk),
                      pl.BlockSpec((1, kp.shape[1], per * dk),
                                   lambda h, qi, r: (r[0], 0, h)),
                      pl.BlockSpec((1, vp.shape[1], per * dv),
                                   lambda h, qi, r: (r[0], 0, h)),
                      *whole, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=tile(per * g * dv),
            scratch_shapes=[
                pltpu.VMEM((1, per * g, block_q, per * dk), q.dtype),
                pltpu.VMEM((1, per * g, block_q, per * dv),
                           q.dtype if per == 1 else jnp.float32),
                # the key head's k, turned
                *([pltpu.VMEM((1, kp.shape[1], dk), k.dtype)]
                  if tables else [])]),
        out_shape=jax.ShapeDtypeStruct(buf.shape, buf.dtype),
        # the result is its buffer (operands count from the row's index)
        input_output_aliases={4 + len(tables): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(jnp.asarray(row, jnp.int32).reshape(1), qp, kp, vp, *tables,
      buf)[:, :s]
