"""EVA attention (Zheng et al., "Efficient Attention via Control Variates",
ICLR 2023) in the deterministic form EvaByte's released code serves: exact
attention inside a window, every earlier window reached through learned
summaries of its chunks, one softmax over both.

For one head with learned ``mu``, ``phi`` in ``R^d``, windows of ``W``
positions and chunks of ``C`` (``C`` divides ``W``), a query at position
``t`` in window ``w = t // W``:

1. **Summaries** (``mix.eva_chunks``, :func:`chunk_summaries`). Chunk ``c``
   = positions ``[C c, C c + C)`` becomes one key and one value, ``kbar_c =
   sum_s softmax_s(mu . k_s * scale) k_s`` and ``vbar_c = sum_s
   softmax_s(phi . k_s * scale) v_s``, both softmaxes over the chunk's ``C``
   positions, in float32.
2. **Attention** (``mix.eva_attention``, :func:`eva_attention`). ``t`` reads
   the keys ``s`` of its own window up to itself, ``w W <= s <= t``, exactly,
   and the summaries of *every chunk of every earlier window*, ``c < w W /
   C``, none of its own window's: ``o_t = (sum_s e^(q_t . k_s * scale) v_s +
   sum_c e^(q_t . kbar_c * scale) vbar_c) / Z_t`` with ``Z_t`` the sum of
   both kinds of weights. Seen from a window it is causal attention over
   ``[summaries of the windows before ; the window's keys]``: a fully
   visible prefix of ``w W / C`` keys in front of a causal block.

**Layout.** ``q``, ``k``, ``v`` and the result are ``(B, S, H * D)``, the
heads merged as a projection leaves them and as the output projection reads
them; the summaries are ``(B, S / C, H * D)``. On a TPU the view ``(B, S, H,
D)`` is another tiling of the same values (eight heads to a tile there, eight
positions here) and every change of view a copy of half a gigabyte, so the
kernels read a head as a block of ``D`` lanes where it lies; only XLA's forms
take the view, where it costs nothing.

Summaries and attention each have two forms of the same mathematics
(:func:`chunks_form`, :func:`eva_form`: rules over the traced shapes and what
the process runs on, noted for ``engine_inventory()["programs"]`` as
``eva_chunks=<form>, eva_attention=<form>``):
every key of the window and every earlier summary is read, nothing is dropped
on small scores. Scores, exponentials and sums are float32; the weights go to
the value product in the values' type, unnormalised, and the result is
divided by their float32 sum, as ops/attention.py ``causal_blocked`` does.

* ``"kernel"`` both: Pallas calls, one a row of the batch. The summaries':
  a tile of 256 positions of all heads, a head's 128 lanes at a time, each
  chunk's 16 positions pooled in VMEM. The attention's: a tile of 512
  queries (it lies in one window) takes its ``(m, l, acc)`` carry from the
  diagonal's block of keys, then one loop walks the summaries before its
  window, 512 a block and the last block masked by column, and its window's
  key blocks before the diagonal's: 3.75 updates of the carry a tile at
  eight windows, where blocks of 128 summaries and a carry that began empty
  made 6 (:func:`_eva_kernel` says what binds it and which forms lost); a
  window's keys and values stay in VMEM over its tiles, a head's summaries
  over all of them, so HBM hands each over once.
* ``"xla"`` and ``"blocked"``: XLA's forms, elsewhere and on the CPU: the
  poolings by a reshape to chunks; a block of queries against ``[summaries ;
  the window's keys up to the block's end]`` under one masked softmax.

What the attention reads is fixed by the shapes, so its counters are too
(:func:`pair_counts`): position pairs read exactly and position pairs reached
through a summary, counted on the device from each query's window's first
position, which is all that either form's masks are built from.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from storm_tpu.ops import parts as P
from storm_tpu.ops.flash_attention import _NEG
from storm_tpu.ops.platform import note as _note
from storm_tpu.ops.platform import one_device as _one_device
from storm_tpu.ops.platform import use_pallas as _use_pallas

F32 = jnp.float32


def _check(s: int, window: int, chunk: int) -> None:
    if window % chunk or s % chunk:
        raise ValueError(f"chunks of {chunk} in windows of {window} over "
                         f"{s} positions")


def chunk_summaries(k: jnp.ndarray, v: jnp.ndarray, mu: jnp.ndarray,
                    phi: jnp.ndarray, chunk: int) -> tuple:
    """``k, v: (B, S, H * D)``, ``mu, phi: (H, D)`` -> ``(kbar, vbar)``, each
    ``(B, S / chunk, H * D)`` in ``k``'s type: every chunk's keys pooled by
    ``softmax(mu . k * D^-1/2)`` and its values by ``softmax(phi . k *
    D^-1/2)`` over the chunk's positions, a head at a time. One row of the
    batch at a time (one loop in the compiled program), in both forms:
    logits, softmaxes and sums in float32."""
    b, s, _ = k.shape
    h, d = mu.shape
    if s % chunk:
        raise ValueError(f"chunks of {chunk} over {s} positions")
    weights = jnp.stack([mu, phi]).astype(F32) * d ** -0.5  # (2, H, D)
    merged = weights.reshape(2, h * d)

    def row(kv):
        kr, vr = (a.astype(F32).reshape(s // chunk, chunk, h, d) for a in kv)
        # (2, S / chunk, chunk, H): a product and a sum a channel, float32
        pool = jax.nn.softmax(
            (kr[None] * weights[:, None, None]).sum(-1), axis=2)
        return tuple((p[..., None] * a).sum(1).astype(k.dtype).reshape(
            s // chunk, h * d) for p, a in zip(pool, (kr, vr)))

    form = chunks_form(s, d, chunk)
    _note("eva_chunks", form)
    with jax.named_scope(P.MIX_EVA_CHUNKS):
        if form == "kernel":
            return lax.map(lambda i: _chunks_row(
                k, v, merged, i, heads=h, chunk=chunk), jnp.arange(b))
        return lax.map(row, (k, v))


_CHUNKS_TILE = 256  # positions a step of the summaries' kernel


def _on_one_tpu() -> bool:
    """A Mosaic call has no partitioning rule (ops/platform.py
    ``one_device``)."""
    return _use_pallas() and _one_device()


def chunks_form(s: int, d: int, chunk: int) -> str:
    """Which form the summaries are built with: ``"kernel"`` on a TPU in a
    process with one device, for whole tiles of positions, whole chunks a
    tile and 16 summaries or more of it (a bfloat16 tile is 16 rows), chunks
    of whole float32 tiles and a head width of whole lane tiles; ``"xla"``
    elsewhere."""
    if (_on_one_tpu() and s % _CHUNKS_TILE == 0
            and _CHUNKS_TILE % (16 * chunk) == 0 and chunk % 8 == 0
            and d % 128 == 0):
        return "kernel"
    return "xla"


def _chunks_kernel(at_ref, k_ref, v_ref, w_ref, kbar_ref, vbar_ref, *, heads,
                   chunk):
    """One tile of positions, every head: ``k_ref, v_ref: (1, T, H * D)``,
    ``w_ref: (2, H * D)`` float32 (``mu`` and ``phi`` with the scale), out
    ``(T / chunk, H * D)``. A head is a block of ``D`` lanes; a chunk is
    ``chunk`` rows of the tile."""
    del at_ref  # read by the block specs
    tile = k_ref.shape[1]
    d = k_ref.shape[2] // heads
    for h in range(heads):
        lanes = pl.ds(h * d, d)
        keys = k_ref[0, :, lanes].astype(F32).reshape(tile // chunk, chunk, d)
        values = v_ref[0, :, lanes].astype(F32).reshape(tile // chunk, chunk,
                                                        d)
        for i, (pooled, out_ref) in enumerate(((keys, kbar_ref),
                                               (values, vbar_ref))):
            logits = jnp.sum(keys * w_ref[i:i + 1, lanes], -1, keepdims=True)
            e = jnp.exp(logits - jnp.max(logits, 1, keepdims=True))
            out_ref[:, lanes] = (
                jnp.sum(e * pooled, 1) / jnp.sum(e, 1)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "chunk", "tile",
                                             "interpret"))
def _chunks_row(k, v, weights, row, *, heads, chunk, tile=_CHUNKS_TILE,
                interpret=False):
    """The summaries of row ``row`` of ``k, v: (B, S, H * D)``, read where
    it lies -> ``(kbar, vbar)``, each ``(S / chunk, H * D)``."""
    _, s, merged = k.shape

    def out():
        return pl.BlockSpec((tile // chunk, merged), lambda i, at: (i, 0))

    def tile_of():
        return pl.BlockSpec((1, tile, merged), lambda i, at: (at[0], i, 0))

    return pl.pallas_call(
        functools.partial(_chunks_kernel, heads=heads, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(s // tile,),
            in_specs=[tile_of(), tile_of(),
                      pl.BlockSpec((2, merged), lambda i, at: (0, 0))],
            out_specs=[out(), out()]),
        out_shape=[jax.ShapeDtypeStruct((s // chunk, merged), k.dtype),
                   jax.ShapeDtypeStruct((s // chunk, merged), v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(jnp.asarray(row, jnp.int32).reshape(1), k, v, weights)


def pair_counts(b: int, s: int, window: int, chunk: int) -> tuple:
    """``(exact, summarised)`` of one step over ``b`` rows, int32: the
    (query, key) position pairs read exactly (a query's own window up to
    itself), and those reached through a summary (every position of every
    earlier window, ``chunk`` a summary read). A position pair once, not
    once a head. Together they are every causal pair, ``b s (s + 1) / 2``:
    EVA drops no position, it reads the earlier windows' at a coarser grain."""
    _check(s, window, chunk)
    with jax.named_scope(P.MIX_EVA_ATTENTION):
        t = jnp.arange(s, dtype=jnp.int32)
        first = t // window * window  # a query's window's first position
        return b * jnp.sum(t - first + 1), b * jnp.sum(first)


def eva_tiles(window: int, chunk: int) -> tuple:
    """``(block_q, block_k, block_s)`` of the kernel: the positions of a
    query tile, the keys of a block and the summaries of a block, the same
    for every window and chunk. A tile in window ``w`` updates its carry
    ``ceil(w (window / chunk) / block_s) + place + 1`` times (``place``: the
    key blocks of its window before its own): what a step costs is mostly a
    step's, not a score's (:func:`_eva_kernel`), so a block of summaries is
    as wide as one of keys, whole or not (the last is masked by column), and
    one loop walks both kinds."""
    del window, chunk
    return 512, 512, 512


def eva_form(s: int, d: int, window: int, chunk: int) -> str:
    """Which form the attention is built with: ``"kernel"`` on a TPU in a
    process with one device, for a sequence of whole query tiles, windows of
    whole key blocks, and a head width of whole lane tiles (a head is a block
    of lanes of the merged heads); ``"blocked"`` elsewhere. Neither a
    window's summaries nor the sequence's need be whole blocks: the kernel
    masks the last block it walks by column, and ``_kernel_row`` pads the
    sequence's to whole blocks (960 become 1,024) as it pads a partial last
    window's keys, so that no block is read past the array."""
    block_q, block_k, _ = eva_tiles(window, chunk)
    if (_on_one_tpu() and s % block_q == 0
            and window % block_k == 0 and block_k % block_q == 0
            and d % 128 == 0):
        return "kernel"
    return "blocked"


def eva_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  kbar: jnp.ndarray, vbar: jnp.ndarray, heads: int,
                  window: int, chunk: int, block: int = 512) -> jnp.ndarray:
    """``q, k, v: (B, S, H * D)``, ``kbar, vbar: (B, S / chunk, H * D)`` ->
    ``(B, S, H * D)`` in ``v``'s type. One row of the batch at a time, one
    loop in the compiled program, in both forms."""
    b, s, merged = q.shape
    d = merged // heads
    _check(s, window, chunk)
    form = eva_form(s, d, window, chunk)
    _note("eva_attention", form)
    scale = d ** -0.5
    with jax.named_scope(P.MIX_EVA_ATTENTION):
        if form == "kernel":
            return lax.map(
                lambda i: _kernel_row(q, k, v, kbar, vbar, i, heads=heads,
                                      window=window, chunk=chunk,
                                      scale=scale), jnp.arange(b))
        return lax.map(
            lambda a: _blocked_row(*(x.reshape(x.shape[0], heads, d)
                                     for x in a), window, chunk, scale,
                                   block).reshape(s, merged),
            (q, k, v, kbar, vbar))


def _blocked_row(q, k, v, kbar, vbar, window, chunk, scale, block):
    """One row ``(S, H, D)`` as XLA computes it: within a window a block of
    ``block`` queries against the summaries before the window and the
    window's keys up to the block's end."""
    s = q.shape[0]
    outs = []
    for begin in range(0, s, window):
        end, n = min(begin + window, s), begin // chunk
        for lo in range(begin, end, block):
            hi = min(lo + block, end)
            keys = jnp.concatenate([kbar[:n], k[begin:hi]])
            values = jnp.concatenate([vbar[:n], v[begin:hi]])
            scores = jnp.einsum("shd,thd->hst", q[lo:hi], keys,
                                preferred_element_type=F32) * scale
            # the summaries are visible to all; the window's keys causally
            later = (jnp.arange(begin, hi)[None, :]
                     > jnp.arange(lo, hi)[:, None])
            scores = jnp.where(jnp.pad(later, ((0, 0), (n, 0))), -jnp.inf,
                               scores)
            weights = jnp.exp(scores - scores.max(-1, keepdims=True))
            out = jnp.einsum("hst,thd->shd", weights.astype(v.dtype), values,
                             preferred_element_type=F32)
            outs.append((out / weights.sum(-1).T[..., None]).astype(v.dtype))
    return jnp.concatenate(outs)


def _eva_kernel(at_ref, q_ref, k_ref, v_ref, kbar_ref, vbar_ref, o_ref, *,
                scale, window, chunk, block):
    """One tile of one head's queries: ``q_ref: (1, BQ, D)``; ``k_ref, v_ref:
    (1, window, D)`` are the keys and values of the tile's window (``window``
    is whole tiles, so a tile lies in one) and stay in VMEM over the window's
    tiles; ``kbar_ref, vbar_ref: (1, whole blocks of summaries, D)`` are the
    head's every summary, resident over the head's tiles.

    **The blocks a tile walks, in order.** (1) The diagonal's block of its
    window's keys, masked query by key, *sets* the carry: every query sees
    its own key there, so ``m`` is a score, and no carry of ``-inf`` and
    zeros is built, spilled and rescaled. (2) One loop of ``walked + ahead``
    steps: the ``walked = ceil(summaries / block)`` blocks of summaries, of
    which the last may hold summaries of the tile's own and later windows
    (or the padding): those columns are masked, by one row of comparisons
    ``column < summaries - block's first`` spread down the tile; then the
    ``ahead`` key blocks of the window before the diagonal's, whose limit is
    the block's width. A step reads its block from the summaries or from the
    window's keys by a select on the step's index (both lie in VMEM; the
    block not wanted is read at a clamped index and dropped). The masked
    columns weigh ``exp(_NEG - m) = 0`` under a maximum that is a score
    since (1). (3) The division by ``l`` and the write.

    **What binds it** (the v5e compiler's schedule at the cell's shapes,
    PR 53; the verify skill has the recipe; the chip ran the four forms it
    was given within 1.2 % of the schedule's ratios: 265.2, 211.8, 203.8
    and 189.7 ms a step of ``evabyte``'s cell, 44 rows of 32 heads):
    the one vector-store slot, and almost every store is a spill. The carry
    is 192 registers of the file's 64 (``acc`` 64; ``m`` and ``l``, ``(512,
    1)``, 64 each), so every step stores and reloads it and every loop's
    border copies it: a step of 128 summaries cost 1,013 bundles where one of
    512 keys cost 1,521, for a quarter of the scores. Bundles a tile (before
    the loops; a summaries' step x steps; between; a key step x steps; the
    end), eight windows of 2,048 in chunks of 16, at 1.5 GHz:

    * blocks of 128 summaries never masked, the diagonal last (PR 49): 671;
      1,013 x 3.5; 479; 1,521 x 1.5; 1,426 = **8,404**;
    * blocks of 512 summaries masked by column: 668; 1,548 x 1.25; 484;
      1,530 x 1.5; 1,427 = 6,809;
    * ... and the diagonal's block first: 1,505; 1,557 x 1.25; 417;
      1,467 x 1.5; 318 = 6,386;
    * ... and one loop over both kinds (this): 1,500; 1,499 x 2.75; 319 =
      **5,941**. The same loop choosing its block by ``lax.cond``: 2,111 a
      step, lost; its step over row groups of 256, 128 or 64 queries so
      that a group's scores stay in registers: 2,260, 2,409, 2,793, lost;
      blocks of 1,024: 2,796; 2,815 x 1.375; 318 = 6,985, lost (half of
      the diagonal's block is then masked);
    * the carry in VMEM scratch refs (as jax's own TPU flash attention
      keeps it), on the second form: 384; 1,849 x 1.25; 17; 1,832 x 1.5;
      2,372 = 7,832: **lost to the second form, do not repeat**."""
    del at_ref  # read by the block specs
    bq = q_ref.shape[1]
    q = q_ref[0]
    first = pl.program_id(1) * bq  # the tile's first position
    begin = first // window * window  # ... its window's first
    inside = first - begin  # ... and its place in the window
    summaries = begin // chunk  # every chunk of every earlier window
    walked = (summaries + block - 1) // block  # ... in so many blocks

    def part(ref, i):
        return ref[0, pl.ds(pl.multiple_of(i * block, block), block), :]

    def scores(keys):
        return lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                               preferred_element_type=F32) * scale

    def weigh(p, values):
        return lax.dot_general(p.astype(values.dtype), values,
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=F32)

    def step(i, carry):
        m, l, acc = carry
        early = i < walked  # a block of summaries, else one of keys
        i_s = jnp.minimum(i, jnp.maximum(walked - 1, 0))
        i_k = jnp.maximum(i - walked, 0)
        keys, values = (jnp.where(early, part(a, i_s), part(b, i_k))
                        for a, b in ((kbar_ref, k_ref), (vbar_ref, v_ref)))
        s = scores(keys)
        # the summaries' last block by column: one row, spread down the tile
        s = jnp.where(lax.broadcasted_iota(jnp.int32, (1, block), 1)
                      < jnp.where(early, summaries - i_s * block, block),
                      s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        return (m_new, l * alpha + p.sum(axis=-1, keepdims=True),
                acc * alpha + weigh(p, values))

    # the diagonal's block (a tile no wider than a block, and dividing it,
    # lies in one) sets the carry: every query sees its own key there
    ahead = inside // block  # the window's blocks wholly before the tile
    s = scores(part(k_ref, ahead))
    s = jnp.where(ahead * block + lax.broadcasted_iota(jnp.int32, s.shape, 1)
                  <= inside + lax.broadcasted_iota(jnp.int32, s.shape, 0),
                  s, _NEG)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    carry = m, p.sum(axis=-1, keepdims=True), weigh(p, part(v_ref, ahead))
    _, l, acc = lax.fori_loop(0, walked + ahead, step, carry)
    o_ref[...] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "heads", "window", "chunk", "scale", "tiles", "interpret"))
def _kernel_row(q, k, v, kbar, vbar, row, *, heads, window, chunk, scale,
                tiles=None, interpret=False):
    """Row ``row`` of ``q, k, v: (B, S, H * D)`` and ``kbar, vbar: (B, S /
    chunk, H * D)``, read where it lies in the whole arrays (the loop over
    rows cuts nothing out of them) -> ``(S, H * D)``. ``tiles``: ``(block_q,
    block_k, block_s)``, :func:`eva_tiles`'s where None (the tests run small
    ones under the interpreter)."""
    _, s, merged = q.shape
    d = merged // heads
    block_q, block_k, block_s = tiles or eva_tiles(window, chunk)
    if (s % block_q or window % block_k or block_k % block_q
            or block_s != block_k):
        raise ValueError(f"tiles {(block_q, block_k, block_s)} over {s} "
                         f"positions in windows of {window}")

    def whole(a, size):
        short = -a.shape[1] % size
        return jnp.pad(a, ((0, 0), (0, short), (0, 0))) if short else a

    # a partial last window's keys are read as a whole window's: zeros after
    # the last position, which lie after every query; the summaries as whole
    # blocks: zeros after the last, which the kernel's column mask hides
    k, v = whole(k, window), whole(v, window)
    kbar, vbar = whole(kbar, block_s), whole(vbar, block_s)

    def summaries(n):
        return pl.BlockSpec((1, n, d), lambda h, qi, at: (at[0], 0, h))

    def of_window():
        return pl.BlockSpec(
            (1, window, d),
            lambda h, qi, at: (at[0], qi * block_q // window, h))

    return pl.pallas_call(
        functools.partial(_eva_kernel, scale=scale, window=window,
                          chunk=chunk, block=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(heads, s // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda h, qi, at: (at[0], qi, h)),
                of_window(), of_window(),
                summaries(kbar.shape[1]), summaries(vbar.shape[1]),
            ],
            out_specs=pl.BlockSpec((block_q, d), lambda h, qi, at: (qi, h))),
        out_shape=jax.ShapeDtypeStruct((s, merged), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(row, jnp.int32).reshape(1), q, k, v, kbar, vbar)


def observe_pair_counts(metrics, cid: str, exact, summarised) -> None:
    """What :func:`pair_counts` counted in one step, fetched to the host (a
    number a layer each), into the registry under ``cid``, as two
    counters."""
    metrics.counter(cid, "eva_pairs_exact").inc(int(exact.sum()))
    metrics.counter(cid, "eva_pairs_summarised").inc(int(summarised.sum()))
