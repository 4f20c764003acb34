"""Sparse causal attention in two passes: a *first pass* picks what each
query reads, a *second pass* is the softmax over the picks. Two first passes
share the second:

* :func:`select_blocks` picks **blocks** of keys a query and key head
  (InfLLM v2, as the MiniCPM4 report describes it, arXiv:2506.07900):
  nothing is learned by that selection, it reads the keys themselves.
* :func:`select_keys` picks **keys**, a query's ``topk`` best by a learned
  indexer's score, one selection for every head (DeepSeek sparse attention,
  as DeepSeek-V3.2-Exp publishes it); below, after the blocks.

**Picked blocks.** For a group ``g`` of query heads over one key head and a query at position
``t`` (0-based):

1. **Pooled keys.** ``c_i = mean(k[stride * i : stride * i + kernel])``, one
   every ``stride`` keys over windows of ``kernel``; ``c_i`` is visible to
   ``t`` once its whole window is: ``stride * i + kernel - 1 <= t``.
2. **First pass** (``mix.sparse_select``). ``p_h = softmax_i(q_h . c_i *
   scale)`` over the visible ``i`` for each head of the group, ``r = sum_h
   p_h``. Block ``j`` (keys ``block * j .. block * j + block - 1``) scores
   ``max r_i`` over ``i`` in ``[ratio * j - 1, ratio * j + ratio - 1]``,
   ``ratio = block / stride`` (a max-pool of ``ratio + 1`` at stride
   ``ratio``, one of padding). The first ``init_blocks`` blocks and the
   ``window / block`` blocks before the query's own, with its own, score
   ``+inf``. ``J(g, t)``: the ``topk`` best among the blocks ``j <= t //
   block`` (the lower index where two score alike), all of them where there
   are fewer. The scores are float32 at ``highest`` from the same ``q`` and
   pooled ``k``: the selection is a router, and a rounding in it sends a
   query to another block (PERF.md section 6, PR 34).
3. **Second pass** (``mix.sparse_attention``). ``o_h = softmax over {s <= t,
   s // block in J(g, t)} of (q_h . k_s * scale)`` times ``v_s``.

:func:`select_blocks` is the first pass, the same code on every platform; it
hands ``J`` over as a mask a query, group and block. The second pass is the
same mathematics in both of its forms (:func:`sparse_form`, a rule over the
traced shapes and what the process runs on, noted for
``engine_inventory()["programs"]`` as ``sparse_attention=<form>``): no
selection is shared by a tile's queries, no block is dropped, nothing stops
early on small scores.

* ``"kernel"``: one Pallas call a row of the batch, ops/flash_attention.py's
  causal layout (a tile is the same positions of a group's heads stacked, a
  key head's keys and values stay in VMEM over its tiles) with the mask in
  place of the diagonal's comparison: a tile walks every key block up to its
  diagonal and each query masks the keys of the blocks it did not pick. A
  TPU's matrix unit sees a group's heads, 16 rows a query, so a query that
  fetched its own 64 blocks would move 60 GB a window; the tiles' keys are
  read once and the scores a query does not want are computed and thrown
  away (PERF.md section 6, PR 45, says what that costs).
* ``"blocked"``: XLA's form, ops/attention.py ``causal_blocked`` with the
  same mask, elsewhere and on the CPU.

**Picked keys** (:func:`indexed_attention`). An indexer of ``Hi`` small heads
scores every causal pair, ``I(t, s) = sum_j w(t, j) ReLU(qI(t, j) . kI(s))``
(one indexer key a position, a weight a query and indexer head; bfloat16
products, float32 sums), and the query at ``t`` reads ``P(t)``: every ``s <=
t`` where ``t < topk`` (nothing to pick, no score computed), else the
``topk`` largest ``I(t, s)`` (the lower ``s`` where two score alike; -0 and
+0 score alike). ``o_h = softmax over s in P(t) of (q_h . k_s * scale)``
times ``v_s``, the same ``P(t)`` for every head: the second pass above under
a mask of one head, a key an entry (``(1, S, S)`` int8: the kernel's index
map sends every key head to it).

:func:`select_keys` (``mix.index_select``) makes one row's mask, a tile of
queries at a time against the keys its last query sees, so the ``S x S``
scores of a row never exist at once. Its form (:func:`select_form`, noted
as ``index_select=<form>``):

* ``"kernel"``: one Pallas call a row; a step keeps a tile's scores in VMEM
  as integers that order as the floats do, finds each query's ``topk``-th
  largest bit by bit (33 counts of the scores at or above a candidate:
  ``parallel/moe.py``'s bisection, on values), settles equal scores at the
  threshold by a second bisection over the keys' positions where there are
  any, and writes the mask.
* ``"top_k"``: ``lax.top_k`` of a tile's scores, elsewhere and on the CPU.

The rows of a step are unrolled, not looped: a row's mask is made, counted
and read before the next row's, and a device trace shows the first pass and
the second as events of their own (a loop around both would be one event
under one name).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from storm_tpu.ops import parts as P
from storm_tpu.ops.flash_attention import (_NEG, _VMEM_LIMIT, causal_tiles,
                                           lane_width)
from storm_tpu.ops.platform import note as _note
from storm_tpu.ops.platform import one_device as _one_device
from storm_tpu.ops.platform import use_pallas as _use_pallas

F32 = jnp.float32


def pooled_keys(k: jnp.ndarray, kernel: int, stride: int) -> jnp.ndarray:
    """``k: (..., S, D)`` -> ``(..., (S - kernel) // stride + 1, D)`` float32:
    the mean of every window of ``kernel`` keys, one a ``stride`` (``kernel``
    a multiple of ``stride``: a window is whole runs of ``stride`` keys)."""
    s, d = k.shape[-2:]
    if kernel % stride or s % stride or s < kernel:
        raise ValueError(f"pooling windows of {kernel} at stride {stride} "
                         f"over {s} keys")
    runs = k.astype(F32).reshape(*k.shape[:-2], s // stride, stride, d).sum(-2)
    n = s // stride - kernel // stride + 1
    return sum(runs[..., a:a + n, :]
               for a in range(kernel // stride)) / kernel


def select_blocks(q: jnp.ndarray, k: jnp.ndarray, *, scale: float,
                  kernel_size: int, kernel_stride: int, block_size: int,
                  topk: int, init_blocks: int, window_size: int,
                  tile: int = 1024) -> jnp.ndarray:
    """The first pass: ``q: (B, Hq, S, D)``, ``k: (B, Hkv, S, D)`` -> ``(B,
    Hkv, S, S / block_size)`` bool, true where block ``j`` is in ``J(g, t)``.
    A row of the batch at a time, a tile of ``tile`` queries at a time
    against the pooled keys its last query sees, so at most ``Hq x tile x S /
    stride`` scores exist at once."""
    _, hq, s, d = q.shape
    hkv = k.shape[1]
    g, ratio = hq // hkv, block_size // kernel_stride
    if hq % hkv or block_size % kernel_stride or s % block_size:
        raise ValueError(f"{hq} heads over {hkv}, blocks of {block_size} at "
                         f"stride {kernel_stride} over {s} positions")
    nb = s // block_size
    local = window_size // block_size

    def row(qk):
        qr, kr = qk  # (Hq, S, D), (Hkv, S, D)
        pooled = pooled_keys(kr, kernel_size, kernel_stride)
        out = []
        for lo in range(0, s, tile):
            hi = min(lo + tile, s)
            t = jnp.arange(lo, hi)
            seen = max(0, (hi - kernel_size) // kernel_stride + 1)
            blocks = -(-hi // block_size)  # those the tile's last query has
            if seen:
                scores = jnp.einsum(
                    "grtd,gid->grti",
                    qr[:, lo:hi].astype(F32).reshape(hkv, g, hi - lo, d),
                    pooled[:, :seen], precision=lax.Precision.HIGHEST) * scale
                visible = (kernel_stride * jnp.arange(seen) + kernel_size - 1
                           <= t[:, None])
                top = jnp.max(jnp.where(visible, scores, -jnp.inf), -1,
                              keepdims=True)
                e = jnp.where(visible, jnp.exp(
                    scores - jnp.where(jnp.isfinite(top), top, 0.0)), 0.0)
                total = e.sum(-1, keepdims=True)
                r = (e / jnp.where(total > 0, total, 1.0)).sum(1)
            else:
                r = jnp.zeros((hkv, hi - lo, 0), F32)
            # r_i at index i + 1: block j reads indices ratio*j .. ratio*j+ratio
            r = jnp.pad(r, ((0, 0), (0, 0), (1, ratio * blocks - seen)))
            score = jnp.maximum(
                r[..., :ratio * blocks].reshape(hkv, hi - lo, blocks,
                                                ratio).max(-1),
                r[..., ratio::ratio])
            j, own = jnp.arange(blocks), (t // block_size)[:, None]
            forced = (j < init_blocks) | ((j >= own - local) & (j <= own))
            score = jnp.where(forced, jnp.inf,
                              jnp.where(j <= own, score, -jnp.inf))
            # the k-th best and where it lies: equal scores go by index
            best, at = lax.top_k(score, min(topk, blocks))
            picked = (score > best[..., -1:]) | (
                (score == best[..., -1:]) & (j <= at[..., -1:]))
            out.append(jnp.pad(picked & (j <= own),
                               ((0, 0), (0, 0), (0, nb - blocks))))
        return jnp.concatenate(out, 1)

    with jax.named_scope(P.MIX_SPARSE_SELECT):
        return lax.map(row, (q, k))


def keys_read(picked: jnp.ndarray, block_size: int) -> tuple:
    """``(read, skipped)`` of a selection ``(..., S, S / block_size)``, int32:
    the keys the picked blocks hold up to each query's position, summed, and
    the causal keys they leave out."""
    s, nb = picked.shape[-2:]
    t, j = jnp.arange(s)[:, None], jnp.arange(nb)
    held = jnp.clip(t + 1 - j * block_size, 0, block_size)  # (S, nb)
    with jax.named_scope(P.MIX_SPARSE_SELECT):
        read = jnp.sum(jnp.where(picked, held, 0), dtype=jnp.int32)
        every = picked.size // (s * nb) * (s * (s + 1) // 2)
        return read, jnp.int32(every) - read


def sparse_form(hq: int, hkv: int, s: int, dk: int, dv: int,
                block_size: int) -> str:
    """Which form the second pass is built with: ``"kernel"`` on a TPU in a
    process with one device, for sequences of whole tiles, key blocks of
    whole selection blocks and head widths the kernel reads as they lie (as
    ops/attention.py ``causal_form``); ``"blocked"`` elsewhere."""
    block_q, block_k = causal_tiles(hq // hkv)
    # 32 positions a tile at least: the mask's int8 tile is 32 sublanes
    if (_use_pallas() and _one_device() and s % block_q == 0
            and s % block_k == 0 and block_k % block_size == 0
            and block_q % 32 == 0 and lane_width(dk) == dk
            and lane_width(dv) == dv):
        return "kernel"
    return "blocked"


def sparse_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     picked: jnp.ndarray, scale: float,
                     block_size: int, block: int = 512) -> jnp.ndarray:
    """The second pass: ``q: (B, Hq, S, Dk)``, ``k: (B, Hkv, S, Dk)``, ``v:
    (B, Hkv, S, Dv)``, ``picked: (B, Hkv, S, S / block_size)`` ->
    ``(B, Hq, S, Dv)``. One row of the batch at a time, one loop in the
    compiled program; softmax in float32, the weights to the value product
    in the values' type and unnormalised, the result divided by their
    float32 sum, in both forms."""
    hq, hkv, s = q.shape[1], k.shape[1], q.shape[2]
    form = sparse_form(hq, hkv, s, q.shape[-1], v.shape[-1], block_size)
    _note("sparse_attention", form)
    with jax.named_scope(P.MIX_SPARSE_ATTENTION):
        if form == "kernel":
            return lax.map(lambda i: _kernel_row(
                q, k, v, _wanted(picked[i], block_size), scale, i),
                jnp.arange(q.shape[0]))
        return lax.map(lambda a: _blocked_row(
            *a[:3], lambda lo, hi: _wanted(a[3], block_size, lo, hi), scale,
            block), (q, k, v, picked))


def _wanted(picked: jnp.ndarray, block_size: int,
            lo: int = 0, hi=None) -> jnp.ndarray:
    """``picked: (Hkv, S, nb)`` -> ``(Hkv, hi - lo, hi)`` bool: whether query
    ``lo + t`` reads key ``s`` (its block is picked and ``s <= lo + t``)."""
    hi = picked.shape[1] if hi is None else hi
    of_block = jnp.repeat(picked[:, lo:hi, :-(-hi // block_size)],
                          block_size, axis=-1)[..., :hi]
    return of_block & (jnp.arange(hi) <= jnp.arange(lo, hi)[:, None])


def _blocked_row(q, k, v, wanted_at, scale, block):
    """One row as XLA computes it: a block of ``block`` queries against the
    keys up to its end, as ``causal_blocked``. ``wanted_at(lo, hi)``: which
    of the keys ``0 .. hi - 1`` the queries ``lo .. hi - 1`` read, bool, a
    key head each or one for all."""
    hq, s, hkv = q.shape[0], q.shape[1], k.shape[0]
    q = q.reshape(hkv, hq // hkv, s, q.shape[-1])
    outs = []
    for lo in range(0, s, block):
        hi = min(lo + block, s)
        scores = jnp.einsum("grsd,gtd->grst", q[:, :, lo:hi], k[:, :hi],
                            preferred_element_type=F32) * scale
        scores = jnp.where(wanted_at(lo, hi)[:, None], scores, -jnp.inf)
        weights = jnp.exp(scores - scores.max(-1, keepdims=True))
        out = jnp.einsum("grst,gtd->grsd", weights.astype(v.dtype), v[:, :hi],
                         preferred_element_type=F32)
        outs.append((out / weights.sum(-1, keepdims=True)).astype(v.dtype))
    return jnp.concatenate(outs, -2).reshape(hq, s, v.shape[-1])


def _mask_kernel(at_ref, q_ref, k_ref, v_ref, m_ref, o_ref, *, scale,
                 block_k):
    """One tile of queries against its key head's keys up to the tile's
    diagonal, block by block, as ops/flash_attention.py ``_attn_kernel``
    with ``causal``; ``m_ref: (1, BQ, S)`` int8 says which keys each of the
    tile's positions reads (the same for the ``G`` heads stacked in the
    tile). A query's own block is always among them and is met last, so
    whatever a wholly masked block left in the carry is scaled away."""
    del at_ref  # read by the block specs
    g, bq, dk = q_ref.shape[1:]
    rows = g * bq
    q = q_ref[0].reshape(rows, dk)
    first = pl.program_id(1) * bq

    def step(i, carry):
        m, l, acc = carry
        at = pl.multiple_of(i * block_k, block_k)
        keys = k_ref[0, pl.ds(at, block_k), :]
        values = v_ref[0, pl.ds(at, block_k), :]
        s = lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * scale
        wanted = m_ref[0, :, pl.ds(at, block_k)].astype(jnp.int32) != 0
        s = jnp.where(wanted, s.reshape(g, bq, block_k), _NEG).reshape(
            rows, block_k)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        return (m_new, l * alpha + p.sum(axis=-1, keepdims=True),
                acc * alpha + lax.dot_general(
                    p.astype(values.dtype), values, (((1,), (0,)), ((), ())),
                    preferred_element_type=F32))

    _, l, acc = lax.fori_loop(
        0, pl.cdiv(first + bq, block_k), step,
        (jnp.full((rows, 1), _NEG, F32), jnp.zeros((rows, 1), F32),
         jnp.zeros((rows, v_ref.shape[2]), F32)))
    o_ref[0] = (acc / l).astype(o_ref.dtype).reshape(o_ref.shape[1:])


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _kernel_row(q, k, v, wanted, scale, row, interpret=False):
    """Row ``row`` of ``q: (B, Hq, S, Dk)``, ``k``, ``v`` (read where it lies
    in the whole arrays, as ``flash_attention(row=...)``) under ``wanted:
    (Hkv, S, S)`` bool or int8, or ``(1, S, S)``: one mask that every key
    head reads (a static index map) -> ``(Hq, S, Dv)``."""
    b, hq, s, dk = q.shape
    hkv, dv = k.shape[1], v.shape[-1]
    g = hq // hkv
    block_q, block_k = causal_tiles(g)
    one_mask = wanted.shape[0] != hkv  # every key head reads head 0's
    out = pl.pallas_call(
        functools.partial(_mask_kernel, scale=scale, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(hkv, s // block_q),
            in_specs=[
                pl.BlockSpec((1, g, block_q, dk),
                             lambda h, qi, at: (at[0] + h, 0, qi, 0)),
                pl.BlockSpec((1, s, dk), lambda h, qi, at: (at[0] + h, 0, 0)),
                pl.BlockSpec((1, s, dv), lambda h, qi, at: (at[0] + h, 0, 0)),
                pl.BlockSpec((1, block_q, s),
                             lambda h, qi, at: (0 if one_mask else h, qi, 0)),
            ],
            out_specs=pl.BlockSpec((1, g, block_q, dv),
                                   lambda h, qi, at: (h, 0, qi, 0))),
        out_shape=jax.ShapeDtypeStruct((hkv, g, s, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(jnp.asarray(row, jnp.int32).reshape(1) * hkv,
      q.reshape(b * hkv, g, s, dk), k.reshape(b * hkv, s, dk),
      v.reshape(b * hkv, s, dv), wanted.astype(jnp.int8))
    return out.reshape(hq, s, dv)


def block_sparse_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           scale: float, *, dense_len: int, **selection):
    """The whole mixer's attention: ``(out, keys read, keys skipped)``. A
    window of ``dense_len`` positions or fewer is plain causal attention
    (ops/attention.py ``causal_attention``: every causal key read); a longer
    one selects (``selection``: :func:`select_blocks`'s sizes) and reads
    its blocks."""
    from storm_tpu.ops.attention import causal_attention

    b, _, s, _ = q.shape
    if s <= dense_len:
        every = b * k.shape[1] * (s * (s + 1) // 2)
        return (causal_attention(q, k, v, scale=scale), jnp.int32(every),
                jnp.int32(0))
    picked = select_blocks(q, k, scale=scale, **selection)
    read, skipped = keys_read(picked, selection["block_size"])
    return (sparse_attention(q, k, v, picked, scale,
                             selection["block_size"]), read, skipped)


def observe_key_counts(metrics, cid: str, read, skipped) -> None:
    """What :func:`block_sparse_attention` counted in one step, fetched to
    the host (a number a layer each), into the registry under ``cid``: the
    keys its queries' picked blocks hold up to their positions and the
    causal keys they leave out, as two counters."""
    metrics.counter(cid, "sparse_keys_read").inc(int(read.sum()))
    metrics.counter(cid, "sparse_keys_skipped").inc(int(skipped.sum()))


# ---- picked keys: a learned indexer's first pass --------------------------------

_SELECT_TILE = 128  # queries a step of the kernel: their scores of 16,384
# keys are 8 MB of VMEM, their counts' partial sums a quarter of the registers
_SELECT_KEYS = 512  # keys a pass of its loops
_LOWEST = -2 ** 31


def select_form(s: int, di: int, topk: int) -> str:
    """Which form :func:`select_keys` is built with: ``"kernel"`` on a TPU
    in a process with one device (as :func:`sparse_form`), for sequences of
    whole blocks of keys and a ``topk`` of whole tiles of queries (so that
    every tile either picks or reads every key before it), indexer heads of
    half a lane tile or a whole one; ``"top_k"`` elsewhere."""
    if (_use_pallas() and _one_device() and s % _SELECT_KEYS == 0
            and topk % _SELECT_TILE == 0 and di in (64, 128)):
        return "kernel"
    return "top_k"


def select_keys(qi: jnp.ndarray, ki: jnp.ndarray, w: jnp.ndarray, *,
                topk: int, tile: int = 1024) -> jnp.ndarray:
    """The indexer's first pass over one row: ``qi: (Hi, S, Di)``, ``ki: (S,
    Di)``, ``w: (S, Hi)`` -> ``(1, S, S)`` int8, 1 where key ``s`` is in
    ``P(t)``. Exact: the ``topk`` largest of the float32 scores, the lower
    position where two are equal. ``tile``: the queries whose scores exist
    at once in the ``"top_k"`` form (the kernel's tile is its own)."""
    s = qi.shape[1]
    form = select_form(s, qi.shape[-1], topk)
    _note("index_select", form)
    with jax.named_scope(P.MIX_INDEX_SELECT):
        if form == "kernel":
            return _select_kernel_row(qi, ki, w.astype(F32), topk=topk)
        return _select_top_k(qi, ki, w.astype(F32), topk, tile)


def _select_top_k(qi, ki, w, topk, tile):
    s = qi.shape[1]
    out = []
    for lo in range(0, s, tile):
        hi = min(lo + tile, s)
        j = jnp.arange(hi)
        causal = j <= jnp.arange(lo, hi)[:, None]
        picked = causal
        if hi > topk:  # its last query has more keys than it reads
            dots = jnp.einsum("htd,sd->hts", qi[:, lo:hi], ki[:hi],
                              preferred_element_type=F32)
            score = jnp.sum(w[lo:hi].T[:, :, None] * jnp.maximum(dots, 0.0),
                            axis=0)
            # -0 and +0 are one score; a key after the query has none
            score = jnp.where(causal, jnp.where(score == 0, 0.0, score),
                              -jnp.inf)
            # the topk-th best and where it lies: equal scores go by index
            best, at = lax.top_k(score, topk)
            picked = causal & ((score > best[:, -1:]) | (
                (score == best[:, -1:]) & (j <= at[:, -1:])))
        out.append(jnp.pad(picked, ((0, 0), (0, s - hi))))
    return jnp.concatenate(out, 0)[None].astype(jnp.int8)


def _count(key_ref, n_blocks, block_k, hit):
    """``(TQ, 1)`` int32: how many of the first ``n_blocks`` blocks' entries
    of ``key_ref: (TQ, S)`` satisfy ``hit(entries (TQ, 128), first column)``;
    lane by lane in the loop, the lanes summed once at its end."""
    tq = key_ref.shape[0]

    def block(i, partial):
        at = pl.multiple_of(i * block_k, block_k)
        for c in range(0, block_k, 128):
            partial = partial + jnp.where(
                hit(key_ref[:, pl.ds(at + c, 128)], at + c), 1, 0)
        return partial

    return jnp.sum(lax.fori_loop(0, n_blocks, block,
                                 jnp.zeros((tq, 128), jnp.int32)),
                   axis=-1, keepdims=True)


def _select_kernel(q_ref, k_ref, w_ref, o_ref, key_ref, *, topk, block_k):
    """One tile of ``TQ`` queries: ``q_ref: (Hi, TQ, Di)``, ``k_ref: (S,
    Di)``, ``w_ref: (TQ, Hi)`` float32 -> ``o_ref: (1, TQ, S)`` int8;
    ``key_ref: (TQ, S)`` int32 holds the tile's scores as integers in the
    floats' order (a negative float's bits reversed; -0 with +0), the
    lowest integer for a key after the query."""
    heads, tq, di = q_ref.shape
    s = k_ref.shape[0]
    first = pl.program_id(0) * tq
    n_blocks = pl.cdiv(first + tq, block_k)  # up to the tile's diagonal
    t = first + lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def column(at, width):
        return at + lax.broadcasted_iota(jnp.int32, (tq, width), 1)

    @pl.when(first + tq <= topk)
    def _():  # every query of the tile reads every key before it
        def block(i, _):
            at = pl.multiple_of(i * block_k, block_k)
            o_ref[0, :, pl.ds(at, block_k)] = jnp.where(
                column(at, block_k) <= t, 1, 0).astype(o_ref.dtype)
            return 0

        lax.fori_loop(0, n_blocks, block, 0)

    @pl.when(first + tq > topk)
    def _():
        q = q_ref[...].reshape(heads * tq, di)  # the heads stacked as rows
        w = w_ref[...]

        def scores(i, _):
            at = pl.multiple_of(i * block_k, block_k)
            dots = lax.dot_general(
                q, k_ref[pl.ds(at, block_k), :], (((1,), (1,)), ((), ())),
                preferred_element_type=F32)
            acc = jnp.zeros((tq, block_k), F32)
            for h in range(heads):
                acc = acc + w[:, h:h + 1] * jnp.maximum(
                    dots[h * tq:(h + 1) * tq], 0.0)
            bits = lax.bitcast_convert_type(acc, jnp.int32)
            key = jnp.where(bits >= 0, bits, (bits ^ 0x7fffffff) + 1)
            key_ref[:, pl.ds(at, block_k)] = jnp.where(
                column(at, block_k) <= t, key, _LOWEST)
            return 0

        lax.fori_loop(0, n_blocks, scores, 0)

        def at_least(v):
            v = jnp.broadcast_to(v, (tq, 128))
            return _count(key_ref, n_blocks, block_k, lambda e, _: e >= v)

        # the topk-th largest: the largest v with topk entries at or above
        # it, the sign first, then bit by bit
        v = jnp.where(at_least(jnp.zeros((tq, 1), jnp.int32)) >= topk,
                      0, _LOWEST)

        def bit(i, v):
            candidate = v | (1 << (30 - i))
            return jnp.where(at_least(candidate) >= topk, candidate, v)

        v = lax.fori_loop(0, 31, bit, v)
        above = jnp.broadcast_to(v, (tq, 128))
        more = _count(key_ref, n_blocks, block_k, lambda e, _: e > above)
        need = topk - more  # of the entries equal to v, the first ``need``
        equal = _count(key_ref, n_blocks, block_k, lambda e, _: e == above)

        # the position of the last of them: the largest p with fewer than
        # ``need`` equal entries before it; where no query has more equal
        # entries than it needs, every one is taken
        def last_taken():
            def bit(i, p):
                candidate = jnp.broadcast_to(
                    p | (1 << ((s - 1).bit_length() - 1 - i)), (tq, 128))
                before = _count(
                    key_ref, n_blocks, block_k, lambda e, at: (e == above) & (
                        column(at, 128) < candidate))
                return jnp.where(before < need, candidate[:, :1], p)

            return lax.fori_loop(0, (s - 1).bit_length(), bit,
                                 jnp.zeros((tq, 1), jnp.int32))

        last = lax.cond(jnp.max(equal - need) > 0, last_taken,
                        lambda: jnp.full((tq, 1), s, jnp.int32))

        def block(i, _):
            at = pl.multiple_of(i * block_k, block_k)
            key = key_ref[:, pl.ds(at, block_k)]
            where = column(at, block_k)
            picked = ((key > v) | ((key == v) & (where <= last))) & (
                where <= t)
            o_ref[0, :, pl.ds(at, block_k)] = jnp.where(
                picked, 1, 0).astype(o_ref.dtype)
            return 0

        lax.fori_loop(0, n_blocks, block, 0)


@functools.partial(jax.jit, static_argnames=("topk", "tile", "block_k",
                                             "interpret"))
def _select_kernel_row(qi, ki, w, *, topk, tile=_SELECT_TILE,
                       block_k=_SELECT_KEYS, interpret=False):
    """:func:`select_keys` of one row as one Pallas call, a tile of ``tile``
    queries a step."""
    heads, s, di = qi.shape
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, block_k=block_k),
        grid=(s // tile,),
        in_specs=[pl.BlockSpec((heads, tile, di), lambda i: (0, i, 0)),
                  pl.BlockSpec((s, di), lambda i: (0, 0)),
                  pl.BlockSpec((tile, heads), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, tile, s), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((1, s, s), jnp.int8),
        scratch_shapes=[pltpu.VMEM((tile, s), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(qi, ki, w)


def blocks_picked(wanted: jnp.ndarray, block: int) -> tuple:
    """``(picked, causal)`` of one row's mask ``(1, S, S)``: of the ``block
    x block`` squares of queries and keys at or under the diagonal, those in
    which some query reads some key (int32, counted), and all of them (from
    shapes): what a second pass that skipped unpicked squares would still
    read."""
    s = wanted.shape[-1]
    if s % block:
        raise ValueError(f"squares of {block} over {s} positions")
    n = s // block
    with jax.named_scope(P.MIX_INDEX_SELECT):
        # A query's picks a block of keys, by a product with the 0/1 matrix
        # of which block a key lies in (exact in int32): the mask is read
        # once where it lies. A view of it a square is another tiling of
        # 268 MB of int8, a copy the compiler makes before it reduces
        # (0.82 ms a row and layer on the chip: PERF.md section 6, PR 59).
        of_block = (jnp.arange(s)[:, None] // block
                    == jnp.arange(n)).astype(wanted.dtype)
        some = jnp.dot(wanted[0], of_block, preferred_element_type=jnp.int32)
        some = some.reshape(n, block, n).sum(1)
        return jnp.sum(some != 0, dtype=jnp.int32), n * (n + 1) // 2


def indexed_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      qi: jnp.ndarray, ki: jnp.ndarray, w: jnp.ndarray,
                      scale: float, *, topk: int, count_block: int,
                      block: int = 512, tile: int = 1024) -> tuple:
    """The whole mixer's attention over picked keys: ``q: (B, Hq, S, Dk)``,
    ``k: (B, Hkv, S, Dk)``, ``v: (B, Hkv, S, Dv)``; the indexer's ``qi: (B,
    Hi, S, Di)``, ``ki: (B, S, Di)``, ``w: (B, S, Hi)`` -> ``(out (B, Hq, S,
    Dv), squares picked, squares causal)`` (:func:`blocks_picked` with
    ``count_block``, all rows together). A window of ``topk`` positions or
    fewer is plain causal attention and nothing is scored. The rows of the
    batch one after another, unrolled: a row's mask is made
    (:func:`select_keys`), counted and read (``mix.sparse_attention``, in
    :func:`sparse_form`'s form) before the next row's."""
    from storm_tpu.ops.attention import causal_attention

    b, hq, s, _ = q.shape
    hkv = k.shape[1]
    if s <= topk:
        n = s // count_block
        every = jnp.int32(b * (n * (n + 1) // 2))
        return causal_attention(q, k, v, scale=scale), every, every
    form = sparse_form(hq, hkv, s, q.shape[-1], v.shape[-1], 1)
    _note("sparse_attention", form)
    outs, picked, causal = [], jnp.int32(0), 0
    index = (qi[0], ki[0], w[0])
    for i in range(b):
        wanted = select_keys(*index, topk=topk, tile=tile)
        some, every = blocks_picked(wanted, count_block)
        # The compiler orders a program by its data: a count that only the
        # step's end reads would be left for the end and keep its 268 MB
        # mask until then, every row's and every layer's. So the mask is
        # counted before it is read ...
        wanted, some = lax.optimization_barrier((wanted, some))
        picked, causal = picked + some, causal + every
        with jax.named_scope(P.MIX_SPARSE_ATTENTION):
            if form == "kernel":
                outs.append(_kernel_row(q, k, v, wanted, scale, i))
            else:
                outs.append(_blocked_row(
                    q[i], k[i], v[i],
                    lambda lo, hi: wanted[:, lo:hi, :hi] != 0, scale, block))
        if i + 1 < b:  # ... and the next row's is made after this one's read
            outs[-1], index = lax.optimization_barrier(
                (outs[-1], (qi[i + 1], ki[i + 1], w[i + 1])))
    with jax.named_scope(P.MIX_SPARSE_ATTENTION):
        return jnp.stack(outs), picked, jnp.int32(causal)


def observe_block_counts(metrics, cid: str, picked, causal) -> None:
    """What :func:`indexed_attention` counted in one step, fetched to the
    host (a number a layer each), into the registry under ``cid``: the
    squares of queries and keys in which a query read a key, and the causal
    squares, as two counters."""
    metrics.counter(cid, "index_blocks_picked").inc(int(picked.sum()))
    metrics.counter(cid, "index_blocks_causal").inc(int(causal.sum()))
