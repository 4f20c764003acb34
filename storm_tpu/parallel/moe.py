"""Expert parallelism: a mixture-of-experts MLP with experts sharded over a
mesh axis (GShard-style dispatch).

Absent from the reference (SURVEY.md §2.4 EP row: "no MoE anywhere"); built
here the declarative TPU way rather than with hand-written all-to-alls:

- expert weights are stacked on a leading E axis and sharded over the
  ``expert`` mesh axis (each device holds E / n_expert_shards experts);
- tokens pick a top-1 expert via a learned gate; a capacity-bounded one-hot
  dispatch tensor turns routing into three einsums (dispatch, expert MLP,
  combine) — all MXU work, no gather/scatter;
- with tokens sharded over ``data`` and experts over ``expert``, XLA/GSPMD
  lowers the dispatch/combine einsums into the all-to-all pattern on ICI;
  user code contains zero explicit collectives (SURVEY.md §2.5).

Capacity semantics: each expert processes at most
``ceil(tokens / E * capacity_factor)``; overflow tokens are dropped (their
output is 0 through the residual connection) — standard GShard/Switch
behavior, deterministic and shape-static for XLA.

:func:`topk_moe_layer` is the other kind (DeepSeek-V3 / Kimi style): top-k of
a wide router, **no token dropped**, a shared expert beside the routed ones,
and the layer is told which experts it holds: it routes over the router's
whole width and computes the part of the result that its own experts give
(what one chip of an expert-parallel group computes before the exchange).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from storm_tpu.ops import parts
from storm_tpu.ops.platform import note as _note


def moe_init(
    rng, dim: int, mlp_dim: int, n_experts: int, dtype=jnp.float32
) -> dict:
    k1, k2, k3 = jax.random.split(rng, 3)
    scale_in = 1.0 / math.sqrt(dim)
    scale_out = 1.0 / math.sqrt(mlp_dim)
    return {
        "gate": (jax.random.normal(k1, (dim, n_experts), dtype) * scale_in),
        "w_in": (jax.random.normal(k2, (n_experts, dim, mlp_dim), dtype) * scale_in),
        "b_in": jnp.zeros((n_experts, mlp_dim), dtype),
        "w_out": (jax.random.normal(k3, (n_experts, mlp_dim, dim), dtype) * scale_out),
        "b_out": jnp.zeros((n_experts, dim), dtype),
    }


def moe_param_specs(expert_axis: str = "expert") -> dict:
    """PartitionSpecs matching :func:`moe_init`: experts sharded on their
    leading axis, gate replicated."""
    return {
        "gate": P(),
        "w_in": P(expert_axis),
        "b_in": P(expert_axis),
        "w_out": P(expert_axis),
        "b_out": P(expert_axis),
    }


def shard_moe_params(mesh: Mesh, params: dict, expert_axis: str = "expert") -> dict:
    specs = moe_param_specs(expert_axis)
    return {
        k: jax.device_put(v, NamedSharding(mesh, specs[k])) for k, v in params.items()
    }


def moe_layer(
    p: dict,
    x: jnp.ndarray,
    capacity_factor: float = 1.25,
    aux_loss_weight: float = 1e-2,
):
    """Top-1 MoE MLP over tokens.

    ``x``: (..., dim) — leading dims are flattened into a token axis.
    Returns ``(y, aux_loss)``: y has x's shape (overflowed tokens yield 0);
    ``aux_loss`` is the Switch-Transformer load-balancing loss (mean over
    experts of fraction-of-tokens x mean-gate-prob, scaled by E), already
    multiplied by ``aux_loss_weight``.
    """
    orig_shape = x.shape
    dim = orig_shape[-1]
    tokens = x.reshape(-1, dim)
    n = tokens.shape[0]
    e = p["w_in"].shape[0]
    cap = max(1, math.ceil(n / e * capacity_factor))

    logits = (tokens @ p["gate"].astype(tokens.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (N, E)
    expert = jnp.argmax(probs, axis=-1)  # (N,)
    onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)  # (N, E)

    # Position of each token within its chosen expert's queue; >= cap drops.
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot  # (N, E)
    keep = onehot * (pos < cap)  # (N, E)
    pos_cap = jax.nn.one_hot(jnp.sum(pos, axis=-1).astype(jnp.int32), cap,
                             dtype=jnp.float32)  # (N, C)
    dispatch = jnp.einsum("ne,nc->nec", keep, pos_cap)  # (N, E, C)
    gate_val = jnp.sum(probs * keep, axis=-1)  # (N,)
    combine = dispatch * gate_val[:, None, None]  # (N, E, C)

    xt = tokens.astype(jnp.float32)
    xe = jnp.einsum("nec,nd->ecd", dispatch, xt).astype(tokens.dtype)  # (E, C, d)
    h = jax.nn.gelu(
        jnp.einsum("ecd,edh->ech", xe, p["w_in"].astype(xe.dtype))
        + p["b_in"].astype(xe.dtype)[:, None, :]
    )
    ye = (
        jnp.einsum("ech,ehd->ecd", h, p["w_out"].astype(h.dtype))
        + p["b_out"].astype(h.dtype)[:, None, :]
    )  # (E, C, d)
    y = jnp.einsum("nec,ecd->nd", combine, ye.astype(jnp.float32))

    # Switch load-balancing loss: encourages uniform routing.
    frac_tokens = jnp.mean(onehot, axis=0)  # (E,)
    mean_prob = jnp.mean(probs, axis=0)  # (E,)
    aux = aux_loss_weight * e * jnp.sum(frac_tokens * mean_prob)

    return y.astype(x.dtype).reshape(orig_shape), aux


def topk_moe_init(rng, dim: int, hidden: int, n_experts: int,
                  n_held: Optional[int] = None, shared: bool = True,
                  dtype=jnp.float32, form: str = "swiglu",
                  shared_hidden: Optional[int] = None,
                  selection_bias: bool = True) -> dict:
    """A router over ``n_experts`` with its selection bias (no
    ``router_bias`` leaf where ``selection_bias`` is false: the router then
    picks by the score alone), ``n_held`` experts of width ``hidden``
    stacked on a leading axis (all of them where ``n_held`` is None), and
    the shared expert, of width ``shared_hidden`` (``hidden`` where None;
    no ``shared`` leaf where ``shared`` is false). ``form``: ``"swiglu"``
    (``gate``, ``up``, ``down``) or ``"relu2"`` (``up``, ``down``: a squared
    ReLU between); the layer reads the form off the parameters."""
    from storm_tpu.ops import layers as L

    if form not in ("swiglu", "relu2"):
        raise ValueError(f"unknown feed-forward {form!r}")
    n_held = n_experts if n_held is None else n_held
    kr, kb, kg, ku, kd, ks = jax.random.split(rng, 6)
    p = {
        "router": L.lecun_normal(kr, (dim, n_experts), dim, dtype),
        "router_bias": jax.random.normal(kb, (n_experts,), dtype) * 0.05,
        "experts": {
            "up": L.lecun_normal(ku, (n_held, dim, hidden), dim, dtype),
            "down": L.lecun_normal(kd, (n_held, hidden, dim), hidden, dtype),
        },
    }
    if form == "swiglu":
        p["experts"]["gate"] = L.lecun_normal(kg, (n_held, dim, hidden), dim,
                                              dtype)
    if not selection_bias:
        del p["router_bias"]
    if shared:
        init = L.swiglu_init if form == "swiglu" else L.relu2_init
        p["shared"] = init(ks, dim, shared_hidden or hidden, dtype)
    return p


def route_topk(p: dict, tokens: jnp.ndarray, top_k: int,
               router: str = "sigmoid", renormalize: bool = True,
               scale: float = 1.0, eps: float = 1e-20):
    """``(experts, weights)``, both ``(N, top_k)``: the ``top_k`` largest of
    score + selection bias, weighted by the score alone (over their sum plus
    ``eps`` where ``renormalize``: a released model's own, ``1e-6`` in some)
    times ``scale``. Scores in float32 from a product at
    ``highest`` precision: a tie broken the other way sends a token to
    another expert. A chosen expert's score is read by comparing its number
    with every column's and taking the largest of what matches (one term, and
    no score is negative: the score to the bit), not by a gather of a scalar
    a pick; a sum there the compiler merges with the renormalisation's."""
    logits = jnp.dot(tokens.astype(jnp.float32),
                     p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if router == "sigmoid":
        score = jax.nn.sigmoid(logits)
    elif router == "softmax":
        score = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown router {router!r}")
    chosen = score
    if "router_bias" in p:
        chosen = score + p["router_bias"].astype(jnp.float32)
    _, experts = jax.lax.top_k(chosen, top_k)
    column = jnp.arange(score.shape[-1], dtype=experts.dtype)
    weights = jnp.max(jnp.where(experts[..., None] == column,
                                score[..., None, :], 0.0), axis=-1)
    if renormalize:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + eps)
    return experts, weights * scale


# The combine's loop: a block is at most this many consecutive tokens, a tile
# this many held assignments of one block. The pair was read on a v5e with a
# quarter and an eighth of the assignments held (PERF.md, PR 37), where a
# block of 256 tokens holds 256 to 512 of them and is one tile: 256 x 512 and
# 512 x 1,024 read the same, 512 x 512 and 128 x 256 a twentieth more (a tile
# gathers its empty places too, and then read and wrote its block's sums;
# since PR 69 a block's first tile writes them, only a further one reads them
# back and adds, and nothing zeroes them: :func:`_combine_held`). Where more is
# held the block is smaller, so that its expected run is still one tile (64
# tokens of 8 assignments each, all held; 96 of 10, half held).
_COMBINE_BLOCK = 256
_COMBINE_ROWS = 512

# A run's last tile may be computed at a smaller size, a multiple of this
# many rows (a bfloat16 tile of the matrix unit's operand is 128 rows deep).
_TILE_STEP = 128

# A tile's tokens are gathered this many rows at a time: the chip's gather of
# 1,024 rows of 4,096 channels read 51.2 us where two of 512 read 19.2 each
# and laying them end to end in fast memory 2 (2,688 channels: 35.7 for 2 x
# 14.9; 2,048: 28.3 for 2 x 11.65; 256 rows cost more a row again: PERF.md,
# PR 65), which was most of what a tile of 1,024 rows' products gained.
_GATHER_ROWS = 512

# The experts' loop cuts a run into tiles of this many rows where the expected
# run fills one, and of half as many where it does not (:func:`run_tile`).
_EXPERT_TILE = 1024


def run_tile(expected_run: float) -> int:
    """The rows of a tile of the experts' loop, from the expected run of a
    held expert (``n * top_k / width``: shapes alone): ``_EXPERT_TILE`` where
    that run fills one, half of it below. Every tile reads its expert's
    matrices whole, so a tile's rows are the FLOP it does a byte of weights:
    512 rows are twice the v5e's ridge of 240 FLOP a byte and no more, and
    at 4,096 x 768 a tile of 1,024 rows read its gate and up products at 91
    % of the chip's peak for 80 at 512 rows, its down product with the
    tile's write at 73 % for 59 (2,688 x 1,856: 8 % less a row; PERF.md, PR
    65). A run shorter than a tile buys only padding with a larger one: it
    is its own last tile, computed at :func:`tile_sizes`' small size at
    best. (2,048 rows gained 3 % more on the products and lost it in a last
    tile of 1,024 rows, there.)"""
    return _EXPERT_TILE if expected_run >= _EXPERT_TILE else _EXPERT_TILE // 2


def tile_sizes(tile: int, expected_run: float, tight: bool) -> tuple:
    """The row counts a loop over runs cut into tiles of ``tile`` has a body
    for: ``(tile,)`` or ``(tile, small)``, ``small`` a multiple of
    ``_TILE_STEP`` under ``tile`` at which a run's last tile is computed
    where its places fit. Two sizes at most: a body is a copy of the loop's
    whole work in the program, a layer, and costs its share of every load
    (PERF.md, PRs 55 and 56). ``expected_run``: the mean length of a run,
    from shapes alone. Of a run of a tile or more the last tile's places are
    as good as uniform over the tile, and half a tile spares the most rows.
    A shorter run is its own last tile, and ``small`` is the least size that
    holds the expectation: by itself where runs are as wide as a router is
    uneven (an expert's: no margin covers them, and the size serves the
    larger half), cleared by four roots of it where the run is a sum over a
    block's tokens and ``tight`` (a block's held assignments: 256 +- 16 miss
    a size of 256 half the time and one of 384 never). Where no size under
    the tile does that the loop keeps one: a size that half the runs miss
    read slower on the chip than none (PERF.md, PR 56). A ``tile`` that is
    no multiple of a step (the toy presets' 16) has one size."""
    if tile % _TILE_STEP:
        return (tile,)
    if expected_run >= tile:
        small = tile // 2
    else:
        small = expected_run + (4.0 * math.sqrt(expected_run) if tight else 0)
    small = max(1, math.ceil(small / _TILE_STEP)) * _TILE_STEP
    return (tile, small) if small < tile else (tile,)


def rows_computed(count, sizes: tuple):
    """How many rows a loop with bodies of ``sizes`` (:func:`tile_sizes`)
    computes for a run of ``count`` places: every tile whole but the last,
    and the last at the least size that holds its places. Of integers, of
    arrays of them, of traced ones."""
    tile, small = sizes[0], sizes[-1]
    left = count % tile
    return count - left + (small + (tile - small) * (left > small)) * (
        left > 0)


def _tiles_by_size(n_tiles, most: int, tile_at, sizes: tuple):
    """The tiles ``0 .. n_tiles - 1`` (``most`` at most; ``tile_at`` as
    :func:`_runs_in_tiles` gives it) in the order the loops meet them:
    ``(tiles, ends)``. ``tiles`` is ``(run, start, filled)``, each
    ``(most,)``: the tiles computed whole first and in their order, then
    those that fit ``sizes``' small one, in theirs (one stable sort of
    ``most`` keys that carries the three along), so a run's whole tiles lie
    together and its last tile, where it is computed small, after all of
    those. ``ends[k]`` is where the ``k``-th of ``sizes`` ends among them.
    (The combine's writing loops hand it a tile a run, the run's first:
    ``n_tiles`` and ``most`` the runs' number.) It only orders and counts:
    under ``moe.route``."""
    with jax.named_scope(parts.MOE_ROUTE):
        number = jnp.arange(most, dtype=jnp.int32)
        run, start, filled = tile_at(number)
        if len(sizes) == 1:
            return (run, start, filled), (n_tiles,)
        behind = jnp.where(number < n_tiles,  # no tile: behind all
                           (filled <= sizes[1]).astype(jnp.int32), 2)
        behind, run, start, filled = jax.lax.sort(
            (behind, run, start, filled), num_keys=1, is_stable=True)
        n_whole = jnp.sum(behind == 0, dtype=jnp.int32)
    return (run, start, filled), (n_whole, n_tiles)


def _loop_by_size(tiles, ends, sizes: tuple, at_rows, carry):
    """``carry`` through ``at_rows(m)(j, (run, start, filled), carry)`` for
    every place ``j`` of :func:`_tiles_by_size`'s order: a loop a size
    ``m``, each with a body of its own static row count. (A ``lax.switch``
    among the sizes inside one loop computes the same, and on a v5e a
    ``conditional`` whose branches hold matrix products cost 13 to 29 us a
    tile, more than a last tile's spared rows: PERF.md, PR 55.)"""
    first = 0
    for m, end in zip(sizes, ends):
        one_tile = at_rows(m)
        carry = jax.lax.fori_loop(first, end, lambda j, c: one_tile(
            j, tuple(a[j] for a in tiles), c), carry)
        first = end
    return carry


def _rows_in_that_order(tiles, ends, counts, total: int, tile: int,
                        zero_row: int):
    """Every assignment's row of the tiles' buffer where the tile at place
    ``j`` of :func:`_tiles_by_size`'s order (two sizes) is written at row
    ``j * tile`` (a write at the loop's own counter is fused into the
    product that makes it; one at a looked-up row is a pass of its own), in
    the dispatch's order: ``(total,)``. An assignment's row is its place
    moved by one of two shifts of its run: its whole tiles lie together from
    the run's first of them, its small last tile where the order put it.
    Which run by comparing the place with every run's ends, as
    :func:`_dispatch` finds its shift; ``zero_row`` for an absent one."""
    run, _, _ = tiles
    held = counts.shape[0]
    slot = jnp.arange(run.shape[0], dtype=jnp.int32)
    mine = (run[None, :] == jnp.arange(held, dtype=jnp.int32)[:, None]) & (
        slot < ends[1])[None, :]
    whole = mine & (slot < ends[0])[None, :]
    n_whole = jnp.sum(whole, axis=1, dtype=jnp.int32)
    first_whole = jnp.min(jnp.where(whole, slot, slot.shape[0]), axis=1)
    last = jnp.sum(jnp.where(mine & ~whole, slot, 0), axis=1)  # one at most
    begin = jnp.cumsum(counts) - counts
    turn = begin + n_whole * tile  # where the run's small last tile begins
    place = jnp.arange(total, dtype=jnp.int32)
    inside = (place >= begin[:, None]) & (place < (begin + counts)[:, None])
    shift = jnp.where(place < turn[:, None],
                      (first_whole * tile - begin)[:, None],
                      (last * tile - turn)[:, None])
    moved = jnp.sum(jnp.where(inside, shift, 0), axis=0)
    return jnp.where(place < jnp.sum(counts), place + moved, zero_row)


def _tiles_note(sizes: tuple) -> str:
    return f"last-{sizes[1]}" if len(sizes) > 1 else "whole"


def _run_bounds(keys, n_runs: int):
    """Where the runs of equal keys ``0 .. n_runs - 1`` start and end in
    ascending ``keys`` (a larger key: none of them, and last): ``(starts,
    ends)``, each ``(n_runs,)``. A run starts where a bisection of the sorted
    keys finds its key, so counting costs ``n_runs + 1`` queries whatever the
    keys' number (and neither a scatter-add nor a pass over them)."""
    bounds = jnp.searchsorted(
        keys, jnp.arange(n_runs + 1, dtype=keys.dtype)).astype(jnp.int32)
    return bounds[:-1], bounds[1:]


def _runs_in_tiles(starts, ends, tile: int):
    """The runs ``starts[r] .. ends[r]`` of some ordered places
    (:func:`_run_bounds`'s, or a part of each), each cut into tiles of
    ``tile`` places: ``(counts, shift, n_tiles, tile_at)``. With the tiles
    laid end to end in the runs' order, place ``q`` of run ``r`` lies at
    ``q + shift[r]`` among the tiles' places; ``n_tiles`` is the tiles'
    number, and ``tile_at(i)`` gives tile ``i`` of them (tiles ``i`` of any
    shape) as ``(run, start, filled)``: its run, where among the places it
    starts, and how many of its places lie inside the run (``tile`` or more
    but in a run's last tile). Which run by comparing ``i`` with every run's
    last tile, and the run's two numbers by comparing it with every run's: a
    gather of a few dozen values by as many indices the chip's compiler
    unrolls into a select a value, and a program's size is paid at every
    load (PERF.md, PR 56)."""
    counts = ends - starts
    tiles = -(-counts // tile)
    tile_ends = jnp.cumsum(tiles)
    shift = (tile_ends - tiles) * tile - starts
    run_ends = ends + shift  # among the tiles' places
    runs = jnp.arange(counts.shape[0], dtype=jnp.int32)

    def tile_at(i):
        i = jnp.asarray(i, jnp.int32)
        run = jnp.sum(tile_ends <= i[..., None], axis=-1, dtype=jnp.int32)
        mine = run[..., None] == runs

        def of_run(a):
            return jnp.sum(jnp.where(mine, a, 0), axis=-1)

        return run, i * tile - of_run(shift), of_run(run_ends) - i * tile

    return counts, shift, tile_ends[-1], tile_at


def _dispatch(local, weights, held: int, tile: int):
    """The grouped product's bookkeeping from ``local`` (every assignment's
    held expert, ``held`` for one held elsewhere) and ``weights``, both flat
    in the order of the assignments: ``(counts, number_at, weight_at, row_at,
    zero_row, n_tiles, tile_at)``. One stable sort by expert (so tokens
    ascend inside an expert, absent ones last) carries each assignment's
    number and weight to its place: ``number_at`` and ``weight_at`` are in
    that order, and so is ``row_at``, the assignment's row of the tiles'
    buffer: its place moved by its expert's shift (which of the ``held``
    shifts by comparing its key with every held expert's number, not by
    indexing), ``zero_row`` for an absent one: one row behind all that the
    most tiles any routing makes can fill. ``counts`` ``(held,)`` and the
    tiles are :func:`_runs_in_tiles`'s. Nothing here gathers or scatters an
    element an assignment."""
    total = local.shape[0]
    place = jnp.arange(total, dtype=jnp.int32)
    expert_at, number_at, weight_at = jax.lax.sort(
        (local, place, weights), num_keys=1, is_stable=True)
    counts, shift, n_tiles, tile_at = _runs_in_tiles(
        *_run_bounds(expert_at, held), tile)
    zero_row = (-(-total // tile) + held) * tile
    mine = expert_at[None, :] == jnp.arange(held, dtype=jnp.int32)[:, None]
    moved = jnp.sum(jnp.where(mine, shift[:, None], 0), axis=0)
    row_at = jnp.where(expert_at < held, place + moved, zero_row)
    return counts, number_at, weight_at, row_at, zero_row, n_tiles, tile_at


def _combine_held(out, row_at, token_at, n: int, top_k: int,
                  held_share: float, none_absent: bool = False):
    """``(y, tiles)``. ``y`` ``(n, dim)`` float32: each token's sum of its
    held assignments' rows of ``out``, reading no other row. The assignments
    come as pairs in any order, ``row_at`` the row of ``out`` and
    ``token_at`` the token (an absent assignment's row is the zero row, the
    last; its token is not read). The grouped product's mirror image: the
    held pairs are grouped by block of consecutive tokens (one sort keyed on
    the block, absent ones last, that carries the pair along), each block's
    run is cut into tiles of ``_COMBINE_ROWS``, and a loop over tiles
    gathers a tile's rows and adds them to the block's tokens by a 0/1
    matrix on the matrix unit (products with 0 and 1 are exact, the sum is
    float32), so the order inside a block is immaterial. ``tiles`` ``(2,)``
    int32: how many tiles wrote their block's sums and how many added to
    them.

    The block is the most tokens, a multiple of 8 and ``_COMBINE_BLOCK`` at
    most, whose expected run ``block * top_k * held_share`` fits one tile
    (``held_share``: the part of the assignments that is held, by shapes):
    256 where an eighth or a quarter of a router is held (the regime the two
    constants were read in), 64 where all of a top-8 router is, 96 where half
    of a ten-a-token router is (480 expected of a tile's 512: no smaller
    size clears the run's spread, so the loop has one). A tile's 0/1 matrix
    and the sums it touches are a block tall, so a block cut into four tiles
    multiplies four times what its rows need. Where the tokens are no whole
    number of blocks (32,768 are 341.33 blocks of 96) the sums are made for
    whole blocks and the result is their first ``n`` rows.

    No tile reads a block's sums but one that must add to them. A block's
    run never passes ``block * top_k``, so where that is the tile's size
    every block is one tile at most, whatever the routing: the loop runs over
    the tiles the data made and each writes its block's sums, noted
    ``combine_write=once``. Only a block without a held assignment then
    keeps the zeros the sums start from, and where the caller knows every
    assignment held (``none_absent``) there is none: the sums are allocated,
    not zeroed. Where a block's run may pass a tile (a part of the router is
    held: ``combine_write=first``) the loop runs over the blocks, every one,
    and writes each block's first tile (one of zeros for a block that holds
    nothing), so the sums are allocated whatever the routing; a block's
    further tiles, which a block of that size makes a few times in a hundred
    (Granite's half of ten a token) or never, are a part of each run
    (:func:`_runs_in_tiles` from a tile past the run's start) and add in a
    loop of their own after it, in their order and at the tile's whole size:
    a token's float32 sums are added in the order one loop over all would.
    Both loops' bodies see the sums a block a page, ``(blocks, block, dim)``
    (a view: a block is whole rows of 8), so that the 0/1 product writes its
    page where it lies: on a v5e a page written by the product's own fusion
    read 4.6 us for a block of 96 x 4,096 where the product and a write
    behind it at a looked-up row read 2.2 and 6.4 (PERF.md, PR 69).

    A block's run is short of a tile wherever a part of the router is held,
    so its last tile, which is mostly its only one, gathers and multiplies
    :func:`tile_sizes`' small size where its places fit, in a loop of that
    size (:func:`_loop_by_size`: the writing loops are one a size). Where the
    blocks are one tile each and the expected run fills it, no run is short
    but a window's last, and the loop has one size. Noted as
    ``combine_tiles=last-<small>`` (``whole``: one size)."""
    dim = out.shape[1]
    a_token = top_k * held_share  # its expected held assignments
    fits = int(_COMBINE_ROWS / a_token) if a_token else _COMBINE_BLOCK
    block = max(8, min(_COMBINE_BLOCK, fits // 8 * 8, -(-n // 8) * 8))
    rows = min(_COMBINE_ROWS, block * top_k)
    blocks = -(-n // block)
    once = rows == block * top_k
    if once and block * a_token >= rows:
        sizes = (rows,)
    else:
        sizes = tile_sizes(rows, block * a_token, tight=True)
    _note("combine_tiles", _tiles_note(sizes))
    _note("combine_write", "once" if once else "first")
    block_at, row_at, token_at = jax.lax.sort(
        (jnp.where(row_at < out.shape[0] - 1, token_at // block, blocks),
         row_at, token_at), num_keys=1, is_stable=False)
    total = row_at.shape[0]
    starts, ends = _run_bounds(block_at, blocks)
    # a slice of ``rows`` from any start inside the assignments stays inside
    row_at, token_at = jnp.pad(row_at, (0, rows)), jnp.pad(token_at, (0, rows))
    lane = jnp.arange(rows, dtype=jnp.int32)
    slot = jnp.arange(block, dtype=jnp.int32)
    # a float32 row times 1 stays float32 only at ``highest``
    exact = None if out.dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST

    def at_rows(m, adds=False):
        def one_tile(_, tile_j, y):
            b, start, filled = tile_j
            valid = lane[:m] < filled  # past the run's end: the zero row
            picked = out[jnp.where(valid, jax.lax.dynamic_slice(
                row_at, (start,), (m,)), out.shape[0] - 1)]
            at = jax.lax.dynamic_slice(token_at, (start,), (m,)) - b * block
            mine = (slot[:, None] == at[None, :]).astype(out.dtype)
            add = jnp.dot(mine, picked, precision=exact,
                          preferred_element_type=jnp.float32)
            if once:
                return jax.lax.dynamic_update_slice(y, add, (b * block, 0))
            # the sums seen a block a page (no bytes move): the product's
            # fusion writes a page at its looked-up number itself, where a
            # write at a looked-up row is a pass of its own behind it
            pages = y.reshape(blocks, block, dim)
            if adds:
                add = pages[b] + add
            return jax.lax.dynamic_update_slice(
                pages, add[None], (b, 0, 0)).reshape(y.shape)
        return one_tile

    sums = (blocks * block, dim), jnp.float32
    if once:
        _, _, n_tiles, tile_at = _runs_in_tiles(starts, ends, rows)
        tiles, upto = _tiles_by_size(n_tiles, -(-total // rows) + blocks,
                                     tile_at, sizes)
        # every block is written where none is empty
        make = jax.lax.empty if none_absent else jnp.zeros
        y = _loop_by_size(tiles, upto, sizes, at_rows, make(*sums))
        return y[:n], jnp.stack([n_tiles, jnp.zeros_like(n_tiles)])
    # a block's first tile is its run from the start, whatever it holds
    tiles, upto = _tiles_by_size(
        blocks, blocks, lambda b: (b, starts, ends - starts), sizes)
    y = _loop_by_size(tiles, upto, sizes, at_rows, jax.lax.empty(*sums))
    # the rest of a run that passes one tile, cut into tiles in its turn
    _, _, n_more, tile_at = _runs_in_tiles(
        jnp.minimum(starts + rows, ends), ends, rows)
    tiles, upto = _tiles_by_size(n_more, -(-total // rows), tile_at,
                                 sizes[:1])
    y = _loop_by_size(tiles, upto, sizes[:1], partial(at_rows, adds=True), y)
    return y[:n], jnp.stack([jnp.full_like(n_more, blocks), n_more])


def topk_moe_layer(p: dict, x: jnp.ndarray, top_k: int, first_expert: int = 0,
                   router: str = "sigmoid", renormalize: bool = True,
                   scale: float = 1.0, tile: Optional[int] = None,
                   eps: float = 1e-20):
    """Dropless top-``top_k`` expert layer over ``(..., dim)`` activations
    (``router``, ``renormalize``, ``scale`` and ``eps`` are
    :func:`route_topk`'s).

    The layer holds experts ``first_expert ..`` (as many as its stacked
    weights have) of the router's width, and returns ``(y, tokens, absent)``:
    ``y`` the held experts' part of the routed sum plus the shared expert,
    ``tokens`` ``(held,)`` how many tokens went to each held expert, and
    ``absent`` how many assignments fell on experts held elsewhere (their
    part is another chip's to add). :func:`topk_moe_layer_tiles` is the
    layer itself, and returns the combine's count of tiles besides.

    The dispatch: the assignments are ordered once, by expert (one held
    elsewhere last) and by token inside an expert, in a sort that carries
    each assignment's number and weight; how many a held expert has is where
    its run ends among the sorted keys (a bisection), and an assignment's
    row of the tiles' buffer is its place in that order plus its expert's
    offset, found by comparing its key with the held experts' numbers (one
    of its expert's two offsets where the loop has two sizes and the tiles
    lie in the buffer in the order the loops meet them:
    :func:`_rows_in_that_order`). No gather or scatter runs over the
    assignments. Noted as ``expert_dispatch=sorted``.

    The grouped product: each expert's run is cut into tiles of ``tile``
    rows (None: :func:`run_tile` of the expected run, from the shapes of the
    call; a number is the toy presets' way to a tile that is no multiple of
    128 rows), and a loop over as many tiles as the routing made gathers a
    tile's tokens (``_GATHER_ROWS`` at a time), runs that expert's
    feed-forward on them (``ops/layers.py feed_forward``: SwiGLU or squared
    ReLU, as the stacked parameters say; the shared expert likewise, at its
    own width) and writes the weighted result to the tile's place in a
    buffer. The loop's length is the data's, so whatever the routing no token is dropped
    and no padding up to a capacity is computed; the only waste is in each
    run's last, partly filled tile, and that tile is computed at a smaller
    size where its places fit one: the loop has at most two sizes
    (:func:`tile_sizes`: ``tile`` and one ``small``, chosen from the shapes
    the layer is called with), the tiles are parted by the size they need
    (:func:`_tiles_by_size`), and each size has a loop of its own that
    gathers, multiplies and writes that many rows at its counter's place in
    the buffer (:func:`_loop_by_size`). Noted as
    ``expert_tiles=last-<small>`` (``whole``: one size, as where ``tile`` is
    no multiple of 128 rows). The buffer alone has the worst case's size,
    and is allocated, not filled: the loops write every row an assignment
    has, rows of a small tile past its size stay as they were allocated and
    no one reads them (the combine reads held rows and the zero row), and
    the one row read without being written, the zero row behind them all,
    is set to zero by itself.

    A token's result is the float32 sum of its assignments' rows there, and
    never a ``[tokens, top_k, dim]`` array (a scatter-add would do it in
    place, but the chip scatters a row at a time, a thousand times slower
    than it gathers). A second loop of the same kind reads the held rows
    alone, a block of tokens at a time (:func:`_combine_held`, which takes
    the (row, token) pairs in the dispatch's order): an absent assignment's
    row is never read. Noted as ``expert_combine=held-rows``. The block
    follows the share of the router that is held, so that a block's run is
    about one tile: 256 tokens at an eighth or a quarter held, 64 where all
    of a top-8 router is, and there a tile is its block's only one and
    writes the block's sums once (``combine_write=once``). Where a block may
    take several tiles (``combine_write=first``) its first writes them too,
    and only a further one reads them back and adds: no form zeroes the sums
    to add every tile to them."""
    return topk_moe_layer_tiles(p, x, top_k, first_expert, router,
                                renormalize, scale, tile, eps)[:3]


def topk_moe_layer_tiles(p: dict, x: jnp.ndarray, top_k: int,
                         first_expert: int, router: str, renormalize: bool,
                         scale: float, tile: Optional[int], eps: float):
    """:func:`topk_moe_layer`, which says what it computes, with one count
    more: ``(y, tokens, absent, tiles)``, ``tiles`` ``(2,)`` the tiles of the
    combine that wrote a block's sums and those that added to them
    (:func:`_combine_held`)."""
    from storm_tpu.ops import layers as L

    shape = x.shape
    dim = shape[-1]
    w = p["experts"]
    held = w["down"].shape[0]
    _note("expert_ffn", "swiglu" if "gate" in w else "relu2")
    _note("expert_dispatch", "sorted")
    # Its three parts under their names in a device trace (ops/parts.py):
    # routing with everything that only orders and counts, the loop over
    # tiles, the combine.
    with jax.named_scope(parts.MOE_ROUTE):
        # the router reads ``x`` as it comes (float32 from a float32 stream:
        # a rounded input breaks ties the other way); the experts compute in
        # the type of their weights
        experts, weights = route_topk(p, x.reshape(-1, dim), top_k, router,
                                      renormalize, scale, eps)
        x = x.astype(w["down"].dtype)
        tokens = x.reshape(-1, dim)
        n = tokens.shape[0]
        width = p["router"].shape[1]
        expected_run = n * top_k / width
        if tile is None:
            tile = run_tile(expected_run)
        tile = max(8, min(int(tile), -(-n // 8) * 8))
        local = experts - first_expert
        local = jnp.where((local >= 0) & (local < held), local,
                          held).reshape(-1)
        (counts, number_at, weight_at, row_at, zero_row, n_tiles,
         tile_at) = _dispatch(local, weights.reshape(-1), held, tile)
        sizes = tile_sizes(tile, expected_run, tight=False)
        tiles, ends = _tiles_by_size(n_tiles, zero_row // tile, tile_at,
                                     sizes)
        if len(sizes) > 1:
            row_at = _rows_in_that_order(tiles, ends, counts, n * top_k,
                                         tile, zero_row)
        token_at = number_at // top_k
        absent = n * top_k - jnp.sum(counts)
        lane = jnp.arange(tile, dtype=jnp.int32)
        # one row more than the tiles can fill, where absent assignments and
        # the combine's empty lanes point: the only row that must be zero
        empty = jax.lax.dynamic_update_slice(
            jax.lax.empty((zero_row + 1, dim), x.dtype),
            jnp.zeros((1, dim), x.dtype), (zero_row, 0))
        # a slice of ``tile`` from any start inside the assignments stays
        # inside
        token_in, weight_in = (jnp.pad(a, (0, tile))
                               for a in (token_at, weight_at))

    def at_rows(m):
        def one_tile(j, tile_j, out):
            e, start, filled = tile_j
            valid = lane[:m] < filled  # rows past the run's end: zero
            ids = jnp.where(valid, jax.lax.dynamic_slice(
                token_in, (start,), (m,)), 0)
            rows = jnp.concatenate([tokens[ids[i:i + _GATHER_ROWS]]
                                    for i in range(0, m, _GATHER_ROWS)])
            y = L.feed_forward({name: s[e] for name, s in w.items()}, rows)
            gain = jnp.where(valid, jax.lax.dynamic_slice(
                weight_in, (start,), (m,)), 0.0)
            y = (y.astype(jnp.float32) * gain[:, None]).astype(out.dtype)
            return jax.lax.dynamic_update_slice(out, y, (j * tile, 0))
        return one_tile

    with jax.named_scope(parts.MOE_EXPERTS):
        out = _loop_by_size(tiles, ends, sizes, at_rows, empty)
    _note("expert_tiles", _tiles_note(sizes))
    _note("expert_combine", "held-rows")
    with jax.named_scope(parts.MOE_COMBINE):
        # the whole router from its first expert on (a traced first: unknown)
        whole = held == width and isinstance(first_expert, int) \
            and first_expert == 0
        y, combined = _combine_held(out, row_at, token_at, n, top_k,
                                    held / width, none_absent=whole)
    if "shared" in p:
        with jax.named_scope(parts.PROJ):
            shared = L.feed_forward(p["shared"], tokens)
        with jax.named_scope(parts.MOE_COMBINE):
            y = y + shared.astype(jnp.float32)
    with jax.named_scope(parts.MOE_COMBINE):
        return y.astype(x.dtype).reshape(shape), counts, absent, combined


def observe_expert_counts(metrics, cid: str, tokens, absent, combined, *,
                          width: int, tile: Optional[int] = None) -> None:
    """What :func:`topk_moe_layer_tiles` counted in one step, fetched to the
    host (``tokens`` ``(layers, held)``, ``absent`` ``(layers,)``,
    ``combined`` ``(layers, 2)``), into the registry under ``cid``: the
    combine's tiles that wrote a block's sums and those that added to them
    as two counters, the assignments held and absent as two more,
    the rows that the experts' loop computed for the held ones
    (:func:`rows_computed` of every expert's count at the sizes the layer
    chose: the step's assignments over the router's ``width`` are the
    expected run the layer saw, its tile ``tile`` or, where None, that run's
    :func:`run_tile`, its sizes :func:`tile_sizes` of both; the held over
    these is how full the tiles were; a step of fewer tokens than the tile
    cuts smaller tiles and computes less than is counted here), and once a
    layer the busiest held expert over the mean."""
    metrics.counter(cid, "expert_assignments_held").inc(int(tokens.sum()))
    rows = metrics.counter(cid, "expert_rows_computed")
    for layer, elsewhere in zip(tokens, absent):
        run = (int(layer.sum()) + int(elsewhere)) / width
        sizes = tile_sizes(run_tile(run) if tile is None else tile, run,
                           tight=False)
        rows.inc(int(rows_computed(layer, sizes).sum()))
    metrics.counter(cid, "expert_assignments_absent").inc(int(absent.sum()))
    written, added = (int(count) for count in combined.sum(axis=0))
    metrics.counter(cid, "combine_tiles_written").inc(written)
    metrics.counter(cid, "combine_tiles_added").inc(added)
    load = metrics.histogram(cid, "expert_tokens_max_over_mean")
    for layer in tokens:
        load.observe(float(layer.max()) / max(float(layer.mean()), 1e-9))


def moe_block_init(rng, dim: int, mlp_dim: int, num_heads: int, n_experts: int):
    """A transformer block whose MLP is an MoE: ln1/attn/ln2 as in the ViT
    block, MoE replacing the dense MLP."""
    from storm_tpu.ops import layers as L
    from storm_tpu.ops.attention import mha_init

    k1, k2 = jax.random.split(rng)
    return {
        "ln1": L.layernorm_init(dim),
        "attn": mha_init(k1, dim, num_heads),
        "ln2": L.layernorm_init(dim),
        "moe": moe_init(k2, dim, mlp_dim, n_experts),
    }


def moe_block(p: dict, x: jnp.ndarray, num_heads: int,
              capacity_factor: float = 1.25):
    """(B, S, D) -> ((B, S, D), aux_loss)."""
    from storm_tpu.ops import layers as L
    from storm_tpu.ops.attention import multi_head_attention

    x = x + multi_head_attention(p["attn"], L.layernorm(p["ln1"], x), num_heads)
    h, aux = moe_layer(p["moe"], L.layernorm(p["ln2"], x),
                       capacity_factor=capacity_factor)
    return x + h, aux
