"""How ``parallel/moe.py topk_moe_layer`` forms a token's sum: against the
plain per-token sum in float32 at ``highest``, over what the routing can do
to a chip that holds some of the experts (all, some, none of a token's
picks; every token on one expert; one block of tokens holding every held
assignment), and that the array the old form made cannot come back unseen."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from storm_tpu.ops import layers as L
from storm_tpu.ops.platform import dispatch_notes
from storm_tpu.parallel import moe
from storm_tpu.parallel.moe import route_topk, topk_moe_init, topk_moe_layer

DIM = 32
SERVED = (moe._COMBINE_BLOCK, moe._COMBINE_ROWS)  # 256 tokens, 512 rows


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Blocks of at most 16 tokens and tiles of 8 held rows: a hundred
    tokens then make several blocks, and a busy block several tiles."""
    monkeypatch.setattr(moe, "_COMBINE_BLOCK", 16)
    monkeypatch.setattr(moe, "_COMBINE_ROWS", 8)


def _block(n, top_k, share, most=16, tile=8):
    """The rule, said again: the most tokens, by eights and ``most`` at
    most, whose expected held assignments fit a tile; never under 8, never
    more than the tokens there are."""
    fits = int(tile / (top_k * share)) // 8 * 8 if share else most
    return max(8, min(most, fits, -(-n // 8) * 8))


def _plain(p, x, top_k, first, scale=2.5):
    """Every held expert on every token, weighted by what the router gave
    it, and the shared expert: nothing grouped, sorted or gathered."""
    t = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    chosen, weight = route_topk(p, t, top_k, scale=scale)
    y = jnp.zeros_like(t)
    for e in range(p["experts"]["down"].shape[0]):
        gain = jnp.sum(jnp.where(chosen == e + first, weight, 0.0), -1)
        y = y + gain[:, None] * L.feed_forward(
            {n: w[e] for n, w in p["experts"].items()}, t)
    return (y + L.feed_forward(p["shared"], t)).reshape(x.shape)


def _layer(n_experts, held, form="relu2", seed=0):
    return topk_moe_init(jax.random.PRNGKey(seed), DIM, 48, n_experts, held,
                         form=form, shared_hidden=40)


def _biased(p, expert, by):
    return {**p, "router_bias": p["router_bias"].at[expert].set(by)}


def _one_block_holds_all(p, n, first, held, block):
    """Tokens whose first channel is large and positive score near 1 on the
    held experts, the others near 0 there and 0.5 elsewhere: block ``block``
    of 16 tokens then makes every held assignment, the other blocks none."""
    router = jnp.zeros_like(p["router"]).at[0, first:first + held].set(4.0)
    x = jax.random.normal(jax.random.PRNGKey(5), (n, DIM)) * 0.1
    x = x.at[:, 0].set(-3.0).at[16 * block:16 * (block + 1), 0].set(3.0)
    return {**p, "router": router}, x


# id: (router width, held, first expert, top-k, tokens, what the routing does)
CASES = {
    "top2-all-held": (8, 8, 0, 2, 111, None),
    "top2-quarter-held": (8, 2, 2, 2, 111, None),
    "top2-half-held": (8, 4, 4, 2, 96, None),
    "top6-quarter-held": (16, 4, 4, 6, 100, None),
    "top8-eighth-held": (32, 4, 0, 8, 64, None),
    "top8-a-thirty-second-held": (32, 1, 7, 8, 64, None),
    "top6-all-held": (8, 8, 0, 6, 50, None),
    "none-held": (16, 2, 6, 2, 100, "none"),
    "every-token-to-one-held": (8, 2, 2, 2, 111, "to-held"),
    "every-token-to-one-absent": (8, 2, 2, 2, 111, "to-absent"),
    "one-block-holds-all": (16, 2, 4, 2, 100, "one-block"),
    "one-token": (8, 2, 0, 2, 1, None),
}


def _case(case, ffn):
    """A case's layer and tokens, routed as the case says."""
    width, held, first, top_k, n, routing = CASES[case]
    p = _layer(width, held, ffn)
    x = jax.random.normal(jax.random.PRNGKey(1), (n, DIM))
    if routing == "none":  # no token picks a held expert
        for e in range(first, first + held):
            p = _biased(p, e, -10.0)
    elif routing == "to-held":
        p = _biased(p, first + 1, 10.0)
    elif routing == "to-absent":
        p = _biased(p, 0, 10.0)
    elif routing == "one-block":
        p, x = _one_block_holds_all(p, n, first, held, block=3)
    return p, x


@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
@pytest.mark.parametrize("ffn", ["relu2", "swiglu"])
def test_a_tokens_sum_is_the_plain_sum(case, ffn):
    width, held, first, top_k, n, routing = CASES[case]
    p, x = _case(case, ffn)
    with jax.default_matmul_precision("highest"):
        with dispatch_notes() as seen:
            y, tokens, absent = jax.jit(lambda p, x: topk_moe_layer(
                p, x, top_k, first_expert=first, scale=2.5, tile=16))(p, x)
        want = _plain(p, x, top_k, first)
    # a block of 8 tokens is cut into tiles of 8 rows: several a block
    assert _block(n, top_k, held / width) * top_k > 8
    assert seen == [f"expert_ffn={ffn}", "expert_dispatch=sorted",
                    "expert_tiles=whole", "expert_combine=held-rows",
                    "combine_tiles=whole", "combine_write=first"]
    np.testing.assert_allclose(y, want, atol=1e-5 * max(1.0, float(
        jnp.abs(want).max())))
    assert int(tokens.sum()) + int(absent) == n * top_k
    if routing == "none":
        assert int(tokens.sum()) == 0
        np.testing.assert_allclose(y, L.feed_forward(p["shared"], x),
                                   atol=1e-6)
    elif routing == "to-held":
        assert int(tokens[1]) == n
    elif routing == "to-absent":
        assert int(absent) >= n
    elif routing == "one-block":
        # 16 tokens make all 32 held assignments: four tiles of 8 in block
        # 3, and six blocks (a ragged one last) with none
        assert tokens.tolist() == [16, 16] and int(absent) == 2 * n - 32


def _assignments(seed, n, top_k, rows, share):
    """A buffer of ``rows`` rows and a zero row behind them, and for each of
    ``n x top_k`` assignments its row: the zero row where it is absent."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    out = jnp.concatenate([jax.random.normal(k1, (rows, DIM)),
                           jnp.zeros((1, DIM))])
    is_held = jax.random.uniform(k2, (n * top_k,)) < share
    row_of = jnp.where(is_held, jax.random.randint(
        k3, (n * top_k,), 0, rows), rows).astype(jnp.int32)
    return out, row_of


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("n,top_k,share", [
    (100, 2, 1.0), (100, 6, 1.0), (64, 8, 1.0), (100, 6, 0.25),
    (37, 8, 0.125), (16, 6, 0.5), (100, 2, 0.0), (7, 6, 0.3),
    (100, 8, 1 / 32)],
    ids=["top2-all", "top6-all", "top8-all", "top6-quarter", "top8-eighth",
         "one-block", "none", "under-a-block", "top8-a-thirty-second"])
def test_the_loop_on_any_share_held(dtype, n, top_k, share):
    """The loop alone, whatever the share held and in either type of row:
    the 0/1 product is exact, so bfloat16 rows sum as their float32 values."""
    out, row_of = _assignments(3, n, top_k, 150, share)
    out = out.astype(dtype)
    want = np.asarray(out.astype(jnp.float32), np.float64)[
        np.asarray(row_of)].reshape(n, top_k, DIM).sum(1)
    token = jnp.arange(n * top_k, dtype=jnp.int32) // top_k
    # the pairs in any order: the combine sorts them by block itself
    mixed = jax.random.permutation(jax.random.PRNGKey(4), n * top_k)
    got, tiles = jax.jit(lambda o, r, t: moe._combine_held(
        o, r, t, n, top_k, share))(out, row_of[mixed], token[mixed])
    assert got.dtype == jnp.float32 and got.shape == (n, DIM)
    assert tiles.dtype == jnp.int32 and tiles.shape == (2,)
    np.testing.assert_allclose(got, want, atol=2e-6)


# (top-k, share held): (block, tile, the small size or None) at the served
# constants and tokens in plenty. The first of each line is what the cells
# and the presets have: 2 x 1/4 the tiny presets, 6 x 1/4 Nemotron's, 8 x 1
# Trinity's and Keye's, 8 x 1/8 Kimi-Linear's and Solar's, 8 x 1/32 Kimi K2's
RULE = {
    (2, 1.0): (256, 512, None), (2, 0.5): (256, 512, 384),
    (2, 0.25): (256, 512, 256), (2, 0.125): (256, 512, 128),
    (2, 1 / 32): (256, 512, 128),
    (6, 1.0): (80, 480, None), (6, 0.5): (168, 512, None),
    (6, 0.25): (256, 512, None), (6, 0.125): (256, 512, 256),
    (6, 1 / 32): (256, 512, 128),
    (8, 1.0): (64, 512, None), (8, 0.5): (128, 512, 256),
    (8, 0.25): (256, 512, 256), (8, 0.125): (256, 512, 384),
    (8, 1 / 32): (256, 512, 128),
}


def _loops_of(jaxpr):
    """Of each loop of a traced combine, in order (a ``while``, or the
    ``scan`` a loop of a static length is traced to): the 0/1 matrix's
    shape, and the shapes its body slices out of an array that it carries
    whole (the read of a block's sums is one page, ``(1, block, dim)``)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name not in ("while", "scan"):
            continue
        body = eqn.params["body_jaxpr" if eqn.primitive.name == "while"
                          else "jaxpr"].jaxpr
        (dot,) = [e for e in body.eqns if e.primitive.name == "dot_general"]
        sliced = [e.outvars[0].aval.shape for e in body.eqns
                  if e.primitive.name == "dynamic_slice"
                  and e.outvars[0].aval.dtype == jnp.float32]
        found.append((dot.invars[0].aval.shape, sliced))
    return found


@pytest.mark.parametrize("tokens", ["whole-blocks", "a-short-last-block",
                                    "a-hundred"])
@pytest.mark.parametrize("top_k,share", list(RULE), ids=[
    f"top{k}-{s:.3g}-held" for k, s in RULE])
def test_the_block_is_the_most_tokens_whose_run_fits_a_tile(
        top_k, share, tokens, monkeypatch):
    """At the served constants: the block and the tile by the rule. Where
    the tile is ``block * top_k`` rows (no block can then make two) every
    tile writes its block's sums without reading them, noted
    ``combine_write=once``; the loop has one size there exactly where the
    expected run fills the tile too, and the sums are allocated and not
    zeroed exactly where besides every assignment is held. Elsewhere
    (``combine_write=first``) the loops of either size write too, one tile a
    block, the sums are allocated whatever is held, and one loop more, of
    the whole size, reads a block's sums and adds. Every row of an allocation
    is written: handed a buffer full of NaN the result is the plain sum."""
    monkeypatch.setattr(moe, "_COMBINE_BLOCK", SERVED[0])
    monkeypatch.setattr(moe, "_COMBINE_ROWS", SERVED[1])
    block, rows, small = RULE[top_k, share]
    assert block == _block(10 ** 6, top_k, share, *SERVED)
    n = {"whole-blocks": 3 * block, "a-short-last-block": 3 * block + 20,
         "a-hundred": 100}[tokens]
    if n == 100:  # 104 tokens a block at most, and a shorter run of them
        block = min(block, 104)
        rows = min(rows, block * top_k)
        small = (moe.tile_sizes(rows, block * top_k * share, True)
                 + (None,))[1]
    assert block == _block(n, top_k, share, *SERVED)
    once = rows == block * top_k
    assert once == (top_k == 2 or share == 1.0)
    if once and share == 1.0:
        small = None

    out, row_of = _assignments(3, n, top_k, 150, share)
    token = jnp.arange(n * top_k, dtype=jnp.int32) // top_k
    mixed = jax.random.permutation(jax.random.PRNGKey(4), n * top_k)
    allocated = []
    monkeypatch.setattr(jax.lax, "empty", lambda shape, dtype: (
        allocated.append(shape), jnp.full(shape, jnp.nan, dtype))[1])

    def combine(o, r, t):
        return moe._combine_held(o, r, t, n, top_k, share,
                                 none_absent=share == 1.0)

    with dispatch_notes() as seen:
        loops = _loops_of(jax.make_jaxpr(combine)(
            out, row_of[mixed], token[mixed]).jaxpr)
    assert seen == ["combine_tiles=" + (f"last-{small}" if small else
                                        "whole"),
                    "combine_write=" + ("once" if once else "first")]
    writers = [(block, m) for m in ((rows, small) if small else (rows,))]
    assert [mine for mine, _ in loops] == writers + [(block, rows)] * (
        not once)
    for at, (_, sliced) in enumerate(loops):
        assert (block, DIM) not in sliced
        assert ((1, block, DIM) in sliced) == (at == len(writers))
    blocks = -(-n // block)
    assert allocated == ([(blocks * block, DIM)] if share == 1.0 or not once
                         else [])
    got, tiles = jax.jit(combine)(out, row_of[mixed], token[mixed])
    assert int(tiles.sum()) == sum(
        -(-held // rows) or (not once) for held in np.bincount(
            np.arange(n * top_k)[np.asarray(row_of) < 150] // top_k // block,
            minlength=blocks))
    assert int(tiles[1]) == int(tiles.sum()) - blocks if not once else (
        int(tiles[1]) == 0)
    want = np.asarray(out, np.float64)[np.asarray(row_of)].reshape(
        n, top_k, DIM).sum(1)
    assert got.dtype == jnp.float32 and got.shape == (n, DIM)
    np.testing.assert_allclose(got, want, atol=2e-6)


# the five cells that hold a part of their router, a quarter of their sizes
# (blocks of 64 tokens at most, tiles of 128 rows, sizes by steps of 32):
# (top-k, share held): (tokens, block, the small size or None)
PART_HELD = {
    "kimi_linear_48b": ((8, 1 / 8), (512, 64, 96)),
    "nemotron_3_nano_30b": ((6, 1 / 4), (512, 64, None)),
    "kimi_k2_6": ((8, 1 / 32), (512, 64, 32)),
    "solar_open2_250b": ((8, 40 / 320), (500, 64, 96)),
    "granite_4_h_small": ((10, 1 / 2), (500, 24, None)),
}


def _added(out, row_at, token_at, n, block, rows, small):
    """The form this one replaced, tile by tile in plain steps: the sums
    start as zeros and every tile reads its block's, adds and writes them
    back, the whole tiles first and then those that fit ``small``."""
    blocks, zero = -(-n // block), out.shape[0] - 1
    at, row_at, token_at = jax.lax.sort(
        (jnp.where(row_at < zero, token_at // block, blocks), row_at,
         token_at), num_keys=1, is_stable=False)
    ends = np.searchsorted(np.asarray(at), np.arange(blocks + 1))
    row_at, token_at = (np.pad(np.asarray(a), (0, rows))
                        for a in (row_at, token_at))
    tiles = [(b, s, ends[b + 1] - s) for b in range(blocks)
             for s in range(ends[b], ends[b + 1], rows)]
    y = jnp.zeros((blocks * block, out.shape[1]), jnp.float32)
    for m, fits in ((rows, lambda f: not small or f > small),
                    (small, lambda f: small and f <= small)):
        for b, s, filled in (t for t in tiles if fits(t[2])):
            picked = out[np.where(np.arange(m) < filled, row_at[s:s + m],
                                  zero)]
            mine = np.arange(block)[:, None] == token_at[s:s + m] - b * block
            y = y.at[b * block:(b + 1) * block].add(jnp.dot(
                jnp.asarray(mine, out.dtype), picked,
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32))
    return y[:n]


@pytest.mark.parametrize("routing", ["drawn", "a-block-overflows",
                                     "a-block-holds-nothing"])
@pytest.mark.parametrize("cell", list(PART_HELD))
def test_a_blocks_first_tile_writes_and_only_a_further_one_adds(
        cell, routing, monkeypatch):
    """Where a part of the router is held (``combine_write=first``), on the
    usual draw, with every assignment of two blocks held (the most a block
    can hold: four tiles of Kimi's 64 x 8, three of Nemotron's, two of
    Granite's 24 x 10; the second block one assignment past a tile, so that
    its further tile would fit the small size) and with a block that holds
    nothing: the plain per-token sum; the form that zeroed the sums and
    added every tile to them, bit for bit, a further tile at the whole size
    too; a tile written a block, the further ones added and counted; and the
    sums allocated, not zeroed: from an allocation full of NaN, a block that
    holds nothing is written zeros."""
    (top_k, share), (n, block, small) = PART_HELD[cell]
    rows = 128
    monkeypatch.setattr(moe, "_COMBINE_BLOCK", 64)
    monkeypatch.setattr(moe, "_COMBINE_ROWS", rows)
    monkeypatch.setattr(moe, "_TILE_STEP", 32)
    out, row_of = _assignments(7, n, top_k, 300, share)
    token = jnp.arange(n * top_k, dtype=jnp.int32) // top_k
    of_block = np.asarray(token) // block
    if routing == "a-block-overflows":
        past = np.flatnonzero(of_block == 3)[:rows + 1]
        row_of = jnp.where((of_block == 1) | np.isin(
            np.arange(n * top_k), past), jnp.arange(n * top_k) % 300,
            jnp.where(of_block == 3, 300, row_of))
    elif routing == "a-block-holds-nothing":
        row_of = jnp.where(of_block == 2, 300, row_of)
    blocks = -(-n // block)
    runs = np.bincount(of_block[np.asarray(row_of) < 300], minlength=blocks)
    further = int(np.maximum(-(-runs // rows) - 1, 0).sum())
    if routing == "a-block-overflows":
        assert runs[1] == block * top_k and runs[3] == rows + 1
        assert further >= -(-block * top_k // rows)  # block 1's, and one
    elif routing == "a-block-holds-nothing":
        assert runs[2] == 0
    # a toy block of Granite's, 120 +- 8 of a tile's 128, passes it unasked
    assert further == 0 or "overflows" in routing or "granite" in cell
    mixed = jax.random.permutation(jax.random.PRNGKey(4), n * top_k)
    allocated = []
    monkeypatch.setattr(jax.lax, "empty", lambda shape, dtype: (
        allocated.append(shape), jnp.full(shape, jnp.nan, dtype))[1])
    with dispatch_notes() as seen:
        got, tiles = jax.jit(lambda o, r, t: moe._combine_held(
            o, r, t, n, top_k, share))(out, row_of[mixed], token[mixed])
    assert seen == ["combine_tiles=" + (f"last-{small}" if small else
                                        "whole"), "combine_write=first"]
    assert allocated == [(blocks * block, DIM)]
    assert tiles.tolist() == [blocks, further]
    want = jax.ops.segment_sum(out[row_of], token, num_segments=n)
    np.testing.assert_allclose(got, want, atol=2e-6)
    old = _added(out, row_of[mixed], token[mixed], n, block, rows, small)
    assert np.array_equal(np.asarray(got).view(np.uint32),
                          np.asarray(old + 0.0).view(np.uint32))


@pytest.mark.parametrize("case", ["top6-quarter-held", "one-block-holds-all",
                                  "none-held", "top2-all-held-once"])
def test_the_combines_tiles_are_counted_into_the_registry(case, monkeypatch):
    """``topk_moe_layer_tiles`` is the layer with one count more, and
    ``observe_expert_counts`` adds it to two counters: where a block may pass
    a tile, a tile written a block (thirteen blocks of 8 tokens, seven of 16)
    and a tile added for each 8 held assignments of a block past its first
    (three where one block of 16 holds all 32); where it cannot
    (``combine_write=once``, at the served constants) the tiles the data
    made, all written and none added. Two steps: a counter adds."""
    from storm_tpu.runtime.metrics import MetricsRegistry

    if case.endswith("once"):
        monkeypatch.setattr(moe, "_COMBINE_BLOCK", SERVED[0])
        monkeypatch.setattr(moe, "_COMBINE_ROWS", SERVED[1])
    width, held, first, top_k, n, _ = CASES[case.removesuffix("-once")]
    p, x = _case(case.removesuffix("-once"), "swiglu")
    how = (first, "sigmoid", True, 2.5, 16, 1e-20)
    with dispatch_notes() as seen:
        y, tokens, absent, tiles = jax.jit(
            lambda p, x: moe.topk_moe_layer_tiles(p, x, top_k, *how))(p, x)
    plain = jax.jit(lambda p, x: topk_moe_layer(
        p, x, top_k, first_expert=first, scale=2.5, tile=16))(p, x)
    assert len(plain) == 3
    for a, b in zip(plain, (y, tokens, absent)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    once = case.endswith("once")
    assert ("combine_write=once" in seen) == once
    chosen, _ = route_topk(p, x, top_k, scale=2.5)
    block = _block(n, top_k, held / width, *(SERVED if once else (16, 8)))
    runs = np.bincount(np.repeat(np.arange(n) // block, top_k)[
        (np.asarray(chosen).reshape(-1) >= first)
        & (np.asarray(chosen).reshape(-1) < first + held)],
        minlength=-(-n // block))
    if once:
        want = [int((runs > 0).sum()), 0]
    else:
        want = [len(runs), int(np.maximum(-(-runs // 8) - 1, 0).sum())]
    assert tiles.tolist() == want
    assert want == {"none-held": [7, 0], "one-block-holds-all": [7, 3],
                    "top2-all-held-once": [1, 0]}.get(case, want)
    registry = MetricsRegistry()
    for _ in range(2):
        moe.observe_expert_counts(
            registry, "bolt", np.asarray(tokens)[None],
            np.asarray(absent)[None], np.asarray(tiles)[None], width=width,
            tile=16)
    got = registry.snapshot()["bolt"]
    assert got["combine_tiles_written"] == 2 * want[0]
    assert got["combine_tiles_added"] == 2 * want[1]
    assert got["combine_tiles_written"] + got["combine_tiles_added"] == 2 * (
        int(np.maximum(-(-runs // (block * top_k if once else 8)),
                       not once).sum()))


@pytest.mark.parametrize("held,first,allocated", [
    (8, 0, True), (8, None, False), (4, 0, False), (4, 4, False)],
    ids=["whole-router", "whole-router-traced-first", "first-half",
         "second-half"])
def test_the_sums_are_allocated_only_where_the_whole_router_is_held(
        held, first, allocated, monkeypatch):
    """At the served constants, top-2 of 8 (a block is one tile in each
    case): the layer allocates its sums where it holds the router's whole
    width from expert 0 on, by shapes and a static ``first_expert`` (a
    traced one could be anything: zeros), and then an allocation full of
    NaN gives the zeroed form's bytes."""
    monkeypatch.setattr(moe, "_COMBINE_BLOCK", SERVED[0])
    monkeypatch.setattr(moe, "_COMBINE_ROWS", SERVED[1])
    n, tile = 100, 16
    p = _layer(8, held, "swiglu")
    x = jax.random.normal(jax.random.PRNGKey(1), (n, DIM))
    sums = (104, DIM)
    got = {}
    for fill in (jnp.nan, 0.0):
        shapes = []
        monkeypatch.setattr(jax.lax, "empty", lambda shape, dtype: (
            shapes.append((shape, dtype)), jnp.full(shape, fill, dtype))[1])
        if first is None:
            got[fill] = jax.jit(lambda p, x, f: topk_moe_layer(
                p, x, 2, first_expert=f, tile=tile))(p, x, 0)
        else:
            got[fill] = jax.jit(lambda p, x: topk_moe_layer(
                p, x, 2, first_expert=first, tile=tile))(p, x)
        assert ((sums, jnp.float32) in shapes) == allocated
        assert len(shapes) == 1 + allocated  # the tiles' buffer, always
    for a, b in zip(got[jnp.nan], got[0.0]):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert np.isfinite(np.asarray(got[jnp.nan][0])).all()


@pytest.mark.parametrize("held,first", [(8, 0), (2, 2)],
                         ids=["all-held", "quarter-held"])
def test_bfloat16_rows_are_summed_in_float32(held, first):
    """The served type: bfloat16 weights and rows, a float32 stream. Against
    the float32 layer on the same rounded weights (so that both route
    alike): within bfloat16's error of the largest value."""
    p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), _layer(8, held))
    x = jax.random.normal(jax.random.PRNGKey(1), (100, DIM)).astype(
        jnp.bfloat16).astype(jnp.float32)
    run = jax.jit(lambda p, x: topk_moe_layer(
        p, x, 2, first_expert=first, scale=2.5, tile=16))
    y, tokens, absent = run(p, x)
    with jax.default_matmul_precision("highest"):
        want, tokens32, absent32 = run(
            jax.tree.map(lambda a: a.astype(jnp.float32), p), x)
    assert y.dtype == jnp.bfloat16
    assert tokens.tolist() == tokens32.tolist() and int(absent) == int(
        absent32)
    np.testing.assert_allclose(y.astype(jnp.float32), want,
                               atol=2e-2 * float(jnp.abs(want).max()))


def _gathered_weights(p, tokens, top_k, router, renormalize, scale):
    """``route_topk`` as it was: the chosen scores by ``take_along_axis``,
    then the same renormalisation and scale."""
    logits = jnp.dot(tokens.astype(jnp.float32),
                     p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    score = (jax.nn.sigmoid(logits) if router == "sigmoid"
             else jax.nn.softmax(logits, axis=-1))
    chosen = score
    if "router_bias" in p:
        chosen = score + p["router_bias"].astype(jnp.float32)
    _, experts = jax.lax.top_k(chosen, top_k)
    weights = jnp.take_along_axis(score, experts, axis=-1)
    if renormalize:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return experts, weights * scale


@pytest.mark.parametrize("top_k", [6, 8])
@pytest.mark.parametrize("width", [128, 256, 384])
@pytest.mark.parametrize("renormalize", [True, False],
                         ids=["renormalized", "as-scored"])
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no-bias"])
@pytest.mark.parametrize("router", ["sigmoid", "softmax"])
def test_chosen_scores_by_comparison_are_the_gathered_ones_to_the_bit(
        router, bias, renormalize, width, top_k):
    """The weights ``route_topk`` returns are ``take_along_axis(score,
    experts)`` with the same renormalisation, every float32 bit of them, and
    the experts chosen are the same: over the router's width one column
    matches a pick, and no score is negative."""
    p = topk_moe_init(jax.random.PRNGKey(width + top_k), DIM, 48, width, 4)
    if not bias:
        p = {k: v for k, v in p.items() if k != "router_bias"}
    x = jax.random.normal(jax.random.PRNGKey(6), (200, DIM)) * 3.0
    experts, weights = jax.jit(lambda p, x: route_topk(
        p, x, top_k, router, renormalize, 2.5))(p, x)
    want_experts, want = jax.jit(lambda p, x: _gathered_weights(
        p, x, top_k, router, renormalize, 2.5))(p, x)
    assert weights.dtype == jnp.float32 and weights.shape == (200, top_k)
    assert np.array_equal(np.asarray(experts), np.asarray(want_experts))
    assert np.array_equal(np.asarray(weights).view(np.uint32),
                          np.asarray(want).view(np.uint32))
    assert float(weights.min()) > 0.0


@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
def test_the_buffer_may_hold_anything_but_its_zero_row(case, monkeypatch):
    """The tiles' buffer is allocated, not filled, and so are the combine's
    sums (blocks of 8 or 16 tokens in tiles of 8 rows: a block's first tile
    writes them, ``combine_write=first``): handed both full of NaN the layer
    gives ``y``, ``tokens`` and ``absent`` of the zeroed form to the bit,
    for a share held, none absent, every assignment absent (every block is
    written zeros), and runs that end in a partly filled tile (111 tokens on
    one expert in tiles of 16). Only the zero row is read without having
    been written."""
    _, held, first, top_k, n, _ = CASES[case]
    p, x = _case(case, "swiglu")
    tile = min(16, -(-n // 8) * 8)  # the layer's, for one token too
    got = {}
    for fill in (jnp.nan, 0.0):
        shapes = []
        monkeypatch.setattr(jax.lax, "empty", lambda shape, dtype: (
            shapes.append(shape), jnp.full(shape, fill, dtype))[1])
        got[fill] = jax.jit(lambda p, x: topk_moe_layer(
            p, x, top_k, first_expert=first, scale=2.5, tile=16))(p, x)
        block = _block(n, top_k, held / CASES[case][0])
        assert shapes == [((-(-n * top_k // tile) + held) * tile + 1, DIM),
                          (-(-n // block) * block, DIM)]
    for a, b in zip(got[jnp.nan], got[0.0]):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert int(got[0.0][1].sum()) + int(got[0.0][2]) == n * top_k


def _lowered(p, x, top_k, first):
    return jax.jit(lambda p, x: topk_moe_layer(
        p, x, top_k, first_expert=first, tile=16)).lower(p, x).as_text()


def _gathered_rows(text):
    return {int(m) for line in text.splitlines() if "gather" in line
            for m in re.findall(r"-> tensor<(\d+)x%d" % DIM, line)}


def test_no_array_of_tokens_by_picks_by_width_is_formed():
    """64 tokens, top-6, a quarter held: the lowered program holds no
    float32 ``[64, 6, dim]`` and gathers 384 rows nowhere (the old form did
    both, and paid 20 ms a layer for it on the chip); its gathers are a tile
    of the experts' loop and a tile of the combine's."""
    p = _layer(16, 4)
    x = jax.random.normal(jax.random.PRNGKey(2), (64, DIM))
    text = _lowered(p, x, 6, 4)
    assert "tensor<64x6x%d" % DIM not in text
    assert 64 * 6 not in _gathered_rows(text)
    assert _gathered_rows(text) == {16, 8}
    # the same where every expert is held
    text = _lowered(_layer(8, 8), x, 6, 0)
    assert "tensor<64x6x%d" % DIM not in text
    assert _gathered_rows(text) == {16, 8}
    # and the pattern does find the old form
    old = jax.jit(lambda o, r: jnp.sum(o[r.reshape(64, 6)], axis=1,
                                       dtype=jnp.float32)).lower(
        jnp.zeros((100, DIM)), jnp.zeros((384,), jnp.int32)).as_text()
    assert "tensor<64x6x%d" % DIM in old


# share held: (router width, held, first expert, top-k)
SHARES = {
    "a-thirty-second-held": (32, 1, 5, 8),
    "an-eighth-held": (32, 4, 8, 8),
    "a-quarter-held": (16, 4, 4, 6),
    "all-held": (8, 8, 0, 2),
}


def _routed(share, routing, n=100):
    """A layer, its tokens and the plain routing: every assignment's held
    expert (``held`` where another chip holds it) and weight."""
    width, held, first, top_k = SHARES[share]
    p = _layer(width, held, "swiglu", seed=2)
    if routing == "skewed":  # every token picks the first held expert
        p = _biased(p, first, 10.0)
    elif routing == "one-expert-empty":  # and no token the last
        p = _biased(p, first + held - 1, -10.0)
    x = jax.random.normal(jax.random.PRNGKey(3), (n, DIM))
    chosen, weight = route_topk(p, x, top_k, scale=2.5)
    local = np.asarray(chosen).reshape(-1) - first
    local = np.where((local >= 0) & (local < held), local, held)
    return p, x, local, np.asarray(weight).reshape(-1)


@pytest.mark.parametrize("routing", ["even", "skewed", "one-expert-empty"])
@pytest.mark.parametrize("share", list(SHARES))
def test_counts_are_the_plain_routings(share, routing):
    """``tokens`` and ``absent`` are ``numpy.bincount`` of the plain routing:
    the same integers, counted where a run of sorted keys ends."""
    width, held, first, top_k = SHARES[share]
    p, x, local, _ = _routed(share, routing)
    _, tokens, absent = jax.jit(lambda p, x: topk_moe_layer(
        p, x, top_k, first_expert=first, scale=2.5, tile=16))(p, x)
    want = np.bincount(local, minlength=held + 1)
    assert tokens.dtype == jnp.int32 and tokens.shape == (held,)
    assert tokens.tolist() == want[:held].tolist()
    assert int(absent) == want[held]
    if routing == "skewed":
        assert int(tokens[0]) == 100
    elif routing == "one-expert-empty":
        assert int(tokens[held - 1]) == 0


@pytest.mark.parametrize("routing", ["even", "skewed", "one-expert-empty"])
@pytest.mark.parametrize("share", list(SHARES))
def test_every_held_assignment_has_a_row_of_its_own(share, routing):
    """The dispatch alone: the order is the stable order by expert with each
    assignment's weight beside it, every held assignment's row of the buffer
    is written by exactly one tile of the experts' loop, from that
    assignment's place, no two share a row, and an absent one points at the
    zero row behind them all."""
    width, held, first, top_k = SHARES[share]
    tile = 16
    _, _, local, weight = _routed(share, routing)
    counts, number_at, weight_at, row_at, zero_row, n_tiles, tile_at = (
        moe._dispatch(jnp.asarray(local, jnp.int32), jnp.asarray(weight),
                      held, tile))
    order = np.argsort(local, kind="stable")
    assert np.asarray(number_at).tolist() == order.tolist()
    assert np.asarray(weight_at).tolist() == weight[order].tolist()
    assert counts.tolist() == np.bincount(local, minlength=held + 1)[
        :held].tolist()
    written = {}  # a row of the buffer -> the place it is written from
    for i in range(int(n_tiles)):
        e, start, filled = (int(v) for v in tile_at(i))
        assert 0 < filled and 0 <= e < held
        for lane in range(min(filled, tile)):
            assert local[order[start + lane]] == e
            written[i * tile + lane] = start + lane
    n_held = int(counts.sum())
    assert sorted(written.values()) == list(range(n_held))
    rows = np.asarray(row_at)
    assert [written[r] for r in rows[:n_held].tolist()] == list(range(n_held))
    assert len(set(rows[:n_held].tolist())) == n_held
    assert (rows[n_held:] == zero_row).all() and zero_row > max(
        written, default=-1)
    assert zero_row == (-(-local.size // tile) + held) * tile


def test_no_gather_or_scatter_runs_over_the_assignments():
    """64 tokens, top-6, a quarter held: the lowered layer holds no scatter
    and no gather that returns a value an assignment, ``route_topk``'s
    chosen scores included (flat or ``[tokens, top_k]``: they are a
    comparison and a maximum); it reads the ordered assignments only where a
    bisection probes them (``held + 1`` and ``blocks + 1`` probes, the blocks
    the rule's: 8 tokens each here), sorts twice beside the router's top-k,
    and makes the tiles' buffer of nothing that fills it."""
    held, top_k, n = 4, 6, 64
    text = _lowered(_layer(16, held), jax.random.normal(
        jax.random.PRNGKey(2), (n, DIM)), top_k, 4)
    assert "scatter" not in text
    assert text.count("stablehlo.sort") == 2 and "chlo.top_k" in text
    gathers = [line for line in text.splitlines() if "stablehlo.gather" in line]
    assert gathers
    flat = "tensor<%dx" % (n * top_k)
    picks = "tensor<%dx%dx" % (n, top_k)
    probes = {held + 1, n // _block(n, top_k, held / 16) + 1}
    assert probes == {5, 9}
    for line in gathers:
        operand, result = re.search(r": \((tensor<[^>]*>), .*\) -> "
                                    r"(tensor<[^>]*>)", line).groups()
        assert not result.startswith((flat, picks)), line
        if operand.startswith(flat):
            assert int(re.match(r"tensor<(\d+)x", result).group(1)) in probes
    # the buffer (24 tiles of 16, a held expert's last each, the zero row)
    # is ``lax.empty``'s, which only the CPU lowers to a fill
    rows = (n * top_k // 16 + held) * 16 + 1
    made = {}
    for eqn in jax.make_jaxpr(lambda p, x: topk_moe_layer(
            p, x, top_k, first_expert=4, tile=16))(
            _layer(16, held), jnp.zeros((n, DIM))).eqns:
        if any(v.aval.shape == (rows, DIM) for v in eqn.outvars):
            made[eqn.primitive.name] = made.get(eqn.primitive.name, 0) + 1
    assert made == {"empty": 1, "dynamic_update_slice": 1, "while": 1}
    # and the pattern does find the old form's: the two of the dispatch and
    # the chosen scores'
    local = jnp.zeros((n * top_k,), jnp.int32)
    old = jax.jit(lambda w, l: (
        w[jnp.argsort(l)], jnp.zeros((held + 1,), jnp.int32).at[l].add(1))
    ).lower(jnp.zeros((n * top_k,)), local).as_text()
    assert "stablehlo.scatter" in old
    assert any(re.search(r"-> %s" % flat, line) for line in old.splitlines()
               if "stablehlo.gather" in line)
    old = jax.jit(lambda s, e: jnp.take_along_axis(s, e, axis=-1)).lower(
        jnp.zeros((n, 16)), jnp.zeros((n, top_k), jnp.int32)).as_text()
    assert any(re.search(r"-> %s" % picks, line) for line in old.splitlines()
               if "stablehlo.gather" in line)
