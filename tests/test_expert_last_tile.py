"""A run's last tile at a smaller size (``parallel/moe.py tile_sizes``): the
sizes against the rule's values at the cells' shapes, and the tile itself from
the expected run (``run_tile``); the layer with two sizes
a loop against the same layer with one, to the bit, for runs that end anywhere
in a tile; the order of the tiles and every assignment's row; the counter of
rows computed against a hand count."""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from storm_tpu.ops.platform import dispatch_notes
from storm_tpu.parallel import moe
from storm_tpu.parallel.moe import (observe_expert_counts, rows_computed,
                                    run_tile, tile_sizes, topk_moe_init,
                                    topk_moe_layer)
from storm_tpu.runtime.metrics import MetricsRegistry

DIM, N, WIDTH, HELD, FIRST, TOP_K = 32, 2100, 8, 2, 2, 2


@pytest.mark.parametrize("tile,expected_run,tight,want", [
    # the experts' loop: tokens a step x top_k / the router's width
    (1024, 32768 * 8 / 256, False, (1024, 512)),  # kimi_linear_48b
    (512, 32768 * 6 / 128, False, (512, 256)),  # nemotron_3_nano_30b
    (512, 16384 * 8 / 384, False, (512, 384)),  # kimi_k2_6: 341 a run
    (512, 32768 * 8 / 320, False, (512, 256)),  # solar_open2_250b
    # the combine's: a block's tokens x top_k x the share held
    (512, 256 * 8 * 32 / 256, True, (512, 384)),  # 256 +- 16
    (512, 256 * 6 * 32 / 128, True, (512,)),  # 384 + 78: no size under 512
    (512, 256 * 8 * 12 / 384, True, (512, 128)),  # 64 +- 8
    (512, 256 * 8 * 40 / 320, True, (512, 384)),
    # the presets' tile, and tiles with no size under them
    (16, 44 * 8 * 2 / 8, False, (16,)), (16, 0.0, True, (16,)),
    (128, 500.0, False, (128,)), (128, 10.0, True, (128,)),
    (200, 150.0, True, (200,)),
    # the rule's edges
    (512, 0.0, False, (512, 128)), (512, 0.0, True, (512, 128)),
    (512, 128.0, False, (512, 128)), (512, 129.0, False, (512, 256)),
    (512, 100.0, True, (512, 256)),  # 100 + 40
    (512, 511.0, False, (512,)), (512, 512.0, False, (512, 256)),
    (384, 384.0, True, (384, 256)), (256, 1000.0, False, (256, 128)),
    (1024, 600.0, True, (1024, 768)),
])
def test_tile_sizes_at_the_cells_shapes_and_the_rules_edges(
        tile, expected_run, tight, want):
    """``tile`` alone or with one ``small``, a multiple of 128 under it."""
    got = tile_sizes(tile, expected_run, tight)
    assert got == want and all(isinstance(m, int) for m in got)
    assert got[0] == tile and len(got) <= 2
    assert all(m % 128 == 0 and 0 < m < tile for m in got[1:])


@pytest.mark.parametrize("n,top_k,width,tile,sizes", [
    (32768, 10, 72, 1024, (1024, 512)),  # granite_4_h_small: 4,551 a run
    (65536, 8, 128, 1024, (1024, 512)),  # trinity_mini, keye_vl2_30b: 4,096
    (32768, 8, 256, 1024, (1024, 512)),  # kimi_linear_48b: 1,024, one tile
    (32768, 6, 128, 1024, (1024, 512)),  # nemotron_3_nano_30b: 1,536
    (32768, 8, 320, 512, (512, 256)),  # solar_open2_250b: 819
    (16384, 8, 384, 512, (512, 384)),  # kimi_k2_6: 341
    (4096, 10, 72, 512, (512, 256)),  # Granite's step of one row: 569
    (4096, 8, 128, 512, (512, 256)),  # Trinity's of a quarter row: 256
    (16384, 8, 128, 1024, (1024, 512)),  # and of one row: 1,024
    (16376, 8, 128, 512, (512, 256)),  # a token fewer: 1,023.5 fill none
    (8, 2, 8, 512, (512, 128)),  # (the layer cuts a tile to its tokens)
])
def test_the_experts_tile_is_the_expected_runs(n, top_k, width, tile, sizes):
    """One rule for every caller, from the shapes of the call: 1,024 rows
    where a held expert's expected run ``n * top_k / width`` fills a tile of
    1,024, 512 below; then ``tile_sizes`` of that tile and run, as before."""
    run = n * top_k / width
    assert run_tile(run) == tile and isinstance(run_tile(run), int)
    assert tile_sizes(run_tile(run), run, tight=False) == sizes


@pytest.mark.parametrize("count,sizes,want", [
    (0, (512, 256), 0), (1, (512, 256), 256), (256, (512, 256), 256),
    (257, (512, 256), 512), (512, (512, 256), 512), (513, (512, 256), 768),
    (1029, (512, 256), 1280), (897, (1024, 512), 1024),
    (1025, (1024, 512), 1536), (385, (512, 384), 512),
    (900, (512, 384), 1024), (896, (512, 384), 896),
    (0, (16,), 0), (5, (16,), 16), (16, (16,), 16), (17, (16,), 32),
    (37, (16,), 48), (129, (200,), 200), (201, (200,), 400)])
def test_rows_computed_against_a_hand_count(count, sizes, want):
    """Whole tiles, and the last at the small size where it fits; of an
    integer, of an array, of a traced scalar."""
    assert rows_computed(count, sizes) == want
    many = rows_computed(np.array([[count, 0], [count, count]]), sizes)
    assert many.tolist() == [[want, 0], [want, want]]
    assert int(jax.jit(lambda c: rows_computed(c, sizes))(count)) == want


def _layer_and_tokens(ffn, run):
    """A layer that holds experts 2 and 3 of 8, and 2,100 tokens of which
    the first ``run`` score near 1 on expert 2 and the others near 0 there
    (every other expert scores a half): expert 2's run is ``run`` rows."""
    p = topk_moe_init(jax.random.PRNGKey(7), DIM, 48, WIDTH, HELD, form=ffn,
                      shared_hidden=40)
    p["router"] = p["router"].at[:, FIRST].set(0.0).at[0, FIRST].set(4.0)
    x = jax.random.normal(jax.random.PRNGKey(8), (N, DIM))
    x = x.at[:, 0].set(-3.0).at[:run, 0].set(3.0)
    return p, x


@contextlib.contextmanager
def _one_size():
    """The loops as they were: no tile is a multiple of this step, so every
    loop has one size, its tile."""
    step = moe._TILE_STEP
    moe._TILE_STEP = 1 << 30
    try:
        yield
    finally:
        moe._TILE_STEP = step


@functools.lru_cache(maxsize=None)
def _compiled(ffn, tile, whole):
    """The layer compiled once a form: a run's length is data."""
    p, x = _layer_and_tokens(ffn, 0)
    with (_one_size() if whole else contextlib.nullcontext()):
        with dispatch_notes() as seen:
            fn = jax.jit(lambda p, x: topk_moe_layer(
                p, x, TOP_K, first_expert=FIRST, scale=2.5,
                tile=tile)).lower(p, x).compile()
    return fn, seen


# a run's length by name, of the experts' loop's sizes
RUNS = {
    "0": lambda tile, small: 0, "1": lambda tile, small: 1,
    "127": lambda tile, small: 127, "128": lambda tile, small: 128,
    "129": lambda tile, small: 129,
    "small-1": lambda tile, small: small - 1,
    "small": lambda tile, small: small,
    "small+1": lambda tile, small: small + 1,
    "tile-1": lambda tile, small: tile - 1, "tile": lambda tile, small: tile,
    "tile+1": lambda tile, small: tile + 1,
    "2tile+5": lambda tile, small: 2 * tile + 5,
}
# tile -> (the experts' loop's small size, the combine's; None: one size):
# 2,100 tokens' 4,200 assignments over a router of 8 are 525 a run, a block's
# 512 held a quarter are 128 + 45 a tile of 512
SMALL = {16: (None, 256), 512: (256, 256), 1024: (640, 256)}


def _note(loop, small):
    return f"{loop}_tiles=" + ("whole" if small is None else f"last-{small}")


@pytest.mark.parametrize("run", list(RUNS), ids=list(RUNS))
@pytest.mark.parametrize("tile", sorted(SMALL))
@pytest.mark.parametrize("ffn", ["relu2", "swiglu"])
def test_the_layer_is_the_one_size_layer_to_the_bit(ffn, tile, run):
    """``y``, the held experts' counts and ``absent``, every bit: a row that
    a small last tile leaves out no one reads, and a 0/1 product over fewer
    places adds fewer zeros. The combine's tiles are 512 places here, so at
    a tile of 16 (one size in the experts' loop) its loop still has two."""
    new, seen = _compiled(ffn, tile, False)
    old, seen_whole = _compiled(ffn, tile, True)
    small, combine = SMALL[tile]
    assert {_note("expert", small), _note("combine", combine)} <= set(seen)
    assert {_note("expert", None), _note("combine", None)} <= set(seen_whole)
    rows = RUNS[run](tile, small or tile // 2)
    p, x = _layer_and_tokens(ffn, rows)
    got, want = new(p, x), old(p, x)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    y, tokens, absent = got
    assert int(tokens[0]) == rows
    assert int(tokens.sum()) + int(absent) == N * TOP_K
    assert np.isfinite(np.asarray(y)).all()


@pytest.mark.parametrize("run", ["1", "small", "small+1", "tile+1",
                                 "2tile+5"])
def test_rows_left_out_of_a_last_tile_are_never_read(run, monkeypatch):
    """The buffer full of NaN and full of zeros give the same bytes at tiles
    of 512 rows: what a last tile of 256 rows does not write stays whatever
    it was, and neither the combine nor anything else reads it."""
    p, x = _layer_and_tokens("swiglu", RUNS[run](512, 256))
    got = {}
    for fill in (jnp.nan, 0.0):
        monkeypatch.setattr(jax.lax, "empty", lambda shape, dtype: jnp.full(
            shape, fill, dtype))
        got[fill] = jax.jit(lambda p, x: topk_moe_layer(
            p, x, TOP_K, first_expert=FIRST, scale=2.5, tile=512))(p, x)
    for a, b in zip(got[jnp.nan], got[0.0]):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert np.isfinite(np.asarray(got[jnp.nan][0])).all()


@pytest.mark.parametrize("tile,width,want", [
    # 2,189 and 2,191 assignments a layer: over 4 columns 547 a run (256
    # beside 512, 640 beside 1,024), over 8 columns 273 (384 beside 512)
    (512, 4, 256 + 256 + 512 + 768 + 1280), (512, 8, 2 * 384 + 512 + 896 + 1408),
    (1024, 4, 4 * 640 + 1664), (16, 4, 16 + 144 + 512 + 528 + 1040),
    # no tile given: the run's own, 512 under a run of 1,024 (547, 273) and
    # 1,024 from there (1,094 over 2 columns: 512 beside 1,024)
    (None, 4, 256 + 256 + 512 + 768 + 1280),
    (None, 8, 2 * 384 + 512 + 896 + 1408), (None, 2, 4 * 512 + 512 + 1536)])
def test_the_counter_of_rows_computed_is_the_hand_count(tile, width, want):
    """Two layers of three held experts with 0, 1 and 129, then 512, 513 and
    1,029 tokens, at the sizes the layer chooses for that tile (None: for
    the run it counted) and width."""
    tokens = np.array([[0, 1, 129], [512, 513, 1029]])
    absent = np.array([2184 + 5 - 130, 2184 + 7 - 2054])
    registry = MetricsRegistry()
    for _ in range(2):  # two steps: a counter adds
        observe_expert_counts(registry, "bolt", tokens, absent,
                              np.array([[9, 0], [9, 2]]), tile=tile,
                              width=width)
    got = registry.snapshot()["bolt"]
    assert (got["combine_tiles_written"], got["combine_tiles_added"]) == (
        36, 4)
    assert got["expert_rows_computed"] == 2 * want
    assert got["expert_assignments_held"] == 2 * tokens.sum()
    assert got["expert_assignments_absent"] == 2 * absent.sum()
    assert got["expert_tokens_max_over_mean"]["count"] == 4


def _long_run(run, n=5000):
    """:func:`_layer_and_tokens`' layer over ``n`` tokens, whose 10,000
    assignments over a router of 8 are an expected run of 1,250: the rule's
    tile is 1,024. Expert 2's run is ``run`` rows."""
    p, _ = _layer_and_tokens("swiglu", 0)
    x = jax.random.normal(jax.random.PRNGKey(9), (n, DIM))
    return p, x.at[:, 0].set(-3.0).at[:run, 0].set(3.0)


@pytest.mark.parametrize("run", [4551, 4608, 4609, 4096 + 512, 1023])
def test_the_rules_tile_of_1024_is_the_layer_at_512_to_the_bit(run):
    """A row's feed-forward is the same products whatever tile it rides in:
    the layer that takes its tile from the run (1,024 rows, a last tile at
    512) against the same layer told 512 (the seven builders' old constant
    of four), at a run of four and a half tiles and around it: ``y``, the
    counts and ``absent``, every bit. (Two held assignments a token at most
    here, so the combine's sum has one order.)"""
    p, x = _long_run(run)
    got = {}
    for tile in (None, 512):
        with dispatch_notes() as seen:
            got[tile] = jax.jit(lambda p, x: topk_moe_layer(
                p, x, TOP_K, first_expert=FIRST, scale=2.5, tile=tile))(p, x)
        assert f"expert_tiles=last-{256 if tile else 512}" in seen
    for a, b in zip(got[None], got[512]):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert int(got[None][1][0]) == run


def test_the_counter_counts_the_rows_of_the_tile_the_layer_ran():
    """No tile given to either: the layer cuts its runs by the expected run
    of its call, the counts' reader by the run it counted, which is the same
    number, so ``expert_rows_computed`` is the rows of the loops that ran:
    the buffer the layer allocates is the worst case of that tile, and the
    count is :func:`rows_computed` of the step's counts at its sizes."""
    p, x = _long_run(4200)
    layer = jax.jit(lambda p, x: topk_moe_layer(
        p, x, TOP_K, first_expert=FIRST, scale=2.5))
    with dispatch_notes() as seen:
        text = layer.lower(p, x).as_text()
    assert "expert_tiles=last-512" in seen
    worst = (-(-5000 * TOP_K // 1024) + HELD) * 1024 + 1  # and the zero row
    assert f"tensor<{worst}x{DIM}xf32>" in text
    _, tokens, absent = layer(p, x)
    registry = MetricsRegistry()
    observe_expert_counts(registry, "bolt", np.asarray(tokens)[None],
                          np.asarray(absent)[None], np.zeros((1, 2), int),
                          width=WIDTH)
    counts = [int(c) for c in tokens]
    assert counts[0] == 4200  # 4,608 rows of (1,024, 512), 4,352 of (512, 256)
    assert registry.snapshot()["bolt"]["expert_rows_computed"] == sum(
        rows_computed(c, (1024, 512)) for c in counts) \
        != sum(rows_computed(c, (512, 256)) for c in counts)


PUBLISHED = ["kimi_linear_48b", "nemotron_3_nano_30b", "kimi_k2_6",
             "solar_open2_250b", "trinity_mini", "keye_vl2_30b",
             "granite_4_h_small"]
TOYS = ["kimi_linear_tiny", "nemotron_h_tiny", "kimi_k2_tiny",
        "solar_open2_tiny", "trinity_tiny", "keye_tiny", "granite_h_tiny"]


@pytest.mark.parametrize("name,want", [(name, None) for name in PUBLISHED] + [
    (name, 16) for name in TOYS])
def test_no_published_width_builder_names_a_tile(name, want, monkeypatch):
    """The seven builders hand the expert branch no tile at the published
    widths (the layer takes it from the shapes of its call) and their
    ``expert_tile`` defaults to none; the toy presets keep 16 rows, a tile
    that is no multiple of 128 on windows of 40 tokens."""
    import inspect
    import sys

    from storm_tpu.models import registry, scorer

    given, experts = [], scorer.experts

    def recorded(*args, **kwargs):
        given.append(kwargs.get("tile"))
        return experts(*args, **kwargs)

    monkeypatch.setattr(scorer, "experts", recorded)
    registry.build_model(name)
    assert given == [want]
    family = sys.modules[registry._BUILDERS[name].__module__]
    (build,) = [f for n, f in vars(family).items()
                if n.startswith("build_") and "expert_tile"
                in inspect.signature(f).parameters]
    assert inspect.signature(build).parameters["expert_tile"].default is None


def _routing(kind, held, top_k, n, width, seed):
    """Every assignment's held expert (``held``: another chip's), flat:
    ``top_k`` distinct picks a token; ``skewed``: expert 0 is every token's
    first; ``one-expert-empty``: no token picks expert 0."""
    rng = np.random.default_rng(seed)
    others = np.stack([rng.permutation(width - 1)[:top_k] + 1
                       for _ in range(n)])
    if kind == "even":
        others = (others + rng.integers(width, size=(n, 1))) % width
    elif kind == "skewed":
        others[:, 0] = 0
    local = others.reshape(-1)
    return np.where(local < held, local, held).astype(np.int32)


@pytest.mark.parametrize("kind", ["even", "skewed", "one-expert-empty"])
@pytest.mark.parametrize("held,top_k,width", [(1, 8, 32), (4, 8, 32),
                                              (4, 6, 16), (8, 2, 8)],
                         ids=["a-thirty-second", "an-eighth", "a-quarter",
                              "all"])
@pytest.mark.parametrize("small", [4, 8, 12])
def test_tiles_by_size_and_every_assignments_row(kind, held, top_k, width,
                                                 small):
    """Tiles of 16 rows beside one small size: the order holds every tile
    once, the whole ones first and the small ones after, each in ascending
    number, a tile small where its places fit; written at its place in that
    order, every held assignment's row is written by exactly one tile, from
    that assignment's place, no two share a row, and an absent one points
    at the zero row behind them all."""
    tile, n, sizes = 16, 100, (16, small)
    local = _routing(kind, held, top_k, n, width, seed=held + top_k)
    weight = np.linspace(0.1, 1.0, local.size).astype(np.float32)
    counts, number_at, _, _, zero_row, n_tiles, tile_at = moe._dispatch(
        jnp.asarray(local), jnp.asarray(weight), held, tile)
    most = zero_row // tile
    tiles, ends = moe._tiles_by_size(n_tiles, most, tile_at, sizes)
    row_at = np.asarray(moe._rows_in_that_order(
        tiles, ends, counts, local.size, tile, zero_row))
    run, start, filled = (np.asarray(a) for a in tiles)
    ends = [int(end) for end in ends]
    assert len(ends) == 2 and ends[0] <= ends[1] == int(n_tiles)
    natural = [tuple(int(v) for v in tile_at(i)) for i in range(int(n_tiles))]
    met = list(zip(run.tolist(), start.tolist(), filled.tolist()))[:ends[1]]
    assert sorted(met) == sorted(natural)
    order = np.argsort(local, kind="stable")
    written, first = {}, 0  # a row of the buffer -> the place it is from
    for size, end in zip(sizes, ends):
        assert met[first:end] == sorted(
            met[first:end], key=natural.index)  # ascending number
        for j in range(first, end):
            e, at, left = met[j]
            assert (left <= small) == (size == small)
            for lane in range(min(left, size)):
                assert local[order[at + lane]] == e
                written[j * tile + lane] = at + lane
        first = end
    n_held = int(np.asarray(counts).sum())
    assert sum(rows_computed(int(c), sizes) for c in np.asarray(counts)) \
        == ends[0] * tile + (ends[1] - ends[0]) * small
    assert sorted(written.values()) == list(range(n_held))
    assert [written[r] for r in row_at[:n_held].tolist()] == list(
        range(n_held))
    assert len(set(row_at[:n_held].tolist())) == n_held
    assert (row_at[n_held:] == zero_row).all()
    assert zero_row > max(written, default=-1)


@pytest.mark.parametrize("name,experts,combine,write", [
    ("kimi_linear_48b", "last-512", "last-384", "first"),
    ("nemotron_3_nano_30b", "last-512", "whole", "first"),
    ("kimi_k2_6", "last-384", "last-128", "first"),
    ("solar_open2_250b", "last-256", "last-384", "first"),
    ("trinity_mini", "last-512", "whole", "once"),
    ("keye_vl2_30b", "last-512", "whole", "once"),
    ("granite_4_h_small", "last-512", "whole", "first"),
    ("kimi_linear_tiny", "whole", None, "once"),
    ("nemotron_h_tiny", "whole", None, "once"),
    ("kimi_k2_tiny", "whole", None, "once"),
    ("solar_open2_tiny", "whole", None, "once"),
    ("trinity_tiny", "whole", "whole", "once"),
    ("keye_tiny", "whole", "whole", "once"),
    ("granite_h_tiny", "whole", "whole", "once")])
def test_a_models_step_names_its_loops_sizes(name, experts, combine, write):
    """What the engine's inventory says of a program: the seven expert models
    traced at their largest step, shapes only (the presets' tile of 16 has
    one size; their combine's follows their rows a step). A block of the
    combine is one tile, written once, where the whole router is held (64
    tokens of 8 assignments) and in the presets' top-2 layers (256 of 2);
    the five cells that hold a part of theirs write a block's first tile
    and add only a further one."""
    from storm_tpu.models.registry import build_model

    model = build_model(name)
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((model.max_rows or 8,) + tuple(
        model.input_shape), jnp.float32)
    with dispatch_notes() as seen:
        jax.eval_shape(model.apply, params, state, x)
    assert f"expert_tiles={experts}" in seen
    assert sum(note.startswith("expert_tiles=") for note in seen) == 1
    tiles = [note for note in seen if note.startswith("combine_tiles=")]
    assert len(tiles) == 1
    assert combine is None or tiles == [f"combine_tiles={combine}"]
    assert [note for note in seen if note.startswith("combine_write=")] == [
        f"combine_write={write}"]
