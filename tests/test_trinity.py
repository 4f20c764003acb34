"""Trinity at toy widths on the CPU (hidden 64; a dense block and a whole
period of three window layers with rotary and one full layer without, 8 query
heads on 2 key heads, a window of 12 keys over 40 tokens; 20 experts top-2,
all held): the window in the shared attention code, in the kernel under the
Pallas interpreter, in XLA's blocked form and as a naive mask, against each
other; the sandwich norm against its formula; the two half-shares of the
expert layer against the all-held layer; that what was there lowers to the
parent's text; and the model through ``InferenceEngine`` against the
benchmark's reference (``benchmarks/references/trinity.py``, float32 at
``highest``) on seeded weights. Probabilities over the whole vocabulary are
compared, never an argmax."""

import functools
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.core import spec  # noqa: E402
from storm_tpu.config import BatchConfig, ModelConfig  # noqa: E402
from storm_tpu.infer.engine import InferenceEngine  # noqa: E402
from storm_tpu.models import scorer as S  # noqa: E402
from storm_tpu.models.registry import build_model, load_or_init  # noqa: E402
from storm_tpu.models.trinity import trinity_mixer  # noqa: E402
from storm_tpu.ops import layers as L  # noqa: E402
from storm_tpu.ops import parts as P  # noqa: E402
from storm_tpu.ops import rope as R  # noqa: E402
from storm_tpu.ops import attention as A  # noqa: E402
from storm_tpu.ops.attention import (causal_attention,  # noqa: E402
                                     causal_attention_merged, causal_blocked,
                                     merge_heads)
from storm_tpu.ops.flash_attention import (flash_attention,  # noqa: E402
                                           flash_attention_merged,
                                           window_walk)
from storm_tpu.ops.kda import rmsnorm_heads  # noqa: E402
from storm_tpu.ops.platform import dispatch_notes  # noqa: E402
from storm_tpu.parallel.moe import topk_moe_layer  # noqa: E402

REFERENCE = spec.plugin("references", "trinity")
TINY = spec.config("trinity_tiny")
SIZES = TINY["published"]
MINI = spec.config("trinity_mini")["published"]


def _distance(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))


# ---- the window: three forms against each other --------------------------------

def _naive(q, k, v, scale, window):
    """Every score formed, the window a mask."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    at = jnp.arange(q.shape[2])
    unseen = (at[None, :] > at[:, None]) | (at[None, :] <= at[:, None]
                                            - window)
    scores = jnp.where(unseen, -jnp.inf,
                       jnp.einsum("bhsd,bhtd->bhst", q, k) * scale)
    return jnp.einsum("bhst,bhtd->bhsd", jax.nn.softmax(scores, -1), v)


def _qkv(hq, hkv, s, d, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(key, (2, n, s, d), jnp.float32)
                 for key, n in zip(ks, (hq, hkv, hkv)))


SEQ, BLOCK = 320, 64  # five key blocks
# a key, a block, no multiple of a block, the sequence, twice the sequence
WINDOWS = (1, BLOCK, 100, SEQ, 2 * SEQ)
# (query heads, key heads, positions a query tile): a tile that divides the
# key block (its diagonal is one step), one as wide, one that divides nothing
TILES = ((8, 2, 16), (4, 4, 64), (6, 2, 21))


@pytest.mark.parametrize("hq,hkv,block_q", TILES)
@pytest.mark.parametrize("window", WINDOWS)
def test_the_three_forms_of_the_window_agree(window, hq, hkv, block_q):
    q, k, v = _qkv(hq, hkv, SEQ, 32)
    scale = 32 ** -0.5
    want = _naive(q, k, v, scale, window)
    kernel = flash_attention(q, k, v, scale=scale, block_q=block_q,
                             block_k=BLOCK, causal=True, window=window,
                             interpret=True)
    one_row = flash_attention(q, k, v, scale=scale, block_q=block_q,
                              block_k=BLOCK, causal=True, window=window,
                              interpret=True, row=1)
    blocked = causal_blocked(q, k, v, scale, BLOCK, window)
    np.testing.assert_allclose(kernel, want, atol=2e-6)
    np.testing.assert_allclose(one_row[0], want[1], atol=2e-6)
    np.testing.assert_allclose(blocked, want, atol=2e-6)
    if window >= SEQ:  # bounds nothing: plain causal attention
        np.testing.assert_allclose(
            blocked, causal_blocked(q, k, v, scale, BLOCK), atol=2e-6)


@pytest.mark.parametrize("s", [300, 40])
def test_a_sequence_of_no_whole_blocks_is_padded_behind_the_window(s):
    q, k, v = _qkv(4, 2, s, 16, seed=2)
    want = _naive(q, k, v, 0.25, 37)
    got = flash_attention(q, k, v, scale=0.25, block_q=32, block_k=64,
                          causal=True, window=37, interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_allclose(causal_blocked(q, k, v, 0.25, 64, 37), want,
                               atol=2e-6)


@pytest.mark.parametrize("window,walk,spoiled_to", [
    (100, "aligned", 192), (128, "tile-end", 176), (96, "tile-end", 208)])
def test_the_kernel_reads_no_key_block_before_a_tiles_windows(
        window, walk, spoiled_to):
    """Keys before every window of a tile may hold anything, NaN too: a
    block that was loaded and multiplied, then masked, would carry it into
    the sums (0 x NaN). The last tile of 16 queries (positions 304-319)
    with a window of 100 reaches back to key 205, and the walk over blocks
    aligned to 64 never reads blocks 0-2 (keys 0-191). The walk counted
    back from the tile's end promises more: nothing before ``first -
    window`` is read (keys 0-175 at a window of 128, where the aligned walk
    loaded the block of keys 128-191; keys 0-207 at 96, whose chunk is 48
    keys wide)."""
    assert window_walk(window, 16, BLOCK) == walk
    q, k, v = _qkv(8, 2, SEQ, 32)
    want = _naive(q, k, v, 0.2, window)[:, :, 304:]
    spoiled = [y.at[:, :, :spoiled_to].set(jnp.nan) for y in (k, v)]
    got = flash_attention(q, *spoiled, scale=0.2, block_q=16, block_k=BLOCK,
                          causal=True, window=window, interpret=True)
    np.testing.assert_allclose(got[:, :, 304:], want, atol=2e-6)
    # the blocked form's last block of 64 queries (256-319) reaches back to
    # key 256 - window + 1: it slices its keys from that key's block
    first = (256 - window + 1) // BLOCK * BLOCK
    spoiled = [y.at[:, :, :first].set(jnp.nan) for y in (k, v)]
    got = causal_blocked(q, *spoiled, 0.2, BLOCK, window)
    np.testing.assert_allclose(got[:, :, 304:], want, atol=2e-6)


# ---- the walk counted back from a tile's own last query ------------------------

@pytest.mark.parametrize("window,block_q,block_k,walk", [
    (2048, 64, 512, "tile-end"),    # Trinity's sliding layers
    (2048, 512, 512, "tile-end"),   # a head on its own keys
    (512, 64, 512, "tile-end"),     # one block: the diagonal's and a chunk
    (4096, 64, 512, "tile-end"),    # eight blocks, the most written out
    (2112, 64, 512, "tile-end"),    # no multiple of a block: a wider chunk
    (4608, 64, 512, "aligned"),     # nine blocks
    (448, 64, 512, "aligned"),      # shorter than a key block
    (2080, 64, 512, "aligned"),     # the tile does not divide it
    (100, 16, 64, "aligned"), (1, 16, 64, "aligned"),
    (64, 21, 64, "aligned"),        # the tile divides no key block
    (None, 64, 512, "aligned"),     # no window: the causal form
])
def test_the_walk_is_a_rule_on_the_window_and_the_tiles(window, block_q,
                                                        block_k, walk):
    assert window_walk(window, block_q, block_k) == walk


# the production ratios an eighth of the size: a tile an eighth of a key block
# (Trinity's 64 of 512), a window of four blocks, sixteen blocks and more
EIGHTH = dict(block_q=8, block_k=64, window=256)


@pytest.mark.parametrize("entry", ["split", "merged"])
@pytest.mark.parametrize("hq,hkv", [(8, 2), (2, 2)])
@pytest.mark.parametrize("s", [1024, 1100])  # sixteen whole blocks; not whole
def test_the_walk_from_the_tiles_end_at_the_production_ratios(s, hq, hkv,
                                                              entry):
    """Both entries of the kernel under the interpreter against every score
    formed and masked: 32 early tiles (plain causal), the tile at which the
    window starts to bind, and 96 and more that walk the diagonal's block,
    three clear blocks and a chunk of 8 keys."""
    assert window_walk(EIGHTH["window"], 8, 64) == "tile-end"
    (q, k, v), merged = _merged(hq, hkv, s, 32, seed=5)
    want = _naive(q, k, v, 0.2, EIGHTH["window"])
    if entry == "split":
        got = flash_attention(q, k, v, scale=0.2, causal=True, interpret=True,
                              **EIGHTH)
    else:
        want, got = merge_heads(want), jnp.zeros((2, s, hq * 32))
        for row in (0, 1):
            got = flash_attention_merged(
                got, *merged, row, heads=hq, kv_heads=hkv, scale=0.2,
                interpret=True, **EIGHTH)
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("s", [256, 264, 272, 384])
@pytest.mark.parametrize("window,block_q", [(256, 8), (128, 64), (192, 32),
                                            (96, 16), (100, 16), (128, 21)])
def test_tiles_before_at_and_after_the_position_where_the_window_binds(
        window, block_q, s):
    """Sequences that end before the window binds a tile (every tile plain
    causal), with the first tile it binds, one past it and many; a window
    that is no multiple of a key block (192 and 96: the chunk is wider than
    a tile), one the tile does not divide and a tile that divides no block
    (100 on 16, 128 on 21: the aligned walk, by the rule)."""
    q, k, v = _qkv(4, 2, s, 16, seed=window + s)
    want = _naive(q, k, v, 0.25, window)
    got = flash_attention(q, k, v, scale=0.25, block_q=block_q, block_k=64,
                          causal=True, window=window, interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-6)
    one_row = flash_attention(q, k, v, scale=0.25, block_q=block_q,
                              block_k=64, causal=True, window=window,
                              interpret=True, row=1)
    np.testing.assert_allclose(one_row[0], want[1], atol=2e-6)


def test_a_window_names_its_own_part_and_note_and_a_wide_one_does_not():
    q, k, v = _qkv(8, 2, 40, 16)

    def parts_of(window):
        with dispatch_notes() as notes:
            text = jax.jit(lambda q, k, v: causal_attention(
                q, k, v, block=16, window=window)).lower(q, k, v).as_text(
                debug_info=True)
        return notes, {part for part in (P.MIX_ATTENTION,
                                         P.MIX_WINDOW_ATTENTION)
                       if part + "/" in text}

    assert parts_of(12) == (["window_attention=blocked-grouped"],
                            {P.MIX_WINDOW_ATTENTION})
    for wide in (None, 40, 80):
        assert parts_of(wide) == (["causal_attention=blocked-grouped"],
                                  {P.MIX_ATTENTION})
    assert P.part_of("jit(fwd)/mix.elementwise/mix.window_attention/while") \
        == P.MIX_WINDOW_ATTENTION
    with pytest.raises(ValueError, match="window"):
        causal_attention(q, k, v, window=0)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, window=4, interpret=True)  # not causal


# ---- heads that stay merged: the norm and the kernel's second entry ------------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6),
                                       (jnp.bfloat16, 2 ** -7)])
@pytest.mark.parametrize("heads,d", [(32, 128), (4, 128), (8, 16)])
def test_the_head_norm_on_merged_heads_is_rmsnorm_on_the_view(heads, d, dtype,
                                                              tol):
    """Trinity-Mini's 32 query and 4 key heads of 128 and the tiny preset's
    8 of 16: ``rmsnorm_heads`` where the heads lie against ``rmsnorm`` on the
    view a head, one learned ``(d,)`` scale for every head; float32 to the
    order of a sum (1e-6 of a value), bfloat16 to one step of its rounding."""
    x = (3 * jax.random.normal(jax.random.PRNGKey(heads), (2, 24, heads * d))
         ).astype(dtype)
    p = {"scale": jnp.linspace(0.5, 1.5, d).astype(dtype)}
    want = L.rmsnorm(p, x.reshape(2, 24, heads, d), 1e-5)
    got = rmsnorm_heads(p, x, heads, 1e-5)
    assert got.dtype == dtype and got.shape == x.shape
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(want.reshape(x.shape), np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("turn", [True, False])
@pytest.mark.parametrize("heads,s", [(32, 256), (4, 384)])
def test_the_norm_in_the_turns_pass_is_the_norm_and_then_the_turn(
        monkeypatch, heads, s, turn):
    """One kernel's pass over q (32 heads; one step of 256 positions) or k (4;
    384 positions are three steps of 128) where they lie, under the
    interpreter, against ``rmsnorm_heads`` and then ``turn_merged``'s other
    form; without tables the norm alone; off the chip the rule sends both to
    those two and says so."""
    x = jax.random.normal(jax.random.PRNGKey(heads), (2, s, heads * 128))
    p = {"scale": jnp.linspace(0.5, 1.5, 128)}
    rotary = R.rotary_tables(s, 100.0 ** (-2.0 * np.arange(64) / 128)) \
        if turn else None
    with dispatch_notes() as notes:
        want = R.norm_turn_merged(p, x, heads, 1e-5, rotary)
    assert notes == ["rotary_turn=halves"] * turn
    np.testing.assert_allclose(
        want, rmsnorm_heads(p, x, heads, 1e-5) if not turn else
        R.turn_merged((rmsnorm_heads(p, x, heads, 1e-5),), *rotary, heads)[0],
        atol=1e-6)
    monkeypatch.setattr(R, "_use_pallas", lambda: True)
    monkeypatch.setattr(R, "_one_device", lambda: True)
    monkeypatch.setattr(
        R, "_norm_turn_lanes", functools.partial(R._norm_turn_lanes,
                                                 interpret=True))
    with dispatch_notes() as notes:
        got = R.norm_turn_merged(p, x, heads, 1e-5, rotary)
    assert notes == ["head_norm=kernel"] + ["rotary_turn=lanes"] * turn
    np.testing.assert_allclose(got, want, atol=5e-6)


def _merged(hq, hkv, s, d, seed=1):
    """q, k, v a head first, and the same lying merged ``(B, S, H * D)``."""
    split = _qkv(hq, hkv, s, d, seed)
    return split, tuple(merge_heads(y) for y in split)


# (query heads, key heads): one, four and eight query heads a key head
GROUPS = ((2, 2), (8, 2), (8, 1))


@pytest.mark.parametrize("form", ["kernel", "blocked"])
@pytest.mark.parametrize("s", [SEQ, 300])  # five whole key blocks; none whole
@pytest.mark.parametrize("window", [None, 100])
@pytest.mark.parametrize("hq,hkv", GROUPS)
def test_the_merged_entry_is_the_head_split_one_transposed(hq, hkv, window, s,
                                                           form):
    """``q, k, v`` as the projections leave them against ``causal_attention``
    on the operands a head first: the kernel under the interpreter, a row's
    call writing its row of the result's buffer and leaving the others as
    they were (a tile of 16 positions, a group's heads stacked; 300 positions
    are padded to whole blocks behind the last), and the blocked form."""
    (q, k, v), merged = _merged(hq, hkv, s, 32)
    want = merge_heads(causal_attention(q, k, v, scale=0.2, block=BLOCK,
                                        window=window))
    if form == "blocked":
        got = causal_attention_merged(*merged, hq, hkv, scale=0.2,
                                      block=BLOCK, window=window)
    else:
        got = jnp.full_like(want, 7.0)
        for row in (1, 0):
            before = got
            got = flash_attention_merged(
                got, *merged, row, heads=hq, kv_heads=hkv, scale=0.2,
                block_q=16, block_k=BLOCK, window=window, interpret=True)
            np.testing.assert_array_equal(got[1 - row], before[1 - row])
    assert got.shape == want.shape == (2, s, hq * 32)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_the_merged_entrys_loop_over_rows_fills_an_unwritten_buffer(
        monkeypatch):
    """What one chip builds, here under the interpreter: whole tiles of 128
    lanes, so ``merged_form`` picks the kernel; the loop over rows starts
    from ``lax.empty`` and every row's call writes its own; the note says
    which entry built the program, and a width that is no whole lane tile
    (Kimi's 192 too) goes to the blocked form on the view."""
    from storm_tpu.ops import flash_attention as F

    monkeypatch.setattr(A, "_use_pallas", lambda: True)
    monkeypatch.setattr(A, "_one_device", lambda: True)
    monkeypatch.setattr(
        F, "flash_attention_merged",
        lambda *a, **kw: flash_attention_merged(*a, interpret=True, **kw))
    assert A.merged_form(8, 2, 512, 128, 128) == "kernel"
    assert A.merged_form(8, 2, 512, 192, 128) == "blocked"
    assert A.merged_form(8, 2, 512 + 128, 128, 128) == "blocked"
    (q, k, v), merged = _merged(8, 2, 512, 128, seed=3)
    for window, name in ((200, "window_attention"), (None, "causal_attention")):
        with dispatch_notes() as notes:
            got = jax.jit(lambda q, k, v: causal_attention_merged(
                q, k, v, 8, 2, window=window))(*merged)
        assert notes == [f"{name}=kernel-grouped-merged"]
        want = merge_heads(causal_blocked(q, k, v, 128 ** -0.5, 128, window))
        np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("entry", ["split", "merged"])
@pytest.mark.parametrize("window,note", [
    (512, "kernel-grouped{}-tile-end"),  # a key block: tiles of 128 divide it
    (576, "kernel-grouped{}"),           # no whole tiles: the aligned walk
])
def test_the_note_says_which_walk_the_program_was_built_with(
        monkeypatch, window, note, entry):
    """What one chip builds, under the interpreter, at 8 query heads on 2 key
    heads of 128 over 1,024 positions (tiles of 128 x 512): both entries note
    the walk ``window_walk`` gives the call's shapes, the aligned one under
    the note it always had, and either agrees with the blocked form."""
    from storm_tpu.ops import flash_attention as F

    monkeypatch.setattr(A, "_use_pallas", lambda: True)
    monkeypatch.setattr(A, "_one_device", lambda: True)
    for name in ("flash_attention", "flash_attention_merged"):
        monkeypatch.setattr(F, name, functools.partial(
            getattr(F, name), interpret=True))
    (q, k, v), merged = _merged(8, 2, 1024, 128, seed=4)
    want = causal_blocked(q, k, v, 128 ** -0.5, 128, window)
    with dispatch_notes() as notes:
        if entry == "split":
            got = jax.jit(lambda q, k, v: causal_attention(
                q, k, v, window=window))(q, k, v)
        else:
            want = merge_heads(want)
            got = jax.jit(lambda q, k, v: causal_attention_merged(
                q, k, v, 8, 2, window=window))(*merged)
    merged_suffix = "-merged" if entry == "merged" else ""
    assert notes == ["window_attention=" + note.format(merged_suffix)]
    np.testing.assert_allclose(got, want, atol=2e-6)


# ---- what was there lowers to the parent's text --------------------------------

# The first 16 hex digits of the sha256 of the lowered text, as the parent of
# PR 57 lowered the same calls (``window`` not given): (query heads a key
# head, key width, value width, positions).
PARENT = {
    (1, 16, 16, 40): ("db72a19b9d65be84", "1b8b94d13e794b59",
                      "bcd1b6bd72a2f583", "98b0c9afaea1c3b4",
                      "98b0c9afaea1c3b4"),
    (4, 128, 128, 256): ("ff1dac5f937336d7", "42524014171a7b63",
                         "56c9aa65a07ba38d", "13a1097979b4b158",
                         "35c9c323039d57e5"),
    (1, 192, 128, 1024): ("6211a9bec4a178e4", "83b55b6a4379bf79",
                          "d02587bf940fc339", "de0de4fb468cf0df",
                          "7374178987454096"),
}


def _digest(*chunks):
    h = hashlib.sha256()
    for chunk in chunks:
        h.update(chunk)
    return h.hexdigest()[:16]


@pytest.mark.parametrize("shape", sorted(PARENT))
def test_without_a_window_attention_lowers_to_the_parents_text(shape):
    """The full kernel, the causal kernel, one row of it, ``causal_attention``
    and ``causal_blocked``. (The six plans' whole programs are held by
    tests/test_scorer.py's digests, which this PR leaves as they were.)"""
    g, dk, dv, s = shape
    spec_of = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.float32)  # noqa
    q, k, v = spec_of(2, 2 * g, s, dk), spec_of(2, 2, s, dk), \
        spec_of(2, 2, s, dv)

    def flash(**kw):
        return lambda q, k, v: flash_attention(
            q, k, v, block_q=64, block_k=128, interpret=True, **kw)

    got = tuple(
        _digest(jax.jit(f).lower(q, k, v).as_text().encode()) for f in (
            flash(causal=False), flash(causal=True),
            flash(causal=True, row=1),
            lambda q, k, v: causal_attention(q, k, v),
            lambda q, k, v: causal_blocked(q, k, v, 0.25, 128)))
    assert got == PARENT[shape]


# the sixth plan, which tests/test_scorer.py's one-head digests leave out
# (eight heads an answer): text, tree and, for the toy, leaves from key 7
EVABYTE = {"evabyte_tiny": ("225843d0e1a1bfff", "ce706d26179cd942",
                            "4bbb8eedebebb9d3"),
           "evabyte": ("ec97b6468a4678ed", "3088d31f4536309d")}


@pytest.mark.parametrize("name", sorted(EVABYTE))
def test_the_sixth_plan_lowers_to_the_parents_text_and_makes_its_trees(name):
    model = build_model(name)
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((2,) + tuple(model.input_shape), jnp.float32)
    text = jax.jit(model.apply).lower(params, state, x).as_text()
    tree = str(jax.tree.map(lambda a: (a.shape, str(a.dtype)),
                            (params, state)))
    got = (_digest(text.encode()), _digest(tree.encode()))
    if name.endswith("_tiny"):
        made = model.init(jax.random.PRNGKey(7))
        got += (_digest(*(np.asarray(leaf).tobytes()
                          for leaf in jax.tree.leaves(made))),)
    assert got == EVABYTE[name]


# ---- the sandwich --------------------------------------------------------------

def test_a_branch_with_a_post_norm_is_its_formula_and_one_without_is_not_touched():
    """``h += RMSNorm_post(branch(RMSNorm_pre(h)))`` with learned scales
    other than 1, against the formula written out."""
    dim, eps = 16, 1e-5

    def branch(post):
        return S.Branch("pre", "w", lambda key: {"w": jax.random.normal(
            key, (dim, dim)) / 4}, lambda p, y, _: jnp.tanh(y @ p["w"]),
            scope=P.PROJ, post=post)

    x = np.arange(12, dtype=np.float32).reshape(2, 6) % 24
    for post in ("after", None):
        model = S.token_scorer("sandwich", 24, (6,), ((branch(post),),),
                               dim=dim, eps=eps, hyper={}, max_rows=2)
        params, state = model.init(jax.random.PRNGKey(4))
        blk = params["layers"][0]
        assert set(blk) == {"pre", "w"} | ({post} if post else set())
        scales = jax.random.uniform(jax.random.PRNGKey(5), (2, dim),
                                    minval=0.5, maxval=2.0)
        blk["pre"] = {"scale": scales[0]}
        if post:
            blk[post] = {"scale": scales[1]}

        def rms(y, scale):
            return y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True) + eps) \
                * scale

        h = params["embed"][x.astype(np.int32)]
        y = jnp.tanh(rms(h, scales[0]) @ blk["w"]["w"])
        h = h + (rms(y, scales[1]) if post else y)
        want = rms(h[:, -1], params["norm"]["scale"]) @ params["head"]
        got, _ = model.apply(params, state, x)
        np.testing.assert_allclose(got, want, atol=1e-5)


def test_every_block_of_the_tiny_model_has_its_four_norms():
    model = build_model("trinity_tiny")
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert len(params["layers"]) == 5
    for i, blk in enumerate(params["layers"]):
        assert set(blk) == {"norm1", "mixer", "post1", "norm2", "ffn",
                            "post2"}
        assert ("experts" in blk["ffn"]) == (i >= 1)
        assert set(blk["mixer"]) == {"q", "k", "v", "gate", "o", "q_norm",
                                     "k_norm"}
    assert state["aux"]["expert_tokens"].shape == (4, 20)
    assert model.hyper["layer_types"] == ("sliding",) * 4 + ("full",)
    assert model.hyper["window"] == 12 and model.hyper["experts_held"] == 20


# ---- the mixer -----------------------------------------------------------------

@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_the_mixer_against_the_reference_row_by_row(kind):
    model = build_model("trinity_tiny")
    params, _ = load_or_init(model, None, 5)
    kinds = [SIZES["layer_types"][i] for i in SIZES["held"]["layers"]]
    p = params["layers"][kinds.index(kind)]["mixer"]
    # scales other than 1, so that the head norms' weights are read
    p = {**p, "q_norm": {"scale": jnp.linspace(0.5, 1.5, 16)},
         "k_norm": {"scale": jnp.linspace(1.5, 0.5, 16)}}
    x = jax.random.normal(jax.random.PRNGKey(9), (3, 40, 64))
    inv_freq = 100.0 ** (-2.0 * np.arange(8) / 16)
    reach = 12 if kind == "sliding_attention" else None
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, x: trinity_mixer(
            p, x, 8, 2, 16, 1e-5, R.rotary_tables(40, inv_freq), reach,
            16))(p, x)
        want = jnp.stack([REFERENCE._attention(p, row, SIZES, kind, 1e-5)
                          for row in x])
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_a_later_token_changes_no_earlier_output_and_a_far_one_none_in_a_window():
    model = build_model("trinity_tiny")
    params, state = load_or_init(model, None, 5)
    p = params["layers"][0]["mixer"]  # a sliding layer
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 40, 64))
    tables = R.rotary_tables(40, 100.0 ** (-2.0 * np.arange(8) / 16))
    f = jax.jit(lambda x: trinity_mixer(p, x, 8, 2, 16, 1e-5, tables, 12, 16))
    base = f(x)
    later = f(x.at[:, 30].add(1.0))
    np.testing.assert_array_equal(later[:, :30], base[:, :30])
    assert not np.allclose(later[:, 30], base[:, 30])
    # position 30 lies in the windows of 30..41 alone
    assert not np.allclose(later[:, 41 - 2], base[:, 41 - 2])
    np.testing.assert_array_equal(later[:, 42:], base[:, 42:])


# ---- the expert layer, every expert held ---------------------------------------

def test_two_half_shares_add_up_to_the_all_held_layer():
    """The guide's section 4, turned round: the cell's layer holds all 20
    experts of its router, the path no other cell runs; two chips that held
    ten each (``first_expert`` 0 and 10: the partial path every other cell
    runs) compute parts that, with the shared expert counted once, add up to
    it, and to what the reference gives."""
    model = build_model("trinity_tiny")
    whole = load_or_init(model, None, 5)[0]["layers"][1]["ffn"]
    assert whole["experts"]["gate"].shape[0] == whole["router"].shape[1] == 20
    x = jax.random.normal(jax.random.PRNGKey(13), (3, 37, 64))
    layer = jax.jit(lambda p, first: topk_moe_layer(
        p, x, 2, first_expert=first, scale=2.826, tile=16),
        static_argnums=1)
    with jax.default_matmul_precision("highest"):
        all_held, tokens, absent = layer(whole, 0)
        assert int(absent) == 0 and int(tokens.sum()) == 2 * 111
        total, seen = jnp.zeros_like(x), 0
        for first in (0, 10):
            share = {"router": whole["router"],
                     "router_bias": whole["router_bias"],
                     "experts": {n: w[first:first + 10]
                                 for n, w in whole["experts"].items()}}
            y, held, elsewhere = layer(share, first)
            assert held.shape == (10,)
            assert int(held.sum()) + int(elsewhere) == 2 * 111
            np.testing.assert_array_equal(held, tokens[first:first + 10])
            total, seen = total + y, seen + int(held.sum())
        total = total + L.swiglu(whole["shared"], x)
        want = jnp.stack([REFERENCE._experts(whole, row, SIZES) for row in x])
    assert seen == 2 * 111
    np.testing.assert_allclose(total, all_held, atol=1e-5)
    np.testing.assert_allclose(all_held, want, atol=1e-5)


def test_the_reference_gathers_every_row_of_an_expert_however_many(
        monkeypatch):
    """Its gathers hold 1,024 rows at a time; with room for 4 a pass (and a
    last pass that is not full) the layer is the same to rounding."""
    model = build_model("trinity_tiny")
    ffn = load_or_init(model, None, 5)[0]["layers"][1]["ffn"]
    x = jax.random.normal(jax.random.PRNGKey(13), (37, 64))
    with jax.default_matmul_precision("highest"):
        want = REFERENCE._experts(ffn, x, SIZES)
        monkeypatch.setattr(REFERENCE, "GATHER", 4)
        got = REFERENCE._experts(ffn, x, SIZES)
    np.testing.assert_allclose(got, want, atol=1e-6)


# ---- the registry and the load -------------------------------------------------

def test_registry_names_the_model_its_cut_and_its_type():
    model = build_model("trinity_mini")
    assert model.input_shape == (16384,) and model.num_classes == 200192
    assert model.max_rows == MINI["held"]["rows_per_step"] == 4
    assert BatchConfig().clipped(model.max_rows).buckets == (4,)
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert {a.dtype for a in jax.tree.leaves(params)} == {
        jnp.dtype(jnp.bfloat16)}
    assert sum(a.size for a in jax.tree.leaves(params)) == 4_241_534_720
    kinds = [MINI["layer_types"][i] for i in MINI["held"]["layers"]]
    assert model.hyper["layer_types"] == tuple(
        k.split("_")[0] for k in kinds)
    assert model.hyper["dense"] == sum(
        i < MINI["num_dense_layers"] for i in MINI["held"]["layers"])
    ffn = params["layers"][1]["ffn"]
    assert ffn["experts"]["gate"].shape == (128, 2048, 1024)
    assert ffn["router"].shape == (2048, 128)
    assert state["aux"]["expert_tokens"].shape == (4, 128)
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "sliding_window", "num_experts",
                "num_experts_per_tok", "rope_theta"):
        assert MINI[key] == model.hyper[{
            "hidden_size": "dim", "num_attention_heads": "heads",
            "num_key_value_heads": "kv_heads", "sliding_window": "window",
            "num_experts": "n_experts", "num_experts_per_tok": "top_k"}.get(
                key, key)], key


def test_the_initialiser_hands_over_the_served_type_and_astypes_values():
    """The bfloat16 load is the float32 draw cast, leaf by leaf; the
    embedding is N(0, 1) over ``sqrt(dim)``, so that the stream starts at
    N(0, 1) a channel."""
    f32 = build_model("trinity_tiny").init(jax.random.PRNGKey(7))[0]
    b16 = build_model("trinity_tiny", param_dtype=jnp.bfloat16).init(
        jax.random.PRNGKey(7))[0]
    for a, b in zip(jax.tree.leaves(f32), jax.tree.leaves(b16)):
        assert b.dtype == jnp.bfloat16
        np.testing.assert_array_equal(a.astype(jnp.bfloat16), b)
    assert float(jnp.std(f32["embed"]) * 8.0) == pytest.approx(1.0, abs=0.05)


# ---- the model through the engine ----------------------------------------------

def _windows(n, seed=3):
    return spec.plugin("inputs", "trinity_tokens").make(
        n, (40,), seed).astype(np.float32)


@pytest.fixture(scope="module")
def reference_rows():
    model = build_model("trinity_tiny")
    params, state = load_or_init(model, None, 5)
    x = _windows(16)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, s, xx: REFERENCE.forward(SIZES, p, s, xx))(
            params, state, x)
    return x, np.asarray(want)


@pytest.fixture(scope="module")
def float32_engine():
    from storm_tpu.config import ShardingConfig
    from storm_tpu.infer.engine import shared_engine

    eng = shared_engine(ModelConfig(
        name="trinity_tiny", dtype="float32", num_classes=96,
        input_shape=(40,), seed=5), ShardingConfig(data_parallel=0),
        BatchConfig())
    eng.warmup()
    return eng


FLOAT32_TOLERANCE = 1e-5  # summation order alone: reads about 1e-6


def test_model_through_the_engine_in_float32(reference_rows, float32_engine):
    x, want = reference_rows
    eng = float32_engine
    got = np.concatenate([eng.predict(x[a:a + 4]) for a in range(0, 16, 4)])
    assert got.shape == (16, 96)
    assert _distance(got, want).max() < FLOAT32_TOLERANCE


def test_bfloat16_is_held_to_its_own_tolerance_and_fails_float32s(
        reference_rows):
    x, want = reference_rows
    eng = InferenceEngine(ModelConfig(
        name="trinity_tiny", dtype="bfloat16", num_classes=96,
        input_shape=(40,), seed=5), batch_cfg=BatchConfig())
    got = np.concatenate([eng.predict(x[a:a + 4]) for a in range(0, 16, 4)])
    err = _distance(got, want)
    assert np.median(err) < 0.05 and err.max() < 0.5
    assert err.min() > FLOAT32_TOLERANCE


def test_the_reference_refuses_another_depth():
    model = build_model("trinity_tiny")
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1, 40), jnp.float32)
    short = {**params, "layers": params["layers"][:3]}
    with pytest.raises(ValueError, match="another depth"):
        jax.eval_shape(lambda p: REFERENCE.forward(SIZES, p, state, x), short)


def test_the_inventory_names_both_loops(float32_engine):
    from storm_tpu.infer.engine import engine_inventory

    eng = float32_engine
    row = next(r for r in engine_inventory()["engines"]
               if r["model"] == "trinity_tiny")
    forms = row["programs"][str(eng.pad_batch(4))].split(", ")
    assert {"window_attention=blocked-grouped",
            "causal_attention=blocked-grouped", "rotary_turn=halves",
            "expert_ffn=swiglu", "expert_dispatch=sorted",
            "expert_combine=held-rows"} <= set(forms)


def test_device_counters_ride_the_result_and_no_assignment_is_absent():
    """Four expert layers, all twenty experts held, top-2: a step of 4
    windows of 40 tokens makes 320 assignments an expert layer, all held."""
    from storm_tpu.infer.continuous import ContinuousBatcher
    from storm_tpu.runtime.metrics import MetricsRegistry

    eng = InferenceEngine(ModelConfig(
        name="trinity_tiny", dtype="float32", num_classes=96,
        input_shape=(40,), seed=5), batch_cfg=BatchConfig())
    handle = eng.dispatch((_windows(4),))
    handle.future.result(60)
    aux = handle.aux
    assert aux["expert_tokens"].shape == (4, 20)
    assert aux["expert_tokens"].sum(1).tolist() == [320] * 4
    assert aux["expert_absent"].tolist() == [0] * 4
    registry_ = MetricsRegistry()
    queue = ContinuousBatcher(eng, eng.batch_cfg)
    queue.bind(registry_, "inference-bolt")
    queue._observe_aux(aux)
    got = registry_.snapshot()["inference-bolt"]
    assert got["expert_assignments_held"] == 4 * 320
    assert not got.get("expert_assignments_absent")
    assert got["expert_tokens_max_over_mean"]["count"] == 4
