"""EvaByte through the program's model code (PR 49): the summaries against a
loop over chunks, EVA attention in both of its forms against the benchmark's
plain reference, what a query reads, the program against the reference in
float32, an answer's eight distributions, the device counters and the load."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.core import spec  # noqa: E402
from storm_tpu.models import evabyte as program  # noqa: E402
from storm_tpu.models.registry import build_model  # noqa: E402
from storm_tpu.ops import eva_attention as ea  # noqa: E402
from storm_tpu.ops.attention import causal_attention  # noqa: E402
from storm_tpu.ops.platform import dispatch_notes  # noqa: E402

REFERENCE = spec.plugin("references", "evabyte")
TINY = spec.config("evabyte_tiny")
SIZES = TINY["published"]
WINDOW, CHUNK = SIZES["window_size"], SIZES["chunk_size"]


def _distance(got, want):
    """Euclidean distance of each row from its reference row over that row's
    length: the benchmark's measure (``core/pairing.py``)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))


def _qkv(n, heads=4, d=16, rows=2, seed=0, dtype=jnp.float32):
    """``q, k, v: (rows, n, heads * d)`` (the heads merged, as the ops take
    them) and ``mu, phi: (heads, d)``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(key, (rows, n, heads * d)).astype(dtype)
               for key in ks[:3])
    mu, phi = (jax.random.normal(key, (heads, d)) for key in ks[3:])
    return q, k, v, mu, phi


def _heads(a, heads=4):
    """The view a head: ``(..., n, heads * d)`` -> ``(..., n, heads, d)``."""
    return a.reshape(*a.shape[:-1], heads, a.shape[-1] // heads)


def _windows(n, length, seed=0):
    return np.random.RandomState(seed).randint(
        8, 40, (n, length)).astype(np.float32)


# ---- the summaries -----------------------------------------------------------------

def test_summaries_are_the_two_poolings_read_a_chunk_at_a_time():
    _, k, v, mu, phi = _qkv(24, rows=1)
    with dispatch_notes() as forms:
        kbar, vbar = ea.chunk_summaries(k, v, mu, phi, 4)
    assert forms == ["eva_chunks=xla"]
    assert kbar.shape == vbar.shape == (1, 6, 64)
    kn, vn, mun, phin = (np.asarray(a, np.float64) for a in (
        _heads(k[0]), _heads(v[0]), mu, phi))
    for c in range(6):
        for h in range(4):
            keys, values = kn[4 * c:4 * c + 4, h], vn[4 * c:4 * c + 4, h]
            for vec, pooled, got in ((mun[h], keys, kbar), (phin[h], values,
                                                            vbar)):
                logit = keys @ vec / 4.0  # sqrt(16)
                w = np.exp(logit - logit.max())
                np.testing.assert_allclose(
                    np.asarray(_heads(got)[0, c, h]), (w / w.sum()) @ pooled,
                    rtol=2e-5, atol=2e-6)
    want = REFERENCE.summaries(_heads(k[0]), _heads(v[0]), mu, phi, 4)
    np.testing.assert_allclose(np.asarray(_heads(kbar[0])),
                               np.asarray(want[0]), rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(_heads(vbar[0])),
                               np.asarray(want[1]), rtol=2e-5, atol=2e-6)
    with pytest.raises(ValueError):
        ea.chunk_summaries(k[:, :22], v[:, :22], mu, phi, 4)


def test_summaries_kernel_under_the_interpreter_is_xlas_form():
    """A tile of 256 positions of two heads of 128 lanes, chunks of 16, in
    bfloat16, row 1 of a batch of two read where it lies."""
    _, k, v, mu, phi = _qkv(512, heads=2, d=128, seed=4, dtype=jnp.bfloat16)
    want = ea.chunk_summaries(k, v, mu, phi, 16)
    weights = (jnp.stack([mu, phi]) * 128 ** -0.5).reshape(2, 256)
    got = ea._chunks_row(k, v, weights, 1, heads=2, chunk=16, interpret=True)
    for a, b in zip(got, want):
        assert a.shape == (32, 256) and a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b[1], np.float32), atol=2e-2)
    # and the rule: off a TPU XLA's form, whatever the shapes
    assert ea.chunks_form(16384, 128, 16) == "xla"


# ---- the attention, both forms, against the reference's -----------------------------

# one window, three, five, and a last window that is partial (two and a half)
LENGTHS = [32, 96, 160, 80]


def _reference_attention(q, k, v, kbar, vbar):
    """The reference's, a row at a time on the view a head, merged again."""
    with jax.default_matmul_precision("highest"):
        return np.stack([np.asarray(REFERENCE.attention(
            *(_heads(a[i]) for a in (q, k, v, kbar, vbar)), WINDOW, CHUNK)
        ).reshape(q.shape[1:]) for i in range(len(q))])


@pytest.mark.parametrize("n", LENGTHS)
def test_blocked_form_is_the_references_attention(n):
    q, k, v, mu, phi = _qkv(n, seed=n)
    kbar, vbar = ea.chunk_summaries(k, v, mu, phi, CHUNK)
    with dispatch_notes() as forms, \
            jax.default_matmul_precision("highest"):
        # a block of 16 queries: two blocks a window, a ragged last one
        got = ea.eva_attention(q, k, v, kbar, vbar, 4, WINDOW, CHUNK,
                               block=16)
    assert forms == ["eva_attention=blocked"]
    assert got.shape == q.shape
    np.testing.assert_allclose(
        np.asarray(got), _reference_attention(q, k, v, kbar, vbar),
        rtol=2e-5, atol=2e-6)


# (block_q, block_k, block_s) under the interpreter, in windows of 32 with 8
# summaries each: a summary block of 2 windows' worth and a tile of half a
# window; of 4 windows' worth (a walked block holds 1, 2, 3 of its 4 quarters
# or all, the rest masked by column) with the window one key block, the tile
# half of it, then all of it (the first window's tile is the diagonal's block
# alone); a tile a quarter of a window, two to a key block; and blocks of one
# window's summaries (none ever masked) with four key blocks a window, so a
# window's last tile walks three before its own
TILES = [(16, 16, 16), (16, 32, 32), (32, 32, 32), (8, 16, 16), (8, 8, 8)]


@pytest.mark.parametrize("n,tiles", [
    pytest.param(n, tiles, id=f"{n}-" + "x".join(map(str, tiles)))
    for n in LENGTHS for tiles in TILES if n % tiles[0] == 0])  # whole tiles
def test_kernel_under_the_interpreter_is_the_references_attention(n, tiles):
    """Row 1 of a batch of two read where it lies, in float32 at ``highest``:
    the diagonal's block first, then one loop over the blocks of summaries
    (the last masked by column, and padded where the sequence's summaries
    are not whole blocks: 20 of them at 80 positions) and the window's
    earlier key blocks, equals the reference and XLA's form."""
    q, k, v, mu, phi = _qkv(n, seed=n + 1)
    kbar, vbar = ea.chunk_summaries(k, v, mu, phi, CHUNK)
    with jax.default_matmul_precision("highest"):
        got = ea._kernel_row(q, k, v, kbar, vbar, 1, heads=4, window=WINDOW,
                             chunk=CHUNK, scale=0.25, tiles=tiles,
                             interpret=True)
        blocked = ea._blocked_row(*(_heads(a[1]) for a in (q, k, v, kbar,
                                                            vbar)),
                                  WINDOW, CHUNK, 0.25, 16)
    assert got.shape == (n, 64)
    want = _reference_attention(q[1:], k[1:], v[1:], kbar[1:], vbar[1:])[0]
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(blocked).reshape(n, 64),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("tiles", [
    (16, 16, 8),   # one loop walks both kinds of block: they are as wide
    (16, 24, 24),  # a window of whole key blocks
    (32, 16, 16),  # a tile lies in one key block
    (24, 24, 24),  # a sequence of whole tiles
])
def test_kernel_refuses_tiles_that_cannot_work(tiles):
    q, k, v, mu, phi = _qkv(96, seed=2)
    kbar, vbar = ea.chunk_summaries(k, v, mu, phi, CHUNK)
    with pytest.raises(ValueError):
        ea._kernel_row(q, k, v, kbar, vbar, 0, heads=4, window=WINDOW,
                       chunk=CHUNK, scale=0.25, tiles=tiles, interpret=True)


def test_kernel_at_the_tiles_of_the_published_sizes_in_bfloat16():
    """512 queries against blocks of 512 keys and of 512 summaries, a head a
    block of 128 lanes: three windows of 2,048 in chunks of 16 (the second
    window's tiles walk a block that holds 128 summaries they see and 256
    they do not, the third's one with 256 of each, and the 384 summaries are
    padded to the block), under the interpreter, against XLA's form on the
    same operands."""
    n, heads, d = 6144, 2, 128
    q, k, v, mu, phi = _qkv(n, heads, d, rows=1, seed=3, dtype=jnp.bfloat16)
    kbar, vbar = ea.chunk_summaries(k, v, mu, phi, 16)
    assert ea.eva_tiles(2048, 16) == (512, 512, 512)
    merged = (q, k, v, kbar, vbar)
    got = ea._kernel_row(*merged, 0, heads=heads, window=2048, chunk=16,
                         scale=d ** -0.5, interpret=True)
    want = ea._blocked_row(*(_heads(a[0], heads) for a in merged), 2048, 16,
                           d ** -0.5, 512)
    np.testing.assert_allclose(
        np.asarray(got, np.float32).reshape(n, heads, d),
        np.asarray(want, np.float32), atol=2e-2)
    # and the rule: off a TPU the blocked form, whatever the shapes
    assert ea.eva_form(16384, 128, 2048, 16) == "blocked"
    # the parent's tiles: a summary block narrower than the key block
    with pytest.raises(ValueError):
        ea._kernel_row(*merged, 0, heads=heads, window=2048, chunk=16,
                       scale=1.0, tiles=(512, 512, 128), interpret=True)


@pytest.mark.parametrize("window", [48, 64])
def test_a_window_of_the_whole_sequence_is_causal_attention(window):
    """No summary is visible: EVA on ``q, k, v`` is plain causal attention,
    whatever the summaries hold."""
    q, k, v, _, _ = _qkv(48, seed=5)
    junk = jnp.full((2, 12, 64), 1e3, jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = ea.eva_attention(q, k, v, junk, junk, 4, window, CHUNK)
        want = causal_attention(*(_heads(a).transpose(0, 2, 1, 3)
                                  for a in (q, k, v)))
    np.testing.assert_allclose(np.asarray(_heads(got)),
                               np.asarray(want.transpose(0, 2, 1, 3)),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("w", [1, 2])
def test_a_windows_first_query_reads_its_own_key_and_every_earlier_summary(w):
    """... and nothing else: the softmax over ``[kbar_0 .. kbar_(8 w - 1) ;
    k_t]`` by hand; a change to any other key or summary moves nothing."""
    q, k, v, mu, phi = _qkv(96, rows=1, seed=7)
    kbar, vbar = ea.chunk_summaries(k, v, mu, phi, CHUNK)
    t, seen = w * WINDOW, w * WINDOW // CHUNK
    with jax.default_matmul_precision("highest"):
        got = np.asarray(_heads(ea.eva_attention(q, k, v, kbar, vbar, 4,
                                                 WINDOW, CHUNK)))[0, t]
    qn, kn, vn, kb, vb = (np.asarray(_heads(a[0]), np.float64)
                          for a in (q, k, v, kbar, vbar))
    for h in range(4):
        keys = np.concatenate([kb[:seen, h], kn[t:t + 1, h]])
        values = np.concatenate([vb[:seen, h], vn[t:t + 1, h]])
        e = np.exp(keys @ qn[t, h] / 4.0)
        np.testing.assert_allclose(got[h], (e / e.sum()) @ values,
                                   rtol=2e-5, atol=2e-6)
    # every other key (its window's later ones, every earlier window's) and
    # every later summary, set to something else
    others = np.ones(96, bool)
    others[t] = False
    k2 = jnp.where(others[None, :, None], k + 3.0, k)
    v2 = jnp.where(others[None, :, None], v - 2.0, v)
    later = (np.arange(24) >= seen)[None, :, None]
    with jax.default_matmul_precision("highest"):
        again = np.asarray(_heads(ea.eva_attention(
            q, k2, v2, jnp.where(later, kbar + 1.0, kbar),
            jnp.where(later, vbar + 1.0, vbar), 4, WINDOW, CHUNK)))[0, t]
    np.testing.assert_array_equal(again, got)


# ---- the counters ------------------------------------------------------------------

@pytest.mark.parametrize("b,s,window,chunk,exact,summarised", [
    (1, 16384, 2048, 16, 16_785_408, 117_440_512),  # the cell's row
    (4, 16384, 2048, 16, 67_141_632, 469_762_048),  # ... and its step
    (2, 96, 32, 4, 3168, 6144),
    (1, 80, 32, 4, 528 + 528 + 136, 32 * 32 + 16 * 64),  # a partial window
    (3, 32, 64, 4, 3 * 528, 0),  # one window: nothing is summarised
])
def test_pair_counts_equal_the_closed_form(b, s, window, chunk, exact,
                                           summarised):
    got = ea.pair_counts(b, s, window, chunk)
    assert (int(got[0]), int(got[1])) == (exact, summarised)
    assert got[0].dtype == got[1].dtype == jnp.int32
    # together, every causal pair: EVA drops none
    assert exact + summarised == b * s * (s + 1) // 2
    # ... and by the masks: a query's keys and the positions its summaries hold
    t = np.arange(s)
    assert exact == b * int((t % window + 1).sum())
    assert summarised == b * int((t // window * window).sum())


def test_the_cells_share_of_pairs_reached_through_summaries():
    exact, summarised = ea.pair_counts(1, 16384, 2048, 16)
    assert int(summarised) // 16 == 7_340_032  # the summaries read
    assert 100 * int(summarised) / (int(exact) + int(summarised)) \
        == pytest.approx(87.49, abs=0.005)


def test_the_queue_counts_the_pairs_in_the_registry():
    """Through the model's own reader, which is all the queue calls."""
    from storm_tpu.runtime.metrics import MetricsRegistry

    registry = MetricsRegistry()
    observe = build_model("evabyte_tiny").observe_aux
    for _ in range(5):
        observe(registry, "inference-bolt", {
            "eva_pairs_exact": np.asarray([67_141_632] * 11, np.int32),
            "eva_pairs_summarised": np.asarray([469_762_048] * 11, np.int32)})
    got = registry.snapshot()["inference-bolt"]
    assert got["eva_pairs_exact"] == 5 * 11 * 67_141_632  # past int32
    assert got["eva_pairs_summarised"] == 5 * 11 * 469_762_048
    assert "sparse_keys_read" not in got


# ---- the program against the reference ------------------------------------------------

def _both(length=96, dtype=jnp.float32, seed=1, rows=3):
    model = program.build_evabyte_tiny(input_shape=(length,),
                                       param_dtype=dtype)
    params, state = model.init(jax.random.PRNGKey(seed))
    x = _windows(rows, length, seed)
    with dispatch_notes() as forms:
        logits, new_state = jax.jit(model.apply)(params, state, x)
    got = jax.nn.softmax(logits.astype(jnp.float32), -1).reshape(rows, -1)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, s, xx: REFERENCE.forward(
            SIZES, p, s, xx))(params, state, x)
    return got, want, forms, new_state


@pytest.mark.parametrize("length", [96, 80, 32])
def test_program_is_the_reference_in_float32(length):
    with jax.default_matmul_precision("highest"):
        got, want, forms, new_state = _both(length)
    assert forms == ["rotary_turn=halves", "eva_chunks=xla",
                     "eva_attention=blocked"]
    assert got.shape == want.shape == (3, 320)
    assert bool(jnp.isfinite(got).all())
    assert _distance(got, want).max() < 1e-5
    s = length
    assert new_state["aux"]["eva_pairs_exact"].tolist() == [
        3 * int((np.arange(s) % 32 + 1).sum())] * 2
    assert new_state["aux"]["eva_pairs_summarised"].tolist() == [
        3 * int((np.arange(s) // 32 * 32).sum())] * 2


def test_through_build_model_an_answer_is_eight_distributions_that_differ():
    model = build_model("evabyte_tiny")
    assert (model.num_classes, model.input_shape, model.max_rows,
            model.input_dtype) == (320, (96,), 4, "float32")
    params, state = model.init(jax.random.PRNGKey(2))
    assert params["embed"].shape == (40, 64)
    assert params["head"].shape == (64, 320)
    x = _windows(2, 96, seed=2)
    logits, _ = jax.jit(model.apply)(params, state, x)
    assert logits.shape == (2, 8, 40) and logits.dtype == jnp.float32
    with jax.default_matmul_precision("highest"):
        want = np.asarray(REFERENCE.forward(SIZES, params, state, x))
    got = np.asarray(jax.nn.softmax(logits, -1))
    assert _distance(got.reshape(2, -1), want).max() < 1e-5
    np.testing.assert_allclose(got.sum(-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(want.reshape(2, 8, 40).sum(-1), 1.0, atol=1e-5)
    for a in range(8):
        for b in range(a):
            assert np.abs(got[:, a] - got[:, b]).max() > 1e-3


def test_the_engine_hands_the_sink_the_eight_rows_end_to_end():
    """``infer/engine.py fwd``: a softmax a head, then one row a record."""
    from storm_tpu.config import BatchConfig, ModelConfig, ShardingConfig
    from storm_tpu.infer.engine import engine_inventory, shared_engine

    engine = shared_engine(ModelConfig(
        name="evabyte_tiny", dtype="float32", num_classes=320,
        input_shape=(96,), seed=3), ShardingConfig(data_parallel=0),
        BatchConfig())
    assert engine.batch_cfg.buckets == (4,)
    x = _windows(3, 96, seed=3)
    out = np.asarray(engine.predict(x))
    assert out.shape == (3, 320)
    np.testing.assert_allclose(out.reshape(3, 8, 40).sum(-1), 1.0, atol=1e-5)
    params, state = build_model("evabyte_tiny").init(jax.random.PRNGKey(3))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(REFERENCE.forward(SIZES, params, state, x))
    assert _distance(out, want).max() < 1e-4
    row = next(r for r in engine_inventory()["engines"]
               if r["model"] == "evabyte_tiny")
    assert row["programs"] == {
        str(engine.pad_batch(4)):
        "rotary_turn=halves, eva_chunks=xla, eva_attention=blocked"}


def test_bfloat16_path_on_its_own_terms():
    """Parameters and branches in bfloat16, the stream float32: rounding and
    no more against the float32 reference on the same leaves."""
    got, want, _, _ = _both(dtype=jnp.bfloat16)
    assert bool(jnp.isfinite(got).all())
    assert _distance(got, want).max() < 0.03
    np.testing.assert_allclose(np.asarray(got).reshape(3, 8, 40).sum(-1), 1.0,
                               atol=1e-3)


def test_a_later_byte_changes_no_earlier_result():
    """The mixer is causal across windows and summaries: the result at the
    positions before a changed input is bit for bit the same, at a window's
    edge as inside a chunk."""
    p = program.eva_mixer_init(jax.random.PRNGKey(0), 64, 4, 16)
    from storm_tpu.ops import rope as R

    tables = R.rotary_tables(96, 100.0 ** (-2.0 * np.arange(8) / 16))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 96, 64))
    base = program.eva_mixer(p, x, 4, 16, WINDOW, CHUNK, tables)[0]
    for t in (33, 64, 95):
        moved = program.eva_mixer(p, x.at[0, t].add(1.0), 4, 16, WINDOW,
                                  CHUNK, tables)[0]
        np.testing.assert_array_equal(np.asarray(moved[0, :t]),
                                      np.asarray(base[0, :t]))
        assert np.abs(np.asarray(moved[0, t:] - base[0, t:])).max() > 1e-4


def test_the_initialisers_leaves_are_in_the_served_type():
    """The load of ``models/scorer.py``: every leaf in bfloat16 as it is
    made; the pooling vectors clipped as the released code clips them; the
    full model's tree by ``eval_shape`` is the issue's count."""
    served = program.build_evabyte_tiny(param_dtype=jnp.bfloat16)
    plain = build_model("evabyte_tiny")
    a, _ = served.init(jax.random.PRNGKey(11))
    b, _ = plain.init(jax.random.PRNGKey(11))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.dtype == jnp.bfloat16 and y.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(x, np.float32), np.asarray(
            y.astype(jnp.bfloat16), np.float32))
    mixer = b["layers"][0]["mixer"]
    for name in ("mu", "phi"):
        assert mixer[name].shape == (4, 16)
        assert float(jnp.abs(mixer[name]).max()) <= 0.25  # 16^-1/2
        assert float(jnp.std(mixer[name])) > 0.1
    # a branch's output projection over the root of the published branches
    assert float(jnp.std(mixer["o"])) == pytest.approx(
        64 ** -0.5 * 8 ** -0.5, rel=0.1)
    assert float(jnp.std(mixer["q"])) == pytest.approx(64 ** -0.5, rel=0.1)
    full = build_model("evabyte")
    params, state = jax.eval_shape(full.init, jax.random.PRNGKey(0))
    assert {x.dtype for x in jax.tree.leaves(params)} == {jnp.dtype(
        jnp.bfloat16)}
    assert sum(x.size for x in jax.tree.leaves(params)) == 2_238_107_648
    assert sum(x.size for x in jax.tree.leaves(params["layers"][0])) \
        == 202_391_552
    assert params["embed"].shape == (320, 4096)
    assert params["head"].shape == (4096, 2560)
    assert state["aux"]["eva_pairs_exact"].shape == (11,)
    assert (full.max_rows, full.input_dtype, full.input_shape,
            full.num_classes) == (4, "float32", (16384,), 2560)
    assert full.hyper["window"] == 2048 and full.hyper["chunk"] == 16
    with pytest.raises(ValueError):  # 2,561 columns are not eight heads
        program.build_evabyte("x", 2561, (32,), layers=1, published_layers=1,
                              dim=16, ffn_width=16, heads=2, head_dim=8,
                              window=16, chunk=4)
    with pytest.raises(ValueError):  # positions that are not whole chunks
        ea.pair_counts(1, 30, 16, 4)


def test_the_turn_in_lanes_is_the_turn_of_halves():
    """``ops/rope.py turn_merged``'s kernel under the interpreter (heads of
    one lane tile, a rotation of the lanes for the exchange of halves)
    against ``rotate_halves`` on the view a head, queries and keys in one
    call."""
    from storm_tpu.ops import rope as R

    q, k, _, _, _ = _qkv(256, heads=2, d=128, seed=9, dtype=jnp.bfloat16)
    cos, sin = R.rotary_tables(256, 100000.0 ** (-2.0 * np.arange(64) / 128))
    with dispatch_notes() as forms:
        want = R.turn_merged((q, k), cos, sin, 2)
    assert forms == ["rotary_turn=halves"]
    got = R._turn_lanes((q, k), jnp.concatenate([cos, cos], -1),
                        jnp.concatenate([-sin, sin], -1), heads=2,
                        interpret=True)
    for a, b, x in zip(got, want, (q, k)):
        assert a.shape == x.shape and a.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert np.abs(np.asarray(got[0] - q, np.float32)).max() > 0.1
    # and the rule: off a TPU the halves, whatever the shapes
    assert R.turn_form(16384, 128) == "halves"
