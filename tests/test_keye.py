"""Keye-VL-2.0's language model (PR 59): the program against the benchmark's
plain reference on the CPU at toy widths; the indexer's selection against a
sort, with tied scores, in both of its forms; the shared second pass under a
mask of one head; M-RoPE's tables; the first softmax router; the half-shares;
and that nothing it touched in the shared code moved another plan."""

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
from storm_tpu.models.registry import build_model, load_or_init  # noqa: E402
from storm_tpu.ops import rope  # noqa: E402
from storm_tpu.ops import sparse_attention as sa  # noqa: E402
from storm_tpu.ops.platform import dispatch_notes  # noqa: E402
from storm_tpu.parallel import moe  # noqa: E402

REFERENCE = spec.plugin("references", "keye")
SIZES = spec.config("keye_tiny")["published"]
SEQ, TOPK = 40, 12


def _ids(model, rows=4, seed=0):
    return np.random.RandomState(seed).randint(
        0, model.num_classes, (rows, *model.input_shape)).astype(np.float32)


def _distance(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))


# ---- the model ------------------------------------------------------------------

def test_the_model_is_the_reference_in_float32():
    model = build_model("keye_tiny")
    params, state = load_or_init(model, None, 3)
    x = _ids(model)
    logits, new_state = jax.jit(model.apply)(params, state, x)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, s, xx: REFERENCE.forward(SIZES, p, s, xx))(
            params, state, x)
    assert _distance(jax.nn.softmax(logits, -1), want).max() < 1e-5
    aux = new_state["aux"]
    # three layers; 40 positions in squares of 8: 15 at or under the diagonal
    assert np.asarray(aux["index_blocks_causal"]).tolist() == [4 * 15] * 3
    assert (np.asarray(aux["index_blocks_picked"]) <= 60).all()
    assert (np.asarray(aux["index_blocks_picked"]) > 0).all()
    assert np.asarray(aux["expert_absent"]).tolist() == [0, 0, 0]
    assert np.asarray(aux["expert_tokens"]).sum(1).tolist() == [4 * 40 * 2] * 3


def test_a_later_token_changes_no_earlier_output_of_the_mixer():
    """Neither through the attention nor through the selection: a query's
    picks are among the keys at or before it."""
    from storm_tpu.models.keye import keye_mixer

    model = build_model("keye_tiny")
    params, _ = load_or_init(model, None, 5)
    p = params["layers"][0]["mixer"]
    tables = (rope.rotary_tables(SEQ, 100.0 ** (-2.0 * np.arange(8) / 16)),
              rope.rotary_tables(SEQ, 100.0 ** (-2.0 * np.arange(4) / 8)))
    x = np.random.RandomState(0).standard_normal((1, SEQ, 64)).astype(
        np.float32)
    other = x.copy()
    other[:, 30:] += 1.0

    def mixed(xx):
        return np.asarray(keye_mixer(p, jnp.asarray(xx), 8, 2, 16, 4, 8, 1e-6,
                                     tables, TOPK, 8, 16, 16)[0])

    a, b = mixed(x), mixed(other)
    assert np.allclose(a[:, :30], b[:, :30], atol=1e-6)
    assert not np.allclose(a[:, 30:], b[:, 30:], atol=1e-3)


def test_a_window_of_topk_or_fewer_is_plain_causal_attention_and_scores_nothing():
    model = build_model("keye_tiny", input_shape=(TOPK,))
    params, state = load_or_init(model, None, 3)
    with dispatch_notes() as notes:
        logits, new_state = jax.jit(model.apply)(params, state, _ids(model))
    assert not [n for n in notes if n.startswith("index_select")]
    with jax.default_matmul_precision("highest"):
        want = REFERENCE.forward(SIZES, params, state, _ids(model))
    assert _distance(jax.nn.softmax(logits, -1), want).max() < 1e-5


def test_registry_names_the_model_its_cut_and_its_type():
    big = build_model("keye_vl2_30b")
    assert big.input_shape == (16384,) and big.num_classes == 151936
    assert big.max_rows == 4 and big.input_dtype == "float32"
    hyper = big.hyper
    assert (hyper["layers"], hyper["dim"], hyper["heads"], hyper["kv_heads"],
            hyper["head_dim"]) == (6, 2048, 32, 4, 128)
    assert (hyper["index_heads"], hyper["index_dim"], hyper["topk"],
            hyper["chunk"]) == (16, 64, 2048, 512)
    assert (hyper["n_experts"], hyper["top_k"], hyper["experts_held"],
            hyper["first_expert"]) == (128, 8, 128, 0)
    assert hyper["mrope_section"] == (16, 24, 24)
    params, _ = jax.eval_shape(big.init, jax.random.PRNGKey(0))
    assert {leaf.dtype for leaf in jax.tree.leaves(params)} == {
        jnp.dtype("bfloat16")}
    assert sum(leaf.size for leaf in jax.tree.leaves(params)) \
        == 4_374_622_464
    ffn = params["layers"][0]["ffn"]
    assert set(ffn) == {"router", "experts"}  # no bias, no shared expert
    assert set(params["layers"][0]["mixer"]) == {
        "q", "k", "v", "o", "q_norm", "k_norm", "index_q", "index_k",
        "index_w", "index_k_norm"}
    with pytest.raises(ValueError):
        from storm_tpu.models.keye import build_keye
        build_keye("x", 8, (8,), layers=1, published_layers=1, dim=8,
                   heads=2, kv_heads=1, head_dim=8, mrope_section=(1, 1, 1),
                   index_heads=1, index_dim=4, topk=2, chunk=4,
                   expert_width=4, n_experts=2, top_k=1, experts_held=2)


def test_the_inventory_notes_both_passes_and_the_counters_reach_the_registry():
    model = build_model("keye_tiny")
    params, state = load_or_init(model, None, 3)
    with dispatch_notes() as notes:
        _, new_state = jax.jit(model.apply)(params, state, _ids(model))
    assert "index_select=top_k" in notes
    assert "sparse_attention=blocked" in notes

    class Counter:
        def __init__(self):
            self.n = 0

        def inc(self, by):
            self.n += by

    class Metrics:
        def __init__(self):
            self.seen = {}

        def counter(self, cid, name):
            return self.seen.setdefault((cid, name), Counter())

        def histogram(self, cid, name):
            class H:
                def observe(self, _):
                    pass
            return H()

    metrics = Metrics()
    model.observe_aux(metrics, "bolt",
                      jax.tree.map(np.asarray, new_state["aux"]))
    assert metrics.seen[("bolt", "index_blocks_causal")].n == 3 * 4 * 15
    assert 0 < metrics.seen[("bolt", "index_blocks_picked")].n <= 180
    assert metrics.seen[("bolt", "expert_assignments_absent")].n == 0


# ---- the selection ---------------------------------------------------------------

def _sorted_picks(qi, ki, w, topk):
    """Each query's ``topk`` best keys by a stable descending sort of its
    float64 scores, all of its keys where there are no more."""
    s = qi.shape[1]
    dots = np.einsum("htd,sd->hts", qi.astype(np.float64),
                     ki.astype(np.float64))
    index = (w.T[:, :, None] * np.maximum(dots, 0)).sum(0)
    index = np.where(index == 0, 0.0, index)
    out = np.zeros((s, s), np.int8)
    for t in range(s):
        order = np.argsort(-index[t, :t + 1], kind="stable")[:topk]
        out[t, order] = 1
    return out


def _indexer(case, heads, s, d, seed=0):
    rng = np.random.RandomState(seed)
    # values a float32 product and sum hold exactly, so that the float64 sort
    # and both forms see the same scores: ties are ties in all three
    qi = rng.randint(-3, 4, (heads, s, d)).astype(np.float32)
    ki = rng.randint(-3, 4, (s, d)).astype(np.float32)
    w = rng.randint(-2, 3, (s, heads)).astype(np.float32)
    if case == "some heads zeroed":
        w[:, 1:] = 0
    if case == "all zero":  # every score alike: the lower index wins
        w[:] = 0
    if case == "negative zero":
        w[:] = -1
        qi[:] = -np.abs(qi)
        ki[:] = np.abs(ki)  # every product <= 0: every score -0 or +0
    return qi, ki, w


CASES = ["random", "some heads zeroed", "all zero", "negative zero"]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("topk", [1, 16, 12, SEQ - 1, SEQ, 2 * SEQ])
def test_select_keys_is_the_sort_at_six_topk_with_tied_scores(topk, case):
    """``topk`` 1, a tile (16), no multiple of a tile, S - 1, S and 2 S, over
    40 positions (no multiple of the tile either)."""
    qi, ki, w = _indexer(case, 4, SEQ, 8)
    got = np.asarray(sa.select_keys(*map(jnp.asarray, (qi, ki, w)),
                                    topk=topk, tile=16))
    assert got.shape == (1, SEQ, SEQ) and got.dtype == np.int8
    assert np.array_equal(got[0], _sorted_picks(qi, ki, w, topk))
    assert (got[0].sum(1) == np.minimum(np.arange(SEQ) + 1, topk)).all()


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("topk", [1, 32, 50, 255, 256, 512])
def test_the_selections_kernel_is_the_sort_under_the_interpreter(topk, case):
    """The kernel at a tile of 32 queries and blocks of 128 keys over 256
    positions: tiles wholly before ``topk``, wholly past it, and (50, 255)
    tiles that hold queries of both kinds, which the rule never gives the
    kernel on the chip."""
    qi, ki, w = _indexer(case, 4, 256, 64, seed=topk)
    got = np.asarray(sa._select_kernel_row(
        *map(jnp.asarray, (qi, ki, w)), topk=topk, tile=32, block_k=128,
        interpret=True))
    assert np.array_equal(got[0], _sorted_picks(qi, ki, w, topk))


def test_the_selection_takes_float32_scores_in_order_across_the_sign():
    """Scores on both sides of zero, tiny and huge: the integers the kernel
    bisects order as the floats do."""
    rng = np.random.RandomState(3)
    s = 128
    qi = rng.standard_normal((2, s, 64)).astype(np.float32)
    ki = (rng.standard_normal((s, 64)) * 10.0 ** rng.randint(
        -20, 15, (s, 1))).astype(np.float32)
    w = rng.standard_normal((s, 2)).astype(np.float32)
    args = tuple(map(jnp.asarray, (qi, ki, w)))
    kernel = np.asarray(sa._select_kernel_row(
        *args, topk=32, tile=32, block_k=128, interpret=True))
    assert np.array_equal(kernel, np.asarray(sa._select_top_k(
        *args, 32, 32)))


def test_select_form_takes_the_kernel_for_whole_tiles_on_one_chip(monkeypatch):
    assert sa.select_form(16384, 64, 2048) == "top_k"  # the CPU
    monkeypatch.setattr(sa, "_use_pallas", lambda: True)
    monkeypatch.setattr(sa, "_one_device", lambda: True)
    assert sa.select_form(16384, 64, 2048) == "kernel"
    assert sa.select_form(16384, 64, 2000) == "top_k"  # a tile of both kinds
    assert sa.select_form(16000, 64, 2048) == "top_k"
    assert sa.select_form(16384, 8, 2048) == "top_k"
    monkeypatch.setattr(sa, "_one_device", lambda: False)
    assert sa.select_form(16384, 64, 2048) == "top_k"


def test_blocks_picked_counts_the_squares_a_query_reads_in():
    wanted = np.zeros((1, 32, 32), np.int8)
    wanted[0, 5, 3] = 1      # square (0, 0)
    wanted[0, 17, 2] = 1     # square (2, 0)
    wanted[0, 31, 31] = 1    # square (3, 3)
    wanted[0, 30, 29] = 1    # the same square
    picked, causal = sa.blocks_picked(jnp.asarray(wanted), 8)
    assert (int(picked), causal) == (3, 10)
    with pytest.raises(ValueError):
        sa.blocks_picked(jnp.asarray(wanted), 5)


# ---- the second pass under a mask of one head ------------------------------------

def _masked_softmax(q, k, v, wanted, scale):
    g = q.shape[0] // k.shape[0]
    out = []
    for h in range(q.shape[0]):
        scores = q[h].astype(np.float64) @ k[h // g].astype(np.float64).T \
            * scale
        scores = np.where(wanted, scores, -np.inf)
        weights = np.exp(scores - scores.max(-1, keepdims=True))
        out.append(weights / weights.sum(-1, keepdims=True)
                   @ v[h // g].astype(np.float64))
    return np.stack(out)


@pytest.mark.parametrize("form", ["kernel", "blocked"])
@pytest.mark.parametrize("heads", [1, 2])  # one mask for all; one a key head
def test_the_second_pass_reads_a_mask_of_one_head_or_of_every_key_head(
        form, heads):
    rng = np.random.RandomState(heads)
    hq, hkv, s, d = 8, 2, 1024, 128
    q = rng.standard_normal((1, hq, s, d)).astype(np.float32)
    k = rng.standard_normal((1, hkv, s, d)).astype(np.float32)
    v = rng.standard_normal((1, hkv, s, d)).astype(np.float32)
    t = np.arange(s)
    wanted = (rng.rand(heads, s, s) < 0.3) & (t[None, :] <= t[:, None])
    wanted |= np.eye(s, dtype=bool)  # a query reads itself
    args = tuple(map(jnp.asarray, (q, k, v)))
    mask = jnp.asarray(wanted.astype(np.int8))
    if form == "kernel":
        got = sa._kernel_row(*args, mask, d ** -0.5, 0, interpret=True)
    else:
        got = sa._blocked_row(
            q[0], k[0], v[0], lambda lo, hi: mask[:, lo:hi, :hi] != 0,
            d ** -0.5, 256)
    want = np.stack([_masked_softmax(
        q[0][g * 4:(g + 1) * 4], k[0][g:g + 1], v[0][g:g + 1],
        wanted[g if heads == 2 else 0], d ** -0.5) for g in range(hkv)])
    assert np.abs(np.asarray(got) - want.reshape(hq, s, d)).max() < 2e-5


def test_indexed_attention_is_the_masked_softmax_under_the_sorts_picks():
    rng = np.random.RandomState(1)
    b, hq, hkv, s, d, hi, di, topk = 2, 4, 2, SEQ, 16, 3, 8, TOPK
    q, k, v = (rng.standard_normal((b, n, s, d)).astype(np.float32)
               for n in (hq, hkv, hkv))
    qi = rng.standard_normal((b, hi, s, di)).astype(np.float32)
    ki = rng.standard_normal((b, s, di)).astype(np.float32)
    w = rng.standard_normal((b, s, hi)).astype(np.float32)
    out, picked, causal = sa.indexed_attention(
        *map(jnp.asarray, (q, k, v, qi, ki, w)), d ** -0.5, topk=topk,
        count_block=8, block=16, tile=16)
    for row in range(b):
        wanted = _sorted_picks(qi[row], ki[row], w[row], topk) != 0
        want = _masked_softmax(q[row], k[row], v[row], wanted, d ** -0.5)
        assert np.abs(np.asarray(out[row]) - want).max() < 2e-5
    assert int(causal) == b * 15 and 0 < int(picked) <= int(causal)


# ---- M-RoPE ----------------------------------------------------------------------

def test_mrope_with_equal_streams_is_rotary_tables_to_the_bit():
    inv_freq = 1e7 ** (-2.0 * np.arange(64) / 128)
    positions = np.broadcast_to(np.arange(300), (3, 300))
    cos, sin = rope.mrope_tables(positions, inv_freq, (16, 24, 24))
    plain_cos, plain_sin = rope.rotary_tables(300, inv_freq)
    assert np.array_equal(np.asarray(cos), np.asarray(plain_cos))
    assert np.array_equal(np.asarray(sin), np.asarray(plain_sin))
    with pytest.raises(ValueError):
        rope.mrope_tables(positions, inv_freq, (16, 24, 23))
    with pytest.raises(ValueError):
        rope.mrope_tables(positions[:2], inv_freq, (16, 24, 24))


def test_mrope_with_unequal_streams_is_the_references():
    """An image-like span inside text: stream 0 stands still over the span
    while streams 1 and 2 walk a grid of 4 x 6."""
    text = np.arange(10)
    grid = np.arange(24)
    positions = np.stack([
        np.concatenate([text, np.full(24, 10), 11 + np.arange(6)]),
        np.concatenate([text, 10 + grid // 6, 11 + np.arange(6)]),
        np.concatenate([text, 10 + grid % 6, 11 + np.arange(6)])])
    sections, theta, d = (2, 3, 3), 100.0, 16
    inv_freq = theta ** (-2.0 * np.arange(d // 2) / d)
    cos, sin = rope.mrope_tables(positions, inv_freq, sections)
    angle = np.asarray(REFERENCE.mrope_angle(positions, theta, d, sections))
    assert np.allclose(np.asarray(cos), np.cos(angle), atol=1e-6)
    assert np.allclose(np.asarray(sin), np.sin(angle), atol=1e-6)
    # written out: frequency i at position t turns by p[stream(i), t] * f_i
    stream = [0, 0, 1, 1, 1, 2, 2, 2]
    for i, which in enumerate(stream):
        assert np.allclose(angle[:, i], positions[which] * inv_freq[i],
                           rtol=1e-6)
    # the span is where the streams part: there the tables differ from plain
    plain_cos, _ = rope.rotary_tables(40, inv_freq)
    assert not np.allclose(np.asarray(cos)[10:34], np.asarray(plain_cos)[10:34])
    # and a turn by them is the reference's turn
    x = np.random.RandomState(0).standard_normal((40, 3, d)).astype(np.float32)
    got = rope.rotate_halves(jnp.asarray(x), cos[:, None], sin[:, None])
    want = REFERENCE._turn(jnp.asarray(x), jnp.asarray(angle))
    assert np.allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# ---- the router and the shares ---------------------------------------------------

def test_softmax_routing_without_a_bias_is_its_formula():
    p = moe.topk_moe_init(jax.random.PRNGKey(0), 32, 8, 20, shared=False,
                          selection_bias=False)
    assert set(p) == {"router", "experts"}
    with_bias = moe.topk_moe_init(jax.random.PRNGKey(0), 32, 8, 20)
    assert set(with_bias) == {"router", "router_bias", "experts", "shared"}
    for key in ("router",):  # the same draw with and without the leaves
        assert np.array_equal(np.asarray(p[key]), np.asarray(with_bias[key]))
    x = jax.random.normal(jax.random.PRNGKey(1), (50, 32))
    experts, weights = moe.route_topk(p, x, 3, router="softmax",
                                      renormalize=True, scale=1.0)
    logits = np.asarray(x, np.float64) @ np.asarray(p["router"], np.float64)
    score = np.exp(logits - logits.max(-1, keepdims=True))
    score /= score.sum(-1, keepdims=True)
    order = np.argsort(-score, -1, kind="stable")[:, :3]
    assert np.array_equal(np.sort(np.asarray(experts), -1), np.sort(order, -1))
    chosen = np.take_along_axis(score, np.asarray(experts), -1)
    assert np.allclose(np.asarray(weights),
                       chosen / (chosen.sum(-1, keepdims=True) + 1e-20),
                       atol=1e-6)
    assert np.allclose(np.asarray(weights).sum(-1), 1.0, atol=1e-6)


def test_two_half_shares_add_up_to_the_all_held_layer():
    """The guide's test that ties a share to the model: experts 0-9 and 10-19
    of the router's 20, each share's routed sum, add up to what the layer
    that holds all 20 gives; no shared expert to count once."""
    p = moe.topk_moe_init(jax.random.PRNGKey(2), 64, 32, 20, shared=False,
                          selection_bias=False)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 40, 64))
    whole, tokens, absent = moe.topk_moe_layer(
        p, x, 2, router="softmax", tile=16)
    assert int(absent) == 0 and int(tokens.sum()) == 4 * 40 * 2
    halves = []
    for first in (0, 10):
        share = {"router": p["router"], "experts": jax.tree.map(
            lambda a: a[first:first + 10], p["experts"])}
        y, tokens_h, absent_h = moe.topk_moe_layer(
            share, x, 2, first_expert=first, router="softmax", tile=16)
        assert int(tokens_h.sum()) + int(absent_h) == 4 * 40 * 2
        assert np.array_equal(np.asarray(tokens_h),
                              np.asarray(tokens[first:first + 10]))
        halves.append(y)
    assert np.allclose(np.asarray(halves[0] + halves[1]), np.asarray(whole),
                       atol=1e-5)
    # and the model holds a share by the same arguments
    half = build_model("keye_tiny")
    from storm_tpu.models.keye import build_keye
    kw = dict(layers=1, published_layers=8, dim=64, heads=8, kv_heads=2,
              head_dim=16, mrope_section=(2, 3, 3), index_heads=4,
              index_dim=8, topk=12, chunk=8, expert_width=32, n_experts=20,
              top_k=2, rope_theta=100.0, expert_tile=16, attention_block=16,
              select_tile=16, param_dtype=jnp.float32)
    model = build_keye("share", 96, (40,), experts_held=10, first_expert=10,
                       **kw)
    params, state = model.init(jax.random.PRNGKey(0))
    assert params["layers"][0]["ffn"]["experts"]["down"].shape[0] == 10
    _, new_state = model.apply(params, state, _ids(half))
    aux = new_state["aux"]
    assert int(aux["expert_tokens"].sum()) + int(aux["expert_absent"].sum()) \
        == 4 * 40 * 2
    assert int(aux["expert_absent"].sum()) > 0


# ---- the shared code moved no other plan -----------------------------------------

# The seventh plan, which tests/test_scorer.py's and tests/test_trinity.py's
# digests leave out: the first 16 hex digits of the sha256 of the lowered
# text, of the tree ``init`` makes and, for the toy, of its leaves from key 7,
# as the parent of PR 59 built them. (The other six plans' lines are in those
# two files, and this PR leaves them as they were. PR 60 changed the text of
# these four lines: both plans hold their whole router, so the combine's
# block is 64 tokens or fewer, one tile each, written once; trees and leaves
# are the parent's. PR 65 changed the text of the two served sizes' lines:
# their tiles of 1,024 rows gather their tokens 512 rows at a time,
# ``parallel/moe.py _GATHER_ROWS``; the tile is what their presets named.
# PR 69 changed all six digests by one count: the layer counts its combine's
# tiles, written and added (``combine_tiles`` in ``aux``: a key more in the
# tree, its zeros among the leaves, and in the text the tiles the data made
# beside a constant 0); the combine's loop, ``once`` in both, is the
# parent's.)
TRINITY = {"trinity_tiny": ("83e0728be884687a", "e5e17429ee791e05",
                            "5f9ea72a2d64f4f5"),
           "trinity_mini": ("77a7ab84498b1515", "e9bb8a91e9bb0eb3")}
KEYE = {"keye_tiny": ("95027fe8934ffd9d", "35cd9f06a0347f12",
                      "5faea694f2c58576"),
        "keye_vl2_30b": ("25c7bd8a54eeb8e4", "c597836176482a71")}


def _digest(*chunks):
    h = hashlib.sha256()
    for chunk in chunks:
        h.update(chunk)
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(TRINITY) + sorted(KEYE))
def test_the_seventh_plan_lowers_to_the_parents_text_and_the_eighth_to_its_own(
        name):
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
    assert got == {**TRINITY, **KEYE}[name]


def test_minicpm_salas_second_pass_lowers_as_the_parent_built_it():
    """The kernel's call with a mask a key head, as ``minicpm_sala`` makes
    it: the index map of the mask is the parent's (``(h, qi, 0)``), so the
    lowered text is the parent's to the letter."""
    spec_of = lambda *dims, t=jnp.float32: jax.ShapeDtypeStruct(dims, t)  # noqa
    q, k, v = spec_of(1, 8, 1024, 128), spec_of(1, 2, 1024, 128), \
        spec_of(1, 2, 1024, 128)
    text = jax.jit(lambda q, k, v, m: sa._kernel_row(
        q, k, v, m, 0.25, 0, interpret=True)).lower(
        q, k, v, spec_of(2, 1024, 1024, t=jnp.bool_)).as_text()
    assert _digest(text.encode()) == PARENT_KERNEL_ROW


PARENT_KERNEL_ROW = "2fa6c52378cef5bc"
