"""MiniCPM-SALA through the program's model code (PR 45): the program against
the benchmark's plain reference in float32 in both forms of the ``minicpm4``
mixer, the selection against a brute-force reading of its equations, the
lightning layer through the shared chunked scan against the token-by-token
recurrence, muP's three scalings, the device counters and the load."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.core import spec  # noqa: E402
from storm_tpu.models import minicpm_sala as program  # noqa: E402
from storm_tpu.models.registry import build_model  # noqa: E402
from storm_tpu.ops import sparse_attention as sa  # noqa: E402
from storm_tpu.ops.platform import dispatch_notes  # noqa: E402

REFERENCE = spec.plugin("references", "minicpm_sala")
TINY = spec.config("minicpm_sala_tiny")
SIZES = TINY["published"]
SPARSE = SIZES["held"]["sparse"]
SELECTION = {k: v for k, v in SPARSE.items() if k != "dense_len"}


def _distance(got, want):
    """Euclidean distance of each row from its reference row over that row's
    length: the benchmark's measure (``core/pairing.py``)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))


def _windows(n, length, seed=0):
    return np.random.RandomState(seed).randint(
        0, 96, (n, length)).astype(np.float32)


def _both(length, dtype=jnp.float32, seed=1, rows=3):
    """The program's probabilities and the reference's for ``rows`` windows
    of ``length`` ids, the parameters drawn in ``dtype``."""
    model = program.build_minicpm_sala_tiny(input_shape=(length,),
                                            param_dtype=dtype)
    params, state = model.init(jax.random.PRNGKey(seed))
    x = _windows(rows, length, seed)
    with dispatch_notes() as forms:
        logits, new_state = jax.jit(model.apply)(params, state, x)
    got = jax.nn.softmax(logits.astype(jnp.float32), -1)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, s, xx: REFERENCE.forward(
            SIZES, p, s, xx))(params, state, x)
    return got, want, forms, new_state


# ---- the program against the reference ----------------------------------------

@pytest.mark.parametrize("length,form", [
    (24, "causal_attention=blocked-grouped"),  # a window under dense_len
    (96, "sparse_attention=blocked"),  # 12 blocks of 8, 6 picked a query
])
def test_program_is_the_reference_in_float32_in_both_forms(length, form):
    with jax.default_matmul_precision("highest"):
        got, want, forms, _ = _both(length)
    assert form in forms and "ssd_scan=chunked" in forms
    assert ("sparse_attention=blocked" in forms) == (length > 32)
    assert got.shape == (3, 96) and bool(jnp.isfinite(got).all())
    assert _distance(got, want).max() < 1e-5


def test_bfloat16_path_on_its_own_terms():
    """Parameters and branches in bfloat16, the stream float32, the
    selection's scores float32: rounding moves a row by a few thousandths of
    its length, far under the distance between two windows' rows."""
    got, want, forms, _ = _both(96, jnp.bfloat16, rows=6)
    assert "sparse_attention=blocked" in forms
    far = _distance(got, want)
    apart = min(_distance(want[i], want[j])
                for i in range(6) for j in range(6) if i != j)
    assert far.max() < 0.03 and apart > 5 * far.max()


def test_with_topk_of_every_block_the_sparse_form_is_the_dense_one():
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (2, h, 96, 16))
               for i, h in enumerate((4, 2, 2)))
    everything = dict(SELECTION, topk=12)
    dense, read, skipped = sa.block_sparse_attention(
        q, k, v, 0.25, dense_len=96, **everything)
    sparse, read2, skipped2 = sa.block_sparse_attention(
        q, k, v, 0.25, dense_len=32, **everything)
    np.testing.assert_allclose(sparse, dense, atol=2e-6)
    assert int(read) == int(read2) == 2 * 2 * 96 * 97 // 2
    assert int(skipped) == int(skipped2) == 0
    fewer, _, left = sa.block_sparse_attention(q, k, v, 0.25, dense_len=32,
                                               **SELECTION)
    assert float(jnp.abs(fewer - dense).max()) > 1e-3 and int(left) > 0


def test_a_later_token_changes_no_earlier_result():
    """Both mixers are causal: the selection of a query reads pooled keys
    whose whole window lies at or before it."""
    model = build_model("minicpm_sala_tiny")
    params, _ = model.init(jax.random.PRNGKey(2))
    u = jax.random.normal(jax.random.PRNGKey(3), (1, 96, 64))
    later = u.at[:, 70:].set(jax.random.normal(jax.random.PRNGKey(4),
                                               (1, 26, 64)))
    rotary = program.R.rotary_tables(96, 100.0 ** (-np.arange(8) / 8))
    sparse = lambda x: program.minicpm4_mixer(  # noqa: E731
        params["layers"][0]["mixer"], x, 4, 2, 16, 1e-6, SPARSE)[0]
    lightning = lambda x: program.lightning_mixer(  # noqa: E731
        params["layers"][1]["mixer"], x, 4, 16, 1e-6, rotary,
        program.lightning_slopes(4, 1, 8), 16)
    for mixer in (sparse, lightning):
        a, b = mixer(u), mixer(later)
        np.testing.assert_array_equal(a[:, :70], b[:, :70])
        assert float(jnp.abs(a[:, 70:] - b[:, 70:]).max()) > 1e-3


# ---- the selection --------------------------------------------------------------

def _picked_by_hand(q, k, kernel_size, kernel_stride, block_size, topk,
                    init_blocks, window_size):
    """``J(g, t)`` as sets, the equations read one query at a time in
    numpy float64: ``q (H, S, d)``, ``k (G, S, d)``."""
    heads, s, d = q.shape
    groups = k.shape[0]
    q, k = np.asarray(q, np.float64), np.asarray(k, np.float64)
    n_pool = (s - kernel_size) // kernel_stride + 1
    pooled = np.stack([k[:, kernel_stride * i:kernel_stride * i + kernel_size]
                       .mean(1) for i in range(n_pool)], 1)  # (G, n_pool, d)
    out = {}
    for g in range(groups):
        for t in range(s):
            seen = [i for i in range(n_pool)
                    if kernel_stride * i + kernel_size - 1 <= t]
            r = np.zeros(n_pool)
            for h in range(g * heads // groups, (g + 1) * heads // groups):
                if seen:
                    logits = pooled[g, seen] @ q[h, t] / math.sqrt(d)
                    e = np.exp(logits - logits.max())
                    r[seen] += e / e.sum()
            own, ratio = t // block_size, block_size // kernel_stride
            score = {}
            for j in range(own + 1):
                near = [i for i in range(ratio * j - 1, ratio * j + ratio)
                        if 0 <= i < n_pool]
                score[j] = max(r[i] for i in near)
                if j < init_blocks or own - window_size // block_size <= j:
                    score[j] = math.inf
            best = sorted(score, key=lambda j: (-score[j], j))[:topk]
            out[g, t] = set(best)
    return out


def test_selection_is_the_equations_read_one_query_at_a_time():
    q = jax.random.normal(jax.random.PRNGKey(5), (1, 4, 96, 16))
    k = jax.random.normal(jax.random.PRNGKey(6), (1, 2, 96, 16))
    with jax.default_matmul_precision("highest"):
        picked = np.asarray(sa.select_blocks(q, k, scale=0.25, tile=40,
                                             **SELECTION))[0]
        whole = np.asarray(sa.select_blocks(q, k, scale=0.25,
                                            **SELECTION))[0]
        plain = np.asarray(REFERENCE.picked_blocks(
            q[0].transpose(1, 0, 2), k[0].transpose(1, 0, 2), SPARSE))
    assert (picked == whole).all() and (picked == plain).all()
    want = _picked_by_hand(q[0], k[0], **SELECTION)
    for (g, t), blocks in want.items():
        assert set(np.flatnonzero(picked[g, t])) == blocks, (g, t)
        own = t // 8
        # fewer blocks than topk: all of them; else topk with the forced ones
        assert len(blocks) == min(6, own + 1)
        assert {0, own, max(own - 1, 0)} <= blocks
    # a pooled key is seen once its whole window of 4 is: position 2 sees
    # none, and picks its own block all the same
    assert picked[:, 2].sum() == 2 and picked[:, 2, 0].all()
    # past six blocks a query picks three by score: they differ by query
    free = {frozenset(b - {0, t // 8, t // 8 - 1})
            for (g, t), b in want.items() if t >= 64}
    assert len(free) > 10 and all(len(f) == 3 for f in free)


def test_the_pools_alignment_and_ties():
    """Block ``j`` reads pooled keys ``4j - 1 .. 4j + 3``. One key far larger
    than the rest, aligned with every query: the pooled windows that hold it
    take all the probability, and the blocks whose five windows touch them
    score highest. Equal scores go to the lower index."""
    s, d = 96, 16
    k = np.full((1, 1, s, d), 0.0, np.float32)
    k[0, 0, 41, 0] = 100.0  # in pooled windows i = 19 (38-41) and 20 (40-43)
    q = np.zeros((1, 2, s, d), np.float32)
    q[..., 0] = 8.0
    picked = np.asarray(sa.select_blocks(
        jnp.asarray(q), jnp.asarray(k), scale=0.25,
        **dict(SELECTION, topk=5)))[0, 0]
    # i = 19, 20 lie in block 5's window (19..23) and in block 4's (15..19);
    # from position 43 both are visible: a query at 95 (own block 11, forced
    # 0, 10, 11) spends its two free picks on blocks 4 and 5
    assert set(np.flatnonzero(picked[95])) == {0, 4, 5, 10, 11}
    # a query at 79 (own block 9, forced 0, 8, 9): 4 and 5 again
    assert set(np.flatnonzero(picked[79])) == {0, 4, 5, 8, 9}
    # all keys alike: every visible pooled key scores alike, ties go low
    flat = np.asarray(sa.select_blocks(
        jnp.ones((1, 2, s, d)), jnp.ones((1, 1, s, d)), scale=0.25,
        **dict(SELECTION, topk=5)))[0, 0]
    assert set(np.flatnonzero(flat[95])) == {0, 1, 2, 10, 11}


def test_keys_read_and_skipped_against_counts_by_hand():
    _, _, _, state = _both(96, rows=2)
    read = np.asarray(state["aux"]["sparse_keys_read"])
    skipped = np.asarray(state["aux"]["sparse_keys_skipped"])
    # 2 rows x 2 groups; a query at t reads its own block up to itself and 8
    # keys of each of its other picked blocks: min(t // 8, 5) of them
    by_hand = 4 * sum(t % 8 + 1 + 8 * min(t // 8, 5) for t in range(96))
    assert read.tolist() == [by_hand, by_hand] == [13248, 13248]
    assert (read + skipped).tolist() == [4 * 96 * 97 // 2] * 2
    _, _, _, dense = _both(24, rows=2)
    assert np.asarray(dense["aux"]["sparse_keys_read"]).tolist() == \
        [4 * 24 * 25 // 2] * 2
    assert not np.asarray(dense["aux"]["sparse_keys_skipped"]).any()
    picked = jnp.zeros((2, 16, 2), bool).at[:, :, 0].set(True)
    got = sa.keys_read(picked, 8)  # block 0 alone: 1..8 keys, then 8
    assert int(got[0]) == 2 * (36 + 8 * 8) and int(got[1]) == 2 * 36


def test_the_queue_counts_the_keys_in_the_registry():
    """Through the model's own reader, which is all the queue calls
    (tests/test_kimi_linear.py goes through the queue itself)."""
    from storm_tpu.runtime.metrics import MetricsRegistry

    registry = MetricsRegistry()
    observe = build_model("minicpm_sala_tiny").observe_aux
    for _ in range(2):
        observe(registry, "inference-bolt", {
            "sparse_keys_read": np.asarray([2_000_000_000], np.int32),
            "sparse_keys_skipped": np.asarray([100, 23], np.int32)})
    got = registry.snapshot()["inference-bolt"]
    assert got["sparse_keys_read"] == 4_000_000_000  # past int32: host sums
    assert got["sparse_keys_skipped"] == 246
    assert "expert_assignments_held" not in got


# ---- the mask kernel under the interpreter ---------------------------------------

def test_mask_kernel_is_the_blocked_form_under_the_interpreter():
    """The Pallas form at the tiles ``causal_tiles`` gives two heads a group
    (256 positions against blocks of 512 keys), row 1 of a batch of two read
    where it lies, against XLA's form on the same selection."""
    b, hq, hkv, s, d = 2, 4, 2, 1024, 128
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (b, h, s, d),
                                 jnp.float32).astype(jnp.bfloat16)
               for i, h in enumerate((hq, hkv, hkv)))
    selection = dict(kernel_size=32, kernel_stride=16, block_size=64, topk=6,
                     init_blocks=1, window_size=128)
    picked = sa.select_blocks(q, k, scale=d ** -0.5, **selection)
    assert int(picked[1, 0, -1].sum()) == 6
    got = sa._kernel_row(q, k, v, sa._wanted(picked[1], 64), d ** -0.5, 1,
                         interpret=True)
    want = sa._blocked_row(
        q[1], k[1], v[1], lambda lo, hi: sa._wanted(picked[1], 64, lo, hi),
        d ** -0.5, 512)
    assert got.shape == want.shape == (hq, s, d)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)
    # and the rule: off a TPU the blocked form, whatever the shapes
    assert sa.sparse_form(32, 2, 16384, 128, 128, 64) == "blocked"


# ---- the lightning layer ----------------------------------------------------------

def test_lightning_layer_through_the_shared_scan_is_the_recurrence():
    """``ssd_chunked`` with ``x = v``, ``dt = 1``, ``a = -slopes``, ``b = k``,
    ``c = q / sqrt(d)``, ``d = 0`` and a group a head, against the reference's
    token-by-token state, over 96 tokens in chunks of 16."""
    model = build_model("minicpm_sala_tiny")
    params, _ = model.init(jax.random.PRNGKey(7))
    leaves = params["layers"][2]["mixer"]
    u = jax.random.normal(jax.random.PRNGKey(8), (2, 96, 64))
    rotary = program.R.rotary_tables(96, 100.0 ** (-np.arange(8) / 8))
    with jax.default_matmul_precision("highest"):
        got = program.lightning_mixer(
            leaves, u, 4, 16, 1e-6, rotary,
            program.lightning_slopes(4, 2, 8), chunk=16)
        want = jnp.stack([REFERENCE._lightning(leaves, row, SIZES, 1e-6, 2)
                          for row in u])
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(
        jnp.abs(want).max())
    # the decay: Lightning Attention-2's slopes times the layer's factor
    slopes = program.lightning_slopes(32, 3, 32)
    assert slopes[0] == pytest.approx(2 ** -0.25 * (1 - 3 / 31 + 1e-5))
    assert slopes[-1] == pytest.approx(2 ** -8 * (1 - 3 / 31 + 1e-5))
    np.testing.assert_allclose(np.exp(-program.lightning_slopes(4, 2, 8)),
                               REFERENCE.decay(SIZES, 2), rtol=1e-12)


# ---- muP, the load ------------------------------------------------------------------

def test_mups_three_scalings_against_hand_values():
    """With every branch's output projection zeroed the stream is ``scale_emb
    E[id]`` and the logits are ``RMSNorm(12 E) W_head / (hidden /
    dim_model_base)``; with one branch back, its share is ``scale_depth /
    sqrt(published layers)`` of what it computes."""
    model = build_model("minicpm_sala_tiny", input_shape=(24,))
    params, state = model.init(jax.random.PRNGKey(9))
    zeroed = jax.tree.map(lambda a: a, params)
    for blk in zeroed["layers"]:
        blk["mixer"]["o"] = jnp.zeros_like(blk["mixer"]["o"])
        blk["ffn"]["down"] = jnp.zeros_like(blk["ffn"]["down"])
    x = _windows(2, 24)
    with jax.default_matmul_precision("highest"):
        logits, _ = model.apply(zeroed, state, x)
        e = 12.0 * np.asarray(params["embed"])[x[:, -1].astype(int)]
        normed = e / np.sqrt((e ** 2).mean(-1, keepdims=True) + 1e-6)
        by_hand = normed / (64 / 16) @ np.asarray(params["head"])
        np.testing.assert_allclose(logits, by_hand, rtol=2e-5, atol=2e-6)
        # the last layer's feed-forward alone, with a unit down projection
        # onto channel 0: the stream gains c * sum(silu(gate) * up)
        zeroed["layers"][-1]["ffn"]["down"] = jnp.zeros((128, 64)).at[
            :, 0].set(1.0)
        zeroed["head"] = jnp.eye(64, 96)
        zeroed["norm"] = {"scale": jnp.ones((64,))}
        got, _ = model.apply(zeroed, state, x)
    ffn = params["layers"][-1]["ffn"]
    h = normed * np.asarray(params["layers"][-1]["norm2"]["scale"])
    branch = (np.asarray(jax.nn.silu(h @ np.asarray(ffn["gate"])))
              * (h @ np.asarray(ffn["up"]))).sum(-1)
    stream = e.copy()
    stream[:, 0] += 1.4 / math.sqrt(8) * branch
    want = stream / np.sqrt((stream ** 2).mean(-1, keepdims=True) + 1e-6) / 4
    np.testing.assert_allclose(got[:, :64], want, rtol=2e-4, atol=2e-5)
    assert 1.4 / math.sqrt(32) == pytest.approx(0.2475, abs=1e-4)


def test_the_initialisers_leaves_are_in_the_served_type():
    """The load of ``models/kimi_k2.py``: every leaf in bfloat16 as it is
    made, the values those ``astype`` of the float32 draw gives; the full
    model's tree by ``eval_shape`` is the issue's count."""
    served = program.build_minicpm_sala_tiny(param_dtype=jnp.bfloat16)
    plain = build_model("minicpm_sala_tiny")
    a, _ = served.init(jax.random.PRNGKey(11))
    b, _ = plain.init(jax.random.PRNGKey(11))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.dtype == jnp.bfloat16 and y.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(x, np.float32), np.asarray(
            y.astype(jnp.bfloat16), np.float32))
    # where the draw starts: the stream at one a channel, the logits at one
    assert float(jnp.std(b["embed"])) == pytest.approx(1 / 12, rel=0.05)
    assert float(jnp.std(b["head"])) == pytest.approx(4 / 8, rel=0.05)
    full = build_model("minicpm_sala")
    params, state = jax.eval_shape(full.init, jax.random.PRNGKey(0))
    assert {x.dtype for x in jax.tree.leaves(params)} == {jnp.dtype(
        jnp.bfloat16)}
    assert sum(x.size for x in jax.tree.leaves(params)) == 1_711_129_600
    assert state["aux"]["sparse_keys_read"].shape == (1,)
    assert (full.max_rows, full.input_dtype, full.input_shape,
            full.num_classes) == (4, "float32", (16384,), 73448)
    assert full.hyper["mixers"] == ("minicpm4",) + ("lightning-attn",) * 3
    with pytest.raises(ValueError):
        program.build_minicpm_sala(
            "x", 8, (8,), mixers=("mamba",), published_layers=1, dim=8,
            ffn_width=8, heads=2, kv_heads=1, head_dim=4, lightning_heads=2,
            lightning_head_dim=4, sparse=SPARSE)
