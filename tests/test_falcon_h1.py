"""Falcon-H1 at toy widths on the CPU (hidden 40; three parallel blocks; 4
Mamba-2 heads of 8 on a state of 12 in **two** B/C groups, a window of 40 in
chunks of 16; 10 query heads on 2 key heads of 8, **five** a key head,
rotary at a base of 100; a SwiGLU of 72; untied embeddings; all fourteen
scalars off 1 and unequal): what this plan asks of the shared code that no
other plan does, each against its plain form, and the model through
``InferenceEngine`` against the benchmark's reference
(``benchmarks/references/falcon_h1.py``, float32 at ``highest``) on seeded
weights. Probabilities over the whole vocabulary are compared, never an
argmax: with random weights the largest logit changes on rounding."""

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
from storm_tpu.models import falcon_h1 as FH  # noqa: E402
from storm_tpu.models import scorer as S  # noqa: E402
from storm_tpu.models.nemotron_h import (mamba_mixer,  # noqa: E402
                                         mamba_mixer_init)
from storm_tpu.models.registry import build_model, load_or_init  # noqa: E402
from storm_tpu.ops import flash_attention as F  # noqa: E402
from storm_tpu.ops import layers as L  # noqa: E402
from storm_tpu.ops import rope as R  # noqa: E402
from storm_tpu.ops import ssd  # noqa: E402
from storm_tpu.ops.attention import (attention_reference,  # noqa: E402
                                     causal_form)
from storm_tpu.ops.parity_checks import ssd_recurrence  # noqa: E402
from storm_tpu.ops.platform import dispatch_notes  # noqa: E402

REFERENCE = spec.plugin("references", "falcon_h1")
OPS = spec.plugin("ops", "falcon_h1")
TINY = spec.config("falcon_h1_tiny")
SIZES = TINY["published"]
DIM, EPS, SEQ = 40, 1e-5, 40
SCALARS = ("embedding_multiplier", "lm_head_multiplier",
           "attention_in_multiplier", "attention_out_multiplier",
           "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier")
M = FH.Mixers(
    heads=SIZES["num_attention_heads"],
    kv_heads=SIZES["num_key_value_heads"], head_dim=SIZES["head_dim"],
    mamba_heads=SIZES["mamba_n_heads"], mamba_head_dim=SIZES["mamba_d_head"],
    groups=SIZES["mamba_n_groups"], state=SIZES["mamba_d_state"],
    conv=SIZES["mamba_d_conv"],
    attention_in=SIZES["attention_in_multiplier"],
    attention_out=SIZES["attention_out_multiplier"],
    key=SIZES["key_multiplier"], ssm_in=SIZES["ssm_in_multiplier"],
    ssm_out=SIZES["ssm_out_multiplier"],
    ssm=tuple(SIZES["ssm_multipliers"]), chunk=16, attention_block=16,
    eps=EPS)


def _distance(got, want):
    """Euclidean distance of each row from its reference row over that row's
    length: the benchmark's measure (``core/pairing.py``)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))


def _close(got, want, rel=1e-5):
    np.testing.assert_allclose(got, want, atol=rel * float(
        jnp.abs(want).max()))


def _tables(seq=SEQ, theta=None, dim=None):
    dim = dim or SIZES["head_dim"]
    theta = float(theta or SIZES["rope_theta"])
    return R.rotary_tables(seq, theta ** (-2.0 * np.arange(dim // 2) / dim))


def _mixers(seed=2):
    p = FH.parallel_mixer_init(jax.random.PRNGKey(seed), DIM, M, 2.0)
    p["mamba"]["norm"]["scale"] = 1.0 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), (32,))
    return p


def _reference_mixers(p, x, sizes=SIZES):
    """``(Mamba-2's, attention's)`` results a row, every scalar where the
    released code puts it."""
    return (jnp.stack([sizes["ssm_out_multiplier"] * REFERENCE._mamba(
        p["mamba"], sizes["ssm_in_multiplier"] * row, sizes, EPS)
        for row in x]),
        jnp.stack([sizes["attention_out_multiplier"] * REFERENCE._attention(
            p["attention"], sizes["attention_in_multiplier"] * row, sizes)
            for row in x]))


# ---- the toy is the case the issue asks for ------------------------------------

def test_the_toy_has_every_trait_the_published_model_adds():
    assert SIZES["num_attention_heads"] // SIZES["num_key_value_heads"] == 5
    assert SIZES["mamba_n_groups"] == 2
    assert SIZES["mamba_d_head"] != SIZES["mamba_d_state"]
    assert SEQ % 16 and SIZES["held"]["ssd_chunk"] == 16
    fourteen = [SIZES[k] for k in SCALARS] + SIZES["ssm_multipliers"] \
        + SIZES["mlp_multipliers"]
    assert len(fourteen) == 14 == len(set(fourteen)) and 1 not in fourteen
    model = build_model("falcon_h1_tiny")
    assert all(model.hyper[k] == SIZES[k] for k in SCALARS)
    assert list(model.hyper["ssm_multipliers"]) == SIZES["ssm_multipliers"]
    assert list(model.hyper["mlp_multipliers"]) == SIZES["mlp_multipliers"]


# ---- the scan on two groups, heads and state of unequal widths -----------------

@pytest.mark.parametrize("step", [1e-4, 0.05, 10.0],
                         ids=["decay-near-1", "a-few-tokens", "decay-near-0"])
@pytest.mark.parametrize("chunk", [8, 16, 40])
def test_scan_at_two_groups_is_the_recurrence(chunk, step):
    """4 heads of 8 on a state of 12 in two groups over 40 tokens, ``x | B |
    C`` side by side as the mixer's convolution writes them: five chunks of
    8, two and a half of 16 (the tail padded), one of 40. Heads 0-1 read
    group 0, heads 2-3 group 1."""
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    shape = (3, SEQ)
    x = jax.random.normal(ks[0], shape + (4, 8))
    dt = step * jax.nn.softplus(jax.random.normal(ks[1], shape + (4,)))
    a = -jax.random.uniform(ks[2], (4,), minval=1.0, maxval=16.0)
    b, c = (jax.random.normal(k, shape + (2, 12)) for k in ks[3:5])
    d = jax.random.normal(ks[5], (4,))
    held = jnp.concatenate([y.reshape(shape + (-1,)) for y in (x, b, c)], -1)
    with jax.default_matmul_precision("highest"), dispatch_notes() as seen:
        got = ssd.ssd_chunked_columns(held, dt, a, d, 2, 12, chunk=chunk)
        want = ssd_recurrence(x, dt, a, b, c, d)
        # the groups are read: both heads of a group on the other's B and C
        # are another answer
        other = ssd_recurrence(x, dt, a, b[:, :, ::-1], c[:, :, ::-1], d)
    assert seen == ["ssd_scan=chunked"]
    _close(got.reshape(x.shape), want)
    assert float(jnp.abs(other - want).max()) > 1e-3 * float(
        jnp.abs(want).max())


def test_the_cells_step_holds_exactly_the_state_fast_memory_keeps(
        monkeypatch):
    """The published mixer (32 heads of 128 on a state of 256) at the
    cell's 4 rows a step holds 16 MiB of float32 state, exactly what the
    compiler keeps in fast memory: the loop over chunks, on a chip too (two
    groups and 32 heads are not the kernel's either way)."""
    wide = spec.config("falcon_h1_34b")["published"]
    shape = (wide["mamba_n_heads"], wide["mamba_d_head"],
             wide["mamba_d_state"], wide["mamba_n_groups"])
    assert shape == (32, 128, 256, 2)
    assert wide["held"]["rows_per_step"] == 4
    assert 4 * 4 * 32 * 128 * 256 == ssd._STATE_KEPT_BYTES
    step = dict(rows=4, seq=16384, heads=32, head_dim=128, groups=2,
                state=256, chunk=128)
    assert ssd.scan_form(**step) == "chunked"
    monkeypatch.setattr(ssd, "_use_pallas", lambda: True)
    monkeypatch.setattr(ssd, "_one_device", lambda: True)
    assert ssd.scan_form(**step) == "chunked"
    assert ssd.scan_form(**{**step, "rows": 8}) == "chunked"


# ---- the five segments' scalars ------------------------------------------------

def test_the_segments_scalars_are_the_same_scalars_folded_into_the_columns():
    """``mamba_mixer`` with ``scales`` against the same mixer without them on
    a projection whose columns carry them: ``(W_in u) * mup = (W_in diag(mup))
    u``. And against the reference, which multiplies the projection's result
    as the released code does."""
    p = mamba_mixer_init(jax.random.PRNGKey(2), DIM, 4, 8, 2, 12, 4)
    assert p["in_proj"].shape == (DIM, 32 + 32 + 24 + 24 + 4)
    assert p["conv"]["w"].shape == (4, 80) and p["conv"]["b"].shape == (80,)
    p["norm"]["scale"] = 1.0 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(3), (32,))
    scales = (0.8, 0.7, 0.65, 0.9, 0.85)
    folded = dict(p, in_proj=p["in_proj"] * np.repeat(
        scales, [32, 32, 24, 24, 4]).astype(np.float32))
    x = jax.random.normal(jax.random.PRNGKey(4), (3, SEQ, DIM))
    with jax.default_matmul_precision("highest"):
        got = mamba_mixer(p, x, 4, 8, 2, 12, 16, EPS, scales=scales)
        want = mamba_mixer(folded, x, 4, 8, 2, 12, 16, EPS)
        bare = mamba_mixer(p, x, 4, 8, 2, 12, 16, EPS)
        same = mamba_mixer(p, x, 4, 8, 2, 12, 16, EPS, scales=None)
        plain = jnp.stack([REFERENCE._mamba(
            p, row, {**SIZES, "ssm_multipliers": list(scales)}, EPS)
            for row in x])
    _close(got, want)
    _close(got, plain)
    assert np.array_equal(np.asarray(bare), np.asarray(same))
    assert float(jnp.abs(got - bare).max()) > 1e-2 * float(
        jnp.abs(want).max())
    # each of the five is read: another value is another answer
    for i in range(5):
        moved = tuple(1.5 * s if j == i else s for j, s in enumerate(scales))
        with jax.default_matmul_precision("highest"):
            other = mamba_mixer(p, x, 4, 8, 2, 12, 16, EPS, scales=moved)
        assert float(jnp.abs(other - got).max()) > 1e-4 * float(
            jnp.abs(got).max()), i


# ---- rotary at the published base ----------------------------------------------

def test_rotary_at_1e11_is_rotate_halves_by_hand_and_reads_positions():
    """The tables at the published base over a head of 128 channels, float32
    from a float64 ``inv_freq``: the turn of merged heads against the halves
    turned by hand; the slowest pair turns by ``t * 1e11^(-126/128)``."""
    seq, heads, hd = 64, 3, 128
    cos, sin = _tables(seq, 1e11, hd)
    assert cos.shape == sin.shape == (seq, hd // 2)
    assert cos.dtype == jnp.float32
    inv = 1e11 ** (-2.0 * np.arange(hd // 2) / hd)
    np.testing.assert_allclose(
        cos, np.cos(np.arange(seq)[:, None] * inv[None, :]), atol=1e-5)
    assert inv[-1] == pytest.approx(1e11 ** (-126 / 128))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, seq, heads * hd))
    with dispatch_notes() as seen:
        (got,) = R.turn_merged((x,), cos, sin, heads)
    assert seen == ["rotary_turn=halves"]
    xs = np.asarray(x, np.float64).reshape(2, seq, heads, hd)
    a, b = xs[..., :hd // 2], xs[..., hd // 2:]
    c, s = (np.asarray(t, np.float64)[None, :, None, :] for t in (cos, sin))
    want = np.concatenate([a * c - b * s, b * c + a * s], -1)
    np.testing.assert_allclose(got.reshape(xs.shape), want, atol=1e-5)
    # the reference's rotate_half on the same tables is the same turn
    plain = REFERENCE._rotate(
        x[0].reshape(seq, heads, hd), jnp.concatenate([cos, cos], -1),
        jnp.concatenate([sin, sin], -1))
    np.testing.assert_allclose(plain, want[0], atol=1e-5)


def test_a_keys_position_changes_the_answer():
    """The same keys and values at other positions are another answer: the
    scores depend on the distance of positions (no other plan's full
    attention turns its keys)."""
    p = _mixers()["attention"]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, SEQ, DIM))
    scale = M.key * M.attention_in ** 2 * M.head_dim ** -0.5
    with jax.default_matmul_precision("highest"):
        got = FH.rotary_gqa(p, x, M.heads, M.kv_heads, _tables(), scale, 16)
        cos, sin = _tables()
        still = FH.rotary_gqa(p, x, M.heads, M.kv_heads,
                              (jnp.ones_like(cos), jnp.zeros_like(sin)),
                              scale, 16)
        far = FH.rotary_gqa(p, x, M.heads, M.kv_heads, _tables(theta=3.0),
                            scale, 16)
    top = float(jnp.abs(got).max())
    assert float(jnp.abs(got - still).max()) > 1e-2 * top
    assert float(jnp.abs(got - far).max()) > 1e-3 * top
    # the first position reads itself alone: no turn moves its answer
    _close(got[:, 0], still[:, 0])


# ---- the parallel block ---------------------------------------------------------

def test_the_block_is_the_sum_of_the_two_mixers_on_one_norm():
    """``parallel_mixer`` against the two mixers run apart on the same normed
    input and added, each against the reference's; and unequal to running
    them one after the other."""
    p = _mixers()
    x = jax.random.normal(jax.random.PRNGKey(7), (3, SEQ, DIM))
    with jax.default_matmul_precision("highest"):
        got = FH.parallel_mixer(p, x, _tables(), M)
        ssm = M.ssm_out * mamba_mixer(
            p["mamba"], x, 4, 8, 2, 12, 16, EPS,
            scales=tuple(M.ssm_in * s for s in M.ssm))
        attn = M.attention_out * M.attention_in * FH.rotary_gqa(
            p["attention"], x, M.heads, M.kv_heads, _tables(),
            M.key * M.attention_in ** 2 * M.head_dim ** -0.5, 16)
        want_ssm, want_attn = _reference_mixers(p, x)
        # in sequence: attention reads what Mamba-2 added
        after = ssm + _reference_mixers(p, x + ssm)[1]
    assert got.dtype == jnp.float32
    _close(ssm, want_ssm)
    _close(attn, want_attn)
    _close(got, want_ssm + want_attn)
    top = float(jnp.abs(got).max())
    assert float(jnp.abs(got - after).max()) > 1e-2 * top
    # neither mixer is small beside the other
    assert 0.1 < float(jnp.abs(ssm).mean() / jnp.abs(attn).mean()) < 10


@pytest.mark.parametrize("key", ["attention_in_multiplier", "key_multiplier",
                                 "attention_out_multiplier",
                                 "ssm_in_multiplier", "ssm_out_multiplier"])
def test_a_folded_scalar_is_the_published_one(key):
    """The program carries ``key_multiplier`` and ``attention_in`` squared in
    the scores' scale, ``attention_in`` once more beside ``attention_out``
    and ``ssm_in`` on the five segments' own; the reference multiplies
    where the released code does. Another value moves both alike."""
    other = {**SIZES, key: 1.7 * SIZES[key]}
    names = {"attention_in_multiplier": "attention_in",
             "key_multiplier": "key",
             "attention_out_multiplier": "attention_out",
             "ssm_in_multiplier": "ssm_in", "ssm_out_multiplier": "ssm_out"}
    m = M._replace(**{names[key]: other[key]})
    p = _mixers()
    x = jax.random.normal(jax.random.PRNGKey(8), (2, SEQ, DIM))
    with jax.default_matmul_precision("highest"):
        got = FH.parallel_mixer(p, x, _tables(), m)
        want = sum(_reference_mixers(p, x, other))
        before = FH.parallel_mixer(p, x, _tables(), M)
    _close(got, want)
    assert float(jnp.abs(got - before).max()) > 1e-3 * float(
        jnp.abs(want).max())


def test_the_feed_forwards_two_scalars():
    p = L.swiglu_init(jax.random.PRNGKey(9), DIM, 72)
    x = jax.random.normal(jax.random.PRNGKey(10), (SEQ, DIM))
    gate, down = SIZES["mlp_multipliers"]
    with jax.default_matmul_precision("highest"):
        got = down * FH.gated_ffn(p, x, gate)
        want = REFERENCE._feed_forward(p, x, SIZES)
        by_hand = down * ((x @ p["up"]) * jax.nn.silu(
            gate * (x @ p["gate"]))) @ p["down"]
        plain = L.swiglu(p, x)
    _close(got, want)
    _close(got, by_hand)
    assert float(jnp.abs(got - plain).max()) > 1e-2 * float(
        jnp.abs(plain).max())
    # the gate's scalar is inside the activation: not a factor of the result
    ratio = np.asarray(got / plain)
    assert ratio.std() > 1e-2


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_product_made_once_is_the_same_feed_forward_to_the_bit(dtype):
    """``gated_ffn`` holds its rounded product behind one barrier, so that a
    chip's compiler makes it on the way out of a product
    (tests/test_tpu_compile.py pins where): the same float32 scalar,
    activation and product from the same two rounded results, rounded once,
    so here, where no product carries the activation, every bit of the
    formula without the barrier (a chip's product hands the activation
    float32 sums the compiler no longer rounds: PERF.md section 6, PR 67);
    and the engine's inventory can say the form engaged."""
    p = jax.tree.map(lambda a: a.astype(dtype),
                     L.swiglu_init(jax.random.PRNGKey(9), DIM, 72))
    x = jax.random.normal(jax.random.PRNGKey(10), (2, SEQ, DIM)).astype(dtype)
    gate = SIZES["mlp_multipliers"][0]

    def unbarriered(p, x):
        f32 = jnp.float32
        act = jax.nn.silu(L.matmul(x, p["gate"]).astype(f32) * gate)
        return L.matmul((act * L.matmul(x, p["up"]).astype(f32)).astype(
            x.dtype), p["down"])

    made_once = jax.jit(lambda p, x: FH.gated_ffn(p, x, gate))
    with dispatch_notes() as seen:
        got = made_once(p, x)
    want = jax.jit(unbarriered)(p, x)
    assert seen == ["gated_ffn=made-once"]
    assert got.dtype == want.dtype == dtype
    assert np.array_equal(np.asarray(got.astype(jnp.float32)),
                          np.asarray(want.astype(jnp.float32)))
    assert float(jnp.abs(got.astype(jnp.float32)).max()) > 0
    assert made_once.lower(p, x).as_text().count("optimization_barrier") == 1


def test_the_draw_stands_each_matrix_over_its_scalar():
    """Every projection's result is what a LeCun matrix gives without a
    scalar: unit deviation a channel for a unit input (the branch outputs
    over their factor besides), whatever the fourteen are."""
    p = FH.parallel_mixer_init(jax.random.PRNGKey(11), 512, M._replace(
        heads=8, head_dim=64, mamba_heads=8, mamba_head_dim=64, state=32),
        3.0)
    w = np.asarray(p["mamba"]["in_proj"])
    at = np.cumsum([0, 512, 512, 64, 64, 8])
    for i, scale in enumerate((0.8, 0.7, 0.65, 0.9, 0.85)):
        seg = w[:, at[i]:at[i + 1]]
        assert seg.std() * 512 ** 0.5 * 0.75 * scale == pytest.approx(
            1.0, rel=0.1), i
    att = p["attention"]
    assert float(att["q"].std()) * 512 ** 0.5 * 1.3 == pytest.approx(
        1.0, rel=0.05)
    assert float(att["k"].std()) * 512 ** 0.5 * 1.3 * 0.6 == pytest.approx(
        1.0, rel=0.05)
    assert float(att["o"].std()) * 512 ** 0.5 * 0.45 * 3.0 == pytest.approx(
        1.0, rel=0.05)
    assert float(p["mamba"]["out_proj"].std()) * 512 ** 0.5 * 0.55 * 3.0 \
        == pytest.approx(1.0, rel=0.05)


# ---- the causal kernel at a group of five --------------------------------------

def test_causal_tiles_are_whole_sublane_tiles_that_divide_a_key_block():
    # the five groups the benchmark's other plans run: as they were
    assert [F.causal_tiles(g) for g in (1, 2, 4, 8, 16)] == [
        (512, 512), (256, 512), (128, 512), (64, 512), (32, 512)]
    assert F.causal_tiles(32) == F.causal_tiles(64) == (16, 512)
    # a group that is no power of two: the power of two below its quotient
    assert F.causal_tiles(5) == (64, 512)
    assert [F.causal_tiles(g)[0] for g in (3, 6, 7, 12)] == [128, 64, 64, 32]
    for g in range(1, 65):
        tile, block = F.causal_tiles(g)
        assert tile >= 16 and tile & (tile - 1) == 0 and block % tile == 0
        assert g * tile <= max(512, 16 * g)


def test_a_group_of_five_takes_the_kernel_at_the_cells_window(monkeypatch):
    """``causal_form`` at the published heads and the cell's 16,384
    positions: the kernel on one chip (102 positions a tile divided no
    window, and XLA's blocked form wrote the scores to memory), the blocked
    form here."""
    from storm_tpu.ops import attention

    assert causal_form(20, 4, 16384, 128, 128) == "blocked"
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    monkeypatch.setattr(attention, "_one_device", lambda: True)
    assert causal_form(20, 4, 16384, 128, 128) == "kernel"
    assert attention.merged_form(20, 4, 16384, 128, 128) == "kernel"
    monkeypatch.setattr(F, "causal_tiles", lambda g: (512 // g, 512))
    assert causal_form(20, 4, 16384, 128, 128) == "blocked"


@pytest.mark.parametrize("tile", [64, 128])
@pytest.mark.parametrize("merged", [False, True], ids=["split", "merged"])
def test_the_causal_kernel_at_a_group_of_five(merged, tile):
    """The kernel under the interpreter at 10 query heads on 2 key heads of
    128 over 256 positions, tiles of 64 (320 stacked rows: what the rule
    gives) and of 128 (640), heads split and merged, against the plain
    softmax with each key head written out for its five query heads."""
    b, hq, hkv, s, d = 2, 10, 2, 256, 128
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d))
    k, v = (jax.random.normal(key, (b, hkv, s, d)) for key in ks[1:])
    with jax.default_matmul_precision("highest"):
        scores = jnp.einsum("bhsd,bhtd->bhst", q, jnp.repeat(k, 5, 1)) \
            * d ** -0.5
        later = jnp.arange(s)[None, :] > jnp.arange(s)[:, None]
        want = jnp.einsum("bhst,bhtd->bhsd", jax.nn.softmax(
            jnp.where(later, -jnp.inf, scores), -1), jnp.repeat(v, 5, 1))
        # the full form of the same heads agrees on the last query
        full = attention_reference(q, jnp.repeat(k, 5, 1),
                                   jnp.repeat(v, 5, 1))
        if merged:
            flat = [y.transpose(0, 2, 1, 3).reshape(b, s, -1)
                    for y in (q, k, v)]
            out = jnp.zeros((b, s, hq * d))
            for row in range(b):
                out = F.flash_attention_merged(
                    out, *flat, row, heads=hq, kv_heads=hkv, block_q=tile,
                    block_k=128, interpret=True)
            got = out.reshape(b, s, hq, d).transpose(0, 2, 1, 3)
        else:
            got = F.flash_attention(q, k, v, block_q=tile, block_k=128,
                                    causal=True, interpret=True)
    _close(got, want, rel=1e-4)
    _close(got[:, :, -1], full[:, :, -1], rel=1e-4)


# ---- the whole model through the engine ----------------------------------------

def _windows(n, seed=3):
    return spec.plugin("inputs", "falcon_h1_tokens").make(
        n, (SEQ,), seed).astype(np.float32)


def _engine(dtype="float32"):
    return InferenceEngine(ModelConfig(
        name="falcon_h1_tiny", dtype=dtype, num_classes=96,
        input_shape=(SEQ,), seed=5), batch_cfg=BatchConfig())


def test_model_through_the_engine_against_the_reference():
    model = build_model("falcon_h1_tiny")
    params, state = load_or_init(model, None, 5)
    assert state == {}  # nothing of the step depends on the data: no ``aux``
    x = _windows(16)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, s, xx: REFERENCE.forward(
            SIZES, p, s, xx))(params, state, x))
    eng = _engine()
    assert eng.batch_cfg.buckets == (4,) and eng.max_rows == 4
    assert eng.in_dtype == jnp.float32
    got = np.concatenate([eng.predict(x[a:a + 4]) for a in range(0, 16, 4)])
    assert got.shape == (16, 96)
    assert _distance(got, want).max() < 1e-4  # summation order: under 1e-6
    # no answer is its last id's row of the head and little else
    assert want.max() < 0.5
    # every one of the fourteen is read: another value is another answer
    for key in SCALARS:
        other = {**SIZES, key: 1.5 * SIZES[key]}
        with jax.default_matmul_precision("highest"):
            moved = np.asarray(REFERENCE.forward(other, params, state, x[:2]))
        assert _distance(moved, want[:2]).min() > 1e-4, key
    for key, n in (("ssm_multipliers", 5), ("mlp_multipliers", 2)):
        for i in range(n):
            values = list(SIZES[key])
            values[i] *= 1.5
            with jax.default_matmul_precision("highest"):
                moved = np.asarray(REFERENCE.forward(
                    {**SIZES, key: values}, params, state, x[:2]))
            assert _distance(moved, want[:2]).min() > 1e-5, (key, i)


def test_the_inventory_names_the_forms():
    from storm_tpu.config import ShardingConfig
    from storm_tpu.infer.engine import engine_inventory, shared_engine

    eng = shared_engine(ModelConfig(
        name="falcon_h1_tiny", dtype="float32", num_classes=96,
        input_shape=(SEQ,), seed=5), ShardingConfig(data_parallel=0),
        BatchConfig())
    eng.warmup()
    row = next(r for r in engine_inventory()["engines"]
               if r["model"] == "falcon_h1_tiny")
    forms = row["programs"][str(eng.pad_batch(4))].split(", ")
    assert set(forms) == {"short_conv=xla", "ssd_scan=chunked",
                          "rotary_turn=halves",
                          "causal_attention=blocked-grouped",
                          "gated_ffn=made-once"}
    handle = eng.dispatch((_windows(4),))
    handle.future.result(60)
    assert not handle.aux


def test_pinning_the_stream_changes_no_value():
    """``token_scorer``'s ``pin_stream`` is a barrier and no operation: the
    same plan with and without it gives the same bits, and without it the
    lowered text has no barrier (the nine plans before this one lower to
    their own text: ``tests/test_scorer.py``)."""
    def plan(pin):
        branch = S.Branch(
            "norm1", "mixer", lambda key: {"w": S._w(key, 16, 16)},
            lambda p, y, _: S._proj(y, p["w"]))
        return S.token_scorer("pinned", 24, (6,), ((branch,),) * 2, dim=16,
                              eps=EPS, hyper={}, max_rows=2, pin_stream=pin)

    x = np.random.default_rng(0).integers(0, 24, (2, 6)).astype(np.float32)
    params, state = plan(False).init(jax.random.PRNGKey(13))
    texts, outs = [], []
    for pin in (False, True):
        model = plan(pin)
        texts.append(jax.jit(model.apply).lower(params, state, x).as_text())
        outs.append(np.asarray(jax.jit(model.apply)(params, state, x)[0]))
    assert "optimization_barrier" not in texts[0]
    assert texts[1].count("optimization_barrier") == 2
    assert np.array_equal(*outs)


def test_registry_names_the_model_and_its_cut():
    model = build_model("falcon_h1_34b")
    assert model.input_shape == (16384,) and model.num_classes == 261120
    assert model.max_rows == 4
    assert (model.hyper["layers"], model.hyper["heads"],
            model.hyper["kv_heads"], model.hyper["mamba_heads"],
            model.hyper["mamba_head_dim"], model.hyper["groups"],
            model.hyper["state"], model.hyper["ffn_width"],
            model.hyper["rope_theta"]) == (
        4, 20, 4, 32, 128, 2, 256, 21504, 1e11)
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert set(params) == {"embed", "norm", "layers", "head"}
    assert state == {} and len(params["layers"]) == 4
    assert all(set(blk) == {"norm1", "mixer", "norm2", "ffn"}
               and set(blk["mixer"]) == {"mamba", "attention"}
               for blk in params["layers"])
    mamba, attn = (params["layers"][3]["mixer"][k]
                   for k in ("mamba", "attention"))
    ffn = params["layers"][3]["ffn"]
    assert mamba["in_proj"].shape == (5120, 9248)
    assert mamba["conv"]["w"].shape == (4, 5120)
    assert mamba["norm"]["scale"].shape == (4096,)
    assert mamba["out_proj"].shape == (4096, 5120)
    assert attn["q"].shape == attn["o"].shape[::-1] == (5120, 2560)
    assert attn["k"].shape == attn["v"].shape == (5120, 512)
    assert ffn["gate"].shape == ffn["up"].shape == (5120, 21504)
    assert params["embed"].shape == (261120, 5120)
    assert params["head"].shape == (5120, 261120)
    assert {leaf.dtype for leaf in jax.tree.leaves(params)} \
        == {jnp.dtype(jnp.bfloat16)}
    # the issue's table, a row at a time, and the benchmark's own count
    layer = sum(x.size for x in jax.tree.leaves(params["layers"][0]))
    assert layer == 430_120_032 == OPS.layer_parameters(
        spec.config("falcon_h1_34b")["published"])
    assert params["embed"].size == 1_336_934_400
    assert params["head"].size + params["norm"]["scale"].size \
        == 1_336_939_520
    total = sum(x.size for x in jax.tree.leaves(params))
    assert total == 4 * layer + 1_336_934_400 + 1_336_939_520 \
        == 4_394_354_048
    assert OPS.parameters(spec.config("falcon_h1_34b")["published"]) == total
    with pytest.raises(ValueError):
        FH.build_falcon_h1(
            "x", 8, (4,), layers=1, published_layers=2, dim=8, ffn_width=8,
            heads=1, kv_heads=1, head_dim=8, mamba_heads=1, mamba_head_dim=8,
            groups=1, state=8, conv=4, embedding_multiplier=1.0,
            lm_head_multiplier=1.0, attention_in_multiplier=1.0,
            attention_out_multiplier=1.0, key_multiplier=1.0,
            ssm_in_multiplier=1.0, ssm_out_multiplier=1.0,
            ssm_multipliers=(1.0,) * 4, mlp_multipliers=(1.0, 1.0))


# ---- the tenth plan's own lines ------------------------------------------------

# The nine plans that were there lower to their parents' text by
# tests/test_scorer.py, tests/test_trinity.py, tests/test_keye.py and
# tests/test_granite.py, whose lines this PR leaves as they were
# (``mamba_mixer``'s ``scales`` and ``token_scorer``'s ``pin_stream`` are read
# at trace time; ``causal_tiles`` is not reached off a chip). The tenth's, as
# this PR built it: the first 16 hex digits of the sha256 of the lowered
# text, of the tree ``init`` makes and, for the toy, of its leaves from key 7.
# PR 67 moved the two texts' digests and nothing else: ``gated_ffn``'s one
# ``optimization_barrier`` is an operation of the lowered text; the trees and
# the leaves are what they were. PR 74 moved the two texts' digests again and
# nothing else: ``rotary_gqa`` hands q and k to ``causal_attention_merged``
# unturned with the tables, so the three projections now stand before the two
# turns in the text where q's turn stood before k's projection (the same
# operations, counted by kind, in another order; on a chip the turn is the
# causal kernel's, tests/test_tpu_compile.py).
FALCON = {"falcon_h1_tiny": ('c28df781d5f28455', 'df3bed545372b86b', 'c0487694e568148f'),
          "falcon_h1_34b": ('f8702263446bc2da', 'e267b4d3131c7b2d')}


def _digest(*chunks):
    h = hashlib.sha256()
    for chunk in chunks:
        h.update(chunk)
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(FALCON))
def test_the_tenth_plan_lowers_to_its_own_text_and_makes_its_trees(name):
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
    assert got == FALCON[name]
