"""Kimi-Linear at toy widths on the CPU (hidden 64, 2 heads, 8 experts top-2,
all four kinds of layer): each mechanism against its plain form, the shares of
an expert layer against the whole, and the model through ``InferenceEngine``
against the benchmark's reference (``benchmarks/references/kimi_linear.py``,
float32 at ``highest``) on seeded weights. Probabilities over the whole
vocabulary are compared, never an argmax: with random weights the largest
logit changes on rounding."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.core import spec  # noqa: E402
from storm_tpu.config import BatchConfig, ModelConfig  # noqa: E402
from storm_tpu.infer.engine import InferenceEngine  # noqa: E402
from storm_tpu.models import kimi_linear as K  # noqa: E402
from storm_tpu.models.registry import build_model, load_or_init  # noqa: E402
from storm_tpu.ops import kda  # noqa: E402
from storm_tpu.ops import layers as L  # noqa: E402
from storm_tpu.ops.attention import causal_attention  # noqa: E402
from storm_tpu.parallel.moe import (route_topk, topk_moe_init,  # noqa: E402
                                    topk_moe_layer)

REFERENCE = spec.plugin("references", "kimi_linear")
TINY = spec.config("kimi_linear_tiny")
SIZES = TINY["published"]


def _distance(got, want):
    """Euclidean distance of each row from its reference row over that row's
    length: the benchmark's measure (``core/pairing.py``)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))


def _recurrence(q, k, v, g, beta):
    """The layer's definition, token by token: decay, delta rule, read."""
    b, s, h, dk = q.shape

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = jnp.exp(g_t)[..., None] * state
        read = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + b_t[..., None, None] * k_t[..., :, None] \
            * (v_t - read)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    xs = tuple(jnp.moveaxis(y, 1, 0) for y in (q, k, v, g, beta))
    _, o = lax.scan(token, jnp.zeros((b, h, dk, v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1)


def _kda_inputs(decay, shape, d, seed=0):
    """``q, k, v, g: shape + (d,)`` and ``beta: shape`` as the layer makes
    them: normalised keys, scaled queries, a log-decay of ``-decay`` times a
    softplus."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = kda.l2norm(jax.random.normal(ks[0], shape + (d,))) * d ** -0.5
    k = kda.l2norm(jax.random.normal(ks[1], shape + (d,)))
    v = jax.random.normal(ks[2], shape + (d,))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], shape + (d,)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape))
    return q, k, v, g, beta


DECAYS = {"slow": 0.1, "fast": 3.0, "within-a-token": 30.0}


def _lanes(y):
    """``(B, S, H, d)`` as the layer holds it, a head a block of lanes."""
    return y.reshape(*y.shape[:2], -1)


def _chunked(q, k, v, g, beta, **kw):
    """The four-dimensional call ``kda_chunked`` took before it was handed
    its operands as the kernel reads them: heads an axis, in and out."""
    o = kda.kda_chunked(*(_lanes(y) for y in (q, k, v, g)), beta,
                        heads=q.shape[2], **kw)
    return o.reshape(*v.shape)


@pytest.mark.parametrize("decay", DECAYS.values(), ids=DECAYS.keys())
def test_chunked_kda_is_the_recurrence(decay):
    """150 tokens are no multiple of the chunk (64) nor of the sub-chunk.
    At the fastest decay ``exp(-G_s)`` overflows float32 inside one chunk:
    the chunked form must not be built from it. Both sides float32 at
    ``highest``: they differ by summation order alone, 1e-5 of the largest
    output is a hundred times that."""
    q, k, v, g, beta = _kda_inputs(decay, (2, 150, 3), 32)
    with jax.default_matmul_precision("highest"):
        want = _recurrence(q, k, v, g, beta)
        got = _chunked(q, k, v, g, beta, chunk=64, sub=16)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(jnp.abs(want).max())


TABLES = ("W", "U0", "Q*exp(G)", "K*exp(G_C-G)", "A_qk", "exp(G_C)")


@functools.lru_cache(maxsize=None)
def _tables_both_ways(decay):
    """One row of 2 heads x 3 chunks of 64 tokens at 128-wide heads: the jnp
    form on ``(H, N, C, d)`` and the kernel (under the Pallas interpreter) on
    the same row as the layer holds it, ``(B, S, H * d)``, second of a batch
    whose first row is zeros, written into room that holds sevens. Both
    brought to ``(H, N, ...)``, and the room's first row beside them."""
    q, k, v, g, beta = _kda_inputs(decay, (2, 3, 64), 128)

    def as_layer(y):  # (H, N, C, d) -> (2, N * C, H * d), the row at index 1
        y = jnp.moveaxis(y, 0, 2).reshape(3 * 64, -1)
        return jnp.stack([jnp.zeros_like(y), y])

    room = tuple(jnp.full(y.shape, 7, y.dtype) for y in kda.empty_tables(
        2, 3, 2, 64, 128, 128, jnp.float32))
    with jax.default_matmul_precision("highest"):
        want = kda._within_chunks(q, k, v, g, beta, sub=16)
        got = kda.within_chunks_kernel(
            1, room, *(as_layer(y) for y in (q, k, v, g)),
            jnp.stack([jnp.zeros_like(beta), beta]), heads=2, chunk=64,
            interpret=True)
    # (N, B, H, C, .) and (B, H, N, dk): this row's, then the other's
    rows = [[jnp.moveaxis(y[:, r], 0, 1) for y in got[:5]] + [got[5][r]]
            for r in (1, 0)]
    return tuple([np.asarray(y) for y in side] for side in (*rows, want))


@pytest.mark.parametrize("table", range(6), ids=TABLES)
@pytest.mark.parametrize("decay", DECAYS.values(), ids=DECAYS.keys())
def test_the_tables_kernel_is_the_jnp_form(decay, table):
    """Each of the six arrays the chain over chunks is handed, float32 both
    sides (the products at ``highest``): 1e-5 of the array's largest value.
    The kernel's sub-chunk is 8 where the jnp form's is 16, its triangle is
    solved row by row where the jnp form's is inverted block by block, and
    its decays are summed by doubling: summation order alone. A call writes
    its own row of the batch and no other."""
    got, other, want = (side[table] for side in _tables_both_ways(decay))
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.isfinite(got).all() and (other == 7).all()
    # (at the two faster decays ``exp(G_C)`` is zero to float32 on both sides)
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


@pytest.fixture
def tables_by_the_kernel(monkeypatch):
    """What a process with one TPU would build: the shape rule's own answer
    with the platform's two questions answered as there, and the kernel run
    by the Pallas interpreter."""
    monkeypatch.setattr(kda, "_use_pallas", lambda: True)
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    monkeypatch.setattr(kda, "within_chunks_kernel", functools.partial(
        kda.within_chunks_kernel, interpret=True))


@pytest.mark.parametrize("decay", DECAYS.values(), ids=DECAYS.keys())
def test_chunked_kda_through_the_kernel_is_the_recurrence(
        decay, tables_by_the_kernel):
    """As ``test_chunked_kda_is_the_recurrence`` at heads the kernel takes
    (128 wide): 150 tokens are no multiple of the chunk, and the choice lands
    in the dispatch notes the engine reads."""
    from storm_tpu.ops.platform import dispatch_notes

    q, k, v, g, beta = _kda_inputs(decay, (2, 150, 2), 128)
    with jax.default_matmul_precision("highest"):
        want = _recurrence(q, k, v, g, beta)
        with dispatch_notes() as seen:
            got = _chunked(q, k, v, g, beta, chunk=64)
    assert seen == ["kda_tables=kernel"]
    assert got.shape == want.shape and bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(jnp.abs(want).max())


@pytest.mark.parametrize("form", ["xla", "kernel"])
@pytest.mark.parametrize("decay", DECAYS.values(), ids=DECAYS.keys())
def test_heads_as_blocks_of_lanes_are_the_heads_alone_to_the_bit(
        decay, form, request):
    """The hand-over in three dimensions cuts a head out as a block of
    lanes, in and out: three heads of 128 side by side give, to the bit in
    float32, what each gives handed over alone, the four-dimensional view
    cut by this test. 150 tokens are padded by both forms; a norm on the way
    out is a head's own."""
    if form == "kernel":
        request.getfixturevalue("tables_by_the_kernel")
    q, k, v, g, beta = _kda_inputs(decay, (2, 150, 3), 128)
    with jax.default_matmul_precision("highest"):
        got = kda.kda_chunked(*(_lanes(y) for y in (q, k, v, g)), beta,
                              heads=3, chunk=64, out=kda.l2norm)
        alone = [kda.kda_chunked(q[:, :, h], k[:, :, h], v[:, :, h],
                                 g[:, :, h], beta[:, :, h:h + 1], heads=1,
                                 chunk=64, out=kda.l2norm) for h in range(3)]
    assert got.shape == (2, 150, 3 * 128)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.concatenate(alone, -1)))


@pytest.mark.parametrize("heads,d", [(3, 128), (2, 16)])
def test_lane_block_norm_is_the_norm_of_each_head(heads, d):
    """``l2norm_heads`` on ``(B, S, H * d)``, its statistic a product with a
    0/1 matrix, against ``l2norm`` on the four-dimensional view: float32
    both, another order of the sum alone. Values up to 1e4 and down to 1e-4
    in one head: every bit of a square reaches the sum."""
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 150, heads * d))
    x = x * 10.0 ** jax.random.randint(jax.random.PRNGKey(9), x.shape, -4, 5)
    want = _lanes(kda.l2norm(x.reshape(2, 150, heads, d)))
    got = kda.l2norm_heads(x, heads)
    assert got.dtype == want.dtype
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-12)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def test_the_mixer_hands_the_kernel_lanes_and_brings_no_branch_back(
        tables_by_the_kernel):
    """2 rows of 128 positions, 2 heads of 128, the kernel's form: q, k, v
    and the decay reach the Pallas call as ``(B, S, heads * head_dim)``, the
    form the projections yield, and no branch goes to ``(B, S, heads,
    head_dim)`` and is reshaped back (on the chip each way re-tiles the whole
    array: eight heads to a tile there, eight positions here). A statistic's
    view by tiles of positions is no such reshape."""
    b, s, heads, d = 2, 128, 2, 128
    p = K.kda_mixer_init(jax.random.PRNGKey(6), 64, heads, d, 4)
    x = jax.ShapeDtypeStruct((b, s, 64), jnp.float32)
    eqns = list(_equations(jax.make_jaxpr(
        lambda p, x: K.kda_mixer(p, x, heads, d, 64, 1e-5))(p, x).jaxpr))
    (call,) = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert [v.aval.shape for v in call.invars[1:5]] == [(b, s, heads * d)] * 4
    assert [str(v.aval.dtype) for v in call.invars[1:5]] == ["float32"] * 4
    back = [e for e in eqns if e.primitive.name == "reshape"
            and e.invars[0].aval.shape == (b, s, heads, d)
            and e.outvars[0].aval.shape == (b, s, heads * d)]
    assert not back


def test_more_chunks_than_a_grid_step_holds(tables_by_the_kernel):
    """600 tokens are 10 chunks where a grid step holds 8: padded to two
    whole steps, the same output as the jnp form's."""
    q, k, v, g, beta = _kda_inputs(3.0, (1, 600, 1), 128, seed=1)
    with jax.default_matmul_precision("highest"):
        got = _chunked(q, k, v, g, beta, chunk=64)
        want = _recurrence(q, k, v, g, beta)
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(jnp.abs(want).max())


class _Device:
    def __init__(self, platform):
        self.platform = platform


@pytest.mark.parametrize("dk,dv,chunk,platform,devices,no_pallas,want", [
    (128, 128, 64, "tpu", 1, False, "kernel"),  # the cell's program
    (256, 128, 64, "tpu", 1, False, "kernel"),
    (128, 128, 16, "tpu", 1, False, "kernel"),
    (16, 16, 16, "tpu", 1, False, "xla"),       # kimi_linear_tiny's heads
    (128, 64, 64, "tpu", 1, False, "xla"),      # values of half a lane tile
    (128, 128, 60, "tpu", 1, False, "xla"),     # no whole sub-chunks
    # a host with several chips: a program may be split over them, and the
    # kernel has no partitioning rule (this suite itself: 8 host devices)
    (128, 128, 64, "tpu", 4, False, "xla"),
    (128, 128, 64, "cpu", 1, False, "xla"),
    (128, 128, 64, "tpu", 1, True, "xla"),      # STORM_TPU_NO_PALLAS
])
def test_tables_form_is_a_function_of_the_traced_shapes_and_the_devices(
        dk, dv, chunk, platform, devices, no_pallas, want, monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda: [_Device(platform)])
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    if no_pallas:
        monkeypatch.setenv("STORM_TPU_NO_PALLAS", "1")
    else:
        monkeypatch.delenv("STORM_TPU_NO_PALLAS", raising=False)
    assert kda.tables_form(dk, dv, chunk) == want


def test_short_convolution_is_causal_and_matches_the_plain_form():
    p = kda.short_conv_init(jax.random.PRNGKey(1), 6, 4)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 9, 6))
    y = kda.short_conv(p, x)
    want = jnp.stack([REFERENCE._conv(p, row) for row in x])
    np.testing.assert_allclose(y, want, atol=1e-6)
    # a later token changes nothing before it
    y2 = kda.short_conv(p, x.at[:, 5].add(1.0))
    np.testing.assert_array_equal(np.asarray(y[:, :5]), np.asarray(y2[:, :5]))
    assert float(jnp.abs(y2[:, 5:9] - y[:, 5:9]).min()) >= 0


def test_causal_attention_of_unequal_widths_against_the_plain_form():
    """Query/key heads of 24 against value heads of 16, 40 tokens in blocks
    of 16 (the last block ragged). Float32 both sides: 1e-5."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, 2, 40, 24))
    k = jax.random.normal(ks[1], (2, 2, 40, 24))
    v = jax.random.normal(ks[2], (2, 2, 40, 16))
    scores = jnp.einsum("bhsd,bhtd->bhst", q, k) * 24 ** -0.5
    mask = jnp.tril(jnp.ones((40, 40), bool))
    want = jnp.einsum("bhst,bhtd->bhsd", jax.nn.softmax(
        jnp.where(mask, scores, -jnp.inf), -1), v)
    got = causal_attention(q, k, v, block=16)
    assert got.shape == (2, 2, 40, 16)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_mla_mixer_against_the_reference_row_by_row():
    p = K.mla_mixer_init(jax.random.PRNGKey(4), 64, 2, 16, 8, 16, 24)
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 40, 64))
    with jax.default_matmul_precision("highest"):
        got = K.mla_mixer(p, x, 2, 16, 8, 16, 24, 1e-5)
        want = jnp.stack([REFERENCE._mla(p, row, SIZES, 1e-5) for row in x])
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_kda_mixer_against_the_reference_row_by_row():
    p = K.kda_mixer_init(jax.random.PRNGKey(6), 64, 2, 16, 4)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 40, 64))
    with jax.default_matmul_precision("highest"):
        got = K.kda_mixer(p, x, 2, 16, 16, 1e-5)
        want = jnp.stack([REFERENCE._kda(p, row, SIZES, 1e-5) for row in x])
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_chips_mixer_check_runs_here_at_toy_size():
    """``ops/parity_checks.py check_kda_mixer`` holds the mixer on the chip
    to the reference at the published widths; its smoke form (128 positions,
    2 heads of 128, float32) runs here and reads summation order alone."""
    from storm_tpu.ops.parity_checks import check_kda_mixer

    (row,) = check_kda_mixer(interpret=True)
    assert row["pass"] and row["metric"] == "rms"
    assert row["rms_rel_err"] < 1e-5


def _plain_experts(p, x, top_k, first, scale):
    """Every held expert on every token, weighted by what the router gave
    it: nothing grouped, nothing dropped."""
    t = x.reshape(-1, x.shape[-1])
    chosen, weight = route_topk(p, t, top_k, scale=scale)
    y = jnp.zeros_like(t)
    for e in range(p["experts"]["gate"].shape[0]):
        gain = jnp.sum(jnp.where(chosen == e + first, weight, 0.0), -1)
        y = y + gain[:, None] * L.swiglu(
            {n: w[e] for n, w in p["experts"].items()}, t)
    return y.reshape(x.shape)


def _moe(seed=0, skew=None, experts=8):
    p = topk_moe_init(jax.random.PRNGKey(seed), 32, 48, experts)
    if skew is not None:  # the selection bias sends every token to one expert
        p["router_bias"] = p["router_bias"].at[skew].set(10.0)
    return p, jax.random.normal(jax.random.PRNGKey(seed + 1), (3, 37, 32))


@pytest.mark.parametrize("skew", [None, 3], ids=["even", "most-to-one"])
def test_expert_layer_drops_no_token(skew):
    """111 tokens, top-2 of 8, tiles of 16 rows. Under the skewed routing
    expert 3 takes every token (seven tiles, the last partly filled) where
    a capacity of 1.25 would keep 35. Float32 both sides: 1e-5."""
    p, x = _moe(skew=skew)
    with jax.default_matmul_precision("highest"):
        y, tokens, absent = jax.jit(lambda p, x: topk_moe_layer(
            p, x, 2, scale=2.446, tile=16))(p, x)
        want = _plain_experts(p, x, 2, 0, 2.446) + L.swiglu(p["shared"], x)
    np.testing.assert_allclose(y, want, atol=1e-5)
    assert int(tokens.sum()) == 2 * 111 and int(absent) == 0
    if skew is not None:
        assert int(tokens[skew]) == 111


@pytest.mark.parametrize("experts,top_k", [(8, 2), (64, 8)],
                         ids=["a-quarter-held", "a-thirty-second-held"])
def test_the_shares_add_up_to_the_whole_layer(experts, top_k):
    """Four chips hold two experts each of eight, or 32 chips two each of 64
    (Kimi K2's share: a thirty-second of the router's width, where most
    tiles of the grouped product and of the combine are nearly empty). The
    parts they compute, with the shared expert counted once, are the uncut
    layer; the assignments each sees as absent are those the others hold."""
    p, x = _moe(skew=3, experts=experts)
    share_of = jax.jit(lambda share, first: topk_moe_layer(
        share, x, top_k, first_expert=first, scale=2.446, tile=16))
    with jax.default_matmul_precision("highest"):
        whole = _plain_experts(p, x, top_k, 0, 2.446) \
            + L.swiglu(p["shared"], x)
        total, seen = jnp.zeros_like(x), 0
        for first in range(0, experts, 2):
            share = {"router": p["router"], "router_bias": p["router_bias"],
                     "experts": {n: w[first:first + 2]
                                 for n, w in p["experts"].items()}}
            y, tokens, absent = share_of(share, first)
            assert int(tokens.sum()) + int(absent) == top_k * 111
            total, seen = total + y, seen + int(tokens.sum())
        total = total + L.swiglu(p["shared"], x)
    assert seen == top_k * 111
    np.testing.assert_allclose(total, whole, atol=1e-5)


def test_softmax_router_and_no_bias():
    p, x = _moe()
    del p["router_bias"]
    chosen, weight = route_topk(p, x.reshape(-1, 32), 2, router="softmax",
                                renormalize=False)
    probs = jax.nn.softmax(x.reshape(-1, 32) @ p["router"], -1)
    np.testing.assert_allclose(weight, jnp.sort(probs, -1)[:, :-3:-1],
                               rtol=1e-5)
    assert chosen.shape == (111, 2)


# ---- the whole model through the engine --------------------------------------

def _windows(n, seed=3):
    return spec.plugin("inputs", "token_ids").make(
        n, (40,), seed).astype(np.float32)


@pytest.fixture(scope="module")
def reference_rows():
    model = build_model("kimi_linear_tiny")
    params, state = load_or_init(model, None, 5)
    x = _windows(16)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, s, xx: REFERENCE.forward(SIZES, p, s, xx))(
            params, state, x)
    return x, np.asarray(want)


def _engine(dtype):
    return InferenceEngine(ModelConfig(
        name="kimi_linear_tiny", dtype=dtype, num_classes=96,
        input_shape=(40,), seed=5), batch_cfg=BatchConfig())


FLOAT32_TOLERANCE = 1e-4  # summation order alone: reads about 2e-6


def test_model_through_the_engine_in_float32(reference_rows):
    x, want = reference_rows
    eng = _engine("float32")
    got = np.concatenate([eng.predict(x[a:a + 8]) for a in (0, 8)])
    assert got.shape == (16, 96)
    assert _distance(got, want).max() < FLOAT32_TOLERANCE


def test_bfloat16_is_held_to_its_own_tolerance_and_fails_float32s(
        reference_rows):
    """The branches compute in bfloat16 (8 bits of mantissa) beside a float32
    stream: a row reads 0.005-0.01 from the reference (the cell's reading at
    the published widths is 0.0045-0.0056). Beyond that, at 8 experts of
    width 32 a rounding now and then flips which expert a token goes to, and
    the row whose last token it was moves by 0.1-0.3 (the cell: 0.026): so
    the typical row is held to 0.03 and the worst to 0.5 (another window's
    answer is 0.4-1.1 away). The float32 tolerance fails on every row: a
    lower precision than the one asked for is seen."""
    x, want = reference_rows
    eng = _engine("bfloat16")
    got = np.concatenate([eng.predict(x[a:a + 8]) for a in (0, 8)])
    err = _distance(got, want)
    assert np.median(err) < 0.03 and err.max() < 0.5
    assert err.min() > FLOAT32_TOLERANCE


def test_ids_reach_the_model_unrounded():
    """bfloat16 holds whole numbers exactly only up to 256; ids are staged
    in float32 whatever the compute type."""
    eng = _engine("bfloat16")
    assert eng.in_dtype == jnp.float32 and eng.dtype == jnp.bfloat16
    model = build_model("kimi_linear_tiny", num_classes=600)
    assert model.input_dtype == "float32"
    big = InferenceEngine(ModelConfig(
        name="kimi_linear_tiny", dtype="bfloat16", num_classes=600,
        input_shape=(40,), seed=5))
    x = np.full((2, 40), 7, np.float32)
    x[0, -1], x[1, -1] = 514, 515  # one bfloat16 value
    a, b = big.predict(x)
    assert np.abs(a - b).max() > 1e-4


@pytest.mark.parametrize("name,rows,clipped_to", [
    ("kimi_linear_tiny", 8, (2, 4, 8)), ("kimi_k2_tiny", 4, (2, 4))])
def test_buckets_are_clipped_to_the_models_bound_and_only_those_warm(
        name, rows, clipped_to):
    """A bound of 8 keeps ``BatchConfig()``'s first bucket; a bound of 4 is
    under all of them and leaves the one bucket ``(4,)``."""
    eng = InferenceEngine(ModelConfig(
        name=name, dtype="float32", num_classes=96, input_shape=(40,),
        seed=5), batch_cfg=BatchConfig())
    assert eng.model.max_rows == rows and eng.max_rows == rows
    assert eng.batch_cfg.buckets == (rows,)
    assert eng.batch_cfg.max_batch == rows
    eng.warmup()
    assert eng.compiled_batches == {rows}
    policy = BatchConfig(max_batch=16, buckets=(2, 4, 16))
    clipped = policy.clipped(rows)
    assert clipped.buckets == clipped_to and clipped.max_batch == rows
    assert clipped.max_wait_ms == policy.max_wait_ms
    # the queue that forms the batches is held to the same bound: a backlog
    # of one bucket and a half fills a step and is cut at the bound
    from storm_tpu.infer.continuous import continuous_for

    queue = continuous_for(eng, BatchConfig())
    assert queue.cfg.max_batch == rows and queue.cfg.buckets == (rows,)
    backlog = _windows(rows + rows // 2)
    subs = [queue.submit(backlog[i:i + 1]) for i in range(len(backlog))]
    got = np.concatenate([sub.future.result(60) for sub in subs])
    assert got.shape == (len(backlog), 96)
    np.testing.assert_allclose(got[:rows], eng.predict(backlog[:rows]),
                               atol=1e-6)
    assert queue.rows_dispatched == len(backlog) and queue.batches >= 2
    assert eng.compiled_batches == {rows}


def test_the_inventory_names_the_form_of_the_tables():
    """Per compiled bucket, which form built the KDA tables of its program:
    here the jnp form by two rules at once (16-wide heads, off the TPU)."""
    from storm_tpu.config import ShardingConfig
    from storm_tpu.infer.engine import engine_inventory, shared_engine

    eng = shared_engine(ModelConfig(
        name="kimi_linear_tiny", dtype="float32", num_classes=96,
        input_shape=(40,), seed=5), ShardingConfig(data_parallel=0),
        BatchConfig())
    eng.warmup()
    row = next(r for r in engine_inventory()["engines"]
               if r["model"] == "kimi_linear_tiny")
    assert list(row["programs"]) == [str(eng.pad_batch(8))]
    forms = row["programs"][str(eng.pad_batch(8))].split(", ")
    assert {"kda_tables=xla", "expert_dispatch=sorted"} <= set(forms)


def test_a_model_without_a_bound_keeps_the_policy_as_given():
    policy = BatchConfig()
    assert policy.clipped(None) is policy and policy.clipped(256) is policy
    eng = InferenceEngine(ModelConfig(name="vit_tiny", dtype="float32",
                                      input_shape=(32, 32, 3)),
                          batch_cfg=policy)
    assert eng.batch_cfg is policy and eng.max_rows is None
    assert eng.batch_cfg.buckets == (8, 32, 128, 256)
    assert eng.in_dtype == eng.dtype and eng._has_aux is False


def test_device_counters_ride_the_result_into_the_registry():
    """Four expert layers, four held experts of eight, top-2: a step of 8
    windows of 40 tokens makes 640 assignments a layer."""
    from storm_tpu.infer.continuous import ContinuousBatcher
    from storm_tpu.runtime.metrics import MetricsRegistry

    eng = _engine("float32")
    handle = eng.dispatch((_windows(8),))
    handle.future.result(60)
    aux = handle.aux
    assert aux["expert_tokens"].shape == (4, 4)
    assert isinstance(aux["expert_tokens"], np.ndarray)
    per_layer = aux["expert_tokens"].sum(1) + aux["expert_absent"]
    assert per_layer.tolist() == [640] * 4
    registry = MetricsRegistry()
    queue = ContinuousBatcher(eng, eng.batch_cfg)
    queue.bind(registry, "inference-bolt")
    queue._observe_aux(aux)
    got = registry.snapshot()["inference-bolt"]
    assert got["expert_assignments_held"] + got[
        "expert_assignments_absent"] == 4 * 640
    assert got["expert_tokens_max_over_mean"]["count"] == 4
    assert got["expert_tokens_max_over_mean"]["max"] >= 1.0
    # a model that counts nothing: nothing fetched, nothing observed
    plain = InferenceEngine(ModelConfig(name="lenet5", dtype="float32"))
    h = plain.dispatch((np.zeros((2, 28, 28, 1), np.float32),))
    h.future.result(60)
    assert h.aux is None


def test_registry_names_the_model_and_its_share():
    model = build_model("kimi_linear_48b")
    assert model.input_shape == (4096,) and model.num_classes == 20480
    assert model.max_rows == 8
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert len(params["layers"]) == 5
    assert "router" not in params["layers"][0]["ffn"]
    assert params["layers"][1]["ffn"]["experts"]["gate"].shape == (
        32, 2304, 1024)
    assert params["layers"][1]["ffn"]["router"].shape == (2304, 256)
    assert "kv_a" in params["layers"][3]["mixer"]
    assert [("conv_q" in blk["mixer"]) for blk in params["layers"]] == [
        True, True, True, False, True]
    assert sum(x.size for x in jax.tree.leaves(params)) == 1_281_911_680
    assert state["aux"]["expert_tokens"].shape == (4, 32)
