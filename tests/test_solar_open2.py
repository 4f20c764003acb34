"""Solar Open 2 at toy widths on the CPU (hidden 64; a period of gated
grouped-query attention, 8 query heads on 2 key heads, and three KDA layers
of 4 heads with steps in (0, 2); 20 experts top-2 of which 5 are held, in
every block): the chunked delta rule with steps past 1 against the
recurrence, in XLA's form and through the kernel interpreted; the two shared
mixers with and without what this family adds; the shares of the expert
layer against the uncut layer; the step counters; and the model through
``InferenceEngine`` against the benchmark's reference
(``benchmarks/references/solar_open2.py``, float32 at ``highest``) on seeded
weights. Probabilities over the whole vocabulary are compared, never an
argmax."""

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
from storm_tpu.models import nemotron_h as N  # noqa: E402
from storm_tpu.models import registry  # noqa: E402
from storm_tpu.models import solar_open2 as S2  # noqa: E402
from storm_tpu.models.registry import build_model, load_or_init  # noqa: E402
from storm_tpu.ops import kda  # noqa: E402
from storm_tpu.ops import layers as L  # noqa: E402
from storm_tpu.ops.attention import causal_attention  # noqa: E402
from storm_tpu.parallel.moe import topk_moe_layer  # noqa: E402

REFERENCE = spec.plugin("references", "solar_open2")
TINY = spec.config("solar_open2_tiny")
SIZES = TINY["published"]


def _distance(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))


# ---- the delta rule with steps in (0, 2) -------------------------------------

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


def _kda_inputs(decay, shape, d, steps, seed=0):
    """``q, k, v, g: shape + (d,)`` and ``beta: shape`` as the layer makes
    them. ``steps`` ``"wide"``: ``2 sigmoid`` of N(0, 1), all of (0, 2);
    ``"reflecting"``: every key of a sequence the same direction but for a
    twentieth of noise, every step in (1.9, 2): the unit-triangular system
    of a chunk is then as far from the identity as the layer can make it."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = kda.l2norm(jax.random.normal(ks[0], shape + (d,))) * d ** -0.5
    k = jax.random.normal(ks[1], shape + (d,))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], shape))
    if steps == "reflecting":
        along = jax.random.normal(ks[5], (shape[0], 1, shape[2], d))
        k = along + 0.05 * k
        beta = jax.random.uniform(ks[4], shape, minval=1.9, maxval=2.0)
    v = jax.random.normal(ks[2], shape + (d,))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], shape + (d,)))
    return q, kda.l2norm(k), v, g, beta


def _chunked(q, k, v, g, beta, **kw):
    o = kda.kda_chunked(*(y.reshape(*y.shape[:2], -1) for y in (q, k, v, g)),
                        beta, heads=q.shape[2], **kw)
    return o.reshape(*v.shape)


@pytest.fixture
def tables_by_the_kernel(monkeypatch):
    """What a process with one TPU would build, the kernel run by the Pallas
    interpreter (as ``tests/test_kimi_linear.py``)."""
    monkeypatch.setattr(kda, "_use_pallas", lambda: True)
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    monkeypatch.setattr(kda, "within_chunks_kernel", functools.partial(
        kda.within_chunks_kernel, interpret=True))


CASES = [("wide", 0.1), ("wide", 3.0), ("reflecting", 0.02),
         ("reflecting", 3.0)]


@pytest.mark.parametrize("form", ["xla", "kernel"])
@pytest.mark.parametrize("steps,decay", CASES,
                         ids=[f"{s}-{d}" for s, d in CASES])
def test_chunked_kda_with_steps_past_one_is_the_recurrence(
        steps, decay, form, request):
    """150 tokens are no multiple of the chunk. With a step past 1 the
    transition reflects along its key; ``_unit_lower_inverse`` and the
    kernel's forward substitution solve ``(I + tril(beta A))`` exactly
    whatever ``beta``'s range. Float32 both sides, the products at
    ``highest``: 1e-5 of the largest output. With near-parallel keys, steps
    over 1.9 and next to no decay ``beta A`` is near 2 all over the triangle,
    a chunk's 64 reflections follow one another and the system's condition
    number is in the thousands: XLA's form reads 1.2e-5 there and the kernel
    under the interpreter 4.6e-3 (its body run operation by operation reads
    5e-6, and 1.2e-2 once the CPU's compiler fuses what the solve starts
    from into the substitution: the arithmetic's order alone; PERF.md
    section 7). On the chip both forms are held to the reference at the
    published widths (``benchmarks/tools/solar_mixer_check.py``)."""
    if form == "kernel":
        request.getfixturevalue("tables_by_the_kernel")
        shape, d = (2, 150, 2), 128
    else:
        shape, d = (2, 150, 3), 32
    q, k, v, g, beta = _kda_inputs(decay, shape, d, steps)
    assert float(beta.max()) > 1.9 and (steps == "wide"
                                        or float(beta.min()) > 1.9)
    if steps == "reflecting":  # neighbours' keys 0.99 alike or more
        alike = jnp.einsum("bshd,bshd->bsh", k[:, 1:], k[:, :-1])
        assert float(alike.min()) > 0.99
    with jax.default_matmul_precision("highest"):
        want = _recurrence(q, k, v, g, beta)
        got = _chunked(q, k, v, g, beta, chunk=64)
    assert bool(jnp.isfinite(got).all())
    limit = 1e-5
    if (steps, decay) == ("reflecting", 0.02):
        limit = 1e-2 if form == "kernel" else 2e-5
    assert float(jnp.abs(got - want).max()) < limit * float(
        jnp.abs(want).max())


# ---- the shared mixers, with and without what this family adds ---------------

def _kda_mixer_before(p, x, heads, head_dim, chunk, eps):
    """``models/kimi_linear.py kda_mixer`` as the parent of PR 52 had it,
    word for word."""
    f32 = jnp.float32

    def branch(name):
        return kda.conv_silu(p["conv_" + name], K._proj(x, p[name]))

    q = kda.l2norm_heads(branch("q"), heads) * (head_dim ** -0.5)
    k = kda.l2norm_heads(branch("k"), heads)
    v = branch("v")
    f = K._proj(x, p["f_down"], p["f_up"]).astype(f32)
    g = -jnp.repeat(jnp.exp(p["a_log"].astype(f32)), head_dim) \
        * jax.nn.softplus(f + p["dt_bias"].astype(f32))
    beta = jax.nn.sigmoid(K._proj(x, p["beta"]).astype(f32))
    o = kda.kda_chunked(q.astype(x.dtype), k, v, g, beta, heads, chunk=chunk,
                        out=lambda o: L.rmsnorm(p["o_norm"], o, eps))
    gate = jax.nn.sigmoid(K._proj(x, p["g_down"], p["g_up"]))
    return K._proj(o * gate, p["o"])


def _gqa_mixer_before(p, x, heads, kv_heads, head_dim):
    """``models/nemotron_h.py gqa_mixer`` as the parent of PR 52 had it."""
    b, s, _ = x.shape

    def split(name, n):
        return N._proj(x, p[name]).reshape(b, s, n, head_dim).transpose(
            0, 2, 1, 3)

    out = causal_attention(split("q", heads), split("k", kv_heads),
                           split("v", kv_heads), scale=head_dim ** -0.5)
    return N._proj(out.transpose(0, 2, 1, 3).reshape(b, s, heads * head_dim),
                   p["o"])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_called_as_the_parent_called_them_both_mixers_are_bit_equal(dtype):
    """``kimi_linear_48b`` and ``nemotron_3_nano_30b`` must not move: the
    step's range and the gate are read at trace time, and without them the
    same operations run in the same order on the same draws
    (``tests/test_scorer.py`` holds their lowered text besides)."""
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 40, 64)).astype(dtype)
    p = jax.tree.map(lambda a: a.astype(dtype), K.kda_mixer_init(
        jax.random.PRNGKey(4), 64, 2, 16, 4))
    now = jax.jit(lambda p, x: K.kda_mixer(p, x, 2, 16, 16, 1e-5))(p, x)
    before = jax.jit(lambda p, x: _kda_mixer_before(p, x, 2, 16, 16, 1e-5))(
        p, x)
    np.testing.assert_array_equal(np.asarray(now, np.float32),
                                  np.asarray(before, np.float32))
    rng = jax.random.PRNGKey(6)
    p, gated = N.gqa_mixer_init(rng, 64, 4, 2, 16), \
        N.gqa_mixer_init(rng, 64, 4, 2, 16, gate=True)
    assert sorted(p) == ["k", "o", "q", "v"]
    assert sorted(gated) == ["gate", "k", "o", "q", "v"]
    for name in p:  # the ungated draw is unmoved
        np.testing.assert_array_equal(p[name], gated[name])
    assert gated["gate"].shape == (64, 64)
    p = jax.tree.map(lambda a: a.astype(dtype), p)
    now = jax.jit(lambda p, x: N.gqa_mixer(p, x, 4, 2, 16))(p, x)
    before = jax.jit(lambda p, x: _gqa_mixer_before(p, x, 4, 2, 16))(p, x)
    np.testing.assert_array_equal(np.asarray(now, np.float32),
                                  np.asarray(before, np.float32))


def test_kimi_linear_tiny_serves_what_the_parents_mixer_gives(monkeypatch):
    """The whole toy model with ``kda_mixer`` swapped for the parent's: the
    same logits to the bit, and no step count in its state."""
    model = build_model("kimi_linear_tiny")
    params, state = model.init(jax.random.PRNGKey(2))
    assert set(state["aux"]) == {"expert_tokens", "expert_absent",
                                 "combine_tiles"}
    x = spec.plugin("inputs", "token_ids").make(3, (40,), 9).astype(
        np.float32)
    now, _ = jax.jit(model.apply)(params, state, x)
    monkeypatch.setattr(K, "kda_mixer", _kda_mixer_before)
    before, _ = jax.jit(build_model("kimi_linear_tiny").apply)(
        params, state, x)
    np.testing.assert_array_equal(np.asarray(now), np.asarray(before))


def test_gated_gqa_mixer_against_the_reference_row_by_row():
    """8 query heads on 2 key heads: query head ``i`` reads key head ``i //
    4``, the full masked softmax, then the gate. Float32 both sides."""
    p = N.gqa_mixer_init(jax.random.PRNGKey(8), 64, 8, 2, 16, gate=True)
    x = jax.random.normal(jax.random.PRNGKey(9), (3, 40, 64))
    with jax.default_matmul_precision("highest"):
        got = N.gqa_mixer(p, x, 8, 2, 16)
        want = jnp.stack([REFERENCE._gqa(p, row, SIZES) for row in x])
        ungated = N.gqa_mixer({k: v for k, v in p.items() if k != "gate"},
                              x, 8, 2, 16)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(jnp.abs(got - ungated).max()) > 1e-2  # the gate matters


def test_kda_mixer_with_steps_in_two_against_the_reference_row_by_row():
    p = K.kda_mixer_init(jax.random.PRNGKey(10), 64, 4, 16, 4)
    x = jax.random.normal(jax.random.PRNGKey(11), (3, 40, 64))
    with jax.default_matmul_precision("highest"):
        got = K.kda_mixer(p, x, 4, 16, 16, 1e-5, step_range=2.0)
        want = jnp.stack([REFERENCE._kda(p, row, SIZES, 1e-5) for row in x])
        narrow = K.kda_mixer(p, x, 4, 16, 16, 1e-5)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(jnp.abs(got - narrow).max()) > 1e-2  # the range matters
    # and the inputs exercise it: about half of the 480 steps are past 1,
    # where the transition reflects along its key
    beta = 2 / (1 + np.exp(-np.asarray(x @ p["beta"], np.float64)))
    assert 150 < int((beta > 1).sum()) < 330 and beta.max() < 2


def test_a_later_token_changes_no_earlier_output():
    model = build_model("solar_open2_tiny")
    params, _ = model.init(jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 40, 64))
    later = x.at[:, 30:].set(jax.random.normal(jax.random.PRNGKey(9),
                                               (2, 10, 64)))
    for blk, mixer in ((params["layers"][0], lambda p, y: N.gqa_mixer(
            p, y, 8, 2, 16)), (params["layers"][1], lambda p, y: K.kda_mixer(
                p, y, 4, 16, 16, 1e-5, step_range=2.0))):
        a, b = mixer(blk["mixer"], x), mixer(blk["mixer"], later)
        np.testing.assert_array_equal(np.asarray(a[:, :30]),
                                      np.asarray(b[:, :30]))
        assert float(jnp.abs(a[:, 30:] - b[:, 30:]).max()) > 1e-4


# ---- the expert layer's shares ------------------------------------------------

def test_the_shares_add_up_to_the_uncut_references_layer():
    """The guide's section 4: four chips hold five experts each of a router
    of 20 columns (no power of two); the parts they compute, with the shared
    expert counted once, are what the reference gives for the whole layer
    with all 20 experts; the assignments each sees as absent are those the
    others hold. Scale 1, top-2 of 20, the shares' tile 16."""
    model = build_model("solar_open2_tiny")
    held = load_or_init(model, None, 5)[0]["layers"][0]["ffn"]
    # the uncut layer: 20 experts, of which the tiny model's own five lead
    rest = jax.tree.map(
        lambda a: jnp.concatenate([a, jax.random.normal(
            jax.random.PRNGKey(12), (15,) + a.shape[1:]) * a.std()]),
        held["experts"])
    whole = {**held, "experts": rest}
    x = jax.random.normal(jax.random.PRNGKey(13), (3, 37, 64))
    uncut = {**SIZES, "held": {"first_expert": 0}}
    share_of = jax.jit(lambda share, first: topk_moe_layer(
        share, x, 2, first_expert=first, scale=1.0, tile=16))
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([REFERENCE._experts(whole, row, uncut) for row in x])
        total, seen = jnp.zeros_like(x), 0
        for first in range(0, 20, 5):
            share = {"router": held["router"],
                     "router_bias": held["router_bias"],
                     "experts": {n: w[first:first + 5]
                                 for n, w in rest.items()}}
            y, tokens, absent = share_of(share, first)
            assert tokens.shape == (5,)
            assert int(tokens.sum()) + int(absent) == 2 * 111
            total, seen = total + y, seen + int(tokens.sum())
        total = total + L.swiglu(held["shared"], x)
        # the first share is what the served model's layer computes
        first_share, _, _ = jax.jit(lambda p: topk_moe_layer(
            p, x, 2, scale=1.0, tile=16))(held)
        ref_share = jnp.stack([REFERENCE._experts(held, row, SIZES)
                               for row in x])
    assert seen == 2 * 111
    np.testing.assert_allclose(total, want, atol=1e-5)
    np.testing.assert_allclose(first_share, ref_share, atol=1e-5)


# ---- the registry and the load -------------------------------------------------

def test_registry_names_the_model_its_share_and_its_type():
    model = build_model("solar_open2_250b")
    assert model.input_shape == (4096,) and model.num_classes == 24576
    assert model.max_rows == SIZES_250B["held"]["rows_per_step"]
    assert model.input_dtype == "float32"
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert len(params["layers"]) == 4
    # one period in its published order, an expert layer in every block
    assert [("gate" in blk["mixer"], "conv_q" in blk["mixer"])
            for blk in params["layers"]] == [(True, False)] + [(False, True)] * 3
    for blk in params["layers"]:
        ffn = blk["ffn"]
        assert ffn["router"].shape == (4096, 320)
        assert ffn["router_bias"].shape == (320,)
        assert ffn["experts"]["gate"].shape == (40, 4096, 1280)
        assert ffn["experts"]["down"].shape == (40, 1280, 4096)
        assert ffn["shared"]["up"].shape == (4096, 1280)
    gqa = params["layers"][0]["mixer"]
    assert gqa["q"].shape == gqa["gate"].shape == (4096, 8192)
    assert gqa["k"].shape == gqa["v"].shape == (4096, 1024)
    assert gqa["o"].shape == (8192, 4096)
    mixer = params["layers"][2]["mixer"]
    assert mixer["q"].shape == mixer["v"].shape == (4096, 8192)
    assert mixer["f_down"].shape == (4096, 128)
    assert mixer["g_up"].shape == (128, 8192)
    assert mixer["beta"].shape == (4096, 64)
    assert mixer["conv_k"]["w"].shape == (4, 8192)
    assert params["embed"].shape == (24576, 4096)
    assert sum(x.size for x in jax.tree.leaves(params)) == 3_308_353_344
    assert {x.dtype for x in jax.tree.leaves(params)} == {
        jnp.dtype(jnp.bfloat16)}
    assert {k: v.shape for k, v in state["aux"].items()} == {
        "expert_tokens": (4, 40), "expert_absent": (4,),
        "combine_tiles": (4, 2)}
    assert {"solar_open2_250b", "solar_open2_tiny"} <= set(
        registry.registry_names())


SIZES_250B = spec.config("solar_open2_250b")["published"]


def test_the_initialiser_hands_over_the_served_type_and_astypes_values():
    key = jax.random.PRNGKey(5)
    served, _ = S2.build_solar_open2_tiny(param_dtype=jnp.bfloat16).init(key)
    drawn, _ = build_model("solar_open2_tiny").init(key)
    assert {a.dtype for a in jax.tree.leaves(served)} == {
        jnp.dtype(jnp.bfloat16)}
    assert {a.dtype for a in jax.tree.leaves(drawn)} == {
        jnp.dtype(jnp.float32)}
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a, np.float32), np.asarray(b.astype(jnp.bfloat16),
                                              np.float32)), served, drawn)
    # every residual branch's output starts at LeCun's over sqrt(2 x 8)
    mixer = drawn["layers"][1]["mixer"]
    assert float(mixer["o"].std()) == pytest.approx(
        64 ** -0.5 * 16 ** -0.5, rel=0.05)
    assert float(mixer["q"].std()) == pytest.approx(64 ** -0.5, rel=0.05)
    assert float(drawn["layers"][0]["ffn"]["router_bias"].std()) \
        == pytest.approx(0.01, rel=0.5)


# ---- the whole model through the engine ----------------------------------------

def _windows(n, seed=3):
    return spec.plugin("inputs", "solar_open2_tokens").make(
        n, (40,), seed).astype(np.float32)


@pytest.fixture(scope="module")
def reference_rows():
    model = build_model("solar_open2_tiny")
    params, state = load_or_init(model, None, 5)
    x = _windows(16)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, s, xx: REFERENCE.forward(SIZES, p, s, xx))(
            params, state, x)
    return x, np.asarray(want)


def _engine(dtype):
    return InferenceEngine(ModelConfig(
        name="solar_open2_tiny", dtype=dtype, num_classes=96,
        input_shape=(40,), seed=5), batch_cfg=BatchConfig())


@pytest.fixture(scope="module")
def float32_engine():
    from storm_tpu.config import ShardingConfig
    from storm_tpu.infer.engine import shared_engine

    eng = shared_engine(ModelConfig(
        name="solar_open2_tiny", dtype="float32", num_classes=96,
        input_shape=(40,), seed=5), ShardingConfig(data_parallel=0),
        BatchConfig())
    eng.warmup()
    return eng


FLOAT32_TOLERANCE = 1e-5  # summation order alone: reads about 5e-7


def test_model_through_the_engine_in_float32(reference_rows, float32_engine):
    x, want = reference_rows
    eng = float32_engine
    got = np.concatenate([eng.predict(x[a:a + 4]) for a in range(0, 16, 4)])
    assert got.shape == (16, 96)
    assert _distance(got, want).max() < FLOAT32_TOLERANCE


def test_bfloat16_is_held_to_its_own_tolerance_and_fails_float32s(
        reference_rows):
    x, want = reference_rows
    eng = _engine("bfloat16")
    got = np.concatenate([eng.predict(x[a:a + 4]) for a in range(0, 16, 4)])
    err = _distance(got, want)
    assert np.median(err) < 0.03 and err.max() < 0.5
    assert err.min() > FLOAT32_TOLERANCE


def test_the_reference_refuses_another_depth_and_a_dense_layer(
        reference_rows):
    model = build_model("solar_open2_tiny")
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1, 40), jnp.float32)
    short = {**params, "layers": params["layers"][:3]}
    with pytest.raises(ValueError, match="another depth"):
        jax.eval_shape(lambda p: REFERENCE.forward(SIZES, p, state, x), short)
    with pytest.raises(ValueError, match="dense"):
        jax.eval_shape(lambda p: REFERENCE.forward(
            {**SIZES, "first_k_dense_replace": 1}, p, state, x), params)


def test_the_inventory_names_the_forms(float32_engine):
    from storm_tpu.infer.engine import engine_inventory

    eng = float32_engine
    row = next(r for r in engine_inventory()["engines"]
               if r["model"] == "solar_open2_tiny")
    assert list(row["programs"]) == [str(eng.pad_batch(4))]
    forms = row["programs"][str(eng.pad_batch(4))].split(", ")
    assert {"causal_attention=blocked-grouped", "kda_tables=xla",
            "short_conv=xla", "expert_ffn=swiglu", "expert_dispatch=sorted",
            "expert_combine=held-rows"} <= set(forms)


def test_device_counters_ride_the_result_into_the_registry():
    """Four expert layers, five held experts of twenty, top-2: a step of 4
    windows of 40 tokens makes 320 assignments an expert layer."""
    from storm_tpu.infer.continuous import ContinuousBatcher
    from storm_tpu.runtime.metrics import MetricsRegistry

    eng = _engine("float32")
    handle = eng.dispatch((_windows(4),))
    handle.future.result(60)
    aux = handle.aux
    assert aux["expert_tokens"].shape == (4, 5)
    per_layer = aux["expert_tokens"].sum(1) + aux["expert_absent"]
    assert per_layer.tolist() == [320] * 4
    assert set(aux) == {"expert_tokens", "expert_absent", "combine_tiles"}
    registry_ = MetricsRegistry()
    queue = ContinuousBatcher(eng, eng.batch_cfg)
    queue.bind(registry_, "inference-bolt")
    queue._observe_aux(aux)
    got = registry_.snapshot()["inference-bolt"]
    assert got["expert_assignments_held"] + got[
        "expert_assignments_absent"] == 4 * 320
    assert got["expert_tokens_max_over_mean"]["count"] == 4
