"""Kimi K2 at toy widths on the CPU (hidden 64, 4 heads, low-rank queries,
YaRN rotary over 32 original positions of 40, 16 experts top-2 of which 4 are
held): the rotary code against numbers worked by hand and against its
definition, the shared latent-attention mixer with and without the new parts,
the load in the served type, and the model through ``InferenceEngine``
against the benchmark's reference (``benchmarks/references/kimi_k2.py``,
float32 at ``highest``) on seeded weights. Probabilities over the whole
vocabulary are compared, never an argmax."""

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
from storm_tpu.config import BatchConfig, ModelConfig  # noqa: E402
from storm_tpu.infer.engine import InferenceEngine  # noqa: E402
from storm_tpu.models import kimi_k2 as K2  # noqa: E402
from storm_tpu.models import kimi_linear as K  # noqa: E402
from storm_tpu.models import registry  # noqa: E402
from storm_tpu.models.registry import build_model, load_or_init  # noqa: E402
from storm_tpu.ops import layers as L  # noqa: E402
from storm_tpu.ops import rope as R  # noqa: E402
from storm_tpu.ops.attention import causal_attention  # noqa: E402

REFERENCE = spec.plugin("references", "kimi_k2")
TINY = spec.config("kimi_k2_tiny")
SIZES = TINY["published"]
PUBLISHED = spec.config("kimi_k2_6")["published"]
MIXER = (4, 16, 8, 16, 24)  # heads, nope, rope, v_dim, kv_rank of the tiny


def _distance(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))


# ---- rotary ------------------------------------------------------------------

def test_yarn_at_the_published_numbers_against_values_worked_by_hand():
    """64 rotary channels, theta 50000, factor 64 over 4,096 positions, 32
    and 1 turns. ``64 ln(4096 / (2 pi n)) / (2 ln 50000)``: 8.91 for 32 turns
    (floor 8), 19.17 for one (ceiling 20). Pair 14 is half way up the ramp."""
    rs = PUBLISHED["rope_scaling"]
    args = (64, 50000.0, 64.0, 4096, 32, 1)
    assert (PUBLISHED["qk_rope_head_dim"], float(PUBLISHED["rope_theta"]),
            float(rs["factor"]), rs["original_max_position_embeddings"],
            rs["beta_fast"], rs["beta_slow"]) == args
    assert R.yarn_correction_range(64, 50000.0, 4096, 32, 1) == (8, 20)
    inv = R.yarn_inv_freq(*args)
    assert inv.shape == (32,)
    f = [50000.0 ** (-2 * i / 64) for i in range(32)]
    assert inv[0] == 1.0 and inv[8] == pytest.approx(f[8])  # kept
    assert f[8] == pytest.approx(0.066874, rel=1e-4)
    assert inv[14] == pytest.approx(0.0087937 * (0.5 / 64 + 0.5), rel=1e-4)
    assert inv[20] == pytest.approx(1.15658e-3 / 64, rel=1e-4)  # stretched
    assert inv[31] == pytest.approx(f[31] / 64)
    assert (np.diff(inv) < 0).all()
    m = R.yarn_mscale(64.0, rs["mscale_all_dim"])
    assert m == pytest.approx(0.1 * math.log(64) + 1) \
        and m * m == pytest.approx(2.0047, abs=5e-5)
    # the reference works them out on its own; both agree
    ref_inv, ref_scale, ref_factor = REFERENCE.yarn(PUBLISHED)
    np.testing.assert_allclose(ref_inv, inv, rtol=1e-12)
    assert ref_scale == pytest.approx(192 ** -0.5 * 2.0047, rel=1e-4)
    assert ref_factor == 1.0
    # nothing stretched: the plain frequencies and scale
    np.testing.assert_allclose(R.yarn_inv_freq(64, 50000.0, 1, 4096), f)
    assert R.yarn_mscale(1.0) == 1.0


def test_the_tiny_twin_has_plain_blended_and_stretched_pairs():
    rs = SIZES["rope_scaling"]
    assert R.yarn_correction_range(8, 10.0, 32, 4, 1) == (0, 3)
    inv = R.yarn_inv_freq(8, float(SIZES["rope_theta"]), rs["factor"],
                          rs["original_max_position_embeddings"],
                          rs["beta_fast"], rs["beta_slow"])
    f = 10.0 ** (-np.arange(4) / 4)
    np.testing.assert_allclose(
        inv, [f[0], f[1] * (2 / 3 + 1 / 12), f[2] * (1 / 3 + 1 / 6),
              f[3] / 4])


def test_rotary_keeps_norms_and_a_score_reads_the_distance_alone():
    inv = R.yarn_inv_freq(8, 10.0, 4.0, 32, 4, 1)
    cos, sin = R.rotary_tables(40, inv)
    assert cos.shape == sin.shape == (40, 4) and cos.dtype == jnp.float32
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    q = jnp.broadcast_to(jax.random.normal(ks[0], (8,)), (40, 8))
    k = jnp.broadcast_to(jax.random.normal(ks[1], (8,)), (40, 8))
    qt, kt = R.rotate_halves(q, cos, sin), R.rotate_halves(k, cos, sin)
    np.testing.assert_allclose(jnp.linalg.norm(qt, axis=-1),
                               jnp.linalg.norm(q, axis=-1), rtol=1e-5)
    scores = np.asarray(qt @ kt.T)  # [t, s]: the same vectors at every place
    for t, s in ((10, 4), (26, 20), (39, 33)):
        assert scores[t, s] == pytest.approx(scores[6, 0], abs=1e-4)
    assert abs(scores[6, 0] - scores[7, 0]) > 1e-3
    np.testing.assert_allclose(qt[0], q[0], atol=1e-6)  # position 0: no turn


def test_reordered_weights_and_turned_halves_give_the_interleaved_scores():
    """The checkpoint pairs channels (2i, 2i + 1). The loader reorders a
    projection's output columns to (evens, odds), once; the program and the
    reference turn halves. Same scores as the pairs turned where they lay
    (here as complex numbers); the reorder moves values and rounds nothing."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(ks[0], (40, 16))
    wq, wk = (jax.random.normal(k, (16, 8)) for k in ks[1:])
    moved = R.halves_first(wq)
    np.testing.assert_array_equal(
        moved, jnp.concatenate([wq[:, 0::2], wq[:, 1::2]], -1))
    np.testing.assert_array_equal(
        R.halves_first(wq, first=2),
        jnp.concatenate([wq[:, :2], wq[:, 2::2], wq[:, 3::2]], -1))
    as_bf16 = R.halves_first(wq.astype(jnp.bfloat16))
    assert as_bf16.dtype == jnp.bfloat16
    np.testing.assert_array_equal(as_bf16, moved.astype(jnp.bfloat16))
    inv = R.yarn_inv_freq(8, 10.0, 4.0, 32, 4, 1)
    cos, sin = R.rotary_tables(40, inv)
    angle = jnp.arange(40.0)[:, None] * jnp.asarray(inv, jnp.float32)[None]

    def in_place(y):  # pair (2i, 2i + 1) as one complex number, turned
        y = np.asarray(y, np.float64)
        return (y[:, 0::2] + 1j * y[:, 1::2]) * np.exp(
            1j * np.asarray(angle, np.float64))

    with jax.default_matmul_precision("highest"):
        q, k = in_place(x @ wq), in_place(x @ wk)
        want = (q @ k.conj().T).real
        for turn in (lambda y: R.rotate_halves(y, cos, sin),
                     lambda y: REFERENCE._turn(y, angle, 1.0)):
            got = turn(x @ R.halves_first(wq)) @ turn(x @ R.halves_first(wk)).T
            np.testing.assert_allclose(got, want, atol=2e-5)


# ---- the shared mixer --------------------------------------------------------

def _mla_mixer_before(p, x, heads, nope, rope, v_dim, kv_rank, eps):
    """``models/kimi_linear.py mla_mixer`` as it stood before low-rank
    queries and rotary (PR 42), word for word."""
    b, s, _ = x.shape
    q = K._proj(x, p["q"]).reshape(b, s, heads, nope + rope)
    kv_a = K._proj(x, p["kv_a"])
    latent = L.rmsnorm(p["kv_norm"], kv_a[..., :kv_rank], eps)
    k_shared = kv_a[..., kv_rank:]
    kv = K._proj(latent, p["kv_b"]).reshape(b, s, heads, nope + v_dim)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_shared[:, :, None, :], (b, s, heads, rope))], -1)
    out = causal_attention(*(y.transpose(0, 2, 1, 3)
                             for y in (q, k, kv[..., nope:])),
                           scale=(nope + rope) ** -0.5)
    return K._proj(out.transpose(0, 2, 1, 3).reshape(b, s, heads * v_dim),
                   p["o"])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_without_low_rank_queries_and_rotary_the_mixer_is_bit_equal(dtype):
    """``kimi_linear_48b`` must not move: the same draws from the same key,
    the same operations in the same order."""
    rng = jax.random.PRNGKey(4)
    p = K.mla_mixer_init(rng, 64, *MIXER)
    ks = jax.random.split(rng, 4)
    assert sorted(p) == ["kv_a", "kv_b", "kv_norm", "o", "q"]
    for name, key, shape in (("q", ks[0], (64, 96)), ("kv_a", ks[1], (64, 32)),
                             ("kv_b", ks[2], (24, 128)), ("o", ks[3], (64, 64))):
        np.testing.assert_array_equal(
            p[name], L.lecun_normal(key, shape, shape[0]))
    p = jax.tree.map(lambda a: a.astype(dtype), p)
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 40, 64)).astype(dtype)
    now = jax.jit(lambda p, x: K.mla_mixer(p, x, *MIXER, 1e-5))(p, x)
    before = jax.jit(lambda p, x: _mla_mixer_before(p, x, *MIXER, 1e-5))(p, x)
    np.testing.assert_array_equal(np.asarray(now, np.float32),
                                  np.asarray(before, np.float32))


def _rotary_mixer():
    p = K.mla_mixer_init(jax.random.PRNGKey(6), 64, *MIXER, q_rank=24)
    assert sorted(p) == ["kv_a", "kv_b", "kv_norm", "o", "q_a", "q_b",
                         "q_norm"]
    inv, scale, factor = REFERENCE.yarn(SIZES)
    rotary = R.rotary_tables(40, inv, factor)
    return p, lambda x: K.mla_mixer(p, x, *MIXER, 1e-5, rotary=rotary,
                                    scale=scale)


def test_mla_mixer_with_low_rank_queries_and_rotary_against_the_reference():
    p, mixer = _rotary_mixer()
    x = jax.random.normal(jax.random.PRNGKey(7), (3, 40, 64))
    with jax.default_matmul_precision("highest"):
        got = mixer(x)
        want = jnp.stack([REFERENCE._mla(p, row, SIZES, 1e-5) for row in x])
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_a_later_token_changes_no_earlier_output():
    _, mixer = _rotary_mixer()
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 40, 64))
    later = x.at[:, 30:].set(jax.random.normal(jax.random.PRNGKey(9),
                                               (2, 10, 64)))
    a, b = mixer(x), mixer(later)
    np.testing.assert_array_equal(np.asarray(a[:, :30]), np.asarray(b[:, :30]))
    assert float(jnp.abs(a[:, 30:] - b[:, 30:]).max()) > 1e-3


# ---- the load ----------------------------------------------------------------

def test_the_initialiser_hands_over_the_served_type_and_astypes_values(
        monkeypatch):
    """A leaf is drawn in float32, scaled, cast: what ``astype`` of the
    float32 tree gives, without that tree ever standing beside it. The
    engine's cast leaves such leaves alone and serves them."""
    key = jax.random.PRNGKey(5)
    served, _ = K2.build_kimi_k2_tiny(param_dtype=jnp.bfloat16).init(key)
    drawn, _ = build_model("kimi_k2_tiny").init(key)
    assert {a.dtype for a in jax.tree.leaves(served)} == {
        jnp.dtype(jnp.bfloat16)}
    assert {a.dtype for a in jax.tree.leaves(drawn)} == {
        jnp.dtype(jnp.float32)}
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a, np.float32), np.asarray(b.astype(jnp.bfloat16),
                                              np.float32)), served, drawn)
    # the loader's reorder, once: a checkpoint's interleaved rotary columns
    # stand as halves in the leaves, and no step moves them again (the draw
    # in one program with its scale differs from the eager one by an ulp)
    plain = K.mla_mixer_init(jax.random.split(key, 2 * 3 + 2)[2], 64, *MIXER,
                             q_rank=24)
    q_b = plain["q_b"].reshape(24, 4, 24)
    np.testing.assert_allclose(
        drawn["layers"][0]["mixer"]["q_b"], jnp.concatenate(
            [q_b[..., :16], q_b[..., 16::2], q_b[..., 17::2]],
            -1).reshape(24, 96), rtol=1e-6)
    np.testing.assert_allclose(
        drawn["layers"][0]["mixer"]["kv_a"], jnp.concatenate(
            [plain["kv_a"][:, :24], plain["kv_a"][:, 24::2],
             plain["kv_a"][:, 25::2]], -1), rtol=1e-6)
    monkeypatch.setitem(
        registry._BUILDERS, "kimi_k2_tiny_served",
        lambda **kw: K2.build_kimi_k2_tiny(param_dtype=jnp.bfloat16, **kw))
    engines = [InferenceEngine(ModelConfig(
        name=name, dtype="bfloat16", num_classes=96, input_shape=(40,),
        seed=5), batch_cfg=BatchConfig())
        for name in ("kimi_k2_tiny_served", "kimi_k2_tiny")]
    jax.tree.map(lambda a, b, c: np.testing.assert_array_equal(
        np.asarray(a, np.float32), np.asarray(b, np.float32))
        or np.testing.assert_array_equal(np.asarray(a, np.float32),
                                         np.asarray(c, np.float32)),
        engines[0].params, engines[1].params, served)


def test_registry_names_the_model_its_share_and_its_type():
    model = build_model("kimi_k2_6")
    assert model.input_shape == (4096,) and model.num_classes == 20480
    assert model.max_rows == 4 and model.input_dtype == "float32"
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert len(params["layers"]) == 5
    assert [("router" in blk["ffn"]) for blk in params["layers"]] == [
        False, True, True, True, True]
    assert params["layers"][0]["ffn"]["gate"].shape == (7168, 18432)
    ffn = params["layers"][1]["ffn"]
    assert ffn["experts"]["gate"].shape == (12, 7168, 2048)
    assert ffn["router"].shape == (7168, 384)
    mixer = params["layers"][3]["mixer"]
    assert mixer["q_a"].shape == (7168, 1536)
    assert mixer["q_b"].shape == (1536, 64 * 192)
    assert mixer["kv_a"].shape == (7168, 576)
    assert mixer["kv_b"].shape == (512, 64 * 256)
    assert mixer["o"].shape == (8192, 7168)
    assert sum(x.size for x in jax.tree.leaves(params)) == 3_496_763_904
    assert {x.dtype for x in jax.tree.leaves(params)} == {
        jnp.dtype(jnp.bfloat16)}
    assert state["aux"]["expert_tokens"].shape == (4, 12)
    assert "kimi_k2_6" in registry.registry_names()


# ---- the whole model through the engine --------------------------------------

def _windows(n, seed=3):
    return spec.plugin("inputs", "kimi_k2_tokens").make(
        n, (40,), seed).astype(np.float32)


@pytest.fixture(scope="module")
def reference_rows():
    model = build_model("kimi_k2_tiny")
    params, state = load_or_init(model, None, 5)
    x = _windows(16)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, s, xx: REFERENCE.forward(SIZES, p, s, xx))(
            params, state, x)
    return x, np.asarray(want)


def _engine(dtype):
    return InferenceEngine(ModelConfig(
        name="kimi_k2_tiny", dtype=dtype, num_classes=96, input_shape=(40,),
        seed=5), batch_cfg=BatchConfig())


@pytest.fixture(scope="module")
def float32_engine():
    """One engine for the tests that read it and change nothing: through
    ``shared_engine``, so that the inventory lists it."""
    from storm_tpu.config import ShardingConfig
    from storm_tpu.infer.engine import shared_engine

    eng = shared_engine(ModelConfig(
        name="kimi_k2_tiny", dtype="float32", num_classes=96,
        input_shape=(40,), seed=5), ShardingConfig(data_parallel=0),
        BatchConfig())
    eng.warmup()
    return eng


FLOAT32_TOLERANCE = 1e-5  # summation order alone: reads about 3e-7


def test_model_through_the_engine_in_float32(reference_rows, float32_engine):
    x, want = reference_rows
    eng = float32_engine
    got = np.concatenate([eng.predict(x[a:a + 4]) for a in range(0, 16, 4)])
    assert got.shape == (16, 96)
    assert _distance(got, want).max() < FLOAT32_TOLERANCE


def test_bfloat16_is_held_to_its_own_tolerance_and_fails_float32s(
        reference_rows):
    """As ``tests/test_kimi_linear.py``: bfloat16 branches beside a float32
    stream read about 0.01 from the reference, a row whose last token a
    rounding sent to another of the 16 narrow experts far more; the float32
    tolerance fails on every row."""
    x, want = reference_rows
    eng = _engine("bfloat16")
    got = np.concatenate([eng.predict(x[a:a + 4]) for a in range(0, 16, 4)])
    err = _distance(got, want)
    assert np.median(err) < 0.03 and err.max() < 0.5
    assert err.min() > FLOAT32_TOLERANCE


def test_the_inventory_names_the_forms(float32_engine):
    from storm_tpu.infer.engine import engine_inventory

    eng = float32_engine
    row = next(r for r in engine_inventory()["engines"]
               if r["model"] == "kimi_k2_tiny")
    assert list(row["programs"]) == [str(eng.pad_batch(4))]
    forms = row["programs"][str(eng.pad_batch(4))].split(", ")
    assert {"rotary=yarn", "causal_attention=blocked", "expert_ffn=swiglu",
            "expert_dispatch=sorted", "expert_combine=held-rows"} <= set(forms)


def test_device_counters_ride_the_result():
    """Two expert layers, four held experts of sixteen, top-2: a step of 4
    windows of 40 tokens makes 320 assignments a layer (an engine of its own:
    one over all the devices pads the step to a row each)."""
    eng = _engine("float32")
    handle = eng.dispatch((_windows(4),))
    handle.future.result(60)
    aux = handle.aux
    assert aux["expert_tokens"].shape == (2, 4)
    per_layer = aux["expert_tokens"].sum(1) + aux["expert_absent"]
    assert per_layer.tolist() == [320] * 2
    assert 0 < aux["expert_tokens"].sum() < 320
