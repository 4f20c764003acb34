"""LFM2 at toy widths on the CPU (hidden 64; ``conv conv | attn conv conv
conv``: two dense layers under a gated short convolution of three taps, then
a whole period; 8 query heads on 2 key heads of 8 with head norms and the
rotary turn; a sigmoid router of 12 columns with a selection bias, top 3,
all held, no shared expert; tied embeddings): what this plan asks of the
shared code that no other plan does, each against its plain form (the
kernels under the Pallas interpreter, at the published head of 64), and the
model through ``InferenceEngine`` against the benchmark's reference
(``benchmarks/references/lfm2.py``, float32 at ``highest``) on seeded
weights. Probabilities over the whole vocabulary are compared, never an
argmax: with random weights the largest logit changes on rounding.

The cut is of depth alone (every expert, every head, every row of the
vocabulary is held), so the model-configs guide's test that the shares add up
to the whole has nothing to add up here."""

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
from storm_tpu.models import lfm2 as M  # noqa: E402
from storm_tpu.models.registry import build_model, load_or_init  # noqa: E402
from storm_tpu.ops import attention as A  # noqa: E402
from storm_tpu.ops import flash_attention as F  # noqa: E402
from storm_tpu.ops import kda  # noqa: E402
from storm_tpu.ops import rope as R  # noqa: E402
from storm_tpu.ops.platform import dispatch_notes  # noqa: E402
from storm_tpu.parallel.moe import (route_topk, topk_moe_init,  # noqa: E402
                                    topk_moe_layer)

REFERENCE = spec.plugin("references", "lfm2")
TINY = spec.config("lfm2_tiny")
SIZES = TINY["published"]
F32 = jnp.float32


def _distance(got, want):
    """Euclidean distance of each row from its reference row over that row's
    length: the benchmark's measure (``core/pairing.py``)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))


def _far(got, want):
    return float(jnp.abs(got.astype(F32) - want.astype(F32)).max())


def _windows(n, seed=11):
    return np.random.RandomState(seed).randint(0, 96, (n, 40)).astype(
        np.float32)


# ---- the gated short convolution -----------------------------------------------

def _shifted_sums(w, x, channels):
    """``c_t * (w_0 u_{t-2} + w_1 u_{t-1} + w_2 u_t)``, ``u = b * x``, written
    out tap by tap in float32."""
    b, c, u = (x[..., i * channels:(i + 1) * channels].astype(F32)
               for i in range(3))
    bu = b * u
    zero = jnp.zeros_like(bu[:, :1])
    back1 = jnp.concatenate([zero, bu[:, :-1]], 1)
    back2 = jnp.concatenate([zero, zero, bu[:, :-2]], 1)
    return c * (w[0] * back2 + w[1] * back1 + w[2] * bu)


def test_the_gated_convolution_is_three_shifted_sums():
    """Against the sums written out, at a window's first two positions too
    (tokens before the first read as zero), and the ranges in the order ``[b
    | c | u]``: another order is another answer."""
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    x = jax.random.normal(ks[0], (3, 40, 3 * 16), F32)
    p = kda.short_conv_init(ks[1], 16, 3)
    with dispatch_notes() as seen:
        got = kda.gated_conv(p, x)
    assert seen == ["gated_conv=xla"]
    want = _shifted_sums(p["w"], x, 16)
    np.testing.assert_allclose(got, want, atol=1e-5)
    b, c, u = x[..., :16], x[..., 16:32], x[..., 32:]
    np.testing.assert_allclose(got[:, 0], c[:, 0] * p["w"][2] * (b * u)[:, 0],
                               atol=1e-6)
    np.testing.assert_allclose(
        got[:, 1], c[:, 1] * (p["w"][1] * (b * u)[:, 0]
                              + p["w"][2] * (b * u)[:, 1]), atol=1e-6)
    swapped = jnp.concatenate([c, b, u], -1)
    assert _far(kda.gated_conv(p, swapped), want) > 0.1


@pytest.mark.parametrize("dtype,bound", [(F32, 1e-5), (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_the_gated_convolutions_kernel_is_its_xla_form(dtype, bound):
    """``conv_silu_kernel`` with its two gates under the interpreter: two
    rows of two blocks of 512 positions (a block hands its last rows on; a
    row starts from zeros), two lane tiles, the three ranges read where they
    lie in one array. Both forms round once, from float32."""
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    channels = 256
    x = jax.random.normal(ks[0], (2, 1024, 3 * channels), F32).astype(dtype)
    p = kda.short_conv_init(ks[1], channels, 3)
    got = kda.conv_silu_kernel(p["w"], None, x, activation=kda._as_is,
                               gates=(2 * channels, channels), rows=512,
                               interpret=True)
    want = kda.gated_conv(p, x)
    assert got.shape == (2, 1024, channels) and got.dtype == dtype
    assert _far(got, want) <= bound * float(jnp.abs(want.astype(F32)).max())
    # and both are the shifted sums
    plain = _shifted_sums(p["w"], x, channels)
    assert _far(got, plain) <= 2 * bound * float(jnp.abs(plain).max())


def test_conv_forms_rule_picks_the_gated_kernel_on_one_chip(monkeypatch):
    """``gated_conv`` goes by ``conv_form``, the rule ``conv_silu`` goes by:
    the kernel on a TPU in a process with one device for whole lane tiles
    and whole blocks of positions, XLA's form elsewhere."""
    seen = {}
    monkeypatch.setattr(kda, "_use_pallas", lambda: True)
    monkeypatch.setattr(kda, "_one_device", lambda: True)
    monkeypatch.setattr(
        kda, "conv_silu_kernel",
        lambda w, b, x, **kw: seen.update(kw) or x[..., :w.shape[1]])
    p = {"w": jnp.ones((3, 2048))}
    x = jax.ShapeDtypeStruct((8, 4096, 6144), jnp.bfloat16)
    with dispatch_notes() as notes:
        jax.eval_shape(lambda x: kda.gated_conv(p, x), x)
    assert notes == ["gated_conv=kernel"]
    assert seen["gates"] == (4096, 2048) and seen["activation"](3.0) == 3.0
    for shape in ((8, 4096 + 64, 6144), (8, 4096, 3 * 2000)):
        q = {"w": jnp.ones((3, shape[2] // 3))}
        with dispatch_notes() as notes:
            jax.eval_shape(lambda x: kda.gated_conv(q, x),
                           jax.ShapeDtypeStruct(shape, jnp.bfloat16))
        assert notes == ["gated_conv=xla"]
    monkeypatch.setattr(kda, "_one_device", lambda: False)
    with dispatch_notes() as notes:
        jax.eval_shape(lambda x: kda.gated_conv(p, x), x)
    assert notes == ["gated_conv=xla"]


def test_the_conv_kernels_program_without_gates_is_the_parents():
    """``conv_silu``'s two call sites (Nemotron's and Kimi-Linear's branches:
    five cells' plans) hand the kernel no gates, and its program is then the
    one it was: the same operands, the same index maps (no added zero), the
    same body."""
    p = kda.short_conv_init(jax.random.PRNGKey(0), 128, 4, bias=True)
    x = jnp.zeros((1, 512, 256), jnp.bfloat16)
    text = str(jax.make_jaxpr(lambda x: kda.conv_silu_kernel(
        p["w"], p["b"], x, interpret=False))(x))
    gated = str(jax.make_jaxpr(lambda x: kda.conv_silu_kernel(
        p["w"][:3], None, x, activation=kda._as_is, gates=(128, 0),
        interpret=False))(x))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == CONV_TEXT
    assert text != gated and "gated" not in text


# the first 16 hex digits of the sha256 of that jaxpr on the parent commit
CONV_TEXT = "eea83706abe60e9c"


# ---- heads of 64: the norm, the turn, the causal kernel -------------------------

def _tables(seq, d, theta=1e6):
    return R.rotary_tables(seq, theta ** (-2.0 * np.arange(d // 2) / d))


@pytest.mark.parametrize("heads", [8, 2])
def test_head_norm_and_turn_at_64_is_the_view_a_head_form(heads):
    """``_norm_turn_lanes`` under the interpreter at heads of 64, two a lane
    tile: against ``rmsnorm`` and ``rotate_halves`` on the view ``(B, S, H,
    64)``, in float32 from the same bfloat16 input. The norm alone and the
    turn alone by the same kernels."""
    from storm_tpu.ops import layers as L

    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    d, seq = 64, 256
    x = jax.random.normal(ks[0], (2, seq, heads * d), F32).astype(
        jnp.bfloat16)
    p = {"scale": 1.0 + 0.2 * jax.random.normal(ks[1], (d,), F32)}
    cos, sin = _tables(seq, d)
    view = x.reshape(2, seq, heads, d).astype(F32)
    normed = L.rmsnorm(p, view, 1e-5)
    want = R.rotate_halves(normed, cos[:, None], sin[:, None]).reshape(
        x.shape)
    scale = jnp.tile(p["scale"], 2).reshape(1, -1)
    got = R._norm_turn_lanes(x, scale, *R._lane_tables(cos, sin),
                             heads=heads, eps=1e-5, interpret=True)
    assert got.dtype == x.dtype
    assert _far(got, want) <= 2.0 ** -6 * float(jnp.abs(want).max())
    got = R._norm_turn_lanes(x, scale, heads=heads, eps=1e-5, interpret=True)
    assert _far(got, normed.reshape(x.shape)) <= 2.0 ** -6 * float(
        jnp.abs(normed).max())
    (got,) = R._turn_lanes((x,), *R._lane_tables(cos, sin), heads=heads,
                           interpret=True)
    want = R.rotate_halves(view, cos[:, None], sin[:, None]).reshape(x.shape)
    assert _far(got, want) <= 2.0 ** -6 * float(jnp.abs(want).max())


def test_turn_form_takes_heads_of_64_two_a_tile(monkeypatch):
    """What the rule gave every shape it gives still, and heads of 64 where
    the caller says they are whole tiles."""
    assert R.turn_form(16384, 128) == R.turn_form(4096, 64, 32) == "halves"
    monkeypatch.setattr(R, "_use_pallas", lambda: True)
    monkeypatch.setattr(R, "_one_device", lambda: True)
    assert R.turn_form(16384, 128) == R.turn_form(16384, 128, 5) == "lanes"
    assert R.turn_form(16384, 64) == "halves"  # unsaid: one head, half a tile
    assert R.turn_form(4096, 64, 32) == R.turn_form(4096, 64, 8) == "lanes"
    assert R.turn_form(4096, 64, 5) == R.turn_form(4096, 32, 8) == "halves"
    assert R.turn_form(4096 + 64, 64, 8) == "halves"
    monkeypatch.setattr(R, "_one_device", lambda: False)
    assert R.turn_form(4096, 64, 32) == "halves"


@pytest.mark.parametrize("hq,hkv", [(16, 4), (6, 2), (4, 4)],
                         ids=["four-a-key-head", "three-a-key-head",
                              "one-a-key-head"])
def test_the_causal_kernel_on_a_lane_tiles_two_heads_is_the_blocked_form(
        hq, hkv):
    """``flash_attention_merged`` under the interpreter at heads of 64: a
    block of k and v is a lane tile's two key heads, the query heads that
    read them are stacked with their channels in their key head's half and
    zeros in the other, and each head's half of the result is rotated to its
    place. Against ``causal_blocked`` on the view a head; a group of three
    puts two key heads' query heads into one lane tile of q."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    seq = 1024
    q, k, v = (jax.random.normal(key, (2, seq, n * 64), F32).astype(
        jnp.bfloat16) for key, n in zip(ks, (hq, hkv, hkv)))
    with dispatch_notes() as seen:
        want = A.causal_attention_merged(q, k, v, hq, hkv)
    assert seen == ["causal_attention=blocked"
                    + ("-grouped" if hq != hkv else "")]
    assert F.heads_a_lane_tile(64, 64, hkv) == 2
    block_q, block_k = F.causal_tiles(2 * hq // hkv)
    out = jnp.zeros_like(q)
    for row in range(2):
        out = F.flash_attention_merged(
            out, q, k, v, row, heads=hq, kv_heads=hkv, scale=0.125,
            block_q=block_q, block_k=block_k, interpret=True)
    assert _far(out, want) <= 2.0 ** -6 * float(jnp.abs(want).max())


def test_merged_form_takes_heads_of_64_in_pairs(monkeypatch):
    """What the rule gave every shape it gives still; heads of 64 are the
    kernel's where the key heads pair off, under twice the group's tile."""
    assert F.heads_a_lane_tile(128, 128, 4) == 1
    assert F.heads_a_lane_tile(64, 64, 7) == 1
    assert F.heads_a_lane_tile(64, 128, 8) == 1
    assert F.lane_width(64) == 128 and F.causal_tiles(8) == (64, 512)
    assert A.merged_form(32, 8, 4096, 64, 64) == "blocked"
    for module in (A,):
        monkeypatch.setattr(module, "_use_pallas", lambda: True)
        monkeypatch.setattr(module, "_one_device", lambda: True)
    assert A.merged_form(32, 8, 4096, 64, 64) == "kernel"
    assert A.merged_form(32, 7, 4096, 64, 64) == "blocked"
    assert A.merged_form(32, 8, 4096 + 64, 64, 64) == "blocked"
    assert A.merged_form(32, 8, 4096, 64, 128) == "blocked"
    assert A.merged_form(32, 8, 4096, 32, 32) == "blocked"
    assert A.causal_form(32, 8, 4096, 64, 64) == "blocked"  # head-split: pads
    assert A.merged_form(8, 2, 512, 128, 128) == "kernel"
    assert A.merged_form(8, 2, 512, 192, 128) == "blocked"
    assert A.merged_form(20, 4, 16384, 128, 128) == "kernel"
    monkeypatch.setattr(A, "_one_device", lambda: False)
    assert A.merged_form(32, 8, 4096, 64, 64) == "blocked"


def test_the_attention_mixer_on_one_chip_is_its_blocked_self(monkeypatch):
    """The whole attention operator with every kernel interpreted (the head
    norm and turn on a lane tile's two heads, the causal kernel on pairs of
    key heads) against the same operator as the CPU builds it."""
    import functools

    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    dim, heads, kv_heads, hd, seq = 128, 8, 2, 64, 512
    p = M.attention_mixer_init(ks[0], dim, heads, kv_heads, hd)
    x = jax.random.normal(ks[1], (2, seq, dim), F32).astype(jnp.bfloat16)
    p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p)
    rotary = _tables(seq, hd)
    with dispatch_notes() as seen:
        want = M.attention_mixer(p, x, heads, kv_heads, hd, 1e-5, rotary)
    assert seen == ["rotary_turn=halves", "causal_attention=blocked-grouped"]
    for module in (A, R):
        monkeypatch.setattr(module, "_use_pallas", lambda: True)
        monkeypatch.setattr(module, "_one_device", lambda: True)
    monkeypatch.setattr(R, "_norm_turn_lanes", functools.partial(
        R._norm_turn_lanes, interpret=True))
    monkeypatch.setattr(F, "flash_attention_merged", functools.partial(
        F.flash_attention_merged, interpret=True))
    with dispatch_notes() as seen:
        got = M.attention_mixer(p, x, heads, kv_heads, hd, 1e-5, rotary)
    assert seen == ["head_norm=kernel", "rotary_turn=lanes",
                    "causal_attention=kernel-grouped-merged-halves"]
    assert _far(got, want) <= 2.0 ** -5 * float(jnp.abs(
        want.astype(F32)).max())


# ---- the router -----------------------------------------------------------------

def test_the_bias_picks_and_the_unbiased_score_weighs():
    """With the selection bias some tokens go elsewhere, and a chosen
    expert's weight is its sigmoid over the chosen sigmoids' sum plus the
    published 1e-6: the bias is in the choice alone."""
    p = topk_moe_init(jax.random.PRNGKey(8), 32, 16, 12, shared=False)
    assert "shared" not in p and p["router_bias"].shape == (12,)
    tokens = jax.random.normal(jax.random.PRNGKey(9), (200, 32), F32)
    experts, weights = route_topk(p, tokens, 3, eps=1e-6)
    plain, _ = route_topk({k: v for k, v in p.items() if k != "router_bias"},
                          tokens, 3, eps=1e-6)
    moved = (np.sort(np.asarray(experts), -1)
             != np.sort(np.asarray(plain), -1)).any(-1)
    assert 0 < moved.sum() < 200
    score = jax.nn.sigmoid(jnp.dot(tokens, p["router"],
                                   precision=jax.lax.Precision.HIGHEST))
    chosen = jnp.take_along_axis(score, experts, -1)
    want = chosen / (chosen.sum(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(weights, want, rtol=1e-6)
    # the published epsilon is not the default's: 5e-7 of a weight apart
    _, other = route_topk(p, tokens, 3)
    gap = np.abs(np.asarray(other) - np.asarray(weights)).max()
    assert 0 < gap < 1e-6


def test_the_default_epsilon_lowers_to_the_parents_text():
    """``route_topk``'s ``eps`` is an argument whose default is what stood
    in its place: the seven expert cells' programs hand none over, and the
    layer's lowered text without one is the text with the old constant."""
    p = topk_moe_init(jax.random.PRNGKey(0), 32, 16, 12)
    x = jax.ShapeDtypeStruct((2, 40, 32), F32)

    def lowered(**kw):
        return jax.jit(lambda p, x: topk_moe_layer(
            p, x, 3, tile=16, **kw)).lower(p, x).as_text()

    assert lowered() == lowered(eps=1e-20)
    assert lowered() != lowered(eps=1e-6)
    assert "9.99999968E-21" in lowered()  # float32's 1e-20
    assert "9.99999968E-21" not in lowered(eps=1e-6)


# ---- the model ------------------------------------------------------------------

def _engine(dtype="float32"):
    return InferenceEngine(ModelConfig(
        name="lfm2_tiny", dtype=dtype, num_classes=96, input_shape=(40,),
        seed=5), batch_cfg=BatchConfig())


def test_model_through_the_engine_against_the_reference():
    model = build_model("lfm2_tiny")
    params, state = load_or_init(model, None, 5)
    x = _windows(16)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, s, xx: REFERENCE.forward(
            SIZES, p, s, xx))(params, state, x))
    eng = _engine()
    assert eng.batch_cfg.buckets == (4,) and eng.max_rows == 4
    assert eng.in_dtype == jnp.float32
    got = np.concatenate([eng.predict(x[a:a + 4]) for a in range(0, 16, 4)])
    assert got.shape == (16, 96)
    assert _distance(got, want).max() < 1e-4  # summation order: under 1e-5
    # no answer is its last id's own row of the tied matrix and little else
    assert want.max() < 0.5
    # every published switch is read: another value is another answer
    for key, other in (("use_expert_bias", False), ("norm_topk_prob", False),
                       ("routed_scaling_factor", 1.5),
                       ("num_experts_per_tok", 2), ("conv_L_cache", 2),
                       ("rope_parameters", {"rope_theta": 10.0})):
        with jax.default_matmul_precision("highest"):
            moved = np.asarray(REFERENCE.forward(
                {**SIZES, key: other}, params, state, x[:4]))
        # (the bias is N(0, 0.01^2): it moves some tokens' picks, not all)
        far = _distance(moved, want[:4])
        assert (far.max() if key == "use_expert_bias" else far.min()) \
            > 1e-4, key


def test_the_step_counts_and_the_inventory_names_the_forms():
    from storm_tpu.config import ShardingConfig
    from storm_tpu.infer.engine import engine_inventory, shared_engine

    eng = shared_engine(ModelConfig(
        name="lfm2_tiny", dtype="float32", num_classes=96,
        input_shape=(40,), seed=5), ShardingConfig(data_parallel=0),
        BatchConfig())
    eng.warmup()
    row = next(r for r in engine_inventory()["engines"]
               if r["model"] == "lfm2_tiny")
    forms = row["programs"][str(eng.pad_batch(4))].split(", ")
    assert set(forms) == {"gated_conv=xla", "gated_ffn=made-once",
                          "rotary_turn=halves",
                          "causal_attention=blocked-grouped",
                          "expert_ffn=swiglu", "expert_dispatch=sorted",
                          "expert_tiles=whole", "expert_combine=held-rows",
                          "combine_tiles=whole", "combine_write=once"}
    handle = eng.dispatch((_windows(4),))
    handle.future.result(60)
    aux = handle.aux
    assert aux["expert_tokens"].shape == (4, 12)  # four expert layers of six
    assert aux["expert_tokens"].sum(1).tolist() \
        == [eng.pad_batch(4) * 40 * 3] * 4  # padded rows are counted too
    assert aux["expert_absent"].tolist() == [0] * 4  # every expert is held


def test_registry_names_the_model_and_its_stage():
    model = build_model("lfm2_24b_a2b")
    assert model.input_shape == (4096,) and model.num_classes == 65536
    assert model.max_rows == 8
    kinds = ("conv", "conv") + ("full_attention", "conv", "conv", "conv") * 2
    assert model.hyper["layer_types"] == kinds
    assert (model.hyper["dense"], model.hyper["heads"],
            model.hyper["kv_heads"], model.hyper["head_dim"],
            model.hyper["taps"], model.hyper["top_k"],
            model.hyper["n_experts"], model.hyper["experts_held"],
            model.hyper["rope_theta"]) == (2, 32, 8, 64, 3, 4, 64, 64, 1e6)
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert set(params) == {"embed", "norm", "layers"}  # tied: no head
    assert all(set(blk) == {"norm1", "mixer", "norm2", "ffn"}
               for blk in params["layers"])
    assert tuple("conv" if "conv" in blk["mixer"] else "full_attention"
                 for blk in params["layers"]) == kinds
    assert tuple("router" in blk["ffn"] for blk in params["layers"]) \
        == (False,) * 2 + (True,) * 8
    conv, attn = (params["layers"][i]["mixer"] for i in (0, 2))
    assert conv["in"].shape == (2048, 6144)
    assert conv["conv"]["w"].shape == (3, 2048) and "b" not in conv["conv"]
    assert conv["out"].shape == (2048, 2048)
    assert attn["q"].shape == attn["o"].shape == (2048, 2048)
    assert attn["k"].shape == attn["v"].shape == (2048, 512)
    assert attn["q_norm"]["scale"].shape == attn["k_norm"]["scale"].shape \
        == (64,)
    dense, ffn = (params["layers"][i]["ffn"] for i in (1, 2))
    assert dense["gate"].shape == dense["up"].shape == (2048, 11776)
    assert ffn["router"].shape == (2048, 64)
    assert ffn["router_bias"].shape == (64,) and "shared" not in ffn
    assert ffn["experts"]["gate"].shape == (64, 2048, 1536)
    assert ffn["experts"]["down"].shape == (64, 1536, 2048)
    assert params["embed"].shape == (65536, 2048)
    assert {leaf.dtype for leaf in jax.tree.leaves(params)} \
        == {jnp.dtype(jnp.bfloat16)}
    # the issue's table, a layer at a time
    sizes = [sum(x.size for x in jax.tree.leaves(blk))
             for blk in params["layers"]]
    assert sizes == [89_139_200] * 2 + [614_600_896] + [620_898_368] * 3 \
        + [614_600_896] + [620_898_368] * 3
    assert params["embed"].size + params["norm"]["scale"].size == 134_219_776
    assert sum(x.size for x in jax.tree.leaves(params)) == 5_267_090_176
    # and the published whole: 2 dense, 28 + 10 expert layers, the matrix
    assert 2 * 89_139_200 + 28 * 620_898_368 + 10 * 614_600_896 \
        + 134_219_776 == 23_843_661_440
    assert state["aux"]["expert_tokens"].shape == (8, 64)
    with pytest.raises(ValueError):
        M.build_lfm2(
            "x", 8, (4,), layer_types=("conv", "mamba"), dense=1,
            published_layers=2, dim=8, heads=1, kv_heads=1, head_dim=8,
            taps=3, dense_width=8, expert_width=8, n_experts=2, top_k=1,
            experts_held=2)


# ---- the eleventh plan's own lines ---------------------------------------------------

# The ten plans that were there lower to their parents' text by
# tests/test_scorer.py, tests/test_trinity.py, tests/test_keye.py,
# tests/test_granite.py and tests/test_falcon_h1.py, whose lines this PR
# leaves as they were. The eleventh's, as this PR built it: the first 16 hex
# digits of the sha256 of the lowered text, of the tree ``init`` makes and,
# for the toy, of its leaves from key 7. (PR 69: all five by one count; the
# layer counts its combine's tiles, written and added, ``combine_tiles`` in
# ``aux``; the combine's loop, ``once`` here, is the parent's.)
LFM2 = {"lfm2_tiny": ('fef615da7ea9e9e0', '9c58c55048f575b1', '18c8b6a6fef81452'),
        "lfm2_24b_a2b": ('f6c4dc28548a7eda', 'b094edc318b169eb')}


def _digest(*chunks):
    h = hashlib.sha256()
    for chunk in chunks:
        h.update(chunk)
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(LFM2))
def test_the_eleventh_plan_lowers_to_its_own_text_and_makes_its_trees(name):
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
    assert got == LFM2[name]
