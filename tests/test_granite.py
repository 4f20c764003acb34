"""Granite 4.0-H at toy widths on the CPU (hidden 32; pattern ``MMAM``; 6
Mamba-2 heads of 4 on **one** B/C group of state 8, a window of 40 in chunks
of 16; 4 query heads on 2 key heads of 8 under a scale of 0.25; a softmax
router of 9 columns, top 3, SwiGLU experts of 32 and a shared expert of 64;
tied embeddings; all four multipliers off 1): what this plan asks of the
shared code that no other plan does, each against its plain form, and the
model through ``InferenceEngine`` against the benchmark's reference
(``benchmarks/references/granite.py``, float32 at ``highest``) on seeded
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
from storm_tpu.models import granite as G  # noqa: E402
from storm_tpu.models import scorer as S  # noqa: E402
from storm_tpu.models.nemotron_h import (gqa_mixer, gqa_mixer_init,  # noqa: E402
                                         mamba_mixer, mamba_mixer_init)
from storm_tpu.models.registry import build_model, load_or_init  # noqa: E402
from storm_tpu.ops import ssd  # noqa: E402
from storm_tpu.ops.parity_checks import ssd_recurrence  # noqa: E402
from storm_tpu.ops.platform import dispatch_notes  # noqa: E402
from storm_tpu.parallel import moe  # noqa: E402
from storm_tpu.parallel.moe import (route_topk, topk_moe_init,  # noqa: E402
                                    topk_moe_layer)

REFERENCE = spec.plugin("references", "granite")
TINY = spec.config("granite_h_tiny")
SIZES = TINY["published"]
DIM, EPS = 32, 1e-5


def _distance(got, want):
    """Euclidean distance of each row from its reference row over that row's
    length: the benchmark's measure (``core/pairing.py``)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))


def _close(got, want, rel=1e-5):
    np.testing.assert_allclose(got, want, atol=rel * float(
        jnp.abs(want).max()))


# ---- the scan on one group -----------------------------------------------------

def test_the_cells_step_holds_twice_the_state_fast_memory_keeps():
    """The published mixer (128 heads of 64 on a state of 128) at the
    cell's 8 rows a step holds 32 MiB of float32 state, twice what the
    compiler keeps in fast memory: on one chip the scan is the kernel, here
    the loop over chunks; 4 rows (the smaller bucket ``max_rows`` would
    leave) are kept, and so is every toy."""
    wide = spec.config("granite_4_h_small")
    sizes = wide["published"]
    held = sizes["held"]
    shape = (sizes["mamba_n_heads"], sizes["mamba_d_head"],
             sizes["mamba_d_state"])
    assert shape == (128, 64, 128) and held["rows_per_step"] == 8
    assert 4 * 8 * 128 * 64 * 128 == 2 * ssd._STATE_KEPT_BYTES
    assert ssd.scan_form(8, 4096, 128, 64, 1, 128, 128) == "chunked"


@pytest.mark.parametrize("step", [1e-4, 0.05, 10.0],
                         ids=["decay-near-1", "a-few-tokens", "decay-near-0"])
@pytest.mark.parametrize("chunk", [8, 16, 40])
def test_scan_at_one_group_is_the_recurrence(chunk, step):
    """6 heads of 4 on one group of state 8 over 40 tokens, ``x | B | C``
    side by side as the mixer's convolution writes them: five chunks of 8,
    two and a half of 16 (the tail padded), one of 40. ``C B^T`` is formed
    once for all six heads."""
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    shape = (3, 40)
    x = jax.random.normal(ks[0], shape + (6, 4))
    dt = step * jax.nn.softplus(jax.random.normal(ks[1], shape + (6,)))
    a = -jax.random.uniform(ks[2], (6,), minval=1.0, maxval=16.0)
    b, c = (jax.random.normal(k, shape + (1, 8)) for k in ks[3:5])
    d = jax.random.normal(ks[5], (6,))
    held = jnp.concatenate([y.reshape(shape + (-1,)) for y in (x, b, c)], -1)
    with jax.default_matmul_precision("highest"), dispatch_notes() as seen:
        got = ssd.ssd_chunked_columns(held, dt, a, d, 1, 8, chunk=chunk)
        want = ssd_recurrence(x, dt, a, b, c, d)
    assert seen == ["ssd_scan=chunked"]
    _close(got.reshape(x.shape), want)


def test_the_scan_past_fast_memory_is_a_kernel_on_one_chip_only(monkeypatch):
    """``scan_form`` reads the shapes and what the process runs on: the
    cell's step is the kernel on a TPU in a process with one device and the
    loop over chunks here; a state that is kept is the loop everywhere; a
    state past it that is not the kernel's (several groups, a sequence of
    no whole chunks, a chunk of no whole lane tiles, heads short of a lane
    tile's worth, heads that are no whole steps of the kernel's, a step of
    less than a lane tile) keeps the loop."""
    step = dict(rows=8, seq=4096, heads=128, head_dim=64, groups=1,
                state=128, chunk=128)
    assert ssd.scan_form(**step) == "chunked"
    monkeypatch.setattr(ssd, "_use_pallas", lambda: True)
    monkeypatch.setattr(ssd, "_one_device", lambda: True)
    assert ssd.scan_form(**step) == "kernel-rows1-heads64"
    # a longer chunk takes fewer heads a step: 64 at 256 ran out of VMEM
    assert ssd.scan_form(**{**step, "chunk": 256}) == "kernel-rows1-heads32"
    assert ssd.kernel_heads(128, 512) == 16 and ssd.kernel_heads(32, 128) == 32
    assert ssd.scan_form(**{**step, "rows": 4}) == "chunked"
    assert ssd.scan_form(**{**step, "rows": 16}) == "kernel-rows1-heads64"
    for other in ({"groups": 8}, {"seq": 4000}, {"chunk": 64},
                  {"heads": 192}, {"state": 384},
                  # 21 heads a step: 1,344 lanes, and 126 of the 128 heads
                  {"chunk": 384, "seq": 3072},
                  # one head a step: half a lane tile, no tile written
                  {"chunk": 8192, "seq": 8192}):
        assert ssd.scan_form(**{**step, **other}) == "chunked", other
    # Nemotron's and MiniCPM-SALA's steps: kept, whatever runs them
    assert ssd.scan_form(8, 4096, 64, 64, 8, 128, 128) == "chunked"
    assert ssd.scan_form(4, 16384, 32, 128, 32, 128, 128) == "chunked"
    monkeypatch.setattr(ssd, "_one_device", lambda: False)
    assert ssd.scan_form(**step) == "chunked"


@pytest.mark.parametrize("dtype,bound", [(jnp.float32, 2e-5),
                                         (jnp.bfloat16, 2.0 ** -7)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("columns", [True, False],
                         ids=["columns", "separate"])
def test_the_scans_kernel_is_the_recurrence(columns, dtype, bound):
    """``ssd_kernel`` interpreted here at the published mixer (128 heads of
    64, a state of 128, one group) in the two steps of 64 heads that ship
    (the second finds its heads' columns by a rotation of the lanes), two
    rows of two chunks of 128: the state is carried from a row's first chunk
    to its second in scratch and starts at zero in the second row; ``x | B |
    C`` side by side or as arrays of their own. Against the recurrence token by
    token in float32, and within a rounding of the loop over chunks: the
    same sums, the step ``dt`` inside the table's exponent and the state's
    weight on ``B`` where the loop puts it on ``x``."""
    b, s, h, p, n, q = 2, 256, 128, 64, 128, 128
    assert ssd.kernel_heads(h, q) == 64
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    x = jax.nn.silu(jax.random.normal(ks[0], (b, s, h, p))).astype(dtype)
    bb, cc = (jax.random.normal(k, (b, s, 1, n)).astype(dtype)
              for k in ks[1:3])
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, s, h)) - 2.0)
    a = -jax.random.uniform(ks[4], (h,), minval=1.0, maxval=16.0)
    d = jax.random.normal(ks[5], (h,))
    flat = [y.reshape(b, s, -1) for y in (x, bb, cc)]
    with jax.default_matmul_precision("highest"):
        want = ssd_recurrence(*(y.astype(jnp.float32) for y in (x, dt, a, bb,
                                                                cc, d)))
        loop = ssd.ssd_chunked(x, dt, a, bb, cc, d, chunk=q)
        runs = jnp.cumsum((dt * a).reshape(b, s // q, q, h), 2).reshape(
            b, s, h)
        got = ssd.ssd_kernel(
            jnp.concatenate(flat, -1) if columns else flat[0],
            () if columns else tuple(flat[1:]), dt, runs, d, n=n, chunk=q,
            interpret=True).reshape(x.shape)
    assert got.dtype == dtype
    top = float(jnp.abs(want).max())
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) < bound * top
    assert float(jnp.abs(got.astype(jnp.float32) - loop.astype(
        jnp.float32)).max()) < bound * top


def test_mamba_mixer_on_one_group_against_the_reference_row_by_row():
    """The mixer whole: 56 columns of projection, the convolution with its
    bias over 40 channels, the scan, the gate before one norm over all 24
    channels."""
    p = mamba_mixer_init(jax.random.PRNGKey(2), DIM, 6, 4, 1, 8, 4)
    assert p["in_proj"].shape == (DIM, 24 + 24 + 16 + 6)
    assert p["conv"]["w"].shape == (4, 40) and p["conv"]["b"].shape == (40,)
    assert p["norm"]["scale"].shape == (24,)
    p["norm"]["scale"] = 1.0 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(3), (24,))
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 40, DIM))
    with jax.default_matmul_precision("highest"):
        got = mamba_mixer(p, x, 6, 4, 1, 8, 16, EPS)
        want = jnp.stack([REFERENCE._mamba(p, row, SIZES, EPS) for row in x])
    _close(got, want)


# ---- attention under a scale that is not the root's ----------------------------------

def test_attention_takes_the_published_multiplier():
    p = gqa_mixer_init(jax.random.PRNGKey(5), DIM, 4, 2, 8)
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(6), (2, 40, DIM))
    with jax.default_matmul_precision("highest"):
        got = gqa_mixer(p, x, 4, 2, 8, scale=0.25)
        want = jnp.stack([REFERENCE._attention(p, row, SIZES) for row in x])
        root = gqa_mixer(p, x, 4, 2, 8)  # as every other plan calls it
        same = gqa_mixer(p, x, 4, 2, 8, scale=8 ** -0.5)
    assert SIZES["attention_multiplier"] == 0.25 != 8 ** -0.5
    _close(got, want)
    assert np.array_equal(np.asarray(root), np.asarray(same))
    assert float(jnp.abs(got - root).max()) > 1e-3 * float(
        jnp.abs(want).max())


# ---- the tied head and the multipliers -----------------------------------------------

def test_a_tied_scorer_has_no_head_and_reads_the_embeddings_rows():
    """A plan of no blocks: ``logits = RMSNorm(scale_emb E[id]) logit_scale
    E^T`` at the last position, from a tree without a ``head``."""
    model = S.token_scorer("tied", 24, (6,), (), dim=16, eps=EPS, hyper={},
                           max_rows=2, scale_emb=3.0, logit_scale=0.5,
                           tied=True, embed_std=0.2)
    params, state = model.init(jax.random.PRNGKey(7))
    assert set(params) == {"embed", "norm", "layers"} and state == {}
    assert params["embed"].shape == (24, 16) and params["layers"] == []
    assert 0.15 < float(params["embed"].std()) < 0.25
    params["norm"]["scale"] = 1.0 + 0.2 * jax.random.normal(
        jax.random.PRNGKey(8), (16,))
    x = np.random.default_rng(0).integers(0, 24, (2, 6)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        logits, _ = model.apply(params, state, x)
        h = 3.0 * params["embed"][x[:, -1].astype(int)]
        last = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + EPS) \
            * params["norm"]["scale"]
        want = (0.5 * last) @ params["embed"].T
    _close(logits, want)
    # without ``embed_std`` the stream starts at N(0, 1) a channel
    plain = S.token_scorer("tied", 24, (6,), (), dim=16, eps=EPS, hyper={},
                           max_rows=2, scale_emb=3.0, tied=True)
    embed = plain.init(jax.random.PRNGKey(7))[0]["embed"]
    np.testing.assert_allclose(embed * 3.0 * 0.2, params["embed"], atol=1e-6)
    with pytest.raises(ValueError):
        S.token_scorer("tied", 24, (6,), (), dim=16, eps=EPS, hyper={},
                       max_rows=2, tied=True, heads=2)


def test_the_served_type_is_read_off_a_leaf_every_model_has():
    model = build_model("granite_h_tiny")
    params, state = model.init(jax.random.PRNGKey(0))
    assert "head" not in params
    assert params["embed"].shape == (96, DIM)
    x = jax.ShapeDtypeStruct((2, 40), jnp.float32)
    served = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    assert jax.eval_shape(model.apply, served, state, x)[0].dtype \
        == jnp.bfloat16
    assert jax.eval_shape(model.apply, params, state, x)[0].dtype \
        == jnp.float32


# ---- the softmax router with a shared expert and no bias --------------------------------

def _layer(held=9, first=0, seed=9):
    """A layer of nine experts, ``held`` of them here from ``first`` on."""
    p = topk_moe_init(jax.random.PRNGKey(seed), DIM, 32, 9, shared_hidden=64,
                      selection_bias=False)
    p["router"] = 2.0 * p["router"]  # weights well apart
    p["experts"] = {n: w[first:first + held] for n, w in p["experts"].items()}
    return p


def test_the_router_is_the_softmax_over_the_chosen_logits():
    p = _layer()
    assert "router_bias" not in p and p["router"].shape == (DIM, 9)
    assert p["shared"]["up"].shape == (DIM, 64)
    assert p["experts"]["gate"].shape == (9, DIM, 32)
    x = jax.random.normal(jax.random.PRNGKey(10), (50, DIM))
    chosen, weight = route_topk(p, x, 3, router="softmax", renormalize=True,
                                scale=1.0)
    with jax.default_matmul_precision("highest"):
        logits, want = jax.lax.top_k(x @ p["router"], 3)
    assert np.array_equal(np.asarray(chosen), np.asarray(want))
    np.testing.assert_allclose(weight, jax.nn.softmax(logits, -1), atol=1e-6)
    np.testing.assert_allclose(weight.sum(-1), 1.0, atol=1e-6)


def test_the_plans_router_comes_in_antithetic_pairs():
    """Column ``2i + 1`` is the negative of column ``2i`` (the ninth of nine
    stays as drawn), to the bit in the served type too; nothing else of the
    layer moves, and each chip's half of 72 holds whole pairs."""
    drawn = _layer()
    paired = G.paired_router(drawn)
    router = np.asarray(paired["router"])
    assert router.shape == (DIM, 9)
    assert np.array_equal(router[:, 1:8:2], -router[:, 0:8:2])
    assert np.array_equal(router[:, 0:8:2],
                          np.asarray(drawn["router"])[:, 0:8:2])
    assert np.array_equal(router[:, 8], np.asarray(drawn["router"])[:, 8])
    assert all(paired[k] is drawn[k] for k in ("experts", "shared"))
    wide = G.paired_router({"router": jax.random.normal(
        jax.random.PRNGKey(15), (DIM, 72))})["router"].astype(jnp.bfloat16)
    assert np.array_equal(np.asarray(wide[:, 1::2], np.float32),
                          -np.asarray(wide[:, 0::2], np.float32))
    for blk in build_model("granite_h_tiny").init(
            jax.random.PRNGKey(0))[0]["layers"]:
        r = np.asarray(blk["ffn"]["router"])
        assert np.array_equal(r[:, 1:8:2], -r[:, 0:8:2])
    # a token's ten are the positive members of its ten largest pairs
    x = jax.random.normal(jax.random.PRNGKey(16), (64, DIM))
    chosen, _ = route_topk({"router": wide.astype(jnp.float32)}, x, 10,
                           router="softmax")
    assert all(len({int(e) // 2 for e in row}) == 10
               for row in np.asarray(chosen))


def test_the_layer_is_its_formula_with_the_shared_expert_at_its_own_width():
    p = _layer()
    x = jax.random.normal(jax.random.PRNGKey(11), (3, 37, DIM))
    sizes = {"num_experts_per_tok": 3}
    with jax.default_matmul_precision("highest"):
        with dispatch_notes() as seen:
            got, tokens, absent = topk_moe_layer(
                p, x, 3, router="softmax", scale=1.0, tile=16)
        want = jnp.stack([REFERENCE._experts(p, row, sizes) for row in x])
    assert "expert_ffn=swiglu" in seen
    _close(got, want)
    assert int(tokens.sum()) == 3 * 111 and int(absent) == 0


def test_the_two_uneven_shares_add_up_to_the_whole_layer():
    """Two chips share a router of nine: one holds experts 0-4, the other
    5-8. The parts they compute, with the shared expert that both compute
    alike counted once, are the uncut reference layer; the assignments one
    sees as absent are those the other holds."""
    whole_p = _layer()
    x = jax.random.normal(jax.random.PRNGKey(12), (111, DIM))
    sizes = {"num_experts_per_tok": 3}
    with jax.default_matmul_precision("highest"):
        whole = REFERENCE._experts(whole_p, x, sizes)
        shared = REFERENCE._swiglu(whole_p["shared"], x)
        total, seen, away = jnp.zeros_like(x), 0, []
        for first, held in ((0, 5), (5, 4)):
            share = _layer(held, first)
            assert share["experts"]["down"].shape[0] == held
            y, tokens, absent = topk_moe_layer(
                share, x, 3, first_expert=first, router="softmax",
                scale=1.0, tile=16)
            assert tokens.shape == (held,)
            assert int(tokens.sum()) + int(absent) == 3 * 111
            total, seen = total + (y - shared), seen + int(tokens.sum())
            away.append(int(absent))
            part = REFERENCE._experts(
                share, x, {**sizes, "held": {"first_expert": first}})
            _close(y, part)
        total = total + shared
    assert seen == 3 * 111 and sum(away) == 3 * 111
    _close(total, whole)


# ---- the combine at a half held of ten a token ------------------------------------------

def test_the_combine_at_the_cells_shapes_is_a_segment_sum():
    """32,768 tokens, ten assignments each, half of them held, rows of 8
    channels: blocks of 96 tokens (480 expected assignments, a tile of 512
    at one size), 341.33 of them, so the result is a real slice of the 342
    blocks' sums; a block's run may pass a tile (480 +- 15 of 512: a few
    of 342 do), so every block's first tile writes and those few blocks'
    second adds."""
    n, top_k, dim, rows = 32768, 10, 8, (640 + 36) * 512
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(13), 3)
    out = jnp.concatenate([jax.random.normal(k1, (rows, dim)),
                           jnp.zeros((1, dim))])
    row_of = jnp.where(jax.random.uniform(k2, (n * top_k,)) < 0.5,
                       jax.random.randint(k3, (n * top_k,), 0, rows),
                       rows).astype(jnp.int32)
    token = jnp.arange(n * top_k, dtype=jnp.int32) // top_k
    mixed = jax.random.permutation(jax.random.PRNGKey(14), n * top_k)
    with dispatch_notes() as seen:
        got, tiles = jax.jit(lambda o, r, t: moe._combine_held(
            o, r, t, n, top_k, 36 / 72))(out, row_of[mixed], token[mixed])
    assert seen == ["combine_tiles=whole", "combine_write=first"]
    runs = np.bincount((np.asarray(token) // 96)[np.asarray(row_of) < rows])
    assert tiles.tolist() == [342, int((runs > 512).sum())]
    assert 0 < tiles[1] < 30 and runs.max() <= 2 * 512
    assert n % 96 and got.shape == (n, dim) and got.dtype == jnp.float32
    want = np.asarray(out, np.float64)[np.asarray(row_of)].reshape(
        n, top_k, dim).sum(1)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert np.abs(want[-32:]).max() > 0  # the last, partly filled block


# ---- the whole model through the engine ------------------------------------------------

def _windows(n, seed=3):
    return spec.plugin("inputs", "granite_tokens").make(
        n, (40,), seed).astype(np.float32)


def _engine(dtype="float32"):
    return InferenceEngine(ModelConfig(
        name="granite_h_tiny", dtype=dtype, num_classes=96,
        input_shape=(40,), seed=5), batch_cfg=BatchConfig())


def test_model_through_the_engine_against_the_reference():
    model = build_model("granite_h_tiny")
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
    assert _distance(got, want).max() < 1e-4  # summation order: under 1e-6
    # no answer is its last id's own row of the tied matrix and little else
    assert want.max() < 0.2
    # every multiplier is read: another value is another answer
    for key in ("embedding_multiplier", "residual_multiplier",
                "logits_scaling", "attention_multiplier"):
        other = {**SIZES, key: 1.5 * SIZES[key]}
        with jax.default_matmul_precision("highest"):
            moved = np.asarray(REFERENCE.forward(other, params, state, x[:2]))
        assert _distance(moved, want[:2]).min() > 1e-3, key


def test_the_step_counts_and_the_inventory_names_the_forms():
    from storm_tpu.config import ShardingConfig
    from storm_tpu.infer.engine import engine_inventory, shared_engine

    eng = shared_engine(ModelConfig(
        name="granite_h_tiny", dtype="float32", num_classes=96,
        input_shape=(40,), seed=5), ShardingConfig(data_parallel=0),
        BatchConfig())
    eng.warmup()
    row = next(r for r in engine_inventory()["engines"]
               if r["model"] == "granite_h_tiny")
    forms = row["programs"][str(eng.pad_batch(4))].split(", ")
    assert set(forms) == {"short_conv=xla", "ssd_scan=chunked",
                          "expert_ffn=swiglu", "expert_dispatch=sorted",
                          "expert_tiles=whole", "expert_combine=held-rows",
                          "combine_tiles=whole", "combine_write=first",
                          "causal_attention=blocked-grouped"}
    handle = eng.dispatch((_windows(4),))
    handle.future.result(60)
    aux = handle.aux
    assert aux["expert_tokens"].shape == (4, 5)
    assert (aux["expert_tokens"].sum(1) + aux["expert_absent"]).tolist() \
        == [eng.pad_batch(4) * 40 * 3] * 4  # padded rows are counted too
    assert 0 < aux["expert_absent"].min()


def test_registry_names_the_model_and_its_share():
    model = build_model("granite_4_h_small")
    assert model.input_shape == (4096,) and model.num_classes == 50176
    assert model.max_rows == 8
    kinds = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert model.hyper["layer_types"] == kinds
    assert (model.hyper["groups"], model.hyper["mamba_heads"],
            model.hyper["top_k"], model.hyper["n_experts"],
            model.hyper["experts_held"],
            model.hyper["attention_multiplier"]) == (
        1, 128, 10, 72, 36, 0.0078125)
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert set(params) == {"embed", "norm", "layers"}
    assert all(set(blk) == {"norm1", "mixer", "norm2", "ffn"}
               for blk in params["layers"])
    assert tuple("mamba" if "in_proj" in blk["mixer"] else "attention"
                 for blk in params["layers"]) == kinds
    mamba, attn = (params["layers"][i]["mixer"] for i in (0, 5))
    ffn = params["layers"][5]["ffn"]
    assert mamba["in_proj"].shape == (4096, 16768)
    assert mamba["conv"]["w"].shape == (4, 8448)
    assert mamba["norm"]["scale"].shape == (8192,)
    assert mamba["out_proj"].shape == (8192, 4096)
    assert attn["q"].shape == (4096, 4096) and attn["k"].shape == (4096, 1024)
    assert ffn["router"].shape == (4096, 72) and "router_bias" not in ffn
    assert ffn["experts"]["gate"].shape == (36, 4096, 768)
    assert ffn["shared"]["down"].shape == (1536, 4096)
    assert params["embed"].shape == (50176, 4096)
    assert {leaf.dtype for leaf in jax.tree.leaves(params)} \
        == {jnp.dtype(jnp.bfloat16)}
    assert sum(x.size for x in jax.tree.leaves(params)) == 4_757_211_776
    assert state["aux"]["expert_tokens"].shape == (10, 36)
    with pytest.raises(ValueError):
        G.build_granite(
            "x", 8, (4,), layer_types=("mamba", "window"), dim=8,
            mamba_heads=1, mamba_head_dim=8, state=8, conv=4, heads=1,
            kv_heads=1, head_dim=8, attention_multiplier=0.5,
            expert_width=8, shared_width=8, n_experts=2, top_k=1,
            experts_held=2)


# ---- the ninth plan's own lines ------------------------------------------------------

# The eight plans that were there lower to their parents' text by
# tests/test_scorer.py, tests/test_trinity.py and tests/test_keye.py, whose
# lines this PR leaves as they were (``tied`` and ``gqa_mixer``'s ``scale``
# are read at trace time). The ninth's, as this PR built it: the first 16 hex
# digits of the sha256 of the lowered text, of the tree ``init`` makes and,
# for the toy, of its leaves from key 7. (PR 65: the served size's text is
# its own again, the experts' tiles 1,024 rows for 512, gathered 512 rows at a
# time: ``parallel/moe.py run_tile`` of a run of 1,137 at this test's two
# rows, 4,551 at the cell's. PR 69: all five digests; the layer counts its
# combine's tiles, written and added (``combine_tiles`` in ``aux``), and the
# served size, which holds half of its router, writes a block's first tile
# into allocated sums and adds only a further one.)
GRANITE = {"granite_h_tiny": ('3cb590ce8439770b', 'd308d084fac8b85e', 'eda6c3abc43764e0'),
           "granite_4_h_small": ('13cffced573d97cb', 'd35172f43fc5a675')}


def _digest(*chunks):
    h = hashlib.sha256()
    for chunk in chunks:
        h.update(chunk)
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(GRANITE))
def test_the_ninth_plan_lowers_to_its_own_text_and_makes_its_trees(name):
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
    assert got == GRANITE[name]
