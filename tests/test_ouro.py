"""Ouro at toy widths on the CPU (hidden 32; three sandwich-normed blocks of
rotary attention at 4 heads of 8, a head on its own keys, and a SwiGLU of 72,
run four times over one set of weights with the last norm between passes and
an exit gate): what this plan asks of the shared skeleton that no other plan
does (``models/scorer.py token_scorer``'s ``passes`` and ``threshold``), each
against its plain form, and the model through ``InferenceEngine`` against the
benchmark's reference (``benchmarks/references/ouro.py``, float32 at
``highest``) on seeded weights, at a threshold of 1 and at thresholds under 1
where rows leave at different passes. Probabilities over the whole vocabulary
are compared, never an argmax: with random weights the largest logit changes
on rounding.

Nothing of the published model is cut (every layer, every pass, every row of
the vocabulary is held), so the model-configs guide's test that the shares
add up to the whole has nothing to add up here."""

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
from storm_tpu.models import ouro as M  # noqa: E402
from storm_tpu.models import scorer as S  # noqa: E402
from storm_tpu.models.registry import build_model, load_or_init  # noqa: E402
from storm_tpu.ops import attention as A  # noqa: E402
from storm_tpu.ops import layers as L  # noqa: E402
from storm_tpu.runtime.metrics import MetricsRegistry  # noqa: E402

REFERENCE = spec.plugin("references", "ouro")
TINY = spec.config("ouro_tiny")
SIZES = TINY["published"]
F32 = jnp.float32
TOY = dict(layers=3, dim=32, ffn_width=72, heads=4, head_dim=8,
           rope_theta=100.0, attention_block=16, param_dtype=F32)


def _distance(got, want):
    """Euclidean distance of each row from its reference row over that row's
    length: the benchmark's measure (``core/pairing.py``)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))


def _windows(n, seed=11):
    return np.random.RandomState(seed).randint(0, 96, (n, 40)).astype(
        np.float32)


def _reference(params, x, threshold):
    with jax.default_matmul_precision("highest"):
        probs, tau, z = jax.jit(lambda p, xx: REFERENCE.exits(
            {**SIZES, "early_exit_threshold": threshold}, p, xx))(params, x)
    return np.asarray(probs), np.asarray(tau), np.asarray(z)


# ---- the model against the reference ----------------------------------------

@pytest.mark.parametrize("threshold", [1, 0.6, 0.45])
def test_the_program_is_the_reference_at_a_threshold_of_1_and_under(threshold):
    """Logits, ``tau`` and the ``exit_pass`` counts. Under 1 the rows of one
    step leave at different passes (the draw's gate reads 0.3-0.7), and each
    reads its own pass's normed last position."""
    model = M.build_ouro_tiny(threshold=float(threshold))
    params, state = load_or_init(model, None, 5)
    x = _windows(12)
    want, tau, _ = _reference(params, x, threshold)
    logits, new = jax.jit(model.apply)(params, state, x)
    got = np.asarray(jax.nn.softmax(logits, -1))
    assert _distance(got, want).max() < 1e-4  # summation order: under 1e-5
    left = np.asarray(new["aux"]["exit_pass"])
    assert left.tolist() == [int((tau == t).sum()) for t in (1, 2, 3, 4)]
    assert left.sum() == 12
    if threshold == 1:
        assert left.tolist() == [0, 0, 0, 12]
    else:
        assert (left > 0).sum() >= 2  # rows leave at different passes
        # and another pass's row is another answer: the choice is read
        other, _, _ = _reference(params, x, 1)
        moved = _distance(other, want)
        assert (moved[tau < 4] > 1e-3).all() and (moved[tau == 4] == 0).all()


def test_model_through_the_engine_against_the_reference():
    model = build_model("ouro_tiny")
    params, state = load_or_init(model, None, 5)
    x = _windows(16)
    want, tau, _ = _reference(params, x, 1)
    assert (tau == 4).all()  # the published threshold: the last pass
    eng = InferenceEngine(ModelConfig(
        name="ouro_tiny", dtype="float32", num_classes=96, input_shape=(40,),
        seed=5), batch_cfg=BatchConfig())
    assert eng.batch_cfg.buckets == (4,) and eng.max_rows == 4
    assert eng.in_dtype == jnp.float32
    got = np.concatenate([eng.predict(x[a:a + 4]) for a in range(0, 16, 4)])
    assert got.shape == (16, 96)
    assert _distance(got, want).max() < 1e-4
    assert want.max() < 0.5  # no window is answered one-hot
    # every published switch the reference reads: another value, another
    # answer
    for key, other in (("total_ut_steps", 3), ("rope_theta", 10.0),
                       ("rms_norm_eps", 1e-2), ("early_exit_threshold", 0.5)):
        moved = np.asarray(jax.jit(
            lambda p, xx, k=key, o=other: REFERENCE.forward(
                {**SIZES, k: o}, p, {}, xx))(params, x[:4]))
        assert _distance(moved, want[:4]).max() > 1e-4, key


def test_the_step_counts_its_exits_into_the_registry():
    from storm_tpu.config import ShardingConfig
    from storm_tpu.infer.engine import engine_inventory, shared_engine

    eng = shared_engine(ModelConfig(
        name="ouro_tiny", dtype="float32", num_classes=96,
        input_shape=(40,), seed=5), ShardingConfig(data_parallel=0),
        BatchConfig())
    eng.warmup()
    row = next(r for r in engine_inventory()["engines"]
               if r["model"] == "ouro_tiny")
    assert set(row["programs"][str(eng.pad_batch(4))].split(", ")) == {
        "rotary_turn=halves", "causal_attention=blocked",
        "gated_ffn=made-once"}
    handle = eng.dispatch((_windows(3),))  # three rows into the bucket of 4
    handle.future.result(60)
    left = handle.aux["exit_pass"]
    padded = eng.pad_batch(3)  # 4 on one device; padded rows are counted too
    assert left.tolist() == [0, 0, 0, padded]
    metrics = MetricsRegistry()
    build_model("ouro_tiny").observe_aux(metrics, "inference-bolt",
                                         handle.aux)
    S.observe_exits(metrics, "inference-bolt", np.asarray([1, 2, 0, 1]),
                    passes=4)
    got = metrics.snapshot()["inference-bolt"]
    assert [got[f"exit_pass_rows_{t}"] for t in (1, 2, 3, 4)] \
        == [1, 2, 0, 1 + padded]
    assert got["passes_run"] == 4 * (4 + padded)  # every row, every pass


# ---- the loop over passes ---------------------------------------------------

def _looped(passes, threshold=1.0):
    return M.build_ouro("toy", 96, (40,), passes=passes,
                        threshold=threshold, **TOY)


def test_four_passes_through_the_loop_are_four_walks_with_the_norm_between():
    """``passes=4`` (one ``fori_loop``) against four Python walks of the
    same plan at ``passes=1``, the last norm on the whole stream after each;
    and the same leaves are read by every pass: one layer's leaf perturbed
    moves every pass's ``z_t``."""
    looped, once = _looped(4, threshold=0.5), _looped(1)
    params, state = looped.init(jax.random.PRNGKey(7))
    x = _windows(4)
    eps = 1e-6

    def walks(p):
        """Each pass's normed last position by the single-pass program's
        own blocks: its stream is read off a head that is the identity."""
        ids = jnp.clip(jnp.round(x), 0, 95).astype(jnp.int32)
        h, lasts = p["embed"][ids].astype(F32), []
        for _ in range(4):
            for blk in p["layers"]:
                for norm, name, post, fn in (
                        ("norm1", "mixer", "post1", attention),
                        ("norm2", "ffn", "post2", feed_forward)):
                    y = fn(blk[name], L.rmsnorm(blk[norm], h, eps))
                    h = h + L.rmsnorm(blk[post], y.astype(F32), eps)
            h = L.rmsnorm(p["norm"], h, eps)
            lasts.append(h[:, -1])
        return jnp.stack(lasts)

    from storm_tpu.models.falcon_h1 import gated_ffn, rotary_gqa
    from storm_tpu.ops import rope as R

    inv_freq = 100.0 ** (-2.0 * np.arange(4) / 8)
    tables = R.rotary_tables(40, inv_freq)

    def attention(p, y):
        return rotary_gqa(p, y, 4, 4, tables, 8 ** -0.5, 16)

    def feed_forward(p, y):
        return gated_ffn(p, y, 1.0)

    z = np.asarray(jax.jit(walks)(params))
    logits, new = jax.jit(looped.apply)(params, state, x)
    z_want, left = S.exit_row(params["exit"], jnp.asarray(z), 0.5)
    want = np.asarray(L.matmul(z_want, params["head"]))
    assert np.abs(np.asarray(logits) - want).max() < 1e-4
    assert np.asarray(new["aux"]["exit_pass"]).tolist() == left.tolist()
    # the single-pass plan's own walk is the first of them
    first = np.asarray(jax.jit(once.apply)(params, {}, x)[0])
    assert np.abs(first - np.asarray(
        L.matmul(jnp.asarray(z[0]), params["head"]))).max() < 1e-4
    # one layer's leaf perturbed: every pass's z_t moves
    nudged = jax.tree.map(lambda a: a, params)
    # (the gate's matrix: a branch's scale is normed away, the SiLU's is not)
    nudged["layers"][1]["ffn"]["gate"] = \
        params["layers"][1]["ffn"]["gate"] * 1.5
    moved = np.abs(np.asarray(jax.jit(walks)(nudged)) - z).max(axis=(1, 2))
    assert (moved > 1e-3).all()
    _, _, z_ref = _reference(params, x, 1)
    assert np.abs(z_ref - z).max() < 1e-4


def test_the_loop_is_a_loop_and_the_defaults_are_the_parents_text():
    """The lowered text of ``passes=4`` is one pass's and the loop's
    signature (the leaves it closes over), not four passes'; a plan handed
    no ``passes`` lowers to the text it lowers to with ``passes=1,
    threshold=1`` spelled out (the ten plans before this one are held to
    their parents' text by their own files' digests)."""
    x = jax.ShapeDtypeStruct((2, 40), F32)

    def text(model):
        params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        return jax.jit(model.apply).lower(params, state, x).as_text()

    four, one = text(_looped(4)), text(_looped(1))
    assert len(four) < 1.25 * len(one)
    assert len(text(_looped(8))) == pytest.approx(len(four), rel=0.01)
    assert four.count("stablehlo.while") == one.count("stablehlo.while") + 1
    kw = dict(dim=32, eps=1e-6, hyper={}, max_rows=4)
    plan = ((S.Branch("norm1", "ffn",
                      lambda key: L.swiglu_init(key, 32, 72),
                      lambda p, y, _: L.swiglu(p, y), scope="proj"),),) * 2
    plain = S.token_scorer("t", 96, (40,), plan, **kw)
    spelled = S.token_scorer("t", 96, (40,), plan, passes=1, threshold=1.0,
                             **kw)
    assert text(plain) == text(spelled)
    assert "exit" not in jax.eval_shape(plain.init, jax.random.PRNGKey(0))[0]
    # no other model's draw moves: the gate's key is one more, the last
    p1, _ = _looped(1).init(jax.random.PRNGKey(3))
    p4, s4 = _looped(4).init(jax.random.PRNGKey(3))
    assert set(p4) - set(p1) == {"exit"}
    assert p4["exit"]["w"].shape == (32,) and p4["exit"]["b"].shape == ()
    assert float(p4["exit"]["b"]) == 0.0
    assert s4["aux"]["exit_pass"].shape == (4,)


def test_a_counting_plan_under_several_passes_is_refused():
    from storm_tpu.parallel.moe import topk_moe_init

    counting = S.experts(
        "norm2", "ffn", lambda key: topk_moe_init(key, 32, 16, 4, 4),
        held=4, top_k=2, first_expert=0, scale=1.0, tile=16)
    kw = dict(dim=32, eps=1e-6, hyper={}, max_rows=4)
    S.token_scorer("t", 96, (40,), ((counting,),), **kw)  # once: fine
    with pytest.raises(ValueError, match="runs once"):
        S.token_scorer("t", 96, (40,), ((counting,),), passes=2, **kw)
    with pytest.raises(ValueError):
        S.token_scorer("t", 96, (40,), ((counting,),), passes=0, **kw)


def test_the_exit_rule_by_hand():
    """Gates of 0.2, 0.5, 0.9 on three passes before the last: the weights
    are 0.2, 0.4, 0.36, 0.04 and their sums 0.2, 0.6, 0.96."""
    logit = lambda g: float(np.log(g / (1 - g)))  # noqa: E731
    # five rows of two channels a pass: channel 0 is the gate's logit (w
    # reads it alone), channel 1 the pass's index, to tell which row is read
    lasts = jnp.stack([
        jnp.tile(jnp.asarray([[gate, float(t)]]), (5, 1)) for t, gate in
        enumerate((logit(0.2), logit(0.5), logit(0.9), 0.0))])
    p = {"w": jnp.asarray([1.0, 0.0]), "b": jnp.asarray(0.0)}
    for threshold, pass_ in ((0.1, 1), (0.2, 1), (0.3, 2), (0.6, 2),
                             (0.7, 3), (0.95, 3), (0.97, 4), (1.0, 4)):
        z, left = S.exit_row(p, lasts, threshold)
        assert left.tolist() == [5 * (t == pass_) for t in (1, 2, 3, 4)], \
            threshold
        assert float(z[0, 1]) == pass_ - 1
    # a saturated gate cannot make a record leave at a threshold of 1
    z, left = S.exit_row(p, lasts.at[0, :, 0].set(1e4), 1.0)
    assert left.tolist() == [0, 0, 0, 5]


# ---- the registry's presets -------------------------------------------------

def test_registry_names_the_model_whole():
    model = build_model("ouro_2_6b")
    assert model.input_shape == (4096,) and model.num_classes == 49152
    assert model.max_rows == 4
    assert model.hyper["passes"] == 4 and model.hyper["threshold"] == 1.0
    assert (model.hyper["layers"], model.hyper["dim"],
            model.hyper["ffn_width"], model.hyper["heads"],
            model.hyper["head_dim"], model.hyper["rope_theta"]) == (
        48, 2048, 5632, 16, 128, 1e6)
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert set(params) == {"embed", "norm", "head", "exit", "layers"}
    assert len(params["layers"]) == 48
    assert all(set(blk) == {"norm1", "mixer", "post1", "norm2", "ffn",
                            "post2"} for blk in params["layers"])
    blk = params["layers"][0]
    assert {k: v.shape for k, v in blk["mixer"].items()} == {
        k: (2048, 2048) for k in "qkvo"}  # no bias, no grouping
    assert blk["ffn"]["gate"].shape == blk["ffn"]["up"].shape == (2048, 5632)
    assert blk["ffn"]["down"].shape == (5632, 2048)
    assert params["embed"].shape == (49152, 2048)
    assert params["head"].shape == (2048, 49152)  # untied
    assert params["exit"]["w"].shape == (2048,)
    assert {leaf.dtype for leaf in jax.tree.leaves(params)} \
        == {jnp.dtype(jnp.bfloat16)}
    # the issue's table
    assert sum(x.size for x in jax.tree.leaves(blk)) == 51_388_416
    assert sum(x.size for x in jax.tree.leaves(params)) == 2_667_974_657
    assert state["aux"]["exit_pass"].shape == (4,)
    # the rule gives the kernel at one query head a key head on a chip
    with pytest.MonkeyPatch.context() as m:
        m.setattr(A, "_use_pallas", lambda: True)
        m.setattr(A, "_one_device", lambda: True)
        assert A.merged_form(16, 16, 4096, 128, 128) == "kernel"
    # the post-norms start at 1 / sqrt(2 x 48), everything else at 1
    tiny = build_model("ouro_tiny").init(jax.random.PRNGKey(1))[0]
    assert float(tiny["layers"][0]["post1"]["scale"][0]) == pytest.approx(
        1 / np.sqrt(6))
    assert float(tiny["layers"][0]["norm1"]["scale"][0]) == 1.0


# ---- the rotary turn inside the merged causal kernel (PR 74) -------------------

def _turn_operands(b, s, hq, hkv, seed):
    """q, k, v merged ``(b, s, H * 128)`` in bfloat16 and the tables ``(s,
    64)``, on grids coarse enough that the turn's float32 arithmetic is
    exact (operands in eighths up to 2, tables in sixty-fourths: a product
    is a multiple of 1/512 under 2, a sum of two under 4, eleven bits): the
    CPU's compiler fuses a multiplication and an addition into one rounding
    in one program and not in the next, which real tables would show as a
    last bit here and there; the one rounding to bfloat16 is the turn's own
    and is taken on every element."""
    rng = np.random.RandomState(seed)
    q, k, v = (jnp.asarray(rng.randint(-16, 17, (b, s, h * 128)) / 8.0,
                           jnp.bfloat16) for h in (hq, hkv, hkv))
    angle = np.arange(s)[:, None] * 100.0 ** (-np.arange(64) / 64.0)[None]
    cos, sin = (jnp.asarray(np.round(f(angle) * 64) / 64, jnp.float32)
                for f in (np.cos, np.sin))
    return q, k, v, cos, sin


def _bits(y):
    return np.asarray(y).view(np.uint16)


def _as_one_chip(monkeypatch):
    """The rules' answers on one chip, the kernels under the interpreter."""
    import functools

    from storm_tpu.ops import flash_attention as F
    from storm_tpu.ops import rope as R

    for module in (A, R):
        monkeypatch.setattr(module, "_use_pallas", lambda: True)
        monkeypatch.setattr(module, "_one_device", lambda: True)
    monkeypatch.setattr(R, "_turn_lanes", functools.partial(
        R._turn_lanes, interpret=True))
    monkeypatch.setattr(F, "flash_attention_merged", functools.partial(
        F.flash_attention_merged, interpret=True))


@pytest.mark.parametrize("hq,hkv,s,tile,window,row", [
    (16, 16, 256, 128, None, 0),  # a group of 1: Ouro's
    (20, 4, 256, 64, None, 0),    # a group of 5: Falcon-H1's
    (4, 4, 384, 128, 100, 0),     # a window
    (4, 2, 200, 64, None, 0),     # positions that are not whole blocks
    (4, 4, 256, 128, None, 1),    # row 1 of a batch of two
], ids=["group1", "group5", "window", "padded", "row1"])
def test_the_kernel_turns_q_and_k_to_the_lanes_kernels_bits(
        monkeypatch, hq, hkv, s, tile, window, row):
    """``flash_attention_merged`` handed unturned q and k with ``rotary`` (the
    lane tables) against ``turn_merged`` (the lanes kernel, where its rule
    grants it) followed by ``flash_attention_merged``: the same bfloat16,
    bit for bit, in the row written, and the other row as it was."""
    from storm_tpu.ops import flash_attention as F
    from storm_tpu.ops import rope as R

    _as_one_chip(monkeypatch)
    q, k, v, cos, sin = _turn_operands(2, s, hq, hkv, seed=hq + s)
    assert R.turn_form(s, 128) == ("lanes" if s % 128 == 0 else "halves")
    (qt,), (kt,) = (R.turn_merged((y,), cos, sin, h)
                    for y, h in ((q, hq), (k, hkv)))
    out = jnp.full((2, s, hq * 128), 7.0, jnp.bfloat16)
    kw = dict(heads=hq, kv_heads=hkv, block_q=tile, block_k=128,
              window=window)
    want = F.flash_attention_merged(out, qt, kt, v, row, **kw)
    got = F.flash_attention_merged(out, q, k, v, row,
                                   rotary=R._lane_tables(cos, sin), **kw)
    plain = F.flash_attention_merged(out, q, k, v, row, **kw)
    assert (_bits(got) == _bits(want)).all()
    assert (_bits(got[1 - row]) == _bits(out[1 - row])).all()
    assert (_bits(got[row]) != _bits(plain[row])).mean() > 0.5


@pytest.mark.parametrize("d,chip,notes", [
    (128, True, ["rotary_turn=causal-kernel",
                 "causal_attention=kernel-grouped-merged"]),
    (64, True, ["rotary_turn=lanes",
                "causal_attention=kernel-grouped-merged-halves"]),
    (128, False, ["rotary_turn=halves", "causal_attention=blocked-grouped"]),
], ids=["heads-of-128", "heads-of-64", "cpu"])
def test_the_rule_sends_the_turn_into_the_kernel_or_before_it(
        monkeypatch, d, chip, notes):
    """``causal_attention_merged(rotary=...)``: the causal kernel turns
    heads of one lane tile on one chip; heads of 64 (two a lane tile) and
    the CPU's blocked form take ``turn_merged`` first, as they always have,
    and either way the result is that of the turn followed by the attention
    without the argument."""
    from storm_tpu.ops import rope as R
    from storm_tpu.ops.platform import dispatch_notes

    if chip:
        _as_one_chip(monkeypatch)
    hq, hkv, s = 8, 4, 512
    q, k, v, cos, sin = _turn_operands(2, s, hq * d // 128, hkv * d // 128,
                                       seed=d)
    cos, sin = cos[:, :d // 2], sin[:, :d // 2]
    with dispatch_notes() as seen:
        got = A.causal_attention_merged(q, k, v, hq, hkv, block=128,
                                        rotary=(cos, sin))
    assert seen == notes
    (qt,), (kt,) = (R.turn_merged((y,), cos, sin, h)
                    for y, h in ((q, hq), (k, hkv)))
    want = A.causal_attention_merged(qt, kt, v, hq, hkv, block=128)
    assert (_bits(got) == _bits(want)).all()
