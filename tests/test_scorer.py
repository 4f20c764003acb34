"""The token scorers' one skeleton (``storm_tpu/models/scorer.py``) through
each of the five language models that count, at toy widths on the CPU: what
every model's file relies on it for and no model's own tests hold, a case a
model. The parameter tree's layout is what ``benchmarks/references/`` index;
the zeroed ``aux`` in the state is what makes the engine fetch a step's
counts; the reader on ``ModelDef`` is all the batcher knows of them."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from storm_tpu.models import kimi_k2, kimi_linear, minicpm_sala  # noqa: E402
from storm_tpu.models import nemotron_h, scorer  # noqa: E402
from storm_tpu.models.registry import build_model  # noqa: E402
from storm_tpu.ops import layers as L  # noqa: E402
from storm_tpu.ops import parts as P  # noqa: E402
from storm_tpu.runtime.metrics import MetricsRegistry  # noqa: E402

TWO = {"norm1", "mixer", "norm2", "ffn"}
# name -> (blocks, a block's keys, the counts in ``aux`` and a layer's shape)
TINY = {
    "kimi_linear_tiny": (5, TWO, {"expert_tokens": (4, 4),
                                  "expert_absent": (4,),
                                  "combine_tiles": (4, 2)}),
    "nemotron_h_tiny": (5, {"norm", "mixer"}, {"expert_tokens": (2, 4),
                                               "expert_absent": (2,),
                                               "combine_tiles": (2, 2)}),
    "kimi_k2_tiny": (3, TWO, {"expert_tokens": (2, 4),
                              "expert_absent": (2,),
                              "combine_tiles": (2, 2)}),
    "minicpm_sala_tiny": (4, TWO, {"sparse_keys_read": (2,),
                                   "sparse_keys_skipped": (2,)}),
    # the first plan whose block 0 routes: four expert layers of four blocks
    "solar_open2_tiny": (4, TWO, {"expert_tokens": (4, 5),
                                  "expert_absent": (4,),
                                  "combine_tiles": (4, 2)}),
}
# each family at its toy widths with a plan in which no branch counts
PLAIN = {
    "kimi_linear_tiny": lambda: kimi_linear.build_kimi_linear(
        "plain", 96, (40,), dim=64, layers=1, kda_heads=2, kda_head_dim=16,
        conv=4, mla_heads=2, nope=16, rope=8, v_dim=16, kv_rank=24,
        dense_width=128, expert_width=32, n_experts=8, top_k=2,
        experts_held=4, chunk=16, expert_tile=16, published_layers=8),
    "nemotron_h_tiny": lambda: nemotron_h.build_nemotron_h(
        "plain", 96, (44,), pattern="M*", published_layers=10, dim=64,
        mamba_heads=4, mamba_head_dim=8, groups=2, state=16, conv=4, heads=4,
        kv_heads=2, head_dim=16, expert_width=32, shared_width=64,
        n_experts=8, top_k=2, experts_held=4, chunk=16, expert_tile=16),
    "kimi_k2_tiny": lambda: kimi_k2.build_kimi_k2(
        "plain", 96, (40,), dim=64, layers=1, heads=4, nope=16, rope=8,
        v_dim=16, q_rank=24, kv_rank=24, dense_width=128, expert_width=32,
        n_experts=16, top_k=2, experts_held=4, rope_theta=10.0,
        yarn_factor=4.0, yarn_original=32, beta_fast=4.0, expert_tile=16,
        published_layers=6, param_dtype=jnp.float32),
    "minicpm_sala_tiny": lambda: minicpm_sala.build_minicpm_sala(
        "plain", 96, (32,), mixers=("lightning-attn",) * 2,
        published_layers=8, dim=64, ffn_width=128, heads=4, kv_heads=2,
        head_dim=16, lightning_heads=4, lightning_head_dim=16,
        sparse={}, dim_model_base=16, rope_theta=100.0, chunk=16,
        param_dtype=jnp.float32),
}
NAMES = sorted(TINY)


def _ids(model, rows=2, seed=1):
    return np.random.default_rng(seed).integers(
        0, model.num_classes, (rows,) + model.input_shape).astype(np.float32)


@pytest.fixture(scope="module")
def ran():
    """name -> (model, params, state, x, logits, new state), made once."""
    made = {}

    def one(name):
        if name not in made:
            model = build_model(name)
            params, state = model.init(jax.random.PRNGKey(0))
            x = _ids(model)
            made[name] = (model, params, state, x) + tuple(
                jax.jit(model.apply)(params, state, x))
        return made[name]

    return one


@pytest.mark.parametrize("name", NAMES)
def test_the_tree_is_the_one_the_references_index(name, ran):
    model, params, _, _, _, _ = ran(name)
    blocks, keys, _ = TINY[name]
    assert set(params) == {"embed", "layers", "norm", "head"}
    assert len(params["layers"]) == blocks
    assert all(set(blk) == keys for blk in params["layers"])
    dim = params["norm"]["scale"].shape[0]
    assert params["embed"].shape == (model.num_classes, dim)
    assert params["head"].shape == (dim, model.num_classes)
    for blk in params["layers"]:
        for key in keys - {"mixer", "ffn"}:
            assert blk[key]["scale"].shape == (dim,)


@pytest.mark.parametrize("name", NAMES)
def test_aux_goes_in_zeroed_with_the_shapes_that_come_back(name, ran):
    _, _, state, _, _, new_state = ran(name)
    want = TINY[name][2]
    assert set(state) == {"aux"} and set(new_state) == {"aux"}
    assert {k: v.shape for k, v in state["aux"].items()} == want
    for key, zeros in state["aux"].items():
        back = new_state["aux"][key]
        assert zeros.dtype == back.dtype == jnp.int32
        assert zeros.shape == back.shape
        assert not np.asarray(zeros).any()
        assert (np.asarray(back) >= 0).all() and np.asarray(back).sum() > 0


@pytest.mark.parametrize("name", sorted(PLAIN))
def test_a_plan_that_counts_nothing_hands_its_state_back(name):
    model = PLAIN[name]()
    params, state = model.init(jax.random.PRNGKey(0))
    assert state == {} and model.observe_aux is None
    held = {"kept": np.float32(3.0)}
    logits, back = model.apply(params, held, _ids(model, rows=1))
    assert back is held
    assert logits.shape == (1, 96)
    assert build_model(name).observe_aux is not None


@pytest.mark.parametrize("name", NAMES)
def test_logits_are_a_row_a_window_in_the_compute_type(name, ran):
    model, params, state, x, logits, _ = ran(name)
    assert logits.shape == (2, model.num_classes)
    assert logits.dtype == jnp.float32
    assert np.isfinite(np.asarray(logits)).all()
    served = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    shapes = jax.eval_shape(model.apply, served, state, x)
    assert shapes[0].dtype == jnp.bfloat16 and shapes[0].shape == logits.shape


@pytest.mark.parametrize("name", NAMES)
def test_ids_are_rounded_and_clipped_to_the_vocabulary(name, ran):
    model, params, state, x, logits, _ = ran(name)
    last = model.num_classes - 1
    x = x.copy()
    x[0, :3], x[1, :3] = (0, last, 7), (0, last, 7)
    rough = x.copy()
    rough[0, :3] = (-5.0, 1e6, 7.4)  # under, over, off an integer
    fwd = jax.jit(model.apply)
    got, want = fwd(params, state, rough)[0], fwd(params, state, x)[0]
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert not np.array_equal(np.asarray(want), np.asarray(logits))


@pytest.mark.parametrize("name", NAMES)
def test_the_model_carries_the_reader_of_what_it_counts(name, ran):
    model, _, _, _, _, new_state = ran(name)
    aux = jax.tree.map(np.asarray, new_state["aux"])
    registry = MetricsRegistry()
    model.observe_aux(registry, "inference-bolt", aux)
    got = registry.snapshot()["inference-bolt"]
    if "expert_tokens" in aux:
        assert got["expert_assignments_held"] == aux["expert_tokens"].sum()
        assert got["expert_assignments_absent"] == aux["expert_absent"].sum()
        # tiles of 16 have one size: every held expert's count up to 16s
        assert got["expert_rows_computed"] == (
            -(-aux["expert_tokens"] // 16) * 16).sum()
        assert got["expert_tokens_max_over_mean"]["count"] == len(
            aux["expert_tokens"])
        # a toy step's top-2 block is one tile at most: each writes, none adds
        assert got["combine_tiles_written"] == aux["combine_tiles"][:, 0].sum()
        assert got["combine_tiles_written"] >= len(aux["combine_tiles"])
        assert got["combine_tiles_added"] == 0
        assert "sparse_keys_read" not in got
    else:
        assert got["sparse_keys_read"] == aux["sparse_keys_read"].sum()
        assert got["sparse_keys_skipped"] == aux["sparse_keys_skipped"].sum()
        assert "expert_assignments_held" not in got


def test_a_fifth_model_is_a_plan_and_each_count_finds_its_reader():
    """A model written against the skeleton alone: two kinds of counting
    branch in one plan, a count stacked over the layers that count it, each
    reader handed its own counts and nobody else's."""
    seen = []

    def counting(key, shape, tag):
        return scorer.Branch(
            "norm", "ffn", lambda k: L.swiglu_init(k, 16, 32),
            lambda p, y, ctx: (L.swiglu(p, y) * ctx,
                               jnp.full(shape, tag, jnp.int32)),
            scope=P.PROJ, counts=((key, shape),),
            observe=lambda m, cid, n: seen.append((key, cid, n.tolist())))

    a, b = counting("a", (), 3), counting("b", (2,), 5)
    model = scorer.token_scorer(
        "fifth", 24, (6,), ((a,), (b,), (a,)), dim=16, eps=1e-5,
        hyper={"dim": 16}, max_rows=2, context=lambda seq: float(seq))
    assert model.hyper == {"dim": 16, "input_shape": (6,), "num_classes": 24}
    assert model.max_rows == 2 and model.input_dtype == "float32"
    params, state = model.init(jax.random.PRNGKey(0))
    assert [set(blk) for blk in params["layers"]] == [{"norm", "ffn"}] * 3
    assert state["aux"]["a"].shape == (2,) and state["aux"]["b"].shape == (1, 2)
    logits, new_state = model.apply(params, state, _ids(model))
    assert logits.shape == (2, 24)
    aux = jax.tree.map(np.asarray, new_state["aux"])
    model.observe_aux(None, "bolt", aux)
    assert sorted(seen) == [("a", "bolt", [3, 3]), ("b", "bolt", [[5, 5]])]


# What the four models that were there lower to and load, as the parent of
# PR 49 (which gave the skeleton its ``heads``) built them: the first 16 hex
# digits of the sha256 of ``jax.jit(model.apply).lower(...)``'s text for two
# rows, of the tree of shapes and types ``init`` makes and, for the toy sizes,
# of its leaves' bytes from key 7. A change to the skeleton that is meant to
# leave these models alone leaves these alone; one that is meant to change a
# model says so by changing its line. (PR 50 changed the text of Nemotron's
# and MiniCPM-SALA's four lines: the scan's loop in ``ops/ssd.py``; their
# trees and leaves are the parent's. PR 51 changed the text of the three
# expert models' six lines: ``parallel/moe.py``'s chosen scores and its
# tiles' buffer; MiniCPM-SALA's two stand. PR 52 gave ``kda_mixer`` a range
# for its step and ``gqa_mixer`` a gate, both read at trace time: the eight
# lines stand, and the sixth plan's two are as PR 52 built it. PR 56 changed
# the text of the four expert models' eight lines: ``parallel/moe.py``'s two
# loops meet the tiles in an order made beforehand and have a second loop
# for a run's last tile at a smaller size; MiniCPM-SALA's two stand. PR 60
# changed the text of the four toy expert models' lines alone: their top-2
# layers' blocks of the combine are one tile each (256 tokens x 2), which
# now writes its sums and does not read them back; the four served sizes,
# which hold a part of an 8- or 6-a-token router, stand to the letter. PR 65
# changed the text of ``kimi_linear_48b``'s line alone: the experts' tile is
# the expected run's (``parallel/moe.py run_tile``), and the two rows this
# test lowers are a run of 256, so tiles of 512 where the preset said 1,024
# (at its cell's eight rows, a run of 1,024, the tile is the preset's, its
# tokens gathered 512 rows at a time); the others' tile here is what their
# preset named, 512 rows, and their text stands. PR 69
# changed every expert plan's three digests: the layer counts its combine's
# tiles, written and added (``combine_tiles`` in ``aux``: a key more in the
# tree and its zeros among the leaves), and where a part of the router is
# held (the served sizes' 8-, 6- and 10-a-token routers) the combine writes
# a block's first tile into allocated sums and adds only a further one;
# MiniCPM-SALA's two stand.)
PARENT = {
    "kimi_linear_tiny": ("ec4ea3f7665a3954", "3018ba58aee207f8",
                         "089d36eff78f2c3d"),
    "nemotron_h_tiny": ("d17f42884162e7ed", "a81bd0e596fbb4f8",
                        "0c67b917a7cf180d"),
    "kimi_k2_tiny": ("08bf04ffe16de474", "7e93bf0bff2d886a",
                     "83c7cb4dbe014207"),
    "minicpm_sala_tiny": ("e80bd69b29e9bb6e", "fbaec783e93f7071",
                          "136d1265de921964"),
    "kimi_linear_48b": ("7e01093b036431d8", "2cbc0633449fec6d"),
    "nemotron_3_nano_30b": ("f443d1dacdc3f71a", "cc75a7fd8bcf6804"),
    "kimi_k2_6": ("5bc69076d3916e59", "b59443cd82574b67"),
    "minicpm_sala": ("5de5b298fd713172", "31e93c80f04d610e"),
    "solar_open2_tiny": ("af9a498deab4de70", "5f1a053db1e6785c",
                         "8e99c9678a70f872"),
    "solar_open2_250b": ("ea6eddb821f801ba", "30f3e62cf85302ba"),
}


def _digest(*chunks):
    import hashlib

    h = hashlib.sha256()
    for chunk in chunks:
        h.update(chunk)
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(PARENT))
def test_one_head_lowers_to_the_parents_text_and_makes_its_trees(name):
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
    assert got == PARENT[name]
    assert model.num_classes == params["head"].shape[1] \
        == params["embed"].shape[0]
