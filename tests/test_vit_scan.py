"""ViT's blocks as one scanned loop over stacked weights (models/vit.py).

``init`` and the checkpoints hold a list of blocks; an engine that serves one
float tree stacks them once at load, beside the list
(``ModelDef.serve_params``), and a long step's ``apply`` then scans one
block's code over the stacked leaves, carrying the stream and the row mean
the next block's first norm needs, while a short step's walks the list
(``_SCAN_MIN_TOKENS``). The two forms are one computation: same logits, same
gradients, and who keeps the list alone (tensor-parallel placement, int8
weights, the train step) runs what it ran.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from storm_tpu.config import BatchConfig, ModelConfig, ShardingConfig
from storm_tpu.infer.engine import InferenceEngine
from storm_tpu.models import build_model
from storm_tpu.models.vit import build_vit, stack_blocks


def _g_heads():
    """Four blocks at ViT-g/14's head geometry, 16 heads of 88."""
    return build_vit("vit_g_heads", 10, (28, 28, 3), patch=14, dim=1408,
                     depth=4, num_heads=16, mlp_dim=256)


MODELS = {"vit_tiny": lambda: build_model("vit_tiny"), "g_heads": _g_heads}


@pytest.fixture
def every_step_long(monkeypatch):
    """The tests' steps are a few dozen tokens: let them take the loop."""
    import storm_tpu.models.vit as vit

    monkeypatch.setattr(vit, "_SCAN_MIN_TOKENS", 0)
    monkeypatch.setattr(vit, "_STACK_MIN_TOKENS", 0)


def _cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)


def _by_list(model, params, x):
    """The forward over the list of blocks, each through ``_block``; jitted,
    as the scanned form is: eager bfloat16 rounds after every op."""
    assert isinstance(params["blocks"], list) and "stacked" not in params
    return jax.jit(lambda p, x: model.apply(p, {}, x)[0])(params, x)


# bfloat16: the CPU's compiler fuses the loop's body otherwise than the
# unrolled blocks, so the roundings fall at other places; a logit of about 1
# may differ by a few steps of 2^-7, and neither form may lie farther from
# the float32 logits than the other by more than that.
@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 5e-2)])
@pytest.mark.parametrize("name", sorted(MODELS))
def test_scanned_apply_gives_the_lists_logits(name, dtype, atol,
                                               every_step_long):
    model = MODELS[name]()
    params, _ = model.init(jax.random.PRNGKey(0))
    params = _cast(params, dtype)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (3,) + model.input_shape).astype(dtype)
    stacked = stack_blocks(params)
    depth = model.hyper["depth"]
    assert isinstance(params["blocks"], list) and len(params["blocks"]) == depth
    assert stacked["stacked"]["mlp_in"]["w"].shape == (
        depth, model.hyper["dim"], model.hyper["mlp_dim"])
    assert stacked["blocks"] is params["blocks"]  # the list stays beside it
    assert stacked["head"] is params["head"]  # nothing else is touched
    want = _by_list(model, params, x)
    got, _ = jax.jit(lambda p, x: model.apply(p, {}, x))(stacked, x)
    assert got.dtype == want.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, atol=atol)
    if dtype != jnp.float32:
        exact, _ = model.apply(_cast(params, jnp.float32), {},
                               x.astype(jnp.float32))
        off = lambda a: float(np.abs(a - np.asarray(exact)).max())
        assert off(got) <= off(want) + atol, (off(got), off(want))


@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_scanned_program_is_one_loop_and_says_so(name, every_step_long):
    """One ``while`` whatever the depth, none from the list; the note the
    engine's inventory prints is made as the program is traced."""
    from storm_tpu.ops.platform import dispatch_notes

    model = MODELS[name]()
    params, _ = model.init(jax.random.PRNGKey(0))
    x = jnp.zeros((2,) + model.input_shape)
    fwd = lambda p, x: model.apply(p, {}, x)[0]
    with dispatch_notes() as seen:
        scanned = jax.jit(fwd).lower(stack_blocks(params), x).as_text()
    assert seen == ["blocks=scan", "attention=xla"]
    with dispatch_notes() as seen:
        unrolled = jax.jit(fwd).lower(params, x).as_text()
    assert seen == ["blocks=unrolled", "attention=xla"]
    assert scanned.count("stablehlo.while") == 1
    assert "stablehlo.while" not in unrolled
    assert len(scanned) < len(unrolled)


@pytest.mark.parametrize("rows,form", [(481, "unrolled"), (482, "scan")])
def test_a_step_takes_the_loop_from_8192_tokens(rows, form):
    """The served tree holds both arrangements and the traced shape picks:
    ``vit_tiny``'s 17 tokens a row make 8,177 tokens at 481 rows and 8,194 at
    482. A loop's slices of a block's weights are copies nothing hides, a
    tenth of a short step and nothing of a long one (models/vit.py
    ``_SCAN_MIN_TOKENS``)."""
    from storm_tpu.ops.platform import dispatch_notes

    model = build_model("vit_tiny")
    served = jax.eval_shape(
        lambda k: stack_blocks(model.init(k)[0]), jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((rows,) + model.input_shape, jnp.float32)
    with dispatch_notes() as seen:
        text = jax.jit(lambda p, x: model.apply(p, {}, x)[0]).lower(
            served, x).as_text()
    assert seen == [f"blocks={form}", "attention=xla"]
    assert ("stablehlo.while" in text) == (form == "scan")


@pytest.mark.parametrize("name", sorted(MODELS))
def test_gradients_through_the_scan_are_the_lists(name, every_step_long):
    model = MODELS[name]()
    params, _ = model.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4,) + model.input_shape)
    y = jnp.arange(4) % model.num_classes

    def loss(p):
        logits, _ = model.apply(p, {}, x, train=True)
        return -jnp.take_along_axis(
            jax.nn.log_softmax(logits), y[:, None], axis=1).mean()

    by_list = jax.jit(jax.grad(loss))(params)
    by_scan = jax.jit(jax.grad(loss))(stack_blocks(params))
    assert isinstance(by_list["blocks"], list)
    # the scanned step reads the stacked leaves alone: the list's get zeros
    assert not any(float(jnp.abs(g).max()) for g in
                   jax.tree.leaves(by_scan["blocks"]))
    want = {**by_list, "blocks": stack_blocks(by_list)["stacked"]}
    got = {**by_scan, "blocks": by_scan["stacked"]}
    del got["stacked"]
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(want)[0],
                            jax.tree.leaves(got)):
        scale = float(jnp.abs(a).max()) + 1e-6
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=2e-5 * scale, err_msg=str(path))


def _train(params, steps=5):
    import optax

    from storm_tpu.parallel.train import make_train_step

    model = build_model("vit_tiny")
    step, opt = make_train_step(model, optax.sgd(0.05))
    opt_state, state = opt.init(params), {}
    x = jax.random.normal(jax.random.PRNGKey(1), (8,) + model.input_shape)
    y = jnp.arange(8) % model.num_classes
    losses = []
    for _ in range(steps):
        params, opt_state, state, loss = step(params, opt_state, state, x, y)
        losses.append(float(loss))
    return params, losses


@pytest.mark.parametrize("stacked", [False, True], ids=["list", "stacked"])
def test_the_train_step_still_trains(stacked, every_step_long):
    """parallel/train.py's step on the tree ``init`` gives (a list, what it
    has always trained) and through the loop over stacked leaves: the loss
    falls on a batch it repeats, and the loop walks the list's losses."""
    first, _ = build_model("vit_tiny").init(jax.random.PRNGKey(0))
    params, losses = _train(stack_blocks(first) if stacked else first)
    assert losses[-1] < losses[0] - 0.05, losses
    assert ("stacked" in params) == stacked
    if stacked:
        np.testing.assert_allclose(losses, _train(first)[1], rtol=1e-4)


def _engine(**kw):
    sharding = kw.pop("sharding", ShardingConfig(data_parallel=1))
    batch = kw.pop("batch", BatchConfig(max_batch=4, buckets=(4,)))
    return InferenceEngine(
        ModelConfig(name="vit_tiny", input_shape=(32, 32, 3),
                    dtype="float32", **kw),
        sharding, batch)


def _probabilities(model, params, x):
    logits, _ = model.apply(params, {}, jnp.asarray(x))
    return np.asarray(jax.nn.softmax(logits, axis=-1))


def test_a_checkpoint_of_a_list_of_blocks_loads_and_serves(
        tmp_path, every_step_long):
    """What the parent wrote (orbax, the tree ``init`` gives: a list of
    blocks) is what this engine loads; it serves the stacked leaves beside
    the list, and its loop answers as the list does."""
    from storm_tpu.models.registry import load_or_init, save_checkpoint

    model = build_model("vit_tiny")
    params, state = model.init(jax.random.PRNGKey(7))
    assert isinstance(params["blocks"], list)
    path = str(tmp_path / "ckpt")
    save_checkpoint(path, params, state, model)
    loaded, _ = load_or_init(model, path, seed=99)
    assert isinstance(loaded["blocks"], list) and "stacked" not in loaded
    eng = _engine(checkpoint=path, seed=99)
    assert isinstance(eng.params["blocks"], list)
    assert eng.params["stacked"]["attn"]["q"]["w"].shape == (2, 64, 64)
    x = np.random.RandomState(0).randn(3, 32, 32, 3).astype(np.float32)
    got = eng.predict(x)
    assert eng.program_forms[4] == "blocks=scan, attention=xla"
    np.testing.assert_allclose(got, _probabilities(model, params, x),
                               atol=1e-5)


@pytest.mark.parametrize("buckets,stacks", [
    ((4,), False), ((4, 512), False), ((4, 512, 4096), True)])
def test_the_engines_largest_step_says_whether_it_stacks(buckets, stacks):
    """Rows of 17 tokens. An engine whose largest bucket is under
    ``_STACK_MIN_TOKENS`` (the step a backlog fills must be one the loop
    costs nothing) holds the loaded tree, the blocks once, and walks the list
    whatever the step. One with such a bucket holds the stacked leaves beside
    the list; its steps from ``_SCAN_MIN_TOKENS`` scan them and its short
    step does not read them."""
    eng = _engine(batch=BatchConfig(max_batch=buckets[-1], buckets=buckets))
    model = build_model("vit_tiny")
    params, _ = model.init(jax.random.PRNGKey(0))
    loaded = sum(a.nbytes for a in jax.tree.leaves(params))
    blocks = sum(a.nbytes for a in jax.tree.leaves(params["blocks"]))
    assert ("stacked" in eng.params) == stacks
    assert eng.param_bytes() == loaded + stacks * blocks
    for rows in buckets:
        x = np.random.RandomState(0).randn(rows - 1, 32, 32, 3).astype(
            np.float32)
        got = eng.predict(x)
        assert eng.program_forms[rows] == (
            "blocks=scan, attention=xla" if stacks and rows >= 512
            else "blocks=unrolled, attention=xla")
        np.testing.assert_allclose(got, _probabilities(model, params, x),
                                   atol=1e-5)


@pytest.mark.parametrize("how,kw", [
    ("tensor_parallel", {"sharding": ShardingConfig(data_parallel=1,
                                                    tensor_parallel=2)}),
    ("int8", {"weights": "int8"}),
])
def test_engines_that_place_or_quantize_by_path_keep_the_list_alone(
        how, kw, every_step_long):
    """Tensor-parallel placement goes by the loaded tree's paths and int8
    weights are leaves of their own kind: those engines hold the list of
    blocks and nothing beside it, build the unrolled program whatever the
    step, and answer as ever."""
    eng = _engine(**kw)
    assert isinstance(eng.params["blocks"], list)
    assert "stacked" not in eng.params
    model = build_model("vit_tiny")
    params, _ = model.init(jax.random.PRNGKey(0))
    x = np.random.RandomState(0).randn(3, 32, 32, 3).astype(np.float32)
    got = eng.predict(x)
    assert eng.program_forms[4] == "blocks=unrolled, attention=xla"
    np.testing.assert_allclose(got, _probabilities(model, params, x),
                               atol=1e-5 if how == "tensor_parallel" else 5e-2)


def test_a_data_parallel_engine_serves_the_stacked_leaves(every_step_long):
    """Replication cares nothing for a tree's shape: every device holds the
    stacked leaves whole."""
    eng = _engine(sharding=ShardingConfig(data_parallel=0))
    assert eng.params["stacked"]["mlp_in"]["w"].shape == (2, 64, 128)
    assert eng.params["stacked"]["mlp_in"]["w"].sharding.is_fully_replicated
    model = build_model("vit_tiny")
    params, _ = model.init(jax.random.PRNGKey(0))
    x = np.random.RandomState(0).randn(8, 32, 32, 3).astype(np.float32)
    np.testing.assert_allclose(eng.predict(x),
                               _probabilities(model, params, x), atol=1e-5)


def test_the_lines_the_language_programs_cache_keys_cover_stand():
    """The compile cache's key covers an operation's source line
    (``infer/engine.py key_on_metadata``), and what ViT's loop added was put
    where no line a language program traces moved: ``fwd`` and ``fwd_q``
    where they stood in ``infer/engine.py``, ``row_mean`` and
    ``layernorm_about`` below everything else in ``ops/layers.py`` (a second
    copy of ``layernorm``'s arithmetic for that reason alone). A change that
    moves one of these makes every language cell compile anew once, as PR 57
    did: move the numbers here with it, knowingly, and let ``layernorm`` call
    ``layernorm_about`` in the same change."""
    import inspect

    from storm_tpu.infer import engine
    from storm_tpu.ops import layers as L

    at = {line.strip(): n for n, line in enumerate(
        inspect.getsource(engine).splitlines(), 1)}
    assert at["def fwd(params, state, x):"] == 688
    assert at["def fwd_q(params, state, xq, scale, offset):"] == 718
    first = lambda f: inspect.getsourcelines(f)[1]
    assert (first(L.layernorm), first(L.feed_forward)) == (158, 260)
    below = {first(f) for f in (L.row_mean, L.layernorm_about)}
    others = {first(f) for f in vars(L).values()
              if inspect.isfunction(f) and f.__module__ == L.__name__} - below
    assert max(others) < min(below)
