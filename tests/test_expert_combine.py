"""How ``parallel/moe.py topk_moe_layer`` forms a token's sum: against the
plain per-token sum in float32 at ``highest``, over what the routing can do
to a chip that holds some of the experts (all, some, none of a token's
picks; every token on one expert; one block of tokens holding every held
assignment), and that the array the old form made cannot come back unseen."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from storm_tpu.ops import layers as L
from storm_tpu.ops.platform import dispatch_notes
from storm_tpu.parallel import moe
from storm_tpu.parallel.moe import route_topk, topk_moe_init, topk_moe_layer

DIM = 32


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Blocks of 16 tokens and tiles of 8 held rows: a hundred tokens then
    make several blocks, and a busy block several tiles."""
    monkeypatch.setattr(moe, "_COMBINE_BLOCK", 16)
    monkeypatch.setattr(moe, "_COMBINE_ROWS", 8)


def _plain(p, x, top_k, first, scale=2.5):
    """Every held expert on every token, weighted by what the router gave
    it, and the shared expert: nothing grouped, sorted or gathered."""
    t = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    chosen, weight = route_topk(p, t, top_k, scale=scale)
    y = jnp.zeros_like(t)
    for e in range(p["experts"]["down"].shape[0]):
        gain = jnp.sum(jnp.where(chosen == e + first, weight, 0.0), -1)
        y = y + gain[:, None] * L.feed_forward(
            {n: w[e] for n, w in p["experts"].items()}, t)
    return (y + L.feed_forward(p["shared"], t)).reshape(x.shape)


def _layer(n_experts, held, form="relu2", seed=0):
    return topk_moe_init(jax.random.PRNGKey(seed), DIM, 48, n_experts, held,
                         form=form, shared_hidden=40)


def _biased(p, expert, by):
    return {**p, "router_bias": p["router_bias"].at[expert].set(by)}


def _one_block_holds_all(p, n, first, held, block):
    """Tokens whose first channel is large and positive score near 1 on the
    held experts, the others near 0 there and 0.5 elsewhere: block ``block``
    of 16 tokens then makes every held assignment, the other blocks none."""
    router = jnp.zeros_like(p["router"]).at[0, first:first + held].set(4.0)
    x = jax.random.normal(jax.random.PRNGKey(5), (n, DIM)) * 0.1
    x = x.at[:, 0].set(-3.0).at[16 * block:16 * (block + 1), 0].set(3.0)
    return {**p, "router": router}, x


# id: (router width, held, first expert, top-k, tokens, what the routing does)
CASES = {
    "top2-all-held": (8, 8, 0, 2, 111, None),
    "top2-quarter-held": (8, 2, 2, 2, 111, None),
    "top2-half-held": (8, 4, 4, 2, 96, None),
    "top6-quarter-held": (16, 4, 4, 6, 100, None),
    "top8-eighth-held": (32, 4, 0, 8, 64, None),
    "top6-all-held": (8, 8, 0, 6, 50, None),
    "none-held": (16, 2, 6, 2, 100, "none"),
    "every-token-to-one-held": (8, 2, 2, 2, 111, "to-held"),
    "every-token-to-one-absent": (8, 2, 2, 2, 111, "to-absent"),
    "one-block-holds-all": (16, 2, 4, 2, 100, "one-block"),
    "one-token": (8, 2, 0, 2, 1, None),
}


@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
@pytest.mark.parametrize("ffn", ["relu2", "swiglu"])
def test_a_tokens_sum_is_the_plain_sum(case, ffn):
    width, held, first, top_k, n, routing = CASES[case]
    p = _layer(width, held, ffn)
    x = jax.random.normal(jax.random.PRNGKey(1), (n, DIM))
    if routing == "none":  # no token picks a held expert
        for e in range(first, first + held):
            p = _biased(p, e, -10.0)
    elif routing == "to-held":
        p = _biased(p, first + 1, 10.0)
    elif routing == "to-absent":
        p = _biased(p, 0, 10.0)
    elif routing == "one-block":
        p, x = _one_block_holds_all(p, n, first, held, block=3)
    with jax.default_matmul_precision("highest"):
        with dispatch_notes() as seen:
            y, tokens, absent = jax.jit(lambda p, x: topk_moe_layer(
                p, x, top_k, first_expert=first, scale=2.5, tile=16))(p, x)
        want = _plain(p, x, top_k, first)
    assert seen == [f"expert_ffn={ffn}", "expert_combine=held-rows"]
    np.testing.assert_allclose(y, want, atol=1e-5 * max(1.0, float(
        jnp.abs(want).max())))
    assert int(tokens.sum()) + int(absent) == n * top_k
    if routing == "none":
        assert int(tokens.sum()) == 0
        np.testing.assert_allclose(y, L.feed_forward(p["shared"], x),
                                   atol=1e-6)
    elif routing == "to-held":
        assert int(tokens[1]) == n
    elif routing == "to-absent":
        assert int(absent) >= n
    elif routing == "one-block":
        # 16 tokens make all 32 held assignments: four tiles of 8 in block
        # 3, and six blocks (a ragged one last) with none
        assert tokens.tolist() == [16, 16] and int(absent) == 2 * n - 32


def _assignments(seed, n, top_k, rows, share):
    """A buffer of ``rows`` rows and a zero row behind them, and for each of
    ``n x top_k`` assignments its row: the zero row where it is absent."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    out = jnp.concatenate([jax.random.normal(k1, (rows, DIM)),
                           jnp.zeros((1, DIM))])
    is_held = jax.random.uniform(k2, (n * top_k,)) < share
    row_of = jnp.where(is_held, jax.random.randint(
        k3, (n * top_k,), 0, rows), rows).astype(jnp.int32)
    return out, row_of


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("n,top_k,share", [
    (100, 2, 1.0), (100, 6, 1.0), (64, 8, 1.0), (100, 6, 0.25),
    (37, 8, 0.125), (16, 6, 0.5), (100, 2, 0.0), (7, 6, 0.3),
    (100, 8, 1 / 32)],
    ids=["top2-all", "top6-all", "top8-all", "top6-quarter", "top8-eighth",
         "one-block", "none", "under-a-block", "top8-a-thirty-second"])
def test_the_loop_on_any_share_held(dtype, n, top_k, share):
    """The loop alone, whatever the share held and in either type of row:
    the 0/1 product is exact, so bfloat16 rows sum as their float32 values."""
    out, row_of = _assignments(3, n, top_k, 150, share)
    out = out.astype(dtype)
    want = np.asarray(out.astype(jnp.float32), np.float64)[
        np.asarray(row_of)].reshape(n, top_k, DIM).sum(1)
    got = jax.jit(lambda o, r: moe._combine_held(o, r, n, top_k))(out, row_of)
    assert got.dtype == jnp.float32 and got.shape == (n, DIM)
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("held,first", [(8, 0), (2, 2)],
                         ids=["all-held", "quarter-held"])
def test_bfloat16_rows_are_summed_in_float32(held, first):
    """The served type: bfloat16 weights and rows, a float32 stream. Against
    the float32 layer on the same rounded weights (so that both route
    alike): within bfloat16's error of the largest value."""
    p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), _layer(8, held))
    x = jax.random.normal(jax.random.PRNGKey(1), (100, DIM)).astype(
        jnp.bfloat16).astype(jnp.float32)
    run = jax.jit(lambda p, x: topk_moe_layer(
        p, x, 2, first_expert=first, scale=2.5, tile=16))
    y, tokens, absent = run(p, x)
    with jax.default_matmul_precision("highest"):
        want, tokens32, absent32 = run(
            jax.tree.map(lambda a: a.astype(jnp.float32), p), x)
    assert y.dtype == jnp.bfloat16
    assert tokens.tolist() == tokens32.tolist() and int(absent) == int(
        absent32)
    np.testing.assert_allclose(y.astype(jnp.float32), want,
                               atol=2e-2 * float(jnp.abs(want).max()))


def _lowered(p, x, top_k, first):
    return jax.jit(lambda p, x: topk_moe_layer(
        p, x, top_k, first_expert=first, tile=16)).lower(p, x).as_text()


def _gathered_rows(text):
    return {int(m) for line in text.splitlines() if "gather" in line
            for m in re.findall(r"-> tensor<(\d+)x%d" % DIM, line)}


def test_no_array_of_tokens_by_picks_by_width_is_formed():
    """64 tokens, top-6, a quarter held: the lowered program holds no
    float32 ``[64, 6, dim]`` and gathers 384 rows nowhere (the old form did
    both, and paid 20 ms a layer for it on the chip); its gathers are a tile
    of the experts' loop and a tile of the combine's."""
    p = _layer(16, 4)
    x = jax.random.normal(jax.random.PRNGKey(2), (64, DIM))
    text = _lowered(p, x, 6, 4)
    assert "tensor<64x6x%d" % DIM not in text
    assert 64 * 6 not in _gathered_rows(text)
    assert _gathered_rows(text) == {16, 8}
    # the same where every expert is held
    text = _lowered(_layer(8, 8), x, 6, 0)
    assert "tensor<64x6x%d" % DIM not in text
    assert _gathered_rows(text) == {16, 8}
    # and the pattern does find the old form
    old = jax.jit(lambda o, r: jnp.sum(o[r.reshape(64, 6)], axis=1,
                                       dtype=jnp.float32)).lower(
        jnp.zeros((100, DIM)), jnp.zeros((384,), jnp.int32)).as_text()
    assert "tensor<64x6x%d" % DIM in old
