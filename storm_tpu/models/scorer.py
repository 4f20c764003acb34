"""A scorer of token records, written once: a window of token ids in, the
next-token distribution at its last position out (``heads`` of them, the
next tokens' one after another, where the model predicts several).

ids -> embedding (times ``scale_emb``) -> a float32 stream ``h`` -> for each
block, for each of its branches, ``h += residual * branch(RMSNorm(h))`` (a
branch that names a ``post`` norm: ``h += residual * RMSNorm_post(branch(
RMSNorm(h)))``, the sandwich) -> the last position's RMS norm (times
``logit_scale``) -> the head (``tied``: the embedding transposed, no leaf of
its own); and what the branches counted on the way,
stacked a layer into ``new_state["aux"]``, which the engine fetches with the
predictions (``infer/engine.py``).

A model's file keeps what is its own: its mixers, its *plan* (a tuple of
blocks, each a tuple of :class:`Branch`), its scalars, what it makes once a
step (``context``: rotary tables) and its presets, and hands them to
:func:`token_scorer`. The parameter tree is ``{"embed", "layers": [{<norm>,
<name>, ...}, ...], "norm", "head"}`` (no ``"head"`` where the model is
``tied``); ``split(rng, branches + 2)`` gives the embedding key 0, the head
key 1 (unused where tied) and every branch of the plan the next.
"""

from __future__ import annotations

from contextlib import nullcontext
from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from storm_tpu.models.registry import ModelDef
from storm_tpu.ops import layers as L
from storm_tpu.ops import parts as P
from storm_tpu.parallel.moe import (observe_expert_counts,
                                    topk_moe_layer_tiles)


def _w(rng, fan_in: int, fan_out: int):
    return L.lecun_normal(rng, (fan_in, fan_out), fan_in)


def _proj(x, *ws):
    """``x`` through the weights in turn, named a projection in a device
    trace (ops/parts.py: the innermost name is the operation's, so a mixer
    is ``mix.elementwise`` but for its products and the loops, which name
    themselves)."""
    with jax.named_scope(P.PROJ):
        for w in ws:
            x = L.matmul(x, w)
        return x


def scaled(p: dict, factors: dict) -> dict:
    """``p`` with every leaf that ``factors`` names times its factor, at any
    depth (an expert layer's ``down`` lies under ``experts`` and under
    ``shared``): where a branch's weights start."""
    return {k: scaled(v, factors) if isinstance(v, dict)
            else v * factors[k] if k in factors else v for k, v in p.items()}


class Branch(NamedTuple):
    """One residual branch of a block."""

    norm: str  # the block's key of the branch's RMS norm ...
    name: str  # ... and of its parameters
    init: Callable  # key -> those parameters, float32, scaled
    apply: Callable  # (p, y, context) -> y, or (y, *counts) where it counts
    # the part ``apply`` runs under (ops/parts.py); None: it names its own
    scope: Optional[str] = P.MIX_ELEMENTWISE
    # where ``y`` takes the compute type: under the "norm", under the
    # branch's own "scope", or None: never (a router reads the float32 norm).
    # A fusion takes its root's name, so the place is part of what a trace's
    # per-part times say.
    cast: Optional[str] = "norm"
    # ((its key in ``aux``, the shape of one layer's count), ...), int32
    counts: tuple = ()
    # (registry, component id, *those counts as host arrays, a row a layer)
    observe: Optional[Callable] = None
    # the block's key of a second RMS norm, of the branch's *output* before
    # the residual add (in float32, under ``norm``); None: there is none
    post: Optional[str] = None
    # where that norm's weights start: what the branch adds to the stream a
    # channel, whatever its own weights' scale
    post_scale: float = 1.0


def experts(norm: str, name: str, init: Callable, *, held: int, top_k: int,
            first_expert: int, scale: float, tile: Optional[int] = None,
            post: Optional[str] = None, post_scale: float = 1.0,
            router: str = "sigmoid", eps: float = 1e-20) -> Branch:
    """The dropless top-k expert layer (parallel/moe.py) as a branch, its
    scores the ``router``'s function of the logits (``"sigmoid"``, or
    ``"softmax"`` over the router's width; ``eps`` beside the chosen
    scores' sum: parallel/moe.py ``route_topk``), with the selection bias
    and the shared expert that ``init``'s tree has: it routes from the
    float32 norm, names its own parts and counts the tokens of each held
    expert, the assignments that fell on absent ones and the combine's tiles
    that wrote and that added. The counts' reader
    is told the router's width (read off ``init``'s shapes) and ``tile`` as
    the layer is: None, and both take the tile from the step's shapes
    (``parallel/moe.py run_tile``); a number, the toy presets' tile of 16
    rows."""
    width = jax.eval_shape(init, jax.ShapeDtypeStruct(
        (2,), jnp.uint32))["router"].shape[1]
    return Branch(
        norm, name, init,
        lambda p, y, _: topk_moe_layer_tiles(
            p, y, top_k, first_expert=first_expert, router=router,
            renormalize=True, scale=scale, tile=tile, eps=eps),
        scope=None, cast=None,
        counts=(("expert_tokens", (held,)), ("expert_absent", ()),
                ("combine_tiles", (2,))),
        observe=partial(observe_expert_counts, tile=tile, width=width),
        post=post, post_scale=post_scale)


def token_scorer(name: str, num_classes: int, input_shape: tuple,
                 blocks: tuple, *, dim: int, eps: float, hyper: dict,
                 max_rows: int, scale_emb: float = 1.0,
                 residual: float = 1.0, logit_scale: float = 1.0,
                 context: Optional[Callable] = None,
                 param_dtype=None, heads: int = 1, tied: bool = False,
                 embed_std: Optional[float] = None,
                 pin_stream: bool = False) -> ModelDef:
    """The model of ``blocks`` over ``num_classes`` rows of the vocabulary.
    With ``heads`` prediction heads ``num_classes`` is what an answer holds,
    ``heads`` distributions over ``num_classes / heads`` rows of the
    vocabulary laid end to end: the embedding has the vocabulary's rows, the
    head ``num_classes`` columns, and the logits leave as ``(B, heads,
    vocabulary)`` for the engine's softmax over the last axis.
    ``context(seq)``: what every branch is handed, made once a step.
    ``param_dtype`` None: ``init`` makes the whole tree in float32, leaf by
    leaf; a type: each leaf is handed over in it as a checkpoint of that type
    would be, a layer's leaves made by one small program in which each is
    drawn in float32, scaled and cast in one pass, so that no float32 leaf is
    ever written to memory and a layer's temporaries are gone before the
    next layer's are made (a float32 twin of 3.5 B parameters does not fit
    beside them). The values are those ``astype`` of the float32 draw gives.
    ``tied`` (``tie_word_embeddings``): the parameter tree has no ``"head"``
    and the logits are ``last @ embed^T``, a product with the embedding's
    own rows under the part ``head``. One matrix can start at one scale,
    and it decides both ends at once: ``embed_std``, a value's deviation
    (None: 1 over ``scale_emb``, the stream at N(0, 1) a channel as every
    untied scorer's). The last position's logit of its own id is ``scale_emb
    logit_scale dim embed_std^2`` over the final stream's root mean square,
    whatever the layers computed, so a tied model's builder chooses
    ``embed_std`` to keep that term among the others.
    ``pin_stream``: the stream after each residual add is one array the
    compiler may not take apart (an optimization barrier; no operation of its
    own). Without it the v5e compiler keeps an earlier stream and every
    branch's result in the served type since, and adds them again inside each
    later norm's fusion: half the stream's bytes a branch it keeps so, 0.67
    GB each at Falcon-H1's 65,536 tokens of 5,120 channels, 1.3 GB of a
    four-layer step's temporaries (PERF.md section 6, PR 66). False: the
    text every plan before it lowers to."""
    (seq,) = input_shape
    if tied and heads != 1:
        raise ValueError("a tied embedding serves one head")
    vocab, rest = divmod(num_classes, heads)
    if rest:
        raise ValueError(f"{num_classes} classes are not {heads} heads over "
                         "one vocabulary")
    f32 = jnp.float32
    counted: dict = {}  # a count's key in ``aux`` -> its shape, a layer each
    readers: dict = {}  # who reads -> the keys it is handed
    for b in (b for blk in blocks for b in blk if b.counts):
        for key, shape in b.counts:
            counted.setdefault(key, []).append(tuple(shape))
        readers[b.observe] = tuple(key for key, _ in b.counts)

    def served(tree):
        return tree if param_dtype is None else jax.tree.map(
            lambda a: a.astype(param_dtype), tree)

    def block_init(spec, *keys):
        made = [init(key) for (_, _, init, _, _), key in zip(spec, keys)]
        return served({
            k: v for (norm, mine, _, post, scale), p in zip(spec, made)
            for k, v in ((norm, L.rmsnorm_init(dim)), (mine, p),
                         (post, {"scale": jnp.full((dim,), scale, f32)}))
            if k})

    def ends_init(ke, kh):
        # A multiplier stands against weights trained under it; a draw that
        # stands for such a checkpoint starts the stream and the logits where
        # every other model's start (N(0, 1) a channel, LeCun's head): the
        # embedding over ``scale_emb`` (a tied matrix: at ``embed_std``, which
        # its builder chooses for both ends), the head over ``logit_scale``.
        embed = jax.random.normal(ke, (vocab, dim), f32)
        if embed_std is not None:
            embed = embed * embed_std
        elif scale_emb != 1:
            embed = embed / scale_emb
        ends = {"embed": embed, "norm": L.rmsnorm_init(dim)}
        if not tied:
            head = _w(kh, dim, num_classes)
            ends["head"] = head if logit_scale == 1 else head / logit_scale
        return served(ends)

    def init(rng):
        ks = jax.random.split(rng, sum(map(len, blocks)) + 2)
        one_block, ends = block_init, ends_init
        if param_dtype is not None:  # a program a layer, one a kind of block
            one_block = jax.jit(block_init, static_argnums=0)
            ends = jax.jit(ends_init)
        params = ends(ks[0], ks[1])
        params["layers"], at = [], 2
        for blk in blocks:
            params["layers"].append(one_block(
                tuple((b.norm, b.name, b.init, b.post, b.post_scale)
                      for b in blk),
                *(ks[at + j] for j in range(len(blk)))))
            at += len(blk)
        # what a step counts on the device, in the state in and out
        aux = {key: jnp.zeros((len(shapes),) + shapes[0], jnp.int32)
               for key, shapes in counted.items()}
        return params, {"aux": aux} if aux else {}

    def apply(params, state, x, train: bool = False):
        with jax.named_scope(P.EMBED):
            # ids ride the float32 instance contract (exact under 2^24)
            ids = jnp.clip(jnp.round(x.astype(f32)), 0,
                           vocab - 1).astype(jnp.int32)
            dtype = params["embed"].dtype
            # The stream is float32 whatever the compute type: a bfloat16
            # stream is rounded at each of its adds, and a router reading it
            # sends three times as many tokens to another expert than the
            # reference does. The branches compute in ``dtype``.
            h = params["embed"][ids].astype(f32)
            if scale_emb != 1:
                h = h * scale_emb
        ctx = context(x.shape[1]) if context else None
        counts = {key: [] for key in counted}
        for blk, branches in zip(params["layers"], blocks):
            for b in branches:
                with jax.named_scope(P.NORM):
                    y = L.rmsnorm(blk[b.norm], h, eps)
                    if b.cast == "norm":
                        y = y.astype(dtype)
                with jax.named_scope(b.scope) if b.scope else nullcontext():
                    if b.cast == "scope":
                        y = y.astype(dtype)
                    y = b.apply(blk[b.name], y, ctx)
                if b.counts:
                    y, *ns = y
                    for (key, _), n in zip(b.counts, ns):
                        counts[key].append(n)
                with jax.named_scope(P.NORM):
                    y = y.astype(f32)
                    if b.post:
                        y = L.rmsnorm(blk[b.post], y, eps)
                    h = h + (y if residual == 1 else residual * y)
                    if pin_stream:
                        h = jax.lax.optimization_barrier(h)
        with jax.named_scope(P.HEAD):
            last = L.rmsnorm(params["norm"], h[:, -1], eps)
            if logit_scale != 1:
                last = last * logit_scale
            logits = L.matmul(last.astype(dtype), params["embed"].T
                              if tied else params["head"])
            if heads != 1:
                logits = logits.reshape(-1, heads, vocab)
        if not counts:
            return logits, state
        return logits, {**state, "aux": {
            key: jnp.stack(ns) for key, ns in counts.items()}}

    def observe_aux(metrics, cid: str, aux: dict) -> None:
        """A step's fetched ``aux`` into the registry, each count by the
        reader that stands beside the op that counted it."""
        for observe, keys in readers.items():
            observe(metrics, cid, *(aux[key] for key in keys))

    return ModelDef(
        name, (seq,), num_classes, init, apply, max_rows=max_rows,
        input_dtype="float32",
        hyper={**hyper, "input_shape": (seq,), "num_classes": num_classes},
        observe_aux=observe_aux if readers else None)
