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

A plan may run ``passes`` times over its one set of leaves (a looped model):
the walk over the blocks is then the body of one ``lax.fori_loop``, the last
norm runs on the *whole* stream after every pass (the next pass starts from
it) and an exit gate reads each pass's normed last position:

    per pass t: h = blocks(h); h = RMSNorm(h); z_t = h[last];
                lambda_t = sigmoid(w_exit . z_t + b_exit)
    p_t = lambda_t prod_{j<t}(1 - lambda_j), p_T the rest; tau = the first t
    with p_1 + ... + p_t >= threshold (T where none); head(z_tau)

and the step counts, in ``aux["exit_pass"]``, how many of its rows left at
each pass.

A model's file keeps what is its own: its mixers, its *plan* (a tuple of
blocks, each a tuple of :class:`Branch`), its scalars, what it makes once a
step (``context``: rotary tables) and its presets, and hands them to
:func:`token_scorer`. The parameter tree is ``{"embed", "layers": [{<norm>,
<name>, ...}, ...], "norm", "head"}`` (no ``"head"`` where the model is
``tied``; ``"exit": {"w", "b"}`` beside them where it runs several passes);
``split(rng, branches + 2)`` gives the embedding key 0, the head key 1
(unused where tied) and every branch of the plan the next; a looped model
asks for one key more, its gate's, the last (no other model's draw moves).
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


def exit_row(p: dict, lasts: jnp.ndarray, threshold: float) -> tuple:
    """``(z, left)``: of each pass's normed last position ``lasts (T, B,
    dim)`` the row each record's answer is read from, ``(B, dim)``, and how
    many of the ``B`` left at each pass, ``(T,)`` int32. The gate ``lambda_t
    = sigmoid(w . z_t + b)`` gives pass ``t`` the weight ``lambda_t
    prod_{j<t}(1 - lambda_j)`` and the last pass the rest; a record leaves
    at the first pass where the weights so far reach ``threshold``, at the
    last where none does. At 1 or more none can (a sigmoid is under 1 and a
    float32's may not be): every record reads the last pass and nothing of
    the gate is computed."""
    passes, rows = lasts.shape[:2]
    if threshold >= 1:
        return lasts[-1], jnp.zeros((passes,), jnp.int32).at[-1].set(rows)
    f32 = jnp.float32
    gate = jax.nn.sigmoid(lasts @ p["w"].astype(f32) + p["b"].astype(f32))
    stay = jnp.cumprod(1.0 - gate[:-1], axis=0)  # past pass t, t < T
    before = jnp.concatenate([jnp.ones((1, rows), f32), stay[:-1]])
    reached = jnp.cumsum(gate[:-1] * before, axis=0) >= threshold
    tau = jnp.where(reached.any(0), jnp.argmax(reached, axis=0), passes - 1)
    z = jnp.take_along_axis(lasts, tau[None, :, None], axis=0)[0]
    return z, jnp.sum(tau[None] == jnp.arange(passes)[:, None], axis=1,
                      dtype=jnp.int32)


def observe_exits(metrics, cid: str, left, *, passes: int) -> None:
    """What a looped step counted (``left (passes,)``: the step's rows,
    padding among them, that left at each pass) into the registry under
    ``cid``: the rows by pass, and the passes the step ran for them (every
    row runs every pass: a row that has left saves nothing in a batch)."""
    for t, n in enumerate(left, 1):
        metrics.counter(cid, f"exit_pass_rows_{t}").inc(int(n))
    metrics.counter(cid, "passes_run").inc(int(left.sum()) * passes)


def token_scorer(name: str, num_classes: int, input_shape: tuple,
                 blocks: tuple, *, dim: int, eps: float, hyper: dict,
                 max_rows: int, scale_emb: float = 1.0,
                 residual: float = 1.0, logit_scale: float = 1.0,
                 context: Optional[Callable] = None,
                 param_dtype=None, heads: int = 1, tied: bool = False,
                 embed_std: Optional[float] = None,
                 pin_stream: bool = False, passes: int = 1,
                 threshold: float = 1.0) -> ModelDef:
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
    text every plan before it lowers to.
    ``passes`` over 1: the blocks run that many times over the same leaves,
    one ``lax.fori_loop`` whose body is a pass (the program's text is one
    pass's whatever ``passes`` says; the leaves and ``context`` are closed
    over, the carry is the stream and the passes' last-position rows), the
    last norm on the whole stream inside it, and the answer is read from the
    pass the exit gate's rule names under ``threshold`` (the module's
    docstring; at 1 or more no sum before the last can reach it, so the
    program reads the last pass alone). Every pass runs for every row of a
    step either way. 1: the walk every plan before it lowers to."""
    (seq,) = input_shape
    if passes < 1:
        raise ValueError(f"{passes} passes")
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
    if passes > 1:
        if counted:
            raise ValueError(
                "a plan whose branches count runs once: no model runs "
                "counting branches several passes, and a count a pass has "
                "no reader")
        readers[partial(observe_exits, passes=passes)] = ("exit_pass",)

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

    def ends_init(ke, kh, kg=None):
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
        if passes > 1:  # the exit gate: [dim] -> 1, with a bias
            ends["exit"] = {"w": _w(kg, dim, 1)[:, 0], "b": jnp.zeros((), f32)}
        return served(ends)

    def init(rng):
        ks = jax.random.split(rng, sum(map(len, blocks)) + 2
                              + (passes > 1))
        one_block, ends = block_init, ends_init
        if param_dtype is not None:  # a program a layer, one a kind of block
            one_block = jax.jit(block_init, static_argnums=0)
            ends = jax.jit(ends_init)
        params = ends(ks[0], ks[1], *(ks[-1:] if passes > 1 else ()))
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
        if passes > 1:  # the rows that left at each pass
            aux["exit_pass"] = jnp.zeros((passes,), jnp.int32)
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

        def walk(h):
            """The blocks once over the stream."""
            for blk, branches in zip(params["layers"], blocks):
                for b in branches:
                    with jax.named_scope(P.NORM):
                        y = L.rmsnorm(blk[b.norm], h, eps)
                        if b.cast == "norm":
                            y = y.astype(dtype)
                    with jax.named_scope(b.scope) if b.scope \
                            else nullcontext():
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
            return h

        if passes == 1:
            h = walk(h)
        else:
            def one_pass(t, carry):
                h, lasts = carry
                h = walk(h)
                with jax.named_scope(P.NORM):  # the next pass starts from it
                    h = L.rmsnorm(params["norm"], h, eps)
                    return h, jax.lax.dynamic_update_index_in_dim(
                        lasts, h[:, -1], t, 0)

            h, lasts = jax.lax.fori_loop(
                0, passes, one_pass,
                (h, jnp.zeros((passes, h.shape[0], dim), f32)))
        with jax.named_scope(P.HEAD):
            if passes == 1:
                last = L.rmsnorm(params["norm"], h[:, -1], eps)
            else:
                last, left = exit_row(params["exit"], lasts, threshold)
            if logit_scale != 1:
                last = last * logit_scale
            logits = L.matmul(last.astype(dtype), params["embed"].T
                              if tied else params["head"])
            if heads != 1:
                logits = logits.reshape(-1, heads, vocab)
        aux = {key: jnp.stack(ns) for key, ns in counts.items()}
        if passes > 1:
            aux["exit_pass"] = left
        if not aux:
            return logits, state
        return logits, {**state, "aux": aux}

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
