"""Gated delta-rule linear attention with a per-channel decay (Kimi Delta
Attention, the linear-attention layer of ``kimi_linear``), computed in chunks,
and the short causal convolution that feeds it.

Per head the layer keeps a state ``S`` in R^{dk x dv} and reads it token by
token:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

with ``g_t <= 0`` a log-decay per key channel and ``beta_t`` in (0, 1), or in
(0, 2) where a model lets a transition reflect (``1 - beta_t < 0`` along
``k_t``; nothing below assumes either). Token by token that is ``S`` dependent
steps of vector work; here a sequence is cut into chunks of ``chunk`` tokens,
everything inside a chunk is matrix products, and only the ``S / chunk`` states
are a chain (Yang et al. 2024, "Parallelizing linear transformers with the delta
rule over sequence length", with the per-channel gate of gated linear attention).

Within a chunk, with ``G_t`` the decay summed from the chunk's start to ``t``
and ``u_t`` the row that token ``t`` writes (``S_t = Diag(exp(g_t)) S_{t-1} +
k_t u_t^T``):

    (I + tril(Diag(beta) A, -1)) U = Diag(beta) (V - (K * exp(G)) S_0)
    A[t, s] = sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])            (s < t)
    O = (Q * exp(G)) S_0 + tril(A_qk) U        (A_qk with q_t for k_t, s <= t)
    S_C = Diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U

**Kept stable in float32.** ``exp(G_t - G_s)`` is at most one, but its factors
``exp(G_t)`` and ``exp(-G_s)`` are not: a channel that forgets within a few
tokens overflows ``exp(-G_s)`` inside one chunk. So ``A`` is never formed from
those two factors. A chunk is cut into sub-chunks of ``sub`` tokens; a block
of ``A`` between two different sub-chunks takes the decay at the later one's
start as the point both factors are measured from (both exponents are then at
most zero), and a block on the diagonal is summed pair by pair on the vector
unit with the exponent of the difference. The unit-triangular system is solved
exactly: each ``sub`` x ``sub`` diagonal block by forward substitution, the
blocks below by block substitution, all in float32 at ``highest`` precision.
The large products (with ``S``, ``U``) take their operands in the type of
``q`` (bfloat16 when serving) and accumulate in float32; ``S`` itself stays
float32 from chunk to chunk.

**Two forms of the within-chunk part, one contract.** What a chunk needs
that does not depend on the state before it (``G``, the tables ``A`` and
``A_qk``, the solve for ``W`` and ``U0`` with ``U = U0 - W S_0``, ``Q *
exp(G)``, ``K * exp(G_C - G)``, ``exp(G_C)``) is built by ``_within_chunks``,
jnp operations that XLA compiles, or by ``within_chunks_kernel``, one Pallas
TPU call. The rules above hold in both; they differ in the order of their
sums. The kernel's grid walks (head, block of ``_KERNEL_CHUNKS`` chunks); a
grid step's block is those chunks' tokens by one head's lanes of ``q, k, v,
g`` as the layer holds them, ``(B, S, H * d)``, so nothing is copied to cut a
row out or to bring the heads forward. Inside a step a chunk at a time: its
``(C, d)`` operands, both ``C x C`` tables and the six results stay in VMEM,
and HBM sees each input and each result once, where XLA's form makes some
forty passes over a row's float32 arrays. A call writes its row into room
for the whole batch (``empty_tables``) laid out as the chain over chunks
reads it, chunks first, so the loop over rows stacks and transposes nothing.
The kernel's sub-chunk is 8, one float32 sublane tile; its triangle is solved
by forward substitution row by row on the vector unit in float32, on ``[W |
U0]`` directly. It is bound by the unit that moves values across lanes (a sum
over channels for every pair of a diagonal block; a column of ``A`` spread
over the lanes for every row that leaves a sub-chunk below it), not by HBM or
the matrix unit.

**A head is a block of lanes, in and out.** ``kda_chunked`` takes ``q, k, v,
g`` as ``(B, S, H * d)``, the form the layer's projections yield and the
kernel reads, and hands its result back so. On a TPU that is no matter of
notation: ``[.., H * d]`` is tiled eight positions by 128 lanes and ``[.., H,
d]`` eight *heads* by 128 lanes, so a reshape from one to the other moves
every element (1.6 ms for a branch of ``f32[8, 4096, 4096]`` on a v5e).
Hence also ``l2norm_heads``, whose statistic a head is a product with a 0/1
matrix, and ``out``, the layer's norm of a head's output rows done where the
chain leaves them, ``dv`` the minor axis. XLA's form cuts heads out itself,
where it pays forty passes anyway. The one pass left is the result's way
from chunks-first to lanes, in the compute type.

``tables_form`` says which form a program is built with, from what the code
can observe and no option: the kernel on a TPU (``STORM_TPU_NO_PALLAS`` off)
in a process with one device (a Mosaic call has no partitioning rule), for
heads of whole lane tiles (``dk`` and ``dv`` multiples of 128) and chunks of
whole sub-chunks; XLA's form elsewhere (the CPU, a host with several chips,
``kimi_linear_tiny``'s 16-wide heads). ``platform.note("kda_tables", form)``
records the choice for ``engine_inventory()["programs"]``.

**The short convolution and the SiLU after it** are likewise two forms of
one contract, chosen by ``conv_form``: ``short_conv`` and the activation as
XLA fuses them, or ``conv_silu_kernel``, one Pallas TPU call that reads the
projection's result once, in the type the projection wrote, where it lies
(all of Kimi-Linear's branch; the first 6,144 columns of Nemotron's ``[x B C
| dt]``, with the bias), each block of positions with the few rows before it, taps,
bias and activation in float32 in VMEM and one rounding on the way out.
(XLA's fusion has the projection write float32 and takes that array four
times, once a tap.) ``platform.note("short_conv", form)``.

**Both forms run a row of the batch at a time, under one loop** (``lax.map``;
for the kernel ``lax.fori_loop``, which carries the room it writes into).
For XLA's form that bounds the float32 temporaries to one row's. For both it
is one ``while`` in the compiled program whose event in a device trace spans
the tables' whole time: the benchmark's ``kda_scan_ms`` finds KDA as the
loops that carry the stacked ``A_qk``, and with the kernel inside that loop
(its grid over one row's heads and chunks, not over rows) the metric keeps
reading tables plus chain.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from storm_tpu.ops import parts as P
from storm_tpu.ops.platform import note as _note
from storm_tpu.ops.platform import one_device as _one_device
from storm_tpu.ops.platform import use_pallas as _use_pallas

_HI = lax.Precision.HIGHEST
# The kernel's own sub-chunk: one float32 sublane tile (half the pair-by-pair
# work of 16, and a sub-chunk's rows are then one register's).
_KERNEL_SUB = 8
# Chunks of one head a grid step of the kernel holds in VMEM: blocks of 128 KB
# to 256 KB an operand; 16 measured the same on the v5e.
_KERNEL_CHUNKS = 8
# The convolution's kernel: the positions of a grid step's block (the least,
# which a sequence must be whole blocks of, and the most: 2,048 x 128 read
# 1.00 ms for ``[8, 4096, 4096]`` on the v5e, 512 x 512 1.09, 512 x 128
# 1.38), its lanes (one tile: channels need be no more than whole tiles), the
# positions it convolves at a time, and the float32 rows (one sublane tile) a
# block hands the next, which bounds the taps at nine.
_CONV_ROWS_LEAST = 512
_CONV_ROWS_MOST = 2048
_CONV_LANES = 128
_CONV_STEP = 64
_CONV_CARRY = 8


def short_conv_init(rng, channels: int, width: int = 4,
                    dtype=jnp.float32, bias: bool = False) -> dict:
    """A depthwise kernel ``[width, channels]``; the last tap is the current
    token's. ``bias``: one more number a channel, U(-1, 1) over the root of
    the width (as a framework's depthwise convolution starts it), so that it
    matters."""
    if not bias:
        return {"w": jax.random.normal(rng, (width, channels), dtype)
                * (1.0 / width) ** 0.5}
    kw, kb = jax.random.split(rng)
    return {**short_conv_init(kw, channels, width, dtype),
            "b": jax.random.uniform(kb, (channels,), dtype, -1.0, 1.0)
            * (1.0 / width) ** 0.5}


def short_conv(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    """Causal depthwise convolution over ``(B, S, C)``: ``y_t = sum_j w[j] *
    x_{t - (width - 1) + j}`` (plus the bias where the kernel has one),
    tokens before the first read as zero."""
    w = p["w"].astype(jnp.float32)
    width, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (width - 1, 0), (0, 0)))
    y = sum(w[j] * xp[:, j:j + s] for j in range(width))
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)
    return y.astype(x.dtype)


def conv_form(channels: int, seq: int, width: int) -> str:
    """Which form runs the convolution with its activation: ``"kernel"``
    (``conv_silu_kernel``) or ``"xla"`` (``short_conv``, then the activation,
    as XLA fuses them). A function of the traced shapes and of what the
    process runs on, as ``tables_form``: the kernel on a TPU in a process
    with one device, for channels of whole lane tiles, sequences of whole
    blocks of positions and taps that reach back no further than the eight
    rows a block hands the next."""
    if (_use_pallas() and _one_device() and channels % _CONV_LANES == 0
            and seq % _CONV_ROWS_LEAST == 0 and width - 1 <= _CONV_CARRY):
        return "kernel"
    return "xla"


def _conv_kernel(*refs, rows: int, step: int, bias: bool, activation,
                 gated: bool = False):
    """One block of positions by one block of lanes of one row of the batch.
    ``wide`` is float32 room for the block under ``_CONV_CARRY`` rows of
    what came before it: the block is widened into it once, and a tap's
    operand is that room read ``j`` rows up. The head rows are zero at a
    row's first block and the previous block's last rows after it (the grid
    walks a row's positions innermost, in order). ``gated``: two more blocks
    of the same positions and lanes, one the input is multiplied by before
    the taps and one the result after them, in float32."""
    x_ref, w_ref = refs[:2]
    b_ref = refs[2] if bias else None
    in_ref, out_ref = refs[-4:-2] if gated else (None, None)
    o_ref, wide = refs[-2:]
    head = _CONV_CARRY
    width = w_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        wide[:head] = jnp.zeros((head, wide.shape[1]), jnp.float32)

    taps = [w_ref[j:j + 1, :] for j in range(width)]
    for lo in range(0, rows, step):
        xf = x_ref[lo:lo + step, :].astype(jnp.float32)
        if gated:
            xf = xf * in_ref[lo:lo + step, :].astype(jnp.float32)
        wide[head + lo:head + lo + step] = xf
        back = head + lo - (width - 1)
        terms = [taps[j] * wide[back + j:back + j + step]
                 for j in range(width - 1)] + [taps[-1] * xf]
        y = sum(terms[1:], terms[0])  # short_conv's order: the oldest first
        if bias:
            y = y + b_ref[...]
        y = activation(y)
        if gated:
            y = y * out_ref[lo:lo + step, :].astype(jnp.float32)
        o_ref[lo:lo + step, :] = y.astype(o_ref.dtype)
    wide[:head] = wide[rows:]


def conv_silu_kernel(w, b, x, *, activation=jax.nn.silu, gates=None,
                     rows: int = None, step: int = None,
                     interpret: bool = False):
    """``activation(short_conv({"w": w, "b": b}, x[..., :C]))`` for ``w:
    (width, C)``, ``b: (C,)`` or None and ``x: (B, S, wide)``, ``wide >=
    C``, as one Pallas TPU call that reads those columns once, in their own
    type and where they lie: taps, bias and activation in float32 in VMEM,
    one rounding on the way out. The grid walks (row of the batch, lane
    tile, block of ``rows`` positions), positions innermost and in order: a
    block hands its last ``_CONV_CARRY`` rows to the next in float32 scratch
    (zeros at a row's first block: tokens before the first read as zero, and
    nothing crosses from one row to the next). ``C`` is whole lane tiles
    (``wide`` need not be), ``S`` a multiple of ``rows`` and ``rows`` of
    ``step``, the positions widened and convolved at a time (what stays in
    registers between a tap and the rounding). ``gates`` ``(before, after)``:
    the first columns in ``x`` (whole lane tiles) of two more ranges of ``C``
    columns, ``activation(short_conv(x[..., :C] * x[..., before:before +
    C])) * x[..., after:after + C]``, each read once beside the first, the
    products in float32 (:func:`gated_conv`)."""
    width, channels = w.shape
    bsz, seq, _ = x.shape
    lanes = _CONV_LANES
    rows = rows or math.gcd(seq, _CONV_ROWS_MOST)
    step = step or min(rows, _CONV_STEP)
    f32 = jnp.float32

    def columns(first):  # a block of the range of ``x`` that begins there
        at = first // lanes
        return pl.BlockSpec((None, rows, lanes), lambda i, c, s: (
            i, s, c + at if at else c))

    operands = [x, w.astype(f32)]
    specs = [columns(0),
             pl.BlockSpec((width, lanes), lambda i, c, s: (0, c))]
    if b is not None:
        operands.append(b.astype(f32).reshape(1, channels))
        specs.append(pl.BlockSpec((1, lanes), lambda i, c, s: (0, c)))
    for first in gates or ():
        operands.append(x)
        specs.append(columns(first))
    return pl.pallas_call(
        functools.partial(_conv_kernel, rows=rows, step=step,
                          bias=b is not None, activation=activation,
                          gated=gates is not None),
        grid=(bsz, channels // lanes, seq // rows),
        in_specs=specs,
        out_specs=pl.BlockSpec((None, rows, lanes),
                               lambda i, c, s: (i, s, c)),
        out_shape=jax.ShapeDtypeStruct((bsz, seq, channels), x.dtype),
        scratch_shapes=[pltpu.VMEM((_CONV_CARRY + rows, lanes), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)


def conv_silu(p: dict, x: jnp.ndarray,
              activation=jax.nn.silu) -> jnp.ndarray:
    """``activation(short_conv(p, x[..., :C]))`` for the ``C`` channels the
    kernel ``p`` has, the first columns of a projection's result ``x: (B, S,
    wide)``, in the form ``conv_form`` gives this program."""
    width, channels = p["w"].shape
    form = conv_form(channels, x.shape[1], width)
    _note("short_conv", form)
    if form == "kernel":
        return conv_silu_kernel(p["w"], p.get("b"), x, activation=activation)
    return activation(short_conv(p, x[..., :channels]))


def _as_is(y):
    return y


def gated_conv(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    """The gated short convolution of a projection's result ``x: (B, S, 3
    C)`` = ``[b | c | u]``, three ranges of the ``C`` channels the kernel
    ``p`` has: ``c_t * short_conv(b * u)_t`` with no activation, ``(B, S,
    C)`` in ``x``'s type. Both products and the taps in float32, one
    rounding out, in either form of :func:`conv_form`'s rule: ``"kernel"``,
    ``conv_silu_kernel`` with its two ``gates`` (the three ranges read once
    each where they lie, nothing between them written), or ``"xla"``, the
    same arithmetic as XLA fuses it. Noted ``gated_conv=<form>``."""
    width, channels = p["w"].shape
    form = conv_form(channels, x.shape[1], width)
    _note("gated_conv", form)
    if form == "kernel":
        return conv_silu_kernel(p["w"], p.get("b"), x, activation=_as_is,
                                gates=(2 * channels, channels))
    f32 = jnp.float32
    b, c, u = (x[..., i * channels:(i + 1) * channels].astype(f32)
               for i in range(3))
    return (c * short_conv(p, b * u)).astype(x.dtype)


def l2norm(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    return (xf * lax.rsqrt(jnp.sum(xf * xf, -1, keepdims=True) + eps)
            ).astype(x.dtype)


def _head_of_lane(wide: int, heads: int, cols: int, dtype) -> jnp.ndarray:
    """``(H * d, cols)``: one where the lane is of the head its column
    counts, zero elsewhere (and in every column past the heads)."""
    lane = lax.broadcasted_iota(jnp.int32, (wide, cols), 0)
    col = lax.broadcasted_iota(jnp.int32, (wide, cols), 1)
    return (lane // (wide // heads) == col).astype(dtype)


def head_sums(x: jnp.ndarray, heads: int) -> jnp.ndarray:
    """The sums of ``x: (..., H * d)`` over each head's block of ``d`` lanes,
    ``(..., H)``, as a product with a 0/1 matrix: ``x`` is read where it
    lies. (A sum over the view ``(..., H, d)`` re-tiles ``x`` on a TPU: eight
    heads to a tile there, eight positions here.) At ``highest`` every bit of
    a float32 ``x`` reaches the sum, as in the view's. The matrix is whole
    lane tiles of columns wide, the heads' first: the v5e's compiler then
    makes it once a program and gives the product the windows that read
    0.40 ms a branch of ``[8, 4096, 4096]``; ``H`` columns wide it builds it
    inside the product, whose windows shrink in a whole model's program
    (1.38 ms)."""
    wide = x.shape[-1]
    cols = -(-heads // 128) * 128
    return jnp.dot(x, _head_of_lane(wide, heads, cols, x.dtype),
                   precision=_HI,
                   preferred_element_type=jnp.float32)[..., :heads]


def over_heads(stat: jnp.ndarray, d: int) -> jnp.ndarray:
    """A number a head ``(..., H)`` spread over its head's ``d`` lanes,
    ``(..., H * d)``, by the same matrix: exact at ``highest`` (a lane's sum
    has one term), and the compiler fuses what is done with it into the
    product's output, where a broadcast to ``(..., H, d)`` and a reshape
    write the spread array to HBM twice."""
    heads = stat.shape[-1]
    return jnp.dot(stat, _head_of_lane(heads * d, heads, heads, stat.dtype).T,
                   precision=_HI, preferred_element_type=jnp.float32)


def l2norm_heads(x: jnp.ndarray, heads: int,
                 eps: float = 1e-6) -> jnp.ndarray:
    """``l2norm`` of each head of ``x: (..., S, H * d)``, a head a block of
    ``d`` lanes: ``x`` stays in lanes, only the statistic is a number a
    head."""
    xf = x.astype(jnp.float32)
    inv = lax.rsqrt(head_sums(xf * xf, heads) + eps)
    return (xf * over_heads(inv, x.shape[-1] // heads)).astype(x.dtype)


def _unit_lower_inverse(n: jnp.ndarray, sub: int) -> jnp.ndarray:
    """``(I + N)^-1`` for strictly lower-triangular ``N`` of shape
    ``(..., C, C)``, ``C`` a multiple of ``sub``: forward substitution inside
    each diagonal ``sub`` x ``sub`` block, block substitution below."""
    c = n.shape[-1]
    nb = c // sub
    lead = n.shape[:-2]
    blocks = n.reshape(*lead, nb, sub, nb, sub)
    eye = jnp.eye(sub, dtype=n.dtype)
    # diagonal blocks, all at once: row i of the inverse is e_i less row i of
    # N times the rows above it
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(nb)], -3)
    rows = [jnp.broadcast_to(eye[0], diag.shape[:-2] + (sub,))]
    for i in range(1, sub):
        rows.append(eye[i] - jnp.einsum(
            "...j,...jk->...k", diag[..., i, :i], jnp.stack(rows, -2),
            precision=_HI))
    inv = jnp.stack(rows, -2)
    # below the diagonal: T_ij = -T_ii sum_{j <= l < i} N_il T_lj
    t = [[None] * nb for _ in range(nb)]
    for i in range(nb):
        t[i][i] = inv[..., i, :, :]
        for j in range(i):
            acc = sum(jnp.einsum("...ab,...bc->...ac",
                                 blocks[..., i, :, l, :], t[l][j],
                                 precision=_HI) for l in range(j, i))
            t[i][j] = -jnp.einsum("...ab,...bc->...ac", t[i][i], acc,
                                  precision=_HI)
    zero = jnp.zeros_like(t[0][0])
    return jnp.concatenate(
        [jnp.concatenate([t[i][j] if j <= i else zero for j in range(nb)], -1)
         for i in range(nb)], -2)


def _within_chunks(q, k, v, g, beta, sub: int):
    """Everything of a chunk that does not need the state before it, for
    ``(H, N, C, d)`` inputs (one row of the batch): the solved ``W`` and
    ``U0`` (``U = U0 - W S_0``), ``Q * exp(G)``, ``K * exp(G_C - G)``,
    ``tril(A_qk)`` and ``exp(G_C)``."""
    cd = q.dtype
    f32 = jnp.float32
    c = q.shape[-2]
    ns = c // sub
    qf, kf = q.astype(f32), k.astype(f32)
    big_g = jnp.cumsum(g.astype(f32), axis=-2)  # (H, N, C, dk), <= 0

    def dot(a, b):  # (.., t, d) x (.., s, d) -> (.., t, s)
        return jnp.einsum("...td,...sd->...ts", a.astype(cd), b.astype(cd),
                          preferred_element_type=f32)

    # blocks on the diagonal: pair by pair, the exponent of the difference
    shape = q.shape[:-2] + (ns, sub, q.shape[-1])
    gs, qs, ks = (y.reshape(shape) for y in (big_g, qf, kf))
    t_idx = jnp.arange(sub)
    later = (t_idx[:, None] >= t_idx[None, :])[..., None]  # s <= t
    pair = jnp.exp(jnp.where(later, gs[..., :, None, :] - gs[..., None, :, :],
                             -jnp.inf))
    d_kk = jnp.sum(ks[..., :, None, :] * ks[..., None, :, :] * pair, -1)
    d_qk = jnp.sum(qs[..., :, None, :] * ks[..., None, :, :] * pair, -1)
    # blocks between different sub-chunks: both factors measured from the
    # decay at the later sub-chunk's start, so neither exponent is positive.
    # A row of blocks is put together left to right, the table top to bottom
    rows_kk, rows_qk = [], []
    for i in range(ns):
        lo, hi = i * sub, (i + 1) * sub
        kk, qk = [d_kk[..., i, :, :]], [d_qk[..., i, :, :]]
        if i:
            ref = big_g[..., lo - 1:lo, :]
            right = kf[..., :lo, :] * jnp.exp(ref - big_g[..., :lo, :])
            decay = jnp.exp(big_g[..., lo:hi, :] - ref)
            kk.insert(0, dot(kf[..., lo:hi, :] * decay, right))
            qk.insert(0, dot(qf[..., lo:hi, :] * decay, right))
        if hi < c:
            kk.append(jnp.zeros(q.shape[:-2] + (sub, c - hi), f32))
            qk.append(kk[-1])
        rows_kk.append(jnp.concatenate(kk, -1))
        rows_qk.append(jnp.concatenate(qk, -1))
    a_kk = jnp.concatenate(rows_kk, -2)
    a_qk = jnp.concatenate(rows_qk, -2)
    bf = beta.astype(f32)[..., None]  # (H, N, C, 1)
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    t_inv = _unit_lower_inverse(jnp.where(strict, bf * a_kk, 0.0), sub)
    decay_in = jnp.exp(big_g)
    w = jnp.einsum("...ts,...sd->...td", t_inv, bf * kf * decay_in,
                   precision=_HI)
    u0 = jnp.einsum("...ts,...sd->...td", t_inv, bf * v.astype(f32),
                    precision=_HI)
    total = big_g[..., -1:, :]
    return (w.astype(cd), u0, (qf * decay_in).astype(cd),
            (kf * jnp.exp(total - big_g)).astype(cd),
            a_qk.astype(cd), jnp.exp(total[..., 0, :]))


def tables_form(dk: int, dv: int, chunk: int) -> str:
    """Which form builds a row's within-chunk tables: ``"kernel"`` (the
    Pallas kernel below) or ``"xla"`` (``_within_chunks``). A function of the
    traced shapes and of what the process runs on, as ``ops/attention.py
    attention_form``: the kernel on a TPU in a process with one device (a
    Mosaic call has no partitioning rule, ops/platform.py ``one_device``),
    for heads that fill whole lane tiles and chunks of whole sub-chunks."""
    if (_use_pallas() and _one_device() and dk % 128 == 0 and dv % 128 == 0
            and chunk % _KERNEL_SUB == 0):
        return "kernel"
    return "xla"


def _chunk_tables(q, k, v, g, beta_row):
    """One chunk of one head in VMEM: ``q, k: (C, dk)``, ``v: (C, dv)`` in
    the compute type, ``g: (C, dk)`` float32, ``beta_row: (1, C)`` float32.
    The same six results as ``_within_chunks``, by the same rules."""
    cd = q.dtype
    f32 = jnp.float32
    c, dk = q.shape
    sub, ns = _KERNEL_SUB, c // _KERNEL_SUB
    qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # beta as a column: the diagonal of its row spread over the rows
    bf = jnp.sum(jnp.where(row == col, beta_row, 0.0), axis=1, keepdims=True)
    # the decay summed from the chunk's start, by doubling steps down the rows
    big_g = g
    tok = lax.broadcasted_iota(jnp.int32, (c, dk), 0)
    shift = 1
    while shift < c:
        big_g = big_g + jnp.where(tok >= shift,
                                  pltpu.roll(big_g, shift, 0), 0.0)
        shift *= 2
    # blocks on the diagonal, pair by pair with the exponent of the
    # difference: column j of every sub-chunk at once, summed over channels
    shape = (ns, sub, dk)
    gs, qs, ks = (y.reshape(shape) for y in (big_g, qf, kf))
    t_in = lax.broadcasted_iota(jnp.int32, shape, 1)
    here = col - (row // sub) * sub  # a column's place in its row's sub-chunk
    a_qk = jnp.zeros((c, c), f32)
    diag_kk = []
    for j in range(sub):
        pair = jnp.exp(jnp.where(t_in >= j, gs - gs[:, j:j + 1, :], -jnp.inf))
        kj = ks[:, j:j + 1, :] * pair
        diag_kk.append(jnp.sum(ks * kj, -1, keepdims=True))
        a_qk = jnp.where(here == j, jnp.sum(qs * kj, -1, keepdims=True
                                            ).reshape(c, 1), a_qk)
    # blocks between different sub-chunks: both factors measured from the
    # decay at the later sub-chunk's start. Keys after that start are not of
    # these blocks: their exponent is held at zero and their columns dropped
    below_kk, rows_qk = [None], [a_qk[:sub]]
    for i in range(1, ns):
        lo, hi = i * sub, (i + 1) * sub
        ref = big_g[lo - 1:lo]
        right = (kf * jnp.exp(jnp.minimum(ref - big_g, 0.0))).astype(cd)
        decay = jnp.exp(big_g[lo:hi] - ref)
        left = jnp.concatenate([kf[lo:hi] * decay, qf[lo:hi] * decay], 0)
        both = lax.dot_general(left.astype(cd), right,
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=f32)  # (2 sub, C)
        before = col[:sub] < lo
        below_kk.append(jnp.where(before, bf[lo:hi] * both[:sub], 0.0))
        rows_qk.append(jnp.where(before, both[sub:], a_qk[lo:hi]))
    a_qk = jnp.concatenate(rows_qk, 0)
    # (I + tril(beta A_kk, -1)) [W | U0] = beta [K exp(G) | V] by forward
    # substitution on the vector unit, in float32: a row that is final leaves
    # every row below it. A sub-chunk's rows are one sublane tile. Within it
    # the coefficients are the sums above as they stand, a value a row
    # (spreading a column of a table over the lanes is the costly step here,
    # and these need none)
    within = [bf * jnp.where(t_in[:, :, :1] > j, kk, 0.0).reshape(c, 1)
              for j, kk in enumerate(diag_kk[:-1])]
    decay_in = jnp.exp(big_g)
    rhs_w, rhs_u = bf * kf * decay_in, bf * vf
    w = [rhs_w[i * sub:(i + 1) * sub] for i in range(ns)]
    u = [rhs_u[i * sub:(i + 1) * sub] for i in range(ns)]
    for s in range(c - 1):
        i0, j = divmod(s, sub)
        w_s, u_s = w[i0][j:j + 1], u[i0][j:j + 1]
        if j < sub - 1:
            coef = within[j][i0 * sub:(i0 + 1) * sub]
            w[i0], u[i0] = w[i0] - coef * w_s, u[i0] - coef * u_s
        for i in range(i0 + 1, ns):
            coef = below_kk[i][:, s:s + 1]
            w[i], u[i] = w[i] - coef * w_s, u[i] - coef * u_s
    w, u = jnp.concatenate(w, 0), jnp.concatenate(u, 0)
    # the decay still to come after a token, summed up the rows as ``big_g``
    # is down them (``G_C - G`` as a difference of two sums of a whole
    # chunk's size would lose the small exponents near the chunk's end)
    to_come, shift = jnp.where(tok < c - 1, pltpu.roll(g, c - 1, 0), 0.0), 1
    while shift < c:
        to_come = to_come + jnp.where(
            tok < c - shift, pltpu.roll(to_come, c - shift, 0), 0.0)
        shift *= 2
    return (w.astype(cd), u,
            (qf * decay_in).astype(cd), (kf * jnp.exp(to_come)).astype(cd),
            jnp.where(row >= col, a_qk, 0.0).astype(cd),
            jnp.exp(big_g[c - 1:c]))


def _tables_kernel(row_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, *refs,
                   chunk):
    del row_ref  # read by the block specs
    outs = refs[len(refs) // 2:]  # after the buffers they are written into

    def one(i, carry):
        tokens = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
        res = _chunk_tables(q_ref[tokens, :], k_ref[tokens, :],
                            v_ref[tokens, :], g_ref[tokens, :],
                            beta_ref[0, pl.ds(i, 1), :])
        for ref, y in zip(outs[:5], res[:5]):
            ref[i] = y
        outs[5][pl.ds(i, 1), :] = res[5]
        return carry

    lax.fori_loop(0, beta_ref.shape[1], one, 0)


def _chunks_a_step(n: int) -> int:
    """Chunks of one head a grid step holds: ``_KERNEL_CHUNKS``, or all of a
    short sequence's."""
    return min(n, _KERNEL_CHUNKS)


def empty_tables(b: int, n: int, h: int, c: int, dk: int, dv: int, cd):
    """Unwritten room for a batch's six results as the chain over chunks
    reads them, chunks first: ``W``, ``U0``, ``Q * exp(G)``, ``K * exp(G_C -
    G)`` and ``tril(A_qk)`` as ``(N, B, H, C, .)``; ``exp(G_C)`` as ``(B, H,
    N, dk)`` (a block of a kernel's result ends in whole tiles)."""
    f32 = jnp.float32
    return tuple(lax.empty((n, b, h, c, d), t) for d, t in (
        (dk, cd), (dv, f32), (dk, cd), (dk, cd), (c, cd))) + (
        lax.empty((b, h, n, dk), f32),)


@functools.partial(jax.jit, static_argnames=("heads", "chunk", "interpret"))
def within_chunks_kernel(row, tables, q, k, v, g, beta, *, heads: int,
                         chunk: int, interpret: bool = False):
    """What ``_within_chunks`` computes for row ``row`` of the batch, written
    into ``tables`` (``empty_tables``; the other rows stay as they are), as
    one Pallas TPU call on the arrays as the layer holds them: ``q, k, g:
    (B, S, H * dk)``, ``v: (B, S, H * dv)`` (a head is a block of lanes, a
    chunk a block of rows: no copy cuts the row out or brings heads to the
    front) and ``beta: (B, H, N, C)``, ``S = N * C``, ``N`` a multiple of the
    chunks a grid step holds. The grid walks (head, block of chunks); a
    step's inputs, tables and results stay in VMEM, and HBM sees each input
    and each result once: the results land where the chain over chunks reads
    them, so the loop over rows stacks nothing."""
    h, c = heads, chunk
    n = q.shape[1] // c
    nb = _chunks_a_step(n)
    f32 = jnp.float32

    def tokens(d):  # rows of a block of chunks, lanes of a head
        return pl.BlockSpec((None, nb * c, d), lambda i, j, r: (r[0], j, i))

    def by_chunk(d):
        return pl.BlockSpec((nb, None, None, c, d),
                            lambda i, j, r: (j, r[0], i, 0, 0))

    widths = [y.shape[-1] for y in tables]
    return tuple(pl.pallas_call(
        functools.partial(_tables_kernel, chunk=c),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(h, n // nb),
            in_specs=[tokens(y.shape[2] // h) for y in (q, k, v, g)]
            + [pl.BlockSpec((None, 1, nb, c),
                            lambda i, j, r: (r[0], i, j, 0))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * 6,
            out_specs=[by_chunk(d) for d in widths[:5]]
            + [pl.BlockSpec((None, None, nb, widths[5]),
                            lambda i, j, r: (r[0], i, j, 0))]),
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype) for y in tables],
        # each result is its buffer (operands count from the row's index)
        input_output_aliases={6 + i: i for i in range(6)},
        interpret=interpret,
    )(jnp.asarray(row, jnp.int32).reshape(1), q, k, v, g.astype(f32),
      beta.astype(f32), *tables))


def kda_chunked(q, k, v, g, beta, heads: int, chunk: int = 64,
                sub: int = 16, out=None):
    """The layer's output ``(B, S, H * dv)`` for ``q, k: (B, S, H * dk)`` (as
    the layer reads them: normalised, ``q`` already scaled), ``v: (B, S, H *
    dv)``, log-decay ``g: (B, S, H * dk)`` (float32, at most zero) and
    ``beta: (B, S, H)``, a head being a block of lanes of ``H = heads``: the
    form the projections yield and the kernel reads, so the layer around
    this call re-tiles nothing. ``out``: what the layer does to a head's
    output rows ``(..., dv)`` on their own (its norm), done where the chain
    leaves them, a head's ``dv`` the minor axis, before the one pass that
    brings them to lanes. A sequence that is no multiple of ``chunk`` is
    padded with tokens that write nothing (``beta`` 0) and forget nothing
    (``g`` 0)."""
    b, s, _ = q.shape
    h = heads
    dk, dv = q.shape[-1] // h, v.shape[-1] // h
    sub = min(sub, chunk)
    if chunk % sub:
        raise ValueError(f"chunk {chunk} is no multiple of sub-chunk {sub}")
    form = tables_form(dk, dv, chunk)
    _note("kda_tables", form)
    n = -(-s // chunk)
    if form == "kernel":  # whole grid steps
        step_chunks = _chunks_a_step(n)
        n = -(-n // step_chunks) * step_chunks
    pad = n * chunk - s
    cd = q.dtype
    f32 = jnp.float32

    def padded(y):
        return jnp.pad(y, ((0, 0), (0, pad), (0, 0)))

    def chunks(y, *d):  # (B, S, H * d) -> (B, H, N, C, d); beta has no d
        return jnp.moveaxis(padded(y).reshape(b, n, chunk, h, *d), 3, 1)

    # Either form a row at a time: one loop in the compiled program, whose
    # device time a trace shows whole, under its name there (ops/parts.py).
    if form == "kernel":
        # the kernel reads its row where the layer left it, a head a block of
        # lanes, and writes it where the chain reads it
        whole = tuple(padded(y) for y in (q, k, v, g.astype(f32))) + (
            chunks(beta),)
        with jax.named_scope(P.MIX_KDA_TABLES):
            w, u0, q_in, k_out, a_qk, decay = lax.fori_loop(
                0, b, lambda row, tables: within_chunks_kernel(
                    row, tables, *whole, heads=h, chunk=chunk),
                empty_tables(b, n, h, chunk, dk, dv, cd))
        decay = jnp.moveaxis(decay, 2, 0)
    else:
        # heads are cut out here: on the CPU a reshape is free, and on a TPU
        # this form pays forty passes over a row's float32 arrays anyway
        rows = (chunks(q, dk), chunks(k, dk), chunks(v, dv),
                chunks(g.astype(f32), dk), chunks(beta))
        with jax.named_scope(P.MIX_KDA_TABLES):
            parts = lax.map(lambda row: _within_chunks(*row, sub=sub), rows)
        w, u0, q_in, k_out, a_qk, decay = (jnp.moveaxis(y, 2, 0)
                                           for y in parts)

    # the chain over chunks, every row and head at once
    def step(state, xs):
        w_c, u0_c, q_c, k_c, a_c, d_c = xs
        sb = state.astype(cd)
        u = u0_c - jnp.einsum("bhtk,bhkv->bhtv", w_c, sb,
                              preferred_element_type=f32)
        ub = u.astype(cd)
        o = (jnp.einsum("bhtk,bhkv->bhtv", q_c, sb,
                        preferred_element_type=f32)
             + jnp.einsum("bhts,bhsv->bhtv", a_c, ub,
                          preferred_element_type=f32))
        state = d_c[..., None] * state + jnp.einsum(
            "bhtk,bhtv->bhkv", k_c, ub, preferred_element_type=f32)
        return state, o.astype(cd)

    with jax.named_scope(P.MIX_KDA_SCAN):
        _, o = lax.scan(step, jnp.zeros((b, h, dk, dv), f32),
                        (w, u0, q_in, k_out, a_qk, decay))
    if out is not None:
        o = out(o)
    # (N, B, H, C, dv) -> (B, S, H * dv). A tile of the TPU's memory holds
    # eight positions by 128 lanes: with a chunk's positions cut into eights
    # the transposition moves whole tiles, one pass in the compute type, and
    # the reshape after it moves nothing. To (B, S, H, dv) and from there to
    # lanes is two passes (and in float32, the type of what reads it).
    lead = (chunk,) if chunk % 8 else (chunk // 8, 8)
    o = jnp.moveaxis(o.reshape(n, b, h, *lead, dv), (0, 2), (1, 2 + len(lead)))
    return o.reshape(b, n * chunk, h * dv)[:, :s]


def rmsnorm_heads(p: dict, x: jnp.ndarray, heads: int,
                  eps: float = 1e-5) -> jnp.ndarray:
    """ops/layers.py ``rmsnorm`` of each head of ``x: (..., H * d)``, a head
    a block of ``d`` lanes and ``p["scale"]: (d,)`` the one learned scale
    every head shares, tiled over them: what ``rmsnorm`` gives on the view
    ``(..., H, d)`` (the mean over a head's ``d`` channels, ``eps`` beside
    it, float32 inside, ``x``'s type out), with ``x`` read and written where
    a projection leaves it. The view is another tiling on a TPU, and the
    float32 ``x`` re-tiled into it and back three passes over an array twice
    ``x``'s size (PERF.md section 6, PR 58); here the statistic alone is a
    number a head (:func:`head_sums`, :func:`over_heads`)."""
    d = x.shape[-1] // heads
    xf = x.astype(jnp.float32)
    inv = lax.rsqrt(head_sums(xf * xf, heads) / d + eps)
    scale = jnp.tile(p["scale"].astype(jnp.float32), heads)
    return (xf * over_heads(inv, d) * scale).astype(x.dtype)
