"""The Mamba-2 state-space layer's scan (state-space duality, Dao and Gu 2024,
"Transformers are SSMs"), computed in chunks.

Per head the layer keeps a state ``S`` in R^{P x N} and reads it token by
token, with one scalar decay a head and token:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t
    y_t = S_t C_t + D x_t

``A < 0`` and ``D`` are one number a head, ``dt_t > 0`` one a head and token,
``x_t`` in R^P; ``B_t`` and ``C_t`` in R^N are shared by the heads of a group
(head ``h`` reads group ``h // (H / G)``). Token by token that is ``S``
dependent steps of vector work. Here a sequence is cut into chunks of
``chunk`` tokens; with ``L_t`` the running sum of ``dt A`` from the chunk's
start (so every exponent below is at most zero):

    Y[t]  = sum_{s <= t} exp(L_t - L_s) (C_t . B_s) dt_s x_s       within
          + exp(L_t) S_prev C_t                                    from before
    S_new = exp(L_Q) S_prev + sum_s exp(L_Q - L_s) dt_s x_s (x) B_s

``C B^T`` is formed once a group, not once a head (with one group, as
Granite 4.0-H publishes it, once for all 128 heads: ``G = 1``, ``R = H``);
the decays' table ``exp(L_t - L_s)`` is a head's, always the exponent of the
difference (its factors ``exp(L_t)`` and ``exp(-L_s)`` overflow where a head
forgets within a chunk), ``B x H x chunk x chunk`` float32 a step of the
loop: 67 MB for 8 rows of 128 heads at a chunk of 128, 268 MB at 256, where a
token's row of it is twice as long; the chunk is the caller's choice and no
part of the mathematics. The products take their operands in the type of ``x`` (bfloat16
when serving) and accumulate in float32; the state stays float32 from chunk
to chunk, the decays are float32 throughout.

**Where the state lives decides what a chunk costs.** The scan is one loop
in the compiled program (a ``fori_loop``; every row of the batch and every
head inside a step): the state is the only chain, a step holds one chunk's
tables (``B x H x chunk x chunk``) and no more, and a device trace shows the
scan's whole time as that one ``while``. In fast memory (``S(1)`` on the
loop's carry in the compiled text) the chunk's fusions read and write the
state there; left in HBM every one of them goes through HBM, five times the
time for twice the state. The v5e compiler keeps 16 MiB (Nemotron's 8 rows
x 64 heads x 64 x 128 x 4 bytes: 100 us a chunk on the chip; Granite's 4
rows x 128 heads in the text compiled for a described v5e) and not 32 MiB
(Granite's 8 rows: no ``S(1)``, 512 us a chunk on the chip; PERF.md section
6, PRs 63 and 64). Falcon-H1's step is the same 16 MiB in another shape, 4
rows x 32 heads of **128 on a state of 256** in **two** groups (the first
call whose heads are as wide as a lane tile and whose state is two): the
loop takes it as it takes any ``g``, ``p`` and ``n``, ``C B^T`` once a group
for its 16 heads, and the carry's placement at exactly the kept size is read
in the compiled text and on the chip by
``benchmarks/tools/falcon_h1_mixer_check.py`` (PERF.md section 6, PR 66).
``scan_form`` reads that off the shapes alone and says which of two forms a
program takes, and ``engine_inventory()`` shows it:

- ``ssd_scan=chunked``: the loop over chunks, every row and head inside a
  step. Wherever the batch's state is kept (Nemotron's step, Falcon-H1's,
  MiniCPM-SALA's lightning layers at 8 MiB, every toy), and wherever it is not but the
  kernel cannot run (a process with several devices, the CPU, several
  groups, a ragged sequence, heads that do not fill the kernel's blocks).
- ``ssd_scan=kernel-rows1-heads64`` (``ssd_kernel``; Granite's 8 rows x 128
  heads on one chip): one Pallas call whose grid walks (row, block of 64
  heads, chunk), the chunks innermost; the block's state is 2 MiB of VMEM
  scratch from a row's first chunk to its last and never in HBM, nor is any
  table. 2.1 ms a layer on the chip where the loop with 8 rows' state in HBM
  took 16.7, and a loop over two blocks of 4 rows, their 16 MiB kept, 9.6
  (read from scratch and not shipped: no workload would run it; PERF.md
  section 6, PR 64).

The loop reads its operands where the layer left them and writes its result
where the layer reads it: ``x`` stays position-major, ``(B, S, channels)``,
and a step takes its chunk by index on the view ``(B, S / chunk, chunk,
channels)`` (a bitcast; a view that is none would have the compiler bring the
whole array into the body's layout before the loop); ``B`` and ``C`` are
columns of that chunk where the layer holds all three side by side
(``ssd_chunked_columns``), slices of their own arrays otherwise; the split of
the channels into groups and heads happens on the chunk, in fast memory. The
result is carried beside the state, ``(B, S, H P)`` in the served type, and a
step writes its chunk into it in place, the skip ``D x`` added in float32
before the one rounding: no array of a branch's size is made, moved or
widened outside the loop, nothing is scattered and no table is rewritten.
The running sums ``L`` alone are made before the loop, for every chunk at
once (``(B, S, H)`` float32, a sixty-fourth of ``x``, as a product with a
triangle of ones at ``highest``: exact products, float32 sums): the v5e
compiler's cumulative sum over a chunk's ``(B, chunk, G, R)`` took 235 us of
a step's 350 inside the loop (PERF.md section 6, PR 50).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from storm_tpu.ops import parts as P
from storm_tpu.ops.platform import note as _note
from storm_tpu.ops.platform import one_device as _one_device
from storm_tpu.ops.platform import use_pallas as _use_pallas


def ssd_chunked(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
                b: jnp.ndarray, c: jnp.ndarray, d: jnp.ndarray,
                chunk: int = 128) -> jnp.ndarray:
    """``x: (B, S, H, P)``, ``dt: (B, S, H)`` (after its softplus), ``a, d:
    (H,)``, ``b, c: (B, S, G, N)`` with ``H % G == 0``. Returns ``y: (B, S,
    H, P)`` in the type of ``x``. ``S`` need not be a multiple of ``chunk``:
    the tail is padded with tokens of ``dt = 0``, which neither decay the
    state nor write to it."""
    bsz, s, h, p = x.shape
    g, n = b.shape[-2:]
    y = _chunks_loop(x.reshape(bsz, s, h * p),
                     (b.reshape(bsz, s, g * n), c.reshape(bsz, s, g * n)),
                     dt, a, d, g, n, chunk)
    return y.reshape(bsz, s, h, p)


def ssd_chunked_columns(xbc: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
                        d: jnp.ndarray, groups: int, state: int,
                        chunk: int = 128) -> jnp.ndarray:
    """The same scan for ``x | B | C`` held side by side, ``xbc: (B, S, H P +
    2 G N)`` as a Mamba-2 layer's convolution writes them: the loop takes a
    chunk of all three at once and the columns apart inside its body, so no
    slice of a branch's size is made for it. Returns ``y: (B, S, H P)``."""
    return _chunks_loop(xbc, None, dt, a, d, groups, state, chunk)


# The most float32 state, ``rows x H x P x N x 4`` bytes, that the v5e compiler
# keeps in fast memory from one chunk to the next (``S(1)`` on the loop's
# carry): 16 MiB is kept (Nemotron's 8 rows x 64 heads of 64 x 128 on the chip,
# 100 us a chunk; 4 rows of Granite's 128 heads in the text compiled for a
# described v5e), 32 MiB is not (Granite's 8 rows: 512 us a chunk, PERF.md
# section 6, PRs 63 and 64).
_STATE_KEPT_BYTES = 16 * 2 ** 20

_LANES = 128
# Heads a step of the kernel at a chunk of 128. A step's fixed work (``C
# B^T``, two turns of a tile, the grid's own) is shared by its heads: 16, 32
# and 64 read 3.0, 2.4 and 2.1 ms a layer in Granite's cell (PERF.md section
# 6, PR 64); at 64 the state is 2 MiB of scratch and the chunk of ``x`` 1 MiB
# a buffer, which is what fits: 64 heads at a chunk of 256 ran out of VMEM on
# the chip, so a longer chunk takes fewer heads in proportion.
_KERNEL_HEADS = 64


def kernel_heads(heads: int, chunk: int) -> int:
    """Heads a step of the kernel: ``_KERNEL_HEADS`` at a chunk of a lane
    tile's length, fewer as the chunk is longer, never more than there are."""
    return min(heads, _KERNEL_HEADS * _LANES // chunk)


def scan_form(rows: int, seq: int, heads: int, head_dim: int, groups: int,
              state: int, chunk: int) -> str:
    """Which form runs the scan: ``"chunked"`` (the loop over chunks with
    every row inside a step) or ``"kernel-rows1-heads<n>"`` (``ssd_kernel``:
    a row and ``n`` heads a step, their state in VMEM scratch, the chunks
    innermost). A function of the traced shapes and of what the process runs
    on. The loop wherever the batch's float32 state is at most
    ``_STATE_KEPT_BYTES``, which the compiler keeps in fast memory. Where it
    is more, the kernel, if it can run: on a TPU in a process with one
    device, for one group, heads that fill lane tiles between them
    (``head_dim`` a divisor of 128, the heads a multiple of 128: their
    ``dt`` and ``L`` are turned a tile at a time) and come in whole steps of
    whole lane tiles (``kernel_heads`` divides them and is a multiple of
    ``128 / head_dim``), a state of whole lane tiles that ``x``'s columns
    are whole blocks of (``B`` and ``C`` are read as blocks of the array
    that holds all three) and a sequence of whole chunks of whole lane
    tiles; the loop, its state in HBM, otherwise."""
    if 4 * rows * heads * head_dim * state <= _STATE_KEPT_BYTES:
        return "chunked"
    step = kernel_heads(heads, chunk)
    if (_use_pallas() and _one_device() and groups == 1
            and _LANES % head_dim == 0 and state % _LANES == 0
            and heads % _LANES == 0 and chunk % _LANES == 0
            and seq % chunk == 0 and (heads * head_dim) % state == 0
            and heads % step == 0 and step % (_LANES // head_dim) == 0):
        return f"kernel-rows1-heads{step}"
    return "chunked"


def _ssd_kernel(x_ref, b_ref, c_ref, rows_ref, last_ref, d_ref, o_ref, state,
                rows_t, *, heads: int, head_dim: int):
    """One chunk of ``heads`` heads of one row. ``x_ref``, ``o_ref``: ``(Q,
    heads P)``; ``b_ref``, ``c_ref``: ``(Q, N)``; ``rows_ref``: ``(Q, 3 H)``,
    for every head of the layer ``L - log dt``, the state's weight ``exp(L_Q
    - L) dt`` and ``L``, a token a row as the layer holds ``dt``;
    ``last_ref``: ``(1, heads P)``, ``exp(L_Q)`` a lane; ``d_ref``: the skip
    a lane; ``state``: ``(N, heads P)`` float32, zero at a row's first chunk
    (the grid walks a row's chunks innermost, in order); ``rows_t``: the
    first two thirds of ``rows_ref`` turned, a head a row. A head's table is
    ``exp(L_t - (L_s - log dt_s))``, the decay and the step in one exponent:
    ``L_t`` varies down the tokens (this step's heads' columns of ``L``,
    brought to the first lanes by one rotation, each spread over a tile),
    ``L_s - log dt_s`` along them (a row of ``rows_t``), and so does the
    state's weight, which therefore goes on ``B^T`` where the loop puts it
    on ``x``. A lane tile holds ``128 / P`` heads: each takes the whole tile
    through its own table and its own weighted ``B^T`` (the matrix unit is a
    tile wide either way) and keeps its own lanes of the two products."""
    f32 = jnp.float32
    q = x_ref.shape[0]
    cd = x_ref.dtype
    share = _LANES // head_dim  # heads a lane tile
    first = pl.program_id(1) * heads
    all_heads = rows_ref.shape[1] // 3
    n = state.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros(state.shape, f32)

    rows_t[...] = rows_ref[:, :2 * all_heads].T  # (2 H, Q)
    # this step's heads' L down the tokens, brought to the first lanes
    cols = pltpu.roll(rows_ref[:, 2 * all_heads:], all_heads - first, 1)
    b_c, c_c = b_ref[...], c_ref[...]
    cb = lax.dot_general(c_c, b_c, (((1,), (1,)), ((), ())),
                         preferred_element_type=f32)  # (t, s)
    b_t = b_c.astype(f32).T  # (N, Q)
    later = (lax.broadcasted_iota(jnp.int32, (q, q), 0)
             >= lax.broadcasted_iota(jnp.int32, (q, q), 1))
    head_of_lane, head_of_state_lane = (lax.broadcasted_iota(
        jnp.int32, (rows, _LANES), 1) // head_dim for rows in (q, n))
    for tile in range(heads // share):
        lanes = slice(tile * _LANES, (tile + 1) * _LANES)
        x_c = x_ref[:, lanes]
        y = seen = new = None
        for k in range(share):
            h = tile * share + k
            # a head's L down the tokens, the same in every lane
            run = jnp.broadcast_to(cols[:, h:h + 1], (q, q))
            m = (cb * jnp.exp(jnp.where(
                later, run - rows_t[pl.ds(first + h, 1), :], -jnp.inf))
                 ).astype(cd)
            own = jnp.dot(m, x_c, preferred_element_type=f32)
            wrote = jnp.dot(
                (b_t * rows_t[pl.ds(all_heads + first + h, 1), :]).astype(cd),
                x_c, preferred_element_type=f32)
            mine = head_of_lane == k
            y = own if k == 0 else jnp.where(mine, own, y)
            seen = jnp.exp(run[:, :_LANES]) if k == 0 else jnp.where(
                mine, jnp.exp(run[:, :_LANES]), seen)
            new = wrote if k == 0 else jnp.where(
                head_of_state_lane == k, wrote, new)
        # what the state before the chunk adds
        y = y + seen * jnp.dot(c_c, state[:, lanes].astype(cd),
                               preferred_element_type=f32)
        # the state after it
        state[:, lanes] = last_ref[:, lanes] * state[:, lanes] + new
        o_ref[:, lanes] = (y + d_ref[:, lanes] * x_c.astype(f32)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "chunk", "interpret"))
def ssd_kernel(x, bc, dt, runs, d, n: int, chunk: int,
               interpret: bool = False):
    """The scan as one Pallas TPU call for one group: ``x: (B, S, H P)`` and
    ``bc`` the pair ``B``, ``C`` (or ``x | B | C`` side by side where ``bc``
    is the empty tuple), ``dt`` and ``runs`` ``(B, S, H)`` float32 (``runs``
    each chunk's running sum of ``dt A``). The grid walks (row, block of
    ``kernel_heads`` heads, chunk), chunks innermost and in order: the
    block's state, ``(N, heads P)`` float32, is VMEM scratch from a row's
    first chunk to its last and never in HBM. A step reads its chunk of
    ``x`` and of ``B`` and ``C`` where they lie (the columns of one array or
    arrays of their own) and writes its chunk of the result. What it needs
    of ``dt`` and ``L`` is made before the call, position-major as ``dt``
    is and an eleventh of ``x``: ``L - log dt``, the state's weight and
    ``L`` side by side, which a step turns in VMEM. (A ``(B, H, S)`` copy of
    anything made of ``dt`` has the compiler write the mixer's whole
    projection positions-minor and copy it back for the convolution, and an
    array laid out a block of heads at a time is padded eightfold to its
    lane tiles: PERF.md section 6, PR 64.) Jitted, so that a model's layers
    of one shape trace the kernel's unrolled body once between them: nine
    traces of it were 4.4 s of a warm start."""
    bsz, s, h = dt.shape
    hp = x.shape[-1] - (0 if len(bc) else 2 * n)
    p = hp // h
    f32 = jnp.float32
    q, hb = chunk, kernel_heads(h, chunk)
    nc, nhb, lb = s // q, h // hb, hb * p
    last = runs.reshape(bsz, nc, q, h)[:, :, -1:]  # (B, nc, 1, H)
    w = (jnp.exp(last - runs.reshape(bsz, nc, q, h))
         * dt.reshape(bsz, nc, q, h)).reshape(bsz, s, h)

    rows = jnp.concatenate([runs - jnp.log(dt), w, runs], -1)  # (B, S, 3 H)
    lanes = [jnp.repeat(jnp.exp(last), p, -1),  # (B, nc, 1, H P)
             jnp.repeat(d.astype(f32), p).reshape(1, hp)]
    if len(bc):
        operands = [x, *bc]
        places = [lambda i, j, c: (i, c, 0)] * 2
    else:
        operands = [x, x, x]
        places = [lambda i, j, c, at=hp // n + k: (i, c, at)
                  for k in range(2)]
    return pl.pallas_call(
        functools.partial(_ssd_kernel, heads=hb, head_dim=p),
        grid=(bsz, nhb, nc),
        in_specs=[
            pl.BlockSpec((None, q, lb), lambda i, j, c: (i, c, j)),
            *(pl.BlockSpec((None, q, n), place) for place in places),
            pl.BlockSpec((None, q, 3 * h), lambda i, j, c: (i, c, 0)),
            pl.BlockSpec((None, None, 1, lb), lambda i, j, c: (i, c, 0, j)),
            pl.BlockSpec((1, lb), lambda i, j, c: (0, j)),
        ],
        out_specs=pl.BlockSpec((None, q, lb), lambda i, j, c: (i, c, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, s, hp), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, lb), f32),
                        pltpu.VMEM((2 * h, q), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands, rows, *lanes)


def _chunks_loop(x, bc, dt, a, d, g, n, chunk):
    """``x: (B, S, H P)`` and ``bc``: ``B`` and ``C`` as ``(B, S, G N)``
    each, or None where they are the columns of ``x`` after its ``H P``."""
    bsz, s, h = dt.shape
    hp = x.shape[-1] - (0 if bc else 2 * g * n)
    r, p = h // g, hp // h
    f32 = jnp.float32
    form = scan_form(bsz, s, h, p, g, n, chunk)
    _note("ssd_scan", form)
    cd = x.dtype
    q = min(chunk, s)
    pad = -s % q
    nc = (s + pad) // q

    def padded(y):  # (B, S, channels) -> (B, S + pad, channels)
        return jnp.pad(y, ((0, 0), (0, pad), (0, 0))) if pad else y

    x, dt = padded(x), padded(dt.astype(f32))
    bc = bc and tuple(padded(y) for y in bc)
    a = a.astype(f32).reshape(g, r)
    d = d.astype(f32).reshape(g, r, 1)
    later = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]  # s <= t
    # L for every chunk at once, each from its chunk's start: (B, S, H), <= 0
    runs = jnp.einsum(
        "ts,bcsh->bcth", later.astype(f32),
        (dt * a.reshape(h)).reshape(bsz, nc, q, h),
        precision=lax.Precision.HIGHEST).reshape(bsz, s + pad, h)

    if form.startswith("kernel"):
        with jax.named_scope(P.MIX_SSD_SCAN):
            return ssd_kernel(x, bc or (), dt, runs, d, n=n, chunk=q)

    def one_chunk(i, carry):  # state (B, G, R, P, N), float32
        state, out = carry

        def chunk_of(y, *split):  # (B, S, channels) -> (B, Q, *split)
            return lax.dynamic_slice_in_dim(
                y, i * q, q, axis=1, allow_negative_indices=False).reshape(
                bsz, q, *split)

        held = lax.dynamic_index_in_dim(
            x.reshape(bsz, nc, q, x.shape[-1]), i, 1, keepdims=False)
        x_c = held[..., :hp].reshape(bsz, q, g, r, p)
        if bc:
            b_c, c_c = (chunk_of(y, g, n) for y in bc)
        else:
            b_c, c_c = (held[..., lo:lo + g * n].reshape(bsz, q, g, n)
                        for lo in (hp, hp + g * n))
        dt_c, run = chunk_of(dt, g, r), chunk_of(runs, g, r)
        run_h = jnp.moveaxis(run, 1, -1)  # (B, G, R, Q)
        # within the chunk: (C B^T) a group, the decays a head
        cb = jnp.einsum("btgn,bsgn->bgts", c_c, b_c,
                        preferred_element_type=f32)
        decay = jnp.exp(jnp.where(
            later, run_h[..., :, None] - run_h[..., None, :], -jnp.inf))
        m = cb[:, :, None] * decay * jnp.moveaxis(dt_c, 1, -1)[..., None, :]
        y = jnp.einsum("bgrts,bsgrp->btgrp", m.astype(cd), x_c,
                       preferred_element_type=f32)
        # what the state before the chunk adds
        y = y + jnp.exp(run)[..., None] * jnp.einsum(
            "bgrpn,btgn->btgrp", state.astype(cd), c_c,
            preferred_element_type=f32)
        # the state after it
        last = run[:, -1]  # (B, G, R)
        w = jnp.exp(last[:, None] - run) * dt_c  # (B, Q, G, R)
        state = jnp.exp(last)[..., None, None] * state + jnp.einsum(
            "bsgrp,bsgn->bgrpn", (x_c.astype(f32) * w[..., None]).astype(cd),
            b_c, preferred_element_type=f32)
        # the skip in float32 and one rounding, into the chunk's own place
        y = (y + d * x_c.astype(f32)).astype(cd).reshape(bsz, q, hp)
        return state, lax.dynamic_update_slice_in_dim(
            out, y, i * q, axis=1, allow_negative_indices=False)

    with jax.named_scope(P.MIX_SSD_SCAN):  # its name in a device trace
        _, y = lax.fori_loop(0, nc, one_chunk, (
            jnp.zeros((bsz, g, r, p, n), f32),
            jnp.zeros((bsz, s + pad, hp), cd)))
    return y[:, :s]
