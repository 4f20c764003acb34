"""Attention, and the rules that say which form serves a program.

Two entry points, each a rule over the traced shapes and over what the
process runs on (a TPU or not, one device or several: ops/platform.py), never
an option or a model's name; each notes its choice (``platform.note``), so
``engine_inventory()["programs"]`` says per bucket what a program was built
with.

* :func:`multi_head_attention` (the ViTs: full attention over ``(B, S, C)``),
  by :func:`attention_form`: ``"rows"``, the Pallas row kernel of
  ops/short_attention.py, for many rows of a short sequence (from eight
  million scores, in a process with one device: ViT-g/14 at 8 to 256 rows);
  ``"flash"``, ops/flash_attention.py, from 1,024 tokens (no model of the
  registry is that long: the parity checks and ``attention_bench.py`` run
  it); ``"xla"``, :func:`attention_reference`, elsewhere and on the CPU.
* :func:`causal_attention` (Kimi-Linear's latent attention, Nemotron-H's
  grouped queries: ``(B, H, S, D)``, ``Dv`` and ``Hkv`` of their own), by
  :func:`causal_form`: ``"kernel"``, one call a row of the batch of
  ops/flash_attention.py's kernel with ``causal`` (a block's scores stay in
  VMEM, key blocks beyond the diagonal are never loaded), on a TPU in a
  process with one device for whole tiles; ``"blocked"``,
  :func:`causal_blocked`, XLA's form, elsewhere and on the CPU. With a
  ``window`` (Trinity's sliding layers) a query reads its last ``window``
  keys alone, by the same rule and in the same two forms: the kernel never
  loads the keys that lie before every window of a query tile (its walk
  over them, counted back from the tile's own end or over aligned blocks,
  is ops/flash_attention.py ``window_walk``'s to choose from the window and
  the tiles, and the note ends ``-tile-end`` for the first), the blocked
  form slices a block of queries' keys from the first block a window
  reaches, and both mask what holds a window's lower edge. Such a loop is
  the part ``mix.window_attention`` in a trace and ``window_attention=...``
  in the inventory: the two loops carry the same shapes, so only a name
  tells them apart.

The forms of one rule are cross-checked in tests/test_ops.py under the Pallas
interpreter and compiled on the chip by ops/parity_checks.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


from storm_tpu.ops import parts as P
from storm_tpu.ops.platform import note as _note
from storm_tpu.ops.platform import one_device as _one_device
from storm_tpu.ops.platform import use_pallas as _use_pallas


def attention_reference(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, scale: Optional[float] = None
) -> jnp.ndarray:
    """Plain softmax(q k^T / sqrt(d)) v. Shapes: (B, H, S, D)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bhsd,bhtd->bhst", q, k).astype(jnp.float32) * scale
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", probs.astype(v.dtype), v)


def _flash_min_seq() -> int:
    """Sequence length above which the Pallas flash kernel dispatches.

    Below it, XLA's own fused attention is FASTER on TPU (measured on-chip:
    vit_b16 S=197 runs 20.3ms/step via XLA vs 29.1ms via flash, a run of
    round 2 with no ledger line) — the S^2 score tensor is small enough that
    fusion beats tiling, so flash only pays off where it was designed to:
    long sequences whose S^2 intermediates would blow HBM traffic/VMEM
    (and the ring-attention SP path, which calls it directly)."""
    import os

    return int(os.environ.get("STORM_TPU_FLASH_MIN_SEQ", "1024"))


def scaled_dot_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, scale: Optional[float] = None
) -> jnp.ndarray:
    """Dispatch: Pallas flash attention on TPU for long sequences, XLA's
    fused attention otherwise (shape-aware — see :func:`_flash_min_seq`)."""
    if _use_pallas() and q.shape[-2] >= _flash_min_seq():
        from storm_tpu.ops.flash_attention import flash_attention

        return flash_attention(q, k, v, scale=scale)
    return attention_reference(q, k, v, scale=scale)


# Scores (batch x heads x tokens x tokens) from which a program's attention is
# the row kernel of ops/short_attention.py. Measured on the v5e (PERF.md §5-6,
# PR 29; ``attention_bench.py`` gives the table): under it XLA keeps the
# scores of a block in fast memory and its own attention is 6-8 % ahead
# (ViT-B/16 at batch 8 and 16: 3.7 and 7.5 million scores); over it XLA writes
# them to HBM twice and reads them twice, transposes q, k, v and the result,
# and the kernel is ahead by 14-31 % of the whole program (ViT-g/14 from batch
# 8, 8.5 million; ViT-B/16 from batch 32).
_ROW_KERNEL_MIN_SCORES = 8_000_000


def attention_form(b: int, s: int, c: int, num_heads: int,
                   itemsize: int) -> str:
    """Which attention a program over ``[b, s, c]`` activations is built
    with: ``"flash"`` (long sequences), ``"rows"`` (many rows of a short
    sequence), or ``"xla"``. A function of the traced shapes and of what the
    process runs on: a TPU or not, and one device or several (the row kernel
    has no partitioning rule, ops/platform.py ``one_device``). Off TPU
    always ``"xla"``."""
    from storm_tpu.ops.short_attention import fits

    if not _use_pallas():
        return "xla"
    if s >= _flash_min_seq():
        return "flash"
    if (b * num_heads * s * s >= _ROW_KERNEL_MIN_SCORES
            and fits(s, c, itemsize) and _one_device()):
        return "rows"
    return "xla"


def causal_form(hq: int, hkv: int, s: int, dk: int, dv: int) -> str:
    """Which form a program's causal attention is built with: ``"kernel"``
    (one call a row of ops/flash_attention.py with ``causal``: a block's
    scores stay in VMEM) or ``"blocked"`` (XLA's, below). A function of the
    traced shapes and of what the process runs on, as :func:`attention_form`
    and ops/kda.py ``tables_form``: the kernel on a TPU in a process with one
    device (a Mosaic call has no partitioning rule, ops/platform.py
    ``one_device``), for a sequence of whole query and key tiles and head
    widths the kernel reads as they lie (``causal_tiles``, ``lane_width``:
    no copy pads an operand on its way in); the blocked form elsewhere (the
    CPU, a host with several chips, the tiny presets' 40 tokens of 16
    channels)."""
    from storm_tpu.ops.flash_attention import causal_tiles, lane_width

    block_q, block_k = causal_tiles(hq // hkv)
    if (_use_pallas() and _one_device() and s % block_q == 0
            and s % block_k == 0 and lane_width(dk) == dk
            and lane_width(dv) == dv):
        return "kernel"
    return "blocked"


def causal_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     scale: Optional[float] = None, block: int = 512,
                     window: Optional[int] = None) -> jnp.ndarray:
    """Causal ``softmax(q k^T * scale) v`` for ``q: (B, Hq, S, Dk)``, ``k: (B,
    Hkv, S, Dk)`` and ``v: (B, Hkv, S, Dv)``. ``Dv`` may differ from ``Dk``
    (latent attention: 192 against 128), and ``Hq`` may be a multiple of
    ``Hkv`` (grouped queries: query head ``i`` reads key head ``i // (Hq /
    Hkv)``; the keys are read in place by their group's query heads, never
    written out once a query head). The scores of a whole batch are never
    formed: one row of the batch at a time (``lax.map``: one loop in the
    compiled program, whose device time a trace shows whole and which the
    benchmark finds by the shapes of the q, k and v it carries). Softmax in
    float32, the weights to the value product in the values' type and
    unnormalised, the result divided by their float32 sum, in both forms.

    Within a row, by :func:`causal_form`: the Pallas kernel of
    ops/flash_attention.py, which keeps a block's scores in VMEM, stops at
    the diagonal and reads the row where it lies in q, k and v (the loop
    cuts nothing out of them); or :func:`causal_blocked`.

    ``window`` (static; None: all of the above as it was): the query at ``t``
    reads the keys ``t - window < s <= t``, at most ``window`` of them,
    itself among them. The same rule picks the form. The kernel walks a
    query tile's keys by ops/flash_attention.py ``window_walk``, a rule on
    the window and the tiles: for a window of whole tiles and one to eight
    key blocks (Trinity's 2,048 keys on tiles of 64 x 512) counted back from
    the tile's own last query, the diagonal's block, the blocks that lie
    inside every one of the tile's windows and one masked chunk at the lower
    edge, ``window + block_q`` columns and nothing before ``first - window``
    (the note ends ``-tile-end``); for every other window over the blocks
    aligned to ``block_k`` from the first one a window reaches, the block or
    two that hold a lower edge masked. The blocked form slices from that
    block and masks likewise. The loop is the part ``mix.window_attention``
    and the note ``window_attention``. A window of the sequence's length or
    more bounds nothing: plain causal attention, by that path and under its
    names."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    hq, hkv, s = q.shape[1], k.shape[1], q.shape[2]
    if hq % hkv:
        raise ValueError(f"{hq} query heads over {hkv} key heads")
    if window is not None and window < 1:
        raise ValueError(f"a window of {window} keys")
    if window is not None and window >= s:
        window = None
    form = causal_form(hq, hkv, s, q.shape[-1], v.shape[-1])
    name = "causal_attention" if window is None else "window_attention"
    grouped = "-grouped" if hq != hkv else ""
    if form == "blocked":
        _note(name, form + grouped)
        return causal_blocked(q, k, v, scale, block, window)

    from storm_tpu.ops.flash_attention import causal_tiles, flash_attention

    block_q, block_k = causal_tiles(hq // hkv)
    _note(name, form + grouped + _walk_suffix(window, block_q, block_k))
    with jax.named_scope(_loop_part(window)):
        return jax.lax.map(lambda i: flash_attention(
            q, k, v, scale=scale, block_q=block_q, block_k=block_k,
            causal=True, row=i, window=window)[0], jnp.arange(q.shape[0]))


def _loop_part(window: Optional[int]) -> str:
    """The part a row loop of causal attention is in a trace."""
    return P.MIX_ATTENTION if window is None else P.MIX_WINDOW_ATTENTION


def _walk_suffix(window: Optional[int], block_q: int, block_k: int) -> str:
    """What a kernel's note ends in where its tiles walk a window's keys
    counted back from their own end (ops/flash_attention.py
    ``window_walk``); nothing for the aligned walk, whose note is as it
    was."""
    from storm_tpu.ops.flash_attention import window_walk

    walk = window_walk(window, block_q, block_k)
    return "" if walk == "aligned" else "-" + walk


def causal_blocked(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   scale: float, block: int = 512,
                   window: Optional[int] = None) -> jnp.ndarray:
    """:func:`causal_attention` as XLA computes it, on every platform: within
    a row a block of ``block`` queries against the keys up to that block's
    end, so the upper triangle is not computed and at most ``Hq x block x S``
    scores exist at once (XLA writes them to HBM between the two
    products). With a ``window`` the keys of a block of queries are sliced
    from the first block of ``block`` keys that the window of the block's
    first query reaches (the blocks before it are not read), and the keys
    at or before ``t - window`` are masked beside the later ones."""
    hq, hkv, s = q.shape[1], k.shape[1], q.shape[2]
    # with one query head a key head the group axis is left out altogether,
    # so that such a program carries the shapes it always has
    grouped = hq != hkv
    scores_of, values_of = (("grsd,gtd->grst", "grst,gtd->grsd") if grouped
                            else ("hsd,htd->hst", "hst,htd->hsd"))

    def row(qkv):
        qr, kr, vr = qkv  # (H, S, D)
        if grouped:
            qr = qr.reshape(hkv, hq // hkv, s, qr.shape[-1])
        outs = []
        for lo in range(0, s, block):
            hi = min(lo + block, s)
            first = 0 if window is None else \
                max(lo - window + 1, 0) // block * block
            keys = slice(first, hi) if first else slice(hi)
            scores = jnp.einsum(scores_of, qr[..., lo:hi, :], kr[:, keys],
                                preferred_element_type=jnp.float32) * scale
            at, query = jnp.arange(first, hi)[None, :], \
                jnp.arange(lo, hi)[:, None]
            unseen = at > query
            if window is not None:
                unseen |= at <= query - window
            scores = jnp.where(unseen, -jnp.inf, scores)
            # the weights go to the product unnormalised and the result is
            # divided by their sum: one pass less over the scores
            weights = jnp.exp(scores - scores.max(-1, keepdims=True))
            out = jnp.einsum(values_of, weights.astype(vr.dtype),
                             vr[:, keys], preferred_element_type=jnp.float32)
            outs.append((out / weights.sum(-1, keepdims=True)
                         ).astype(vr.dtype))
        out = jnp.concatenate(outs, -2)
        return out.reshape(hq, s, out.shape[-1]) if grouped else out

    with jax.named_scope(_loop_part(window)):
        return jax.lax.map(row, (q, k, v))


def mha_init(rng, dim: int, num_heads: int, dtype=jnp.float32) -> dict:
    from storm_tpu.ops.layers import dense_init

    ks = jax.random.split(rng, 4)
    return {
        "q": dense_init(ks[0], dim, dim, dtype),
        "k": dense_init(ks[1], dim, dim, dtype),
        "v": dense_init(ks[2], dim, dim, dtype),
        "o": dense_init(ks[3], dim, dim, dtype),
    }


def split_heads(y: jnp.ndarray, num_heads: int) -> jnp.ndarray:
    """(B, S, H*D) -> (B, H, S, D)."""
    b, s, c = y.shape
    return y.reshape(b, s, num_heads, c // num_heads).transpose(0, 2, 1, 3)


def merge_heads(y: jnp.ndarray) -> jnp.ndarray:
    """(B, H, S, D) -> (B, S, H*D)."""
    b, h, s, d = y.shape
    return y.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def multi_head_attention(p: dict, x: jnp.ndarray, num_heads: int) -> jnp.ndarray:
    """Self-attention over (B, S, C) activations."""
    from storm_tpu.ops.layers import dense

    b, s, c = x.shape
    with jax.named_scope(P.PROJ):
        q, k, v = dense(p["q"], x), dense(p["k"], x), dense(p["v"], x)
    form = attention_form(b, s, c, num_heads, x.dtype.itemsize)
    _note("attention", form)
    if form == "rows":
        from storm_tpu.ops.short_attention import keys_form, short_attention
        _note("rows_keys", keys_form(s))
        out = short_attention(q, k, v, num_heads)  # names itself
    else:
        with jax.named_scope(P.MIX_ELEMENTWISE):
            heads = tuple(split_heads(y, num_heads) for y in (q, k, v))
        with jax.named_scope(P.MIX_ATTENTION):
            out = scaled_dot_attention(*heads)
        with jax.named_scope(P.MIX_ELEMENTWISE):
            out = merge_heads(out)
    with jax.named_scope(P.PROJ):
        return dense(p["o"], out)


# ---- causal attention over merged heads ---------------------------------------

def merged_form(hq: int, hkv: int, s: int, dk: int, dv: int) -> str:
    """:func:`causal_form` for heads that lie merged ``(B, S, H * D)``:
    ``"kernel"`` by its rule where a head is whole lane tiles besides (a
    head is then a block of lanes to the kernel's block specs; 192 lanes
    are a tile and a half, which no block can begin at), or half a tile
    with the key heads paired off (a block of lanes is then a tile's two
    key heads, which the kernel reads as one of twice the width under twice
    the group: ops/flash_attention.py ``heads_a_lane_tile``), ``"blocked"``
    elsewhere."""
    from storm_tpu.ops.flash_attention import heads_a_lane_tile

    per = heads_a_lane_tile(dk, dv, hkv)
    if per * dk % 128 or per * dv % 128:
        return "blocked"
    return causal_form(hq, hkv // per, s, per * dk, per * dv)


def causal_attention_merged(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            heads: int, kv_heads: int,
                            scale: Optional[float] = None, block: int = 512,
                            window: Optional[int] = None,
                            rotary: Optional[tuple] = None) -> jnp.ndarray:
    """:func:`causal_attention` for a caller that holds its heads merged, as
    its projections leave them: ``q: (B, S, Hq * Dk)``, ``k: (B, S, Hkv *
    Dk)``, ``v: (B, S, Hkv * Dv)`` to ``(B, S, Hq * Dv)``. The same softmax,
    the same ``window``, the same loop over rows under the same part's name
    and the same note, by :func:`merged_form`: the kernel reads a head as a
    block of lanes of the merged arrays and writes the result so
    (ops/flash_attention.py ``flash_attention_merged``; the note ends
    ``-merged``, or ``-merged-halves`` where two heads of 64 are a block),
    and no array is transposed to ``(B, H, S, D)`` and back, a
    copy of each on a TPU; elsewhere :func:`causal_blocked` on that view, the
    CPU's and the several-chips' path as it was. A caller chooses this entry
    by what it holds; the head-split one is untouched.

    ``rotary = (cos, sin)`` (tables ``(S, Dk / 2)``; None: q and k are read
    as they come): q and k come unturned, and every channel of every head
    is turned as ops/rope.py ``turn_merged`` turns it. Where the kernel
    reads a head as one lane tile and the lanes kernel would have turned it
    (``heads_a_lane_tile == 1`` and ``turn_form == "lanes"``: a head of 128
    on one chip) the causal kernel turns q and k itself, on the tiles it
    holds in VMEM, to the same bits (``flash_attention_merged``'s
    ``rotary``; noted ``rotary_turn=causal-kernel``): q and k make no pass
    through HBM for the turn, and only the two tables' fusions are left
    under ``mix.rope``. Everywhere else (heads of 64, the blocked form, the
    CPU, several devices) ``turn_merged`` runs first, under its own
    notes."""
    b, s, _ = q.shape
    dk, dv = k.shape[-1] // kv_heads, v.shape[-1] // kv_heads
    if heads % kv_heads:
        raise ValueError(f"{heads} query heads over {kv_heads} key heads")
    if window is not None and window < 1:
        raise ValueError(f"a window of {window} keys")
    if window is not None and window >= s:
        window = None
    if scale is None:
        scale = dk ** -0.5
    from storm_tpu.ops import flash_attention as F
    from storm_tpu.ops import rope as R

    form = merged_form(heads, kv_heads, s, dk, dv)
    per = F.heads_a_lane_tile(dk, dv, kv_heads)
    tables = None
    if rotary is not None:
        if form == "kernel" and per == 1 and R.turn_form(s, dk) == "lanes":
            _note("rotary_turn", "causal-kernel")
            with jax.named_scope(P.MIX_ROPE):
                tables = R._lane_tables(*rotary)
        else:
            (q,) = R.turn_merged((q,), *rotary, heads)
            (k,) = R.turn_merged((k,), *rotary, kv_heads)
    grouped = "-grouped" if heads != kv_heads else ""
    name = "causal_attention" if window is None else "window_attention"
    if form == "blocked":
        _note(name, form + grouped)
        out = causal_blocked(
            *(split_heads(y, n) for y, n in (
                (q, heads), (k, kv_heads), (v, kv_heads))),
            scale, block, window)
        return merge_heads(out)
    block_q, block_k = F.causal_tiles(per * heads // kv_heads)
    _note(name, form + grouped + "-merged" + ("-halves" if per > 1 else "")
          + _walk_suffix(window, block_q, block_k))
    with jax.named_scope(_loop_part(window)):
        # a row's call writes its row of the result where it lies
        return jax.lax.fori_loop(
            0, b, lambda i, out: F.flash_attention_merged(
                out, q, k, v, i, heads=heads, kv_heads=kv_heads, scale=scale,
                block_q=block_q, block_k=block_k, window=window,
                rotary=tables),
            jax.lax.empty((b, s, heads * dv), q.dtype))
