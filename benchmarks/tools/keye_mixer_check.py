#!/usr/bin/env python3
"""One chip check beside a cell of the ``keye`` family: the indexer's
selection in the shared sparse-attention code, the second pass under its
mask, and the program's mixer whole, against float32 at the published
widths, one window:

    python3 benchmarks/tools/keye_mixer_check.py --config keye_vl2_30b \
        --seed 5900000021

One JSON line a check (``ms``: the program's call, the best of three, on the
host's clock around ``block_until_ready``: a time to tune by, no metric):

- ``select``: ``ops/sparse_attention.py select_keys`` on the mixer's own
  indexer queries, key and weights of one window as the program makes them
  (bfloat16 projections; bfloat16 products, float32 sums), against the
  float32 sort (``references/keye.py picks``), twice: ``agree`` against the
  sort of float32 scores at ``highest`` *of the same inputs* (what the
  kernel's own arithmetic costs: the products of bfloat16 numbers are exact
  in float32, so only the sums' order can part them), and
  ``agree_float32`` against the reference's own indexer in float32 from the
  same leaves and input (what the program's precision costs). Each is the
  share of the picks, of the queries past ``topk``, that are the sort's;
  beside it, for the picks that differ, ``worst_gap``: the most by which a
  key the program left out outscores the sort's ``topk``-th, over the
  spread of the row's scores.
- ``second_pass``: the masked kernel (and XLA's blocked form) under the
  program's own mask of one head, on N(0, 1) queries, keys and values, against
  the ``S x S`` masked softmax a head in float32 *under the same mask*.
- ``mixer``: ``models/keye.py keye_mixer`` whole against
  ``references/keye.py _attention`` from the same leaves and input.

``--time-top-k`` adds a line ``select_top_k``: the selection's other form
(``lax.top_k`` of 1,024 queries' scores at a time) on the same inputs, timed
the same way, and whether it gives the kernel's mask.

Exit code 1 where the second pass reads over ``--limit``, the mixer over
``--mixer-limit`` (a query's result is a mean of ``topk`` values, so one
pick in a thousand moved by a rounding shows as a few hundredths of it), or
the selection agrees with the sort of its own inputs on under 0.999 of the
picks."""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--limit", type=float, default=0.02)
    ap.add_argument("--mixer-limit", type=float, default=0.1)
    ap.add_argument("--time-top-k", action="store_true",
                    help="last, time the selection's other form too")
    ap.add_argument("--rehearse", action="store_true",
                    help="any platform (the tests' toy configurations)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.core import spec
    from storm_tpu.infer.engine import enable_compile_cache
    from storm_tpu.models.keye import keye_mixer
    from storm_tpu.models.scorer import _proj
    from storm_tpu.ops import layers as L
    from storm_tpu.ops import rope
    from storm_tpu.ops import sparse_attention as sa
    from storm_tpu.ops.platform import dispatch_notes

    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print("no TPU: the check is the chip's", file=sys.stderr)
        return 2
    enable_compile_cache()
    config = spec.config(args.config)
    sizes = config["published"]
    held, ix = sizes["held"], sizes["sa_config"]
    runner = spec.plugin("runners", config["runner"])
    reference = spec.plugin("references", config["reference"])
    params, _ = runner.parameters(config, args.seed)
    leaves = params["layers"][0]["mixer"]
    del params
    heads, kv_heads = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d, eps = sizes["head_dim"], sizes["rms_norm_eps"]
    ih, idim, topk = (ix["indexer_num_heads"], ix["indexer_head_dim"],
                      ix["topk"])
    s = held["sequence_length"]
    dtype = jnp.dtype(config["model"]["dtype"])
    f32 = jnp.float32
    ks = jax.random.split(jax.random.PRNGKey(args.seed % 2 ** 31), 4)
    x = jax.random.normal(ks[0], (1, s, sizes["hidden_size"]), f32)
    x = x.astype(dtype)
    q, k, v = (jax.random.normal(key, (1, n, s, d), f32).astype(dtype)
               for key, n in zip(ks[1:], (heads, kv_heads, kv_heads)))
    theta = float(sizes["rope_theta"])
    tables = (rope.mrope_tables(
        np.broadcast_to(np.arange(s), (3, s)),
        theta ** (-2.0 * np.arange(d // 2) / d),
        sizes["rope_scaling"]["mrope_section"]),
        rope.rotary_tables(s, theta ** (-2.0 * np.arange(idim // 2) / idim)))
    device = jax.devices()[0].device_kind

    def timed(fn, *given):
        best = None
        for _ in range(3):
            t0 = time.time()
            out = jax.block_until_ready(fn(*given))
            took = 1e3 * (time.time() - t0)
            best = took if best is None else min(best, took)
        return out, best

    def line(**row):
        row = {"config": args.config, "seed": args.seed, "length": s,
               "topk": topk, "device": device, **row}
        print(json.dumps(row), flush=True)
        return not row["pass"]

    bad = 0

    # ---- the selection, on the mixer's own indexer -----------------------
    @jax.jit
    def indexer(p, u):
        cos, sin = tables[1]
        qi = rope.rotate_halves(
            _proj(u, p["index_q"]).reshape(1, s, ih, idim), cos[:, None],
            sin[:, None])
        ki = rope.rotate_halves(L.layernorm(
            p["index_k_norm"], _proj(u, p["index_k"]), eps), cos, sin)
        return qi[0].transpose(1, 0, 2), ki[0], _proj(u, p["index_w"])[0]

    qi, ki, w = indexer(leaves, x)

    def scores(qi, ki, w, lo, hi):
        """float32 index scores of queries lo..hi-1 against every key."""
        a, b, c = (y.astype(f32) for y in (qi[:, lo:hi], ki, w[lo:hi]))
        at = jnp.arange(s)[None, :] <= jnp.arange(lo, hi)[:, None]
        return jnp.where(at, sum(
            c[:, j:j + 1] * jax.nn.relu(a[j] @ b.T) for j in range(ih)),
            -jnp.inf)

    with dispatch_notes() as forms:
        select = jax.jit(lambda a, b, c: sa.select_keys(a, b, c, topk=topk))
        got, ms = timed(select, qi, ki, w)
    got = np.asarray(got[0]) != 0
    with jax.default_matmul_precision("highest"):
        rq, rk, rw = jax.jit(lambda p, u: reference.indexer(
            p, u, sizes, jnp.broadcast_to(jnp.arange(s), (3, s)), eps))(
            leaves, x[0].astype(f32))
    rq = rq.transpose(1, 0, 2)
    block = min(s, 1024)
    sorted_picks = jax.jit(
        lambda a, b, c, lo: (lambda sc: (reference.picks(sc, topk), sc))(
            scores(a, b, c, lo, lo + block)), static_argnums=3)
    row = {}
    for name, given in (("", (qi, ki, w)), ("_float32", (rq, rk, rw))):
        agree, total, worst = 0, 0, 0.0
        for lo in range(max(0, (topk // block) * block), s, block):
            with jax.default_matmul_precision("highest"):
                want, sc = map(np.asarray, sorted_picks(*given, lo))
            mine = got[lo:lo + block]
            rows = np.arange(lo, lo + block) >= topk
            agree += int((mine & want)[rows].sum())
            total += int(want[rows].sum())
            # a key the program left out though the sort read it: how far it
            # outscores the sort's topk-th, over the row's spread
            left = np.where(want & ~mine, sc, -np.inf).max(1)
            kth = np.where(want, sc, np.inf).min(1)
            spread = np.where(np.isfinite(sc), sc, -np.inf).max(1) \
                - np.where(np.isfinite(sc), sc, np.inf).min(1)
            gap = np.where(np.isfinite(left), (left - kth) / spread, 0.0)
            worst = max(worst, float(gap[rows].max(initial=0.0)))
        row.update({"agree" + name: agree / max(total, 1),
                    "worst_gap" + name: worst, "picks": total})
    bad += line(check="select", forms=forms, ms=ms, **row,
                **{"pass": row["agree"] >= 0.999})

    # ---- the second pass under that mask ----------------------------------
    wanted = jnp.asarray(got[None].astype(np.int8))

    def plain_masked(q, k, v, mask):
        def head(qkv):
            q_h, k_h, v_h = qkv
            scores = jnp.where(mask, q_h @ k_h.T * d ** -0.5, -jnp.inf)
            return jax.nn.softmax(scores, -1) @ v_h

        return jax.lax.map(head, (q, jnp.repeat(k, heads // kv_heads, 0),
                                  jnp.repeat(v, heads // kv_heads, 0)))

    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(plain_masked)(
            *(y[0].astype(f32) for y in (q, k, v)), wanted[0] != 0),
            np.float64)
    rms = np.sqrt((want ** 2).mean())
    form = sa.sparse_form(heads, kv_heads, s, d, d, 1)
    passes = {"blocked": jax.jit(lambda q, k, v, m: sa._blocked_row(
        q[0], k[0], v[0], lambda lo, hi: m[:, lo:hi, :hi] != 0, d ** -0.5,
        512))}
    if form == "kernel":
        passes["kernel"] = lambda q, k, v, m: sa._kernel_row(
            q, k, v, m, d ** -0.5, 0)
    for name, program in passes.items():
        out, ms = timed(program, q, k, v, wanted)
        out = np.asarray(out, np.float64)
        err = float(np.sqrt(((out - want) ** 2).mean()) / rms)
        bad += line(check="second_pass", form=name, ms=ms,
                    max_over_rms=float(np.abs(out - want).max() / rms),
                    rms_over_rms=err,
                    **{"pass": bool(np.isfinite(out).all()
                                    and err <= args.limit)})

    # ---- the mixer whole ----------------------------------------------------
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, u: reference._attention(
            p, u, sizes, jnp.broadcast_to(jnp.arange(s), (3, s)), eps))(
            leaves, x[0].astype(f32)), np.float64)
    rms = np.sqrt((want ** 2).mean())
    with dispatch_notes() as forms:
        mixer = jax.jit(lambda p, u: keye_mixer(
            p, u, heads, kv_heads, d, ih, idim, eps, tables, topk,
            ix["q_chunk_size"]))
        (out, picked, causal), ms = timed(mixer, leaves, x)
    out = np.asarray(out[0], np.float64)
    err = float(np.sqrt(((out - want) ** 2).mean()) / rms)
    bad += line(check="mixer", forms=forms, ms=ms,
                blocks_picked=int(picked), blocks_causal=int(causal),
                max_over_rms=float(np.abs(out - want).max() / rms),
                rms_over_rms=err,
                **{"pass": bool(np.isfinite(out).all()
                                and err <= args.mixer_limit)})
    if args.time_top_k:  # what the kernel is instead of: XLA's ``lax.top_k``
        other = jax.jit(lambda a, b, c: sa._select_top_k(
            a, b, c.astype(f32), topk, min(s, 1024)))
        t0 = time.time()
        mask = jax.block_until_ready(other(qi, ki, w))
        first = 1e3 * (time.time() - t0)
        _, ms = timed(other, qi, ki, w)
        line(check="select_top_k", form="top_k", ms=ms, first_call_ms=first,
             same_mask=bool(((np.asarray(mask[0]) != 0) == got).all()),
             **{"pass": True})
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
