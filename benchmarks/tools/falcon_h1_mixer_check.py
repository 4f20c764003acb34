#!/usr/bin/env python3
"""One chip check beside a cell of the ``falcon_h1`` family: the parallel
mixer (a Mamba-2 layer on two B/C groups and a rotary attention of five
query heads a key head, on one normed input, added under their own
multipliers) against the configuration's plain reference at the published
widths and the cell's step; the attention loop's time at two query tiles;
the scan's loop's time and where its carry lives:

    python3 benchmarks/tools/falcon_h1_mixer_check.py \
        --config falcon_h1_34b --seed 6600000021

One JSON line a check:

- ``mixer``: ``models/falcon_h1.py parallel_mixer`` whole (six projections,
  the convolution, the chunked scan, the gated norm, the turn, the causal
  kernel, the sum) on a step's rows (``held.rows_per_step`` windows) in the
  served type, against ``references/falcon_h1.py`` (``ssm_out Mamba2(ssm_in
  n) + attention_out Attention(attention_in n)``, the state token by token,
  every scalar where the released code puts it) in float32 at ``highest``,
  from the same leaves and the same input: the largest and the
  root-mean-square distance over the reference's root mean square.
- ``attention``: ``ops/attention.py causal_attention_merged`` alone on a
  step's rows with ``ops/flash_attention.py causal_tiles`` giving the group
  a query tile of each of ``--tiles`` positions: the median of ``--repeats``
  timed calls on the host's clock around ``block_until_ready``, in
  milliseconds, and the largest distance between the tiles' results (they
  are the same sums in another order). ``shipped`` marks the tile the rule
  gives.
- ``scan``: ``ops/ssd.py ssd_chunked_columns`` alone on a step's rows and on
  one row, timed the same way, and ``carry_kept``: whether the compiled
  text's loop that carries the float32 state holds it in fast memory
  (``S(1)`` on that operand of the ``while``).

The leaves are the program's own initialiser's (``parallel_mixer_init``)
from ``--seed``, cast to the served type: one layer's mixers, not the cell's
8.8 GB. Each line carries the forms the program noted. Exit code 1 where a
distance reads over ``--limit`` or a form on the chip is XLA's (``blocked``,
``halves``)."""

import argparse
import json
import os
import re
import statistics
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
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--tiles", type=int, nargs="+", default=[64, 128])
    ap.add_argument("--rehearse", action="store_true",
                    help="any platform (the tests' toy configurations)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.core import spec
    from storm_tpu.infer.engine import enable_compile_cache
    from storm_tpu.models import falcon_h1 as FH
    from storm_tpu.ops import flash_attention as F
    from storm_tpu.ops import rope as R
    from storm_tpu.ops.attention import causal_attention_merged
    from storm_tpu.ops.platform import dispatch_notes
    from storm_tpu.ops.ssd import ssd_chunked_columns

    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip and not args.rehearse:
        print("no TPU: the check is the chip's", file=sys.stderr)
        return 2
    enable_compile_cache()
    config = spec.config(args.config)
    sizes = config["published"]
    held = sizes["held"]
    reference = spec.plugin("references", config["reference"])
    dtype = jnp.dtype(config["model"]["dtype"])
    f32 = jnp.float32
    dim, eps = sizes["hidden_size"], sizes["rms_norm_eps"]
    s, rows = held["sequence_length"], held["rows_per_step"]
    m = FH.Mixers(
        heads=sizes["num_attention_heads"],
        kv_heads=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
        mamba_heads=sizes["mamba_n_heads"],
        mamba_head_dim=sizes["mamba_d_head"],
        groups=sizes["mamba_n_groups"], state=sizes["mamba_d_state"],
        conv=sizes["mamba_d_conv"],
        attention_in=sizes["attention_in_multiplier"],
        attention_out=sizes["attention_out_multiplier"],
        key=sizes["key_multiplier"], ssm_in=sizes["ssm_in_multiplier"],
        ssm_out=sizes["ssm_out_multiplier"],
        ssm=tuple(sizes["ssm_multipliers"]), chunk=held["ssd_chunk"],
        attention_block=min(512, s), eps=eps)
    ks = jax.random.split(jax.random.PRNGKey(args.seed % 2 ** 31), 8)
    p = jax.tree.map(lambda a: a.astype(dtype), FH.parallel_mixer_init(
        ks[0], dim, m, (3 * sizes["num_hidden_layers"]) ** 0.5))
    inv_freq = float(sizes["rope_theta"]) ** (
        -2.0 * np.arange(m.head_dim // 2) / m.head_dim)
    # a normed input: unit root mean square a token
    x = jax.random.normal(ks[1], (rows, s, dim), f32).astype(dtype)
    row = {"config": args.config, "seed": args.seed, "length": s,
           "rows": rows, "device": jax.devices()[0].device_kind}
    bad = 0

    def plain(p, u):  # one row
        return sizes["ssm_out_multiplier"] * reference._mamba(
            p["mamba"], sizes["ssm_in_multiplier"] * u, sizes, eps) \
            + sizes["attention_out_multiplier"] * reference._attention(
                p["attention"], sizes["attention_in_multiplier"] * u, sizes)

    with jax.default_matmul_precision("highest"):
        # the reference reads what the program reads: the served input
        want = np.asarray(jax.jit(lambda p, x: jax.lax.map(
            lambda u: plain(p, u), x.astype(f32)))(p, x), np.float64)
    rms = np.sqrt((want ** 2).mean())
    with dispatch_notes() as forms:
        got = np.asarray(jax.jit(lambda p, x: FH.parallel_mixer(
            p, x, R.rotary_tables(s, inv_freq), m))(p, x), np.float64)
    line = {**row, "check": "mixer", "forms": forms,
            "reference_rms": float(rms),
            "max_over_rms": float(np.abs(got - want).max() / rms),
            "rms_over_rms": float(np.sqrt(((got - want) ** 2).mean()) / rms)}
    xla = [f for f in forms if f.endswith(("=halves", "blocked-grouped"))]
    line["pass"] = bool(np.isfinite(got).all()
                        and line["rms_over_rms"] <= args.limit
                        and not (on_chip and xla))
    bad += not line["pass"]
    print(json.dumps(line), flush=True)
    del want, got

    def timed(fn, *operands):
        out = fn(*operands)
        out.block_until_ready()  # compiled
        times = []
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            fn(*operands).block_until_ready()
            times.append(1e3 * (time.perf_counter() - t0))
        return out, {"repeats": args.repeats,
                     "ms_median": statistics.median(times),
                     "ms_min": min(times)}

    # the attention loop alone, the group's query tile at each of --tiles
    group = m.heads // m.kv_heads
    q, k, v = (jax.random.normal(key, (rows, s, n * m.head_dim), f32
                                 ).astype(dtype)
               for key, n in zip(ks[2:5], (m.heads, m.kv_heads, m.kv_heads)))
    rule = F.causal_tiles
    shipped = rule(group)
    first = None
    for tile in args.tiles:
        F.causal_tiles = lambda g, tile=tile: (tile, shipped[1])
        try:
            with dispatch_notes() as forms:
                out, took = timed(jax.jit(
                    lambda q, k, v: causal_attention_merged(
                        q, k, v, m.heads, m.kv_heads,
                        scale=m.head_dim ** -0.5)), q, k, v)
        finally:
            F.causal_tiles = rule
        out = np.asarray(out, np.float64)
        first = out if first is None else first
        print(json.dumps({
            **row, "check": "attention", "group": group, "query_tile": tile,
            "stacked_rows": group * tile, "shipped": tile == shipped[0],
            "forms": forms, **took,
            "max_from_first_tile": float(np.abs(out - first).max()),
            "pass": True}), flush=True)

    # the scan alone, as the convolution hands it over: a step's rows, then
    # one row (what a loop over rows would run four times)
    inner, gn = m.mamba_heads * m.mamba_head_dim, m.groups * m.state
    a = -jnp.exp(p["mamba"]["a_log"].astype(f32))
    xbc = jax.nn.silu(jax.random.normal(
        ks[5], (rows, s, inner + 2 * gn), f32)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[6], (rows, s, m.mamba_heads),
                                           f32)
                         + p["mamba"]["dt_bias"].astype(f32))
    for n in sorted({rows, 1}, reverse=True):
        scan = jax.jit(lambda xbc, dt: ssd_chunked_columns(
            xbc, dt, a, p["mamba"]["d"], m.groups, m.state, m.chunk))
        with dispatch_notes() as forms:
            text = scan.lower(xbc[:n], dt[:n]).compile().as_text()
        carried = f"f32[{n},{m.groups},{m.mamba_heads // m.groups}," \
            f"{m.mamba_head_dim},{m.state}]"
        loops = [ln for ln in text.splitlines()
                 if " while(" in ln and carried in ln]
        kept = [bool(re.search(re.escape(carried) + r"\{[^}]*S\(1\)", ln))
                for ln in loops]
        _, took = timed(scan, xbc[:n], dt[:n])
        print(json.dumps({
            **row, "check": "scan", "rows": n, "chunk": m.chunk,
            "state_bytes": 4 * n * m.mamba_heads * m.mamba_head_dim * m.state,
            "forms": forms, "loops_found": len(loops),
            "carry_kept": bool(kept) and all(kept), **took, "pass": True}),
            flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
