#!/usr/bin/env python3
"""One chip check beside a cell of the ``lfm2`` family: each part of the
architecture's own alone, at the published widths and the cell's step, in
the form the program's rules give and in XLA's, timed and compared:

    python3 benchmarks/tools/lfm2_mixer_check.py \
        --config lfm2_24b_a2b --seed 6800000021

One JSON line a check:

- ``conv_mixer`` and ``attention_mixer``: ``models/lfm2.py``'s two operators
  whole (projections among them) on a step's rows (``held.rows_per_step``
  windows) in the served type, against ``references/lfm2.py`` (three shifted
  sums; head norms, the turn and a full masked softmax) in float32 at
  ``highest``, from the same leaves and the same input: the largest and the
  root-mean-square distance over the reference's root mean square.
- ``gated_conv``: ``ops/kda.py gated_conv`` alone on a projection's result
  ``(rows, S, 3 hidden)``, once in each form (``kernel``: one pass over the
  three ranges where they lie; ``xla``: XLA's fusion of the same
  arithmetic): the median of ``--repeats`` timed calls on the host's clock
  around ``block_until_ready``, in milliseconds, and the largest distance
  between the two results. ``shipped`` marks the form the rule gives.
- ``attention``: ``ops/attention.py causal_attention_merged`` alone, the
  kernel on a lane tile's two heads against the blocked form, likewise.
- ``head_norm_turn``: ``ops/rope.py norm_turn_merged`` of q and of k, the
  one pass over a lane tile's two heads against the two-pass form
  (``rmsnorm_heads``, then the turn on the view a head), likewise.
- ``experts``: ``parallel/moe.py topk_moe_layer`` at the cell's shape (a
  sigmoid router with its bias held whole, the published experts a token,
  no shared expert, ``1e-6`` beside the chosen sum) timed, and against
  ``references/lfm2.py``'s every expert on every token.

The leaves are the program's own initialisers' from ``--seed``, cast to the
served type: one layer's, not the cell's 10.5 GB. Each line carries the forms
the program noted. On the CPU (``--rehearse``) the rules give XLA's forms
alone and a check has one line. Exit code 1 where a distance reads over
``--limit``."""

import argparse
import json
import os
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
    ap.add_argument("--rehearse", action="store_true",
                    help="any platform (the tests' toy configurations)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.core import spec
    from storm_tpu.infer.engine import enable_compile_cache
    from storm_tpu.models import lfm2 as M
    from storm_tpu.ops import attention as A
    from storm_tpu.ops import kda
    from storm_tpu.ops import rope as R
    from storm_tpu.ops.platform import dispatch_notes
    from storm_tpu.parallel.moe import topk_moe_init, topk_moe_layer

    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print("no TPU: the check is the chip's", file=sys.stderr)
        return 2
    enable_compile_cache()
    config = spec.config(args.config)
    sizes = config["published"]
    held = sizes["held"]
    reference = spec.plugin("references", config["reference"])
    dtype = jnp.dtype(config["model"]["dtype"])
    f32 = jnp.float32
    dim, eps = sizes["hidden_size"], sizes["norm_eps"]
    heads, kv_heads = sizes["num_attention_heads"], \
        sizes["num_key_value_heads"]
    hd, taps = held["head_dim"], sizes["conv_L_cache"]
    s, rows = held["sequence_length"], held["rows_per_step"]
    ks = jax.random.split(jax.random.PRNGKey(args.seed % 2 ** 31), 10)
    inv_freq = float(sizes["rope_parameters"]["rope_theta"]) ** (
        -2.0 * np.arange(hd // 2) / hd)
    row = {"config": args.config, "seed": args.seed, "length": s,
           "rows": rows, "device": jax.devices()[0].device_kind}
    bad = 0

    def served(tree):
        return jax.tree.map(lambda a: a.astype(dtype), tree)

    def timed(fn, *operands):
        out = fn(*operands)
        jax.block_until_ready(out)  # compiled
        times = []
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*operands))
            times.append(1e3 * (time.perf_counter() - t0))
        return out, {"repeats": args.repeats,
                     "ms_median": statistics.median(times),
                     "ms_min": min(times)}

    def against(check, got, want, forms, **more):
        nonlocal bad
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        rms = np.sqrt((want ** 2).mean())
        line = {**row, "check": check, "forms": forms,
                "reference_rms": float(rms),
                "max_over_rms": float(np.abs(got - want).max() / rms),
                "rms_over_rms": float(
                    np.sqrt(((got - want) ** 2).mean()) / rms), **more}
        line["pass"] = bool(np.isfinite(got).all()
                            and line["rms_over_rms"] <= args.limit)
        bad += not line["pass"]
        print(json.dumps(line), flush=True)

    def both_forms(check, module, rule, xla, fn, *operands):
        """``fn`` timed as ``module.<rule>`` builds it and, where that is a
        kernel's form, again with the rule answering ``xla``."""
        nonlocal bad
        with dispatch_notes() as forms:
            out, took = timed(jax.jit(fn), *operands)
        shipped = list(forms)
        runs = [(shipped, out, took)]
        if not any(f.endswith("=" + xla) or ("=" + xla + "-") in f
                   for f in shipped):
            was = getattr(module, rule)
            setattr(module, rule, lambda *a, **kw: xla)
            try:
                with dispatch_notes() as forms:
                    out, took = timed(jax.jit(lambda *o: fn(*o)), *operands)
            finally:
                setattr(module, rule, was)
            runs.append((list(forms), out, took))
        first = None
        for forms, out, took in runs:
            leaves = [np.asarray(o, np.float64)
                      for o in jax.tree.leaves(out)]
            first = first or leaves
            far = max(float(np.abs(a - b).max())
                      for a, b in zip(leaves, first))
            scale = max(float(np.sqrt((b ** 2).mean())) for b in first)
            line = {**row, "check": check, "forms": forms,
                    "shipped": forms == shipped, **took,
                    "max_from_shipped_over_rms": far / scale}
            line["pass"] = bool(all(np.isfinite(a).all() for a in leaves)
                                and far / scale <= 8 * args.limit)
            bad += not line["pass"]
            print(json.dumps(line), flush=True)

    # a normed input: unit root mean square a token
    x = jax.random.normal(ks[0], (rows, s, dim), f32).astype(dtype)
    tables = jax.jit(lambda: R.rotary_tables(s, inv_freq))()

    # ---- the gated short convolution
    p = served(M.conv_mixer_init(ks[1], dim, taps))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: jax.lax.map(
            lambda n: reference._gated_conv(p, n, sizes), x.astype(f32)))(
                p, x)
    with dispatch_notes() as forms:
        got = jax.jit(M.conv_mixer)(p, x)
    against("conv_mixer", got, want, forms)
    del got, want
    wide = jax.random.normal(ks[2], (rows, s, 3 * dim), f32).astype(dtype)
    both_forms("gated_conv", kda, "conv_form", "xla",
               lambda w: kda.gated_conv(p["conv"], w), wide)
    del wide

    # ---- attention at the published head
    p = served(M.attention_mixer_init(ks[3], dim, heads, kv_heads, hd))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: jax.lax.map(
            lambda n: reference._attention(p, n, sizes, eps),
            x.astype(f32)))(p, x)
    with dispatch_notes() as forms:
        got = jax.jit(lambda p, x, t: M.attention_mixer(
            p, x, heads, kv_heads, hd, eps, t, min(512, s)))(p, x, tables)
    against("attention_mixer", got, want, forms)
    del got, want
    q, k, v = (jax.random.normal(key, (rows, s, n * hd), f32).astype(dtype)
               for key, n in zip(ks[4:7], (heads, kv_heads, kv_heads)))
    both_forms("attention", A, "merged_form", "blocked",
               lambda q, k, v: A.causal_attention_merged(
                   q, k, v, heads, kv_heads, scale=hd ** -0.5,
                   block=min(512, s)), q, k, v)
    both_forms("head_norm_turn", R, "turn_form", "halves",
               lambda q, k, t: (
                   R.norm_turn_merged(p["q_norm"], q, heads, eps, t),
                   R.norm_turn_merged(p["k_norm"], k, kv_heads, eps, t)),
               q, k, tables)
    del q, k, v

    # ---- the expert layer at the cell's run
    width, top_k = sizes["num_experts"], sizes["num_experts_per_tok"]
    p = served(topk_moe_init(ks[7], dim, sizes["moe_intermediate_size"],
                             width, held["num_experts"], shared=False))
    n = jax.random.normal(ks[8], (rows, s, dim), f32)  # the float32 norm
    tile = held["expert_tile"] if held["expert_tile"] % 128 else None
    with dispatch_notes() as forms:
        (got, counts, absent), took = timed(jax.jit(
            lambda p, n: topk_moe_layer(p, n, top_k, tile=tile, eps=1e-6)),
            p, n)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, n: jax.lax.map(
            lambda m: reference._experts(p, m, sizes), n))(p, n)
    counts = np.asarray(counts)
    against("experts", got, want, forms, **took, held=int(counts.size),
            width=width, expected_run=rows * s * top_k / width,
            run_max=int(counts.max()), run_min=int(counts.min()),
            absent=int(absent))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
