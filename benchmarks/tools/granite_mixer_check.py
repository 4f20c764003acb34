#!/usr/bin/env python3
"""One chip check beside a cell of the ``granite`` family: the Mamba-2 mixer
on one B/C group and the expert layer with a part of a ten-a-token softmax
router held, each against the configuration's plain reference at the
published widths, one window; and the scan's time at two chunks:

    python3 benchmarks/tools/granite_mixer_check.py \
        --config granite_4_h_small --seed 6300000021

One JSON line a check, the program's result in the served type against
float32 at ``highest``, as the largest and the root-mean-square distance over
the reference's root mean square:

- ``mamba``: ``models/nemotron_h.py mamba_mixer`` whole (both projections, the
  convolution, the chunked scan, the gated norm) at chunks of 128 and 256,
  against ``references/granite.py _mamba``, the state token by token, from
  the same leaves and the same input.
- ``experts``: ``parallel/moe.py topk_moe_layer`` (softmax router, no
  selection bias, the held experts of the router's width, the shared expert
  at its own) against ``references/granite.py _experts``: every held expert
  on every token.
- ``scan``: ``ops/ssd.py ssd_chunked_columns`` alone on a step's rows
  (``held.rows_per_step`` windows) at chunks of 128 and 256: the median of
  ``--repeats`` timed calls on the host's clock around ``block_until_ready``,
  in milliseconds. No distance: a time.

The leaves are the program's own initialisers' (``mamba_mixer_init``,
``topk_moe_init``) from ``--seed``, cast to the served type: one layer's, not
the cell's 9.5 GB. Each line carries the forms the program noted. Exit code 1
where a distance reads over ``--limit``."""

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CHUNKS = (128, 256)


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
    from storm_tpu.models.nemotron_h import mamba_mixer, mamba_mixer_init
    from storm_tpu.ops.platform import dispatch_notes
    from storm_tpu.ops.ssd import ssd_chunked_columns
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
    dim, eps = sizes["hidden_size"], sizes["rms_norm_eps"]
    heads, hd = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    groups, state = sizes["mamba_n_groups"], sizes["mamba_d_state"]
    s, top_k = held["sequence_length"], sizes["num_experts_per_tok"]
    ks = jax.random.split(jax.random.PRNGKey(args.seed % 2 ** 31), 6)

    def served(tree):
        return jax.tree.map(lambda a: a.astype(dtype), tree)

    mamba = served(mamba_mixer_init(ks[0], dim, heads, hd, groups, state,
                                    sizes["mamba_d_conv"]))
    experts = served(topk_moe_init(
        ks[1], dim, sizes["intermediate_size"], sizes["num_local_experts"],
        held["num_local_experts"],
        shared_hidden=sizes["shared_intermediate_size"],
        selection_bias=False))
    # a normed input: unit root mean square a token
    x = jax.random.normal(ks[2], (1, s, dim), f32)

    # (check, what varies, the program, its input, the plain form)
    checks = [("mamba", {"chunk": chunk},
               lambda p, u, chunk=chunk: mamba_mixer(
                   p, u, heads, hd, groups, state, chunk, eps),
               (mamba, x.astype(dtype)),
               lambda p, u: reference._mamba(p, u, sizes, eps))
              for chunk in CHUNKS]
    checks.append(("experts", {"held": held["num_local_experts"],
                               "width": sizes["num_local_experts"]},
                   lambda p, u: topk_moe_layer(
                       p, u, top_k, first_expert=held["first_expert"],
                       router="softmax", renormalize=True, scale=1.0,
                       tile=held["expert_tile"])[0],
                   (experts, x),
                   lambda p, u: reference._experts(p, u, sizes)))
    row = {"config": args.config, "seed": args.seed, "length": s,
           "device": jax.devices()[0].device_kind}
    bad = 0
    for check, varies, program, given, plain in checks:
        p, u = given
        with jax.default_matmul_precision("highest"):
            # the reference reads what the program reads: the served input
            want = np.asarray(jax.jit(plain)(p, u[0].astype(f32)),
                              np.float64)
        rms = np.sqrt((want ** 2).mean())
        with dispatch_notes() as forms:
            got = np.asarray(jax.jit(program)(p, u)[0], np.float64)
        line = {**row, "check": check, **varies, "forms": forms,
                "max_over_rms": float(np.abs(got - want).max() / rms),
                "rms_over_rms": float(np.sqrt(((got - want) ** 2).mean())
                                      / rms)}
        line["pass"] = bool(np.isfinite(got).all()
                            and line["rms_over_rms"] <= args.limit)
        bad += not line["pass"]
        print(json.dumps(line), flush=True)

    # the scan alone at a step's rows, as the convolution hands it over
    rows = held["rows_per_step"]
    inner, gn = heads * hd, groups * state
    xbc = jax.nn.silu(jax.random.normal(
        ks[3], (rows, s, inner + 2 * gn), f32)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[4], (rows, s, heads), f32)
                         + mamba["dt_bias"].astype(f32))
    a = -jnp.exp(mamba["a_log"].astype(f32))
    for chunk in CHUNKS:
        scan = jax.jit(lambda xbc, dt, chunk=chunk: ssd_chunked_columns(
            xbc, dt, a, mamba["d"], groups, state, chunk))
        scan(xbc, dt).block_until_ready()  # compiled
        times = []
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            scan(xbc, dt).block_until_ready()
            times.append(1e3 * (time.perf_counter() - t0))
        print(json.dumps({**row, "check": "scan", "chunk": chunk,
                          "rows": rows, "repeats": args.repeats,
                          "ms_median": statistics.median(times),
                          "ms_min": min(times), "pass": True}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
