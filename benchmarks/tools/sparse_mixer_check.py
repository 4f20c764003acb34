#!/usr/bin/env python3
"""One chip check beside a cell whose model picks keys: the program's sparse
mixer against the configuration's plain reference, at the published widths,
one window at a time, at lengths either side of the mixer's ``dense_len``:

    python3 benchmarks/tools/sparse_mixer_check.py --config minicpm_sala \
        --seed 4500000021 8192 16384

One JSON line a length: the program's mixer in the served type (the form its
shape rule picks: dense up to ``dense_len``, the selection and the attention
over the picked blocks past it) against ``references/<model>.py`` in float32
at ``highest`` from the same leaves and the same input, as the largest and
the root-mean-square distance over the result's root mean square; past
``dense_len`` also how many queries (a position and group) picked another
set of blocks than the reference's selection from float32 ``q`` and ``k``
did. Exit code 1 where a length reads over ``--limit``."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--limit", type=float, default=0.05)
    ap.add_argument("--rehearse", action="store_true",
                    help="any platform (the tests' toy configurations)")
    ap.add_argument("lengths", nargs="+", type=int)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.core import spec
    from storm_tpu.infer.engine import enable_compile_cache
    from storm_tpu.models import minicpm_sala as program
    from storm_tpu.ops import sparse_attention
    from storm_tpu.ops.platform import dispatch_notes

    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print("no TPU: the check is the chip's", file=sys.stderr)
        return 2
    enable_compile_cache()
    config = spec.config(args.config)
    sizes = config["published"]
    runner = spec.plugin("runners", config["runner"])
    reference = spec.plugin("references", config["reference"])
    params, _ = runner.parameters(config, args.seed)
    layer = sizes["held"]["mixer_types"].index("minicpm4")
    leaves = params["layers"][layer]["mixer"]
    del params
    heads, groups = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d, eps = sizes["head_dim"], sizes["rms_norm_eps"]
    sparse = sizes["held"]["sparse"]
    dtype = jnp.dtype(config["model"]["dtype"])
    bad = 0
    for s in args.lengths:
        u = jax.random.normal(jax.random.PRNGKey(args.seed % 2 ** 31 + s),
                              (1, s, sizes["hidden_size"]), jnp.float32
                              ).astype(dtype)
        with dispatch_notes() as forms:
            got = jax.jit(lambda p, x: program.minicpm4_mixer(
                p, x, heads, groups, d, eps, sparse)[0])(leaves, u)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda p, x: reference._minicpm4(
                p, x, sizes, eps))(leaves, u[0].astype(jnp.float32))
        got, want = (np.asarray(a, np.float64) for a in (got[0], want))
        rms = np.sqrt((want ** 2).mean())
        row = {"config": args.config, "seed": args.seed, "length": s,
               "forms": forms, "device": jax.devices()[0].device_kind,
               "max_over_rms": float(np.abs(got - want).max() / rms),
               "rms_over_rms": float(np.sqrt(((got - want) ** 2).mean())
                                     / rms)}
        if s > sparse["dense_len"]:
            def qk(p, x, f32):
                x = x[0].astype(jnp.float32) if f32 else x[0]
                p = jax.tree.map(lambda a: a.astype(x.dtype), p)
                q = reference._rmsnorm(p["q_norm"], (x @ p["q"]).reshape(
                    s, heads, d), eps)
                k = reference._rmsnorm(p["k_norm"], (x @ p["k"]).reshape(
                    s, groups, d), eps)
                return q.astype(x.dtype), k.astype(x.dtype)

            with jax.default_matmul_precision("highest"):
                plain = jax.jit(lambda p, x: reference.picked_blocks(
                    *qk(p, x, True), sparse))(leaves, u)
            selection = {k: v for k, v in sparse.items() if k != "dense_len"}
            served = jax.jit(lambda p, x: sparse_attention.select_blocks(
                *(y.transpose(1, 0, 2)[None] for y in qk(p, x, False)),
                scale=d ** -0.5, **selection)[0])(leaves, u)
            differ = np.asarray(plain != served)
            row.update(queries=int(differ.shape[0] * differ.shape[1]),
                       queries_flipped=int(differ.any(-1).sum()),
                       blocks_flipped=int(differ.sum()) // 2)
        row["pass"] = bool(row["rms_over_rms"] <= args.limit)
        bad += not row["pass"]
        print(json.dumps(row), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
