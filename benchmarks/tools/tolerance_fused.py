#!/usr/bin/env python3
"""``tools/tolerance.py``'s two readings for a configuration whose largest
leaf does not fit in float32 twice over beside the parameters
(``falcon_h1_34b``: an embedding and a head of 261,120 x 5,120, 2.7 GB each
in bfloat16 and 5.3 GB in float32, beside 8.8 GB of parameters on a 16 GB
chip), on the chip at the cell's sizes, one JSON line a seed:

    python3 benchmarks/tools/tolerance_fused.py --config falcon_h1_34b \
        6600000031:f8 6600000032:f8 6600000033

The same program, the same control (a seed followed by ``:f8``: every matrix
among the parameters and every projection's input rounded to float8 e4m3,
each scaled to its tensor's largest value), the same judgement
(``core/pairing.py match_rows`` under ``min(tolerance.relative_distance, row
separation / 2)``), the same line and the same exit codes as
``tools/tolerance.py``, whose words stand for all of it. What differs is how
a leaf is rounded: there each of the rounding's five steps makes an array of
the leaf's size, two of them in float32 at once (10.7 GB for this head);
here the steps are **one jitted pass a leaf** that the compiler fuses (no
float32 array is written) and whose result takes the leaf's own buffer
(``donate_argnums``), so the control needs no room beyond the parameters'.
The values are the same to the bit: the same operations in the same order on
each element. A ``benchmark`` PR may give ``tools/tolerance.py`` this
rounding and delete this file (PERF.md section 7)."""

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="any platform (the tests' toy configurations)")
    ap.add_argument("--stage", choices=["program", "float8"],
                    help=argparse.SUPPRESS)  # a child: one seed, one reading
    ap.add_argument("seeds", nargs="+", help="<seed> or <seed>:f8")
    args = ap.parse_args()
    if args.stage:
        return reading(args, int(args.seeds[0]))
    bad = 0
    for item in args.seeds:
        seed, _, control = item.partition(":")
        row = {}
        for stage in ("program", "float8") if control else ("program",):
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--config",
                 args.config, "--stage", stage, seed]
                + ["--rehearse"] * args.rehearse,
                stdout=subprocess.PIPE, text=True)
            if proc.returncode == 2:
                return 2
            try:
                row.update(json.loads(proc.stdout.strip().splitlines()[-1]))
            except (IndexError, ValueError):
                row[stage] = {"error": f"rc {proc.returncode}"}
            bad += proc.returncode != 0
        print(json.dumps(row), flush=True)
    return 1 if bad else 0


def reading(args, seed: int) -> int:
    """One seed's reference and one reading against it, as one JSON line."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.core import pairing, spec
    from storm_tpu.infer.engine import enable_compile_cache
    from storm_tpu.models.registry import build_model
    from storm_tpu.ops import layers

    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print("no TPU: the readings are the chip's", file=sys.stderr)
        return 2
    enable_compile_cache()
    config = spec.config(args.config)
    runner = spec.plugin("runners", config["runner"])
    reference = spec.plugin("references", config["reference"])
    shape = tuple(config["model"]["input_shape"])
    model = build_model(config["model"]["name"],
                        num_classes=int(config["model"]["num_classes"]),
                        input_shape=shape)
    step = int(model.max_rows or 8)
    limit = float(config["tolerance"]["relative_distance"])

    served = jax.jit(lambda p, s, xx: jax.nn.softmax(
        model.apply(p, s, xx)[0].astype(jnp.float32), -1))
    plain = jax.jit(lambda p, s, xx: reference.forward(
        config["published"], p, s, xx))

    def round8(a):
        top = jnp.max(jnp.abs(a.astype(jnp.float32)))
        scale = jnp.where(top > 0, 448.0 / top, 1.0)
        return ((a.astype(jnp.float32) * scale).astype(jnp.float8_e4m3fn)
                .astype(jnp.float32) / scale).astype(a.dtype)

    # one fused pass a leaf, the result in the leaf's own buffer
    round_leaf = jax.jit(round8, donate_argnums=0)

    inputs = config["inputs"]
    n = int(inputs.get("candidates", 32))
    x = np.round(spec.plugin("inputs", inputs["kind"]).make(
        n, shape, seed), int(inputs["decimals"])).astype(np.float32)
    params, state = runner.parameters(config, seed)
    t0 = time.time()
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(plain(params, state, x), np.float64)
    reference_s = time.time() - t0
    separation = pairing.row_separation(ref)
    tol = min(limit, separation / 2)
    if args.stage == "float8":
        # in place of the original, leaf by leaf: two trees may not fit
        leaves, tree = jax.tree.flatten(params)
        for i, leaf in enumerate(leaves):
            if leaf.ndim >= 2:
                leaves[i] = round_leaf(leaf)
        params = jax.tree.unflatten(tree, leaves)
        matmul = layers.matmul
        layers.matmul = lambda a, w: matmul(round8(a), w)
    got = np.concatenate([np.asarray(served(params, state, x[a:a + step]))
                          for a in range(0, n, step)])
    idx, _ = pairing.match_rows(got, ref, tol)
    # a row's distance from its own reference row, whichever lies nearest
    own = np.sqrt(((got - ref) ** 2).sum(1) / (ref ** 2).sum(1))
    wrong = idx != np.arange(n)
    print(json.dumps({
        "config": args.config, "seed": seed, "tolerance": tol,
        "row_separation": separation, "reference_s": reference_s,
        "device": jax.devices()[0].device_kind,
        args.stage: {
            "correct": bool(not wrong.any()), "rows_failed": int(wrong.sum()),
            "rows": n, "min": float(own.min()),
            "median": float(np.median(own)), "max": float(own.max()),
            "sorted": [float(f"{e:.4g}") for e in np.sort(own)]}}),
        flush=True)
    # the program has to answer every row, the control not to
    return int(bool(wrong.any()) == (args.stage == "program"))


if __name__ == "__main__":
    sys.exit(main())
