#!/usr/bin/env python3
"""One chip check beside a cell of the ``trinity`` family: the window in the
shared attention code, and the program's mixer whole, against the
configuration's plain reference at the published widths, one window:

    python3 benchmarks/tools/trinity_mixer_check.py --config trinity_mini \
        --seed 5700000021

One JSON line a check, kind and form, the program's result in the served
type against float32 at ``highest``, as the largest and the root-mean-square
distance over the reference's root mean square:

- ``attention``: ``ops/attention.py causal_attention(window=sliding_window)``
  on one row of N(0, 1) queries, keys and values (``[1, 32, S, 128]`` on
  ``[1, 4, S, 128]`` at the published sizes), by the Pallas kernel (what
  ``causal_form`` picks on one chip: the key blocks before a tile's windows
  never loaded) and by XLA's blocked form (this tool answers the rule's
  question about the platform in the kernel's place: no option of the program
  does), against the full ``S x S`` masked softmax a head written out here.
- ``mixer``: ``models/trinity.py trinity_mixer`` whole (head norms, rotary in
  the sliding kind, the gate) of a ``sliding`` and of a ``full`` layer, each
  in both forms, against ``references/trinity.py _attention`` from the same
  leaves and the same input.

Each line carries the forms the program noted (``window_attention=
kernel-grouped``). Exit code 1 where a line reads over ``--limit``."""

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
    ap.add_argument("--limit", type=float, default=0.02)
    ap.add_argument("--rehearse", action="store_true",
                    help="any platform (the tests' toy configurations)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.core import spec
    from storm_tpu.infer.engine import enable_compile_cache
    from storm_tpu.models.trinity import trinity_mixer
    from storm_tpu.ops import attention, rope
    from storm_tpu.ops.platform import dispatch_notes

    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print("no TPU: the check is the chip's", file=sys.stderr)
        return 2
    enable_compile_cache()
    config = spec.config(args.config)
    sizes = config["published"]
    held = sizes["held"]
    runner = spec.plugin("runners", config["runner"])
    reference = spec.plugin("references", config["reference"])
    params, _ = runner.parameters(config, args.seed)
    kinds = [sizes["layer_types"][i] for i in held["layers"]]
    leaves = {kind: params["layers"][kinds.index(kind)]["mixer"]
              for kind in ("sliding_attention", "full_attention")}
    del params
    heads, kv_heads = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d, eps = sizes["head_dim"], sizes["rms_norm_eps"]
    s, window = held["sequence_length"], sizes["sliding_window"]
    dtype = jnp.dtype(config["model"]["dtype"])
    ks = jax.random.split(jax.random.PRNGKey(args.seed % 2 ** 31), 4)
    x = jax.random.normal(ks[0], (1, s, sizes["hidden_size"]), jnp.float32)
    q, k, v = (jax.random.normal(key, (1, n, s, d), jnp.float32).astype(dtype)
               for key, n in zip(ks[1:], (heads, kv_heads, kv_heads)))
    inv_freq = float(sizes["rope_theta"]) ** (-2.0 * np.arange(d // 2) / d)

    def plain_window(q, k, v):
        """Every head's full masked softmax, a head at a time."""
        at = jnp.arange(s)
        unseen = (at[None, :] > at[:, None]) \
            | (at[None, :] <= at[:, None] - window)

        def head(qkv):
            q_h, k_h, v_h = qkv
            scores = jnp.where(unseen, -jnp.inf, q_h @ k_h.T * d ** -0.5)
            return jax.nn.softmax(scores, -1) @ v_h

        return jax.lax.map(head, (q, jnp.repeat(k, heads // kv_heads, 0),
                                  jnp.repeat(v, heads // kv_heads, 0)))

    def mixer_program(kind):
        reach = window if kind == "sliding_attention" else None
        return lambda p, u: trinity_mixer(
            p, u, heads, kv_heads, d, eps, rope.rotary_tables(s, inv_freq),
            reach)[0]

    # (check, kind, the program, its arguments, the plain form, its arguments)
    f32 = jnp.float32
    checks = [("attention", "sliding_attention",
               lambda q, k, v: attention.causal_attention(
                   q, k, v, d ** -0.5, window=window)[0],
               (q, k, v), plain_window,
               tuple(y[0].astype(f32) for y in (q, k, v)))]
    checks += [("mixer", kind, mixer_program(kind),
                (leaves[kind], x.astype(dtype)),
                lambda p, u, kind=kind: reference._attention(
                    p, u, sizes, kind, eps),
                (leaves[kind], x[0].astype(dtype).astype(f32)))
               for kind in leaves]
    bad = 0
    for check, kind, program, given, plain, plainly in checks:
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(plain)(*plainly), np.float64)
        rms = np.sqrt((want ** 2).mean())
        for xla in (False, True):
            kept = attention._use_pallas
            if xla:  # XLA's form: the rule's platform question answered no
                attention._use_pallas = lambda: False
            try:
                with dispatch_notes() as forms:  # a new function: traced anew
                    got = jax.jit(lambda *a: program(*a))(*given)
            finally:
                attention._use_pallas = kept
            got = np.asarray(got, np.float64)
            row = {"config": args.config, "seed": args.seed, "check": check,
                   "kind": kind, "length": s, "window": window,
                   "forms": forms, "device": jax.devices()[0].device_kind,
                   "max_over_rms": float(np.abs(got - want).max() / rms),
                   "rms_over_rms": float(np.sqrt(((got - want) ** 2).mean())
                                         / rms)}
            row["pass"] = bool(np.isfinite(got).all()
                               and row["rms_over_rms"] <= args.limit)
            bad += not row["pass"]
            print(json.dumps(row), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
