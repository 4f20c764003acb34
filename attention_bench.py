"""Time a ViT forward on the chip with XLA's attention and with the row kernel.

The evidence behind ``ops/attention.py _ROW_KERNEL_MIN_SCORES`` (PERF.md §6,
PR 29): no benchmark cell runs a TPU program under the threshold, so this
script is what shows the other side. Not part of the benchmark.

    python attention_bench.py g14:8:8 g14:8:256 b16:12:16 b16:12:32

Each argument is ``model:blocks:batch`` (``g14`` = ViT-g/14 widths, ``b16`` =
ViT-B/16). Per argument it builds the bf16 forward twice, the rule forced
to ``xla`` and to ``rows``, and prints one JSON line a form: the mean step
on the host's clock around ``block_until_ready``, the scores of a block,
what the rule itself would choose, and how far the two forms' outputs lie
apart. Fails off TPU: a CPU time is no device time.
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from storm_tpu.models.vit import build_vit
from storm_tpu.ops import attention

_WIDTHS = {  # patch, dim, heads, mlp
    "g14": (14, 1408, 16, 6144),
    "b16": (16, 768, 12, 3072),
}


def _step_ms(fwd, args, seconds=2.0):
    out = fwd(*args)
    out.block_until_ready()
    n, t0 = 0, time.perf_counter()
    while time.perf_counter() - t0 < seconds or n < 3:
        out = fwd(*args)
        n += 1
    out.block_until_ready()
    return (time.perf_counter() - t0) / n * 1e3, np.asarray(out, np.float32)


def main(argv):
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"attention_bench.py measures a TPU; found {dev.platform}")
    rule = attention.attention_form
    for arg in argv:
        name, blocks, batch = arg.split(":")
        patch, dim, heads, mlp = _WIDTHS[name]
        model = build_vit(name, 1000, (224, 224, 3), patch=patch, dim=dim,
                          depth=int(blocks), num_heads=heads, mlp_dim=mlp)
        params, state = jax.jit(model.init)(jax.random.PRNGKey(0))
        params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
        x = jax.random.normal(jax.random.PRNGKey(1),
                              (int(batch), 224, 224, 3), jnp.bfloat16)
        tokens = (224 // patch) ** 2 + 1
        first = None
        for form in ("xla", "rows"):
            attention.attention_form = lambda *a, form=form: form
            try:
                # a new function a form: jit keeps a function's trace
                fwd = jax.jit(lambda p, s, x: jax.nn.softmax(model.apply(
                    p, s, x, train=False)[0].astype(jnp.float32), -1))
                ms, out = _step_ms(fwd, (params, state, x))
            finally:
                attention.attention_form = rule
            first = out if first is None else first
            print(json.dumps({
                "device": dev.device_kind, "model": name,
                "blocks": int(blocks), "batch": int(batch), "form": form,
                "step_ms": ms,
                "scores": int(batch) * heads * tokens * tokens,
                "rule_chooses": rule(int(batch), tokens, dim, heads, 2),
                "max_rel_dist_to_xla": float(
                    (np.linalg.norm(out - first, axis=1)
                     / np.linalg.norm(first, axis=1)).max()),
            }), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
