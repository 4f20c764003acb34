"""Compiled-kernel parity checks: every Pallas kernel vs its jnp reference.

Why this module exists: the interpret-mode tests in tests/test_ops.py prove
the *kernel math* but run under the Pallas interpreter on CPU — a Mosaic
compilation bug (tiling, layout, masking) would be invisible to them. These
checks run the SAME kernels compiled (``interpret=False``) and compare
against the jnp references to tight tolerances; they are the "correct
softmax out of the serving path" obligation the reference carries in its
engine (InferenceBolt.java:81-86), applied to the TPU fast paths.

One consumer runs them compiled, so the chip has one entry:
  - tests/test_tpu_kernels.py — pytest wrappers, skipped (not passed)
    off-TPU: ``JAX_PLATFORMS=tpu python -m pytest tests/test_tpu_kernels.py``.
The interpreter's side (the same mathematics on the CPU) is tests/test_ops.py
and the models' own test files, which call single checks with
``interpret=True``.
"""

from __future__ import annotations

from typing import List

import numpy as np


def _row(kernel: str, case: str, dtype: str, got, want,
         rel_tol: float = None, abs_tol: float = None) -> dict:
    """Error row. Matmul kernels compare RELATIVE to the reference's max
    magnitude (TPU MXU multiplies f32 at bf16 precision by default, so a
    K-independent absolute bound would be meaningless across shapes);
    elementwise kernels use absolute error. The reference is computed at
    precision=highest so the measured error is the kernel's own."""
    abs_err = float(np.abs(got - want).max())
    scale = float(np.abs(want).max())
    rel_err = abs_err / scale if scale else abs_err
    if rel_tol is not None:
        ok, tol, metric = rel_err <= rel_tol, rel_tol, "rel"
    else:
        ok, tol, metric = abs_err <= abs_tol, abs_tol, "abs"
    return {"kernel": kernel, "case": case, "dtype": dtype,
            "max_abs_err": round(abs_err, 8),
            "max_rel_err": round(rel_err, 8),
            "metric": metric, "tol": tol, "pass": bool(ok)}


def check_flash_attention(interpret: bool = False) -> List[dict]:
    """Compiled flash attention vs the jnp reference path.

    Cases: the long-context flagship shape (S=2048, the regime the kernel
    exists for — multi-query-block grid, full online-softmax carry), a
    non-pow2 padded shape, and bf16 at S=2048 (the serving dtype). Error
    is measured in f32 against an f32 reference; bf16 tolerance reflects
    one output rounding step (~8-bit mantissa), not accumulated error —
    the kernel's carry is f32 throughout."""
    import jax
    import jax.numpy as jnp

    from storm_tpu.ops.attention import attention_reference
    from storm_tpu.ops.flash_attention import flash_attention

    rows = []
    # Two certifications per f32 case (measured on-chip, round 5):
    #   @highest — kernel traced under precision=highest: isolates Mosaic
    #     compilation (tiling/masking/layout) from MXU multiply precision;
    #     measured 4.6e-7 rel on S=2048, so 1e-5 is a real bug detector.
    #   @default — the serving configuration (MXU multiplies f32 at bf16
    #     precision): measured ~3.5e-3 rel, bounded at 5e-3.
    cases = [
        ("S2048", (1, 2, 2048, 64), jnp.float32),
        ("S2048_bf16", (1, 2, 2048, 64), jnp.bfloat16),
        ("S4096_multiblock", (1, 1, 4096, 128), jnp.float32),
        ("S600_padded", (1, 1, 600, 64), jnp.float32),
    ]
    for case, (b, h, s, d), dt in cases:
        q, k, v = (
            jax.random.normal(jax.random.PRNGKey(i), (b, h, s, d), jnp.float32)
            .astype(dt) for i in range(3))
        # Reference sees the SAME (possibly bf16-rounded) inputs upcast to
        # f32 at highest matmul precision, so the measured error is the
        # kernel's own — accumulation order, MXU multiply precision, and
        # output rounding — not the input cast.
        with jax.default_matmul_precision("highest"):
            want = np.asarray(attention_reference(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32)), np.float32)
            if dt == jnp.float32:
                got_hi = np.asarray(
                    flash_attention(q, k, v, interpret=interpret), np.float32)
                rows.append(_row("flash_attention", f"{case}@highest",
                                 np.dtype(dt).name, got_hi, want,
                                 rel_tol=1e-5))
        got = np.asarray(flash_attention(q, k, v, interpret=interpret),
                         np.float32)
        rel_tol = 1e-2 if dt == jnp.bfloat16 else 5e-3
        rows.append(_row("flash_attention", f"{case}@default",
                         np.dtype(dt).name, got, want, rel_tol=rel_tol))
    return rows


def check_short_attention(interpret: bool = False) -> List[dict]:
    """Compiled row-kernel attention vs the jnp reference on the same
    ``[B, S, H*D]`` operands.

    Cases: ViT-g/14's tokens and head width (257 x 16 heads of 88: neither a
    sublane nor a lane multiple; two tiles of keys and one key a column),
    ViT-B/16's (197 x 12 heads of 64: two heads a lane tile, the keys whole)
    in the serving dtype, a toy width in float32, the most keys taken as
    columns (264 = 256 + 8), and heads of 128 lanes (no mask, no odd key)."""
    import jax
    import jax.numpy as jnp

    from storm_tpu.ops.short_attention import _forward, reference

    rows = []
    cases = [
        ("g14_S257_H16_D88", (2, 257, 1408), 16, jnp.bfloat16),
        ("b16_S197_H12_D64", (2, 197, 768), 12, jnp.bfloat16),
        ("toy_S33_H4_D24", (3, 33, 96), 4, jnp.float32),
        ("odd8_S264_H16_D88", (2, 264, 1408), 16, jnp.bfloat16),
        ("whole_S256_H8_D128", (2, 256, 1024), 8, jnp.bfloat16),
    ]
    for case, shape, heads, dt in cases:
        q, k, v = (
            jax.random.normal(jax.random.PRNGKey(i), shape, jnp.float32)
            .astype(dt) for i in range(3))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(reference(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), heads), np.float32)
        got = np.asarray(_forward(q, k, v, heads=heads, interpret=interpret),
                         np.float32)
        rel_tol = 1e-2 if dt == jnp.bfloat16 else 5e-3
        rows.append(_row("short_attention", case, np.dtype(dt).name, got,
                         want, rel_tol=rel_tol))
    return rows


def check_w8a16(interpret: bool = False) -> List[dict]:
    """Compiled fused w8a16 dequant-matmul vs explicit dequantize-then-dot.

    Shapes exercise M/N/K padding, the multi-chunk K loop, 3-D (token)
    activations, and bf16 activations (the serving dtype for
    weights="int8_fused")."""
    import jax.numpy as jnp

    from storm_tpu.infer.engine import quantize_params
    from storm_tpu.ops.quant_matmul import w8a16_matmul

    import jax

    rng = np.random.RandomState(0)
    rows = []
    # Same two-row scheme as flash attention: @highest isolates Mosaic
    # compilation (tight 1e-5), @default certifies the serving precision
    # (bf16 MXU multiply, measured ~2e-3 rel, bounded at 5e-3).
    cases = [
        ("4x64@64x128", (4, 64), 64, 128, jnp.float32),
        ("5x100@100x70_padded", (5, 100), 100, 70, jnp.float32),
        ("2x9x48@48x200_tokens", (2, 9, 48), 48, 200, jnp.float32),
        ("1x700@700x10_multichunk", (1, 700), 700, 10, jnp.float32),
        ("64x768@768x3072_bf16", (64, 768), 768, 3072, jnp.bfloat16),
    ]
    for case, xshape, k, n, dt in cases:
        x = jnp.asarray(rng.randn(*xshape), jnp.float32).astype(dt)
        w = jnp.asarray(rng.randn(k, n), jnp.float32)
        q = quantize_params({"w": w})["w"]
        # Same-input reference (dtype-rounded x upcast to f32) at highest
        # matmul precision: measures the kernel's accumulation + output
        # rounding, not the input cast.
        with jax.default_matmul_precision("highest"):
            want = np.asarray(
                jnp.matmul(x.astype(jnp.float32),
                           q["__q"].astype(jnp.float32) * q["__s"]),
                np.float32)
            if dt == jnp.float32:
                got_hi = np.asarray(
                    w8a16_matmul(x, q["__q"], q["__s"], interpret=interpret),
                    np.float32)
                rows.append(_row("w8a16_matmul", f"{case}@highest",
                                 np.dtype(dt).name, got_hi, want,
                                 rel_tol=1e-5))
        got = np.asarray(
            w8a16_matmul(x, q["__q"], q["__s"], interpret=interpret),
            np.float32)
        rel_tol = 2e-2 if dt == jnp.bfloat16 else 5e-3
        rows.append(_row("w8a16_matmul", f"{case}@default",
                         np.dtype(dt).name, got, want, rel_tol=rel_tol))
    return rows


def check_kda_tables(interpret: bool = False) -> List[dict]:
    """Compiled within-chunk tables of KDA (``ops/kda.py
    within_chunks_kernel``) vs the jnp form (``_within_chunks``).

    One row of the batch at the Kimi cell's shapes (32 heads of 128, 64 chunks
    of 64 tokens, bfloat16 operands), at a typical decay and at one that
    forgets within a token (``exp(-G)`` overflows float32 inside a chunk),
    and a small float32 row. Each of the six results is a row of its own.
    The reference is the jnp form in float32 at ``highest`` on the same
    (dtype-rounded) inputs; the tolerance is the served type's rounding of
    the products' operands, as for the attention kernels. (Under the
    interpreter, this runner's smoke test, the heads and chunks are few.)"""
    import jax
    import jax.numpy as jnp

    from storm_tpu.ops import kda

    f32 = jnp.float32
    many = (2, 2) if interpret else (32, 64)
    cases = [
        ("kimi_decay1", many, 1.0, jnp.bfloat16),
        ("kimi_decay30_within_a_token", many, 30.0, jnp.bfloat16),
        ("toy_decay3", (2, 8), 3.0, f32),
    ]
    names = ("w", "u0", "q_in", "k_out", "a_qk", "decay")
    c, d = 64, 128
    rows = []
    for case, (h, n), decay, dt in cases:
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        lead = (h, n, c)
        q = (kda.l2norm(jax.random.normal(ks[0], lead + (d,)))
             * d ** -0.5).astype(dt)
        k = kda.l2norm(jax.random.normal(ks[1], lead + (d,))).astype(dt)
        v = jax.random.normal(ks[2], lead + (d,)).astype(dt)
        g = -decay * jax.nn.softplus(jax.random.normal(ks[3], lead + (d,)))
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], lead))
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda *a: kda._within_chunks(*a, sub=16))(
                q.astype(f32), k.astype(f32), v.astype(f32), g, beta)

        def as_layer(y):  # (H, N, C, d) -> (1, N * C, H * d)
            return jnp.moveaxis(y, 0, 2).reshape(1, n * c, h * d)

        got = kda.within_chunks_kernel(
            0, kda.empty_tables(1, n, h, c, d, d, dt),
            *(as_layer(y) for y in (q, k, v, g)), beta[None], heads=h,
            chunk=c, interpret=interpret)
        # (N, 1, H, C, .) and (1, H, N, dk) -> (H, N, ...)
        got = [jnp.moveaxis(y[:, 0], 0, 1) for y in got[:5]] + [got[5][0]]
        rel_tol = 1e-2 if dt == jnp.bfloat16 else 5e-3
        for name, x, y in zip(names, got, want):
            rows.append(_row("kda_tables", f"{case}_H{h}_N{n}:{name}",
                             np.dtype(dt).name, np.asarray(x, np.float32),
                             np.asarray(y, np.float32), rel_tol=rel_tol))
    return rows


def check_causal_attention(interpret: bool = False) -> List[dict]:
    """The compiled causal kernel, one row of a batch of two read where it
    lies, vs the blocked form of ``ops/attention.py causal_attention`` on the
    same operands.

    Cases: the two language cells' own shapes in the serving dtype (Kimi's
    latent attention, 32 heads of 4,096 tokens, 192-wide keys against
    128-wide values: the width Mosaic must lay out as a tile and a half;
    Nemotron's 32 query heads over 2 key heads of 128), each with the tiles
    ``causal_form`` gives its program, and a small float32 one whose padded
    tail (300 tokens into 512) and narrow widths go through the wrapper's
    padding. Under the interpreter the cells' sequences are cut to 1,024
    tokens (minutes otherwise); the tiles stay."""
    import jax
    import jax.numpy as jnp

    from storm_tpu.ops import attention
    from storm_tpu.ops.flash_attention import causal_tiles, flash_attention

    window = 1024 if interpret else 4096
    rows = []
    blocked = jax.jit(lambda q, k, v: attention.causal_blocked(
        q, k, v, q.shape[-1] ** -0.5))
    cases = [
        ("kimi_H32_D192_128", 32, 32, window, 192, 128, jnp.bfloat16),
        ("nemotron_H32over2_D128", 32, 2, window, 128, 128, jnp.bfloat16),
        ("toy_H4over2_S300_D24_16", 4, 2, 300, 24, 16, jnp.float32),
    ]
    for case, hq, hkv, s, dk, dv, dt in cases:
        q, k, v = (
            jax.random.normal(jax.random.PRNGKey(i), (2, h, s, d),
                              jnp.float32).astype(dt)
            for i, (h, d) in enumerate(((hq, dk), (hkv, dk), (hkv, dv))))
        block_q, block_k = causal_tiles(hq // hkv)
        got = flash_attention(q, k, v, block_q=block_q, block_k=block_k,
                              causal=True, row=1, interpret=interpret)
        want = blocked(q[1:], k[1:], v[1:])
        rel_tol = 1e-2 if dt == jnp.bfloat16 else 5e-3
        rows.append(_row("causal_attention", case, np.dtype(dt).name,
                         np.asarray(got, np.float32),
                         np.asarray(want, np.float32), rel_tol=rel_tol))
    return rows


def check_kda_mixer(interpret: bool = False) -> List[dict]:
    """Kimi-Linear's KDA mixer (``models/kimi_linear.py kda_mixer``) whole,
    as a process builds it (on the chip: the tables' kernel, every branch a
    head a block of lanes from the projections to it), vs the benchmark's
    plain reference (``benchmarks/references/kimi_linear.py _kda``: heads an
    axis, the state read token by token) in float32 at ``highest``.

    One row at the published widths (4,096 positions into 2,304, 32 heads of
    128) in bfloat16; the reference sees the same (bfloat16-rounded) weights
    and input. The measure is the root mean square of the difference over
    the reference's: the branches are rounded to bfloat16 at five points and
    the chain's products take bfloat16 operands, which reads 0.5 %; a head
    or a chunk out of place reads 1. (Under the interpreter, this runner's
    smoke test, 128 positions and 2 heads in float32, whatever form the
    tables take there.)"""
    import jax
    import jax.numpy as jnp

    from benchmarks.core import spec  # the checkout's root is on the path
    from storm_tpu.models import kimi_linear as K

    reference = spec.plugin("references", "kimi_linear")
    s, dim, heads, d, dt, tol = ((128, 64, 2, 128, jnp.float32, 1e-4)
                                 if interpret else
                                 (4096, 2304, 32, 128, jnp.bfloat16, 2e-2))
    sizes = {"linear_attn_config": {"num_heads": heads, "head_dim": d}}
    p = jax.tree.map(lambda a: a.astype(dt),
                     K.kda_mixer_init(jax.random.PRNGKey(0), dim, heads, d, 4))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, s, dim)).astype(dt)
    got = np.asarray(jax.jit(lambda p, x: K.kda_mixer(
        p, x, heads, d, 64, 1e-5))(p, x)[0], np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, x: reference._kda(
            p, x, sizes, 1e-5))(jax.tree.map(
                lambda a: a.astype(jnp.float32), p),
            x[0].astype(jnp.float32)))
    rms = float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))
    return [{**_row("kda_mixer", f"S{s}_H{heads}_D{d}", np.dtype(dt).name,
                    got, want, rel_tol=tol),
             "rms_rel_err": round(rms, 8), "metric": "rms",
             "pass": bool(rms <= tol)}]


def check_short_conv(interpret: bool = False) -> List[dict]:
    """The compiled convolution-and-SiLU kernel (``ops/kda.py
    conv_silu_kernel``) vs the plain form (``short_conv``, then
    ``jax.nn.silu``) in float32 on the same (bfloat16-rounded) input.

    Both published shapes in bfloat16, with the blocks their programs take:
    Kimi-Linear's branch (all of ``[8, 4096, 4096]``, no bias) and
    Nemotron's (the first 6,144 columns of ``[x B C | dt]``, ``[8, 4096,
    6208]``, whose last lane tile is partial, with the bias). The kernel
    rounds once, so its worst element may lie one bfloat16 step from the
    reference: the measure is the difference in steps of the reference's
    size (of 2^-7 under it: where the taps cancel, the two forms' float32
    sums differ by more than a step of what is left). Under the interpreter
    a small float32 case, three blocks of positions by two lane tiles out
    of a wider array, to 1e-6."""
    import jax
    import jax.numpy as jnp

    from storm_tpu.ops import kda

    f32 = jnp.float32
    cases = ([("toy", (2, 48, 444), 256, True, f32, dict(rows=16, step=8))]
             if interpret else
             [("kimi_branch", (8, 4096, 4096), 4096, False, jnp.bfloat16, {}),
              ("nemotron_xbc", (8, 4096, 6208), 6144, True, jnp.bfloat16, {})])
    plain = jax.jit(lambda p, x: jax.nn.silu(kda.short_conv(p, x)))
    rows = []
    for case, shape, channels, bias, dt, blocks in cases:
        kp, kx = jax.random.split(jax.random.PRNGKey(0))
        p = kda.short_conv_init(kp, channels, 4, bias=bias)
        x = jax.random.normal(kx, shape, f32).astype(dt)
        got = np.asarray(kda.conv_silu_kernel(
            p["w"], p.get("b"), x, interpret=interpret, **blocks), np.float32)
        want = np.asarray(plain(p, x[..., :channels].astype(f32)))
        row = _row("short_conv", f"{case}_C{channels}_of{shape[-1]}",
                   np.dtype(dt).name, got, want, abs_tol=1e-6)
        if dt == jnp.bfloat16:
            size = np.maximum(np.abs(want), 2.0 ** -7)
            steps = float((np.abs(got - want)
                           / 2.0 ** (np.floor(np.log2(size)) - 7)).max())
            row.update(bf16_steps=round(steps, 4), metric="bf16_steps",
                       tol=1.0, **{"pass": bool(steps <= 1.0)})
        rows.append(row)
    return rows


def ssd_recurrence(x, dt, a, b, c, d):
    """The state-space layer's definition, token by token: decay, write,
    read, skip (``ops/ssd.py``'s first two equations). ``x: (B, S, H, P)``,
    ``dt: (B, S, H)``, ``a, d: (H,)``, ``b, c: (B, S, G, N)``; each head
    reads its group's ``B`` and ``C``; the state has the type of ``x``."""
    import jax.numpy as jnp
    from jax import lax

    h, g = x.shape[2], b.shape[2]

    def token(state, xs):  # (B, H, P, N)
        x_t, dt_t, b_t, c_t = xs
        b_t, c_t = (jnp.repeat(y, h // g, axis=1) for y in (b_t, c_t))
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    xs = tuple(jnp.moveaxis(y, 1, 0) for y in (x, dt, b, c))
    _, y = lax.scan(token, jnp.zeros(x.shape[:1] + x.shape[2:]
                                     + b.shape[-1:], x.dtype), xs)
    return jnp.moveaxis(y, 0, 1) + d[:, None] * x


def check_ssd_scan(interpret: bool = False) -> List[dict]:
    """The chunked state-space scan (``ops/ssd.py``) as a process builds it,
    at both published shapes in bfloat16, vs the recurrence token by token
    in float32 at ``highest`` on the same (bfloat16-rounded) operands.

    ``nemotron``: 8 windows of 4,096, 64 heads of 64 over 8 groups of 128, a
    step of 0.05 times a softplus, ``A`` in [-16, -1], a skip ``D`` of order
    one, ``x | B | C`` side by side as the Mamba-2 mixer hands them
    (``ssd_chunked_columns``). ``lightning``: 4 windows of 16,384, 32 heads
    of 128 and a group a head, a step of 1, Lightning Attention's decays, no
    skip, q scaled by ``128 ** -0.5`` (``ssd_chunked``, as
    ``models/minicpm_sala.py`` calls it). ``granite``: Nemotron's draw at 8
    windows of 4,096 with 128 heads of 64 on one group of 128, whose 32 MiB
    of state is more than the compiler keeps in fast memory: ``ssd_kernel``
    on a TPU in a process with one device. The measure is the root
    mean square of the difference over the reference's: the chunk's products
    take bfloat16 operands (the table ``m``, the state, ``x`` times its weight)
    and the result is rounded once. A chunk or a head out of place reads 1.
    (Under the interpreter, this runner's smoke test: 150 positions, ragged,
    in float32; ``granite`` the kernel itself, interpreted, two chunks of
    128 and the 128 heads in the two steps of 64 that ship.)"""
    import jax
    import jax.numpy as jnp

    from storm_tpu.ops import ssd

    f32 = jnp.float32
    cases = ([("nemotron", (2, 150), 4, 8, 2, 16, f32, 1e-4),
              ("lightning", (2, 150), 4, 16, 4, 16, f32, 1e-4),
              ("granite", (2, 256), 128, 64, 1, 128, f32, 1e-4)]
             if interpret else
             [("nemotron", (8, 4096), 64, 64, 8, 128, jnp.bfloat16, 1e-2),
              ("lightning", (4, 16384), 32, 128, 32, 128, jnp.bfloat16,
               1e-2),
              ("granite", (8, 4096), 128, 64, 1, 128, jnp.bfloat16, 1e-2)])
    rows = []
    for case, shape, h, p, g, n, dtype, tol in cases:
        ks = jax.random.split(jax.random.PRNGKey(0), 6)
        x = jax.random.normal(ks[0], shape + (h, p)).astype(dtype)
        b = jax.random.normal(ks[3], shape + (g, n)).astype(dtype)
        c = jax.random.normal(ks[4], shape + (g, n)).astype(dtype)
        if case != "lightning":
            dt = 0.05 * jax.nn.softplus(jax.random.normal(ks[1], shape + (h,)))
            a = -jax.random.uniform(ks[2], (h,), minval=1.0, maxval=16.0)
            d = jax.random.normal(ks[5], (h,))
            xbc = jnp.concatenate([y.reshape(shape + (-1,))
                                   for y in (x, b, c)], -1)
            if case == "granite" and interpret:
                runs = jnp.cumsum((dt * a).reshape(
                    shape[0], -1, 128, h), 2).reshape(dt.shape)
                got = ssd.ssd_kernel(
                    xbc, (), dt, runs, d, n=n, chunk=128,
                    interpret=True).reshape(x.shape)
            else:
                got = jax.jit(lambda xbc, dt, a, d: ssd.ssd_chunked_columns(
                    xbc, dt, a, d, g, n))(xbc, dt, a, d).reshape(x.shape)
        else:
            dt = jnp.ones(shape + (h,), f32)
            a = -(2.0 ** (-8.0 * (jnp.arange(h) + 1) / h))
            d = jnp.zeros((h,), f32)
            c = (c.astype(f32) * n ** -0.5).astype(dtype)
            got = jax.jit(ssd.ssd_chunked)(x, dt, a, b, c, d)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(ssd_recurrence)(
                x.astype(f32), dt, a, b.astype(f32), c.astype(f32), d))
        got = np.asarray(got, np.float32)
        rms = float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))
        rows.append({**_row("ssd_scan", f"{case}_S{shape[1]}_H{h}_P{p}",
                            np.dtype(dtype).name, got, want, rel_tol=tol),
                     "rms_rel_err": round(rms, 8), "metric": "rms",
                     "pass": bool(rms <= tol)})
    return rows


def run_all(interpret: bool = False) -> List[dict]:
    return (check_flash_attention(interpret)
            + check_short_attention(interpret)
            + check_w8a16(interpret)
            + check_kda_tables(interpret)
            + check_kda_mixer(interpret)
            + check_short_conv(interpret)
            + check_ssd_scan(interpret)
            + check_causal_attention(interpret))
