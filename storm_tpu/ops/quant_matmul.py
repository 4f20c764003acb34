"""Pallas TPU fused w8a16 dequant-matmul.

Why: the baseline w8a16 path (`storm_tpu.infer.engine.dequantize_params`)
dequantizes int8 weights inside jit and relies on XLA to fuse the
int8→bf16 convert+scale into each weight's consumer. When XLA instead
materializes the dequantized matrix, the HBM read per matmul doubles —
exactly the traffic weight-only quantization exists to avoid. This kernel
*guarantees* the int8 bytes are what leaves HBM: each program reads an
(int8 K×bn weight tile + bm×K activation tile) into VMEM, upcasts in
registers, accumulates f32 on the MXU, and applies the per-output-channel
scale once to the accumulator (valid because quantization is symmetric
per last axis: ``x @ (q * s) == (x @ q) * s``).

Reference parity note: the reference has no quantization at all (its
engine is TF-Java float32, InferenceBolt.java:80-86); this is part of the
beyond-parity serving path (`ModelConfig.weights = "int8_fused"`).

Layout: ``x (..., K) @ q (K, N) * s (N,) -> (..., N)`` in x.dtype. Leading
dims flatten to M. Grid is (M/bm, N/bn); K lives fully in VMEM per program
(K ≤ a few thousand for every model in the zoo) and is consumed in
``block_k`` chunks with zero-padding — zeros contribute nothing to the
accumulator, so no masking is needed. M/N are padded to block multiples
and sliced off on return.

CPU/tests: ``interpret=True`` runs the same kernel under the Pallas
interpreter — cross-checked against the jnp dequant reference in
tests/test_ops.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

_LANE = 128


def _qmm_kernel(x_ref, q_ref, s_ref, o_ref, *, block_k):
    kp = x_ref.shape[1]
    nk = kp // block_k

    acc0 = jnp.zeros((x_ref.shape[0], o_ref.shape[1]), jnp.float32)

    def body(i, acc):
        xb = x_ref[:, pl.ds(i * block_k, block_k)]  # (BM, BK) activations
        qb = q_ref[pl.ds(i * block_k, block_k), :].astype(xb.dtype)
        return acc + lax.dot_general(
            xb, qb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    acc = lax.fori_loop(0, nk, body, acc0)
    o_ref[...] = (acc * s_ref[0].astype(jnp.float32)).astype(o_ref.dtype)


def _pad_to(a, axis, mult):
    n = a.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def w8a16_matmul(
    x: jnp.ndarray,
    q: jnp.ndarray,
    s: jnp.ndarray,
    *,
    block_m: int = 512,
    block_n: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """``x (..., K) @ (q (K, N) int8 * s (N,)) -> (..., N)`` in x.dtype.

    Block defaults from an on-chip sweep of round 2 (no ledger line): the
    round-1 128/128/256 tiles ran the vit_b16 mlp_in shape at 1.39 ms vs
    0.60-0.67 ms with 512-wide tiles (~2.2x). Even tuned, XLA's own
    dequant+matmul fusion remains faster at the zoo's compute-bound
    shapes — ``weights="int8"`` is the recommended w8a16 mode; this
    kernel's guarantee (int8 bytes are all that leaves HBM) matters in
    weight-bandwidth-bound regimes (very large K x N, small M)."""
    *lead, k = x.shape
    kq, n = q.shape
    assert k == kq, f"contraction mismatch: x K={k}, q K={kq}"
    assert s.shape == (n,), f"scale must be ({n},), got {s.shape}"

    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    # Mosaic wants (8, 128)-aligned f32 tiles: round the row block up to a
    # multiple of 8 rather than using a small M verbatim.
    bm = min(block_m, ((max(8, m) + 7) // 8) * 8)
    x2 = _pad_to(_pad_to(x2, 1, block_k), 0, bm)
    qp = _pad_to(_pad_to(q, 0, block_k), 1, block_n)
    sp = _pad_to(s.astype(jnp.float32).reshape(1, n), 1, block_n)
    mp, kp = x2.shape
    np_ = qp.shape[1]

    out = pl.pallas_call(
        functools.partial(_qmm_kernel, block_k=block_k),
        grid=(mp // bm, np_ // block_n),
        in_specs=[
            pl.BlockSpec((bm, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((kp, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        interpret=interpret,
    )(x2, qp, sp)
    return out[:m, :n].reshape(*lead, n)


def qdense(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    """Dense layer over quantized weights ``{"__q", "__s"}`` (the
    `quantize_params` leaf format), Pallas-fused on TPU."""
    from storm_tpu.ops.platform import use_pallas

    w = p["w"]
    if use_pallas():
        y = w8a16_matmul(x, w["__q"], w["__s"])
    else:
        wd = (w["__q"].astype(x.dtype) * w["__s"].astype(x.dtype))
        y = jnp.dot(x, wd, preferred_element_type=jnp.float32).astype(x.dtype)
    return y + p["b"]
