"""Shared Pallas-vs-reference dispatch predicate for the ops package.

Kernels (flash attention, w8a16 dequant-matmul) run as Pallas on TPU and
take the jnp reference paths elsewhere (CPU tests, unsupported shapes).
``STORM_TPU_NO_PALLAS`` forces the reference paths everywhere — the
escape hatch for debugging numeric diffs. A backend that cannot be
opened (chip missing, or held by another process) is an error, never a
quiet switch to the reference path.
"""

from __future__ import annotations

import os

import jax


def use_pallas() -> bool:
    if os.environ.get("STORM_TPU_NO_PALLAS"):
        return False
    return jax.devices()[0].platform == "tpu"
