"""Device mesh construction.

The TPU-native meaning of the reference's operator ``parallelismHint``
(MainTopology.java:26-28): instead of N replicated JVM executors each holding
a full model copy (InferenceBolt.java:57-58), one ``jax.sharding.Mesh`` over
the slice's chips, with the batch axis sharded across ``data`` and
(optionally) params sharded across ``model``. Collectives ride ICI — XLA
inserts them from sharding annotations (psum/all-gather), no NCCL-equivalent
calls in user code.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh


def open_devices() -> list:
    """``jax.devices()``, with the one start-up failure a serving host
    really meets spelled out. A TPU belongs to one process at a time; the
    second process to open it gets libtpu's lockfile error, which tells
    the operator to delete the lock instead of naming the holder."""
    try:
        return jax.devices()
    except RuntimeError as e:
        msg = str(e)
        if "lockfile" in msg or "already in use" in msg:
            raise RuntimeError(
                "cannot open the TPU: another process on this host holds "
                "it, and a chip belongs to one process at a time. Place "
                "every engine of this host on ONE worker process and start "
                "processes that host no engine with JAX_PLATFORMS=cpu. "
                f"(libtpu: {msg})") from e
        raise


def device_info() -> dict:
    """The devices this process runs on, as JAX reports them. Every result
    a bench script prints carries all three keys: a bare device count says
    nothing of the platform, and a CPU run must never read as a chip."""
    devs = open_devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def make_mesh(
    data_parallel: int = 0,
    tensor_parallel: int = 1,
    axis_names: Sequence[str] = ("data", "model"),
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a (data, model) mesh.

    ``data_parallel=0`` means "use all remaining devices". Device order is
    kept as enumerated — on a real slice this preserves ICI-neighbor
    adjacency along the trailing (model) axis, where tensor-parallel
    collectives are most bandwidth-hungry.
    """
    devs = list(devices if devices is not None else open_devices())
    n = len(devs)
    if tensor_parallel < 1 or n % tensor_parallel:
        raise ValueError(f"tensor_parallel={tensor_parallel} must divide device count {n}")
    if data_parallel <= 0:
        data_parallel = n // tensor_parallel
    if data_parallel * tensor_parallel > n:
        raise ValueError(
            f"dp*tp = {data_parallel}*{tensor_parallel} exceeds {n} devices"
        )
    used = devs[: data_parallel * tensor_parallel]
    arr = np.array(used).reshape(data_parallel, tensor_parallel)
    return Mesh(arr, tuple(axis_names))


def default_mesh() -> Mesh:
    """All devices on the data axis (pure DP — the reference's model)."""
    return make_mesh()
