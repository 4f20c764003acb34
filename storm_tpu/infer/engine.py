"""The TPU inference engine: jit-compiled model apply over a device mesh.

Replaces the reference's inference engine layer (layer 4, SURVEY.md §1):
``SavedModelBundle.load`` + per-tuple ``session.run`` over JNI
(InferenceBolt.java:57, :80-86) becomes a jit-compiled JAX function over a
``Mesh`` with the batch axis sharded across ``data`` and params replicated
(or TP-sharded across ``model``). One engine is shared by all inference
operator tasks on a host — the mesh, not operator replication, is the
parallelism (the reference instead loaded one full model copy per bolt).

Outputs are softmax probabilities, matching the reference's fetch of
``"output/Softmax:0"`` (InferenceBolt.java:84).
"""

from __future__ import annotations

import gc
import itertools
import logging
import os
import queue
import re
import sys
import threading
import time
import weakref
from collections import OrderedDict
from concurrent.futures import Future
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from storm_tpu.config import BatchConfig, ModelConfig, ShardingConfig
from storm_tpu.models.registry import ModelDef, build_model, load_or_init
from storm_tpu.obs import copyledger as _copyledger
from storm_tpu.obs.profile import ensure_installed, new_step_row, setup_span
from storm_tpu.ops.parts import HEAD
from storm_tpu.ops.platform import dispatch_notes
from storm_tpu.parallel.mesh import make_mesh
from storm_tpu.parallel.sharding import (
    batch_sharding,
    replicated,
    shard_params_ep,
    shard_params_tp,
)

logger = logging.getLogger(__name__)


# ---- weight-only int8 quantization (w8a16 serving) ----------------------------


def quantize_params(params, min_ndim: int = 2):
    """f32/bf16 param pytree -> same tree with weight leaves replaced by
    ``{"__q": int8, "__s": f32 per-output-channel scales}``.

    Symmetric per-output-channel (last axis) quantization; leaves below
    ``min_ndim`` (biases, norm scales) stay full precision — they are tiny
    and precision-critical."""
    def quant(leaf):
        if not hasattr(leaf, "ndim") or leaf.ndim < min_ndim or \
                leaf.dtype.kind not in "fV":  # V: bfloat16 shows as void-kind
            return leaf
        w = np.asarray(leaf, np.float32)
        axes = tuple(range(w.ndim - 1))
        scale = np.max(np.abs(w), axis=axes) / 127.0
        scale = np.maximum(scale, 1e-12).astype(np.float32)
        q = np.clip(np.rint(w / scale), -127, 127).astype(np.int8)
        return {"__q": q, "__s": scale}

    return jax.tree.map(quant, params)


def _is_qleaf(x) -> bool:
    return isinstance(x, dict) and "__q" in x


def dequantize_params(qparams, dtype, keep_dense: bool = False):
    """Inverse of :func:`quantize_params`; runs INSIDE jit so XLA fuses the
    int8->dtype multiply into each weight's first use.

    ``keep_dense=True`` ("int8_fused" mode) leaves dense-layer weights
    quantized: :func:`storm_tpu.ops.layers.dense` detects them and runs
    the Pallas fused dequant-matmul, so they stay int8 all the way to
    VMEM. "Dense-layer weight" is identified by tree path — a 2-D qleaf
    under a ``"w"`` key, the `dense_init` layout — NOT by rank alone:
    other 2-D params (e.g. the MoE gate) are consumed as raw arrays and
    must be dequantized here. Conv kernels (4-D, also ``"w"``) are
    dequantized — XLA's conv has no fused-dequant kernel equivalent."""
    def deq(path, l):
        if not _is_qleaf(l):
            return l
        if keep_dense and l["__q"].ndim == 2 and path and \
                getattr(path[-1], "key", None) == "w":
            return l
        return l["__q"].astype(dtype) * l["__s"].astype(dtype)

    return jax.tree_util.tree_map_with_path(deq, qparams, is_leaf=_is_qleaf)


# ---- split-phase pipeline plumbing --------------------------------------------


class StagingPool:
    """Preallocated, recycled host staging buffers keyed by (shape, dtype).

    The dispatch phase stages a batch into one of these with a single
    fused write (replacing the ``np.concatenate`` + pad-``concatenate`` +
    ``astype`` copies of the stacked path), hands it to ``device_put``,
    and keeps holding it until the batch's FETCH completes — jax backends
    may alias a suitably-aligned host buffer instead of copying (CPU
    zero-copy donation), so recycling before the dependent execution
    finished could corrupt an in-flight batch. ``limit`` bounds buffers
    per key; ``acquire`` blocks (on the caller's worker thread) when that
    many are in flight, which the pipeline ring normally prevents.
    """

    def __init__(self, limit: int) -> None:
        self.limit = max(1, int(limit))
        self.allocated = 0  # fresh np.empty calls ever made (alloc guard)
        self._lock = threading.Lock()
        self._free: Dict[tuple, List[np.ndarray]] = {}
        self._sems: Dict[tuple, threading.Semaphore] = {}

    def acquire(self, shape: tuple, dtype) -> np.ndarray:
        key = (tuple(shape), np.dtype(dtype))
        with self._lock:
            sem = self._sems.get(key)
            if sem is None:
                sem = self._sems[key] = threading.Semaphore(self.limit)
        sem.acquire()
        with self._lock:
            free = self._free.setdefault(key, [])
            if free:
                return free.pop()
            self.allocated += 1
        return np.empty(shape, dtype)

    def release(self, buf: np.ndarray) -> None:
        key = (buf.shape, np.dtype(buf.dtype))
        with self._lock:
            self._free.setdefault(key, []).append(buf)
            sem = self._sems[key]
        sem.release()

    def stats(self) -> Dict[str, int]:
        """Utilization snapshot for the observatory's occupancy gauges:
        buffers ever allocated, currently free, and (the difference) held
        by in-flight batches."""
        with self._lock:
            free = sum(len(v) for v in self._free.values())
            return {"allocated": self.allocated, "free": free,
                    "in_use": max(0, self.allocated - free),
                    "limit": self.limit}


class EngineWatchdogTimeout(RuntimeError):
    """A batch overran ``batch.watchdog_ms`` on the fetch ring.

    Raised on the fetch thread INSIDE the per-batch try, so it rides the
    existing isolation path: only the stuck batch's future fails (its
    sources replay) and the ring/staging slots are released — the device
    program may still be running, but the pipeline stops waiting on it."""


class EngineQuarantined(RuntimeError):
    """Dispatch refused: this engine tripped its watchdog
    ``batch.watchdog_trips`` times in a row and is quarantined. Callers
    fail the batch (sources replay) until the operator swaps in a
    replacement engine (see InferenceOperator's on_quarantine hook)."""


class InflightBatch:
    """Handle for one batch inside the split-phase pipeline.

    ``future`` resolves (on the engine's fetch thread) to the host
    ``np.ndarray`` result sliced to the true batch size — or to the
    exception that failed THIS batch only. ``timings`` carries the
    per-phase wall-clock attribution once known: ``h2d_ms`` (staging +
    host->device transfer + async jit launch; includes XLA compile on a
    cold bucket shape), ``compute_ms`` (launch -> results ready, i.e.
    device queue + execute) and ``d2h_ms`` (the blocking device->host
    copy). ``compute_ms``/``d2h_ms`` are filled by the fetch phase, so
    read them only after ``future`` resolves.
    """

    __slots__ = ("future", "n", "padded", "timings", "profile_key", "_out",
                 "_buf", "_t_put", "_t_launched", "watchdog_ms", "on_done",
                 "aux", "step")

    def __init__(self, n: int, padded: int) -> None:
        self.future: Future = Future()
        self.n = n
        self.padded = padded
        self.timings: Dict[str, float] = {}
        # Cost-profile attribution: which engine's curve this batch feeds
        # (set by dispatch; None = don't profile, e.g. test doubles).
        self.profile_key: Optional[str] = None
        self._out = None  # device array, dropped after fetch
        # What the model counted on the device during this step (its
        # ``new_state["aux"]``): device arrays from launch, host arrays once
        # the fetch thread has brought them over with the result. None for
        # a model that counts nothing.
        self.aux = None
        self._buf = None  # staging buffer, recycled after fetch
        # staged on the host, device_put + launch next; 0.0 = not to be
        # timed (a program's first call compiles or loads it)
        self._t_put = 0.0
        self._t_launched = 0.0
        # Watchdog contract (set by dispatch): fetch waits at most
        # watchdog_ms (0 = forever) and reports the outcome to on_done —
        # a bound engine method, so the handle pins the engine only while
        # this batch is in flight (the fetch THREAD still holds no ref).
        self.watchdog_ms = 0.0
        self.on_done = None
        # This step's row of the step log (``obs/profile.py``; None: not
        # logged). Built here and by the queue that cut the batch, appended
        # once by the fetch thread to the profile sink's ring.
        self.step: Optional[dict] = None

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        return self.future.result(timeout)


def _fetch_loop(fetch_q: "queue.SimpleQueue", ring: threading.Semaphore,
                staging: StagingPool, step_ms: Dict[int, float]) -> None:
    """Dedicated fetch thread: completes in-flight batches in dispatch
    order. Blocking here is the point — one batch's device->host RTT
    overlaps the NEXT batch's staging/H2D (dispatch holds the lock, fetch
    never does) and the one-after's device compute. Module-level so the
    thread never references the engine (see _ensure_fetch_thread); a None
    sentinel (engine finalizer, tests) shuts it down.

    It also keeps ``step_ms`` (the engine's dict, not the engine): the
    device time of one step of each padded bucket, as the least seen of
    "ready, less the later of this batch's hand-over to the device (its
    ``device_put``; a backend that computes inside the launch call has
    computed by the launch's return) and the batch before becoming ready"
    — with a batch queued behind another that is the step itself,
    queueing excluded. Only moments this thread SAW count: a batch
    that was ready before the thread came to it (a stalled host) would
    read as late as the thread was, and the next one as short, and a
    least-seen never forgets a reading that was too short."""
    prev_ready, prev_seen = 0.0, True
    while True:
        handle = fetch_q.get()
        if handle is None:
            return
        seen = False
        try:
            seen = _not_ready_yet(handle._out)
            _watchdog_wait(handle)
            t1 = time.perf_counter()
            if seen and handle._t_put and (
                    prev_seen or handle._t_put >= prev_ready):
                step = (t1 - max(handle._t_put, prev_ready)) * 1e3
                if step < step_ms.get(handle.padded, float("inf")):
                    step_ms[handle.padded] = step
            prev_ready = t1
            res = np.asarray(handle._out)
            if handle.aux is not None:
                handle.aux = jax.tree.map(np.asarray, handle.aux)
            t2 = time.perf_counter()
            handle.timings["compute_ms"] = (t1 - handle._t_launched) * 1e3
            handle.timings["d2h_ms"] = (t2 - t1) * 1e3
            handle._out = None
            row = handle.step
            if row is not None:
                wall = time.time()
                row["t_ready"], row["t_fetched"] = t1 + (wall - t2), wall
                row["seen"] = seen
            # the members' callbacks run inside: the queue's ``_finish``
            # stamps ``t_resolved`` into the row before the append below
            handle.future.set_result(res[:handle.n])
            # Copy ledger: the blocking device->host materialization is
            # one full-result copy into a fresh host array.
            _copyledger.record("d2h", res.nbytes, copies=1, allocs=1,
                               records=handle.n,
                               engine=handle.profile_key or "-")
            # Cost profiler (storm_tpu/obs/profile.py): per-(engine,
            # bucket) curves fed right where all three phase timings are
            # finally known. One sink check per BATCH; must never fail
            # (or even slow) a batch.
            sink = _profile_sink
            if sink is not None and handle.profile_key is not None:
                try:
                    sink.record_batch(handle.profile_key, handle.padded,
                                      handle.n, handle.timings, row)
                except Exception:
                    pass
        except BaseException as e:  # noqa: BLE001 - fail ONLY this batch
            handle._out = None
            handle.future.set_exception(e)
            _notify_done(handle, e)
            # when the device is done with it nobody saw
            prev_ready, seen = time.perf_counter(), False
        else:
            _notify_done(handle, None)
        finally:
            prev_seen = seen
            buf, handle._buf = handle._buf, None
            if buf is not None:
                staging.release(buf)
            ring.release()


def _not_ready_yet(out) -> bool:
    """Whether the fetch thread will SEE this result become ready. A
    function of its own so that no local of ``_fetch_loop`` keeps the
    device array: the loop's frame lives until interpreter exit, and a
    device array freed from a daemon thread then aborts the process."""
    is_ready = getattr(out, "is_ready", None)
    return is_ready is not None and not is_ready()


def _watchdog_wait(handle: InflightBatch) -> None:
    """Wait for the batch's device result, bounded by ``watchdog_ms``.

    With no deadline (or a result object that can't report readiness)
    this is the plain blocking wait. With one, poll ``is_ready()`` —
    jax.Array exposes it without blocking — and raise
    :class:`EngineWatchdogTimeout` past the deadline so the stuck batch
    fails alone instead of wedging the whole fetch ring behind it."""
    out = handle._out
    ms = handle.watchdog_ms
    is_ready = getattr(out, "is_ready", None)
    if ms <= 0 or is_ready is None:
        out.block_until_ready()
        return
    deadline = time.monotonic() + ms / 1e3
    while not is_ready():
        if time.monotonic() > deadline:
            raise EngineWatchdogTimeout(
                f"batch (n={handle.n}, padded={handle.padded}) exceeded "
                f"watchdog_ms={ms:g} on the fetch ring")
        time.sleep(min(0.002, ms / 1e4))
    out.block_until_ready()


class _HangingResult:
    """Chaos wrapper: a device result that refuses to report ready until
    its hold expires (:meth:`ChaosInjector.engine_hang_s`) — gives the
    fetch-ring watchdog a genuinely stuck batch to catch without having
    to wedge a real device program."""

    __slots__ = ("_inner", "_until")

    def __init__(self, inner, until: float) -> None:
        self._inner = inner
        self._until = until

    def is_ready(self) -> bool:
        if time.monotonic() < self._until:
            return False
        ir = getattr(self._inner, "is_ready", None)
        return True if ir is None else ir()

    def block_until_ready(self):
        rem = self._until - time.monotonic()
        if rem > 0:
            time.sleep(rem)
        bur = getattr(self._inner, "block_until_ready", None)
        if bur is not None:
            bur()
        return self

    def __array__(self, dtype=None):
        a = np.asarray(self._inner)
        return a if dtype is None else a.astype(dtype, copy=False)


def _notify_done(handle: InflightBatch, exc) -> None:
    cb = handle.on_done
    handle.on_done = None  # drop the engine ref with the batch
    if cb is None:
        return
    try:
        cb(exc)
    except Exception:
        pass  # a watchdog accounting hook must never fail the loop


# ---- cost-profile sink (storm_tpu/obs/profile.py) ----------------------------

# Process-wide observer for completed batches + cold compiles, same spirit
# as the per-engine ``on_compile`` hook but installed once for every
# engine (the ProfileStore is process-scoped, like the engine cache).
# None = profiling off; the hot path pays one global read per batch.
_profile_sink = None


def set_profile_sink(sink) -> None:
    """Install (or, with None, remove) the process profile sink. ``sink``
    needs ``record_batch(key, padded, rows, timings)`` and
    ``record_compile(key, padded, ms)`` — see
    :class:`storm_tpu.obs.profile.ProfileStore`."""
    global _profile_sink
    _profile_sink = sink


def _report_compile(key: str, padded: int, ms: float) -> None:
    sink = _profile_sink
    if sink is not None:
        try:
            sink.record_compile(key, padded, ms)
        except Exception:
            pass  # an observability hook must never fail a batch


# Where the persistent compile cache lives when the environment does not
# place it. Fixed, inside the checkout (git-ignored): the directory is part
# of the cache key, so a path that moves between runs never hits.
DEFAULT_COMPILE_CACHE_DIR = str(
    Path(__file__).resolve().parents[2] / ".jax_cache")


def key_on_metadata() -> None:
    """Make the compile cache's key cover the operations' metadata, and
    keep that metadata to what the program is (``enable_compile_cache``
    says why)."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(os.path.dirname(
                          DEFAULT_COMPILE_CACHE_DIR)) + "/")


def enable_compile_cache() -> str:
    """Turn on jax's persistent executable cache for this process and
    return its directory. Called once by each entry point (``main`` run /
    serve, the dist worker, ``chip_smoke.py``'s children, the bench
    scripts) before the first compile — jax latches the directory then.

    ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside: jax reads
    it by itself and this function sets no directory. Unset, the cache
    goes to :data:`DEFAULT_COMPILE_CACHE_DIR`. Worker processes inherit
    the environment, so every process of a run shares one cache and a
    restarted daemon reloads its bucket shapes instead of recompiling
    them. The persistence gate drops from jax's 1.0 s default so the
    small models in the zoo are cached too.

    The key covers the operations' metadata. By default jax strips it from
    the key, and a program that differs from a cached one in metadata alone
    (the parts' names of ``ops/parts.py``) is handed the cached executable,
    whose device trace then names the other program's parts (seen on the
    CPU: an unscoped function loaded its scoped twin's executable). What
    the metadata holds is kept to what the program is: an operation's own
    source line, not the chain of calls that led there (two entry points
    that build the same engine share its executables; the limit of one
    frame, because without full tracebacks jax writes the names in a form
    from which XLA drops the scopes), with paths written relative to the
    checkout (the same tree at another path still hits)."""
    # the set-up log hears every compile and cache look-up from here on
    ensure_installed()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    key_on_metadata()
    return jax.config.jax_compilation_cache_dir


def profile_key_of(model_cfg: ModelConfig) -> str:
    """Which curve an engine of ``model_cfg`` feeds in the process
    ProfileStore. Checkpoint-qualified so cascade tiers / swap variants
    sharing a registry name keep separate curves."""
    ckpt = getattr(model_cfg, "checkpoint", None)
    return f"{model_cfg.name}@{ckpt}" if ckpt else model_cfg.name


class InferenceEngine:
    def __init__(
        self,
        model_cfg: ModelConfig,
        sharding_cfg: Optional[ShardingConfig] = None,
        batch_cfg: Optional[BatchConfig] = None,
        mesh=None,
        softmax: bool = True,
    ) -> None:
        self.model_cfg = model_cfg
        self.sharding_cfg = sharding_cfg or ShardingConfig()
        with setup_span("model.build"):
            self.model: ModelDef = build_model(
                model_cfg.name,
                num_classes=model_cfg.num_classes,
                input_shape=tuple(model_cfg.input_shape),
                **getattr(model_cfg, "extra", {}),
            )
        # The configured buckets, none over the rows the model lets a step
        # hold (None for most: the policy as given, the same object).
        self.max_rows = self.model.max_rows
        self.batch_cfg = (batch_cfg or BatchConfig()).clipped(self.max_rows)
        self.dtype = jnp.dtype(model_cfg.dtype)
        # The type a staged batch reaches the device in: the compute type,
        # unless the model's instances are not to be rounded to it (ids).
        self.in_dtype = (jnp.dtype(self.model.input_dtype)
                         if self.model.input_dtype else self.dtype)
        # Serving parallelism beyond DP: at most ONE of tp/sp/ep sizes the
        # mesh's second axis (composing them needs a 3D mesh — train-side
        # territory; serving keeps one knob per engine).
        #   tp — Megatron param sharding ("model" axis);
        #   sp — sequence axis sharded, ring attention ("seq" axis; needs
        #        an SP-aware model forward, ModelDef.apply_sp);
        #   ep — MoE expert tensors sharded ("expert" axis; apply is
        #        unchanged, GSPMD lowers dispatch/combine to all-to-alls).
        self.sp = int(getattr(self.sharding_cfg, "sequence_parallel", 1))
        self.ep = int(getattr(self.sharding_cfg, "expert_parallel", 1))
        tp_req = int(self.sharding_cfg.tensor_parallel)
        if sum(x > 1 for x in (tp_req, self.sp, self.ep)) > 1:
            raise ValueError(
                "tensor_parallel, sequence_parallel, and expert_parallel "
                "are mutually exclusive for serving")
        if self.sp > 1:
            if self.model.apply_sp is None:
                raise ValueError(
                    f"model {model_cfg.name!r} has no apply_sp; "
                    "sequence_parallel > 1 needs an SP-aware family "
                    "(e.g. longseq_encoder)")
            if self.model.input_shape[0] % self.sp:
                raise ValueError(
                    f"sequence {self.model.input_shape[0]} not divisible "
                    f"by sequence_parallel={self.sp}")
        if self.sp > 1:
            axis2, size2 = "seq", self.sp
        elif self.ep > 1:
            axis2, size2 = "expert", self.ep
        else:
            axis2, size2 = None, tp_req
        # the first jax.devices() of a process opens the chip
        with setup_span("devices"):
            self.mesh = mesh if mesh is not None else make_mesh(
                self.sharding_cfg.data_parallel,
                size2,
                ("data", axis2) if axis2 else self.sharding_cfg.axis_names,
            )
        self.data_axis = ("data" if axis2
                          else self.sharding_cfg.axis_names[0])
        # Multi-process serving (global mesh spanning several OS
        # processes, e.g. multi-host slices): device_put of the SAME host
        # batch from every process onto a global sharding is the SPMD
        # contract jax supports natively, but fetching results needs an
        # explicit cross-process allgather — np.asarray on a
        # non-fully-addressable array raises. Certified by
        # tests/test_dist.py::test_multiprocess_serving.
        self._multiprocess = any(
            d.process_index != jax.process_index()
            for d in self.mesh.devices.flat)
        self._lock = threading.Lock()
        # Split-phase pipeline state (see dispatch/_fetch_loop). Depth 0 or
        # multi-process serving (the results fetch is a cross-process
        # COLLECTIVE that must stay ordered under the dispatch lock)
        # disable the ring and fall back to the serialized predict.
        depth = max(0, int(getattr(self.batch_cfg, "pipeline_depth", 2)))
        self.pipeline_depth = 0 if self._multiprocess else depth
        pool = int(getattr(self.batch_cfg, "staging_pool", 0)) \
            or self.pipeline_depth + 1
        self._staging = StagingPool(pool)
        self._ring: Optional[threading.Semaphore] = (
            threading.BoundedSemaphore(self.pipeline_depth)
            if self.pipeline_depth else None)
        self._fetch_q: "queue.SimpleQueue[Optional[InflightBatch]]" = \
            queue.SimpleQueue()
        self._fetch_thread: Optional[threading.Thread] = None
        self._fetch_thread_lock = threading.Lock()
        # Dispatch slots visible to the engine's queue: ring depth when
        # pipelined, else the single serialized predict slot.
        self.ring_capacity = max(1, self.pipeline_depth)
        # Device milliseconds of one step of each padded bucket: what the
        # queue's formation rule reads to tell a model whose
        # step grows with its bucket from a launch-bound one
        # (infer/continuous.py). The fetch thread keeps it, as the least
        # step seen of each compiled program (_fetch_loop); a program's
        # first run is not read (even with the compile taken out it reads
        # high: 81 ms for ViT-g/14's 35 ms 8-row step on the v5e, PERF.md
        # PR 26), so a bucket has no entry until traffic has used it once.
        self.step_ms: Dict[int, float] = {}
        # Watchdog / quarantine state (batch.watchdog_ms, watchdog_trips):
        # consecutive fetch-deadline trips counted on the fetch thread via
        # the handle's on_done hook; at the threshold the engine flips to
        # quarantined (dispatch raises EngineQuarantined) and fires
        # on_quarantine exactly once so the operator can swap a fresh one.
        self.quarantined = False
        self.on_quarantine = None
        self._watchdog_trips = 0
        self._watchdog_lock = threading.Lock()

        params, state = load_or_init(self.model, model_cfg.checkpoint, model_cfg.seed)
        if self.ep > 1:
            # Fail loudly on misconfig — silent full replication across an
            # expert mesh would burn ep-fold HBM/compute while the user
            # believes experts are sharded. Same key set as
            # shard_params_ep (one source of truth: moe_param_specs).
            from storm_tpu.parallel.moe import moe_param_specs

            expert_keys = {
                k for k, spec in moe_param_specs().items()
                if "expert" in (spec or ())
            }
            expert_dims = []
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                    params)[0]:
                keys = [getattr(k, "key", None) for k in path]
                if "moe" in keys and keys[-1] in expert_keys:
                    expert_dims.append(leaf.shape[0])
            if not expert_dims:
                raise ValueError(
                    f"model {model_cfg.name!r} has no MoE params; "
                    "expert_parallel > 1 needs an MoE family "
                    "(e.g. moe_vit_tiny)")
            if any(e % self.ep for e in expert_dims):
                raise ValueError(
                    f"n_experts {set(expert_dims)} not divisible by "
                    f"expert_parallel={self.ep}")
        cast = lambda t: jax.tree.map(
            lambda a: a.astype(self.dtype) if a.dtype == jnp.float32 else a, t
        )
        # Param placement: replicate on a pure-DP mesh; Megatron-style TP
        # shard when the mesh has a non-trivial model axis. This is what the
        # reference structurally cannot do — its model is one opaque blob
        # per bolt (InferenceBolt.java:57), so a model that doesn't fit one
        # device cannot be served; here `tensor_parallel > 1` splits the
        # attention/MLP kernels across the model axis and XLA inserts the
        # ICI psum on the row-parallel matmuls.
        self.model_axis = (
            self.sharding_cfg.axis_names[1]
            if len(self.sharding_cfg.axis_names) > 1 else "model")
        self.tp = int(self.mesh.shape.get(self.model_axis, 1))
        if self.tp > 1:
            place_params = lambda t: shard_params_tp(
                self.mesh, t, self.model_axis)
        elif self.ep > 1:
            place_params = lambda t: shard_params_ep(self.mesh, t, "expert")
        else:
            place_params = lambda t: jax.device_put(t, replicated(self.mesh))
        # Cross-process placement only accepts HOST buffers (each process
        # supplies the same value and jax takes its local shards); a
        # committed single-device jax array would demand a cross-host
        # device transfer the backend refuses. Init/orbax hand us
        # committed arrays, so materialize to numpy first.
        _hostify = (lambda t: jax.tree.map(
            lambda a: np.asarray(a) if hasattr(a, "dtype") else a, t)
        ) if self._multiprocess else (lambda t: t)
        if self._multiprocess:
            _inner_place = place_params
            place_params = lambda t: _inner_place(_hostify(t))
        # BN statistics stay f32 (cast only f32 leaves to compute dtype would
        # nuke them too) — so cast params only; state is small and stays f32.
        self._w8 = getattr(model_cfg, "weights", "float") in (
            "int8", "int8_fused")
        self._w8_fused = getattr(model_cfg, "weights", "float") == "int8_fused"
        with setup_span("parameters.serve") as span:
            self._place(params, state, cast, place_params, _hostify)
            span.attrs["bytes"] = self.param_bytes()
        # jit must pin params to their committed placement (replicated OR
        # TP-sharded) — read the shardings off the placed arrays so both
        # paths share one code path.
        p_shardings = jax.tree.map(lambda a: a.sharding, self.params)

        apply = self.model.apply
        apply_sp = self.model.apply_sp
        out_shard = batch_sharding(self.mesh, self.data_axis)
        if self.sp > 1:
            # inputs (N, S, ...): batch over data, sequence over seq
            x_shard = NamedSharding(self.mesh, P(self.data_axis, "seq"))
        else:
            x_shard = out_shard
        dtype = self.dtype
        w8 = self._w8
        w8_fused = self._w8_fused
        sp = self.sp
        mesh_ref = self.mesh

        # Which form each op's shape rule chose (ops/platform.py
        # ``dispatch_notes``), by padded batch. Observation only: written as
        # a bucket's program is traced (once a bucket, jit keeps the trace),
        # read by :func:`engine_inventory`; nothing in the program reads it.
        forms = self.program_forms = {}
        # A model that counts on the device (tokens per expert) carries an
        # ``"aux"`` entry in its state, in and out: its program then returns
        # ``(predictions, aux)`` and the fetch thread brings both over. Any
        # other model's program returns the predictions alone, as ever.
        has_aux = self._has_aux = isinstance(state, dict) and "aux" in state

        def fwd(params, state, x):
            if w8:
                params = dequantize_params(params, dtype, keep_dense=w8_fused)
            with dispatch_notes() as seen:
                if sp > 1:
                    logits, new_state = apply_sp(params, state, x, mesh_ref,
                                                 "seq", train=False)
                else:
                    logits, new_state = apply(params, state, x, train=False)
            forms[x.shape[0]] = ", ".join(seen)
            with jax.named_scope(HEAD):  # a part's name in a device trace
                logits = logits.astype(jnp.float32)
                out = jax.nn.softmax(logits, axis=-1) if softmax else logits
                # several prediction heads (models/scorer.py): a softmax a
                # head, laid end to end in the one row a record's answer is
                out = out.reshape(out.shape[0], -1)
            return (out, new_state["aux"]) if has_aux else out

        out_shardings = ((out_shard, replicated(self.mesh)) if has_aux
                         else out_shard)
        self._fwd = jax.jit(
            fwd,
            in_shardings=(p_shardings, replicated(self.mesh), x_shard),
            out_shardings=out_shardings,
        )
        # uint8 transfer path: the wire carries affine-quantized bytes plus a
        # per-batch (scale, offset); dequantization runs on device inside the
        # same jit program, so XLA fuses it into the first conv/matmul's input.
        self._quantize = model_cfg.transfer_dtype == "uint8"

        def fwd_q(params, state, xq, scale, offset):
            x = (xq.astype(jnp.float32) * scale + offset).astype(dtype)
            return fwd(params, state, x)

        self._fwd_q = jax.jit(
            fwd_q,
            in_shardings=(
                p_shardings,
                replicated(self.mesh),
                x_shard,
                replicated(self.mesh),
                replicated(self.mesh),
            ),
            out_shardings=out_shardings,
        )
        self._x_sharding = x_shard
        self._scalar_sharding = replicated(self.mesh)
        self.compiled_batches: set = set()
        # Observability hook: called as ``on_compile(padded_batch, ms)``
        # the first time a bucket shape executes (= XLA compile on the hot
        # path). The inference operator wires it to the flight recorder.
        self.on_compile = None
        # Cost-profile identity: which curve this engine's batches feed in
        # the process ProfileStore.
        self.profile_key = profile_key_of(model_cfg)
        # The ``engine.build`` span that made this engine (``shared_engine``;
        # None for one built directly): its warm-up's spans stand under it.
        self.build_span = None
        # numbers this engine's dispatches in the step log
        self._step_count = itertools.count()

    def _place(self, params, state, cast, place_params, hostify) -> None:
        """The loaded tree as this engine serves it, on the mesh: cast to
        the compute type or quantised, arranged (:meth:`_served`) and
        placed; the state beside it, replicated."""
        if self._w8:
            # int8 weights + scales live in HBM; dequant happens inside the
            # jit program (fused), so the stored footprint is ~1/2 of bf16.
            # Non-quantized leaves (biases, norm params) still get the
            # compute-dtype cast — an f32 bias-add would promote every
            # downstream activation to f32 and defeat w8a16.
            qtree = jax.tree.map(
                lambda l: l if _is_qleaf(l) else (
                    l.astype(self.dtype) if l.dtype == jnp.float32 else l),
                quantize_params(params), is_leaf=_is_qleaf,
            )
            self.params = place_params(qtree)
        else:
            params = cast(params)  # the float32 tree goes before another is made
            self.params = place_params(self._served(params))
        self.state = jax.device_put(hostify(state), replicated(self.mesh))

    def _served(self, params):
        """The model's own arrangement of its float tree for the steps this
        engine runs (``ModelDef.serve_params``: where a ViT's largest bucket
        is a long step, the blocks stacked beside the list, so that its
        program is one scanned block), made once, here, at load. An engine
        that splits the tree over a tensor- or expert-parallel axis places
        it by the loaded tree's paths (``parallel/sharding.py``) and keeps
        that tree, as one that serves int8 leaves does; ``apply`` takes any."""
        maker = self.model.serve_params
        if maker is None or self.tp > 1 or self.ep > 1:
            return params
        return maker(params, self.pad_batch(self.batch_cfg.max_batch))

    # ---- occupancy telemetry (storm_tpu/obs) ---------------------------------

    @property
    def ring_inflight(self) -> int:
        """Pipeline-ring slots currently occupied by in-flight batches.
        Reads the semaphore's internal counter — telemetry only (the
        value can be a step stale; the ring itself stays the bound)."""
        if self._ring is None:
            return 0
        return max(0, self.pipeline_depth - self._ring._value)

    def staging_stats(self) -> Dict[str, int]:
        return self._staging.stats()

    # ---- memory accounting ---------------------------------------------------

    def param_bytes(self) -> int:
        """Device bytes held by this engine's params+state (per replica).
        The multi-model co-residency budget (BASELINE config 5) is the sum
        of these across live engines — see :func:`engine_inventory`."""
        return sum(
            x.nbytes for t in (self.params, self.state)
            for x in jax.tree.leaves(t) if hasattr(x, "nbytes")
        )

    def param_bytes_per_device(self) -> int:
        """Largest per-device slice of params+state actually resident in
        HBM. Pure DP: equals :meth:`param_bytes` (full replica everywhere).
        TP: the sharded kernels contribute ~1/tp each, so a model bigger
        than one chip's HBM fits when ``param_bytes_per_device`` does."""
        per: Dict[int, int] = {}
        for t in (self.params, self.state):
            for x in jax.tree.leaves(t):
                for s in getattr(x, "addressable_shards", ()):
                    did = s.device.id
                    per[did] = per.get(did, 0) + s.data.nbytes
        return max(per.values(), default=0)

    # ---- shape management ----------------------------------------------------

    @property
    def input_shape(self) -> Tuple[int, ...]:
        return tuple(self.model.input_shape)

    def pad_batch(self, n: int) -> int:
        """Pad a batch size to the compiled-bucket grid, respecting the mesh:
        every bucket must divide evenly across the data axis. Oversized
        batches (a single record larger than max_batch) round up to the
        next dp multiple instead of crashing — they just compile one extra
        shape."""
        dp = self.mesh.shape[self.data_axis]
        b = self.batch_cfg.bucket_for(n)
        if b < n:
            b = n
        return max(dp, ((b + dp - 1) // dp) * dp)

    def warmup(self, buckets: Optional[Tuple[int, ...]] = None) -> None:
        """Pre-compile the bucket shapes so first traffic doesn't hit XLA
        compile latency (batch formation reads measured step times)."""
        for b in buckets or self.batch_cfg.buckets:
            n = self.pad_batch(b)
            if n in self.compiled_batches:
                continue
            with setup_span("warmup.bucket", under=self.build_span,
                            bucket=b, padded=n):
                x = np.zeros((n, *self.input_shape), self.in_dtype)
                np.asarray(self.predict(x))
        if any(self.program_forms.values()):
            logger.info("engine %s programs by bucket: %s", self.model_cfg.name,
                        "; ".join(f"{b}: {f}" for b, f
                                  in sorted(self.program_forms.items())))

    # ---- the hot call --------------------------------------------------------

    def predict(self, x: np.ndarray) -> np.ndarray:
        """Blocking batched forward: pad -> device -> fwd -> host.

        Called from a worker thread (asyncio.to_thread) so the event loop
        keeps batching while the device computes. Thread-safe. With the
        split-phase pipeline enabled this is one ``dispatch`` + wait; with
        ``pipeline_depth=0`` (or multi-process serving) it is the fully
        serialized stage/put/fwd/fetch chain.
        """
        if self._ring is None:
            return self._predict_serial(x)
        return self.dispatch((x,)).future.result()

    def dispatch(self, parts: Sequence[np.ndarray],
                 queued: Optional[dict] = None) -> InflightBatch:
        """Split-phase entry: stage ``parts`` (per-record arrays, already
        shape-validated) into a pooled staging buffer with one fused
        write, ship it to the device and launch the jit program
        asynchronously; the blocking results fetch happens on the
        engine's dedicated fetch thread in dispatch order. Returns an
        :class:`InflightBatch` immediately — its future resolves to the
        host result (or the exception that failed THIS batch only).

        Blocking (bounded): when ``pipeline_depth`` batches are already
        in flight the call parks on the ring until a fetch completes, so
        call it from a worker thread, never the event loop. With the
        pipeline disabled it degrades to the serialized predict wrapped
        in an already-resolved handle.

        ``queued``: the moments the caller's queue took before the cut
        (``obs/profile.py new_step_row``), for the step log; they are on the handle
        before the batch can be ready.
        """
        if self.quarantined:
            raise EngineQuarantined(
                f"engine {self.model_cfg.name!r} is quarantined after "
                f"{self._watchdog_trips} consecutive watchdog trips")
        n = sum(int(p.shape[0]) for p in parts)
        handle = InflightBatch(n, self.pad_batch(n))
        handle.profile_key = self.profile_key
        if _profile_sink is not None:
            handle.step = new_step_row(next(self._step_count),
                                       self.profile_key, handle.padded, n,
                                       queued)
        wd = float(getattr(self.batch_cfg, "watchdog_ms", 0.0) or 0.0)
        if wd > 0:
            handle.watchdog_ms = wd
            handle.on_done = self._watchdog_note
        if self._ring is None:
            x = parts[0] if len(parts) == 1 else np.concatenate(parts)
            try:
                handle.future.set_result(self._predict_serial(x))
            except BaseException as e:  # noqa: BLE001 - fail ONLY this batch
                handle.future.set_exception(e)
            return handle
        self._ensure_fetch_thread()
        self._ring.acquire()
        try:
            self._dispatch_phase(handle, parts)
        except BaseException as e:  # noqa: BLE001 - fail ONLY this batch
            buf, handle._buf = handle._buf, None
            if buf is not None:
                self._staging.release(buf)
            self._ring.release()
            handle.future.set_exception(e)
            return handle
        self._fetch_q.put(handle)
        return handle

    def _stage(self, buf: np.ndarray, parts: Sequence[np.ndarray],
               n: int) -> None:
        """The ONE host-side write of the dispatch phase: copy each part
        into the preallocated padded buffer (casting to the buffer dtype
        as it lands) and zero the padding rows — fusing what the stacked
        path did in three full-batch copies (concat, pad-concat, astype)."""
        ofs = 0
        for p in parts:
            k = p.shape[0]
            buf[ofs:ofs + k] = p
            ofs += k
        if ofs < buf.shape[0]:
            buf[ofs:] = 0

    def _program_span(self, padded: int, step: Optional[dict] = None):
        """The ``program`` span of a cold bucket's first dispatch: stage,
        put, trace, lowering, cache look-up or compile, and launch. Its
        milliseconds are what ``on_compile`` and the profile's compile table
        are handed."""
        return setup_span("program", padded=padded, engine=self.profile_key,
                          step=step["step"] if step else None)

    def _stage_and_launch(self, handle: InflightBatch,
                          parts: Sequence[np.ndarray], cold: bool):
        """Stage ``parts`` into a pooled buffer, put it and launch the
        bucket's program: ``(buffer, when it was staged, the program's
        result)``."""
        padded, n = handle.padded, handle.n
        if self._quantize:
            # Stage at full precision first (range must come from the real
            # rows), then affine-quantize IN PLACE in the f32 buffer and
            # cast once into the uint8 wire buffer — no temporaries beyond
            # the two pooled buffers. The f32 buffer never reaches jax, so
            # it recycles immediately; the uint8 one is held until fetch.
            f32 = self._staging.acquire((padded, *self.input_shape),
                                        np.float32)
            try:
                self._stage(f32, parts, n)
                lo = float(f32[:n].min())
                hi = float(f32[:n].max())
                scale = np.float32(max((hi - lo) / 255.0, 1e-12))
                offset = np.float32(lo)
                buf = self._staging.acquire((padded, *self.input_shape),
                                            np.uint8)
                handle._buf = buf
                np.subtract(f32, offset, out=f32)
                np.divide(f32, scale, out=f32)
                np.rint(f32, out=f32)
                np.clip(f32, 0, 255, out=f32)
                np.copyto(buf, f32, casting="unsafe")
            finally:
                self._staging.release(f32)
            # Copy ledger: quantized staging is two full-batch passes —
            # the fused f32 stage write plus the uint8 cast into the
            # wire buffer (the in-place affine passes rewrite the same
            # f32 bytes; they are not counted as extra copies).
            _copyledger.record("staging", f32.nbytes + buf.nbytes,
                               copies=2, records=n,
                               engine=self.profile_key or "-")
            t_put = time.perf_counter()
            handle._t_put = 0.0 if cold else t_put
            with self._lock:
                xd = jax.device_put(buf, self._x_sharding)
                out = self._fwd_q(self.params, self.state, xd, scale, offset)
        else:
            buf = self._staging.acquire((padded, *self.input_shape),
                                        self.in_dtype)
            handle._buf = buf
            self._stage(buf, parts, n)
            # Copy ledger: the ONE fused host-side write of the
            # dispatch phase (pad + cast into the pooled buffer).
            _copyledger.record("staging", buf.nbytes, copies=1,
                               records=n, engine=self.profile_key or "-")
            t_put = time.perf_counter()
            handle._t_put = 0.0 if cold else t_put
            with self._lock:
                xd = jax.device_put(buf, self._x_sharding)
                out = self._fwd(self.params, self.state, xd)
        return buf, t_put, out

    def _dispatch_phase(self, handle: InflightBatch,
                        parts: Sequence[np.ndarray]) -> None:
        t0 = time.perf_counter()
        padded, n = handle.padded, handle.n
        cold = padded not in self.compiled_batches
        if cold:
            # the cliff with its cause: a row of the set-up log, under the
            # warm-up's bucket or, where traffic met the bucket cold, a root
            with self._program_span(padded, handle.step) as span:
                buf, t_put, out = self._stage_and_launch(handle, parts, cold)
                span.attrs["form"] = self.program_forms.get(padded)
        else:
            buf, t_put, out = self._stage_and_launch(handle, parts, cold)
        t1 = time.perf_counter()
        if handle.step is not None:
            wall = time.time()
            handle.step["t_staged"] = t_put + (wall - t1)
            handle.step["t_launched"] = wall
        # Copy ledger: host->device transfer of the staged buffer (a CPU
        # backend may alias instead of copying, but the bytes handed to
        # device_put are the same either way). Recorded after t1 so the
        # hook never leaks into the h2d_ms timing it sits beside.
        _copyledger.record("h2d", buf.nbytes, copies=1, records=n,
                           engine=self.profile_key or "-")
        self.compiled_batches.add(padded)
        if cold:
            self._report_cold(padded, span.ms)
        if self._has_aux:
            out, handle.aux = out
        hold = self._chaos_hang_s()
        if hold > 0:
            out = _HangingResult(out, time.monotonic() + hold)
        handle._out = out
        handle._t_launched = t1
        # Staging + H2D + async launch (plus XLA compile when cold — the
        # on_compile event disambiguates the cliff in a post-mortem).
        handle.timings["h2d_ms"] = (t1 - t0) * 1e3

    @staticmethod
    def _chaos_hang_s() -> float:
        """One-shot engine-hang injection (chaos control RPC); 0 when the
        injector is unarmed — the common case pays one global read."""
        from storm_tpu.resilience.chaos import get_injector

        return get_injector().engine_hang_s()

    def _watchdog_note(self, exc) -> None:
        """Fetch-thread callback (InflightBatch.on_done): count
        CONSECUTIVE watchdog trips; at ``batch.watchdog_trips`` flip to
        quarantined exactly once, fire ``on_quarantine`` (the operator's
        replacement hook) and evict this engine from the shared cache so
        the next ``shared_engine`` call builds a fresh one."""
        if not isinstance(exc, EngineWatchdogTimeout):
            # A hung batch that eventually lands still reports success
            # here — keep the trip count once quarantined so the
            # fail-fast message names the real streak.
            if exc is None and not self.quarantined:
                with self._watchdog_lock:
                    self._watchdog_trips = 0
            return
        limit = int(getattr(self.batch_cfg, "watchdog_trips", 0) or 0)
        with self._watchdog_lock:
            self._watchdog_trips += 1
            trips = self._watchdog_trips
            if limit <= 0 or trips < limit or self.quarantined:
                return
            self.quarantined = True
        logger.error(
            "engine %s QUARANTINED after %d consecutive watchdog trips "
            "(watchdog_ms=%g); dispatch now refuses batches until a "
            "replacement is swapped in",
            self.model_cfg.name, trips, getattr(self.batch_cfg,
                                                "watchdog_ms", 0.0))
        # Evict BEFORE the replacement hook: the hook rebuilds via
        # shared_engine off-thread, and a cache hit on the still-cached
        # quarantined engine would "swap in" the dead engine forever.
        try:
            unload_engine(self)
        except Exception:
            logger.exception("evicting quarantined engine failed")
        cb = self.on_quarantine
        if cb is not None:
            try:
                cb(trips)
            except Exception:
                logger.exception("on_quarantine hook failed")

    def _ensure_fetch_thread(self) -> None:
        if self._fetch_thread is not None:
            return
        with self._fetch_thread_lock:
            if self._fetch_thread is None:
                # The thread must NOT hold the engine (not even via a bound
                # method): cache eviction (set_engine_cache_limit) detects
                # orphaned engines by refcount, and a long-lived thread
                # reference would pin every engine that ever dispatched.
                # It gets only the queue/ring/pool — none of which hold
                # params — and a finalizer stops it when the engine dies.
                t = threading.Thread(
                    target=_fetch_loop,
                    args=(self._fetch_q, self._ring, self._staging,
                          self.step_ms),
                    daemon=True,
                    name=f"storm-tpu-fetch-{self.model_cfg.name}")
                t.start()
                self._fetch_thread = t
                weakref.finalize(self, self._fetch_q.put, None)

    # _fetch_loop is module-level (see _ensure_fetch_thread for why).

    def _predict_serial(self, x: np.ndarray) -> np.ndarray:
        """The pre-pipeline serialized chain (pad -> cast -> device_put ->
        fwd -> fetch, one batch at a time). Kept as the ``pipeline_depth=0``
        escape hatch and as the multi-process path — the cross-process
        allgather is a collective whose issue order the dispatch lock must
        cover end to end (see :meth:`_gather_locked`)."""
        n = x.shape[0]
        padded = self.pad_batch(n)
        if padded in self.compiled_batches:
            out, gathered = self._serial_step(x, n, padded)
        else:
            with self._program_span(padded) as span:
                out, gathered = self._serial_step(x, n, padded)
                span.attrs["form"] = self.program_forms.get(padded)
            self._report_cold(padded, span.ms)
        if gathered is None:
            # single-process: the host fetch happens OUTSIDE the lock so
            # one batch's device->host RTT doesn't serialize the next
            # batch's dispatch
            gathered = np.asarray(out)
        return gathered[:n]

    def _serial_step(self, x: np.ndarray, n: int, padded: int):
        """Pad, cast, put and run ``x``: ``(the program's result, what the
        processes gathered of it or None)``."""
        if self._quantize:
            # Range from the real rows only (padding would drag lo to 0).
            lo = float(x.min())
            hi = float(x.max())
            scale = np.float32(max((hi - lo) / 255.0, 1e-12))
            offset = np.float32(lo)
        if padded != n:
            x = np.concatenate([x, np.zeros((padded - n, *x.shape[1:]), x.dtype)])
        if self._quantize:
            xw = np.clip(np.rint((x - offset) / scale), 0, 255).astype(np.uint8)
            with self._lock:
                xd = jax.device_put(xw, self._x_sharding)
                out = self._fwd_q(self.params, self.state, xd, scale, offset)
                if self._has_aux:
                    out = out[0]
                gathered = self._gather_locked(out)
        else:
            # Cast on the HOST (ml_dtypes gives numpy a bfloat16) so the
            # host->device transfer ships half the bytes of f32.
            if x.dtype != self.in_dtype:
                x = x.astype(self.in_dtype)
            with self._lock:
                xd = jax.device_put(x, self._x_sharding)
                out = self._fwd(self.params, self.state, xd)
                if self._has_aux:  # the serialized path reports none
                    out = out[0]
                gathered = self._gather_locked(out)
        self.compiled_batches.add(padded)
        return out, gathered

    def _report_cold(self, padded: int, ms: float) -> None:
        """A cold bucket's first dispatch took ``ms`` (its ``program``
        span's): to the profile's compile table and the operator's hook."""
        _report_compile(self.profile_key, padded, ms)
        if self.on_compile is not None:
            try:
                self.on_compile(padded, ms)
            except Exception:
                pass  # an observability hook must never fail a batch

    def _gather_locked(self, out) -> "Optional[np.ndarray]":
        """Multi-process results fetch — a cross-process COLLECTIVE
        (process_allgather), so it must stay under the dispatch lock:
        every process has to issue its device_put/forward/gather sequence
        in one consistent order, and the lock serializes this process's
        side of that contract. The other half is the caller's: in
        multi-process serving every process feeds identical batches in
        identical order (one operator task per process — see
        tests/mh_serve_worker.py; concurrent tasks could still interleave
        lock ACQUISITION differently across processes). Returns None in
        single-process mode (fetch happens outside the lock)."""
        if not self._multiprocess:
            return None
        from jax.experimental import multihost_utils

        return multihost_utils.process_allgather(out, tiled=True)


# ---- engine sharing across operator tasks ------------------------------------

_ENGINES: "OrderedDict[tuple, InferenceEngine]" = OrderedDict()
_ENGINES_LOCK = threading.Lock()
# key -> in-progress build; concurrent shared_engine calls for the same key
# wait on it instead of each allocating a full duplicate param copy.
_BUILDS: Dict[tuple, Future] = {}
# Optional hard cap on total cached param bytes; None = cap at 85% of the
# device HBM limit when known (the threshold round 1 only warned about).
# Eviction only ever drops engines nothing outside the cache references,
# so a cap can never force a live engine to be rebuilt as a duplicate.
_ENGINE_CACHE_LIMIT: Optional[int] = None
# Auxiliary engines (round 20): non-classify engines — the decode tier's
# DecodeEngine above all — register here (weakly) so the observatory's
# occupancy sweep enumerates them alongside the classify cache without
# this module importing their packages. They manage their own lifecycle;
# the cache's HBM cap and eviction never touch them.
_AUX_ENGINES: "weakref.WeakSet" = weakref.WeakSet()


def register_aux_engine(engine) -> None:
    """Surface an externally-owned engine through :func:`live_engines`
    (weak — dropping the last strong ref unregisters it)."""
    with _ENGINES_LOCK:
        _AUX_ENGINES.add(engine)


def _freeze(v):
    """Hashable deep-freeze for cache keys (TOML arrays arrive as lists)."""
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    return v


def shared_engine(
    model_cfg: ModelConfig,
    sharding_cfg: Optional[ShardingConfig] = None,
    batch_cfg: Optional[BatchConfig] = None,
) -> InferenceEngine:
    """One engine per (model, dtype, shape, mesh) per process: operator tasks
    share params in HBM instead of the reference's per-replica model copies
    (InferenceBolt.java:57-58 + per-bolt Model boxes in the diagram)."""
    key = (
        model_cfg.name,
        model_cfg.dtype,
        model_cfg.transfer_dtype,
        tuple(model_cfg.input_shape),
        model_cfg.num_classes,
        model_cfg.checkpoint,
        model_cfg.seed,
        getattr(model_cfg, "weights", "float"),
        # builder kwargs are part of the model identity (width=0.5 vs 1.0
        # must not share one cached engine); deep-freeze so TOML-sourced
        # list values stay hashable
        _freeze(getattr(model_cfg, "extra", {})),
        (sharding_cfg.data_parallel, sharding_cfg.tensor_parallel,
         getattr(sharding_cfg, "sequence_parallel", 1),
         getattr(sharding_cfg, "expert_parallel", 1))
        if sharding_cfg
        else None,
        # Batch policy is part of the identity: pad_batch/warmup read the
        # engine's buckets, so two operators with different batching must
        # not share one engine.
        (batch_cfg.max_batch, tuple(batch_cfg.buckets),
         getattr(batch_cfg, "pipeline_depth", 2),
         getattr(batch_cfg, "staging_pool", 0)) if batch_cfg else None,
    )
    with _ENGINES_LOCK:
        if key in _ENGINES:
            _ENGINES.move_to_end(key)  # LRU: most-recently-used last
            return _ENGINES[key]
        fut = _BUILDS.get(key)
        owner = fut is None
        if owner:
            fut = Future()
            _BUILDS[key] = fut
    if not owner:
        # Another thread owns the build: wait for its result instead of
        # allocating a duplicate param copy — N bolt tasks swapping the
        # same model concurrently must cost ONE build (param HBM +
        # compile), not N. The owner's finally below guarantees this
        # future resolves (value or exception) — no unbounded hang.
        return fut.result()
    # We own the build. Build OUTSIDE the lock: compile can take tens of
    # seconds and the UI thread polls engine_inventory under this lock.
    # The try starts IMMEDIATELY after registration so an async exception
    # (KeyboardInterrupt) landing anywhere before completion still pops
    # the _BUILDS entry and resolves the future — a stale entry would
    # serve a phantom engine forever; an unresolved future would hang
    # waiters (no timeout) permanently.
    engine = None
    try:
        with setup_span("engine.build",
                        engine=profile_key_of(model_cfg)) as span:
            engine = InferenceEngine(model_cfg, sharding_cfg, batch_cfg)
        engine.build_span = span
        if _insert_would_exceed_budget(engine):
            # Collect BEFORE taking the lock: an engine held only by a
            # reference cycle (e.g. a completed swap's rollback closure)
            # looks externally-referenced to the refcount probe until the
            # cycle collector runs. gc.collect() under _ENGINES_LOCK would
            # stall every cache reader for a full-heap pass AND can
            # deadlock — finalizers may re-enter the cache (unload_engine,
            # inventory), and the lock is not reentrant.
            gc.collect()
        with _ENGINES_LOCK:
            _ENGINES[key] = engine
            try:
                _evict_to_budget_locked(keep=key)
                _log_hbm_inventory()
            except Exception:
                # Bookkeeping only: the engine is built and cached —
                # neither the owner nor the waiters should fail because
                # eviction or the inventory log hiccuped.
                logger.exception("engine cache bookkeeping failed")
    finally:
        with _ENGINES_LOCK:
            _BUILDS.pop(key, None)
        if engine is not None:
            fut.set_result(engine)
        else:
            exc = sys.exc_info()[1]
            fut.set_exception(
                exc
                if exc is not None
                else RuntimeError("engine build aborted before completion")
            )
    return engine


class NullEngine:
    """Device-free engine: ``predict`` returns a uniform distribution
    instantly. Plugs into ``InferenceBolt(engine=NullEngine(...))`` to
    measure the FRAMEWORK's share of the Kafka->Kafka path — broker
    queueing, spout fetch/decode, batching, executor hops, encode,
    produce — with device time pinned to zero (the framework's share
    alone; not measured by the benchmark).

    Not a mock of the full InferenceEngine surface — just the protocol the
    operator uses: ``input_shape``, ``warmup``, ``predict``,
    ``dispatch``."""

    def __init__(self, input_shape: Tuple[int, ...], num_classes: int) -> None:
        self.input_shape = tuple(input_shape)
        self.num_classes = int(num_classes)
        self.ring_capacity = 1

    def warmup(self, buckets=None) -> None:  # no device, nothing to compile
        pass

    def predict(self, x: np.ndarray) -> np.ndarray:
        n = x.shape[0]
        return np.full((n, self.num_classes), 1.0 / self.num_classes,
                       np.float32)

    def dispatch(self, parts: Sequence[np.ndarray]) -> InflightBatch:
        # Already-resolved handle with zeroed phase timings: the stage
        # table then shows the framework path with h2d/compute/d2h ~0,
        # same as device_ms under predict.
        n = sum(int(p.shape[0]) for p in parts)
        handle = InflightBatch(n, n)
        handle.timings = {"h2d_ms": 0.0, "compute_ms": 0.0, "d2h_ms": 0.0}
        handle.future.set_result(
            np.full((n, self.num_classes), 1.0 / self.num_classes,
                    np.float32))
        return handle


def unload_engine(engine: InferenceEngine) -> bool:
    """Drop ``engine`` from the process cache so its HBM can be reclaimed
    once no bolt references it (live model swaps otherwise accumulate
    rollback engines forever). Returns True if it was cached."""
    with _ENGINES_LOCK:
        for k, e in list(_ENGINES.items()):
            if e is engine:
                del _ENGINES[k]
                return True
    return False


def set_engine_cache_limit(max_param_bytes: Optional[int]) -> None:
    """Cap total cached engine param bytes; least-recently-used engines are
    dropped from the cache on the next ``shared_engine`` insert. ``None``
    restores the default (85% of device HBM when the backend reports it).

    **Best-effort semantics**: only *orphaned* engines (no references
    outside the cache) are evicted — dropping one a bolt still serves from
    would free nothing and force a duplicate build. Orphan detection is
    refcount-based (CPython only; elsewhere nothing is ever evicted), so an
    engine pinned by a reference *cycle* stays resident until the cycle
    collector runs — eviction triggers ``gc.collect()`` first when over
    budget to break such cycles. Degradation is always in the safe
    direction (keep, never double-free), but the cap is a target, not a
    hard bound."""
    global _ENGINE_CACHE_LIMIT
    with _ENGINES_LOCK:
        _ENGINE_CACHE_LIMIT = max_param_bytes


def _refs_of_value(d: dict, k) -> int:
    """getrefcount of ``d[k]`` through one fixed call shape, so the
    internal-reference overhead is identical between the calibration probe
    and the real check (CPython's calling convention changed this count
    between 3.10 and 3.11 — never hard-code it)."""
    return sys.getrefcount(d[k])


_REF_BASELINE: Optional[int] = None


def _ref_baseline() -> int:
    """Refcount of an object whose ONLY reference is a dict value, measured
    through :func:`_refs_of_value` at runtime on this interpreter."""
    global _REF_BASELINE
    if _REF_BASELINE is None:
        _REF_BASELINE = _refs_of_value({0: object()}, 0)
    return _REF_BASELINE


def _externally_referenced(k: tuple) -> bool:
    """Best-effort: does anything OUTSIDE the cache still hold ``_ENGINES[k]``?
    Non-CPython lacks refcount semantics — treat everything as referenced
    (never evict; degrades to round 1's warn-only behavior, which is safe)."""
    try:
        return _refs_of_value(_ENGINES, k) > _ref_baseline()
    except Exception:  # pragma: no cover - non-CPython
        return True


def _cache_limit() -> Optional[int]:
    limit = _ENGINE_CACHE_LIMIT
    if limit is None:
        hbm = _device_hbm_limit()
        limit = int(0.85 * hbm) if hbm else None
    return limit


def _insert_would_exceed_budget(engine: "InferenceEngine") -> bool:
    """Brief-lock budget probe used to decide whether to gc.collect()
    before inserting ``engine`` (the collect itself must run unlocked)."""
    limit = _cache_limit()
    if limit is None:
        return False
    with _ENGINES_LOCK:
        total = sum(e.param_bytes_per_device() for e in _ENGINES.values())
    return total + engine.param_bytes_per_device() > limit


def _evict_to_budget_locked(keep: tuple) -> None:
    limit = _cache_limit()
    if limit is None:
        return
    # Per-DEVICE bytes: the budget is one chip's HBM, and TP-sharded
    # engines only hold ~1/tp of their params on each device — counting
    # global bytes would evict orphans that actually fit.
    total = sum(e.param_bytes_per_device() for e in _ENGINES.values())
    for k in list(_ENGINES):  # oldest first
        if total <= limit:
            break
        if k == keep:  # never evict the engine being handed out
            continue
        if _externally_referenced(k):
            # A bolt still serves from it: evicting would free nothing AND
            # make the next lookup build a duplicate param copy — worse HBM
            # pressure than doing nothing. Only orphans (e.g. rollback
            # engines left behind by completed model swaps) are dropped.
            continue
        e = _ENGINES.pop(k)
        per_dev = e.param_bytes_per_device()
        total -= per_dev
        logger.info(
            "evicted orphaned LRU engine %s (%.1fMB/device) from cache "
            "(budget %.1fMB)",
            e.model_cfg.name, per_dev / 1e6, limit / 1e6)
        del e  # drop the last reference -> HBM reclaimed


def live_engines() -> list:
    """Strong refs to every cached engine (observatory occupancy sweep:
    ring/staging state lives on the engine objects, not in
    :func:`engine_inventory`'s attribution rows)."""
    with _ENGINES_LOCK:
        return list(_ENGINES.values()) + list(_AUX_ENGINES)


def engine_inventory() -> dict:
    """Live engines in this process and their per-replica HBM param
    footprints — the multi-model co-residency budget (BASELINE config 5;
    engines accumulate across pipelines and live model swaps)."""
    with _ENGINES_LOCK:
        engines = list(_ENGINES.values())
    rows = [
        {
            "model": e.model_cfg.name,
            # Distinguishes cascade tiers / swap variants that share a
            # registry name but serve different weights.
            "checkpoint": getattr(e.model_cfg, "checkpoint", None) or None,
            "weights": getattr(e.model_cfg, "weights", "float"),
            "dtype": str(e.dtype),
            "param_bytes": e.param_bytes(),
            # What one chip actually holds (≈ param_bytes/tp when sharded)
            # — the figure the 85% HBM warning and cache budget use.
            "param_bytes_per_device": e.param_bytes_per_device(),
            # Which kernels each compiled bucket's program was built with
            # ("attention=rows" / "attention=xla"; empty for a model whose
            # ops have no shape rule).
            "programs": {str(b): f for b, f
                         in sorted(e.program_forms.items())},
        }
        for e in engines
    ]
    return {"engines": rows,
            "total_param_bytes": sum(r["param_bytes"] for r in rows),
            "total_param_bytes_per_device": sum(
                r["param_bytes_per_device"] for r in rows)}


def _device_hbm_limit() -> Optional[int]:
    try:
        stats = jax.devices()[0].memory_stats()
        if stats:
            return stats.get("bytes_limit")
    except Exception:  # pragma: no cover - backend-dependent
        pass
    return None


def _log_hbm_inventory() -> None:
    # Called with _ENGINES_LOCK held (param_bytes only reads engine attrs).
    # Per-DEVICE bytes: the limit being compared against is one chip's HBM,
    # and TP-sharded engines hold only ~1/tp of their params per device.
    rows = [(e.model_cfg.name, e.param_bytes_per_device())
            for e in _ENGINES.values()]
    total = sum(b for _, b in rows)
    limit = _device_hbm_limit()
    detail = ", ".join(f"{n}={b / 1e6:.1f}MB" for n, b in rows)
    logger.info(
        "engine HBM inventory: %s (total %.1fMB/device)", detail, total / 1e6)
    if limit and total > 0.85 * limit:
        logger.warning(
            "co-resident engine params at %.0f%% of device memory "
            "(%.1fMB of %.1fMB per device) — multi-model HBM budget "
            "nearly exhausted",
            100 * total / limit, total / 1e6, limit / 1e6,
        )
