"""DecodeEngine: the KV-cache-backed autoregressive step kernel.

One ``predict(rows)`` call is one co-batched step over int32 ``(B, 3)``
rows ``[slot, token, pos]``:

- **decode/prefill rows** (``slot >= 0``) write their k/v into the
  leased arena slot at ``pos`` and attend causally over the slot's
  cached prefix. Within EACH layer, all rows' k/v are written BEFORE
  anyone gathers, so a prompt submitted as T same-slot rows in one
  batch prefills correctly — position i attends to positions 0..i
  written moments earlier in the same batch. Prefill is therefore not a
  separate code path: it is a decode step with more rows, and it
  co-batches with single-token steps from other sessions.
- **classify rows** (``slot == -1``) are the stateless next-char view
  (:func:`storm_tpu.models.chartiny.stateless_logits` semantics): the
  row attends only to itself at position 0 and touches no cache. This
  is what lets plain classify traffic share the decode engine's
  continuous-batcher queue.

The engine is predict-only on purpose: the continuous batcher runs it
serialized on its dispatcher thread, which makes the arena's
write-then-gather ordering trivially safe per engine replica (the
arena lock still guards the operator's event-loop lease/serialize
calls running concurrently).

**Early exit** (the cascade knob): after layer 0, rows whose interim
logits (shared head) clear ``early_exit_threshold`` max-softmax skip
the remaining layers' attention+MLP — their k/v is STILL written every
layer (from the frozen hidden) so the cache stays complete for future
steps; those entries are shallow-representation approximations, which
is the cascade trade documented in docs/ARCHITECTURE.md. Greedy argmax over
the exit logits keeps the whole thing deterministic.
"""

from __future__ import annotations

import threading
import time
from types import SimpleNamespace
from typing import Dict, Optional, Tuple

import numpy as np

from storm_tpu.models import chartiny as ct
from storm_tpu.obs import copyledger as _copyledger
from storm_tpu.obs import profile as _profile_mod
from storm_tpu.decode.kvcache import KvCacheManager

STATELESS = -1  # slot value for classify rows

__all__ = ["DecodeEngine", "shared_decode_engine", "STATELESS"]


class DecodeEngine:
    """Stateful per-step forward over a :class:`KvCacheManager` arena.

    Satisfies the continuous batcher's predict-only contract
    (``predict(x) -> (B, num_classes)``) and the observatory's
    occupancy-row contract (``profile_key``, ``model_cfg.name``,
    ``ring_inflight``/``ring_capacity``).
    """

    def __init__(self, *, seed: int = 0, blocks: int = 32,
                 max_seq: int = ct.MAX_SEQ,
                 early_exit_threshold: Optional[float] = None,
                 engine_key: str = "char_tiny@decode") -> None:
        self.params = ct.build_params(seed)
        self.seed = int(seed)
        self.kv = KvCacheManager(blocks, ct.N_LAYERS, max_seq, ct.D_MODEL,
                                 engine_key=engine_key)
        self.early_exit_threshold = early_exit_threshold
        self.profile_key = engine_key
        # Continuous-batcher queue identity + observatory naming: decode
        # submissions share this engine name, and the model registry's
        # classify view of the same weights is also "char_tiny".
        self.model_cfg = SimpleNamespace(name="char_tiny")
        self.ring_capacity = 1  # serialized predict-only engine
        self.ring_inflight = 0
        self._profile = _profile_mod.profile_store()
        self.steps = 0
        self.rows_decode = 0
        self.rows_classify = 0
        self.early_exits = 0
        self._lock = threading.Lock()  # counters only; predict serialized

    # ---- the step kernel -----------------------------------------------------

    def predict(self, x: np.ndarray) -> np.ndarray:
        """One co-batched step: ``x`` int (B, 3) rows [slot, token, pos]
        -> (B, VOCAB) next-token logits."""
        t0 = time.perf_counter()
        rows = np.asarray(x)
        if rows.ndim != 2 or rows.shape[1] != 3:
            raise ValueError(
                f"decode rows must be (B, 3) [slot, token, pos], "
                f"got {rows.shape}")
        rows = rows.astype(np.int64, copy=False)
        slots, tokens, poss = rows[:, 0], rows[:, 1], rows[:, 2]
        b = len(rows)
        cached = slots >= 0
        if np.any(poss[cached] >= self.kv.max_seq):
            raise ValueError(
                f"position {int(poss[cached].max())} exceeds kv arena "
                f"max_seq {self.kv.max_seq}")

        h = self.params["embed"][tokens] + self.params["pos"][
            np.where(cached, poss, 0)]
        # Attention window: widest prefix any row in this batch needs.
        t_max = int(poss[cached].max()) + 1 if cached.any() else 1
        # Attendability per row: cached rows see j <= pos_i over their
        # slot's prefix; stateless rows see only their own j == 0 entry.
        jj = np.arange(t_max)
        mask = np.where(cached[:, None], jj[None, :] <= poss[:, None],
                        jj[None, :] == 0)

        exit_logits = np.zeros((b, ct.VOCAB), np.float32)
        exited = np.zeros(b, bool)
        live = np.ones(b, bool)  # rows still computing full depth
        arena = self.kv.arena
        for layer in range(ct.N_LAYERS):
            # q/k/v for EVERY row — exited rows keep writing k/v from
            # their frozen hidden so their cache prefix stays complete.
            q, k, v = ct.qkv(self.params, layer, h)
            # ---- write phase: all rows land in the arena first --------------
            if cached.any():
                arena[slots[cached], layer, 0, poss[cached]] = k[cached]
                arena[slots[cached], layer, 1, poss[cached]] = v[cached]
            # ---- gather + attend for rows still in flight -------------------
            idx = np.nonzero(live & ~exited)[0]
            if idx.size:
                keys = np.zeros((idx.size, t_max, ct.D_MODEL), np.float32)
                vals = np.zeros((idx.size, t_max, ct.D_MODEL), np.float32)
                sub_cached = cached[idx]
                if sub_cached.any():
                    src = idx[sub_cached]
                    keys[sub_cached] = arena[slots[src], layer, 0, :t_max]
                    vals[sub_cached] = arena[slots[src], layer, 1, :t_max]
                if (~sub_cached).any():
                    src = idx[~sub_cached]
                    keys[~sub_cached, 0] = k[src]
                    vals[~sub_cached, 0] = v[src]
                h_idx = ct.attn_out(self.params, layer, h[idx], q[idx],
                                    keys, vals, mask[idx])
                h_idx = ct.mlp_out(self.params, layer, h_idx)
                h[idx] = h_idx
            if layer == 0 and self.early_exit_threshold is not None:
                lg = ct.logits_head(self.params, h)
                m = lg.max(axis=-1, keepdims=True)
                p = np.exp(lg - m)
                conf = (p.max(axis=-1) / p.sum(axis=-1))
                newly = (conf >= self.early_exit_threshold) & ~exited
                exit_logits[newly] = lg[newly]
                exited |= newly

        logits = ct.logits_head(self.params, h)
        if exited.any():
            logits[exited] = exit_logits[exited]

        # Advance per-slot lengths to the furthest position written.
        if cached.any():
            for s in np.unique(slots[cached]):
                self.kv.advance(int(s), int(poss[(slots == s)].max()) + 1)

        ms = (time.perf_counter() - t0) * 1e3
        n_dec = int(cached.sum())
        with self._lock:
            self.steps += 1
            self.rows_decode += n_dec
            self.rows_classify += b - n_dec
            self.early_exits += int(exited.sum())
        if _profile_mod.enabled():
            self._profile.record_batch(self.profile_key, b, b,
                                       {"compute_ms": ms})
        if n_dec and _copyledger.active():
            # One k/v row per layer per cached input lands in the arena.
            _copyledger.record(
                "kv_append",
                n_dec * ct.N_LAYERS * 2 * ct.D_MODEL * 4,
                copies=0, allocs=0, records=n_dec,
                engine=self.profile_key)
        return logits.astype(np.float32)

    # ---- convenience ---------------------------------------------------------

    def greedy_step(self, slot: int, token: int, pos: int) -> int:
        """Single-row deterministic step (tests / replay oracle)."""
        lg = self.predict(np.array([[slot, token, pos]], np.int64))
        return int(np.argmax(lg[0]))

    def prefill_rows(self, slot: int, tokens, start: int = 0) -> np.ndarray:
        """The (T, 3) row block that prefills ``tokens`` into ``slot``
        starting at position ``start`` — one submission, one batch."""
        toks = np.asarray(tokens, np.int64).reshape(-1)
        out = np.empty((len(toks), 3), np.int64)
        out[:, 0] = slot
        out[:, 1] = toks
        out[:, 2] = np.arange(start, start + len(toks))
        return out

    def stats(self) -> dict:
        with self._lock:
            return {
                "engine": self.profile_key,
                "steps": self.steps,
                "rows_decode": self.rows_decode,
                "rows_classify": self.rows_classify,
                "early_exits": self.early_exits,
                "kv": self.kv.occupancy(),
            }


# ---- process-shared engine (one arena per config, like shared_engine) --------

_SHARED: Dict[Tuple, DecodeEngine] = {}
_SHARED_LOCK = threading.Lock()


def shared_decode_engine(*, seed: int = 0, blocks: int = 32,
                         max_seq: int = ct.MAX_SEQ,
                         early_exit_threshold: Optional[float] = None
                         ) -> DecodeEngine:
    """Process-cached :class:`DecodeEngine` keyed on its config, so every
    decode bolt replica in a process shares one arena + one batcher
    queue (the co-batching premise). Registers with the classify
    engine cache's auxiliary list so observatory occupancy sweeps see
    it."""
    key = (int(seed), int(blocks), int(max_seq), early_exit_threshold)
    with _SHARED_LOCK:
        eng = _SHARED.get(key)
        if eng is None:
            eng = DecodeEngine(seed=seed, blocks=blocks, max_seq=max_seq,
                               early_exit_threshold=early_exit_threshold)
            _SHARED[key] = eng
            from storm_tpu.infer.engine import register_aux_engine

            register_aux_engine(eng)
        return eng


def _reset_engines() -> None:
    """Test hook: drop the shared-engine cache (arenas die with it)."""
    with _SHARED_LOCK:
        _SHARED.clear()
