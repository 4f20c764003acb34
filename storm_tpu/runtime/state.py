"""Stateful bolts: per-task key-value state with checkpoint/restore.

The reference checkpoints nothing (SURVEY.md §5.4: the model is immutable,
stream position lives in ZooKeeper and is deliberately ignored on start).
Storm itself, however, ships ``IStatefulBolt`` + ``KeyValueState`` — per-bolt
state that survives executor restarts — and that capability belongs to the
layer-1 runtime this framework owns. Semantics here:

- one :class:`KeyValueState` per bolt task, single-owner (the executor's
  asyncio task), so snapshots are taken between tuples and are always
  consistent — no barrier protocol needed in-process;
- checkpoints are periodic (``topology.checkpoint_interval_s``) plus one
  final checkpoint on graceful stop; restore happens in ``prepare`` via the
  ``init_state`` hook (same call order as Storm: prepare -> initState ->
  execute...);
- delivery is at-least-once (SURVEY.md §2.5): a crash between a state
  update and the next checkpoint replays tuples whose effects were already
  checkpointed — state updates should be idempotent or tolerate overcount,
  exactly as with Storm's non-transactional state;
- backends: :class:`MemoryStateBackend` (survives executor replacement
  within the process — the supervisor-restart path) and
  :class:`FileStateBackend` (atomic JSON files; survives worker-process
  death — the dist-recovery path, storm_tpu/dist/controller.py);
- state is keyed per (component, task_index) and is NOT migrated between
  tasks when a rebalance changes parallelism — same per-task semantics as
  Storm's ``KeyValueState``. Keyed aggregates that must survive a
  parallelism change belong in an external store.
"""

from __future__ import annotations

import json
import os
import tempfile
from typing import Any, Dict, Iterator, Optional, Tuple as Tup

from storm_tpu.runtime.base import Bolt


class KeyValueState:
    """Dict-like state for one bolt task. Keys and values must be
    JSON-serializable when a :class:`FileStateBackend` is in play."""

    def __init__(self, data: Optional[Dict[str, Any]] = None) -> None:
        self._data: Dict[str, Any] = dict(data or {})
        self.dirty = False

    def get(self, key: str, default: Any = None) -> Any:
        return self._data.get(key, default)

    def put(self, key: str, value: Any) -> None:
        self._data[key] = value
        self.dirty = True

    def delete(self, key: str) -> None:
        if key in self._data:
            del self._data[key]
            self.dirty = True

    def items(self) -> Iterator[Tup[str, Any]]:
        return iter(self._data.items())

    def __len__(self) -> int:
        return len(self._data)

    def __contains__(self, key: str) -> bool:
        return key in self._data

    def snapshot(self) -> Dict[str, Any]:
        """Point-in-time copy (shallow: values are assumed replaced, not
        mutated in place — mutate-in-place values must be re-``put``)."""
        return dict(self._data)


class MemoryStateBackend:
    """Process-local store: state survives executor replacement (the
    supervisor sweep, runtime/cluster.py:_supervise) but not the process."""

    def __init__(self) -> None:
        self._store: Dict[Tup[str, int], Tup[int, Dict[str, Any]]] = {}

    def save(self, component: str, task: int, version: int,
             snapshot: Dict[str, Any]) -> None:
        self._store[(component, task)] = (version, dict(snapshot))

    def load(self, component: str, task: int) -> Optional[Tup[int, Dict[str, Any]]]:
        got = self._store.get((component, task))
        if got is None:
            return None
        version, snap = got
        return version, dict(snap)


class FileStateBackend:
    """Durable store: one JSON file per (component, task), written
    atomically (tmp + rename), so a crash mid-checkpoint leaves the
    previous checkpoint intact. Survives worker-process death — a
    recovered dist worker (same host, same ``state_dir``) restores it."""

    def __init__(self, state_dir: str) -> None:
        self.state_dir = state_dir
        os.makedirs(state_dir, exist_ok=True)

    def _path(self, component: str, task: int) -> str:
        safe = component.replace("/", "_")
        return os.path.join(self.state_dir, f"{safe}-{task}.json")

    def save(self, component: str, task: int, version: int,
             snapshot: Dict[str, Any]) -> None:
        path = self._path(component, task)
        fd, tmp = tempfile.mkstemp(dir=self.state_dir, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump({"version": version, "data": snapshot}, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
            # fsync the directory too: os.replace makes the rename
            # atomic but not durable — a power cut after replace can
            # still lose the directory entry and resurrect the OLD
            # checkpoint (or none) on remount.
            dfd = os.open(self.state_dir, os.O_RDONLY)
            try:
                os.fsync(dfd)
            finally:
                os.close(dfd)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    def load(self, component: str, task: int) -> Optional[Tup[int, Dict[str, Any]]]:
        path = self._path(component, task)
        try:
            with open(path) as f:
                blob = json.load(f)
        except FileNotFoundError:
            return None
        return int(blob["version"]), blob["data"]


def make_backend(state_dir: str):
    """Backend from config: ``topology.state_dir`` set -> durable files,
    empty -> in-memory."""
    return FileStateBackend(state_dir) if state_dir else MemoryStateBackend()


class StatefulBolt(Bolt):
    """Bolt with framework-managed state (Storm's ``IStatefulBolt``).

    Subclasses implement :meth:`init_state` (called once per task after
    ``prepare``, with restored state on a restart) and use ``self.state``
    in ``execute``. The executor checkpoints periodically and on graceful
    stop; :meth:`pre_checkpoint` runs immediately before each snapshot so
    bolts can fold transient aggregates into the state."""

    state: KeyValueState

    def init_state(self, state: KeyValueState) -> None:
        self.state = state

    def pre_checkpoint(self) -> None:
        """Hook: flush in-flight aggregates into ``self.state`` before the
        snapshot is taken."""

    def checkpoint_now(self) -> None:
        """Force an immediate state snapshot. Bound to the executor's
        checkpoint when running inside a topology; a no-op for bolts driven
        standalone (tests). A bolt that commits progress (the decode bolt)
        calls this before acking."""
