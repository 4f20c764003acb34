"""Shared Pallas-vs-reference dispatch predicate for the ops package.

Kernels (flash attention, w8a16 dequant-matmul) run as Pallas on TPU and
take the jnp reference paths elsewhere (CPU tests, unsupported shapes).
``STORM_TPU_NO_PALLAS`` forces the reference paths everywhere — the
escape hatch for debugging numeric diffs. A backend that cannot be
opened (chip missing, or held by another process) is an error, never a
quiet switch to the reference path.
"""

from __future__ import annotations

import contextlib
import os
import threading

import jax


def use_pallas() -> bool:
    if os.environ.get("STORM_TPU_NO_PALLAS"):
        return False
    return jax.devices()[0].platform == "tpu"


def one_device() -> bool:
    """Whether this process has a single device, so that no program it builds
    can be split over several. A Pallas TPU call has no partitioning rule:
    jax refuses to lower one in a program that jit partitions over a mesh
    ("Mosaic kernels cannot be automatically partitioned"). A shape rule
    cannot see at trace time whether its program will be split, so it takes
    a kernel where this answers True, and XLA's form elsewhere, whoever the
    caller is (an engine, ``parallel/train.py``, a dry run)."""
    return jax.device_count() == 1


_notes = threading.local()


@contextlib.contextmanager
def dispatch_notes():
    """Collect what the ops' shape rules choose while a program is traced in
    this thread: yields a list that fills with ``"<op>=<form>"``, one entry a
    distinct choice. Observation only: no rule reads it. The engine wraps the
    trace of each bucket's program in it, so its inventory can say which form
    each program was built with."""
    before = getattr(_notes, "seen", None)
    seen = _notes.seen = []
    try:
        yield seen
    finally:
        _notes.seen = before


def note(op: str, form: str) -> None:
    seen = getattr(_notes, "seen", None)
    entry = f"{op}={form}"
    if seen is not None and entry not in seen:
        seen.append(entry)
