"""Model registry: name -> builder.

Replaces the reference's model identity mechanism — a hard-coded SavedModel
blob shipped inside the application jar with hard-coded tensor names
(InferenceBolt.java:49-58, :83-84) — with named builders producing
transparent JAX param pytrees. Checkpoints load via orbax from
``ModelConfig.checkpoint``; absent a checkpoint, params are seeded
deterministically from ``ModelConfig.seed``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class ModelDef:
    """A model family instance: pure init/apply pair + metadata.

    ``apply(params, state, x, train=False) -> (logits, new_state)`` where
    ``state`` carries running statistics (BatchNorm) and is empty for
    stateless models."""

    name: str
    input_shape: tuple  # per-instance (H, W, C)
    num_classes: int
    init: Callable[[jax.Array], Tuple[Any, Any]]
    apply: Callable[..., Tuple[jnp.ndarray, Any]]
    flagship: bool = False
    # Optional sequence-parallel forward for long-context serving:
    # ``apply_sp(params, state, x, mesh, seq_axis, train=False)`` runs with
    # the S axis of ``x`` sharded over ``seq_axis`` (ring attention), never
    # materializing the full sequence on one chip. None = SP-unaware.
    apply_sp: Any = None
    # Compute-relevant hyperparameters that are NOT recoverable from param
    # shapes (num_heads above all: attention projections are dim x dim for
    # ANY head count, so a checkpoint trained with 8 heads loads cleanly
    # into a 2-head model and silently computes wrong outputs — a round-3 review
    # medium). Saved alongside checkpoints and validated at load.
    hyper: Any = None
    # The most rows one device step may hold; None: whatever the batch
    # policy says. A model whose row is thousands of tokens states it, and
    # the engine clips its buckets to it (``BatchConfig.clipped``): bucket
    # 256 of 4,096-token rows would be a million tokens a step.
    max_rows: Optional[int] = None
    # The type instances reach the device in; None: the engine's compute
    # type. Token ids ride the float32 instance contract exactly (under
    # 2^24) and would not survive a cast to bfloat16.
    input_dtype: Any = None
    # ``observe_aux(registry, component id, aux)``: reads what a step counted
    # on the device (``new_state["aux"]``, fetched with the predictions) into
    # the registry. Set by the builder of a model that counts (models/
    # scorer.py composes its branches' readers); None: it counts nothing.
    observe_aux: Any = None
    serve_params: Any = None  # (params, a step's most rows) -> the tree served


_BUILDERS: Dict[str, Callable[..., ModelDef]] = {}


def register(name: str) -> Callable:
    def deco(fn: Callable[..., ModelDef]) -> Callable[..., ModelDef]:
        _BUILDERS[name] = fn
        return fn

    return deco


def _load_builtin() -> None:
    # Import model modules lazily so registration happens on demand.
    from storm_tpu.models import (  # noqa: F401
        chartiny,
        evabyte,
        falcon_h1,
        granite,
        keye,
        kimi_k2,
        kimi_linear,
        lenet,
        lfm2,
        longseq,
        minicpm_sala,
        mixer,
        mobilenet,
        moe_vit,
        nemotron_h,
        ouro,
        resnet,
        solar_open2,
        trinity,
        vit,
    )


def registry_names() -> list:
    _load_builtin()
    return sorted(_BUILDERS)


def build_model(name: str, **kwargs) -> ModelDef:
    _load_builtin()
    if name not in _BUILDERS:
        raise KeyError(f"unknown model {name!r}; available: {registry_names()}")
    return _BUILDERS[name](**kwargs)


def init_params(model: ModelDef, seed: int = 0):
    return model.init(jax.random.PRNGKey(seed))


_HYPER_SIDECAR = "storm_tpu_hyper.json"


def _check_hyper(model: ModelDef, checkpoint: str) -> None:
    """Refuse to load a checkpoint whose recorded hyperparameters disagree
    with the model's. Param shapes can't catch these (e.g. num_heads:
    projections are dim x dim for any head count) — a mismatch loads
    cleanly and computes differently-partitioned attention with no error
    (a round-3 review, medium: models/longseq.py num_heads 8 -> 2)."""
    import json
    import os

    sidecar = os.path.join(checkpoint, _HYPER_SIDECAR)
    if model.hyper is None or not os.path.exists(sidecar):
        return  # pre-sidecar checkpoint or hyper-less model: best effort
    try:
        with open(sidecar) as f:
            saved = json.load(f)
    except (OSError, ValueError) as e:
        # A corrupt sidecar must not brick an otherwise-valid checkpoint —
        # the check is an extra guard, not a load dependency.
        import logging

        logging.getLogger("storm_tpu.models").warning(
            "unreadable hyper sidecar %s (%s); skipping the "
            "hyperparameter compatibility check", sidecar, e)
        return
    mismatches = {
        k: (saved[k], v) for k, v in model.hyper.items()
        if k in saved and _canon(saved[k]) != _canon(v)}
    if mismatches:
        detail = ", ".join(
            f"{k}: checkpoint={s!r} model={m!r}"
            for k, (s, m) in sorted(mismatches.items()))
        raise ValueError(
            f"checkpoint {checkpoint!r} was saved with different "
            f"hyperparameters than model {model.name!r} ({detail}). "
            "Loading it would compute silently-wrong outputs even though "
            "param shapes match; rebuild the model with the checkpoint's "
            "hyperparameters (ModelConfig.extra) or retrain.")


def _canon(v):
    # JSON round-trips tuples as lists; compare structurally.
    return list(v) if isinstance(v, tuple) else v


def load_or_init(model: ModelDef, checkpoint: Optional[str], seed: int = 0):
    """Load params/state from an orbax checkpoint dir, or initialize."""
    from storm_tpu.obs.profile import setup_span

    with setup_span("parameters",
                    source="checkpoint" if checkpoint else "seed") as span:
        params, state = init_params(model, seed)
        if checkpoint:
            import orbax.checkpoint as ocp

            _check_hyper(model, checkpoint)
            with ocp.StandardCheckpointer() as ckptr:
                restored = ckptr.restore(
                    checkpoint, {"params": params, "state": state})
            params, state = restored["params"], restored["state"]
        leaves = [a for a in jax.tree.leaves((params, state))
                  if hasattr(a, "nbytes")]
        span.attrs.update(leaves=len(leaves),
                          bytes=sum(int(a.nbytes) for a in leaves))
    return params, state


def save_checkpoint(path: str, params, state,
                    model: Optional[ModelDef] = None) -> None:
    import orbax.checkpoint as ocp

    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(path, {"params": params, "state": state})
        ckptr.wait_until_finished()
    if model is not None and model.hyper is not None:
        import json
        import os
        import tempfile

        # Atomic publish (mkstemp + fsync + replace, the state.py pattern):
        # a crash mid-write must not leave a truncated sidecar that fails
        # every subsequent load of a valid checkpoint.
        fd, tmp = tempfile.mkstemp(dir=path, suffix=".hyper.tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump({"model": model.name, **model.hyper}, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, os.path.join(path, _HYPER_SIDECAR))
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
