"""The system under test as its users run it: ``build_standard_topology``
(spout -> inference bolt -> sink, dead letters aside) on a ``LocalCluster``
over the in-process ``MemoryBroker``, in this process, which holds the chip.

The one file of the benchmark that drives the program. It sets what the
configuration and the traffic file state (the model, its type and seed, the
guarantees, the payload's spout scheme) and nothing else: operator
parallelism, ``BatchConfig()`` and ``max_spout_pending`` stay the program's
defaults, so a PR that improves a default shows in the cells.
"""

from __future__ import annotations

import os
import subprocess

from benchmarks.core import spec


def prepare(root: str, rehearse: bool) -> dict:
    """Build the native parser from the committed sources, as a deployment
    does (the ``.so`` is not tracked), and place the compile cache. Returns
    what was done, for the run's first line."""
    native = os.path.join(root, "storm_tpu", "native")
    # a rehearsal beside other tests leaves a library that is there alone:
    # two builds at once would write the same file
    if not (rehearse and os.path.exists(
            os.path.join(native, "libstormtpu.so"))):
        made = subprocess.run(["make", "-C", native, "all"],
                              capture_output=True, text=True)
        if made.returncode != 0:
            raise RuntimeError("make -C storm_tpu/native all failed:\n"
                               + made.stdout + made.stderr)
    from storm_tpu.infer.engine import enable_compile_cache
    from storm_tpu.native import native_available

    # JAX_COMPILATION_CACHE_DIR where the machine sets it, else .jax_cache/
    # in the checkout: a fixed path either way, so a second run hits.
    cache_dir = enable_compile_cache()
    # No size limit on it. A cell's programs have to stay until its next
    # run: ViT-g/14's four buckets and the reference are some 300 MB of
    # executables, and under the 192 MiB that the chip machine's
    # JAX_COMPILATION_CACHE_MAX_SIZE allows, each one written evicted the one
    # the next run asked for first, so every run compiled for 146 s (PERF.md,
    # PR 23).
    import jax

    jax.config.update("jax_compilation_cache_max_size", -1)
    if not native_available() and not rehearse:
        raise RuntimeError("native parser built but did not load")
    return {"compile_cache": cache_dir, "native": native_available()}


def _set(cfg, dotted: str, value) -> None:
    section, _, key = dotted.partition(".")
    target = getattr(cfg, section)
    if not hasattr(target, key):
        raise KeyError(f"the program's config has no {dotted}")
    setattr(target, key, tuple(value) if isinstance(value, list) else value)


def make_config(config: dict, traffic: dict, seed: int):
    from storm_tpu.config import Config, ModelConfig

    if "register" in config:
        # published sizes the program's registry does not name yet
        spec.plugin("models", config["register"]).register(config)
    model = config["model"]
    cfg = Config()
    cfg.model = ModelConfig(
        name=model["name"], dtype=model["dtype"],
        num_classes=int(model["num_classes"]),
        input_shape=tuple(model["input_shape"]), seed=int(seed % 2 ** 31))
    for source in (config.get("program", {}), traffic.get("program", {})):
        for dotted, value in source.items():
            _set(cfg, dotted, value)
    return cfg


def parameters(config: dict, seed: int):
    """The float32 parameters and state the engine is built from: the
    program initialises them on the device from the seed, and the engine
    casts its own copy to the served type."""
    from storm_tpu.models.registry import build_model, load_or_init

    m = make_config(config, {}, seed).model
    model = build_model(m.name, num_classes=m.num_classes,
                        input_shape=tuple(m.input_shape))
    return load_or_init(model, None, m.seed)


class Served:
    """A submitted topology and the broker around it."""

    name = "bench"

    def __init__(self, config: dict, traffic: dict, seed: int) -> None:
        from storm_tpu.connectors import MemoryBroker
        from storm_tpu.main import build_standard_topology
        from storm_tpu.runtime.cluster import LocalCluster

        self.cfg = make_config(config, traffic, seed)
        self.broker = MemoryBroker(
            default_partitions=self.cfg.broker.partitions)
        self.input_topic = self.cfg.broker.input_topic
        self.output_topic = self.cfg.broker.output_topic
        self.dead_letter_topic = self.cfg.broker.dead_letter_topic
        topology = build_standard_topology(self.cfg, self.broker)
        self.cluster = LocalCluster()
        try:
            # builds the engine and warms every bucket of BatchConfig()
            self.cluster.submit_topology(self.name, self.cfg, topology)
        except BaseException:
            self.cluster.shutdown()
            raise

    def append(self, payload: bytes) -> None:
        self.broker.produce(self.input_topic, payload)

    def landed(self) -> int:
        return (self.broker.topic_size(self.output_topic)
                + self.broker.topic_size(self.dead_letter_topic))

    def registry(self) -> dict:
        return self.cluster.metrics(self.name)

    def settle(self, timeout_s: float) -> bool:
        """True once every tuple tree is acked or failed and inboxes are
        empty."""
        return bool(self.cluster.drain(self.name, timeout_s=timeout_s))

    def errors(self) -> list:
        return [repr(e) for e in self.cluster.errors(self.name)]

    def outputs(self):
        """``(timestamps, rows)`` of the output topic, and the number of
        dead letters. A record's timestamp is the broker's, taken at its
        append (``connectors/memory.py``)."""
        import json

        import numpy as np

        records = self.broker.drain_topic(self.output_topic)
        stamps, rows = [], []
        for rec in records:
            preds = json.loads(rec.value)["predictions"]
            stamps += [rec.timestamp] * len(preds)
            rows += preds
        dead = self.broker.topic_size(self.dead_letter_topic)
        width = int(self.cfg.model.num_classes)
        return (np.asarray(stamps, np.float64),
                np.asarray(rows, np.float64).reshape(len(rows), width), dead)

    def close(self) -> None:
        self.cluster.shutdown()
