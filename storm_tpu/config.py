"""Typed configuration for the whole framework.

Replaces the reference's four config mechanisms (SURVEY.md §5.6): compile-time
parallelism constants (MainTopology.java:25-28), three positional CLI args
(:36-38), edit-the-source cluster endpoints (:33-34), and hard-coded model
metadata (InferenceBolt.java:83-86) — with one dataclass tree loadable from
TOML/JSON and overridable from the CLI. Nothing requires a rebuild.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Optional

from storm_tpu.cascade.policy import CascadeConfig


@dataclass
class BatchConfig:
    """Batch-formation policy of an engine's queue
    (:mod:`storm_tpu.infer.continuous`).

    The reference runs batch=1 per ``session.run`` (InferenceBolt.java:80-86);
    here a batch of up to ``max_batch`` rows is cut from the engine's one
    queue into a free slot of its pipeline ring as the running step ends
    (on an idle device: once the first row has waited ``max_wait_ms``),
    and padded up to the nearest
    of ``buckets`` so XLA compiles a small, fixed set of shapes.
    """

    max_batch: int = 256
    # How long the first row waits for company on an IDLE device. While
    # the device works, a batch is cut into a free ring slot as the
    # running step ends, whatever the clock says.
    max_wait_ms: float = 5.0
    # Padding buckets (ascending). Batches are padded to the smallest bucket
    # >= their size; the final entry must equal max_batch.
    buckets: tuple = (8, 32, 128, 256)
    # Per operator task: the task's bound on rows it has outstanding in
    # the engine's queue, max_inflight * max_batch.
    max_inflight: int = 2
    # An idle device dispatches on arrival instead of ageing the first
    # row to max_wait_ms (a busy one refills a free slot as the running
    # step ends either way).
    eager: bool = False
    # Split-phase device pipeline depth: batches allowed inside the ENGINE
    # between dispatch (stage -> device_put -> async jit launch) and fetch
    # (blocking device->host copy on the engine's fetch thread), so the
    # H2D of batch N+1 overlaps the compute of batch N and the D2H of
    # batch N-1. 0 disables the pipeline entirely and restores the fully
    # serialized pad/put/fwd/fetch predict (the pre-pipeline engine).
    # Distinct from ``max_inflight``, which bounds rows per OPERATOR
    # task; the ring bounds batches per shared engine across all tasks.
    pipeline_depth: int = 2
    # Preallocated host staging buffers per padded bucket shape (the
    # zero-copy staging pool: one fused write replaces the concat + pad +
    # cast copies of the stacked path). Each in-flight batch holds one
    # buffer from dispatch until its fetch completes. 0 = auto
    # (pipeline_depth + 1, so a dispatch never waits on a recycling fetch).
    staging_pool: int = 0
    # Fairness starvation bound for the engine queue's weighted
    # round-robin: a tenant:lane key passed over for this many batch
    # formations is served first in the next one.
    starvation_rounds: int = 4
    # Per-batch deadline on the fetch side of the dispatch/fetch ring:
    # a batch whose device result is not ready within this many ms after
    # launch fails with EngineWatchdogTimeout — failing ONLY its own
    # sources (the exception-isolation contract) and releasing its ring
    # slot + staging buffer, instead of wedging the fetch thread forever.
    # 0 disables the watchdog (plain block_until_ready).
    watchdog_ms: float = 0.0
    # Consecutive watchdog trips that quarantine the engine: it is
    # dropped from the shared-engine cache (so the next build is a fresh
    # replacement) and refuses new dispatches. 0 = never quarantine.
    watchdog_trips: int = 3
    # Batch-native egress: records that arrived together as a RecordFrame
    # leave as ONE coalesced predictions payload per dispatched batch
    # (one encode, one emit, one output message). False restores the
    # one-output-message-per-record contract even for frame ingress —
    # for downstream consumers (or harnesses) that count/key per-record
    # messages — while keeping the zero-copy ingress + view-decode path.
    frame_egress: bool = True

    def __post_init__(self) -> None:
        if float(self.watchdog_ms) < 0:
            raise ValueError(
                f"batch.watchdog_ms must be >= 0, got {self.watchdog_ms!r}")
        if int(self.watchdog_trips) < 0:
            raise ValueError(
                "batch.watchdog_trips must be >= 0, got "
                f"{self.watchdog_trips!r}")
        if int(self.starvation_rounds) < 1:
            raise ValueError(
                "batch.starvation_rounds must be >= 1, got "
                f"{self.starvation_rounds!r}")
        if int(self.pipeline_depth) < 0:
            raise ValueError(
                f"batch.pipeline_depth must be >= 0, got {self.pipeline_depth!r}")
        if int(self.staging_pool) < 0:
            raise ValueError(
                f"batch.staging_pool must be >= 0, got {self.staging_pool!r}")
        self.buckets = tuple(sorted(set(int(b) for b in self.buckets)))
        if not self.buckets:
            self.buckets = (self.max_batch,)
        if self.buckets[-1] != self.max_batch:
            self.buckets = tuple(b for b in self.buckets if b < self.max_batch) + (
                self.max_batch,
            )

    def clipped(self, max_rows: Optional[int]) -> "BatchConfig":
        """This policy with no bucket over ``max_rows`` (a model's bound on
        the rows of one step, ``ModelDef.max_rows``); itself where there is
        no bound or nothing is over it."""
        if max_rows is None or self.max_batch <= max_rows:
            return self
        return dataclasses.replace(
            self, max_batch=int(max_rows),
            buckets=tuple(b for b in self.buckets if b < max_rows))

    def bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]


@dataclass
class ModelConfig:
    """Which model an inference operator runs, and how.

    Replaces the hard-coded SavedModel blob + tensor names
    (InferenceBolt.java:57, :83-84) with a registry name and an optional
    checkpoint path (artifact store instead of ship-model-inside-the-jar,
    InferenceBolt.java:49-51).
    """

    name: str = "lenet5"  # key into storm_tpu.models.registry
    checkpoint: Optional[str] = None  # orbax checkpoint dir; None = random init
    dtype: str = "bfloat16"  # compute dtype on TPU
    num_classes: int = 10
    input_shape: tuple = (28, 28, 1)  # per-instance HWC
    seed: int = 0
    # Extra kwargs for the registry builder (e.g. mobilenetv2 width=0.5,
    # vit depth overrides) — family-specific knobs without config schema churn.
    extra: dict = dataclasses.field(default_factory=dict)
    # 'float' keeps params in the compute dtype; 'int8' stores weight-only
    # quantized params (int8 + per-output-channel scales) in HBM and
    # dequantizes inside the jit program — ~2-4x smaller param footprint,
    # XLA fuses the dequant into the first use (w8a16 serving).
    weights: str = "float"
    # Wire dtype for the host->device transfer. None ships the compute dtype
    # (bf16 = half the bytes of f32); "uint8" affine-quantizes per batch on
    # the host and dequantizes on device inside the jit program — 4x fewer
    # bytes than f32 over the host->device link. Lossy (8-bit) and
    # therefore opt-in.
    transfer_dtype: Optional[str] = None

    def __post_init__(self) -> None:
        if self.transfer_dtype not in (None, "uint8"):
            raise ValueError(f"unsupported transfer_dtype {self.transfer_dtype!r}")
        if self.weights not in ("float", "int8", "int8_fused"):
            raise ValueError(
                "model.weights must be float|int8|int8_fused, "
                f"got {self.weights!r}")


@dataclass
class ShardingConfig:
    """How the operator's work maps onto the TPU mesh.

    ``data_parallel`` is the TPU-native meaning of the reference's
    ``INFERENCE_BOLT_PARAL = 4`` (MainTopology.java:27): shards of the batch
    axis over the ICI mesh rather than replicated JVM executors.
    """

    data_parallel: int = 1  # dp axis size (0 = use all available devices)
    tensor_parallel: int = 1  # tp axis size (param sharding)
    # sp axis size: shard the SEQUENCE axis of long-context models across
    # chips (ring attention over ICI) — for sequences whose activations
    # exceed one chip. Only models publishing ``apply_sp`` (e.g.
    # longseq_encoder) can serve with sp > 1; mutually exclusive with
    # tensor_parallel for serving.
    sequence_parallel: int = 1
    # ep axis size: shard MoE expert tensors over chips for serving (the
    # routing einsums lower to all-to-alls). Only meaningful for MoE
    # families; mutually exclusive with tp/sp for serving.
    expert_parallel: int = 1
    axis_names: tuple = ("data", "model")


@dataclass
class OffsetsConfig:
    """Stream-position policy for the ingest spout.

    ``policy='latest'`` reproduces the reference's freshness-over-completeness
    semantics (start at latest, ignore stored offsets, drop backlog —
    MainTopology.java:101-103). ``policy='resume'`` commits offsets and
    resumes, which the reference deliberately lacked (SURVEY.md §5.4).
    """

    # 'txn': resolve positions from committed offsets like 'resume', but
    # NEVER commit on ack — a transactional sink commits the consumed
    # offsets inside its producer transaction (KIP-98 exactly-once); a
    # spout-side commit would race ahead of uncommitted output.
    policy: str = "latest"  # 'latest' | 'earliest' | 'resume' | 'txn'
    max_behind: Optional[int] = 0  # drop records more than N offsets behind; None = unbounded
    group_id: Optional[str] = None  # None = fresh random group per run (reference behavior)
    # True: partitions come from Kafka consumer-group coordination
    # (JoinGroup/SyncGroup) instead of static task-index assignment —
    # spout tasks then cooperate with ANY consumer sharing the group.
    # Requires a wire-protocol broker (KafkaWireBroker).
    group_protocol: bool = False

    def __post_init__(self) -> None:
        if self.group_protocol and not self.group_id:
            # every task would otherwise mint its own uuid group and be
            # assigned ALL partitions -> N-fold duplicate consumption
            raise ValueError(
                "offsets.group_protocol requires an explicit group_id "
                "(tasks must share one group to split partitions)")
        if self.policy not in ("latest", "earliest", "resume", "txn"):
            raise ValueError(f"unknown offsets policy {self.policy!r}")
        if self.policy == "txn" and not self.group_id:
            raise ValueError(
                "offsets.policy='txn' requires an explicit group_id — the "
                "transactional sink commits offsets to it, and a restart "
                "must resume from the SAME group to be exactly-once")
        if self.policy == "txn" and self.max_behind is not None:
            raise ValueError(
                "offsets.policy='txn' requires max_behind=None — dropping "
                "stale records under a freshness clamp contradicts the "
                "exactly-once contract (set it explicitly)")
        if self.policy == "txn" and self.group_protocol:
            # TxnOffsetCommit v0 carries no group generation (KIP-447
            # fencing is post-reference-era): a task whose partition was
            # rebalanced away could still commit a STALE offset for it
            # inside a transaction, regressing the group position and
            # duplicating records — exactly what 'txn' promises not to do.
            # Static task-index assignment has no handoffs, so no window.
            raise ValueError(
                "offsets.policy='txn' requires group_protocol=False: "
                "v0-era TxnOffsetCommit has no rebalance fencing, so a "
                "revoked partition's in-flight offsets could regress the "
                "group position (use static partition assignment)")


@dataclass
class SinkConfig:
    """Producer-side delivery policy: the three ack modes of the reference's
    KafkaBolt (async-with-callback / sync / fire-and-forget,
    KafkaBolt.java:129-155)."""

    mode: str = "async"  # 'async' | 'sync' | 'fire_and_forget' | 'transactional'
    acks: int = 1  # mirrors acks=1 (MainTopology.java:113)
    # mode='transactional' (exactly-once egress, KIP-98): tuples buffer
    # into one transaction per micro-batch and ack only after commit.
    txn_batch: int = 64
    txn_ms: float = 100.0
    # Consumer group to commit consumed offsets to INSIDE the producer
    # transaction (AddOffsetsToTxn/TxnOffsetCommit) — closing the KIP-98
    # consume-transform-produce loop. Must equal the spout's
    # offsets.group_id, with offsets.policy='txn'. None = egress-only
    # transactions (offsets commit separately; effectively-once across a
    # crash between produce and offset commit).
    offsets_group: Optional[str] = None

    def __post_init__(self) -> None:
        if self.mode not in ("async", "sync", "fire_and_forget",
                             "transactional"):
            raise ValueError(f"unknown sink mode {self.mode!r}")


@dataclass
class TopologyConfig:
    """Topology-level knobs: the reference's parallelism constants
    (MainTopology.java:25-28) plus runtime policies, all runtime-settable."""

    name: str = "inference-topology"
    spout_parallelism: int = 2  # KAFKA_SPOUT_PARAL
    inference_parallelism: int = 4  # INFERENCE_BOLT_PARAL
    sink_parallelism: int = 2  # KAFKA_BOLT_PARAL
    max_spout_pending: int = 2048  # in-flight roots per spout instance
    # Records per emitted spout tuple. 1 = the reference's per-record
    # granularity; N>1 amortizes ledger/executor overhead at high message
    # rates (replay granularity becomes the chunk).
    spout_chunk: int = 1
    # Tuple-value scheme (Storm StringScheme vs RawScheme,
    # MainTopology.java:100): "string" = decode records to str (compatible
    # with every component and with the JSON dist wire);
    # "raw" = emit broker bytes untouched, skipping a bytes->str->bytes
    # round trip on the inference hot path. Under dist-run, "raw" needs
    # wire_format="binary" (the default) to cross worker boundaries.
    # DEPRECATION NOTE (r19): under dist-run the effective default is now
    # "raw" (+ spout_frames) whenever wire_format="binary" and no scheme
    # was pinned in the config file or via --set; wire_format="json" still
    # pins "string" (raw bytes cannot cross the JSON wire — the submit
    # check rejects that combination with an actionable error). The
    # "string"-everywhere dist default is deprecated; pin
    # topology.spout_scheme="string" explicitly to keep it.
    spout_scheme: str = "string"
    # Batch-native ingress (r19 zero-copy plan): with scheme="raw" and
    # spout_chunk>1, each chunk rides as ONE RecordFrame tuple value
    # (runtime/frames.py) — routing moves a reference instead of N
    # payload objects, the dist wire carries the frame as one slot, and
    # egress coalesces to one predictions payload per frame group.
    # Replay/ack granularity is unchanged (the chunk). Off by default
    # locally; dist-run turns it on alongside the raw-scheme default.
    spout_frames: bool = False
    # Inter-worker tuple wire under dist-run: "binary" = length-prefixed
    # CRC-protected frames (storm_tpu/dist/wire.py; bytes/ndarray values
    # cross without re-encoding), with per-peer fallback to JSON for
    # workers that don't advertise the binary version (mixed-version
    # clusters); "json" = pin the legacy envelope everywhere — the
    # compatibility wire for old receivers.
    wire_format: str = "binary"
    # Shared-memory delivery lane between CO-LOCATED dist workers (same
    # host key, negotiated via the control ping): the sender writes the
    # encoded delivery frame once into a multiprocessing.shared_memory
    # segment and ships only a small CRC-protected header over the TCP
    # stream; the receiver decodes zero-copy views over the segment.
    # Cross-host peers (or payloads under shm_min_bytes, where segment
    # setup costs more than the copy it saves) fall back to TCP frames.
    shm_wire: bool = True
    shm_min_bytes: int = 65536
    message_timeout_s: float = 30.0  # at-least-once replay timeout
    inbox_capacity: int = 4096  # bounded executor queues (backpressure)
    tick_interval_s: float = 0.0  # 0 = no tick tuples
    checkpoint_interval_s: float = 5.0  # stateful-bolt checkpoint cadence
    state_dir: str = ""  # durable bolt-state dir; "" = in-memory backend
    # Per-task resource hints for resource-aware dist placement (Storm's
    # RAS): {"component-id": {"memory_mb": N, "cpu": pct}}.
    component_resources: dict = field(default_factory=dict)

    def __post_init__(self) -> None:
        if self.wire_format not in ("binary", "json"):
            raise ValueError(
                f"unknown wire_format {self.wire_format!r} "
                "(expected 'binary' or 'json')")


@dataclass
class BrokerConfig:
    """Where records come from / go to. Replaces the empty-string
    ``zkHosts``/``bootstrap`` edit-the-source fields (MainTopology.java:33-34)."""

    kind: str = "memory"  # 'memory' | 'kafka'
    bootstrap: str = ""  # host:port list for kind='kafka'
    input_topic: str = "input"
    output_topic: str = "output"
    dead_letter_topic: str = "dead-letter"
    partitions: int = 4  # partitions for memory broker topics
    # 'v1' = 0.11-era message sets (the reference's broker generation);
    # 'v2' = KIP-98 record batches (CRC32C), what modern brokers store.
    message_format: str = "v1"
    # KIP-98 idempotent produce (requires message_format='v2'): retried
    # sends reuse their sequence, so the broker appends at most once —
    # the sink's retry path stops duplicating records.
    idempotent: bool = False
    # Egress codec for kind='kafka' (None = uncompressed); gzip/snappy/lz4,
    # message_format='v2' only. Ingest decodes all three regardless.
    compression: Optional[str] = None
    # Consumer isolation (kind='kafka'): 'read_committed' fetches via
    # Fetch v4 (KIP-98) and filters aborted transactions' records — what
    # an exactly-once pipeline's INPUT side should use when upstream
    # producers are transactional. Default matches pre-KIP-98 consumers.
    isolation: str = "read_uncommitted"
    # Transport security (kind='kafka'). 0.11-era brokers already spoke
    # SASL/SSL; the reference never configured it (MainTopology.java:
    # 95-118) but a production contract should. SASL mechanism: PLAIN
    # (the era's standard; tokens are raw pre-KIP-152 frames).
    security_protocol: str = "PLAINTEXT"  # | SSL | SASL_PLAINTEXT | SASL_SSL
    # PLAIN (era standard) | SCRAM-SHA-256 | SCRAM-SHA-512 (KIP-84;
    # password never crosses the wire, server signature verified)
    sasl_mechanism: str = "PLAIN"
    sasl_username: str = ""
    sasl_password: str = ""
    ssl_cafile: str = ""  # CA bundle for broker cert verification
    # self-signed broker certs without a matching SAN: keep encryption +
    # chain verification, skip only hostname matching
    ssl_check_hostname: bool = True
    # explicit, separate opt-out of CERT verification entirely
    # (encryption without authentication — last resort)
    ssl_verify: bool = True

    def security_dict(self) -> Optional[dict]:
        """The wire client's ``security`` parameter, or None for
        PLAINTEXT (no handshake overhead on the default path)."""
        if self.security_protocol == "PLAINTEXT":
            return None
        return {
            "protocol": self.security_protocol,
            "sasl_mechanism": self.sasl_mechanism,
            "sasl_username": self.sasl_username,
            "sasl_password": self.sasl_password,
            "ssl_cafile": self.ssl_cafile or None,
            "ssl_check_hostname": self.ssl_check_hostname,
            "ssl_verify": self.ssl_verify,
        }

    def __post_init__(self) -> None:
        if self.kind not in ("memory", "kafka"):
            raise ValueError(f"broker.kind must be memory|kafka, got {self.kind!r}")
        if self.idempotent and self.message_format != "v2":
            raise ValueError(
                "broker.idempotent requires broker.message_format='v2'")
        if self.message_format not in ("v1", "v2"):
            raise ValueError(
                f"broker.message_format must be v1|v2, got {self.message_format!r}")
        if self.compression is not None:
            if self.compression not in ("gzip", "snappy", "lz4"):
                raise ValueError(
                    f"broker.compression must be gzip|snappy|lz4, "
                    f"got {self.compression!r}")
            if self.message_format != "v2":
                raise ValueError(
                    "broker.compression requires broker.message_format='v2'")
        if self.isolation not in ("read_uncommitted", "read_committed"):
            raise ValueError(
                f"broker.isolation must be read_uncommitted|read_committed, "
                f"got {self.isolation!r}")
        if self.security_protocol not in (
                "PLAINTEXT", "SSL", "SASL_PLAINTEXT", "SASL_SSL"):
            raise ValueError(
                "broker.security_protocol must be PLAINTEXT|SSL|"
                f"SASL_PLAINTEXT|SASL_SSL, got {self.security_protocol!r}")
        # lazy import: config is foundational and the connectors package
        # imports it back at module load (spout/sink), so a top-level
        # import here would cycle through a half-initialized module
        from storm_tpu.connectors.kafka_protocol import SASL_MECHANISMS

        if self.sasl_mechanism not in SASL_MECHANISMS:
            raise ValueError(
                "broker.sasl_mechanism must be one of "
                f"{'|'.join(SASL_MECHANISMS)}, got {self.sasl_mechanism!r}")
        if (self.security_protocol.startswith("SASL")
                and not self.sasl_username):
            raise ValueError(
                "broker.security_protocol=SASL_* requires sasl_username "
                "(mechanism PLAIN)")


def _apply_section(target, values: dict) -> None:
    """Apply a dict of key->value onto a config dataclass instance, coercing
    lists to tuples where the field is a tuple and re-running validation."""
    for k, v in values.items():
        if not hasattr(target, k):
            raise KeyError(f"unknown config key {k!r} for {type(target).__name__}")
        cur = getattr(target, k)
        if isinstance(cur, tuple) and isinstance(v, list):
            v = tuple(v)
        setattr(target, k, v)
        if k == "spout_scheme" and isinstance(target, TopologyConfig):
            # dist-run defaults the scheme to "raw" ONLY when the user
            # never pinned one (file or CLI override) — see main.py.
            target._scheme_pinned = True
    if hasattr(target, "__post_init__"):
        target.__post_init__()


#: The env var the dist controller exports its resolved control-plane
#: token through (and every client falls back to). Single source of truth
#: for transport/ctl/controller — config.py so the CLI doesn't need grpc.
CONTROL_TOKEN_ENV = "STORM_TPU_CONTROL_TOKEN"


def env_control_token() -> str:
    """The ONE env-fallback read shared by the UI, dist plane, and ctl —
    resolution must never diverge between the binary's serving modes."""
    import os

    return os.environ.get(CONTROL_TOKEN_ENV, "")


@dataclass
class ControlConfig:
    """Control-plane authentication (VERDICT r4 missing #4).

    The Kafka edge carries SASL/SSL (BrokerConfig), but the surfaces that
    can kill/rebalance/swap a topology — the UI admin POST routes and the
    dist controller<->worker gRPC — would otherwise be plaintext and
    unauthenticated; the same era-argument that justified broker security
    (reference pom.xml:55-78) applies to them.

    ``auth_token`` is a shared secret: requests must carry it
    (``Authorization: Bearer <token>`` on HTTP, ``x-storm-tpu-token``
    gRPC metadata), mismatches are rejected and logged. ``"env:NAME"``
    reads the secret from environment variable NAME so it never lives in
    a config file. ``""`` (the default) falls back to
    $STORM_TPU_CONTROL_TOKEN — one posture for the UI, the dist gRPC
    plane, and ctl alike — and disables auth only when that is also
    unset (loopback-dev, the previous behavior). The dist controller
    exports the resolved token to its spawned workers via the same var."""

    auth_token: str = ""
    #: Directory for the controller's write-ahead journal ("" = no
    #: journal: a controller crash forgets the mesh and a restart
    #: rebuilds every worker from scratch). With a journal dir, a
    #: restarted controller replays the log and REATTACHES to live
    #: workers — warm engines stay warm.
    journal_dir: str = ""
    #: Compact (snapshot + truncate) the journal after this many
    #: appends since the last snapshot.
    journal_snapshot_every: int = 64
    #: Whether a journal-backed controller attempts reattach on start
    #: (False = always cold-rebuild, e.g. after deliberate mesh wipe).
    reattach: bool = True

    def __post_init__(self) -> None:
        if int(self.journal_snapshot_every) < 1:
            raise ValueError("control.journal_snapshot_every must be >= 1")

    def resolve_token(self) -> str:
        import os

        t = self.auth_token
        if t.startswith("env:"):
            name = t[4:]
            val = os.environ.get(name, "")
            if not val:
                raise ValueError(
                    f"control.auth_token says {t!r} but ${name} is unset/empty")
            return val
        return t or env_control_token()


@dataclass
class TracingConfig:
    """Per-record distributed tracing + flight recorder (runtime/tracing.py).

    Off by default: ``sample_rate=0`` keeps the hot path allocation-free
    (sampled context objects are only minted for sampled roots)."""

    # Fraction of root tuples that carry a TraceContext (0 = off, 1 = all).
    sample_rate: float = 0.0
    # Completed traces kept in the in-process ring buffer (per process).
    store_capacity: int = 256
    # e2e latency above which the sink logs a flight-recorder SLO-breach
    # event (0 = disabled).
    slo_ms: float = 0.0
    # JSONL flight-recorder file ("" = in-memory ring only).
    flight_path: str = ""
    # In-memory flight-recorder ring size (events).
    flight_capacity: int = 512
    # Rotation: roll flight_path -> .1 -> ... when it exceeds this size,
    # keeping at most flight_max_files generations.
    flight_max_bytes: int = 4 * 1024 * 1024
    flight_max_files: int = 3

    def __post_init__(self) -> None:
        if not 0.0 <= float(self.sample_rate) <= 1.0:
            raise ValueError(
                f"tracing.sample_rate must be in [0, 1], got {self.sample_rate!r}")


@dataclass
class ObsConfig:
    """Continuous profiling & SLO-burn observatory (storm_tpu/obs/).

    The per-(engine, bucket) cost profiler itself is always-on and
    near-free (one dict update per device batch; PERF.md §6, PR 41, has
    what the logs cost on the chip); ``enabled`` gates the *control loop*:
    the Observatory task that steps the burn tracker, publishes occupancy
    gauges, and runs the regression sentinel. The burn tracker needs
    ``tracing.slo_ms`` set — without it the sink never counts breaches
    and burn stays 0.
    """

    enabled: bool = False
    # Observatory step cadence (burn tracker + occupancy gauges).
    interval_s: float = 1.0
    # SLO objective: fraction of delivered records inside tracing.slo_ms.
    # The error budget is 1 - slo_objective.
    slo_objective: float = 0.99
    # Multi-window burn: both windows must exceed burn_threshold to trip
    # (fast reacts, slow de-flaps). Burn 1.0 = spending budget exactly.
    burn_fast_window_s: float = 60.0
    burn_slow_window_s: float = 600.0
    burn_threshold: float = 1.0
    # Regression sentinel: compare live stage costs against this
    # saved `profile --json` snapshot ("" = sentinel off); flag a (engine,
    # bucket, stage) cell when live mean > regression_factor x baseline,
    # once it has at least min_samples live observations.
    baseline_path: str = ""
    regression_factor: float = 1.5
    sentinel_interval_s: float = 10.0
    min_samples: int = 20
    # Bottleneck attribution (obs/bottleneck.py): a component counts as
    # "at capacity" above capacity_hot busy-fraction of the wallclock
    # window (also the Autoscaler's named-bottleneck scale-up trigger);
    # an edge is "growing" above lag_growth_eps rows/s; a saturated but
    # no-longer-growing inbox still attributes above lag_depth_hot
    # queued records; no leader is named below bottleneck_min_score
    # (an idle topology has no bottleneck).
    capacity_hot: float = 0.8
    lag_growth_eps: float = 1.0
    lag_depth_hot: int = 64
    bottleneck_min_score: float = 0.4
    # Copy ledger (obs/copyledger.py): a ``copy_amplification_high``
    # flight event fires when the windowed amplification ratio (bytes
    # moved / bytes ingested) exceeds this ceiling; 0 disables the
    # check. De-flapped: the event re-arms only after the ratio falls
    # back under 80% of the ceiling.
    copy_amp_ceiling: float = 32.0

    def __post_init__(self) -> None:
        if self.interval_s <= 0 or self.sentinel_interval_s <= 0:
            raise ValueError("obs intervals must be > 0")
        if not 0.0 < float(self.capacity_hot) <= 1.0:
            raise ValueError(
                f"obs.capacity_hot must be in (0, 1], got "
                f"{self.capacity_hot!r}")
        if self.lag_growth_eps < 0 or self.lag_depth_hot < 0:
            raise ValueError("obs lag thresholds must be >= 0")
        if self.bottleneck_min_score < 0:
            raise ValueError("obs.bottleneck_min_score must be >= 0")
        if not 0.0 < float(self.slo_objective) < 1.0:
            raise ValueError(
                f"obs.slo_objective must be in (0, 1), got "
                f"{self.slo_objective!r}")
        if (self.burn_fast_window_s <= 0
                or self.burn_slow_window_s < self.burn_fast_window_s):
            raise ValueError(
                "need 0 < obs.burn_fast_window_s <= obs.burn_slow_window_s")
        if self.regression_factor <= 1.0:
            raise ValueError("obs.regression_factor must be > 1")
        if self.copy_amp_ceiling < 0:
            raise ValueError("obs.copy_amp_ceiling must be >= 0")


@dataclass
class PlanConfig:
    """SLO-aware joint planner (storm_tpu/plan/): offline solve + online
    correct.

    The offline half (``storm-tpu plan``) needs no
    config at all — it solves over a ProfileStore snapshot for an explicit
    (rate, SLO) target. This section configures the *online* half: when
    ``enabled``, the daemon attaches a :class:`storm_tpu.plan.corrector.
    PlanCorrector` to the Observatory loop; it consumes the bottleneck
    verdict + SLO-burn tracker and moves only the named limiter's knob,
    and the Autoscaler defers its own global scale-up to it.
    """

    enabled: bool = False
    # Offline solve at daemon startup when both targets are set and a
    # profile baseline is available (obs.baseline_path or live curves):
    # the plan is logged and served on the /plan route; it is NOT applied
    # automatically — apply is an operator decision (docs/OPERATIONS.md).
    rate_rows_s: float = 0.0
    slo_p99_ms: float = 0.0
    # Solver feasibility margin: candidates must keep predicted device
    # utilization at or below this fraction.
    headroom: float = 0.8
    # Compile-cost amortization horizon for shapes not yet warm.
    horizon_s: float = 600.0
    # Framework overhead floor added to every predicted e2e p99 (host
    # scheduling, serialization, transport — everything outside the
    # profiled device stages and the modeled batching waits).
    overhead_ms: float = 15.0
    # Charged for a cold shape when the profile has no compile sample yet.
    default_compile_ms: float = 500.0
    # A (engine, bucket) curve with fewer device-stage samples than this
    # counts as "cold" in coverage and is excluded from the solve.
    min_samples: int = 8
    # ---- online corrector ----------------------------------------------------
    correct: bool = True
    # Consecutive hot Observatory steps (burn tripped AND a named leader)
    # before the corrector moves a knob.
    hot_steps: int = 2
    # Consecutive calm steps before one correction step is reverted.
    calm_steps: int = 6
    # Post-move cooldown steps during which the corrector holds still
    # (hysteresis: one bounded step, then watch).
    hold_steps: int = 3
    # Hard parallelism bound for corrector moves; 0 = per-kind defaults
    # (ACCEL_MAX_PARALLELISM for inference bolts, CPU_MAX_PARALLELISM
    # otherwise — see runtime/autoscale.py).
    max_parallelism: int = 0

    def __post_init__(self) -> None:
        if not 0.0 < float(self.headroom) <= 1.0:
            raise ValueError(
                f"plan.headroom must be in (0, 1], got {self.headroom!r}")
        if self.rate_rows_s < 0 or self.slo_p99_ms < 0:
            raise ValueError("plan targets must be >= 0")
        if self.horizon_s <= 0:
            raise ValueError("plan.horizon_s must be > 0")
        if self.overhead_ms < 0 or self.default_compile_ms < 0:
            raise ValueError("plan cost floors must be >= 0")
        if min(self.hot_steps, self.calm_steps) < 1 or self.hold_steps < 0:
            raise ValueError(
                "need plan.hot_steps/calm_steps >= 1 and hold_steps >= 0")
        if self.min_samples < 1:
            raise ValueError("plan.min_samples must be >= 1")
        if self.max_parallelism < 0:
            raise ValueError("plan.max_parallelism must be >= 0 (0 = auto)")


@dataclass
class QosConfig:
    """Admission control & QoS: per-tenant token-bucket rate limiting at the
    spout edge, weighted priority lanes with earliest-deadline-first batch
    formation in the inference operator, and an adaptive load-shedding
    controller that drops best-effort traffic *before* the autoscaler
    reacts (scale-out takes seconds; shedding takes one control step).

    Off by default: ``enabled=False`` keeps every hot path untouched — no
    record classification, no extra tuple field, FIFO batch formation.

    A record's tenant and lane ride on its broker key, ``tenant:lane``
    (both optional): ``b"gold:high"`` is tenant *gold* in lane *high*,
    ``b"gold"`` is tenant *gold* in ``default_lane``, and a key-less
    record is tenant = its topic, lane = ``default_lane``.
    """

    enabled: bool = False
    # Priority lanes, highest priority first. Keys naming an unknown lane
    # (or no lane at all) fall into ``default_lane``.
    lanes: tuple = ("high", "normal", "best_effort")
    default_lane: str = "normal"
    # Per-lane delivery deadlines (ms after broker append), aligned with
    # ``lanes``: batch formation is earliest-deadline-first over these, so
    # a fresh high-deadline record preempts queued best-effort ones
    # instead of FIFO-queuing behind them.
    lane_deadline_ms: tuple = (50.0, 200.0, 1000.0)
    # Token-bucket admission at the spout edge: records/sec per tenant
    # (0 = unlimited). ``tenant_rates`` overrides the default per tenant
    # id. Each spout task gets an even split of the tenant's rate (static
    # partition assignment spreads a tenant's records across tasks).
    tenant_rate: float = 0.0
    tenant_burst_s: float = 1.0  # bucket depth, in seconds of rate
    tenant_rates: dict = field(default_factory=dict)
    # Load-shedding controller: cadence + signal thresholds + hysteresis.
    # A signal is *hot* when above its threshold; ``shed_hot_steps``
    # consecutive hot intervals raise the shed level by one,
    # ``shed_calm_steps`` consecutive calm intervals (every signal below
    # half its threshold) lower it. Level N sheds the N lowest-priority
    # lanes; the top lane is never shed.
    shed_interval_s: float = 1.0
    shed_inbox_frac: float = 0.5   # inference inbox occupancy fraction
    shed_wait_ms: float = 0.0      # batch-wait p95 threshold (0 = off)
    shed_breach_rate: float = 1.0  # sink SLO breaches/sec (needs tracing.slo_ms)
    shed_hot_steps: int = 2
    shed_calm_steps: int = 5
    # Graceful degradation for shed traffic: "" rejects with a typed
    # ``overloaded`` record on the output topic (fast, never times out);
    # a model registry name routes shed lanes to that (cheaper) engine
    # instead of rejecting.
    degrade_model: str = ""

    def __post_init__(self) -> None:
        self.lanes = tuple(str(lane) for lane in self.lanes)
        self.lane_deadline_ms = tuple(float(x) for x in self.lane_deadline_ms)
        if not self.lanes or len(set(self.lanes)) != len(self.lanes):
            raise ValueError("qos.lanes must be non-empty and unique")
        if len(self.lane_deadline_ms) != len(self.lanes):
            raise ValueError(
                f"qos.lane_deadline_ms has {len(self.lane_deadline_ms)} "
                f"entries for {len(self.lanes)} lanes")
        if self.default_lane not in self.lanes:
            raise ValueError(
                f"qos.default_lane {self.default_lane!r} not in qos.lanes")
        if self.shed_interval_s <= 0:
            raise ValueError("qos.shed_interval_s must be > 0")
        if self.shed_hot_steps < 1 or self.shed_calm_steps < 1:
            raise ValueError("qos shed hot/calm steps must be >= 1")

    # ---- lane helpers (one definition shared by spout/operator/shedder) ---

    def lane_index(self, lane: Optional[str]) -> int:
        """Priority index of ``lane`` (0 = highest); unknown lanes get the
        default lane's index."""
        try:
            return self.lanes.index(lane)
        except ValueError:
            return self.lanes.index(self.default_lane)

    def deadline_for(self, lane: Optional[str]) -> float:
        return self.lane_deadline_ms[self.lane_index(lane)]

    @property
    def max_shed_level(self) -> int:
        """Highest useful shed level: every lane but the top one shed."""
        return len(self.lanes) - 1

    def shed_eligible(self, lane: Optional[str], level: int) -> bool:
        """Does shed ``level`` drop ``lane``? Level N sheds the N
        lowest-priority lanes; the top lane never sheds."""
        if level <= 0:
            return False
        shed_from = len(self.lanes) - min(int(level), self.max_shed_level)
        return self.lane_index(lane) >= shed_from

    def rate_for(self, tenant: str) -> float:
        return float(self.tenant_rates.get(tenant, self.tenant_rate))


@dataclass
class ResilienceConfig:
    """Transport retry / circuit-breaker / replay-pacing knobs (round 14).

    TOML: ``[resilience]``. These parameterize
    :mod:`storm_tpu.resilience`: the deadline-budgeted retry policy
    wrapping WorkerClient RPCs, the per-peer circuit breaker in the
    PeerSender path, and the token bucket that paces post-recovery
    replay drains.
    """

    # Retry policy (exponential backoff + full jitter).
    retry_attempts: int = 4
    retry_base_ms: float = 50.0
    retry_cap_ms: float = 2000.0
    # Total wall-clock budget across all attempts of one logical send.
    retry_deadline_s: float = 30.0
    # Circuit breaker: consecutive failures that open a peer's circuit,
    # and how long it stays open before the half-open probe.
    circuit_failures: int = 5
    circuit_reset_s: float = 3.0
    # Replay-storm suppression: tuples/s a sender pushes at a freshly
    # recovered peer during the pacing window. 0 = auto (derived from
    # max_spout_pending over the window, i.e. the ledger's own bound).
    replay_rate: float = 0.0
    replay_window_s: float = 10.0

    def __post_init__(self) -> None:
        if int(self.retry_attempts) < 1:
            raise ValueError("resilience.retry_attempts must be >= 1, got "
                             f"{self.retry_attempts!r}")
        for name in ("retry_base_ms", "retry_cap_ms", "retry_deadline_s",
                     "circuit_reset_s", "replay_rate", "replay_window_s"):
            if float(getattr(self, name)) < 0:
                raise ValueError(
                    f"resilience.{name} must be >= 0, got "
                    f"{getattr(self, name)!r}")
        if int(self.circuit_failures) < 1:
            raise ValueError("resilience.circuit_failures must be >= 1, "
                             f"got {self.circuit_failures!r}")


@dataclass
class ChaosConfig:
    """Dist-grade fault injection (round 14). TOML: ``[chaos]``.

    Rides ``cfg.to_dict()`` through the submit recipe, so arming it on
    the controller arms every worker's process-wide injector
    (:mod:`storm_tpu.resilience.chaos`). All injections are logged as
    ``chaos_injection`` flight events. NEVER enable in production; the
    daemon/soak/bench drive it to measure recovery, not to serve.
    """

    enabled: bool = False
    seed: int = 0
    # Added latency per outbound Deliver/Ack RPC (+ uniform jitter).
    wire_latency_ms: float = 0.0
    wire_jitter_ms: float = 0.0
    # Fraction of outbound send attempts dropped (raised as ChaosDrop,
    # which the retry/circuit stack treats as a real outage).
    wire_drop_pct: float = 0.0
    # Fraction of outbound frames bit-flipped — exercises the CRC check
    # in dist/wire.py and the WireError -> replay path behind it.
    corrupt_pct: float = 0.0
    # Engine-hang injection: hold each injected batch's result this long
    # (arm per-batch via the worker 'chaos' control RPC knob
    # engine_hang_next; the config only sets the hold duration).
    engine_hang_ms: float = 0.0
    # Daemon-driven worker chaos: SIGKILL a random worker every this many
    # seconds under ``dist`` runs (0 = off). Recovery comes from the
    # heartbeat monitor; the kill itself is logged by the controller.
    kill_worker_s: float = 0.0
    # Daemon-driven controller chaos: this many seconds into a dist run
    # the daemon abandons its controller (drops every handle, workers
    # keep serving) and builds a fresh one from the journal to prove
    # reattach (0 = off; requires control.journal_dir).
    kill_controller_s: float = 0.0

    def __post_init__(self) -> None:
        for name in ("wire_drop_pct", "corrupt_pct"):
            v = float(getattr(self, name))
            if not 0.0 <= v <= 1.0:
                raise ValueError(
                    f"chaos.{name} must be in [0, 1], got {v!r}")
        for name in ("wire_latency_ms", "wire_jitter_ms", "engine_hang_ms",
                     "kill_worker_s", "kill_controller_s"):
            if float(getattr(self, name)) < 0:
                raise ValueError(
                    f"chaos.{name} must be >= 0, got "
                    f"{getattr(self, name)!r}")


@dataclass
class PipelineConfig:
    """One model pipeline (spout -> inference -> sink) inside a multi-model
    topology: several of these share one process and one TPU slice
    (BASELINE.json config 5, "MNIST+CIFAR bolts sharing one v5e-8"). Params
    for each model are co-resident in HBM; compiled executables are cached
    per (model, bucket) by the engine layer."""

    name: str = "pipeline"
    model: ModelConfig = field(default_factory=ModelConfig)
    batch: BatchConfig = field(default_factory=BatchConfig)
    sharding: ShardingConfig = field(default_factory=ShardingConfig)
    offsets: OffsetsConfig = field(default_factory=OffsetsConfig)
    input_topic: str = "input"
    output_topic: str = "output"
    dead_letter_topic: str = "dead-letter"
    # Records per spout tuple for THIS pipeline; 0 = inherit
    # topology.spout_chunk.
    spout_chunk: int = 0
    # "" = inherit topology.spout_scheme (see TopologyConfig).
    spout_scheme: str = ""
    spout_parallelism: int = 1
    inference_parallelism: int = 1
    sink_parallelism: int = 1

    @classmethod
    def from_dict(cls, d: dict) -> "PipelineConfig":
        p = cls()
        for k, v in d.items():
            if not hasattr(p, k):
                raise KeyError(f"unknown pipeline key {k!r}")
            cur = getattr(p, k)
            if dataclasses.is_dataclass(cur) and isinstance(v, dict):
                _apply_section(cur, v)
            else:
                setattr(p, k, v)
        return p


@dataclass
class Config:
    topology: TopologyConfig = field(default_factory=TopologyConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    batch: BatchConfig = field(default_factory=BatchConfig)
    sharding: ShardingConfig = field(default_factory=ShardingConfig)
    offsets: OffsetsConfig = field(default_factory=OffsetsConfig)
    sink: SinkConfig = field(default_factory=SinkConfig)
    broker: BrokerConfig = field(default_factory=BrokerConfig)
    control: ControlConfig = field(default_factory=ControlConfig)
    tracing: TracingConfig = field(default_factory=TracingConfig)
    qos: QosConfig = field(default_factory=QosConfig)
    # Continuous profiling & SLO-burn observatory (storm_tpu/obs/): cost
    # curves the planner consumes + burn-rate shed signal. TOML: [obs].
    obs: ObsConfig = field(default_factory=ObsConfig)
    # SLO-aware joint planner (storm_tpu/plan/): offline cost-model solve
    # over the profile curves + online bottleneck-named corrector in the
    # Observatory loop. TOML: [plan].
    plan: PlanConfig = field(default_factory=PlanConfig)
    # Confidence-gated model cascade (storm_tpu/cascade/): tiered serving
    # where easy records accept at a cheap tier and only the hard residue
    # escalates to the flagship. TOML: [cascade].
    cascade: CascadeConfig = field(default_factory=CascadeConfig)
    # Mesh resilience (storm_tpu/resilience/): transport retry policy,
    # per-peer circuit breakers, replay pacing. TOML: [resilience].
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    # Dist-grade fault injection for drills/benches. TOML: [chaos].
    chaos: ChaosConfig = field(default_factory=ChaosConfig)
    # Multi-model topology: non-empty => ``run`` builds one spout->infer->sink
    # chain per entry instead of the single-model DAG. TOML: [[pipelines]].
    pipelines: list = field(default_factory=list)

    # ---- loading / overriding -------------------------------------------------

    @classmethod
    def from_dict(cls, d: dict) -> "Config":
        cfg = cls()
        cfg.apply_dict(d)
        return cfg

    def apply_dict(self, d: dict) -> None:
        for section, values in d.items():
            if not hasattr(self, section):
                raise KeyError(f"unknown config section {section!r}")
            if section == "pipelines":
                if not isinstance(values, list):
                    raise TypeError("config section 'pipelines' must be a list of tables")
                self.pipelines = [
                    v if isinstance(v, PipelineConfig) else PipelineConfig.from_dict(v)
                    for v in values
                ]
                continue
            sub = getattr(self, section)
            if not isinstance(values, dict):
                raise TypeError(f"config section {section!r} must be a table/dict")
            _apply_section(sub, values)

    @classmethod
    def load(cls, path: str | Path) -> "Config":
        """Load TOML or JSON config file."""
        path = Path(path)
        text = path.read_text()
        if path.suffix == ".json":
            return cls.from_dict(json.loads(text))
        import tomllib

        return cls.from_dict(tomllib.loads(text))

    def apply_overrides(self, overrides: list) -> None:
        """Apply ``section.key=value`` CLI overrides."""
        patch: dict = {}
        for item in overrides:
            key, _, raw = item.partition("=")
            if not _:
                raise ValueError(f"override must be section.key=value: {item!r}")
            section, _, k = key.partition(".")
            try:
                val = json.loads(raw)
            except json.JSONDecodeError:
                val = raw
            patch.setdefault(section, {})[k] = val
        self.apply_dict(patch)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)
