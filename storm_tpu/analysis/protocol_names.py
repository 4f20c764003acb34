"""Control-plane protocol registry — GENERATED, do not edit by hand.

Regenerate after adding a control command, journal kind, or flight event:

    storm-tpu lint --regen-protocol-registry

Generated from the tree's own call sites: ``.control()``/``.probe()``
sends and ``cmd ==`` handler arms, journal ``_jappend``/fold arms, and
every literal ``flight.event(...)`` name with the fields common to all of
its sites. ``storm_tpu/analysis/protocol.py`` (PRT001-003) checks call
sites against this file statically; ``runtime/tracing.py`` warns once at
runtime for event names built from variables — together they catch the
drift whose only other symptom is a command that bounces, a journal record
replay silently drops, or a dashboard row that never appears.
"""

from __future__ import annotations

import fnmatch

#: commands with a `cmd ==` handler arm (dist/worker.py)
CONTROL_COMMANDS = frozenset({
    'activate',
    'chaos',
    'component_stats',
    'copies',
    'deactivate',
    'decode_sessions',
    'drain',
    'drain_worker',
    'health',
    'kill',
    'metrics',
    'parallelism',
    'ping',
    'profile',
    'rebalance',
    'seek',
    'shutdown',
    'start_bolts',
    'start_spouts',
    'state_report',
    'submit',
    'swap_model',
    'traces',
    'update_peer',
    'utilization',
})

#: commands sent via .control()/.probe() in the tree
CONTROL_SENT = frozenset({
    'activate',
    'component_stats',
    'copies',
    'deactivate',
    'decode_sessions',
    'drain',
    'drain_worker',
    'health',
    'kill',
    'metrics',
    'parallelism',
    'ping',
    'profile',
    'rebalance',
    'seek',
    'shutdown',
    'start_bolts',
    'start_spouts',
    'state_report',
    'submit',
    'swap_model',
    'traces',
    'update_peer',
    'utilization',
})

#: journal kinds with an apply() fold arm (dist/journal.py)
JOURNAL_KINDS = frozenset({
    'activation',
    'kill',
    'peer_update',
    'rebalance',
    'submit',
    'swap_model',
    'workers',
})

#: journal kinds appended in the tree
JOURNAL_EMITTED = frozenset({
    'activation',
    'kill',
    'peer_update',
    'rebalance',
    'submit',
    'swap_model',
    'workers',
})

#: literal flight-event name -> fields every site provides
FLIGHT_EVENTS = {
    'autoscale_decision': ('bottleneck', 'capacity', 'component', 'direction', 'inbox_frac', 'p50_ms', 'parallelism'),
    'batch_formed': ('component', 'device_ms', 'fill', 'records', 'size', 'sources'),
    'bottleneck_shift': ('capacity', 'component', 'device_frac', 'e2e_p95_ms', 'inflow_growth_per_s', 'previous', 'reasons', 'score'),
    'cascade_escalation': (),
    'chaos_injection': ('target',),
    'copy_amplification_high': ('amplification', 'ceiling', 'ingest_bytes', 'top_bytes_per_record', 'top_stage'),
    'decode_session_evicted': ('cached_rows', 'session'),
    'decode_session_migrated': ('cached_rows', 'committed', 'session'),
    'decode_session_started': ('max_new_tokens', 'prompt_len', 'restored', 'session'),
    'dist_circuit_close': ('peer',),
    'dist_circuit_open': ('opens', 'peer'),
    'dist_heartbeat_miss': ('consecutive', 'error', 'worker'),
    'dist_peer_replaced': ('addr', 'idx'),
    'dist_reattached': ('dead', 'reattach_s', 'reconciled', 'replayed', 'survivors'),
    'dist_worker_draining': ('worker',),
    'dist_worker_recovered': ('worker',),
    'dist_worker_restarted': ('drained', 'restart_s', 'worker'),
    'engine_quarantined': ('component', 'model', 'trips'),
    'engine_replaced': ('component', 'model'),
    'executor_restart': ('component', 'error', 'task', 'topology'),
    'plan_correction': ('action', 'burn', 'component', 'parallelism', 'score'),
    'profile_regression': ('baseline_ms', 'bucket', 'engine', 'live_ms', 'ratio', 'stage'),
    'ring_handoff': ('component', 'remapped_fraction'),
    'scenario_phase': (),
    'shed_decision': ('breach_rate', 'burn_rate', 'component', 'direction', 'inbox_frac', 'level', 'wait_p95_ms'),
    'shed_degrade': ('component', 'lane', 'level', 'records'),
    'shed_reject': ('component', 'lane', 'level', 'records'),
    'slo_breach': ('component', 'e2e_ms', 'slo_ms', 'trace_id'),
    'slo_burn': ('breaches', 'budget', 'delivered', 'fast_burn', 'slow_burn', 'threshold'),
    'tree_timeout': ('topology', 'trees'),
    'wire_error': ('error', 'nbytes'),
    'worker_drained': ('checkpoints', 'flushed', 'worker'),
    'worker_draining': ('worker',),
    'xla_compile': ('batch_shape', 'compile_ms', 'component'),
}

FLIGHT_EVENT_PATTERNS = (
)


def is_known_event(name: str) -> bool:
    if name in FLIGHT_EVENTS:
        return True
    return any(fnmatch.fnmatchcase(name, p)
               for p in FLIGHT_EVENT_PATTERNS)
