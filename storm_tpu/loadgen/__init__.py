"""Trace-driven fleet load generation & the scenario scorecard.

- :mod:`storm_tpu.loadgen.trace` — seeded deterministic workload traces
  (heavy-tailed tenants, diurnal waves, flash crowds; save/load/replay).
- :mod:`storm_tpu.loadgen.scorecard` — per-cell targets, scoring, and
  the CLI table renderer.
- :mod:`storm_tpu.loadgen.fleet` — the scenario x pattern matrix driver
  (``run_fleet`` returns the scorecard dict; no script of the tree calls it).
"""

from storm_tpu.loadgen.trace import (Trace, TraceEvent, TraceSpec,
                                     generate, load_trace, replay)
from storm_tpu.loadgen.scorecard import (CellTargets, render_table,
                                         score_cell)

__all__ = ["Trace", "TraceEvent", "TraceSpec", "generate", "load_trace",
           "replay", "CellTargets", "render_table", "score_cell"]
