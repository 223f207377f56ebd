"""First-class metrics layer: per-request event logs and rollups.

Submodules:

* ``events``    — `EventLog`: the per-request event stream (arrival /
  admit / first-token / tokens / finish / preempt / swap timestamps)
  captured by `Engine.step()` and merged across cluster replicas.
* ``streaming`` — `StreamingQuantiles`: an exact, mergeable percentile
  accumulator (validated against ``numpy.percentile``).
* ``rollup``    — `rollup()`: turn an event log into TTFT / TBT /
  completion-time / slowdown distributions (mean + p50/p90/p99),
  SLO-attainment curves, and preemption/swap counters.
* ``emitters``  — shared JSON and markdown-table emitters used by every
  benchmark artifact.
* ``spans``     — `PhaseRecorder`: profiler spans and always-on counters
  (time, calls, longest call, programs built) for the host phases of a
  real-mode ``Engine.step``; sim engines share the no-op `NO_PHASES`.
"""

from repro.metrics.emitters import report_json, report_markdown
from repro.metrics.events import Event, EventLog, check_invariants
from repro.metrics.rollup import (DEFAULT_SLOS, ideal_service_times,
                                  rollup)
from repro.metrics.streaming import StreamingQuantiles

__all__ = [
    "Event",
    "EventLog",
    "StreamingQuantiles",
    "check_invariants",
    "ideal_service_times",
    "rollup",
    "report_json",
    "report_markdown",
    "DEFAULT_SLOS",
]
