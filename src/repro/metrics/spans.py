"""Host phases of the serving engine: profiler spans and always-on counters.

A `PhaseRecorder` times named phases of ``Engine.step``. Each
``with rec("engine.pages"):`` does two things:

* it opens a ``jax.profiler.TraceAnnotation`` of that name, so a trace
  taken meanwhile shows the phase on the profiler's ``/host:CPU`` plane,
  on the same clock as the device's ops;
* it adds the phase's ``time.perf_counter()`` duration to counters that
  are always on (`PhaseStat`): seconds, calls, the longest single call,
  and the programs JAX built or loaded from its persistent cache while
  the phase was the innermost open one.

Programs are counted by one process-wide ``jax.monitoring`` listener on
JAX's backend-compile event, registered when the first recorder is made
and credited to the innermost phase open in the process at that moment.

Sim mode uses `NO_PHASES`, one shared recorder that does nothing: the
simulator runs millions of steps and pays for no timing. Recording is
observation only; scheduling and tokens are the same with it on or off.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time


@dataclasses.dataclass
class PhaseStat:
    """Always-on counters of one phase."""

    seconds: float = 0.0    # wall time spent inside the phase
    calls: int = 0          # times the phase was entered
    max_s: float = 0.0      # the longest single call
    compiles: int = 0       # programs built or loaded while innermost


# the phases open in this process, innermost last: the compile listener
# credits a program to the last one
_open: list[PhaseStat] = []
_COMPILE_EVENT = ""         # JAX's backend-compile event, once listening


def _on_duration(event: str, duration: float, **_):
    if _open and event == _COMPILE_EVENT:
        _open[-1].compiles += 1


def _listen():
    global _COMPILE_EVENT
    if _COMPILE_EVENT:
        return
    import jax
    from jax._src import dispatch
    _COMPILE_EVENT = dispatch.BACKEND_COMPILE_EVENT
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


class _Phase:
    """One entry into a phase: the profiler span and the timing."""

    __slots__ = ("stat", "span", "t0")

    def __init__(self, stat: PhaseStat, span):
        self.stat = stat
        self.span = span

    def __enter__(self):
        self.span.__enter__()
        _open.append(self.stat)
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        _open.pop()
        self.span.__exit__(*exc)
        s = self.stat
        s.seconds += dt
        s.calls += 1
        if dt > s.max_s:
            s.max_s = dt


class PhaseRecorder:
    """Profiler spans and always-on counters for named host phases.

    ``stats`` maps each phase name to its `PhaseStat`; a phase appears
    once first entered. Phases nest: an outer phase's seconds include
    its inner phases', and a program built inside an inner phase counts
    there only.
    """

    def __init__(self):
        _listen()
        import jax
        self._span = jax.profiler.TraceAnnotation
        self.stats: dict[str, PhaseStat] = {}

    def __call__(self, name: str) -> _Phase:
        """A context manager timing one entry into phase ``name``."""
        stat = self.stats.get(name)
        if stat is None:
            stat = self.stats[name] = PhaseStat()
        return _Phase(stat, self._span(name))

    def snapshot(self) -> dict[str, dict]:
        """The counters now, as plain dicts keyed by phase name."""
        return {k: dataclasses.asdict(v) for k, v in self.stats.items()}


class _NoPhases:
    """A recorder that records nothing (sim mode)."""

    _none = contextlib.nullcontext()

    def __call__(self, name: str):
        """The shared do-nothing context manager."""
        return self._none

    def snapshot(self) -> dict:
        """Always empty."""
        return {}


#: The shared recorder of every sim-mode engine.
NO_PHASES = _NoPhases()
