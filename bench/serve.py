"""Drives the program's serving engine open-loop on the wall clock.

The engine is built as ``launch/serve.py --real`` builds it:
``build_model(cfg)`` at its defaults, ``Engine(mode="real",
policy="trail", kv_layout="paged")`` with the configuration's engine
sizes, and the real-mode default predictor, ``OraclePredictor``. The
harness calls ``Engine.submit`` once a request's due time has passed,
with ``arrival=eng.now``, and ``Engine.step`` while there is work.

Timestamps are the harness's own: ``WallEventLog`` stamps
``time.perf_counter()`` at every event the engine emits (arrival,
admit, first_token, tokens, finish, preempt), in place of the engine's
cost-model clock. The engine emits first_token, tokens and finish after
it has fetched the decode step's outputs, so the device work behind them
is done.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from repro.metrics.events import Event, EventLog
from repro.serving.predictors import OraclePredictor


class WallEventLog(EventLog):
    """An event log that stamps the wall clock, not the engine's clock."""

    def emit(self, t: float, rid: int, kind: str, value: float = 0.0):
        """Append one event at ``time.perf_counter()``."""
        self.events.append(Event(time.perf_counter(), rid, kind, float(value)))


class RecordingOracle(OraclePredictor):
    """The real-mode default predictor, keeping the probe's posteriors.

    Behaves as ``OraclePredictor``; it also keeps each posterior the
    engine hands it, keyed by (rid, tokens generated), for the check.
    """

    def __init__(self, pc, seed: int):
        super().__init__(pc, seed=seed)
        self.posteriors: dict[tuple[int, int], np.ndarray] = {}

    def on_token(self, req, probe_probs=None) -> float:
        """Keep ``probe_probs``, then refine as ``OraclePredictor`` does."""
        if probe_probs is not None:
            self.posteriors[(req.rid, len(req.generated))] = np.array(
                probe_probs, np.float32)
        return super().on_token(req, probe_probs)


@dataclasses.dataclass
class StepLog:
    """One engine step inside the traced window."""

    decode_rows: list        # (context before the step, tokens emitted)
    prefill: list            # (first position, tokens) newly prefilled
    prompts_done: int        # prompts whose prefill ended in this step


def engine_sizes(config: dict) -> dict:
    """EngineConfig sizes, with ``mem_budget`` equal to the page pool.

    The pool holds ``max_batch * ceil(max_len / page_size)`` pages
    besides the null page (``Model.init_cache``).
    """
    from flops import Shapes
    e = dict(config["engine"])
    pages = e["max_batch"] * -(-e["max_len"] // e["page_size"])
    e["mem_budget"] = pages * e["page_size"] * Shapes.of(config).kv_token_bytes()
    return e


def build_engine(cfg, config: dict, model, params, seed: int):
    """The engine under test, with a fresh wall-clock event log."""
    from repro.serving.engine import Engine, EngineConfig
    from weights import seed32
    ecfg = EngineConfig(mode="real", policy="trail", kv_layout="paged",
                        **engine_sizes(config))
    log = WallEventLog()
    pred = RecordingOracle(cfg.probe, seed=seed32(seed, "predictor"))
    return Engine(cfg, ecfg, predictor=pred, model=model, params=params,
                  event_log=log)


class OpenLoop:
    """Open-loop submission of a schedule to one engine.

    ``run`` submits each arrival once ``perf_counter()`` has passed its
    due time and steps the engine while it has work; with no work it
    sleeps until the next due time. Host spans (``bench.submit``,
    ``bench.step``, ``bench.idle``) go into the profiler's trace while
    one is being taken.
    """

    def __init__(self, eng):
        self.eng = eng
        self.reqs: dict = {}          # rid -> Request
        self.due: dict = {}           # rid -> absolute due time
        self.segment: dict = {}       # rid -> schedule segment
        self.submitted: dict = {}     # rid -> absolute submit time
        self.queue: list = []         # (time, queue length) per step
        self.step_times: list = []    # (start, seconds) per step
        self.steps: list = []         # StepLog per step while logging
        self.log_steps = False
        self._hw: dict = {}           # rid -> prefill high-water mark
        self.next_rid = 0

    def submit(self, arrival, due_abs: float):
        """Submit one arrival now, as due at ``due_abs``."""
        from repro.serving.request import Request
        rid = self.next_rid
        self.next_rid += 1
        r = Request(rid=rid, arrival=self.eng.now, prompt=list(arrival.prompt),
                    max_new_tokens=arrival.out_len,
                    true_out_len=arrival.out_len)
        self.reqs[rid] = r
        self.due[rid] = due_abs
        self.segment[rid] = arrival.segment
        self.eng.submit(r)
        self.submitted[rid] = time.perf_counter()
        return r

    def step(self):
        """One engine step, logging its work while ``log_steps`` is set."""
        eng = self.eng
        if not self.log_steps:
            self._timed_step()
            return
        ev = eng.events
        mark = len(ev)
        before = {rid: r.entry.prefill_done for rid, r in self.reqs.items()
                  if not r.done}
        self._timed_step()
        rows, prefill, done = [], [], 0
        for e in ev.events[mark:]:
            if e.kind == "tokens":
                r = self.reqs[e.rid]
                rows.append((r.context_len - int(e.value), int(e.value)))
        for rid, b in before.items():
            r = self.reqs[rid]
            after = r.entry.prefill_done
            hw = max(self._hw.get(rid, 0), b)
            if after > hw:
                prefill.append((hw, after - hw))
                self._hw[rid] = after
                if after >= len(r.prompt) - 1 and hw < len(r.prompt) - 1:
                    done += 1
        self.steps.append(StepLog(rows, prefill, done))

    def _timed_step(self):
        t = time.perf_counter()
        self.eng.step()
        now = time.perf_counter()
        self.step_times.append((t, now - t))
        self.queue.append((now, self.eng.queue_len()))

    def run(self, arrivals, t0: float, stop, hooks=None):
        """Serve ``arrivals`` (due relative to ``t0``) until ``stop(now)``.

        ``hooks`` maps a time relative to ``t0`` to a callable run once
        when the wall clock passes it (trace start and stop).
        """
        import jax
        span = jax.profiler.TraceAnnotation
        pending = sorted(hooks.items()) if hooks else []
        i, n = 0, len(arrivals)
        while True:
            now = time.perf_counter()
            while pending and now >= t0 + pending[0][0]:
                pending.pop(0)[1]()
            if stop(now, i):
                break
            if i < n and t0 + arrivals[i].due <= now:
                with span("bench.submit"):
                    while i < n and t0 + arrivals[i].due <= now:
                        self.submit(arrivals[i], t0 + arrivals[i].due)
                        i += 1
            if self.eng.has_work():
                with span("bench.step"):
                    self.step()
            else:
                wake = t0 + arrivals[i].due if i < n else now + 1e-3
                if pending:
                    wake = min(wake, t0 + pending[0][0])
                with span("bench.idle"):
                    time.sleep(max(wake - time.perf_counter(), 0.0))
        for _, fn in pending:
            fn()
        return i
