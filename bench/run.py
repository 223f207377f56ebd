#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the accelerator JAX finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, in this order:

1. set-up: find the chips the cell asks for (or exit 1), point JAX's
   persistent compilation cache at ``.jax_cache/`` in the checkout
   (``$JAX_COMPILATION_CACHE_DIR`` where set), build the configuration's
   model with ``build_model`` at its defaults, make its weights on the
   device from ``--seed`` in one jitted call, build the engine, then
   warm it up: one request per prefill width up to the largest chunk,
   then the cell's own traffic for a few seconds, drained;
2. the schedule: arrivals of the cell's traffic at the cell's rate,
   submitted open-loop on the wall clock. A lead-in comes first and is
   not counted; the measured window of ``--seconds`` follows; the run
   then drains until every request due in the window has finished, or
   until the cell's cap. With ``--trace 1`` the profiler traces a few
   seconds at the end of the window;
3. the check: the engine is freed, the weights are made again from the
   seed, and a sample of finished requests runs through the float32
   reference (``check.py``);
4. one JSON line on standard output, last: ``correct``, ``attempted``
   (requests due in the window), ``failed`` (of those, the ones that did
   not finish), ``metrics`` (the cell's end-to-end metrics, or with
   ``--trace 1`` its per-layer metrics), ``device`` and, traced, the
   ``breakdown``; ``checks`` comes last, each compared number with its
   limit. Standard error ends with the same numbers.

Every metric is read by its reader in ``bench/metrics/``. Times are the
harness's own ``time.perf_counter()``, taken at the engine's event
boundaries, from each request's due time.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

TRACE_DIR = BENCH / ".out" / "trace"


class NoDevice(Exception):
    """JAX found no accelerator of the platform, or too few chips."""


def find_devices(chips: int, platform: str | None) -> dict:
    """The device as JAX reports it; raises NoDevice where it does not fit."""
    import jax
    devs = jax.devices()
    if platform is not None and devs[0].platform != platform:
        raise NoDevice(f"JAX's first device is {devs[0].platform!r}, "
                       f"not {platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


class Compiles:
    """Counts the programs JAX compiles or loads, with their times."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.times: list[float] = []
        self.secs: list[float] = []
        self.hits: list[float] = []
        name = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(event, duration, **_):
            if event == name:
                self.times.append(time.perf_counter())
                self.secs.append(duration)

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits.append(time.perf_counter())

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def between(self, lo: float, hi: float) -> tuple[int, int, float]:
        """(programs built, of them loaded from the cache, seconds) in [lo, hi)."""
        inside = [s for t, s in zip(self.times, self.secs) if lo <= t < hi]
        return (len(inside), sum(lo <= t < hi for t in self.hits),
                float(sum(inside)))


class GcPauses:
    """Times Python's garbage collections while installed."""

    def __init__(self):
        self.spans: list[tuple[float, float, int]] = []  # start, s, generation
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.spans.append((self._t, time.perf_counter() - self._t,
                               info["generation"]))

    def remove(self):
        """Stop timing."""
        gc.callbacks.remove(self._on)


def serve_until_idle(drv, arrivals, cap_s: float):
    """Serve ``arrivals`` from now, then drain; at most ``cap_s`` seconds."""
    t0 = time.perf_counter()
    n = len(arrivals)
    drv.run(arrivals, t0, lambda now, i: (i >= n and not drv.eng.has_work())
            or now > t0 + cap_s)


def warm_resets(pool):
    """Build the engine's reset masks for every count the window frees.

    ``flush_resets`` builds its masks from the list of slots and pages
    freed since its last call, one set of small programs per length of
    that list, which the window would otherwise build (or load) as each
    length first occurs, stalling the host for up to seconds. Here
    requests that exist only for this take and free 1 to
    ``2 * max_len / page_size`` pages (two requests of the longest
    context finishing in one step) and 1 to ``max_batch`` slots, each
    count followed by a flush, while the engine has no work.
    """
    rid = -1
    for n in range(1, 2 * pool.pages_per_seq + 1):
        pool.blocks.ensure(rid, n * pool.page_size)
        pool.blocks.free_request(rid)
        pool.flush_resets()
    for n in range(1, pool.n_slots + 1):
        rids = range(-1, -1 - n, -1)
        for r in rids:
            pool.assign(r)
        for r in rids:
            pool.release(r)
        pool.flush_resets()


def warm_up(cell, drv, seed: int):
    """Compile every program the window drives, then serve real traffic.

    One request per prefill width (powers of two from 8 to the engine's
    ``prefill_chunk``, plus one longer than a chunk), each with two
    decode steps; the reset masks of every count of freed pages and
    slots (``warm_resets``); then the cell's own traffic for
    ``warmup_s`` seconds at its rate, drained.
    """
    import traffic
    e, p = cell.config["engine"], cell.params
    vocab = cell.config["program"]["vocab_size"]
    rng = traffic.stream(seed, "warmup", "tokens")
    widths, w = [], 8
    while w < e["prefill_chunk"]:
        widths.append(w)
        w *= 2
    widths += [e["prefill_chunk"], e["prefill_chunk"] + widths[-1] // 2]
    for w in widths:
        a = traffic.Arrival(0.0, rng.integers(1, vocab, w + 1).tolist(),
                            2 * e["probe_interval"], "warmup")
        serve_until_idle(drv, [a], 120.0)
    warm_resets(drv.eng.pool)
    warm = traffic.segment(cell.traffic, seed, "warmup", p["rate_rps"], 0.0,
                           p["warmup_s"], vocab)
    serve_until_idle(drv, warm, p["warmup_s"] + p["drain_cap_s"])


def request_records(drv) -> list:
    """Per-request times (absolute perf_counter) from the wall event log."""
    recs = {rid: types.SimpleNamespace(
        rid=rid, segment=drv.segment[rid], due=drv.due[rid],
        submitted=drv.submitted[rid], arrival=math.nan, admit=math.nan,
        first=math.nan, finish=math.nan, tokens=[], preempts=0,
        prompt_len=len(r.prompt), out_len=r.true_out_len)
        for rid, r in drv.reqs.items()}
    for e in drv.eng.events.events:
        r = recs.get(e.rid)
        if r is None:
            continue
        if e.kind == "arrival" and math.isnan(r.arrival):
            r.arrival = e.t
        elif e.kind == "admit" and math.isnan(r.admit):
            r.admit = e.t
        elif e.kind == "first_token":
            r.first = e.t
        elif e.kind == "tokens":
            r.tokens.append((e.t, int(e.value)))
        elif e.kind == "finish":
            r.finish = e.t
        elif e.kind == "preempt":
            r.preempts += 1
    return sorted(recs.values(), key=lambda r: r.rid)


def stats_snapshot(eng) -> dict:
    """The engine's counters at one moment."""
    s = eng.stats
    return {"n_preemptions": s.n_preemptions,
            "recomputed_tokens": s.recomputed_tokens,
            "prefilled_tokens": s.prefilled_tokens,
            "iterations": s.iterations}


def read_metrics(names, ctx) -> dict:
    """Each named metric's reader on ``ctx``; a reader's None is left out."""
    import spec
    out = {}
    for m in names:
        v = spec.reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def queue_slope(queue, lo: float, hi: float) -> float:
    """Least-squares growth of the queue over [lo, hi), requests/s."""
    import numpy as np
    pts = [(t, q) for t, q in queue if lo <= t < hi]
    if len(pts) < 2:
        return 0.0
    t, q = np.array(pts, float).T
    return float(np.polyfit(t - lo, q, 1)[0])


def run(cell, seed: int, seconds: float, trace: bool, platform="tpu",
        log=print, control: str | None = None) -> dict:
    """One run of ``cell``; returns the result line's object.

    ``platform`` None skips the look for an accelerator (the CPU tests).
    ``control`` names a precision at which the reference also stands in
    the program's place, read over the same sample into
    ``result["control"]`` (``bench/control.py``; never in a benchmark
    run).
    """
    import jax
    import numpy as np

    import check
    import serve
    import spec
    import traffic
    import weights
    from flops import Shapes
    from repro.launch.compile_cache import use_compile_cache
    from repro.models.model import build_model

    device = find_devices(cell.workload["chips"], platform)
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = Compiles()
    p, conf = cell.params, cell.config
    cfg = spec.model_config(conf)
    vocab = cfg.vocab_size
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    params = weights.make_params(shapes, seed)
    jax.block_until_ready(params)
    eng = serve.build_engine(cfg, conf, model, params, seed)
    drv = serve.OpenLoop(eng)
    warm_up(cell, drv, seed)
    gc.collect()
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s ({len(compiles.times)} programs built, "
        f"{len(compiles.hits)} from the persistent cache)")

    # ---- the schedule: lead-in, window, drain ------------------------
    arrivals = traffic.schedule(
        cell.traffic, seed, p["rate_rps"], seconds, lead_in=p["lead_in_s"],
        tail=p["drain_cap_s"], vocab=vocab)
    rid0 = drv.next_rid
    counted = {rid0 + i for i, a in enumerate(arrivals) if a.counted}
    t0 = time.perf_counter() + p["lead_in_s"]
    t_end = t0 + seconds
    snaps, profiler_s = {}, []

    def at_end():
        snaps["end"] = stats_snapshot(eng)
    hooks = {0.0: lambda: snaps.__setitem__("start", stats_snapshot(eng)),
             seconds: at_end}
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

        def trace_on():
            t = time.perf_counter()
            jax.profiler.start_trace(str(TRACE_DIR))
            drv.log_steps = True
            profiler_s.append(time.perf_counter() - t)

        def trace_off():
            at_end()
            t = time.perf_counter()
            drv.log_steps = False
            jax.profiler.stop_trace()
            profiler_s.append(time.perf_counter() - t)
        # stopping the profiler holds the host for seconds, so the traced
        # span closes the window and the stall falls after it
        hooks[seconds - p["trace_s"]], hooks[seconds] = trace_on, trace_off

    def stop(now, i):
        if now < t_end:
            return False
        if all(r in drv.reqs and drv.reqs[r].done for r in counted):
            return True
        return now > t_end + p["drain_cap_s"] or (
            i >= len(arrivals) and not eng.has_work())
    pauses = GcPauses()
    drv.run(arrivals, t0, stop, hooks)
    t_stop = time.perf_counter()
    pauses.remove()
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    if peak is not None:
        device["memory_peak_bytes"] = int(peak)

    if profiler_s:
        log(f"profiler start held the host {profiler_s[0]:.3f} s, "
            f"stop {profiler_s[-1]:.3f} s")
    recs = request_records(drv)
    window = [r for r in recs if r.rid in counted]
    failed = sum(math.isnan(r.finish) for r in window)
    late = np.array([r.submitted - r.due for r in recs
                     if r.segment in ("lead_in", "window")])
    growth = queue_slope(drv.queue, t0, t_end)
    log(f"generator lateness over {len(late)} requests: median "
        f"{np.median(late):.6f} s, p99 {np.percentile(late, 99):.6f} s, "
        f"max {late.max():.6f} s")
    built, hits, secs = compiles.between(t0 - p["lead_in_s"], t0)
    log(f"programs built in the lead-in: {built} ({hits} from the cache, "
        f"{secs:.3f} s)")
    built, hits, secs = compiles.between(t0, t_end)
    log(f"programs built in the window: {built} ({hits} from the cache, "
        f"{secs:.3f} s)")
    gcs = [(d, g) for t, d, g in pauses.spans if t0 <= t < t_end]
    gc_max = max(gcs, default=(0.0, -1))
    log(f"garbage collections in the window: {len(gcs)}, "
        f"{sum(d for d, _ in gcs):.3f} s in all, longest {gc_max[0]:.3f} s "
        f"(generation {gc_max[1]})")
    longest = sorted(((d, t - t0) for t, d in drv.step_times
                      if t0 <= t < t_end), reverse=True)[:3]
    log("longest engine steps in the window: " + ", ".join(
        f"{d:.3f} s at {at:.1f} s" for d, at in longest))
    extra = {"queue_growth_rps": growth, "window_compiles": built,
             "window_compile_s": secs, "gc_max_s": gc_max[0],
             "step_max_s": longest[0][0] if longest else 0.0,
             "lateness_max_s": float(late.max()), "setup_s": setup_s}
    log(f"queue growth over the window: {growth:.4f} requests/s; "
        f"drain ended {t_stop - t_end:.3f} s after the window, "
        f"{failed} of {len(window)} counted requests unfinished")

    ctx = types.SimpleNamespace(
        cell=cell, shapes=Shapes.of(conf), k=conf["engine"]["probe_interval"],
        records=recs, window=window, t0=t0, t_end=t_end, t_stop=t_stop,
        seconds=seconds, setup_s=setup_s, stats=snaps, steps=drv.steps,
        trace=None,
        peaks=spec.peaks(device["kind"]) if platform or trace else None)
    e2e = read_metrics(cell.end_to_end, ctx)
    for name, m in e2e.items():
        log(f"{name}: {m['value']!r} {m['unit']}")

    # ---- the check: engine freed, reference on a sample ---------------
    preempted = {r.rid for r in recs if r.preempts}
    finished = [drv.reqs[r.rid] for r in window if not math.isnan(r.finish)]
    rng = np.random.default_rng([seed % (1 << 64), 7])
    sample = check.draw_sample(finished, rng, p["check_tokens"],
                               p["check_rows"], preempted)
    posteriors = eng.predictor.posteriors
    drv_steps = drv.steps
    del eng, drv, params
    model.__dict__.pop("_engine_jit_cache", None)
    gc.collect()
    ref = spec.reference(conf)
    params = weights.make_params(shapes, seed)
    read = check.readings(ref, params, ref.settings(conf), sample, posteriors,
                          conf["engine"]["max_len"], vocab)
    if control is not None:
        ctrl = check.readings(ref, params, ref.settings(conf), sample,
                              posteriors, conf["engine"]["max_len"], vocab,
                              quant=control)
    del params
    correct, checks = check.verdict(read, p["limits"], failed)
    log(f"compared {read['tokens']} served tokens of {len(sample)} requests")

    result = {"correct": bool(correct), "attempted": len(window),
              "failed": int(failed), "metrics": e2e, "device": device}
    if trace:
        import trace_reduce as tr
        ctx.trace = tr.load(str(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        calls = ctx.trace.programs.get("jit_decode_multi", [0.0, 0])[1]
        logged = sum(1 for st in drv_steps if st.decode_rows)
        log(f"traced {ctx.trace.window_s:.3f} s: decode calls {calls} in the "
            f"trace, {logged} in the harness's step log")
        result["metrics"] = read_metrics(cell.per_layer, ctx)
        result["device"]["busy_s"] = ctx.trace.busy_s
        result["device"]["window_s"] = ctx.trace.window_s
        result["breakdown"] = {"device_ops": ctx.trace.top_ops,
                               "idle_gaps": ctx.trace.idle_gaps}
        for name, m in result["metrics"].items():
            log(f"{name}: {m['value']!r} {m['unit']}")
    result["extra"] = extra
    if control is not None:
        result["program"], result["control"] = read, ctrl
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result


def main(argv=None) -> int:
    """Parse the command line, run the cell, print the result line."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)
    try:
        import jax  # noqa: F401

        import spec
        cell = spec.load_cell(args.workload)
        import repro.serving.engine  # noqa: F401
    except (ImportError, OSError, KeyError) as e:
        log(f"bench: {e!r}; run from the root of a checkout of the repository")
        return 2
    try:
        result = run(cell, args.seed, args.seconds, bool(args.trace), log=log)
    except NoDevice as e:
        log(f"bench: {e}; nothing was measured")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
