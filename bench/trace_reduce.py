"""Reduction of a profiler trace to device busy time, programs and gaps.

A traced run writes ``<dir>/plugins/profile/<time>/*.xplane.pb``.
``read`` takes from it three lists of intervals, in nanoseconds on the
trace's clock:

- device operations: the ``XLA Ops`` line of each ``/device:TPU:<n>``
  plane;
- device programs: the ``XLA Modules`` line of the same planes, named
  by the jitted function (``jit_prefill_chunk``);
- host spans: events on the host plane whose names start with
  ``bench.``, which the harness writes around its calls.

``summarize`` reduces them over the window the host spans cover: the
union of each device's operation intervals (busy time, averaged over
devices), the time and call count of each program, the ten operations
that took most time, and the ten longest idle gaps of the device, each
named by the host span that covers the gap's middle.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
HOST_PREFIX = "bench."


@dataclasses.dataclass
class Interval:
    """One event: a name and [start, end) in nanoseconds."""

    name: str
    start: float
    end: float


@dataclasses.dataclass
class Events:
    """The intervals ``summarize`` reads, device lists keyed by plane."""

    ops: dict
    modules: dict
    host: list


@dataclasses.dataclass
class Summary:
    """What a traced window shows."""

    window_s: float
    busy_s: float
    programs: dict        # base name -> [seconds, calls]
    top_ops: list         # [name, seconds], most time first
    idle_gaps: list       # [host span, seconds], longest first


def xplane_path(trace_dir: str) -> str:
    """The one ``.xplane.pb`` file a trace directory holds."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {len(found)}")
    return found[0]


def read(profile) -> Events:
    """The intervals of a ``jax.profiler.ProfileData``."""
    ops, modules, host = {}, {}, []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = _intervals(line)
                elif line.name == "XLA Modules":
                    modules[plane.name] = _intervals(line)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += [e for e in _intervals(line)
                         if e.name.startswith(HOST_PREFIX)]
    return Events(ops, modules, sorted(host, key=lambda e: e.start))


def _intervals(line) -> list:
    # an XLA op is named by its HLO text, "%fusion.12 = bf16[...] fusion(...)":
    # keep the instruction's name
    return [Interval(e.name.split(" = ")[0].lstrip("%"), e.start_ns,
                     e.start_ns + e.duration_ns) for e in line.events]


def program_name(name: str) -> str:
    """A program's base name: ``jit_decode_multi(12)`` -> ``jit_decode_multi``."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def union(intervals, lo: float, hi: float) -> list:
    """Disjoint [start, end) covering ``intervals`` clipped to [lo, hi)."""
    out = []
    for e in sorted(intervals, key=lambda e: e.start):
        s, t = max(e.start, lo), min(e.end, hi)
        if t <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return out


def summarize(ev: Events) -> Summary:
    """Reduce the intervals over the window the host spans cover."""
    if not ev.host:
        raise ValueError("the trace holds no bench.* host span")
    if not ev.ops:
        raise ValueError("the trace holds no device operation")
    lo = min(e.start for e in ev.host)
    hi = max(e.end for e in ev.host)
    busy, gaps = 0.0, []
    for plane, ops in ev.ops.items():
        u = union(ops, lo, hi)
        busy += sum(t - s for s, t in u)
        edges = [lo] + [x for st in u for x in st] + [hi]
        for s, t in zip(edges[0::2], edges[1::2]):
            if t > s:
                gaps.append((s, t))
    busy /= len(ev.ops)
    if busy <= 0:
        raise ValueError("no device operation inside the window of the host "
                         "spans: the trace's host and device clocks disagree")
    programs = {}
    for mods in ev.modules.values():
        for m in mods:
            if lo <= m.start < hi:
                p = programs.setdefault(program_name(m.name), [0.0, 0])
                p[0] += (m.end - m.start) * 1e-9
                p[1] += 1
    for p in programs.values():
        p[1] //= max(len(ev.modules), 1)
    top = {}
    for ops in ev.ops.values():
        for o in ops:
            if lo <= o.start < hi:
                top[o.name] = top.get(o.name, 0.0) + (o.end - o.start) * 1e-9
    top_ops = sorted(([k, v] for k, v in top.items()), key=lambda kv: -kv[1])
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[_cover(ev.host, (s + t) / 2), (t - s) * 1e-9] for s, t in gaps[:10]]
    return Summary((hi - lo) * 1e-9, busy * 1e-9, programs, top_ops[:10], idle)


def _cover(host, t: float) -> str:
    """The innermost host span covering time ``t``, or ``other``."""
    best = None
    for e in host:
        if e.start <= t < e.end and (best is None or e.start >= best.start):
            best = e
    return best.name if best else "other"


def load(trace_dir: str) -> Summary:
    """Read and reduce the trace a traced run wrote to ``trace_dir``."""
    import jax
    return summarize(read(jax.profiler.ProfileData.from_file(
        xplane_path(trace_dir))))
