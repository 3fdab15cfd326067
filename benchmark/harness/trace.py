"""From a profiler trace to numbers.

``reduce`` and its helpers are pure functions over ``(start, duration,
name)`` intervals in seconds on one clock; ``read_xplane`` is the thin
reader that gets such intervals out of ``jax.profiler.ProfileData``.
Per-program roofline shares, stable program names and spans inside the
program are the next tracing issue's (PERF.md, Open questions)."""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

TOP = 10
NAME_CHARS = 160  # the trace names an operation by its whole HLO line
# where the TPU runtime puts what on a device plane of the trace
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def union(intervals: list) -> list:
    """Sorted, disjoint (start, end) covering the same instants."""
    out: list = []
    for s, e in sorted((s, s + d) for s, d, *_ in intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: list, t0: float, t1: float) -> list:
    """The parts of (start, duration, name) intervals inside [t0, t1]."""
    out = []
    for s, d, *rest in intervals:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((a, b - a, *rest))
    return out


def busy_seconds(intervals: list) -> float:
    return sum(e - s for s, e in union(intervals))


def gaps(intervals: list, t0: float, t1: float) -> list:
    """(start, duration) of every stretch of [t0, t1] no interval covers."""
    out, at = [], t0
    for s, e in union(clip(intervals, t0, t1)):
        if s > at:
            out.append((at, s - at))
        at = max(at, e)
    if t1 > at:
        out.append((at, t1 - at))
    return out


def innermost(spans: dict, t: float) -> str:
    """Name of the host span open at instant ``t`` that started last (the
    innermost of nested spans); 'none' when none is open.  ``spans`` is
    {name: [(start, duration), ...]}."""
    best, best_start = "none", float("-inf")
    for name, items in spans.items():
        for s, d in items:
            if s <= t < s + d and s > best_start:
                best, best_start = name, s
    return best


def timeline(spans: dict) -> tuple:
    """(times, names): ``names[i]`` is the innermost span over
    [times[i], times[i + 1]); before the first and after the last boundary
    nothing is open."""
    times = sorted({t for items in spans.values()
                    for s, d in items for t in (s, s + d)})
    return times, [innermost(spans, t) for t in times]


def attribute(gap: tuple, line: tuple) -> dict:
    """Seconds of one (start, duration) gap per innermost host span, the
    gap cut at every span boundary inside it."""
    times, names = line
    s, e = gap[0], gap[0] + gap[1]
    out: dict = {}
    i = bisect.bisect_right(times, s) - 1
    at = s
    while at < e:
        nxt = times[i + 1] if i + 1 < len(times) else float("inf")
        name = names[i] if 0 <= i < len(names) else "none"
        upto = min(e, nxt)
        if upto > at:
            out[name] = out.get(name, 0.0) + (upto - at)
        at, i = upto, i + 1
    return out


def program_name(execution: str) -> str:
    """``jit_trino_kernels_compact(1234)`` -> ``jit_trino_kernels_compact``."""
    return re.sub(r"\(.*$", "", execution)


def named_by_program(ops: list, programs: list) -> list:
    """Each (start, duration, name) operation renamed ``<program> / <op>``:
    the program execution (``XLA Modules`` line of the same plane) whose
    interval holds the operation's start.  An operation that runs in two
    programs becomes two names; one that no execution holds keeps its own.
    Durations are untouched, so every sum stays what it was."""
    runs = sorted(programs)
    starts = [r[0] for r in runs]
    out = []
    for s, d, name in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][0] + runs[i][1]:
            name = f"{program_name(runs[i][2])} / {name.lstrip('%')}"
        out.append((s, d, name))
    return out


def top_operations(ops: list, n: int = TOP) -> list:
    """[[name, total seconds], ...], the ``n`` largest totals first."""
    total: dict = {}
    for _, d, name in ops:
        total[name] = total.get(name, 0.0) + d
    return [[k[:NAME_CHARS], v] for k, v in
            sorted(total.items(), key=lambda kv: (-kv[1], kv[0]))[:n]]


def idle_gaps_by_span(ops: list, spans: dict, t0: float, t1: float,
                      n: int = TOP) -> list:
    """The ``n`` longest idle gaps of the device inside [t0, t1], each named
    by the innermost host span that covers most of it: [[name, seconds]]."""
    line = timeline(spans)
    longest = sorted(gaps(ops, t0, t1), key=lambda g: -g[1])[:n]
    out = []
    for g in longest:
        parts = attribute(g, line)
        out.append([max(parts, key=parts.get), g[1]])
    return out


def idle_seconds_by_span(ops: list, spans: dict, t0: float, t1: float) -> dict:
    """ALL idle time inside [t0, t1] per innermost host span."""
    line = timeline(spans)
    out: dict = {}
    for g in gaps(ops, t0, t1):
        for k, v in attribute(g, line).items():
            out[k] = out.get(k, 0.0) + v
    return out


@dataclass
class Trace:
    """What the per-layer readers see of one traced window.  Seconds on the
    profiler's clock; ``ops`` and ``programs`` per device plane."""

    ops: dict = field(default_factory=dict)        # plane -> [(s, d, name)]
    programs: dict = field(default_factory=dict)   # plane -> [(s, d, name)]
    spans: dict = field(default_factory=dict)      # name -> [(s, d)]
    window: tuple = (0.0, 0.0)
    seen: list = field(default_factory=list)       # "plane / line: n events"

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Device-busy seconds in the window, averaged over the chips."""
        if not self.ops:
            return 0.0
        return sum(busy_seconds(clip(o, *self.window))
                   for o in self.ops.values()) / len(self.ops)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def program_runs(self) -> int:
        return sum(len(clip(p, *self.window)) for p in self.programs.values())

    def first_plane_ops(self) -> list:
        return clip(self.ops[sorted(self.ops)[0]], *self.window) \
            if self.ops else []

    def breakdown(self) -> dict:
        """``device_ops`` by program: the ledger keeps the front of a name,
        and an HLO line alone does not say whose it is."""
        ops = self.first_plane_ops()
        mine = self.programs.get(sorted(self.ops)[0], []) if self.ops else []
        return {"device_ops": top_operations(named_by_program(ops, mine)),
                "idle_gaps": idle_gaps_by_span(ops, self.spans, *self.window)}


def newest_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def read_xplane(path: str, span_prefix: str, span_names: list,
                window_span: str, host_ops_stat: str | None = None) -> Trace:
    """Device operations and program executions from the device planes, the
    benchmark's own host spans (events named ``span_prefix`` + a name of
    ``span_names``) from the host plane.  The window is from the first
    ``window_span`` span's start to the last one's end.

    ``host_ops_stat`` is for CPU rehearsals only: the CPU backend has no
    device plane, its operations are host-plane events that carry that stat
    (``hlo_op``).  A rehearsal's numbers are never reported as metrics."""
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(path)
    tr = Trace(spans={n: [] for n in span_names})
    for plane in data.planes:
        is_device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            events = list(line.events)
            tr.seen.append(f"{plane.name} / {line.name}: {len(events)}")
            if is_device and line.name in (OPS_LINE, PROGRAMS_LINE):
                into = tr.ops if line.name == OPS_LINE else tr.programs
                into.setdefault(plane.name, []).extend(
                    (e.start_ns / 1e9, e.duration_ns / 1e9, e.name)
                    for e in events)
            elif plane.name == HOST_PLANE:
                for e in events:
                    short = e.name[len(span_prefix):] \
                        if e.name.startswith(span_prefix) else None
                    if short in tr.spans:
                        tr.spans[short].append(
                            (e.start_ns / 1e9, e.duration_ns / 1e9))
                    elif host_ops_stat and any(
                            k == host_ops_stat for k, _ in e.stats):
                        tr.ops.setdefault(plane.name, []).append(
                            (e.start_ns / 1e9, e.duration_ns / 1e9, e.name))
    w = sorted(tr.spans.get(window_span, []))
    if w:
        tr.window = (w[0][0], max(s + d for s, d in w))
    return tr
