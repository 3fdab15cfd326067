"""The program's own spans, laid over the device trace.

The program times its layer boundaries itself, in its flight recorder
(``trino_tpu/telemetry/profiler.py``: ``query``, ``execute``, ``plan``,
``schedule``, ``task``, ``operator``, ``host-sync``, ``launch``,
``compile`` ...), on the recorder's clock (epoch seconds).  The device
trace runs on the profiler session's clock.  One boundary is on both: the
runner's ``execute``, wrapped from outside by the benchmark
(``bench.execute``, trace clock) and recorded from inside by the program
(``execute``, recorder clock).  ``offset`` pairs the k-th of one with the
k-th of the other and takes the median difference of their starts;
``mapped`` then moves every recorder event onto the trace's clock, where
``harness/trace.py``'s reduction can attribute the device's idle time to it.

Everything up to ``for_run`` is a pure function over intervals; ``begin``
and ``for_run`` are the two calls the per-layer readers share.  A program
without ``events_since`` (the parent of PR 26) gives ``None`` everywhere.
"""

from __future__ import annotations

import heapq
import statistics
from dataclasses import dataclass, field

from . import trace as T

ANCHOR_SPAN = "execute"     # the benchmark's span around the runner's entry
ANCHOR_KIND = "execute"     # the recorder's kind for the same boundary
MAX_SPREAD_S = 0.5e-3       # widest per-query differences an offset may have
# a device-idle second under one of these is not explained by any span
# finer than a task: inside a task but under no operator, sync or launch;
# inside execute but under no plan, schedule or task; or outside the program
COARSE = ("task", "execute", "query", "none")


@dataclass
class Offset:
    """``trace = recorder + seconds``; ``seconds`` is None with the reason
    in ``why`` when the two clocks could not be matched."""

    seconds: float | None
    spread: float = 0.0
    pairs: int = 0
    why: str = ""


def offset(outside: list, inside: list,
           max_spread: float = MAX_SPREAD_S) -> Offset:
    """``outside``: (start, duration) of the benchmark's spans on the trace
    clock; ``inside``: the same boundary's on the recorder's clock.  The
    k-th of each in time order are one call."""
    if not outside or len(outside) != len(inside):
        return Offset(None, pairs=min(len(outside), len(inside)),
                      why=f"{len(outside)} spans outside, {len(inside)} "
                          f"inside: not the same calls")
    diffs = [o[0] - i[0] for o, i in zip(sorted(outside), sorted(inside))]
    spread = max(diffs) - min(diffs)
    if spread > max_spread:
        return Offset(None, spread, len(diffs),
                      f"per-call differences spread {spread * 1e3:.3f} ms, "
                      f"over {max_spread * 1e3:.3f} ms")
    return Offset(statistics.median(diffs), spread, len(diffs))


@dataclass(frozen=True)
class Span:
    start: float        # seconds on the trace's clock
    seconds: float
    kind: str
    name: str
    tid: int
    query: str
    task: str = ""
    args: dict = field(default_factory=dict)

    @property
    def end(self) -> float:
        return self.start + self.seconds


def mapped(events: list, off: float, t0: float, t1: float) -> list:
    """Recorder events (``profiler.events_since`` dicts) as ``Span``s on the
    trace's clock, those that touch [t0, t1] only, oldest first."""
    out = []
    for e in events:
        s = e["ts"] + off
        if s < t1 and s + e["dur"] > t0:
            out.append(Span(s, e["dur"], e["kind"], e["name"], e["tid"],
                            e.get("query", ""), e.get("task", ""),
                            e.get("args") or {}))
    return sorted(out, key=lambda x: x.start)


def of_kind(spans: list, kind: str) -> list:
    return [s for s in spans if s.kind == kind]


def by_kind(spans: list) -> dict:
    """{kind: [(start, seconds)]}: the shape ``harness/trace``'s span
    functions take."""
    out: dict = {}
    for s in spans:
        out.setdefault(s.kind, []).append((s.start, s.seconds))
    return out


def union_seconds(spans: list) -> float:
    return T.busy_seconds([(s.start, s.seconds) for s in spans])


# how deep a kind lies in the program: the tasks of a query run on threads
# of their own, so "the span that started last" (harness/trace.innermost)
# would let a task that starts late on one thread hide an operator that is
# at work on another; the deepest kind open on ANY thread explains the
# instant, and among equals the one that started last.  A thread that
# stands in an exchange poll explains nothing while another is at work (the
# final stage's task polls all through the leaf stage): it ranks with its
# task, below every operator
DEPTH = {"query": 0, "execute": 1, "plan": 2, "schedule": 2, "task": 3,
         "exchange-wait": 3, "host-sync": 5, "launch": 5, "compile": 5}
OTHER_DEPTH = 4     # operator, batch-staged, fused-region, spill ...


def timeline(spans: dict, depth: dict | None = None) -> tuple:
    """``harness/trace.timeline`` (the innermost open span between every two
    boundaries) in one sweep with a heap, for the thousands of operator and
    launch spans a query has; the original asks every span at every
    boundary.  Without ``depth`` the innermost is the span that started
    last, as there; with it, the deepest kind wins first."""
    def rank(name, start):
        # a name may carry its kind in front: "host-sync:agg.live"
        return (-(depth.get(name.partition(":")[0], OTHER_DEPTH)
                  if depth else 0), -start)

    items = sorted((s, s + d, name) for name, ivs in spans.items()
                   for s, d in ivs if d > 0)
    times = sorted({t for s, e, _ in items for t in (s, e)})
    names, heap, at = [], [], 0
    for t in times:
        while at < len(items) and items[at][0] <= t:
            s, e, name = items[at]
            heapq.heappush(heap, (rank(name, s), e, name))
            at += 1
        # an ended span may sit below the top; it is dropped when it
        # surfaces, so only the top has to be checked
        while heap and heap[0][1] <= t:
            heapq.heappop(heap)
        names.append(heap[0][2] if heap else "none")
    return times, names


def idle_seconds_by_kind(ops: list, spans: list, t0: float, t1: float) -> dict:
    """``harness/trace.idle_seconds_by_span`` over program spans: every idle
    second of the device in [t0, t1] under the kind of the innermost span
    open then (``DEPTH``; 'none' outside all)."""
    line = timeline(by_kind(spans), DEPTH)
    out: dict = {}
    for g in T.gaps(ops, t0, t1):
        for k, v in T.attribute(g, line).items():
            out[k] = out.get(k, 0.0) + v
    return out


def longest_idle_gaps(ops: list, spans: list, t0: float, t1: float,
                      n: int) -> list:
    """The ``n`` longest idle gaps of the device in [t0, t1], each with its
    seconds per innermost program span, by kind AND name, largest first:
    [(seconds, [("operator:AggOperator.finish", seconds), ...])]."""
    named: dict = {}
    for s in spans:
        named.setdefault(f"{s.kind}:{s.name}", []).append(
            (s.start, s.seconds))
    line = timeline(named, DEPTH)
    return [(g[1], top(T.attribute(g, line), 6))
            for g in sorted(T.gaps(ops, t0, t1), key=lambda g: -g[1])[:n]]


def totals_by_name(spans: list) -> tuple:
    """({name: seconds}, {name: how many}) over spans."""
    seconds, count = {}, {}
    for s in spans:
        seconds[s.name] = seconds.get(s.name, 0.0) + s.seconds
        count[s.name] = count.get(s.name, 0) + 1
    return seconds, count


def top(totals: dict, n: int) -> list:
    return sorted(totals.items(), key=lambda kv: (-kv[1], kv[0]))[:n]


# ------------------------------------------------- the readers' two calls

def _recorder():
    """The program's flight recorder, or None where it cannot hand out its
    events (a program from before PR 26)."""
    try:
        from trino_tpu.telemetry import profiler
    except ImportError:
        return None
    return profiler if hasattr(profiler, "events_since") else None


def begin(run):
    """Before the window: the instant the window's events start from.  One
    instant per run, whichever reader asks first."""
    if not hasattr(run, "program_spans_from"):
        rec = _recorder()
        run.program_spans_from = rec.now() if rec is not None else None
    return run.program_spans_from


def recorded(run) -> tuple | None:
    """After any window: (events, how many the rings dropped) since
    ``begin``, on the recorder's own clock — for a reader that wants
    durations only.  None where the recorder hands out no events.  The
    recorder keeps a bounded number of finished queries: a long window's
    events are those of its newest queries."""
    rec = _recorder()
    since = getattr(run, "program_spans_from", None)
    if rec is None or since is None:
        return None
    return rec.events_since(since), rec.dropped_since(since)


def for_run(run, since) -> list | None:
    """After a traced window: the program's spans inside it, on the trace's
    clock; None (with the reason on an observation line) when the program
    records none, dropped some, or the clocks do not match.  Computed once
    per run."""
    if hasattr(run, "program_spans"):
        return run.program_spans
    from .deploy import say

    run.program_spans = None
    rec = _recorder()
    if rec is None or since is None or run.trace is None:
        say("program spans: the program's recorder hands out no events "
            "(no events_since); the metrics that read them are left out")
        return None
    events = rec.events_since(since)
    dropped = rec.dropped_since(since)
    if dropped:
        say(f"program spans: the recorder dropped {dropped} events of the "
            f"window (ring full: TRINO_TPU_PROFILE_RING); not read")
        return None
    inside = [(e["ts"], e["dur"]) for e in events
              if e["kind"] == ANCHOR_KIND]
    off = offset(run.trace.spans.get(ANCHOR_SPAN, []), inside)
    if off.seconds is None:
        say(f"program spans: no offset between the clocks: {off.why}")
        return None
    run.program_spans = mapped(events, off.seconds, *run.trace.window)
    kinds: dict = {}
    for s in run.program_spans:
        kinds[s.kind] = kinds.get(s.kind, 0) + 1
    say(f"program spans: {len(run.program_spans)} in the window, 0 dropped; "
        f"trace = recorder + {off.seconds:.6f} s from {off.pairs} execute "
        f"pairs, spread {off.spread * 1e6:.0f} us; by kind: "
        + " ".join(f"{k}={v}" for k, v in sorted(kinds.items())))
    return run.program_spans
