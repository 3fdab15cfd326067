"""Compiled programs: host time inside the calls of the engine's compiled
programs, per traced query: the sum of the program's ``launch`` spans (one
per call of a ``program(name)`` wrapper: argument handling, dispatch and,
where the runtime's queue is full, the wait for room)."""

from harness import program_spans as P
from harness.deploy import say

TOP = 5


def begin(run):
    return P.begin(run)


def read(run, since):
    spans = P.for_run(run, since)
    if spans is None:
        return None
    launches = P.of_kind(spans, "launch")
    by_name, count = P.totals_by_name(launches)
    total = sum(by_name.values())
    say(f"launch_ms: {len(launches)} launches, {total * 1e3:.3f} ms of host "
        f"time in the window; most by program: "
        + "; ".join(f"{name} {sec * 1e3:.3f} ms in {count[name]} "
                    f"(mean {sec / count[name] * 1e6:.0f} us)"
                    for name, sec in P.top(by_name, TOP)))
    return total / run.queries * 1e3
