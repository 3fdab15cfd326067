"""Operators: how long the hash join's probes took on the host, per traced
query: the union of the program's ``join-probe`` spans on each thread,
summed over the threads (one span a probe page of a ``LookupJoinOperator``,
from its entry to its return: the probe's programs launched, a gather's
width planned).  Prints the pages and milliseconds by path and by the
probe's place in its pipeline.  None on a program that records no such
span."""

from harness import program_spans as P
from harness import turn
from harness.deploy import say


def begin(run):
    return P.begin(run)


def read(run, since):
    got = turn.spans(run, "join-probe")
    if got is None:
        return None
    probes = got["join-probe"]
    by: dict = {}
    for e in probes:
        a = e.get("args") or {}
        key = (a.get("join"), a.get("path"), a.get("keys"))
        n, s = by.get(key, (0, 0.0))
        by[key] = (n + 1, s + e["dur"])
    say(f"join_probe_ms: {len(probes)} probe pages in the window of "
        f"{run.queries} queries; by (place, path, keys): "
        + ("; ".join(f"{k[0]} {k[1]} {k[2]}: {n} pages, {s * 1e3:.3f} ms"
                     for k, (n, s) in sorted(by.items(), key=str))
           or "none"))
    return turn.union_by_thread(probes) / run.queries * 1e3
