"""Operators: how long the hash join's builds took on the host, per traced
query: the union of the program's ``join-build`` spans on each thread,
summed over the threads (one span a build table, from
``JoinBuildSink.finish_input``'s entry to its return: the pages
concatenated, the table's programs launched and, for a single integer key,
its scalars fetched and the direct-address table made).  Prints the builds
by path with their lanes.  None on a program that records no such span."""

from harness import program_spans as P
from harness import turn
from harness.deploy import say


def begin(run):
    return P.begin(run)


def read(run, since):
    got = turn.spans(run, "join-build")
    if got is None:
        return None
    builds = got["join-build"]
    by: dict = {}
    for e in builds:
        a = e.get("args") or {}
        key = (a.get("path"), a.get("keys"), a.get("lanes"))
        n, s = by.get(key, (0, 0.0))
        by[key] = (n + 1, s + e["dur"])
    say(f"join_build_ms: {len(builds)} builds in the window of "
        f"{run.queries} queries; by (path, keys, lanes): "
        + ("; ".join(f"{k[0]} {k[1]} {k[2]}: {n} builds, {s * 1e3:.3f} ms"
                     for k, (n, s) in sorted(by.items(), key=str))
           or "none"))
    return turn.union_by_thread(builds) / run.queries * 1e3
