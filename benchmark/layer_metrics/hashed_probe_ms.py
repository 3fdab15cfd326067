"""Operators: how long the hashed join's probes took on the host, per
traced query: the union of the program's ``join-probe`` spans whose
``path`` is ``hashed`` on each thread, summed over the threads (one span a
probe page through the sorted-hash build table; pages through a
direct-address table are left out).  Prints the hashed pages and
milliseconds by the probe's place and key count.  None on a program that
records no such span."""

from harness import program_spans as P
from harness import turn
from harness.deploy import say


def begin(run):
    return P.begin(run)


def read(run, since):
    got = turn.spans(run, "join-probe")
    if got is None:
        return None
    hashed = [e for e in got["join-probe"]
              if (e.get("args") or {}).get("path") == "hashed"]
    by: dict = {}
    for e in hashed:
        a = e["args"]
        key = (a.get("join"), a.get("keys"))
        n, s = by.get(key, (0, 0.0))
        by[key] = (n + 1, s + e["dur"])
    say(f"hashed_probe_ms: {len(hashed)} hashed probe pages of "
        f"{len(got['join-probe'])} in the window of {run.queries} queries; "
        f"by (place, keys): "
        + ("; ".join(f"{k[0]} {k[1]}: {n} pages, {s * 1e3:.3f} ms"
                     for k, (n, s) in sorted(by.items(), key=str))
           or "none"))
    return turn.union_by_thread(hashed) / run.queries * 1e3
