"""Operators: how long the task threads stood still in blocking
device-to-host transfers, per traced query: the union of the program's
``host-sync`` spans on each thread, summed over the threads (a span is
written only when the transfer had to wait, for as long as it did)."""

from harness import program_spans as P
from harness.deploy import say

TOP = 5


def begin(run):
    return P.begin(run)


def read(run, since):
    spans = P.for_run(run, since)
    if spans is None:
        return None
    syncs = P.of_kind(spans, "host-sync")
    threads = {s.tid for s in syncs}
    total = sum(P.union_seconds([s for s in syncs if s.tid == t])
                for t in threads)
    by_tag, count = P.totals_by_name(syncs)
    say(f"sync_wait_ms: {len(syncs)} blocking syncs on {len(threads)} "
        f"threads, {total * 1e3:.3f} ms in the window; most wait by tag: "
        + "; ".join(f"{tag} {sec * 1e3:.3f} ms in {count[tag]}"
                    for tag, sec in P.top(by_tag, TOP)))
    return total / run.queries * 1e3
