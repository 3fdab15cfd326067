"""Device: of the seconds the device was idle in the traced window, the
share that no span of the program finer than a task explains — the
innermost open span was a ``task`` (inside a task, under no operator, sync
or launch), an ``execute`` (under no plan, schedule or task), a ``query``
or none.  The program's spans are the flight recorder's, mapped onto the
trace's clock; prints the whole table of idle seconds by innermost kind."""

from harness import program_spans as P
from harness.deploy import say

GAPS = 8    # the longest idle gaps printed, each by innermost span and name


def begin(run):
    return P.begin(run)


def read(run, since):
    spans = P.for_run(run, since)
    ops = run.trace.first_plane_ops()
    if spans is None or not ops:
        return None
    idle = P.idle_seconds_by_kind(ops, spans, *run.trace.window)
    total = sum(idle.values())
    if total <= 0:
        return None
    say(f"idle_unattributed_share: device idle {total:.6f} s of the "
        f"{run.trace.window_s:.6f} s window ({run.queries} queries), by "
        f"innermost program span: "
        + "; ".join(f"{k} {v:.6f} s ({100 * v / total:.1f} %)"
                    for k, v in P.top(idle, len(idle))))
    for seconds, parts in P.longest_idle_gaps(ops, spans,
                                              *run.trace.window, GAPS):
        say(f"idle_unattributed_share: idle gap {seconds * 1e3:.3f} ms under "
            + "; ".join(f"{k} {v * 1e3:.3f} ms" for k, v in parts))
    return 100.0 * sum(idle.get(k, 0.0) for k in P.COARSE) / total
