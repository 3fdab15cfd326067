"""Statement protocol: what a query waits between its POST and the runner's
entry — a dispatcher slot, memory admission, the hand-over to a pool thread
— per traced query: the start of the program's ``query`` span to the start
of its ``execute`` span (flight recorder, its own clock).  With one client
it is the hand-over alone; with more clients than slots it is the queue."""

from harness import program_spans as P
from harness import sharing
from harness.deploy import say


def begin(run):
    return P.begin(run)


def read(run, since):
    evs = sharing.events(run)
    if evs is None:
        return None
    waits = sharing.admission_waits(evs)
    if not waits:
        return None
    ms = sorted(w * 1e3 for w in waits.values())
    stated = [e["args"]["queued_ms"] for e in sharing.of_kind(evs, "query")
              if "queued_ms" in (e.get("args") or {})]
    say(f"admission_wait_ms: {len(ms)} queries, least {ms[0]:.3f} most "
        f"{ms[-1]:.3f} ms; the program's own queued_ms: "
        + (f"mean {sum(stated) / len(stated):.3f} most {max(stated):.3f} ms "
           f"on {len(stated)} query events" if stated else "not recorded"))
    return sum(ms) / len(ms)
