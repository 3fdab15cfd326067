"""Dispatch and schedule: how many executions the runner had open at once —
the time-weighted mean number of open ``execute`` spans over the traced
window as the server saw it (first POST received to last page served;
flight recorder, its own clock).  As many as there are clients is the most
a closed loop can give; what is missing is spent outside the runner: the
protocol, the client, and the tail in which the streams end one by one."""

from harness import program_spans as P
from harness import sharing
from harness.deploy import say


def begin(run):
    return P.begin(run)


def read(run, since):
    evs = sharing.events(run)
    if evs is None:
        return None
    got = sharing.in_flight_mean(evs)
    if got is None:
        return None
    mean, window_s = got
    at_start = [e["args"]["in_flight"] for e in sharing.of_kind(evs, "execute")
                if "in_flight" in (e.get("args") or {})]
    say(f"queries_in_flight: mean {mean:.3f} open executions over a "
        f"{window_s:.3f} s window of {len(sharing.of_kind(evs, 'execute'))} "
        f"queries; in_flight at each start: "
        + (" ".join(str(n) for n in at_start) if at_start
           else "not recorded"))
    return mean
