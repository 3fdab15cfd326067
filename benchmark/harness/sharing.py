"""How the queries of a window shared the server: pure functions over the
program's flight-recorder events (``profiler.events_since`` dicts, on the
recorder's own clock; no device trace and no clock offset is needed, so
overlapping queries cannot spoil a pairing).  Three per-layer readers share
them: ``admission_wait_ms``, ``queries_in_flight``, ``task_cpu_share``.

A program without ``events_since`` gives ``None`` from ``events``; one whose
``task`` events carry no ``cpu_s`` (the parent of PR 34) gives ``None`` from
``cpu_share`` and a value from the other two."""

from __future__ import annotations

from . import program_spans as P
from . import trace as T

# inside a task, the spans during which its thread stood still for a
# reason the program names: a blocking device-to-host transfer, a poll of
# an upstream buffer that had nothing yet
WAITS = ("host-sync", "exchange-wait")


def events(run) -> list | None:
    """The window's events, or None (with the reason on an observation
    line) where the recorder hands out none or dropped some.  Read once
    per run, whichever reader asks first."""
    if hasattr(run, "sharing_events"):
        return run.sharing_events
    from .deploy import say

    run.sharing_events = None
    got = P.recorded(run)
    if got is None:
        say("sharing: the program's recorder hands out no events")
    elif got[1]:
        say(f"sharing: the recorder dropped {got[1]} events of the window "
            f"(ring full: TRINO_TPU_PROFILE_RING); not read")
    else:
        run.sharing_events = got[0]
    return run.sharing_events


def of_kind(evs: list, kind: str) -> list:
    return [e for e in evs if e["kind"] == kind]


def admission_waits(evs: list) -> dict:
    """{query id: seconds from the start of its ``query`` span (POST
    received) to the start of its ``execute`` span (the runner's entry)}:
    the wait for a dispatcher slot, memory admission and the hand-over."""
    posted = {e["query"]: e["ts"] for e in of_kind(evs, "query")}
    return {e["query"]: e["ts"] - posted[e["query"]]
            for e in of_kind(evs, "execute") if e["query"] in posted}


def in_flight_mean(evs: list) -> tuple | None:
    """(time-weighted mean number of open ``execute`` spans, window
    seconds) over the window as the server saw it: the first POST received
    to the last page served (the ``query`` spans; the ``execute`` spans
    where a program records none)."""
    executes = of_kind(evs, "execute")
    edges = of_kind(evs, "query") or executes
    if not executes:
        return None
    t0 = min(e["ts"] for e in edges)
    t1 = max(e["ts"] + e["dur"] for e in edges)
    if t1 <= t0:
        return None
    inside = T.clip([(e["ts"], e["dur"]) for e in executes], t0, t1)
    return sum(d for _, d in inside) / (t1 - t0), t1 - t0


def task_times(evs: list) -> list | None:
    """Per ``task`` event (seconds): its wall, the union of its own
    ``WAITS`` spans inside it, and its ``cpu_s``.  None where a task event
    carries no ``cpu_s``."""
    waits: dict = {}
    for e in evs:
        if e["kind"] in WAITS:
            waits.setdefault((e["query"], e.get("task", "")), []).append(
                (e["ts"], e["dur"]))
    out = []
    for t in of_kind(evs, "task"):
        cpu = (t.get("args") or {}).get("cpu_s")
        if cpu is None:
            return None
        own = T.clip(waits.get((t["query"], t.get("task", "")), []),
                     t["ts"], t["ts"] + t["dur"])
        out.append({"query": t["query"], "task": t.get("task", ""),
                    "wall": t["dur"], "waited": T.busy_seconds(own),
                    "cpu": cpu})
    return out


def cpu_share(times: list) -> float | None:
    """Thread-CPU seconds over the seconds the tasks' threads could have
    run (wall less their named waits), in %."""
    could = sum(t["wall"] - t["waited"] for t in times)
    return 100.0 * sum(t["cpu"] for t in times) / could if could > 0 \
        else None
