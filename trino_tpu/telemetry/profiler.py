"""Query flight recorder: per-thread lock-free timeline profiler.

The device-timeline half of the observability plane (the other half is
telemetry/journal.py): every driver thread owns a fixed-capacity ring of
timestamped events — operator enter/exit (exec/driver.py), batch staged
(exec/prefetch.py DeviceStager), fused-region enter/exit
(execution/stage_compiler.py), exchange/collective waits
(execution/exchange.py, remote.py, collective_exchange.py), spill/revoke
(exec/spill.py) and speculation gates (execution/speculation.py).
Recording is one ``time.time()`` call plus a tuple store into the ring —
no contended locks, no device syncs — so the default level keeps the
SyncGuard zero-hot-sync invariant (tests/test_profiler.py asserts it).

The recorder is the program's ONE span system (PR 26): the layer
boundaries — ``query`` (server/protocol.py), ``execute``, ``plan``,
``schedule``, ``task`` (the runners, through execution/tracing.py's
``Tracer.span``), ``operator`` (exec/driver.py), ``host-sync``
(exec/syncguard.py), ``launch`` and ``compile``
(caching/executable_cache.py ``program``) — are all events here, on
``now()``.  The coarse kinds (``ANNOTATED``) also enter a
``jax.profiler.TraceAnnotation("trino.<kind>[:<name>]")``, so an open
profiler session shows the program's rows beside the device's on the
session's own clock; ``operator``, ``launch`` and ``query`` stay
ring-only.  Three attributes say how queries in flight together shared the
process: ``task`` carries ``cpu_s`` (thread-CPU seconds of the task's
thread and its pipeline-group threads; what is left of the wall after it
and the task's ``host-sync`` / ``exchange-wait`` spans, the thread stood
runnable and did not run), ``execute`` carries ``in_flight`` (executions
open on its runner when it began, itself included) and ``query`` carries
``queued_ms`` (POST received to the hand-over to the runner).
``events_since``/``dropped_since`` hand every query's events
from an instant on to a reader that lays them over a device trace
(benchmark/harness/program_spans.py finds the offset between the clocks).

Levels (``TRINO_TPU_PROFILE``):

- ``off``/``0``  — recording disabled entirely.
- ``default``/``1`` (unset) — timestamped wall-time events.  Because the
  exec hot path dispatches asynchronously, an operator event at this level
  credits *dispatch* wall time (exactly like OperatorStats).
- ``full``/``2`` — additionally brackets operator regions with
  ``jax.block_until_ready`` on the produced batch, so the event duration is
  true device time.  This deliberately syncs (counted via SyncGuard under
  the ``profiler.full`` tag) and is opt-in for exactly that reason.

Rings are thread-local; a thread's current (query_id, task_id) context is
stamped onto every event it records, so one worker serving tasks of many
queries still attributes correctly.  Finished queries are *harvested* into
a bounded per-query store, which also accepts remote events shipped back
from worker processes in task status JSON; ``chrome_trace()`` renders the
merged coordinator+worker timeline as Chrome ``trace_event`` JSON
(viewable in Perfetto / chrome://tracing), with real OS pids separating
the processes.
"""

from __future__ import annotations

import os
import threading
import time
import weakref
from collections import OrderedDict, deque
from typing import Optional

__all__ = [
    "OPERATOR", "FUSED", "RESIDENT", "EXCHANGE", "STAGE", "SPILL",
    "SPECULATION", "TASK", "ADAPTIVE", "RECOVERY", "QUERY", "EXECUTE",
    "PLAN", "SCHEDULE", "HOST_SYNC", "LAUNCH", "COMPILE", "ANNOTATED",
    "level", "enabled", "is_full", "set_level", "event", "instant", "span",
    "annotate", "query_event", "events_since", "dropped_since", "find",
    "now", "set_context", "capture_context", "apply_context", "sync_batch",
    "collect", "harvest", "add_remote_events", "take_task_events",
    "events_for", "chrome_trace", "reset_for_test",
]

# event kinds (the ``cat`` field of the chrome trace)
OPERATOR = "operator"
FUSED = "fused-region"
RESIDENT = "resident-plan"  # trino.resident.* whole-plan program track
EXCHANGE = "exchange-wait"
STAGE = "batch-staged"
SPILL = "spill"
SPECULATION = "speculation"
TASK = "task"
ADAPTIVE = "adaptive"
RECOVERY = "recovery"
# the layer boundaries (PR 26), coarsest first
QUERY = "query"          # POST received -> last page served (server)
EXECUTE = "execute"      # inside the runner's execute(): the clock anchor
PLAN = "plan"            # _plan_stmt, and the plan-cache lookup
SCHEDULE = "schedule"    # _execute_subplan: stage set-up, result collection
HOST_SYNC = "host-sync"  # a device->host transfer that blocked: the wait
LAUNCH = "launch"        # host time inside one call of a compiled program
COMPILE = "compile"      # a program compiled, or loaded from the disk cache

# kinds that also enter a jax.profiler.TraceAnnotation (a few dozen a
# query); operator and launch are too many for a trace viewer, and query
# is held open by no one thread
ANNOTATED = frozenset({EXECUTE, PLAN, SCHEDULE, TASK, HOST_SYNC, COMPILE})

_OFF, _DEFAULT, _FULL = 0, 1, 2


def _level_from_env() -> int:
    v = os.environ.get("TRINO_TPU_PROFILE", "").strip().lower()
    if v in ("off", "0", "none", "false"):
        return _OFF
    if v in ("full", "2"):
        return _FULL
    return _DEFAULT


_LEVEL = _level_from_env()
_CAP = int(os.environ.get("TRINO_TPU_PROFILE_RING", "4096"))
_MAX_RINGS = 512       # dead-thread rings retained beyond this are pruned
_DROPPED_KEPT = 1024   # overwritten events a ring remembers the times of
_MAX_PROFILES = 64     # finished-query profiles retained


def level() -> int:
    return _LEVEL


def enabled() -> bool:
    return _LEVEL > _OFF


def is_full() -> bool:
    return _LEVEL >= _FULL


def set_level(lvl: Optional[int]) -> int:
    """Override the profiling level (None re-reads the env); returns the
    previous level so tests can restore it."""
    global _LEVEL
    prev = _LEVEL
    _LEVEL = _level_from_env() if lvl is None else int(lvl)
    return prev


class _Ring:
    """One thread's event ring.  Append is an index store under the GIL —
    no lock; the registry lock is taken once, at ring creation."""

    __slots__ = ("buf", "cap", "idx", "tid", "tname", "thread_ref",
                 "qid", "task", "overwrites", "dropped_ts")

    def __init__(self, cap: int):
        t = threading.current_thread()
        self.buf: list = []
        self.cap = cap
        self.idx = 0
        self.tid = t.ident or 0
        self.tname = t.name
        self.thread_ref = weakref.ref(t)
        self.qid = ""
        self.task = ""
        self.overwrites = 0
        # start times of the newest overwritten events (dropped_since)
        self.dropped_ts: deque = deque(maxlen=_DROPPED_KEPT)

    def push(self, ev: tuple) -> None:
        if len(self.buf) < self.cap:
            self.buf.append(ev)
        else:
            at = self.idx % self.cap
            self.dropped_ts.append(self.buf[at][0])
            self.buf[at] = ev
            self.overwrites += 1
        self.idx += 1


_RINGS: list[_Ring] = []
_RINGS_LOCK = threading.Lock()
_TLS = threading.local()

_PROFILES: "OrderedDict[str, dict]" = OrderedDict()
_PROFILES_LOCK = threading.Lock()


def _ring() -> _Ring:
    r = getattr(_TLS, "ring", None)
    if r is None:
        r = _Ring(_CAP)
        with _RINGS_LOCK:
            _RINGS.append(r)
            if len(_RINGS) > _MAX_RINGS:
                # prune oldest dead-thread rings; live threads always stay
                live = [x for x in _RINGS
                        if (t := x.thread_ref()) is not None and t.is_alive()]
                dead = [x for x in _RINGS if x not in live]
                _RINGS[:] = dead[-(_MAX_RINGS - len(live)):] + live \
                    if len(live) < _MAX_RINGS else live
        _TLS.ring = r
    return r


def now() -> float:
    """Event timebase: epoch seconds (``time.time``) — unlike perf_counter
    it is comparable across coordinator and worker processes on one host,
    which is what lets the merged timeline stitch without offset games."""
    return time.time()


def event(kind: str, name: str, t0: float, t1: Optional[float] = None,
          **args) -> None:
    """Record one complete (begin+duration) event on this thread's ring."""
    if not _LEVEL:
        return
    r = _ring()
    if t1 is None:
        t1 = time.time()
    r.push((t0, t1 - t0, kind, name, r.qid, r.task, args or None))


def instant(kind: str, name: str, **args) -> None:
    if not _LEVEL:
        return
    r = _ring()
    r.push((time.time(), 0.0, kind, name, r.qid, r.task, args or None))


_ANNOTATION = None  # jax.profiler.TraceAnnotation, bound at first use


def _annotation(kind: str, name: str, **args):
    """The ``trino.<kind>[:<name>]`` row of an open profiler session (the
    constructor costs about a microsecond when none is open)."""
    global _ANNOTATION
    if _ANNOTATION is None:
        import jax.profiler

        _ANNOTATION = jax.profiler.TraceAnnotation
    return _ANNOTATION(f"trino.{kind}:{name}" if name else f"trino.{kind}",
                       **args)


def annotate(kind: str, name: str, **args) -> None:
    """A marker row in an open profiler session, for what is only known
    when it is over (a compile: ``jax.monitoring`` reports afterwards)."""
    if _LEVEL:
        with _annotation(kind, name, **args):
            pass


class span:
    """Context-manager form of :func:`event`: one ``now()`` on entry, one
    on exit, one tuple store.  ``t0``/``t1`` are kept (and set at every
    level, ``off`` included) for callers that take their own start and end
    from the recorder's clock (execution/tracing.py ``Span``); ``set`` adds
    attributes before the exit."""

    __slots__ = ("kind", "name", "args", "t0", "t1", "_ann")

    def __init__(self, kind: str, name: str = "", **args):
        self.kind = kind
        self.name = name
        self.args = args
        self.t0 = self.t1 = 0.0
        self._ann = None

    def set(self, **args) -> "span":
        self.args.update(args)
        return self

    def __enter__(self) -> "span":
        if _LEVEL and self.kind in ANNOTATED:
            self._ann = _annotation(self.kind, self.name)
            self._ann.__enter__()
        self.t0 = time.time()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.t1 = time.time()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        event(self.kind, self.name, self.t0, self.t1, **self.args)


def set_context(query_id: str, task_id: str = "") -> tuple:
    """Stamp the calling thread's (query, task) identity onto subsequent
    events; returns the previous context for restore."""
    r = _ring()
    prev = (r.qid, r.task)
    r.qid, r.task = query_id or "", task_id or ""
    return prev


def capture_context() -> tuple:
    r = getattr(_TLS, "ring", None)
    return (r.qid, r.task) if r is not None else ("", "")


def apply_context(ctx: tuple) -> None:
    """Adopt a context captured on another thread (driver group threads
    inherit the spawning task thread's identity)."""
    r = _ring()
    r.qid, r.task = ctx


def sync_batch(batch) -> None:
    """``TRINO_TPU_PROFILE=full`` only: block until the batch's device
    buffers are ready so the enclosing operator event charges true device
    time instead of async dispatch time.  Deliberately a blocking sync —
    counted through SyncGuard so the cost stays attributed."""
    if _LEVEL < _FULL or batch is None:
        return
    try:
        import jax

        from ..exec import syncguard as SG

        for c in getattr(batch, "columns", ()):
            data = getattr(c, "data", None)
            if data is not None and not hasattr(data, "ctypes"):
                SG.count_sync("profiler.full", blocking=True)
                jax.block_until_ready(data)  # sync-ok: opt-in full profile
    except Exception:  # noqa: BLE001 — profiling never fails a query
        pass


# ------------------------------------------------------------------ export


def _ev_dict(ev: tuple, pid: int, tid: int, tname: str) -> dict:
    d = {"ts": ev[0], "dur": ev[1], "kind": ev[2], "name": ev[3],
         "task": ev[5], "pid": pid, "tid": tid, "thread": tname}
    if ev[6]:
        d["args"] = ev[6]
    return d


def collect(query_id: str, task_id: Optional[str] = None) -> list[dict]:
    """Non-destructive sweep of every ring for one query's events (rings
    keep their contents; wrap-around is the only eviction)."""
    with _RINGS_LOCK:
        rings = list(_RINGS)
    pid = os.getpid()
    out = []
    for r in rings:
        for ev in list(r.buf):
            if ev is not None and ev[4] == query_id and \
                    (task_id is None or ev[5] == task_id):
                out.append(_ev_dict(ev, pid, r.tid, r.tname))
    return out


def _store(query_id: str) -> dict:
    p = _PROFILES.get(query_id)
    if p is None:
        p = {"events": [], "procs": {}}
        _PROFILES[query_id] = p
        while len(_PROFILES) > _MAX_PROFILES:
            _PROFILES.popitem(last=False)
    else:
        _PROFILES.move_to_end(query_id)
    return p


def harvest(query_id: str, process_name: str = "coordinator") -> int:
    """Copy this process's ring events for ``query_id`` into the bounded
    per-query store (run at query completion, before rings wrap)."""
    if not query_id:
        return 0
    evs = collect(query_id)
    overwrites = 0
    with _RINGS_LOCK:
        for r in _RINGS:
            overwrites += r.overwrites
            r.overwrites = 0
    from . import metrics as tm

    if evs:
        tm.PROFILE_EVENTS.inc(len(evs))
    if overwrites:
        tm.PROFILE_DROPPED.inc(overwrites)
    with _PROFILES_LOCK:
        p = _store(query_id)
        p["events"].extend(evs)
        p["procs"][str(os.getpid())] = process_name
    return len(evs)


def add_remote_events(query_id: str, events: list[dict],
                      process_name: str = "worker") -> None:
    """Fold a worker ring (shipped back in task status JSON) into the
    query's profile; events already carry the worker's pid/tid."""
    if not query_id or not events:
        return
    with _PROFILES_LOCK:
        p = _store(query_id)
        p["events"].extend(events)
        for ev in events:
            pid = str(ev.get("pid", ""))
            if pid and pid not in p["procs"]:
                p["procs"][pid] = process_name


def take_task_events(query_id: str, task_id: str,
                     limit: int = 2000) -> list[dict]:
    """A worker task's events, bounded for the status-JSON wire (newest
    kept — the tail of a truncated timeline is where failures live)."""
    evs = collect(query_id, task_id)
    evs.sort(key=lambda e: e["ts"])
    return evs[-limit:]


def events_for(query_id: str) -> list[dict]:
    with _PROFILES_LOCK:
        p = _PROFILES.get(query_id)
        stored = list(p["events"]) if p is not None else []
        procs = dict(p["procs"]) if p is not None else {}
    if not stored:
        # live query: render straight from the rings
        stored = collect(query_id)
        if stored:
            procs[str(os.getpid())] = "coordinator"
    return stored


def query_event(query_id: str, t0: float, t1: float, **args) -> None:
    """The ``query`` span of the statement protocol: POST received to last
    page served.  It crosses handler threads, so it is written once, as a
    complete event, straight into the query's stored profile (the query's
    ring events were harvested when its execution ended)."""
    if not _LEVEL or not query_id:
        return
    t = threading.current_thread()
    ev = (t0, t1 - t0, QUERY, query_id, query_id, "", args or None)
    with _PROFILES_LOCK:
        p = _store(query_id)
        p["events"].append(_ev_dict(ev, os.getpid(), t.ident or 0, t.name))
        p["procs"].setdefault(str(os.getpid()), "coordinator")


def find(query_id: str, kind: str) -> list[dict]:
    """A finished query's stored events of one kind (the statement
    protocol reads its ``execute`` span for ``stats``)."""
    with _PROFILES_LOCK:
        p = _PROFILES.get(query_id)
        return [e for e in p["events"] if e["kind"] == kind] \
            if p is not None else []


def events_since(t: float) -> list[dict]:
    """Every event of this process that started at or after instant ``t``
    (on ``now()``'s clock), whatever its query: the stored profiles and the
    live rings together, each event once, oldest first.  For a reader that
    lays the program's spans over a device trace of the same stretch."""
    with _RINGS_LOCK:
        rings = list(_RINGS)
    pid = os.getpid()
    out: dict = {}
    with _PROFILES_LOCK:
        for qid, p in _PROFILES.items():
            for e in p["events"]:
                if e["ts"] >= t and e.get("pid", pid) == pid:
                    out[(e["tid"], e["ts"], e["kind"], e["name"])] = \
                        e | {"query": qid}
    for r in rings:
        for ev in list(r.buf):
            if ev[0] >= t:
                out.setdefault((r.tid, ev[0], ev[2], ev[3]),
                               _ev_dict(ev, pid, r.tid, r.tname)
                               | {"query": ev[4]})
    return sorted(out.values(), key=lambda e: e["ts"])


def dropped_since(t: float) -> int:
    """Events that started at or after ``t`` and were overwritten in their
    ring before any harvest stored them (each ring remembers the start
    times of its newest ``_DROPPED_KEPT`` overwritten events; a long-lived
    thread's ring wraps all the time, and what it overwrites is old)."""
    with _RINGS_LOCK:
        rings = list(_RINGS)
    lost = [(r.tid, ts) for r in rings for ts in list(r.dropped_ts)
            if ts >= t]
    if not lost:
        return 0
    with _PROFILES_LOCK:
        kept = {(e["tid"], e["ts"]) for p in _PROFILES.values()
                for e in p["events"] if e["ts"] >= t}
    return sum(1 for key in lost if key not in kept)


def chrome_trace(query_id: str) -> Optional[dict]:
    """The merged timeline as Chrome ``trace_event`` JSON ("X" complete
    events, microsecond timestamps normalized to the query's first event),
    or None for an unknown/unprofiled query."""
    with _PROFILES_LOCK:
        p = _PROFILES.get(query_id)
        events = list(p["events"]) if p is not None else []
        procs = dict(p["procs"]) if p is not None else {}
    if not events:
        events = collect(query_id)
        if events:
            procs[str(os.getpid())] = "coordinator"
    if not events:
        return None
    t0 = min(e["ts"] for e in events)
    trace: list[dict] = []
    seen_procs: dict = {}
    seen_threads: set = set()
    for e in sorted(events, key=lambda e: e["ts"]):
        pid = int(e.get("pid", 0))
        tid = int(e.get("tid", 0))
        if pid not in seen_procs:
            name = procs.get(str(pid), "process")
            seen_procs[pid] = name
            trace.append({"ph": "M", "name": "process_name", "pid": pid,
                          "tid": 0, "args": {"name": name}})
        if (pid, tid) not in seen_threads:
            seen_threads.add((pid, tid))
            trace.append({"ph": "M", "name": "thread_name", "pid": pid,
                          "tid": tid,
                          "args": {"name": e.get("thread", str(tid))}})
        out = {"name": e["name"], "cat": e["kind"], "ph": "X",
               "ts": (e["ts"] - t0) * 1e6, "dur": max(e["dur"], 0.0) * 1e6,
               "pid": pid, "tid": tid}
        args = dict(e.get("args") or {})
        if e.get("task"):
            args["task"] = e["task"]
        if args:
            out["args"] = args
        trace.append(out)
    return {"traceEvents": trace, "displayTimeUnit": "ms",
            "otherData": {"query_id": query_id,
                          "processes": {str(k): v
                                        for k, v in seen_procs.items()}}}


def reset_for_test() -> None:
    """Drop all rings, contexts and stored profiles (test isolation)."""
    global _RINGS
    with _RINGS_LOCK:
        _RINGS = []
    with _PROFILES_LOCK:
        _PROFILES.clear()
    _TLS.__dict__.clear()
