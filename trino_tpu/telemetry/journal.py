"""Durable query journal: rotating JSONL of query lifecycle events.

The persistence half of the flight recorder (the timeline half is
telemetry/profiler.py): a ``QueryJournal`` is an ``EventListener`` plugin
that appends one JSON line per QueryCreated/QueryCompleted event — the
full QueryStats rollup, plan fingerprint, resource group and error code —
to a size-bounded, rotating journal file.  The reference persists the same
record through its event-listener plugins (mysql-event-listener /
http-event-listener); here the sink is local disk because the journal is
also *read back*:

- ``system.runtime.query_history`` (connectors/system.py) scans it through
  the ordinary Connector SPI, so pre-restart queries stay SQL-queryable;
- ``resource_manager.estimate_peak_memory`` falls back to
  :func:`seeded_peak` when the in-process registry has no history for a
  plan fingerprint, turning the PR 8 admission estimator from per-process
  folklore into memory that survives coordinator restarts.

Knobs: ``TRINO_TPU_JOURNAL_DIR`` (location; default a per-uid tempdir),
``TRINO_TPU_JOURNAL_MAX_BYTES`` (rotate threshold per file, default 4 MiB),
``TRINO_TPU_JOURNAL_FILES`` (rotated generations kept, default 3),
``TRINO_TPU_JOURNAL=0`` (disable).  Every record carries a versioned
``schema`` field; tools/lint_journal_schema.py enforces the contract.
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
from typing import Optional

from ..spi.eventlistener import (
    EventListener,
    QueryCompletedEvent,
    QueryCreatedEvent,
)

__all__ = [
    "SCHEMA_VERSION", "REQUIRED_FIELDS", "PLAN_STATS_FIELDS", "QueryJournal",
    "JournalFollower", "default_dir", "journal_enabled", "get_journal",
    "history", "seeded_peak", "sample_records", "reset_for_test",
]

# v2: adds the per-query ``plan_stats`` event — observed per-plan-node
# stats (rows/bytes/groups/skew keyed by logical node fingerprint) that
# planner/history.py feeds back into the cost model on the next planning
# of the same query shape
SCHEMA_VERSION = 2
# every journal record, of any event type, carries at least these
REQUIRED_FIELDS = ("schema", "event", "ts", "query_id")

# the scalar stats a plan_stats node entry may carry (all optional)
PLAN_STATS_FIELDS = ("rows", "bytes", "groups", "skew")

_FILE = "query_journal.jsonl"


def _safe_node(node: str) -> str:
    return "".join(c if c.isalnum() or c in "_.-" else "_" for c in node)


def default_dir() -> str:
    try:
        uid = os.getuid()
    except AttributeError:  # non-POSIX
        uid = 0
    return os.path.join(tempfile.gettempdir(), f"trino-tpu-journal-{uid}")


def journal_enabled() -> bool:
    return os.environ.get("TRINO_TPU_JOURNAL", "1").strip().lower() \
        not in ("0", "off", "false", "no")


def _record_from_created(ev: QueryCreatedEvent) -> dict:
    return {
        "schema": SCHEMA_VERSION,
        "event": "query_created",
        "ts": ev.create_time,
        "query_id": ev.query_id,
        "sql": ev.sql,
        "user": ev.user,
    }


def _record_from_completed(ev: QueryCompletedEvent) -> dict:
    from . import runtime as rt

    return {
        "schema": SCHEMA_VERSION,
        "event": "query_completed",
        "ts": ev.end_time,
        "query_id": ev.query_id,
        "sql": ev.sql,
        "user": ev.user,
        "state": ev.state,
        "wall_ms": float(ev.wall_ms),
        "cpu_ms": float(ev.cpu_ms),
        "output_rows": int(ev.output_rows),
        "input_rows": int(ev.input_rows),
        "input_bytes": int(ev.input_bytes),
        "retry_count": int(ev.retry_count),
        "peak_memory_bytes": int(ev.peak_memory_bytes),
        "queued_time_ms": float(ev.queued_time_ms),
        "resource_group": ev.resource_group,
        "speculative_wins": int(ev.speculative_wins),
        "error": None if ev.error is None else str(ev.error),
        "error_code": ev.error_code,
        "fingerprint": rt.fingerprint(ev.sql),
    }


def _record_plan_stats(query_id: str, fingerprint: str,
                       nodes: dict, ts: float) -> dict:
    """``nodes`` maps logical plan-node fingerprint (planner/history.py)
    -> {rows, bytes, groups, skew} (each scalar optional)."""
    return {
        "schema": SCHEMA_VERSION,
        "event": "plan_stats",
        "ts": ts,
        "query_id": query_id,
        "fingerprint": fingerprint,
        "nodes": nodes,
    }


def sample_records() -> list[dict]:
    """One representative record per event type the journal can emit —
    the corpus tools/lint_journal_schema.py validates."""
    created = _record_from_created(
        QueryCreatedEvent("q_sample", "SELECT 1", user="lint"))
    ok = _record_from_completed(QueryCompletedEvent(
        "q_sample", "SELECT 1", state="FINISHED", user="lint",
        wall_ms=1.5, output_rows=1, cpu_ms=0.5, peak_memory_bytes=1 << 20,
        input_rows=10, input_bytes=100, retry_count=0, queued_time_ms=0.25,
        resource_group="global.adhoc", speculative_wins=1))
    failed = _record_from_completed(QueryCompletedEvent(
        "q_sample2", "SELECT 1/0", state="FAILED", user="lint",
        error="DIVISION_BY_ZERO: division by zero",
        error_code="DIVISION_BY_ZERO"))
    blacklist = {
        "schema": SCHEMA_VERSION,
        "event": "blacklist_entry",
        "ts": 1700000000.0,
        "query_id": "q_sample2",
        "worker": "worker-1",
        "weight": 1.0,
        "reason": "INTERNAL: injected task failure",
    }
    plan_stats = _record_plan_stats(
        "q_sample", "a2f1c3d4",
        {"e3b0c442": {"rows": 450000, "bytes": 7340032, "skew": 1.25},
         "9f86d081": {"rows": 45000, "bytes": 524288},
         "31b2e8c0": {"groups": 1024}},
        ts=1700000000.0)
    return [created, ok, failed, blacklist, plan_stats]


class QueryJournal(EventListener):
    """Size-bounded rotating JSONL sink + reader."""

    def __init__(self, directory: Optional[str] = None,
                 max_bytes: Optional[int] = None,
                 max_files: Optional[int] = None):
        self.directory = directory or \
            os.environ.get("TRINO_TPU_JOURNAL_DIR") or default_dir()
        self.max_bytes = max_bytes if max_bytes is not None else int(
            os.environ.get("TRINO_TPU_JOURNAL_MAX_BYTES", str(4 << 20)))
        self.max_files = max_files if max_files is not None else int(
            os.environ.get("TRINO_TPU_JOURNAL_FILES", "3"))
        # a coordinator fleet shares one TRINO_TPU_JOURNAL_DIR: each member
        # appends to its OWN stream (cross-process appends to one file would
        # race its rotation) and readers fold every member's stream
        node = os.environ.get("TRINO_TPU_HA_NODE_ID", "").strip()
        name = _FILE if not node else \
            _FILE[:-len(".jsonl")] + "-" + _safe_node(node) + ".jsonl"
        self.path = os.path.join(self.directory, name)
        self._lock = threading.Lock()
        # first write of this process checks for a torn tail line (a crash
        # mid-write); appending straight onto it would corrupt the next
        # record too, so a newline is inserted first
        self._tail_checked = False

    # ------------------------------------------------------- listener side
    def query_created(self, event: QueryCreatedEvent) -> None:
        self._write(_record_from_created(event))

    def query_completed(self, event: QueryCompletedEvent) -> None:
        self._write(_record_from_completed(event))

    def plan_stats(self, query_id: str, fingerprint: str,
                   nodes: dict, ts: float) -> None:
        """Append one observed-plan-stats record (history-based
        optimization feed; planner/history.py is both writer and reader)."""
        self._write(_record_plan_stats(query_id, fingerprint, nodes, ts))

    def _write(self, rec: dict) -> None:
        from . import metrics as tm

        line = json.dumps(rec, default=str) + "\n"
        data = line.encode("utf-8")
        with self._lock:
            os.makedirs(self.directory, exist_ok=True)
            try:
                size = os.path.getsize(self.path)
            except OSError:
                size = 0
            if not self._tail_checked:
                self._tail_checked = True
                if size:
                    with open(self.path, "rb") as f:
                        f.seek(-1, os.SEEK_END)
                        if f.read(1) != b"\n":
                            line = "\n" + line
                            data = line.encode("utf-8")
            if size and size + len(data) > self.max_bytes:
                self._rotate()
            with open(self.path, "a", encoding="utf-8") as f:
                f.write(line)
        tm.JOURNAL_RECORDS.inc()
        tm.JOURNAL_BYTES.inc(len(data))

    def _rotate(self) -> None:
        """journal.jsonl -> .1 -> .2 ... -> .max_files (dropped)."""
        from . import metrics as tm

        oldest = f"{self.path}.{self.max_files}"
        if os.path.exists(oldest):
            os.remove(oldest)
        for i in range(self.max_files - 1, 0, -1):
            src = f"{self.path}.{i}"
            if os.path.exists(src):
                os.replace(src, f"{self.path}.{i + 1}")
        os.replace(self.path, f"{self.path}.1")
        tm.JOURNAL_ROTATIONS.inc()

    # --------------------------------------------------------- reader side
    def files(self) -> list[str]:
        """This member's journal files oldest-first (rotated generations
        then current)."""
        out = [f"{self.path}.{i}" for i in range(self.max_files, 0, -1)]
        out.append(self.path)
        return [p for p in out if os.path.exists(p)]

    def fleet_files(self) -> list[str]:
        """Every fleet member's journal files under the shared directory,
        oldest-first per stream, streams in name order — the READ set.  In
        a single-coordinator deployment this is exactly :meth:`files`; in a
        fleet it additionally folds the sibling ``query_journal-*`` streams
        other coordinators rotate, so journal-seeded admission estimates
        and ``system.runtime.query_history`` see the whole fleet's memory,
        not just the local rotation set."""
        stem = _FILE[:-len(".jsonl")]
        streams: dict[str, list[tuple[int, str]]] = {}
        try:
            names = os.listdir(self.directory)
        except OSError:
            return self.files()
        for name in names:
            if not name.startswith(stem):
                continue
            base, gen = name, 0
            if ".jsonl." in name:
                base, _, suffix = name.rpartition(".")
                if not suffix.isdigit():
                    continue
                gen = int(suffix)
            if not base.endswith(".jsonl"):
                continue
            streams.setdefault(base, []).append(
                (gen, os.path.join(self.directory, name)))
        out = []
        for base in sorted(streams):
            # oldest generation first (highest .N), current (gen 0) last
            for _gen, path in sorted(streams[base], reverse=True):
                out.append(path)
        return out or self.files()

    def read(self, events: Optional[tuple] = None) -> list[dict]:
        """Every parseable record, oldest-first; a torn tail line (crash
        mid-write) is skipped, not fatal — the journal must be readable
        after any kill."""
        out: list[dict] = []
        for path in self.fleet_files():
            try:
                with open(path, encoding="utf-8") as f:
                    for line in f:
                        rec = _record_of(line, events)
                        if rec is not None:
                            out.append(rec)
            except OSError:
                continue
        return out


def _record_of(line, events: Optional[tuple]) -> Optional[dict]:
    """The journal record a line (text or bytes) holds, if it is one and of
    a wanted event type; garbage and torn lines are nobody's record."""
    line = line.strip()
    if not line:
        return None
    try:
        rec = json.loads(line)
    except ValueError:
        return None
    if not isinstance(rec, dict) or "schema" not in rec:
        return None
    return rec if events is None or rec.get("event") in events else None


def _stream_of(path: str) -> str:
    """The stream a journal file belongs to: its path without the rotated
    generation's ``.N`` (every member of a fleet appends to its own)."""
    base, _, suffix = path.rpartition(".")
    return base if suffix.isdigit() else path


class JournalFollower:
    """Hands out the fleet's journal records of some event types, each once:
    a cursor per file (path, inode, bytes consumed), moved by what was
    appended.  The trigger is :func:`_journal_signature` (a ``stat()`` per
    file of the fleet set, so a peer's append is seen).  While the same
    files only grow, :meth:`poll` reads each from its cursor and consumes
    whole lines (a torn tail waits for its newline).  Anything else — a file
    shrank, vanished, appeared, changed identity, rotated — makes the next
    poll start from nothing and say so: a rotation drops the oldest
    generation, so a consumer's fold may have to lose records again.

    Not thread-safe: the consumer holds its own lock around ``poll``."""

    def __init__(self, journal: QueryJournal, events: tuple):
        self.journal = journal
        self.events = events
        # a line of another event type is not parsed: json.dumps writes the
        # type's name in quotes, so a line without it cannot be of the type
        self._needles = tuple(json.dumps(e).encode("utf-8") for e in events)
        self._sig: Optional[tuple] = None
        self._cursors: dict[str, tuple[int, int]] = {}  # path -> (ino, at)

    def poll(self) -> Optional[tuple[bool, list, int]]:
        """None while nothing moved; else ``(from_nothing, [(stream,
        record)], bytes_read)``, the records oldest-first per stream and
        the streams in :meth:`QueryJournal.fleet_files` order.  With
        ``from_nothing`` the records are every record on disk, and what
        earlier polls handed out is void."""
        sig = _journal_signature(self.journal)
        if sig == self._sig:
            return None
        same_files = [(p, ino) for p, _, _, ino in sig] == \
            [(p, ino) for p, (ino, _) in self._cursors.items()]
        grew = self._sig is not None and same_files and \
            all(size >= self._cursors[p][1] for p, size, _, _ in sig)
        if not grew:
            self._cursors = {p: (ino, 0) for p, _, _, ino in sig}
        records: list = []
        total = 0
        for path, size, _, ino in sig:
            at = self._cursors[path][1]
            if size <= at:
                continue
            try:
                with open(path, "rb") as f:
                    if os.fstat(f.fileno()).st_ino != ino:
                        continue  # rotated since the stat, see below
                    f.seek(at)
                    data = f.read()
            except OSError:
                continue  # gone since the stat: the next signature says so
            total += len(data)
            whole = data.rfind(b"\n") + 1
            self._cursors[path] = (ino, at + whole)
            stream = _stream_of(path)
            for line in data[:whole].splitlines():
                if any(n in line for n in self._needles):
                    rec = _record_of(line, self.events)
                    if rec is not None:
                        records.append((stream, rec))
        self._sig = sig
        return not grew, records, total


# ------------------------------------------------------------ process state

_SINGLETON: Optional[QueryJournal] = None
_SINGLETON_LOCK = threading.Lock()
# fingerprint → [peaks] seed map, keyed by the journal file-set signature
# it was built from: (sig, cache).  Rebuilt whenever a journal file
# appears, rotates or grows — and every finished query grows one, so an
# admission decision that gets here (a capped memory manager and no
# in-memory peak for the fingerprint) re-reads every file.  The history
# table (planner/history.py) follows the journal through a JournalFollower
# instead; this fold can take the same reader when it lands on a hot path
_SEED_CACHE: Optional[tuple] = None
_SEED_LOCK = threading.Lock()


def _journal_signature(j: QueryJournal) -> tuple:
    # the FLEET file set: a peer coordinator's append or rotation must
    # invalidate the admission seed cache exactly like a local one
    sig = []
    for path in j.fleet_files():
        try:
            st = os.stat(path)
        except OSError:
            continue
        sig.append((path, st.st_size, st.st_mtime_ns, st.st_ino))
    return tuple(sig)


def get_journal() -> Optional[QueryJournal]:
    """The process-wide journal (one file lock, shared by every runner in
    the process), or None when disabled via TRINO_TPU_JOURNAL=0."""
    global _SINGLETON
    if not journal_enabled():
        return None
    with _SINGLETON_LOCK:
        if _SINGLETON is None:
            _SINGLETON = QueryJournal()
        return _SINGLETON


def history() -> list[dict]:
    """Completed-query records from disk, oldest-first — the
    system.runtime.query_history feed (always re-read: restarts and other
    coordinator processes may have appended)."""
    j = get_journal()
    if j is None:
        return []
    return j.read(events=("query_completed",))


def seeded_peak(fp: str, history_len: int = 5) -> int:
    """Journal-seeded admission estimate: max peak of the fingerprint's
    most recent FINISHED runs on disk, 0 when unknown.  The seed map is
    memoized on the journal file-set signature (path, size, mtime, inode):
    a handful of stat() calls while no file moved, a re-read of every file
    after any append, local or a peer's (see ``_SEED_CACHE``)."""
    global _SEED_CACHE
    j = get_journal()
    if j is None:
        return 0
    with _SEED_LOCK:
        sig = _journal_signature(j)
        if _SEED_CACHE is None or _SEED_CACHE[0] != sig:
            cache: dict[str, list[int]] = {}
            for rec in j.read(events=("query_completed",)):
                if rec.get("state") != "FINISHED":
                    continue
                peak = int(rec.get("peak_memory_bytes", 0) or 0)
                if peak <= 0:
                    continue
                cache.setdefault(rec.get("fingerprint", ""), []).append(peak)
            _SEED_CACHE = (sig, cache)
        peaks = _SEED_CACHE[1].get(fp)
    if not peaks:
        return 0
    return max(peaks[-history_len:])


def reset_for_test() -> None:
    """Forget the singleton and the seed cache — the in-process stand-in
    for a coordinator restart (env changes take effect on next use)."""
    global _SINGLETON, _SEED_CACHE
    with _SINGLETON_LOCK:
        _SINGLETON = None
    _SEED_CACHE = None
