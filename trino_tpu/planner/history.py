"""History-based optimization: journaled runtime truth fed back into the
cost model (reference: the HBO design Trino/Presto ship as
HistoryBasedPlanStatisticsCalculator — observed plan-node statistics
keyed by a logical plan fingerprint, consulted before estimates).

Two halves, both here so the fingerprint definition cannot drift:

- **Recording** (:func:`record_query_stats`): at the end of a successful
  distributed query the runner hands over its fragments, stages and the
  adaptive controller; every fragment's observed output (sink
  ``rows_enqueued``/``bytes_enqueued``, adaptive staging counters, probe
  heavy-hitter share) is written to the PR 11 query journal as one
  ``plan_stats`` record keyed by each fragment root's *logical
  fingerprint*.
- **Reading** (:class:`HistoryProvider`): ``estimate_rows`` and the
  iterative optimizer's reorder/distribution rules look observed stats up
  by the same fingerprint; a hit replaces the estimate.  The provider's
  table is kept beside the journal (:class:`_HistoryTable`): a read costs
  a stat() per journal file, plus the bytes appended since the last read
  (about a kilobyte a finished query) folded into the live table.  Only
  a rotation, or a file that shrank or changed identity, re-reads the
  journal from nothing.

The fingerprint is **row-equivalence** hashing, not structural hashing:
two plan shapes that must produce the same row stream hash equal, so a
stat recorded against the *executed* plan (post-prune, post-fragmentation,
adaptively flipped) still matches the *candidate* subtree the optimizer
is costing on the next run.  Concretely:

- expressions render by channel **name**, never index (names are assigned
  once at translation and survive pruning/projection);
- Project / Sort / Output / Exchange are transparent (row-preserving);
- TableScan keys on (catalog, table) only — columns, advisory constraint
  and pushed limit are row-irrelevant or derived;
- Aggregate ignores the step: FINAL is transparent-to-source, so the
  plan-time SINGLE aggregation and the executed PARTIAL->shuffle->FINAL
  chain share one fingerprint;
- INNER/CROSS joins hash their sides and key pairs orderless, so the
  run-1 order and the reordered run-2 plan (and BROADCAST vs PARTITIONED)
  share one fingerprint;
- RemoteSource substitutes the producer fragment's fingerprint.

Misses degrade to estimates; history can change plans, never results.
Plan-cache poisoning is prevented by :func:`history_epoch`, a digest of
the folded table (not of the records it was folded from) mixed into the
Tier A key (caching/plan_cache.py): a finished query that observed what
is already known leaves every cached plan reachable, one that observed
another number strands exactly the plans made before it.  A statement is
planned and its plan published inside :func:`pinned`, under one table.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import threading
import time
from dataclasses import dataclass
from typing import Callable, Optional

from ..spi import knobs
from ..sql.ir import Call, InputRef, Literal, OuterRef, RowExpression
from ..telemetry import journal
from .plan import (
    Aggregate,
    DistinctLimit,
    Exchange,
    Filter,
    GroupId,
    Join,
    Limit,
    Output,
    PlanNode,
    Project,
    RemoteSource,
    SemiJoin,
    Sort,
    TableScan,
    TopN,
    Union,
    Unnest,
    Values,
    Window,
)

__all__ = [
    "NodeStats", "HistoryProvider", "hbo_enabled", "provider_if_enabled",
    "history_epoch", "pinned", "thread_bytes_read", "plan_span_attrs",
    "logical_fingerprint", "fragment_fingerprints", "record_query_stats",
]


def hbo_enabled() -> bool:
    return knobs.get_str("TRINO_TPU_HBO").strip().lower() not in ("0", "off")


# ---------------------------------------------------------------- fingerprint


def _render(e: RowExpression, names: tuple) -> str:
    """Name-based expression rendering: stable across channel remapping."""
    if isinstance(e, InputRef):
        return names[e.index] if e.index < len(names) else f"#{e.index}"
    if isinstance(e, Literal):
        return f"lit:{e.value!r}"
    if isinstance(e, Call):
        return f"{e.name}({','.join(_render(a, names) for a in e.args)})"
    if isinstance(e, OuterRef):
        return f"outer:{e.index}"
    return repr(e)


def _digest(parts: tuple) -> str:
    return hashlib.sha1(repr(parts).encode("utf-8")).hexdigest()[:16]


def logical_fingerprint(node: PlanNode,
                        resolve: Optional[Callable[[int], str]] = None) -> str:
    """Row-equivalence fingerprint of a plan subtree.  ``resolve`` maps a
    RemoteSource's fragment id to the producer fragment's fingerprint
    (record side); plan-time trees have no RemoteSource."""

    def fp(n: PlanNode) -> str:
        if isinstance(n, (Project, Sort, Output, Exchange)):
            return fp(n.source)
        if isinstance(n, TableScan):
            return _digest(("scan", n.catalog, n.table))
        if isinstance(n, Filter):
            from .optimizer import _split_and

            names = n.source.output_names
            conjuncts = tuple(sorted(
                _render(c, names) for c in _split_and(n.predicate)))
            return _digest(("filter", conjuncts, fp(n.source)))
        if isinstance(n, Aggregate):
            if n.step == "FINAL":
                return fp(n.source)
            names = n.source.output_names
            keys = tuple(sorted(names[k] for k in n.group_keys))
            aggs = tuple(sorted(
                (a.fn, names[a.arg] if a.arg >= 0 else "*", a.distinct)
                for a in n.aggregates))
            return _digest(("agg", keys, aggs, fp(n.source)))
        if isinstance(n, Join):
            lnames = n.left.output_names
            rnames = n.right.output_names
            pairs = tuple(sorted(
                tuple(sorted((lnames[l], rnames[r])))
                for l, r in zip(n.left_keys, n.right_keys)))
            residual = ""
            if n.residual is not None:
                residual = _render(n.residual, tuple(lnames) + tuple(rnames))
            sides = (fp(n.left), fp(n.right))
            if n.join_type in ("INNER", "CROSS"):
                # orderless: the reordered plan keeps the fingerprint
                sides = tuple(sorted(sides))
            return _digest(("join", n.join_type, pairs, residual) + sides)
        if isinstance(n, SemiJoin):
            snames = n.source.output_names
            fnames = n.filter_source.output_names
            pairs = tuple((snames[s], fnames[f])
                          for s, f in zip(n.source_keys, n.filter_keys))
            residual = ""
            if n.residual is not None:
                residual = _render(n.residual, tuple(snames) + tuple(fnames))
            return _digest(("semijoin", n.negated, n.null_aware, pairs,
                            residual, fp(n.source), fp(n.filter_source)))
        if isinstance(n, Limit):
            return _digest(("limit", n.count, fp(n.source)))
        if isinstance(n, TopN):
            keys = tuple((n.source.output_names[k.channel], k.ascending)
                         for k in n.keys)
            return _digest(("topn", n.count, keys, fp(n.source)))
        if isinstance(n, DistinctLimit):
            return _digest(("distinctlimit", n.count, fp(n.source)))
        if isinstance(n, Values):
            return _digest(("values", len(n.rows)))
        if isinstance(n, Union):
            return _digest(("union",) + tuple(sorted(fp(s)
                                                     for s in n.sources)))
        if isinstance(n, Window):
            names = n.source.output_names
            fns = tuple((f.fn, tuple(names[a] for a in f.args))
                        for f in n.functions)
            return _digest(("window",
                            tuple(names[k] for k in n.partition_keys),
                            fns, fp(n.source)))
        if isinstance(n, GroupId):
            return _digest(("groupid", n.sets, fp(n.source)))
        if isinstance(n, Unnest):
            return _digest(("unnest", n.unnest_channels, fp(n.source)))
        if isinstance(n, RemoteSource):
            if resolve is not None:
                return resolve(n.fragment_id)
            return _digest(("remote", n.fragment_id))
        # coarse default: type + children (TableWriter, Replicate, ...)
        return _digest((type(n).__name__,) + tuple(fp(c)
                                                   for c in n.children))

    return fp(node)


def fragment_fingerprints(fragments) -> dict:
    """Fingerprint every fragment root, resolving RemoteSources to their
    producer fragment's fingerprint (fragments form a DAG; iterate until
    all dependencies are available)."""
    fps: dict[int, str] = {}
    pending = list(fragments)
    while pending:
        rest = []
        for f in pending:
            try:
                fps[f.id] = logical_fingerprint(
                    f.root, resolve=lambda fid: fps[fid])
            except KeyError:
                rest.append(f)
        if len(rest) == len(pending):  # unresolvable — record what we have
            break
        pending = rest
    return fps


# ------------------------------------------------------------------- provider


@dataclass
class NodeStats:
    rows: Optional[int] = None
    bytes: Optional[int] = None
    groups: Optional[int] = None
    skew: Optional[float] = None


class _HistoryTable:
    """The folded plan_stats table of one journal, kept beside it: a
    :class:`~trino_tpu.telemetry.journal.JournalFollower` hands over what
    was appended, and only that is folded.  One table per stream, because
    the fold's order is the read set's (streams in name order, each oldest
    first) and a peer's stream may grow in the middle of it; ``table`` is
    their merge in that order, the newer observation winning field by
    field, and what the planner reads.  ``table`` and its entries are
    replaced, never changed, so a planner keeps the table it began with
    while another thread folds."""

    def __init__(self, j):
        self.journal = j
        self.follower = journal.JournalFollower(j, ("plan_stats",))
        self.streams: dict[str, dict[str, dict]] = {}  # fp -> {field: v}
        self.table: dict[str, NodeStats] = {}
        self.epoch = ""

    def refresh(self) -> int:
        """Fold what the journal got since the last call; returns the bytes
        read.  Costs a stat() per journal file while nothing moved."""
        from ..telemetry import metrics as tm

        polled = self.follower.poll()
        if polled is None:
            return 0
        from_nothing, records, nbytes = polled
        tm.HBO_JOURNAL_BYTES_READ.inc(nbytes)
        (tm.HBO_TABLE_REBUILDS if from_nothing else tm.HBO_TABLE_FOLDS).inc()
        if from_nothing:
            self.streams = {}
        touched = set()
        for stream, rec in records:
            nodes = rec.get("nodes")
            if not isinstance(nodes, dict):
                continue
            mine = self.streams.setdefault(stream, {})
            for fp, st in nodes.items():
                if isinstance(st, dict):
                    mine.setdefault(fp, {}).update(
                        (name, st[name]) for name in journal.PLAN_STATS_FIELDS
                        if st.get(name) is not None)
                    touched.add(fp)
        table = {} if from_nothing else self.table
        for fp in touched:
            fields: dict = {}
            for stream in sorted(self.streams):
                fields.update(self.streams[stream].get(fp, {}))
            merged = NodeStats(**fields)
            if table.get(fp) != merged:
                if table is self.table:
                    table = dict(table)
                table[fp] = merged
        if table is not self.table:
            self.table = table
            self.epoch = _epoch_of(table)
        return nbytes


def _epoch_of(table: dict) -> str:
    """Digest of the table the planner would consult: equal tables give
    equal epochs, whatever records they were folded from."""
    if not table:
        return ""
    rows = sorted((fp,) + dataclasses.astuple(st)
                  for fp, st in table.items())
    return hashlib.sha1(repr(rows).encode("utf-8")).hexdigest()[:12]


_STATE: Optional[_HistoryTable] = None
_TABLE_LOCK = threading.Lock()
# per thread: the snapshot pinned() holds, and the journal bytes this
# thread's reads of the table consumed (the recorder's ``plan`` span)
_TLS = threading.local()
_UNREAD = object()  # the pin inside pinned() before the block's first read


def _stats_table() -> tuple[dict, str]:
    """(fingerprint -> NodeStats, epoch) as the journal's plan_stats
    records stand now, newest record winning per fingerprint and field —
    or, inside :func:`pinned`, as the block's first read found them."""
    global _STATE
    pin = getattr(_TLS, "pin", None)
    if pin is not None and pin is not _UNREAD:
        return pin
    j = journal.get_journal()
    if j is None:
        return {}, ""
    with _TABLE_LOCK:
        if _STATE is None or _STATE.journal is not j:
            _STATE = _HistoryTable(j)
        try:
            nbytes = _STATE.refresh()
        except BaseException:
            _STATE = None  # half a fold is no table: the next read rebuilds
            raise
        out = _STATE.table, _STATE.epoch
    _TLS.bytes_read = thread_bytes_read() + nbytes
    if pin is _UNREAD:
        _TLS.pin = out
    return out


@contextlib.contextmanager
def pinned():
    """One table for everything this thread reads inside the block, taken
    at the block's first read.  A statement is planned and its plan
    published (caching/plan_cache.py ``store``) inside one block, so the
    Tier A key's epoch is the epoch of the table the optimizer read,
    whatever other streams append meanwhile.  Nests."""
    if getattr(_TLS, "pin", None) is not None:
        yield
        return
    _TLS.pin = _UNREAD
    try:
        yield
    finally:
        _TLS.pin = None


def thread_bytes_read() -> int:
    """Journal bytes this thread's reads of the table have consumed."""
    return getattr(_TLS, "bytes_read", 0)


def plan_span_attrs(read_before: int) -> dict:
    """What the recorder's ``plan`` span says of history: the epoch the
    lookup or the planning went by (call it inside their :func:`pinned`
    block) and the journal bytes read for it."""
    return {"epoch": history_epoch(),
            "journal_bytes_read": thread_bytes_read() - read_before}


def history_epoch() -> str:
    """Digest of the folded table the planner would consult right now
    (sorted fingerprint, rows, bytes, groups, skew); mixed into the Tier A
    plan-cache key so history-driven plans never outlive the history that
    shaped them.  A record that repeats what is known leaves it as it was;
    one that changes a number changes it.  "" when HBO is off or no stats
    exist."""
    if not hbo_enabled():
        return ""
    try:
        return _stats_table()[1]
    except Exception:
        return ""


class HistoryProvider:
    """Per-planning view over the shared stats table (fresh instance per
    optimize call so lookup/hit counters are per-query for the trace)."""

    def __init__(self, table: dict, epoch: str = ""):
        self.table = table
        self.epoch = epoch  # of ``table``: what EXPLAIN ANALYZE prints
        self.lookups = 0
        self.hits = 0
        self._fp_cache: dict[int, str] = {}

    def fingerprint(self, node: PlanNode) -> str:
        key = id(node)
        fp = self._fp_cache.get(key)
        if fp is None:
            fp = logical_fingerprint(node)
            self._fp_cache[key] = fp
        return fp

    def stats_for(self, node: PlanNode) -> Optional[NodeStats]:
        self.lookups += 1
        st = self.table.get(self.fingerprint(node))
        if st is not None:
            self.hits += 1
        return st

    def observed_rows(self, node: PlanNode) -> Optional[float]:
        st = self.stats_for(node)
        if st is None:
            return None
        if st.rows is not None:
            return float(st.rows)
        if st.groups is not None:  # summed partial groups: upper bound
            return float(st.groups)
        return None


def provider_if_enabled() -> Optional[HistoryProvider]:
    """A fresh HistoryProvider when HBO is on and observed stats exist;
    None otherwise (planning falls back to estimates)."""
    if not hbo_enabled():
        return None
    try:
        table, epoch = _stats_table()
    except Exception:
        return None
    if not table:
        return None
    return HistoryProvider(table, epoch)


def reset_for_test() -> None:
    global _STATE
    with _TABLE_LOCK:
        _STATE = None


# ------------------------------------------------------------------ recording


def _is_partial_agg_root(node: PlanNode) -> bool:
    while isinstance(node, (Exchange, Project, Output)):
        node = node.source
    return isinstance(node, Aggregate) and node.step == "PARTIAL"


def record_query_stats(fragments, stages, skip_fids, adaptive,
                       query_id: str, sql_fingerprint: str) -> int:
    """Write one plan_stats journal record for a finished distributed
    query.  ``stages`` maps fragment id -> stage (with sink ``buffers``);
    ``skip_fids`` holds fragments whose sinks bypassed the buffers (fused/
    resident/collective edges); ``adaptive`` (optional) supplies staging
    counters and skew for deferred producers.  Returns the number of
    fingerprints recorded; never raises into the query path."""
    if not hbo_enabled():
        return 0
    j = journal.get_journal()
    if j is None:
        return 0
    fps = fragment_fingerprints(fragments)
    observed = adaptive.observed_stats() if adaptive is not None else {}
    nodes: dict[str, dict] = {}
    for f in fragments:
        fp = fps.get(f.id)
        if fp is None:
            continue
        ob = observed.get(f.id)
        if ob is not None:
            rows, nbytes, skew = ob["rows"], ob["bytes"], ob.get("skew")
        else:
            if f.id in skip_fids:
                continue  # sink bypassed OutputBuffer: no counters
            st = stages.get(f.id)
            buffers = getattr(st, "buffers", None)
            if not buffers:
                continue
            rows = sum(b.rows_enqueued for b in buffers)
            nbytes = sum(b.bytes_enqueued for b in buffers)
            skew = None
            nparts = buffers[0].num_partitions
            if getattr(f, "output_kind", "") == "BROADCAST" and nparts > 1:
                # broadcast sinks enqueue every batch once per partition
                rows //= nparts
                nbytes //= nparts
        entry = nodes.setdefault(fp, {})
        if _is_partial_agg_root(f.root):
            entry["groups"] = int(rows)
        else:
            entry["rows"] = int(rows)
            entry["bytes"] = int(nbytes)
        if skew is not None:
            entry["skew"] = float(skew)
    if not nodes:
        return 0
    j.plan_stats(query_id, sql_fingerprint, nodes, ts=time.time())
    return len(nodes)
