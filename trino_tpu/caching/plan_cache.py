"""Tier A: the logical plan cache.

Dashboard traffic re-submits the same statement text thousands of times;
before this cache every submission re-paid parse → analyze → plan →
optimize.  A hit skips all four: the runner goes straight from SQL text to
a cloned optimized plan tree (the reference engine's query-plan cache
role).

Key = SQL fingerprint (telemetry.runtime.fingerprint, for observability)
⊕ the exact statement text (fingerprints normalize case, which would
merge ``'BUILDING'`` with ``'building'`` — the text disambiguates) ⊕ the
session properties that shape planning/execution ⊕ the engine env knobs
that select alternate executables (``TRINO_TPU_FUSED_STAGE`` etc., so a
knob flip can never serve a plan built for the other implementation) ⊕
the catalog **generation counter** (connectors/catalog.py), which bumps
on every DDL/ANALYZE so schema or stats changes invalidate wholesale.

Hits hand out ``copy.deepcopy`` clones: plan nodes are frozen dataclasses
but carry compare-excluded mutable payloads (TupleDomain constraints), so
sharing one tree across concurrent executions would be a footgun.  A
clone is microseconds against the multi-millisecond plan pipeline it
replaces.

``TRINO_TPU_PLAN_CACHE=0`` (checked per lookup) disables the tier:
every query re-plans exactly as before, bit for bit.
"""

from __future__ import annotations

import copy
import os
import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Optional

__all__ = [
    "PlanEntry", "lookup", "store", "clone", "scan_tables",
    "planning_env_key", "session_key", "enabled", "stats",
    "invalidate_all", "reset_for_test",
]

# env knobs that change which jitted programs execute a plan (and hence
# the bitwise result of float aggregation): a flip must miss
PLANNING_ENV_KNOBS = (
    "TRINO_TPU_FUSED_STAGE", "TRINO_TPU_FUSED_CAP", "TRINO_TPU_HBO",
    "TRINO_TPU_JOIN_REORDER_DP_LIMIT", "TRINO_TPU_BROADCAST_ROW_LIMIT",
)

# session properties that shape the logical plan or the execution layout
# (split counts change partial-agg accumulation order → float bits)
SESSION_KEY_PROPS = (
    "default_catalog", "splits_per_node", "node_count", "dynamic_filtering",
    "task_concurrency", "hbm_limit_bytes", "spill_to_disk_bytes",
    "use_collectives", "exchange_serde", "scale_writers",
    "writer_task_limit",
)


def enabled() -> bool:
    return os.environ.get("TRINO_TPU_PLAN_CACHE", "1").strip().lower() \
        not in ("0", "off", "false", "no")


def _max_entries() -> int:
    return int(os.environ.get("TRINO_TPU_PLAN_CACHE_ENTRIES", "256"))


def planning_env_key() -> tuple:
    return tuple(os.environ.get(k, "") for k in PLANNING_ENV_KNOBS)


def session_key(session) -> tuple:
    return tuple(getattr(session, p, None) for p in SESSION_KEY_PROPS)


@dataclass
class PlanEntry:
    """One cached optimized plan + everything the execution fast path
    needs without re-walking: the scanned (catalog, table) set feeding the
    result-cache version vector, and the generation-free key prefix the
    result cache keys on (a harmless catalog-generation bump must re-plan
    but may still serve a version-validated cached result)."""

    plan: object
    tables: tuple
    result_key_base: tuple
    fingerprint: str
    cacheable_result: bool


_LOCK = threading.Lock()
_ENTRIES: OrderedDict = OrderedDict()
_HITS = 0
_MISSES = 0
_EVICTIONS = 0
_INVALIDATIONS = 0


def scan_tables(plan) -> tuple:
    """Sorted unique (catalog, table) pairs the plan reads."""
    from ..planner.plan import TableScan

    out = set()

    def walk(node):
        if isinstance(node, TableScan):
            out.add((node.catalog, node.table))
        for c in node.children:
            walk(c)

    walk(plan)
    return tuple(sorted(out))


def _result_cacheable(plan) -> bool:
    """Table functions have no version token and may synthesize volatile
    data; plans containing them never enter the result tier."""
    from ..planner.plan import TableFunctionScan, TableWriter

    def walk(node):
        if isinstance(node, (TableFunctionScan, TableWriter)):
            return False
        return all(walk(c) for c in node.children)

    return walk(plan)


def _has_writer(plan) -> bool:
    """Writer plans stay out of Tier A: the hit path re-checks SELECT
    access only, so a cached INSERT/CTAS/DELETE rewrite would bypass the
    write-privilege check that guards the cold path."""
    from ..planner.plan import TableWriter

    def walk(node):
        if isinstance(node, TableWriter):
            return True
        return any(walk(c) for c in node.children)

    return walk(plan)


def _key(sql: str, session, catalog, flavor: str) -> tuple:
    from ..telemetry.runtime import fingerprint

    # flavor partitions plan shapes ("local" vs "fragmented" — the
    # distributed runner's trees carry exchange nodes); the catalog
    # instance id keeps the process-global cache partitioned per catalog:
    # two runners with fresh catalogs (and fresh memory connectors) must
    # never see each other's plans or results
    # the history epoch keys out plans shaped by observed stats: it digests
    # the folded table the planner reads (planner/history.py), so a record
    # that changes an observed number changes it and strands the plans made
    # before, and one that repeats what is known leaves them reachable.
    # ``store`` runs inside history.pinned() with the planning it
    # publishes: its epoch is the epoch of the table the optimizer read
    from ..planner.history import history_epoch

    return (flavor, fingerprint(sql), sql.strip(), session_key(session),
            planning_env_key(), getattr(catalog, "instance_id", id(catalog)),
            getattr(catalog, "generation", 0), history_epoch())


def lookup(sql: str, session, catalog,
           flavor: str = "local") -> Optional[PlanEntry]:
    global _HITS, _MISSES
    if not enabled():
        return None
    key = _key(sql, session, catalog, flavor)
    from ..telemetry import metrics as tm

    with _LOCK:
        entry = _ENTRIES.get(key)
        if entry is not None:
            _ENTRIES.move_to_end(key)
            _HITS += 1
        else:
            _MISSES += 1
    if entry is None:
        tm.CACHE_PLAN_MISSES.inc()
        return None
    tm.CACHE_PLAN_HITS.inc()
    return entry


def store(sql: str, session, catalog, plan,
          flavor: str = "local") -> PlanEntry:
    """Build the entry for a freshly planned statement and (when the tier
    is enabled) publish it.  Always returns the entry — the execution fast
    path uses it even when caching is off."""
    global _EVICTIONS
    from ..telemetry.runtime import fingerprint

    key = _key(sql, session, catalog, flavor)
    publish = enabled() and not _has_writer(plan)
    # the caller executes ``plan`` (execution attaches mutable TupleDomain
    # constraints to scan nodes) — the cache must hold a pristine copy
    entry = PlanEntry(
        plan=clone(plan) if publish else plan,
        tables=scan_tables(plan),
        # key[:-1] drops the catalog generation — the result tier
        # re-validates freshness through per-table version tokens instead
        result_key_base=key[:-1],
        fingerprint=fingerprint(sql),
        cacheable_result=_result_cacheable(plan),
    )
    if not publish:
        return entry
    from ..telemetry import metrics as tm

    with _LOCK:
        _ENTRIES[key] = entry
        while len(_ENTRIES) > _max_entries():
            _ENTRIES.popitem(last=False)
            _EVICTIONS += 1
            tm.CACHE_PLAN_EVICTIONS.inc()
        tm.CACHE_PLAN_ENTRIES.set(len(_ENTRIES))
    return entry


def clone(plan):
    """A private copy of a cached tree for one execution."""
    return copy.deepcopy(plan)


def invalidate_all() -> None:
    global _INVALIDATIONS
    from ..telemetry import metrics as tm

    with _LOCK:
        n = len(_ENTRIES)
        _ENTRIES.clear()
        _INVALIDATIONS += n
        if n:
            tm.CACHE_PLAN_INVALIDATIONS.inc(n)
        tm.CACHE_PLAN_ENTRIES.set(0)


def stats() -> dict:
    with _LOCK:
        return {
            "tier": "plan", "name": "plan", "entries": len(_ENTRIES),
            "bytes": 0, "hits": _HITS, "misses": _MISSES,
            "evictions": _EVICTIONS, "invalidations": _INVALIDATIONS,
        }


def reset_for_test() -> None:
    global _HITS, _MISSES, _EVICTIONS, _INVALIDATIONS
    with _LOCK:
        _ENTRIES.clear()
        _HITS = _MISSES = _EVICTIONS = _INVALIDATIONS = 0
