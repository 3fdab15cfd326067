"""Central registry of every ``TRINO_TPU_*`` environment knob.

The engine grew ~45 env knobs across five PR generations, each declared
nowhere but its read site — so a typo'd read silently returns the default,
an operator cannot enumerate what is tunable, and docs drift freely.  This
module is the single source of truth: every knob's name, type, default,
and one-line doc, in one table.

Three consumers hold the registry honest:

- the ``knob-registry`` tpulint rule rejects any ``TRINO_TPU_*`` string
  literal in the tree that is not declared here (catching misspellings
  and undeclared additions statically — the declarations below are pure
  literals precisely so the linter can read them without importing jax);
- ``docs/KNOBS.md`` is *generated* from this table
  (``python -m tools.analysis --write-knob-docs``) and the ``knob-docs``
  rule fails when the committed file drifts from the registry;
- the typed accessors below (:func:`get_str` & friends) raise
  :class:`KeyError` on an undeclared name, so even dynamically-built knob
  reads cannot bypass the registry at runtime.

Reading through the accessors is recommended but not required — existing
``os.environ.get("TRINO_TPU_X", ...)`` sites stay valid as long as the
literal is declared.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional

__all__ = ["Knob", "KNOBS", "declared", "knob", "get_str", "get_int",
           "get_float", "get_bool"]


@dataclass(frozen=True)
class Knob:
    """One declared environment knob.  ``default`` is the *string* form as
    the environment would carry it ("" = unset, code-side fallback applies);
    ``type`` is documentation plus accessor validation, one of
    ``str | int | float | bool | enum | json | path``."""

    name: str
    type: str
    default: str
    doc: str
    choices: Optional[tuple] = None


# NOTE for editors: declarations must stay PURE LITERALS — the tpulint
# knob-registry rule and the KNOBS.md generator read this file with ast,
# not import, so a computed default would be invisible to both.
_DECLARATIONS = (
    Knob("TRINO_TPU_ADAPTIVE", "enum", "auto",
         "Adaptive query execution (runtime join-distribution switching, "
         "skew-aware repartitioning); 0 is bit-for-bit legacy.",
         choices=("auto", "1", "0")),
    Knob("TRINO_TPU_AUTOSCALE", "bool", "0",
         "Elastic worker autoscaling: a controller watches admission queue "
         "pressure and cluster memory and grows or drains the worker fleet "
         "through the zero-loss shutdown protocol."),
    Knob("TRINO_TPU_AUTOSCALE_IDLE_ROUNDS", "int", "3",
         "Consecutive pressure-free controller rounds before the "
         "autoscaler drains one worker down toward the floor."),
    Knob("TRINO_TPU_AUTOSCALE_INTERVAL_S", "float", "5",
         "Autoscaler controller cadence (seconds between policy rounds)."),
    Knob("TRINO_TPU_AUTOSCALE_MAX_WORKERS", "int", "4",
         "Autoscaler ceiling: the controller never grows the worker fleet "
         "past this."),
    Knob("TRINO_TPU_AUTOSCALE_MIN_WORKERS", "int", "1",
         "Autoscaler floor: the controller never drains the worker fleet "
         "below this."),
    Knob("TRINO_TPU_AUTOSCALE_QUEUE_S", "float", "0.5",
         "Scale-up trigger: admission queued-seconds accumulated per "
         "controller round at or above this means queue pressure."),
    Knob("TRINO_TPU_BLACKLIST_PATH", "path", "",
         "Shared durable cluster-blacklist file (append-only JSONL).  When "
         "set, every coordinator in the fleet appends its strikes here and "
         "merges peers' entries on read (TTL-decayed) instead of keeping "
         "process-local state; unset keeps the per-coordinator journal "
         "persistence."),
    Knob("TRINO_TPU_BLACKLIST_THRESHOLD", "float", "2",
         "Failure score at or above which a worker enters the cross-query "
         "cluster blacklist."),
    Knob("TRINO_TPU_BLACKLIST_TTL_S", "float", "300",
         "Cluster-blacklist entry TTL; scores decay to zero over this "
         "window."),
    Knob("TRINO_TPU_BROADCAST_ROW_LIMIT", "int", "2000000",
         "Static planner threshold: a join build side estimated at or "
         "below this many rows is broadcast instead of repartitioned."),
    Knob("TRINO_TPU_BROADCAST_THRESHOLD_BYTES", "int", "33554432",
         "Adaptive activation-barrier threshold: observed build bytes "
         "below this flip a repartitioned join to broadcast (and above, "
         "the reverse)."),
    Knob("TRINO_TPU_CLUSTER_MEMORY_BYTES", "int", "",
         "Cluster-wide reserved-memory cap enforced by the low-memory "
         "killer; unset disables the cap."),
    Knob("TRINO_TPU_COALESCE_TARGET_ROWS", "int", "65536",
         "Scan-ingest batch coalescing target row count."),
    Knob("TRINO_TPU_DRAIN_TIMEOUT_S", "float", "300",
         "Graceful-drain budget: a SHUTTING_DOWN worker abandons "
         "unfinished tasks and exits with code 9 past this."),
    Knob("TRINO_TPU_ENCODED_EXEC", "enum", "auto",
         "Compressed execution: operators consume dictionary codes, RLE "
         "runs, and lazy columns directly (decode at most once per "
         "query); 0 is bit-for-bit legacy expand-at-scan.",
         choices=("auto", "1", "0")),
    Knob("TRINO_TPU_EXCHANGE_STALL_S", "float", "1800",
         "Exchange take() stall watchdog: a source that produces nothing "
         "for this long fails the take with PAGE_TRANSPORT_TIMEOUT."),
    Knob("TRINO_TPU_EXEC_CACHE", "bool", "1",
         "Tier B executable-registry kill switch; 0 restores the legacy "
         "unbounded per-site memos."),
    Knob("TRINO_TPU_EXEC_CACHE_ENTRIES", "int", "256",
         "LRU capacity (entries) of each registered executable memo."),
    Knob("TRINO_TPU_EXEC_WARM", "bool", "1",
         "Replay exec_warm.json (journaled executable memo keys) on the "
         "worker boot path."),
    Knob("TRINO_TPU_FUSED_CAP", "int", "8192",
         "Fused-stage FINAL combine capacity (groups per task); overflow "
         "falls back to the legacy collective path for that query."),
    Knob("TRINO_TPU_FUSED_STAGE", "enum", "auto",
         "Whole-stage GSPMD compilation of PARTIAL->shuffle->FINAL seams; "
         "0 is bit-for-bit legacy collectives.",
         choices=("auto", "1", "0")),
    Knob("TRINO_TPU_HA", "bool", "0",
         "Horizontally-scaled HA control plane: the coordinator registers "
         "a heartbeated lease in TRINO_TPU_HA_DIR, owns queries by "
         "consistent hash, and claims dead peers' WAL directories; 0 is "
         "bit-for-bit single-coordinator legacy."),
    Knob("TRINO_TPU_HA_DIR", "path", "",
         "Shared cluster directory for the coordinator fleet (lease files, "
         "claim markers, per-coordinator query-state WAL roots); required "
         "when TRINO_TPU_HA=1."),
    Knob("TRINO_TPU_HA_HEARTBEAT_S", "float", "2",
         "Coordinator lease renewal cadence; must be well under the lease "
         "TTL."),
    Knob("TRINO_TPU_HA_LEASE_TTL_S", "float", "10",
         "Coordinator lease expiry: a lease not renewed for this long is "
         "dead and a peer may claim its WAL directory."),
    Knob("TRINO_TPU_HA_NODE_ID", "str", "",
         "Stable coordinator identity in the fleet directory (also "
         "suffixes the per-coordinator journal file); unset derives "
         "host-pid."),
    Knob("TRINO_TPU_HA_ROUTE_RETRY_S", "float", "15",
         "Front-tier retry-and-rehash budget: how long a routed request "
         "keeps probing live coordinators while the owner is mid-failover "
         "before reporting the query still QUEUED."),
    Knob("TRINO_TPU_HASH_IMPL", "enum", "auto",
         "Grouping/join hash index implementation.",
         choices=("auto", "pallas", "sort")),
    Knob("TRINO_TPU_HASH_INTERPRET", "bool", "0",
         "Run the Pallas hash kernels in interpret mode (CPU-only "
         "environments and kernel debugging)."),
    Knob("TRINO_TPU_HBO", "enum", "auto",
         "History-based optimization: the cost model prefers journaled "
         "per-fingerprint observed stats (rows, build bytes, partial-agg "
         "groups) over estimate_rows, and queries record plan_stats at "
         "completion; 0 disables both sides bit-for-bit.",
         choices=("auto", "1", "0")),
    Knob("TRINO_TPU_HBO_ROWS_PER_TASK", "int", "250000",
         "History-driven task fan-out: observed fragment rows divided by "
         "this sets the task count (capped at the worker count) for "
         "fragments whose fingerprint has history."),
    Knob("TRINO_TPU_INTERNAL_SECRET", "str", "",
         "Shared secret authenticating intra-cluster HTTP "
         "(coordinator<->worker); auto-generated per cluster boot when "
         "unset."),
    Knob("TRINO_TPU_JOIN_REORDER_DP_LIMIT", "int", "6",
         "Largest inner-join cluster (leaf relation count) the iterative "
         "optimizer enumerates exhaustively (left-deep dynamic "
         "programming); bigger clusters use the greedy ordering.  0 "
         "disables enumeration."),
    Knob("TRINO_TPU_JOURNAL", "bool", "1",
         "Durable query journal (JSONL EventListener); 0 disables."),
    Knob("TRINO_TPU_JOURNAL_DIR", "path", "",
         "Journal directory; unset uses a per-uid tempdir."),
    Knob("TRINO_TPU_JOURNAL_FILES", "int", "3",
         "Rotated journal generations kept."),
    Knob("TRINO_TPU_JOURNAL_MAX_BYTES", "int", "4194304",
         "Journal rotate threshold per file."),
    Knob("TRINO_TPU_MESH_SHAPE", "str", "",
         "Mesh-shape override for resident-plan programs (\"8\" or "
         "\"2x4\"); the dimension product caps the mesh width a plan may "
         "claim.  Unset sizes the mesh from the stage task count."),
    Knob("TRINO_TPU_OOM_POLICY", "enum", "largest_query",
         "Victim selection policy for the cluster low-memory killer.",
         choices=("largest_query", "lowest_priority", "youngest")),
    Knob("TRINO_TPU_PALLAS", "bool", "1",
         "Master switch for Pallas kernels; 0 forces the jnp fallbacks."),
    Knob("TRINO_TPU_PLAN_CACHE", "bool", "1",
         "Tier A fingerprinted logical-plan cache; 0 disables (checked "
         "per lookup)."),
    Knob("TRINO_TPU_PLAN_CACHE_ENTRIES", "int", "256",
         "Plan-cache LRU capacity (entries)."),
    Knob("TRINO_TPU_PREFETCH", "bool", "1",
         "Async scan ingest (ordered multi-split prefetch); 0 is the "
         "bit-for-bit synchronous legacy path, 1 forces it on even on "
         "single-core hosts."),
    Knob("TRINO_TPU_PREFETCH_QUEUE_BYTES", "int", "268435456",
         "Prefetch queue byte bound (backpressure)."),
    Knob("TRINO_TPU_PREFETCH_QUEUE_DEPTH", "int", "8",
         "Prefetch queue depth in coalesced batches."),
    Knob("TRINO_TPU_PREFETCH_THREADS", "int", "-1",
         "Prefetch decode threads; -1 auto-tunes from host cores "
         "(cpu_count-1 capped at 4; 0 on single-core hosts)."),
    Knob("TRINO_TPU_PROFILE", "enum", "default",
         "Flight-recorder level: default is a clock read + tuple store "
         "with zero hot syncs; full brackets operators with "
         "block_until_ready for true device time.",
         choices=("off", "default", "full")),
    Knob("TRINO_TPU_PROFILE_RING", "int", "4096",
         "Per-thread profiler event-ring capacity."),
    Knob("TRINO_TPU_QUERY_DEFAULT_MEMORY", "int", "67108864",
         "Admission fallback peak-memory estimate for queries with no "
         "journaled plan-fingerprint history."),
    Knob("TRINO_TPU_QUERY_MAX_MEMORY", "int", "0",
         "Per-query reserved-memory ceiling; exceeding it fails the query "
         "EXCEEDED_MEMORY_LIMIT.  0 = unlimited."),
    Knob("TRINO_TPU_QUERY_STATE", "bool", "1",
         "Write-ahead query-state log for retry_policy=TASK queries "
         "(coordinator crash recovery); 0 disables logging and recovery."),
    Knob("TRINO_TPU_QUERY_STATE_DIR", "path", "",
         "Query-state WAL directory; unset uses a per-uid tempdir next to "
         "the query journal."),
    Knob("TRINO_TPU_RESIDENT_MAX_FRAGMENTS", "int", "8",
         "Largest fragment count one resident-plan program may absorb; "
         "bigger coalesced subtrees stay on the fused/legacy path."),
    Knob("TRINO_TPU_RESIDENT_PLAN", "enum", "auto",
         "Whole-query GSPMD compilation (one program per maximal "
         "TPU-resident plan); 0 keeps the task-per-worker fused/legacy "
         "path bit-for-bit.",
         choices=("auto", "1", "0")),
    Knob("TRINO_TPU_RESOURCE_GROUPS", "json", "",
         "Hierarchical resource-group tree (weights, concurrency and "
         "queue limits, selectors) as JSON; unset uses one flat default "
         "group."),
    Knob("TRINO_TPU_RESULT_CACHE", "bool", "1",
         "Tier C versioned result cache; 0 disables (checked per "
         "lookup)."),
    Knob("TRINO_TPU_RESULT_CACHE_BYTES", "int", "67108864",
         "Result-cache LRU byte budget."),
    Knob("TRINO_TPU_SINK_MAX_BYTES", "int", "268435456",
         "Per-sink buffered-bytes cap (backpressure bound on output "
         "buffers)."),
    Knob("TRINO_TPU_SKEW_FACTOR", "float", "2.0",
         "Adaptive skew threshold: a join key heavier than this multiple "
         "of the mean partition weight is split across probe tasks."),
    Knob("TRINO_TPU_SPECULATION", "bool", "0",
         "Leaf-stage straggler speculation for retry_policy=QUERY "
         "streaming queries."),
    Knob("TRINO_TPU_SPECULATION_NONLEAF", "bool", "0",
         "Extend streaming straggler speculation to non-leaf stages by "
         "teeing producer pages into the durable spool (requires "
         "speculation on)."),
    Knob("TRINO_TPU_SPOOL_DIR", "path", "",
         "Base directory for durable FTE spool roots; unset uses the "
         "system tempdir."),
    Knob("TRINO_TPU_SPOOL_MAX_BYTES", "int", "1073741824",
         "Spool retention byte budget: the GC reclaims expired/leaked "
         "roots oldest-first once retained spools exceed this."),
    Knob("TRINO_TPU_SPOOL_TTL_S", "float", "3600",
         "Retention TTL for unreleased spool roots (crashed or abandoned "
         "queries); the boot sweep reclaims roots idle past this."),
    Knob("TRINO_TPU_STAGE_DEVICE", "bool", "1",
         "Double-buffered device staging of coalesced scan batches; 0 "
         "leaves batches on host until the operator touches them."),
    Knob("TRINO_TPU_TEST_BOOT_FAIL", "bool", "0",
         "Test-only: worker processes exit at boot to exercise the boot "
         "timeout path."),
    Knob("TRINO_TPU_TPCH_VECTOR_DECODE", "bool", "1",
         "Vectorized TPC-H string decode via vocab/code tables; 0 keeps "
         "the legacy per-row decode for bench baselines."),
)

KNOBS: dict = {k.name: k for k in _DECLARATIONS}

_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")


def declared(name: str) -> bool:
    return name in KNOBS


def knob(name: str) -> Knob:
    try:
        return KNOBS[name]
    except KeyError:
        raise KeyError(
            f"undeclared TRINO_TPU knob {name!r} — declare it in "
            f"trino_tpu/spi/knobs.py (the registry is the single source "
            f"of truth; see docs/KNOBS.md)") from None


def get_str(name: str) -> str:
    k = knob(name)
    return os.environ.get(k.name, k.default)


def get_int(name: str) -> Optional[int]:
    raw = get_str(name).strip()
    return int(raw) if raw else None


def get_float(name: str) -> Optional[float]:
    raw = get_str(name).strip()
    return float(raw) if raw else None


def get_bool(name: str) -> bool:
    raw = get_str(name).strip().lower()
    if raw in _FALSE or raw == "":
        return False
    return raw in _TRUE or raw not in _FALSE
