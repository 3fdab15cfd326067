"""Per-operator execution statistics (the OperatorStats equivalent).

Mirrors the role of operator/OperatorStats.java + OperationTimer: the Driver
credits wall time and row/batch counts to each operator as it moves pages, and
EXPLAIN ANALYZE renders the totals per pipeline (reference:
operator/ExplainAnalyzeOperator.java:36, sql/planner/planprinter/PlanPrinter).

Row counts use physical batch rows (padded slots included) so collecting
stats never forces a device sync on the hot path.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field


@dataclass
class ScanIngestStats:
    """Counters for the async scan-ingest pipeline (prefetch + coalesce +
    device staging).  One instance per ScanOperator; ``merge`` folds shard
    instances into the query-level roll-up rendered by QueryStats.text()."""

    scan_bytes: int = 0          # host bytes produced by connector sources
    scan_rows: int = 0
    scan_batches: int = 0        # raw connector batches
    coalesced_batches: int = 0   # merged batches emitted to the pipeline
    coalesced_rows: int = 0
    staged_batches: int = 0      # batches dispatched to device
    splits_opened: int = 0
    source_read_s: float = 0.0   # time inside connector get_next_batch
    consumer_wait_s: float = 0.0  # consumer blocked waiting on prefetch
    stage_s: float = 0.0         # device_put dispatch time
    queue_depth_max: int = 0
    queue_depth_sum: int = 0
    queue_samples: int = 0
    first_batch_t: float | None = None
    last_batch_t: float | None = None

    def observe_batch(self, nbytes: int, rows: int) -> None:
        now = time.perf_counter()
        if self.first_batch_t is None:
            self.first_batch_t = now
        self.last_batch_t = now
        self.scan_bytes += nbytes
        self.scan_rows += rows
        self.scan_batches += 1

    @property
    def wall_s(self) -> float:
        if self.first_batch_t is None or self.last_batch_t is None:
            return 0.0
        return self.last_batch_t - self.first_batch_t

    @property
    def gbps(self) -> float:
        """Scan ingest GB/s over the first->last batch window."""
        w = self.wall_s
        return (self.scan_bytes / w) / 1e9 if w > 0 else 0.0

    @property
    def queue_depth_avg(self) -> float:
        return self.queue_depth_sum / self.queue_samples if self.queue_samples else 0.0

    def merge(self, other: "ScanIngestStats") -> None:
        self.scan_bytes += other.scan_bytes
        self.scan_rows += other.scan_rows
        self.scan_batches += other.scan_batches
        self.coalesced_batches += other.coalesced_batches
        self.coalesced_rows += other.coalesced_rows
        self.staged_batches += other.staged_batches
        self.splits_opened += other.splits_opened
        self.source_read_s += other.source_read_s
        self.consumer_wait_s += other.consumer_wait_s
        self.stage_s += other.stage_s
        self.queue_depth_max = max(self.queue_depth_max, other.queue_depth_max)
        self.queue_depth_sum += other.queue_depth_sum
        self.queue_samples += other.queue_samples
        # overall window spans the earliest first batch to the latest last
        for t in (other.first_batch_t,):
            if t is not None and (self.first_batch_t is None or t < self.first_batch_t):
                self.first_batch_t = t
        for t in (other.last_batch_t,):
            if t is not None and (self.last_batch_t is None or t > self.last_batch_t):
                self.last_batch_t = t

    def text(self) -> str:
        return (
            f"scan[prefetch]: {self.scan_bytes / 1e9:.3f} GB "
            f"({self.scan_rows} rows, {self.scan_batches} batches -> "
            f"{self.coalesced_batches} coalesced) @ {self.gbps:.2f} GB/s, "
            f"queue depth avg {self.queue_depth_avg:.1f} max {self.queue_depth_max}, "
            f"read {self.source_read_s * 1e3:.1f} ms / wait "
            f"{self.consumer_wait_s * 1e3:.1f} ms / stage {self.stage_s * 1e3:.1f} ms"
        )


@dataclass
class ResilienceStats:
    """Counters for the query-level resilience layer (retry_policy=QUERY,
    heartbeat detection, worker replacement, exchange backoff) — the
    QueryStats/tracing surface of execution/failure_detector.py and the
    remote runner's retry loop."""

    query_retries: int = 0
    backoff_waits: int = 0
    backoff_wait_s: float = 0.0
    blacklisted_workers: int = 0
    worker_replacements: int = 0
    heartbeat_transitions: int = 0
    exchange_fetch_failures: int = 0
    exchange_backoff_trips: int = 0

    def merge(self, other: "ResilienceStats") -> None:
        self.query_retries += other.query_retries
        self.backoff_waits += other.backoff_waits
        self.backoff_wait_s += other.backoff_wait_s
        self.blacklisted_workers += other.blacklisted_workers
        self.worker_replacements += other.worker_replacements
        self.heartbeat_transitions += other.heartbeat_transitions
        self.exchange_fetch_failures += other.exchange_fetch_failures
        self.exchange_backoff_trips += other.exchange_backoff_trips

    @classmethod
    def delta(cls, after: "ResilienceStats",
              before: "ResilienceStats") -> "ResilienceStats":
        """after - before, field-wise (runner counters are cumulative; a
        query's own numbers are the delta across its retry loop)."""
        return cls(
            query_retries=after.query_retries - before.query_retries,
            backoff_waits=after.backoff_waits - before.backoff_waits,
            backoff_wait_s=after.backoff_wait_s - before.backoff_wait_s,
            blacklisted_workers=(after.blacklisted_workers
                                 - before.blacklisted_workers),
            worker_replacements=(after.worker_replacements
                                 - before.worker_replacements),
            heartbeat_transitions=(after.heartbeat_transitions
                                   - before.heartbeat_transitions),
            exchange_fetch_failures=(after.exchange_fetch_failures
                                     - before.exchange_fetch_failures),
            exchange_backoff_trips=(after.exchange_backoff_trips
                                    - before.exchange_backoff_trips),
        )

    @property
    def any(self) -> bool:
        return any((self.query_retries, self.backoff_waits,
                    self.blacklisted_workers, self.worker_replacements,
                    self.heartbeat_transitions, self.exchange_fetch_failures,
                    self.exchange_backoff_trips))

    def text(self) -> str:
        return (
            f"resilience: {self.query_retries} query retries "
            f"({self.backoff_waits} backoff waits, "
            f"{self.backoff_wait_s * 1e3:.0f} ms), "
            f"{self.blacklisted_workers} blacklists, "
            f"{self.worker_replacements} worker replacements, "
            f"{self.heartbeat_transitions} heartbeat transitions, "
            f"{self.exchange_fetch_failures} exchange fetch failures "
            f"({self.exchange_backoff_trips} backoff trips)"
        )


@dataclass
class FusedStageStats:
    """Counters for whole-stage GSPMD compilation (execution/stage_compiler.py):
    how many batches the fused accumulate program absorbed, how often the
    shape-bucket cache hit vs traced, and how many seam merges / legacy
    fallbacks ran.  One instance per FusedStageExec; ``merge`` folds the
    per-sink instances into the query-level roll-up."""

    stages: int = 0            # fused stage seams that executed
    compiles: int = 0          # distinct (program, bucket) traces
    cache_hits: int = 0        # jitted calls served by an existing trace
    jit_calls: int = 0         # accumulate-program dispatches (one per batch)
    batches: int = 0           # input batches absorbed
    input_rows: int = 0        # physical rows (padded slots included)
    merges: int = 0            # seam merge programs executed (one per stage)
    fallbacks: int = 0         # overflow -> legacy per-operator re-runs

    def merge(self, other: "FusedStageStats") -> None:
        self.stages += other.stages
        self.compiles += other.compiles
        self.cache_hits += other.cache_hits
        self.jit_calls += other.jit_calls
        self.batches += other.batches
        self.input_rows += other.input_rows
        self.merges += other.merges
        self.fallbacks += other.fallbacks

    @property
    def any(self) -> bool:
        return any((self.stages, self.jit_calls, self.batches,
                    self.merges, self.fallbacks))

    def text(self) -> str:
        return (
            f"fused: {self.stages} stages, {self.batches} batches "
            f"({self.input_rows} rows) in {self.jit_calls} jit calls, "
            f"{self.compiles} compiles / {self.cache_hits} cache hits, "
            f"{self.merges} seam merges, {self.fallbacks} fallbacks"
        )


@dataclass
class ResidentPlanStats:
    """Counters for whole-query GSPMD compilation (execution/plan_compiler.py):
    maximal TPU-resident plans compiled as ONE program per batch, interior
    seams (broadcast builds + the agg repartition) fused in-program, and the
    legacy re-runs taken when a plan can't hold (duplicate build keys, state
    overflow).  One instance per ResidentPlanExec; ``merge`` folds them into
    the query-level roll-up."""

    plans: int = 0             # resident plans that executed
    programs: int = 0          # distinct (program, bucket) traces compiled
    seams: int = 0             # interior exchange edges fused in-program
    batches: int = 0           # probe batches absorbed
    jit_calls: int = 0         # whole-plan program dispatches (one per batch)
    cache_hits: int = 0        # dispatches served by an existing trace
    input_rows: int = 0        # physical probe rows (padded slots included)
    merges: int = 0            # terminal seam merges (one per plan)
    code_seam_columns: int = 0  # dict-code lanes crossing an interior seam
    fallbacks: int = 0         # overflow/dup-key -> legacy re-runs
    fallback_reasons: list[str] = field(default_factory=list)

    def merge(self, other: "ResidentPlanStats") -> None:
        self.plans += other.plans
        self.programs += other.programs
        self.seams += other.seams
        self.batches += other.batches
        self.jit_calls += other.jit_calls
        self.cache_hits += other.cache_hits
        self.input_rows += other.input_rows
        self.merges += other.merges
        self.code_seam_columns += other.code_seam_columns
        self.fallbacks += other.fallbacks
        self.fallback_reasons.extend(other.fallback_reasons)

    @property
    def launches_per_batch(self) -> float:
        return self.jit_calls / self.batches if self.batches else 0.0

    @property
    def any(self) -> bool:
        return any((self.plans, self.batches, self.jit_calls,
                    self.merges, self.fallbacks))

    def text(self) -> str:
        why = f" ({', '.join(self.fallback_reasons)})" \
            if self.fallback_reasons else ""
        return (
            f"resident: {self.plans} plans ({self.seams} seams fused), "
            f"{self.batches} batches ({self.input_rows} rows) in "
            f"{self.jit_calls} jit calls "
            f"({self.launches_per_batch:.2f} launches/batch), "
            f"{self.programs} programs / {self.cache_hits} cache hits, "
            f"{self.code_seam_columns} code-seam columns, "
            f"{self.merges} merges, {self.fallbacks} fallbacks{why}"
        )


@dataclass
class SharingStats:
    """How a query shared its runner with the others in flight
    (telemetry/runtime.py QueryRecord; the flight recorder's ``execute``
    and ``task`` events carry the same figures per event)."""

    in_flight: int = 0       # executions open when this one began, itself too
    task_cpu_s: float = 0.0  # thread-CPU seconds of the tasks' threads
    task_wall_s: float = 0.0

    @property
    def any(self) -> bool:
        return self.in_flight > 0

    def text(self) -> str:
        share = 100.0 * self.task_cpu_s / self.task_wall_s \
            if self.task_wall_s else 0.0
        return (f"sharing: {self.in_flight} in flight at start, task cpu "
                f"{self.task_cpu_s:.3f} s of {self.task_wall_s:.3f} s task "
                f"wall ({share:.1f} %)")


@dataclass
class AdaptiveStats:
    """Counters + decision tags for the adaptive execution plane
    (execution/adaptive.py): phased stage activations and the join-
    distribution / skew decisions taken at activation barriers.  The
    ``decisions`` list carries compact human-readable tags
    (``flip_to_broadcast[f3]``, ``skew_split[f5:k2]``, ``keep[f3]``) that
    surface verbatim in EXPLAIN ANALYZE and system.runtime.queries."""

    activations: int = 0       # stages activated by the phased scheduler
    decision_points: int = 0   # barriers where a decision was evaluated
    broadcast_flips: int = 0   # PARTITIONED -> REPLICATED rewrites
    partition_flips: int = 0   # REPLICATED -> PARTITIONED rewrites
    skew_splits: int = 0       # heavy keys split across probe tasks
    memo_hits: int = 0         # decisions replayed from the runtime memo
    decisions: list[str] = field(default_factory=list)

    def merge(self, other: "AdaptiveStats") -> None:
        self.activations += other.activations
        self.decision_points += other.decision_points
        self.broadcast_flips += other.broadcast_flips
        self.partition_flips += other.partition_flips
        self.skew_splits += other.skew_splits
        self.memo_hits += other.memo_hits
        self.decisions.extend(other.decisions)

    @property
    def any(self) -> bool:
        return any((self.activations, self.decision_points,
                    self.broadcast_flips, self.partition_flips,
                    self.skew_splits))

    def text(self) -> str:
        tags = ", ".join(self.decisions) if self.decisions else "none"
        return (
            f"adaptive: {self.activations} phased activations, "
            f"{self.decision_points} decision points "
            f"({self.broadcast_flips} -> broadcast, "
            f"{self.partition_flips} -> partitioned, "
            f"{self.skew_splits} skew splits, "
            f"{self.memo_hits} memo hits); decisions: {tags}"
        )


@dataclass
class EncodingStats:
    """Counters for compressed execution (TRINO_TPU_ENCODED_EXEC): batches
    by encoding, bytes saved vs a flat representation, lazy columns that
    were filtered away before their thunk ever ran, and dictionary codes
    surviving exchanges; and, per aggregation computed, the reduction path
    it took and what became of compaction in front of it.  One instance
    per encoding-aware operator; ``merge`` folds them into the query-level
    roll-up."""

    rle_batches: int = 0        # batches carrying >=1 RLE column
    dict_batches: int = 0       # batches carrying >=1 dictionary column
    lazy_columns: int = 0       # LAZY columns created by staging
    lazy_materialized: int = 0  # thunks that actually ran
    bytes_saved: int = 0        # flat-equivalent minus encoded bytes
    lazy_skipped_bytes: int = 0  # payload bytes never staged
    rle_agg_rows: int = 0       # rows aggregated as value * run_count
    code_group_batches: int = 0  # group-bys that ran on int32 codes
    code_join_batches: int = 0   # joins probed in code space
    exchange_code_pages: int = 0  # pages whose codes crossed a shuffle
    # HashAggregationOperator, once per flush / finish / merge / stream:
    agg_masked: int = 0      # O(lanes) masked reductions (kernels.small_agg)
    agg_codes_sort: int = 0  # argsort of fused dictionary codes
    agg_sort: int = 0        # lexsort / hash group ids, or global DISTINCT
    agg_compacted: int = 0   # a sorting path compacted its sparse input
    agg_compaction_skipped: int = 0  # masked path took the dead lanes as-is
    # the streaming masked aggregation (HashAggregationOperator._streams):
    agg_streamed_batches: int = 0  # batches folded into a running state
    agg_fold_launches: int = 0  # ... in this many launches (a group each)
    agg_fused_feed: int = 0    # aggregations that absorbed their filter/project
    agg_state_seals: int = 0   # states sealed by a change of dictionaries
    # decimal AVGs (operators._avg_partners), once per aggregation computed:
    agg_avg_shared: int = 0    # read a paired SUM's int64 state
    agg_avg_unscaled: int = 0  # summed their unscaled integers in f64

    def count_aggregation(self, path: str, compaction: str, slots) -> None:
        """path: masked | codes-sort | sort; compaction: compacted |
        skipped | none (not a candidate, or counted and found dense);
        slots: the aggregation's operators._Slots."""
        self.agg_avg_shared += slots.avg_shared
        self.agg_avg_unscaled += slots.avg_unscaled
        if path == "masked":
            self.agg_masked += 1
        elif path == "codes-sort":
            self.agg_codes_sort += 1
        else:
            self.agg_sort += 1
        if compaction == "compacted":
            self.agg_compacted += 1
        elif compaction == "skipped":
            self.agg_compaction_skipped += 1

    def merge(self, other: "EncodingStats") -> None:
        self.rle_batches += other.rle_batches
        self.dict_batches += other.dict_batches
        self.lazy_columns += other.lazy_columns
        self.lazy_materialized += other.lazy_materialized
        self.bytes_saved += other.bytes_saved
        self.lazy_skipped_bytes += other.lazy_skipped_bytes
        self.rle_agg_rows += other.rle_agg_rows
        self.code_group_batches += other.code_group_batches
        self.code_join_batches += other.code_join_batches
        self.exchange_code_pages += other.exchange_code_pages
        self.agg_masked += other.agg_masked
        self.agg_codes_sort += other.agg_codes_sort
        self.agg_sort += other.agg_sort
        self.agg_compacted += other.agg_compacted
        self.agg_compaction_skipped += other.agg_compaction_skipped
        self.agg_streamed_batches += other.agg_streamed_batches
        self.agg_fold_launches += other.agg_fold_launches
        self.agg_fused_feed += other.agg_fused_feed
        self.agg_state_seals += other.agg_state_seals
        self.agg_avg_shared += other.agg_avg_shared
        self.agg_avg_unscaled += other.agg_avg_unscaled

    @property
    def any(self) -> bool:
        return any((self.rle_batches, self.dict_batches, self.lazy_columns,
                    self.bytes_saved, self.lazy_skipped_bytes,
                    self.rle_agg_rows, self.code_group_batches,
                    self.code_join_batches, self.exchange_code_pages,
                    self.agg_masked, self.agg_codes_sort, self.agg_sort))

    def text(self) -> str:
        never = self.lazy_columns - self.lazy_materialized
        return (
            f"encoding: {self.rle_batches} RLE / {self.dict_batches} dict "
            f"batches, {self.lazy_columns} lazy columns "
            f"({never} never materialized, "
            f"{self.lazy_skipped_bytes / 1e6:.2f} MB skipped), "
            f"{self.bytes_saved / 1e6:.2f} MB saved vs flat, "
            f"{self.rle_agg_rows} RLE-agg rows, "
            f"{self.code_group_batches} code group-bys / "
            f"{self.code_join_batches} code joins, "
            f"{self.exchange_code_pages} code pages through exchange; "
            f"aggregations: {self.agg_masked} masked / "
            f"{self.agg_codes_sort} codes-sort / {self.agg_sort} sort, "
            f"{self.agg_compacted} compacted, "
            f"{self.agg_compaction_skipped} compaction skipped, "
            f"{self.agg_streamed_batches} batches streamed in "
            f"{self.agg_fold_launches} launches "
            f"({self.agg_fused_feed} aggregations fused with their "
            f"filter/project, {self.agg_state_seals} state seals); "
            f"decimal avg: {self.agg_avg_shared} read their sum's integers, "
            f"{self.agg_avg_unscaled} summed unscaled"
        )


@dataclass
class OperatorStats:
    name: str
    input_rows: int = 0
    output_rows: int = 0
    input_batches: int = 0
    output_batches: int = 0
    wall_s: float = 0.0


@dataclass
class PipelineStats:
    operators: list[OperatorStats] = field(default_factory=list)


@dataclass
class QueryStats:
    """One query's (or one task's) operator stats, per pipeline."""

    label: str = ""
    pipelines: list[PipelineStats] = field(default_factory=list)
    scan: ScanIngestStats | None = None
    sync: "object | None" = None  # syncguard.SyncStats delta for this query
    resilience: ResilienceStats | None = None  # retry/heartbeat delta
    fused: FusedStageStats | None = None  # whole-stage compilation counters
    resident: ResidentPlanStats | None = None  # whole-plan compilation counters
    adaptive: AdaptiveStats | None = None  # adaptive-execution decisions
    encoding: EncodingStats | None = None  # compressed-execution counters
    sharing: SharingStats | None = None  # in flight at start, task CPU

    def merge_scan(self, ingest: ScanIngestStats) -> None:
        if self.scan is None:
            self.scan = ScanIngestStats()
        self.scan.merge(ingest)

    def merge_encoding(self, enc: EncodingStats) -> None:
        if self.encoding is None:
            self.encoding = EncodingStats()
        self.encoding.merge(enc)

    def merge_fused(self, fused: FusedStageStats) -> None:
        if self.fused is None:
            self.fused = FusedStageStats()
        self.fused.merge(fused)

    def merge_resident(self, resident: ResidentPlanStats) -> None:
        if self.resident is None:
            self.resident = ResidentPlanStats()
        self.resident.merge(resident)

    def merge_sync(self, sync) -> None:
        if self.sync is None:
            from .syncguard import SyncStats

            self.sync = SyncStats()
        self.sync.merge(sync)

    def text(self) -> str:
        lines = []
        if self.label:
            lines.append(self.label)
        if self.scan is not None and self.scan.scan_batches:
            lines.append("  " + self.scan.text())
        if self.sync is not None and (
                self.sync.host_syncs or self.sync.exchange_pages_device
                or self.sync.exchange_pages_densified):
            lines.append("  " + self.sync.text())
        if self.resilience is not None and self.resilience.any:
            lines.append("  " + self.resilience.text())
        if self.fused is not None and self.fused.any:
            lines.append("  " + self.fused.text())
        if self.resident is not None and self.resident.any:
            lines.append("  " + self.resident.text())
        if self.adaptive is not None and self.adaptive.any:
            lines.append("  " + self.adaptive.text())
        if self.encoding is not None and self.encoding.any:
            lines.append("  " + self.encoding.text())
        if self.sharing is not None and self.sharing.any:
            lines.append("  " + self.sharing.text())
        for i, p in enumerate(self.pipelines):
            lines.append(f"  pipeline {i}:")
            for op in p.operators:
                lines.append(
                    f"    {op.name}: {op.wall_s * 1e3:.1f} ms, "
                    f"in {op.input_rows} rows/{op.input_batches} batches, "
                    f"out {op.output_rows} rows/{op.output_batches} batches")
        return "\n".join(lines)
