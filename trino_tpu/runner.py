"""StandaloneQueryRunner: SQL string → result batch, in process.

The single-node equivalent of the reference's StandaloneQueryRunner
(core/trino-main/src/main/java/io/trino/testing/StandaloneQueryRunner.java):
parse → plan → optimize → local-plan → drive.  The distributed runner
(coordinator + workers + exchanges) layers on top of the same pieces.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

from .connectors.catalog import Catalog, default_catalog
from .exec.driver import (collect_encoding_stats, collect_scan_stats,
                          run_pipelines)
from .exec.local_planner import LocalPlanner
from .exec.stats import QueryStats
from .execution.tracing import annotate_scan_span, annotate_sync_span
from .planner.logical import LogicalPlanner
from .planner.optimizer import optimize
from .planner.plan import PlanNode, plan_text
from .spi.batch import Column, ColumnBatch
from .spi.types import VARCHAR
from .sql import ast
from .sql.parser import parse_statement

__all__ = ["QueryResult", "StandaloneQueryRunner"]


def text_result(name: str, lines: list[str]) -> "QueryResult":
    return QueryResult([name], ColumnBatch(
        [name], [Column.from_values(VARCHAR, lines)]))


def count_result(name: str, n: int) -> "QueryResult":
    from .spi.types import BIGINT

    return QueryResult([name], ColumnBatch(
        [name], [Column(BIGINT, np.array([n], np.int64))]))


def _refresh_materialized_view(name: str, catalog, run_select,
                               default_catalog_name: str = "memory") -> int:
    """(Re)materialize a view into its backing table in the 'memory'
    catalog; returns the row count (reference:
    operator/RefreshMaterializedViewOperator.java:27)."""
    from .connectors.catalog import ViewDefinition  # noqa: F401
    from .spi.connector import ColumnSchema, TableSchema

    view = catalog.views[name]
    conn = catalog.connector("memory")
    # capture the base tables' data_version vector BEFORE reading them:
    # Catalog.mv_is_stale compares these against current tokens, and a base
    # mutation racing the refresh must leave the MV looking stale, not fresh
    try:
        from .caching import plan_cache
        from .planner.logical import LogicalPlanner

        base_plan = LogicalPlanner(catalog, default_catalog_name).plan(
            ast.QueryStatement(view.query))
        base_versions = catalog.table_versions(
            plan_cache.scan_tables(base_plan))
    except Exception:  # noqa: BLE001 — staleness stays conservative (None)
        base_versions = None
    result = run_select(ast.QueryStatement(view.query))
    batch = result.batch.compact()
    backing = f"__mv_{name}"
    conn.drop_table(backing)
    conn.create_table(TableSchema(backing, tuple(
        ColumnSchema(n, c.type)
        for n, c in zip(result.names, batch.columns))))
    sink = conn.create_page_sink(backing)
    sink.append(batch.rename(list(result.names)))
    conn.finish_insert(backing, sink.finish())
    view.backing = ("memory", backing)
    view.base_versions = base_versions
    return batch.num_rows


def _literal_value(e):
    """Constant AST node -> python value (SET SESSION / CALL arguments)."""
    if isinstance(e, (ast.IntLiteral, ast.DoubleLiteral, ast.BooleanLiteral,
                      ast.StringLiteral)):
        return e.value
    if isinstance(e, ast.DecimalLiteral):
        return float(e.text)
    if isinstance(e, ast.NullLiteral):
        return None
    raise ValueError("expected a constant")


# knobs SET SESSION may touch; identity/transaction/injection state is NOT
# settable through SQL (a restricted user must not setattr session.user)
SETTABLE_SESSION_PROPERTIES = {
    "default_catalog", "splits_per_node", "node_count", "dynamic_filtering",
    "hbm_limit_bytes", "spill_to_disk_bytes", "use_collectives",
    "exchange_serde", "retry_policy", "task_retry_attempts",
    "task_scheduler", "executor_workers", "query_concurrency",
    "query_max_queued", "scale_writers", "writer_task_limit",
    "task_concurrency", "fte_speculative", "fte_speculative_delay_s",
    "fte_memory_growth",
    "query_retry_attempts", "retry_initial_delay_s", "retry_max_delay_s",
    "heartbeat_interval_s", "heartbeat_failure_threshold",
    "max_worker_replacements", "exchange_backoff_min_s",
    "exchange_backoff_max_s", "exchange_max_failure_duration_s",
    "speculation", "speculation_lag_multiplier", "speculation_min_delay_s",
    "speculation_nonleaf",
    "blacklist_ttl_s", "blacklist_threshold", "drain_timeout_s",
    "adaptive", "broadcast_threshold_bytes", "skew_factor",
}


def execute_session_stmt(stmt, session) -> Optional["QueryResult"]:
    """SET SESSION (reference: execution/SetSessionTask.java): mutate a
    public Session knob with loose literal typing."""
    if not isinstance(stmt, ast.SetSession):
        return None
    name = stmt.name.lower()
    if name not in SETTABLE_SESSION_PROPERTIES:
        raise KeyError(f"unknown or protected session property: {name}")
    value = _literal_value(stmt.value)
    current = getattr(session, name)
    if isinstance(current, bool) and not isinstance(value, bool):
        value = str(value).lower() in ("true", "1")
    elif isinstance(current, int) and not isinstance(value, bool) \
            and value is not None:
        value = int(value)
    setattr(session, name, value)
    return text_result("result", [f"{name} = {value}"])


def execute_ddl(stmt, catalog, default_catalog_name: str,
                run_select) -> Optional["QueryResult"]:
    """Metadata statements shared by both runners (CREATE TABLE with
    columns, DROP TABLE, DELETE).  Returns None for non-DDL statements.
    Reference: metadata/MetadataManager create/drop, and DELETE planned as
    scan+filter+rewrite (the simple connectors have no row-id deletes)."""
    out = _execute_ddl(stmt, catalog, default_catalog_name, run_select)
    if out is not None:
        # any metadata statement (DDL, views, functions, ANALYZE stats,
        # procedures) may change how future statements plan: cached
        # logical plans against the old catalog state must miss
        catalog.bump_generation()
    return out


def _execute_ddl(stmt, catalog, default_catalog_name: str,
                 run_select) -> Optional["QueryResult"]:
    from .spi.connector import ColumnSchema, TableSchema
    from .spi.types import parse_type

    if isinstance(stmt, ast.CreateFunction):
        from .sql.analyzer import is_builtin_function

        if is_builtin_function(stmt.name):
            raise ValueError(
                f"cannot create function {stmt.name!r}: shadows a builtin")
        catalog.sql_functions[stmt.name.lower()] = (
            stmt.params, stmt.return_type, stmt.body)
        return count_result("rows", 0)
    if isinstance(stmt, ast.DropFunction):
        if catalog.sql_functions.pop(stmt.name.lower(), None) is None:
            raise KeyError(f"no such function: {stmt.name}")
        return count_result("rows", 0)
    if isinstance(stmt, ast.CreateView):
        from .connectors.catalog import ViewDefinition

        name = stmt.name.split(".")[-1]
        if name in catalog.views and not stmt.replace:
            raise ValueError(f"view already exists: {name}")
        catalog.views[name] = ViewDefinition(stmt.query, stmt.materialized)
        if stmt.materialized:
            _refresh_materialized_view(name, catalog, run_select,
                                       default_catalog_name)
        return count_result("rows", 0)
    if isinstance(stmt, ast.DropView):
        name = stmt.name.split(".")[-1]
        view = catalog.views.pop(name, None)
        if view is None:
            if stmt.if_exists:
                return count_result("rows", 0)
            raise KeyError(f"no such view: {name}")
        if view.backing is not None:
            catalog.connector(view.backing[0]).drop_table(view.backing[1])
        return count_result("rows", 0)
    if isinstance(stmt, ast.RefreshMaterializedView):
        name = stmt.name.split(".")[-1]
        if name not in catalog.views or not catalog.views[name].materialized:
            raise KeyError(f"no such materialized view: {name}")
        rows = _refresh_materialized_view(name, catalog, run_select,
                                          default_catalog_name)
        return count_result("rows", rows)
    if isinstance(stmt, ast.CallProcedure):
        cat, proc = _split_name(stmt.name, default_catalog_name)
        procs = catalog.connector(cat).get_procedures()
        if proc not in procs:
            raise KeyError(f"no such procedure: {cat}.{proc}")
        out = procs[proc](*[_literal_value(a) for a in stmt.args])
        return text_result("result", [str(out)])
    if isinstance(stmt, ast.Analyze):
        cat, table, schema = catalog.resolve_table(
            stmt.table, default_catalog_name)
        conn = catalog.connector(cat)
        from .spi.connector import TableStatistics

        rows = 0
        ndv: dict[str, set] = {c.name: set() for c in schema.columns}
        cols = [c.name for c in schema.columns]
        for split in conn.get_splits(table, 1, 1):
            src = conn.create_page_source(split, cols)
            while not src.is_finished():
                b = src.get_next_batch()
                if b is None:
                    continue
                b = b.compact()
                rows += b.num_rows
                for name_, col in zip(b.names, b.columns):
                    data = np.asarray(col.data)
                    if col.valid is not None:
                        data = data[np.asarray(col.valid)]
                    if col.dictionary is not None:
                        # codes are per-batch namespaces: count VALUES
                        ndv[name_].update(col.dictionary[np.unique(data)])
                    else:
                        ndv[name_].update(np.unique(data).tolist())
        conn.set_analyzed_statistics(table, TableStatistics(
            row_count=float(rows),
            ndv={k: float(len(v)) for k, v in ndv.items()}))
        return count_result("rows", rows)
    if isinstance(stmt, ast.CreateTable):
        cat, table = _split_name(stmt.table, default_catalog_name)
        conn = catalog.connector(cat)
        conn.create_table(TableSchema(table, tuple(
            ColumnSchema(n, parse_type(t)) for n, t in stmt.columns)))
        return count_result("rows", 0)
    if isinstance(stmt, ast.DropTable):
        cat, table = _split_name(stmt.table, default_catalog_name)
        conn = catalog.connector(cat)
        try:
            conn.get_table_schema(table)
        except KeyError:
            if stmt.if_exists:
                return count_result("rows", 0)
            raise
        conn.drop_table(table)
        return count_result("rows", 0)
    if isinstance(stmt, ast.Delete):
        cat, table, schema = catalog.resolve_table(
            stmt.table, default_catalog_name)
        conn = catalog.connector(cat)
        from .spi.connector import Connector as _BaseConnector

        impl = getattr(type(conn), "create_page_sink", None)
        if impl is None or impl is _BaseConnector.create_page_sink:
            raise ValueError(f"connector {cat} does not support DELETE")
        stats = conn.get_table_statistics(table)
        before = int(stats.row_count) if stats.row_count == stats.row_count else None
        if before is None:  # no stats: count the table first
            cq = ast.Query(ast.QuerySpec(
                (ast.SelectItem(ast.FunctionCall("count", (), is_star=True)),),
                False, ast.Table(f"{cat}.{table}"), None, (), None))
            before = int(run_select(ast.QueryStatement(cq)).rows()[0][0])
        # rows to KEEP: NOT coalesce(pred, false) — NULL predicates keep
        if stmt.where is None:
            keep_where = ast.BooleanLiteral(False)
        else:
            keep_where = ast.Not(ast.FunctionCall(
                "coalesce", (stmt.where, ast.BooleanLiteral(False))))
        q = ast.Query(ast.QuerySpec(
            (ast.SelectItem(None),), False,
            ast.Table(f"{cat}.{table}"), keep_where, (), None))
        kept = run_select(ast.QueryStatement(q))
        # stage the kept rows FIRST: every risky step (serde, disk) happens
        # before the original table is touched, so a failed rewrite cannot
        # destroy data
        staging = f"__rewrite_{table}"
        conn.drop_table(staging)
        conn.create_table(TableSchema(staging, schema.columns))
        try:
            sink = conn.create_page_sink(staging)
            sink.append(kept.batch)
            conn.finish_insert(staging, sink.finish())
        except BaseException:
            conn.drop_table(staging)
            raise
        conn.drop_table(table)
        conn.create_table(TableSchema(table, schema.columns))
        sink = conn.create_page_sink(table)
        for split in conn.get_splits(staging, 1, 1):
            src = conn.create_page_source(
                split, [c.name for c in schema.columns])
            while not src.is_finished():
                b = src.get_next_batch()
                if b is not None:
                    sink.append(b)
        conn.finish_insert(table, sink.finish())
        conn.drop_table(staging)
        kept_rows = kept.batch.compact().num_rows
        return count_result("rows", before - kept_rows)
    return None


def run_with_query_events(qid: str, sql: str, user: str, listeners, tracer,
                          thunk):
    """Shared query lifecycle wrapper: created/completed events, the root
    tracing span, the process query registry entry
    (telemetry/runtime.py -> system.runtime.queries) and the query-level
    metrics (telemetry/metrics.py) around ``thunk`` (both runners use this;
    reference: QueryMonitor emitting eventlistener events around the
    dispatch).  ``cpu_ms`` is process CPU over the query window —
    concurrent queries overlap in it, like the reference's per-node
    cumulative totals."""
    import time as _time

    from .spi.eventlistener import QueryCompletedEvent, QueryCreatedEvent
    from .telemetry import metrics as tm
    from .telemetry import profiler
    from .telemetry import runtime as rt

    # the root span opens first: it is the flight recorder's ``execute``
    # span, the one boundary a benchmark also sees from outside, so the
    # less runs between the caller's clock read and this one the better
    # the two clocks can be matched (benchmark/harness/program_spans.py)
    prof_ctx = profiler.set_context(qid)
    root = tracer.span("trino.query", query_id=qid)
    in_flight = tracer.query_opened()
    root.__enter__().record(in_flight=in_flight)
    listeners.query_created(QueryCreatedEvent(qid, sql, user))
    rec = rt.query_started(qid, sql, user)
    rec.in_flight = in_flight
    tm.QUERIES_STARTED.inc()
    t0 = _time.perf_counter()
    cpu0 = _time.process_time()

    def _finish(state: str, rows: int, error, error_code=None):
        tracer.query_closed()
        wall = (_time.perf_counter() - t0) * 1e3
        cpu = (_time.process_time() - cpu0) * 1e3
        tm.QUERY_WALL_SECONDS.record(wall / 1e3)
        (tm.QUERIES_FINISHED if state == "FINISHED"
         else tm.QUERIES_FAILED).inc()
        peak = tm.update_device_memory_watermark() or 0
        rt.query_finished(rec, state, wall, cpu, rows, error,
                          peak_memory_bytes=peak)
        # this process's ring events move into the bounded per-query
        # profile store before the rings can wrap (worker-process events
        # arrive separately, via task status JSON)
        profiler.harvest(qid)
        # Tier B warm journal: persist any memo keys this query minted so
        # the next process can pre-instantiate them at boot (no-op when
        # nothing changed — one flag check per query)
        try:
            from .caching import executable_cache

            executable_cache.flush_warm_keys()
        except Exception:  # noqa: BLE001 — persistence is best-effort
            pass
        profiler.apply_context(prof_ctx)
        listeners.query_completed(QueryCompletedEvent(
            qid, sql, state, user, wall, rows, error,
            cpu_ms=cpu, peak_memory_bytes=peak,
            input_rows=rec.input_rows, input_bytes=rec.input_bytes,
            retry_count=rec.retry_count,
            queued_time_ms=rec.queued_ms,
            resource_group=rec.resource_group,
            speculative_wins=rec.speculative_wins,
            error_code=error_code))

    try:
        result = thunk()
    except BaseException as e:
        from .spi.errors import classify

        root.__exit__(type(e), e, e.__traceback__)
        _finish("FAILED", -1, str(e), error_code=classify(e).code.name)
        raise
    root.__exit__(None, None, None)
    rows = result.batch.live_count if result.batch.columns else 0
    _finish("FINISHED", rows, None)
    return result


def check_select_access(plan, access_control, user: str) -> None:
    """Every table the plan scans needs SELECT on its projected columns
    (reference: AccessControlManager.checkCanSelectFromColumns called from
    StatementAnalyzer)."""
    from .planner.plan import TableScan

    def walk(node):
        if isinstance(node, TableScan):
            access_control.check_can_select(
                user, node.catalog, node.table, node.columns)
        for c in node.children:
            walk(c)

    walk(plan)


def check_ddl_access(stmt, access_control, user: str,
                     default_catalog_name: str) -> None:
    """Pre-execution privilege checks for metadata/write statements."""
    if isinstance(stmt, (ast.CreateTable, ast.CreateTableAsSelect)):
        cat, table = _split_name(stmt.table, default_catalog_name)
        access_control.check_can_create_table(user, cat, table)
    elif isinstance(stmt, ast.DropTable):
        cat, table = _split_name(stmt.table, default_catalog_name)
        access_control.check_can_drop_table(user, cat, table)
    elif isinstance(stmt, ast.InsertInto):
        cat, table = _split_name(stmt.table, default_catalog_name)
        access_control.check_can_insert(user, cat, table)
    elif isinstance(stmt, ast.Delete):
        cat, table = _split_name(stmt.table, default_catalog_name)
        access_control.check_can_delete(user, cat, table)


def _split_name(name: str, default: str) -> tuple[str, str]:
    parts = name.split(".")
    if len(parts) == 1:
        return default, parts[0]
    return parts[0], parts[-1]


@dataclass
class QueryResult:
    names: list[str]
    batch: ColumnBatch

    def rows(self) -> list[tuple]:
        return self.batch.to_pylist()


@dataclass
class Session:
    """Per-query knobs (the SystemSessionProperties miniature)."""

    default_catalog: str = "tpch"
    user: str = "user"
    splits_per_node: int = 4
    node_count: int = 1
    dynamic_filtering: bool = True
    # per-task HBM pool limit for blocking operators' buffered device bytes
    hbm_limit_bytes: int = 16 << 30
    # per-operator host-buffer bytes before the disk spill tier engages
    # (0 = disabled)
    spill_to_disk_bytes: int = 0
    # REPARTITION edges run as device collectives (all_to_all) when the
    # mesh has enough devices; host exchange is the fallback
    use_collectives: bool = True
    # serialize exchange pages to compressed wire bytes (network mode)
    exchange_serde: bool = False
    # NONE = streaming pipelined scheduler; TASK = fault-tolerant execution
    # (stage-by-stage spooled exchange + per-task retry); QUERY = streaming
    # scheduler with coordinator query-level retry — on a retryable
    # (non-USER) failure the whole subplan re-runs with the implicated
    # worker blacklisted (reference: coordinator query retries keep the
    # pipelined overlap; recovery unit is the query)
    retry_policy: str = "NONE"
    task_retry_attempts: int = 2
    # retry_policy=QUERY knobs: attempt budget and the deterministic
    # exponential backoff between re-runs (spi/errors.py Backoff)
    query_retry_attempts: int = 2
    retry_initial_delay_s: float = 0.1
    retry_max_delay_s: float = 2.0
    # heartbeat failure detection over worker /v1/status
    # (execution/failure_detector.py): sweep cadence and how many
    # consecutive probe misses declare a worker GONE
    heartbeat_interval_s: float = 0.5
    heartbeat_failure_threshold: int = 3
    # how many GONE workers the runner may respawn over its lifetime
    # (0 = never replace; capacity shrinks instead)
    max_worker_replacements: int = 2
    # per-source exchange backoff (HttpExchangeClient): delay bounds and the
    # failure-duration budget after which an unreachable producer surfaces
    # as a classified EXTERNAL failure instead of a silent stall
    exchange_backoff_min_s: float = 0.05
    exchange_backoff_max_s: float = 2.0
    exchange_max_failure_duration_s: float = 120.0
    # intra-task parallelism: concurrent source drivers per pipeline over a
    # local gather exchange (reference: LocalExchange.java:67 +
    # AddLocalExchanges.java:111; task_concurrency session property)
    task_concurrency: int = 1
    # THREADS = a thread per task; TIME_SHARING = bounded worker pool with
    # MLFQ quanta (TimeSharingTaskExecutor)
    task_scheduler: str = "THREADS"
    executor_workers: int = 4
    # dispatcher admission: concurrent queries per runner (resource groups;
    # reference: execution/resourcegroups/InternalResourceGroup.java:75)
    query_concurrency: int = 16
    query_max_queued: int = 200
    # multi-tenant serving (execution/resource_manager.py): the selector
    # workload tag (maps to a resource group via TRINO_TPU_RESOURCE_GROUPS
    # selectors), the ticket priority under scheduling_policy=query_priority
    # and the OOM-killer victim ordering, the admission-queue wait budget,
    # and the per-query reservation cap (0 = TRINO_TPU_QUERY_MAX_MEMORY env
    # or unlimited)
    source: str = ""
    query_priority: int = 0
    query_queued_timeout_s: float = 300.0
    query_max_memory_bytes: int = 0
    # active transaction (execution/transaction.py); None = autocommit
    transaction: object = None
    _transaction_manager: object = None
    # engine-level failure injection (execution/failure_injector.py;
    # reference: execution/FailureInjector.java:35)
    failure_injector: object = None
    # base directory for the durable FTE spool (None = system temp)
    fte_spool_dir: object = None
    # FTE tier 2 (reference: TaskExecutionClass.java:19 STANDARD/SPECULATIVE,
    # ExponentialGrowthPartitionMemoryEstimator.java:55): stragglers get a
    # speculative attempt once half the stage committed and the task exceeds
    # max(2x median stage duration, fte_speculative_delay_s); a memory
    # failure multiplies the next attempt's HBM budget by fte_memory_growth
    fte_speculative: bool = True
    fte_speculative_delay_s: float = 0.25
    fte_memory_growth: float = 2.0
    # streaming-path straggler speculation (execution/speculation.py): the
    # tri-state None defers to TRINO_TPU_SPECULATION; a leaf task whose wall
    # time exceeds max(lag_multiplier x stage-median, min_delay) without a
    # committed page gets a racing twin under first-commit-wins
    speculation: object = None
    speculation_lag_multiplier: float = 2.0
    speculation_min_delay_s: float = 0.25
    # non-leaf streaming speculation (tri-state None defers to
    # TRINO_TPU_SPECULATION_NONLEAF): producers feeding an eligible
    # non-leaf stage tee their pages into a durable spool so a straggling
    # consumer's twin can re-read committed upstream output — the retention
    # FTE's spool provides, now available to retry_policy=QUERY
    speculation_nonleaf: object = None
    # cross-query cluster blacklist (coordinator-held, TTL decay): None
    # defers to TRINO_TPU_BLACKLIST_TTL_S / TRINO_TPU_BLACKLIST_THRESHOLD
    blacklist_ttl_s: object = None
    blacklist_threshold: object = None
    # coordinator-driven graceful drain budget (None = the
    # TRINO_TPU_DRAIN_TIMEOUT_S env knob, default 30s coordinator-side)
    drain_timeout_s: object = None
    # adaptive execution (execution/adaptive.py): tri-state None defers to
    # TRINO_TPU_ADAPTIVE ("auto" default; "0" is bit-for-bit legacy, "1"
    # forces the phased scheduler); 0 thresholds defer to
    # TRINO_TPU_BROADCAST_THRESHOLD_BYTES / TRINO_TPU_SKEW_FACTOR
    adaptive: object = None
    broadcast_threshold_bytes: int = 0
    skew_factor: float = 0.0
    # INSERT/CTAS fan out over round-robin writer tasks when the source is
    # large (SCALED_WRITER_* partitionings in miniature; planned by estimate)
    scale_writers: bool = False
    writer_task_limit: int = 4


class StandaloneQueryRunner:
    def __init__(self, catalog: Optional[Catalog] = None,
                 session: Optional[Session] = None):
        import itertools

        from .execution.tracing import Tracer
        from .spi.eventlistener import EventListenerManager
        from .spi.security import AccessControlManager

        self.catalog = catalog if catalog is not None else default_catalog()
        self.session = session if session is not None else Session()
        self.tracer = Tracer()
        self.event_listeners = EventListenerManager()
        self.access_control = AccessControlManager()
        self._qids = itertools.count(1)
        sysconn = self.catalog._connectors.get("system")
        if sysconn is not None and hasattr(sysconn, "attach"):
            sysconn.attach(self)
        from .telemetry import journal as _journal

        j = _journal.get_journal()
        if j is not None:
            self.event_listeners.add(j)
        from .caching import executable_cache

        executable_cache.init_compile_cache()

    def create_plan(self, sql: str) -> PlanNode:
        return self._plan_stmt(parse_statement(sql))

    def _plan_stmt(self, stmt: ast.Statement) -> PlanNode:
        from .planner import history

        with self.tracer.span("trino.planner") as sp, history.pinned():
            read = history.thread_bytes_read()
            planner = LogicalPlanner(self.catalog, self.session.default_catalog)
            plan = planner.plan(stmt)
            plan = optimize(plan, self.catalog)
            sp.record(cache_hit=False, **history.plan_span_attrs(read))
        check_select_access(plan, self.access_control, self.session.user)
        return plan

    def explain(self, sql: str) -> str:
        return plan_text(self.create_plan(sql))

    def execute(self, sql: str,
                query_id: Optional[str] = None) -> QueryResult:
        # an explicit query_id (the HTTP dispatcher passes its own) keeps
        # one identity across the protocol, the registries and the profile
        return run_with_query_events(
            query_id or f"sq_{next(self._qids)}", sql, self.session.user,
            self.event_listeners, self.tracer, lambda: self._execute(sql))

    def profile(self, query_id: str) -> Optional[dict]:
        """Chrome trace_event JSON of a profiled query (telemetry/
        profiler.py timeline), or None when unknown."""
        from .telemetry import profiler

        return profiler.chrome_trace(query_id)

    def _execute(self, sql: str) -> QueryResult:
        from .caching import plan_cache, result_cache

        # Tier A fast path: a cached plan skips parse → analyze → plan →
        # optimize entirely (only statements that reached _plan_stmt are
        # ever stored, so DDL/session/transaction texts always miss here)
        entry = plan_cache.lookup(sql, self.session, self.catalog)
        if entry is not None:
            return self._execute_cached_plan(entry)
        stmt = parse_statement(sql)
        from .execution.transaction import handle_transaction_stmt

        txn = handle_transaction_stmt(stmt, self.session, self.catalog)
        if txn is not None:
            return txn
        check_ddl_access(stmt, self.access_control, self.session.user,
                         self.session.default_catalog)
        sess = execute_session_stmt(stmt, self.session)
        if sess is not None:
            return sess
        if isinstance(stmt, ast.Explain):
            return self._execute_explain(stmt)
        if isinstance(stmt, ast.ShowTables):
            conn = self.catalog.connector(self.session.default_catalog)
            return text_result("Table", sorted(
                list(conn.list_tables()) + list(self.catalog.views)))
        if isinstance(stmt, ast.ShowColumns):
            cat, table, schema = self.catalog.resolve_table(
                stmt.table, self.session.default_catalog)
            return text_result(
                "Column", [f"{c.name} {c.type}" for c in schema.columns])
        ddl = execute_ddl(stmt, self.catalog, self.session.default_catalog,
                          lambda st: self._execute_stmt(st, False)[0])
        if ddl is not None:
            return ddl
        from .planner import history

        # planned and published under one history table: the key's epoch
        # is the epoch of the table the optimizer read
        with history.pinned():
            plan = self._plan_stmt(stmt)
            entry = plan_cache.store(sql, self.session, self.catalog, plan)
        # Tier C: capture the table-version vector BEFORE executing — a
        # mutation racing the read then strands the entry under a stale
        # key (never served) instead of publishing stale data as fresh
        versions = result_cache.version_vector(entry.tables, self.catalog)
        key = result_cache.result_key(entry, versions)
        result, _ = self._execute_stmt(stmt, collect_stats=False, plan=plan)
        result_cache.store(key, result, entry.tables)
        return result

    def _execute_cached_plan(self, entry) -> QueryResult:
        """Run from a Tier-A hit: re-check access (the cache is keyed on
        session knobs, not identity), try Tier C, else execute a private
        clone of the cached tree and publish the result."""
        from .caching import plan_cache, result_cache

        check_select_access(entry.plan, self.access_control,
                            self.session.user)
        versions = result_cache.version_vector(entry.tables, self.catalog)
        key = result_cache.result_key(entry, versions)
        cached = result_cache.lookup(key)
        if cached is not None:
            return cached
        result, _ = self._execute_stmt(
            None, collect_stats=False, plan=plan_cache.clone(entry.plan))
        result_cache.store(key, result, entry.tables)
        return result

    def _execute_stmt(self, stmt: ast.Statement, collect_stats: bool,
                      plan: Optional[PlanNode] = None,
                      ) -> tuple[QueryResult, Optional[QueryStats]]:
        if plan is None:
            plan = self._plan_stmt(stmt)
        local = LocalPlanner(
            self.catalog,
            splits_per_node=self.session.splits_per_node,
            node_count=self.session.node_count,
            dynamic_filtering=self.session.dynamic_filtering,
            hbm_limit_bytes=self.session.hbm_limit_bytes,
            spill_to_disk_bytes=self.session.spill_to_disk_bytes,
            task_concurrency=self.session.task_concurrency,
        ).plan(plan)
        stats = QueryStats() if collect_stats else None
        from .exec import syncguard

        sync_before = syncguard.snapshot()
        with self.tracer.span("trino.execution") as sp:
            run_pipelines(local.pipelines, stats)
            ingest = collect_scan_stats(local.pipelines)
            sync_delta = syncguard.take_delta(sync_before)
            annotate_scan_span(sp, ingest)
            annotate_sync_span(sp, sync_delta)
        from .telemetry import metrics as tm
        from .telemetry import runtime as rt

        tm.observe_scan(ingest)
        tm.observe_sync(sync_delta)
        tm.observe_encoding(collect_encoding_stats(local.pipelines))
        if ingest is not None:
            rt.add_input(rt.current_record(), ingest.scan_rows,
                         ingest.scan_bytes)
        batches = local.collector.batches
        if batches:
            batch = ColumnBatch.concat(batches)
        else:
            batch = ColumnBatch(
                local.output_names,
                [Column(t, np.empty(0, t.storage_dtype))
                 for t in local.output_types],
            )
        return QueryResult(local.output_names, batch), stats

    def _execute_explain(self, stmt: ast.Explain) -> QueryResult:
        """EXPLAIN -> plan text; EXPLAIN ANALYZE -> run it, then render the
        plan with per-operator wall/row/batch stats (the
        ExplainAnalyzeOperator.java:36 equivalent)."""
        inner = stmt.statement
        plan = self._plan_stmt(inner)
        lines = plan_text(plan).splitlines()
        # rule-firing trace of the optimizer run that shaped this plan
        # (planner/iterative/driver.py publishes it per-thread)
        from .planner.iterative import last_report

        trace = last_report()
        if trace is not None:
            lines.extend(trace.lines(timings=stmt.analyze))
        if stmt.analyze:
            import time as _time

            t0 = _time.perf_counter()
            _, stats = self._execute_stmt(inner, collect_stats=True, plan=plan)
            wall = _time.perf_counter() - t0
            lines.append(f"total: {wall * 1e3:.1f} ms")
            lines.extend(stats.text().splitlines())
        return text_result("Query Plan", lines)
