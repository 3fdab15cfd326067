"""DistributedQueryRunner: coordinator + N workers in one process.

The reference's central testing trick (testing/trino-testing/.../
DistributedQueryRunner.java:101 boots a real coordinator + N workers in one
JVM) and its pipelined scheduler in miniature (execution/scheduler/
PipelinedQueryScheduler.java:157 all-at-once stage activation): every
fragment is scheduled as ``task_count`` concurrent tasks up front; tasks
stream pages to each other through pull-token OutputBuffers; the root
(OUTPUT) fragment's buffer feeds the client.

Task threads model worker task executors (a thread per task stands in for
TimeSharingTaskExecutor quanta; numpy/XLA release the GIL in the kernels,
so scans/joins on different tasks genuinely overlap).
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass
from typing import Optional

from ..connectors.catalog import Catalog, default_catalog
from ..exec.driver import run_pipelines
from ..exec.local_planner import LocalPlanner
from ..exec.stats import QueryStats
from ..planner.add_exchanges import add_exchanges
from ..planner.logical import LogicalPlanner
from ..planner.optimizer import optimize
from ..planner.plan import PlanNode
from ..runner import QueryResult, Session, text_result
from ..spi.batch import Column, ColumnBatch
from ..sql import ast
from ..sql.parser import parse_statement
from .exchange import ExchangeClient, OutputBuffer
from .fragmenter import PlanFragment, SubPlan, fragment_plan
from .task import PartitionedOutputSink

__all__ = ["DistributedQueryRunner"]


@dataclass
class _Stage:
    fragment: PlanFragment
    task_count: int
    buffers: list[OutputBuffer]  # one per task


class DistributedQueryRunner:
    def __init__(self, catalog: Optional[Catalog] = None,
                 worker_count: int = 3,
                 session: Optional[Session] = None):
        from .control import HeartbeatFailureDetector, NodeManager
        from .resource_manager import (
            ClusterMemoryManager,
            build_dispatch_manager,
        )

        self.catalog = catalog if catalog is not None else default_catalog()
        self.worker_count = worker_count
        self.session = session if session is not None else Session(
            node_count=worker_count)
        # control plane: discovery (in-process workers announce at boot),
        # heartbeat-gated membership, resource-group admission + query FSM
        self.nodes = NodeManager()
        self.nodes.announce("coordinator", coordinator=True)
        for i in range(worker_count):
            self.nodes.announce(f"worker-{i}")
        self.failure_detector = HeartbeatFailureDetector(self.nodes)
        for i in range(worker_count):
            self.failure_detector.monitor(f"worker-{i}", lambda: True)
        # admission: the TRINO_TPU_RESOURCE_GROUPS tree when configured,
        # else the flat global group sized from the session knobs — plus the
        # coordinator's cluster memory view + low-memory killer
        self.dispatcher = build_dispatch_manager(self.session)
        self.memory_manager = ClusterMemoryManager()
        import itertools

        from ..spi.eventlistener import EventListenerManager
        from ..spi.security import AccessControlManager
        from .tracing import Tracer

        self.tracer = Tracer()
        self.event_listeners = EventListenerManager()
        self.access_control = AccessControlManager()
        self._qids = itertools.count(1)
        from ..telemetry import journal as _journal

        j = _journal.get_journal()
        if j is not None:
            self.event_listeners.add(j)
        # query-level resilience surface (retry_policy=QUERY): cumulative
        # counters + an append-only event log of retries / blacklists /
        # heartbeat transitions / replacements, shared with the process
        # runner's WorkerFailureDetector
        from ..exec.stats import ResilienceStats

        self.resilience = ResilienceStats()
        self.resilience_events: list = []
        # cross-query worker blacklist: per-query blacklists die with their
        # query, so a flaky worker would get a task from every new query —
        # this one is coordinator-held, TTL-decayed, and consulted by task
        # placement (remote) / speculation stats across queries
        from .speculation import ClusterBlacklist

        # persist=True: strikes are journaled (telemetry/journal.py) and the
        # TTL-decayed remainder re-seeds this blacklist after a restart —
        # a flaky worker does not get a clean slate from a coordinator bounce
        self.cluster_blacklist = ClusterBlacklist(
            ttl_s=self.session.blacklist_ttl_s,
            threshold=self.session.blacklist_threshold,
            persist=True)
        # cumulative speculation outcome counters (per-query details go to
        # resilience_events)
        self.speculative_starts = 0
        self.speculative_wins = 0
        # cumulative count of fused-stage overflow fallbacks (whole-stage
        # compilation re-running a subplan on the legacy per-operator path)
        self.fused_fallbacks = 0
        # cumulative count of resident-plan fallbacks (whole-query GSPMD
        # compilation bailing to the task-per-worker path: state overflow,
        # duplicate build keys, build failures)
        self.resident_fallbacks = 0
        # system catalog (connectors/system.py): bind this runner so
        # dispatcher-tracked query state shows up in system.runtime.queries
        sysconn = self.catalog._connectors.get("system")
        if sysconn is not None and hasattr(sysconn, "attach"):
            sysconn.attach(self)
        from ..caching import executable_cache

        executable_cache.init_compile_cache()

    # ------------------------------------------------------------------ plan
    def create_plan(self, sql: str) -> PlanNode:
        return self._plan_stmt(parse_statement(sql))

    def _plan_stmt(self, stmt: ast.Statement) -> PlanNode:
        from ..planner import history
        from ..runner import check_select_access

        with self.tracer.span("trino.planner") as sp, history.pinned():
            read = history.thread_bytes_read()
            plan = LogicalPlanner(
                self.catalog, self.session.default_catalog).plan(stmt)
            plan = optimize(plan, self.catalog)
            sp.record(cache_hit=False, **history.plan_span_attrs(read))
            check_select_access(plan, self.access_control,
                                self.session.user)
            writer_tasks = 1
            if self.session.scale_writers:
                writer_tasks = max(1, min(self.session.writer_task_limit,
                                          self.worker_count))
            return add_exchanges(plan, writer_tasks=writer_tasks)

    def create_subplan(self, sql: str) -> SubPlan:
        return fragment_plan(self.create_plan(sql))

    def explain(self, sql: str) -> str:
        return self.create_subplan(sql).text()

    # --------------------------------------------------------------- execute
    def execute(self, sql: str,
                query_id: Optional[str] = None) -> QueryResult:
        from ..runner import run_with_query_events

        return run_with_query_events(
            query_id or f"dq_{next(self._qids)}", sql, self.session.user,
            self.event_listeners, self.tracer, lambda: self._execute(sql))

    def profile(self, query_id: str) -> Optional[dict]:
        """Chrome trace_event JSON of a profiled query's merged
        coordinator+worker timeline, or None when unknown."""
        from ..telemetry import profiler

        return profiler.chrome_trace(query_id)

    def _execute(self, sql: str) -> QueryResult:
        from ..caching import plan_cache, result_cache
        from ..planner import history
        from ..runner import check_ddl_access, check_select_access
        from ..telemetry import profiler

        # Tier A fast path (see runner.py): a hit skips parse → analyze →
        # plan → optimize → add_exchanges; only statements that reached
        # _plan_stmt were ever stored, so non-SELECT texts always miss
        with profiler.span(profiler.PLAN, "cache-lookup") as lookup, \
                history.pinned():
            read = history.thread_bytes_read()
            entry = plan_cache.lookup(sql, self.session, self.catalog,
                                      flavor="fragmented")
            lookup.set(cache_hit=entry is not None,
                       **history.plan_span_attrs(read))
        if entry is not None:
            check_select_access(entry.plan, self.access_control,
                                self.session.user)
            versions = result_cache.version_vector(entry.tables,
                                                   self.catalog)
            key = result_cache.result_key(entry, versions)
            cached = result_cache.lookup(key)
            if cached is not None:
                return cached

            def run_cached(fsm):
                fsm.set("PLANNING")
                subplan = fragment_plan(plan_cache.clone(entry.plan))
                fsm.set("STARTING")
                fsm.set("RUNNING")
                out = self._execute_subplan(subplan, None)
                fsm.set("FINISHING")
                return out

            out = self.dispatcher.submit(sql, self.session, run_cached)
            result_cache.store(key, out, entry.tables)
            return out
        stmt = parse_statement(sql)
        from .transaction import handle_transaction_stmt

        txn = handle_transaction_stmt(stmt, self.session, self.catalog)
        if txn is not None:
            return txn
        check_ddl_access(stmt, self.access_control, self.session.user,
                         self.session.default_catalog)
        from ..runner import execute_session_stmt

        sess = execute_session_stmt(stmt, self.session)
        if sess is not None:
            return sess
        if isinstance(stmt, ast.Explain):
            subplan = fragment_plan(self._plan_stmt(stmt.statement))
            lines = subplan.text().splitlines()
            if stmt.analyze:
                from ..planner.iterative import last_report

                trace = last_report()
                if trace is not None and trace.history_lookups:
                    lines.append(trace.history_line(epoch=True))
                stats: list[QueryStats] = []
                self._execute_subplan(subplan, stats)
                for s in sorted(stats, key=lambda s: s.label):
                    lines.extend(s.text().splitlines())
            return text_result("Query Plan", lines)
        if isinstance(stmt, ast.ShowTables):
            conn = self.catalog.connector(self.session.default_catalog)
            return text_result("Table", conn.list_tables())
        if isinstance(stmt, ast.ShowColumns):
            cat, table, schema = self.catalog.resolve_table(
                stmt.table, self.session.default_catalog)
            return text_result(
                "Column", [f"{c.name} {c.type}" for c in schema.columns])
        from ..runner import execute_ddl

        ddl = execute_ddl(
            stmt, self.catalog, self.session.default_catalog,
            lambda st: self._execute_subplan(
                fragment_plan(self._plan_stmt(st)), None))
        if ddl is not None:
            return ddl

        store_ctx = {}

        def run(fsm):
            fsm.set("PLANNING")
            # planned and published under one history table: the key's
            # epoch is the epoch of the table the optimizer read
            with history.pinned():
                plan = self._plan_stmt(stmt)
                new_entry = plan_cache.store(sql, self.session, self.catalog,
                                             plan, flavor="fragmented")
            # version vector read BEFORE execution (see runner.py: a
            # racing mutation strands the entry, never serves stale)
            store_ctx["key"] = result_cache.result_key(
                new_entry,
                result_cache.version_vector(new_entry.tables, self.catalog))
            store_ctx["tables"] = new_entry.tables
            subplan = fragment_plan(plan)
            fsm.set("STARTING")
            fsm.set("RUNNING")
            out = self._execute_subplan(subplan, None)
            fsm.set("FINISHING")
            return out

        out = self.dispatcher.submit(sql, self.session, run)
        if store_ctx.get("key") is not None:
            result_cache.store(store_ctx["key"], out, store_ctx["tables"])
        return out

    def _execute_subplan(self, subplan: SubPlan,
                         stats_sink: Optional[list]) -> QueryResult:
        from ..telemetry import profiler

        # everything between the plan and the answer that is not inside a
        # task: stage set-up, spawning and joining tasks, result collection
        with profiler.span(profiler.SCHEDULE, "subplan",
                           stages=len(subplan.all_fragments())):
            if self.session.retry_policy == "TASK":
                from .fte import run_fte_query

                return self._to_result(
                    subplan, run_fte_query(self, subplan, stats_sink))
            if self.session.retry_policy == "QUERY":
                return self._run_query_retry(subplan, stats_sink)
            return self._run_streaming(subplan, stats_sink)

    def _run_query_retry(self, subplan: SubPlan,
                         stats_sink: Optional[list]) -> QueryResult:
        """retry_policy=QUERY: streaming execution with coordinator-level
        retry (reference: coordinator query retries — the pipelined overlap
        is kept; the recovery unit is the whole query).  On a retryable
        failure: blacklist the implicated worker for this query, replace
        GONE workers (``_prepare_retry``), back off deterministically, and
        re-run the subplan.  USER-classified errors fail fast, always."""
        import time as _time

        from ..exec.stats import ResilienceStats
        from ..spi.errors import Backoff, classify

        sess = self.session
        before = ResilienceStats()
        before.merge(self.resilience)
        backoff = Backoff(min_delay_s=sess.retry_initial_delay_s,
                          max_delay_s=sess.retry_max_delay_s,
                          max_failure_duration_s=float("inf"))
        blacklist: set = set()
        attempts = 1 + max(0, int(sess.query_retry_attempts))
        try:
            for attempt in range(attempts):
                try:
                    return self._run_streaming(
                        subplan, stats_sink, attempt=attempt,
                        blacklist=frozenset(blacklist))
                except BaseException as e:  # noqa: BLE001 — classified below
                    te = classify(e)
                    if not te.is_retryable() or attempt == attempts - 1:
                        raise
                    if te.remote_host and te.remote_host not in blacklist:
                        blacklist.add(te.remote_host)
                        self.resilience.blacklisted_workers += 1
                        self.resilience_events.append(
                            ("blacklist", te.remote_host, te.code.name))
                    if te.remote_host:
                        # score the failure cross-query too: enough strikes
                        # within the TTL and the worker stops receiving
                        # tasks from NEW queries as well
                        from ..telemetry import runtime as _rt2

                        _rec = _rt2.current_record()
                        self.cluster_blacklist.record_failure(
                            te.remote_host, reason=te.code.name,
                            query_id=_rec.query_id if _rec else "")
                    self._prepare_retry()
                    backoff.failure()
                    delay = backoff.delay_s
                    self.resilience.query_retries += 1
                    self.resilience.backoff_waits += 1
                    self.resilience.backoff_wait_s += delay
                    self.resilience_events.append(
                        ("query_retry", attempt + 1, te.code.name, delay))
                    _time.sleep(delay)
            raise AssertionError("unreachable: retry loop exhausted")
        finally:
            delta = ResilienceStats.delta(self.resilience, before)
            if delta.any:
                from ..telemetry import metrics as tm
                from ..telemetry import runtime as rt
                from .tracing import annotate_resilience_span

                tm.observe_resilience(delta)
                rec = rt.current_record()
                if rec is not None:
                    rt.add_retries(rec, delta.query_retries)
                span = self.tracer.current()
                if span is not None:
                    annotate_resilience_span(span, delta)
                if stats_sink is not None:
                    stats_sink.append(QueryStats(label="resilience:",
                                                 resilience=delta))

    def _prepare_retry(self) -> None:
        """Hook run between query-retry attempts; the process runner
        overrides it to sweep heartbeats and replace GONE workers."""

    def _run_streaming(self, subplan: SubPlan, stats_sink: Optional[list],
                       attempt: int = 0,
                       blacklist: frozenset = frozenset(),
                       use_fused: bool = True) -> QueryResult:
        from ..telemetry import runtime as _rt
        from .resource_manager import find_group

        # register with the cluster memory manager: the handle carries the
        # OOM-killer kill flag the scheduling/drain loops below poll, and
        # every task's memory pool is booked under this query id
        qrec = _rt.current_record()
        mem_qid = qrec.query_id if qrec is not None else f"q@{id(subplan):x}"
        max_mem = (self.session.query_max_memory_bytes
                   or int(os.environ.get("TRINO_TPU_QUERY_MAX_MEMORY",
                                         "0") or 0) or None)
        handle = self.memory_manager.register_query(
            mem_qid, priority=self.session.query_priority,
            group=find_group(self.dispatcher.root,
                             qrec.resource_group if qrec is not None else ""),
            max_memory=max_mem)
        try:
            return self._run_streaming_inner(
                subplan, stats_sink, attempt, blacklist, use_fused,
                handle, mem_qid)
        finally:
            self.memory_manager.unregister_query(mem_qid)

    def _run_streaming_inner(self, subplan: SubPlan,
                             stats_sink: Optional[list], attempt: int,
                             blacklist: frozenset, use_fused: bool,
                             handle, mem_qid: str) -> QueryResult:
        from .collective_exchange import (
            CollectiveRepartitionExchange,
            collectives_available,
        )
        from .stage_compiler import FusedStageOverflow, plan_fused_stages

        fragments = subplan.all_fragments()
        task_counts, consumer_tasks = self.stage_task_counts(fragments)
        stages: dict[int, _Stage] = {
            f.id: _Stage(f, task_counts[f.id], []) for f in fragments
        }
        # One byte budget for every scheduler: the time-sharing executor
        # flips sinks non-blocking, whose drivers then park via
        # ``needs_input`` until consumer acks free capacity — no quantum is
        # ever pinned inside ``enqueue``, so the old 1 GiB escape cap is
        # gone.  TRINO_TPU_SINK_MAX_BYTES overrides.
        env_cap = os.environ.get("TRINO_TPU_SINK_MAX_BYTES")
        sink_cap = max(int(env_cap), 1 << 20) if env_cap else 256 << 20
        for f in fragments:
            tc = stages[f.id].task_count
            nparts = consumer_tasks.get(f.id, 1)
            stages[f.id].buffers = [
                OutputBuffer(nparts, max_bytes=sink_cap)
                for _ in range(tc)
            ]

        # whole-stage compilation (execution/stage_compiler.py): fragmenter-
        # marked PARTIAL->shuffle->FINAL seams run as one jitted program per
        # batch-bucket plus one seam merge; the collective exchange and the
        # host buffers cover every remaining edge
        fused_edges: dict = {}
        resident_edges: dict = {}
        if use_fused:
            fused_edges = plan_fused_stages(
                fragments, self.session, task_counts, consumer_tasks)
            # whole-query compilation (execution/plan_compiler.py): maximal
            # device-resident subtrees — broadcast join spine + agg seam —
            # run as ONE program per batch; a coalesced core fragment's
            # plain fused seam is subsumed by its resident plan
            from .plan_compiler import plan_resident_plans

            resident_edges = plan_resident_plans(
                fragments, self.session, task_counts, consumer_tasks)
            for fid in resident_edges:
                fused_edges.pop(fid, None)
        # device-collective REPARTITION edges (all_to_all over the mesh)
        # where producer/consumer task counts line up; host buffers remain
        # the fallback for every other edge
        collective_edges: dict[int, CollectiveRepartitionExchange] = {}
        if self.session.use_collectives:
            for f in fragments:
                tc = stages[f.id].task_count
                if (f.id not in fused_edges
                        and f.id not in resident_edges
                        and f.output_kind == "REPARTITION"
                        and consumer_tasks.get(f.id) == tc
                        and collectives_available(tc)):
                    collective_edges[f.id] = CollectiveRepartitionExchange(
                        tc, f.output_keys,
                        f.root.output_names, f.root.output_types)
        # kept as attributes for observability/tests; tasks receive the
        # dict as an argument so concurrent queries cannot cross-wire
        self._collective_edges = collective_edges
        self._fused_edges = fused_edges
        self._resident_edges = resident_edges
        edges = {**collective_edges, **fused_edges, **resident_edges}

        errors: list[BaseException] = []
        adaptive = None
        if self.session.task_scheduler == "TIME_SHARING":
            hung = self._run_time_sharing(
                fragments, stages, errors, stats_sink, edges,
                attempt, handle=handle, memory_owner=mem_qid)
        else:
            from ..telemetry import runtime as _rt

            # task spans nest under the coordinator thread's open query
            # span via explicit cross-thread parenting (tracing.py parent=)
            parent_span = self.tracer.current()
            qrec = _rt.current_record()
            # streaming straggler speculation (leaf stages only): a leaf
            # twin re-reads its splits from the connector; a non-leaf twin
            # would need its producers' pages back, but the streaming
            # exchange frees them on ack — that retention is what FTE's
            # durable spool provides, so non-leaf speculation stays with
            # retry_policy=TASK (see execution/speculation.py)
            from .speculation import (
                SPECULATIVE,
                STANDARD,
                StreamingSpeculation,
                StreamingSpoolTee,
                nonleaf_speculation_enabled,
                speculation_enabled,
            )

            # adaptive execution plane (execution/adaptive.py): phased
            # activation + runtime join-distribution decisions.  ``0`` is
            # bit-for-bit legacy; ``auto`` engages only when the plan has
            # decision edges; ``1`` forces phased scheduling regardless.
            from .adaptive import AdaptiveExec, adaptive_mode

            mode = adaptive_mode(self.session)
            if mode != "0":
                adaptive = AdaptiveExec(stages, fragments, edges,
                                        sink_cap, self.session, errors)
                if mode == "auto" and not adaptive.sites:
                    adaptive = None
            spec: Optional[StreamingSpeculation] = None
            spec_gates: dict = {}
            if speculation_enabled(self.session):
                from ..planner.plan import TableWriter

                def _writes(node) -> bool:
                    return isinstance(node, TableWriter) or any(
                        _writes(c) for c in node.children)

                spec = StreamingSpeculation(
                    lag_multiplier=self.session.speculation_lag_multiplier,
                    min_delay_s=self.session.speculation_min_delay_s,
                    events=self.resilience_events)
                for f in fragments:
                    if (f.source_fragments or f.id in edges
                            or stages[f.id].task_count < 2
                            or _writes(f.root)
                            or (adaptive is not None
                                and adaptive.is_deferred_producer(f.id))):
                        continue  # twin needs re-readable, side-effect-free
                        # (deferred producers also feed barrier statistics:
                        # a twin would double-count the staging sketch)
                    spec.register_stage(f.id, stages[f.id].task_count)
                    for t in range(stages[f.id].task_count):
                        spec_gates[(f.id, t)] = spec.register_task(f.id, t)

            tee: Optional[StreamingSpoolTee] = None
            if (spec is not None and adaptive is None
                    and nonleaf_speculation_enabled(self.session)):
                # non-leaf twin eligibility (r15): a stage whose sources
                # all land in plain OutputBuffers can speculate too — its
                # producers tee winner pages into a durable per-task spool
                # (SpoolTeeBuffer), and the twin re-reads committed tee
                # dirs once EVERY source task has committed.  Collective/
                # fused edges and adaptive routing bypass stage.buffers,
                # so those fragments stay leaf-only.
                nonleaf = [
                    f for f in fragments
                    if f.source_fragments and f.id not in edges
                    and stages[f.id].task_count >= 2
                    and not _writes(f.root)
                    and all(src not in edges for src in f.source_fragments)
                ]
                if nonleaf:
                    from .durable_spool import make_spool_root

                    from . import spool_gc

                    tee = StreamingSpoolTee(make_spool_root(
                        getattr(self.session, "fte_spool_dir", None)))
                    spool_gc.acquire(
                        tee.root, qrec.query_id if qrec is not None
                        else "adhoc")
                    for f in nonleaf:
                        srcs = tuple(f.source_fragments)
                        spec.register_stage(
                            f.id, stages[f.id].task_count,
                            eligible=lambda _s=srcs: tee.ready(_s))
                        for t in range(stages[f.id].task_count):
                            spec_gates[(f.id, t)] = \
                                spec.register_task(f.id, t)
                        for src in srcs:
                            tee.want(src, stages[src].task_count)

            def _spawn_stage(fid: int) -> list[threading.Thread]:
                stage = stages[fid]
                out = []
                for t in range(stage.task_count):
                    ctx = None
                    if (fid, t) in spec_gates:
                        ctx = {"gate": spec_gates[(fid, t)],
                               "kind": STANDARD,
                               "cancel": spec.cancel_event(fid, t, STANDARD)}
                    th = threading.Thread(
                        target=self._run_task,
                        args=(stage, t, stages, errors, stats_sink,
                              edges, attempt, parent_span, qrec, mem_qid,
                              ctx, adaptive, tee),
                        name=f"task-{fid}.{t}",
                        daemon=True,
                    )
                    th.start()
                    out.append(th)
                return out

            if adaptive is None:
                threads: list[threading.Thread] = []
                for f in fragments:
                    threads.extend(_spawn_stage(f.id))
            else:
                # phased activation: only groups with no unresolved
                # decision sites upstream get tasks now; the rest hold no
                # threads or buffers' worth of pages and stay rewritable
                threads = adaptive.start(_spawn_stage)

            def _spawn_twin(fid: int, t: int) -> threading.Thread:
                # twin attempts use attempt+1000 (mirrors fte.py's
                # SPECULATIVE attempt base) so attempt-scoped injector
                # rules do not refire on the twin
                twin_ctx = {"gate": spec_gates[(fid, t)],
                            "kind": SPECULATIVE,
                            "cancel": spec.cancel_event(fid, t, SPECULATIVE)}
                tw = threading.Thread(
                    target=self._run_task,
                    args=(stages[fid], t, stages, errors, stats_sink,
                          edges, attempt + 1000, parent_span, qrec,
                          mem_qid, twin_ctx, adaptive, tee),
                    name=f"task-{fid}.{t}-speculative",
                    daemon=True,
                )
                tw.start()
                return tw

            from .task import STALL_TIMEOUT_S

            # polled join (not a plain join) so an OOM-killer verdict can
            # unblock tasks parked on full/empty buffers mid-query
            deadline = time.monotonic() + 2 * STALL_TIMEOUT_S
            pending = list(threads)
            aborted = False
            while ((pending
                    or (adaptive is not None and not adaptive.done()))
                   and time.monotonic() < deadline):
                if pending:
                    pending[0].join(timeout=0.1)
                else:
                    time.sleep(0.02)
                pending = [th for th in pending if th.is_alive()]
                if adaptive is not None:
                    if errors or aborted:
                        # a failed task already aborted the buffers; force
                        # the plane done so un-activated groups never spawn
                        adaptive.abort()
                    else:
                        pending.extend(adaptive.advance(_spawn_stage))
                if spec is not None and not errors and not aborted:
                    pending.extend(spec.tick(_spawn_twin))
                if not aborted and handle.poll() is not None:
                    aborted = True
                    for s in stages.values():
                        for b in s.buffers:
                            b.abort()
                    for ex in edges.values():
                        ex.abort()
                    if adaptive is not None:
                        adaptive.abort()
            hung = [th.name for th in pending if th.is_alive()]
            if adaptive is not None and not errors:
                hung += adaptive.unactivated()
            if tee is not None:
                # all tasks (and any twins) are done or hung: the tee spool
                # served its purpose.  A coordinator killed before this
                # line leaks the root to the boot-time spool_gc sweep.
                from . import spool_gc

                spool_gc.release(tee.root)
            if spec is not None:
                self.speculative_starts += spec.starts
                self.speculative_wins += spec.wins
                if spec.wins:
                    from ..telemetry import runtime as _rt

                    qrec = _rt.current_record()
                    if qrec is not None:
                        qrec.speculative_wins += spec.wins
        kerr = handle.killed_error()
        if errors or hung or kerr is not None:
            for s in stages.values():
                for b in s.buffers:
                    b.abort()
            for ex in edges.values():
                ex.abort()
            if adaptive is not None:
                adaptive.abort()
            if kerr is not None:
                # the kill verdict wins over secondary task errors: aborted
                # buffers make tasks fail with cascade exceptions that would
                # otherwise mask the CLUSTER_OUT_OF_MEMORY cause
                raise kerr
            if errors:
                if use_fused and any(isinstance(e, FusedStageOverflow)
                                     for e in errors):
                    # a task saw more groups than the fused state cap (or a
                    # resident plan couldn't hold): the legacy per-operator
                    # path has no such limit — re-run this subplan on it
                    # (stats surface the event; raise TRINO_TPU_FUSED_CAP /
                    # fix the plan shape to avoid it)
                    from .plan_compiler import ResidentPlanOverflow

                    res = [e for e in errors
                           if isinstance(e, ResidentPlanOverflow)]
                    if res:
                        self.resident_fallbacks += 1
                        from ..telemetry import metrics as _tm

                        _tm.RESIDENT_FALLBACKS.inc()
                        if stats_sink is not None:
                            from ..exec.stats import ResidentPlanStats

                            stats_sink.append(QueryStats(
                                label="resident plans:",
                                resident=ResidentPlanStats(
                                    fallbacks=1,
                                    fallback_reasons=[str(res[0])[:120]])))
                    else:
                        self.fused_fallbacks += 1
                        if stats_sink is not None:
                            from ..exec.stats import FusedStageStats

                            stats_sink.append(QueryStats(
                                label="fused stages:",
                                fused=FusedStageStats(fallbacks=1)))
                    return self._run_streaming(subplan, stats_sink, attempt,
                                               blacklist, use_fused=False)
                raise errors[0]
            raise TimeoutError(f"tasks did not complete: {hung}")

        if fused_edges:
            from ..exec.stats import FusedStageStats

            from .tracing import annotate_fused_span

            roll = FusedStageStats()
            for ex in fused_edges.values():
                roll.merge(ex.stats)
            from ..telemetry.metrics import observe_fused

            observe_fused(roll)
            span = self.tracer.current()
            if span is not None:
                annotate_fused_span(span, roll)
            if stats_sink is not None:
                stats_sink.append(QueryStats(label="fused stages:",
                                             fused=roll))

        if resident_edges:
            from ..exec.stats import ResidentPlanStats

            from .plan_compiler import ResidentPlanExec
            from .tracing import annotate_resident_span

            rroll = ResidentPlanStats()
            for ex in resident_edges.values():
                if isinstance(ex, ResidentPlanExec):
                    rroll.merge(ex.rstats)
            from ..telemetry.metrics import observe_resident

            observe_resident(rroll)
            span = self.tracer.current()
            if span is not None:
                annotate_resident_span(span, rroll)
            if stats_sink is not None:
                stats_sink.append(QueryStats(label="resident plans:",
                                             resident=rroll))

        if adaptive is not None and adaptive.stats.any:
            from ..telemetry.metrics import observe_adaptive

            observe_adaptive(adaptive.stats)
            if stats_sink is not None:
                stats_sink.append(QueryStats(label="adaptive:",
                                             adaptive=adaptive.stats))

        if stats_sink is not None:
            from ..exec.stats import SharingStats
            from ..telemetry import runtime as _rt

            qrec = _rt.current_record()
            if qrec is not None:
                stats_sink.append(QueryStats(
                    label="sharing:", sharing=SharingStats(
                        qrec.in_flight, qrec.task_cpu_s, qrec.task_wall_s)))

        # close the runtime-truth loop: journal per-fingerprint observed
        # stats so the NEXT run of this (or any row-equivalent) plan shape
        # costs joins/aggregations from reality (planner/history.py)
        try:
            from ..planner.history import record_query_stats
            from ..telemetry import runtime as _rt

            qrec = _rt.current_record()
            skip = (set(fused_edges) | set(resident_edges)
                    | set(collective_edges))
            n = record_query_stats(
                fragments, stages, skip, adaptive,
                qrec.query_id if qrec is not None else mem_qid,
                qrec.fingerprint if qrec is not None else "")
            if n:
                from ..telemetry.metrics import HBO_RECORDS

                HBO_RECORDS.inc(n)
        except Exception:
            from ..telemetry.metrics import HBO_RECORD_ERRORS

            HBO_RECORD_ERRORS.inc()

        # drain the root stage's buffer as the client
        from .task import maybe_deserialize

        root = stages[subplan.fragment.id]
        client = ExchangeClient(root.buffers, 0)
        batches = []
        while not client.is_finished():
            handle.check()
            b = client.poll(timeout=0.2)
            if b is not None:
                batches.append(maybe_deserialize(b))
        # a kill that lands during FINISHING still fails the query: the
        # victim must always observe its own kill or the killer's
        # capacity projection (total -= victim bytes) goes stale
        handle.check()
        return self._to_result(subplan, batches)

    def fte_run_attempt(self, fragment, task_index: int, task_count: int,
                        nparts: int, upstream: dict, spool_root: str,
                        attempt: int, stats_sink: Optional[list],
                        memory_multiplier: float = 1.0) -> str:
        """Run ONE task attempt against the durable spool; returns the
        committed attempt directory.  In-process execution here; the
        process runner overrides this with a worker-process dispatch.
        ``memory_multiplier`` scales the task's HBM budget — the FTE
        scheduler grows it exponentially after a memory failure
        (ExponentialGrowthPartitionMemoryEstimator.java:55)."""
        import os as _os

        from .durable_spool import DurableSpoolClient, DurableSpoolWriter
        from .failure_injector import GET_RESULTS_FAILURE, TASK_FAILURE
        from .fte import fte_task_dir
        from .task import PartitionedOutputSink as _Sink

        injector = getattr(self.session, "failure_injector", None)
        if injector is not None:
            injector.maybe_stall(fragment.id, task_index, attempt)
            injector.maybe_fail(TASK_FAILURE, fragment.id, task_index,
                                attempt)

        def on_read(_d, _fid=fragment.id, _t=task_index, _a=attempt):
            if injector is not None:
                injector.maybe_fail(GET_RESULTS_FAILURE, _fid, _t, _a)
                injector.maybe_corrupt_spool(_d, _fid, _t, _a)

        clients = {}
        for src, info in upstream.items():
            if info["merge"]:
                clients[src] = [
                    DurableSpoolClient([d], task_index, on_read)
                    for d in info["dirs"]
                ]
            else:
                clients[src] = DurableSpoolClient(
                    info["dirs"], task_index, on_read)
        planner = LocalPlanner(
            self.catalog,
            splits_per_node=self.session.splits_per_node,
            node_count=self.worker_count,
            task_index=task_index,
            task_count=task_count,
            remote_clients=clients,
            dynamic_filtering=self.session.dynamic_filtering,
            hbm_limit_bytes=int(
                self.session.hbm_limit_bytes * memory_multiplier),
        )
        local = planner.plan(fragment.root)
        task_dir = fte_task_dir(spool_root, fragment.id, task_index)
        _os.makedirs(task_dir, exist_ok=True)
        writer = DurableSpoolWriter(task_dir, attempt, nparts)
        sink = _Sink(
            writer,
            fragment.output_kind if fragment.output_kind != "OUTPUT"
            else "GATHER",
            fragment.output_keys, serde=True)
        local.pipelines[-1][-1] = sink
        stats = None
        if stats_sink is not None:
            stats = QueryStats(
                label=f"fragment {fragment.id} task {task_index}:")
        try:
            run_pipelines(local.pipelines, stats)
        except BaseException:
            writer.abort()
            raise
        writer.set_finished()
        if stats is not None:
            stats_sink.append(stats)
        return writer.committed

    # -------------------------------------------------------------- recovery
    def pending_fte_recoveries(self) -> list:
        """In-flight ``retry_policy="TASK"`` queries a dead coordinator
        left in the query-state WAL (execution/query_state.py) — the boot
        recovery work list the protocol dispatcher drains."""
        from . import query_state

        if not query_state.enabled():
            return []
        return query_state.pending()

    def resume_fte_query(self, pq) -> QueryResult:
        """Rehydrate one recovered query: decode the WAL's plan snapshot
        and re-enter the FTE loop with its committed-attempt map seeded —
        committed attempts are never re-executed (run_fte_query skips
        them; the WAL's attempt counters make that assertable).  Runs
        under the ORIGINAL query id so a reattaching client's
        ``GET /v1/statement/{id}`` polling resolves."""
        from ..runner import run_with_query_events
        from ..telemetry import metrics as tm
        from ..telemetry import profiler
        from . import query_state
        from .fte import run_fte_query

        subplan = query_state.decode_plan(pq.plan_b64)
        tm.FTE_QUERY_RECOVERIES.inc()
        profiler.instant(profiler.RECOVERY, "query-resume",
                         query_id=pq.query_id,
                         committed=len(pq.committed),
                         fingerprint=pq.fingerprint)

        def thunk():
            return self._to_result(
                subplan, run_fte_query(self, subplan, None, resume=pq))

        return run_with_query_events(
            pq.query_id, pq.sql, self.session.user, self.event_listeners,
            self.tracer, thunk)

    # ----------------------------------------------------------------- drain
    def drain_worker(self, node_id: str) -> dict:
        """Coordinator-driven graceful drain of an in-process worker slot:
        mark it draining in discovery so ``active_worker_count`` (and hence
        every NEW query's task placement) stops using it.  In-process tasks
        share the coordinator's address space, so running work simply
        completes; there is no process to wait on or replace."""
        from ..telemetry import metrics as tm

        tm.DRAINS.inc()
        self.resilience_events.append(("drain", node_id, "started"))
        self.nodes.drain(node_id)
        self.resilience_events.append(("drain", node_id, "drained"))
        return {"worker": node_id, "escalated": False}

    def restore_worker(self, node_id: str) -> None:
        """Undo an in-process drain (the rolling-restart drill's stand-in
        for booting a replacement process)."""
        self.nodes.restore(node_id)
        self.resilience_events.append(("drain", node_id, "restored"))

    @property
    def active_worker_count(self) -> int:
        """Live, non-draining workers per discovery + failure detection;
        falls back to the static count if the control plane sees none
        (mirrors NodeScheduler consulting the FailureDetector)."""
        # on-demand heartbeat round (deterministic without the background
        # pinger thread; start() enables continuous monitoring)
        self.failure_detector.ping_once()
        alive = [w for w in self.nodes.active_workers()
                 if w not in self.failure_detector.failed_nodes()]
        return len(alive) or self.worker_count

    def stage_task_counts(self, fragments) -> tuple[dict, dict]:
        """(fragment -> task count, fragment -> consumer task count); the
        output-buffer partition count of a fragment is its consumer's task
        count (the root's consumer is the client: 1)."""
        workers = self.active_worker_count
        writer_cap = max(1, min(self.session.writer_task_limit, workers))
        task_counts = {}
        for f in fragments:
            if f.partitioning == "SINGLE":
                task_counts[f.id] = 1
            elif f.partitioning == "ARBITRARY":
                # scaled-writer fragments honor the configured writer limit
                task_counts[f.id] = writer_cap
            else:
                task_counts[f.id] = workers
        self._history_fanout(fragments, task_counts, workers)
        consumer_tasks: dict[int, int] = {}
        for f in fragments:
            for src in f.source_fragments:
                consumer_tasks[src] = task_counts[f.id]
        return task_counts, consumer_tasks

    def _history_fanout(self, fragments, task_counts: dict,
                        workers: int) -> None:
        """Shrink a hash stage's task count when history says its input is
        small: N tasks each jitting a program over a trickle of rows costs
        more than the parallelism buys.  Only ever shrinks — an
        underestimate here cannot break correctness, just parallelism —
        and only for intermediate (non-scan, non-SINGLE) stages."""
        try:
            from ..planner.history import (
                fragment_fingerprints,
                hbo_enabled,
                _stats_table,
            )
            from ..spi import knobs

            if not hbo_enabled():
                return
            per_task = knobs.get_int("TRINO_TPU_HBO_ROWS_PER_TASK") or 0
            if per_task <= 0:
                return
            table, _ = _stats_table()
            if not table:
                return
            fps = fragment_fingerprints(fragments)
            by_id = {f.id: f for f in fragments}
            for f in fragments:
                if task_counts.get(f.id, 1) <= 1 or f.partitioning != "HASH":
                    continue
                rows = 0
                for src in f.source_fragments:
                    st = table.get(fps.get(src, ""))
                    n = None if st is None else (
                        st.rows if st.rows is not None else st.groups)
                    if n is None or src not in by_id:
                        rows = None
                        break
                    rows += n
                if rows is None:
                    continue
                t = max(1, min(workers, -(-rows // per_task)))
                if t < task_counts[f.id]:
                    task_counts[f.id] = t
                    from ..telemetry import runtime as _rt
                    from ..telemetry.metrics import HBO_FANOUT_ADJUSTED

                    HBO_FANOUT_ADJUSTED.inc()
                    qrec = _rt.current_record()
                    if qrec is not None:
                        _rt.add_adaptive(qrec, f"hbo_fanout:f{f.id}:{t}")
        except Exception:
            # advisory only: a failed adjustment must never fail scheduling
            from ..telemetry.metrics import HBO_RECORD_ERRORS

            HBO_RECORD_ERRORS.inc()

    def _to_result(self, subplan: SubPlan, batches: list) -> QueryResult:
        names = list(subplan.fragment.root.output_names)
        types = list(subplan.fragment.root.output_types)
        if batches:
            return QueryResult(names, ColumnBatch.concat(batches))
        import numpy as np

        return QueryResult(names, ColumnBatch(names, [
            Column(t, np.empty(0, t.storage_dtype)) for t in types]))

    def _build_task(self, stage: _Stage, task_index: int,
                    stages: dict[int, "_Stage"],
                    stats_sink: Optional[list],
                    collective: dict,
                    attempt: int = 0,
                    memory_owner: Optional[str] = None,
                    spec_ctx: Optional[dict] = None,
                    adaptive=None,
                    tee=None,
                    ) -> tuple[list, Optional[QueryStats]]:
        from .speculation import SPECULATIVE, SpeculationLost

        f = stage.fragment
        # engine-level fault injection on the in-process streaming path,
        # keyed by (fragment, task, attempt) exactly like the FTE path —
        # this is what makes retry_policy=QUERY deterministically testable
        injector = getattr(self.session, "failure_injector", None)
        if injector is not None:
            from .failure_injector import TASK_FAILURE

            cancel = spec_ctx["cancel"] if spec_ctx is not None else None
            injector.maybe_stall(
                f.id, task_index, attempt,
                # an injected stall must not outlive its query: bail as soon
                # as the task's buffer is aborted (query failed / OOM-killed)
                # or a speculative twin won the race
                should_cancel=lambda: (
                    stage.buffers[task_index].aborted
                    or (cancel is not None and cancel.is_set())))
            if cancel is not None and cancel.is_set():
                raise SpeculationLost(spec_ctx["kind"])
            injector.maybe_fail(TASK_FAILURE, f.id, task_index, attempt)
        clients = {}
        for src in f.source_fragments:
            if (tee is not None and spec_ctx is not None
                    and spec_ctx["kind"] == SPECULATIVE):
                # non-leaf twin: the streaming exchange already freed the
                # pages its primary consumed — re-read the committed tee
                # spool instead (eligibility guaranteed every source task
                # committed before this twin launched)
                from .durable_spool import DurableSpoolClient

                dirs = tee.committed_dirs(src)
                if dirs is None:
                    raise SpeculationLost(spec_ctx["kind"])
                if stages[src].fragment.output_kind == "MERGE":
                    clients[src] = [DurableSpoolClient([d], task_index)
                                    for d in dirs]
                else:
                    clients[src] = DurableSpoolClient(dirs, task_index)
                continue
            routed = (adaptive.routed_buffer(src)
                      if adaptive is not None else None)
            if routed is not None:
                # deferred edge: consume the router's re-distributed pages,
                # not the producer's staging buffers
                clients[src] = ExchangeClient([routed], task_index)
            elif src in collective:
                clients[src] = collective[src]
            elif stages[src].fragment.output_kind == "MERGE":
                # order-preserving gather: one client PER producer so the
                # merge operator sees each task's sorted stream separately
                clients[src] = [ExchangeClient([b], task_index)
                                for b in stages[src].buffers]
            else:
                clients[src] = ExchangeClient(stages[src].buffers, task_index)
        planner = LocalPlanner(
            self.catalog,
            splits_per_node=self.session.splits_per_node,
            node_count=self.worker_count,
            task_index=task_index,
            task_count=stage.task_count,
            remote_clients=clients,
            dynamic_filtering=self.session.dynamic_filtering,
            hbm_limit_bytes=self.session.hbm_limit_bytes,
            task_concurrency=self.session.task_concurrency,
        )
        if memory_owner is not None:
            # book this task's HBM pool under the query id so the cluster
            # memory manager sees in-process reservations too
            self.memory_manager.register_pool(memory_owner,
                                              planner.memory.pool)
        # swap the collector for the task's output sink; a fused producer
        # fragment plans only its FEED subtree — the Filter/Project chain,
        # the PARTIAL aggregation and the seam shuffle run inside the fused
        # sink's jitted programs (execution/stage_compiler.py)
        from .plan_compiler import (
            ResidentBuildHandle,
            ResidentBuildSinkOperator,
            ResidentPlanExec,
            ResidentPlanSinkOperator,
        )
        from .stage_compiler import FusedStageExec, FusedStageSinkOperator

        ex = collective.get(f.id)
        if isinstance(ex, ResidentPlanExec):
            # a resident core fragment plans only the scan FEED below the
            # join spine — joins, chain, PARTIAL agg and the interior seams
            # all run inside the whole-plan program
            local = planner.plan(ex.spec.feed)
            sink = ResidentPlanSinkOperator(ex, task_index)
        elif isinstance(ex, ResidentBuildHandle):
            local = planner.plan(f.root)
            sink = ResidentBuildSinkOperator(ex, task_index)
        elif isinstance(ex, FusedStageExec):
            local = planner.plan(ex.spec.feed)
            sink = FusedStageSinkOperator(ex, task_index)
        elif ex is not None:
            from .collective_exchange import CollectiveOutputSink

            local = planner.plan(f.root)
            sink = CollectiveOutputSink(ex, task_index)
        else:
            local = planner.plan(f.root)
            out = stage.buffers[task_index]
            if spec_ctx is not None:
                # racing attempts write through the task's gate: the first
                # page (or empty finish) claims the stream, the loser's
                # first write raises SpeculationLost — downstream consumers
                # only ever see one attempt's pages
                from .speculation import GatedBuffer

                out = GatedBuffer(out, spec_ctx["gate"], spec_ctx["kind"])
            if tee is not None and tee.wants(f.id):
                # this fragment feeds a speculation-eligible non-leaf
                # stage: tee winner pages into the durable spool so a
                # straggling consumer's twin can re-read them.  Outside
                # the gate — a losing attempt never reaches the tee.
                from .speculation import SpoolTeeBuffer

                out = SpoolTeeBuffer(
                    out,
                    tee.writer(f.id, task_index,
                               stage.buffers[task_index].num_partitions,
                               attempt=attempt),
                    on_commit=lambda d, _f=f.id, _t=task_index:
                        tee.mark_committed(_f, _t, d))
            kind = f.output_kind if f.output_kind != "OUTPUT" else "GATHER"
            sketch, sketch_keys = None, ()
            if adaptive is not None:
                ov = adaptive.sink_override(f.id, task_index)
                if ov is not None:
                    # deferred producer: land everything in the single-
                    # partition staging buffer (already swapped into
                    # stage.buffers) and feed the heavy-hitter sketch
                    kind = "GATHER"
                    sketch, sketch_keys = ov
            sink = PartitionedOutputSink(
                out, kind,
                f.output_keys, serde=self.session.exchange_serde,
                sketch=sketch, sketch_keys=sketch_keys,
                coalesce_rows=f.sink_coalesce_rows)
            # output pages stay on the device until a consumer acks them:
            # the task's pool is charged for them
            sink.attach_memory(planner.memory)
        local.pipelines[-1][-1] = sink
        stats = None
        if stats_sink is not None:
            stats = QueryStats(label=f"fragment {f.id} task {task_index}:")
            stats_sink.append(stats)  # list.append is thread-safe
        return local.pipelines, stats

    def _run_time_sharing(self, fragments, stages, errors, stats_sink,
                          collective, attempt: int = 0, handle=None,
                          memory_owner=None) -> list[str]:
        """Schedule every task on a bounded MLFQ executor
        (exec/executor.py); returns the names of tasks that never finished."""
        import time as _time

        from ..exec.executor import TimeSharingTaskExecutor

        executor = TimeSharingTaskExecutor(self.session.executor_workers)
        try:
            handles = []
            try:
                for f in fragments:
                    stage = stages[f.id]
                    for t in range(stage.task_count):
                        pipelines, stats = self._build_task(
                            stage, t, stages, stats_sink, collective, attempt,
                            memory_owner=memory_owner)
                        handles.append(
                            (f, t, executor.submit(pipelines, stats),
                             pipelines))
            except BaseException:
                # a task that failed to BUILD (e.g. injected fault) must not
                # leave already-submitted siblings blocked on its buffers
                for s in stages.values():
                    for b in s.buffers:
                        b.abort()
                for ex in collective.values():
                    ex.abort()
                raise
            # poll every handle so the FIRST failure aborts all buffers
            # immediately (matching THREADS-mode fail-fast)
            from .task import STALL_TIMEOUT_S

            deadline = _time.monotonic() + 2 * STALL_TIMEOUT_S
            pending = list(range(len(handles)))
            aborted = False
            while pending and _time.monotonic() < deadline:
                if (not aborted and handle is not None
                        and handle.poll() is not None):
                    # OOM-killer verdict: unblock everything now; the caller
                    # raises the CLUSTER_OUT_OF_MEMORY error
                    aborted = True
                    for s in stages.values():
                        for b in s.buffers:
                            b.abort()
                    for ex in collective.values():
                        ex.abort()
                still = []
                for i in pending:
                    f, t, h, pipelines = handles[i]
                    if not h.done.is_set():
                        still.append(i)
                        continue
                    if h.error is None:
                        # deferred expression errors (ops/expr.py channel):
                        # checked per finished task, same as run_pipelines
                        from ..ops.expr import check_error_scalars

                        try:
                            check_error_scalars([
                                e for p in pipelines for op in p
                                for e in getattr(op, "pending_errors", ())
                            ])
                        except Exception as err:  # noqa: BLE001
                            h.error = err
                    if h.error is not None:
                        errors.append(h.error)
                        for s in stages.values():
                            for b in s.buffers:
                                b.abort()
                        for ex in collective.values():
                            ex.abort()
                if len(still) == len(pending):
                    _time.sleep(0.02)
                pending = still
            return [f"task-{handles[i][0].id}.{handles[i][1]}"
                    for i in pending]
        finally:
            executor.shutdown()

    def _run_task(self, stage: _Stage, task_index: int,
                  stages: dict[int, "_Stage"], errors: list,
                  stats_sink: Optional[list] = None,
                  collective: Optional[dict] = None,
                  attempt: int = 0, parent_span=None,
                  query_record=None, memory_owner=None,
                  spec_ctx: Optional[dict] = None,
                  adaptive=None, tee=None) -> None:
        import time as _time

        from ..exec.driver import collect_encoding_stats, collect_scan_stats
        from ..telemetry import metrics as tm
        from ..telemetry import runtime as rt
        from .speculation import SpeculationLost
        from .tracing import annotate_scan_span

        tm.TASKS_CREATED.inc()
        trec = rt.task_started(
            query_record.query_id if query_record is not None else "",
            f"f{stage.fragment.id}.t{task_index}", stage.fragment.id,
            task_index, "local")
        from ..telemetry import profiler

        # task threads are fresh per task: stamp the query/task identity so
        # every driver/exchange event this thread (and its pipeline group
        # threads, via run_pipelines context inheritance) records attributes
        profiler.set_context(trec.query_id, trec.task_id)
        t0 = _time.perf_counter()
        # what the thread ran, beside how long the task took: the rest of
        # the wall it waited (host-sync, exchange-wait) or stood runnable
        # behind another thread (the interpreter lock, the OS)
        cpu0 = _time.thread_time()
        group_cpu_s = 0.0
        pipelines = None
        state = "FINISHED"
        err = None
        with self.tracer.span(
                "trino.task", parent=parent_span,
                **{"trino.task.id": trec.task_id,
                   "trino.task.worker": "local"}) as sp:
            try:
                pipelines, stats = self._build_task(
                    stage, task_index, stages, stats_sink, collective or {},
                    attempt, memory_owner=memory_owner, spec_ctx=spec_ctx,
                    adaptive=adaptive, tee=tee)
                group_cpu_s = run_pipelines(pipelines, stats)
            except SpeculationLost:
                # this attempt lost the first-commit race — its twin owns
                # the output stream; unwind without touching the query
                state = "CANCELED"
                sp.set("speculation.lost", True)
            except BaseException as e:  # noqa: BLE001 — surfaced to
                # coordinator
                gate = spec_ctx["gate"] if spec_ctx is not None else None
                if gate is not None and gate.owner is not None \
                        and gate.owner != spec_ctx["kind"]:
                    # a loser failing for real changes nothing: the other
                    # attempt owns the stream and is still healthy
                    state = "CANCELED"
                    sp.set("speculation.lost", True)
                    self.resilience_events.append(
                        ("speculative_loser_error", stage.fragment.id,
                         task_index, type(e).__name__))
                else:
                    errors.append(e)
                    state = "FAILED"
                    err = f"{type(e).__name__}: {e}"
                    sp.set("error", type(e).__name__)
                    # unblock every sibling immediately: producers stuck in
                    # enqueue backpressure, consumers polling this (now
                    # dead) task, and partners parked at a collective
                    # all_to_all barrier would otherwise wait out the full
                    # join timeout before the real error surfaces
                    for s in stages.values():
                        for b in s.buffers:
                            b.abort()
                    for ex in (collective or {}).values():
                        ex.abort()
                    if adaptive is not None:
                        adaptive.abort()
            ingest = collect_scan_stats(pipelines) if pipelines else None
            if pipelines:
                tm.observe_encoding(collect_encoding_stats(pipelines))
            if ingest is not None:
                annotate_scan_span(sp, ingest)
                tm.observe_scan(ingest)
                if query_record is not None:
                    rt.add_input(query_record, ingest.scan_rows,
                                 ingest.scan_bytes)
            # closing the span writes the flight recorder's ``task`` event
            cpu_s = _time.thread_time() - cpu0 + group_cpu_s
            sp.record(state=state, cpu_s=round(cpu_s, 6))
        wall_s = _time.perf_counter() - t0
        rt.add_task_time(query_record, cpu_s, wall_s)
        tm.TASK_WALL_SECONDS.record(wall_s)
        if state == "FAILED":
            tm.TASKS_FAILED.inc()
        rt.task_finished(trec, state, error=err)
