"""Driver: moves batches through an operator chain.

Mirrors Trino's Driver.processInternal hot loop (reference:
operator/Driver.java:372 — ``page = current.getOutput(); next.addInput(page)``
per adjacent operator pair, finish propagation, early close on satisfied
LIMITs).  Single-threaded and synchronous: blocking here means an operator
simply declines input until a bridge is ready, and pipelines are executed in
dependency order by the task runner (build pipelines before probe pipelines —
the moral equivalent of HashBuilder blocking LookupJoin via the
LookupSourceFactory future).
"""

from __future__ import annotations

import time
from typing import Optional, Sequence

from ..spi.errors import GENERIC_INTERNAL_ERROR, TrinoError
from ..telemetry import profiler
from .operators import Operator
from .stats import (EncodingStats, OperatorStats, PipelineStats, QueryStats,
                    ScanIngestStats)

__all__ = ["Driver", "run_pipelines", "collect_scan_stats",
           "collect_encoding_stats"]


def collect_scan_stats(pipelines: Sequence[Sequence[Operator]]
                       ) -> Optional[ScanIngestStats]:
    """Roll up per-ScanOperator ingest counters (None if no scans ran)."""
    total: Optional[ScanIngestStats] = None
    for p in pipelines:
        for op in p:
            ingest = getattr(op, "ingest_stats", None)
            if ingest is not None and ingest.scan_batches:
                if total is None:
                    total = ScanIngestStats()
                total.merge(ingest)
    return total


def collect_encoding_stats(pipelines: Sequence[Sequence[Operator]]
                           ) -> Optional[EncodingStats]:
    """Roll up per-operator compressed-execution counters (None when no
    operator saw an encoded batch)."""
    total: Optional[EncodingStats] = None
    for p in pipelines:
        for op in p:
            enc = getattr(op, "encoding_stats", None)
            if enc is not None and enc.any:
                if total is None:
                    total = EncodingStats()
                total.merge(enc)
    return total


def _take_trace_attrs(op: Operator) -> dict:
    """What ``op``'s last add_input / finish_input decided (today: the
    aggregation's reduction path and compaction), moved onto the recorder's
    event of that call so it is reported once."""
    attrs = op.trace_attrs
    if attrs is None:
        return {}
    op.trace_attrs = None
    return attrs


class Driver:
    def __init__(self, operators: Sequence[Operator],
                 stats: Optional[PipelineStats] = None):
        assert operators, "empty pipeline"
        self.operators = list(operators)
        self.stats = stats
        self._names = [type(op).__name__ for op in self.operators]
        if stats is not None:
            stats.operators.extend(
                OperatorStats(name) for name in self._names)

    def _emit(self, i: int, page) -> None:
        """Credit a page moving from operator i to i+1."""
        s = self.stats
        if s is None or page is None:
            return
        src, dst = s.operators[i], s.operators[i + 1]
        src.output_rows += page.num_rows
        src.output_batches += 1
        dst.input_rows += page.num_rows
        dst.input_batches += 1

    def run(self) -> None:
        """Run to completion (single-driver execution)."""
        while True:
            status = self.process()
            if status == "finished":
                return
            if status == "blocked":
                stuck = [type(o).__name__ for o in self.operators
                         if not o.is_finished()]
                raise TrinoError(GENERIC_INTERNAL_ERROR,
                                 f"driver stalled; unfinished: {stuck}")

    def process(self, deadline: float = float("inf")) -> str:
        """One scheduling quantum: move pages until ``deadline`` (a
        time.perf_counter() timestamp), the driver finishes, or no operator
        can make progress.  Returns 'finished' | 'progressed' | 'blocked'
        (blocked = alive but waiting on an external input, e.g. an exchange
        or a bridge).  This is the yieldable unit the time-sharing executor
        schedules (reference: operator/Driver.processFor +
        TimeSharingTaskExecutor quanta)."""
        ops = self.operators
        n = len(ops)
        timed = self.stats is not None
        st = self.stats.operators if timed else None
        # ONE clock for OperatorStats.wall_s and the flight recorder: the
        # recorder's ``now()``, read once before and once after each call
        # (no device syncs, no locks), then an add for the stats and a
        # tuple store for the recorder.  At TRINO_TPU_PROFILE=full the
        # produced page is blocked-on first, so the enclosing event
        # charges true device time instead of async dispatch time.
        prof = profiler.enabled()
        prof_full = prof and profiler.is_full()
        clocked = timed or prof
        now = profiler.now
        names = self._names
        any_progress = False
        while not ops[-1].is_finished():
            progressed = False
            for i in range(n - 1):
                cur, nxt = ops[i], ops[i + 1]
                # early close: downstream done (e.g. LIMIT satisfied)
                if nxt.is_finished() and not cur.is_finished():
                    cur.close()
                    progressed = True
                    continue
                if not cur.is_finished() and nxt.needs_input():
                    t0 = now() if clocked else 0.0
                    page = cur.get_output()
                    t1 = now() if clocked else 0.0
                    if timed:
                        st[i].wall_s += t1 - t0
                    if page is not None:
                        if prof:
                            if prof_full:
                                profiler.sync_batch(page)
                                t1 = now()
                            profiler.event(profiler.OPERATOR, names[i], t0,
                                           t1, rows=page.num_rows)
                        t0 = now() if clocked else 0.0
                        nxt.add_input(page)
                        t1 = now() if clocked else 0.0
                        if timed:
                            st[i + 1].wall_s += t1 - t0
                        if prof:
                            profiler.event(profiler.OPERATOR, names[i + 1],
                                           t0, t1, rows=page.num_rows,
                                           **_take_trace_attrs(nxt))
                        self._emit(i, page)
                        progressed = True
                if cur.is_finished() and not nxt.input_done:
                    if i + 2 == n:
                        # pre-finish barrier: deferred masked-lane errors
                        # must surface BEFORE the sink marks its stream
                        # finished — a streaming consumer could otherwise
                        # observe a complete, "successful" result (NULL
                        # lanes) from a task that is about to fail
                        from ..ops.expr import check_error_scalars

                        check_error_scalars([
                            e for op in ops
                            for e in getattr(op, "pending_errors", ())])
                    t0 = now() if clocked else 0.0
                    nxt.finish_input()
                    t1 = now() if clocked else 0.0
                    if timed:
                        st[i + 1].wall_s += t1 - t0
                    if prof:
                        # finish is where blocking operators (agg flush,
                        # sort, join build seal) do their heavy lifting
                        profiler.event(profiler.OPERATOR,
                                       names[i + 1] + ".finish", t0, t1,
                                       **_take_trace_attrs(nxt))
                    progressed = True
            if ops[-1].is_finished():
                break
            if not progressed:
                return "progressed" if any_progress else "blocked"
            any_progress = True
            if time.perf_counter() >= deadline:
                return "progressed"
        # upstream of an early-finished sink gets closed so sources release
        for op in ops[:-1]:
            if not op.is_finished():
                op.close()
        return "finished"


def run_pipelines(pipelines: Sequence[Sequence[Operator]],
                  stats: Optional[QueryStats] = None) -> float:
    """Execute pipelines in dependency order (build sides first); returns
    the thread-CPU seconds the pipeline-group threads consumed (0.0 when
    every pipeline ran on the calling thread, whose own CPU the caller
    reads: the ``task`` event's ``cpu_s``).
    Pipelines belonging to one local-exchange cluster (tagged with the same
    ``_concurrent_group`` on their source operator — producers, parallel
    aggregation drivers AND the consumer chain) run on concurrent threads
    with bounded buffers between them: a full buffer parks the producer, an
    empty one parks the consumer, so memory stays bounded and the stages
    genuinely pipeline (numpy/XLA release the GIL inside kernels).  The
    legacy concurrent-union grouping (UnionSinkOperator with a concurrent
    bridge) is kept for plain UNION chains."""
    import threading

    from . import syncguard
    from .operators import UnionSinkOperator

    sync_before = syncguard.snapshot() if stats is not None else None
    group_cpu = []  # one entry per ended group thread; append is atomic

    def run_one(p, stop=None) -> None:
        ps = None
        if stats is not None:
            ps = PipelineStats()
            stats.pipelines.append(ps)
        Driver(p, ps).run()

    def run_parked(p, stop=None) -> None:
        """Drive to completion, sleeping briefly while parked on a bounded
        buffer (the thread-pool analogue of isBlocked() futures).  ``stop``
        aborts the loop when a sibling pipeline of the cluster failed."""
        ps = None
        if stats is not None:
            ps = PipelineStats()
            stats.pipelines.append(ps)
        d = Driver(p, ps)
        while True:
            if stop is not None and stop.is_set():
                return
            status = d.process()
            if status == "finished":
                return
            time.sleep(2e-4)

    def run_group(group, runner) -> None:
        from ..telemetry import profiler

        errors: list[BaseException] = []
        stop = threading.Event()
        # group threads inherit the spawning task thread's profiler
        # identity, so their operator events attribute to the right query
        prof_ctx = profiler.capture_context()

        def wrapped(q):
            try:
                profiler.apply_context(prof_ctx)
                runner(q, stop)
            except BaseException as e:  # noqa: BLE001
                errors.append(e)
                stop.set()  # unpark siblings so the group can unwind
            finally:
                # the thread is this function: its CPU clock began at zero
                group_cpu.append(time.thread_time())

        threads = [threading.Thread(target=wrapped, args=(q,),
                                    daemon=True) for q in group]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]

    i = 0
    n = len(pipelines)
    while i < n:
        p = pipelines[i]
        group = [p]
        gid = getattr(p[0], "_concurrent_group", None)
        if gid is not None:
            while (i + 1 < n and getattr(
                    pipelines[i + 1][0], "_concurrent_group", None) is gid):
                i += 1
                group.append(pipelines[i])
            run_group(group, run_parked)
            i += 1
            continue
        if isinstance(p[-1], UnionSinkOperator) and p[-1].bridge.concurrent:
            bridge = p[-1].bridge
            while (i + 1 < n
                   and isinstance(pipelines[i + 1][-1], UnionSinkOperator)
                   and pipelines[i + 1][-1].bridge is bridge):
                i += 1
                group.append(pipelines[i])
        if len(group) > 1:
            run_group(group, run_one)
        else:
            run_one(p)
        i += 1

    if stats is not None:
        ingest = collect_scan_stats(pipelines)
        if ingest is not None:
            stats.merge_scan(ingest)
        enc = collect_encoding_stats(pipelines)
        if enc is not None:
            stats.merge_encoding(enc)
        stats.merge_sync(syncguard.take_delta(sync_before))

    # deferred masked-lane expression errors (DIVISION_BY_ZERO, overflow...)
    # surface here: ONE batched scalar fetch across every operator of the
    # task, raising before any result is returned (ops/expr.py error channel)
    from ..ops.expr import check_error_scalars

    check_error_scalars([
        e for p in pipelines for op in p
        for e in getattr(op, "pending_errors", ())
    ])
    return sum(group_cpu)
