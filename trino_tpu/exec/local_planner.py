"""Local execution planner: plan tree → operator pipelines.

The LocalExecutionPlanner equivalent (reference: sql/planner/
LocalExecutionPlanner.java:403 — visitTableScan:2088, visitAggregation:1876,
visitJoin:2449): walks the optimized plan bottom-up building one operator
chain per pipeline; a join's build side becomes its own pipeline connected
through a JoinBridge (mirrors createSubContext + JoinBridge wiring at
LocalExecutionPlanner.java:2613).

Pipelines come back in dependency order: every build pipeline precedes the
pipeline that probes it, so a sequential run is correct (concurrent drivers
arrive with the task executor).
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from ..connectors.catalog import Catalog
from ..planner import plan as P
from ..spi.batch import Column, ColumnBatch
from ..spi.types import Type
from ..sql.ir import InputRef
from . import join_exec as JX
from .dynamic_filter import DynamicFilterHolder
from .revoking import TaskMemoryContext
from .operators import (
    BufferedInputMixin,
    DistinctLimitOperator,
    FilterProjectOperator,
    GroupIdOperator,
    HashAggregationOperator,
    JoinBridge,
    JoinBuildSink,
    LimitOperator,
    LocalUnionBridge,
    LookupJoinOperator,
    Operator,
    OutputCollector,
    RenameOperator,
    ReplicateOperator,
    ScanOperator,
    SemiJoinOperator,
    TableFunctionOperator,
    SortOperator,
    TableWriterOperator,
    TopNOperator,
    UnnestOperator,
    UnionSinkOperator,
    UnionSourceOperator,
    ValuesOperator,
    WindowOperator,
    plan_aggregation_feed,
    plan_lazy_scan,
)

__all__ = ["LocalExecutionPlan", "LocalPlanner"]


class LocalExecutionPlan:
    def __init__(self, pipelines: list[list[Operator]], collector: OutputCollector,
                 output_names: Sequence[str], output_types: Sequence[Type]):
        self.pipelines = pipelines
        self.collector = collector
        self.output_names = list(output_names)
        self.output_types = list(output_types)


class LocalPlanner:
    def __init__(self, catalog: Catalog, splits_per_node: int = 4,
                 node_count: int = 1, task_index: int = 0,
                 task_count: int = 1, remote_clients=None,
                 dynamic_filtering: bool = True,
                 hbm_limit_bytes: int = 16 << 30,
                 spill_to_disk_bytes: int = 0,
                 task_concurrency: int = 1):
        self.task_concurrency = task_concurrency
        self.catalog = catalog
        self.splits_per_node = splits_per_node
        self.node_count = node_count
        # distributed: this task's share of splits + exchange clients per
        # upstream fragment id (filled by the stage scheduler)
        self.task_index = task_index
        self.task_count = task_count
        self.remote_clients = remote_clients or {}
        self.dynamic_filtering = dynamic_filtering
        # per-task HBM pool: blocking operators reserve buffered device
        # bytes as revocable memory (exec/revoking.py)
        self.memory = TaskMemoryContext(hbm_limit_bytes, spill_to_disk_bytes)
        self.pipelines: list[list[Operator]] = []

    def plan(self, root: P.PlanNode) -> LocalExecutionPlan:
        chain = self._chain(root)
        collector = OutputCollector()
        chain.append(collector)
        self.pipelines.append(chain)
        if self.task_concurrency > 1:
            self.pipelines = [
                q for p in self.pipelines for q in self._parallelize(p)]
        for p in self.pipelines:
            plan_lazy_scan(p)
            plan_aggregation_feed(p)
            for op in p:
                if isinstance(op, BufferedInputMixin):
                    op.attach_memory(self.memory)
        return LocalExecutionPlan(
            self.pipelines, collector, root.output_names, root.output_types)

    def _parallelize(self, pipeline: list[Operator]) -> list[list[Operator]]:
        """Intra-task parallelism (LocalExchange.java:67 +
        AddLocalExchanges.java:111): a pipeline whose source is a multi-split
        scan forks into ``task_concurrency`` parallel driver chains.  Row-
        parallel operators (filter/project, INNER/LEFT/SINGLE lookup joins,
        semi joins — all probing the shared build bridge) clone into every
        chain; at the first grouped aggregation the rows cross a bounded
        HASH local exchange into ``task_concurrency`` parallel aggregation
        drivers (disjoint group spaces, so their outputs simply concatenate);
        everything further downstream runs in one consumer chain behind a
        GATHER exchange.  All pipelines of one exchange cluster are tagged
        with a ``_concurrent_group`` id — the driver runner executes the
        whole cluster concurrently with backpressure from the bounded
        buffers."""
        if not isinstance(pipeline[0], ScanOperator):
            return [pipeline]
        scan = pipeline[0]
        c = min(self.task_concurrency, len(scan.splits))
        if c < 2:
            return [pipeline]
        from .local_exchange import (
            GATHER,
            HASH,
            LocalExchange,
            LocalExchangeSinkOperator,
            LocalExchangeSourceOperator,
        )

        def clone(op: Operator) -> Optional[Operator]:
            if isinstance(op, FilterProjectOperator):
                return FilterProjectOperator(
                    op.predicate, op.projections,
                    op.output_names, op.output_types)
            if isinstance(op, LookupJoinOperator) and op.join_type in (
                    "INNER", "LEFT", "SINGLE") and op.left_keys:
                return LookupJoinOperator(
                    op.bridge, op.left_keys, op.join_type, op.residual,
                    op.output_names, op.output_types, op.place, op.riders)
            if isinstance(op, SemiJoinOperator) and op.source_keys:
                return SemiJoinOperator(
                    op.bridge, op.source_keys, op.null_aware, op.residual,
                    op.output_names, op.output_types)
            return None

        prefix = [scan]
        for op in pipeline[1:]:
            if clone(op) is not None:
                prefix.append(op)
            else:
                break
        rest = pipeline[len(prefix):]
        if not rest:  # nothing downstream to feed (shouldn't happen)
            return [pipeline]
        last = prefix[-1]
        names = (scan.columns if last is scan else last.output_names)

        # partition point: grouped aggregation -> HASH exchange + c clones
        agg = rest[0] if (isinstance(rest[0], HashAggregationOperator)
                          and rest[0].group_keys) else None

        gid = object()  # unique tag for this exchange cluster

        def tag(p: list[Operator]) -> list[Operator]:
            p[0]._concurrent_group = gid
            return p

        chains: list[list[Operator]] = []
        exch1 = LocalExchange(
            c, c if agg is not None else 1,
            HASH if agg is not None else GATHER,
            key_channels=(agg.group_keys if agg is not None else ()))
        for i in range(c):
            shard = ScanOperator(
                scan.connector, scan.splits[i::c], scan.columns,
                dynamic_filters=scan.dynamic_filters,
                constraint=scan.constraint, limit=scan.limit)
            ops: list[Operator] = [shard]
            ops += [clone(op) for op in prefix[1:]]
            ops.append(LocalExchangeSinkOperator(exch1, i, names))
            chains.append(tag(ops))
        if agg is None:
            consumer = tag([LocalExchangeSourceOperator(exch1, 0)] + rest)
            return chains + [consumer]
        gather = LocalExchange(c, 1, GATHER)
        for j in range(c):
            agg_clone = HashAggregationOperator(
                agg.group_keys, agg.aggs, agg.output_names,
                agg.output_types, agg.step)
            chains.append(tag([
                LocalExchangeSourceOperator(exch1, j), agg_clone,
                LocalExchangeSinkOperator(gather, j, agg.output_names)]))
        consumer = tag([LocalExchangeSourceOperator(gather, 0)] + rest[1:])
        return chains + [consumer]

    # ------------------------------------------------------------------
    def _chain(self, node: P.PlanNode) -> list[Operator]:
        if isinstance(node, P.TableScan):
            conn = self.catalog.connector(node.catalog)
            splits = conn.get_splits(
                node.table, self.splits_per_node, self.node_count)
            mine = [s for i, s in enumerate(splits)
                    if i % self.task_count == self.task_index]
            return [ScanOperator(conn, mine, node.columns,
                                 constraint=node.constraint,
                                 limit=node.limit)]

        if isinstance(node, P.RemoteSource):
            from ..execution.collective_exchange import (
                CollectiveRepartitionExchange,
                CollectiveSourceOperator,
            )
            from ..execution.task import (
                MergeSourceOperator,
                RemoteExchangeSourceOperator,
            )

            client = self.remote_clients[node.fragment_id]
            if isinstance(client, CollectiveRepartitionExchange):
                return [CollectiveSourceOperator(client, self.task_index)]
            if isinstance(client, list):  # MERGE: per-producer streams
                return [MergeSourceOperator(
                    client, node.sort_keys,
                    node.output_names, node.output_types)]
            return [RemoteExchangeSourceOperator(client)]

        if isinstance(node, P.Filter):
            chain = self._chain(node.source)
            last = chain[-1] if chain else None
            if (isinstance(last, FilterProjectOperator)
                    and last.projections is None):
                # Filter over Filter: AND the predicates into one program
                from ..spi.types import BOOLEAN
                from ..sql.ir import Call

                pred = node.predicate if last.predicate is None else Call(
                    BOOLEAN, "$and", (last.predicate, node.predicate))
                chain[-1] = FilterProjectOperator(
                    pred, None, node.output_names, node.output_types)
                return chain
            chain.append(FilterProjectOperator(
                node.predicate, None, node.output_names, node.output_types))
            return chain

        if isinstance(node, P.Project):
            chain = self._chain(node.source)
            last = chain[-1] if chain else None
            if (isinstance(last, FilterProjectOperator)
                    and last.projections is None):
                # Project over Filter: ONE fused filter+project program per
                # batch instead of two (ScanFilterAndProject fusion —
                # reference: operator/ScanFilterAndProjectOperator.java:68)
                chain[-1] = FilterProjectOperator(
                    last.predicate, node.expressions,
                    node.output_names, node.output_types)
                return chain
            chain.append(FilterProjectOperator(
                None, node.expressions, node.output_names, node.output_types))
            return chain

        if isinstance(node, P.Aggregate):
            if (node.step == "FINAL"
                    and isinstance(node.source, P.RemoteSource)):
                from ..execution.stage_compiler import (
                    FusedStageExec,
                    FusedStageSourceOperator,
                )

                client = self.remote_clients.get(node.source.fragment_id)
                if isinstance(client, FusedStageExec):
                    # whole-stage compilation: the producer stage already
                    # ran PARTIAL + all_to_all + FINAL inside one jitted
                    # program; this pipeline just takes its device shard
                    return [FusedStageSourceOperator(client,
                                                     self.task_index)]
            chain = self._chain(node.source)
            chain.append(HashAggregationOperator(
                node.group_keys, node.aggregates,
                node.output_names, node.output_types, node.step))
            return chain

        if isinstance(node, P.GroupId):
            chain = self._chain(node.source)
            chain.append(GroupIdOperator(
                node.key_channels, node.passthrough, node.sets,
                node.output_names, node.output_types))
            return chain

        if isinstance(node, P.Unnest):
            chain = self._chain(node.source)
            chain.append(UnnestOperator(
                node.replicate, node.unnest_channels, node.ordinality,
                node.output_names, node.output_types))
            return chain

        if isinstance(node, P.Join):
            bridge = JoinBridge()
            # dynamic filtering: INNER/RIGHT probe rows that cannot match are
            # droppable, so the build-side key domain prunes the probe scan
            # (exec/dynamic_filter.py; server/DynamicFilterService.java:105)
            holders = [None] * len(node.right_keys)
            scan_attach = []
            if (self.dynamic_filtering and node.left_keys
                    and node.join_type in ("INNER", "RIGHT")):
                for k, lch in enumerate(node.left_keys):
                    col = _trace_to_scan_col(node.left, lch)
                    if col is not None:
                        holders[k] = DynamicFilterHolder()
                        scan_attach.append((col, holders[k]))
            build = self._chain(node.right)
            build.append(JoinBuildSink(
                bridge, node.right_keys,
                node.right.output_types, node.right.output_names,
                dynamic_filter_holders=holders))
            self.pipelines.append(build)
            chain = self._chain(node.left)
            if scan_attach and isinstance(chain[0], ScanOperator):
                chain[0].dynamic_filters.extend(scan_attach)
            if node.left_keys:
                _add_riders(chain, _probe_words(node))
            chain.append(LookupJoinOperator(
                bridge, node.left_keys, node.join_type, node.residual,
                node.output_names, node.output_types,
                sum(isinstance(op, LookupJoinOperator) for op in chain)))
            return chain

        if isinstance(node, P.SemiJoin):
            bridge = JoinBridge()
            build = self._chain(node.filter_source)
            build.append(JoinBuildSink(
                bridge, node.filter_keys,
                node.filter_source.output_types, node.filter_source.output_names))
            self.pipelines.append(build)
            chain = self._chain(node.source)
            chain.append(SemiJoinOperator(
                bridge, node.source_keys, node.null_aware, node.residual,
                node.output_names, node.output_types))
            return chain

        if isinstance(node, P.Union):
            bridge = LocalUnionBridge(len(node.sources))
            for src in node.sources:
                chain = self._chain(src)
                chain.append(UnionSinkOperator(bridge, node.output_names))
                self.pipelines.append(chain)
            return [UnionSourceOperator(bridge)]

        if isinstance(node, P.MatchRecognize):
            from .match_recognize import MatchRecognizeOperator

            chain = self._chain(node.source)
            chain.append(MatchRecognizeOperator(
                node.partition_channels, node.order_keys, node.pattern,
                node.defines, node.measures, node.skip_past,
                node.output_names, node.output_types,
                node.source.output_names))
            return chain

        if isinstance(node, P.Window):
            chain = self._chain(node.source)
            chain.append(WindowOperator(
                node.partition_keys, node.order_keys, node.functions,
                node.output_names, node.output_types))
            return chain

        if isinstance(node, P.Sort):
            chain = self._chain(node.source)
            chain.append(SortOperator(node.keys))
            return chain

        if isinstance(node, P.TopN):
            chain = self._chain(node.source)
            chain.append(TopNOperator(node.count, node.keys))
            return chain

        if isinstance(node, P.Limit):
            chain = self._chain(node.source)
            chain.append(LimitOperator(node.count))
            return chain

        if isinstance(node, P.Replicate):
            chain = self._chain(node.source)
            chain.append(ReplicateOperator(node.count_channel))
            return chain

        if isinstance(node, P.DistinctLimit):
            chain = self._chain(node.source)
            chain.append(DistinctLimitOperator(node.count))
            return chain

        if isinstance(node, P.Values):
            batch = _values_batch(node)
            return [ValuesOperator(batch)]

        if isinstance(node, P.TableFunctionScan):
            return [TableFunctionOperator(node.bound, node.output_names)]

        if isinstance(node, P.Output):
            chain = self._chain(node.source)
            chain.append(RenameOperator(node.output_names))
            return chain

        if isinstance(node, P.Exchange):
            # single-node: exchanges are pass-through; the distributed task
            # runner replaces these with collective/buffered edges
            return self._chain(node.source)

        if isinstance(node, P.TableWriter):
            chain = self._chain(node.source)
            conn = self.catalog.connector(node.catalog)
            try:
                schema = conn.get_table_schema(node.table)
            except KeyError:  # CTAS: create target from source schema
                from ..spi.connector import ColumnSchema, TableSchema
                schema = TableSchema(node.table, tuple(
                    ColumnSchema(n, t) for n, t in
                    zip(node.source.output_names, node.source.output_types)))
                try:
                    conn.create_table(schema)
                except ValueError:
                    # parallel writer tasks race to create the CTAS target;
                    # first one wins (scaled writers)
                    schema = conn.get_table_schema(node.table)
            # INSERT maps select output to table columns by POSITION
            chain.append(RenameOperator([c.name for c in schema.columns]))
            sink = conn.create_page_sink(node.table)
            chain.append(TableWriterOperator(
                sink,
                on_finish=lambda frags: conn.finish_insert(node.table, frags)))
            return chain

        raise NotImplementedError(f"no operator for {type(node).__name__}")


def _trace_down(node: P.PlanNode, ch: int) -> tuple[P.PlanNode, int]:
    """Follow an output channel down the probe-side left spine while it
    passes through unchanged: the node and channel where it stops (a scan,
    a computed value, a join's build side, a remote or union boundary)."""
    while True:
        if isinstance(node, (P.Filter, P.Exchange)):
            node = node.source
        elif (isinstance(node, P.Project)
              and isinstance(node.expressions[ch], InputRef)):
            node, ch = node.source, node.expressions[ch].index
        elif (isinstance(node, P.Join)
              and ch < len(node.left.output_types)):
            node = node.left
        elif (isinstance(node, P.SemiJoin)
              and ch < len(node.source.output_types)):
            node = node.source
        else:
            return node, ch


def _trace_to_scan_col(node: P.PlanNode, ch: int) -> Optional[int]:
    """Map an output channel down the probe-side left spine to a TableScan
    column index, or None if the channel is computed / crosses a remote or
    union boundary.  Descends only paths whose rows pass through unchanged
    (a dropped probe row cannot change other rows' results)."""
    node, ch = _trace_down(node, ch)
    return ch if isinstance(node, P.TableScan) else None


def _probe_words(node: P.Join) -> int:
    """The 32-bit words a lane that the probe of ``node`` reads whatever
    width it gathers at, counted from the plan's types as
    join_exec.gather_words counts columns: its key columns (a validity mask
    on a key out of a join's build side, whose output carries the match
    mask) and one read of its join table.  Its build columns are left out:
    a probe that compacts gathers them over its own cap, not over the lanes
    it is handed, and the plan cannot tell which probes will."""
    def words(ch: int) -> int:
        stop, _ = _trace_down(node.left, ch)
        return JX.column_words(node.left.output_types[ch].storage_dtype,
                               isinstance(stop, P.Join))

    return sum(words(ch) for ch in node.left_keys) + 1


def _add_riders(chain: list[Operator], words: int) -> None:
    """A probe appended to ``chain`` rides the output width of the probes
    before it: its ``words`` join their riders, through the filters and
    projections between (which mask lanes, never drop them), up to the
    first operator of another kind."""
    for op in reversed(chain):
        if isinstance(op, LookupJoinOperator) and op.left_keys:
            op.riders += words
        elif not isinstance(op, FilterProjectOperator):
            return


def _values_batch(node: P.Values) -> ColumnBatch:
    cols = []
    for i, t in enumerate(node.output_types):
        vals = [row[i] for row in node.rows]
        cols.append(Column.from_values(t, vals))
    return ColumnBatch(list(node.output_names), cols)
