"""Operators: the batch-at-a-time data plane.

Mirrors Trino's operator contract (reference: operator/Operator.java:21 —
``needsInput``/``addInput``/``getOutput``/``isFinished``) with the same
streaming/blocking split:

- streaming: ScanOperator, FilterProjectOperator (the fused
  ScanFilterAndProjectOperator analogue — operator/
  ScanFilterAndProjectOperator.java:68), LookupJoinOperator
  (operator/join/LookupJoinOperator.java:37), LimitOperator.
- blocking (accumulate → finish → emit): HashAggregationOperator
  (operator/HashAggregationOperator.java:53), SortOperator/TopNOperator
  (operator/OrderByOperator.java:44, TopNOperator.java:34), JoinBuildSink
  (operator/join/HashBuilderOperator.java:57), DistinctLimitOperator.

The per-row compiled inner loops of the JVM design are replaced by the
jitted kernels in exec/kernels.py; operators are thin host-side glue that
moves fixed-shape column arrays in and out of those programs.
"""

from __future__ import annotations

import threading
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..caching.executable_cache import program
from ..ops.expr import compile_expression
from ..sql.analyzer import STAT_AGGS
from ..spi.batch import (Column, ColumnBatch, encoded_exec, pad_to_bucket,
                         unify_dictionaries)
from ..spi.errors import SUBQUERY_MULTIPLE_ROWS, TrinoError
from ..spi.connector import Connector, ConnectorPageSink, Split
from ..spi.types import BIGINT, BOOLEAN, DOUBLE, DecimalType, Type, is_string
from ..sql.ir import InputRef, RowExpression, referenced_inputs
from ..planner.plan import AggCall, SortKey, WindowFunc
from . import kernels as K
from . import syncguard as SG
from . import window_kernels as WK
from .prefetch import (
    BatchCoalescer,
    DeviceStager,
    IngestConfig,
    PrefetchingPageSource,
    encode_scan_batch,
)
from .revoking import device_nbytes
from .stats import EncodingStats, ScanIngestStats

__all__ = [
    "Operator",
    "ScanOperator",
    "ValuesOperator",
    "LocalUnionBridge",
    "UnionSinkOperator",
    "UnionSourceOperator",
    "FilterProjectOperator",
    "plan_lazy_scan",
    "plan_aggregation_feed",
    "HashAggregationOperator",
    "JoinBridge",
    "JoinBuildSink",
    "LookupJoinOperator",
    "SemiJoinOperator",
    "SortOperator",
    "TopNOperator",
    "WindowOperator",
    "LimitOperator",
    "GroupIdOperator",
    "ReplicateOperator",
    "TableFunctionOperator",
    "UnnestOperator",
    "DistinctLimitOperator",
    "TableWriterOperator",
    "OutputCollector",
    "RenameOperator",
]


class Operator:
    """Synchronous single-driver operator protocol."""

    input_done: bool = False
    _closed: bool = False
    # what the last add_input / finish_input decided, for the flight
    # recorder: the driver moves it onto that call's ``operator`` event
    trace_attrs: Optional[dict] = None

    def needs_input(self) -> bool:
        return not self.input_done and not self._closed

    def add_input(self, batch: ColumnBatch) -> None:
        raise NotImplementedError

    def finish_input(self) -> None:
        self.input_done = True

    def get_output(self) -> Optional[ColumnBatch]:
        return None

    def is_finished(self) -> bool:
        raise NotImplementedError

    def close(self) -> None:
        """Downstream no longer needs output (e.g. LIMIT satisfied)."""
        self._closed = True
        self.input_done = True


# ---------------------------------------------------------------------------
# sources


class ScanOperator(Operator):
    """Reads splits via the connector page source (operator/
    TableScanOperator.java:46).  ``dynamic_filters`` [(column_idx, holder)]
    prune rows before padding/device transfer (the probe side of
    DynamicFilterService — see exec/dynamic_filter.py).

    With ``TRINO_TPU_PREFETCH=1`` (the default) the scan runs the async
    ingest pipeline of exec/prefetch.py: splits decode on background
    threads into a bounded queue, small batches coalesce up to the target
    power-of-two bucket, and the next batch's ``jax.device_put`` is
    dispatched while the previous one computes downstream.  With
    ``TRINO_TPU_PREFETCH=0`` the synchronous one-split-at-a-time path below
    runs bit-for-bit as before."""

    def __init__(self, connector: Connector, splits: Sequence[Split],
                 columns: Sequence[str], dynamic_filters=None,
                 constraint=None, limit: Optional[int] = None):
        self.connector = connector
        self.splits = list(splits)
        self.columns = list(columns)
        self.dynamic_filters = list(dynamic_filters or [])
        # pushed-down LIMIT: stop opening further splits once this many
        # rows are out (only exact for unmasked batches; the engine Limit
        # above re-enforces the precise count)
        self.limit = limit
        self._emitted_rows = 0
        # advisory TupleDomain from predicate pushdown (exec/domain_filter.py)
        self.constraint = constraint if (
            constraint is not None and not constraint.is_all) else None
        self._name_to_idx = {n: i for i, n in enumerate(self.columns)}
        self._domain_dict_cache: dict = {}
        self.rows_pruned_by_domain = 0
        self._source = None
        self.input_done = True
        # -- async ingest state (exec/prefetch.py) --
        self.ingest_cfg = IngestConfig.from_env()
        self.ingest_stats = ScanIngestStats()
        # compressed execution: channels the downstream FilterProject only
        # passes through (set by plan_lazy_scan) stage as LAZY columns —
        # their bytes cross to the device only if something touches them
        self.lazy_channels: frozenset[int] = frozenset()
        self.encoding_stats = EncodingStats()
        self._prefetcher: Optional[PrefetchingPageSource] = None
        self._coalescer: Optional[BatchCoalescer] = None
        self._stager: Optional[DeviceStager] = None
        self._staged: Optional[ColumnBatch] = None
        self._hold_back: Optional[ColumnBatch] = None
        self._ingest_done = False

    def needs_input(self) -> bool:
        return False

    def _apply_constraint(self, batch: ColumnBatch) -> ColumnBatch:
        from .domain_filter import tuple_domain_mask

        mask = tuple_domain_mask(batch, self.constraint, self._name_to_idx,
                                 self._domain_dict_cache)
        if mask is None or mask.all():
            return batch
        self.rows_pruned_by_domain += int(batch.num_rows - mask.sum())
        return batch.filter(mask)

    def _apply_dynamic_filters(self, batch: ColumnBatch) -> ColumnBatch:
        mask = None
        for col_idx, holder in self.dynamic_filters:
            c = batch.columns[col_idx]
            m = holder.probe_mask(c.data, c.valid, c.dictionary)
            if m is not None:
                mask = m if mask is None else (mask & m)
        if mask is None or mask.all():
            return batch
        for _, holder in self.dynamic_filters:
            holder.rows_pruned += int(batch.num_rows - mask.sum())
            break  # credit once per batch
        return batch.filter(mask)

    def get_output(self) -> Optional[ColumnBatch]:
        if self.ingest_cfg.enabled:
            return self._get_output_async()
        return self._get_output_sync()

    def _get_output_sync(self) -> Optional[ColumnBatch]:
        while True:
            if self._closed:
                return None
            if self._source is None:
                if (self.limit is not None
                        and self._emitted_rows >= self.limit):
                    # pushed-down LIMIT satisfied: drop remaining splits
                    self.splits = []
                if not self.splits:
                    return None
                # kwarg only when constrained: wrapper connectors with the
                # bare (split, columns) signature keep working
                if self.constraint is not None:
                    self._source = self.connector.create_page_source(
                        self.splits.pop(0), self.columns,
                        constraint=self.constraint)
                else:
                    self._source = self.connector.create_page_source(
                        self.splits.pop(0), self.columns)
            if self._source.is_finished():
                self._source.close()
                self._source = None
                continue
            batch = self._source.get_next_batch()
            if batch is not None:
                # device-pinned batches (live mask set) skip host-side
                # dynamic filtering — pulling them down would cost more
                # than the pruning saves
                if self.constraint is not None and batch.live is None:
                    batch = self._apply_constraint(batch)
                    if batch.num_rows == 0:
                        continue
                if self.dynamic_filters and batch.live is None:
                    batch = self._apply_dynamic_filters(batch)
                    if batch.num_rows == 0:
                        continue
                # bucket scan output shapes so every downstream jitted
                # program compiles once per (pipeline, bucket)
                if self.limit is not None and batch.live is None:
                    self._emitted_rows += batch.num_rows
                self.ingest_stats.observe_batch(batch.nbytes, batch.num_rows)
                if encoded_exec():
                    batch = encode_scan_batch(
                        batch, self.lazy_channels, self.encoding_stats)
                return pad_to_bucket(batch)

    # -- async ingest path --------------------------------------------------

    def _ensure_ingest(self) -> None:
        if self._prefetcher is not None or self._ingest_done:
            return
        self._prefetcher = PrefetchingPageSource(
            self.connector, self.splits, self.columns,
            constraint=self.constraint, config=self.ingest_cfg,
            stats=self.ingest_stats, limit_rows=self.limit)
        self.splits = []  # owned by the prefetcher now
        self._coalescer = BatchCoalescer(
            self.ingest_cfg.coalesce_rows, stats=self.ingest_stats)
        self._stager = DeviceStager(stats=self.ingest_stats,
                                    lazy_channels=self.lazy_channels,
                                    enc_stats=self.encoding_stats)

    def _stage(self, batch: ColumnBatch) -> ColumnBatch:
        if self.ingest_cfg.stage_device:
            from ..telemetry import profiler

            if profiler.enabled():
                t0 = profiler.now()
                staged = self._stager.stage(batch)
                profiler.event(profiler.STAGE, "scan.stage", t0,
                               rows=batch.num_rows, bytes=batch.nbytes)
                return staged
            return self._stager.stage(batch)
        return batch

    def _produce_next(self) -> Optional[ColumnBatch]:
        """One coalesced+staged batch, or None at end of input.  Filters run
        consumer-side (holder counters are not thread-safe); a device-pinned
        batch (``live`` set) flushes the coalescer first so row order holds,
        then passes through like the sync path."""
        if self._ingest_done:
            return None
        self._ensure_ingest()
        while True:
            if (self.limit is not None
                    and self._emitted_rows >= self.limit):
                # pushed-down LIMIT satisfied: abort prefetch, flush tail
                self._prefetcher.close()
                self._ingest_done = True
                flushed = self._coalescer.flush()
                return None if flushed is None else self._stage(flushed)
            batch = self._prefetcher.get_next_batch()
            if batch is None:
                self._ingest_done = True
                flushed = self._coalescer.flush()
                return None if flushed is None else self._stage(flushed)
            if batch.live is not None:
                flushed = self._coalescer.flush()
                if flushed is not None:
                    self._hold_back = pad_to_bucket(batch)
                    return self._stage(flushed)
                return pad_to_bucket(batch)
            if self.constraint is not None:
                batch = self._apply_constraint(batch)
            if self.dynamic_filters:
                batch = self._apply_dynamic_filters(batch)
            if batch.num_rows == 0:
                continue
            if self.limit is not None:
                self._emitted_rows += batch.num_rows
            self._coalescer.add(batch)
            if self._coalescer.ready():
                return self._stage(self._coalescer.flush())

    def _get_output_async(self) -> Optional[ColumnBatch]:
        if self._closed:
            return None
        if self._hold_back is not None:
            out, self._hold_back = self._hold_back, None
        elif self._staged is not None:
            out, self._staged = self._staged, None
        else:
            out = self._produce_next()
        # double buffering: dispatch the next batch's device transfer now so
        # it overlaps downstream compute on `out`
        if out is not None and self._staged is None \
                and self._hold_back is None:
            self._staged = self._produce_next()
        return out

    def is_finished(self) -> bool:
        if self._closed:
            return True
        if not self.ingest_cfg.enabled:
            return self._source is None and not self.splits
        if self._staged is not None or self._hold_back is not None:
            return False
        if self._prefetcher is None:
            return self._ingest_done or not self.splits
        return self._ingest_done and self._coalescer.buffered_rows == 0

    def close(self) -> None:
        super().close()
        if self._prefetcher is not None:
            self._prefetcher.close()  # drop in-flight + unclaimed splits


class TableFunctionOperator(Operator):
    """Leaf table-function source (reference:
    operator/LeafTableFunctionOperator.java:41): drains the bound
    function's batch generator."""

    def __init__(self, bound, output_names):
        self.output_names = list(output_names)
        self._iter = bound.batches()
        self._done = False
        self.input_done = True

    def needs_input(self) -> bool:
        return False

    def get_output(self) -> Optional[ColumnBatch]:
        if self._done or self._closed:
            return None
        batch = next(self._iter, None)
        if batch is None:
            self._done = True
            return None
        return pad_to_bucket(batch.rename(self.output_names))

    def is_finished(self) -> bool:
        return self._done or self._closed


class ValuesOperator(Operator):
    def __init__(self, batch: ColumnBatch):
        self._batch = batch
        self.input_done = True

    def needs_input(self) -> bool:
        return False

    def get_output(self) -> Optional[ColumnBatch]:
        b, self._batch = self._batch, None
        return b

    def is_finished(self) -> bool:
        return self._batch is None


# ---------------------------------------------------------------------------
# union (local gather between pipelines)


class LocalUnionBridge:
    """In-task handoff for Union inputs: each input pipeline ends in a
    UnionSinkOperator appending here; the consumer pipeline starts from a
    UnionSourceOperator.  The single-driver analogue of a gathering
    LocalExchange (reference: operator/exchange/LocalExchange.java:67)."""

    def __init__(self, num_inputs: int):
        from collections import deque

        self.num_inputs = num_inputs
        self.batches: "deque[ColumnBatch]" = deque()
        self.finished_inputs = 0
        self._lock = threading.Lock()  # sinks may run on concurrent drivers
        # True only for task_concurrency source forks: the driver runner
        # threads sibling chains for these (plain UNION branches may hold
        # memory-accounted operators that assume one thread)
        self.concurrent = False

    def input_finished(self) -> None:
        with self._lock:
            self.finished_inputs += 1

    @property
    def all_finished(self) -> bool:
        return self.finished_inputs >= self.num_inputs


class UnionSinkOperator(Operator):
    def __init__(self, bridge: LocalUnionBridge, names: Sequence[str]):
        self.bridge = bridge
        self.names = list(names)

    def add_input(self, batch: ColumnBatch) -> None:
        if batch.num_rows:
            self.bridge.batches.append(batch.rename(self.names))

    def finish_input(self) -> None:
        super().finish_input()
        self.bridge.input_finished()

    def is_finished(self) -> bool:
        return self.input_done


class UnionSourceOperator(Operator):
    def __init__(self, bridge: LocalUnionBridge):
        self.bridge = bridge
        self.input_done = True

    def needs_input(self) -> bool:
        return False

    def get_output(self) -> Optional[ColumnBatch]:
        if self._closed or not self.bridge.all_finished:
            return None
        if self.bridge.batches:
            return self.bridge.batches.popleft()
        return None

    def is_finished(self) -> bool:
        return self._closed or (self.bridge.all_finished
                                and not self.bridge.batches)


# ---------------------------------------------------------------------------
# filter + project (the jit-fusion point)


def _to_cols(batch: ColumnBatch):
    """(data, valid) pairs, device-passthrough: jax arrays stay on device."""
    return [(c.data, c.valid) for c in batch.columns]


class _FilterProjectProgram:
    """One compiled filter+project: ``run`` is the named program, ``body``
    the function it traces -- ``(cols, live) -> (outs, live, err_code)`` --
    which a consumer that folds the outputs away (the streaming masked
    aggregation) traces inside a program of its own instead, reading only
    the ``needed`` input channels (None = all of them)."""

    __slots__ = ("run", "projs", "body", "needed", "out_meta", "views",
                 "consumers")

    def __init__(self, run, projs, body, needed, out_meta):
        self.run = run
        self.projs = projs
        self.body = body
        self.needed = needed
        self.out_meta = out_meta   # (type, dictionary) per output column
        self.views: dict = {}      # input structure -> (shapes out, raises)
        self.consumers: dict = {}  # consumer's static key -> its program

    def view(self, sig: tuple, cols, live) -> tuple:
        """(the body's output batch for inputs of structure ``sig`` as
        shapes, whether the body can raise) -- abstractly (jax.eval_shape:
        one trace, nothing runs).  The error scalar is None when nothing
        that can raise was traced."""
        hit = self.views.get(sig)
        if hit is None:
            outs, out_live, err = jax.eval_shape(self.body, cols, live)
            hit = self.views[sig] = (self.output_batch(outs, out_live),
                                     err is not None)
        return hit

    def output_batch(self, outs, live) -> ColumnBatch:
        """The body's ``outs`` and ``live`` -- arrays, tracers or shapes --
        as the batch the operator would have handed on."""
        return ColumnBatch(
            [str(i) for i in range(len(outs))],
            [Column(t, d, v, dictionary)
             for (d, v), (t, dictionary) in zip(outs, self.out_meta)], live)


class FilterProjectOperator(Operator):
    """Fused filter+project compiled to ONE jitted XLA program per
    (expression set, shape bucket): the predicate ANDs into the batch's
    ``live`` selection mask instead of compacting (dynamic shapes defeat
    XLA), projections evaluate on every lane, and columns stay device-
    resident between operators.  Replaces sql/gen/PageFunctionCompiler.java:
    104 bytecode + operator/ScanFilterAndProjectOperator.java:68 fusion."""

    # Cross-execution program cache: operators are rebuilt per query run, but
    # the jitted XLA program depends only on (expressions, input types,
    # dictionaries, output dtypes).  jax.jit caches by function identity, so
    # a fresh closure per run would recompile every time.  Values hold their dictionary arrays so the
    # id()-based key component can never be recycled by the allocator.
    # Guarded by a lock: distributed worker threads share this cache.
    _PROGRAM_CACHE: dict = {}
    _PROGRAM_CACHE_LOCK = threading.Lock()

    def __init__(self, predicate: Optional[RowExpression],
                 projections: Optional[Sequence[RowExpression]],
                 output_names: Sequence[str], output_types: Sequence[Type]):
        self.predicate = predicate
        self.projections = list(projections) if projections is not None else None
        self.output_names = list(output_names)
        self.output_types = list(output_types)
        self._pending: Optional[ColumnBatch] = None
        self._compiled = None
        self._compiled_dicts = None
        # device int32 scalars, one per batch whose program traced an
        # error-capable op (division, overflow...); drained by the runner
        self.pending_errors: list = []
        self.encoding_stats = EncodingStats()
        # the aggregation directly behind this operator, when local planning
        # found one that may fold this operator's work into its own
        # per-batch program (plan_aggregation_feed)
        self.consumer: Optional["HashAggregationOperator"] = None

    def _compile(self, batch: ColumnBatch):
        dicts = [c.dictionary for c in batch.columns]
        if self._compiled is not None and all(
            a is b for a, b in zip(self._compiled_dicts, dicts)
        ):
            return self._compiled
        types = [c.type for c in batch.columns]
        key = (
            self.predicate,
            None if self.projections is None else tuple(self.projections),
            tuple(types),
            tuple(id(d) if d is not None else None for d in dicts),
            tuple(self.output_types),
        )
        cache = FilterProjectOperator._PROGRAM_CACHE
        with FilterProjectOperator._PROGRAM_CACHE_LOCK:
            hit = cache.get(key)
            if hit is not None:
                self._compiled, self._compiled_dicts = hit[0], dicts
                return self._compiled
            if len(cache) >= 1024:  # bound: evict oldest (insertion order)
                cache.pop(next(iter(cache)))
        pred = (
            compile_expression(self.predicate, types, dicts)
            if self.predicate is not None
            else None
        )
        projs = (
            [compile_expression(e, types, dicts) for e in self.projections]
            if self.projections is not None
            else None
        )
        out_dtypes = [t.storage_dtype for t in self.output_types]

        def run(cols, live):
            from ..ops.expr import (
                expr_condition_mask,
                expr_error_scope,
                reduce_error_lanes,
            )

            n = next(d for d, _ in cols if d is not None).shape[0]
            with expr_error_scope() as errs:
                if pred is not None:
                    with expr_condition_mask(live):
                        data, valid = pred(cols)
                    mask = data if valid is None else data & valid
                    if getattr(mask, "ndim", 1) == 0:
                        mask = jnp.broadcast_to(mask, (n,))
                    live = mask if live is None else live & mask
                if projs is None:
                    outs = [(d, v) for d, v in cols]
                else:
                    outs = []
                    with expr_condition_mask(live):
                        for ce, dt in zip(projs, out_dtypes):
                            d, v = ce(cols)
                            d = jnp.asarray(d)
                            if d.ndim == 0:
                                d = jnp.broadcast_to(d, (n,))
                            d = d.astype(dt)
                            if v is not None:
                                v = jnp.asarray(v)
                                if v.ndim == 0:
                                    v = jnp.broadcast_to(v, (n,))
                            outs.append((d, v))
                err = reduce_error_lanes(errs, (n,))
            # one int32 scalar (or None when nothing error-capable was
            # traced); each recording already carries its lane mask (input
            # live for the predicate, post-filter live for projections), so
            # a filtered-out row can't raise but a failing WHERE clause can
            err_code = None if err is None else jnp.max(err)
            return outs, live, err_code

        needed = None
        if self.projections is not None:
            needed = frozenset(
                referenced_inputs(self.predicate)
                if self.predicate is not None else ()).union(
                *(referenced_inputs(e) for e in self.projections))
        out_meta = (list(zip(types, dicts)) if projs is None else
                    [(t, ce.dictionary)
                     for t, ce in zip(self.output_types, projs)])
        self._compiled = _FilterProjectProgram(
            program("operators.filter_project", run), projs, run, needed,
            out_meta)
        self._compiled_dicts = dicts
        with FilterProjectOperator._PROGRAM_CACHE_LOCK:
            FilterProjectOperator._PROGRAM_CACHE.setdefault(
                key, (self._compiled, dicts))
        return self._compiled

    def needs_input(self) -> bool:
        return self._pending is None and super().needs_input()

    def _encoded_plan(self, batch: ColumnBatch):
        """(needed_channels, passthrough) for the encoded fast path, or
        None to use the legacy all-channels path.

        ``needed`` are input channels the compiled program actually reads
        (predicate inputs + every non-trivial projection's inputs); they
        feed the jit as real arrays, materializing LAZY / expanding RLE
        on device.  ``passthrough`` maps output position -> input channel
        for bare InputRef projections, whose columns bypass the program
        entirely and KEEP their encoding — this is the late-
        materialization seam: a selective predicate only ever touches its
        own channels, and payload columns ride through still encoded."""
        needed: set[int] = set()
        if self.predicate is not None:
            needed |= referenced_inputs(self.predicate)
        passthrough: dict[int, int] = {}
        if self.projections is None:
            # pure filter: every column passes through positionally
            passthrough = {i: i for i in range(batch.num_columns)}
        else:
            for j, e in enumerate(self.projections):
                if (isinstance(e, InputRef)
                        and str(batch.columns[e.index].type)
                        == str(self.output_types[j])):
                    passthrough[j] = e.index
                else:
                    needed |= referenced_inputs(e)
        if any(i >= batch.num_columns for i in needed):
            return None  # malformed ref; let the legacy path raise
        return needed, passthrough

    def _add_input_encoded(self, batch: ColumnBatch) -> bool:
        """Encoding-aware filter+project: compute the mask from needed
        channels only; RLE/LAZY columns that merely pass through are never
        expanded or staged.  Returns False to fall back to legacy."""
        plan = self._encoded_plan(batch)
        if plan is None:
            return False
        needed, passthrough = plan
        batch = pad_to_bucket(batch)
        n = batch.num_rows
        cols_in = []
        for i, c in enumerate(batch.columns):
            if i in needed:
                if c.encoding == "RLE":
                    cols_in.append((K.rle_fill(c.rle_value, n), c.valid))
                else:  # touching .data materializes LAZY exactly once
                    cols_in.append((c.data, c.valid))
            else:
                # dead placeholder: device-created zeros cost no PCIe and
                # XLA removes the unused input from the program
                dtype = (np.int32 if c.dictionary is not None
                         else c.type.storage_dtype)
                cols_in.append((jnp.zeros(n, dtype), None))
        prog = self._compile(batch)
        projs = prog.projs
        outs, live, err_code = prog.run(cols_in, batch.live)
        if err_code is not None:
            self.pending_errors.append(err_code)
        cols = []
        if projs is None:
            for i, ((d, v), c) in enumerate(zip(outs, batch.columns)):
                if i in passthrough:
                    cols.append(c)
                else:
                    cols.append(Column(c.type, d, v, c.dictionary))
        else:
            for j, ((d, v), t, ce) in enumerate(
                    zip(outs, self.output_types, projs)):
                if j in passthrough:
                    cols.append(batch.columns[passthrough[j]])
                else:
                    cols.append(Column(t, d, v, ce.dictionary))
        self._observe_encoded(batch, needed)
        self._pending = ColumnBatch(self.output_names, cols, live)
        return True

    def observe_encoded(self, batch: ColumnBatch, needed) -> None:
        """Count ``batch``'s encodings as a program reading the ``needed``
        channels (None = all) meets them."""
        if encoded_exec() and any(c.encoding in ("RLE", "LAZY")
                                  for c in batch.columns):
            self._observe_encoded(
                batch, needed if needed is not None
                else range(batch.num_columns))

    def _observe_encoded(self, batch: ColumnBatch, needed) -> None:
        es = self.encoding_stats
        saved = 0
        n_rle = n_dict = 0
        for i, c in enumerate(batch.columns):
            enc = c.encoding
            if enc == "RLE":
                n_rle += 1
            elif enc == "DICT":
                n_dict += 1
            if enc in ("RLE", "LAZY") and i not in needed:
                saved += c.flat_nbytes - c.nbytes
        if n_rle:
            es.rle_batches += 1
        if n_dict:
            es.dict_batches += 1
        if saved > 0:
            es.bytes_saved += saved

    def program_inputs(self, batch: ColumnBatch):
        """(program, input structure, cols, live) for a consumer that traces
        this operator's body inside its own program: channels the body does
        not read are ``(None, None)`` -- never expanded, staged or copied.
        ``batch`` is bucket-shaped (pad_to_bucket)."""
        prog = self._compile(batch)
        needed = prog.needed
        cols = []
        for i, c in enumerate(batch.columns):
            if needed is not None and i not in needed:
                cols.append((None, None))
            elif c.encoding == "RLE":
                cols.append((K.rle_fill(c.rle_value, len(c)), c.valid))
            else:  # touching .data materializes LAZY exactly once
                cols.append((c.data, c.valid))
        sig = (batch.num_rows, tuple((d is None, v is None) for d, v in cols),
               batch.live is None)
        return prog, sig, cols, batch.live

    def add_input(self, batch: ColumnBatch) -> None:
        if batch.num_columns == 0:
            self._pending = batch.rename(self.output_names)
            return
        if self.consumer is not None and self.consumer.absorbs(batch):
            # the aggregation behind filters, projects and folds this batch
            # in one program of its own: hand the input through as it came
            self._pending = batch
            return
        if (encoded_exec()
                and any(c.encoding in ("RLE", "LAZY") for c in batch.columns)
                and self._add_input_encoded(batch)):
            return
        batch = pad_to_bucket(batch)
        prog = self._compile(batch)
        projs = prog.projs
        outs, live, err_code = prog.run(_to_cols(batch), batch.live)
        if err_code is not None:
            # device scalar; checked in ONE batched fetch at pipeline end
            # (run_pipelines -> ops.expr.check_error_scalars)
            self.pending_errors.append(err_code)
        if projs is None:
            cols = [Column(c.type, d, v, c.dictionary)
                    for (d, v), c in zip(outs, batch.columns)]
        else:
            cols = [Column(t, d, v, ce.dictionary)
                    for (d, v), t, ce in zip(outs, self.output_types, projs)]
        self._pending = ColumnBatch(self.output_names, cols, live)

    def get_output(self) -> Optional[ColumnBatch]:
        b, self._pending = self._pending, None
        return b

    def is_finished(self) -> bool:
        return self.input_done and self._pending is None


def plan_lazy_scan(pipeline: Sequence[Operator]) -> None:
    """Late-materialization planning: when a scan feeds straight into a
    filtering FilterProject, every channel the filter only passes through
    stages as LAZY — the mask computes from predicate columns alone, and a
    selective filter's payload bytes never cross to the device (the
    LazyBlock contract of ScanFilterAndProjectOperator).  Called once per
    pipeline at local-planning time; a no-op unless TRINO_TPU_ENCODED_EXEC
    allows encoded execution."""
    if not encoded_exec() or len(pipeline) < 2:
        return
    scan, fp = pipeline[0], pipeline[1]
    if not (isinstance(scan, ScanOperator)
            and isinstance(fp, FilterProjectOperator)
            and fp.predicate is not None):
        return
    needed = set(referenced_inputs(fp.predicate))
    if fp.projections is not None:
        for e in fp.projections:
            if not isinstance(e, InputRef):
                needed |= referenced_inputs(e)
    scan.lazy_channels = frozenset(
        i for i in range(len(scan.columns)) if i not in needed)


def plan_aggregation_feed(pipeline: Sequence[Operator]) -> None:
    """A FilterProjectOperator directly in front of an aggregation that may
    stream (not FINAL, no DISTINCT) is made known to it as its ``feed``: on
    the first batch the aggregation decides whether it streams, and if it
    does its one program per group of batches also evaluates the feed's
    predicate and projections (HashAggregationOperator.absorbs); if not,
    both run as they did.  Called once per pipeline at local-planning time,
    after intra-task parallelism has put its exchanges in: with one between
    the two operators nothing is adjacent and the aggregation, if it
    streams, folds with launches of its own."""
    for fp, agg in zip(pipeline, pipeline[1:]):
        if (isinstance(fp, FilterProjectOperator)
                and isinstance(agg, HashAggregationOperator)
                and agg.step != "FINAL"
                and not any(a.distinct for a in agg.aggs)):
            fp.consumer, agg.feed = agg, fp


class RenameOperator(Operator):
    def __init__(self, names: Sequence[str]):
        self.names = list(names)
        self._pending = None

    def needs_input(self) -> bool:
        return self._pending is None and super().needs_input()

    def add_input(self, batch: ColumnBatch) -> None:
        self._pending = batch.rename(self.names)

    def get_output(self):
        b, self._pending = self._pending, None
        return b

    def is_finished(self) -> bool:
        return self.input_done and self._pending is None


# ---------------------------------------------------------------------------
# memory-accounted input buffering (the revocable-memory participants)


_COMPACT_FACTOR = 4  # compact when live rows < lanes/4
_COMPACT_MIN_LANES = 1 << 16  # below this a count sync costs more than it saves
# the two costs _masked_reads_dead_lanes_cheaper weighs, as read on a v5e by
# tools/compaction_crossover.py (PERF.md section 6, PR 27; wall seconds)
_COUNT_SYNC_S = 1.0e-3                # the blocking exec.compact-count fetch
_COMPACT_S_PER_LANE = 3.6e-9          # kernels.compact: stable sort + gathers
_MASKED_S_PER_LANE_REDUCTION = 150e-12       # small_agg: one more state column
_MASKED_S_PER_LANE_GROUP_REDUCTION = 2.7e-12  # ... in one more group
# batches the streamed aggregation folds in one launch (kernels.
# fold_group_body), as read on a v5e (PERF.md section 6, PR 37).  Alone
# (tools/fold_group_probe.py) one launch of Q6's / Q1's body costs the host
# 119 / 228 us, one that folds 4 batches 142 / 279 us, 8 batches 217 / 339
# us (0.23 / 0.19 of eight singles), 16 batches 273 / 494 us.  In the
# engine, one process and one load, Q6 over SF10 takes 44.6 ms a query at
# 8 and 47.3 ms at 4 (+6.2 %), three streams and Q1 are level; the cold
# compile of Q6's program is 82 s at 8, 37 s at 4, 11 s at 1.  16 would hold
# twice the batches for 10 us a batch
_FOLD_GROUP = 8


def _compaction_candidate(batch: ColumnBatch) -> bool:
    """A device batch with a live mask and enough lanes that a count sync
    can pay for itself.  Static: reads no device value."""
    live = batch.live
    return (live is not None and not isinstance(live, np.ndarray)
            and batch.num_rows >= _COMPACT_MIN_LANES)


def _masked_reads_dead_lanes_cheaper(lanes: int, space: int,
                                     reductions: int) -> bool:
    """Should the masked aggregation take ``lanes`` uncompacted lanes?  It
    costs O(lanes x group space x reductions) (kernels.small_agg vmaps its
    masked reductions over the group space) and ignores dead lanes by
    construction; compacting first costs a count sync, one stable sort of
    the lanes and a gather per column, and can shrink the reduction's input
    at best to nothing.  So: whichever of the two is cheaper over all the
    lanes, from the measured unit costs above.  On a v5e the masked path
    wins up to about 1000 groups x reductions at 2^20 to 2^22 lanes, and
    further on both sides of that (the sync dominates below, the sort grows
    faster than its lanes above).  Static and host-side: no sync, no look
    at the data."""
    masked_s = lanes * reductions * (
        _MASKED_S_PER_LANE_REDUCTION
        + space * _MASKED_S_PER_LANE_GROUP_REDUCTION)
    return masked_s <= _COUNT_SYNC_S + lanes * _COMPACT_S_PER_LANE


def _maybe_compact_device(batch: ColumnBatch) -> ColumnBatch:
    """Shrink a sparsely-live device batch to bucket(live) lanes, for a
    caller whose next step is super-linear in lanes: SortOperator's device
    sort, and the aggregation's sorting reductions (group_ids_codes,
    group_ids_auto, the global DISTINCT route through grouped_reduce) --
    HashAggregationOperator._compute asks AFTER it has chosen its path.  A
    selective filter or join keeps its batch's fat static shape (the
    sync-free contract of join_exec.run_unique); paying ONE live-count sync
    here stops those dead lanes from riding through the sort.  A reduction
    that is O(lanes) does not call this: the masked aggregation reads dead
    lanes for less than the sort that would remove them (see
    _masked_reads_dead_lanes_cheaper).  Host batches, batches under
    _COMPACT_MIN_LANES and dense batches come back as they are (``is``)."""
    if not _compaction_candidate(batch):
        return batch
    count = int(SG.fetch(jnp.sum(jnp.asarray(batch.live)),
                         "exec.compact-count"))
    if count * _COMPACT_FACTOR <= batch.num_rows:
        return K.compact_device_batch(batch, count)
    return batch


class BufferedInputMixin:
    """Blocking operators accumulate ``self._batches``; with a
    TaskMemoryContext attached (exec/revoking.py) the buffered DEVICE bytes
    are reserved as revocable HBM and evicted to host RAM on revoke."""

    _mem = None  # TaskMemoryContext, set via attach_memory

    def attach_memory(self, mem) -> None:
        self._mem = mem
        if mem is not None:
            mem.register(self)

    def account_memory(self) -> None:
        if self._mem is not None:
            from .revoking import batch_device_residual

            self._mem.update(self, batch_device_residual(self))
            self._maybe_spill_to_disk()

    def revoke_memory(self) -> int:
        from .revoking import batch_device_nbytes

        freed = 0
        batches = getattr(self, "_batches", [])
        for i, b in enumerate(batches):
            d = batch_device_nbytes(b)
            if d:
                batches[i] = b.to_host()
                freed += d
        if freed:
            self.spill_count = getattr(self, "spill_count", 0) + 1
        return freed

    def release_memory(self) -> None:
        """Drop the input buffer + its reservation after finish consumes it
        (a lingering reservation would trigger pointless spills of dead
        buffers in later operators sharing the pool)."""
        self._batches = []
        if self._mem is not None:
            self._mem.update(self, 0)

    def _maybe_spill_to_disk(self) -> None:
        """Third tier: buffered batches exceeding the session's disk
        threshold go to a serde spill file (exec/spill.py).  Device-staged
        batches count toward the threshold too — a disk limit is an explicit
        request for bounded buffering, so they evict to host on the way down
        (otherwise async-ingest scans would route every batch around this
        tier as device arrays)."""
        limit = getattr(self._mem, "spill_to_disk_bytes", 0) if self._mem else 0
        if not limit:
            return
        batches = getattr(self, "_batches", None)
        if not batches or not batches[0].columns:
            return
        if sum(b.nbytes for b in batches) <= limit:
            return
        from .spill import Spiller

        if getattr(self, "_spiller", None) is None:
            self._spiller = Spiller()
        for b in batches:
            if not isinstance(b.columns[0].data, np.ndarray):
                b = b.to_host()
            self._spiller.spill(b)
        self._batches = []

    def buffered_batches(self) -> list:
        """The operator's full input: disk-spilled pages restored first,
        then the in-memory tail (finish-time accessor)."""
        spiller = getattr(self, "_spiller", None)
        if spiller is not None:
            restored = list(spiller.read_back())
            spiller.close()
            self._spiller = None
            self._batches = restored + self._batches
        return self._batches


# ---------------------------------------------------------------------------
# aggregation


def _round_half_up_div_int(s: np.ndarray, c: np.ndarray) -> np.ndarray:
    q = (2 * np.abs(s) + c) // (2 * c)
    return np.where(s < 0, -q, q)


def _concat_device(batches: Sequence[ColumnBatch]) -> ColumnBatch:
    """Concatenate (possibly masked) batches on device, padded to the
    total's power-of-two bucket.  Dead/padding rows are carried in ``live``
    so the result has a cache-friendly static shape — this is how blocking
    operators materialize input without leaving the device.  A single
    batch that carries a ``live`` mask is bucket-shaped as far as anyone
    downstream cares (pad_to_bucket's rule: a masked aggregation's page has
    its static group space for lanes) and comes back as it is, instead of
    three eager operations a column to pad six rows to eight."""
    if (len(batches) == 1 and batches[0].live is not None
            and not isinstance(batches[0].live, np.ndarray)
            and all(c.encoding != "RLE" for c in batches[0].columns)):
        return batches[0]
    names = batches[0].names
    total = sum(b.num_rows for b in batches)
    cap = K.bucket(total)
    pad = cap - total
    any_live = pad > 0 or any(b.live is not None for b in batches)
    out_cols = []
    for i in range(len(names)):
        cs = [b.columns[i] for b in batches]
        if cs[0].type.is_dictionary_encoded:
            cs = unify_dictionaries(cs)
        # RLE runs expand with a device-side fill: one scalar crosses the
        # host boundary instead of the whole run
        parts = [K.rle_fill(c.rle_value, len(c)) if c.encoding == "RLE"
                 else jnp.asarray(c.data) for c in cs]
        if pad:
            parts.append(jnp.zeros(pad, parts[0].dtype))
        data = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
        valid = None
        if any(c.valid is not None for c in cs):
            vparts = [
                jnp.asarray(c.valid) if c.valid is not None
                else jnp.ones(len(c), jnp.bool_)
                for c in cs
            ]
            if pad:
                vparts.append(jnp.zeros(pad, jnp.bool_))
            valid = jnp.concatenate(vparts) if len(vparts) > 1 else vparts[0]
        out_cols.append(Column(cs[0].type, data, valid, cs[0].dictionary))
    live = None
    if any_live:
        lparts = [
            jnp.asarray(b.live) if b.live is not None
            else jnp.ones(b.num_rows, jnp.bool_)
            for b in batches
        ]
        if pad:
            lparts.append(jnp.zeros(pad, jnp.bool_))
        live = jnp.concatenate(lparts) if len(lparts) > 1 else lparts[0]
    return ColumnBatch(names, out_cols, live)


def _agg_spec(a: AggCall, inp: ColumnBatch, out_t: Type):
    """kernel (fn, data, valid, dtype, distinct) for one AggCall."""
    if a.fn == "count" and a.arg < 0:
        return ("count_star", None, None, np.int64, False)
    col = inp.columns[a.arg]
    data, valid = col.data, col.valid
    if a.fn == "avg":
        # decomposes into sum+count; dtype promotes to f64 on device
        return ("avg", data, valid, np.float64, a.distinct)
    if a.fn in STAT_AGGS:
        # decomposes into (sum, sum-of-squares, count) states
        return (a.fn, data, valid, np.float64, a.distinct)
    if a.fn == "sum":
        if out_t == DOUBLE:
            dtype = np.float64
        elif out_t.name == "real":
            dtype = np.float32  # f32 lanes: the pallas fast path
        else:
            dtype = np.int64
        return ("sum", data, valid, dtype, a.distinct)
    if a.fn == "count":
        return ("count", data, valid, np.int64, a.distinct)
    return (a.fn, data, valid, data.dtype, a.distinct)


def _is_long_decimal_agg(a: AggCall, inp: ColumnBatch) -> bool:
    """An exact wide-decimal SUM/AVG: int64 limb-plane sums on device (an
    eager gather through the dictionary's limb tables), bignum
    recombination per group on the host."""
    if a.fn not in ("sum", "avg") or a.arg < 0:
        return False
    t = inp.columns[a.arg].type
    return isinstance(t, DecimalType) and t.precision > 18


def _aggregation_specs(aggs: Sequence[AggCall], step: str, inp: ColumnBatch,
                       use_masked: bool) -> tuple:
    """(specs, (avg_slots, stat_slots, ld_slots)): the reduction kernels'
    aggs list for ``aggs`` over ``inp``, and which AggCall expanded into
    which run of state columns.  Reads only what the columns hold (arrays,
    tracers inside a program, or shapes when only the layout is asked
    for), so the buffered path, the per-batch fold and the fused
    filter/project program build the same specs from the same code."""
    live = inp.live

    def fold_live(valid):
        """Dead rows never contribute: fold ``live`` into validity.
        The masked path folds live via the fused group id instead."""
        if use_masked or live is None:
            return valid
        if valid is None:
            return live
        return jnp.asarray(valid) & jnp.asarray(live)

    # kernel specs; avg expands to (sum, count) state pairs, the variance
    # family to (sum, sumsq, count) triples.  FINAL merges partial
    # states: count -> sum of counts, others same fn.
    specs, avg_slots, stat_slots, ld_slots = [], {}, {}, {}

    for idx, a in enumerate(aggs):
        if _is_long_decimal_agg(a, inp):
            ld_col = inp.columns[a.arg]
            # exact wide-decimal SUM/AVG: int64 limb-plane sums on
            # device, bignum recombination per group on host
            # (kernels.decimal_limb_tables; Int128Math.java's role)
            if a.distinct:
                raise NotImplementedError(
                    "DISTINCT long-decimal aggregate")
            ld_slots[idx] = a.fn
            valid_f = fold_live(ld_col.valid)
            codes_dev = jnp.asarray(ld_col.data)
            for tab in K.decimal_limb_tables(ld_col.dictionary):
                specs.append(("sum", jnp.asarray(tab)[codes_dev],
                              valid_f, np.int64, False))
            specs.append(("count", ld_col.data, valid_f, np.int64,
                          False))
            continue
        if step == "FINAL":
            c = inp.columns[a.arg]
            data, valid = c.data, fold_live(c.valid)
            if a.fn == "avg":
                avg_slots[idx] = len(specs)
                c2 = inp.columns[a.arg + 1]
                specs.append(("sum", data, valid, np.float64, False))
                specs.append(("sum", c2.data, fold_live(None), np.int64, False))
            elif a.fn in STAT_AGGS:
                stat_slots[idx] = len(specs)
                c2 = inp.columns[a.arg + 1]
                c3 = inp.columns[a.arg + 2]
                specs.append(("sum", data, valid, np.float64, False))
                specs.append(("sum", c2.data, fold_live(c2.valid), np.float64, False))
                specs.append(("sum", c3.data, fold_live(None), np.int64, False))
            elif a.fn in ("count", "count_star"):
                specs.append(("sum", data, fold_live(None), np.int64, False))
            else:
                specs.append((a.fn, data, valid, data.dtype, False))
            continue
        s = _agg_spec(a, inp, a.type)
        s = (s[0], s[1], fold_live(s[2]), s[3], s[4])
        if s[0] == "avg":
            avg_slots[idx] = len(specs)
            scale = 0
            if a.arg >= 0 and isinstance(inp.columns[a.arg].type, DecimalType):
                scale = inp.columns[a.arg].type.scale
            # scale-free f64 sum state; the division happens INSIDE the
            # compiled reduce program (pre tag), never as an eager
            # full-size op with a launch (and a compile) of its own
            specs.append(("sum", s[1], s[2], np.float64, s[4],
                          ("scale", scale)))
            specs.append(("count", s[1], s[2], np.int64, s[4]))
        elif s[0] in STAT_AGGS:
            stat_slots[idx] = len(specs)
            specs.append(("sum", s[1], s[2], np.float64, False))
            specs.append(("sum", s[1], s[2], np.float64, False,
                          ("square",)))
            specs.append(("count", s[1], s[2], np.int64, False))
        else:
            specs.append(s)
    return specs, (avg_slots, stat_slots, ld_slots)


def _masked_operands(group_keys: Sequence[int], aggs: Sequence[AggCall],
                     step: str, inp: ColumnBatch) -> tuple:
    """(kernels.MaskedOperands, slots) of the masked reduction of ``inp``."""
    specs, slots = _aggregation_specs(aggs, step, inp, True)
    return K.small_agg_operands([inp.columns[i] for i in group_keys],
                                inp.live, specs), slots


class _ColumnMeta(NamedTuple):
    """What finalization still reads of an input column once its values
    are folded away."""

    type: Type
    dictionary: Optional[np.ndarray]


class _MaskedStream:
    """The running state of a streaming masked aggregation, for one
    generation of input dictionaries: the device-resident ``state``
    (kernels.small_agg_state_layout's columns stacked by dtype, then the
    feed's error scalar when its body can raise), and the statics that
    finalization and the next batch's check read.  ``program`` folds a
    group of up to _FOLD_GROUP batches into the state in one launch;
    ``key`` is what the batches of one group share -- the program and the
    structure of its inputs: with ``fused`` the (program, input structure)
    of the filter/project whose body ``program`` traces in front of each
    fold.  ``pending`` holds the group's slots until it is launched,
    ``held_bytes`` what they occupy on the device meanwhile, beside the
    state's own ``state_bytes``."""

    __slots__ = ("ops", "layout", "shapes", "columns", "slots", "compaction",
                 "lanes", "state", "key", "fused", "program", "pending",
                 "held_bytes", "state_bytes")

    def __init__(self, ops, columns, slots, compaction, state, key, fused,
                 program):
        self.ops = ops._replace(flat=[])  # the layout, not the batch
        self.layout = ops.layout
        self.shapes = ops.state_shapes
        self.columns = columns
        self.slots = slots
        self.compaction = compaction
        self.lanes = 0
        self.state = state
        self.key = key
        self.fused = fused
        self.program = program
        self.pending: list = []
        self.held_bytes = 0
        self.state_bytes = sum(rows * lanes * np.dtype(d).itemsize
                               for (rows, lanes), d in self.shapes)

    @property
    def has_error(self) -> bool:
        return len(self.state) > len(self.shapes)

    def continues(self, ops, columns, channels, has_error: bool) -> bool:
        """Can a batch with these operands and columns merge into this
        state?  Same group space and state columns, and the very same
        dictionary objects behind every channel finalization decodes."""
        mine = self.ops
        return (mine.sizes == ops.sizes and mine.has_valid == ops.has_valid
                and self.layout == ops.layout
                and self.has_error == has_error
                and all(self.columns[i].dictionary is columns[i].dictionary
                        for i in channels))

    def attrs(self, batches: int) -> dict:
        """``batches``: how many the call that reports this launched (a
        group; 0 where its batch was only held)."""
        return {"path": "masked", "compaction": self.compaction,
                "lanes": self.lanes, "mode": "streamed",
                "fused": self.fused, "batches": batches}


def _filter_project_fold_body(prog: _FilterProjectProgram, group_keys: tuple,
                              aggs: tuple, step: str):
    """``(state, cols, live) -> state``: one batch through the
    filter/project's own body (predicate, projections, error lanes), the
    aggregation's specs built over its outputs the way the unfused operator
    builds them over a batch, and the fold into the state; the body's error
    scalar rides in the state as a running max."""

    def fold_one(state, cols, live):
        outs, live, err_code = prog.body(cols, live)
        ops, _ = _masked_operands(group_keys, aggs, step,
                                  prog.output_batch(outs, live))
        n = len(ops.state_shapes)
        merged = K.small_agg_fold_body(*ops.static)(state[:n], *ops.flat)
        if err_code is not None:
            merged += (jnp.maximum(state[n], err_code),)
        return merged

    return fold_one


def _filter_project_agg_program(prog: _FilterProjectProgram,
                                group_keys: tuple, aggs: tuple, step: str):
    """ONE program per group of up to _FOLD_GROUP batches for
    filter/project + masked aggregation: ``(state, n, ((cols, live), ...))
    -> state`` (kernels.fold_group_body) runs _filter_project_fold_body for
    each slot in arrival order into the donated state.  Kept with the
    filter/project's compiled program, so it lives and dies with the
    dictionaries it was built for."""
    donate = K.donate_ok()
    key = (group_keys, aggs, step, _FOLD_GROUP, donate)
    with FilterProjectOperator._PROGRAM_CACHE_LOCK:
        hit = prog.consumers.get(key)
        if hit is None:
            hit = prog.consumers[key] = program(
                "operators.filter_project_agg",
                K.fold_group_body(
                    _filter_project_fold_body(prog, group_keys, aggs, step),
                    _FOLD_GROUP),
                donate_argnums=(0,) if donate else ())
    return hit


class HashAggregationOperator(BufferedInputMixin, Operator):
    """Grouped aggregation: accumulate batches, then sort-based segment
    reduction (replaces operator/HashAggregationOperator.java:53 +
    FlatHash.java:42 with the kernels in exec/kernels.py).

    Which way input is consumed is decided once, from static properties of
    the first batch (``_streams``):

    - **streamed** -- a PARTIAL or SINGLE aggregation that will take the
      masked path (small dictionary-code group space or a global aggregate,
      no DISTINCT, no long decimal, under the masked/compaction crossover)
      buffers nothing: each batch is reduced and merged into a small
      device-resident state by ONE program (kernels.small_agg_fold), the
      state goes through finalization once at finish and one page leaves.
      With a FilterProjectOperator directly in front (``feed``, set by
      plan_aggregation_feed) that one program also evaluates the predicate
      and the projections (operators.filter_project_agg), and the filter
      operator hands its input through untouched.  A launch folds a GROUP
      of up to _FOLD_GROUP batches: a batch's operands are held (``_hold``)
      until the group is full or something ends it -- a batch of another
      structure or under other dictionaries, finish_input, close -- and are
      folded in arrival order.
    - **buffered** -- everything else (the paths that sort, DISTINCT, long
      decimals, RLE folds, every FINAL step) accumulates ``_batches`` and
      reduces them at finish, as before.  PARTIAL steps with group keys
      flush early: when the buffered input exceeds ``flush_rows``, the
      accumulated batches are pre-aggregated and emitted immediately
      (states are mergeable by FINAL), so a worker's memory stays bounded
      by the flush window rather than its whole input — the
      InMemoryHashAggregationBuilder partial-flush behavior
      (operator/aggregation/builder/InMemoryHashAggregationBuilder.java)."""

    FLUSH_ROWS = 1 << 20
    SPILL_PARTITIONS = 16

    def __init__(self, group_keys: Sequence[int], aggs: Sequence[AggCall],
                 output_names: Sequence[str], output_types: Sequence[Type],
                 step: str = "SINGLE"):
        self.group_keys = list(group_keys)
        self.aggs = list(aggs)
        self.output_names = list(output_names)
        self.output_types = list(output_types)
        self.step = step
        self._batches: list[ColumnBatch] = []
        self._buffered_rows = 0
        self._flushed: list[ColumnBatch] = []
        self._result: Optional[ColumnBatch] = None
        self._emitted = False
        self.encoding_stats = EncodingStats()
        # streaming masked aggregation: None until the first batch decides
        self._streamed: Optional[bool] = None
        self._stream: Optional[_MaskedStream] = None
        # SINGLE step: partial-state pages of streams a dictionary change
        # sealed, merged by a FINAL-step twin at finish
        self._sealed: list[ColumnBatch] = []
        # the FilterProjectOperator in front (plan_aggregation_feed), whose
        # body the per-batch program traces when this aggregation streams
        self.feed: Optional[FilterProjectOperator] = None
        self._fused: Optional[bool] = None
        self._launched = 0     # batches launched since the last report
        self.pending_errors: list = []
        # input channels whose dictionaries finalization decodes through
        self._channels = sorted(set(self.group_keys).union(
            a.arg for a in self.aggs if a.arg >= 0))
        # partitioned state spill (SpillableHashAggregationBuilder.java):
        # one spill file per hash partition of pre-aggregated states
        self._state_spillers: Optional[list] = None
        self._spill_layout = None  # (p_names, p_types, final_calls)

    # -- partitioned state spill -------------------------------------------
    def _spill_eligible(self) -> bool:
        return (self.step in ("SINGLE", "FINAL")
                and not any(a.distinct for a in self.aggs))

    def _maybe_spill_to_disk(self) -> None:
        """Disk tier override: instead of dumping RAW input pages, pre-
        aggregate the buffer into mergeable partial states, hash-partition
        them by group key, and append each partition to its own spill file
        (reference: operator/aggregation/builder/
        SpillableHashAggregationBuilder.java — spill states, merge on
        unspill, memory bounded by the largest partition)."""
        limit = getattr(self._mem, "spill_to_disk_bytes", 0) if self._mem else 0
        if not limit or not self._batches:
            return
        if not self._spill_eligible():
            super()._maybe_spill_to_disk()  # raw-page fallback (distinct)
            return
        host_bytes = sum(
            b.nbytes for b in self._batches
            if b.columns and isinstance(b.columns[0].data, np.ndarray))
        device_rows = sum(
            b.num_rows for b in self._batches
            if b.columns and not isinstance(b.columns[0].data, np.ndarray))
        if host_bytes <= limit and device_rows * 8 <= limit:
            return
        self._spill_states()

    def _ensure_spill_layout(self):
        if self._spill_layout is not None:
            return self._spill_layout
        from ..planner.add_exchanges import partial_agg_layout

        nk = len(self.group_keys)
        if self.step == "FINAL":
            # input IS already a state layout: spill rows pass through and
            # merge with the operator's own call list
            self._spill_layout = (None, None, list(self.aggs))
            return self._spill_layout
        layouts = partial_agg_layout(self.aggs, None)
        p_names = [f"k{i}" for i in range(nk)]
        p_types: list = [None] * nk  # filled from input at first spill
        f_calls = []
        ch = nk
        for a, states in zip(self.aggs, layouts):
            f_calls.append(AggCall(a.fn, ch, a.type, False))
            for j, (fn, t) in enumerate(states):
                p_names.append(f"s{ch}_{j}")
                p_types.append(t)
            ch += len(states)
        self._spill_layout = (p_names, p_types, f_calls)
        return self._spill_layout

    def _partial_state_batch(self) -> ColumnBatch:
        """Pre-aggregate the current buffer into mergeable partial states
        (or pass state rows through under FINAL)."""
        if self.step == "FINAL":
            return ColumnBatch.concat(self._batches)
        p_names, p_types, _ = self._ensure_spill_layout()
        tmp = self._partial_twin(self._batches[0].columns)
        tmp._batches = self._batches
        return tmp._compute().compact()

    def _partial_twin(self, columns) -> "HashAggregationOperator":
        """This aggregation as a PARTIAL step over the same input: what it
        emits are mergeable states (key types from ``columns``)."""
        p_names, p_types, _ = self._ensure_spill_layout()
        nk = len(self.group_keys)
        key_types = [columns[c].type for c in self.group_keys]
        return HashAggregationOperator(
            self.group_keys, self.aggs, p_names, key_types + p_types[nk:],
            "PARTIAL")

    def _merge_states(self, pages: list[ColumnBatch]) -> ColumnBatch:
        """Partial-state pages -> this aggregation's output, by a FINAL-step
        twin (dictionaries unify in its _concat_device)."""
        _, _, f_calls = self._ensure_spill_layout()
        merger = HashAggregationOperator(
            list(range(len(self.group_keys))), f_calls, self.output_names,
            self.output_types, "FINAL")
        merger._batches = pages
        return merger._compute()

    def _spill_states(self) -> None:
        from .spill import Spiller
        from ..execution.task import _partition_key_tuple

        state = self._partial_state_batch()
        if self._state_spillers is None:
            self._state_spillers = [Spiller()
                                    for _ in range(self.SPILL_PARTITIONS)]
        nk = len(self.group_keys)
        if nk:
            keys = [_partition_key_tuple(state.columns[c])
                    for c in range(nk)]
            parts = K.partition_assignments(keys, self.SPILL_PARTITIONS)
        else:
            parts = np.zeros(state.num_rows, np.int32)
        for p in range(self.SPILL_PARTITIONS):
            sub = state.filter(parts == p)
            if sub.num_rows:
                self._state_spillers[p].spill(sub)
        self._batches = []
        self._buffered_rows = 0
        self.spill_count = getattr(self, "spill_count", 0) + 1
        if self._mem is not None:
            self._mem.update(self, 0)

    def _merge_spilled(self) -> list[ColumnBatch]:
        """Per-partition merge of spilled states (merge-on-unspill): memory
        is bounded by one partition's states at a time."""
        outs: list[ColumnBatch] = []
        for sp in self._state_spillers:
            batches = list(sp.read_back())
            sp.close()
            if not batches:
                continue
            out = self._merge_states(batches)
            if out.num_rows:
                outs.append(out)
        self._state_spillers = None
        return outs

    def _can_flush(self) -> bool:
        # PARTIAL states merge downstream; SINGLE/FINAL must see all input.
        # (distinct never reaches PARTIAL — AddExchanges routes it SINGLE.)
        return self.step == "PARTIAL" and bool(self.group_keys)

    def add_input(self, batch: ColumnBatch) -> None:
        if self._fused:
            self._fold_fused(batch)  # the feed's input, handed through
            return
        if not batch.num_rows:
            return
        if self._streamed is None:
            self._streamed = self._streams(batch)
        if self._streamed:
            self._fold(batch)
            return
        self._batches.append(batch)
        self._buffered_rows += batch.num_rows
        if self._can_flush() and self._buffered_rows >= self.FLUSH_ROWS:
            out = self._compute()
            if out.num_rows:
                self._flushed.append(out)
            self._batches = []
            self._buffered_rows = 0
        self.account_memory()

    # -- streaming masked aggregation --------------------------------------
    def _streams(self, inp: ColumnBatch) -> bool:
        """Fold each batch into a running state instead of buffering it?
        Decided once, from what is static in the first batch (``inp`` may
        hold shapes only): the step, the masked path's own conditions, no
        long-decimal aggregate (its limb gather is eager, its finalize on
        the host), no RLE fold, and groups x reductions under the crossover
        past which the masked path counts and compacts first."""
        if self.step == "FINAL":
            # a few tiny pages: one launch at finish beats one per page
            return False
        use_masked, space, _ = self._choose_path(inp)
        if not use_masked or any(_is_long_decimal_agg(a, inp)
                                 for a in self.aggs):
            return False
        if encoded_exec() and self._rle_eligible([inp]):
            return False
        return _masked_reads_dead_lanes_cheaper(
            inp.num_rows, space, self._reduction_count(inp))

    def absorbs(self, batch: ColumnBatch) -> bool:
        """Asked by the filter/project in front (``feed``) for each of its
        input batches: will this aggregation run the feed's body inside its
        own per-batch program?  Decided on the first batch with rows, from
        the shapes the body would produce (nothing runs)."""
        if self._fused is None and self._streamed is None and batch.num_rows:
            prog, sig, cols, live = self.feed.program_inputs(
                pad_to_bucket(batch))
            self._fused = self._streams(prog.view(sig, cols, live)[0])
            if self._fused:
                self._streamed = True
                self.encoding_stats.agg_fused_feed += 1
        return bool(self._fused)

    def _open_stream(self, inp: ColumnBatch, ops, slots, key, program,
                     has_error=False, fused=False) -> _MaskedStream:
        """The stream the batch ``inp`` (arrays or shapes) folds into: the
        running one's state if it can take the batch, else a fresh one --
        after sealing the old, whose dictionaries this batch left behind.
        Either way the batches held for the old stream's program are folded
        first, so the state sees every batch in arrival order."""
        self._launch_group()
        old = self._stream
        columns = [_ColumnMeta(c.type, c.dictionary) for c in inp.columns]
        fresh = old is None or not old.continues(ops, columns,
                                                 self._channels, has_error)
        if fresh and old is not None:
            self._seal()
        st = self._stream = _MaskedStream(
            ops, columns, slots,
            "skipped" if _compaction_candidate(inp) else "none",
            K.small_agg_zero_state(ops, has_error) if fresh else old.state,
            key, fused, program)
        if fresh and self._mem is not None:
            self._mem.update(self, st.state_bytes)
        return st

    def _hold(self, st: _MaskedStream, slot: tuple, lanes: int,
              resident: bool) -> None:
        """One batch's operands into the stream's pending group; the group
        is launched when it is full (else by whatever ends it:
        _open_stream, _close_stream).  What the group newly keeps alive on
        the device is reserved while it is held: a staged batch or another
        operator's output is, a ``resident`` batch (ColumnBatch.resident: a
        pinned table's own storage) is there anyway and costs nothing
        here."""
        st.lanes = lanes
        st.pending.append(slot)
        self.encoding_stats.agg_streamed_batches += 1
        if len(st.pending) == _FOLD_GROUP:
            self._launch_group()
        elif self._mem is not None and not resident:
            held = sum(map(device_nbytes, {
                id(a): a for a in jax.tree_util.tree_leaves(slot)}.values()))
            if held:
                st.held_bytes += held
                self._mem.update(self, st.state_bytes + st.held_bytes)
        self.trace_attrs = st.attrs(self._launched)
        self._launched = 0

    def _launch_group(self) -> None:
        """The pending group into the state, in arrival order: ONE launch
        (no-op when nothing is held).  Slots the group does not fill are
        fed its first batch again; the program reads ``n`` of them."""
        st = self._stream
        if st is None or not st.pending:
            return
        group, st.pending = st.pending, []
        n = len(group)
        group.extend(group[:1] * (_FOLD_GROUP - n))
        st.state = st.program(st.state, K.slot_count(n), tuple(group))
        self.encoding_stats.agg_fold_launches += 1
        self._launched += n
        if st.held_bytes:
            st.held_bytes = 0
            self._mem.update(self, st.state_bytes)

    def _fold(self, batch: ColumnBatch) -> None:
        """One batch towards the running state, by the aggregation's own
        program (kernels.small_agg_fold)."""
        batch = pad_to_bucket(batch)
        cols, live, resident = batch.columns, batch.live, batch.resident
        for i in self._channels:
            c = cols[i]
            if c.encoding == "RLE":  # one scalar crosses, not the run
                if cols is batch.columns:
                    cols = list(cols)
                cols[i] = Column(c.type, K.rle_fill(c.rle_value, len(c)),
                                 c.valid, c.dictionary)
        if live is None and not self._channels:
            live = np.ones(batch.num_rows, np.bool_)  # count(*): the lanes
        if cols is not batch.columns or live is not batch.live:
            batch = ColumnBatch(batch.names, cols, live)
        ops, slots = _masked_operands(self.group_keys, self.aggs, self.step,
                                      batch)
        key = (ops.static, batch.num_rows)
        st = self._stream
        if (st is None or st.key != key
                or not st.continues(ops, cols, self._channels, False)):
            st = self._open_stream(
                batch, ops, slots, key,
                K.small_agg_fold_program(ops, _FOLD_GROUP))
        self._hold(st, tuple(ops.flat), batch.num_rows, resident)

    def _fold_fused(self, batch: ColumnBatch) -> None:
        """One of the feed's input batches towards predicate, projections
        and the fold into the running state (operators.
        filter_project_agg)."""
        if not batch.num_rows:
            return
        fp = self.feed
        batch = pad_to_bucket(batch)
        prog, sig, cols, live = fp.program_inputs(batch)
        fp.observe_encoded(batch, prog.needed)
        st = self._stream
        if st is None or st.key != (prog, sig):
            view, has_error = prog.view(sig, cols, live)
            ops, slots = _masked_operands(self.group_keys, self.aggs,
                                          self.step, view)
            st = self._open_stream(
                view, ops, slots, (prog, sig),
                _filter_project_agg_program(
                    prog, tuple(self.group_keys), tuple(self.aggs),
                    self.step), has_error, True)
        self._hold(st, (cols, live), batch.num_rows, batch.resident)

    def _emit_stream(self, st: _MaskedStream) -> ColumnBatch:
        """A stream's state through finalization: one page."""
        reduced, presence, keys_out, num_groups = K.small_agg_state_out(
            st.state[:len(st.shapes)], st.ops)
        if self.group_keys:
            self.encoding_stats.code_group_batches += 1
        self.encoding_stats.count_aggregation("masked", st.compaction)
        return self._emit(reduced, presence, keys_out, num_groups,
                          st.columns, st.slots)

    def _close_stream(self, as_states: bool) -> ColumnBatch:
        """Take the running stream out: what it still holds folded, its
        error scalar to ``pending_errors``, its state out as a page -- of
        this step's own output, or (``as_states``, SINGLE) of mergeable
        partial states."""
        self._launch_group()
        st, self._stream = self._stream, None
        if st.has_error:
            self.pending_errors.append(st.state[-1])
        self.trace_attrs = st.attrs(self._launched)
        if not as_states:
            return self._emit_stream(st)
        twin = self._partial_twin(st.columns)
        twin.encoding_stats = self.encoding_stats
        return twin._emit_stream(st)

    def _seal(self) -> None:
        """A batch arrived under other dictionaries than the state's: the
        state leaves as a partial-state page (PARTIAL: downstream merges it
        like any other; SINGLE: kept for the FINAL-step merge at finish)."""
        self.encoding_stats.agg_state_seals += 1
        if self.step == "PARTIAL":
            page = self._close_stream(False)
            if page.num_rows:
                self._flushed.append(page)
        else:
            self._sealed.append(self._close_stream(True))

    def _finish_stream(self) -> None:
        if self._sealed:
            self._sealed.append(self._close_stream(True))
            self._result = self._merge_states(self._sealed)
            self._sealed = []
        else:
            self._result = self._close_stream(False)
        self.release_memory()

    def close(self) -> None:
        """Downstream needs no more output: what a stream still holds is
        let go unfolded, and its reservation with it."""
        super().close()
        st = self._stream
        if st is not None and st.pending:
            st.pending = []
            if st.held_bytes:
                st.held_bytes = 0
                self._mem.update(self, st.state_bytes)

    def _reduction_count(self, inp: ColumnBatch) -> int:
        """State columns _compute will ask the reduction kernel for, from
        the aggregate list and the input's types alone: avg is (sum,
        count), the variance family (sum, sum of squares, count), a
        long-decimal sum or avg six limb sums and a count."""
        total = 0
        for a in self.aggs:
            if _is_long_decimal_agg(a, inp):
                total += 7
            elif a.fn == "avg":
                total += 2
            elif a.fn in STAT_AGGS:
                total += 3
            else:
                total += 1
        return total

    def finish_input(self) -> None:
        super().finish_input()
        if self._stream is not None:
            self._finish_stream()
            return
        if self._state_spillers is not None:
            # flush the tail, then merge partition-by-partition (memory
            # bounded by the largest partition, not the whole input)
            if self._batches:
                self._spill_states()
            self._flushed.extend(self._merge_spilled())
            self._result = None
            self._emitted = True
            self.release_memory()
            return
        if self._flushed and not self._batches:
            self._result = None  # everything already emitted via flushes
            self._emitted = True
            self.release_memory()
            return
        self._result = self._compute()
        self.release_memory()

    def _empty_result(self, nk: int) -> ColumnBatch:
        if nk:  # grouped agg over empty input -> empty result
            cols = [Column(t, np.empty(0, t.storage_dtype))
                    for t in self.output_types]
            return ColumnBatch(self.output_names, cols)
        # global agg over empty input -> one row of defaults
        cols = []
        i = 0
        for a in self.aggs:
            if self.step == "PARTIAL" and a.fn == "avg":
                cols.append(Column(self.output_types[i],
                                   np.zeros(1, np.float64), np.zeros(1, bool)))
                cols.append(Column(self.output_types[i + 1], np.zeros(1, np.int64)))
                i += 2
                continue
            if self.step == "PARTIAL" and a.fn in STAT_AGGS:
                cols.append(Column(self.output_types[i],
                                   np.zeros(1, np.float64), np.zeros(1, bool)))
                cols.append(Column(self.output_types[i + 1], np.zeros(1, np.float64)))
                cols.append(Column(self.output_types[i + 2], np.zeros(1, np.int64)))
                i += 3
                continue
            t = self.output_types[i]
            i += 1
            if a.fn == "count":
                cols.append(Column(t, np.zeros(1, np.int64)))
            else:
                cols.append(Column(t, np.zeros(1, t.storage_dtype),
                                   np.zeros(1, bool)))
        return ColumnBatch(self.output_names, cols)

    # RLE-aware aggregation: fns computable arithmetically from one stored
    # value + a live/valid count, without ever expanding the run
    _RLE_AGG_FNS = frozenset(("sum", "count", "count_star", "min", "max"))

    def _rle_eligible(self, batches: Sequence[ColumnBatch]) -> bool:
        """Can ``batches`` fold as RLE runs (_rle_fast_path)?  A global,
        non-DISTINCT sum/count/min/max whose every argument column is an
        RLE run with host masks."""
        if (len(self.group_keys) or self.step == "FINAL"
                or not self.aggs
                or any(a.distinct for a in self.aggs)
                or not all(a.fn in self._RLE_AGG_FNS for a in self.aggs)):
            return False
        for b in batches:
            if b.live is not None and not isinstance(b.live, np.ndarray):
                return False  # counting a device mask would cost a sync
            for a in self.aggs:
                if a.arg < 0:
                    continue
                c = b.columns[a.arg]
                if c.encoding != "RLE":
                    return False
                if c.valid is not None and not isinstance(c.valid, np.ndarray):
                    return False
                if c.dictionary is not None and a.fn == "sum":
                    return False  # dict codes don't sum; min/max do (sorted)
        first = batches[0]
        for a in self.aggs:  # min/max on codes needs ONE shared dictionary
            if a.arg < 0 or first.columns[a.arg].dictionary is None:
                continue
            from ..spi.batch import _same_dictionary

            d0 = first.columns[a.arg].dictionary
            if not all(_same_dictionary(b.columns[a.arg].dictionary, d0)
                       for b in batches[1:]):
                return False
        return True

    def _rle_fast_path(self) -> ColumnBatch:
        """Global aggregation over RLE inputs (``_rle_eligible``): SUM(x)
        over a constant run is ``value * run_count`` (the
        RunLengthEncodedBlock shortcut of the reference's aggregation
        operators) — pure host arithmetic over per-batch scalars, no
        concat, no device dispatch, no expansion."""

        def counted(b: ColumnBatch, c: Column) -> int:
            """Rows of this run that are live AND valid."""
            if c.valid is None and b.live is None:
                return len(c)
            m = np.ones(len(c), np.bool_)
            if c.valid is not None:
                m &= np.asarray(c.valid)
            if b.live is not None:
                m &= np.asarray(b.live)
            return int(m.sum())

        out_cols: list[Column] = []
        rows_folded = 0
        for a, t in zip(self.aggs, self.output_types):
            if a.fn == "count_star":
                total = sum(b.live_count for b in self._batches)
                out_cols.append(Column(t, np.array([total], np.int64)))
                continue
            pairs = [(b.columns[a.arg], counted(b, b.columns[a.arg]))
                     for b in self._batches]
            rows_folded += sum(cnt for _, cnt in pairs)
            if a.fn == "count":
                total = sum(cnt for _, cnt in pairs)
                out_cols.append(Column(t, np.array([total], np.int64)))
                continue
            alive = [(c, cnt) for c, cnt in pairs if cnt > 0]
            if not alive:  # sum/min/max over all-NULL input -> NULL
                out_cols.append(Column(t, np.zeros(1, t.storage_dtype),
                                       np.zeros(1, np.bool_),
                                       pairs[0][0].dictionary))
                continue
            dict_ = alive[0][0].dictionary
            if a.fn == "sum":
                dtype = np.dtype(t.storage_dtype)
                if dtype.kind == "f":
                    v = float(sum(float(c.rle_value) * cnt
                                  for c, cnt in alive))
                else:  # exact: python bignum until the final cast
                    v = sum(int(c.rle_value) * cnt for c, cnt in alive)
                out_cols.append(Column(t, np.array([v], dtype)))
            else:
                pick = min if a.fn == "min" else max
                v = pick(c.rle_value for c, _ in alive)
                out_cols.append(Column(
                    t, np.array([v], np.asarray(v).dtype), None, dict_))
        self.encoding_stats.rle_agg_rows += rows_folded
        self.encoding_stats.rle_batches += len(self._batches)
        return ColumnBatch(self.output_names, out_cols)

    def _choose_path(self, inp: ColumnBatch) -> tuple:
        """(use_masked, group space, path name) from what is static in
        ``inp``: key dictionaries, validity, DISTINCT, the aggregate list.
        Masked-reduction fast path: small dictionary-code group space and
        no DISTINCT -> no sort, no gather, no num_groups sync
        (kernels.small_agg); compaction changes none of what is read here,
        so the path is chosen BEFORE anyone pays for one."""
        nk = len(self.group_keys)
        key_cols = [inp.columns[i] for i in self.group_keys]
        space = K.small_codes_group_space(key_cols) if nk else 1
        use_masked = (space is not None and space <= K.MASKED_AGG_LIMIT
                      and not any(a.distinct for a in self.aggs)
                      and (nk or inp.live is not None
                           or any(a.arg >= 0 for a in self.aggs)))
        path = ("masked" if use_masked
                else "codes-sort" if nk and space is not None else "sort")
        return use_masked, space, path

    def _compute(self) -> ColumnBatch:
        """The buffered way: reduce everything in ``_batches`` at once."""
        nk = len(self.group_keys)
        if not self.buffered_batches():
            return self._empty_result(nk)
        if encoded_exec() and self._rle_eligible(self._batches):
            return self._rle_fast_path()
        inp = _concat_device(self._batches)
        key_cols = [inp.columns[i] for i in self.group_keys]
        use_masked, space, path = self._choose_path(inp)
        if nk and space is not None:
            # every key is a small dictionary code: the whole group-by runs
            # in code space (one post-agg gather decodes group keys)
            self.encoding_stats.code_group_batches += 1
        # compaction is a cost of the paths that sort: argsort, lexsort /
        # hash, grouped_reduce.  The masked path is O(lanes) and ignores
        # dead lanes by construction; it takes them as they come -- no
        # count sync, no sort, no gather -- unless groups x reductions is
        # so large that sorting them away first is the cheaper way round
        skip = (use_masked and _compaction_candidate(inp)
                and _masked_reads_dead_lanes_cheaper(
                    inp.num_rows, space, self._reduction_count(inp)))
        compaction = "skipped" if skip else "none"
        if not skip:
            padded, inp = inp, _maybe_compact_device(inp)
            if inp is not padded:
                compaction = "compacted"
                key_cols = [inp.columns[i] for i in self.group_keys]
        self.encoding_stats.count_aggregation(path, compaction)
        self.trace_attrs = {"path": path, "compaction": compaction,
                            "lanes": inp.num_rows, "mode": "buffered",
                            "fused": False}
        live = inp.live  # None = all rows real
        n = inp.num_rows

        presence = None
        if nk and not use_masked:
            keys = [(c.data, c.valid) for c in key_cols]
            if space is not None:
                # all keys are small dictionary codes: static group space,
                # single-key sort, zero host syncs; empty groups ride out
                # as dead rows in the output's live mask
                perm, gid, num_groups, presence, keys_out = (
                    K.group_ids_codes(key_cols, live))
            else:
                # TRINO_TPU_HASH_IMPL routes between the lexsort path and
                # the Pallas open-addressing path; both honor the same
                # (perm, gid, num_groups) contract, so everything downstream
                # (grouped_reduce, group_keys_out) is implementation-blind
                perm, gid, num_groups = K.group_ids_auto(keys, live)
                if num_groups == 0:  # every row dead (fully filtered input)
                    return self._empty_result(nk)
                keys_out = K.group_keys_out(perm, gid, num_groups, keys)
        elif not nk and not use_masked:
            keys_out = []
            perm = jnp.arange(n)
            gid = jnp.zeros(n, jnp.int32)
            num_groups = 1

        specs, slots = _aggregation_specs(self.aggs, self.step, inp,
                                          use_masked)
        if use_masked:
            reduced, presence, keys_out, num_groups = (
                K.small_grouped_aggregate(key_cols, live, specs))
        else:
            reduced = (K.grouped_reduce(perm, gid, num_groups, specs)
                       if specs else [])
        return self._emit(reduced, presence, keys_out, num_groups,
                          inp.columns, slots)

    def _emit(self, reduced, presence, keys_out, num_groups: int, columns,
              slots: tuple) -> ColumnBatch:
        """The output page from the reduced per-group arrays.  ``columns``
        gives the input's types and dictionaries (their values are not
        read): a batch's own columns, or a stream's _ColumnMeta."""
        nk = len(self.group_keys)
        avg_slots, stat_slots, ld_slots = slots
        key_cols = [columns[i] for i in self.group_keys]

        # finalization (avg division, variance combine, output casts) runs
        # as ONE compiled program over the tiny per-group arrays: zero eager
        # dispatches, and the output columns STAY ON DEVICE so the
        # collective exchange path can feed them straight into all_to_all
        plan: list[tuple] = []
        arrays: list = []
        col_types: list = []
        col_dicts: list = []
        order: list = []  # ("prog",) | ("host", Column) in output position

        def emit(entry, srcs, t, dict_=None):
            plan.append(entry)
            arrays.extend(srcs)
            col_types.append(t)
            col_dicts.append(dict_)
            order.append(("prog", None))

        def emit_host(column):
            order.append(("host", column))

        for (d, v), c in zip(keys_out, key_cols):
            emit(("copy", None, v is not None),
                 [d] + ([v] if v is not None else []), c.type, c.dictionary)
        ri = 0
        ncols = nk
        for idx, a in enumerate(self.aggs):
            t = self.output_types[ncols]
            if idx in ld_slots:
                # exact wide-decimal finalize: pull the tiny per-group limb
                # sums (+count) in ONE round trip, recombine with bignums
                fnname = ld_slots[idx]
                limbs = reduced[ri:ri + 6]
                cnt_res = reduced[ri + 7 - 1]
                ri += 7
                pulled = SG.fetch(
                    [d for d, _ in limbs] + [cnt_res[0]],
                    "agg.decimal-limbs")
                counts = np.asarray(pulled[-1])
                src_scale = 0
                if a.arg >= 0:
                    src_t = columns[a.arg].type
                    if isinstance(src_t, DecimalType):
                        src_scale = src_t.scale
                import decimal as _dec

                values: list = []
                for g in range(num_groups):
                    if int(counts[g]) == 0:
                        values.append(None)
                        continue
                    total = K.combine_limb_sums(
                        [p[g] for p in pulled[:6]])
                    if fnname == "avg":
                        with _dec.localcontext() as ctx:
                            ctx.prec = 80
                            q = (_dec.Decimal(total).scaleb(-src_scale)
                                 / int(counts[g]))
                            values.append(int(q.scaleb(t.scale).quantize(
                                0, rounding=_dec.ROUND_HALF_UP)))
                    else:
                        from ..spi.batch import rescale_scaled_int

                        values.append(rescale_scaled_int(
                            total, src_scale, t.scale))
                from ..spi.batch import encode_sorted_objects

                codes, valid, dict_ = encode_sorted_objects(values, 0)
                emit_host(Column(t, codes, valid, dict_))
                ncols += 1
                continue
            if idx in avg_slots:
                s_data, s_valid = reduced[ri]
                c_data, _ = reduced[ri + 1]
                ri += 2
                if self.step == "PARTIAL":
                    # emit mergeable states: scale-free sum + count
                    emit(("copy", "<f8", s_valid is not None),
                         [s_data] + ([s_valid] if s_valid is not None else []),
                         t)
                    emit(("count", None, False), [c_data],
                         self.output_types[ncols + 1])
                    ncols += 2
                    continue
                emit(("avg_final", np.dtype(t.storage_dtype).str,
                      s_valid is not None),
                     [s_data] + ([s_valid] if s_valid is not None else [])
                     + [c_data], t)
                ncols += 1
                continue
            if idx in stat_slots:
                # variance family: combine (sum, sumsq, count) states
                # (reference: operator/aggregation/VarianceAccumulator)
                s_data, s_valid = reduced[ri]
                q_data, _ = reduced[ri + 1]
                c_data, _ = reduced[ri + 2]
                ri += 3
                if self.step == "PARTIAL":
                    emit(("copy", "<f8", s_valid is not None),
                         [s_data] + ([s_valid] if s_valid is not None else []),
                         t)
                    emit(("copy", "<f8", False), [q_data],
                         self.output_types[ncols + 1])
                    emit(("count", None, False), [c_data],
                         self.output_types[ncols + 2])
                    ncols += 3
                    continue
                emit(("stat_final", a.fn, np.dtype(t.storage_dtype).str,
                      s_valid is not None),
                     [s_data] + ([s_valid] if s_valid is not None else [])
                     + [q_data, c_data], t)
                ncols += 1
                continue
            d, v = reduced[ri]
            ri += 1
            if a.fn not in ("sum", "min", "max", "any_value"):
                v = None  # count never NULL
            dict_ = None
            if self.step != "FINAL" and a.arg >= 0:
                dict_ = columns[a.arg].dictionary
            elif self.step == "FINAL" and a.fn in ("min", "max", "any_value"):
                dict_ = columns[a.arg].dictionary
            emit(("copy", np.dtype(t.storage_dtype).str, v is not None),
                 [d] + ([v] if v is not None else []), t, dict_)
            ncols += 1
        outs = iter(K.finalize_groups(plan, arrays)) if plan else iter([])
        prog_meta = iter(zip(col_types, col_dicts))
        out_cols = []
        for kind, payload in order:
            if kind == "host":
                out_cols.append(payload)
            else:
                d, v = next(outs)
                t, dc = next(prog_meta)
                out_cols.append(Column(t, d, v, dc))
        return ColumnBatch(self.output_names, out_cols, presence)

    def get_output(self) -> Optional[ColumnBatch]:
        if self._flushed:
            return self._flushed.pop(0)
        if self.input_done and self._result is not None and not self._emitted:
            self._emitted = True
            return self._result
        return None

    def is_finished(self) -> bool:
        return (self.input_done and self._emitted
                and not self._flushed) or self._closed


# ---------------------------------------------------------------------------
# joins


class JoinBridge:
    """Build-side handoff between pipelines (the LookupSourceFactory
    equivalent — operator/join/PartitionedLookupSourceFactory.java)."""

    def __init__(self):
        self.table = None  # join_exec.DeviceJoinTable
        self.batch: Optional[ColumnBatch] = None
        self.key_dicts: list[Optional[np.ndarray]] = []
        self._dense: Optional[ColumnBatch] = None

    @property
    def ready(self) -> bool:
        return self.table is not None

    def dense(self) -> ColumnBatch:
        """Host-compacted build batch (cross-join / epilogue paths only)."""
        if self._dense is None:
            self._dense = self.batch.compact()
        return self._dense


def _probe_key_remap(col: Column, build_dict: Optional[np.ndarray]):
    """Host-side remap table translating probe dictionary codes into the
    build side's code space (-1 = value absent, can never match), or None
    when the code spaces already agree.  The table is tiny (dictionary-
    sized); the per-row gather happens inside the probe program on device."""
    pdict = col.dictionary
    if pdict is None and build_dict is None:
        return None
    if build_dict is None or len(build_dict) == 0:
        return np.full(max(len(pdict), 1), -1, np.int32)
    if pdict is None or pdict is build_dict:
        return None
    if pdict.shape == build_dict.shape and (pdict == build_dict).all():
        return None
    pos = np.searchsorted(build_dict, pdict)
    clipped = np.clip(pos, 0, len(build_dict) - 1)
    ok = build_dict[clipped] == pdict
    return np.where(ok, clipped, -1).astype(np.int32)


class JoinBuildSink(BufferedInputMixin, Operator):
    """Accumulates the build side, then builds the sorted-hash join table
    (operator/join/HashBuilderOperator.java:57)."""

    def __init__(self, bridge: JoinBridge, key_channels: Sequence[int],
                 types: Sequence[Type], names: Sequence[str],
                 dynamic_filter_holders=None):
        self.bridge = bridge
        self.key_channels = list(key_channels)
        self.types = list(types)
        self.names = list(names)
        # one holder per key channel (or None) — filled at finish so the
        # probe-side scan can prune (exec/dynamic_filter.py)
        self.dynamic_filter_holders = list(dynamic_filter_holders or [])
        self._batches: list[ColumnBatch] = []

    def add_input(self, batch: ColumnBatch) -> None:
        if batch.num_rows:
            self._batches.append(batch)
            self.account_memory()

    def finish_input(self) -> None:
        from . import join_exec as JX

        super().finish_input()
        if self.buffered_batches():
            # no live-compaction here: the build program sorts dead rows
            # last natively, and a count sync would cost more than the
            # slightly fatter argsort it saves
            batch = _concat_device(self._batches)
        else:
            batch = ColumnBatch(self.names, [
                Column(t, np.empty(0, t.storage_dtype)) for t in self.types])
        live = batch.live
        keys = []
        for ch in self.key_channels:
            c = batch.columns[ch]
            keys.append((c.data, c.valid))
        for k, holder in zip(range(len(self.key_channels)),
                             self.dynamic_filter_holders):
            if holder is not None:
                c = batch.columns[self.key_channels[k]]
                holder.fill_device(c.data, c.valid, live, c.dictionary)
        self.bridge.batch = batch
        self.bridge.key_dicts = [
            batch.columns[ch].dictionary for ch in self.key_channels]
        self.bridge.table = JX.build_table(
            keys, live=live, num_rows=batch.num_rows)
        self.release_memory()

    def is_finished(self) -> bool:
        return self.input_done


def _null_columns(batch: ColumnBatch, n: int) -> list[Column]:
    return [
        Column(c.type, np.zeros(n, c.data.dtype),
               np.zeros(n, bool), c.dictionary)
        for c in batch.columns
    ]


# residual predicates over join candidate pairs: jitted once per
# (expression, types, dictionaries) and evaluated on bucket-padded pair
# batches so repeated probes reuse a handful of compiled programs (the same
# cross-execution caching strategy as FilterProjectOperator._PROGRAM_CACHE)
_RESIDUAL_CACHE: dict = {}
_RESIDUAL_LOCK = threading.Lock()


def _residual_program(expr: RowExpression, types, dicts):
    key = (expr, tuple(types),
           tuple(id(d) if d is not None else None for d in dicts))
    with _RESIDUAL_LOCK:
        hit = _RESIDUAL_CACHE.get(key)
        if hit is not None:
            return hit[0]
    ce = compile_expression(expr, list(types), list(dicts))

    def run(cols):
        data, valid = ce(cols)
        return data if valid is None else (data & valid)

    prog = program("operators.join_residual", run)
    with _RESIDUAL_LOCK:
        _RESIDUAL_CACHE.setdefault(key, (prog, list(dicts)))
        if len(_RESIDUAL_CACHE) > 1024:
            _RESIDUAL_CACHE.pop(next(iter(_RESIDUAL_CACHE)))
    return prog


def _pad_indices(idx: np.ndarray) -> tuple[np.ndarray, int]:
    """Pad an index vector to its power-of-two bucket (clamped repeats of
    slot 0 keep gathers in-range; callers mask the tail with ``live``)."""
    n = len(idx)
    cap = K.bucket(n)
    if cap == n:
        return idx, n
    return np.concatenate([idx, np.zeros(cap - n, idx.dtype)]), n


def _nested_loop_pairs(probe: ColumnBatch, build: ColumnBatch,
                       residual: Optional[RowExpression]):
    """Host nested-loop pair expansion shared by the cross join and the
    keyless semi-join (operator/join/NestedLoopJoinOperator.java:45): the
    full (probe x build) product, filtered by the jitted residual program.
    Returns post-residual (pi, bi) index arrays."""
    nb = build.num_rows
    pi = np.repeat(np.arange(probe.num_rows, dtype=np.int64), nb)
    bi = np.tile(np.arange(nb, dtype=np.int64), probe.num_rows)
    if residual is None or not len(pi):
        return pi, bi
    pidx, n = _pad_indices(pi)
    bidx, _ = _pad_indices(bi)
    cols = ([c.take(pidx) for c in probe.columns]
            + [c.take(bidx) for c in build.columns])
    pair = ColumnBatch([f"c{i}" for i in range(len(cols))], cols)
    prog = _residual_program(
        residual, [c.type for c in pair.columns],
        [c.dictionary for c in pair.columns])
    mask = np.asarray(
        SG.fetch(prog(_to_cols(pair)), "join.nested-loop-residual"))[:n]
    return pi[mask], bi[mask]


class LookupJoinOperator(Operator):
    """Probe side of the equi-join (operator/join/LookupJoinOperator.java:37).
    Streams probe batches against the finished build table.  The whole probe
    runs on device (exec/join_exec.py): candidate ranges, expansion, exact
    verification, residual, and output gathers are jitted programs; the only
    blocking host interaction per batch is the one scalar candidate-count
    sync that picks the expansion bucket.  RIGHT/FULL track matched build
    positions across all probe batches and emit the unmatched build rows
    null-extended after input finishes (the OUTER lookup-source variants of
    the reference)."""

    def __init__(self, bridge: JoinBridge, left_keys: Sequence[int],
                 join_type: str, residual: Optional[RowExpression],
                 output_names: Sequence[str], output_types: Sequence[Type]):
        self.bridge = bridge
        self.left_keys = list(left_keys)
        self.join_type = join_type
        self.residual = residual
        self.output_names = list(output_names)
        self.output_types = list(output_types)
        from collections import deque

        from . import join_exec as JX

        self._pending: "deque[ColumnBatch]" = deque()
        self._build_matched = None  # device bool per build slot (RIGHT/FULL)
        self._emitted_unmatched = False
        # probe-side dictionaries observed, for null-extended unmatched rows
        self._probe_dicts: Optional[list] = None
        # sync-free expand state: capacity planners fed by async-landed
        # totals, and the deferred-commit queue for estimated-cap batches
        # whose overflow flag is still in flight (exec/join_exec.py)
        # keyed planners: the same join shape re-planned in a later
        # execution starts from the prior run's observed totals
        ident = (join_type, tuple(self.left_keys),
                 tuple(self.output_names), residual is not None)
        self._planner = JX.ExpandPlanner(key=("pairs",) + ident)
        self._uplanner = JX.ExpandPlanner(key=("unique",) + ident)
        self._inflight = JX.OverflowQueue()
        self.pending_errors: list = []  # deferred cardinality violations
        self.encoding_stats = EncodingStats()

    def needs_input(self) -> bool:
        return self.bridge.ready and not self._pending and super().needs_input()

    def _add_cross_input(self, probe: ColumnBatch) -> None:
        """Nested-loop fallback (operator/join/NestedLoopJoinOperator.java:45)
        — host-side; inherently quadratic and only planned for tiny inputs."""
        probe = probe.compact()
        build = self.bridge.dense()
        nb = build.num_rows
        self._dense_build = build  # epilogue indexes match this batch
        if self.join_type == "SINGLE" and nb > 1 and probe.num_rows:
            raise TrinoError(SUBQUERY_MULTIPLE_ROWS,
                             "scalar subquery returned multiple rows")
        pi, bi = _nested_loop_pairs(probe, build, self.residual)
        if self.join_type in ("RIGHT", "FULL"):
            if self._build_matched is None:
                self._build_matched = np.zeros(nb, bool)
            if len(bi):
                m = np.asarray(self._build_matched)
                m[bi] = True
                self._build_matched = m
            self._probe_dicts = [c.dictionary for c in probe.columns]
        if self.join_type in ("LEFT", "SINGLE", "FULL"):
            matched = np.zeros(probe.num_rows, bool)
            matched[pi] = True
            un = np.nonzero(~matched)[0]
            if len(un):
                left_cols = [c.take(un) for c in probe.columns]
                right_cols = _null_columns(build, len(un))
                self._pending.append(ColumnBatch(
                    self.output_names, left_cols + right_cols))
        if len(pi):
            cols = ([c.take(pi) for c in probe.columns]
                    + [c.take(bi) for c in build.columns])
            self._pending.append(ColumnBatch(self.output_names, cols))

    def add_input(self, probe: ColumnBatch) -> None:
        from . import join_exec as JX

        if not self.left_keys:  # cross join (nested-loop fallback)
            self._add_cross_input(probe)
            return
        # the probe programs are compiled per lane count: a dense page (a
        # remote producer's, a host operator's) takes its bucket first, so
        # they see powers of two from every source
        probe = pad_to_bucket(probe)
        build = self.bridge.batch
        table = self.bridge.table
        keys = [(JX.key_input(probe.columns[ch]), probe.columns[ch].valid)
                for ch in self.left_keys]
        remaps = [
            _probe_key_remap(probe.columns[ch], self.bridge.key_dicts[k])
            for k, ch in enumerate(self.left_keys)
        ]
        if any(r is not None for r in remaps):
            # dictionary keys probe as remapped int32 CODES, never values
            self.encoding_stats.code_join_batches += 1
        # uniqueness comes from the per-BUILD scalar fetch (amortized over
        # every probe batch); a duplicate-key build takes the pair path
        if table.num_rows and table.unique:
            if self.join_type in ("INNER", "RIGHT"):
                # FK->PK probe: ranges+verify, then a width-adaptive gather
                # (exec/join_exec.py r5 design notes)
                self._add_inner_unique(probe, table, build, keys, remaps)
            else:
                # LEFT/SINGLE/FULL keep every probe row: the wide one-program
                # path with zero per-batch syncs
                self._add_unique_input(probe, table, build, keys, remaps)
            return
        self._add_pairs(probe, table, build, keys, remaps)

    def _null_extended(self, probe: ColumnBatch, build: ColumnBatch,
                       un_live) -> ColumnBatch:
        """Unmatched probe rows ride the ORIGINAL probe batch shape with a
        live mask (no gather, no compaction): probe columns pass through,
        build columns are all-NULL."""
        n = probe.num_rows
        right_cols = [
            Column(c.type, jnp.zeros(n, c.type.storage_dtype),
                   jnp.zeros(n, jnp.bool_), c.dictionary)
            for c in build.columns
        ]
        return ColumnBatch(
            self.output_names, list(probe.columns) + right_cols, un_live)

    def _add_pairs(self, probe: ColumnBatch, table, build,
                   keys, remaps) -> None:
        """General (non-unique build) probe: candidate ranges + padded
        expand.  The expand bucket comes from build-side statistics
        (ExpandPlanner) and overflow checks are deferred to async flag
        polls, so the steady state never blocks on the candidate total."""
        from . import join_exec as JX

        need_matched = self.join_type in ("LEFT", "SINGLE", "FULL")
        if self.join_type in ("RIGHT", "FULL"):
            self._probe_dicts = [c.dictionary for c in probe.columns]
        if probe.num_rows == 0:
            return
        if table.num_rows == 0:  # empty build: no pairs, all probes unmatched
            if need_matched:
                self._pending.append(
                    self._null_extended(probe, build, probe.live))
            return
        probe_cols = [(c.data, c.valid) for c in probe.columns]
        build_cols = [(c.data, c.valid) for c in build.columns]
        pair_types = ([c.type for c in probe.columns]
                      + [c.type for c in build.columns])
        pair_dicts = ([c.dictionary for c in probe.columns]
                      + [c.dictionary for c in build.columns])

        def commit(res) -> None:
            pairs, ok, matched, maxc, build_id, _overflow = res
            if self.join_type == "SINGLE":
                # scalar subquery: >1 match per probe row is a cardinality
                # violation (EnforceSingleRowNode semantics).  The check
                # stays a device scalar on the deferred error channel —
                # raised by check_error_scalars at pipeline end, costing
                # zero extra syncs here (ops/expr.py)
                from ..ops.expr import SUBQUERY_MULTIPLE_ROWS

                self.pending_errors.append(jnp.where(
                    jnp.asarray(maxc) > 1, SUBQUERY_MULTIPLE_ROWS, 0))
            if self.join_type in ("RIGHT", "FULL"):
                if self._build_matched is None:
                    self._build_matched = jnp.zeros(build.num_rows, jnp.bool_)
                self._build_matched = jnp.asarray(
                    self._build_matched).at[build_id].max(ok)
            out_cols = [Column(t, d, v, dc) for (d, v), t, dc in
                        zip(pairs, pair_types, pair_dicts)]
            self._pending.append(
                ColumnBatch(self.output_names, out_cols, ok))
            if need_matched:
                un_live = ~matched if probe.live is None else (
                    jnp.asarray(probe.live) & ~matched)
                self._pending.append(
                    self._null_extended(probe, build, un_live))

        with SG.hot_region():
            lo, counts, total_a = JX.probe_ranges_device(
                table, keys, remaps, probe.live)
            cap, provable = self._planner.plan(probe.num_rows, table.max_run)
            self._planner.observe_async(total_a)
            res = JX.run_pairs(
                table, lo, counts, total_a, keys, remaps, probe_cols,
                build_cols, pair_types, pair_dicts, self.residual,
                need_matched, cap=cap, donate=provable)
            if provable:  # cap >= any possible total: no overflow, no retry
                commit(res)
                return

            def retry():
                # rare: the estimated bucket truncated candidates — re-run
                # at the exact total (landed long ago by drain time)
                total_h = max(int(total_a.get()), 1)
                self._planner.observe(total_h)
                return JX.run_pairs(
                    table, lo, counts, total_h, keys, remaps, probe_cols,
                    build_cols, pair_types, pair_dicts, self.residual,
                    need_matched)

            self._inflight.push(
                SG.async_scalar(res[5], "join.expand-overflow"),
                res, retry, commit)
            self._inflight.drain()

    def _add_inner_unique(self, probe: ColumnBatch, table, build,
                          keys, remaps) -> None:
        """INNER/RIGHT probe against a unique build: ranges and the match
        count stay on device, and the gather's width is sized from a match
        count in every batch — an earlier batch's, the seed an earlier
        execution of the same plan shape left, or (neither known) this
        batch's own, fetched once."""
        from . import join_exec as JX

        if probe.num_rows == 0:
            return
        ok_live, bid, cnt_a = JX.run_unique_ranges_device(
            table, keys, remaps, probe.live)
        if self.join_type == "RIGHT":
            self._probe_dicts = [c.dictionary for c in probe.columns]
        probe_cols = [(c.data, c.valid) for c in probe.columns]
        build_cols = [(c.data, c.valid) for c in build.columns]
        pair_types = ([c.type for c in probe.columns]
                      + [c.type for c in build.columns])
        pair_dicts = ([c.dictionary for c in probe.columns]
                      + [c.dictionary for c in build.columns])
        need_bm = self.join_type == "RIGHT"

        def commit(res) -> None:
            p_out, b_out, live, bm, _overflow = res
            if need_bm and bm is not None:
                if self._build_matched is None:
                    self._build_matched = bm
                else:
                    self._build_matched = jnp.asarray(
                        self._build_matched) | bm
            if p_out is None:  # wide: probe columns pass through untouched
                left_cols = list(probe.columns)
            else:
                left_cols = [Column(c.type, d, v, c.dictionary)
                             for c, (d, v) in zip(probe.columns, p_out)]
            right_cols = [Column(c.type, d, v, c.dictionary)
                          for c, (d, v) in zip(build.columns, b_out)]
            self._pending.append(ColumnBatch(
                self.output_names, left_cols + right_cols, live))

        lanes = probe.num_rows
        est, origin = self._uplanner.estimate()
        if est is None:
            # a statement's first execution in this process: no earlier
            # batch, no seed.  One scalar fetch of program A's count (0.4 ms
            # on a v5e) instead of going wide for want of an estimate; it
            # is this operator's one deliberate wait, outside the hot
            # region (exec/join_exec.py, the unique-build design note)
            est, origin = int(cnt_a.get()), "count"
            self._uplanner.observe(est)
        else:
            self._uplanner.observe_async(cnt_a)
        cap = JX.plan_unique_cap(
            lanes, est * JX.EST_HEADROOM, JX.gather_words(probe_cols),
            JX.gather_words(build_cols))
        SG.count_unique_gather(compact=cap is not None,
                               seeded=origin == "seed")
        # cap == lanes: the wide leg
        self.trace_attrs = {"cap": lanes if cap is None else cap,
                            "lanes": lanes, "estimate": origin}
        with SG.hot_region():
            res = JX.run_unique_gather(
                table, ok_live, bid, cap, probe_cols, build_cols,
                pair_types, pair_dicts, self.residual, need_bm)
            if cap is None or origin == "count":
                # the wide path cannot overflow, nor can a cap sized from
                # the batch's own count
                commit(res)
                return

            def retry():
                # compact bucket overflowed: re-run wide (provably safe)
                SG.count_unique_gather(compact=False, seeded=False)
                return JX.run_unique_gather(
                    table, ok_live, bid, None, probe_cols, build_cols,
                    pair_types, pair_dicts, self.residual, need_bm)

            self._inflight.push(
                SG.async_scalar(res[4], "join.unique-overflow"),
                res, retry, commit)
            self._inflight.drain()

    def finish_input(self) -> None:
        super().finish_input()
        # a probe of ONE batch makes no later estimate() call that would
        # fold its count in: land it here (never a wait), so the next
        # execution of this plan shape finds its seed.  The pair path's
        # planner is left as it was (its cap never goes under the probe's
        # width, and no cell runs it: ROADMAP queue 1)
        self._uplanner.land()

    def _add_unique_input(self, probe: ColumnBatch, table, build,
                          keys, remaps) -> None:
        """Unique-build probe: ONE program, probe columns pass through, the
        output rides the probe batch's shape with the match mask as live.
        Covers every join type: LEFT/SINGLE/FULL keep unmatched probe rows
        as NULL-extended lanes of the same batch (no second batch), SINGLE
        can never violate cardinality (<=1 match by construction)."""
        from . import join_exec as JX

        need_res_cols = self.residual is not None
        probe_cols = ([(c.data, c.valid) for c in probe.columns]
                      if need_res_cols else [])
        build_cols = [(c.data, c.valid) for c in build.columns]
        if need_res_cols:
            pair_types = ([c.type for c in probe.columns]
                          + [c.type for c in build.columns])
            pair_dicts = ([c.dictionary for c in probe.columns]
                          + [c.dictionary for c in build.columns])
        else:
            pair_types, pair_dicts = [], []
        need_bm = self.join_type in ("RIGHT", "FULL")
        with SG.hot_region():
            bgather, ok_live, build_matched, _ = JX.run_unique(
                table, keys, remaps, probe_cols, build_cols,
                pair_types, pair_dicts, self.residual, need_bm,
                live=probe.live)
        if need_bm:
            self._probe_dicts = [c.dictionary for c in probe.columns]
            if self._build_matched is None:
                self._build_matched = build_matched
            else:
                self._build_matched = (
                    jnp.asarray(self._build_matched) | build_matched)
        right_cols = [Column(c.type, d, v, c.dictionary)
                      for c, (d, v) in zip(build.columns, bgather)]
        if self.join_type in ("INNER", "RIGHT"):
            out_live = ok_live
        else:  # LEFT / SINGLE / FULL: unmatched probe rows stay live,
            # their build columns already read NULL (valid folds the mask)
            out_live = probe.live
        self._pending.append(ColumnBatch(
            self.output_names, list(probe.columns) + right_cols, out_live))

    _dense_build: Optional[ColumnBatch] = None  # set by the cross path

    def _unmatched_build_batch(self) -> Optional[ColumnBatch]:
        """RIGHT/FULL epilogue: build rows no probe row matched, with NULL
        probe-side columns (runs once; host-side)."""
        build = (self._dense_build if self._dense_build is not None
                 else self.bridge.batch)
        if build is None or build.num_rows == 0:
            return None
        matched = (np.asarray(self._build_matched)
                   if self._build_matched is not None
                   else np.zeros(build.num_rows, bool))
        alive = (np.ones(build.num_rows, bool) if build.live is None
                 else np.asarray(build.live))
        un = np.nonzero(alive & ~matched)[0]
        if not len(un):
            return None
        lw = len(self.output_types) - build.num_columns
        n = len(un)
        left_cols = []
        for i, t in enumerate(self.output_types[:lw]):
            d = (self._probe_dicts[i]
                 if self._probe_dicts is not None else None)
            left_cols.append(Column(t, np.zeros(n, t.storage_dtype),
                                    np.zeros(n, bool), d))
        right_cols = [c.take(un) for c in build.columns]
        return ColumnBatch(self.output_names, left_cols + right_cols)

    def get_output(self) -> Optional[ColumnBatch]:
        if len(self._inflight):
            # commit landed estimated-cap batches; at input end the tail
            # entries are waited on (the only blocking poll of the query)
            self._inflight.drain(block=self.input_done)
            if self.input_done:
                # the counts handed over in flight landed before the flags
                # just waited on
                self._uplanner.land()
        if self._pending:
            return self._pending.popleft()
        if (self.input_done and not self._closed
                and self.join_type in ("RIGHT", "FULL")
                and not self._emitted_unmatched):
            self._emitted_unmatched = True
            return self._unmatched_build_batch()
        return None

    def is_finished(self) -> bool:
        if self._closed:
            return True
        done = (self.input_done and not self._pending
                and not len(self._inflight))
        if self.join_type in ("RIGHT", "FULL"):
            return done and self._emitted_unmatched
        return done


class SemiJoinOperator(Operator):
    """Mark join for IN / EXISTS (operator/HashSemiJoinOperator.java:47):
    output = source channels + a BOOLEAN match column.  Three-valued
    semantics for null-aware IN: no-match becomes NULL (not FALSE) when the
    probe key is NULL or the build side contains a NULL key, so a downstream
    ``$not`` yields NULL and the row is filtered — exactly NOT IN."""

    def __init__(self, bridge: JoinBridge, source_keys: Sequence[int],
                 null_aware: bool, residual: Optional[RowExpression],
                 output_names: Sequence[str], output_types: Sequence[Type]):
        self.bridge = bridge
        self.source_keys = list(source_keys)
        self.null_aware = null_aware
        self.residual = residual
        self.output_names = list(output_names)
        self.output_types = list(output_types)
        from collections import deque

        from . import join_exec as JX

        self._pending: "deque[ColumnBatch]" = deque()
        self._planner = JX.ExpandPlanner(key=(
            "semi", tuple(self.source_keys), null_aware,
            tuple(self.output_names), residual is not None))
        self._inflight = JX.OverflowQueue()

    def needs_input(self) -> bool:
        return self.bridge.ready and not self._pending and super().needs_input()

    def _add_keyless_input(self, batch: ColumnBatch) -> None:
        """EXISTS with only non-equi residuals decorrelates to a keyless
        semi-join: every probe row pairs with every build row and the
        residual alone decides the mark (host nested-loop fallback)."""
        batch = batch.compact()
        build = self.bridge.dense()
        pi, _ = _nested_loop_pairs(batch, build, self.residual)
        matched = np.zeros(batch.num_rows, bool)
        matched[pi] = True
        mark = Column(BOOLEAN, matched, None)
        self._pending.append(ColumnBatch(
            self.output_names, list(batch.columns) + [mark], batch.live))

    def add_input(self, batch: ColumnBatch) -> None:
        from . import join_exec as JX

        if not self.source_keys:
            self._add_keyless_input(batch)
            return
        table = self.bridge.table
        build = self.bridge.batch
        if table.num_rows == 0:
            # IN over the empty set is FALSE (never UNKNOWN)
            mark = Column(BOOLEAN, np.zeros(batch.num_rows, bool), None)
            self._pending.append(ColumnBatch(
                self.output_names, list(batch.columns) + [mark], batch.live))
            return
        if batch.num_rows == 0:
            mark = Column(BOOLEAN, np.zeros(0, bool), None)
            self._pending.append(ColumnBatch(
                self.output_names, list(batch.columns) + [mark], batch.live))
            return
        keys = []
        remaps = []
        for k, ch in enumerate(self.source_keys):
            c = batch.columns[ch]
            bdict = (self.bridge.key_dicts[k]
                     if k < len(self.bridge.key_dicts) else None)
            keys.append((JX.key_input(c), c.valid))
            remaps.append(_probe_key_remap(c, bdict))
        # IN over the empty set is FALSE (never UNKNOWN) even for NULL probes
        semi = (self.null_aware, table.has_null_key, table.live_rows > 0)
        if table.unique:
            if self.residual is not None:
                probe_cols = [(c.data, c.valid) for c in batch.columns]
                build_cols = [(c.data, c.valid) for c in build.columns]
                pair_types = ([c.type for c in batch.columns]
                              + [c.type for c in build.columns])
                pair_dicts = ([c.dictionary for c in batch.columns]
                              + [c.dictionary for c in build.columns])
            else:
                probe_cols, build_cols, pair_types, pair_dicts = [], [], [], []
            with SG.hot_region():
                _, _, _, mark_out = JX.run_unique(
                    table, keys, remaps, probe_cols, build_cols,
                    pair_types, pair_dicts, self.residual, False, semi=semi,
                    live=batch.live)
            mark_data, mark_valid = mark_out
            mark = Column(BOOLEAN, mark_data, mark_valid)
            self._pending.append(ColumnBatch(
                self.output_names, list(batch.columns) + [mark], batch.live))
            return
        if self.residual is not None:
            probe_cols = [(c.data, c.valid) for c in batch.columns]
            build_cols = [(c.data, c.valid) for c in build.columns]
            pair_types = ([c.type for c in batch.columns]
                          + [c.type for c in build.columns])
            pair_dicts = ([c.dictionary for c in batch.columns]
                          + [c.dictionary for c in build.columns])
        else:
            probe_cols, build_cols, pair_types, pair_dicts = [], [], [], []

        def commit(res) -> None:
            mark_data, mark_valid = res[4]
            mark = Column(BOOLEAN, mark_data, mark_valid)
            self._pending.append(ColumnBatch(
                self.output_names, list(batch.columns) + [mark], batch.live))

        with SG.hot_region():
            lo, counts, total_a = JX.probe_ranges_device(
                table, keys, remaps, batch.live)
            cap, provable = self._planner.plan(batch.num_rows, table.max_run)
            self._planner.observe_async(total_a)
            res = JX.run_pairs(
                table, lo, counts, total_a, keys, remaps, probe_cols,
                build_cols, pair_types, pair_dicts, self.residual, False,
                semi=semi, cap=cap, donate=provable)
            if provable:
                commit(res)
                return

            def retry():
                total_h = max(int(total_a.get()), 1)
                self._planner.observe(total_h)
                return JX.run_pairs(
                    table, lo, counts, total_h, keys, remaps, probe_cols,
                    build_cols, pair_types, pair_dicts, self.residual,
                    False, semi=semi)

            self._inflight.push(
                SG.async_scalar(res[5], "join.expand-overflow"),
                res, retry, commit)
            self._inflight.drain()

    def get_output(self) -> Optional[ColumnBatch]:
        if len(self._inflight):
            self._inflight.drain(block=self.input_done)
        return self._pending.popleft() if self._pending else None

    def is_finished(self) -> bool:
        return (self.input_done and not self._pending
                and not len(self._inflight))


# ---------------------------------------------------------------------------
# window


class WindowOperator(BufferedInputMixin, Operator):
    """Window-function evaluation (operator/WindowOperator.java:69): blocking
    — accumulate, then one jitted program per (spec, shape bucket) computes
    every function and scatters results back to input order (see
    exec/window_kernels.py)."""

    def __init__(self, partition_keys: Sequence[int],
                 order_keys: Sequence[SortKey],
                 functions: Sequence[WindowFunc],
                 output_names: Sequence[str], output_types: Sequence[Type]):
        self.partition_keys = list(partition_keys)
        self.order_keys = list(order_keys)
        self.functions = list(functions)
        self.output_names = list(output_names)
        self.output_types = list(output_types)
        self._batches: list[ColumnBatch] = []
        self._result: Optional[ColumnBatch] = None
        self._emitted = False

    def add_input(self, batch: ColumnBatch) -> None:
        if batch.num_rows:
            self._batches.append(batch)
            self.account_memory()

    def finish_input(self) -> None:
        super().finish_input()
        if not self.buffered_batches():
            self._result = ColumnBatch(
                self.output_names,
                [Column(t, np.empty(0, t.storage_dtype))
                 for t in self.output_types])
            return
        inp = ColumnBatch.concat(self._batches)  # compacts + unifies dicts
        pkeys = [(inp.columns[c].data, inp.columns[c].valid)
                 for c in self.partition_keys]
        okeys = [(inp.columns[k.channel].data, inp.columns[k.channel].valid,
                  k.ascending, k.nulls_first) for k in self.order_keys]
        specs = []
        fn_dicts = []
        for f in self.functions:
            acols = [inp.columns[c] for c in f.args]
            if len(acols) > 1 and acols[0].type.is_dictionary_encoded:
                # lag/lead default drawn from a different dictionary column
                acols = unify_dictionaries(acols)
            args = [(c.data, c.valid) for c in acols]
            fn_dicts.append(acols[0].dictionary if acols else None)
            specs.append({
                "fn": f.fn, "args": args, "offset": f.offset,
                "frame": f.frame, "dtype": f.type.storage_dtype,
            })
        results = WK.compute_windows(pkeys, okeys, specs, inp.num_rows)
        out_cols = list(inp.columns)
        for f, (data, valid), fdict in zip(self.functions, results, fn_dicts):
            dict_ = None
            if f.args and f.fn not in ("count", "sum", "avg"):
                dict_ = fdict
            if f.fn in ("row_number", "rank", "dense_rank", "percent_rank",
                        "cume_dist", "ntile", "count", "count_star"):
                valid = None  # never NULL
            out_cols.append(Column(f.type, data, valid, dict_))
        self._result = ColumnBatch(self.output_names, out_cols)
        self.release_memory()

    def get_output(self) -> Optional[ColumnBatch]:
        if self._result is not None and not self._emitted:
            self._emitted = True
            return self._result
        return None

    def is_finished(self) -> bool:
        return (self.input_done and self._emitted) or self._closed


# ---------------------------------------------------------------------------
# sort / topn / limit / distinct


def _sort_key_tuples(batch: ColumnBatch, keys: Sequence[SortKey]):
    out = []
    for k in keys:
        c = batch.columns[k.channel]
        out.append((np.asarray(c.data),
                    None if c.valid is None else np.asarray(c.valid),
                    k.ascending, k.nulls_first))
    return out


def _any_device(batches: Sequence[ColumnBatch]) -> bool:
    for b in batches:
        if b.live is not None and not isinstance(b.live, np.ndarray):
            return True
        for c in b.columns:
            if not isinstance(c.data, np.ndarray):
                return True
    return False


class SortOperator(BufferedInputMixin, Operator):
    """Full sort (operator/OrderByOperator.java:44).  Device-resident input
    sorts on chip as ONE jitted program (lexsort + payload gather, dead rows
    last) with zero host syncs; small host-resident input keeps the numpy
    path — a handful of post-aggregation rows is not worth an upload, a
    compiled sort program and a download."""

    limit: Optional[int] = None  # TopN sets this

    def __init__(self, keys: Sequence[SortKey]):
        self.keys = list(keys)
        self._batches: list[ColumnBatch] = []
        self._result = None
        self._emitted = False

    def add_input(self, batch: ColumnBatch) -> None:
        if batch.num_rows:
            self._batches.append(batch)
            self.account_memory()

    def _sorted_batch(self, batches: Sequence[ColumnBatch],
                      out_n: Optional[int]) -> ColumnBatch:
        if _any_device(batches):
            inp = _maybe_compact_device(_concat_device(batches))
            keys = [(inp.columns[k.channel].data, inp.columns[k.channel].valid,
                     k.ascending, k.nulls_first) for k in self.keys]
            cols = [(c.data, c.valid) for c in inp.columns]
            n = inp.num_rows
            cap = None if out_n is None else min(out_n, n)
            outs, live = K.device_sort(keys, cols, inp.live, cap)
            out_cols = [Column(c.type, d, v, c.dictionary)
                        for (d, v), c in zip(outs, inp.columns)]
            return ColumnBatch(inp.names, out_cols, live)
        inp = ColumnBatch.concat(batches)
        perm = K.sort_perm(_sort_key_tuples(inp, self.keys))
        if out_n is not None:
            perm = np.asarray(perm)[:out_n]
        return inp.take(perm)

    def finish_input(self) -> None:
        super().finish_input()
        if not self.buffered_batches():
            self._emitted = True
            return
        self._result = self._sorted_batch(self._batches, self.limit)
        self.release_memory()

    def get_output(self):
        if self._result is not None and not self._emitted:
            self._emitted = True
            return self._result
        return None

    def is_finished(self) -> bool:
        return self.input_done and self._emitted


class TopNOperator(SortOperator):
    """Streaming top-N (operator/TopNOperator.java:34): when the buffer
    outgrows a multiple of N, it is compacted to the current best N rows, so
    state stays O(N + batch) instead of O(input)."""

    def __init__(self, count: int, keys: Sequence[SortKey]):
        super().__init__(keys)
        self.count = count
        self.limit = count
        self._buffered_rows = 0
        self._shrink_at = max(4 * count, 1 << 16)

    def add_input(self, batch: ColumnBatch) -> None:
        if not batch.num_rows:
            return
        self._batches.append(batch)
        self._buffered_rows += batch.num_rows
        if self._buffered_rows > self._shrink_at:
            self._shrink()
        self.account_memory()

    def _shrink(self) -> None:
        best = self._sorted_batch(self.buffered_batches(), self.count)
        self._batches = [best]
        self._buffered_rows = best.num_rows


class GroupIdOperator(Operator):
    """Grouping-sets row expansion (reference: operator/GroupIdOperator.java:32):
    each input batch yields one output batch per grouping set — grouping
    columns absent from the set become all-NULL copies, aggregation-argument
    channels pass through untouched, and a constant $groupid column tags the
    set.  Masking instead of replicating row-by-row keeps every emitted batch
    the same fixed shape as its input (XLA-friendly; no dynamic fan-out)."""

    def __init__(self, key_channels, passthrough, sets, output_names,
                 output_types):
        self.key_channels = list(key_channels)
        self.passthrough = list(passthrough)
        self.sets = [tuple(s) for s in sets]
        self.output_names = list(output_names)
        self.gid_type = output_types[-1]
        self._queue: list[ColumnBatch] = []

    def needs_input(self) -> bool:
        return not self._queue and super().needs_input()

    def add_input(self, batch: ColumnBatch) -> None:
        n = batch.num_rows
        for gid, live_keys in enumerate(self.sets):
            cols = []
            for idx, ch in enumerate(self.key_channels):
                c = batch.columns[ch]
                if idx in live_keys:
                    cols.append(c)
                else:
                    # all-NULL copy; keep the array backend (host vs device)
                    if isinstance(c.data, np.ndarray):
                        invalid = np.zeros(n, dtype=np.bool_)
                    else:
                        import jax.numpy as jnp

                        invalid = jnp.zeros(n, dtype=jnp.bool_)
                    cols.append(Column(c.type, c.data, invalid, c.dictionary))
            for ch in self.passthrough:
                cols.append(batch.columns[ch])
            cols.append(Column(self.gid_type,
                               np.full(n, gid, dtype=np.int64)))
            self._queue.append(ColumnBatch(self.output_names, cols, batch.live))

    def get_output(self) -> Optional[ColumnBatch]:
        if self._queue:
            return self._queue.pop(0)
        return None

    def is_finished(self) -> bool:
        return self.input_done and not self._queue


class UnnestOperator(Operator):
    """Array row expansion (reference: operator/unnest/UnnestOperator.java:42).
    Host-side by design: fan-out is inherently dynamic-shape, and array
    values live in the host dictionary (spi/types.ArrayType).  Multiple
    arrays zip-pad to the longest per row (Trino semantics); rows where
    every array is empty/NULL are dropped (CROSS JOIN UNNEST)."""

    def __init__(self, replicate, unnest_channels, ordinality, output_names,
                 output_types):
        self.replicate = list(replicate)
        self.unnest_channels = list(unnest_channels)
        self.ordinality = ordinality
        self.output_names = list(output_names)
        self.output_types = list(output_types)
        self._pending: Optional[ColumnBatch] = None

    def needs_input(self) -> bool:
        return self._pending is None and super().needs_input()

    def add_input(self, batch: ColumnBatch) -> None:
        batch = batch.compact()
        n = batch.num_rows
        if n == 0:
            return
        per_col: list[list[tuple]] = []
        for ch in self.unnest_channels:
            c = batch.columns[ch]
            codes = np.asarray(c.data)
            valid = c.valid_mask()
            d = c.dictionary
            per_col.append([
                tuple(d[codes[i]]) if valid[i] else () for i in range(n)])
        lengths = np.array(
            [max(len(a[i]) for a in per_col) for i in range(n)],
            dtype=np.int64)
        idx = np.repeat(np.arange(n), lengths)
        if not len(idx):
            return
        pos = np.concatenate([np.arange(l) for l in lengths if l])
        cols = [batch.columns[ch].take(idx) for ch in self.replicate]
        k = len(self.replicate)
        for j in range(len(per_col)):
            et = self.output_types[k + j]
            vals = [
                per_col[j][r][p] if p < len(per_col[j][r]) else None
                for r, p in zip(idx, pos)]
            cols.append(Column.from_values(et, vals))
        if self.ordinality:
            cols.append(Column(self.output_types[-1],
                               (pos + 1).astype(np.int64)))
        self._pending = ColumnBatch(self.output_names, cols)

    def get_output(self) -> Optional[ColumnBatch]:
        b, self._pending = self._pending, None
        return b

    def is_finished(self) -> bool:
        return self.input_done and self._pending is None


class ReplicateOperator(Operator):
    """Emit each row N times, N from a count channel (the row-expansion leg
    of INTERSECT/EXCEPT ALL — see planner Replicate node)."""

    def __init__(self, count_channel: int):
        self.count_channel = count_channel
        self._pending: Optional[ColumnBatch] = None

    def needs_input(self) -> bool:
        return self._pending is None and super().needs_input()

    def add_input(self, batch: ColumnBatch) -> None:
        batch = batch.compact()
        counts = np.asarray(batch.columns[self.count_channel].data)
        counts = np.clip(counts, 0, None)
        idx = np.repeat(np.arange(batch.num_rows), counts)
        if len(idx):
            self._pending = batch.take(idx)

    def get_output(self) -> Optional[ColumnBatch]:
        b, self._pending = self._pending, None
        return b

    def is_finished(self) -> bool:
        return self.input_done and self._pending is None


class LimitOperator(Operator):
    def __init__(self, count: int):
        self.count = count
        self._remaining = count
        self._pending = None

    def needs_input(self) -> bool:
        return self._remaining > 0 and self._pending is None and super().needs_input()

    def add_input(self, batch: ColumnBatch) -> None:
        batch = batch.compact()
        if batch.num_rows > self._remaining:
            batch = batch.slice(0, self._remaining)
        self._remaining -= batch.num_rows
        self._pending = batch

    def get_output(self):
        b, self._pending = self._pending, None
        return b

    def is_finished(self) -> bool:
        return (self.input_done or self._remaining == 0) and self._pending is None


class DistinctLimitOperator(BufferedInputMixin, Operator):
    """DISTINCT (optionally limited): dedup via the grouping kernel."""

    def __init__(self, count: Optional[int]):
        self.count = count
        self._batches: list[ColumnBatch] = []
        self._result = None
        self._emitted = False

    def add_input(self, batch: ColumnBatch) -> None:
        if batch.num_rows:
            self._batches.append(batch)
            self.account_memory()

    def finish_input(self) -> None:
        super().finish_input()
        if not self.buffered_batches():
            self._emitted = True
            return
        inp = ColumnBatch.concat(self._batches)
        keys = [(np.asarray(c.data),
                 None if c.valid is None else np.asarray(c.valid))
                for c in inp.columns]
        perm, gid, n = K.group_ids(keys)
        # first occurrence of each group (keeps input order stable-ish)
        first = np.full(n, inp.num_rows, dtype=np.int64)
        np.minimum.at(first, np.asarray(gid), np.asarray(perm))
        out = inp.take(np.sort(first))
        if self.count is not None:
            out = out.slice(0, self.count)
        self._result = out
        self.release_memory()

    def get_output(self):
        if self._result is not None and not self._emitted:
            self._emitted = True
            return self._result
        return None

    def is_finished(self) -> bool:
        return self.input_done and self._emitted


# ---------------------------------------------------------------------------
# sinks


class TableWriterOperator(Operator):
    """Writes batches into a connector sink; emits the row count
    (operator/TableWriterOperator.java:68)."""

    def __init__(self, sink: ConnectorPageSink, on_finish=None):
        self.sink = sink
        self.on_finish = on_finish
        self._rows = 0
        self._emitted = False

    def add_input(self, batch: ColumnBatch) -> None:
        batch = batch.compact()
        self._rows += batch.num_rows
        self.sink.append(batch)

    def finish_input(self) -> None:
        super().finish_input()
        fragments = self.sink.finish()
        if self.on_finish is not None:
            self.on_finish(fragments)

    def get_output(self):
        if self.input_done and not self._emitted:
            self._emitted = True
            return ColumnBatch(["rows"], [Column(BIGINT, np.array([self._rows]))])
        return None

    def is_finished(self) -> bool:
        return self.input_done and self._emitted


class OutputCollector(Operator):
    """Terminal sink: buffers result batches for the client."""

    def __init__(self):
        self.batches: list[ColumnBatch] = []

    def add_input(self, batch: ColumnBatch) -> None:
        if batch.num_rows:
            self.batches.append(batch)

    def is_finished(self) -> bool:
        return self.input_done
