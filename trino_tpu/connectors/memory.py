"""In-memory table connector + /dev/null connector.

Mirror ``plugin/trino-memory`` (MemoryConnector — the v1 write target) and
``plugin/trino-blackhole`` (BlackHoleConnector — perf-test sink).  Tables live
as lists of ColumnBatches on the host; splits partition the batch list so
multi-split scans exercise the same paths as the generator connector.
"""

from __future__ import annotations

import threading
from typing import Any, Optional, Sequence

import numpy as np

from ..spi.batch import ColumnBatch
from ..spi.connector import (
    Connector,
    ConnectorPageSink,
    ConnectorPageSource,
    Split,
    TableSchema,
    TableStatistics,
)

__all__ = ["MemoryConnector", "BlackholeConnector"]


class _ListPageSource(ConnectorPageSource):
    def __init__(self, batches: list[ColumnBatch], columns: Sequence[str]):
        self._batches = batches
        self._columns = list(columns)
        self._i = 0

    def get_next_batch(self) -> Optional[ColumnBatch]:
        if self._i >= len(self._batches):
            return None
        b = self._batches[self._i]
        self._i += 1
        return b.select(self._columns)

    def is_finished(self) -> bool:
        return self._i >= len(self._batches)


def _batch_overlaps(b: ColumnBatch, constraint) -> bool:
    """Min/max zone-map check: can any row of the host batch satisfy the
    TupleDomain?  Device-pinned batches (live mask) always pass — pulling
    them down for stats would defeat the pinning.  Stats are computed once
    per batch and memoized on the batch object (the reference keeps
    per-page min/max in connector metadata, e.g. ORC stripe stats)."""
    if b.live is not None:
        return True
    stats = getattr(b, "_domain_stats", None)
    if stats is None:
        stats = {}
        b._domain_stats = stats
    missing = [n for n in constraint.domains
               if n not in stats and n in b.names]
    if missing:
        for name in missing:
            c = b.columns[b.names.index(name)]
            data = np.asarray(c.data)
            valid = None if c.valid is None else np.asarray(c.valid)
            has_null = bool((~valid).any()) if valid is not None else False
            if c.dictionary is not None:
                present = data if valid is None else data[valid]
                if present.size:
                    vals = c.dictionary[np.unique(present)]
                    # long-decimal dictionaries hold python ints: zone-map
                    # bounds must stay in storage space, not stringify
                    if isinstance(vals[0], int):
                        stats[name] = (int(vals[0]), int(vals[-1]), has_null)
                    else:
                        stats[name] = (str(vals[0]), str(vals[-1]), has_null)
                else:
                    stats[name] = (None, None, has_null)
            elif np.issubdtype(data.dtype, np.number) or data.dtype == bool:
                present = data if valid is None else data[valid]
                if present.size:
                    mn, mx = present.min(), present.max()
                    if isinstance(mn, np.floating) and (
                            np.isnan(mn) or np.isnan(mx)):
                        continue  # NaNs poison comparisons: no stats
                    stats[name] = (mn.item(), mx.item(), has_null)
                else:
                    stats[name] = (None, None, has_null)
    mins = {k: v[0] for k, v in stats.items()}
    maxs = {k: v[1] for k, v in stats.items()}
    nulls = {k: v[2] for k, v in stats.items()}
    return constraint.overlaps_stats(mins, maxs, nulls)


class _MemoryPageSink(ConnectorPageSink):
    def __init__(self, connector: "MemoryConnector", table: str):
        self._connector = connector
        self._table = table
        self._staged: list[ColumnBatch] = []

    def append(self, batch: ColumnBatch) -> bool:
        self._staged.append(batch)
        return True

    def finish(self) -> list[Any]:
        return [self._staged]


class MemoryConnector(Connector):
    name = "memory"

    def __init__(self):
        self._lock = threading.Lock()
        self._schemas: dict[str, TableSchema] = {}
        self._data: dict[str, list[ColumnBatch]] = {}
        # live-row counts of device-pinned tables (padding rows excluded;
        # computed once at pin time to avoid per-query device syncs)
        self._pinned_rows: dict[str, int] = {}
        # observability: batches skipped by TupleDomain min/max pruning
        self.batches_pruned = 0
        # data_version tokens: drawn from one instance-wide monotonic
        # counter so a drop/recreate cycle can never reissue an old token
        # (a reset-to-zero per-table counter would let a result cached
        # against the ORIGINAL table at v0 be served for the NEW one)
        self._versions: dict[str, int] = {}
        self._next_version = 0

    def _bump_version(self, table: str) -> None:
        # callers hold self._lock
        self._next_version += 1
        self._versions[table] = self._next_version
        from ..caching import result_cache

        result_cache.invalidate_table(self.name, table)

    def data_version(self, table: str):
        with self._lock:
            if table not in self._schemas:
                raise KeyError(f"memory: no such table {table!r}")
            return self._versions.get(table, 0)

    def list_tables(self) -> list[str]:
        with self._lock:
            return sorted(self._schemas)

    def get_table_schema(self, table: str) -> TableSchema:
        with self._lock:
            if table not in self._schemas:
                raise KeyError(f"memory: no such table {table!r}")
            return self._schemas[table]

    def get_table_statistics(self, table: str) -> TableStatistics:
        analyzed = getattr(self, "_analyzed_stats", {}).get(table)
        if analyzed is not None:
            return analyzed
        with self._lock:
            if table in self._pinned_rows:
                rows = self._pinned_rows[table]
            else:
                rows = sum(b.num_rows for b in self._data.get(table, []))
        return TableStatistics(row_count=float(rows))

    def get_procedures(self) -> dict:
        """CALL memory.truncate_table('t') / memory.pin_table('t')
        (reference: spi/procedure/Procedure.java — connector-registered
        procedures dispatched by CallTask)."""

        def truncate_table(table: str) -> str:
            with self._lock:
                if table not in self._schemas:
                    raise KeyError(f"memory: no such table {table!r}")
                self._data[table] = []
                self._pinned_rows.pop(table, None)
                self._bump_version(table)
            return f"truncated {table}"

        def pin_table(table: str) -> str:
            self.pin_to_device(table)
            return f"pinned {table}"

        return {"truncate_table": truncate_table, "pin_table": pin_table}

    def create_table(self, schema: TableSchema) -> None:
        with self._lock:
            if schema.name in self._schemas:
                raise ValueError(f"memory: table {schema.name!r} already exists")
            self._schemas[schema.name] = schema
            self._data[schema.name] = []
            self._bump_version(schema.name)

    def drop_table(self, table: str) -> None:
        with self._lock:
            self._schemas.pop(table, None)
            self._data.pop(table, None)
            self._pinned_rows.pop(table, None)
            self._versions.pop(table, None)
            from ..caching import result_cache

            result_cache.invalidate_table(self.name, table)

    def get_splits(self, table: str, splits_per_node: int, node_count: int) -> list[Split]:
        with self._lock:
            n = len(self._data.get(table, []))
        want = max(1, splits_per_node * node_count)
        n_splits = min(want, max(n, 1))
        bounds = np.linspace(0, n, n_splits + 1, dtype=np.int64)
        return [
            Split("memory", table, (int(bounds[i]), int(bounds[i + 1])))
            for i in range(n_splits)
            if bounds[i + 1] > bounds[i] or n == 0 and i == 0
        ]

    def create_page_source(self, split: Split, columns: Sequence[str],
                           constraint=None) -> ConnectorPageSource:
        lo, hi = split.info
        with self._lock:
            batches = self._data[split.table][lo:hi]
        if constraint is not None and not constraint.is_all:
            kept = [b for b in batches
                    if _batch_overlaps(b, constraint)]
            self.batches_pruned += len(batches) - len(kept)
            batches = kept
        return _ListPageSource(batches, columns)

    def create_page_sink(self, table: str) -> ConnectorPageSink:
        self.get_table_schema(table)  # existence check
        return _MemoryPageSink(self, table)

    def finish_insert(self, table: str, fragments: list[Any]) -> None:
        with self._lock:
            for staged in fragments:
                self._data[table].extend(staged)
                if table in self._pinned_rows:
                    self._pinned_rows[table] += sum(
                        b.live_count for b in staged)
            self._bump_version(table)

    # ---- transactions ----------------------------------------------------
    def begin_transaction(self):
        """Snapshot handle: per-table batch-list lengths + the table set.
        Rollback undoes INSERT/CTAS/CREATE TABLE performed since BEGIN by
        truncating back to the snapshot (DELETE's drop-and-rewrite is not
        transactional — mirrors the reference memory connector, which only
        supports INSERT/CREATE in a transaction)."""
        with self._lock:
            return {
                "tables": set(self._schemas),
                "lengths": {t: len(b) for t, b in self._data.items()},
            }

    def commit_transaction(self, handle) -> None:
        pass  # writes applied eagerly; commit just drops the snapshot

    def rollback_transaction(self, handle) -> None:
        if handle is None:
            return
        with self._lock:
            for t in list(self._schemas):
                if t not in handle["tables"]:
                    self._schemas.pop(t, None)
                    self._data.pop(t, None)
                    self._pinned_rows.pop(t, None)
                    self._versions.pop(t, None)
            for t, n in handle["lengths"].items():
                if t in self._data and len(self._data[t]) > n:
                    removed = self._data[t][n:]
                    del self._data[t][n:]
                    if t in self._pinned_rows:
                        self._pinned_rows[t] -= sum(
                            b.live_count for b in removed)
                    self._bump_version(t)

    def pin_to_device(self, table: str) -> None:
        """Make a table device-resident: batches become bucket-padded jax
        arrays living in HBM, so scans hand columns straight to the jitted
        pipeline with no host->device upload per query.  The TPU-native
        equivalent of the reference keeping hot pages in worker heap
        (MemoryPagesStore) — here the 'heap' is device memory."""
        import jax
        import jax.numpy as jnp
        import numpy as _np

        from ..spi.batch import Column, ColumnBatch, round_up_pow2

        from ..spi.batch import pad_to_bucket

        with self._lock:
            batches = self._data.get(table, [])
            total_rows = 0
            pinned = []
            for b in batches:
                already_dev = (b.columns
                               and not isinstance(b.columns[0].data, _np.ndarray))
                if already_dev:
                    # born on device (device-side generation / jitted
                    # pipeline output): keep it — a compact() here would
                    # drag the whole table through the host
                    lv = b.live
                    if lv is None:
                        lv = jnp.ones(b.num_rows, jnp.bool_)
                    pinned.append(ColumnBatch(b.names, list(b.columns),
                                              jax.device_put(jnp.asarray(lv)),
                                              resident=True))
                    total_rows += b.live_count
                    continue
                b = pad_to_bucket(b.compact())
                total_rows += b.live_count
                live = b.live
                if live is None:
                    # a live mask marks the batch device-pinned downstream
                    # (ScanOperator skips host work for it) — attach an
                    # all-ones mask even when no padding was needed
                    live = _np.ones(b.num_rows, _np.bool_)
                cols = [
                    Column(c.type, jax.device_put(jnp.asarray(c.data)),
                           None if c.valid is None
                           else jax.device_put(jnp.asarray(c.valid)),
                           c.dictionary)
                    for c in b.columns
                ]
                pinned.append(ColumnBatch(
                    b.names, cols, jax.device_put(jnp.asarray(live)),
                    resident=True))
            self._data[table] = pinned
            self._pinned_rows[table] = total_rows


class _NullSink(ConnectorPageSink):
    def __init__(self):
        self.rows = 0

    def append(self, batch: ColumnBatch) -> bool:
        self.rows += batch.num_rows
        return True

    def finish(self) -> list[Any]:
        return [self.rows]


class BlackholeConnector(Connector):
    name = "blackhole"

    def __init__(self):
        self._schemas: dict[str, TableSchema] = {}

    def list_tables(self) -> list[str]:
        return sorted(self._schemas)

    def get_table_schema(self, table: str) -> TableSchema:
        if table not in self._schemas:
            raise KeyError(f"blackhole: no such table {table!r}")
        return self._schemas[table]

    def create_table(self, schema: TableSchema) -> None:
        self._schemas[schema.name] = schema

    def drop_table(self, table: str) -> None:
        self._schemas.pop(table, None)

    def get_splits(self, table, splits_per_node, node_count):
        return []

    def create_page_sink(self, table: str) -> ConnectorPageSink:
        self.get_table_schema(table)
        return _NullSink()
