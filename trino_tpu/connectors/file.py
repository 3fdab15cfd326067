"""File connector: persistent columnar storage on local disk.

The engine's durable-table connector (the role plugin/trino-hive plays for
warehouse files): a table is a directory holding ``schema.json`` plus one
page file per written fragment.  Pages are the engine's serde frames
(execution/serde.py), so the same wire format serves the exchange, the
spiller, and storage.  The IO hot path — frame scanning and reads — goes
through the native C++ library (native/pagefile.cpp via ctypes,
trino_tpu/native.py), built from source on first use.

Splits map 1:1 to page files, so multi-task scans parallelize over files.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Any, Optional, Sequence


from .. import native
from ..execution.serde import deserialize_batch, serialize_batch
from ..spi.batch import ColumnBatch
from ..spi.connector import (
    ColumnSchema,
    Connector,
    ConnectorPageSink,
    ConnectorPageSource,
    Split,
    TableSchema,
    TableStatistics,
)
from ..spi.types import parse_type

__all__ = ["FileConnector"]


def _read_frames(path: str) -> list[bytes]:
    """All serde frames of a page file, through the native scan + read."""
    import ctypes

    lib = native.load()
    cap = 4096
    while True:
        out = (ctypes.c_int64 * (2 * cap))()
        n = lib.ttp_scan_frames(path.encode(), out, cap)
        if n < 0:
            raise IOError(f"corrupt page file: {path}")
        if n <= cap:
            break
        cap = n
    frames = []
    for i in range(n):
        off, length = out[2 * i], out[2 * i + 1]
        buf = (ctypes.c_uint8 * length)()
        if lib.ttp_read_frame(path.encode(), off, length, buf) != length:
            raise IOError(f"short read: {path}")
        frames.append(bytes(buf))
    return frames


class _FilePageSource(ConnectorPageSource):
    def __init__(self, path: str, columns: Sequence[str]):
        self._frames = _read_frames(path)
        self._columns = list(columns)
        self._i = 0

    def get_next_batch(self) -> Optional[ColumnBatch]:
        if self._i >= len(self._frames):
            return None
        batch = deserialize_batch(self._frames[self._i])
        self._i += 1
        return batch.select(self._columns)

    def is_finished(self) -> bool:
        return self._i >= len(self._frames)


class _FilePageSink(ConnectorPageSink):
    def __init__(self, path: str):
        self._path = path
        self._file = open(path, "wb")
        self.rows = 0

    def append(self, batch: ColumnBatch) -> bool:
        from ..execution.serde import write_frame

        batch = batch.compact()
        if batch.num_rows == 0:
            return True
        write_frame(self._file, serialize_batch(batch))
        self.rows += batch.num_rows
        return True

    def finish(self) -> list[Any]:
        self._file.close()
        return [(self._path, self.rows)]


class FileConnector(Connector):
    name = "file"

    def __init__(self, root: Optional[str] = None):
        # root=None: create a temp directory lazily on first use, so idle
        # catalogs don't litter /tmp
        self._root = root
        # reentrant: metadata paths touch self.root under the lock
        self._lock = threading.RLock()
        self._sink_seq = 0

    @property
    def root(self) -> str:
        with self._lock:
            if self._root is None:
                import tempfile

                self._root = tempfile.mkdtemp(prefix="trino-tpu-file-")
            os.makedirs(self._root, exist_ok=True)
            return self._root

    # ---- metadata -------------------------------------------------------
    def _dir(self, table: str) -> str:
        return os.path.join(self.root, table)

    def _meta_path(self, table: str) -> str:
        return os.path.join(self._dir(table), "schema.json")

    def list_tables(self) -> list[str]:
        return sorted(
            d for d in os.listdir(self.root)
            if os.path.exists(self._meta_path(d)))

    def get_table_schema(self, table: str) -> TableSchema:
        try:
            with open(self._meta_path(table)) as f:
                meta = json.load(f)
        except FileNotFoundError:
            raise KeyError(f"file: no such table {table!r}")
        return TableSchema(table, tuple(
            ColumnSchema(c["name"], parse_type(c["type"]))
            for c in meta["columns"]))

    def get_table_statistics(self, table: str) -> TableStatistics:
        analyzed = getattr(self, "_analyzed_stats", {}).get(table)
        if analyzed is not None:
            return analyzed
        try:
            with open(self._meta_path(table)) as f:
                meta = json.load(f)
        except FileNotFoundError:
            return TableStatistics()
        return TableStatistics(row_count=float(meta.get("rows", 0)))

    def data_version(self, table: str):
        """On-disk content signature: the page-file list (names embed
        pid + a monotonic sink sequence, so they are never reused) plus
        the row count.  Equal signature ⇒ equal bytes on disk, across
        drop/recreate cycles and across processes."""
        try:
            with open(self._meta_path(table)) as f:
                meta = json.load(f)
        except FileNotFoundError:
            raise KeyError(f"file: no such table {table!r}")
        return f"{meta.get('rows', 0)}:{','.join(meta.get('pages', []))}"

    def _invalidate(self, table: str) -> None:
        from ..caching import result_cache

        result_cache.invalidate_table(self.name, table)

    def create_table(self, schema: TableSchema) -> None:
        d = self._dir(schema.name)
        if os.path.exists(self._meta_path(schema.name)):
            raise ValueError(f"file: table {schema.name!r} already exists")
        os.makedirs(d, exist_ok=True)
        with open(self._meta_path(schema.name), "w") as f:
            json.dump({
                "columns": [{"name": c.name, "type": str(c.type)}
                            for c in schema.columns],
                "rows": 0,
                "pages": [],
            }, f)
        self._invalidate(schema.name)

    def drop_table(self, table: str) -> None:
        shutil.rmtree(self._dir(table), ignore_errors=True)
        self._invalidate(table)

    # ---- scan -----------------------------------------------------------
    def get_splits(self, table: str, splits_per_node: int,
                   node_count: int) -> list[Split]:
        with open(self._meta_path(table)) as f:
            meta = json.load(f)
        return [Split("file", table, os.path.join(self._dir(table), p))
                for p in meta["pages"]]

    def create_page_source(self, split: Split, columns: Sequence[str],
                           constraint=None) -> ConnectorPageSource:
        return _FilePageSource(split.info, columns)

    # ---- write ----------------------------------------------------------
    def create_page_sink(self, table: str) -> ConnectorPageSink:
        self.get_table_schema(table)  # existence check
        with self._lock:
            self._sink_seq += 1
            name = f"part-{os.getpid()}-{self._sink_seq}.bin"
        return _FilePageSink(os.path.join(self._dir(table), name))

    def finish_insert(self, table: str, fragments: list[Any]) -> None:
        with self._lock:
            with open(self._meta_path(table)) as f:
                meta = json.load(f)
            for frag in fragments:
                path, rows = frag[0] if isinstance(frag, list) else frag
                if rows == 0:
                    try:
                        os.unlink(path)
                    except OSError:
                        pass
                    continue
                meta["pages"].append(os.path.basename(path))
                meta["rows"] += rows
            with open(self._meta_path(table), "w") as f:
                json.dump(meta, f)
        self._invalidate(table)