"""Brings one configuration up in this process: TPC-H tables generated on
the device and pinned behind the memory connector, a TrinoTpuServer over the
in-process DistributedQueryRunner.  Copied from chip_smoke.py (PR 22), where
this body was proven on the chip; parametrised by the configuration's file.

From the program this takes the system under test only: its connectors, its
runner and its server."""

from __future__ import annotations

import importlib.metadata
import os
import time

import numpy as np

_T0 = time.monotonic()


def say(msg: str) -> None:
    print(f"[{time.monotonic() - _T0:7.1f}s] {msg}", flush=True)


def gb(n) -> str:
    if n is None:
        return "n/a"
    return f"{n / 1e9:.2f}GB" if n >= 1e9 else f"{n / 1e6:.1f}MB"


def device_report(cache_dir: str) -> dict:
    """The device line of every run, and the ``device`` of its result."""
    import jax
    import jaxlib

    devs = jax.devices()
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    held = [e.stat().st_size for e in os.scandir(cache_dir)] \
        if os.path.isdir(cache_dir) else []
    say(f"device: platform={devs[0].platform} kind={devs[0].device_kind!r} "
        f"count={len(devs)} jax={jax.__version__} jaxlib="
        f"{jaxlib.__version__} libtpu={libtpu} compile_cache={cache_dir} "
        f"({len(held)} files, {gb(sum(held))})")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def hbm(dev) -> dict:
    """bytes in use / peak on one device, where the backend reports them."""
    st = dev.memory_stats() or {}
    return {"in_use": st.get("bytes_in_use"),
            "peak": st.get("peak_bytes_in_use"),
            "limit": st.get("bytes_limit")}


def peak_bytes(devs) -> int:
    """The peak on the fullest chip; 0 where the backend reports none."""
    return max(hbm(d)["peak"] or 0 for d in devs)


# ------------------------------------------------------------------ loading

def _device_chunks(batch, n_live: int, rows: int) -> list:
    """Split one device-born, bucket-padded table batch into ``rows``-row
    device batches, dropping chunks that hold nothing but padding.  Each
    full-length column is released as soon as it is sliced, so the peak is
    the table plus one column, not two tables."""
    import jax
    import jax.numpy as jnp

    from trino_tpu.spi.batch import Column, ColumnBatch

    names, columns, live = batch.names, batch.columns, batch.live
    cap = batch.num_rows
    del batch
    rows = min(rows, cap)
    starts = list(range(0, n_live, rows))
    if live is None:
        live = jnp.ones(cap, jnp.bool_)

    def cut(a):
        # dynamic_slice: ONE compiled program per (dtype, rows), whatever
        # the number of chunks (a static a[s:s+rows] compiles per start)
        out = [jax.lax.dynamic_slice_in_dim(a, s, rows) for s in starts]
        jax.block_until_ready(out)
        return out

    lives = cut(live)
    sliced = []
    while columns:
        c = columns.pop(0)
        sliced.append((c.type, cut(c.data),
                       None if c.valid is None else cut(c.valid),
                       c.dictionary))
        del c
    return [
        ColumnBatch(list(names),
                    [Column(t, d[i], None if v is None else v[i], dic)
                     for t, d, v, dic in sliced], lives[i])
        for i in range(len(starts))
    ]


def _scan(conn, table: str, cols: list, splits: int = 1):
    """Every batch of ``table`` through the connector's own page source."""
    for split in conn.get_splits(table, splits, 1):
        src = conn.create_page_source(split, cols)
        while not src.is_finished():
            b = src.get_next_batch()
            if b is not None:
                yield b


def _host_chunks(tpch, table: str, cols: list, rows: int) -> list:
    """Tables with no device generator (customer, ...): the host page
    source, regrouped into ``rows``-row batches."""
    from trino_tpu.spi.batch import ColumnBatch

    whole = ColumnBatch.concat(list(_scan(tpch, table, cols, splits=4)))
    return [whole.slice(s, min(s + rows, whole.num_rows))
            for s in range(0, whole.num_rows, rows)]


def load_tables(sf: float, batch_rows: int, tables: list):
    """TPC-H at ``sf`` resident in device memory behind the memory
    connector, in ``batch_rows``-row device batches, pinned, with the
    source's table statistics set as ANALYZE would leave them (without
    column NDVs the planner broadcasts lineitem).  Prints per table the
    rows, batches, bytes resident and HBM in use.  Returns (catalog,
    {table: live rows})."""
    import jax

    from trino_tpu.connectors.catalog import default_catalog
    from trino_tpu.connectors.tpch import generate_table_device
    from trino_tpu.spi.connector import TableSchema

    dev = jax.devices()[0]
    catalog = default_catalog(scale_factor=sf)
    tpch = catalog.connector("tpch")
    mem = catalog.connector("memory")
    rows_of = {}
    for t in tables:
        t0 = time.monotonic()
        before = hbm(dev)["in_use"] or 0
        schema = tpch.get_table_schema(t)
        cols = schema.column_names()
        n = int(tpch.row_count(t))
        whole = generate_table_device(tpch, t, cols)
        if whole is None:
            chunks = _host_chunks(tpch, t, cols, batch_rows)
        else:
            chunks = _device_chunks(whole, n, batch_rows)
            del whole
        mem.create_table(TableSchema(t, schema.columns))
        mem.finish_insert(t, [chunks])
        mem.pin_to_device(t)
        mem.set_analyzed_statistics(t, tpch.get_table_statistics(t))
        rows_of[t] = n
        now = hbm(dev)
        say(f"load: {t} sf={sf:g} rows={n} batches={len(chunks)} x "
            f"{batch_rows} resident={gb((now['in_use'] or 0) - before)} "
            f"hbm_in_use={gb(now['in_use'])} peak={gb(now['peak'])} "
            f"in {time.monotonic() - t0:.1f}s")
    return catalog, rows_of


def host_columns(catalog, table: str, cols: list) -> dict:
    """Live rows of ``cols`` pulled to the host through the connector's own
    page source, as plain numpy arrays — the plain reference's input, never
    inside a timed region.  A dictionary-coded column comes as its int32
    codes under its name and its values under ``<name>.dict``."""
    parts: dict = {c: [] for c in cols}
    dicts: dict = {}
    for b in _scan(catalog.connector("memory"), table, cols):
        b = b.to_host()
        live = None if b.live is None else np.asarray(b.live)
        for c in cols:
            col = b.column(c)
            if col.dictionary is not None:
                d = np.asarray(col.dictionary)
                if c in dicts and not np.array_equal(dicts[c], d):
                    raise AssertionError(
                        f"{table}.{c}: batches carry different dictionaries")
                dicts[c] = d
            d = np.asarray(col.data)
            parts[c].append(d if live is None else d[live])
    out = {c: np.concatenate(v) for c, v in parts.items()}
    out.update({f"{c}.dict": d for c, d in dicts.items()})
    return out


# ------------------------------------------------------------------ serving

def start_server(catalog, workers: int):
    """TrinoTpuServer over the in-process DistributedQueryRunner — the chip
    path today (README, 'Running').  ``workers`` tasks per stage, so the
    fragmenter, a PARTIAL->FINAL seam and an exchange exist on one chip."""
    from trino_tpu.execution.distributed_runner import DistributedQueryRunner
    from trino_tpu.runner import Session
    from trino_tpu.server.protocol import TrinoTpuServer

    runner = DistributedQueryRunner(
        catalog, worker_count=workers,
        session=Session(default_catalog="memory", node_count=workers))
    server = TrinoTpuServer(runner).start()
    return runner, server
