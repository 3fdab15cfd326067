"""chip_smoke.py — the quickest proof that the served TPC-H path still starts
on a TPU chip.

    python chip_smoke.py              one chip: load, serve, check
    python chip_smoke.py --chips 4    only the path across four chips and
                                      the one-device run it is compared with

One process, the only one that touches JAX; no subprocess probe and no
platform chosen in code.  It loads TPC-H ``lineitem`` / ``orders`` /
``customer`` into HBM through the normal connectors (device-side generation
-> memory connector -> ``pin_to_device``), serves Q1, Q6 and Q3 over
``POST /v1/statement`` from a ``TrinoTpuServer`` on the in-process
``DistributedQueryRunner``, checks the same three queries at SF0.01 against
the sqlite oracle and Q1/Q6 at the full scale factor against a plain numpy
evaluation, and prints what it observed per query.  Every failure
propagates: a wrong answer, a wrong platform or an exception is a non-zero
exit.  The last stdout line is the driver's contract,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
everything else is on earlier lines, and none of it is a benchmark result.

``--sf`` is the rehearsal size argument: on a CPU backend the body runs at
that size and the script still exits non-zero at the end ("not a TPU").
Without ``--sf`` a non-TPU backend fails before any work, and a TPU runs the
sizes set below — each a cut from SF10 whose reason is printed first.
"""

from __future__ import annotations

import argparse
import contextlib
import datetime
import decimal
import importlib.metadata
import json
import logging
import math
import os
import re
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

# The size a one-chip deployment holds is SF10 (BASELINE.json config #2,
# ROADMAP R1), and SF10 loads and answers Q1/Q6: 6.8 GB resident, 12.3 GB
# peak of 16.9 GB (my chip run, PR 22).  What does not fit is the script's
# 1200 s, and the cause is compile time, not the chip.  A program that holds
# a 64-bit sort takes the v5e compiler 8-13 s at a 2^13-row bucket and
# 30-60 s (once 199 s) from 2^14 up.  From SF2 up Q3's probe batches reach
# join._uranges_fn compacted to their exact, data-dependent row count: 24
# distinct shapes at SF2 and some 130 at SF10 (CPU rehearsals), each one such
# compile.  At SF1 the shapes are bucketed, and a cold run took 1154 s (my
# chip run, PR 22): Q3 cold 583 s, of which 595 s compile over two task
# threads, and Q3 "warm" 262 s because history-based planning re-planned it
# at other buckets (40 new programs).  SF0.25 keeps most of Q3's sort
# programs at 2^13 and leaves the limit a margin.  The cut is printed on an
# earlier line of every run; CHANGES.md (PR 22) has the seconds per program.
DEFAULT_SF = 0.25
SF_CUT_REASON = (
    "compile time, not the chip: SF10 loads and answers Q1/Q6 (6.8 GB "
    "resident), but every 64-bit sort program costs the v5e compiler "
    "30-60 s from a 2^14-row bucket up; from SF2 up Q3's join probe is "
    "compiled once per data-dependent batch size (24 such programs at SF2, "
    "~130 at SF10), and at SF1 a cold run took 1154 s of this script's "
    "1200 s")
# Rows per staged batch.  bench.py stages a table as ONE batch; at SF10 that
# is a 2^26-row bucket for every program over lineitem.  2^20 keeps every
# per-batch program at the bucket tier-1's slow compile twins hold.
BATCH_ROWS = 1 << 20
TINY_SF = 0.01
# --chips 4 runs the mesh path at a size that keeps the per-operator sort
# programs under the 2^14-row compile cliff: four chips are charged four
# times over, a cold run there is compile-bound like every other (the
# seam-merge program alone: 235 s compiled for v5e:2x2 in the sandbox, at any
# SF), and Q3's resident plan only exists while orders fits the broadcast
# row limit (SF <= 2).  Printed as a cut on every such run.
FOUR_CHIP_SF = 0.005
FOUR_CHIP_BATCH_ROWS = 1 << 13
FOUR_CHIP_CUT_REASON = (
    "four chips cost four times as much per second and a cold run is "
    "compile-bound at any size; this proves that the mesh programs compile, "
    "run and answer right on four chips, and where bytes land — not how "
    "much a deployment holds")
SMOKE_QUERIES = (1, 6, 3)
TABLES = ("lineitem", "orders", "customer")
FOUR_CHIP_TABLES = TABLES + ("supplier", "nation", "region")
_T0 = time.monotonic()


def say(msg: str) -> None:
    print(f"[{time.monotonic() - _T0:7.1f}s] {msg}", flush=True)


# ---------------------------------------------------------------- observing

class CompileLog(logging.Handler):
    """Backend compiles seen by this process: (program, argument shapes,
    seconds) per XLA compile — seconds from jax.monitoring, shapes from the
    'Compiling ...' debug line of JAX's lowering — plus persistent-cache
    hits.  The jitted programs carry no stable names yet (ROADMAP S2), so
    the shapes are what tells one ``jit(fn)`` from another."""

    _EVENT = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _LOGGER = "jax._src.interpreters.pxla"

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.compiles: list = []
        self.cache_hits = 0
        # tasks compile on their own threads: the 'Compiling' line and the
        # duration event of one compile arrive on the same one
        self._last = threading.local()

    def __enter__(self) -> "CompileLog":
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        lg = logging.getLogger(self._LOGGER)
        self._was = (lg.level, lg.propagate)
        lg.setLevel(logging.DEBUG)
        lg.propagate = False
        lg.addHandler(self)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)
        lg = logging.getLogger(self._LOGGER)
        lg.removeHandler(self)
        lg.setLevel(self._was[0])
        lg.propagate = self._was[1]

    def emit(self, record: logging.LogRecord) -> None:
        if str(record.msg).startswith("Compiling %s with global shapes"):
            self._last.shapes = re.sub(r"ShapedArray\(([^)]*)\)", r"\1",
                                       str(record.args[1]))

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event == self._EVENT:
            self.compiles.append((kw.get("fun_name", "?"),
                                  getattr(self._last, "shapes", ""), secs))

    def _on_event(self, event: str, **kw) -> None:
        if event == self._HIT:
            self.cache_hits += 1

    def mark(self) -> tuple:
        return len(self.compiles), self.cache_hits

    def since(self, mark: tuple = (0, 0)) -> dict:
        done = self.compiles[mark[0]:]
        return {"compiles": len(done),
                "compile_s": round(sum(c[2] for c in done), 2),
                "cache_hits": self.cache_hits - mark[1],
                "slow": [f"{c[2]:.1f}s {c[0]}{c[1][:160]}"
                         for c in done if c[2] >= 5.0]}


def _program_calls() -> dict:
    from trino_tpu.caching import executable_cache

    return {r["name"]: r["hits"] + r["misses"]
            for r in executable_cache.registry_stats()}


def device_report(cache_dir: str) -> dict:
    import jax
    import jaxlib

    devs = jax.devices()
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    say(f"device: platform={devs[0].platform} kind={devs[0].device_kind!r} "
        f"count={len(devs)} jax={jax.__version__} jaxlib="
        f"{jaxlib.__version__} libtpu={libtpu} compile_cache={cache_dir}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def hbm(dev) -> dict:
    """bytes in use / peak on one device, where the backend reports them."""
    st = dev.memory_stats() or {}
    return {"in_use": st.get("bytes_in_use"),
            "peak": st.get("peak_bytes_in_use"),
            "limit": st.get("bytes_limit")}


def _gb(n) -> str:
    if n is None:
        return "n/a"
    return f"{n / 1e9:.2f}GB" if n >= 1e9 else f"{n / 1e6:.1f}MB"


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
    """Tables with no device generator (customer, supplier, ...): the host
    page source, regrouped into ``rows``-row batches."""
    from trino_tpu.spi.batch import ColumnBatch

    whole = ColumnBatch.concat(list(_scan(tpch, table, cols, splits=4)))
    return [whole.slice(s, min(s + rows, whole.num_rows))
            for s in range(0, whole.num_rows, rows)]


def load_tables(sf: float, batch_rows: int, tables=TABLES):
    """The staging bench.py:_stage_memory_tables uses, in ``batch_rows``
    batches: TPC-H at ``sf`` resident in device memory behind the memory
    connector.  Returns (catalog, {table: live rows})."""
    from trino_tpu.connectors.catalog import default_catalog
    from trino_tpu.connectors.tpch import generate_table_device
    from trino_tpu.spi.connector import TableSchema

    catalog = default_catalog(scale_factor=sf)
    tpch = catalog.connector("tpch")
    mem = catalog.connector("memory")
    rows_of = {}
    for t in tables:
        t0 = time.monotonic()
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
        # a loaded table carries its source's statistics, as ANALYZE would
        # leave them (the host-side ANALYZE scan is no way to get them at
        # this size): without column NDVs the planner broadcasts lineitem
        mem.set_analyzed_statistics(t, tpch.get_table_statistics(t))
        rows_of[t] = n
        say(f"load: {t} sf={sf:g} rows={n} batches={len(chunks)} "
            f"x {batch_rows} in {time.monotonic() - t0:.1f}s")
    return catalog, rows_of


def _host_columns(catalog, table: str, cols: list) -> dict:
    """Live rows of ``cols`` pulled to the host through the connector's own
    page source (the plain-reference side; never inside a timed region)."""
    parts: dict = {c: [] for c in cols}
    for b in _scan(catalog.connector("memory"), table, cols):
        b = b.to_host()
        live = None if b.live is None else np.asarray(b.live)
        for c in cols:
            d = np.asarray(b.column(c).data)
            parts[c].append(d if live is None else d[live])
    return {c: np.concatenate(v) for c, v in parts.items()}


# ------------------------------------------------------------------ serving

def start_server(catalog, workers: int):
    """TrinoTpuServer over the in-process DistributedQueryRunner — the chip
    path today (README, 'Running').  ``workers`` tasks per stage, so the
    fragmenter, a PARTIAL->FINAL seam and an exchange exist on one chip."""
    from trino_tpu.execution.distributed_runner import DistributedQueryRunner
    from trino_tpu.runner import Session
    from trino_tpu.server.client import Client
    from trino_tpu.server.protocol import TrinoTpuServer

    runner = DistributedQueryRunner(
        catalog, worker_count=workers,
        session=Session(default_catalog="memory", node_count=workers))
    server = TrinoTpuServer(runner).start()
    host, port = server.address
    return runner, server, Client(host, port, timeout=1100.0)


def _decode(columns: list, rows: list) -> list:
    """Statement-protocol JSON back to python values (decimals and dates
    travel as strings)."""
    out = []
    for r in rows:
        vals = []
        for c, v in zip(columns, r):
            if v is not None and c["type"].startswith("decimal"):
                v = decimal.Decimal(v)
            elif v is not None and c["type"] == "date":
                v = datetime.date.fromisoformat(v)
            vals.append(v)
        out.append(tuple(vals))
    return out


def serve(client, runner, log: CompileLog, label: str, sql: str) -> list:
    """One statement over HTTP; prints what it cost and what ran."""
    import jax

    from trino_tpu.exec import kernels as K
    from trino_tpu.exec import syncguard
    from trino_tpu.telemetry.metrics import REGISTRY

    mark, sync0, calls0 = log.mark(), syncguard.snapshot(), _program_calls()
    fb0 = (runner.fused_fallbacks, runner.resident_fallbacks)
    t0 = time.perf_counter()
    columns, rows = client.execute(sql)
    wall = time.perf_counter() - t0
    sync = syncguard.take_delta(sync0)
    ran = {k: v - calls0.get(k, 0) for k, v in _program_calls().items()
           if v - calls0.get(k, 0)}
    comp = log.since(mark)
    say(f"{label}: wall={wall:.3f}s rows={len(rows)} "
        f"compiles={comp['compiles']} compile_s={comp['compile_s']} "
        f"persistent_cache_hits={comp['cache_hits']} "
        f"host_syncs={sync.host_syncs} blocking_syncs={sync.blocking_syncs} "
        f"peak_hbm={_gb(hbm(jax.devices()[0])['peak'])}")
    for line in comp["slow"]:
        say(f"{label}: compile {line}")
    say(f"{label}: hash_impl={K.hash_impl()} -> "
        f"{'pallas hash kernels' if K.hash_kernels_selected(1) else 'sort/searchsorted'}; "
        f"programs {json.dumps(ran, sort_keys=True)}")
    say(f"{label}: edges fused={len(runner._fused_edges)} "
        f"resident={len(runner._resident_edges)} "
        f"collective={len(runner._collective_edges)}; reruns on the "
        f"per-operator path: fused={runner.fused_fallbacks - fb0[0]} "
        f"resident={runner.resident_fallbacks - fb0[1]} "
        f"(trino_fused_fallbacks_total="
        f"{REGISTRY.snapshot()['trino_fused_fallbacks_total']['value']}); "
        f"reduce programs split after a compile failure="
        f"{len(K._FAILED_REDUCE_SPECS)}")
    return _decode(columns, rows)


# ----------------------------------------------------------------- checking

def _tiny_sql(sql: str) -> str:
    return re.sub(r"\b(lineitem|orders|customer)\b", r"tiny.\1", sql)


def check_tiny(client, runner, log: CompileLog) -> None:
    """Q1/Q6/Q3 at SF0.01 through the same server against the sqlite
    oracle.  The tiny tables are a second tpch connector in the same
    catalog, so this leg also drives the streamed scan path (host page
    source -> prefetch -> device_put) the pinned tables bypass."""
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.connectors.tpch_queries import QUERIES
    from trino_tpu.testing.oracle import SqliteOracle, assert_same_rows

    tiny = TpchConnector(scale_factor=TINY_SF)
    runner.catalog.register("tiny", tiny)
    oracle = SqliteOracle()
    for t in TABLES:
        oracle.load_table(
            t, _scan(tiny, t, tiny.get_table_schema(t).column_names()))
    for q in SMOKE_QUERIES:
        got = serve(client, runner, log, f"q{q} sf{TINY_SF:g}",
                    _tiny_sql(QUERIES[q]))
        assert_same_rows(got, oracle.query(QUERIES[q]))
        say(f"q{q} sf{TINY_SF:g}: {len(got)} rows equal the sqlite oracle's")


def _close(a, b, what: str) -> None:
    if not math.isclose(float(a), float(b), rel_tol=1e-9, abs_tol=1e-6):
        raise AssertionError(f"{what}: served {a} != numpy {b}")


def check_full(catalog, answers: dict, sf: float) -> None:
    """Q1 and Q6 at the full scale factor against a plain numpy evaluation
    over the columns pulled to the host."""
    from trino_tpu.bench_kernels import Q1Batch, q1_numpy

    t0 = time.monotonic()
    q1_cols = ["l_returnflag", "l_linestatus", "l_quantity",  # Q1Batch order
               "l_extendedprice", "l_discount", "l_tax", "l_shipdate"]
    cols = _host_columns(catalog, "lineitem", q1_cols)
    say(f"check: lineitem columns on the host, {len(cols['l_shipdate'])} "
        f"rows in {time.monotonic() - t0:.1f}s")
    uniq, ref = q1_numpy(Q1Batch(*(cols[c] for c in q1_cols)))
    got = answers[1]
    if len(got) != len(uniq):
        raise AssertionError(f"q1: {len(got)} groups != numpy {len(uniq)}")
    for i, row in enumerate(got):  # both ordered by (returnflag, linestatus)
        n = int(ref["count"][i])
        for j, want in enumerate((
                ref["qty"][i] / 100, ref["price"][i] / 100,
                ref["disc_price"][i] / 100, ref["charge"][i] / 100,
                ref["qty"][i] / 100 / n, ref["price"][i] / 100 / n,
                ref["disc"][i] / 100 / n)):
            _close(row[2 + j], want, f"q1 row {i} col {2 + j}")
        if row[9] != n:
            raise AssertionError(f"q1 row {i}: count {row[9]} != numpy {n}")
    say(f"q1 sf{sf:g}: {len(got)} groups equal the numpy evaluation")
    # Q6: 1994-01-01 <= shipdate < 1995-01-01, discount in [0.05, 0.07],
    # quantity < 24; scaled-int64 arithmetic, so the sum is exact
    d0 = (datetime.date(1994, 1, 1) - datetime.date(1970, 1, 1)).days
    d1 = (datetime.date(1995, 1, 1) - datetime.date(1970, 1, 1)).days
    m = ((cols["l_shipdate"] >= d0) & (cols["l_shipdate"] < d1)
         & (cols["l_discount"] >= 5) & (cols["l_discount"] <= 7)
         & (cols["l_quantity"] < 2400))
    want6 = decimal.Decimal(int(
        (cols["l_extendedprice"][m] * cols["l_discount"][m]).sum())
    ).scaleb(-4)
    if answers[6] != [(want6,)]:
        raise AssertionError(f"q6: served {answers[6]} != numpy {want6}")
    say(f"q6 sf{sf:g}: revenue {want6} equals the numpy evaluation")


# ---------------------------------------------------------------- one chip

def run_one_chip(sf: float, batch_rows: int, workers: int = 2) -> None:
    """load -> serve -> check.  Also the body tests/test_chip_smoke.py runs
    on the CPU at SF0.01."""
    import jax

    from trino_tpu.caching import result_cache
    from trino_tpu.connectors.tpch_queries import QUERIES
    from trino_tpu.execution.collective_exchange import collectives_available
    from trino_tpu.testing.oracle import assert_same_rows

    dev = jax.devices()[0]
    with CompileLog() as log:
        t0 = time.monotonic()
        catalog, rows_of = load_tables(sf, batch_rows)
        comp = log.since()
        say(f"load: sf={sf:g} rows={sum(rows_of.values())} resident="
            f"{_gb(hbm(dev)['in_use'])} peak={_gb(hbm(dev)['peak'])} of "
            f"{_gb(hbm(dev)['limit'])} in {time.monotonic() - t0:.1f}s "
            f"(compiles={comp['compiles']} compile_s={comp['compile_s']} "
            f"persistent_cache_hits={comp['cache_hits']})")
        for line in comp["slow"]:
            say(f"load: compile {line}")
        runner, server, client = start_server(catalog, workers)
        say(f"serve: TrinoTpuServer on {server.address}, "
            f"DistributedQueryRunner worker_count={workers}, result cache "
            f"off; collectives_available({workers})="
            f"{collectives_available(workers)} on {len(jax.devices())} "
            f"device(s) — it gates the collective exchange, the fused stage "
            f"and the resident plan alike, so expect the per-operator path")
        answers: dict = {}
        try:
            with result_cache.disabled():
                for q in SMOKE_QUERIES:
                    answers[q] = serve(client, runner, log,
                                       f"q{q} sf{sf:g} cold", QUERIES[q])
                    warm = serve(client, runner, log,
                                 f"q{q} sf{sf:g} warm", QUERIES[q])
                    # doubles (Q1's avgs) may differ in the last bits: the
                    # partial states merge in task-arrival order
                    assert_same_rows(warm, answers[q], ordered=True)
                check_tiny(client, runner, log)
        finally:
            server.stop()
        check_full(catalog, answers, sf)
        comp = log.since()
        say(f"done: compiles={comp['compiles']} compile_s={comp['compile_s']} "
            f"persistent_cache_hits={comp['cache_hits']} "
            f"peak_hbm={_gb(hbm(dev)['peak'])}")


# -------------------------------------------------------------- four chips

def run_four_chips(sf: float, batch_rows: int) -> None:
    """The path across chips: one process drives four devices.  Q1 (fused
    PARTIAL->FINAL seam), Q5 with the tiled raw-row all_to_all forced, Q3
    (resident plan), each compared with the same query on a one-task runner
    in this process."""
    import jax

    from trino_tpu.caching import result_cache
    from trino_tpu.connectors.tpch_queries import QUERIES
    from trino_tpu.execution import collective_exchange as CE
    from trino_tpu.execution.distributed_runner import DistributedQueryRunner
    from trino_tpu.runner import Session
    from trino_tpu.testing.oracle import assert_same_rows

    n = 4
    devs = jax.devices()[:n]
    if len(devs) < n:
        raise SystemExit(f"chip_smoke --chips 4: found {len(devs)} device(s)")

    def per_chip(label: str) -> None:
        say(f"{label}: bytes in use per chip "
            + " ".join(f"[{d.id}] {_gb(hbm(d)['in_use'])} "
                       f"(peak {_gb(hbm(d)['peak'])})" for d in devs))

    def runner_of(catalog, width: int):
        return DistributedQueryRunner(
            catalog, worker_count=width,
            session=Session(default_catalog="memory", node_count=width))

    def run(label: str, q: int, want: str) -> None:
        mark = log.mark()
        seen.clear()
        reruns = (wide.fused_fallbacks, wide.resident_fallbacks)
        t0 = time.perf_counter()
        got = wide.execute(QUERIES[q]).rows()
        wall = time.perf_counter() - t0
        edges = {"fused": len(wide._fused_edges),
                 "resident": len(wide._resident_edges),
                 "collective": len(wide._collective_edges)}
        comp = log.since(mark)
        say(f"{label}: wall={wall:.3f}s rows={len(got)} edges={edges} "
            f"compiles={comp['compiles']} compile_s={comp['compile_s']} "
            f"persistent_cache_hits={comp['cache_hits']} reruns on the "
            f"per-operator path: fused={wide.fused_fallbacks - reruns[0]} "
            f"resident={wide.resident_fallbacks - reruns[1]}")
        for line in comp["slow"]:
            say(f"{label}: compile {line}")
        say(f"{label}: device sets of the arrays entering the mesh programs "
            f"{sorted(seen)}")
        per_chip(label)
        if not edges[want]:
            raise AssertionError(f"{label}: no {want} edge engaged: {edges}")
        if {len(ids) for ids in seen} != {n}:
            raise AssertionError(
                f"{label}: mesh-program inputs do not all span {n} devices")
        t0 = time.perf_counter()
        assert_same_rows(got, narrow.execute(QUERIES[q]).rows())
        say(f"{label}: rows equal the one-device run's "
            f"({time.perf_counter() - t0:.3f}s, "
            f"{log.since(mark)['compiles'] - comp['compiles']} compiles)")

    with CompileLog() as log, _watch_mesh_inputs() as seen:
        catalog, _ = load_tables(sf, batch_rows, FOUR_CHIP_TABLES)
        per_chip("load")
        wide, narrow = runner_of(catalog, n), runner_of(catalog, 1)
        with result_cache.disabled():
            run("q1 x4 fused seam", 1, "fused")
            old = CE.TILED_THRESHOLD_ROWS
            CE.TILED_THRESHOLD_ROWS = 0
            os.environ["TRINO_TPU_FUSED_STAGE"] = "0"
            try:
                run("q5 x4 tiled all_to_all", 5, "collective")
            finally:
                CE.TILED_THRESHOLD_ROWS = old
                del os.environ["TRINO_TPU_FUSED_STAGE"]
            run("q3 x4 resident plan", 3, "resident")
        comp = log.since()
        say(f"done: compiles={comp['compiles']} "
            f"compile_s={comp['compile_s']}")


@contextlib.contextmanager
def _watch_mesh_inputs():
    """The device set of every global array the engine assembles for a mesh
    program: jax.make_array_from_single_device_arrays is the one door into
    the shard_map programs (collective exchange, fused seam, resident
    plan).  An observation made from the smoke, not an engine option."""
    import jax

    seen: set = set()
    make = jax.make_array_from_single_device_arrays

    def watching(shape, sharding, arrays, *a, **kw):
        seen.add(tuple(sorted(x.device.id for x in arrays)))
        return make(shape, sharding, arrays, *a, **kw)

    jax.make_array_from_single_device_arrays = watching
    try:
        yield seen
    finally:
        jax.make_array_from_single_device_arrays = make


# --------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--sf", type=float, default=None,
                    help="rehearsal size (default: the deployment's SF)")
    ap.add_argument("--batch-rows", type=int, default=None)
    args = ap.parse_args(argv)

    # history-based planning reads a durable per-user journal; a smoke run
    # starts from none, in a directory of its own that it removes
    journal = tempfile.mkdtemp(prefix="chip-smoke-journal-")
    was = os.environ.get("TRINO_TPU_JOURNAL_DIR")
    os.environ["TRINO_TPU_JOURNAL_DIR"] = journal
    try:
        return _main(args)
    finally:
        if was is None:
            del os.environ["TRINO_TPU_JOURNAL_DIR"]
        else:
            os.environ["TRINO_TPU_JOURNAL_DIR"] = was
        shutil.rmtree(journal, ignore_errors=True)


def _main(args) -> int:
    from trino_tpu.caching.executable_cache import init_compile_cache

    device = device_report(init_compile_cache())
    not_tpu = (f"chip_smoke: not a TPU — JAX reports platform "
               f"{device['platform']!r}; this script proves the chip path "
               f"and never passes anywhere else")
    if device["platform"] != "tpu" and args.sf is None:
        print(not_tpu, file=sys.stderr)
        return 1
    if args.chips == 4:
        sf = FOUR_CHIP_SF if args.sf is None else args.sf
        if args.sf is None:
            say(f"cut: SF{sf:g} instead of SF10 — {FOUR_CHIP_CUT_REASON}")
        run_four_chips(sf, args.batch_rows or FOUR_CHIP_BATCH_ROWS)
    else:
        sf = DEFAULT_SF if args.sf is None else args.sf
        if args.sf is None:
            say(f"cut: SF{sf:g} instead of SF10 — {SF_CUT_REASON}")
        run_one_chip(sf, args.batch_rows or BATCH_ROWS)
    if device["platform"] != "tpu":
        print(not_tpu + " (rehearsal body ran to the end)", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
