"""A page that stays in the process stays on the device (PR 36).

The exchange sink (execution/task.PartitionedOutputSink) hands a page on as
the producer made it -- device-resident, bucket-shaped, with its ``live``
mask -- wherever producer and consumer share the process, and pulls it to
the host and cuts it to its rows only where it is serialized (``serde``, an
FTE spool, the HTTP worker plane).  Every consumer's answer must equal the
dense path's, and the sqlite oracle's where SQL is involved."""

import threading

import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.caching import plan_cache, result_cache
from trino_tpu.connectors.catalog import default_catalog
from trino_tpu.connectors.tpch_queries import QUERIES
from trino_tpu.exec import kernels as K
from trino_tpu.exec import syncguard as SG
from trino_tpu.exec.revoking import batch_device_nbytes
from trino_tpu.execution import task as T
from trino_tpu.execution.distributed_runner import DistributedQueryRunner
from trino_tpu.execution.exchange import ExchangeClient, OutputBuffer
from trino_tpu.execution.serde import deserialize_batch
from trino_tpu.execution.task import PartitionedOutputSink, SerializedPage
from trino_tpu.runner import Session
from trino_tpu.spi.batch import Column, ColumnBatch
from trino_tpu.spi.types import BIGINT, BOOLEAN, DATE, DOUBLE, VARCHAR
from trino_tpu.telemetry import profiler
from trino_tpu.testing.oracle import SqliteOracle, assert_same_rows

TABLES = ("lineitem", "orders", "customer", "nation", "region")


@pytest.fixture(autouse=True)
def _one_chip_path(monkeypatch):
    """What one chip runs: no fused stage, no collective edge -- every
    fragment boundary is a PartitionedOutputSink over an OutputBuffer."""
    monkeypatch.setenv("TRINO_TPU_FUSED_STAGE", "0")
    monkeypatch.setenv("TRINO_TPU_RESIDENT_PLAN", "0")
    # the test's pages are small: without this every one of them would go
    # by the host (test_small_pages_go_by_the_host holds that rule)
    monkeypatch.setattr(T, "_RESIDENT_MIN_LANES", 1)


@pytest.fixture(scope="module")
def catalog():
    return default_catalog(scale_factor=0.01)


@pytest.fixture(scope="module")
def oracle(catalog):
    orc = SqliteOracle()
    orc.load_connector_tables(catalog.connector("tpch"), TABLES)
    return orc


def _dist(catalog, workers=2, **session):
    return DistributedQueryRunner(
        catalog, worker_count=workers,
        session=Session(node_count=workers, use_collectives=False, **session))


# ---------------------------------------------------------------- pages

def _device_batch(n_live: int, lanes: int, seed: int = 0) -> ColumnBatch:
    """``lanes`` lanes on the device, the first ``n_live`` of them live
    after a shuffle: bigint keys (some NULL), a double, a dictionary."""
    rng = np.random.default_rng(seed)
    live = np.zeros(lanes, bool)
    live[rng.permutation(lanes)[:n_live]] = True
    k = rng.integers(-50, 50, lanes)
    d = np.array(["a", "bb", "ccc", "dddd", "e"], dtype=object)
    return ColumnBatch(
        ["k", "x", "s"],
        [Column(BIGINT, jnp.asarray(k), jnp.asarray(rng.random(lanes) > 0.1)),
         Column(DOUBLE, jnp.asarray(rng.random(lanes))),
         Column(VARCHAR, jnp.asarray(rng.integers(0, 5, lanes, np.int32)),
                None, d)],
        jnp.asarray(live))


def _rows(pages) -> list:
    """The pages' live rows, sorted, each as its repr (NaN equals NaN)."""
    out = []
    for p in pages:
        if isinstance(p, SerializedPage):
            p = deserialize_batch(p.data)
        out.extend(repr(r) for r in p.to_pylist())
    return sorted(out)


def _drain(buf: OutputBuffer, partition: int) -> list:
    client = ExchangeClient([buf], partition)
    pages = []
    while not client.is_finished():
        p = client.poll(timeout=0)
        if p is not None:
            pages.append(p)
    return pages


def _through(kind, batches, n, keys=(), serde=False, **kw):
    buf = OutputBuffer(n)
    sink = PartitionedOutputSink(buf, kind, keys, serde=serde, **kw)
    for b in batches:
        sink.add_input(b)
    sink.finish_input()
    return buf, [_drain(buf, p) for p in range(n)]


def _is_pow2(n: int) -> bool:
    return n > 0 and n & (n - 1) == 0


@pytest.mark.parametrize("kind,n", [
    ("BROADCAST", 3), ("GATHER", 1), ("ROUND_ROBIN", 2), ("REPARTITION", 3),
    ("MERGE", 1)])
def test_sink_hands_on_masked_device_pages(kind, n):
    batches = [_device_batch(37, 128, seed=1), _device_batch(100, 128, seed=2)]
    want = _rows(batches)
    buf, parts = _through(kind, batches, n, keys=[0, 2])
    for pages in parts:
        for p in pages:
            assert isinstance(p, ColumnBatch)
            assert _is_pow2(p.num_rows)
            assert batch_device_nbytes(p) > 0, "page left the device"
            assert p.live is not None and not isinstance(p.live, np.ndarray)
    got = [_rows(pages) for pages in parts]
    if kind == "BROADCAST":
        assert all(g == want for g in got)
        assert buf.rows_enqueued == 137 * n
    else:
        assert sorted(sum(got, [])) == want
        assert buf.rows_enqueued == 137
    # bytes are those of the live rows, not of the lanes
    per_row = 8 + 1 + 8 + 4
    dict_bytes = batches[0].columns[2].nbytes - 128 * 4
    fan = n if kind == "BROADCAST" else 1
    pages_out = sum(len(p) for p in parts) // fan
    assert buf.bytes_enqueued == fan * (137 * per_row + pages_out * dict_bytes)


@pytest.mark.parametrize("serde", [False, True])
@pytest.mark.parametrize("kind", ["BROADCAST", "REPARTITION", "GATHER"])
def test_serialized_pages_are_dense_and_hold_the_same_rows(kind, serde):
    batches = [_device_batch(37, 128, seed=3)]
    n = 1 if kind == "GATHER" else 3
    _, dev = _through(kind, batches, n, keys=[0])
    _, wire = _through(kind, batches, n, keys=[0], serde=serde)
    for d_pages, w_pages in zip(dev, wire):
        assert _rows(d_pages) == _rows(w_pages)
        if serde:
            for p in w_pages:
                assert isinstance(p, SerializedPage)
                b = deserialize_batch(p.data)
                assert b.live is None and batch_device_nbytes(b) == 0


def _typed_keys(lanes=64):
    rng = np.random.default_rng(7)
    d = np.array([f"v{i}" for i in range(11)], dtype=object)
    return {
        "bigint": Column(BIGINT, jnp.asarray(rng.integers(-9, 9, lanes))),
        "bigint_null": Column(BIGINT, jnp.asarray(rng.integers(0, 5, lanes)),
                              jnp.asarray(rng.random(lanes) > 0.3)),
        "date": Column(DATE, jnp.asarray(
            rng.integers(9000, 9100, lanes, np.int32))),
        "double": Column(DOUBLE, jnp.asarray(
            np.where(rng.random(lanes) > 0.8, np.nan,
                     rng.integers(0, 4, lanes) / 3.0))),
        "boolean": Column(BOOLEAN, jnp.asarray(rng.random(lanes) > 0.5)),
        "varchar": Column(VARCHAR, jnp.asarray(
            rng.integers(0, 11, lanes, np.int32)), None, d),
        "varchar_null": Column(VARCHAR, jnp.asarray(
            rng.integers(0, 11, lanes, np.int32)),
            jnp.asarray(rng.random(lanes) > 0.3), d),
    }


@pytest.mark.parametrize("names", [
    ["bigint"], ["bigint_null"], ["date"], ["double"], ["boolean"],
    ["varchar"], ["varchar_null"], ["bigint", "varchar"],
    ["varchar_null", "double", "date"]])
@pytest.mark.parametrize("n", [2, 5])
def test_a_row_lands_in_the_same_partition_on_both_paths(names, n):
    """A stage may mix in-process and remote consumers: the device path's
    partition of a row is the host path's, for every key type the host
    path hashes (dictionary columns by VALUE hash)."""
    cols = _typed_keys()
    rid = Column(BIGINT, jnp.arange(64))
    batch = ColumnBatch(names + ["rid"], [cols[c] for c in names] + [rid],
                        jnp.asarray(np.arange(64) % 7 != 0))
    keys = list(range(len(names)))
    _, dev = _through("REPARTITION", [batch], n, keys=keys)
    _, host = _through("REPARTITION", [batch], n, keys=keys, serde=True)
    for p in range(n):
        assert _rows(dev[p]) == _rows(host[p])
    # and a dictionary's CODES do not decide: the same values under another
    # dictionary order route the same way
    if names == ["varchar"]:
        c = cols["varchar"]
        perm = np.random.default_rng(1).permutation(11)
        inv = np.argsort(perm)
        other = Column(VARCHAR, jnp.asarray(inv[np.asarray(c.data)]
                                            .astype(np.int32)),
                       None, c.dictionary[perm])
        b2 = ColumnBatch(["varchar", "rid"], [other, rid], batch.live)
        _, dev2 = _through("REPARTITION", [b2], n, keys=[0])
        for p in range(n):
            assert _rows(dev2[p]) == _rows(dev[p])


@pytest.mark.parametrize("kind,n", [("BROADCAST", 2), ("REPARTITION", 2),
                                    ("GATHER", 1)])
def test_all_dead_pages(kind, n):
    """An empty page is not known without a sync: it is enqueued (or, once
    its count has landed, dropped), and nothing downstream minds."""
    dead = _device_batch(0, 64, seed=5)
    buf, parts = _through(kind, [dead, _device_batch(3, 64, seed=6)], n,
                          keys=[0])
    assert buf.rows_enqueued == (3 * n if kind == "BROADCAST" else 3)
    assert sum(len(_rows(p)) for p in parts) == (3 * n if kind == "BROADCAST"
                                                else 3)
    buf, parts = _through(kind, [dead], n, keys=[0])
    assert buf.rows_enqueued == 0 and buf.finished
    assert all(_rows(p) == [] for p in parts)


@pytest.mark.parametrize("rows", [7, 8, 9, 255, 256, 257])
def test_dense_page_at_a_bucket_edge(rows):
    """A page that comes dense (a host operator's) takes its bucket: a
    consumer sees powers of two from every producer."""
    k = np.arange(rows, dtype=np.int64)
    batch = ColumnBatch(["k"], [Column(BIGINT, k)])
    buf, parts = _through("REPARTITION", [batch], 2, keys=[0])
    for pages in parts:
        for p in pages:
            assert _is_pow2(p.num_rows)
    assert sorted(sum((_rows(p) for p in parts), [])) == sorted(
        repr((i,)) for i in range(rows))
    assert buf.rows_enqueued == rows
    buf, (pages,) = _through("GATHER", [batch], 1)
    assert [p.num_rows for p in pages] == [K.bucket(rows)]
    assert buf.rows_enqueued == rows and buf.bytes_enqueued == rows * 8


def test_sparse_page_is_shrunk_to_a_bucket_once_for_all_consumers():
    lanes = 1 << 12
    batch = _device_batch(1000, lanes, seed=8)
    buf, parts = _through("BROADCAST", [batch], 3)
    pages = [p for pages in parts for p in pages]
    assert [p.num_rows for p in pages] == [1024] * 3
    assert pages[0] is pages[1] is pages[2], "shrunk once a consumer"
    assert _rows(parts[0]) == _rows([batch])
    assert buf.rows_enqueued == 3000
    # at least half live: left as it is
    full = _device_batch(lanes // 2 + 1, lanes, seed=9)
    _, ((page,),) = _through("GATHER", [full], 1)
    assert page.num_rows == lanes and page.columns[0].data is \
        full.columns[0].data


def test_a_waiting_page_keeps_the_stream_in_order():
    """The page that waits for its count holds later pages back: a MERGE
    stream's order is the producer's."""
    lanes = 1 << 12
    big = _device_batch(10, lanes, seed=10)
    small = _device_batch(5, 64, seed=11)
    _, ((first, second),) = _through("MERGE", [big, small], 1)
    assert _rows([first]) == _rows([big])
    assert _rows([second]) == _rows([small])


def test_coalescing_counts_live_rows_and_concatenates_to_a_bucket():
    batches = [_device_batch(40, 64, seed=s) for s in range(20, 26)]
    buf, parts = _through("REPARTITION", batches, 2, keys=[0],
                          coalesce_rows=60)
    assert sorted(sum((_rows(p) for p in parts), [])) == _rows(batches)
    for pages in parts:
        assert 1 <= len(pages) < len(batches)
        assert all(_is_pow2(p.num_rows) for p in pages)
    assert buf.rows_enqueued == 240


def test_backpressure_counts_lanes_and_memory_is_charged():
    from trino_tpu.exec.revoking import TaskMemoryContext

    batch = _device_batch(700, 1024, seed=12)
    buf = OutputBuffer(1)
    sink = PartitionedOutputSink(buf, "GATHER")
    mem = TaskMemoryContext(1 << 30)
    sink.attach_memory(mem)
    sink.add_input(batch)
    held = batch_device_nbytes(batch)
    # waiting for its count or in the buffer: the task's pool holds it
    assert mem.reserved_bytes() == held
    while sink._held:
        sink.needs_input()
    sink.add_input(_device_batch(0, 8, seed=1))  # accounts again
    assert buf._bytes == batch.nbytes        # the lanes, not the live rows
    assert buf.device_bytes == held
    # revoked: the page moves to the host, lanes and mask as they are
    assert sink.revoke_memory() == held and buf.device_bytes == 0
    sink.finish_input()
    assert mem.reserved_bytes() == 0
    (page,) = _drain(buf, 0)
    assert batch_device_nbytes(page) == 0 and page.num_rows == 1024
    assert _rows([page]) == _rows([batch])
    assert buf.rows_enqueued == 700 and buf._bytes == 0


def test_sink_event_says_what_it_handed_on():
    buf = OutputBuffer(2)
    sink = PartitionedOutputSink(buf, "BROADCAST")
    sink.add_input(_device_batch(9, 64, seed=13))
    assert sink.trace_attrs == {"exchange": "BROADCAST", "lanes": 64,
                                "handed": "device"}
    sink.finish_input()
    assert sink.trace_attrs == {"exchange": "BROADCAST", "pages": 1,
                                "lanes": 64, "live_rows": 9}
    wire = PartitionedOutputSink(OutputBuffer(1), "GATHER", serde=True)
    before = SG.snapshot()
    wire.add_input(_device_batch(9, 64, seed=13))
    assert wire.trace_attrs["handed"] == "densified"
    d = SG.take_delta(before)
    assert d.exchange_pages_densified == 1 and d.exchange_densified_bytes > 0
    assert "exchange pages 0 device/1 densified" in d.text()


@pytest.mark.parametrize("kind,n", [("BROADCAST", 2), ("REPARTITION", 3),
                                    ("GATHER", 1)])
def test_small_pages_go_by_the_host(kind, n, monkeypatch):
    """Under _RESIDENT_MIN_LANES lanes a page is pulled to the host and cut
    (one transfer beats a count program, its fetch and the consumers'
    launches over a few rows) -- and still leaves bucket-shaped, its live
    rows counted at once; from that many lanes up it stays on the device."""
    monkeypatch.setattr(T, "_RESIDENT_MIN_LANES", 256)
    small, large = _device_batch(37, 128, seed=14), _device_batch(
        200, 256, seed=15)
    buf, parts = _through(kind, [small], n, keys=[0])
    for pages in parts:
        for p in pages:
            assert batch_device_nbytes(p) == 0 and _is_pow2(p.num_rows)
            assert p.live is None or isinstance(p.live, np.ndarray)
    fan = n if kind == "BROADCAST" else 1
    assert buf.rows_enqueued == 37 * fan
    assert sorted(sum((_rows(p) for p in parts), [])) == sorted(
        _rows([small]) * fan)
    _, parts = _through(kind, [large], n, keys=[0])
    assert all(batch_device_nbytes(p) > 0 for pages in parts for p in pages)
    assert sorted(sum((_rows(p) for p in parts), [])) == sorted(
        _rows([large]) * fan)
    # a small page behind a waiting large one keeps its place in the stream
    _, ((first, second),) = _through("MERGE", [large, small], 1)
    assert _rows([first]) == _rows([large])
    assert _rows([second]) == _rows([small])


# ---------------------------------------------------------------- queries

_SQL = {
    "broadcast_join_build": (
        "select n_name, count(*), sum(c_acctbal) from customer, nation "
        "where c_nationkey = n_nationkey and n_regionkey < 3 "
        "group by n_name", False),
    "repartition_final_aggregation": (
        "select l_orderkey, sum(l_quantity), count(*) from lineitem "
        "where l_shipdate > date '1995-03-15' group by l_orderkey", False),
    "repartition_dictionary_keys": (
        "select l_shipmode, l_returnflag, count(*), min(l_shipdate) "
        "from lineitem group by l_shipmode, l_returnflag", False),
    "partitioned_join_probe": (
        "select o_orderpriority, count(*), sum(l_extendedprice) "
        "from orders right join lineitem on o_orderkey = l_orderkey "
        "and o_orderdate < date '1995-03-15' group by o_orderpriority",
        False),
    "gather_output": (
        "select o_orderkey, o_totalprice from orders "
        "where o_orderdate < date '1992-02-01'", False),
    "merge_order_by": (
        "select c_custkey, c_acctbal from customer where c_acctbal > 9000 "
        "order by c_acctbal desc, c_custkey", True),
    "all_dead_build_and_probe": (
        "select count(*), sum(l_quantity) from lineitem, orders "
        "where l_orderkey = o_orderkey and o_orderdate < date '1900-01-01'",
        False),
    "semi_join_over_exchange": (
        "select count(*) from orders where o_custkey in "
        "(select c_custkey from customer where c_acctbal < 0)", False),
    "q3": (QUERIES[3], True),
    "q18": (QUERIES[18], True),
}


@pytest.fixture(scope="module")
def runners(catalog):
    return {
        "device": _dist(catalog),
        "serde": _dist(catalog, exchange_serde=True),
        "fte": _dist(catalog, retry_policy="TASK"),
    }


@pytest.mark.parametrize("path", ["device", "serde", "fte"])
@pytest.mark.parametrize("name", sorted(_SQL))
def test_answers_equal_the_oracle_on_every_path(runners, oracle, name, path):
    sql, ordered = _SQL[name]
    before = SG.snapshot()
    with result_cache.disabled():
        rows = runners[path].execute(sql).rows()
    assert_same_rows(rows, oracle.query(sql), ordered=ordered)
    d = SG.take_delta(before)
    if path == "device":
        assert d.exchange_pages_device > 0 and d.exchange_pages_densified == 0
    else:
        assert d.exchange_pages_densified > 0 and d.exchange_pages_device == 0


def test_remote_http_consumer_receives_dense_serialized_pages(oracle):
    """The HTTP worker plane serializes: a worker process's sink densifies
    and the coordinator's answer is the oracle's."""
    from trino_tpu.execution.remote import ProcessDistributedQueryRunner

    runner = ProcessDistributedQueryRunner(
        {"factory": "trino_tpu.connectors.catalog:default_catalog",
         "kwargs": {"scale_factor": 0.01}},
        worker_count=2, session=Session(node_count=2),
        env_overrides={
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
    try:
        for name in ("repartition_final_aggregation", "broadcast_join_build",
                     "merge_order_by"):
            sql, ordered = _SQL[name]
            assert_same_rows(runner.execute(sql).rows(), oracle.query(sql),
                             ordered=ordered)
    finally:
        runner.close()


def test_no_compact_between_sink_and_consumer(catalog, monkeypatch):
    """Producer and consumer in one process, ``exchange_serde`` off: nothing
    on the way from PartitionedOutputSink.add_input to the consumer's first
    program pulls a page to the host."""
    calls = []
    in_sink = threading.local()
    compact, to_host = ColumnBatch.compact, ColumnBatch.to_host
    add_input = PartitionedOutputSink.add_input

    def spy_add(self, batch):
        in_sink.on = True
        try:
            return add_input(self, batch)
        finally:
            in_sink.on = False

    def spy_compact(self):
        if getattr(in_sink, "on", False) and batch_device_nbytes(self):
            calls.append("compact")
        return compact(self)

    def spy_to_host(self):
        if getattr(in_sink, "on", False) and batch_device_nbytes(self):
            calls.append("to_host")
        return to_host(self)

    monkeypatch.setattr(PartitionedOutputSink, "add_input", spy_add)
    monkeypatch.setattr(ColumnBatch, "compact", spy_compact)
    monkeypatch.setattr(ColumnBatch, "to_host", spy_to_host)
    with result_cache.disabled():
        _dist(catalog).execute(QUERIES[3]).rows()
    assert calls == []


def test_q3_twice_no_blocking_fetch_in_add_input_and_equal_counts(
        catalog, monkeypatch, tmp_path):
    """Q3 twice through DistributedQueryRunner(worker_count=2) under
    SG.forbidden(): no blocking fetch inside the sink's add_input; the
    buffers' live-row counts are equal over the runs, so the history's epoch
    holds and the second run's plan-cache lookup hits."""
    monkeypatch.setenv("TRINO_TPU_HBO", "1")
    monkeypatch.setenv("TRINO_TPU_JOURNAL_DIR", str(tmp_path))
    from trino_tpu.planner import history
    from trino_tpu.telemetry import journal

    journal.reset_for_test()
    history.reset_for_test()
    plan_cache.reset_for_test()
    add_input = PartitionedOutputSink.add_input

    def hot_add(self, batch):
        with SG.hot_region():
            return add_input(self, batch)

    monkeypatch.setattr(PartitionedOutputSink, "add_input", hot_add)
    counts = []
    record = history.record_query_stats

    def spy_record(fragments, stages, *a, **kw):
        counts.append(sorted(
            (fid, sum(b.rows_enqueued for b in st.buffers),
             sum(b.bytes_enqueued for b in st.buffers))
            for fid, st in stages.items() if getattr(st, "buffers", None)))
        return record(fragments, stages, *a, **kw)

    # the runner imports it at every query's end
    monkeypatch.setattr(history, "record_query_stats", spy_record)
    dist = _dist(catalog)
    rows = []
    started = profiler.now()
    try:
        with result_cache.disabled(), SG.forbidden():
            for _ in range(5):
                rows.append(dist.execute(QUERIES[3]).rows())
    finally:
        journal.reset_for_test()
        history.reset_for_test()
    assert all(r == rows[0] for r in rows)
    # live rows, not lanes (Q3 at SF0.01 joins 276 customers, 14,424 orders),
    # and equal from run to run: from the second on to the byte (the first
    # run's plan has a FINAL stage of two tasks, the history's re-plan one)
    assert len(counts) == 5 and all(c == counts[1] for c in counts[1:])
    assert [r for _, r, _ in counts[0][2:]] == [97, 14424, 276], counts[0]
    assert [c[2:] for c in counts] == [counts[0][2:]] * 5
    stats = plan_cache.stats()
    # the table settles after the first runs' records (PR 35: Q3's fourth
    # lookup on); were a count to wobble, every lookup would miss
    assert stats["hits"] >= 1, stats
    events = [e for e in profiler.events_since(started)
              if e.get("name", "").startswith("PartitionedOutputSink")]
    handed = [e["args"]["handed"] for e in events
              if not e["name"].endswith(".finish") and e.get("args")]
    # ``host``: what a host operator made (the output stage's rows)
    assert "device" in handed and "densified" not in handed
