"""Async scan-ingest pipeline (exec/prefetch.py + ScanOperator async path).

Covers the ingest contracts: split-order preservation under concurrent
prefetch, queue backpressure under a tiny budget, early close on a satisfied
pushed-down LIMIT with splits in flight, coalescer correctness across
dictionary columns and dynamic-filter interaction, crash propagation from a
prefetch thread, and the scan's rows against the connector's page sources
read directly.
"""

import os
import threading
import time

import numpy as np
import pytest

from trino_tpu.exec.operators import ScanOperator
from trino_tpu.exec.prefetch import (
    BatchCoalescer,
    IngestConfig,
    PrefetchingPageSource,
    coalesce_pad,
)
from trino_tpu.spi.batch import Column, ColumnBatch
from trino_tpu.spi.connector import Connector, ConnectorPageSource, Split
from trino_tpu.spi.types import BIGINT, VARCHAR


def _bigint_batch(values):
    return ColumnBatch(["v"], [Column(BIGINT, np.asarray(values, np.int64))])


class _SlowSource(ConnectorPageSource):
    def __init__(self, batches, delay=0.0, fail_at=None):
        self._batches = list(batches)
        self._i = 0
        self._delay = delay
        self._fail_at = fail_at

    def get_next_batch(self):
        if self._fail_at is not None and self._i >= self._fail_at:
            raise RuntimeError("connector exploded")
        if self._delay:
            time.sleep(self._delay)
        b = self._batches[self._i]
        self._i += 1
        return b

    def is_finished(self):
        return self._i >= len(self._batches) and self._fail_at is None


class _FakeConnector(Connector):
    """N splits, each yielding its batches through a throttled source."""

    name = "fake"

    def __init__(self, per_split_batches, delay=0.0, fail_split=None,
                 fail_at=0):
        self._per_split = per_split_batches
        self._delay = delay
        self._fail_split = fail_split
        self._fail_at = fail_at
        self.opened = []

    def splits(self):
        return [Split("fake", "t", i) for i in range(len(self._per_split))]

    def create_page_source(self, split, columns):
        self.opened.append(split.info)
        fail = self._fail_at if split.info == self._fail_split else None
        return _SlowSource(self._per_split[split.info],
                           delay=self._delay, fail_at=fail)


def _drain(src):
    out = []
    while True:
        b = src.get_next_batch()
        if b is None:
            return out
        out.append(b)


def test_split_order_preserved():
    # split k contributes values [100k, 100k+5); concurrent workers must not
    # reorder them on the consumer side
    conn = _FakeConnector([
        [_bigint_batch([s * 100 + i]) for i in range(5)]
        for s in range(6)
    ], delay=0.002)
    cfg = IngestConfig(threads=3, queue_depth=4)
    src = PrefetchingPageSource(conn, conn.splits(), ["v"], config=cfg)
    got = [int(b.columns[0].data[0]) for b in _drain(src)]
    assert got == [s * 100 + i for s in range(6) for i in range(5)]
    assert src.stats.splits_opened == 6
    assert src.stats.scan_rows == 30


def test_backpressure_small_budget():
    conn = _FakeConnector([
        [_bigint_batch(list(range(64))) for _ in range(8)]
        for _ in range(4)
    ])
    cfg = IngestConfig(threads=2, queue_depth=2, queue_bytes=1)
    src = PrefetchingPageSource(conn, conn.splits(), ["v"], config=cfg)
    seen = 0
    while True:
        b = src.get_next_batch()
        if b is None:
            break
        seen += 1
        time.sleep(0.002)  # slow consumer: producers must park, not pile up
    assert seen == 32
    # bound = budget + one in-flight insert per producer thread + the
    # starved-consumer exemption
    assert src.stats.queue_depth_max <= cfg.queue_depth + cfg.threads + 1


def test_early_close_drops_unclaimed_splits():
    conn = _FakeConnector([
        [_bigint_batch([i]) for i in range(4)] for _ in range(8)
    ], delay=0.02)
    cfg = IngestConfig(threads=1, queue_depth=2)
    src = PrefetchingPageSource(conn, conn.splits(), ["v"], config=cfg)
    assert src.get_next_batch() is not None
    src.close()
    for t in src._threads:
        t.join(timeout=5.0)
    assert not any(t.is_alive() for t in src._threads)
    assert src.stats.splits_opened < 8  # unclaimed splits never opened
    assert src.get_next_batch() is None


def test_scan_limit_early_close(monkeypatch):
    monkeypatch.setenv("TRINO_TPU_COALESCE_TARGET_ROWS", "8")
    conn = _FakeConnector([
        [_bigint_batch(list(range(8))) for _ in range(2)] for _ in range(8)
    ], delay=0.02)
    scan = ScanOperator(conn, conn.splits(), ["v"], limit=8)
    rows = 0
    while not scan.is_finished():
        b = scan.get_output()
        if b is None:
            break
        rows += b.live_count
    scan.close()
    assert rows >= 8
    for t in scan._prefetcher._threads:
        t.join(timeout=5.0)
    # LIMIT satisfied after the first split: the prefetcher must not have
    # churned through all 8
    assert scan.ingest_stats.splits_opened < 8


@pytest.mark.parametrize("cores,threads", [(1, 1), (2, 1), (4, 3), (64, 4)])
def test_default_threads_follow_the_host(monkeypatch, cores, threads):
    # cores - 1, at least one (a one-core host prefetches too), at most 4
    monkeypatch.delenv("TRINO_TPU_PREFETCH_THREADS", raising=False)
    monkeypatch.setattr(os, "cpu_count", lambda: cores)
    assert IngestConfig.default_threads() == threads
    assert IngestConfig.from_env().threads == threads


def test_zero_threads_setting_still_prefetches(monkeypatch):
    monkeypatch.setenv("TRINO_TPU_PREFETCH_THREADS", "0")
    assert IngestConfig.from_env().threads == 1


def test_one_core_host_scan_runs_the_prefetcher(monkeypatch):
    monkeypatch.delenv("TRINO_TPU_PREFETCH_THREADS", raising=False)
    monkeypatch.setattr(os, "cpu_count", lambda: 1)
    conn = _FakeConnector([
        [_bigint_batch([s * 10 + i]) for i in range(3)] for s in range(3)
    ])
    scan = ScanOperator(conn, conn.splits(), ["v"])
    vals = []
    while not scan.is_finished():
        b = scan.get_output()
        if b is None:
            break
        vals.extend(v for (v,) in b.to_pylist())
    scan.close()
    assert vals == [s * 10 + i for s in range(3) for i in range(3)]
    assert len(scan._prefetcher._threads) == 1
    assert scan.ingest_stats.splits_opened == 3


def test_crash_in_prefetch_thread_propagates():
    conn = _FakeConnector(
        [[_bigint_batch([1])] for _ in range(3)],
        fail_split=1, fail_at=1)
    src = PrefetchingPageSource(conn, conn.splits(), ["v"],
                                config=IngestConfig(threads=2))
    with pytest.raises(RuntimeError, match="scan prefetch thread failed"):
        _drain(src)


def test_coalesce_pad_dictionary_and_valid():
    b1 = ColumnBatch.from_pydict({
        "s": (VARCHAR, ["apple", "pear", None]),
        "n": (BIGINT, [1, None, 3]),
    })
    b2 = ColumnBatch.from_pydict({
        "s": (VARCHAR, ["pear", "zebra"]),
        "n": (BIGINT, [4, 5]),
    })
    out = coalesce_pad([b1, b2])
    assert out.num_rows == 8  # 5 rows -> bucket 8
    assert out.live is not None and int(out.live.sum()) == 5
    assert out.compact().to_pylist() == [
        ("apple", 1), ("pear", None), (None, 3), ("pear", 4), ("zebra", 5)]


def test_coalescer_merges_to_target():
    c = BatchCoalescer(target_rows=16)
    for i in range(5):
        c.add(_bigint_batch(list(range(i * 6, i * 6 + 6))))
        if c.ready():
            break
    assert c.ready()
    out = c.flush()
    assert out.live_count == 18 and out.num_rows == 32
    assert c.flush() is None


def test_scan_dynamic_filter_with_coalescing(monkeypatch):
    from trino_tpu.exec.dynamic_filter import DynamicFilterHolder

    monkeypatch.setenv("TRINO_TPU_COALESCE_TARGET_ROWS", "32")
    conn = _FakeConnector([
        [_bigint_batch(list(range(s * 10, s * 10 + 10)))] for s in range(4)
    ])
    holder = DynamicFilterHolder()
    holder.fill(np.asarray([2, 3, 11, 35], np.int64), None, None)
    scan = ScanOperator(conn, conn.splits(), ["v"],
                        dynamic_filters=[(0, holder)])
    vals = []
    while not scan.is_finished():
        b = scan.get_output()
        if b is None:
            break
        vals.extend(v for (v,) in b.to_pylist())
    # range pruning keeps [2..35]; exact set keeps the 4 build values
    assert vals == [2, 3, 11, 35]
    assert holder.rows_pruned > 0
    assert scan.ingest_stats.coalesced_batches >= 1


def test_prefetch_off_matches_on(monkeypatch):
    # the scan (prefetched, coalesced, staged) yields exactly the rows of
    # the connector's page sources read directly, split by split, in order
    from trino_tpu.connectors.tpch import TpchConnector

    monkeypatch.setenv("TRINO_TPU_COALESCE_TARGET_ROWS", "4096")
    conn = TpchConnector(scale_factor=0.01, batch_rows=1000)
    cols = ["l_orderkey", "l_linenumber", "l_returnflag", "l_quantity",
            "l_comment"]
    splits = conn.get_splits("lineitem", 4, 1)
    assert len(splits) > 1
    want = []
    for split in splits:
        src = conn.create_page_source(split, cols)
        while not src.is_finished():
            b = src.get_next_batch()
            if b is not None:
                want.extend(b.to_pylist())
    scan = ScanOperator(conn, splits, cols)
    got = []
    while not scan.is_finished():
        b = scan.get_output()
        if b is None:
            break
        got.extend(b.to_pylist())
    scan.close()
    assert got == want and len(want) > 4096
    st = scan.ingest_stats
    assert 0 < st.coalesced_batches < st.scan_batches
    assert st.staged_batches == st.coalesced_batches


def test_scan_stats_in_query_stats(monkeypatch):
    from trino_tpu.runner import StandaloneQueryRunner

    r = StandaloneQueryRunner()
    rows = r.execute(
        "explain analyze select count(*) from orders").rows()
    text = "\n".join(str(v) for (v,) in rows)
    assert "scan[prefetch]" in text and "GB/s" in text
    # the execution span carries the trino.scan.* attributes
    spans = [s for root in r.tracer.finished for s in _walk(root)]
    scan_spans = [s for s in spans
                  if "trino.scan.gb-per-s" in s.attributes]
    assert scan_spans and any(
        s.attributes.get("trino.scan.rows", 0) > 0 for s in scan_spans)


def _walk(span):
    yield span
    for c in span.children:
        yield from _walk(c)


def test_backpressure_threads_exit_on_consumer_abandon():
    # a consumer that stops pulling and closes must unpark parked producers
    conn = _FakeConnector([
        [_bigint_batch(list(range(64))) for _ in range(4)]
        for _ in range(4)
    ])
    cfg = IngestConfig(threads=2, queue_depth=1, queue_bytes=1)
    src = PrefetchingPageSource(conn, conn.splits(), ["v"], config=cfg)
    assert src.get_next_batch() is not None
    src.close()
    for t in src._threads:
        t.join(timeout=5.0)
    assert not any(t.is_alive() for t in src._threads)
