"""Pallas kernels (ops/pallas_kernels.py) + the REAL-sum engine fast path
(exec/kernels.grouped_reduce).  Kernels run in interpret mode on the CPU
test mesh; tests/test_tpu_compile.py compiles the segment-sum kernel for
a described v5e device."""

import numpy as np

from trino_tpu.connectors.catalog import default_catalog
from trino_tpu.ops import pallas_kernels as PK
from trino_tpu.runner import StandaloneQueryRunner
from trino_tpu.testing.oracle import SqliteOracle, assert_same_rows


def test_masked_segment_sum_matches_numpy():
    rng = np.random.default_rng(0)
    n, g = 5000, 7
    vals = rng.standard_normal(n).astype(np.float32)
    gid = rng.integers(0, g, n).astype(np.int32)
    live = rng.random(n) > 0.3
    out = np.asarray(PK.masked_segment_sum_f32(
        vals, gid, live, g, interpret=True))
    expected = np.array([
        vals[(gid == k) & live].sum() for k in range(g)], np.float32)
    np.testing.assert_allclose(out[:g], expected, rtol=1e-4)


def test_masked_segment_sum_dead_rows_beyond_groups():
    # dead rows carry gid >= num_groups (the grouping kernel's contract)
    vals = np.ones(2048, np.float32)
    gid = np.full(2048, 9, np.int32)
    gid[:100] = 0
    out = np.asarray(PK.masked_segment_sum_f32(
        vals, gid, None, 4, interpret=True))
    assert out[0] == 100.0
    assert out[1:4].sum() == 0.0


def test_engine_real_sum_uses_pallas(monkeypatch):
    import trino_tpu.exec.kernels as K

    calls = []
    orig = K._pallas_f32_sum

    def spy(*a, **kw):
        r = orig(*a, **kw)
        calls.append(r is not None)
        return r

    monkeypatch.setattr(K, "_pallas_f32_sum", spy)
    monkeypatch.setenv("TRINO_TPU_PALLAS", "force")  # interpret mode on CPU
    catalog = default_catalog(scale_factor=0.01)
    runner = StandaloneQueryRunner(catalog)
    oracle = SqliteOracle()
    conn = catalog.connector("tpch")
    schema = conn.get_table_schema("lineitem")
    cols = schema.column_names()
    batches = []
    for s in conn.get_splits("lineitem", 2, 1):
        src = conn.create_page_source(s, cols)
        while not src.is_finished():
            b = src.get_next_batch()
            if b is not None:
                batches.append(b)
    oracle.load_table("lineitem", batches)
    # group on a NUMERIC key: dictionary-coded keys now take the masked
    # small-group path (kernels.small_grouped_aggregate) and never reach
    # the pallas f32 segment-sum; a non-dictionary key keeps the sort-based
    # path where the pallas fast lane lives
    sql = ("select l_linenumber, sum(cast(l_quantity as real)) "
           "from lineitem group by l_linenumber")
    result = runner.execute(sql).rows()
    assert calls and any(calls), "REAL sum did not route through pallas"
    assert_same_rows(result, oracle.query(sql))
