"""Memory accounting + HBM->host revocation (reference:
memory/MemoryPool.java:44, execution/MemoryRevokingScheduler.java:47,
lib/trino-memory-context)."""

import numpy as np
import pytest

from trino_tpu.exec.operators import SortOperator
from trino_tpu.exec.revoking import TaskMemoryContext, batch_device_nbytes
from trino_tpu.planner.plan import SortKey
from trino_tpu.runner import Session, StandaloneQueryRunner
from trino_tpu.spi.batch import Column, ColumnBatch
from trino_tpu.spi.memory import (
    AggregatedMemoryContext,
    ExceededMemoryLimitError,
    MemoryPool,
)
from trino_tpu.spi.types import BIGINT


def test_pool_and_context_roundtrip():
    pool = MemoryPool("hbm", 1000)
    root = AggregatedMemoryContext(pool=pool)
    a = root.new_local("a")
    b = root.new_local("b")
    a.set_bytes(400)
    b.set_bytes(500)
    assert pool.reserved == 900
    with pytest.raises(ExceededMemoryLimitError):
        a.set_bytes(600)
    a.set_bytes(0)
    b.set_bytes(0)
    assert pool.reserved == 0


def _device_batch(n):
    import jax.numpy as jnp

    return ColumnBatch(
        ["k"], [Column(BIGINT, jnp.arange(n, dtype=jnp.int64))])


def test_revocation_evicts_device_batches_to_host():
    mem = TaskMemoryContext(hbm_limit_bytes=64 * 1024)
    op = SortOperator([SortKey(0)])
    op.attach_memory(mem)
    # each batch = 8KB on device; 64KB pool forces eviction along the way
    for _ in range(20):
        op.add_input(_device_batch(1024))
    assert getattr(op, "spill_count", 0) >= 1
    assert mem.reserved_bytes() <= 64 * 1024
    # evicted batches are host numpy now
    host = sum(1 for b in op._batches if batch_device_nbytes(b) == 0)
    assert host >= 1
    op.finish_input()
    out = op.get_output()
    # device sort emits a bucket-padded batch; live rows carry the data
    assert out.live_count == 20 * 1024  # nothing lost


def test_disk_spill_tier():
    """Host-buffered batches over the threshold go to a serde spill file
    and come back at finish with identical results."""
    import trino_tpu.exec.operators as OPS

    session = Session(spill_to_disk_bytes=64 * 1024)
    runner = StandaloneQueryRunner(session=session)
    spills = []
    orig = OPS.BufferedInputMixin._maybe_spill_to_disk

    def spy(self):
        orig(self)
        sp = getattr(self, "_spiller", None)
        if sp is not None and sp.pages_spilled:
            spills.append(sp.pages_spilled)

    OPS.BufferedInputMixin._maybe_spill_to_disk = spy
    try:
        rows = runner.execute(
            "select l_orderkey, o_orderdate from lineitem, orders "
            "where l_orderkey = o_orderkey order by l_orderkey, o_orderdate "
            "limit 5").rows()
    finally:
        OPS.BufferedInputMixin._maybe_spill_to_disk = orig
    assert spills, "expected disk spills with a 64KB threshold"
    plain = StandaloneQueryRunner().execute(
        "select l_orderkey, o_orderdate from lineitem, orders "
        "where l_orderkey = o_orderkey order by l_orderkey, o_orderdate "
        "limit 5").rows()
    assert rows == plain


def test_spiller_roundtrip():
    import numpy as np

    from trino_tpu.exec.spill import Spiller
    from trino_tpu.spi.batch import Column, ColumnBatch

    sp = Spiller()
    batches = [
        ColumnBatch(["x"], [Column(BIGINT, np.arange(i, i + 5, dtype=np.int64))])
        for i in range(0, 20, 5)
    ]
    for b in batches:
        sp.spill(b)
    back = list(sp.read_back())
    sp.close()
    assert [b.to_pylist() for b in back] == [b.to_pylist() for b in batches]


def test_query_larger_than_pool_completes():
    """A join+sort query whose device buffers exceed a tiny HBM pool must
    finish (by spilling to host RAM) with correct results."""
    session = Session(hbm_limit_bytes=256 * 1024)  # 256 KB
    runner = StandaloneQueryRunner(session=session)
    rows = runner.execute(
        "select l_orderkey, count(*) from lineitem, orders "
        "where l_orderkey = o_orderkey group by l_orderkey "
        "order by l_orderkey limit 5").rows()
    assert len(rows) == 5
    unlimited = StandaloneQueryRunner()
    assert rows == unlimited.execute(
        "select l_orderkey, count(*) from lineitem, orders "
        "where l_orderkey = o_orderkey group by l_orderkey "
        "order by l_orderkey limit 5").rows()


@pytest.mark.parametrize("keys, spills_states", [
    # the Q1 shape (6 groups) streams into a running masked-aggregation
    # state since PR 30: nothing is buffered, so there is nothing to spill
    ("l_returnflag, l_linestatus", False),
    # 3 x 2 x 7 x 4 = 168 groups, over MASKED_AGG_LIMIT: the codes-sort
    # path buffers its input, and the 1-byte budget spills its states
    ("l_returnflag, l_linestatus, l_shipmode, l_shipinstruct", True),
])
def test_partitioned_state_spill_agg(keys, spills_states):
    """Q1-style aggregation at a forced tiny disk budget: an operator that
    buffers pre-aggregates to mergeable states, hash-partitions them to
    spill files, and merges partition-by-partition at finish — results
    exact, spill_count > 0 (reference: SpillableHashAggregationBuilder
    .java); one that streams holds only its state and never spills."""
    import trino_tpu.exec.operators as OPS
    from trino_tpu.connectors.catalog import default_catalog
    from trino_tpu.runner import StandaloneQueryRunner
    from trino_tpu.testing.oracle import assert_same_rows

    spills = []
    orig = OPS.HashAggregationOperator._spill_states

    def spy(self):
        orig(self)
        spills.append(self.spill_count)

    session = Session(default_catalog="tpch", spill_to_disk_bytes=1)
    runner = StandaloneQueryRunner(default_catalog(scale_factor=0.05),
                                   session=session)
    baseline = StandaloneQueryRunner(default_catalog(scale_factor=0.05))
    nk = len(keys.split(","))
    sql = (f"select {keys}, sum(l_quantity), "
           "avg(l_extendedprice), count(*), min(l_discount), "
           f"max(l_shipdate) from lineitem group by {keys} "
           f"order by {', '.join(str(i + 1) for i in range(nk))}")
    OPS.HashAggregationOperator._spill_states = spy
    try:
        got = runner.execute(sql).rows()
    finally:
        OPS.HashAggregationOperator._spill_states = orig
    if spills_states:
        assert spills, "agg never spilled despite the 1-byte budget"
    else:
        assert not spills, "a streaming aggregation buffered its input"
    want = baseline.execute(sql).rows()
    assert_same_rows(got, want, ordered=True)


def test_partitioned_spill_high_cardinality():
    """High-cardinality grouped sum under spill: groups cross spill events
    and must merge exactly across partitions."""
    import trino_tpu.exec.operators as OPS
    from trino_tpu.connectors.catalog import default_catalog
    from trino_tpu.runner import StandaloneQueryRunner
    from trino_tpu.testing.oracle import assert_same_rows

    session = Session(default_catalog="tpch", spill_to_disk_bytes=1,
                      splits_per_node=4)
    runner = StandaloneQueryRunner(default_catalog(scale_factor=0.02),
                                   session=session)
    baseline = StandaloneQueryRunner(default_catalog(scale_factor=0.02))
    sql = ("select l_orderkey, sum(l_quantity), count(*) from lineitem "
           "group by l_orderkey")
    got = runner.execute(sql).rows()
    want = baseline.execute(sql).rows()
    assert_same_rows(got, want, ordered=False)
