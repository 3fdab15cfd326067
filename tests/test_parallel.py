"""Static/SPMD aggregation kernels + driver entry points on the 8-device
CPU mesh (the DistributedQueryRunner-style in-process multi-node check)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from trino_tpu.parallel.static_agg import AggSpec, static_grouped_agg
from trino_tpu.parallel.distributed import (
    broadcast_gather,
    distributed_grouped_agg,
    make_mesh,
)


def test_static_agg_matches_numpy():
    rng = np.random.RandomState(1)
    n = 512
    keys = jnp.asarray(rng.randint(0, 7, n).astype(np.int64))
    x = jnp.asarray(rng.rand(n))
    mask = jnp.asarray(rng.rand(n) < 0.8)
    r = static_grouped_agg(
        [keys], [None],
        [(AggSpec("sum", jnp.float64), x, None),
         (AggSpec("min", jnp.float64), x, None),
         (AggSpec("count_star", jnp.int64), None, None)],
        cap=16, row_mask=mask)
    kk = np.asarray(keys)[np.asarray(mask)]
    xx = np.asarray(x)[np.asarray(mask)]
    used = np.asarray(r.slot_used)
    assert int(r.num_groups) == len(np.unique(kk))
    got = {int(k): (float(s), float(m), int(c)) for k, s, m, c, u in zip(
        np.asarray(r.keys[0]), np.asarray(r.values[0]),
        np.asarray(r.values[1]), np.asarray(r.values[2]), used) if u}
    for k in np.unique(kk):
        sel = xx[kk == k]
        s, m, c = got[int(k)]
        assert np.isclose(s, sel.sum()) and np.isclose(m, sel.min())
        assert c == len(sel)


def test_static_agg_nullable_keys_and_values_match_numpy():
    # two keys (one nullable), nullable values, a row mask: sum / count(*)
    # / min per group against numpy; NULL keys form their own group
    rng = np.random.default_rng(11)
    n, cap = 3000, 1024
    k1, v1 = rng.integers(0, 200, n).astype(np.int64), rng.random(n) < 0.9
    k2 = rng.integers(0, 3, n).astype(np.int64)
    data, dval = rng.standard_normal(n), rng.random(n) < 0.85
    mask = rng.random(n) < 0.9
    r = static_grouped_agg(
        [jnp.asarray(k1), jnp.asarray(k2)], [jnp.asarray(v1), None],
        [(AggSpec("sum", jnp.float64), jnp.asarray(data), jnp.asarray(dval)),
         (AggSpec("count_star", jnp.int64), None, None),
         (AggSpec("min", jnp.float64), jnp.asarray(data), jnp.asarray(dval))],
        cap, row_mask=jnp.asarray(mask))
    want = {}
    for i in np.flatnonzero(mask):
        key = (int(k1[i]) if v1[i] else None, int(k2[i]))
        want.setdefault(key, []).append(i)
    assert int(r.num_groups) == len(want) <= cap
    got = {}
    kv = np.asarray(r.key_valids[0])
    vals = [np.asarray(x) for x in r.values]
    valid = [None if x is None else np.asarray(x) for x in r.value_valids]
    for s in np.flatnonzero(np.asarray(r.slot_used)):
        key = (int(np.asarray(r.keys[0])[s]) if kv[s] else None,
               int(np.asarray(r.keys[1])[s]))
        got[key] = (vals[0][s], valid[0][s], int(vals[1][s]), vals[2][s],
                    valid[2][s])
    assert set(got) == set(want)
    for key, rows in want.items():
        rows = np.asarray(rows)
        xs = data[rows][dval[rows]]
        s_, s_ok, cnt, mn, mn_ok = got[key]
        assert cnt == len(rows)
        assert bool(s_ok) == bool(mn_ok) == (len(xs) > 0)
        if len(xs):
            assert np.isclose(s_, xs.sum()) and mn == xs.min()


def test_static_agg_overflow_signal():
    keys = jnp.arange(32, dtype=jnp.int64)
    x = jnp.ones(32)
    r = static_grouped_agg([keys], [None],
                           [(AggSpec("sum", jnp.float64), x, None)], cap=8)
    assert int(r.num_groups) == 32  # exceeds cap -> caller re-runs bigger


def test_distributed_agg_8dev():
    mesh = make_mesh(8)
    rng = np.random.RandomState(2)
    n = 256
    keys = jnp.asarray(rng.randint(0, 6, n).astype(np.int64))
    x = jnp.asarray(np.arange(n, dtype=np.float64))
    mask = jnp.ones(n, bool)
    fn = distributed_grouped_agg(
        mesh, "x", [jnp.int64],
        [AggSpec("sum", jnp.float64), AggSpec("count_star", jnp.int64)], cap=8)
    (okeys,), (osums, ocnt), used, overflow = fn(keys, x, x, mask)
    assert int(np.asarray(overflow).max()) <= 8
    got = {}
    for k, s, c, u in zip(*map(np.asarray, (okeys, osums, ocnt, used))):
        if u:
            got[int(k)] = (float(s), int(c))
    kk, xx = np.asarray(keys), np.asarray(x)
    for k in np.unique(kk):
        sel = xx[kk == k]
        assert got[int(k)] == (float(sel.sum()), len(sel))
    assert sum(c for _, c in got.values()) == n


def test_broadcast_gather():
    mesh = make_mesh(8)
    x = jnp.arange(64, dtype=jnp.int64)
    out = broadcast_gather(mesh, "x")(x)
    assert np.asarray(out).shape == (64,)
    assert (np.asarray(out) == np.arange(64)).all()


def test_graft_entry_singlechip():
    import __graft_entry__ as g

    fn, args = g.entry()
    keys, values, used = jax.jit(fn)(*args)
    jax.block_until_ready(values)
    counts = np.asarray(values[-1])
    assert counts[np.asarray(used)].sum() > 0


def test_graft_dryrun_multichip():
    import __graft_entry__ as g

    g.dryrun_multichip(8)


def test_intra_task_parallel_drivers():
    """task_concurrency forks multi-split scans into concurrent source
    driver chains merged through the local gather exchange
    (LocalExchange.java:67 role) — results identical, >1 source chain."""
    from trino_tpu.connectors.catalog import default_catalog
    from trino_tpu.exec.operators import UnionSinkOperator
    from trino_tpu.runner import Session, StandaloneQueryRunner

    catalog = default_catalog(scale_factor=0.01)
    par = StandaloneQueryRunner(
        catalog, session=Session(task_concurrency=4, splits_per_node=8))
    seq = StandaloneQueryRunner(catalog)
    sqls = [
        "select l_returnflag, count(*), sum(l_quantity) from lineitem "
        "group by l_returnflag order by 1",
        "select count(*) from lineitem, orders where l_orderkey = o_orderkey "
        "and o_orderdate < date '1995-01-01'",
        "select max(l_extendedprice) from lineitem where l_discount > 0.05",
    ]
    for sql in sqls:
        assert par.execute(sql).rows() == seq.execute(sql).rows()
    # the plan really forked: count parallel sink chains
    from trino_tpu.exec.local_exchange import LocalExchangeSinkOperator
    from trino_tpu.exec.local_planner import LocalPlanner

    lp = LocalPlanner(catalog, splits_per_node=8, task_concurrency=4)
    plan = lp.plan(par.create_plan(sqls[0]))
    sinks = sum(1 for p in plan.pipelines
                if isinstance(p[-1], LocalExchangeSinkOperator))
    assert sinks >= 2, f"expected parallel source chains, got {sinks}"


def test_parallel_partitioned_aggregation_drivers():
    """Grouped aggregation behind a multi-split scan runs task_concurrency
    PARALLEL aggregation drivers fed by a HASH local exchange
    (AddLocalExchanges.java:111 + LocalExchange.java:67) — not just
    parallel sources; results identical to sequential."""
    from trino_tpu.connectors.catalog import default_catalog
    from trino_tpu.exec.local_exchange import (
        HASH,
        LocalExchangeSourceOperator,
    )
    from trino_tpu.exec.local_planner import LocalPlanner
    from trino_tpu.exec.operators import HashAggregationOperator
    from trino_tpu.runner import Session, StandaloneQueryRunner

    catalog = default_catalog(scale_factor=0.01)
    sql = ("select o_custkey, count(*), sum(o_totalprice) from orders "
           "group by o_custkey order by 2 desc, 1 limit 7")
    lp = LocalPlanner(catalog, splits_per_node=8, task_concurrency=4)
    runner = StandaloneQueryRunner(
        catalog, session=Session(task_concurrency=4, splits_per_node=8))
    plan = lp.plan(runner.create_plan(sql))
    agg_drivers = [
        p for p in plan.pipelines
        if isinstance(p[0], LocalExchangeSourceOperator)
        and any(isinstance(op, HashAggregationOperator) for op in p)
    ]
    assert len(agg_drivers) >= 2, "expected parallel aggregation drivers"
    assert agg_drivers[0][0].exchange.mode == HASH
    seq = StandaloneQueryRunner(catalog)
    assert runner.execute(sql).rows() == seq.execute(sql).rows()


def test_parallel_join_probe_drivers():
    """INNER-join probes clone into every parallel chain (each probing the
    shared build bridge) and a downstream grouped agg still partitions."""
    from trino_tpu.connectors.catalog import default_catalog
    from trino_tpu.runner import Session, StandaloneQueryRunner

    catalog = default_catalog(scale_factor=0.01)
    par = StandaloneQueryRunner(
        catalog, session=Session(task_concurrency=4, splits_per_node=8))
    seq = StandaloneQueryRunner(catalog)
    sql = ("select o_orderpriority, count(*) from lineitem, orders "
           "where l_orderkey = o_orderkey and l_shipdate > date '1996-01-01' "
           "group by o_orderpriority order by 1")
    assert par.execute(sql).rows() == seq.execute(sql).rows()


def test_local_exchange_backpressure_bounded():
    """A producer flooding a bounded local exchange parks instead of
    buffering unboundedly (the isBlocked() contract)."""
    import numpy as np

    from trino_tpu.exec.local_exchange import (
        GATHER,
        LocalExchange,
        LocalExchangeSinkOperator,
    )
    from trino_tpu.spi.batch import Column, ColumnBatch
    from trino_tpu.spi.types import BIGINT

    ex = LocalExchange(1, 1, GATHER, buffer_batches=2)
    sink = LocalExchangeSinkOperator(ex, 0, ["x"])
    b = ColumnBatch(["x"], [Column(BIGINT, np.arange(4))])
    assert sink.needs_input()
    sink.add_input(b)
    sink.add_input(b)
    assert not sink.needs_input()  # full: producer parks
    assert ex.poll(0) is not None
    assert sink.needs_input()  # drained below the bound: resumes


def test_intra_task_parallel_distributed():
    from trino_tpu.connectors.catalog import default_catalog
    from trino_tpu.execution.distributed_runner import DistributedQueryRunner
    from trino_tpu.runner import Session, StandaloneQueryRunner

    catalog = default_catalog(scale_factor=0.01)
    dist = DistributedQueryRunner(
        catalog, worker_count=2,
        session=Session(node_count=2, task_concurrency=2, splits_per_node=4))
    seq = StandaloneQueryRunner(catalog)
    sql = ("select o_orderpriority, count(*) from orders "
           "group by o_orderpriority order by 1")
    assert dist.execute(sql).rows() == seq.execute(sql).rows()
