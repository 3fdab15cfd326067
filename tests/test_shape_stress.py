"""Shape-bucket boundary stress (SURVEY §7 hard part 1: bucketed static
shapes + masked overflow are the single biggest divergence risk).

Exercises exact power-of-two bucket edges (n, n±1), group counts crossing
the masked-aggregation and small-codes caps, empty mesh partitions, and
join fan-outs at expansion-bucket edges — all oracle-checked."""

import numpy as np
import pytest

from trino_tpu.connectors.catalog import default_catalog
from trino_tpu.exec import kernels as K
from trino_tpu.execution.distributed_runner import DistributedQueryRunner
from trino_tpu.runner import Session, StandaloneQueryRunner


def _runner():
    return StandaloneQueryRunner(default_catalog(scale_factor=0.01),
                                 session=Session(default_catalog="memory"))


@pytest.mark.parametrize("n", [7, 8, 9, 127, 128, 129, 4095, 4096, 4097])
def test_row_counts_at_bucket_edges(n):
    r = _runner()
    r.execute(f"create table be{n} (k bigint, v bigint)")
    rows = ", ".join(f"({i % 5}, {i})" for i in range(n))
    r.execute(f"insert into be{n} values {rows}")
    got = r.execute(f"select k, count(*), sum(v), min(v), max(v) "
                    f"from be{n} group by k order by k").rows()
    ks = [i % 5 for i in range(n)]
    for k, cnt, s, lo, hi in got:
        idx = [i for i in range(n) if ks[i] == k]
        assert cnt == len(idx) and s == sum(idx)
        assert lo == min(idx) and hi == max(idx)
    # filters leaving exactly 0 / 1 / n-1 live rows
    assert r.execute(f"select count(*) from be{n} where v < 0").rows() == [(0,)]
    assert r.execute(f"select count(*) from be{n} where v = 0").rows() == [(1,)]
    assert r.execute(
        f"select count(*) from be{n} where v > 0").rows() == [(n - 1,)]


@pytest.mark.parametrize("g", [
    K.MASKED_AGG_LIMIT - 1, K.MASKED_AGG_LIMIT, K.MASKED_AGG_LIMIT + 1])
def test_group_counts_across_masked_cap(g):
    """Dictionary-key group spaces at the masked-reduction cap boundary:
    the masked, codes-sort and general lexsort paths must agree."""
    r = _runner()
    r.execute("create table gc (s varchar, v bigint)")
    n = 3 * g
    rows = ", ".join(f"('k{i % g:05d}', {i})" for i in range(n))
    r.execute(f"insert into gc values {rows}")
    got = r.execute("select s, count(*), sum(v) from gc group by s").rows()
    assert len(got) == g
    total = sum(c for _, c, _ in got)
    assert total == n
    byk = {s: (c, sv) for s, c, sv in got}
    expect0 = [i for i in range(n) if i % g == 0]
    assert byk["k00000"] == (len(expect0), sum(expect0))
    r.execute("drop table gc")


def test_empty_partitions_on_mesh():
    """8 tasks over a 3-row table: most tasks see zero splits/rows; the
    PARTIAL->FINAL pipeline must still produce exact results."""
    dist = DistributedQueryRunner(
        default_catalog(scale_factor=0.01), worker_count=8,
        session=Session(default_catalog="memory", node_count=8))
    dist.execute("create table tiny (k bigint)")
    dist.execute("insert into tiny values (1), (2), (2)")
    assert dist.execute(
        "select k, count(*) from tiny group by k order by k").rows() == [
        (1, 1), (2, 2)]
    assert dist.execute("select count(*), sum(k) from tiny").rows() == [(3, 5)]
    # empty input to a global aggregate on every task
    assert dist.execute(
        "select count(*), sum(k) from tiny where k > 99").rows() == [(0, None)]


@pytest.mark.parametrize("fanout", [1, 2, 7, 8, 9])
def test_join_fanout_at_expansion_edges(fanout):
    """Join candidate totals right at the pair-expansion bucket edges."""
    r = _runner()
    r.execute(f"create table jl{fanout} (k bigint)")
    r.execute(f"insert into jl{fanout} values (1), (2)")
    r.execute(f"create table jr{fanout} (k bigint, v bigint)")
    rows = ", ".join(f"(1, {i})" for i in range(fanout)) + ", (3, 99)"
    r.execute(f"insert into jr{fanout} values {rows}")
    got = r.execute(
        f"select count(*), sum(v) from jl{fanout} l join jr{fanout} r "
        f"on l.k = r.k").rows()
    assert got == [(fanout, sum(range(fanout)))]


def test_distinct_and_topn_at_edges():
    r = _runner()
    r.execute("create table de (k bigint)")
    n = 1024  # exactly a bucket
    rows = ", ".join(f"({i % 256})" for i in range(n))
    r.execute(f"insert into de values {rows}")
    assert r.execute("select count(distinct k) from de").rows() == [(256,)]
    top = r.execute("select k from de order by k desc limit 8").rows()
    assert [t[0] for t in top] == [255] * 4 + [254] * 4


# ---------------------------------------------------------------------------
# the join path's shape set (PR 36): a probe page that crossed a REPARTITION
# exchange used to arrive cut to its exact row count, and every join program
# was compiled once a distinct count (~130 shapes at SF10, a minute each)

_SHAPE_SQL = {
    "lineitem_orders": (
        "select count(*), sum(l_extendedprice) from lineitem join orders "
        "on l_orderkey = o_orderkey where o_orderdate < date '1995-03-15'"),
    "right_join": (
        "select count(*), count(o_orderkey), sum(l_quantity) from orders "
        "right join lineitem on o_orderkey = l_orderkey "
        "and o_orderdate < date '1995-03-15'"),
    "q3": None,  # connectors.tpch_queries.QUERIES[3]
}


def _is_pow2(n: int) -> bool:
    return n > 0 and n & (n - 1) == 0


@pytest.mark.parametrize("resident", ["large_pages", "every_page"])
@pytest.mark.parametrize("batch_rows", [4096, 1024])
@pytest.mark.parametrize("name", sorted(_SHAPE_SQL))
def test_join_programs_compile_at_power_of_two_lanes_only(
        name, batch_rows, resident, monkeypatch):
    """Every join planned PARTITIONED (a build over the planner's broadcast
    limit; a RIGHT join always is), three tasks a stage, many small probe
    pages with as many different live counts out of the REPARTITION sinks:
    each ``join.*`` program sees power-of-two lane counts only (the dense
    table, sized to its key range, is the next issue's), and the number of
    shapes does not grow with the number of pages.  Both ways a page can
    cross: by the host, cut and padded to its bucket (these pages are small:
    ``large_pages``), and on the device under a mask (``every_page``)."""
    import jax

    from trino_tpu.caching import executable_cache as EC
    from trino_tpu.caching import result_cache
    from trino_tpu.connectors.tpch_queries import QUERIES
    from trino_tpu.exec.operators import LookupJoinOperator
    from trino_tpu.execution import task as T
    from trino_tpu.planner import optimizer as O
    from trino_tpu.testing.oracle import assert_same_rows

    monkeypatch.setenv("TRINO_TPU_FUSED_STAGE", "0")
    monkeypatch.setenv("TRINO_TPU_RESIDENT_PLAN", "0")
    monkeypatch.setattr(O, "_BROADCAST_LIMIT", 0)
    if resident == "every_page":
        monkeypatch.setattr(T, "_RESIDENT_MIN_LANES", 1)
    monkeypatch.setenv("TRINO_TPU_COALESCE_TARGET_ROWS", str(batch_rows))
    shapes: dict = {}
    dense_tables: set = set()
    call = EC._Program.__call__

    def spy(self, *args, **kwargs):
        out = call(self, *args, **kwargs)
        if self.name.startswith("trino_join_"):
            shapes.setdefault(self.name, set()).add(tuple(
                x.shape[0] for x in jax.tree_util.tree_leaves((args, kwargs))
                if getattr(x, "ndim", 0)))
            if self.name == "trino_join_dense_build":
                dense_tables.update(
                    x.shape[0] for x in jax.tree_util.tree_leaves(out)
                    if getattr(x, "ndim", 0))
        return out

    monkeypatch.setattr(EC._Program, "__call__", spy)
    pages = []
    add_input = LookupJoinOperator.add_input

    def count_pages(self, probe):
        pages.append(probe.num_rows)
        return add_input(self, probe)

    monkeypatch.setattr(LookupJoinOperator, "add_input", count_pages)
    catalog = default_catalog(scale_factor=0.01)
    catalog.connector("tpch").batch_rows = batch_rows
    sql = _SHAPE_SQL[name] or QUERIES[3]
    dist = DistributedQueryRunner(
        catalog, worker_count=3,
        session=Session(node_count=3, use_collectives=False))
    assert "PARTITIONED" in dist.explain(sql)
    with result_cache.disabled():
        rows = dist.execute(sql).rows()
    probe_pages = list(pages)
    seen = {program: set(sigs) for program, sigs in shapes.items()}
    assert_same_rows(
        rows, StandaloneQueryRunner(catalog).execute(sql).rows(),
        ordered=name == "q3")
    # lineitem's 60,175 rows in small pages, each cut three ways by the
    # sink: as many live counts as pages
    assert len(probe_pages) >= 9, probe_pages
    assert all(_is_pow2(n) for n in probe_pages)
    assert seen, "no join program ran"
    for program, signatures in seen.items():
        lanes = {n for sig in signatures for n in sig} - dense_tables
        assert all(_is_pow2(n) for n in lanes), (program, sorted(lanes))
        # a shape a bucket (and a join), not a shape a page: the parent
        # compiled one a page here
        assert len(signatures) <= 8, (program, sorted(signatures))
