"""Regression tests for review findings: scalar-subquery semantics, string
join dictionaries, null-aware NOT IN, distinct-agg NULL collisions, GROUP BY
validation, oracle transpile precedence."""

import numpy as np
import pytest

from trino_tpu.exec import kernels as K
from trino_tpu.exec.operators import JoinBridge, JoinBuildSink, SemiJoinOperator
from trino_tpu.runner import StandaloneQueryRunner
from trino_tpu.spi import BIGINT, BOOLEAN, VARCHAR, Column, ColumnBatch
from trino_tpu.sql.analyzer import AnalysisError
from trino_tpu.testing.oracle import transpile


@pytest.fixture(scope="module")
def runner():
    return StandaloneQueryRunner()


def test_correlated_count_subquery_returns_zero(runner):
    # every order matches zero lineitems under quantity < 0: count must be
    # 0 (not NULL), so the equality keeps all rows
    rows = runner.execute(
        "select count(*) from orders o where 0 = "
        "(select count(*) from lineitem l "
        " where l.l_orderkey = o.o_orderkey and l.l_quantity < 0)"
    ).rows()
    assert rows == [(15000,)]


def test_uncorrelated_empty_scalar_subquery_yields_null(runner):
    # empty scalar subquery -> NULL (not zero rows): IS NULL keeps all 25
    rows = runner.execute(
        "select count(*) from nation where "
        "(select r_regionkey from region where r_name = 'NOPE') is null"
    ).rows()
    assert rows == [(25,)]


def test_multirow_scalar_subquery_raises(runner):
    with pytest.raises(RuntimeError, match="multiple rows"):
        runner.execute(
            "select count(*) from nation where n_regionkey = "
            "(select r_regionkey from region)")


def test_string_join_across_dictionaries(runner):
    runner.execute("create table memory.nat_names as select n_name from nation "
                   "where n_regionkey = 2")
    rows = runner.execute(
        "select count(*) from nation a, memory.nat_names b "
        "where a.n_name = b.n_name").rows()
    assert rows == [(5,)]


def test_group_by_validation(runner):
    with pytest.raises(AnalysisError, match="GROUP BY"):
        runner.execute(
            "select o_custkey, count(*) from orders group by o_orderkey")


def _mark_of(source_batch, build_batch, build_keys, source_keys, null_aware):
    bridge = JoinBridge()
    sink = JoinBuildSink(bridge, build_keys, build_batch.types, build_batch.names)
    sink.add_input(build_batch)
    sink.finish_input()
    op = SemiJoinOperator(bridge, source_keys, null_aware, None,
                          list(source_batch.names) + ["mark"],
                          list(source_batch.types) + [BOOLEAN])
    op.add_input(source_batch)
    out = op.get_output()
    mark = out.columns[-1]
    return mark.to_pylist()


def test_not_in_empty_set_with_null_probe():
    probe = ColumnBatch(["x"], [Column.from_values(BIGINT, [1, None, 3])])
    build = ColumnBatch(["y"], [Column.from_values(BIGINT, [])])
    # x IN (empty) is FALSE for every row, even NULL x
    assert _mark_of(probe, build, [0], [0], null_aware=True) == [False, False, False]


def test_not_in_with_build_null():
    probe = ColumnBatch(["x"], [Column.from_values(BIGINT, [1, 2, None])])
    build = ColumnBatch(["y"], [Column.from_values(BIGINT, [1, None])])
    # 1 IN (1, NULL) -> TRUE; 2 IN (1, NULL) -> UNKNOWN; NULL IN ... -> UNKNOWN
    assert _mark_of(probe, build, [0], [0], null_aware=True) == [True, None, None]


def test_distinct_count_null_storage_collision():
    # group has a NULL (storage fill 0) AND a genuine value 0: count(distinct)
    # must count the real 0 and ignore the NULL
    data = np.array([0, 0, 5], dtype=np.int64)
    valid = np.array([False, True, True])
    gidk = np.zeros(3, dtype=np.int64)
    perm, gid, n = K.group_ids([(gidk, None)])
    (res,) = K.grouped_reduce(perm, gid, n,
                              [("count", data, valid, np.int64, True)])
    assert list(res[0]) == [2]  # distinct {0, 5}


def test_any_value_skips_nulls():
    # group [7 (valid), NULL (storage fill 0)]: any_value must return 7
    data = np.array([7, 0], dtype=np.int64)
    valid = np.array([True, False])
    gidk = np.zeros(2, dtype=np.int64)
    perm, gid, n = K.group_ids([(gidk, None)])
    (res,) = K.grouped_reduce(perm, gid, n,
                              [("any_value", data, valid, np.int64, False)])
    vals, v = res
    assert list(vals) == [7] and list(v) == [True]


def test_correlated_count_in_expression(runner):
    # count wrapped in an expression: default value is the expression at
    # count=0, i.e. 0+1=1 for every order with no matching lineitem
    rows = runner.execute(
        "select count(*) from orders o where 1 = "
        "(select count(*) + 1 from lineitem l "
        " where l.l_orderkey = o.o_orderkey and l.l_quantity < 0)"
    ).rows()
    assert rows == [(15000,)]


def test_distributed_varchar_repartition():
    from trino_tpu.execution.distributed_runner import DistributedQueryRunner

    # count(distinct) forces a repartition keyed on a VARCHAR column; the
    # routing must hash string values, not per-producer dictionary codes
    sql = ("select n_name, count(distinct s_suppkey) from supplier, nation "
           "where s_nationkey = n_nationkey group by n_name")
    from trino_tpu.connectors.catalog import default_catalog

    cat = default_catalog(0.01)
    dist = DistributedQueryRunner(cat, worker_count=3)
    sa = StandaloneQueryRunner(cat)
    from trino_tpu.testing.oracle import assert_same_rows

    assert_same_rows(dist.execute(sql).rows(), sa.execute(sql).rows())


def test_transpile_fold_is_context_limited():
    assert "0.05" in transpile("x >= 0.06 - 0.01")
    assert "0.07" in transpile("x between 0.06 - 0.01 and 0.06 + 0.01")
    # precedence traps must NOT fold
    assert "1.0" not in transpile("select 0.5 + 0.5 * x from t")
    assert "0.1" not in transpile("select 1 - 0.5 - 0.4 from t")


# --- round-2 advisor findings ------------------------------------------------


def test_correlated_sum_coalesce_zero_rows(runner):
    # coalesce(sum(..), 0) over a zero-match correlated subquery must be 0,
    # not NULL (advisor: decorrelation only restored count-family defaults)
    rows = runner.execute(
        "select count(*) from orders o where 0 = "
        "(select coalesce(sum(l.l_quantity), 0) from lineitem l "
        " where l.l_orderkey = o.o_orderkey and l.l_quantity < 0)"
    ).rows()
    assert rows == [(15000,)]


def test_correlated_sum_zero_rows_is_null(runner):
    # bare sum over zero matches stays NULL
    rows = runner.execute(
        "select count(*) from orders o where "
        "(select sum(l.l_quantity) from lineitem l "
        " where l.l_orderkey = o.o_orderkey and l.l_quantity < 0) is null"
    ).rows()
    assert rows == [(15000,)]


def test_keyless_semijoin_residual_only():
    # EXISTS decorrelated to a semi-join with no equi keys (residual only)
    # crashed the probe with an empty key list (advisor finding)
    build = ColumnBatch(["b"], [Column(BIGINT, np.asarray([5, 7], np.int64))])
    bridge = JoinBridge()
    sink = JoinBuildSink(bridge, [], [BIGINT], ["b"])
    sink.add_input(build)
    sink.finish_input()
    op = SemiJoinOperator(bridge, [], False, None, ["a", "m"], [BIGINT, BOOLEAN])
    op.add_input(ColumnBatch(["a"], [Column(BIGINT, np.asarray([1, 2, 3], np.int64))]))
    out = op.get_output()
    assert list(np.asarray(out.columns[1].data)) == [True, True, True]


def test_sort_desc_int64_min():
    perm = K.sort_perm([
        (np.asarray([5, np.iinfo(np.int64).min, -3], np.int64), None, False, False)
    ])
    assert list(perm) == [0, 2, 1]  # INT64_MIN last in descending order


def test_float_zero_hash_and_group():
    # -0.0 and +0.0 must hash/group/partition identically
    d = np.asarray([0.0, -0.0, 1.5], np.float64)
    h = np.asarray(K.hash_combine([d]))
    assert h[0] == h[1]
    perm, gid, n = K.group_ids([(d, None)])
    assert n == 2
    p = K.partition_assignments([(d, None)], 7)
    assert p[0] == p[1]


def test_float_nan_single_group():
    nan1 = np.uint64(0x7FF8000000000001).view(np.float64)
    d = np.asarray([np.nan, nan1, 2.0], np.float64)
    perm, gid, n = K.group_ids([(d, None)])
    assert n == 2
    h = np.asarray(K.hash_combine([d]))
    assert h[0] == h[1]


def _nullable_ints_with_dead_rows(rng):
    n = 4096
    keys = [(rng.integers(-40, 40, n).astype(np.int64), rng.random(n) < 0.85)]
    return keys, rng.random(n) < 0.9, None


def _float_specials(rng):
    specials = np.array([np.nan, -np.nan, 0.0, -0.0, np.inf, -np.inf,
                         1.5, -1.5, 1e300, 1e-300])
    n = 2000
    keys = [(specials[rng.integers(0, len(specials), n)], None),
            (rng.integers(0, 3, n).astype(np.int64), None)]
    return keys, None, 24  # -0 = 0 and NaN is ONE group: 8 values x 3


def _all_duplicates(rng):
    return [(np.zeros(1024, np.int64), None)], None, 1


def _nullable_bools(rng):
    n = 1024
    keys = [(rng.random(n) < 0.5, rng.random(n) < 0.7)]
    return keys, None, 3  # True / False / NULL


def _every_row_dead(rng):
    # a fully filtered batch: the aggregation returns no groups for it
    return [(rng.integers(0, 9, 512).astype(np.int64), None)], \
        np.zeros(512, bool), 0


def _sql_group_key(keys, i):
    """Row i's GROUP BY key as numpy reads SQL equality: NULL is one value,
    -0 equals 0, every NaN is one value."""
    out = []
    for d, v in keys:
        if v is not None and not v[i]:
            out.append(("null",))
        elif d.dtype.kind == "f":
            out.append(("nan",) if np.isnan(d[i]) else float(d[i]) + 0.0)
        else:
            out.append(d[i].item())
    return tuple(out)


@pytest.mark.parametrize("make", [
    _nullable_ints_with_dead_rows, _float_specials, _all_duplicates,
    _nullable_bools, _every_row_dead], ids=[
        "nullable_ints_dead_rows", "float_specials", "all_duplicates",
        "nullable_bools", "every_row_dead"])
def test_group_ids_partitions_match_numpy(make):
    # two rows share a group exactly when their keys are equal; gid is
    # nondecreasing over the sorted rows, dead rows last with gid >= n
    keys, live, want_groups = make(np.random.default_rng(1))
    rows = len(keys[0][0])
    perm, gid, n = K.group_ids(keys, live)
    perm, gid = np.asarray(perm), np.asarray(gid)
    assert (np.diff(gid) >= 0).all()
    row_gid = np.empty(rows, np.int64)
    row_gid[perm] = gid
    alive = np.ones(rows, bool) if live is None else live
    assert (row_gid[~alive] >= n).all()
    by_key, by_gid = {}, {}
    for i in np.flatnonzero(alive):
        k, g = _sql_group_key(keys, i), int(row_gid[i])
        assert by_key.setdefault(k, g) == g, "equal keys split"
        assert by_gid.setdefault(g, k) == k, "distinct keys merged"
    assert n == len(by_key) == len(by_gid)
    assert sorted(by_gid) == list(range(n))
    if want_groups is not None:
        assert n == want_groups


def test_float_join_nan_and_negzero_match():
    from trino_tpu.exec import join_exec as JX
    from trino_tpu.spi import DOUBLE

    build = [(np.asarray([np.nan, -0.0], np.float64), None)]
    table = JX.build_table(build)
    probe = [(np.asarray([np.nan, 0.0, 3.0], np.float64), None)]
    lo, counts, total = JX.probe_ranges_device(table, probe, [None])
    probe_idx = [(np.arange(3, dtype=np.int64), None)]
    pairs, ok, _, _, bid, _ = JX.run_pairs(
        table, lo, counts, int(total.get()), probe, [None], probe_idx,
        build, [BIGINT, DOUBLE], [None, None], residual=None,
        need_matched=False)
    ok = np.asarray(ok)
    got = sorted(zip(np.asarray(pairs[0][0])[ok].tolist(),
                     np.asarray(bid)[ok].tolist()))
    assert got == [(0, 0), (1, 1)]


def test_failed_task_aborts_peers_quickly():
    import time

    from trino_tpu.execution.distributed_runner import DistributedQueryRunner

    r = DistributedQueryRunner(worker_count=2)
    t0 = time.time()
    with pytest.raises(Exception):
        # multi-row scalar subquery: cardinality violation raises inside a
        # task at runtime (jnp arithmetic never traps, so use this instead)
        r.execute("select (select r_regionkey from region) from orders")
    assert time.time() - t0 < 120  # peers unwind promptly, not via timeout


def test_float_hash_full_entropy():
    # doubles that collide when rounded to float32 must hash differently
    # (hash_combine decomposes the full 53-bit significand arithmetically);
    # on TPU the x64 emulation has f32 exponent range, so the contract there
    # is consistency with device equality instead — covered by kernel checks
    base = 1.7e15
    d = np.asarray([base + 1, base + 2, 1.5e300, 1.6e300], np.float64)
    h = np.asarray(K.hash_combine([d])).tolist()
    assert len(set(h)) == 4


def test_sort_nan_vs_inf():
    # NaN sorts after +inf ascending, before it descending (Trino convention)
    d = np.asarray([np.nan, np.inf, 1.0, -np.inf], np.float64)
    asc = K.sort_perm([(d, None, True, False)])
    assert [d[i] for i in asc[:3]] == [-np.inf, 1.0, np.inf] and np.isnan(d[asc[3]])
    desc = K.sort_perm([(d, None, False, False)])
    assert np.isnan(d[desc[0]]) and [d[i] for i in desc[1:]] == [np.inf, 1.0, -np.inf]
