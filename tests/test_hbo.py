"""History-based optimization: fingerprint invariances, journal round
trip, second-run planning, fan-out shrink, plan-cache epoch keying, and
every TPC-H statement's second run, planned from recorded history, against
its first (reference: Trino's HBO design —
io.trino.cost.HistoryBasedPlanStatisticsCalculator — and
AbstractTestQueryFramework.assertQuery)."""

import pytest

from trino_tpu.connectors.catalog import default_catalog
from trino_tpu.connectors.tpch_queries import QUERIES
from trino_tpu.planner import history
from trino_tpu.planner.plan import Filter, Join, Project, TableScan
from trino_tpu.runner import Session
from trino_tpu.sql.ir import Call, InputRef, Literal
from trino_tpu.spi.types import BIGINT, BOOLEAN
from trino_tpu.telemetry import journal
from trino_tpu.testing.oracle import assert_same_rows

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


def _reset_planning_caches():
    """Plan + result tiers and the history table: everything keyed on
    journal state.  Jitted-program memos stay warm — recompiling every
    kernel per test would dominate the suite's wall clock."""
    from trino_tpu.caching import plan_cache, result_cache

    plan_cache.reset_for_test()
    result_cache.reset_for_test()
    history.reset_for_test()


@pytest.fixture
def journal_env(tmp_path, monkeypatch):
    """Isolated journal + HBO on; every cache that could leak state
    across tests is reset on the way in AND out."""
    monkeypatch.setenv("TRINO_TPU_JOURNAL_DIR", str(tmp_path / "journal"))
    monkeypatch.setenv("TRINO_TPU_HBO", "1")
    journal.reset_for_test()
    _reset_planning_caches()
    yield
    journal.reset_for_test()
    _reset_planning_caches()


# ------------------------------------------------------- fingerprints


def _scan(table="nation", cols=("a", "b")):
    return TableScan(cols, (BIGINT,) * len(cols), catalog="tpch",
                     table=table, columns=tuple("c_" + c for c in cols))


def _gt(ch, lit):
    return Call(BOOLEAN, "gt", (InputRef(BIGINT, ch), Literal(BIGINT, lit)))


def test_fingerprint_ignores_inner_join_side_order():
    l, r = _scan("customer"), _scan("orders", cols=("x", "y"))
    ab = Join(l.output_names + r.output_names, (BIGINT,) * 4,
              l, r, "INNER", (0,), (0,), None)
    ba = Join(r.output_names + l.output_names, (BIGINT,) * 4,
              r, l, "INNER", (0,), (0,), None)
    assert history.logical_fingerprint(ab) == history.logical_fingerprint(ba)
    # an outer join is NOT side-symmetric
    lab = Join(ab.output_names, ab.output_types, l, r, "LEFT",
               (0,), (0,), None)
    lba = Join(ba.output_names, ba.output_types, r, l, "LEFT",
               (0,), (0,), None)
    assert (history.logical_fingerprint(lab)
            != history.logical_fingerprint(lba))


def test_fingerprint_ignores_distribution_and_projections():
    l, r = _scan("customer"), _scan("orders", cols=("x", "y"))
    j = Join(l.output_names + r.output_names, (BIGINT,) * 4,
             l, r, "INNER", (0,), (0,), None, distribution="BROADCAST")
    from dataclasses import replace
    assert (history.logical_fingerprint(j) ==
            history.logical_fingerprint(
                replace(j, distribution="PARTITIONED")))
    ident = Project(j.output_names, j.output_types, j,
                    tuple(InputRef(BIGINT, i) for i in range(4)))
    assert (history.logical_fingerprint(ident)
            == history.logical_fingerprint(j))


def test_fingerprint_is_channel_remap_stable():
    """The same named predicate fingerprints identically whether it sits
    on the scan or above a channel-shuffling projection."""
    s = _scan()
    direct = Filter(s.output_names, s.output_types, s, _gt(0, 5))
    swapped = Project(("b", "a"), (BIGINT, BIGINT), s,
                      (InputRef(BIGINT, 1), InputRef(BIGINT, 0)))
    remapped = Filter(swapped.output_names, swapped.output_types,
                      swapped, _gt(1, 5))  # channel 1 is still column "a"
    assert (history.logical_fingerprint(direct)
            == history.logical_fingerprint(remapped))


def test_fingerprint_sorts_conjuncts():
    s = _scan()
    p12 = Call(BOOLEAN, "$and", (_gt(0, 1), _gt(1, 2)))
    p21 = Call(BOOLEAN, "$and", (_gt(1, 2), _gt(0, 1)))
    f12 = Filter(s.output_names, s.output_types, s, p12)
    f21 = Filter(s.output_names, s.output_types, s, p21)
    assert (history.logical_fingerprint(f12)
            == history.logical_fingerprint(f21))
    # different constants are different plans
    other = Filter(s.output_names, s.output_types, s, _gt(0, 99))
    assert (history.logical_fingerprint(f12)
            != history.logical_fingerprint(other))


# ------------------------------------------------- journal round trip


def test_provider_round_trips_through_journal(journal_env):
    j = journal.get_journal()
    j.plan_stats("q1", "sqlfp", {"fp_a": {"rows": 1000, "bytes": 5000}},
                 ts=1.0)
    j.plan_stats("q2", "sqlfp", {"fp_a": {"rows": 2000},
                                 "fp_b": {"groups": 7}}, ts=2.0)
    history.reset_for_test()
    provider = history.provider_if_enabled()
    assert provider is not None
    st = provider.table["fp_a"]
    assert st.rows == 2000      # newest record wins
    assert st.bytes == 5000     # fields merge, not clobber
    assert provider.table["fp_b"].groups == 7
    assert history.history_epoch() != ""


def test_hbo_off_disables_provider_and_epoch(journal_env, monkeypatch):
    journal.get_journal().plan_stats("q1", "f", {"fp": {"rows": 5}}, ts=1.0)
    history.reset_for_test()
    assert history.provider_if_enabled() is not None
    monkeypatch.setenv("TRINO_TPU_HBO", "0")
    assert history.provider_if_enabled() is None
    assert history.history_epoch() == ""


def test_history_epoch_tracks_recorded_stats(journal_env):
    assert history.history_epoch() == ""  # no observations yet
    journal.get_journal().plan_stats("q1", "f", {"fp": {"rows": 5}}, ts=1.0)
    history.reset_for_test()
    e1 = history.history_epoch()
    assert e1 != ""
    journal.get_journal().plan_stats("q2", "f", {"fp": {"rows": 9}}, ts=2.0)
    history.reset_for_test()
    e2 = history.history_epoch()
    assert e2 not in ("", e1)


def test_plan_cache_key_includes_history_epoch(journal_env):
    from trino_tpu.caching.plan_cache import _key

    catalog = default_catalog(scale_factor=0.01)
    session = Session()
    k1 = _key("select 1", session, catalog, "plan")
    journal.get_journal().plan_stats("q1", "f", {"fp": {"rows": 5}}, ts=1.0)
    history.reset_for_test()
    k2 = _key("select 1", session, catalog, "plan")
    assert k1 != k2  # stale history must not serve a cached plan


# ------------------------------------------- second-run planning (e2e)


_WRONG_SQL = """
select c.c_mktsegment, count(*) n
from customer c
join (select o_custkey from orders
      where o_orderkey > -1 and o_orderkey > -2
        and o_orderkey > -3 and o_orderkey > -4) o
  on c.c_custkey = o.o_custkey
group by c.c_mktsegment order by c.c_mktsegment
"""


def _walk(node):
    yield node
    for c in node.children:
        yield from _walk(c)


def _join_plan(runner, sql):
    """(distribution, base tables feeding the build side) of the sole
    join, following remote exchanges."""
    from trino_tpu.planner.plan import RemoteSource

    frags = runner.create_subplan(sql).all_fragments()
    by_id = {f.id: f for f in frags}
    join = next(n for f in frags for n in _walk(f.root)
                if isinstance(n, Join))

    def tables(node, seen):
        out = set()
        for n in _walk(node):
            if isinstance(n, TableScan):
                out.add(n.table)
            elif isinstance(n, RemoteSource) and n.fragment_id not in seen:
                seen.add(n.fragment_id)
                out |= tables(by_id[n.fragment_id].root, seen)
        return out

    return join.distribution, sorted(tables(join.right, set()))


def _fresh_distributed(workers=2, sf=0.02):
    from trino_tpu.execution.distributed_runner import DistributedQueryRunner

    _reset_planning_caches()
    return DistributedQueryRunner(
        default_catalog(scale_factor=sf), worker_count=workers,
        session=Session(node_count=workers, adaptive="0"))


def test_second_run_plans_correct_build_side(journal_env, monkeypatch):
    """The BENCH_r13 mis-estimate in miniature: run 1 broadcasts the big
    orders side off a 0.4^4 selectivity underestimate; after its observed
    stats land in the journal, a fresh runner must NOT plan orders as a
    broadcast build — and rows stay identical."""
    monkeypatch.setenv("TRINO_TPU_BROADCAST_ROW_LIMIT", "1000")

    r1 = _fresh_distributed()
    dist1, build1 = _join_plan(r1, _WRONG_SQL)
    assert (dist1, build1) == ("BROADCAST", ["orders"])  # the wrong plan
    rows1 = r1.execute(_WRONG_SQL).rows()

    r2 = _fresh_distributed()
    dist2, build2 = _join_plan(r2, _WRONG_SQL)
    assert not (dist2 == "BROADCAST" and "orders" in build2), \
        f"history did not fix the build side: {dist2} {build2}"
    rows2 = r2.execute(_WRONG_SQL).rows()
    assert rows1 == rows2

    # HBO=0 must reproduce the static (history-free) plan bit-for-bit
    monkeypatch.setenv("TRINO_TPU_HBO", "0")
    r3 = _fresh_distributed()
    assert _join_plan(r3, _WRONG_SQL) == (dist1, build1)
    assert r3.execute(_WRONG_SQL).rows() == rows1


def test_history_shrinks_task_fanout(journal_env, monkeypatch):
    """A HASH stage whose observed input is far below
    TRINO_TPU_HBO_ROWS_PER_TASK gets its task count shrunk on the next
    run, and the decision is tagged on the query record."""
    from trino_tpu.telemetry import runtime as rt

    # keep every producer -> consumer seam on real sink buffers: fused
    # and collective edges bypass the counters the recorder reads, so
    # the scan stage's row count would never land in the journal
    monkeypatch.setenv("TRINO_TPU_FUSED_STAGE", "0")
    from trino_tpu.execution.distributed_runner import DistributedQueryRunner

    def fresh():
        _reset_planning_caches()
        return DistributedQueryRunner(
            default_catalog(scale_factor=0.01), worker_count=2,
            session=Session(node_count=2, adaptive="0",
                            use_collectives=False))

    sql = ("select o_custkey, count(*) c from orders "
           "group by o_custkey order by o_custkey limit 5")
    r1 = fresh()
    rows1 = r1.execute(sql).rows()

    r2 = fresh()
    rows2 = r2.execute(sql).rows()
    assert rows1 == rows2
    assert "hbo_fanout" in rt.queries()[-1].adaptive_decisions


# ------------------------- the second run, planned from history (e2e)
#
# The benchmark's cells leave TRINO_TPU_HBO at "auto" with a journal, so
# every timed query there is a repeat planned from what earlier runs
# recorded.  These run that configuration: history on, a fresh journal, the
# result tier off (a served result would skip planning and execution).


_ORDERED = {1, 2, 3, 5, 7, 8, 9, 10, 11, 12, 13, 14, 16, 18, 21, 22}


@pytest.fixture
def one_chip_env(journal_env, monkeypatch):
    """What one chip runs: on the suite's 8-device mesh "auto" takes the
    fused-stage and collective edges, whose sinks bypass the buffers the
    recorder reads (see test_history_shrinks_task_fanout)."""
    from trino_tpu.caching import result_cache

    monkeypatch.setenv("TRINO_TPU_FUSED_STAGE", "0")
    with result_cache.disabled():
        yield


@pytest.fixture(scope="module")
def served_catalog():
    """One catalog for every runner of the module, as a server's runners
    share theirs: generating SF0.01 anew costs a second an execution."""
    return default_catalog(scale_factor=0.01)


def _served_runner(catalog):
    """The cells' deployment in small: two tasks a stage over SF0.01."""
    from trino_tpu.execution.distributed_runner import DistributedQueryRunner

    return DistributedQueryRunner(
        catalog, worker_count=2,
        session=Session(node_count=2, use_collectives=False))


def _recorded_history():
    """The provider a plan made NOW would consult; fails unless history is
    on and holds records — without that a second run is only a rerun."""
    provider = history.provider_if_enabled()
    assert provider is not None and provider.table, \
        "history is off or the journal holds no plan_stats records"
    return provider


@pytest.mark.parametrize("q", sorted(QUERIES))
def test_second_run_from_history_matches_first(q, one_chip_env,
                                               served_catalog):
    """Every TPC-H statement, run, then planned again from the statistics
    its first run recorded and run on a new runner: the same rows."""
    first = _served_runner(served_catalog).execute(QUERIES[q]).rows()
    _reset_planning_caches()
    _recorded_history()
    second = _served_runner(served_catalog).execute(QUERIES[q]).rows()
    assert_same_rows(second, first, ordered=q in _ORDERED)


@pytest.fixture(scope="module")
def cells_oracle(served_catalog):
    """sqlite over the three tables the cells' queries read."""
    from trino_tpu.testing.oracle import SqliteOracle

    oracle = SqliteOracle()
    oracle.load_connector_tables(
        served_catalog.connector("tpch"), ("customer", "orders", "lineitem"))
    return oracle


@pytest.mark.parametrize("q", [1, 3, 6], ids=["q1", "q3", "q6"])
def test_served_repeat_with_history(q, one_chip_env, served_catalog,
                                    cells_oracle):
    """The queries the cells send, three times back to back on one runner
    with the plan cache on: every answer is the oracle's, and from the
    second on the plan is looked up under the history the first left."""
    runner = _served_runner(served_catalog)
    expected = cells_oracle.query(QUERIES[q])
    for run in range(3):
        if run:
            _recorded_history()
        assert_same_rows(runner.execute(QUERIES[q]).rows(), expected,
                         ordered=True)
