"""History-based optimization: fingerprint invariances, journal round
trip, second-run planning, fan-out shrink, plan-cache epoch keying, and
every TPC-H statement's second run, planned from recorded history, against
its first (reference: Trino's HBO design —
io.trino.cost.HistoryBasedPlanStatisticsCalculator — and
AbstractTestQueryFramework.assertQuery)."""

import json
import os

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


# --------------------------- the table kept beside the journal (PR 35)


def _fold_from_nothing(j) -> dict:
    """The rule the table is held to, applied to the whole journal: every
    plan_stats record in read order, the newer winning field by field."""
    table: dict = {}
    for rec in j.read(events=("plan_stats",)):
        for fp, st in rec["nodes"].items():
            cur = table.setdefault(fp, history.NodeStats())
            for name in journal.PLAN_STATS_FIELDS:
                if st.get(name) is not None:
                    setattr(cur, name, st[name])
    return table


def _assert_kept_table_is_the_fold(j, step: str):
    """The table this process keeps (folded from what was appended since
    its last read) against a fold of everything on disk, and against a
    second keeper that starts from nothing now."""
    table, epoch = history._stats_table()
    expected = _fold_from_nothing(j)
    assert table == expected, f"after {step}"
    assert epoch == history._epoch_of(expected), f"after {step}"
    fresh = history._HistoryTable(j)
    fresh.refresh()
    assert (fresh.table, fresh.epoch) == (table, epoch), f"after {step}"


def _small_journal(monkeypatch, max_bytes=700, max_files=2):
    """About five records a file, current + two generations kept."""
    monkeypatch.setenv("TRINO_TPU_JOURNAL_MAX_BYTES", str(max_bytes))
    monkeypatch.setenv("TRINO_TPU_JOURNAL_FILES", str(max_files))
    journal.reset_for_test()
    return journal.get_journal()


def _append_torn(j, nodes: dict, cut: int = 50):
    """A record whose write stopped after ``cut`` bytes; returns the rest."""
    line = json.dumps(journal._record_plan_stats("q_torn", "f", nodes, 9.0))
    with open(j.path, "a", encoding="utf-8") as f:
        f.write(line[:cut])
    return line[cut:] + "\n"


def _peer(j, monkeypatch, node="coordA"):
    """A fleet peer's journal over the same directory: its stream's name
    sorts BEFORE the local one, so it grows in the middle of the order."""
    monkeypatch.setenv("TRINO_TPU_HA_NODE_ID", node)
    peer = journal.QueryJournal(directory=j.directory)
    monkeypatch.delenv("TRINO_TPU_HA_NODE_ID")
    assert peer.path < j.path
    return peer


_KEPT_TABLE_EVENTS = ["same_observation", "changed_observation", "torn_tail",
                      "rotation", "oldest_generation_lost", "peer_stream"]


@pytest.mark.parametrize("event", _KEPT_TABLE_EVENTS)
def test_kept_table_equals_a_fold_from_nothing(event, journal_env,
                                               monkeypatch):
    j = _small_journal(monkeypatch) if event in (
        "rotation", "oldest_generation_lost") else journal.get_journal()
    steps = iter(range(1000))

    def check(what):
        _assert_kept_table_is_the_fold(j, f"step {next(steps)}: {what}")

    check("an empty journal")
    j.plan_stats("q0", "f", {"fp_a": {"rows": 10, "bytes": 100},
                             "fp_b": {"groups": 3}}, ts=1.0)
    check("the first record")
    if event == "same_observation":
        for i in range(3):
            j.plan_stats(f"q{i + 1}", "f", {"fp_a": {"rows": 10, "bytes": 100},
                                            "fp_b": {"groups": 3}}, ts=2.0)
            check("the same observation again")
    elif event == "changed_observation":
        j.plan_stats("q1", "f", {"fp_a": {"rows": 11}}, ts=2.0)
        check("one field changed")
        assert history._stats_table()[0]["fp_a"] == history.NodeStats(
            rows=11, bytes=100), "fields merge, the newer wins"
        j.plan_stats("q2", "f", {"fp_c": {"rows": 1, "skew": 1.5}}, ts=3.0)
        check("a new fingerprint")
        j.plan_stats("q3", "f", {"fp_a": {"rows": 10}}, ts=4.0)
        check("changed back")
    elif event == "torn_tail":
        rest = _append_torn(j, {"fp_a": {"rows": 77}})
        check("a torn tail")
        assert history._stats_table()[0]["fp_a"].rows == 10
        with open(j.path, "a", encoding="utf-8") as f:
            f.write(rest)
        check("the tail's completion")
        assert history._stats_table()[0]["fp_a"].rows == 77
        j.plan_stats("q2", "f", {"fp_a": {"rows": 78}}, ts=3.0)
        check("an append after it")
    elif event == "rotation":
        for i in range(6):  # about five records a file: rotates once
            j.plan_stats(f"q{i + 1}", "f", {"fp_a": {"rows": 20 + i}}, ts=2.0)
            check(f"append {i}")
        assert len(j.files()) == 2
        assert "fp_b" in history._stats_table()[0]
    elif event == "oldest_generation_lost":
        for i in range(24):
            j.plan_stats(f"q{i + 1}", "f", {"fp_a": {"rows": 20 + i}}, ts=2.0)
            check(f"append {i}")
        assert len(j.files()) == 3
        assert "fp_b" not in history._stats_table()[0], \
            "q0 went with the oldest generation, and fp_b with it"
    elif event == "peer_stream":
        peer = _peer(j, monkeypatch)
        peer.plan_stats("p0", "f", {"fp_a": {"rows": 500, "skew": 2.0},
                                    "fp_p": {"rows": 5}}, ts=2.0)
        check("a peer's stream appears")
        assert history._stats_table()[0]["fp_a"] == history.NodeStats(
            rows=10, bytes=100, skew=2.0), \
            "the local stream is read after the peer's, and wins"
        peer.plan_stats("p1", "f", {"fp_a": {"rows": 600},
                                    "fp_p": {"rows": 6}}, ts=3.0)
        check("the peer's stream grows")
        assert history._stats_table()[0]["fp_a"].rows == 10
        assert history._stats_table()[0]["fp_p"].rows == 6
        j.plan_stats("q1", "f", {"fp_p": {"rows": 7}}, ts=4.0)
        check("the local stream grows")
        peer.plan_stats("p2", "f", {"fp_p": {"rows": 8}}, ts=5.0)
        check("the peer again")
        assert history._stats_table()[0]["fp_p"].rows == 7


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_kept_table_equals_a_fold_from_nothing_after_any_sequence(
        seed, journal_env, monkeypatch):
    """Sixty events drawn from every kind above, over files that rotate
    every few records and two peers that come and go."""
    import random

    rng = random.Random(seed)
    j = _small_journal(monkeypatch, max_bytes=900, max_files=2)
    writers = [j]
    torn = None
    for step in range(60):
        kind = rng.choice(["append"] * 5 + ["peer", "torn", "vanish"])
        if torn is not None:  # a torn tail is completed before a writer
            with open(j.path, "a", encoding="utf-8") as f:  # appends to it
                f.write(torn)
            torn = None
            kind = "completion"
        elif kind == "append":
            fp = rng.choice(["fp_a", "fp_b", "fp_c", "fp_d"])
            st = {name: rng.randrange(3) for name in rng.sample(
                list(journal.PLAN_STATS_FIELDS), rng.randrange(1, 4))}
            rng.choice(writers).plan_stats(f"q{step}", "f", {fp: st}, 1.0)
        elif kind == "peer" and len(writers) < 3:
            writers.append(_peer(j, monkeypatch, f"coord{len(writers)}"))
            writers[-1].plan_stats(f"q{step}", "f", {"fp_a": {"rows": 9}}, 1.0)
        elif kind == "torn" and os.path.exists(j.path):
            torn = _append_torn(j, {"fp_b": {"rows": 40 + step}})
        elif kind == "vanish" and len(writers) > 1:
            gone = writers.pop()
            for path in gone.files():
                os.remove(path)
        _assert_kept_table_is_the_fold(j, f"step {step}: {kind}")


def test_readers_of_the_kept_table_under_concurrent_appends(journal_env,
                                                           monkeypatch):
    """Eight threads read the table while three append (files rotating
    under them): a snapshot never moves under its reader, its epoch is its
    own table's, and what is kept at the end is the fold from nothing."""
    import sys
    import threading
    import time

    j = _small_journal(monkeypatch, max_bytes=4000, max_files=2)
    stop = threading.Event()
    errors: list = []

    def guarded(fn):
        def run():
            try:
                fn()
            except BaseException as e:  # noqa: BLE001 — re-raised below
                errors.append(e)
                stop.set()
        return run

    def writer(k):
        def loop():
            for i in range(150):
                j.plan_stats(f"q{k}_{i}", "f",
                             {f"fp_{i % 5}": {"rows": (i + k) % 4}}, ts=1.0)
        return loop

    def reader():
        while not stop.is_set():
            table, epoch = history._stats_table()
            frozen = {fp: history.NodeStats(**vars(st))
                      for fp, st in table.items()}
            time.sleep(0)
            assert table == frozen, "a reader's table moved under it"
            assert epoch == history._epoch_of(table)

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        readers = [threading.Thread(target=guarded(reader)) for _ in range(8)]
        writers = [threading.Thread(target=guarded(writer(k)))
                   for k in range(3)]
        for t in readers + writers:
            t.start()
        for t in writers:
            t.join(timeout=120)
        stop.set()
        for t in readers:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(switch)
        stop.set()
    assert not errors, errors[0]
    assert not any(t.is_alive() for t in readers + writers)
    _assert_kept_table_is_the_fold(j, "the last append")


def test_epoch_follows_the_table_not_the_records(journal_env):
    """A record that repeats what is known leaves the epoch, and with it
    every Tier A key, as it was; one changed value changes it; the same
    table reached again has the same epoch again."""
    from trino_tpu.caching.plan_cache import _key

    j = journal.get_journal()
    catalog = default_catalog(scale_factor=0.01)
    seen = {"rows": 5, "bytes": 50}
    j.plan_stats("q1", "f", {"fp": dict(seen)}, ts=1.0)
    e1 = history.history_epoch()
    k1 = _key("select 1", Session(), catalog, "plan")
    assert e1 != "" and k1[-1] == e1
    for i in range(3):
        j.plan_stats(f"q{i + 2}", "f", {"fp": dict(seen)}, ts=2.0 + i)
        assert history.history_epoch() == e1
    j.plan_stats("q5", "f", {"fp": {"rows": 5}}, ts=5.0)  # a subset
    assert history.history_epoch() == e1
    assert _key("select 1", Session(), catalog, "plan") == k1
    j.plan_stats("q6", "f", {"fp": {"rows": 6}}, ts=6.0)
    e2 = history.history_epoch()
    assert e2 not in ("", e1)
    assert _key("select 1", Session(), catalog, "plan") != k1
    j.plan_stats("q7", "f", {"fp": {"rows": 5}}, ts=7.0)
    assert history.history_epoch() == e1, "equal tables, equal epochs"
    j.plan_stats("q8", "f", {"fp2": {"groups": 1}}, ts=8.0)
    assert history.history_epoch() not in ("", e1, e2)


def _hbo_counters() -> dict:
    from trino_tpu.telemetry import metrics as tm

    return {"appended": tm.JOURNAL_BYTES.value(),
            "read": tm.HBO_JOURNAL_BYTES_READ.value(),
            "folds": tm.HBO_TABLE_FOLDS.value(),
            "rebuilds": tm.HBO_TABLE_REBUILDS.value()}


def test_a_long_windows_journal_is_read_once(journal_env):
    """D14: a journal as long as a served window leaves (300 finished
    queries), then 50 plan-cache lookups, each after one more finished
    query: what is read is what was appended, and nothing is re-read."""
    from trino_tpu.caching.plan_cache import _key
    from trino_tpu.spi.eventlistener import (
        QueryCompletedEvent,
        QueryCreatedEvent,
    )

    j = journal.get_journal()
    catalog = default_catalog(scale_factor=0.01)
    sql = QUERIES[6]

    def finished_query(i):
        j.query_created(QueryCreatedEvent(f"q{i}", sql, user="test"))
        j.plan_stats(f"q{i}", "f", {"fp_scan": {"rows": 59791, "bytes": 1 << 20},
                                    "fp_agg": {"groups": 2}}, ts=float(i))
        j.query_completed(QueryCompletedEvent(f"q{i}", sql, state="FINISHED",
                                              user="test"))

    for i in range(300):
        finished_query(i)
    start = _hbo_counters()
    k0 = _key(sql, Session(), catalog, "fragmented")
    first = _hbo_counters()
    assert first["rebuilds"] - start["rebuilds"] == 1, "the first read"
    assert first["read"] - start["read"] == os.path.getsize(j.path)
    for i in range(300, 350):
        finished_query(i)
        assert _key(sql, Session(), catalog, "fragmented") == k0, \
            "known values: every lookup goes to the same Tier A key"
    last = _hbo_counters()
    appended = last["appended"] - first["appended"]
    assert appended > 50 * 1000, "a finished query leaves about 1.3 KB"
    assert last["read"] - first["read"] <= 1.1 * appended
    assert last["read"] - first["read"] >= appended
    assert last["folds"] - first["folds"] == 50
    assert last["rebuilds"] == first["rebuilds"], "nothing is re-read"


def test_a_plan_is_stored_under_the_table_it_was_made_from(journal_env):
    """Inside ``pinned()`` the block's first read is the block's table: a
    record another stream lands between the optimizer's read and ``store``
    cannot put the plan under an epoch it was not planned under."""
    from trino_tpu.caching.plan_cache import _key

    j = journal.get_journal()
    catalog = default_catalog(scale_factor=0.01)
    j.plan_stats("q1", "f", {"fp": {"rows": 5}}, ts=1.0)
    with history.pinned():
        provider = history.provider_if_enabled()  # the optimizer's read
        j.plan_stats("q2", "f", {"fp": {"rows": 6}}, ts=2.0)
        with history.pinned():  # nests: _plan_stmt inside the runner's
            assert history.history_epoch() == provider.epoch
        key_at_store = _key("select 1", Session(), catalog, "plan")
        assert history.provider_if_enabled().table is provider.table
    assert key_at_store[-1] == provider.epoch
    assert provider.table["fp"].rows == 5, "a planner's table never moves"
    assert history.history_epoch() not in ("", provider.epoch)
    assert history.provider_if_enabled().table["fp"].rows == 6


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
    """The queries the cells send, five times back to back on one runner
    with the plan cache on: every answer is the oracle's, from the second
    on the plan is looked up under the history the first left, and once
    the recorded numbers repeat (from the third run on; Q3 from the fourth:
    its second run, planned from the first's history, records other
    numbers) every lookup hits — the epoch is the table's, not the
    records'."""
    from trino_tpu.caching import plan_cache

    runner = _served_runner(served_catalog)
    expected = cells_oracle.query(QUERIES[q])
    settled = 3 if q == 3 else 2
    epochs = []
    for run in range(5):
        if run:
            _recorded_history()
        hits = plan_cache.stats()["hits"]
        assert_same_rows(runner.execute(QUERIES[q]).rows(), expected,
                         ordered=True)
        epochs.append(history.history_epoch())
        if run >= settled:
            assert plan_cache.stats()["hits"] == hits + 1, \
                f"run {run} planned afresh: epochs so far {epochs}"
    assert len(set(epochs[settled - 1:])) == 1, epochs
    assert plan_cache.stats()["entries"] <= settled


@pytest.mark.parametrize("runner_kind", ["distributed", "standalone"])
def test_explain_analyze_names_the_epoch_its_plan_was_made_under(
        runner_kind, one_chip_env, served_catalog):
    """EXPLAIN ANALYZE always plans afresh; its ``history:`` line names the
    table that planning read.  Plain EXPLAIN stays as it was."""
    from trino_tpu.runner import StandaloneQueryRunner

    _served_runner(served_catalog).execute(QUERIES[3])
    epoch = history.history_epoch()
    assert epoch
    runner = _served_runner(served_catalog) if runner_kind == "distributed" \
        else StandaloneQueryRunner(served_catalog)

    def history_lines(sql):
        return [r[0] for r in runner.execute(sql).rows()
                if r[0].startswith("history:")]

    plain = history_lines("explain " + QUERIES[3])
    assert all("epoch" not in line for line in plain)
    (line,) = history_lines("explain analyze " + QUERIES[3])
    assert line.startswith("history: hit (")
    assert line.endswith(f", epoch {epoch}")
