"""Sync-free probe/expand hot loop: the planner's padded expand against the
exact-total form and a nested-loop reference, overflow->retry correctness,
capacity planning, the deferred-commit OverflowQueue, and SyncGuard
enforcement that steady-state probe batches perform ZERO blocking host
syncs.
"""

import numpy as np
import pytest

from trino_tpu.exec import join_exec as JX
from trino_tpu.exec import kernels as K
from trino_tpu.exec import syncguard as SG
from trino_tpu.exec.operators import JoinBridge, JoinBuildSink, LookupJoinOperator
from trino_tpu.spi import BIGINT, Column, ColumnBatch


def _keys(arr, valid=None):
    return [(np.asarray(arr), None if valid is None else np.asarray(valid))]


def _pair_set(pi, bi):
    return set(zip(np.asarray(pi).tolist(), np.asarray(bi).tolist()))


def _expected_pairs(build, probe, bvalid=None, pvalid=None):
    out = set()
    for p, pv in enumerate(probe):
        if pvalid is not None and not pvalid[p]:
            continue
        for b, bv in enumerate(build):
            if bvalid is not None and not bvalid[b]:
                continue
            if pv == bv:
                out.add((p, b))
    return out


# ---------------------------------------------------------------------------
# join_exec.probe_ranges_device: candidate counts against numpy


def _misses_nulls_dead_rows(rng):
    nb, npr = 4000, 6000
    return (rng.integers(0, 500, nb).astype(np.int64), rng.random(nb) < 0.9,
            rng.random(nb) < 0.95,
            rng.integers(0, 700, npr).astype(np.int64),  # some keys miss
            rng.random(npr) < 0.9, rng.random(npr) < 0.95)


def _empty_build(rng):
    return (np.empty(0, np.int64), None, None,
            np.arange(50, dtype=np.int64), None, None)


@pytest.mark.parametrize("make", [_misses_nulls_dead_rows, _empty_build],
                         ids=["misses_nulls_dead_rows", "empty_build"])
def test_probe_ranges_match_numpy(make):
    # each probe row's run [lo, lo + count) of the sorted build holds
    # exactly the live, non-NULL build rows whose key equals its own
    bk, bv, blive, pk, pv, plive = make(np.random.default_rng(7))
    table = JX.build_table(_keys(bk, bv), live=blive, num_rows=len(bk))
    lo, counts, total = JX.probe_ranges_device(table, _keys(pk, pv), [None],
                                               plive)
    lo, counts = np.asarray(lo), np.asarray(counts)
    b_ok = np.ones(len(bk), bool)
    for m in (bv, blive):
        if m is not None:
            b_ok &= m
    p_ok = np.ones(len(pk), bool)
    for m in (pv, plive):
        if m is not None:
            p_ok &= m
    want = np.array([int((b_ok & (bk == k)).sum()) if ok else 0
                     for k, ok in zip(pk, p_ok)], np.int64)
    np.testing.assert_array_equal(counts, want)
    assert int(total.get()) == int(want.sum())
    perm = np.asarray(table.perm) if len(bk) else None
    for i in np.flatnonzero(want):
        np.testing.assert_array_equal(
            np.sort(perm[lo[i]:lo[i] + counts[i]]),
            np.flatnonzero(b_ok & (bk == pk[i])))


# ---------------------------------------------------------------------------
# join_exec.run_pairs: provable / estimated caps vs the exact host total


def _run_pairs_at(table, keys, cap, donate=False, total=None):
    """One pair program over ``keys``; the probe column it gathers is the
    probe ROW INDEX, so (pairs[0][0], bid) under ``ok`` are the matched
    (probe_idx, build_idx) pairs."""
    lo, counts, total_a = JX.probe_ranges_device(table, keys, [None])
    t = total_a if total is None else total
    probe_idx = np.arange(len(keys[0][0]), dtype=np.int64)
    pairs, ok, matched, maxc, bid, overflow = JX.run_pairs(
        table, lo, counts, t, keys, [None],
        [(probe_idx, None)], [(table.key_datas[0], None)],
        [BIGINT, BIGINT], [None, None],
        residual=None, need_matched=True, cap=cap, donate=donate)
    return pairs, ok, bid, overflow


def _matched_pairs(pairs, ok, bid):
    ok = np.asarray(ok)
    return _pair_set(np.asarray(pairs[0][0])[ok], np.asarray(bid)[ok])


def _dup_runs_of_4():
    # dup runs of 4 keep bucket(n_probe * max_run) within PROVABLE_SLACK of
    # the probe width
    rng = np.random.default_rng(11)
    build = np.repeat(np.arange(50, dtype=np.int64), 4)
    probe = rng.integers(0, 60, size=128).astype(np.int64)
    return build, None, probe, None


def _null_keys_heavy_dups():
    # NULL keys on both sides never match, over heavy duplicate runs and
    # some probe keys outside the build domain
    rng = np.random.default_rng(3)
    build = rng.integers(0, 50, size=300).astype(np.int64)
    bvalid = rng.random(300) > 0.1
    probe = rng.integers(0, 60, size=257).astype(np.int64)
    pvalid = rng.random(257) > 0.1
    return build, bvalid, probe, pvalid


@pytest.mark.parametrize("inputs", [_dup_runs_of_4, _null_keys_heavy_dups])
def test_run_pairs_provable_cap_matches_exact_total(inputs):
    build, bvalid, probe, pvalid = inputs()
    table = JX.build_table(_keys(build, bvalid))
    keys = _keys(probe, pvalid)
    expected = _expected_pairs(build, probe, bvalid, pvalid)

    # the exact-total form (what an overflow retry runs): the landed
    # candidate total picks the bucket
    total = int(JX.probe_ranges_device(table, keys, [None])[2].get())
    pairs_x, ok_x, bid_x, _ = _run_pairs_at(table, keys, cap=None, total=total)

    # planner cap from build-side stats (max_run), no total sync: the
    # planner must prove the cap and skip the flag
    cap, provable = JX.ExpandPlanner().plan(len(probe), table.max_run)
    assert provable
    pairs_p, ok_p, bid_p, overflow = _run_pairs_at(
        table, keys, cap=cap, donate=provable)
    assert not bool(np.asarray(overflow))

    assert _matched_pairs(pairs_x, ok_x, bid_x) == expected
    assert _matched_pairs(pairs_p, ok_p, bid_p) == expected
    assert int(np.asarray(ok_p).sum()) == len(expected)  # no pair twice


def test_run_pairs_overflow_flag_and_retry():
    build = np.repeat(np.arange(4, dtype=np.int64), 32)  # runs of 32
    probe = np.arange(4, dtype=np.int64)  # total = 4 * 32 = 128
    table = JX.build_table(_keys(build))
    keys = _keys(probe)

    _, ok_t, _, overflow = _run_pairs_at(table, keys, cap=16)
    assert bool(np.asarray(overflow))  # 128 candidates > 16 lanes: flagged
    # the retry contract: re-run at the exact (now host-known) bucket
    lo, counts, total_a = JX.probe_ranges_device(table, keys, [None])
    total = int(total_a.get())
    assert total == 128
    pairs, ok, bid, overflow2 = _run_pairs_at(
        table, keys, cap=K.bucket(total))
    assert not bool(np.asarray(overflow2))
    ok = np.asarray(ok)
    assert int(ok.sum()) == 128
    assert set(np.asarray(bid)[ok].tolist()) == set(range(len(build)))


def test_run_pairs_empty_probe_zero_match():
    build = np.arange(16, dtype=np.int64)
    table = JX.build_table(_keys(build))
    keys = _keys(np.array([100, 101], dtype=np.int64))
    pairs, ok, bid, overflow = _run_pairs_at(table, keys, cap=8)
    assert int(np.asarray(ok).sum()) == 0
    assert not bool(np.asarray(overflow))


# ---------------------------------------------------------------------------
# capacity planning


def test_planner_provable_for_unique_build():
    cap, provable = JX.ExpandPlanner().plan(1024, max_run=1)
    assert provable and cap == 1024


def test_planner_estimates_then_crosses_bound():
    p = JX.ExpandPlanner()
    # bound = 16 * 1000 lanes >> PROVABLE_SLACK * bucket(16): not provable,
    # first estimate falls back to the probe width
    cap, provable = p.plan(16, max_run=1000)
    assert not provable and cap == K.bucket(16)
    # a landed total pushes the estimate past the provable bound: the
    # planner snaps to the bound (never exceeds what can be proven needed)
    p.observe(16000)
    cap, provable = p.plan(16, max_run=1000)
    assert provable and cap == K.bucket(16 * 1000)


def test_planner_unknown_max_run_never_provable():
    p = JX.ExpandPlanner()
    cap, provable = p.plan(64, max_run=None)
    assert not provable and cap == K.bucket(64)


@pytest.mark.parametrize("lanes,count,probe_words,build_words,riders,cap", [
    (1024, 10, 7, 7, 0, 16),           # sparse: compact, to a power of four
    (1024, 800, 7, 7, 0, None),        # dense: stay wide
    # where the chip's costs cross at 2^20 lanes for Q3's two builds (PR 33)
    (1 << 20, 1 << 18, 7, 7, 0, 1 << 18), (1 << 20, 1 << 19, 7, 7, 0, None),
    (1 << 20, 1 << 16, 7, 3, 0, 1 << 16), (1 << 20, 1 << 17, 7, 3, 0, None),
    (1024, 10, 7, 0, 0, None),         # no build column: nothing to gather
    # ... unless later probes ride the output's width
    (1024, 10, 7, 0, 6, 16),
    # TPC-H Q9's `part` probe at SF1 (5.4 % of a 2^20-lane page, EST_HEADROOM
    # x the matches): its own gather of one column stays wide, the 12 words
    # a lane the nation, partsupp and orders probes behind it read make the
    # page narrow
    (1 << 20, 114_032, 15, 2, 0, None), (1 << 20, 114_032, 15, 2, 12, 1 << 18),
    # Q3's two places (SF0.25) and Q5's places 2 and 3 (SF1) with the riders
    # each has: the decisions they make without
    (1 << 20, 53_030, 7, 7, 4, 1 << 16), (1 << 16, 10_744, 18, 3, 0, None),
    (1 << 20, 319_168, 22, 5, 11, None), (1 << 20, 12_842, 30, 4, 4, 1 << 14),
])
def test_plan_unique_cap(lanes, count, probe_words, build_words, riders, cap):
    assert JX.plan_unique_cap(
        lanes, count, probe_words, build_words, riders) == cap


# ---------------------------------------------------------------------------
# OverflowQueue: deferred commits, retry on landed-True flags


def test_overflow_queue_commits_in_order_and_retries():
    import jax.numpy as jnp

    q = JX.OverflowQueue()
    committed = []
    retried = []

    def entry(i, overflow):
        def retry():
            retried.append(i)
            return f"retry-{i}"

        q.push(SG.async_scalar(jnp.asarray(overflow), f"t{i}"),
               f"spec-{i}", retry, committed.append)

    before = SG.snapshot()
    entry(0, False)
    entry(1, True)  # truncated: must re-run, never commit the speculation
    entry(2, False)
    q.drain(block=True)
    assert committed == ["spec-0", "retry-1", "spec-2"]
    assert retried == [1]
    assert SG.take_delta(before).expand_retries == 1
    assert len(q) == 0


def test_overflow_queue_blocks_past_max_inflight():
    import jax.numpy as jnp

    q = JX.OverflowQueue()
    committed = []
    for i in range(JX.MAX_INFLIGHT + 2):
        q.push(SG.async_scalar(jnp.asarray(False), "t"), i, lambda: None,
               committed.append)
        q.drain()  # non-blocking: may or may not commit yet
    assert len(q) <= JX.MAX_INFLIGHT + 1  # backpressure bound
    q.drain(block=True)
    assert committed == list(range(JX.MAX_INFLIGHT + 2))


# ---------------------------------------------------------------------------
# SyncGuard: steady-state probe batches are sync-free, and violations raise


def test_forbidden_raises_inside_hot_region():
    import jax.numpy as jnp

    with SG.forbidden():
        with SG.hot_region():
            with pytest.raises(SG.SyncViolation):
                SG.count_sync("test.tag", blocking=True)
        # outside the hot region the same sync is fine
        SG.count_sync("test.tag", blocking=True)
    # non-blocking polls never violate
    with SG.forbidden(), SG.hot_region():
        h = SG.async_scalar(jnp.asarray(1), "test.poll")
        h.get_if_ready()


def _probe_driver(op, batch):
    op.add_input(batch)
    out = []
    while (b := op.get_output()) is not None:
        out.append(b.compact())
    return out


def test_lookup_join_steady_state_zero_hot_syncs():
    """The acceptance contract: after warm-up, probe batches flow through
    LookupJoinOperator with ZERO blocking host syncs — SyncGuard forbidden
    mode raises on any violation, and the per-region counter stays 0."""
    rng = np.random.default_rng(5)
    nb = 3200
    build_keys = np.repeat(np.arange(100, dtype=np.int64), 32)  # dup runs
    build_vals = rng.integers(0, 1000, size=nb).astype(np.int64)
    bridge = JoinBridge()
    sink = JoinBuildSink(bridge, [0], [BIGINT, BIGINT], ["bk", "bv"])
    sink.add_input(ColumnBatch(
        ["bk", "bv"], [Column.from_values(BIGINT, build_keys.tolist()),
                       Column.from_values(BIGINT, build_vals.tolist())]))
    sink.finish_input()
    op = LookupJoinOperator(bridge, [0], "INNER", None,
                            ["pk", "pv", "bk", "bv"], [BIGINT] * 4)

    def batch(seed):
        r = np.random.default_rng(seed)
        pk = r.integers(0, 110, size=1024).astype(np.int64)
        return pk, ColumnBatch(
            ["pk", "pv"], [Column.from_values(BIGINT, pk.tolist()),
                           Column.from_values(BIGINT, pk.tolist())])

    total_rows = 0
    expected = 0
    hits = np.bincount(build_keys, minlength=110)
    # warm-up: jit compiles, planner converges, build scalars land
    for seed in range(3):
        pk, b = batch(seed)
        expected += int(hits[pk].sum())
        total_rows += sum(o.num_rows for o in _probe_driver(op, b))

    # steady state: same shapes — any blocking sync inside the hot loop
    # now raises SyncViolation, and the tally must stay at zero
    before = SG.snapshot()
    with SG.forbidden():
        for seed in range(3, 8):
            pk, b = batch(seed)
            expected += int(hits[pk].sum())
            total_rows += sum(o.num_rows for o in _probe_driver(op, b))
    assert SG.take_delta(before).hot_loop_syncs == 0

    op.finish_input()
    while not op.is_finished():
        b = op.get_output()
        if b is not None:
            total_rows += b.compact().num_rows
    assert total_rows == expected


# ---------------------------------------------------------------------------
# query-level equivalence + observability


@pytest.fixture(scope="module")
def harness():
    from trino_tpu.connectors.catalog import default_catalog
    from trino_tpu.runner import StandaloneQueryRunner
    from trino_tpu.testing.oracle import SqliteOracle

    catalog = default_catalog(scale_factor=0.01)
    oracle = SqliteOracle()
    oracle.load_connector_tables(
        catalog.connector("tpch"), ("nation", "orders", "lineitem"))
    return StandaloneQueryRunner(catalog), oracle


@pytest.mark.parametrize("sql,expected", [
    ("select count(*) from orders o join lineitem l "
     "on o.o_orderkey = l.l_orderkey", None),
    ("select count(*) from nation a join nation b "
     "on a.n_regionkey = b.n_regionkey", [(125,)]),
])
def test_join_query_matches_oracle(harness, sql, expected):
    from trino_tpu.testing.oracle import assert_same_rows

    runner, oracle = harness
    rows = runner.execute(sql).rows()
    assert_same_rows(rows, oracle.query(sql))
    if expected is not None:
        assert rows == expected


def test_explain_analyze_reports_sync_stats():
    from trino_tpu.runner import StandaloneQueryRunner

    r = StandaloneQueryRunner()
    out = "\n".join(str(row[0]) for row in r.execute(
        "explain analyze select count(*) from nation a join nation b "
        "on a.n_regionkey = b.n_regionkey").rows())
    assert "host syncs" in out
