"""The unique-build probe's gather is sized from a match count in every
batch: an earlier batch's, the seed an earlier execution of the same plan
shape left, or — neither known — the batch's own.  Counts only (CPU): which
leg ran, at what cap, from which estimate, and that the rows are exact.
"""

import numpy as np
import pytest

from trino_tpu.exec import join_exec as JX
from trino_tpu.exec import syncguard as SG
from trino_tpu.exec.operators import (JoinBridge, JoinBuildSink,
                                      LookupJoinOperator)
from trino_tpu.spi import BIGINT, Column, ColumnBatch

LANES = 4096
BUILD_ROWS = 1024
NAMES = ["pk", "pv", "bk", "bv"]
SEED_KEY = ("unique", "INNER", (0,), tuple(NAMES), False)


@pytest.fixture(autouse=True)
def _no_seeds():
    JX.reset_estimate_seeds_for_test()
    yield
    JX.reset_estimate_seeds_for_test()


@pytest.fixture(scope="module")
def bridge():
    b = JoinBridge()
    sink = JoinBuildSink(b, [0], [BIGINT, BIGINT], ["bk", "bv"])
    keys = np.arange(BUILD_ROWS, dtype=np.int64)
    sink.add_input(ColumnBatch(["bk", "bv"], [
        Column.from_values(BIGINT, keys.tolist()),
        Column.from_values(BIGINT, (keys * 7).tolist())]))
    sink.finish_input()
    return b


def _batch(matches: int, seed: int = 0) -> tuple[ColumnBatch, list]:
    """LANES probe rows of which ``matches`` hit the build; the rows the
    join must return."""
    rng = np.random.default_rng(seed)
    pk = BUILD_ROWS + rng.integers(0, 1 << 20, LANES)  # all misses
    hit = rng.choice(LANES, matches, replace=False)
    pk[hit] = rng.integers(0, BUILD_ROWS, matches)
    pv = np.arange(LANES, dtype=np.int64)
    want = sorted((int(pk[i]), int(pv[i]), int(pk[i]), int(pk[i]) * 7)
                  for i in hit)
    return ColumnBatch(["pk", "pv"], [
        Column.from_values(BIGINT, pk.tolist()),
        Column.from_values(BIGINT, pv.tolist())]), want


def _operator(bridge) -> LookupJoinOperator:
    return LookupJoinOperator(bridge, [0], "INNER", None, NAMES, [BIGINT] * 4)


def _run(op, batches) -> tuple[list, list]:
    """Drive ``op`` over ``batches`` to its end: (rows out, the trace
    attributes of each add_input)."""
    rows, attrs = [], []

    def take():
        while (out := op.get_output()) is not None:
            out = out.compact()
            rows.extend(zip(*(c.to_pylist() for c in out.columns)))

    for b in batches:
        op.add_input(b)
        attrs.append(op.trace_attrs)
        take()
    op.finish_input()
    while not op.is_finished():
        take()
    return sorted(rows), attrs


def _seed(total: int) -> None:
    """What an earlier execution that saw ``total`` matches left behind."""
    JX.ExpandPlanner(key=SEED_KEY).observe(total)


# (case, seed left by an earlier execution, matches in the batch, expected
#  cap (the power of four over EST_HEADROOM x the estimate; LANES = the wide
#  leg) / estimate origin, wide, compact, seeded, retries)
CASES = [
    # (b) a statement's first execution: the batch's own count
    ("own-count", None, 40, 256, "count", 0, 1, 0, 0),
    # (a, second half) a seed of the same identity plans the compact cap
    ("seeded", 40, 40, 256, "seed", 0, 1, 1, 0),
    # (c) a seed too small overflows: one counted wide re-run, exact rows
    ("seed-too-small", 4, 40, 16, "seed", 1, 1, 1, 1),
    # (d) a seed far too large only pads
    ("seed-too-large", 400, 40, 1024, "seed", 0, 1, 1, 0),
    # (f) a dense probe stays wide, from its own count or from a seed
    ("dense", None, LANES, LANES, "count", 1, 0, 0, 0),
    ("dense-seeded", LANES, LANES, LANES, "seed", 1, 0, 1, 0),
]


@pytest.mark.parametrize(
    "seed,matches,cap,origin,wide,compact,seeded,retries",
    [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_one_batch_probe_sizes_its_gather(
        bridge, seed, matches, cap, origin, wide, compact, seeded, retries):
    if seed is not None:
        _seed(seed)
    batch, want = _batch(matches)
    before = SG.snapshot()
    rows, attrs = _run(_operator(bridge), [batch])
    d = SG.take_delta(before)
    assert attrs == [{"cap": cap, "lanes": LANES, "estimate": origin}]
    assert rows == want  # exact, whatever the estimate was worth
    assert (d.unique_gather_wide, d.unique_gather_compact,
            d.unique_gather_seeded) == (wide, compact, seeded)
    assert (d.expand_overflows, d.expand_retries) == (retries, retries)
    assert d.hot_loop_syncs == 0


def test_one_batch_probe_leaves_its_seed_for_the_next_operator(bridge):
    """(a) By the time a one-batch operator has finished, its count is in
    the seed store — also when it took its estimate from a seed and so
    handed its own count over in flight — and a fresh operator with the
    same identity starts from it."""
    batch, want = _batch(40)
    rows, attrs = _run(_operator(bridge), [batch])
    assert rows == want and attrs[0]["estimate"] == "count"
    with JX._EST_SEEDS_LOCK:
        assert JX._EST_SEEDS[SEED_KEY] == 40

    # the second execution sees more matches than the first: its in-flight
    # count must raise the seed by the time it has finished
    batch, want = _batch(90, seed=1)
    rows, attrs = _run(_operator(bridge), [batch])
    assert rows == want
    assert attrs[0] == {"cap": 256, "lanes": LANES,
                        "estimate": "seed"}
    with JX._EST_SEEDS_LOCK:
        assert JX._EST_SEEDS[SEED_KEY] == 90
    est, origin = _operator(bridge)._uplanner.estimate()
    assert (est, origin) == (90, "seed")


def test_later_batches_take_their_estimate_from_earlier_ones(bridge):
    """Only a probe's FIRST batch waits for its own count; the next ones are
    sized from the counts landed so far, with no wait in the hot region."""
    batches, want = [], []
    for i in range(3):
        b, w = _batch(40 + i, seed=10 + i)
        batches.append(b)
        want.extend(w)
    before = SG.snapshot()
    with SG.forbidden():
        rows, attrs = _run(_operator(bridge), batches)
    d = SG.take_delta(before)
    assert rows == sorted(want)
    assert [a["estimate"] for a in attrs] == ["count", "batch", "batch"]
    assert all(a["cap"] < LANES for a in attrs)
    assert (d.unique_gather_wide, d.unique_gather_compact,
            d.unique_gather_seeded, d.hot_loop_syncs) == (0, 3, 0, 0)


# ---------------------------------------------------------------------------
# (e) the cell's statement, deployed as the cell deploys it, twice

Q3 = """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
       o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate
limit 10
"""
Q3_TABLES = ("lineitem", "orders", "customer")
Q3_BATCH_ROWS = 16384


def _pinned_catalog(scale_factor: float):
    """TPC-H behind the memory connector in fixed-size pinned device
    batches with the source's statistics: benchmark/harness/deploy.py's
    load at a tiny size."""
    from trino_tpu.connectors.catalog import default_catalog
    from trino_tpu.spi.connector import TableSchema

    catalog = default_catalog(scale_factor=scale_factor)
    tpch, mem = catalog.connector("tpch"), catalog.connector("memory")
    for t in Q3_TABLES:
        schema = tpch.get_table_schema(t)
        parts = []
        for split in tpch.get_splits(t, 1, 1):
            src = tpch.create_page_source(split, schema.column_names())
            while not src.is_finished():
                b = src.get_next_batch()
                if b is not None:
                    parts.append(b)
        whole = ColumnBatch.concat(parts)
        chunks = [whole.slice(s, min(s + Q3_BATCH_ROWS, whole.num_rows))
                  for s in range(0, whole.num_rows, Q3_BATCH_ROWS)]
        mem.create_table(TableSchema(t, schema.columns))
        mem.finish_insert(t, [chunks])
        mem.pin_to_device(t)
        mem.set_analyzed_statistics(t, tpch.get_table_statistics(t))
    return catalog


def test_q3_twice_second_run_gathers_compact_from_seeds(monkeypatch):
    from trino_tpu.caching import result_cache
    from trino_tpu.execution.distributed_runner import DistributedQueryRunner
    from trino_tpu.runner import Session
    from trino_tpu.telemetry import profiler
    from trino_tpu.testing.oracle import SqliteOracle, assert_same_rows

    # what one chip runs: no fused stage, no collectives
    monkeypatch.setenv("TRINO_TPU_FUSED_STAGE", "0")
    catalog = _pinned_catalog(0.01)
    oracle = SqliteOracle()
    oracle.load_connector_tables(catalog.connector("tpch"), Q3_TABLES)
    runner = DistributedQueryRunner(
        catalog, worker_count=2,
        session=Session(default_catalog="memory", node_count=2,
                        use_collectives=False))
    with result_cache.disabled():
        first = runner.execute(Q3).rows()
        before, t0 = SG.snapshot(), profiler.now()
        second = runner.execute(Q3).rows()
        d = SG.take_delta(before)
    assert first == second
    assert_same_rows(second, oracle.query(Q3), ordered=True)
    gathers = [e["args"] for e in profiler.events_since(t0)
               if e["kind"] == profiler.OPERATOR
               and e["name"] == "LookupJoinOperator"
               and "cap" in (e.get("args") or {})]
    # lineitem's probe of orders: every batch compacts, an operator's first
    # from the seed the first run left and its later ones from its own
    first_join = [g for g in gathers if g["lanes"] == Q3_BATCH_ROWS]
    assert first_join and all(g["cap"] * 8 <= Q3_BATCH_ROWS
                              for g in first_join)
    assert "count" not in {g["estimate"] for g in gathers}
    assert d.unique_gather_seeded > 0
    assert d.unique_gather_compact >= len(first_join)
    # ... so nothing downstream runs at the scan's width: the probe of
    # customer reads the first join's narrow output (and gathers its two
    # columns wide over it: compacting those lanes again would cost more)
    assert all(g["lanes"] * 8 <= Q3_BATCH_ROWS for g in gathers
               if g not in first_join)
    assert d.unique_gather_wide + d.unique_gather_compact == len(gathers)
    assert d.expand_retries == 0
