"""Who asks for compaction (PR 27): the ``exec.compact-count`` sync and the
``kernels.compact`` program are a cost of the reductions that SORT their
lanes; the masked aggregation takes its sparse, padded input as it comes.
Checked on the counters a chip trace reads: syncguard's tags and the flight
recorder's ``launch`` / ``operator`` events."""

import numpy as np
import jax.numpy as jnp
import pytest

from trino_tpu.connectors.catalog import default_catalog
from trino_tpu.exec import operators as O
from trino_tpu.exec import syncguard as SG
from trino_tpu.exec.operators import (HashAggregationOperator, SortOperator,
                                      TopNOperator)
from trino_tpu.planner.plan import AggCall, SortKey
from trino_tpu.runner import StandaloneQueryRunner
from trino_tpu.spi.batch import Column, ColumnBatch
from trino_tpu.spi.types import BIGINT, VARCHAR
from trino_tpu.telemetry import profiler

LANES = 1 << 17  # over _COMPACT_MIN_LANES, and its own power-of-two bucket
FLAGS = np.array(["A", "N", "R"], dtype=object)
COMPACT = "trino_kernels_compact"
SYNC = "exec.compact-count"


@pytest.fixture(autouse=True)
def _recorder():
    prev = profiler.set_level(1)
    profiler.reset_for_test()
    yield
    profiler.set_level(prev)
    profiler.reset_for_test()


def _input(kind: str, groups: int = 3, seed: int = 7):
    """A device-resident batch of LANES lanes (flag code, high-NDV key, value)
    with a device ``live`` mask, and the same columns on the host."""
    rng = np.random.default_rng(seed)
    live = {"sparse": rng.random(LANES) < 0.02,
            "dense": rng.random(LANES) < 0.9,
            "dead": np.zeros(LANES, bool)}[kind]
    flag = rng.integers(0, groups, LANES).astype(np.int32)
    key = rng.integers(0, 5000, LANES).astype(np.int64)
    val = rng.integers(-1000, 1000, LANES).astype(np.int64)
    dictionary = (FLAGS if groups == 3 else
                  np.array([f"g{i:03d}" for i in range(groups)], dtype=object))
    batch = ColumnBatch(
        ["flag", "key", "val"],
        [Column(VARCHAR, jnp.asarray(flag), None, dictionary),
         Column(BIGINT, jnp.asarray(key)), Column(BIGINT, jnp.asarray(val))],
        jnp.asarray(live))
    return batch, (live, flag, key, val, dictionary)


def _observe(run):
    """(result, compact-count syncs, kernels.compact launches) of ``run()``."""
    before = SG.snapshot()
    t0 = profiler.now()
    out = run()
    syncs = SG.take_delta(before).by_tag.get(SYNC, 0)
    launches = sum(1 for e in profiler.events_since(t0)
                   if e["kind"] == profiler.LAUNCH and e["name"] == COMPACT)
    return out, syncs, launches


def _aggregate(op: HashAggregationOperator, batch: ColumnBatch):
    op.add_input(batch)
    op.finish_input()
    out = op.get_output()
    return sorted(out.to_pylist(), key=repr) if out is not None else []


def _global_sum_count():
    return HashAggregationOperator(
        [], [AggCall("sum", 2, BIGINT), AggCall("count", -1, BIGINT)],
        ["s", "c"], [BIGINT, BIGINT], step="PARTIAL")


def _ref_global_sum_count(live, flag, key, val, _d):
    n = int(live.sum())
    return [(int(val[live].sum()) if n else None, n)]


def _small_dict_group_by():
    return HashAggregationOperator(
        [0], [AggCall("sum", 2, BIGINT), AggCall("count", -1, BIGINT)],
        ["flag", "s", "c"], [VARCHAR, BIGINT, BIGINT], step="PARTIAL")


def _ref_small_dict_group_by(live, flag, key, val, d):
    return sorted(((d[g], int(val[live & (flag == g)].sum()),
                    int((live & (flag == g)).sum()))
                   for g in np.unique(flag[live])), key=repr)


def _high_ndv_group_by():
    return HashAggregationOperator(
        [1], [AggCall("sum", 2, BIGINT)], ["key", "s"], [BIGINT, BIGINT])


def _ref_high_ndv_group_by(live, flag, key, val, _d):
    sums: dict = {}
    for k, v in zip(key[live].tolist(), val[live].tolist()):
        sums[k] = sums.get(k, 0) + v
    return sorted(sums.items(), key=repr)


def _global_distinct():
    return HashAggregationOperator(
        [], [AggCall("count", 1, BIGINT, distinct=True)], ["c"], [BIGINT])


def _ref_global_distinct(live, flag, key, val, _d):
    return [(len(np.unique(key[live])),)]


AGGREGATIONS = {
    # name: (operator, host reference, path, does the path sort its lanes)
    "global_sum_count": (_global_sum_count, _ref_global_sum_count,
                         "masked", False),
    "small_dict_group_by": (_small_dict_group_by, _ref_small_dict_group_by,
                            "masked", False),
    "high_ndv_group_by": (_high_ndv_group_by, _ref_high_ndv_group_by,
                          "sort", True),
    "global_distinct": (_global_distinct, _ref_global_distinct,
                        "sort", True),
}


@pytest.mark.parametrize("kind", ["sparse", "dense", "dead"])
@pytest.mark.parametrize("name", list(AGGREGATIONS) + ["top_n"])
def test_only_the_sorting_paths_pay_for_compaction(name, kind):
    batch, host = _input(kind)
    if name == "top_n":
        op = TopNOperator(10, [SortKey(2, ascending=False)])
        sorts = True

        def run():
            op.add_input(batch)  # over _shrink_at: sorts here already
            op.finish_input()
            out = op.get_output()
            return [r[2] for r in out.to_pylist()] if out is not None else []

        live, _, _, val, _ = host
        expected = sorted(val[live].tolist(), reverse=True)[:10]
    else:
        make, ref, path, sorts = AGGREGATIONS[name]
        op = make()
        run = lambda: _aggregate(op, batch)  # noqa: E731
        expected = ref(*host)
        if name == "global_sum_count" and kind == "dead":
            expected = [(None, 0)]
        if name == "global_distinct" and kind == "dead":
            expected = [(0,)]

    got, syncs, launches = _observe(run)
    assert got == expected
    if not sorts:
        # the masked reduction reads the dead lanes: nobody counts them,
        # nobody sorts them away
        assert (syncs, launches) == (0, 0)
        es = op.encoding_stats
        assert (es.agg_masked, es.agg_compaction_skipped,
                es.agg_compacted) == (1, 1, 0)
        # (since PR 30 these stream: ``lanes`` is the last batch's; since
        # PR 37 a launch takes a group: the one batch, at finish)
        assert op.trace_attrs == {"path": "masked", "compaction": "skipped",
                                  "lanes": LANES, "mode": "streamed",
                                  "fused": False, "batches": 1}
    else:
        # a sort follows: one count sync each time, and the compaction when
        # under a quarter of the lanes live
        sparse = kind != "dense"
        assert syncs >= 1 and (launches >= 1) == sparse
        if name != "top_n":
            es = op.encoding_stats
            assert (es.agg_sort, es.agg_compacted,
                    es.agg_compaction_skipped) == (1, int(sparse), 0)
            assert op.trace_attrs["path"] == path
            assert op.trace_attrs["compaction"] == (
                "compacted" if sparse else "none")
            assert (op.trace_attrs["lanes"] < LANES) == sparse


def test_sort_operator_still_compacts():
    batch, (live, _, _, val, _) = _input("sparse")
    op = SortOperator([SortKey(2)])

    def run():
        op.add_input(batch)
        op.finish_input()
        return [r[2] for r in op.get_output().to_pylist()]

    got, syncs, launches = _observe(run)
    assert got == sorted(val[live].tolist())
    assert (syncs, launches) == (1, 1)


def test_codes_sort_path_compacts():
    """A dictionary group space over MASKED_AGG_LIMIT argsorts fused codes
    (group_ids_codes): a sorting path, so it still compacts."""
    groups = O.K.MASKED_AGG_LIMIT + 72
    batch, host = _input("sparse", groups=groups)
    op = _small_dict_group_by()
    got, syncs, launches = _observe(lambda: _aggregate(op, batch))
    assert got == _ref_small_dict_group_by(*host)
    assert (syncs, launches) == (1, 1)
    assert op.trace_attrs["path"] == "codes-sort"
    assert op.trace_attrs["compaction"] == "compacted"
    assert op.encoding_stats.agg_codes_sort == 1


@pytest.mark.parametrize("lanes, groups, reductions, masked_cheaper", [
    # the grid read on the chip (tools/compaction_crossover.py, PERF.md s.6):
    (1 << 25, 1, 1, True),      # Q6: 6 ms against a 229 ms compaction
    (1 << 20, 6, 11, True),     # Q1: 3.4 ms against 5.3 ms
    (1 << 22, 128, 4, True),    # 8.2 ms against 16.2 ms
    (1 << 22, 128, 11, False),  # 18.4 ms against 16.2 ms
    (1 << 20, 128, 20, False),  # 10.6 ms against 5.3 ms
    (1 << 16, 128, 20, True),   # the count sync alone outweighs 2^16 lanes
])
def test_the_rule_follows_the_chip_readings(lanes, groups, reductions,
                                            masked_cheaper):
    assert O._masked_reads_dead_lanes_cheaper(
        lanes, groups, reductions) == masked_cheaper


@pytest.mark.parametrize("kind", ["sparse", "dense"])
def test_past_the_crossover_the_masked_path_compacts_as_before(
        kind, monkeypatch):
    """Groups x reductions beyond what the masked kernel reads cheaper than a
    sort: today's behaviour is kept -- count, and compact when sparse.  (A
    cheap compaction brings the crossover down to a size a CPU test can
    afford; the rule's own constants are the test above.)"""
    monkeypatch.setattr(O, "_COUNT_SYNC_S", 0.0)
    monkeypatch.setattr(O, "_COMPACT_S_PER_LANE",
                        O._MASKED_S_PER_LANE_REDUCTION * 3)
    groups, n_aggs = 100, 2
    assert not O._masked_reads_dead_lanes_cheaper(LANES, groups, n_aggs)
    assert O._masked_reads_dead_lanes_cheaper(LANES, groups, n_aggs - 1)
    batch, (live, flag, key, val, d) = _input(kind, groups=groups)
    op = HashAggregationOperator(
        [0], [AggCall("sum", 2, BIGINT)] * n_aggs,
        ["flag"] + [f"s{i}" for i in range(n_aggs)],
        [VARCHAR] + [BIGINT] * n_aggs, step="PARTIAL")
    got, syncs, launches = _observe(lambda: _aggregate(op, batch))
    expected = sorted(
        ((d[g],) + (int(val[live & (flag == g)].sum()),) * n_aggs
         for g in np.unique(flag[live])), key=repr)
    assert got == expected
    sparse = kind == "sparse"
    assert (syncs, launches) == (1, int(sparse))
    assert op.trace_attrs["path"] == "masked"
    assert op.trace_attrs["compaction"] == ("compacted" if sparse else "none")


def test_reduction_count_reads_the_aggregate_list():
    batch, _ = _input("dense")
    op = HashAggregationOperator(
        [0], [AggCall("sum", 2, BIGINT), AggCall("avg", 2, BIGINT),
              AggCall("stddev_samp", 2, BIGINT), AggCall("count", -1, BIGINT)],
        ["flag", "s", "a", "d", "c"], [VARCHAR] + [BIGINT] * 4)
    assert op._reduction_count(batch) == 1 + 2 + 3 + 1


def test_small_and_host_inputs_are_no_candidates():
    """Under _COMPACT_MIN_LANES, or with a host mask, nobody was ever
    counted: recorded as ``none``, not as a skip."""
    n = 1000
    batch = ColumnBatch(
        ["flag", "key", "val"],
        [Column(VARCHAR, np.zeros(n, np.int32), None, FLAGS),
         Column(BIGINT, np.arange(n, dtype=np.int64)),
         Column(BIGINT, np.ones(n, np.int64))],
        np.arange(n) % 2 == 0)
    for make in (_global_sum_count, _high_ndv_group_by):
        op = make()
        _, syncs, launches = _observe(lambda: _aggregate(op, batch))
        assert (syncs, launches) == (0, 0)
        assert op.trace_attrs["compaction"] == "none"
        assert op.encoding_stats.agg_compaction_skipped == 0


def test_finish_event_and_explain_analyze_record_path_and_compaction(
        monkeypatch):
    """The flight recorder's ``operator`` event of the aggregation's finish
    carries the path and what became of compaction, and EXPLAIN ANALYZE
    prints the query's roll-up beside the encoding counters."""
    monkeypatch.setenv("TRINO_TPU_RESULT_CACHE", "0")
    runner = StandaloneQueryRunner(default_catalog(scale_factor=0.01))
    t0 = profiler.now()
    runner.execute("select sum(l_extendedprice * l_discount) from lineitem "
                   "where l_quantity < 24")
    finishes = [e for e in profiler.events_since(t0)
                if e["kind"] == profiler.OPERATOR
                and e["name"] == "HashAggregationOperator.finish"]
    assert finishes
    for e in finishes:
        assert e["args"]["path"] == "masked"
        assert e["args"]["compaction"] in ("skipped", "none")
        assert e["args"]["lanes"] > 0
    text = "\n".join(r[0] for r in runner.execute(
        "explain analyze select l_returnflag, l_linestatus, sum(l_quantity) "
        "from lineitem group by l_returnflag, l_linestatus").rows())
    assert "aggregations: " in text and " masked / 0 codes-sort / 0 sort" in text
    assert "0 compacted" in text
