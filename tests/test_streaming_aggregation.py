"""The streaming masked aggregation (PR 30): a PARTIAL or SINGLE aggregation
that will take the masked path folds each batch into a small device-resident
state with ONE program -- the filter/project's own when one sits directly in
front -- instead of buffering its input and reducing it later.  Since PR 37
that program takes a GROUP of up to ``O._FOLD_GROUP`` batches a launch: the
operator holds a batch's operands until the group is full or something ends
it (another signature, other dictionaries, finish, close).

Equivalence is streamed against buffered (the same operator with its
decision steered to "buffer", the way every aggregation ran before); the
counts are the ones a CPU run may report: flight-recorder ``launch`` events,
syncguard's hot region, ``PjitFunction`` rows of a ``jax.profiler`` trace,
pages out, and the ``agg_*`` counters."""

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.connectors.catalog import default_catalog
from trino_tpu.exec import operators as O
from trino_tpu.exec import syncguard as SG
from trino_tpu.exec.driver import Driver
from trino_tpu.exec.operators import (FilterProjectOperator,
                                      HashAggregationOperator, Operator,
                                      OutputCollector, plan_aggregation_feed)
from trino_tpu.execution.distributed_runner import DistributedQueryRunner
from trino_tpu.ops.expr import QueryError, check_error_scalars
from trino_tpu.planner.plan import AggCall
from trino_tpu.runner import StandaloneQueryRunner
from trino_tpu.spi.batch import Column, ColumnBatch
from trino_tpu.spi.types import (BIGINT, BOOLEAN, DOUBLE, VARCHAR,
                                 DecimalType)
from trino_tpu.sql.ir import Call, InputRef, Literal
from trino_tpu.telemetry import profiler

ROWS = 4096
GROUP = O._FOLD_GROUP
FLAGS = np.array(["A", "N", "R"], dtype=object)
FLAGS_WIDER = np.array(["A", "B", "N", "R"], dtype=object)
STATUS = np.array(["F", "O"], dtype=object)
NAMES = ["flag", "status", "v", "d"]

FOLD = "trino_kernels_small_agg_fold"
FUSED = "trino_operators_filter_project_agg"
FILTER = "trino_operators_filter_project"


@pytest.fixture(autouse=True)
def _recorder(monkeypatch):
    monkeypatch.setenv("TRINO_TPU_RESULT_CACHE", "0")
    prev = profiler.set_level(1)
    profiler.reset_for_test()
    yield
    profiler.set_level(prev)
    profiler.reset_for_test()


# ---------------------------------------------------------------- the data

def _batch(seed, n=ROWS, *, flags=FLAGS, nulls=False, live="dense",
           device=False):
    """flag / status (dictionary codes), v BIGINT, d DOUBLE; ``nulls`` puts
    NULLs into the first key and both arguments; ``live``: dense (90 %), dead
    (none), or None (no mask)."""
    rng = np.random.default_rng(seed)
    put = jnp.asarray if device else np.asarray

    def valid():
        return put(rng.random(n) < 0.8) if nulls else None

    cols = [
        Column(VARCHAR, put(rng.integers(0, len(flags), n).astype(np.int32)),
               valid(), flags),
        Column(VARCHAR, put(rng.integers(0, 2, n).astype(np.int32)), None,
               STATUS),
        Column(BIGINT, put(rng.integers(-1000, 1000, n).astype(np.int64)),
               valid()),
        Column(DOUBLE, put(rng.normal(10.0, 3.0, n)), valid()),
    ]
    mask = {"dense": rng.random(n) < 0.9, "dead": np.zeros(n, bool),
            None: None}[live]
    return ColumnBatch(NAMES, cols, None if mask is None else put(mask))


# every reduction the masked path has, over a BIGINT, a DOUBLE and a
# dictionary-typed argument
AGGS = [AggCall("sum", 2, BIGINT), AggCall("count", 2, BIGINT),
        AggCall("count", -1, BIGINT), AggCall("min", 2, BIGINT),
        AggCall("max", 2, BIGINT), AggCall("avg", 2, DOUBLE),
        AggCall("stddev_samp", 3, DOUBLE), AggCall("var_pop", 3, DOUBLE),
        AggCall("sum", 3, DOUBLE), AggCall("max", 3, DOUBLE),
        AggCall("max", 1, VARCHAR)]


def _agg(group_keys=(0, 1), aggs=None, step="SINGLE"):
    aggs = AGGS if aggs is None else aggs
    return HashAggregationOperator(
        list(group_keys), aggs,
        [NAMES[k] for k in group_keys] + [f"a{i}" for i in range(len(aggs))],
        [VARCHAR] * len(group_keys) + [a.type for a in aggs], step)


def _pages(op: Operator, batches):
    pages = []
    for b in batches:
        op.add_input(b)
        while (p := op.get_output()) is not None:
            pages.append(p)
    op.finish_input()
    while (p := op.get_output()) is not None:
        pages.append(p)
    assert op.is_finished()
    return pages


def _rows(pages):
    return sorted((r for p in pages for r in p.to_pylist()), key=repr)


def _same(got, want):
    assert len(got) == len(want), (got, want)
    for g, w in zip(got, want):
        assert len(g) == len(w)
        for a, b in zip(g, w):
            if isinstance(a, float) and isinstance(b, float):
                assert a == pytest.approx(b, rel=1e-9, abs=1e-9)
            else:
                assert a == b, (g, w)


def _buffered(monkeypatch):
    """Every aggregation made from here on buffers, as before PR 30."""
    monkeypatch.setattr(HashAggregationOperator, "_streams",
                        lambda self, inp: False)


def _three(**kw):
    return [_batch(s, **kw) for s in (1, 2, 3)]


STREAMS = {
    # name: (batches, group keys, seals expected, launches expected: one a
    # group, and a group ends where the signature or the dictionaries change)
    "global": (lambda: _three(), (), 0, 1),
    "grouped": (lambda: _three(), (0, 1), 0, 1),
    "global_no_live_mask": (lambda: _three(live=None), (), 0, 1),
    "grouped_device_batches": (lambda: _three(device=True), (0, 1), 0, 1),
    "nullable_keys_and_arguments": (lambda: _three(nulls=True), (0, 1), 0, 1),
    "global_nullable_arguments": (lambda: _three(nulls=True), (), 0, 1),
    "an_all_filtered_batch": (
        lambda: [_batch(1), _batch(2, live="dead"), _batch(3)], (0, 1), 0, 1),
    "a_zero_row_batch_in_the_group": (
        lambda: [_batch(1), _batch(2, live=None).slice(0, 0), _batch(3)],
        (0, 1), 0, 1),
    "only_filtered_batches_global": (
        lambda: [_batch(1, live="dead"), _batch(2, live="dead")], (), 0, 1),
    "only_filtered_batches_grouped": (
        lambda: [_batch(1, live="dead"), _batch(2, live="dead")], (0, 1), 0,
        1),
    "last_batch_in_a_smaller_bucket": (
        # another signature: the two held batches are folded first
        lambda: [_batch(1, live=None), _batch(2, live=None),
                 _batch(3, n=1000, live=None)], (0, 1), 0, 2),
    "validity_appears_mid_stream": (
        # the null slot changes the group space: a new state
        lambda: [_batch(1), _batch(2, nulls=True), _batch(3, nulls=True)],
        (0, 1), 1, 2),
    "dictionary_change_mid_stream": (
        lambda: [_batch(1), _batch(2), _batch(3, flags=FLAGS_WIDER),
                 _batch(4, flags=FLAGS_WIDER)], (0, 1), 1, 2),
    "dictionary_change_every_batch": (
        lambda: [_batch(s, flags=FLAGS.copy()) for s in (1, 2, 3)],
        (0,), 2, 3),
    "a_group_and_a_remainder": (
        lambda: [_batch(s, n=512) for s in range(GROUP + 3)], (0, 1), 0, 2),
}


@pytest.mark.parametrize("name", list(STREAMS))
def test_streamed_equals_buffered(name, monkeypatch):
    make, keys, seals, groups = STREAMS[name]
    batches = make()
    streamed = sum(1 for b in batches if b.num_rows)
    op = _agg(keys)
    t0 = profiler.now()
    got = _pages(op, batches)
    es = op.encoding_stats
    assert op._streamed and not op._batches
    assert (es.agg_streamed_batches, es.agg_fold_launches) \
        == (streamed, groups)
    assert (es.agg_state_seals, es.agg_fused_feed) == (seals, 0)
    assert len(got) == 1                      # SINGLE: one page, seals merge
    launches = [e["name"] for e in profiler.events_since(t0)
                if e["kind"] == profiler.LAUNCH]
    assert launches.count(FOLD) == groups     # one launch a group
    assert op.trace_attrs["mode"] == "streamed"
    assert op.trace_attrs["lanes"] == O.K.bucket(batches[-1].num_rows)

    _buffered(monkeypatch)
    ref = _agg(keys)
    want = _pages(ref, batches)
    assert not ref._streamed and ref.trace_attrs["mode"] == "buffered"
    _same(_rows(got), _rows(want))
    if "only_filtered" in name:
        # a global aggregate over no live row is one row of defaults
        assert len(_rows(got)) == (0 if keys else 1)


@pytest.mark.parametrize("keys", [(), (0, 1)], ids=["global", "grouped"])
@pytest.mark.parametrize("zero_row_batch", [False, True],
                         ids=["no_batch", "a_zero_row_batch"])
def test_empty_input(keys, zero_row_batch):
    """Nothing to decide on: grouped -> no rows, global -> one row of
    defaults, and no state was ever made."""
    op = _agg(keys, [AggCall("sum", 2, BIGINT), AggCall("count", -1, BIGINT)])
    batches = [_batch(1, live=None).slice(0, 0)] if zero_row_batch else []
    t0 = profiler.now()
    rows = _rows(_pages(op, batches))
    assert rows == ([] if keys else [(None, 0)])
    assert op._streamed is None and op._stream is None
    assert not [e for e in profiler.events_since(t0)
                if e["kind"] == profiler.LAUNCH]


def test_partial_seals_leave_as_pages():
    """Under PARTIAL a sealed state is just one more page of mergeable
    states: it leaves at once and downstream adds it up."""
    aggs = [AggCall("sum", 2, BIGINT), AggCall("count", -1, BIGINT)]
    batches = [_batch(1), _batch(2, flags=FLAGS_WIDER), _batch(3, flags=FLAGS)]
    op = _agg((0,), aggs, step="PARTIAL")
    pages = _pages(op, batches)
    assert len(pages) == 3 and op.encoding_stats.agg_state_seals == 2
    assert op.encoding_stats.agg_masked == 3
    totals: dict = {}
    for flag, s, c in _rows(pages):
        ps, pc = totals.get(flag, (0, 0))
        totals[flag] = (ps + (s or 0), pc + c)
    want: dict = {}
    for b in batches:
        live = np.asarray(b.live)
        flags = b.columns[0].dictionary[np.asarray(b.columns[0].data)]
        for f in np.unique(flags[live]):
            m = live & (flags == f)
            ps, pc = want.get(f, (0, 0))
            want[f] = (ps + int(np.asarray(b.columns[2].data)[m].sum()),
                       pc + int(m.sum()))
    assert {k: v for k, v in totals.items() if v[1]} == want


def test_partial_avg_and_variance_states_stream(monkeypatch):
    """PARTIAL emits (sum, count) and (sum, sum of squares, count) states:
    the same pages streamed and buffered."""
    aggs = [AggCall("avg", 2, DOUBLE), AggCall("stddev_pop", 3, DOUBLE)]
    batches = _three()
    op = _agg((0, 1), aggs)._partial_twin(batches[0].columns)
    got = _pages(op, batches)
    assert op._streamed and len(got) == 1 and got[0].num_columns == 2 + 5
    _buffered(monkeypatch)
    ref = _agg((0, 1), aggs)._partial_twin(batches[0].columns)
    ref.FLUSH_ROWS = 1 << 30
    _same(_rows(got), _rows(_pages(ref, batches)))


# ------------------------------------------------- what must NOT stream

def _high_ndv_batches():
    out = []
    for b in _three():
        key = Column(BIGINT, np.arange(b.num_rows, dtype=np.int64) % 1500)
        out.append(ColumnBatch(NAMES, [key] + b.columns[1:], b.live))
    return out


def _long_decimal_batches():
    from trino_tpu.spi.types import DecimalType

    t = DecimalType(30, 2)
    out = []
    for b in _three():
        values = [10 ** 20 + int(x) for x in np.asarray(b.columns[2].data)]
        out.append(ColumnBatch(
            NAMES, b.columns[:2] + [Column.from_values(t, values),
                                    b.columns[3]], b.live))
    return t, out


def _not_streaming_case(name):
    if name == "distinct":
        return _agg((0,), [AggCall("count", 2, BIGINT, distinct=True)]), \
            _three()
    if name == "long_decimal_sum":
        t, batches = _long_decimal_batches()
        from trino_tpu.spi.types import DecimalType

        return _agg((0,), [AggCall("sum", 2, DecimalType(38, 2))]), batches
    if name == "final_step":
        # a FINAL step's input is a few tiny pages of states
        return _agg((0,), [AggCall("sum", 1, BIGINT)], step="FINAL"), [
            ColumnBatch(["flag", "s"], [b.columns[0], b.columns[2]], b.live)
            for b in _three()]
    if name == "high_ndv_key":
        op = HashAggregationOperator(
            [0], [AggCall("sum", 2, BIGINT)], ["k", "s"], [BIGINT, BIGINT])
        return op, _high_ndv_batches()
    assert name == "rle_fold"
    batches = [ColumnBatch(
        NAMES, b.columns[:2] + [Column.rle(BIGINT, 7, b.num_rows),
                                b.columns[3]], None) for b in _three()]
    return _agg((), [AggCall("sum", 2, BIGINT)]), batches


@pytest.mark.parametrize("name", ["distinct", "long_decimal_sum",
                                  "final_step", "high_ndv_key", "rle_fold"])
def test_these_buffer_as_before(name):
    op, batches = _not_streaming_case(name)
    t0 = profiler.now()
    pages = _pages(op, batches)
    assert op._streamed is False and op._stream is None
    assert op.encoding_stats.agg_streamed_batches == 0
    assert pages and sum(p.num_rows for p in pages) > 0
    assert FOLD not in [e["name"] for e in profiler.events_since(t0)
                        if e["kind"] == profiler.LAUNCH]
    if name != "rle_fold":  # (the RLE fold never reaches a reduction path)
        assert op.trace_attrs["mode"] == "buffered"
        assert op.trace_attrs["fused"] is False


def test_past_the_crossover_it_buffers_counts_and_compacts(monkeypatch):
    """Groups x reductions beyond what the masked kernel reads cheaper than
    a sort: the operator buffers, and _compute counts and compacts as it
    did (tests/test_compaction_policy.py has the rule's own numbers)."""
    monkeypatch.setattr(O, "_COUNT_SYNC_S", 0.0)
    monkeypatch.setattr(O, "_COMPACT_S_PER_LANE",
                        O._MASKED_S_PER_LANE_REDUCTION * 3)
    groups = np.array([f"g{i:03d}" for i in range(100)], dtype=object)
    aggs = [AggCall("sum", 2, BIGINT)] * 2
    assert not O._masked_reads_dead_lanes_cheaper(ROWS, 100, len(aggs))
    assert O._masked_reads_dead_lanes_cheaper(ROWS, 100, 1)
    batches = [_batch(s, flags=groups) for s in (1, 2)]
    over, under = _agg((0,), aggs), _agg((0,), aggs[:1])
    _pages(over, batches)
    _pages(under, batches)
    assert over._streamed is False and under._streamed is True
    assert over.trace_attrs["path"] == "masked"
    assert over.trace_attrs["mode"] == "buffered"


# ------------------------------------------------------- the fused program

class _Source(Operator):
    def __init__(self, batches):
        self._batches = list(batches)

    def needs_input(self):
        return False

    def get_output(self):
        return self._batches.pop(0) if self._batches else None

    def is_finished(self):
        return not self._batches


def _ref(i, t=BIGINT):
    return InputRef(t, i)


def _filter_project(predicate=True):
    """where v % 3 <> 0 (or none): flag, status, v * 2, d."""
    pred = Call(BOOLEAN, "ne", (Call(BIGINT, "modulus",
                                     (_ref(2), Literal(BIGINT, 3))),
                                Literal(BIGINT, 0))) if predicate else None
    return FilterProjectOperator(
        pred,
        [_ref(0, VARCHAR), _ref(1, VARCHAR),
         Call(BIGINT, "multiply", (_ref(2), Literal(BIGINT, 2))),
         _ref(3, DOUBLE)],
        NAMES, [VARCHAR, VARCHAR, BIGINT, DOUBLE])


def _finish_attrs(t0):
    """What the aggregation's ``.finish`` events since ``t0`` carried (the
    driver moves ``trace_attrs`` onto them)."""
    return [e["args"] for e in profiler.events_since(t0)
            if e["kind"] == profiler.OPERATOR
            and e["name"] == "HashAggregationOperator.finish"]


def _pipeline(batches, fp, agg, fuse=True):
    sink = OutputCollector()
    ops = [_Source(batches), fp, agg, sink]
    if fuse:
        plan_aggregation_feed(ops)
    Driver(ops).run()
    return sink.batches


FUSED_STREAMS = ["global", "grouped", "grouped_device_batches",
                 "nullable_keys_and_arguments", "an_all_filtered_batch",
                 "last_batch_in_a_smaller_bucket",
                 "dictionary_change_mid_stream"]


@pytest.mark.parametrize("step", ["SINGLE", "PARTIAL"])
@pytest.mark.parametrize("name", FUSED_STREAMS)
def test_fused_equals_unfused_equals_buffered(name, step, monkeypatch):
    make, keys, seals, groups = STREAMS[name]
    batches = make()
    aggs = AGGS if step == "SINGLE" else [
        AggCall("sum", 2, BIGINT), AggCall("count", -1, BIGINT),
        AggCall("min", 3, DOUBLE)]
    fp, agg = _filter_project(), _agg(keys, aggs, step)
    t0 = profiler.now()
    fused = _pipeline(batches, fp, agg)
    launches = [e["name"] for e in profiler.events_since(t0)
                if e["kind"] == profiler.LAUNCH]
    es = agg.encoding_stats
    assert launches.count(FUSED) == groups    # one launch a group
    assert FILTER not in launches and FOLD not in launches
    assert (es.agg_fused_feed, es.agg_streamed_batches, es.agg_fold_launches,
            es.agg_state_seals) == (1, len(batches), groups, seals)
    assert len(fused) == (1 if step == "SINGLE" else 1 + seals)
    assert es.agg_masked == 1 + seals
    attrs, = _finish_attrs(t0)
    assert (attrs["mode"], attrs["fused"]) == ("streamed", True)
    assert attrs["lanes"] == O.K.bucket(batches[-1].num_rows)
    assert attrs["compaction"] == "none"      # under _COMPACT_MIN_LANES
    # ``v * 2`` can overflow: one error scalar a state, none a batch
    assert not fp.pending_errors and len(agg.pending_errors) == 1 + seals
    check_error_scalars(agg.pending_errors)

    unfused_agg = _agg(keys, aggs, step)
    unfused = _pipeline(batches, _filter_project(), unfused_agg, fuse=False)
    assert unfused_agg._streamed
    assert unfused_agg.encoding_stats.agg_fused_feed == 0
    _buffered(monkeypatch)
    ref = _agg(keys, aggs, step)
    ref.FLUSH_ROWS = 1 << 30
    buffered = _pipeline(batches, _filter_project(), ref)
    assert ref._streamed is False

    def totals(pages):
        """PARTIAL pages of one generation each: add up per group."""
        if step == "SINGLE":
            return _rows(pages)
        acc: dict = {}
        nk = len(keys)
        for r in _rows(pages):
            s, c, m = acc.get(r[:nk], (None, 0, None))
            rs, rc, rm = r[nk:]
            s = rs if s is None else s if rs is None else s + rs
            m = rm if m is None else m if rm is None else min(m, rm)
            acc[r[:nk]] = (s, c + rc, m)
        return sorted((k + v for k, v in acc.items() if v[1]), key=repr)

    _same(totals(fused), totals(buffered))
    _same(totals(unfused), totals(buffered))


def test_a_feed_that_does_not_stream_runs_both_operators_as_before():
    """The decision is the aggregation's: a high-NDV key buffers, and the
    filter/project in front runs its own program per batch."""
    batches = _high_ndv_batches()
    fp = FilterProjectOperator(
        None, [_ref(0), _ref(2)], ["k", "v"], [BIGINT, BIGINT])
    agg = HashAggregationOperator(
        [0], [AggCall("sum", 1, BIGINT)], ["k", "s"], [BIGINT, BIGINT])
    t0 = profiler.now()
    pages = _pipeline(batches, fp, agg)
    launches = [e["name"] for e in profiler.events_since(t0)
                if e["kind"] == profiler.LAUNCH]
    assert agg.feed is fp and agg._fused is False and not agg._streamed
    assert launches.count(FILTER) == len(batches) and FUSED not in launches
    assert sum(p.num_rows for p in pages) == 1500


@pytest.mark.parametrize("step, distinct, absorbed", [
    ("PARTIAL", False, True), ("SINGLE", False, True),
    ("FINAL", False, False), ("SINGLE", True, False)])
def test_the_peephole_hands_over_only_what_may_stream(step, distinct,
                                                      absorbed):
    fp = _filter_project()
    agg = _agg((0,), [AggCall("count", 2, BIGINT, distinct=distinct)], step)
    pipeline = [_Source([]), fp, agg, OutputCollector()]
    plan_aggregation_feed(pipeline)
    assert (agg.feed is fp, fp.consumer is agg) == (absorbed, absorbed)
    # ... and never across another operator
    other = [_Source([]), _filter_project(), O.RenameOperator(NAMES),
             _agg((0,)), OutputCollector()]
    plan_aggregation_feed(other)
    assert other[1].consumer is None and other[3].feed is None


def _division(filtered: bool):
    """sum(v / (v % 2)) = sum(|v|) over odd v; an even v divides by zero --
    raised only if such a row is live after the filter ``v % 2 <> 0``."""
    two = Literal(BIGINT, 2)
    odd = Call(BOOLEAN, "ne", (Call(BIGINT, "modulus", (_ref(2), two)),
                               Literal(BIGINT, 0)))
    return FilterProjectOperator(
        odd if filtered else None,
        [_ref(0, VARCHAR),
         Call(BIGINT, "divide", (_ref(2), Call(BIGINT, "modulus",
                                               (_ref(2), two))))],
        ["flag", "q"], [VARCHAR, BIGINT])


@pytest.mark.parametrize("filtered", [False, True],
                         ids=["zero_divisor_in_a_live_row",
                              "zero_divisor_in_a_filtered_out_row"])
def test_the_error_scalar_rides_in_the_state(filtered):
    """One int32 in the state, a running max over the batches, appended to
    ``pending_errors`` once at finish: a failing row that is live raises, one
    that the WHERE clause took out does not."""
    batches = _three()
    agg = HashAggregationOperator(
        [0], [AggCall("sum", 1, BIGINT)], ["flag", "s"], [VARCHAR, BIGINT])
    fp = _division(filtered)
    sink = OutputCollector()
    ops = [_Source(batches), fp, agg, sink]
    plan_aggregation_feed(ops)
    if not filtered:
        with pytest.raises(QueryError, match="(?i)division"):
            Driver(ops).run()
        return
    Driver(ops).run()
    assert agg._fused and len(agg.pending_errors) == 1
    assert not fp.pending_errors
    check_error_scalars(agg.pending_errors)
    want: dict = {}
    for b in batches:
        v = np.asarray(b.columns[2].data)
        flags = FLAGS[np.asarray(b.columns[0].data)]
        m = np.asarray(b.live) & (v % 2 != 0)
        for f in FLAGS:
            want[f] = want.get(f, 0) + int(np.abs(v[m & (flags == f)]).sum())
    assert dict(_rows(sink.batches)) == want


# ------------------------------------------- the counts a CPU run can give

def _pjit_names(trace_dir):
    """Names of the jitted calls on the trace's host plane, in order: an
    engine program is ``trino_<site>``, an eager ``jnp`` operation its own
    name (``concatenate``, ``broadcast_in_dim`` ...).  The tracer writes a
    call twice, one event inside the other: the inner one is dropped."""
    xplane = sorted(glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    data = jax.profiler.ProfileData.from_file(xplane)
    calls = sorted(
        (e.start_ns, -e.duration_ns, e.name[len("PjitFunction("):-1])
        for plane in data.planes if plane.name == "/host:CPU"
        for line in plane.lines
        for e in line.events if e.name.startswith("PjitFunction("))
    names, open_until = [], -1
    for start, neg_dur, name in calls:
        if start >= open_until:
            names.append(name)
            open_until = start - neg_dur
    return names


def test_one_named_launch_a_group_and_nothing_else(tmp_path):
    """The fused hot loop on device-resident batches: per GROUP of batches
    ONE named program (the recorder's ``launch`` events and the trace's
    ``PjitFunction`` rows agree) and nothing else in between -- no eager
    ``jnp`` dispatch, no host sync, nothing at all for a batch that is only
    held; one page out."""
    n = 2 * GROUP + 3

    def batches():
        return [_batch(s, n=512, device=True) for s in range(n)]

    _pipeline(batches(), _filter_project(), _agg())       # warm: compiles
    hot = batches()
    fp, agg = _filter_project(), _agg()
    plan_aggregation_feed([fp, agg])

    def move(b):
        fp.add_input(b)
        agg.add_input(fp.get_output())

    move(hot[0])                              # opens the stream: zero state
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    before = SG.snapshot()
    t0 = profiler.now()
    launched = []
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with SG.forbidden(), SG.hot_region():
            for b in hot[1:]:
                move(b)
                launched.append(agg.trace_attrs["batches"])
            fp.finish_input()
            agg.finish_input()                # the remainder: one more
    finally:
        jax.profiler.stop_trace()
    # batches 1 .. 2K+3: a launch when the K-th and the 2K-th arrive, the
    # three left at finish
    assert launched == ([0] * (GROUP - 2) + [GROUP] + [0] * (GROUP - 1)
                        + [GROUP] + [0] * 3)
    assert agg.trace_attrs["batches"] == 3
    events = profiler.events_since(t0)
    named = [e["name"] for e in events if e["kind"] == profiler.LAUNCH]
    assert named[:3] == [FUSED] * 3 and FUSED not in named[3:]
    assert not [e for e in events if e["kind"] == profiler.HOST_SYNC]
    delta = SG.take_delta(before)
    assert (delta.host_syncs, delta.hot_loop_syncs) == (0, 0)
    # nothing eager in between, and after the last fold only finalization
    pjit = _pjit_names(tmp_path)
    assert pjit[:3] == [FUSED] * 3 and pjit == named
    out = agg.get_output()
    assert out is not None and agg.get_output() is None
    es = agg.encoding_stats
    assert (es.agg_streamed_batches, es.agg_fold_launches, es.agg_fused_feed,
            es.agg_state_seals, es.agg_masked) == (n, 3, 1, 0, 1)
    _same(_rows([out]), _rows(_pipeline(batches(), _filter_project(),
                                        _agg(), fuse=False)))


def test_memory_accounting_sees_the_state_and_the_held_batches():
    """The state for as long as the stream lives; a held batch's device
    bytes from the call that holds it to the launch that folds it (host
    batches hold nothing on the device)."""
    from trino_tpu.exec.revoking import TaskMemoryContext, batch_device_nbytes

    mem = TaskMemoryContext(1 << 30, 0)
    op = _agg()
    op.attach_memory(mem)
    op.add_input(_batch(1))
    op.add_input(_batch(2))
    layout = op._stream.layout
    state_bytes = 6 * sum(np.dtype(d).itemsize for _, d in layout)
    assert sum(int(np.asarray(c).nbytes) for c in op._stream.state) \
        == state_bytes
    assert mem.reserved_bytes() == state_bytes      # host batches
    held = [_batch(s, device=True) for s in range(3, 1 + GROUP)]
    one = batch_device_nbytes(held[0])
    assert one > ROWS * 8
    for i, b in enumerate(held[:-1], start=1):
        op.add_input(b)               # the same signature: the group grows
        assert mem.reserved_bytes() == state_bytes + i * one
    assert op.encoding_stats.agg_fold_launches == 0
    op.add_input(held[-1])            # the group is full: launched, let go
    assert op.encoding_stats.agg_fold_launches == 1
    assert not op._stream.pending and mem.reserved_bytes() == state_bytes
    op.add_input(_batch(20, device=True))
    assert mem.reserved_bytes() == state_bytes + one
    assert op.revoke_memory() == 0            # nothing buffered to revoke
    op.finish_input()
    assert op.get_output().num_rows == 6 and mem.reserved_bytes() == 0
    assert op.encoding_stats.agg_fold_launches == 2


@pytest.mark.parametrize("fused", [False, True], ids=["unfused", "fused"])
def test_a_pinned_tables_batches_reserve_nothing_while_held(fused):
    """What a scan reads from a table pinned to the device is the
    connector's own storage (``ColumnBatch.resident``, kept through the page
    source's column selection): the group that holds it keeps nothing alive
    that was not, so only the state is reserved -- where the same arrays
    from anywhere else (a staged batch) are reserved for as long as they
    are held."""
    from trino_tpu.connectors.memory import MemoryConnector
    from trino_tpu.exec.revoking import TaskMemoryContext, batch_device_nbytes
    from trino_tpu.spi.connector import ColumnSchema, TableSchema

    mem_conn = MemoryConnector()
    mem_conn.create_table(TableSchema("t", [
        ColumnSchema(n, t) for n, t in zip(
            NAMES, [VARCHAR, VARCHAR, BIGINT, DOUBLE])]))
    mem_conn.finish_insert("t", [[_batch(s, live=None) for s in range(3)]])
    mem_conn.pin_to_device("t")
    src = mem_conn.create_page_source(mem_conn.get_splits("t", 1, 1)[0], NAMES)
    pinned = [src.get_next_batch() for _ in range(3)]
    assert all(b.resident and batch_device_nbytes(b) > ROWS * 8
               for b in pinned)

    def reserved_while_held(batches):
        mem = TaskMemoryContext(1 << 30, 0)
        fp, agg = _filter_project(), _agg()
        agg.attach_memory(mem)
        if fused:
            plan_aggregation_feed([fp, agg])
        seen = []
        for b in batches:
            fp.add_input(b)
            agg.add_input(fp.get_output())
            seen.append(mem.reserved_bytes() - agg._stream.state_bytes)
        assert len(agg._stream.pending) == 3
        agg.finish_input()
        assert mem.reserved_bytes() == 0
        return seen, _rows([agg.get_output()])

    held, rows = reserved_while_held(pinned)
    # fused, the pinned batch itself is held; unfused, the filter/project's
    # fresh output is, which nothing else keeps alive
    assert (held == [0, 0, 0]) == fused
    staged = [ColumnBatch(b.names, b.columns, b.live) for b in pinned]
    held_staged, rows_staged = reserved_while_held(staged)
    assert held_staged[0] > ROWS * 8 and held_staged[2] == 3 * held_staged[0]
    assert rows == rows_staged


def test_close_with_a_group_pending_leaves_nothing_held():
    """Downstream is done (a LIMIT): the held batches are let go unfolded,
    their reservation with them."""
    from trino_tpu.exec.revoking import TaskMemoryContext

    mem = TaskMemoryContext(1 << 30, 0)
    op = _agg()
    op.attach_memory(mem)
    t0 = profiler.now()
    for s in range(3):
        op.add_input(_batch(s, device=True))
    st = op._stream
    assert len(st.pending) == 3 and st.held_bytes > 0
    state_bytes = mem.reserved_bytes() - st.held_bytes
    assert state_bytes == st.state_bytes > 0
    op.close()
    assert op.is_finished() and not st.pending and st.held_bytes == 0
    assert mem.reserved_bytes() == state_bytes
    assert FOLD not in [e["name"] for e in profiler.events_since(t0)
                        if e["kind"] == profiler.LAUNCH]


# ------------------------------------------ groups of every length (PR 37)

PRICE = DecimalType(12, 2)
PRICED = NAMES + ["p"]
LENGTHS = [1, GROUP - 1, GROUP, GROUP + 1, 2 * GROUP + 3]
# integer, decimal and double states, every merge (add, min, max)
PRICED_AGGS = [AggCall("sum", 2, BIGINT), AggCall("count", -1, BIGINT),
               AggCall("min", 2, BIGINT), AggCall("max", 2, BIGINT),
               AggCall("sum", 4, DecimalType(18, 2)),
               AggCall("min", 4, PRICE), AggCall("sum", 3, DOUBLE)]


def _priced(seed, n=512):
    b = _batch(seed, n)
    cents = np.random.default_rng(seed + 1000).integers(0, 10 ** 9, n)
    return ColumnBatch(
        PRICED, b.columns + [Column(PRICE, cents.astype(np.int64))], b.live)


def _priced_filter_project():
    fp = _filter_project()
    return FilterProjectOperator(
        fp.predicate, fp.projections + [_ref(4, PRICE)], PRICED,
        fp.output_types + [PRICE])


def _priced_agg(keys, step):
    return HashAggregationOperator(
        list(keys), PRICED_AGGS,
        [PRICED[k] for k in keys] + [f"a{i}" for i in range(len(PRICED_AGGS))],
        [VARCHAR] * len(keys) + [a.type for a in PRICED_AGGS], step)


def _exactly(got, want):
    """Integer and decimal columns bit for bit; DOUBLE sums to rounding."""
    assert [[v for v in r if not isinstance(v, float)] for r in got] \
        == [[v for v in r if not isinstance(v, float)] for r in want]
    _same(got, want)


@pytest.mark.parametrize("fused", [False, True], ids=["unfused", "fused"])
@pytest.mark.parametrize("step", ["SINGLE", "PARTIAL"])
@pytest.mark.parametrize("keys", [(), (0, 1)], ids=["global", "grouped"])
@pytest.mark.parametrize("n", LENGTHS)
def test_grouped_equals_single_fold_equals_buffered(n, keys, step, fused,
                                                    monkeypatch):
    """A stream of 1, K-1, K, K+1 and 2K+3 batches: ceil(n / K) launches,
    and the page that leaves is the one a launch a batch gives and the one
    the buffered operator gives."""
    batches = [_priced(s) for s in range(n)]

    def run():
        agg = _priced_agg(keys, step)
        agg.FLUSH_ROWS = 1 << 30
        t0 = profiler.now()
        pages = _pipeline(batches, _priced_filter_project(), agg, fuse=fused)
        names = [e["name"] for e in profiler.events_since(t0)
                 if e["kind"] == profiler.LAUNCH]
        return agg, _rows(pages), names.count(FUSED if fused else FOLD)

    agg, grouped, launches = run()
    es = agg.encoding_stats
    assert launches == es.agg_fold_launches == -(-n // GROUP)
    assert (es.agg_streamed_batches, es.agg_fused_feed) == (n, int(fused))
    check_error_scalars(agg.pending_errors)

    monkeypatch.setattr(O, "_FOLD_GROUP", 1)
    agg, single, launches = run()
    assert launches == agg.encoding_stats.agg_fold_launches == n
    _exactly(grouped, single)

    _buffered(monkeypatch)
    agg, buffered, launches = run()
    assert agg._streamed is False and launches == 0
    _exactly(grouped, buffered)


def test_a_failing_row_mid_group_raises_before_the_sink_finishes():
    """A division by zero in the third batch of a group of five: nothing is
    launched until the source ends, and the error still surfaces at the
    pre-finish barrier, before the sink's stream is marked finished."""
    assert GROUP >= 5
    batches = []
    for s in range(5):
        b = _batch(s, n=512)
        v = np.asarray(b.columns[2].data) | 1             # odd everywhere
        if s == 2:
            v = v.copy()
            v[np.flatnonzero(np.asarray(b.live))[7]] = 4  # ... but here
        batches.append(ColumnBatch(
            NAMES, b.columns[:2] + [Column(BIGINT, v)] + b.columns[3:],
            b.live))
    agg = HashAggregationOperator(
        [0], [AggCall("sum", 1, BIGINT)], ["flag", "s"], [VARCHAR, BIGINT])
    sink = OutputCollector()
    ops = [_Source(batches), _division(False), agg, sink]
    plan_aggregation_feed(ops)
    t0 = profiler.now()
    with pytest.raises(QueryError, match="(?i)division"):
        Driver(ops).run()
    assert not sink.input_done
    assert agg.encoding_stats.agg_streamed_batches == 5
    assert [e["name"] for e in profiler.events_since(t0)
            if e["kind"] == profiler.LAUNCH].count(FUSED) == 1


# ----------------------------------------------------- through the engine

Q6 = ("select sum(l_extendedprice * l_discount) from lineitem where "
      "l_shipdate >= date '1994-01-01' and l_shipdate < date '1995-01-01' "
      "and l_discount between 0.05 and 0.07 and l_quantity < 24")
Q1 = ("select l_returnflag, l_linestatus, sum(l_quantity), "
      "sum(l_extendedprice * (1 - l_discount)), avg(l_quantity), "
      "avg(l_discount), count(*) from lineitem where l_shipdate <= date "
      "'1998-09-02' group by l_returnflag, l_linestatus order by 1, 2")
SQL = {
    "q6_global_sum": (Q6, True),
    "q1_grouped": (Q1, True),
    "variance_family_min_max": (
        "select l_linestatus, stddev(l_quantity), var_pop(l_extendedprice), "
        "min(l_shipdate), max(l_shipmode), count(l_comment) from lineitem "
        "where l_quantity > 3 group by l_linestatus order by 1", True),
    "nullable_arguments_and_key": (
        "select case when l_returnflag = 'R' then null else l_returnflag "
        "end, sum(case when l_quantity > 25 then l_quantity end), "
        "count(nullif(l_linenumber, 1)), min(nullif(l_discount, 0.05)) "
        "from lineitem where l_tax < 0.07 group by 1 order by 1", True),
    "every_row_filtered_global": (
        "select sum(l_quantity), count(*) from lineitem "
        "where l_quantity < 0 and l_tax >= 0", True),
    "every_row_filtered_grouped": (
        "select l_returnflag, count(*) from lineitem where l_quantity < 0 "
        "and l_tax >= 0 group by l_returnflag", True),
    "count_distinct": (
        "select l_returnflag, count(distinct l_suppkey) from lineitem "
        "where l_quantity > 3 group by l_returnflag order by 1", False),
    "high_ndv_key": (
        "select l_orderkey, sum(l_quantity) from lineitem where "
        "l_quantity > 3 group by l_orderkey order by 2 desc, 1 limit 5",
        False),
}


def _modes(t0):
    """mode of every PARTIAL/SINGLE aggregation that reported one."""
    return [e["args"]["mode"] for e in profiler.events_since(t0)
            if e["kind"] == profiler.OPERATOR
            and e["name"] == "HashAggregationOperator.finish"
            and "mode" in e.get("args", {})]


@pytest.mark.parametrize("name", list(SQL))
def test_sql_streamed_equals_buffered(name, monkeypatch):
    sql, streams = SQL[name]
    runner = StandaloneQueryRunner(default_catalog(scale_factor=0.01))
    t0 = profiler.now()
    got = runner.execute(sql).rows()
    modes = _modes(t0)
    assert ("streamed" in modes) == streams, modes
    launches = [e["name"] for e in profiler.events_since(t0)
                if e["kind"] == profiler.LAUNCH]
    assert (FUSED in launches) == streams
    _buffered(monkeypatch)
    t0 = profiler.now()
    want = StandaloneQueryRunner(
        default_catalog(scale_factor=0.01)).execute(sql).rows()
    assert "streamed" not in _modes(t0)
    _same(got, want)


@pytest.mark.parametrize("sql, raises", [
    ("select sum(l_partkey / (l_linenumber - 1)) from lineitem "
     "where l_quantity < 24", True),
    ("select sum(l_partkey / (l_linenumber - 1)) from lineitem "
     "where l_quantity < 24 and l_linenumber > 1", False),
], ids=["live_row", "filtered_out_row"])
def test_sql_division_by_zero(sql, raises):
    runner = StandaloneQueryRunner(default_catalog(scale_factor=0.01))
    t0 = profiler.now()
    if raises:
        with pytest.raises(Exception, match="(?i)division"):
            runner.execute(sql)
    else:
        assert runner.execute(sql).rows()[0][0] > 0
    assert FUSED in [e["name"] for e in profiler.events_since(t0)
                     if e["kind"] == profiler.LAUNCH]


def test_distributed_partial_streams_final_buffers_and_explain_says_so(
        monkeypatch):
    """PARTIAL -> exchange -> FINAL on two tasks, as one chip runs it (no
    fused stage, no collectives): each PARTIAL task folds its batches in the
    filter's program and ships ONE page; FINAL buffers its tiny pages;
    EXPLAIN ANALYZE prints the roll-up."""
    from trino_tpu.runner import Session

    monkeypatch.setenv("TRINO_TPU_FUSED_STAGE", "0")
    runner = DistributedQueryRunner(
        default_catalog(scale_factor=0.01), worker_count=2,
        session=Session(node_count=2, use_collectives=False))
    want = StandaloneQueryRunner(
        default_catalog(scale_factor=0.01)).execute(Q1).rows()
    t0 = profiler.now()
    _same(runner.execute(Q1).rows(), want)
    events = profiler.events_since(t0)
    modes = _modes(t0)
    assert modes.count("streamed") == 2 and "buffered" in modes
    launches = [e["name"] for e in events if e["kind"] == profiler.LAUNCH]
    folded = [e for e in events if e["kind"] == profiler.OPERATOR
              and e["name"] == "HashAggregationOperator"
              and e.get("args", {}).get("fused")]
    # lineitem at SF0.01 is one batch a task: a group of one each, launched
    # at finish (the ``add_input`` event reports 0, ``.finish`` the one)
    assert launches.count(FUSED) == len(folded) == 2
    assert [e["args"]["batches"] for e in folded] == [0, 0]
    assert sorted(a["batches"] for a in _finish_attrs(t0)
                  if a.get("mode") == "streamed") == [1, 1]
    # one page a PARTIAL task, whatever the number of batches
    assert launches.count("trino_kernels_small_agg_state_out") == 2
    text = "\n".join(r[0] for r in runner.execute(
        "explain analyze " + Q1).rows())
    per_task = re.findall(r"(\d+) batches streamed in (\d+) launches "
                          r"\((\d+) aggregations fused with their "
                          r"filter/project, 0 state seals\)", text)
    assert sorted(per_task)[-2:] == [("1", "1", "1")] * 2
    assert sorted(per_task)[:-2] == [("0", "0", "0")] * (len(per_task) - 2)
