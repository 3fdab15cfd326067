"""A decimal AVG sums its argument's unscaled integers, and the division by
10^scale happens once per group, at finalization.  Where a non-DISTINCT SUM
over the same channel sits in the same aggregation, the AVG reads that SUM's
exact int64 state and reduces no sum of its own (operators._avg_partners).

Answers are checked against the exact decimal mean and the sqlite oracle on
every path an aggregation takes: the streamed masked fold, the buffered
masked path, the sort path and PARTIAL -> FINAL over two workers.  The
compiled shape is checked on TPC-H Q1's and Q6's one-batch fold bodies.
The recorder's and EXPLAIN ANALYZE's counts of the two forms are checked
too."""

import decimal
import hashlib
import math
import pathlib
import re
from decimal import Decimal

import jax
import numpy as np
import pytest

from tools import fold_group_probe as P
from trino_tpu.caching import result_cache
from trino_tpu.connectors.catalog import default_catalog
from trino_tpu.exec import kernels as K
from trino_tpu.exec import operators as O
from trino_tpu.exec.operators import HashAggregationOperator
from trino_tpu.execution.distributed_runner import DistributedQueryRunner
from trino_tpu.planner.plan import AggCall
from trino_tpu.runner import Session, StandaloneQueryRunner
from trino_tpu.spi.batch import Column, ColumnBatch, pad_to_bucket
from trino_tpu.spi.connector import ColumnSchema, TableSchema
from trino_tpu.spi.types import DOUBLE, VARCHAR, DecimalType
from trino_tpu.telemetry import profiler
from trino_tpu.testing.oracle import SqliteOracle, assert_same_rows

FLAGS = np.array(["a", "b", "c", "d"], dtype=object)  # "d": x is NULL
ROWS = 1500
BATCHES = 3
SCALES = (0, 2, 4)
PATHS = ("streamed", "buffered", "sort", "two_workers")
FORMS = {"paired": "sum(x), avg(x), count(x)",
         "unpaired": "avg(x), count(x)"}
SHAPES = {"global": ("", "", ""),
          "global_all_null": ("", "where g = 'd'", ""),
          "grouped": ("g, ", "", "group by g order by g")}


@pytest.fixture(autouse=True)
def _recorder(monkeypatch):
    monkeypatch.setenv("TRINO_TPU_RESULT_CACHE", "0")
    monkeypatch.setenv("TRINO_TPU_FUSED_STAGE", "0")
    prev = profiler.set_level(1)
    profiler.reset_for_test()
    yield
    profiler.set_level(prev)
    profiler.reset_for_test()


# ---------------------------------------------------------------- the data

def _batches(scale: int) -> list:
    """g (dictionary codes; every x of "d" NULL), x DECIMAL(12, scale): 20 %
    NULL elsewhere, both signs, mostly positive."""
    rng = np.random.default_rng(40 + scale)
    t = DecimalType(12, scale)
    out = []
    for _ in range(BATCHES):
        g = rng.integers(0, len(FLAGS), ROWS).astype(np.int32)
        x = rng.integers(-10 ** 7, 10 ** 9, ROWS).astype(np.int64)
        valid = (rng.random(ROWS) < 0.8) & (g != 3)
        out.append(ColumnBatch(["g", "x"], [Column(VARCHAR, g, None, FLAGS),
                                            Column(t, x, valid)]))
    return out


def _catalog(batches):
    catalog = default_catalog(scale_factor=0.01)
    mem = catalog.connector("memory")
    mem.create_table(TableSchema("t", (
        ColumnSchema("g", VARCHAR),
        ColumnSchema("x", batches[0].columns[1].type))))
    mem.finish_insert("t", [batches])
    return catalog


def _runner(path: str, catalog, monkeypatch):
    if path == "buffered":
        monkeypatch.setattr(HashAggregationOperator, "_streams",
                            lambda self, inp: False)
    elif path == "sort":
        monkeypatch.setattr(K, "MASKED_AGG_LIMIT", 0)
    if path == "two_workers":
        return DistributedQueryRunner(
            catalog, worker_count=2,
            session=Session(default_catalog="memory", node_count=2,
                            use_collectives=False))
    return StandaloneQueryRunner(
        catalog, session=Session(default_catalog="memory"))


def _exact(batches, scale: int, form: str, shape: str) -> list:
    """The answer from python integers: SUM exact, AVG the exact decimal
    mean rounded to DOUBLE (its output type), COUNT."""
    ints: dict = {}
    for b in batches:
        g, x = b.columns
        for code, v, ok in zip(g.data, x.data, x.valid):
            if shape != "global_all_null" or code == 3:
                ints.setdefault(int(code), []).append(int(v) if ok else None)
    groups = ([[v for vs in ints.values() for v in vs]]
              if shape.startswith("global") else
              [ints.get(c, []) for c in range(len(FLAGS))])
    rows = []
    for c, vs in enumerate(groups):
        vs = [v for v in vs if v is not None]
        total = Decimal(sum(vs)).scaleb(-scale) if vs else None
        with decimal.localcontext() as ctx:
            ctx.prec = 60
            mean = float(total / len(vs)) if vs else None
        row = (total, mean, len(vs)) if form == "paired" else (mean, len(vs))
        rows.append((FLAGS[c],) + row if shape == "grouped" else row)
    return rows


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("scale", SCALES)
def test_decimal_avg_is_the_exact_mean(scale, path, form, shape,
                                       monkeypatch):
    batches = _batches(scale)
    keys, where, group = SHAPES[shape]
    sql = f"select {keys}{FORMS[form]} from t {where} {group}"
    got = _runner(path, _catalog(batches), monkeypatch).execute(sql).rows()
    want = _exact(batches, scale, form, shape)
    assert len(got) == len(want)
    at = (shape == "grouped") + (form == "paired")  # the AVG's column
    for g, w in zip(got, want):
        assert g[:at] == w[:at] and g[at + 1:] == w[at + 1:]
        if w[at] is None or path != "two_workers":
            # one step: the integer total over count x 10^scale in ONE
            # correctly rounded division
            assert g[at] == w[at]
        else:
            # PARTIAL -> FINAL adds two scale-free partial sums first
            assert math.isclose(g[at], w[at], rel_tol=1e-15)
    oracle = SqliteOracle()
    oracle.load_table("t", batches)
    assert_same_rows(got, oracle.query(sql), ordered=True)


@pytest.mark.parametrize("case", ["distinct", "long_decimal"])
def test_what_does_not_pair_is_unchanged(case, monkeypatch):
    """AVG(DISTINCT x) beside SUM(x) sums its own distinct values; a long
    decimal's AVG beside its SUM keeps the exact limb path and its
    DECIMAL(38, s) answer."""
    if case == "distinct":
        batches = _batches(2)
        sql = ("select g, sum(x), avg(distinct x) from t "
               "group by g order by g")
        got = _runner("streamed", _catalog(batches),
                      monkeypatch).execute(sql).rows()
        vals: dict = {}
        for b in batches:
            g, x = b.columns
            for code, v, ok in zip(g.data, x.data, x.valid):
                vals.setdefault(int(code), []).append(int(v) if ok else None)
        for row in got:
            vs = [v for v in vals[list(FLAGS).index(row[0])] if v is not None]
            if not vs:
                assert row[1:] == (None, None)
                continue
            assert row[1] == Decimal(sum(vs)).scaleb(-2)
            d = set(vs)
            assert math.isclose(row[2], sum(d) / 100 / len(d), rel_tol=1e-15)
        return
    runner = StandaloneQueryRunner(default_catalog(scale_factor=0.01),
                                   session=Session(default_catalog="memory"))
    runner.execute("create table w (g varchar, v decimal(38,2))")
    runner.execute("insert into w values ('a', 99999999999999999999.25), "
                   "('a', 0.26), ('b', -50000000000000000000.50), "
                   "('b', null), ('c', null)")
    rows = runner.execute("select g, sum(v), avg(v) from w group by g "
                          "order by g").rows()
    assert rows == [
        ("a", Decimal("99999999999999999999.51"),
         Decimal("49999999999999999999.76")),
        ("b", Decimal("-50000000000000000000.50"),
         Decimal("-50000000000000000000.50")),
        ("c", None, None)]


# ------------------------------------------------------- the compiled shape

QUERIES = pathlib.Path(P.ROOT, "benchmark", "queries")
Q1 = (QUERIES / "q1.sql").read_text()
Q6 = (QUERIES / "q6.sql").read_text()
# Q6's one-batch fold body at 2^12 lanes, lowered (pre-optimization text,
# no debug information), as it was before decimal AVGs summed unscaled.
# Q6 has no AVG, so that change may not move its program by a byte; a
# change meant to move it sets the new digest.
Q6_BODY_SHA256 = (
    "b209baa9b6985d54ee924bd75d7087c428192938b59d2cdb206e96c6cf3cc940")


def _fold_body(sql: str):
    """(lowered, optimized HLO text) of ``sql``'s one-batch fold body
    (operators._filter_project_fold_body), taken from one run of the query
    over a pinned lineitem of 2^12-lane batches as fold_group_probe takes
    it."""
    catalog = P.pinned_lineitem(0.01, 1 << 12, 2)
    with result_cache.disabled():
        agg, batches, _ = P.captured_fold(catalog, sql)
    prog, sig, cols, live = agg.feed.program_inputs(pad_to_bucket(batches[0]))
    view, has_error = prog.view(sig, cols, live)
    ops, _ = O._masked_operands(agg.group_keys, agg.aggs, agg.step, view)
    body = O._filter_project_fold_body(prog, tuple(agg.group_keys),
                                       tuple(agg.aggs), agg.step)
    lowered = jax.jit(body).lower(K.small_agg_zero_state(ops, has_error),
                                  cols, live)
    return lowered, lowered.compile().as_text()


def test_q1_folds_one_f64_sum_and_no_full_width_f64_arithmetic():
    """Q1's three AVGs: two read sum(l_quantity)'s and sum(l_extendedprice)'s
    int64 states, avg(l_discount) sums unscaled in f64.  One f64 reduction
    (three before), and no f64 multiply or divide over the batch's lanes."""
    _, text = _fold_body(Q1)
    lanes = 1 << 12
    reduces = re.findall(r"= (\w+)\[6\]\{0\} reduce\(", text)
    assert reduces.count("f64") == 1
    assert reduces.count("s64") == 4  # the four decimal SUMs
    assert not re.search(
        rf"= f64\[(\d+,)?{lanes}\]\{{[0-9,]+\}} (multiply|divide)\(", text)


def test_q6_fold_body_is_the_program_it_was():
    lowered, _ = _fold_body(Q6)
    digest = hashlib.sha256(lowered.as_text().encode()).hexdigest()
    assert digest == Q6_BODY_SHA256


# ------------------------------------------------------------ the counters

@pytest.mark.parametrize("query, counts", [(Q1, (2, 1)), (Q6, (0, 0))],
                         ids=["q1", "q6"])
def test_the_recorder_and_explain_count_both_forms(query, counts):
    runner = DistributedQueryRunner(
        default_catalog(scale_factor=0.01), worker_count=2,
        session=Session(node_count=2, use_collectives=False))
    t0 = profiler.now()
    runner.execute(query)
    partial = [e["args"] for e in profiler.events_since(t0)
               if e["kind"] == profiler.OPERATOR
               and e["name"] == "HashAggregationOperator.finish"
               and e["args"].get("mode") == "streamed"]
    assert len(partial) == 2
    assert {(a["avg_shared"], a["avg_unscaled"]) for a in partial} == {counts}
    text = "\n".join(r[0] for r in runner.execute(
        "explain analyze " + query).rows())
    per_task = re.findall(r"decimal avg: (\d+) read their sum's integers, "
                          r"(\d+) summed unscaled", text)
    assert sorted(per_task)[-2:] == [tuple(map(str, counts))] * 2


def test_the_crossover_counts_what_the_kernel_reduces():
    """A paired AVG asks the reduction kernel for its count alone."""
    t = DecimalType(12, 2)
    inp = ColumnBatch(["x", "y"], [Column(t, np.zeros(4, np.int64)),
                                   Column(t, np.zeros(4, np.int64))])
    def count(*calls):
        return HashAggregationOperator(
            [], list(calls), [f"c{i}" for i in range(len(calls))],
            [c.type for c in calls], "PARTIAL")._reduction_count(inp)

    dec = DecimalType(18, 2)
    assert count(AggCall("sum", 0, dec), AggCall("avg", 0, DOUBLE)) == 2
    assert count(AggCall("sum", 1, dec), AggCall("avg", 0, DOUBLE)) == 3
    assert count(AggCall("sum", 0, dec),
                 AggCall("avg", 0, DOUBLE, True)) == 3
