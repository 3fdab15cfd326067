"""TPC-H generator connector tests: determinism, FK integrity, split union,
spec-shaped distributions; memory/blackhole connectors; oracle harness."""

import numpy as np
import pytest

from trino_tpu.connectors.catalog import default_catalog
from trino_tpu.connectors.memory import BlackholeConnector, MemoryConnector
from trino_tpu.connectors import tpch as tpch_mod
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.spi import BIGINT, VARCHAR, ColumnBatch, ColumnSchema, TableSchema
from trino_tpu.testing.oracle import SqliteOracle, assert_same_rows, transpile


@pytest.fixture(scope="module")
def conn():
    return TpchConnector(scale_factor=0.01)


def read_all(conn, table, columns, splits_per_node=4):
    splits = conn.get_splits(table, splits_per_node, 1)
    batches = []
    for s in splits:
        src = conn.create_page_source(s, columns)
        while not src.is_finished():
            b = src.get_next_batch()
            if b is not None:
                batches.append(b)
    return ColumnBatch.concat(batches)


def test_cardinalities(conn):
    assert conn.row_count("nation") == 25
    assert conn.row_count("region") == 5
    assert conn.row_count("supplier") == 100
    assert conn.row_count("customer") == 1500
    assert conn.row_count("orders") == 15000
    li = conn.row_count("lineitem")
    assert 15000 * 3 < li < 15000 * 5  # ~4 lines/order


def test_determinism_and_split_union(conn):
    whole = read_all(conn, "orders", ["o_orderkey", "o_custkey"], splits_per_node=1)
    parts = read_all(conn, "orders", ["o_orderkey", "o_custkey"], splits_per_node=3)
    assert whole.num_rows == parts.num_rows == 15000
    a = np.sort(np.asarray(whole.column("o_orderkey").data))
    b = np.sort(np.asarray(parts.column("o_orderkey").data))
    assert (a == b).all()
    assert (a == np.arange(1, 15001)).all()
    # same values regardless of split layout
    wa = np.asarray(whole.column("o_custkey").data)
    pa = np.asarray(parts.column("o_custkey").data)
    order_w = np.argsort(np.asarray(whole.column("o_orderkey").data))
    order_p = np.argsort(np.asarray(parts.column("o_orderkey").data))
    assert (wa[order_w] == pa[order_p]).all()


def test_fk_integrity(conn):
    li = read_all(conn, "lineitem", ["l_orderkey", "l_partkey", "l_suppkey"])
    ps = read_all(conn, "partsupp", ["ps_partkey", "ps_suppkey"])
    # every lineitem (partkey, suppkey) must exist in partsupp (Q9 joins on it)
    li_pairs = set(zip(np.asarray(li.column("l_partkey").data).tolist(),
                       np.asarray(li.column("l_suppkey").data).tolist()))
    ps_pairs = set(zip(np.asarray(ps.column("ps_partkey").data).tolist(),
                       np.asarray(ps.column("ps_suppkey").data).tolist()))
    assert li_pairs <= ps_pairs
    # suppkeys within range
    sk = np.asarray(li.column("l_suppkey").data)
    assert sk.min() >= 1 and sk.max() <= conn.row_count("supplier")
    # orderkeys dense 1..N
    ok = np.asarray(li.column("l_orderkey").data)
    assert set(np.unique(ok)) == set(range(1, 15001))


def _part_names(conn):
    col = read_all(conn, "part", ["p_name"]).column("p_name")
    return [str(v) for v in np.asarray(col.dictionary)[np.asarray(col.data)]]


def test_part_name_is_five_distinct_colours(conn):
    # spec 4.2.3: five distinct words of the colour list, single spaces
    colours = set(tpch_mod._COLORS)
    names = _part_names(conn)
    assert len(names) == conn.row_count("part")
    for name in names:
        words = name.split(" ")
        assert len(words) == 5 and len(set(words)) == 5, name
        assert set(words) <= colours, name
    # seeded: the same names in every read
    assert names == _part_names(conn)


def test_green_keeps_five_of_ninety_two_parts():
    names = _part_names(TpchConnector(scale_factor=0.05))
    share = sum("green" in n for n in names) / len(names)
    assert 0.04 <= share <= 0.07     # 5/92 = 5.4 %
    # nearly every name is its own dictionary entry
    assert len(set(names)) > 0.99 * len(names)


@pytest.mark.parametrize("n,k", [(92, 5), (6, 6), (3, 1)])
def test_distinct_picks_draw_without_replacement(n, k):
    keys = np.arange(1, 5001, dtype=np.uint64)
    picks = tpch_mod._distinct_picks(keys, range(80, 80 + k), n)
    assert picks.shape == (5000, k)
    assert picks.min() >= 0 and picks.max() < n
    assert all(len(set(row)) == k for row in picks.tolist())
    # every value is drawn at every position, about equally often
    counts = np.bincount(picks[:, 0], minlength=n)
    assert counts.min() > 0.5 * 5000 / n


def test_customers_without_orders(conn):
    o = read_all(conn, "orders", ["o_custkey"])
    ck = np.asarray(o.column("o_custkey").data)
    assert (ck % 3 != 0).all()  # every third customer never orders
    assert ck.min() >= 1 and ck.max() <= 1500


def test_date_correlations_and_flags(conn):
    li = read_all(conn, "lineitem",
                  ["l_shipdate", "l_commitdate", "l_receiptdate",
                   "l_returnflag", "l_linestatus"])
    ship = np.asarray(li.column("l_shipdate").data)
    rec = np.asarray(li.column("l_receiptdate").data)
    assert ((rec > ship) & (rec <= ship + 30)).all()
    flags = li.column("l_returnflag").to_pylist()
    status = li.column("l_linestatus").to_pylist()
    assert set(flags) == {"A", "N", "R"}
    assert set(status) == {"F", "O"}
    # Q1 predicate keeps ~98% of rows
    import datetime

    cut = (datetime.date(1998, 9, 2) - datetime.date(1970, 1, 1)).days
    frac = (ship <= cut).mean()
    assert 0.95 < frac < 1.0


def test_dictionaries_shared_across_splits(conn):
    parts = []
    for s in conn.get_splits("lineitem", 3, 1):
        src = conn.create_page_source(s, ["l_shipmode"])
        while not src.is_finished():
            b = src.get_next_batch()
            if b is not None:
                parts.append(b.column("l_shipmode"))
    assert all(p.dictionary is parts[0].dictionary for p in parts[1:])
    assert list(parts[0].dictionary) == sorted(parts[0].dictionary)


def test_orderstatus_consistency(conn):
    """o_orderstatus must agree with the lineitems' linestatus."""
    o = read_all(conn, "orders", ["o_orderkey", "o_orderstatus"])
    li = read_all(conn, "lineitem", ["l_orderkey", "l_linestatus"])
    status = dict(zip(np.asarray(o.column("o_orderkey").data).tolist(),
                      o.column("o_orderstatus").to_pylist()))
    from collections import defaultdict

    by_order = defaultdict(set)
    for okey, ls in zip(np.asarray(li.column("l_orderkey").data).tolist(),
                        li.column("l_linestatus").to_pylist()):
        by_order[okey].add(ls)
    for okey, statuses in list(by_order.items())[:2000]:
        expect = "F" if statuses == {"F"} else ("O" if statuses == {"O"} else "P")
        assert status[okey] == expect, okey


def test_memory_connector_roundtrip():
    mem = MemoryConnector()
    schema = TableSchema("t", (ColumnSchema("a", BIGINT), ColumnSchema("s", VARCHAR)))
    mem.create_table(schema)
    sink = mem.create_page_sink("t")
    b = ColumnBatch.from_pydict({"a": (BIGINT, [1, 2]), "s": (VARCHAR, ["x", None])})
    sink.append(b)
    mem.finish_insert("t", sink.finish())
    splits = mem.get_splits("t", 2, 1)
    out = []
    for s in splits:
        src = mem.create_page_source(s, ["s", "a"])
        while not src.is_finished():
            nb = src.get_next_batch()
            if nb is not None:
                out.append(nb)
    got = ColumnBatch.concat(out)
    assert got.names == ["s", "a"]
    assert got.to_pylist() == [("x", 1), (None, 2)]


def test_blackhole_sink():
    bh = BlackholeConnector()
    bh.create_table(TableSchema("sink", (ColumnSchema("a", BIGINT),)))
    s = bh.create_page_sink("sink")
    s.append(ColumnBatch.from_pydict({"a": (BIGINT, [1, 2, 3])}))
    assert s.finish() == [3]
    assert bh.get_splits("sink", 4, 2) == []


def test_catalog_resolution():
    cat = default_catalog(0.01)
    c, t, schema = cat.resolve_table("lineitem", "tpch")
    assert (c, t) == ("tpch", "lineitem") and len(schema.columns) == 16
    c, t, _ = cat.resolve_table("tpch.orders", "memory")
    assert (c, t) == ("tpch", "orders")
    with pytest.raises(KeyError):
        cat.resolve_table("nope.orders", "tpch")


def test_oracle_transpile_and_query(conn):
    oracle = SqliteOracle()
    oracle.load_table("nation", [read_all(conn, "nation",
                                          ["n_nationkey", "n_name", "n_regionkey"])])
    sql = transpile("select n_name from nation where n_regionkey = 3")
    assert "interval" not in sql
    rows = oracle.query("select count(*) from nation where n_regionkey = 1")
    assert rows == [(5,)]
    # date literal + interval arithmetic
    t = transpile("select * from x where d < date '1993-07-01' + interval '3' month")
    assert "add_months(8582, 3)" in t
    t = transpile("select * from x where d <= date '1998-12-01' - interval '90' day")
    assert "(10561 + -90)" in t
    rows = oracle.query("select tpch_year(9000), tpch_quarter(9000)")
    assert rows == [(1994, 3)]


def test_oracle_assert_same_rows():
    import datetime
    import decimal

    assert_same_rows(
        [(decimal.Decimal("1.50"), datetime.date(1995, 1, 1), "x")],
        [(1.5, 9131, "x")],
    )
    with pytest.raises(AssertionError):
        assert_same_rows([(1,)], [(2,)])
