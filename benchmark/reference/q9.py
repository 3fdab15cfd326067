"""TPC-H Q9 with its validation parameter (COLOR green), plain numpy: the
parts whose name holds 'green', sorted-key joins by searchsorted (the
partsupp match as one two-column key packed into one integer), the order's
year from its days through datetime64, exact profit in scaled integers,
grouped by (nation, year), ordered by nation, then year descending."""

import decimal

import numpy as np

COLUMNS = {
    "part": {"p_partkey": 8, "p_name": 4},
    "supplier": {"s_suppkey": 8, "s_nationkey": 8},
    "lineitem": {"l_orderkey": 8, "l_partkey": 8, "l_suppkey": 8,
                 "l_quantity": 8, "l_extendedprice": 8, "l_discount": 8},
    "partsupp": {"ps_partkey": 8, "ps_suppkey": 8, "ps_supplycost": 8},
    "orders": {"o_orderkey": 8, "o_orderdate": 4},
    "nation": {"n_nationkey": 8, "n_name": 4},
}
_COLOR = "green"
_SUPP_BITS = 32     # a supplier key fits 32 bits: the pair's low part


def _member(sorted_keys: np.ndarray, needles: np.ndarray):
    """(position in sorted_keys, found) per needle."""
    if len(sorted_keys) == 0:
        return np.zeros(len(needles), np.int64), np.zeros(len(needles), bool)
    pos = np.searchsorted(sorted_keys, needles).clip(0, len(sorted_keys) - 1)
    return pos, sorted_keys[pos] == needles


def _pair(partkey: np.ndarray, suppkey: np.ndarray) -> np.ndarray:
    """(partkey, suppkey) as one sortable integer."""
    return (partkey.astype(np.int64) << _SUPP_BITS) | suppkey.astype(
        np.int64)


def _sorted_by(keys: np.ndarray, *values: np.ndarray) -> tuple:
    order = np.argsort(keys, kind="stable")
    return (keys[order],) + tuple(v[order] for v in values)


def reference(tables: dict) -> list:
    pa, su, li = tables["part"], tables["supplier"], tables["lineitem"]
    ps, od, na = tables["partsupp"], tables["orders"], tables["nation"]
    # the parts whose name holds the colour
    green = np.array([_COLOR in str(s) for s in pa["p_name.dict"]], bool)
    parts = np.sort(pa["p_partkey"][green[pa["p_name"]]])
    _, hit = _member(parts, li["l_partkey"])
    l_part, l_supp, l_order = (li["l_partkey"][hit], li["l_suppkey"][hit],
                               li["l_orderkey"][hit])
    qty = li["l_quantity"][hit].astype(np.int64)
    price = li["l_extendedprice"][hit].astype(np.int64)
    disc = li["l_discount"][hit].astype(np.int64)
    # -> supplier's nation
    s_key, s_nation = _sorted_by(su["s_suppkey"], su["s_nationkey"])
    pos, hit = _member(s_key, l_supp)
    keep = [hit]
    l_nation = s_nation[pos]
    # -> partsupp on (partkey, suppkey)
    p_key, p_cost = _sorted_by(_pair(ps["ps_partkey"], ps["ps_suppkey"]),
                               ps["ps_supplycost"])
    pos, hit = _member(p_key, _pair(l_part, l_supp))
    keep.append(hit)
    cost = p_cost[pos].astype(np.int64)
    # -> the order's year
    o_key, o_date = _sorted_by(od["o_orderkey"], od["o_orderdate"])
    pos, hit = _member(o_key, l_order)
    keep.append(hit)
    year = (o_date[pos].astype("datetime64[D]").astype("datetime64[Y]")
            .astype(np.int64) + 1970)
    # -> nation's name
    n_key, n_name = _sorted_by(na["n_nationkey"],
                               na["n_name.dict"][na["n_name"]])
    pos, hit = _member(n_key, l_nation)
    keep.append(hit)
    ok = np.logical_and.reduce(keep)
    # profit at scale 4: price x (1 - discount) - supplycost x quantity
    amount = price * (100 - disc) - cost * qty
    groups, inverse = np.unique(
        np.stack([pos[ok], year[ok]], axis=1), axis=0, return_inverse=True)
    total = np.zeros(len(groups), np.int64)
    np.add.at(total, inverse.reshape(-1), amount[ok])
    rows = [(str(n_name[g[0]]), int(g[1]), int(t))
            for g, t in zip(groups, total)]
    rows.sort(key=lambda r: (r[0], -r[1]))
    return [(nation, y, decimal.Decimal(t).scaleb(-4))
            for nation, y, t in rows]
