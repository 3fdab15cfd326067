"""TPC-H Q5 with its validation parameters (REGION ASIA, DATE 1994-01-01),
plain numpy: filter region and the orders' year, sorted-key joins by
searchsorted (the customer/supplier nation match as one two-column key),
exact revenue in scaled integers, grouped by n_name, ordered by revenue
desc (then n_name, to be definite)."""

import datetime
import decimal

import numpy as np

COLUMNS = {
    "region": {"r_regionkey": 8, "r_name": 4},
    "nation": {"n_nationkey": 8, "n_name": 4, "n_regionkey": 8},
    "supplier": {"s_suppkey": 8, "s_nationkey": 8},
    "customer": {"c_custkey": 8, "c_nationkey": 8},
    "orders": {"o_orderkey": 8, "o_custkey": 8, "o_orderdate": 4},
    "lineitem": {"l_orderkey": 8, "l_suppkey": 8, "l_extendedprice": 8,
                 "l_discount": 8},
}
_EPOCH = datetime.date(1970, 1, 1)
_FROM = (datetime.date(1994, 1, 1) - _EPOCH).days
_TO = (datetime.date(1995, 1, 1) - _EPOCH).days
_NATION_BITS = 8     # a nation key fits 8 bits: the pair's low part


def _member(sorted_keys: np.ndarray, needles: np.ndarray):
    """(position in sorted_keys, found) per needle."""
    if len(sorted_keys) == 0:
        return np.zeros(len(needles), np.int64), np.zeros(len(needles), bool)
    pos = np.searchsorted(sorted_keys, needles).clip(0, len(sorted_keys) - 1)
    return pos, sorted_keys[pos] == needles


def _pair(custkey: np.ndarray, nationkey: np.ndarray) -> np.ndarray:
    """(custkey, nationkey) as one sortable integer."""
    return (custkey.astype(np.int64) << _NATION_BITS) | nationkey.astype(
        np.int64)


def reference(tables: dict) -> list:
    re_, na, su = tables["region"], tables["nation"], tables["supplier"]
    cu, od, li = tables["customer"], tables["orders"], tables["lineitem"]
    asia = np.flatnonzero(re_["r_name.dict"] == "ASIA")
    regions = np.sort(re_["r_regionkey"][np.isin(re_["r_name"], asia)])
    _, in_asia = _member(regions, na["n_regionkey"])
    n_order = np.argsort(na["n_nationkey"][in_asia], kind="stable")
    n_key = na["n_nationkey"][in_asia][n_order]
    n_name = na["n_name.dict"][na["n_name"][in_asia][n_order]]
    # supplier -> nation (in ASIA only), sorted by suppkey
    _, s_in = _member(n_key, su["s_nationkey"])
    s_order = np.argsort(su["s_suppkey"][s_in], kind="stable")
    s_key = su["s_suppkey"][s_in][s_order]
    s_nation = su["s_nationkey"][s_in][s_order]
    # the orders of one year, sorted by orderkey
    o_keep = (od["o_orderdate"] >= _FROM) & (od["o_orderdate"] < _TO)
    o_order = np.argsort(od["o_orderkey"][o_keep], kind="stable")
    o_key = od["o_orderkey"][o_keep][o_order]
    o_cust = od["o_custkey"][o_keep][o_order]
    # lineitem -> order, then -> supplier
    pos, hit = _member(o_key, li["l_orderkey"])
    l_cust = o_cust[pos[hit]]
    price = li["l_extendedprice"][hit]
    disc = li["l_discount"][hit]
    pos, hit = _member(s_key, li["l_suppkey"][hit])
    l_cust, price, disc = l_cust[hit], price[hit], disc[hit]
    l_nation = s_nation[pos[hit]]
    # (o_custkey, s_nationkey) = (c_custkey, c_nationkey)
    cust = np.sort(_pair(cu["c_custkey"], cu["c_nationkey"]))
    _, hit = _member(cust, _pair(l_cust, l_nation))
    revenue = (price[hit].astype(np.int64) * (100 - disc[hit]))   # scale 4
    group = np.searchsorted(n_key, l_nation[hit])
    total = np.zeros(len(n_key), np.int64)
    np.add.at(total, group, revenue)
    seen = np.zeros(len(n_key), bool)
    seen[group] = True
    rows = [(str(n_name[g]), int(total[g])) for g in np.flatnonzero(seen)]
    rows.sort(key=lambda r: (-r[1], r[0]))
    return [(name, decimal.Decimal(t).scaleb(-4)) for name, t in rows]
