"""TPC-H Q3 with its validation parameters (SEGMENT BUILDING, DATE
1995-03-15), plain numpy: filter, sorted-key joins by searchsorted,
np.unique grouping over scaled integers (exact revenue), top 10."""

import datetime
import decimal

import numpy as np

COLUMNS = {
    "customer": {"c_custkey": 8, "c_mktsegment": 4},
    "orders": {"o_orderkey": 8, "o_custkey": 8, "o_orderdate": 4,
               "o_shippriority": 8},
    "lineitem": {"l_orderkey": 8, "l_extendedprice": 8, "l_discount": 8,
                 "l_shipdate": 4},
}
_EPOCH = datetime.date(1970, 1, 1)
_DATE = (datetime.date(1995, 3, 15) - _EPOCH).days


def _member(sorted_keys: np.ndarray, needles: np.ndarray):
    """(position in sorted_keys, found) per needle."""
    if len(sorted_keys) == 0:
        return np.zeros(len(needles), np.int64), np.zeros(len(needles), bool)
    pos = np.searchsorted(sorted_keys, needles).clip(0, len(sorted_keys) - 1)
    return pos, sorted_keys[pos] == needles


def reference(tables: dict) -> list:
    cu, od, li = tables["customer"], tables["orders"], tables["lineitem"]
    segment = np.flatnonzero(cu["c_mktsegment.dict"] == "BUILDING")
    cust = np.sort(cu["c_custkey"][np.isin(cu["c_mktsegment"], segment)])
    _, bought = _member(cust, od["o_custkey"])
    o_keep = bought & (od["o_orderdate"] < _DATE)
    order = np.argsort(od["o_orderkey"][o_keep], kind="stable")
    o_key = od["o_orderkey"][o_keep][order]         # unique: a primary key
    o_date = od["o_orderdate"][o_keep][order]
    o_prio = od["o_shippriority"][o_keep][order]
    l_keep = li["l_shipdate"] > _DATE
    pos, hit = _member(o_key, li["l_orderkey"][l_keep])
    pos = pos[hit]
    revenue = (li["l_extendedprice"][l_keep][hit].astype(np.int64)
               * (100 - li["l_discount"][l_keep][hit]))          # scale 4
    groups, inverse = np.unique(pos, return_inverse=True)
    total = np.zeros(len(groups), np.int64)
    np.add.at(total, inverse, revenue)
    # order by revenue desc, o_orderdate (then orderkey, to be definite)
    top = np.lexsort((o_key[groups], o_date[groups], -total))[:10]
    return [(int(o_key[groups[i]]),
             decimal.Decimal(int(total[i])).scaleb(-4),
             _EPOCH + datetime.timedelta(days=int(o_date[groups[i]])),
             int(o_prio[groups[i]]))
            for i in top]
