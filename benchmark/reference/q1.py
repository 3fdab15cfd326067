"""TPC-H Q1 with its validation parameter (DELTA 90), plain numpy over
scaled integers: sums are exact, averages are floats (the configuration's
tolerance applies to them).  One masked dot product per group and sum,
no sort."""

import datetime
import decimal

import numpy as np

COLUMNS = {"lineitem": {"l_returnflag": 4, "l_linestatus": 4,
                        "l_quantity": 8, "l_extendedprice": 8,
                        "l_discount": 8, "l_tax": 8, "l_shipdate": 4}}
_CUTOFF = (datetime.date(1998, 12, 1) - datetime.timedelta(days=90)
           - datetime.date(1970, 1, 1)).days


def _dec(n, scale: int) -> decimal.Decimal:
    return decimal.Decimal(int(n)).scaleb(-scale)


def reference(tables: dict) -> list:
    li = tables["lineitem"]
    flags, statuses = li["l_returnflag.dict"], li["l_linestatus.dict"]
    live = li["l_shipdate"] <= _CUTOFF
    key = li["l_returnflag"] * np.int32(len(statuses)) + li["l_linestatus"]
    qty, price, disc = li["l_quantity"], li["l_extendedprice"], li["l_discount"]
    disc_price = price * (100 - disc)               # scale 4
    charge = disc_price * (100 + li["l_tax"])       # scale 6
    rows = []
    for k in np.flatnonzero(np.bincount(key[live])):
        m = (live & (key == k)).astype(np.int64)    # an exact masked sum
        n = int(m.sum())
        s_qty, s_price, s_disc = (int(np.dot(c, m)) for c in (qty, price, disc))
        rows.append((
            str(flags[k // len(statuses)]), str(statuses[k % len(statuses)]),
            _dec(s_qty, 2), _dec(s_price, 2),
            _dec(np.dot(disc_price, m), 4), _dec(np.dot(charge, m), 6),
            s_qty / 100 / n, s_price / 100 / n, s_disc / 100 / n, n))
    # dictionaries are sorted, so key order is (returnflag, linestatus) order
    return rows
