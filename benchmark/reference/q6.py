"""TPC-H Q6 with its validation parameters (DATE 1994-01-01, DISCOUNT 0.06,
QUANTITY 24), plain numpy over scaled integers, so the sum is exact.
check_full's evaluation from chip_smoke.py (PR 22)."""

import datetime
import decimal

# the columns the query references, with the bytes one value takes as the
# deployment stores it (decimal(15,2): int64 hundredths; date: int32 days)
COLUMNS = {"lineitem": {"l_shipdate": 4, "l_discount": 8, "l_quantity": 8,
                        "l_extendedprice": 8}}
_EPOCH = datetime.date(1970, 1, 1)


def reference(tables: dict) -> list:
    li = tables["lineitem"]
    d0 = (datetime.date(1994, 1, 1) - _EPOCH).days
    d1 = (datetime.date(1995, 1, 1) - _EPOCH).days
    m = ((li["l_shipdate"] >= d0) & (li["l_shipdate"] < d1)
         & (li["l_discount"] >= 5) & (li["l_discount"] <= 7)
         & (li["l_quantity"] < 2400))
    revenue = int((li["l_extendedprice"][m].astype("int64")
                   * li["l_discount"][m]).sum())
    return [(decimal.Decimal(revenue).scaleb(-4),)]
