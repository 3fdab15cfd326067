"""The comparison that decides ``correct``."""

import datetime
from decimal import Decimal

from harness import check

WANT = [("A", Decimal("10.50"), 2.5, 3, datetime.date(1995, 3, 1))]


def test_exact_and_tolerant_columns():
    same = [("A", Decimal("10.5"), 2.5 * (1 + 1e-12), 3,
             datetime.date(1995, 3, 1))]
    assert check.mismatch(same, WANT, 1e-9) is None
    assert "col 1" in check.mismatch(
        [("A", Decimal("10.51"), 2.5, 3, datetime.date(1995, 3, 1))],
        WANT, 1e-9)
    assert "col 2" in check.mismatch(
        [("A", Decimal("10.50"), 2.5 * (1 + 1e-6), 3,
          datetime.date(1995, 3, 1))], WANT, 1e-9)
    assert "col 2" in check.mismatch(
        [("A", Decimal("10.50"), None, 3, datetime.date(1995, 3, 1))],
        WANT, 1e-9)
    assert "rows" in check.mismatch([], WANT, 1e-9)
    assert "columns" in check.mismatch([("A",)], WANT, 1e-9)
    # order matters: the queries state one
    two = WANT + [("B", Decimal("1"), 1.0, 1, datetime.date(1995, 3, 2))]
    assert check.mismatch(list(reversed(two)), two, 1e-9) is not None
