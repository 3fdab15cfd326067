"""The end-to-end arithmetic on small hand-made lists."""

import pytest

from harness import stats


def test_median_and_percentile_nearest_rank():
    s = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert stats.median(s) == 3.0
    assert stats.percentile(s, 0) == 1.0
    assert stats.percentile(s, 100) == 5.0
    assert stats.percentile(list(range(1, 101)), 90) == 90
    assert stats.percentile([7.0], 90) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


@pytest.mark.parametrize("n,q,beyond,ok", [
    (100, 90, 10, True), (99, 90, 9, False), (200, 95, 10, True),
    (62, 50, 31, True), (12, 50, 6, False), (1000, 99, 10, True)])
def test_ten_beyond_rule(n, q, beyond, ok):
    assert stats.beyond(n, q) == beyond
    assert stats.supported(n, q) is ok


def test_rows_and_bytes_arithmetic():
    columns = {"lineitem": {"a": 8, "b": 4}, "orders": {"c": 8}}
    rows = {"lineitem": 1000, "orders": 10, "customer": 7}
    assert stats.rows_read(columns, rows) == 1010
    assert stats.least_bytes(columns, rows) == 1000 * 12 + 10 * 8
    # a rate is over ALL the window's seconds
    assert stats.rate(3 * 1010, 2.0) == 1515.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)
