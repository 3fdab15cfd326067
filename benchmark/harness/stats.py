"""The arithmetic of the end-to-end metrics.  Pure functions of lists."""

from __future__ import annotations

import math

BEYOND = 10  # samples a percentile wants beyond it (choosing-metrics, 1)


def percentile(samples: list, q: float) -> float:
    """Nearest-rank percentile of all samples (bench.py's ``_pct`` rule:
    index round(q * (n - 1))); q in [0, 100]."""
    if not samples:
        raise ValueError("percentile of no samples")
    s = sorted(samples)
    return s[min(len(s) - 1, int(q / 100.0 * (len(s) - 1) + 0.5))]


def median(samples: list) -> float:
    return percentile(samples, 50)


def beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie beyond the q-th percentile."""
    return int(math.floor(n * (1.0 - q / 100.0) + 1e-9))


def supported(n: int, q: float) -> bool:
    """The ten-beyond rule: a tail is worth its name only when at least
    ``BEYOND`` samples lie beyond it."""
    return beyond(n, q) >= BEYOND


def rate(total: float, seconds: float) -> float:
    """Work over ALL the time of the window."""
    if seconds <= 0:
        raise ValueError("rate over no time")
    return total / seconds


def rows_read(columns_of: dict, table_rows: dict) -> int:
    """Live rows of the base tables one execution of a query reads:
    ``columns_of`` is the reference's COLUMNS ({table: {column: bytes}}),
    ``table_rows`` the configuration's row counts."""
    return sum(table_rows[t] for t in columns_of)


def least_bytes(columns_of: dict, table_rows: dict) -> int:
    """The fewest bytes one execution has to read from device memory: each
    referenced column once, at its stored width, over the table's live
    rows."""
    return sum(table_rows[t] * sum(cols.values())
               for t, cols in columns_of.items())
