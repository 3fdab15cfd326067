"""Median wall of one query on the client's clock, POST to last page, over
every query the window completed with a right answer."""

from harness import stats


def read(run) -> float:
    return stats.median(run.latencies)
