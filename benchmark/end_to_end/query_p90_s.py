"""90th percentile of the same walls.  Listed only for cells whose window
completes about a hundred queries, so that ten samples lie beyond it; the
sample count is on an earlier line of every run."""

from harness import stats


def read(run) -> float:
    return stats.percentile(run.latencies, 90)
