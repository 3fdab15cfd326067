"""Live rows of the base tables read by the queries the window completed
with a right answer, over ALL the seconds of the window."""

from harness import stats


def read(run) -> float:
    return stats.rate(run.rows_scanned, run.window_s)
