"""90th percentile of the same walls.  Listed only for cells whose window
completes a hundred queries or more, so that ten samples lie beyond it
(a 46 s window of Q6 at SF10 holds about 670, 67 beyond); the sample count
and every wall are on earlier lines of every run."""

from harness import stats


def read(run) -> float:
    return stats.percentile(run.latencies, 90)
