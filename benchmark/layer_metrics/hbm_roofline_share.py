"""Compiled programs: the least time the chip could take to read what the
traced queries reference (each referenced column once at its stored width
over the live rows — harness/stats.least_bytes — over the HBM peak of the
device_kind table), as a share of the device's busy time in the window.
A share of all the query's programs together, bounded by memory bandwidth;
per-program shares need stable program names (PERF.md, Open questions)."""

from harness import peaks


def read(run, _):
    busy = run.trace.busy_s()
    if busy <= 0 or run.device["platform"] != "tpu":  # a rehearsal has no HBM
        return None
    least_s = run.least_bytes / peaks.hbm_bytes_per_s(run.device["kind"])
    return 100.0 * least_s / busy
