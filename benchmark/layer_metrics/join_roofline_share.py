"""Compiled programs: the least time the chip could take to move what the
traced queries' hash joins must move (``harness/join_bytes``: each build's
key columns over its lanes, each probe page's key columns over its lanes
and its gathered columns over the gather's lanes, from the program's
``join-build`` / ``join-probe`` events) at the HBM peak of the device_kind
table, as a share of the device time of the operations whose program is
``jit_trino_join_*``.  Prints the bytes and each program's device time.
None on a program that records no such events, or where no join program
ran on a device."""

from harness import join_bytes as JB
from harness import peaks
from harness import program_spans as P
from harness import turn
from harness.deploy import say


def begin(run):
    return P.begin(run)


def read(run, since):
    if run.device["platform"] != "tpu":     # a rehearsal has no HBM
        return None
    got = turn.spans(run, "join-build", "join-probe")
    if got is None:
        return None
    seconds = JB.program_seconds(run.trace)
    device_s = sum(seconds.values())
    if device_s <= 0:
        return None
    builds, probes = got["join-build"], got["join-probe"]
    least = JB.least_bytes(builds, probes)
    say(f"join_roofline_share: {least} bytes ({len(builds)} builds, "
        f"{len(probes)} probe pages) against {device_s * 1e3:.3f} ms of "
        f"join programs: "
        + "; ".join(f"{k} {v * 1e3:.3f} ms" for k, v in sorted(
            seconds.items(), key=lambda kv: -kv[1])))
    least_s = least / peaks.hbm_bytes_per_s(run.device["kind"])
    return 100.0 * least_s / device_s
