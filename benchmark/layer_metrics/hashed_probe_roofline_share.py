"""Compiled programs: the least time the chip could take to read what the
traced queries' hashed probes must read — each hashed probe page's key
columns once over its lanes (the ``lanes`` and ``key_bytes`` of the
program's ``join-probe`` events whose ``path`` is ``hashed``) — at the HBM
peak of the device_kind table, as a share of the device time of the
operations whose program is ``jit_trino_join_uranges`` (the hashed probe's
range search).  Prints the bytes and that device time.  None on a program
that records no such events, where no hashed page was probed, or where no
``jit_trino_join_uranges`` ran on a device."""

from harness import join_bytes as JB
from harness import peaks
from harness import program_spans as P
from harness import turn
from harness.deploy import say

PROGRAM = "jit_trino_join_uranges"


def begin(run):
    return P.begin(run)


def read(run, since):
    if run.device["platform"] != "tpu":     # a rehearsal has no HBM
        return None
    got = turn.spans(run, "join-probe")
    if got is None:
        return None
    hashed = [e["args"] for e in got["join-probe"]
              if (e.get("args") or {}).get("path") == "hashed"]
    device_s = JB.program_seconds(run.trace, PROGRAM).get(PROGRAM, 0.0)
    if not hashed or device_s <= 0:
        return None
    least = sum(a["lanes"] * a["key_bytes"] for a in hashed)
    say(f"hashed_probe_roofline_share: {least} key bytes over "
        f"{len(hashed)} hashed probe pages against {device_s * 1e3:.3f} ms "
        f"of {PROGRAM}")
    return 100.0 * least / peaks.hbm_bytes_per_s(run.device["kind"]) \
        / device_s
