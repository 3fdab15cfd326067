"""The hash join's least bytes and its programs' device time: what
``join_roofline_share`` divides.  Pure functions over the program's
flight-recorder ``join-build`` and ``join-probe`` events (their ``args``:
``lanes``, ``key_bytes``, ``gather_lanes``, ``gather_bytes``, all known to
the operator on the host) and over ``harness/trace.Trace``.

Bytes count only what the join's programs cannot avoid moving, so a share
of them cannot pass 100 %:

- a build reads each key column once over its lanes (``join.build``);
- a probe page reads each key column once over its lanes
  (``join.uranges`` / ``join.dense_uranges`` / ``join.unique`` /
  ``join.ranges``);
- its gather writes each gathered column once over the gather's lanes
  (``join.unique_gather`` / ``join.unique`` / ``join.pairs``): the build
  columns, and the probe columns where the gather compacts them too.

Reads of the build table (sorted hashes, direct-address slots), the
gathers' reads of their sources and the masks are left out."""

from __future__ import annotations

from . import trace as T

PROGRAMS = "jit_trino_join_"    # every program of exec/join_exec.py


def build_bytes(args: dict) -> int:
    return args["lanes"] * args["key_bytes"]


def probe_bytes(args: dict) -> int:
    return (args["lanes"] * args["key_bytes"]
            + args["gather_lanes"] * args["gather_bytes"])


def least_bytes(builds: list, probes: list) -> int:
    """Over recorder events of the two kinds."""
    return (sum(build_bytes(e["args"]) for e in builds)
            + sum(probe_bytes(e["args"]) for e in probes))


def program_seconds(tr: T.Trace, prefix: str = PROGRAMS) -> dict:
    """{program: device seconds in the window} over the operations whose
    program (the ``XLA Modules`` execution that holds their start) is named
    ``prefix``*: per plane the union of those operations, averaged over the
    planes as ``Trace.busy_s`` does."""
    if not tr.ops:
        return {}
    out: dict = {}
    for plane, ops in tr.ops.items():
        named = T.named_by_program(T.clip(ops, *tr.window),
                                   tr.programs.get(plane, []))
        by: dict = {}
        for s, d, name in named:
            prog = name.partition(" / ")[0]
            if prog.startswith(prefix):
                by.setdefault(prog, []).append((s, d))
        for prog, ivs in by.items():
            out[prog] = out.get(prog, 0.0) + T.busy_seconds(ivs) / len(tr.ops)
    return out
