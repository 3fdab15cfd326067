"""The hash join's least bytes and its programs' device time
(``harness/join_bytes``), and the three readers over them
(``join_probe_ms``, ``join_build_ms``, ``join_roofline_share``), on
hand-made recorder events and a hand-made trace: a program whose recorder
has no constant for the join's kinds (the parent) gives None."""

from types import SimpleNamespace

import pytest

from harness import join_bytes as JB
from harness import manifest
from harness import program_spans as P
from harness import trace as T

MAN = manifest.Manifest()
READERS = {n: MAN.metric_reader("per_layer", n)
           for n in ("join_probe_ms", "join_build_ms", "join_roofline_share")}
KINDS = {"JOIN_BUILD": "join-build", "JOIN_PROBE": "join-probe"}
V5E = 819e9


def ev(ts, dur, kind, tid=1, **args):
    return {"ts": ts, "dur": dur, "kind": kind, "name": kind, "query": "q",
            "task": "", "tid": tid, "pid": 1, "thread": "t", "args": args}


def join_events():
    """Two builds (one two-column key of 2^18 lanes, one dense 2^16) and
    three probe pages of 2^20 lanes on two threads: 10 ms and 5 ms
    overlapping by 2 ms on thread 1 (union 13 ms), 4 ms on thread 2."""
    return [
        ev(1.000, 0.006, "join-build", 1, path="hashed", rows=None,
           lanes=1 << 18, keys=2, key_bytes=16),
        ev(1.000, 0.002, "join-build", 2, path="dense", rows=25_000,
           lanes=1 << 16, keys=1, key_bytes=8),
        ev(1.010, 0.010, "join-probe", 1, join=0, path="hashed", keys=1,
           lanes=1 << 20, key_bytes=8, gather_lanes=1 << 16,
           gather_bytes=40),
        ev(1.018, 0.005, "join-probe", 1, join=1, path="hashed", keys=2,
           lanes=1 << 16, key_bytes=16, gather_lanes=1 << 16,
           gather_bytes=48),
        ev(1.010, 0.004, "join-probe", 2, join=0, path="dense", keys=1,
           lanes=1 << 20, key_bytes=8, gather_lanes=1 << 20,
           gather_bytes=12),
    ]


def a_trace():
    """One device plane: a 2 ms join program holding two ops of 0.5 ms and
    one of 1 ms, half of it past the window's end; a compaction program's
    op of 3 ms; an op no program holds."""
    plane = "/device:TPU:0"
    return T.Trace(
        ops={plane: [(0.0010, 0.0005, "%fusion.1"),
                     (0.0012, 0.0005, "%sort.2"),
                     (0.0030, 0.0030, "%gather.3"),
                     (0.0100, 0.0010, "%fusion.4"),
                     (0.0200, 0.0010, "%copy.5")]},
        programs={plane: [(0.0010, 0.0010, "jit_trino_join_uranges(7)"),
                          (0.0030, 0.0030, "jit_trino_kernels_compact(8)"),
                          (0.0100, 0.0020, "jit_trino_join_build(9)")]},
        window=(0.0, 0.0105))


def a_run(monkeypatch, events, kinds=True, platform="tpu"):
    rec = SimpleNamespace(now=lambda: 0.0, events_since=lambda t: events,
                          dropped_since=lambda t: 0,
                          **(KINDS if kinds else {}))
    monkeypatch.setattr(P, "_recorder", lambda: rec)
    return SimpleNamespace(
        queries=2, trace=a_trace(), program_spans_from=0.0,
        device={"platform": platform, "kind": "TPU v5 lite"})


def test_bytes_count_each_key_column_once_and_each_gathered_lane_once():
    evs = join_events()
    builds = [e for e in evs if e["kind"] == "join-build"]
    probes = [e for e in evs if e["kind"] == "join-probe"]
    assert JB.build_bytes(builds[0]["args"]) == (1 << 18) * 16
    assert JB.probe_bytes(probes[0]["args"]) == \
        (1 << 20) * 8 + (1 << 16) * 40
    assert JB.least_bytes(builds, probes) == (
        (1 << 18) * 16 + (1 << 16) * 8
        + (1 << 20) * 8 + (1 << 16) * 40
        + (1 << 16) * 16 + (1 << 16) * 48
        + (1 << 20) * 8 + (1 << 20) * 12)
    assert JB.least_bytes([], []) == 0


def test_program_seconds_takes_the_join_programs_ops_inside_the_window():
    got = JB.program_seconds(a_trace())
    assert set(got) == {"jit_trino_join_uranges", "jit_trino_join_build"}
    # two overlapping ops of 0.5 ms: union 0.7 ms
    assert got["jit_trino_join_uranges"] == pytest.approx(0.0007)
    # 1 ms op, half of it past the window's end
    assert got["jit_trino_join_build"] == pytest.approx(0.0005)
    assert JB.program_seconds(T.Trace()) == {}


def test_probe_and_build_readers_take_the_union_per_thread(monkeypatch):
    run = a_run(monkeypatch, join_events())
    assert READERS["join_probe_ms"].read(run, 0.0) == \
        pytest.approx((0.013 + 0.004) / 2 * 1e3)
    assert READERS["join_build_ms"].read(run, 0.0) == \
        pytest.approx((0.006 + 0.002) / 2 * 1e3)


def test_roofline_share_is_least_time_over_the_join_programs(monkeypatch):
    evs = join_events()
    run = a_run(monkeypatch, evs)
    least = JB.least_bytes(evs[:2], evs[2:])
    got = READERS["join_roofline_share"].read(run, 0.0)
    assert got == pytest.approx(100 * least / V5E / 0.0012)


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_program_without_the_join_kinds_gives_none(monkeypatch, name):
    run = a_run(monkeypatch, join_events(), kinds=False)
    assert READERS[name].read(run, 0.0) is None


def test_no_share_on_a_rehearsal_or_without_join_programs(monkeypatch):
    reader = READERS["join_roofline_share"]
    assert reader.read(a_run(monkeypatch, join_events(), platform="cpu"),
                       0.0) is None
    run = a_run(monkeypatch, join_events())
    run.trace.programs = {p: [] for p in run.trace.programs}
    assert reader.read(run, 0.0) is None
