"""The hashed probe's two readers (``hashed_probe_ms``,
``hashed_probe_roofline_share``) on hand-made recorder events and a
hand-made trace: pages through a direct-address table are left out, the
share is None off the TPU, without hashed pages or without
``jit_trino_join_uranges``, and a program whose recorder has no constant
for the join's kinds (the parent) gives None."""

from types import SimpleNamespace

import pytest

from harness import manifest
from harness import program_spans as P
from harness import trace as T

MAN = manifest.Manifest()
READERS = {n: MAN.metric_reader("per_layer", n)
           for n in ("hashed_probe_ms", "hashed_probe_roofline_share")}
KINDS = {"JOIN_BUILD": "join-build", "JOIN_PROBE": "join-probe"}
V5E = 819e9
PLANE = "/device:TPU:0"


def ev(ts, dur, kind, tid=1, **args):
    return {"ts": ts, "dur": dur, "kind": kind, "name": kind, "query": "q",
            "task": "", "tid": tid, "pid": 1, "thread": "t", "args": args}


def probe(ts, dur, tid, path, lanes=1 << 20, keys=2):
    return ev(ts, dur, "join-probe", tid, join=3 if path == "hashed" else 0,
              path=path, keys=keys, lanes=lanes, key_bytes=8 * keys,
              gather_lanes=lanes, gather_bytes=16)


def events():
    """A hashed build, and five probe pages on two threads: hashed pages
    of 10 ms and 6 ms overlapping by 2 ms on thread 1 (union 14 ms) and
    one of 4 ms on thread 2; two dense pages of 50 ms that must not count."""
    return [
        ev(1.000, 0.030, "join-build", 1, path="hashed", rows=800_000,
           lanes=1 << 20, keys=2, key_bytes=16, slots=0),
        probe(1.100, 0.010, 1, "hashed"),
        probe(1.108, 0.006, 1, "hashed", lanes=1 << 19),
        probe(1.100, 0.004, 2, "hashed"),
        probe(1.200, 0.050, 1, "dense", keys=1),
        probe(1.200, 0.050, 2, "dense", keys=1),
    ]


def a_trace(uranges_s=0.100):
    """One device plane: a hashed range search of ``uranges_s`` holding one
    op, a dense probe's program and a build's, whose ops must not count."""
    return T.Trace(
        ops={PLANE: [(0.010, uranges_s, "%sort.1"),
                     (0.200, 0.050, "%gather.2"),
                     (0.300, 0.030, "%sort.3")]},
        programs={PLANE: [(0.010, uranges_s, "jit_trino_join_uranges(7)"),
                          (0.200, 0.050, "jit_trino_join_dense_uranges(8)"),
                          (0.300, 0.030, "jit_trino_join_build(9)")]},
        window=(0.0, 1.0))


def a_run(monkeypatch, evs, kinds=True, platform="tpu", trace=None):
    rec = SimpleNamespace(now=lambda: 0.0, events_since=lambda t: evs,
                          dropped_since=lambda t: 0,
                          **(KINDS if kinds else {}))
    monkeypatch.setattr(P, "_recorder", lambda: rec)
    return SimpleNamespace(
        queries=2, trace=trace or a_trace(), program_spans_from=0.0,
        device={"platform": platform, "kind": "TPU v5 lite"})


def hashed_key_bytes():
    return ((1 << 20) + (1 << 19) + (1 << 20)) * 16


def test_probe_ms_is_the_union_of_hashed_pages_per_thread(monkeypatch):
    run = a_run(monkeypatch, events())
    got = READERS["hashed_probe_ms"].read(run, 0.0)
    assert got == pytest.approx((0.014 + 0.004) / 2 * 1e3)


def test_dense_pages_are_left_out(monkeypatch):
    only_dense = [e for e in events()
                  if (e["args"].get("path"), e["kind"]) != ("hashed",
                                                            "join-probe")]
    run = a_run(monkeypatch, only_dense)
    assert READERS["hashed_probe_ms"].read(run, 0.0) == 0.0
    # no hashed page: no share, however long the other join programs ran
    assert READERS["hashed_probe_roofline_share"].read(run, 0.0) is None
    # dense pages beside the hashed ones change neither reading
    with_dense = a_run(monkeypatch, events())
    without = a_run(monkeypatch, [e for e in events()
                                  if e["args"].get("path") != "dense"])
    for reader in READERS.values():
        assert reader.read(with_dense, 0.0) == reader.read(without, 0.0)


def test_share_is_key_bytes_at_the_peak_over_the_range_search(monkeypatch):
    run = a_run(monkeypatch, events())
    got = READERS["hashed_probe_roofline_share"].read(run, 0.0)
    assert got == pytest.approx(100 * hashed_key_bytes() / V5E / 0.100)


@pytest.mark.parametrize("uranges_s", [1e-3, 1e-4, 1e-5])
def test_share_cannot_pass_100_while_the_search_reads_its_keys(
        monkeypatch, uranges_s):
    """The least time of reading the keys bounds the search's time from
    below: a search no shorter than that reads at most 100 %; a trace
    shorter than it would read more, which is the fault the bound finds."""
    least_s = hashed_key_bytes() / V5E
    run = a_run(monkeypatch, events(),
                trace=a_trace(uranges_s=max(uranges_s, least_s)))
    got = READERS["hashed_probe_roofline_share"].read(run, 0.0)
    assert 0 < got <= 100 + 1e-9


def test_no_share_off_the_tpu_or_without_the_range_search(monkeypatch):
    reader = READERS["hashed_probe_roofline_share"]
    assert reader.read(a_run(monkeypatch, events(), platform="cpu"),
                       0.0) is None
    tr = a_trace()
    tr.programs = {PLANE: [p for p in tr.programs[PLANE]
                           if "join_uranges" not in p[2]
                           or "dense" in p[2]]}
    assert reader.read(a_run(monkeypatch, events(), trace=tr), 0.0) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_program_without_the_join_kinds_gives_none(monkeypatch, name):
    run = a_run(monkeypatch, events(), kinds=False)
    assert READERS[name].read(run, 0.0) is None
