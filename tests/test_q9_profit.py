"""TPC-H Q9 (six tables; five probes chained on each lineitem page, the
fourth of them the hashed join on partsupp's two-column key
``(ps_partkey, ps_suppkey)``, behind ``p_name like '%green%'``) through the
runner the benchmark serves on one chip, on the CPU: its answer against
``benchmark/reference/q9.py`` with exact decimals and in the order the
query states, the flight recorder's ``join-probe`` / ``join-build`` events
by path, and the LIKE's ``like-match`` event and its counter, once a
lowering of the pattern; none of them adds a host sync.

The tables are SF0.05, the size the benchmark's CPU rehearsal of the cell
uses."""

import importlib.util
import os
import sys
from collections import Counter

import numpy as np
import pytest

from trino_tpu.caching import result_cache
from trino_tpu.exec import operators as O
from trino_tpu.exec import syncguard as SG
from trino_tpu.exec.operators import FilterProjectOperator
from trino_tpu.execution.distributed_runner import DistributedQueryRunner
from trino_tpu.ops import like_dfa
from trino_tpu.runner import Session
from trino_tpu.telemetry import metrics as tm
from trino_tpu.telemetry import profiler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from harness import deploy  # noqa: E402

sys.path.pop(0)

TABLES = ["lineitem", "orders", "partsupp", "part", "supplier", "nation"]
SF = 0.05
BATCH_ROWS = 65536
Q9 = open(os.path.join(ROOT, "benchmark", "queries", "q9.sql")).read()


def _reference():
    spec = importlib.util.spec_from_file_location(
        "q9_reference", os.path.join(ROOT, "benchmark", "reference", "q9.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _one_chip_path(monkeypatch):
    """What one chip runs: no fused stage, no resident plan, no collective
    edge; every execution runs (no result cache); the recorder on."""
    monkeypatch.setenv("TRINO_TPU_FUSED_STAGE", "0")
    monkeypatch.setenv("TRINO_TPU_RESIDENT_PLAN", "0")
    prev = profiler.set_level(1)
    with result_cache.disabled():
        yield
    profiler.set_level(prev)


@pytest.fixture(scope="module")
def pinned():
    """The six tables pinned behind the memory connector in 2^16-row
    batches with the source's statistics (the benchmark's own load)."""
    catalog, _ = deploy.load_tables(SF, BATCH_ROWS, TABLES)
    return catalog


def _runner(catalog):
    return DistributedQueryRunner(
        catalog, worker_count=2,
        session=Session(default_catalog="memory", node_count=2,
                        use_collectives=False))


def _of(evs, kind):
    return [e for e in evs if e["kind"] == kind]


def _fresh_lowering(monkeypatch):
    """Filter programs lowered anew: the LIKE matches its pattern again."""
    monkeypatch.setattr(FilterProjectOperator, "_PROGRAM_CACHE", {})


def test_q9_matches_the_reference_exactly(pinned):
    ref = _reference()
    tables = {t: deploy.host_columns(pinned, t, list(cols))
              for t, cols in ref.COLUMNS.items()}
    want = ref.reference(tables)
    got = _runner(pinned).execute(Q9).rows()
    # 25 nations x 7 order years
    assert len(want) == 175
    assert [r[0] for r in want] == sorted(r[0] for r in want)
    # nation, then year descending, and Decimal against Decimal: exact
    assert [tuple(r) for r in got] == want


def test_q9_trace_has_one_hashed_join_behind_four_dense_and_no_sync(
        pinned, monkeypatch):
    # every device copy reads as landed, so the counts are of the syncs the
    # code asks for: how many of those had to wait is timing
    monkeypatch.setattr(SG, "_is_ready", lambda x: True)
    runner = _runner(pinned)
    for _ in range(2):      # the estimate seeds settle: later runs alike
        runner.execute(Q9)
    probes_before = {p: c.value() for p, c in (
        ("dense", tm.JOIN_PROBES_DENSE), ("hashed", tm.JOIN_PROBES_HASHED))}
    syncs = {}
    for level in (0, 1):
        profiler.set_level(level)
        _fresh_lowering(monkeypatch)    # the LIKE's event is in both runs
        before = SG.snapshot()
        runner.execute(Q9, query_id=f"q9-level{level}")
        d = SG.take_delta(before)
        syncs[level] = (d.host_syncs, d.async_polls, d.by_tag)
    evs = profiler.events_for("q9-level1")
    probes = _of(evs, profiler.JOIN_PROBE)
    paths = {}
    for e in probes:
        a = e["args"]
        assert a["lanes"] > 0 and a["key_bytes"] >= 8 * a["keys"]
        assert 0 <= a["gather_lanes"] <= a["lanes"]
        paths.setdefault(a["join"], set()).add((a["path"], a["keys"]))
    assert sorted(paths) == [0, 1, 2, 3, 4]
    kinds = Counter(p for v in paths.values() for p in v)
    assert kinds == {("dense", 1): 4, ("hashed", 2): 1}
    builds = _of(evs, profiler.JOIN_BUILD)
    assert Counter(b["args"]["path"] for b in builds) == \
        {"dense": 8, "hashed": 2}
    for b in builds:
        a = b["args"]
        if a["path"] == "hashed":
            # partsupp's (ps_partkey, ps_suppkey), every row: no direct
            # address holds 10,000 x 500 keys in 16 x its key bytes
            assert (a["keys"], a["slots"], a["rows"]) == (2, 0, 40_000)
            assert a["lanes"] >= a["rows"]
        else:
            assert a["keys"] == 1 and a["lanes"] >= a["rows"] > 0
    assert len(_of(evs, profiler.LIKE_MATCH)) >= 1
    # the recorder's events cost no host sync: off and on alike
    assert syncs[0] == syncs[1] and syncs[1][0]
    # both runs counted their pages, by path
    n = Counter(e["args"]["path"] for e in probes)
    assert n["hashed"] == len(probes) // 5 and n["dense"] == 4 * n["hashed"]
    assert tm.JOIN_PROBES_DENSE.value() - probes_before["dense"] \
        == 2 * n["dense"]
    assert tm.JOIN_PROBES_HASHED.value() - probes_before["hashed"] \
        == 2 * n["hashed"]


def test_q9_part_probe_narrows_the_page_for_the_probes_behind_it(pinned):
    """`part` keeps about 5 % of a page: its own gather of one build column
    would stay wide, but the nation, partsupp and orders probes behind it
    read their keys and tables over every lane it hands on, so it compacts
    the page and they run over its narrow output; the rows stay exact
    (above)."""
    runner = _runner(pinned)
    for _ in range(2):      # the estimate seeds settle: later runs alike
        runner.execute(Q9)
    before = SG.snapshot()
    runner.execute(Q9, query_id="q9-riders")
    d = SG.take_delta(before)
    evs = profiler.events_for("q9-riders")
    pages = {}
    for e in _of(evs, profiler.JOIN_PROBE):
        a = e["args"]
        pages.setdefault(a["join"], []).append(
            (a["lanes"], a["gather_lanes"]))
    part = pages[1]
    assert all(g < lanes for lanes, g in part)
    narrow = Counter(g for _, g in part)
    for place in (2, 3, 4):
        assert Counter(lanes for lanes, _ in pages[place]) == narrow
    # the riders decided it, once a page of `part`
    assert d.unique_gather_compact_riders == len(part)
    # the full-width places carry riders; the last of the narrow ones none
    riders = {}
    for e in _of(evs, profiler.OPERATOR):
        a = e.get("args") or {}
        if e["name"] == "LookupJoinOperator" and "riders" in a:
            riders.setdefault(a["lanes"] == BATCH_ROWS, set()).add(
                a["riders"])
    assert min(riders[True]) > 0 and 0 in riders[False]


def _probe_pages(runner, sql, query_id):
    """Run ``sql`` once: ({place: [join-probe attributes a page]},
    SyncGuard delta)."""
    before = SG.snapshot()
    runner.execute(sql, query_id=query_id)
    d = SG.take_delta(before)
    pages = {}
    for e in _of(profiler.events_for(query_id), profiler.JOIN_PROBE):
        pages.setdefault(e["args"]["join"], []).append(e["args"])
    return pages, d


def _gathering_every_build_column(monkeypatch):
    """The probe as it was: every build column gathered, its keys too."""
    monkeypatch.setattr(O, "_build_keys_from_probe",
                        lambda probe, build, *_: (None,) * build.num_columns)


def test_q9_probes_take_their_build_keys_from_the_page(pinned, monkeypatch):
    """Every place emits its build's key columns as the page's own keys
    (s_suppkey; p_partkey; n_nationkey; ps_partkey and ps_suppkey;
    o_orderkey): each page's gather moves 8 bytes a lane less a key than
    gathering every build column does, at the width that probe takes, and
    with no host sync added; the answer stays exact (above)."""
    monkeypatch.setattr(SG, "_is_ready", lambda x: True)
    runner = _runner(pinned)
    for _ in range(2):      # the estimate seeds settle: later runs alike
        runner.execute(Q9)
    taken, d = _probe_pages(runner, Q9, "q9-keys")
    with monkeypatch.context() as m:
        _gathering_every_build_column(m)
        gathered, d_all = _probe_pages(runner, Q9, "q9-keys-gathered")
    assert {j: {a["keys_from_probe"] for a in pages}
            for j, pages in taken.items()} == {
        0: {1}, 1: {1}, 2: {1}, 3: {2}, 4: {1}}
    assert {a["keys_from_probe"] for pages in gathered.values()
            for a in pages} == {0}
    for j in taken:
        # the same width at every place: part still compacts its page
        widths = [(a["lanes"], a["gather_lanes"]) for a in taken[j]]
        assert Counter(widths) == Counter(
            (a["lanes"], a["gather_lanes"]) for a in gathered[j])
        for a, b in zip(sorted(taken[j], key=lambda a: a["gather_lanes"]),
                        sorted(gathered[j], key=lambda a: a["gather_lanes"])):
            assert b["gather_bytes"] - a["gather_bytes"] \
                == 8 * a["keys_from_probe"]
    assert all(g < lanes for lanes, g in (
        (a["lanes"], a["gather_lanes"]) for a in taken[1]))
    assert d.unique_gather_keys_from_probe == sum(
        a["keys_from_probe"] for pages in taken.values() for a in pages)
    assert d_all.unique_gather_keys_from_probe == 0
    # no host sync added
    assert (d.host_syncs, d.async_polls, d.by_tag) == \
        (d_all.host_syncs, d_all.async_polls, d_all.by_tag)


def test_like_match_moves_once_per_lowering(pinned, monkeypatch):
    calls = []
    real = like_dfa.like_match

    def spy(dictionary, pattern, escape=None):
        calls.append((len(dictionary), pattern))
        return real(dictionary, pattern, escape)

    monkeypatch.setattr(like_dfa, "like_match", spy)
    _fresh_lowering(monkeypatch)
    names = deploy.host_columns(pinned, "part", ["p_name"])["p_name.dict"]
    green = sum("green" in str(s) for s in names)
    runner = _runner(pinned)
    moved = []
    for i in range(2):
        before = tm.LIKE_DICTIONARY_ENTRIES.value()
        n_calls = len(calls)
        runner.execute(Q9, query_id=f"q9-like{i}")
        evs = _of(profiler.events_for(f"q9-like{i}"), profiler.LIKE_MATCH)
        lowered = len(calls) - n_calls
        assert len(evs) == lowered
        assert tm.LIKE_DICTIONARY_ENTRIES.value() - before \
            == lowered * len(names)
        for e in evs:
            # '%', five letters, '%': the vector matcher over every name
            assert e["args"] == {"entries": len(names), "matched": green,
                                 "tokens": 7, "path": "vector"}
        moved.append(lowered)
    # the first execution lowered the pattern; the second found its program
    assert moved[0] >= 1 and moved[1] == 0
    assert all(c == (len(names), "%green%") for c in calls)


def test_like_match_names_the_re_path_on_a_small_dictionary():
    mask, tokens, path = like_dfa.like_match(
        np.array(["dark green", "red", "greenish"], object), "%green%")
    assert (list(mask), tokens, path) == ([True, False, True], 7, "re")
