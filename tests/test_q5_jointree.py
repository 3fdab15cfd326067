"""TPC-H Q5 (six tables; five probes chained on each lineitem page, among
them ``l_orderkey = o_orderkey`` over one year's orders and the two-column
``(o_custkey, s_nationkey) = (c_custkey, c_nationkey)``) through the runner
the benchmark serves on one chip, on the CPU: its answer against
``benchmark/reference/q5.py`` with exact decimals, and the flight
recorder's ``join-build`` / ``join-probe`` events, which add no host sync.
Every build of Q5 takes the direct-address table, the two-column key packed
mixed-radix, so the general hashed join is held against a plain numpy join
on seeded tables whose keys are drawn from a sparse space (and one
two-column case against the packed table).

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
from trino_tpu.execution.distributed_runner import DistributedQueryRunner
from trino_tpu.runner import Session
from trino_tpu.spi.batch import Column, ColumnBatch
from trino_tpu.spi.connector import ColumnSchema, TableSchema
from trino_tpu.spi.types import BIGINT
from trino_tpu.telemetry import metrics as tm
from trino_tpu.telemetry import profiler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from harness import deploy  # noqa: E402

sys.path.pop(0)

TABLES = ["lineitem", "orders", "customer", "supplier", "nation", "region"]
SF = 0.05
BATCH_ROWS = 65536
Q5 = open(os.path.join(ROOT, "benchmark", "queries", "q5.sql")).read()


def _reference():
    spec = importlib.util.spec_from_file_location(
        "q5_reference", os.path.join(ROOT, "benchmark", "reference", "q5.py"))
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


# ------------------------------------------------------------------- Q5


def test_q5_matches_the_reference_exactly(pinned):
    ref = _reference()
    tables = {t: deploy.host_columns(pinned, t, list(cols))
              for t, cols in ref.COLUMNS.items()}
    want = ref.reference(tables)
    got = _runner(pinned).execute(Q5).rows()
    assert len(want) >= 2
    # names in order, and Decimal against Decimal: exact, no tolerance
    assert [tuple(r) for r in got] == want


def test_q5_trace_has_two_hashed_joins_and_three_dense_and_no_sync(
        pinned, monkeypatch):
    # every device copy reads as landed, so the counts are of the syncs the
    # code asks for: how many of those had to wait is timing
    monkeypatch.setattr(SG, "_is_ready", lambda x: True)
    runner = _runner(pinned)
    for _ in range(2):      # the estimate seeds settle: later runs alike
        runner.execute(Q5)
    probes_before = {p: c.value() for p, c in (
        ("dense", tm.JOIN_PROBES_DENSE), ("hashed", tm.JOIN_PROBES_HASHED))}
    syncs = {}
    for level in (0, 1):
        profiler.set_level(level)
        before = SG.snapshot()
        runner.execute(Q5, query_id=f"q5-level{level}")
        d = SG.take_delta(before)
        syncs[level] = (d.host_syncs, d.async_polls, d.by_tag)
    evs = profiler.events_for("q5-level1")
    probes = _of(evs, profiler.JOIN_PROBE)
    paths, widths = {}, {}
    for e in probes:
        a = e["args"]
        assert a["lanes"] > 0 and a["key_bytes"] >= 8 * a["keys"]
        assert 0 <= a["gather_lanes"] <= a["lanes"]
        paths.setdefault(a["join"], set()).add((a["path"], a["keys"]))
        widths.setdefault(a["join"], set()).add(
            (a["lanes"], a["gather_lanes"]))
    assert sorted(paths) == [0, 1, 2, 3, 4]
    # every place gathers as it would with no riders: the probes behind
    # place 3 read too little a lane to make it compact, and the last one,
    # customer's, emits both its build columns from the page's own keys:
    # with nothing left to gather it stays wide (it narrowed to 256 lanes
    # while it gathered them: test_q5_probes_take_their_build_keys_...)
    assert widths == {j: {(BATCH_ROWS, BATCH_ROWS)} for j in range(5)}
    # the name is from when two of the five places took the hashed join:
    # orders' year and the two-column customer key are dense too now
    kinds = Counter(p for v in paths.values() for p in v)
    assert kinds == {("dense", 1): 4, ("dense", 2): 1}
    builds = _of(evs, profiler.JOIN_BUILD)
    assert Counter((b["args"]["path"], b["args"]["keys"]) for b in builds) \
        == {("dense", 1): 8, ("dense", 2): 2}
    for b in builds:
        a = b["args"]
        assert a["lanes"] >= a["rows"] > 0
        assert a["key_bytes"] == 8 * a["keys"]
        # the table holds every live row, in at most 16 x the key bytes
        assert a["rows"] <= a["slots"] <= 16 * a["lanes"] * a["key_bytes"] / 4
    # the recorder's events cost no host sync: off and on alike
    assert syncs[0] == syncs[1] and syncs[1][0]
    # both runs counted their pages, by path: none hashed
    n = Counter(e["args"]["path"] for e in probes)
    assert n["hashed"] == 0 and n["dense"] == len(probes)
    assert tm.JOIN_PROBES_DENSE.value() - probes_before["dense"] \
        == 2 * n["dense"]
    assert tm.JOIN_PROBES_HASHED.value() - probes_before["hashed"] == 0


def _probe_pages(runner, query_id):
    """Run Q5 once: ({place: [join-probe attributes a page]}, SyncGuard
    delta)."""
    before = SG.snapshot()
    runner.execute(Q5, query_id=query_id)
    d = SG.take_delta(before)
    pages = {}
    for e in _of(profiler.events_for(query_id), profiler.JOIN_PROBE):
        pages.setdefault(e["args"]["join"], []).append(e["args"])
    return pages, d


def test_q5_probes_take_their_build_keys_from_the_page(pinned, monkeypatch):
    """Every place emits its build's key columns as the page's own keys —
    two at customer's, the last place of the plan this size takes without
    history — so each page's gather moves 8 bytes a lane less a key than
    gathering every build column does, with no host sync added; the answer
    stays exact (above).  Places 0-3 run at the width they would if they
    gathered every build column.  The last one gathered customer's two
    columns, 4 words a lane: 65,536 x 4 x 8.3 ns = 2.2 ms wide against
    65,536 x 3.5 ns + 256 x (35 + 4 + 3) x 8.3 ns = 0.32 ms compact, so it
    narrowed to 256 lanes; with nothing left to gather it stays wide and
    launches no gather at all."""
    monkeypatch.setattr(SG, "_is_ready", lambda x: True)
    runner = _runner(pinned)
    for _ in range(2):      # the estimate seeds settle: later runs alike
        runner.execute(Q5)
    taken, d = _probe_pages(runner, "q5-keys")
    with monkeypatch.context() as m:
        m.setattr(O, "_build_keys_from_probe",
                  lambda probe, build, *_: (None,) * build.num_columns)
        gathered, d_all = _probe_pages(runner, "q5-keys-gathered")
    assert {j: {a["keys_from_probe"] for a in pages}
            for j, pages in taken.items()} == {
        0: {1}, 1: {1}, 2: {1}, 3: {1}, 4: {2}}
    assert {a["keys_from_probe"] for pages in gathered.values()
            for a in pages} == {0}

    def widths(pages):
        return Counter((a["lanes"], a["gather_lanes"]) for a in pages)

    for j in range(4):
        assert widths(taken[j]) == widths(gathered[j])
        for a, b in zip(taken[j], gathered[j]):
            assert b["gather_bytes"] - a["gather_bytes"] \
                == 8 * a["keys_from_probe"]
    assert widths(gathered[4]) == {(BATCH_ROWS, 256): len(gathered[4])}
    assert widths(taken[4]) == {(BATCH_ROWS, BATCH_ROWS): len(taken[4])}
    assert {a["gather_bytes"] for a in taken[4]} == {0}
    assert d.unique_gather_keys_from_probe == sum(
        a["keys_from_probe"] for pages in taken.values() for a in pages)
    assert d_all.unique_gather_keys_from_probe == 0
    # no host sync added
    assert d.host_syncs == d_all.host_syncs
    assert d.by_tag == d_all.by_tag


# ---------------------------------------------- the hashed join, by case


def _table(mem, name, cols):
    """A memory table of BIGINT columns: {name: (values, valid|None)}."""
    mem.create_table(TableSchema(name, tuple(ColumnSchema(c, BIGINT)
                                             for c in cols)))
    mem.finish_insert(name, [[ColumnBatch(list(cols), [
        Column(BIGINT, np.asarray(v, np.int64),
               None if ok is None else np.asarray(ok, bool))
        for v, ok in cols.values()])]])


def _numpy_join(p_keys, b_keys):
    """(probe row, build row) of every pair whose keys are all equal and
    valid: a plain sort-merge over the build's rows."""
    valid_p = np.all([ok for _, ok in p_keys], axis=0)
    valid_b = np.all([ok for _, ok in b_keys], axis=0)
    by = {}
    for j in np.flatnonzero(valid_b):
        by.setdefault(tuple(int(v[j]) for v, _ in b_keys), []).append(j)
    return sorted((int(i), int(j)) for i in np.flatnonzero(valid_p)
                  for j in by.get(tuple(int(v[i]) for v, _ in p_keys), ()))


def _case(name, rng):
    """(probe keys, build keys): [(values, valid)] a key column."""
    n_p, n_b = 3000, 800
    ones_p, ones_b = np.ones(n_p, bool), np.ones(n_b, bool)
    # keys drawn from 10^12 values: no direct-address table holds them
    sparse = rng.choice(10 ** 12, n_b, replace=False)
    if name in ("two_column_key", "two_column_dense"):
        if name == "two_column_key":
            b1, b2 = sparse, rng.choice(10 ** 12, n_b, replace=False)
        else:  # 64 x 64 slots: the packed table
            pairs = rng.choice(64 * 64, n_b, replace=False)
            b1, b2 = pairs // 64, pairs % 64
        pick = rng.integers(0, n_b, n_p)
        p1 = b1[pick]
        # 30 % of the lanes hold a first column that is there and a second
        # that is some other row's
        p2 = np.where(rng.random(n_p) < 0.3, b2[rng.integers(0, n_b, n_p)],
                      b2[pick])
        return ([(p1, ones_p), (p2, ones_p)], [(b1, ones_b), (b2, ones_b)])
    if name == "sparse_single_key":
        return ([(sparse[rng.integers(0, n_b, n_p)], ones_p)],
                [(sparse, ones_b)])
    if name == "duplicate_probe_keys":
        return ([(sparse[rng.integers(0, 8, n_p)], ones_p)],
                [(sparse, ones_b)])
    if name == "misses":
        hit = rng.random(n_p) < 0.1
        return ([(np.where(hit, sparse[rng.integers(0, n_b, n_p)],
                           rng.integers(10 ** 12, 2 * 10 ** 12, n_p)),
                  ones_p)], [(sparse, ones_b)])
    assert name == "null_keys"
    return ([(sparse[rng.integers(0, n_b, n_p)], rng.random(n_p) > 0.2)],
            [(sparse, rng.random(n_b) > 0.2)])


CASES = ["two_column_key", "sparse_single_key", "duplicate_probe_keys",
         "misses", "null_keys", "two_column_dense"]


@pytest.mark.parametrize("case", CASES)
def test_hashed_join_matches_a_numpy_join(case, pinned):
    rng = np.random.default_rng(41 + CASES.index(case))
    p_keys, b_keys = _case(case, rng)
    mem = pinned.connector("memory")
    names = [f"k{i}" for i in range(len(p_keys))]
    pt, bt = f"hj_probe_{case}", f"hj_build_{case}"
    _table(mem, pt, dict(zip(["pid"] + names,
                             [(np.arange(len(p_keys[0][0])), None)]
                             + p_keys)))
    _table(mem, bt, dict(zip(["bid"] + names,
                             [(np.arange(len(b_keys[0][0])), None)]
                             + b_keys)))
    on = " and ".join(f"p.{k} = b.{k}" for k in names)
    t0 = profiler.now()
    got = _runner(pinned).execute(
        f"select p.pid, b.bid from {pt} p join {bt} b on {on}").rows()
    want = _numpy_join(p_keys, b_keys)
    assert want and len(want) < 3000 * 800
    assert sorted((int(a), int(b)) for a, b in got) == want
    paths = {e["args"]["path"] for e in profiler.events_since(t0)
             if e["kind"] == profiler.JOIN_PROBE}
    assert paths == {"dense" if case.endswith("_dense") else "hashed"}
