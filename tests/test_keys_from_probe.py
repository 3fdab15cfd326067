"""A unique-build probe emits each build key column that its probe key
equals bit for bit as that probe column instead of gathering it: on a
matched lane the two hold the same value (the direct-address table
addresses the whole key, the hashed probe verifies it key by key), and the
column's validity stays the match mask.  Every case runs the probe twice,
once as it is and once with every build column gathered (the rule that
took none), at the same leg, and the batches must agree lane for lane on
the live lanes; then what the rule took is checked against the join
event, the SyncGuard count and the /v1/metrics counter.  Counts only
(CPU)."""

import zlib

import numpy as np
import pytest

from trino_tpu.exec import join_exec as JX
from trino_tpu.exec import operators as O
from trino_tpu.exec import syncguard as SG
from trino_tpu.exec.operators import (JoinBridge, JoinBuildSink,
                                      LookupJoinOperator)
from trino_tpu.spi import (BIGINT, BOOLEAN, DATE, DOUBLE, INTEGER, VARCHAR,
                           Column, ColumnBatch)
from trino_tpu.sql.ir import InputRef, Literal, call
from trino_tpu.telemetry import metrics as tm
from trino_tpu.telemetry import profiler

LANES = 4096
NB = 1024


@pytest.fixture(autouse=True)
def _clean():
    JX.reset_estimate_seeds_for_test()
    prev = profiler.set_level(1)
    yield
    profiler.set_level(prev)
    JX.reset_estimate_seeds_for_test()


def _values(t, raw):
    """Column values of type ``t`` from integer draws."""
    if t == VARCHAR:
        return [f"k{int(v):012d}" for v in raw]
    if t == DOUBLE:
        return [float(v) / 4 for v in raw]
    return [int(v) for v in raw]


def _tables(space: str, n_keys: int, hit_share: float, rng):
    """(probe key draws, build key draws, hit lanes): [array a key column]
    over LANES probe rows and NB unique build rows.  ``dense`` keys span a
    small range (the direct-address table, packed for two columns);
    ``sparse`` ones are drawn from 10^12 values (the hashed probe)."""
    if space == "dense":
        if n_keys == 1:
            bkeys = [rng.permutation(NB) + 100]
        else:  # 64 x 64 slots
            pairs = rng.choice(64 * 64, NB, replace=False)
            bkeys = [pairs // 64, pairs % 64 + 5]
        miss = [np.full(LANES, 10_000)] * n_keys
    else:
        bkeys = [rng.choice(10 ** 12, NB, replace=False)
                 for _ in range(n_keys)]
        miss = [rng.integers(10 ** 12, 2 * 10 ** 12, LANES)] * n_keys
    hit = rng.random(LANES) < hit_share
    pick = rng.integers(0, NB, LANES)
    pkeys = [np.where(hit, b[pick], m) for b, m in zip(bkeys, miss)]
    return pkeys, bkeys, hit


def _run(case, taking: bool, monkeypatch):
    """Drive one probe over one page; (input page, output batches, the
    probe's join-probe events, SyncGuard delta)."""
    rng = np.random.default_rng(zlib.crc32(case["name"].encode()))
    ptype, btype = case["types"]
    pkeys, bkeys, _ = _tables(case["space"], case["keys"], case["hits"], rng)
    bnames = [f"bk{i}" for i in range(case["keys"])] + ["bv"]
    build = ColumnBatch(bnames, [
        Column.from_values(btype, _values(btype, k)) for k in bkeys]
        + [Column.from_values(BIGINT, (np.arange(NB) * 7).tolist())])
    bridge = JoinBridge()
    # the build's keys come after its payload column: the key channels are
    # not the key order's positions
    order = [case["keys"]] + list(range(case["keys"]))
    build = ColumnBatch([bnames[i] for i in order],
                        [build.columns[i] for i in order])
    sink = JoinBuildSink(bridge, list(range(1, case["keys"] + 1)),
                         [c.type for c in build.columns], build.names)
    sink.add_input(build)
    sink.finish_input()
    pnames = ["pv"] + [f"pk{i}" for i in range(case["keys"])]
    page = ColumnBatch(pnames, [
        Column.from_values(BIGINT, rng.integers(0, 10 ** 4, LANES).tolist())]
        + [Column.from_values(ptype, _values(ptype, k)) for k in pkeys])
    residual = None
    if case.get("residual"):
        # the build's first key is even: the residual reads a key taken
        # from the probe
        residual = call("eq", BOOLEAN, call(
            "modulus", btype, InputRef(btype, len(pnames) + 1),
            Literal(btype, 2)), Literal(btype, 0))
    names = pnames + build.names
    op = LookupJoinOperator(
        bridge, list(range(1, case["keys"] + 1)), case["join"], residual,
        names, [c.type for c in page.columns + build.columns])
    if not taking:
        monkeypatch.setattr(O, "_build_keys_from_probe",
                            lambda p, b, *_: (None,) * b.num_columns)
    leg = case["leg"]
    monkeypatch.setattr(JX, "plan_unique_cap", lambda lanes, count, *_: (
        None if leg == "wide" else JX.unique_cap_bucket(count)))
    t0 = profiler.now()
    before = SG.snapshot()
    out = []
    op.add_input(page)
    op.finish_input()
    while not op.is_finished():
        b = op.get_output()
        if b is not None:
            out.append(b)
    d = SG.take_delta(before)
    evs = [e["args"] for e in profiler.events_since(t0)
           if e["kind"] == profiler.JOIN_PROBE]
    monkeypatch.undo()
    return page, out, evs, d


def _mask(m, n):
    return np.ones(n, bool) if m is None else np.asarray(m)


# name, join, (probe type, build type), key space, key columns, share of
# lanes that match, the leg program B runs at (None: the LEFT join's one
# program), build keys taken from the probe
CASES = [
    ("dense-one-key-wide", "INNER", (BIGINT, BIGINT), "dense", 1, 0.9,
     "wide", 1),
    ("dense-one-key-compact", "INNER", (BIGINT, BIGINT), "dense", 1, 0.01,
     "compact", 1),
    ("dense-packed-two-keys-wide", "INNER", (BIGINT, BIGINT), "dense", 2,
     0.9, "wide", 2),
    ("dense-packed-two-keys-compact", "INNER", (BIGINT, BIGINT), "dense", 2,
     0.01, "compact", 2),
    ("hashed-two-keys-wide", "INNER", (BIGINT, BIGINT), "sparse", 2, 0.9,
     "wide", 2),
    ("hashed-two-keys-compact", "INNER", (BIGINT, BIGINT), "sparse", 2,
     0.01, "compact", 2),
    ("date-key", "INNER", (DATE, DATE), "dense", 1, 0.5, "wide", 1),
    ("residual-reads-the-key", "INNER", (BIGINT, BIGINT), "dense", 1, 0.1,
     "compact", 1),
    ("residual-reads-the-key-wide", "INNER", (BIGINT, BIGINT), "sparse", 2,
     0.5, "wide", 2),
    ("left-join-residual-reads-the-key", "LEFT", (BIGINT, BIGINT), "dense",
     1, 0.5, None, 1),
    ("left-join-null-extended", "LEFT", (BIGINT, BIGINT), "dense", 1, 0.5,
     None, 1),
    ("left-join-hashed", "LEFT", (BIGINT, BIGINT), "sparse", 2, 0.5, None,
     2),
    ("double-key-gathers", "INNER", (DOUBLE, DOUBLE), "sparse", 1, 0.5,
     "wide", 0),
    ("varchar-key-gathers", "INNER", (VARCHAR, VARCHAR), "sparse", 1, 0.5,
     "wide", 0),
    ("types-differ-gather", "INNER", (INTEGER, BIGINT), "dense", 1, 0.5,
     "wide", 0),
    ("left-join-double-key-gathers", "LEFT", (DOUBLE, DOUBLE), "sparse", 1,
     0.5, None, 0),
]


@pytest.mark.parametrize("case", [
    dict(zip(("name", "join", "types", "space", "keys", "hits", "leg",
              "taken"), c)) | ({"residual": True} if "residual" in c[0]
                               else {})
    for c in CASES], ids=[c[0] for c in CASES])
def test_unique_probe_takes_its_build_keys_from_the_page(case, monkeypatch):
    page, got, evs, d = _run(case, True, monkeypatch)
    _, want, evs_all, d_all = _run(case, False, monkeypatch)
    n_probe, taken = len(page.columns), case["taken"]
    # the same batches, lane for lane where live
    assert len(got) == len(want) == 1
    g, w = got[0], want[0]
    assert g.num_rows == w.num_rows and g.names == w.names
    live = _mask(g.live, g.num_rows)
    np.testing.assert_array_equal(live, _mask(w.live, w.num_rows))
    if case["join"] == "INNER":
        assert 0 < live.sum() < LANES
    else:  # LEFT: every probe row stays
        assert live.all() and g.num_rows == LANES
    for gc, wc in zip(g.columns, w.columns):
        assert (gc.type, gc.dictionary is None) == (wc.type,
                                                   wc.dictionary is None)
        gv = _mask(gc.valid, g.num_rows) & live
        np.testing.assert_array_equal(gv, _mask(wc.valid, w.num_rows) & live)
        np.testing.assert_array_equal(np.asarray(gc.data)[gv],
                                      np.asarray(wc.data)[gv])
    if case["join"] == "LEFT":
        # the null-extended lanes: live, and every build column null there
        unmatched = live & ~_mask(g.columns[n_probe].valid, g.num_rows)
        assert unmatched.sum() > 0
        for c in g.columns[n_probe:]:
            assert not _mask(c.valid, g.num_rows)[unmatched].any()
    # each taken key is the page's own key column: the array the probe was
    # handed on the wide leg and in the one-program LEFT join, the
    # compacted key on the compact leg
    build_keys = range(1, case["keys"] + 1)
    for k, bch in enumerate(build_keys):
        out, pch = g.columns[n_probe + bch], 1 + k
        if taken:
            assert out.data is g.columns[pch].data
            if case["leg"] != "compact":
                assert out.data is page.columns[pch].data
            else:
                assert g.num_rows < LANES
        else:
            assert out.data is not g.columns[pch].data
    if case["types"][1] in (BIGINT, DATE):  # the path the case is about
        assert evs[0]["path"] == ("dense" if case["space"] == "dense"
                                  else "hashed")
    # the event, the count and the counter say what was taken, and the
    # gather's bytes fell by the taken keys' bytes
    assert [e["keys_from_probe"] for e in evs] == [taken]
    assert [e["keys_from_probe"] for e in evs_all] == [0]
    key_bytes = sum(JX.lane_bytes([(page.columns[1 + k].data, None)])
                    for k in range(case["keys"])) if taken else 0
    assert evs_all[0]["gather_bytes"] - evs[0]["gather_bytes"] == key_bytes
    assert evs[0]["gather_lanes"] == evs_all[0]["gather_lanes"]
    assert (d.unique_gather_keys_from_probe,
            d_all.unique_gather_keys_from_probe) == (taken, 0)
    assert d.hot_loop_syncs == 0
    was = tm.JOIN_BUILD_KEYS_FROM_PROBE.value()
    tm.observe_sync(d)
    assert tm.JOIN_BUILD_KEYS_FROM_PROBE.value() - was == taken
    assert f"{taken} keys from the probe" in d.text()
