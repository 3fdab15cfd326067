"""Hash-vs-sort equivalence for the TRINO_TPU_HASH_IMPL paths.

The open-addressing kernels (ops/pallas_kernels.hash_insert/hash_probe) run
here in interpret mode on the CPU test mesh — the only mode they run in: the
v5e compiler refuses both (tests/test_tpu_compile.py).  Every test drives the same inputs through both the
lexsort implementation and the Pallas hash implementation and asserts the
operator-level contracts agree: same group partitions, same join probe
ranges, bit-identical query output.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from trino_tpu.exec import join_exec as JX
from trino_tpu.exec import kernels as K
from trino_tpu.exec import syncguard as SG
from trino_tpu.ops import pallas_kernels as PK

@pytest.fixture(autouse=True)
def _fresh_state(monkeypatch):
    # isolate the impl knob per test
    monkeypatch.delenv("TRINO_TPU_HASH_IMPL", raising=False)
    monkeypatch.delenv("TRINO_TPU_HASH_INTERPRET", raising=False)


def _partition_map(n, perm, gid, num_groups):
    """row -> group id (or None for dead rows), as assigned by one impl."""
    out = [None] * n
    p = np.asarray(perm)
    g = np.asarray(gid)
    for i in range(n):
        out[p[i]] = int(g[i]) if g[i] < num_groups else None
    return out

def assert_same_partition(keys, live, n):
    """group_ids and hash_group_ids agree up to group-id relabeling."""
    p1, g1, ng1 = K.group_ids(keys, live)
    p2, g2, ng2 = K.hash_group_ids(keys, live)
    assert ng1 == ng2
    a = _partition_map(n, p1, g1, ng1)
    b = _partition_map(n, p2, g2, ng2)
    fwd = {}
    for x, y in zip(a, b):
        assert (x is None) == (y is None)
        if x is None:
            continue
        assert fwd.setdefault(x, y) == y, "rows co-grouped by one impl split"
    assert len(fwd) == ng1
    # gid contract holds for the hash impl too: nondecreasing, dead rows last
    g2 = np.asarray(g2)
    assert (np.diff(g2) >= 0).all()
    return ng1


# ---------------------------------------------------------------------------
# kernel level


def test_insert_probe_roundtrip_with_dead_rows():
    rng = np.random.default_rng(0)
    n, S = 3000, 8192
    key = rng.integers(0, 500, n).astype(np.uint32)
    planes = jnp.asarray(key)[None, :]
    h32 = jnp.asarray(key * np.uint32(2654435761), jnp.uint32)
    live = jnp.asarray(rng.random(n) < 0.9)
    gid, count, table, sgid = PK.hash_insert(
        planes, h32, live, S, interpret=True)
    gid, c = np.asarray(gid), int(count)
    lv = np.asarray(live)
    assert c == len(np.unique(key[lv]))
    assert (gid[~lv] == S).all()
    # same key -> same gid; distinct keys -> distinct gids; ids dense
    seen = {}
    for k, g in zip(key[lv], gid[lv]):
        assert seen.setdefault(int(k), int(g)) == int(g)
    assert sorted(seen.values()) == list(range(c))
    # probe: present keys hit their gid, absent keys miss with -1
    pk = np.concatenate([key[:100], np.arange(1000, 1100).astype(np.uint32)])
    ph = jnp.asarray(pk * np.uint32(2654435761), jnp.uint32)
    pg = np.asarray(PK.hash_probe(table, sgid, jnp.asarray(pk)[None, :], ph,
                                  interpret=True))
    for k, g in zip(pk[:100], pg[:100]):
        if int(k) in seen:
            assert g == seen[int(k)]
    assert (pg[100:] == -1).all()


def test_insert_probe_collision_heavy_same_slots():
    # adversarial hash: every key lands in one of FOUR slots, so almost all
    # placements resolve by in-kernel linear probing, not by the hash
    n, S = 2048, 4096
    key = (np.arange(n) % 37).astype(np.uint32)
    h32 = jnp.asarray(key % 4, jnp.uint32)
    planes = jnp.asarray(key)[None, :]
    gid, count, table, sgid = PK.hash_insert(
        planes, h32, None, S, interpret=True)
    gid, c = np.asarray(gid), int(count)
    assert c == 37
    seen = {}
    for k, g in zip(key, gid):
        assert seen.setdefault(int(k), int(g)) == int(g)
    assert sorted(seen.values()) == list(range(37))
    pg = np.asarray(PK.hash_probe(table, sgid, planes, h32, interpret=True))
    assert (pg == gid).all()


# ---------------------------------------------------------------------------
# grouping equivalence


def test_group_ids_equivalence_nullable_ints():
    rng = np.random.default_rng(1)
    n = 4096
    keys = [(jnp.asarray(rng.integers(-40, 40, n).astype(np.int64)),
             jnp.asarray(rng.random(n) < 0.85))]
    live = jnp.asarray(rng.random(n) < 0.9)
    assert_same_partition(keys, live, n)


def test_group_ids_equivalence_float_specials():
    specials = np.array([np.nan, -np.nan, 0.0, -0.0, np.inf, -np.inf,
                         1.5, -1.5, 1e300, 1e-300])
    rng = np.random.default_rng(2)
    n = 2000
    k1 = jnp.asarray(specials[rng.integers(0, len(specials), n)])
    k2 = jnp.asarray(rng.integers(0, 3, n).astype(np.int64))
    ng = assert_same_partition([(k1, None), (k2, None)], None, n)
    # -0 == 0 and NaN is ONE group under SQL grouping: 8 values x 3
    assert ng == 24


def test_group_ids_equivalence_all_duplicates_and_bool():
    n = 1024
    keys = [(jnp.zeros(n, jnp.int64), None)]
    assert assert_same_partition(keys, None, n) == 1
    rng = np.random.default_rng(3)
    keys = [(jnp.asarray(rng.random(n) < 0.5),
             jnp.asarray(rng.random(n) < 0.7))]
    assert assert_same_partition(keys, None, n) == 3  # True / False / NULL


def test_hash_group_ids_empty_input():
    perm, gid, ng = K.hash_group_ids(
        [(jnp.zeros(0, jnp.int64), None)], None)
    assert ng == 0 and perm.shape == (0,) and gid.shape == (0,)


def test_group_ids_auto_routing(monkeypatch):
    n = 512
    keys = [(jnp.asarray(np.arange(n) % 9, ), None)]
    calls = []
    orig = K.hash_group_ids

    def spy(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(K, "hash_group_ids", spy)
    monkeypatch.setenv("TRINO_TPU_HASH_IMPL", "sort")
    K.group_ids_auto(keys, None)
    assert not calls
    monkeypatch.setenv("TRINO_TPU_HASH_IMPL", "pallas")
    _, _, ng = K.group_ids_auto(keys, None)
    assert calls and ng == 9


# ---------------------------------------------------------------------------
# join probe ranges: value-identical (lo, counts, total) between impls


def _ranges(impl, monkeypatch, bk, bv, blive, pk, pv, plive):
    monkeypatch.setenv("TRINO_TPU_HASH_IMPL", impl)
    t = JX.build_table(
        [(jnp.asarray(bk), None if bv is None else jnp.asarray(bv))],
        live=None if blive is None else jnp.asarray(blive),
        num_rows=len(bk))
    assert (t.hash_idx is not None) == (impl == "pallas" and len(bk) > 0)
    lo, counts, total = JX.probe_ranges_device(
        t, [(jnp.asarray(pk), None if pv is None else jnp.asarray(pv))],
        [None], None if plive is None else jnp.asarray(plive))
    return np.asarray(lo), np.asarray(counts), int(total.get())


def test_join_ranges_equivalence(monkeypatch):
    rng = np.random.default_rng(7)
    nb, npr = 4000, 6000
    bk = rng.integers(0, 500, nb).astype(np.int64)
    bv = rng.random(nb) < 0.9
    blive = rng.random(nb) < 0.95
    pk = rng.integers(0, 700, npr).astype(np.int64)  # some keys miss
    pv = rng.random(npr) < 0.9
    plive = rng.random(npr) < 0.95
    lo1, c1, t1 = _ranges("sort", monkeypatch, bk, bv, blive, pk, pv, plive)
    lo2, c2, t2 = _ranges("pallas", monkeypatch, bk, bv, blive, pk, pv, plive)
    assert t1 == t2
    assert (c1 == c2).all()
    m = c1 > 0
    assert (lo1[m] == lo2[m]).all()  # lo only meaningful where rows match


def test_join_ranges_empty_build_side(monkeypatch):
    empty = np.empty(0, np.int64)
    pk = np.arange(50, dtype=np.int64)
    lo1, c1, t1 = _ranges("sort", monkeypatch, empty, None, None,
                          pk, None, None)
    lo2, c2, t2 = _ranges("pallas", monkeypatch, empty, None, None,
                          pk, None, None)
    assert t1 == t2 == 0
    assert (c1 == 0).all() and (c2 == 0).all()


def test_join_hash_probe_zero_hot_loop_syncs(monkeypatch):
    # steady state: index build + probe ranges never block on the device
    monkeypatch.setenv("TRINO_TPU_HASH_IMPL", "pallas")
    rng = np.random.default_rng(9)
    bk = rng.integers(0, 300, 2000).astype(np.int64)
    t = JX.build_table([(jnp.asarray(bk), None)], num_rows=len(bk))
    assert t.hash_idx is not None
    pk = jnp.asarray(rng.integers(0, 400, 3000).astype(np.int64))
    before = SG.snapshot()
    with SG.hot_region():
        lo, counts, total = JX.probe_ranges_device(t, [(pk, None)], [None])
    delta = SG.take_delta(before)
    assert delta.hot_loop_syncs == 0
    assert delta.blocking_syncs == 0
    assert int(total.get()) > 0  # the one sanctioned fetch, outside the loop


# ---------------------------------------------------------------------------
# operator level: bit-identical query output under both impls


def _query_rows(monkeypatch, impl, sql, runner):
    monkeypatch.setenv("TRINO_TPU_HASH_IMPL", impl)
    return runner.execute(sql).rows()


@pytest.fixture(scope="module")
def tpch_runner():
    from trino_tpu.connectors.catalog import default_catalog
    from trino_tpu.runner import StandaloneQueryRunner

    return StandaloneQueryRunner(default_catalog(scale_factor=0.01))


def test_group_by_query_bit_identical(monkeypatch, tpch_runner):
    # l_partkey is numeric + high-NDV: bypasses the small-codes fast path,
    # so the aggregation genuinely routes through group_ids_auto
    sql = ("select l_partkey, count(*), sum(l_quantity), min(l_extendedprice)"
           " from lineitem group by l_partkey order by l_partkey")
    sort_rows = _query_rows(monkeypatch, "sort", sql, tpch_runner)
    hash_rows = _query_rows(monkeypatch, "pallas", sql, tpch_runner)
    assert sort_rows == hash_rows
    assert len(sort_rows) > 100


def test_join_query_bit_identical(monkeypatch, tpch_runner):
    # duplicate-keyed build side keeps the join off the unique fast path
    sql = ("select o_orderpriority, count(*) from orders, lineitem "
           "where o_orderkey = l_orderkey and l_quantity < 10 "
           "group by o_orderpriority order by o_orderpriority")
    sort_rows = _query_rows(monkeypatch, "sort", sql, tpch_runner)
    hash_rows = _query_rows(monkeypatch, "pallas", sql, tpch_runner)
    assert sort_rows == hash_rows
    assert len(sort_rows) == 5


# ---------------------------------------------------------------------------
# static partial-agg reuse of the same kernels


def test_static_agg_hash_route_equivalence(monkeypatch):
    from trino_tpu.parallel.static_agg import AggSpec, static_grouped_agg

    rng = np.random.default_rng(11)
    n, cap = 3000, 1024
    k1 = jnp.asarray(rng.integers(0, 200, n).astype(np.int64))
    v1 = jnp.asarray(rng.random(n) < 0.9)
    k2 = jnp.asarray(rng.integers(0, 3, n).astype(np.int64))
    data = jnp.asarray(rng.standard_normal(n))
    dval = jnp.asarray(rng.random(n) < 0.85)
    mask = jnp.asarray(rng.random(n) < 0.9)
    aggs = [(AggSpec("sum", jnp.float64), data, dval),
            (AggSpec("count_star", jnp.int64), None, None),
            (AggSpec("min", jnp.float64), data, dval)]

    def run(impl):
        monkeypatch.setenv("TRINO_TPU_HASH_IMPL", impl)
        r = static_grouped_agg([k1, k2], [v1, None], aggs, cap,
                               row_mask=mask)
        ng = int(r.num_groups)
        assert ng <= cap  # stay out of the overflow regime for comparison
        rows = []
        for i in range(ng):
            rows.append((
                int(r.keys[0][i]), bool(r.key_valids[0][i]),
                int(r.keys[1][i]),
                round(float(r.values[0][i]), 9),
                bool(r.value_valids[0][i]),
                int(r.values[1][i]),
                round(float(r.values[2][i]), 9),
                bool(r.value_valids[2][i])))
        return ng, sorted(rows)

    ng1, rows1 = run("sort")
    ng2, rows2 = run("pallas")
    # slot ORDER differs (first occurrence vs key order); content must not
    assert ng1 == ng2
    assert rows1 == rows2


# ---------------------------------------------------------------------------
# bench-scale leg, excluded from tier-1 by the slow marker


@pytest.mark.slow
def test_group_ids_equivalence_1m_ndv():
    rng = np.random.default_rng(42)
    n = 2_000_000
    keys = [(jnp.asarray(rng.integers(0, 1_500_000, n).astype(np.int64)),
             None)]
    p1, g1, ng1 = K.group_ids(keys, None)
    p2, g2, ng2 = K.hash_group_ids(keys, None)
    assert ng1 == ng2
    # spot-check co-grouping on a sample instead of the O(n) python loop
    a = np.empty(n, np.int64)
    b = np.empty(n, np.int64)
    a[np.asarray(p1)] = np.asarray(g1)
    b[np.asarray(p2)] = np.asarray(g2)
    idx = rng.integers(0, n, 50_000)
    k = np.asarray(keys[0][0])
    for i, j in zip(idx[:-1], idx[1:]):
        assert (a[i] == a[j]) == (k[i] == k[j])
        assert (b[i] == b[j]) == (k[i] == k[j])
