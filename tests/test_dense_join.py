"""Direct-address (dense) join tables: a unique build on integer keys whose
packed key range is small enough gets a dense[slot] lookup table, the key
packed mixed-radix (slot = sum of (k_i - lo_i) x stride_i) — probes are ONE
gather with no hashing, no binary search, no verify.  The table is admitted
by its bytes: at most DENSE_KEY_BYTES_FACTOR x the bytes the build's key
columns hold.  Every TPC-H PK/FK edge and Q5's (custkey, nationkey)
qualify; sparse or duplicate keys must fall back to the sorted-hash paths
with identical results, and with one key the programs are the ones they
were before composite keys."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.exec import join_exec as JX


def _keys(arr, valid=None):
    return [(np.asarray(arr), None if valid is None else np.asarray(valid))]


def test_dense_table_built_for_dense_unique_keys():
    t = JX.build_table(_keys(np.arange(1, 20001, dtype=np.int64)))
    assert t.dense is not None
    assert t.dense_lo == (1,)
    assert t.dense_ranges == (20000,)
    assert t.slots == 20000
    assert t.unique


def test_dense_rejected_for_sparse_range():
    k = np.arange(0, 20000, dtype=np.int64) * 1000  # 80 MB of table
    t = JX.build_table(_keys(k))
    assert t.dense is None
    assert t.slots == 0
    assert t.unique  # still unique: hash path serves it


def test_dense_rejected_for_duplicate_keys():
    k = np.concatenate([np.arange(40000), np.arange(40000)]).astype(np.int64)
    t = JX.build_table(_keys(k))
    assert t.dense is None
    assert not t.unique


def test_dense_probe_matches_hash_probe():
    rng = np.random.default_rng(7)
    build = np.arange(100, 66000, dtype=np.int64)
    probe = rng.integers(0, 70000, size=1 << 15).astype(np.int64)
    dense_t = JX.build_table(_keys(build))
    assert dense_t.dense is not None
    assert dense_t.unique
    ok, bid, cnt = JX.run_unique_ranges_device(dense_t, _keys(probe), [None])
    ok = np.asarray(ok)
    bid = np.asarray(bid)
    expected = (probe >= 100) & (probe < 66000)
    np.testing.assert_array_equal(ok, expected)
    np.testing.assert_array_equal(bid[ok], probe[expected] - 100)
    assert cnt.get() == int(expected.sum())


def test_dense_probe_respects_live_and_valid():
    build = np.arange(0, 70000, dtype=np.int64)
    t = JX.build_table(_keys(build))
    assert t.dense is not None
    probe = np.array([0, 1, 2, 3], dtype=np.int64)
    valid = np.array([True, False, True, True])
    live = np.array([True, True, False, True])
    assert t.unique
    ok, bid, cnt = JX.run_unique_ranges_device(
        t, _keys(probe, valid), [None], live=live)
    np.testing.assert_array_equal(np.asarray(ok),
                                  [True, False, False, True])
    assert cnt.get() == 2


# ------------------------------------------------- composite keys, packed

NB, LIVE_B, NP = 2048, 1500, 1 << 12
R1, R2 = 100, 40  # the first column spans -50..49, the second 0..39


def _composite(case, rng):
    """(build keys, build live, probe keys, probe live, remaps) for a
    two-column key: 1500 distinct live pairs over 2048 build lanes, with
    NULLs in either column; the dead lanes hold far-off keys that must
    neither match nor widen the range.  The probe draws the build's pairs
    and then bends 30 % of its lanes as ``case`` says."""
    pairs = rng.choice(R1 * R2, LIVE_B, replace=False)
    dead = NB - LIVE_B
    b1 = np.concatenate([pairs // R2 - 50, np.full(dead, 10 ** 9)])
    b2 = np.concatenate([pairs % R2, np.full(dead, -10 ** 9)])
    bv1, bv2 = np.ones(NB, bool), np.ones(NB, bool)
    bv1[rng.choice(LIVE_B, 60, replace=False)] = False
    bv2[rng.choice(LIVE_B, 60, replace=False)] = False
    blive = np.arange(NB) < LIVE_B
    pick = rng.integers(0, LIVE_B, NP)
    p1, p2 = b1[pick], b2[pick]
    bent = rng.random(NP) < 0.3
    pv1 = pv2 = plive = None
    remaps = [None, None]
    if case == "misses":  # pairs in range, most of them absent
        p1 = np.where(bent, rng.integers(-50, 50, NP), p1)
        p2 = np.where(bent, rng.integers(0, R2, NP), p2)
    elif case == "null_either_column":
        pv1, pv2 = rng.random(NP) > 0.15, rng.random(NP) > 0.15
    elif case == "out_of_range_in_one_column":
        # (k1, k2 + 40) packs to the slot of (k1 + 1, k2), (k1, k2 - 40) to
        # (k1 - 1, k2): only the range check of the one column tells
        p2 = np.where(bent, p2 + np.where(rng.random(NP) < 0.5, R2, -R2),
                      p2)
    elif case == "negative_values":  # below the first column's least
        p1 = np.where(bent, p1 - R1, p1)
    elif case == "live":
        plive = rng.random(NP) > 0.25
    else:
        assert case == "dictionary_miss"
        # the second column as dictionary codes: the probe's dictionary
        # holds 8 values more, remapped to -1 (absent from the build's)
        b2, p2 = b2.astype(np.int32), np.where(
            bent, rng.integers(R2, R2 + 8, NP), p2).astype(np.int32)
        b2[LIVE_B:] = 0
        remaps = [None, np.where(np.arange(R2 + 8) < R2,
                                 np.arange(R2 + 8), -1).astype(np.int32)]
    return ([(b1, bv1), (b2, bv2)], blive, [(p1, pv1), (p2, pv2)], plive,
            remaps)


def _both_paths(monkeypatch, bkeys, blive):
    """The same build as a direct-address table and as sorted hashes."""
    dense_t = JX.build_table(bkeys, live=blive)
    with monkeypatch.context() as m:
        m.setattr(JX, "DENSE_MAX_SLOTS", 0)
        hashed_t = JX.build_table(bkeys, live=blive)
    assert (dense_t.path, hashed_t.path) == ("dense", "hashed")
    assert dense_t.unique and hashed_t.unique
    return dense_t, hashed_t


CASES = ["misses", "null_either_column", "out_of_range_in_one_column",
         "negative_values", "live", "dictionary_miss"]


@pytest.mark.parametrize("case", CASES)
def test_composite_dense_probe_matches_hashed_probe(case, monkeypatch):
    rng = np.random.default_rng(42 + CASES.index(case))
    bkeys, blive, pkeys, plive, remaps = _composite(case, rng)
    dense_t, hashed_t = _both_paths(monkeypatch, bkeys, blive)
    # the dead lanes' keys widened nothing
    (lo1, lo2), (r1, r2) = dense_t.dense_lo, dense_t.dense_ranges
    assert -50 <= lo1 and lo1 + r1 <= 50 and 0 <= lo2 and lo2 + r2 <= R2
    got = []
    for t in (dense_t, hashed_t):
        ok, bid, cnt = JX.run_unique_ranges_device(t, pkeys, remaps,
                                                   live=plive)
        got.append((np.asarray(ok), np.asarray(bid), cnt.get()))
    (ok_d, bid_d, n_d), (ok_h, bid_h, n_h) = got
    np.testing.assert_array_equal(ok_d, ok_h)
    np.testing.assert_array_equal(bid_d[ok_d], bid_h[ok_h])
    assert n_d == n_h == int(ok_h.sum())
    assert 0 < n_h < NP
    # what the bent lanes must do: match nothing they do not hold
    b1, b2 = bkeys[0][0][bid_d[ok_d]], bkeys[1][0][bid_d[ok_d]]
    p1, p2 = pkeys[0][0][ok_d], pkeys[1][0][ok_d]
    if remaps[1] is not None:
        p2 = remaps[1][p2]
    np.testing.assert_array_equal((b1, b2), (p1, p2))


@pytest.mark.parametrize("kind", ["left", "full", "semi"])
def test_composite_unique_program_equal_on_both_paths(kind, monkeypatch):
    """The one-program unique probe (LEFT/SINGLE, FULL's build-matched
    flags, the semi-join mark) over a packed table and over sorted
    hashes."""
    rng = np.random.default_rng(7)
    bkeys, blive, pkeys, _, remaps = _composite("null_either_column", rng)
    plive = rng.random(NP) > 0.25
    payload = [(np.arange(NB, dtype=np.int64) * 7, rng.random(NB) > 0.1)]
    outs = []
    for t in _both_paths(monkeypatch, bkeys, blive):
        if kind == "semi":
            out = JX.run_unique(t, pkeys, remaps, [], [], [], [], None, False,
                                semi=(True, t.has_null_key, True),
                                live=plive)
        else:
            out = JX.run_unique(t, pkeys, remaps, [], payload, [], [], None,
                                kind == "full", live=plive)
        outs.append(jax.tree_util.tree_map(np.asarray, out))
    (cols_d, live_d, bm_d, mark_d), (cols_h, live_h, bm_h, mark_h) = outs
    np.testing.assert_array_equal(live_d, live_h)
    assert 0 < live_d.sum() < NP
    for (d_d, v_d), (d_h, v_h) in zip(cols_d, cols_h):
        np.testing.assert_array_equal(v_d, v_h)
        np.testing.assert_array_equal(d_d[v_d], d_h[v_h])
    if kind == "full":
        np.testing.assert_array_equal(bm_d, bm_h)
    if kind == "semi":
        np.testing.assert_array_equal(mark_d[0], mark_h[0])
        np.testing.assert_array_equal(mark_d[1], mark_h[1])


# ---------------------------------------------------- admission by bytes


@pytest.mark.parametrize("top,path", [(255, "dense"), (256, "hashed")])
def test_composite_admitted_by_its_bytes(top, path):
    """1024 lanes of two 8-byte keys hold 16 KiB: the table may take 16 x
    that, 65,536 slots of 4 bytes.  A range of 256 x 256 fills it; 256 x
    257 is one column of slots over, and stays hashed."""
    lane = np.arange(1024)
    k1 = (lane % 256).astype(np.int64)
    k2 = np.array([0, 85, 170, top])[lane // 256].astype(np.int64)
    t = JX.build_table([(k1, None), (k2, None)])
    assert t.unique
    assert t.path == path
    assert t.slots == (256 * 256 if path == "dense" else 0)


# ------------------------------------------- one key: the same programs

N = 4096
LO, SIZE = 7, 5000
I64 = jax.ShapeDtypeStruct((N,), jnp.int64)
MASK = jax.ShapeDtypeStruct((N,), jnp.bool_)
TABLE = jax.ShapeDtypeStruct((SIZE,), jnp.int32)
DENSE = ((SIZE,), (LO,))

# sha256 of each single-key program's lowered text at 4096 lanes (a table
# of 5000 slots from 7), as it was before keys were packed: with one key
# the slot is key - lo, so TPC-H Q3's join programs may not move by a byte.
# A change meant to move one sets its new digest.
SINGLE_KEY_SHA256 = {
    "build": "540edd8b0f1b9feecbe0c78fea16d27dfb36e1e6126d92548168cee3e228cbf5",
    "dense_build":
        "1f6d7d210281989bc3e3d6ffc05517a8df25d55cd83333a63d72a9a65ef62b69",
    "dense_uranges":
        "851f1634bc926381e285a983652990b2a4610d61dc81195f07ca433b0e51512e",
    "dense_uranges_remap":
        "a4dd4b0f38a7891bbc21027f2b9092cc354656f1a8356b97b7bddb705de26471",
    "unique_left":
        "b106d41e06dd2a9aa4ef8e62e0ca19c041987b4405ee67eed196c0f6c2003e64",
    "unique_semi":
        "6f3aa3e6326afa61b7d257ff6532face64eb0ea6488ce1e480a702cf565107a2",
}


def _lowered(name):
    if name == "build":
        return JX._build_fn(1, (True,), True, True).lower(I64, MASK, MASK)
    if name == "dense_build":
        return JX._dense_build_fn((SIZE,), (LO,), (True,), True).lower(
            I64, MASK, MASK)
    if name == "dense_uranges":
        return JX._dense_uranges_fn((SIZE,), (LO,), (True,), (False,),
                                    True).lower(TABLE, I64, MASK, MASK)
    if name == "dense_uranges_remap":
        return JX._dense_uranges_fn((SIZE,), (LO,), (False,), (True,),
                                    False).lower(
            TABLE, jax.ShapeDtypeStruct((N,), jnp.int32),
            jax.ShapeDtypeStruct((64,), jnp.int32))
    if name == "unique_left":
        return JX._make_unique_fn(
            1, (True,), (False,), [], [], 0, 1, (), (True,), None, False,
            None, True, DENSE).lower(TABLE, I64, I64, MASK, I64, MASK, I64,
                                     MASK)
    assert name == "unique_semi"
    return JX._make_unique_fn(
        1, (True,), (False,), [], [], 0, 0, (), (), None, False,
        (True, False, True), False, DENSE).lower(TABLE, I64, I64, MASK, I64)


@pytest.mark.parametrize("name", sorted(SINGLE_KEY_SHA256))
def test_single_key_dense_programs_are_the_programs_they_were(name):
    text = _lowered(name).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == SINGLE_KEY_SHA256[name]
