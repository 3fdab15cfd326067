"""Direct-address (dense) join tables: a unique single-int-key build whose
key range is dense gets a dense[key - lo] lookup table — probes are ONE
gather with no hashing, no binary search, no verify.  Every TPC-H PK/FK
edge qualifies; sparse or duplicate keys must fall back to the sorted-hash
paths with identical results."""

import numpy as np

from trino_tpu.exec import join_exec as JX


def _keys(arr, valid=None):
    return [(np.asarray(arr), None if valid is None else np.asarray(valid))]


def test_dense_table_built_for_dense_unique_keys():
    t = JX.build_table(_keys(np.arange(1, 20001, dtype=np.int64)))
    assert t.dense is not None
    assert t.dense_lo == 1
    assert t.unique


def test_dense_rejected_for_sparse_range():
    k = np.arange(0, 20000, dtype=np.int64) * 1000  # range >> 4x rows
    t = JX.build_table(_keys(k))
    assert t.dense is None
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
