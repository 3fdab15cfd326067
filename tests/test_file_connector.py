"""File connector (persistent columnar storage) + native C++ page-file IO
(native/pagefile.cpp via ctypes; reference role: plugin/trino-hive native
readers + buffer/PageSerializer)."""

import os

import numpy as np
import pytest

from trino_tpu import native
from trino_tpu.connectors.catalog import default_catalog
from trino_tpu.execution.distributed_runner import DistributedQueryRunner
from trino_tpu.runner import Session, StandaloneQueryRunner


@pytest.fixture()
def runner(tmp_path):
    return StandaloneQueryRunner(
        default_catalog(scale_factor=0.01, file_root=str(tmp_path)),
        session=Session(default_catalog="file"))


def test_native_library_builds():
    lib = native.load()
    assert lib is not None, "C++ page-file library failed to build"
    assert os.path.exists(native.lib_path())


def test_native_build_failure_is_an_error(tmp_path, monkeypatch):
    # the library is built from source on first use; a source that does not
    # compile must fail the caller, not hand back None
    bad = tmp_path / "pagefile.cpp"
    bad.write_text("this is not C++\n")
    monkeypatch.setattr(native, "_SRC", str(bad))
    monkeypatch.setattr(native, "_SO", str(tmp_path / "libpagefile.so"))
    monkeypatch.setattr(native, "_lib", None)
    with pytest.raises(RuntimeError, match="failed to build"):
        native.load()
    assert not os.path.exists(tmp_path / "libpagefile.so")


def test_native_bitmap_roundtrip():
    import ctypes

    lib = native.load()
    assert lib is not None
    bools = np.array([1, 0, 1, 1, 0, 0, 0, 1, 1, 0, 1], np.uint8)
    packed = np.zeros((len(bools) + 7) // 8, np.uint8)
    lib.ttp_pack_bits(bools.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                      len(bools),
                      packed.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    assert bytes(packed) == np.packbits(bools.astype(bool)).tobytes()
    out = np.zeros(len(bools), np.uint8)
    lib.ttp_unpack_bits(packed.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                        len(bools),
                        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    assert (out == bools).all()


def test_native_zlib_roundtrip():
    import ctypes
    import zlib

    lib = native.load()
    assert lib is not None
    payload = os.urandom(1000) + b"\x00" * 50_000
    src = (ctypes.c_uint8 * len(payload)).from_buffer_copy(payload)
    cap = lib.ttp_deflate_bound(len(payload))
    dst = (ctypes.c_uint8 * cap)()
    n = lib.ttp_deflate(src, len(payload), dst, cap, 1)
    assert 0 < n < len(payload)
    assert zlib.decompress(bytes(dst[:n])) == payload
    back = (ctypes.c_uint8 * len(payload))()
    m = lib.ttp_inflate(dst, n, back, len(payload))
    assert m == len(payload) and bytes(back) == payload


def test_file_table_lifecycle(runner, tmp_path):
    runner.execute("create table ft as select n_nationkey, n_name, n_regionkey "
                   "from tpch.nation")
    assert os.path.exists(tmp_path / "ft" / "schema.json")
    rows = runner.execute(
        "select n_regionkey, count(*) from ft group by n_regionkey").rows()
    assert sorted(rows) == [(i, 5) for i in range(5)]
    # insert appends a second page file
    runner.execute("insert into ft select n_nationkey, n_name, n_regionkey "
                   "from tpch.nation where n_regionkey = 0")
    assert runner.execute("select count(*) from ft").rows() == [(30,)]
    # strings / NULL semantics survive the disk roundtrip
    assert runner.execute(
        "select n_name from ft where n_nationkey = 3 limit 1").rows() == [("CANADA",)]
    runner.execute("drop table ft")
    assert runner.execute("show tables").rows() == []


def test_file_table_survives_new_catalog(tmp_path):
    root = str(tmp_path)
    a = StandaloneQueryRunner(default_catalog(0.01, file_root=root),
                              session=Session(default_catalog="file"))
    a.execute("create table keep as select r_regionkey, r_name from tpch.region")
    # a brand-new catalog over the same root sees the persisted table
    b = StandaloneQueryRunner(default_catalog(0.01, file_root=root),
                              session=Session(default_catalog="file"))
    assert sorted(b.execute("select r_name from keep").rows()) == [
        ("AFRICA",), ("AMERICA",), ("ASIA",), ("EUROPE",), ("MIDDLE EAST",)]


def test_file_scan_distributed(tmp_path):
    catalog = default_catalog(0.01, file_root=str(tmp_path))
    d = DistributedQueryRunner(
        catalog, worker_count=2,
        session=Session(node_count=2, default_catalog="file"))
    d.execute("create table big as select o_orderkey, o_totalprice "
              "from tpch.orders")
    rows = d.execute(
        "select count(*), sum(o_totalprice) from big").rows()
    assert rows[0][0] == 15000


def test_delete_on_file_table(runner):
    runner.execute("create table fd as select n_nationkey, n_regionkey "
                   "from tpch.nation")
    assert runner.execute(
        "delete from fd where n_regionkey < 2").rows() == [(10,)]
    assert runner.execute("select count(*) from fd").rows() == [(15,)]
