"""Device-side TPC-H generation must be bit-identical to the host generator.

The bench stages orders/lineitem via trino_tpu.connectors.tpch.
generate_table_device (columns born in accelerator memory, no host
transfer); correctness of every oracle-diffed query depends on both
generators producing the same values from the same splitmix64 arithmetic.
"""

import numpy as np
import pytest

from trino_tpu.connectors.tpch import TpchConnector, generate_table_device

SF = 0.01


def _host_table(conn, table, cols):
    batches = []
    for s in conn.get_splits(table, 4, 1):
        src = conn.create_page_source(s, cols)
        while not src.is_finished():
            b = src.get_next_batch()
            if b is not None:
                batches.append(b)
    from trino_tpu.spi.batch import ColumnBatch

    return ColumnBatch.concat(batches)


def _decode(col, n):
    data = np.asarray(col.data)[:n]
    if col.dictionary is not None:
        return col.dictionary[data]
    return data


@pytest.mark.parametrize("table", ["orders", "lineitem"])
def test_device_matches_host(table):
    conn = TpchConnector(scale_factor=SF)
    cols = conn.get_table_schema(table).column_names()
    dev = generate_table_device(conn, table, cols)
    assert dev is not None
    host = _host_table(TpchConnector(scale_factor=SF), table, cols)
    n = host.num_rows
    live = np.asarray(dev.live) if dev.live is not None else None
    if live is not None:
        assert int(live.sum()) == n
        assert live[:n].all()
    for name in cols:
        d = _decode(dev.column(name), n)
        h = _decode(host.column(name), n)
        np.testing.assert_array_equal(
            d, h, err_msg=f"{table}.{name} device/host mismatch")


def test_unsupported_table_returns_none():
    conn = TpchConnector(scale_factor=SF)
    assert generate_table_device(conn, "customer", ["c_custkey"]) is None
