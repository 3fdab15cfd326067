"""SF1 correctness net on the default JAX device: oracle-diff a TPC-H subset at
scale factor 1 (6M lineitem rows) — the scale where shape-bucket cliffs,
collective edges and masked aggregation paths actually engage (round-4
VERDICT item #8; run: python tools/sf1_check.py [q,q,...])."""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    queries = [int(q) for q in (sys.argv[1] if len(sys.argv) > 1
                                else "1,3,5,6,10,12,14,19").split(",")]
    sf = float(os.environ.get("SF", "1"))
    import jax

    from trino_tpu.caching.executable_cache import init_compile_cache
    from trino_tpu.connectors.catalog import default_catalog
    from trino_tpu.connectors.tpch_queries import QUERIES
    from trino_tpu.runner import Session, StandaloneQueryRunner
    from trino_tpu.testing.oracle import SqliteOracle, assert_same_rows

    init_compile_cache()
    d = jax.devices()[0]
    print(f"device: platform={d.platform} kind={d.device_kind!r} "
          f"count={len(jax.devices())}", flush=True)

    catalog = default_catalog(scale_factor=sf)
    runner = StandaloneQueryRunner(catalog, session=Session())
    oracle = SqliteOracle()
    conn = catalog.connector("tpch")
    t0 = time.time()
    for t in ["nation", "region", "supplier", "customer", "part", "partsupp",
              "orders", "lineitem"]:
        schema = conn.get_table_schema(t)
        cols = schema.column_names()
        batches = []
        for s in conn.get_splits(t, 4, 1):
            src = conn.create_page_source(s, cols)
            while not src.is_finished():
                b = src.get_next_batch()
                if b is not None:
                    batches.append(b)
        oracle.load_table(t, batches)
        print(f"loaded {t} into oracle ({time.time() - t0:.0f}s)", flush=True)
    for q in queries:
        sql = QUERIES[q]
        t0 = time.time()
        got = runner.execute(sql).rows()
        engine_s = time.time() - t0
        t0 = time.time()
        want = oracle.query(sql)
        oracle_s = time.time() - t0
        assert_same_rows(got, want, ordered="order by" in sql.lower())
        print(f"q{q:02d} OK rows={len(got)} engine={engine_s:.1f}s "
              f"sqlite={oracle_s:.1f}s", flush=True)
    print("SF1 ORACLE CHECK PASSED", flush=True)


if __name__ == "__main__":
    main()
