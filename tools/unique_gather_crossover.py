"""Where does compacting a unique-build probe stop being cheaper than
gathering wide?

Times, on the attached device, what join_exec.plan_unique_cap weighs for
program B of the unique-build probe (``join.unique_gather``): the wide leg
(every build column gathered at the probe batch's lanes) against the
compact leg (an index of the live lanes, then probe AND build columns
gathered at ``cap`` lanes), over a grid of match densities, for the two
build shapes of TPC-H Q3's joins; and beside them the pieces: one gather by
width and element size, candidate ways to index the live lanes, and the
blocking fetch of program A's count.

    chiprun --timeout 1500 -- python3 tools/unique_gather_crossover.py

Prints one JSON line per point; the same goes to
chiprun_out/unique_gather_crossover.json.  A CPU run (JAX_PLATFORMS=cpu
--lanes 14) rehearses the body and measures nothing worth keeping.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# live share of the probe lanes: Q3's four probes read 0.2-2.5 %
SHARES = (0.002, 0.01, 0.025, 0.06, 0.12, 0.24, 0.49)
# (name, log2 build rows, int64 columns, int32 columns): Q3's two builds
BUILDS = (("orders", 18, 3, 1), ("customer", 13, 1, 1))
PROBE_COLUMNS = "3,1"  # int64, int32 columns a lineitem probe carries


def timed(fn, reps: int) -> tuple[float, float]:
    """(first call's seconds: compile and run, median of ``reps`` more)."""
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    first = time.perf_counter() - t0
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        out.append(time.perf_counter() - t0)
    return first, statistics.median(out)


def index_methods(lanes: int, cap: int) -> dict:
    """Ways to list the first ``cap`` live lanes in lane order, dead lanes
    after them: each maps a bool[lanes] to an int32[cap] gather index."""
    import jax
    import jax.numpy as jnp

    iota = jnp.arange(lanes, dtype=jnp.int32)

    def argsort(ok):
        return jnp.argsort(~ok)[:cap].astype(jnp.int32)

    def packed_sort(ok):
        # one int32 operand: live lanes keep their index, dead ones sort
        # after every live one
        k = jnp.sort(jnp.where(ok, iota, iota + lanes))[:cap]
        return jnp.where(k >= lanes, k - lanes, k)

    def nonzero(ok):
        return jnp.nonzero(ok, size=cap, fill_value=0)[0].astype(jnp.int32)

    def cumsum_scatter(ok):
        pos = jnp.cumsum(ok.astype(jnp.int32)) - 1
        tgt = jnp.where(ok & (pos < cap), pos, cap)
        return jnp.zeros(cap, jnp.int32).at[tgt].set(iota, mode="drop")

    def cumsum_search(ok):
        cs = jnp.cumsum(ok.astype(jnp.int32))
        j = jnp.arange(1, cap + 1, dtype=jnp.int32)
        return jnp.clip(jnp.searchsorted(cs, j, side="left", method="scan"),
                        0, lanes - 1).astype(jnp.int32)

    def top_k(ok):
        k = jnp.where(ok, iota, iota + lanes)
        return -jax.lax.top_k(-k, cap)[0] % lanes

    return {"argsort": argsort, "packed_sort": packed_sort,
            "nonzero": nonzero, "cumsum_scatter": cumsum_scatter,
            "cumsum_search": cumsum_search, "top_k": top_k}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--lanes", type=int, default=20, help="log2 probe lanes")
    ap.add_argument("--sections", default="1234",
                    help="1 gathers, 2 indexes, 3 the count's fetch, 4 "
                         "program B wide against compact")
    ap.add_argument("--probe-columns", default=PROBE_COLUMNS,
                    help="int64,int32 probe columns of section 4 (Q3's "
                         "second probe carries the first join's output: "
                         "7,4 stands for its 18 words)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import trino_tpu  # noqa: F401  (x64 on)
    from trino_tpu.caching.executable_cache import init_compile_cache
    from trino_tpu.exec import join_exec as JX
    from trino_tpu.exec import kernels as K
    from trino_tpu.spi.types import BIGINT, INTEGER

    init_compile_cache()
    dev = jax.devices()[0]
    lanes = 1 << args.lanes
    points = []

    def emit(point: dict) -> None:
        point["platform"], point["device_kind"] = dev.platform, dev.device_kind
        points.append(point)
        print(json.dumps(point), flush=True)

    rng = np.random.default_rng(33)

    def device(a):
        return jax.block_until_ready(jnp.asarray(a))

    # 1. one gather, by width and element size
    for lg_table in sorted({b[1] for b in BUILDS}):
        if lg_table > args.lanes or "1" not in args.sections:
            continue
        for dtype in (np.int32, np.int64):
            table = device(rng.integers(0, 1 << 30, 1 << lg_table, dtype))
            for lg in sorted({13, 16, 18, args.lanes}):
                if lg > args.lanes:
                    continue
                idx = device(rng.integers(0, 1 << lg_table, 1 << lg,
                                          np.int64))
                fn = jax.jit(lambda t, i: t[i])
                first, run_s = timed(lambda: fn(table, idx), args.reps)
                emit({"program": "gather", "table_rows": 1 << lg_table,
                      "dtype": np.dtype(dtype).name, "lanes": 1 << lg,
                      "run_s": run_s, "first_s": first,
                      "ns_per_lane": run_s * 1e9 / (1 << lg)})

    # 2. the index of the live lanes, by method and cap
    for share, cap in ((0.002, lanes >> 7), (0.025, lanes >> 4),
                       (0.12, lanes >> 2)) if "2" in args.sections else ():
        ok = device(rng.random(lanes) < share)
        payload = device(rng.integers(0, 1 << 30, lanes, np.int32))
        want = np.flatnonzero(np.asarray(ok))[:cap]
        for name, method in index_methods(lanes, cap).items():
            if name in ("top_k", "cumsum_search") and cap > lanes >> 4:
                continue  # k log(lanes) gathers: not past a sixteenth
            def fn(o, p, m=method):
                order = m(o)
                return order, p[order]

            fn = jax.jit(fn)
            try:
                first, run_s = timed(lambda: fn(ok, payload), args.reps)
                got = np.asarray(fn(ok, payload)[0])[:len(want)]
                right = bool((got == want).all())
            except Exception as e:  # a method the compiler refuses
                emit({"program": "index", "method": name, "lanes": lanes,
                      "cap": cap, "error": repr(e)[:200]})
                continue
            emit({"program": "index", "method": name, "lanes": lanes,
                  "cap": cap, "live": int(len(want)), "right": right,
                  "run_s": run_s, "first_s": first})

    # 3. the blocking fetch of program A's count, after A
    if "3" in args.sections:
        build_keys = np.arange(1 << BUILDS[0][1], dtype=np.int64)
        table = JX.build_table([(build_keys, None)])
        pkeys = device(rng.integers(0, (1 << BUILDS[0][1]) * 40, lanes,
                                    np.int64))

        def ranges_only():
            return JX.run_unique_ranges_device(
                table, [(pkeys, None)], [None])[:2]

        def ranges_and_count():
            return np.int64(JX.run_unique_ranges_device(
                table, [(pkeys, None)], [None])[2].get())

        _, with_fetch = timed(ranges_and_count, args.reps)
        first, without = timed(ranges_only, args.reps)
        emit({"program": "join.dense_uranges" if table.dense is not None
              else "join.uranges", "lanes": lanes, "run_s": without,
              "first_s": first, "with_count_fetch_s": with_fetch,
              "count_fetch_s": with_fetch - without})

    # 4. program B itself, wide against compact, over match densities
    p64, p32 = (int(x) for x in args.probe_columns.split(","))
    probe_cols = ([(device(rng.integers(0, 1 << 40, lanes, np.int64)), None)
                   for _ in range(p64)]
                  + [(device(rng.integers(0, 1 << 20, lanes, np.int32)), None)
                     for _ in range(p32)])
    for bname, lg_build, b64, b32 in BUILDS:
        if "4" not in args.sections:
            continue
        nb = 1 << lg_build
        build_cols = ([(device(rng.integers(0, 1 << 40, nb, np.int64)), None)
                       for _ in range(b64)]
                      + [(device(rng.integers(0, 1 << 20, nb, np.int32)),
                          None) for _ in range(b32)])
        types = [BIGINT] * p64 + [INTEGER] * p32 + [BIGINT] * b64 \
            + [INTEGER] * b32
        dicts = [None] * len(types)
        btable = JX.build_table([(np.arange(nb, dtype=np.int64), None)])
        bid = device(rng.integers(0, nb, lanes, np.int64))

        def gather(ok, cap):
            res = JX.run_unique_gather(btable, ok, bid, cap, probe_cols,
                                       build_cols, types, dicts, None, False)
            return res[1], res[2]

        for share in SHARES:
            ok = device(rng.random(lanes) < share)
            count = int(np.asarray(ok).sum())
            first_w, wide_s = timed(lambda: gather(ok, None), args.reps)
            cap = K.bucket(count * JX.EST_HEADROOM)
            point = {"program": "join.unique_gather", "build": bname,
                     "build_rows": nb, "lanes": lanes, "share": share,
                     "live": count, "wide_s": wide_s, "wide_first_s": first_w}
            for c in sorted({K.bucket(count), cap}):
                if c >= lanes:
                    continue
                first_c, compact_s = timed(lambda: gather(ok, c), args.reps)
                point[f"compact_{c}_s"] = compact_s
                point[f"compact_{c}_first_s"] = first_c
            point["planned_cap"] = JX.plan_unique_cap(
                lanes, count * JX.EST_HEADROOM, JX.gather_words(probe_cols),
                JX.gather_words(build_cols))
            emit(point)

    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "unique_gather_crossover.json"), "w") as f:
        json.dump(points, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
