"""Where does reading dead lanes stop being cheaper than sorting them away?

Times, on the attached device, the two programs the aggregation operator
chooses between for a sparsely-live input (operators.
_masked_reads_dead_lanes_cheaper): ``kernels.compact`` (count sync, stable
sort of the lanes, a gather per column) and ``kernels.small_agg`` over the
uncompacted lanes, on a grid of lanes x group space x reductions.

    chiprun --timeout 1500 -- python3 tools/compaction_crossover.py

Prints one JSON line per point and a fit at the end; the same goes to
chiprun_out/compaction_crossover.json.  A CPU run (JAX_PLATFORMS=cpu
--lanes-cap 18) rehearses the body and measures nothing worth keeping.
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

LIVE_SHARE = 0.02  # Q6's filter leaves 1.9 % of a task's lanes live
COMPACT_LANES = (16, 18, 20, 22, 25)  # log2
COMPACT_COLUMNS = 2
# (log2 lanes, group space, reductions)
MASKED_GRID = (
    (20, 6, 11), (20, 128, 4), (20, 128, 20),
    (22, 1, 1), (22, 1, 4), (22, 6, 11), (22, 16, 8), (22, 128, 1),
    (22, 128, 2), (22, 128, 4), (22, 128, 11),
    (25, 1, 1), (25, 1, 3), (25, 6, 4),
)


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


def fit(points: list) -> dict:
    """The unit costs operators._masked_reads_dead_lanes_cheaper weighs, per
    lane count: compaction (sync apart) a lane; the masked kernel a lane and
    reduction at one group; and a lane, group and reduction from the two
    points of most reductions at the widest group space."""
    out: dict = {}
    masked = [p for p in points if p["program"] == "kernels.small_agg"]
    for c in (p for p in points if p["program"] == "kernels.compact"):
        lanes = c["lanes"]
        row = {"count_sync_s": c["count_sync_s"],
               "compact_s_per_lane": c["run_s"] / lanes}
        same = [m for m in masked if m["lanes"] == lanes]
        one = [m for m in same if m["groups"] == 1]
        if one:
            m = max(one, key=lambda m: m["reductions"])
            row["masked_s_per_lane_reduction"] = (
                m["run_s"] / (lanes * m["reductions"]))
        wide = sorted((m for m in same if m["groups"] == max(
            (x["groups"] for x in same), default=0)),
            key=lambda m: m["reductions"])
        if len(wide) >= 2:
            a, b = wide[-2], wide[-1]
            row["masked_s_per_lane_group_reduction"] = (
                (b["run_s"] - a["run_s"]) / (lanes * b["groups"] * (
                    b["reductions"] - a["reductions"])))
        out[str(lanes)] = row
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--lanes-cap", type=int, default=25,
                    help="skip points over 2^this many lanes")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import trino_tpu  # noqa: F401  (x64 on)
    from trino_tpu.caching.executable_cache import init_compile_cache
    from trino_tpu.exec import kernels as K
    from trino_tpu.exec import syncguard as SG
    from trino_tpu.spi.batch import Column, ColumnBatch
    from trino_tpu.spi.types import BIGINT, VARCHAR

    init_compile_cache()
    dev = jax.devices()[0]
    points = []

    def emit(point: dict) -> None:
        point["platform"], point["device_kind"] = dev.platform, dev.device_kind
        points.append(point)
        print(json.dumps(point), flush=True)

    def data(lg: int, cols: int, seed: int = 0):
        key = jax.random.PRNGKey(seed)
        ks = jax.random.split(key, cols + 2)
        n = 1 << lg
        live = jax.random.uniform(ks[0], (n,)) < LIVE_SHARE
        codes = jax.random.randint(ks[1], (n,), 0, 1 << 30, jnp.int32)
        vals = [jax.random.randint(k, (n,), -1000, 1000, jnp.int64)
                for k in ks[2:]]
        return jax.block_until_ready((live, codes, vals))

    for lg in COMPACT_LANES:
        if lg > args.lanes_cap:
            continue
        live, _, vals = data(lg, COMPACT_COLUMNS)
        batch = ColumnBatch([f"c{i}" for i in range(len(vals))],
                            [Column(BIGINT, v) for v in vals], live)
        _, sync_s = timed(lambda: np.asarray(
            SG.fetch(jnp.sum(live), "bench.compact-count")), args.reps)
        count = int(jnp.sum(live))
        first, run_s = timed(lambda: [
            c.data for c in K.compact_device_batch(batch, count).columns],
            args.reps)
        emit({"program": "kernels.compact", "lanes": 1 << lg,
              "columns": COMPACT_COLUMNS, "live": count,
              "count_sync_s": sync_s, "run_s": run_s, "first_s": first,
              "ns_per_lane": (run_s + sync_s) * 1e9 / (1 << lg)})
        del batch, live, vals

    for lg in sorted({g[0] for g in MASKED_GRID}):
        if lg > args.lanes_cap:
            continue
        grid = [g[1:] for g in MASKED_GRID if g[0] == lg]
        live, codes, vals = data(lg, max(reds for _, reds in grid))
        for groups, reds in grid:
            dictionary = np.array([f"g{i:03d}" for i in range(groups)],
                                  object)
            keys = ([Column(VARCHAR, codes % groups, None, dictionary)]
                    if groups > 1 else [])
            specs = [("sum", v, None, np.int64, False) for v in vals[:reds]]
            first, run_s = timed(lambda: K.small_grouped_aggregate(
                keys, live, specs)[0], args.reps)
            emit({"program": "kernels.small_agg", "lanes": 1 << lg,
                  "groups": groups, "reductions": reds, "run_s": run_s,
                  "first_s": first, "ps_per_lane_group_reduction":
                  run_s * 1e12 / ((1 << lg) * groups * reds)})
        del live, codes, vals

    emit({"program": "fit", **fit(points)})
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "compaction_crossover.json"), "w") as f:
        json.dump(points, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
