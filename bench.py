"""Benchmark: TPC-H Q1 + Q3 through the FULL engine on the available device.

Unlike a kernel microbench, this drives parse -> plan -> optimize -> operators
(the same path `StandaloneQueryRunner` gives users), so it moves when the
engine regresses.  Data is staged into the memory connector first (CTAS via
the engine) so the timed region measures query execution over host-resident
tables — the moral equivalent of the reference's benchto harness reading
warmed Hive tables (testing/trino-benchto-benchmarks/.../tpch.yaml).

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline"}:
- value   = scanned input rows / median wall-clock, summed over Q1+Q3
- vs_baseline = speedup over the SAME engine running the SAME queries on an
  8-worker CPU DistributedQueryRunner in a subprocess (the self-measured CPU
  reference BASELINE.md mandates; the reference repo publishes no absolute
  numbers).
A bytes/s sanity line goes to stderr: scanned-bytes/s must stay below HBM
peak (~0.8 TB/s on v5e) or the measurement is rejected as bogus.

Env knobs: BENCH_SF (default 2; BENCH_SF=10 is the SF10 utilization profile
leg — per-query rows/s, rows/s/chip and GB/s land in the JSON for
BASELINE.md's honest-baseline tables), BENCH_ITERS (default 3),
BENCH_BASELINE_WORKERS (default 8), BENCH_SKIP_BASELINE=1 to skip.
The run names the device it measured on (platform, device_kind, device
count).  No accelerator and no explicit JAX_PLATFORMS=cpu is an error, never
a fallback; harnesses that spawn worker or coordinator processes force those
children to JAX_PLATFORMS=cpu (a chip belongs to one process) and say so in
their output.

Subcommands: ``--scan`` (ingest microbench), ``--ndv [1e3,1e4,...]``
(TRINO_TPU_HASH_IMPL hash-vs-sort NDV-ladder bake-off, see run_ndv_bench),
``--qps`` (two-tenant weighted-fair sustained-load harness + OOM drill,
see run_qps_bench; BENCH_QPS_DURATION/BENCH_QPS_SF/BENCH_QPS_CLIENTS),
``--warm`` (cache-plane cold/warm/warm-after-mutation ladder, see
run_warm_bench; BENCH_WARM_SF/BENCH_WARM_REPS), ``--adaptive`` (adaptive
execution on/off A/B over a skewed-key TPC-H variant and a mis-estimated
broadcast plan, see run_adaptive_bench; BENCH_ADAPTIVE_SF/
BENCH_ADAPTIVE_WORKERS), ``--hbo`` (history-based optimization second-run
leg over the same mis-estimated broadcast plan, see run_hbo_bench; same
env knobs as --adaptive).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

# Published HBM peak per chip, keyed by device_kind as JAX reports it; a
# device that is not in the table is an error, not a default.
# v5e: 819 GB/s (Google Cloud documentation, "TPU v5e").
HBM_PEAK_BYTES_PER_SEC = {"TPU v5 lite": 819e9}

Q1 = """
select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
       sum(l_extendedprice) as sum_base_price,
       sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
       sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
       avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price,
       avg(l_discount) as avg_disc, count(*) as count_order
from lineitem where l_shipdate <= date '1998-12-01' - interval '90' day
group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus
"""

Q3 = """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
       o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate limit 10
"""

QUERIES = {"q1": Q1, "q3": Q3}
TABLES = {"q1": ["lineitem"], "q3": ["customer", "orders", "lineitem"]}


def _device() -> dict:
    """The device this process measures on, as JAX reports it, after placing
    the compile cache by the one rule (executable_cache.init_compile_cache).
    A CPU backend is an error unless it was asked for by name: a measurement
    path that finds no chip fails, it does not carry on under the same
    metric names."""
    import jax

    from trino_tpu.caching.executable_cache import init_compile_cache

    init_compile_cache()
    d = jax.devices()[0]
    if d.platform == "cpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            "bench: JAX found no accelerator and JAX_PLATFORMS=cpu was not "
            "set explicitly; refusing to measure on a fallback backend")
    return {"platform": d.platform, "device_kind": d.device_kind,
            "device_count": len(jax.devices())}


def _stage_memory_tables(sf: float):
    """Generate TPC-H tables once and stage them in the memory connector as
    one consolidated batch per table (the warmed-table equivalent of the
    reference's benchto setup; big batches keep the per-batch dispatch and
    sync count off the measured path).  The big tables (orders/lineitem) are
    generated ON the device — on an accelerator the columns are born in HBM
    and staging never pushes row data through the host; on
    the CPU backend the same vectorized XLA generator is still orders of
    magnitude faster than the per-row host page source (which made
    BENCH_SF=10 staging run for hours on the fallback)."""
    from trino_tpu.connectors.catalog import default_catalog
    from trino_tpu.connectors.tpch import generate_table_device
    from trino_tpu.spi.batch import ColumnBatch
    from trino_tpu.spi.connector import TableSchema

    catalog = default_catalog(scale_factor=sf)
    tpch = catalog.connector("tpch")
    mem = catalog.connector("memory")
    for t in sorted({t for ts in TABLES.values() for t in ts}):
        schema = tpch.get_table_schema(t)
        cols = schema.column_names()
        batch = generate_table_device(tpch, t, cols)
        if batch is None:
            batches = []
            for s in tpch.get_splits(t, 4, 1):
                src = tpch.create_page_source(s, cols)
                while not src.is_finished():
                    b = src.get_next_batch()
                    if b is not None:
                        batches.append(b)
            batch = ColumnBatch.concat(batches)
        mem.create_table(TableSchema(t, schema.columns))
        mem.finish_insert(t, [[batch]])
        mem.pin_to_device(t)  # hot tables live in device memory
    return catalog


def _scan_stats(runner, sql: str) -> tuple[float, float]:
    """(rows, bytes) the plan's table scans read (post column pruning)."""
    from trino_tpu.planner.plan import TableScan

    rows = 0.0
    nbytes = 0.0

    def walk(node):
        nonlocal rows, nbytes
        if isinstance(node, TableScan):
            stats = runner.catalog.connector(node.catalog).get_table_statistics(
                node.table)
            r = stats.row_count
            rows += r
            nbytes += r * sum(
                __import__("numpy").dtype(t.storage_dtype).itemsize
                for t in node.output_types)
        for c in node.children:
            walk(c)

    walk(runner.create_plan(sql))
    return rows, nbytes


def _time_queries(runner, iters: int) -> dict[str, float]:
    """Median wall-clock per query (after one warmup compile run)."""
    import jax

    times: dict[str, float] = {}
    for name, sql in QUERIES.items():
        runner.execute(sql)  # warmup: compile every jitted program
        samples = []
        for _ in range(iters):
            t0 = time.perf_counter()
            r = runner.execute(sql)
            for c in r.batch.columns:  # force any device work to finish
                jax.block_until_ready(c.data)
            samples.append(time.perf_counter() - t0)
        samples.sort()
        times[name] = samples[len(samples) // 2]
    return times


def _build_qps_plane(catalog, workers: int = 2, root_slots: int = 4,
                     heavy_weight: int = 3, light_weight: int = 1,
                     memory_capacity=None):
    """Two-tenant serving plane: ONE weighted-fair DispatchManager + ONE
    ClusterMemoryManager shared by two runners whose sessions differ only in
    ``source`` — the selector routes heavy/light traffic into sibling groups
    competing for ``root_slots`` concurrency slots at weights 3:1."""
    from trino_tpu.execution.control import DispatchManager
    from trino_tpu.execution.distributed_runner import DistributedQueryRunner
    from trino_tpu.execution.resource_manager import (
        ClusterMemoryManager,
        ResourceGroup,
    )
    from trino_tpu.runner import Session

    root = ResourceGroup("global", hard_concurrency_limit=root_slots,
                         scheduling_policy="weighted_fair", max_queued=1000)
    root.subgroup("heavy", weight=heavy_weight,
                  hard_concurrency_limit=root_slots)
    root.subgroup("light", weight=light_weight,
                  hard_concurrency_limit=root_slots)
    dispatcher = DispatchManager(
        root, selector=lambda sql, s: getattr(s, "source", ""))
    mm = ClusterMemoryManager(capacity_bytes=memory_capacity)
    runners = {}
    for name in ("heavy", "light"):
        r = DistributedQueryRunner(
            catalog, worker_count=workers,
            session=Session(default_catalog="memory", source=name,
                            node_count=workers))
        r.dispatcher = dispatcher
        r.memory_manager = mm
        runners[name] = r
    return root, dispatcher, mm, runners


def _pct(sorted_vals: list, q: float) -> float:
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(q * (len(sorted_vals) - 1) + 0.5))
    return sorted_vals[i]


def _result_cache_off(fn):
    """The qps/OOM legs measure *execution* — admission, fair scheduling,
    the cluster kill path.  A served cached result would skip the very
    machinery under measurement, so the result tier is pinned off for the
    duration of the leg (plan/executable tiers stay on: their hits still
    execute)."""
    import functools

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        from trino_tpu.caching import result_cache

        with result_cache.disabled():
            return fn(*args, **kwargs)
    return wrapper


@_result_cache_off
def run_qps_sustained(duration_s: float, catalog, clients_per_group: int = 5,
                      sql: str = None) -> dict:
    """The sustained-load leg: closed-loop clients per tenant hammer the
    shared admission plane for ``duration_s``; returns completed-work
    counts, per-group latency/queue-wait percentiles, queue depth and kill
    counts.  Saturation (clients > root slots) is what makes the
    completed-work ratio track the 3:1 configured weights."""
    import threading

    from trino_tpu.telemetry import runtime as rt

    sql = sql or Q1
    root, dispatcher, mm, runners = _build_qps_plane(catalog)
    for r in runners.values():
        r.execute(sql)  # warmup: compile outside the measured window
    stop = threading.Event()
    done: dict[str, list] = {"heavy": [], "light": []}
    failed = {"heavy": 0, "light": 0}

    def client(group: str):
        r = runners[group]
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                r.execute(sql)
            except Exception:
                failed[group] += 1
                continue
            done[group].append(time.perf_counter() - t0)

    depth: list[int] = []

    def monitor():
        while not stop.is_set():
            depth.append(root.queued_total)
            time.sleep(0.05)

    threads = [threading.Thread(target=client, args=(g,), daemon=True)
               for g in ("heavy", "light") for _ in range(clients_per_group)]
    threads.append(threading.Thread(target=monitor, daemon=True))
    for t in threads:
        t.start()
    time.sleep(duration_s)
    stop.set()
    for t in threads:
        t.join(timeout=60)

    waits = {"heavy": [], "light": []}
    for q in rt.queries():
        g = q.resource_group.rsplit(".", 1)[-1]
        if g in waits:
            waits[g].append(q.queued_ms)
    out = {"duration_s": duration_s,
           "clients_per_group": clients_per_group,
           "weights": {"heavy": 3, "light": 1},
           "queue_depth_max": max(depth, default=0),
           "queue_depth_mean": round(sum(depth) / len(depth), 2)
           if depth else 0.0,
           "oom_kills": mm.oom_kills}
    for g in ("heavy", "light"):
        lat = sorted(done[g])
        qw = sorted(waits[g])
        out[g] = {"completed": len(lat), "failed": failed[g],
                  "latency_p50_ms": round(_pct(lat, 0.50) * 1e3, 1),
                  "latency_p99_ms": round(_pct(lat, 0.99) * 1e3, 1),
                  "queue_wait_p50_ms": round(_pct(qw, 0.50), 1),
                  "queue_wait_p99_ms": round(_pct(qw, 0.99), 1)}
    light = max(1, out["light"]["completed"])
    out["fairness_ratio"] = round(out["heavy"]["completed"] / light, 3)
    return out


@_result_cache_off
def run_qps_oom_drill(catalog, capacity_bytes: int = 64 << 20,
                      pressure_bytes: int = 256 << 20,
                      timeout_s: float = 60.0) -> dict:
    """The OOM-killer drill: a capped ClusterMemoryManager, one running
    query, and a synthetic worker snapshot attributing ``pressure_bytes``
    to it — the killer's actual input plane is worker /v1/status JSON, so
    injecting a snapshot exercises the real kill path end to end: the
    drain loop polls the handle, raises CLUSTER_OUT_OF_MEMORY, and a
    follow-up query completes once the pressure clears."""
    import threading

    from trino_tpu.spi.errors import TrinoError

    root, dispatcher, mm, runners = _build_qps_plane(
        catalog, memory_capacity=capacity_bytes)
    mm.enforce_interval_s = 0.0  # drill: enforce on every poll
    r = runners["heavy"]
    r.execute(Q1)  # warmup
    result: dict = {}

    def victim():
        try:
            for _ in range(2000):  # long enough for the kill to land
                r.execute(Q1)
            result["error"] = None
        except TrinoError as e:
            result["error"] = e.code.name
        except Exception as e:  # pragma: no cover - diagnostic
            result["error"] = f"{type(e).__name__}: {e}"

    th = threading.Thread(target=victim, daemon=True)
    th.start()
    # keep pressure on whichever query is registered right now until a kill
    # lands (a query finishing between sweeps takes its accounting with it)
    deadline = time.monotonic() + timeout_s
    killed = False
    while not killed and time.monotonic() < deadline:
        with mm._lock:
            live = list(mm._handles.values())
        if live:
            h = live[0]
            mm.update_worker("synthetic-pressure", {"tasks": {
                "t0": {"query_id": h.query_id,
                       "memory_reserved_bytes": pressure_bytes}}})
            mm.enforce()
            killed = h.killed
        time.sleep(0.005)
    th.join(timeout=timeout_s)
    hung = th.is_alive()
    # pressure clears with the worker snapshot; steady state must return
    mm.forget_worker("synthetic-pressure")
    post_ok = False
    if not hung:
        try:
            runners["light"].execute(Q1)
            post_ok = True
        except Exception:
            post_ok = False
    return {"capacity_bytes": capacity_bytes,
            "pressure_bytes": pressure_bytes,
            "victim_error": result.get("error"),
            "victim_hung": hung,
            "oom_kills": mm.oom_kills,
            "post_drill_query_ok": post_ok}


def run_qps_bench(duration_s: float = None, sf: float = None,
                  clients_per_group: int = None, write: bool = True) -> dict:
    """``bench.py --qps``: the multi-tenant serving benchmark.  Two resource
    groups at 3:1 weights under saturating closed-loop load (acceptance:
    completed-work ratio within +-25% of 3.0, bounded light-group queue
    wait), then the capped-memory OOM drill.  Writes BENCH_r08.json."""
    duration_s = duration_s if duration_s is not None else float(
        os.environ.get("BENCH_QPS_DURATION", "30"))
    sf = sf if sf is not None else float(
        os.environ.get("BENCH_QPS_SF", "0.05"))
    clients_per_group = clients_per_group or int(
        os.environ.get("BENCH_QPS_CLIENTS", "5"))
    _device()
    catalog = _stage_memory_tables(sf)
    sustained = run_qps_sustained(duration_s, catalog,
                                  clients_per_group=clients_per_group)
    drill = run_qps_oom_drill(catalog)
    result = {
        "metric": f"qps_two_group_weighted_fair_sf{sf:g}",
        "value": sustained["fairness_ratio"],
        "unit": "heavy/light completed ratio (target 3.0 +-25%)",
        "sustained": sustained,
        "oom_drill": drill,
    }
    print(json.dumps(result))
    if write:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_r08.json"), "w") as f:
            json.dump(result, f, indent=1)
            f.write("\n")
    return result


def _chaos_spec_drill() -> dict:
    """Speculation tail-cut acceptance: one injected TASK_STALL straggler
    on a leaf stage; TRINO_TPU_SPECULATION=1 must cut the wall to <=0.5x
    of the no-speculation run with identical rows and the loser provably
    cancelled (first-commit-wins — the row sets match exactly, so no
    double-commit)."""
    from trino_tpu.connectors.catalog import default_catalog
    from trino_tpu.execution.distributed_runner import DistributedQueryRunner
    from trino_tpu.execution.failure_injector import (
        TASK_STALL,
        FailureInjector,
    )
    from trino_tpu.runner import Session

    sql = ("select l_returnflag, count(*), sum(l_quantity) from lineitem "
           "group by l_returnflag order by l_returnflag")
    prev = os.environ.get("TRINO_TPU_FUSED_STAGE")
    os.environ["TRINO_TPU_FUSED_STAGE"] = "0"  # leaf-only eligibility
    try:
        def once(spec: bool):
            inj = FailureInjector()
            # collectives off: a twin cannot join an in-flight all_to_all,
            # so collective-edge leaves are speculation-ineligible and the
            # drill would never speculate on a multi-device mesh
            r = DistributedQueryRunner(
                default_catalog(scale_factor=0.01), worker_count=4,
                session=Session(node_count=4, failure_injector=inj,
                                speculation=spec, use_collectives=False))
            leaf = [f for f in r.create_subplan(sql).all_fragments()
                    if not f.source_fragments][0]
            inj.inject(TASK_STALL, fragment_id=leaf.id, task_index=0,
                       attempt=0, stall_s=3.0)
            t0 = time.perf_counter()
            rows = r.execute(sql).rows()
            return time.perf_counter() - t0, rows, r

        wall_off, rows_off, _ = once(False)
        wall_on, rows_on, r = once(True)
    finally:
        if prev is None:
            os.environ.pop("TRINO_TPU_FUSED_STAGE", None)
        else:
            os.environ["TRINO_TPU_FUSED_STAGE"] = prev
    return {
        "wall_s_no_speculation": round(wall_off, 3),
        "wall_s_speculation": round(wall_on, 3),
        "ratio": round(wall_on / wall_off, 3),
        "rows_identical": sorted(rows_off) == sorted(rows_on),
        "speculative_starts": r.speculative_starts,
        "speculative_wins": r.speculative_wins,
        "pass": (wall_on <= 0.5 * wall_off
                 and sorted(rows_off) == sorted(rows_on)
                 and r.speculative_wins >= 1),
    }


def _chaos_rolling_restart_drill() -> dict:
    """Rolling-restart acceptance: drain every worker one at a time (real
    PUT /v1/shutdown + replacement) under sustained query load — zero
    queries lost."""
    import threading

    from trino_tpu.execution.remote import ProcessDistributedQueryRunner
    from trino_tpu.runner import Session
    from trino_tpu.testing.chaos import CATALOG_SPEC, _ENV, QUERY_MIX

    r = ProcessDistributedQueryRunner(
        CATALOG_SPEC, worker_count=2,
        session=Session(node_count=2, retry_policy="QUERY",
                        retry_initial_delay_s=0.01,
                        heartbeat_interval_s=0.2, drain_timeout_s=10.0),
        env_overrides=_ENV)
    stop = threading.Event()
    ok, failed = [], []

    def load():
        i = 0
        while not stop.is_set():
            sql = QUERY_MIX[i % len(QUERY_MIX)]
            i += 1
            try:
                r.execute(sql).rows()
                ok.append(sql)
            except Exception as e:  # noqa: BLE001 - any loss is a failure
                failed.append(f"{type(e).__name__}: {e}")

    try:
        r.execute(QUERY_MIX[0]).rows()  # warm up before the restarts
        th = threading.Thread(target=load, daemon=True)
        th.start()
        summaries = r.rolling_restart()
        time.sleep(1.0)
        stop.set()
        th.join(60)
        states = r.execute(
            "select worker, state from system.runtime.workers").rows()
    finally:
        r.close()
    return {
        "workers_drained": len(summaries),
        "escalated": sum(1 for s in summaries if s["escalated"]),
        "queries_completed": len(ok),
        "queries_lost": len(failed),
        "failures": failed[:5],
        "final_worker_states": sorted(states),
        "pass": (len(failed) == 0 and len(ok) > 0
                 and sum(1 for _, st in states if st == "ACTIVE") == 2),
    }


def run_fte_chaos_bench(write: bool = True) -> dict:
    """``bench.py --chaos-fte`` (also appended to ``--chaos``): the FTE
    chaos-certification leg for PR 15.  A seeded fault campaign over
    ``retry_policy="TASK"`` — the streaming menu plus SPOOL_CORRUPTION
    bit flips on committed spool files — followed by the coordinator
    kill -9 drill: SIGKILL mid-query, restart, resume from the query-state
    WAL with zero re-execution of committed attempts.  Acceptance is the
    PR-9 bar (100%% of queries accounted, zero hangs) plus the drill's
    ``pass``.  Writes BENCH_r15.json."""
    n = int(os.environ.get("BENCH_FTE_CHAOS_SCENARIOS", "10"))
    seed = int(os.environ.get("BENCH_FTE_CHAOS_SEED", "1515"))
    _device()

    from trino_tpu.telemetry.metrics import REGISTRY
    from trino_tpu.testing.chaos import run_coordinator_kill_drill, run_fte_chaos

    print(f"fte chaos leg: {n} scenarios from seed {seed}", file=sys.stderr)
    t0 = time.perf_counter()
    soak = run_fte_chaos(n_scenarios=n, base_seed=seed)
    soak_wall = time.perf_counter() - t0
    print("coordinator kill -9 drill", file=sys.stderr)
    t0 = time.perf_counter()
    drill = run_coordinator_kill_drill()
    drill_wall = time.perf_counter() - t0
    drill_out = {k: v for k, v in drill.items() if k != "rows"}
    drill_out["n_rows"] = len(drill.get("rows") or [])

    accounted = (soak["n_queries"] - soak["hangs"] - soak["unexpected"]
                 ) / max(soak["n_queries"], 1)
    result = {
        "metric": f"fte_chaos_{n}_scenarios_accounted_fraction",
        "value": round(accounted, 4),
        "unit": "fraction of FTE queries oracle-correct or correctly "
                "classified (target 1.0, zero hangs)",
        "soak_wall_s": round(soak_wall, 1),
        "drill_wall_s": round(drill_wall, 1),
        "soak": soak,
        "coordinator_kill_drill": drill_out,
        "metrics": {k: v for k, v in REGISTRY.snapshot().items()
                    if k.startswith("trino_fte_")},
    }
    print(json.dumps({k: v for k, v in result.items() if k != "soak"}))
    if write:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_r15.json"), "w") as f:
            json.dump(result, f, indent=1)
            f.write("\n")
    return result


def run_ha_bench(write: bool = True) -> dict:
    """``bench.py --ha``: the HA control-plane certification (PR 20).
    Writes BENCH_r20.json.  Three legs:

    1. **Lease takeover under load**: a two-coordinator fleet behind the
       stateless front tier (server/front_tier.py) at steady QPS; one
       coordinator holds an unrescuable in-flight FTE query and is killed
       -9.  The peer must claim the lease, adopt the query, and finish it
       under its ORIGINAL id through the tier's reroute path — zero lost
       queries, zero re-execution of committed attempts, and post-takeover
       p99 < 5x steady p99.
    2. **Elastic autoscaling**: a real process-worker cluster under
       memory-capped admission; the WorkerAutoscaler must add a worker
       while ``trino_admission_queued_seconds`` accumulates and drain one
       (zero-loss PUT /v1/shutdown) once the pressure passes.
    3. **Legacy parity**: with TRINO_TPU_HA=0 the chaos query mix is
       bit-for-bit oracle-correct, no HA state appears on disk, and no
       trino_ha_* activity is recorded.
    """
    import shutil
    import signal
    import statistics
    import tempfile
    import threading

    _device()

    from trino_tpu.execution import ha as ha_mod
    from trino_tpu.execution import query_state
    from trino_tpu.telemetry import metrics as tm
    from trino_tpu.testing import chaos
    from trino_tpu.testing.chaos import _http_json

    repo = os.path.dirname(os.path.abspath(__file__))
    steady_n = int(os.environ.get("BENCH_HA_QUERIES", "8"))
    lease_ttl = float(os.environ.get("BENCH_HA_LEASE_TTL_S", "2"))

    # ---------------------------------------- leg 1: takeover under load
    print("ha leg 1: lease takeover under steady QPS", file=sys.stderr)
    work = tempfile.mkdtemp(prefix="trino-tpu-ha-bench-")
    ha_root = os.path.join(work, "ha")
    base_env = dict(os.environ)
    base_env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "TRINO_TPU_HA": "1",
        "TRINO_TPU_HA_DIR": ha_root,
        "TRINO_TPU_HA_LEASE_TTL_S": str(lease_ttl),
        "TRINO_TPU_HA_HEARTBEAT_S": "0.5",
        "TRINO_TPU_QUERY_STATE": "1",
        "TRINO_TPU_SPOOL_DIR": os.path.join(work, "spool"),
        "TRINO_TPU_JOURNAL_DIR": os.path.join(work, "journal"),
        "TRINO_TPU_RESULT_CACHE": "0",
        "PYTHONPATH": repo + os.pathsep + base_env.get("PYTHONPATH", ""),
    })
    child_cmd = [sys.executable, "-c",
                 "from trino_tpu.testing.chaos import _ha_coordinator_child;"
                 " _ha_coordinator_child()"]

    def _boot(node, extra):
        port_file = os.path.join(work, f"port-{node}")
        env = {**base_env, "TRINO_TPU_HA_NODE_ID": node,
               "TRINO_TPU_QUERY_STATE_DIR":
                   os.path.join(ha_root, "wal", node),
               "CHAOS_PORT_FILE": port_file, **extra}
        proc = subprocess.Popen(child_cmd, env=env, cwd=repo)
        deadline = time.monotonic() + 180.0
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(f"HA child {node} died at boot")
            if os.path.exists(port_file):
                with open(port_file, encoding="utf-8") as f:
                    return proc, int(f.read().strip())
            time.sleep(0.1)
        proc.kill()
        raise TimeoutError(f"HA child {node} never wrote its port")

    def _poll_tier(tier_port, first, timeout_s=120.0):
        out, rows = first, list(first.get("data", []))
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            state = out.get("stats", {}).get("state")
            nxt = out.get("nextUri")
            if state == "FAILED" or (state == "FINISHED" and not nxt):
                return state, rows
            out = _http_json(
                "GET", f"http://127.0.0.1:{tier_port}{nxt}", timeout=60.0)
            rows += out.get("data", [])
        return "TIMEOUT", rows

    def _run_via_tier(tier_port, sql):
        t0 = time.monotonic()
        first = _http_json("POST",
                           f"http://127.0.0.1:{tier_port}/v1/statement",
                           sql.encode("utf-8"), timeout=60.0)
        state, _rows = _poll_tier(tier_port, first)
        return state, time.monotonic() - t0

    from trino_tpu.server.front_tier import FrontTier

    leg1: dict = {}
    proc_a = proc_b = None
    tier = None
    try:
        proc_a, port_a = _boot("coordA", {"CHAOS_STALL_S": "300"})
        proc_b, port_b = _boot("coordB", {})
        tier = FrontTier(root=ha_root, ttl=lease_ttl, retry_s=30.0).start()
        tier_port = tier.address[1]

        # the pinned in-flight query: eats coordA's one-shot stall
        sub = _http_json("POST",
                         f"http://127.0.0.1:{port_a}/v1/statement",
                         chaos._DRILL_SQL.encode("utf-8"))
        drill_qid = sub["id"]
        wal_a = os.path.join(ha_root, "wal", "coordA", drill_qid + ".wal")
        pq = None
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            pq = query_state.load(wal_a)
            if pq is not None and len(pq.committed) >= 1:
                break
            time.sleep(0.1)
        if pq is None or not pq.committed:
            raise TimeoutError("no committed attempt before the kill")
        starts_at_kill = dict(pq.attempt_counts)
        committed_at_kill = dict(pq.committed)

        steady = [_run_via_tier(tier_port, sql) for sql in
                  (chaos.QUERY_MIX * 3)[:steady_n]]
        assert all(s == "FINISHED" for s, _ in steady), steady

        reroutes_before = tm.HA_REROUTES.value()
        t_kill = time.monotonic()
        os.kill(proc_a.pid, signal.SIGKILL)
        proc_a.wait(timeout=30)
        # takeover: coordB claims the expired lease + WAL custody
        lease_a = os.path.join(ha_root, "coordinators", "coordA.json")
        deadline = time.monotonic() + 60.0
        while os.path.exists(lease_a) and time.monotonic() < deadline:
            time.sleep(0.1)
        takeover_s = time.monotonic() - t_kill

        # the in-flight query finishes under its original id, polled
        # through the tier (reroute: the hash owner is gone)
        first = _http_json(
            "GET",
            f"http://127.0.0.1:{tier_port}/v1/statement/{drill_qid}/0",
            timeout=60.0)
        drill_state, drill_rows = _poll_tier(tier_port, first)

        post = [_run_via_tier(tier_port, sql) for sql in
                (chaos.QUERY_MIX * 3)[:steady_n]]

        wal_root = os.path.join(ha_root, "wal")
        claimed = [d for d in sorted(os.listdir(wal_root))
                   if d.startswith("coordA.claimed-coordB-")]
        final = query_state.load(os.path.join(
            wal_root, claimed[0], drill_qid + ".wal")) if claimed else None
        re_executed = {}
        if final is not None:
            re_executed = {
                f"f{fid}_t{t}": final.attempt_counts.get((fid, t), 0)
                - starts_at_kill.get((fid, t), 0)
                for (fid, t) in committed_at_kill
                if final.attempt_counts.get((fid, t), 0)
                > starts_at_kill.get((fid, t), 0)}

        steady_walls = sorted(w for _s, w in steady)
        post_walls = sorted(w for _s, w in post)

        def p99(walls):
            return walls[min(len(walls) - 1,
                             int(0.99 * len(walls)))] if walls else 0.0

        leg1 = {
            "steady_queries": len(steady),
            "post_queries": len(post),
            "lost_queries": sum(1 for s, _ in steady + post
                                if s != "FINISHED")
            + (0 if drill_state == "FINISHED" else 1),
            "in_flight_state": drill_state,
            "in_flight_rows": len(drill_rows),
            "committed_at_kill": len(committed_at_kill),
            "committed_reexecuted": re_executed,
            "claimed_dirs": claimed,
            "takeover_s": round(takeover_s, 2),
            "tier_reroutes": tm.HA_REROUTES.value() - reroutes_before,
            "steady_p50_s": round(statistics.median(steady_walls), 3),
            "steady_p99_s": round(p99(steady_walls), 3),
            "post_p99_s": round(p99(post_walls), 3),
            "p99_ratio": round(p99(post_walls)
                               / max(p99(steady_walls), 1e-9), 2),
        }
        # NB: tier_reroutes is informational — in a 2-member fleet the
        # claimant IS the post-death rehash owner, so the probe path
        # (covered by tests/test_ha.py) rarely fires here
        leg1["pass"] = (leg1["lost_queries"] == 0
                        and drill_state == "FINISHED"
                        and re_executed == {} and bool(claimed)
                        and leg1["p99_ratio"] < 5.0)
    finally:
        if tier is not None:
            tier.stop()
        for p in (proc_a, proc_b):
            if p is not None and p.poll() is None:
                p.kill()
                p.wait(timeout=15)
        shutil.rmtree(work, ignore_errors=True)

    # -------------------------------------------- leg 2: elastic workers
    print("ha leg 2: worker autoscaling", file=sys.stderr)
    from trino_tpu.execution.remote import ProcessDistributedQueryRunner
    from trino_tpu.runner import Session
    from trino_tpu.server.protocol import TrinoTpuServer

    # query_concurrency=1: concurrent clients genuinely queue at the
    # resource-group gate, which records trino_admission_queued_seconds —
    # the autoscaler's pressure signal
    session = Session(node_count=1, retry_policy="QUERY",
                      query_concurrency=1)
    runner = ProcessDistributedQueryRunner(
        chaos.CATALOG_SPEC, worker_count=1, session=session,
        env_overrides=chaos._ENV)
    srv = TrinoTpuServer(runner, max_concurrent=4)
    srv.start()
    asc = ha_mod.WorkerAutoscaler(runner, min_workers=1, max_workers=2,
                                  queue_s=0.2, idle_rounds=3,
                                  interval_s=0.5)
    leg2: dict = {}
    try:
        host, port = srv.address
        results: list = []

        def client(n):
            for i in range(n):
                sql = chaos.QUERY_MIX[i % len(chaos.QUERY_MIX)]
                first = _http_json(
                    "POST", f"http://{host}:{port}/v1/statement",
                    sql.encode("utf-8"), timeout=120.0)
                out, state = first, None
                deadline = time.monotonic() + 120.0
                while time.monotonic() < deadline:
                    state = out.get("stats", {}).get("state")
                    nxt = out.get("nextUri")
                    if state == "FAILED" or (state == "FINISHED"
                                             and not nxt):
                        break
                    out = _http_json(
                        "GET", f"http://{host}:{port}{nxt}", timeout=60.0)
                results.append(state)

        asc.start()
        workers_before = len(runner.workers)
        clients = [threading.Thread(target=client, args=(4,))
                   for _ in range(3)]
        for c in clients:
            c.start()
        for c in clients:
            c.join()
        workers_peak = max([workers_before]
                           + [e[1] for e in asc.events if e[0] == "up"])
        # pressure gone: the idle streak must drain back to the floor
        deadline = time.monotonic() + 30.0
        while len(runner.workers) > 1 and time.monotonic() < deadline:
            time.sleep(0.2)
        workers_after = len(runner.workers)
        asc.stop()
        queued_snap = tm.ADMISSION_QUEUED_SECONDS.snapshot()
        leg2 = {
            "queries": len(results),
            "lost_queries": sum(1 for s in results if s != "FINISHED"),
            "workers_before": workers_before,
            "workers_peak": workers_peak,
            "workers_after": workers_after,
            "events": [list(e) for e in asc.events],
            "admission_queued_count": queued_snap["count"],
            "admission_queued_sum_s": round(queued_snap["sum"], 3),
        }
        leg2["pass"] = (leg2["lost_queries"] == 0
                        and workers_peak == 2 and workers_after == 1
                        and any(e[0] == "up" for e in asc.events)
                        and any(e[0] == "down" for e in asc.events))
    finally:
        asc.stop()
        srv.stop()
        runner.close()

    # ----------------------------------------------- leg 3: legacy parity
    print("ha leg 3: TRINO_TPU_HA=0 parity", file=sys.stderr)
    from trino_tpu.connectors.catalog import default_catalog
    from trino_tpu.execution.distributed_runner import DistributedQueryRunner
    from trino_tpu.testing.oracle import assert_same_rows

    assert os.environ.get("TRINO_TPU_HA", "0") in ("", "0"), \
        "leg 3 must run with HA off"
    ha_counters_before = {
        k: v["value"] for k, v in tm.REGISTRY.snapshot().items()
        if k.startswith("trino_ha_") and v["kind"] == "counter"
        and k != "trino_ha_reroutes_total"}  # leg 1's tier ran in-process
    expected = chaos.build_expected()
    legacy = DistributedQueryRunner(default_catalog(scale_factor=0.01),
                                    worker_count=2,
                                    session=Session(node_count=2))
    mismatches = 0
    for sql in chaos.QUERY_MIX:
        r1 = legacy.execute(sql).rows()
        r2 = legacy.execute(sql).rows()
        try:
            assert_same_rows(r1, expected[sql], ordered=False)
            assert_same_rows(r2, expected[sql], ordered=False)
        except AssertionError:
            mismatches += 1
    ha_counters_after = {
        k: v["value"] for k, v in tm.REGISTRY.snapshot().items()
        if k.startswith("trino_ha_") and v["kind"] == "counter"
        and k != "trino_ha_reroutes_total"}
    leg3 = {
        "queries": 2 * len(chaos.QUERY_MIX),
        "mismatches": mismatches,
        "ha_counter_deltas": {
            k: ha_counters_after[k] - ha_counters_before.get(k, 0)
            for k in ha_counters_after},
        "pass": mismatches == 0 and all(
            ha_counters_after[k] == ha_counters_before.get(k, 0)
            for k in ha_counters_after),
    }

    result = {
        "harness": chaos.CPU_HARNESS,
        "metric": "ha_takeover_p99_ratio",
        "value": leg1.get("p99_ratio"),
        "unit": "post-takeover p99 / steady p99 (target < 5.0; zero lost, "
                "zero re-executed committed attempts)",
        "takeover": leg1,
        "autoscaler": leg2,
        "legacy_parity": leg3,
        "pass": bool(leg1.get("pass") and leg2.get("pass")
                     and leg3.get("pass")),
        "metrics": {k: v for k, v in tm.REGISTRY.snapshot().items()
                    if k.startswith("trino_ha_")},
    }
    print(json.dumps({k: v for k, v in result.items()
                      if k not in ("metrics",)}))
    if write:
        with open(os.path.join(repo, "BENCH_r20.json"), "w") as f:
            json.dump(result, f, indent=1)
            f.write("\n")
    return result


def run_chaos_bench(write: bool = True) -> dict:
    """``bench.py --chaos``: the chaos-certification soak.  A seeded
    randomized fault-injection campaign (trino_tpu/testing/chaos.py) over
    in-process and real-process clusters, plus the two acceptance drills
    (speculation tail-cut, rolling restart).  Writes BENCH_r09.json."""
    n = int(os.environ.get("BENCH_CHAOS_SCENARIOS", "25"))
    seed = int(os.environ.get("BENCH_CHAOS_SEED", "1009"))
    _device()

    from trino_tpu.telemetry.metrics import REGISTRY
    from trino_tpu.testing.chaos import run_chaos

    print(f"chaos soak: {n} scenarios from seed {seed}", file=sys.stderr)
    t0 = time.perf_counter()
    soak = run_chaos(n_scenarios=n, base_seed=seed)
    soak_wall = time.perf_counter() - t0
    print("speculation tail-cut drill", file=sys.stderr)
    spec = _chaos_spec_drill()
    print("rolling-restart drill", file=sys.stderr)
    rolling = _chaos_rolling_restart_drill()

    accounted = (soak["n_queries"] - soak["hangs"] - soak["unexpected"]
                 ) / max(soak["n_queries"], 1)
    result = {
        "metric": f"chaos_soak_{n}_scenarios_accounted_fraction",
        "value": round(accounted, 4),
        "unit": "fraction of queries oracle-correct or correctly classified"
                " (target 1.0, zero hangs)",
        "soak_wall_s": round(soak_wall, 1),
        "soak": soak,
        "speculation_drill": spec,
        "rolling_restart_drill": rolling,
        "metrics": {k: v for k, v in REGISTRY.snapshot().items()
                    if k.startswith(("trino_speculative", "trino_drains",
                                     "trino_blacklisted"))},
    }
    print(json.dumps({k: v for k, v in result.items() if k != "soak"}))
    if write:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_r09.json"), "w") as f:
            json.dump(result, f, indent=1)
            f.write("\n")
    return result


def run_warm_bench(write: bool = True) -> dict:
    """``bench.py --warm``: the repeated-traffic cold/warm ladder for the
    three-tier cache plane (trino_tpu/caching/).  Three legs over the Q1+Q3
    mix:

    - **cold** — empty caches: parse -> plan -> optimize -> compile -> run.
    - **warm** — identical texts re-submitted: Tier A skips planning, Tier C
      serves the versioned result without touching the executors.
      Acceptance: warm p50 at least 10x under cold p50.
    - **warm-after-mutation** — INSERT into lineitem, re-run: every row set
      must match a cache-disabled oracle run (the result tier re-validates
      on the bumped table version; a stale serve fails the bench).

    Env knobs: BENCH_WARM_SF (default 0.05), BENCH_WARM_REPS (default 20).
    Writes BENCH_r12.json with p50/p99 per leg and per-tier hit rates."""
    sf = float(os.environ.get("BENCH_WARM_SF", "0.05"))
    reps = int(os.environ.get("BENCH_WARM_REPS", "20"))
    _device()

    import jax

    from trino_tpu import caching
    from trino_tpu.runner import Session, StandaloneQueryRunner

    caching.reset_for_test()
    catalog = _stage_memory_tables(sf)
    runner = StandaloneQueryRunner(
        catalog, session=Session(default_catalog="memory", splits_per_node=1))

    def timed(sql: str):
        t0 = time.perf_counter()
        r = runner.execute(sql)
        for c in r.batch.columns:  # force any device work to finish
            jax.block_until_ready(c.data)
        return (time.perf_counter() - t0) * 1e3, r

    def oracle_rows(sql: str):
        """The same query with Tier A/C disabled — the staleness oracle."""
        os.environ["TRINO_TPU_PLAN_CACHE"] = "0"
        os.environ["TRINO_TPU_RESULT_CACHE"] = "0"
        try:
            return runner.execute(sql).rows()
        finally:
            del os.environ["TRINO_TPU_PLAN_CACHE"]
            del os.environ["TRINO_TPU_RESULT_CACHE"]

    # leg 1 — cold: first submission of each text
    cold_ms = {name: round(timed(sql)[0], 2) for name, sql in QUERIES.items()}

    # leg 2 — warm: identical texts, reps times each
    warm_samples: list[float] = []
    warm_rows: dict[str, list] = {}
    for _ in range(reps):
        for name, sql in QUERIES.items():
            ms, r = timed(sql)
            warm_samples.append(ms)
            warm_rows[name] = r.rows()
    stale = any(warm_rows[name] != oracle_rows(sql)
                for name, sql in QUERIES.items())

    # leg 3 — mutation: bump lineitem (Q1 and Q3 both scan it), re-run
    runner.execute("insert into lineitem select * from lineitem "
                   "where l_orderkey = 1")
    post_ms: dict[str, float] = {}
    for name, sql in QUERIES.items():
        ms, r = timed(sql)
        post_ms[name] = round(ms, 2)
        if r.rows() != oracle_rows(sql):
            stale = True

    tiers = {}
    for row in caching.cache_rows():
        total = row["hits"] + row["misses"]
        tiers[row["name"]] = dict(
            row, hit_rate=round(row["hits"] / total, 3) if total else 0.0)

    warm_samples.sort()
    cold_sorted = sorted(cold_ms.values())
    cold_p50 = _pct(cold_sorted, 0.5)
    warm_p50 = _pct(warm_samples, 0.5)
    speedup = cold_p50 / warm_p50 if warm_p50 else 0.0
    result = {
        "metric": f"warm_path_speedup_p50_sf{sf:g}",
        "value": round(speedup, 1),
        "unit": "cold p50 / warm p50 wall (target >= 10x, no stale serve)",
        "pass_10x": speedup >= 10.0,
        "stale_serve": stale,
        "cold_ms": cold_ms,
        "warm_p50_ms": round(warm_p50, 3),
        "warm_p99_ms": round(_pct(warm_samples, 0.99), 3),
        "warm_after_mutation_ms": post_ms,
        "tiers": tiers,
    }
    print(json.dumps(result))
    if write:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_r12.json"), "w") as f:
            json.dump(result, f, indent=1)
            f.write("\n")
    return result


# --adaptive leg 1: ~80% of the probe rows collapse onto ONE join key, so a
# static hash-partitioned join lands most of the work on a single task; the
# runtime skew split fans that key out across several probe tasks.  count and
# a DECIMAL sum only: both are exact and summation-order independent, so the
# off/on row comparison is bit-for-bit even though the split reorders pages.
# The sum spans both join sides so the iterative optimizer cannot compact
# the heavy key away with a pre-join partial aggregation
_ADAPTIVE_SKEW_SQL = """
select count(*) n, sum(p.o_totalprice + b.c_acctbal) s
from (select case when o_orderkey % 5 < 4 then 1
             else o_custkey end as k, o_totalprice from orders) p
join (select c_custkey, c_acctbal from customer) b on p.k = b.c_custkey
"""

# --adaptive leg 2: a genuine optimizer mis-estimate.  The four always-true
# range conjuncts each get the 0.4 one-sided-range selectivity from
# _conjunct_selectivity, so the optimizer estimates the orders subquery at
# 0.4^4 = 2.6% of its true size, makes it the smallest relation, and
# BROADCASTs it as the build side — every task re-builds the full 150k*sf-row
# hash table.  The runtime flip to PARTITIONED splits the build 1/n per task
# (a WORK reduction, visible even on a single-core host)
_ADAPTIVE_WRONG_SQL = """
select c.c_mktsegment, count(*) n, sum(o.o_totalprice) s
from customer c
join (select o_custkey, o_totalprice from orders
      where o_orderkey > -1 and o_orderkey > -2
        and o_orderkey > -3 and o_orderkey > -4) o
  on c.c_custkey = o.o_custkey
group by c.c_mktsegment order by c.c_mktsegment
"""


@_result_cache_off
def _adaptive_ab(sql: str, sf: float, workers: int, iters: int,
                 env: dict, on_session_kw: dict) -> dict:
    """One A/B leg: median wall for adaptive=0 vs adaptive=1 on a fresh
    runner each, identical (sorted) rows required, decision tags captured
    from the telemetry record of the adaptive run."""
    from trino_tpu import caching
    from trino_tpu.connectors.catalog import default_catalog
    from trino_tpu.execution.distributed_runner import DistributedQueryRunner
    from trino_tpu.runner import Session
    from trino_tpu.telemetry import runtime as rt

    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        out: dict = {}
        rows: dict[str, list] = {}
        for mode, kw in (("off", {"adaptive": "0"}),
                         ("on", dict(on_session_kw, adaptive="1"))):
            caching.reset_for_test()
            r = DistributedQueryRunner(
                default_catalog(scale_factor=sf), worker_count=workers,
                session=Session(node_count=workers, **kw))
            r.execute(sql)  # warmup: compile every jitted program
            samples = []
            for _ in range(iters):
                t0 = time.perf_counter()
                res = r.execute(sql)
                samples.append(time.perf_counter() - t0)
            samples.sort()
            rows[mode] = sorted(res.rows())
            out[f"wall_s_{mode}"] = round(samples[len(samples) // 2], 3)
            if mode == "on":
                out["decisions"] = rt.queries()[-1].adaptive_decisions
        out["speedup"] = round(out["wall_s_off"] / max(out["wall_s_on"],
                                                       1e-9), 2)
        out["rows_identical"] = rows["off"] == rows["on"]
        return out
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def run_adaptive_bench(write: bool = True) -> dict:
    """``bench.py --adaptive``: the adaptive-execution acceptance A/B.

    Two legs, each adaptive=1 vs the bit-for-bit legacy adaptive=0 on the
    same data and plan inputs:

    - **skewed_key** — ~80% of probe rows on one join key, static plan
      forced PARTITIONED: the heavy partition serializes the legacy run;
      the runtime skew split must cut wall by >= 2x.  A split moves no
      work, it only balances it, so the wall target needs >= ``workers``
      usable cores; on a smaller host the leg is judged on the measured
      trino_adaptive_skew_imbalance_ratio gauge (max partition weight
      before/after — exactly what a parallel host converts to wall) and
      the JSON records which criterion applied.
    - **wrong_side_broadcast** — a selectivity mis-estimate (stacked
      always-true range conjuncts) broadcasts the big build side; the
      runtime flip to PARTITIONED must cut wall by >= 1.5x.  The flip is
      a work reduction (n duplicate hash builds -> 1), so the wall
      target holds on any host.

    Env knobs: BENCH_ADAPTIVE_SF (default 0.3), BENCH_ADAPTIVE_WORKERS
    (default 4), BENCH_ITERS (default 3).  Writes BENCH_r13.json."""
    sf = float(os.environ.get("BENCH_ADAPTIVE_SF", "0.3"))
    workers = int(os.environ.get("BENCH_ADAPTIVE_WORKERS", "4"))
    iters = int(os.environ.get("BENCH_ITERS", "3"))
    _device()

    from trino_tpu.telemetry import metrics as tm
    from trino_tpu.telemetry.metrics import REGISTRY

    cores = len(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity") \
        else (os.cpu_count() or 1)
    print(f"adaptive A/B: sf={sf:g} workers={workers} cores={cores}",
          file=sys.stderr)
    # threshold=1 byte: the tiny build must NOT flip to broadcast, so the
    # leg isolates the skew split (a broadcast flip would also fix skew,
    # but it is leg 2's mechanism)
    skew = _adaptive_ab(
        _ADAPTIVE_SKEW_SQL, sf, workers, iters,
        env={"TRINO_TPU_BROADCAST_ROW_LIMIT": "0"},
        on_session_kw={"broadcast_threshold_bytes": 1, "skew_factor": 1.2})
    skew["imbalance_ratio"] = round(tm.ADAPTIVE_SKEW_IMBALANCE.value(), 2)
    print(f"skewed_key: {skew}", file=sys.stderr)
    wrong = _adaptive_ab(
        _ADAPTIVE_WRONG_SQL, sf, workers, iters,
        env={}, on_session_kw={"broadcast_threshold_bytes": 1 << 20})
    print(f"wrong_side_broadcast: {wrong}", file=sys.stderr)

    # wall-clock is the skew criterion when the host can actually run the
    # tasks in parallel; a 1-core container cannot turn load balance into
    # wall, so there the sketch-measured imbalance ratio (what a parallel
    # host realises) is the honest stand-in — recorded either way
    skew_on_wall = cores >= workers
    skew_ok = (skew["rows_identical"] and "skew_split" in skew["decisions"]
               and (skew["speedup"] >= 2.0 if skew_on_wall
                    else skew["imbalance_ratio"] >= 2.0))
    result = {
        "metric": f"adaptive_skew_split_speedup_sf{sf:g}",
        "value": skew["speedup"],
        "unit": "adaptive=0 wall / adaptive=1 wall "
                "(skew target >= 2x, wrong-broadcast target >= 1.5x)",
        "workers": workers,
        "iters": iters,
        "cores": cores,
        "skew_criterion": ("wall_speedup >= 2.0" if skew_on_wall else
                           "imbalance_ratio >= 2.0 (host has fewer cores "
                           "than workers; wall cannot see load balance)"),
        "skewed_key": skew,
        "wrong_side_broadcast": wrong,
        "pass": (skew_ok
                 and wrong["speedup"] >= 1.5 and wrong["rows_identical"]
                 and "flip_to_partitioned" in wrong["decisions"]),
        "metrics": {k: v for k, v in REGISTRY.snapshot().items()
                    if k.startswith("trino_adaptive")},
    }
    print(json.dumps(result))
    if write:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_r13.json"), "w") as f:
            json.dump(result, f, indent=1)
            f.write("\n")
    return result


def _hbo_walk(node):
    yield node
    for c in node.children:
        yield from _hbo_walk(c)


def _hbo_build_side(runner, sql: str) -> dict:
    """Plan (no execution) and report the sole join's distribution plus
    which base tables feed its build (right) side, following remote
    exchanges across fragments."""
    from trino_tpu.planner.plan import Join, RemoteSource, TableScan

    frags = runner.create_subplan(sql).all_fragments()
    by_id = {f.id: f for f in frags}
    join = next(n for f in frags for n in _hbo_walk(f.root)
                if isinstance(n, Join))

    def tables(node, seen):
        out = set()
        for n in _hbo_walk(node):
            if isinstance(n, TableScan):
                out.add(n.table)
            elif isinstance(n, RemoteSource) and n.fragment_id not in seen:
                seen.add(n.fragment_id)
                out |= tables(by_id[n.fragment_id].root, seen)
        return out

    return {"distribution": join.distribution,
            "build_tables": sorted(tables(join.right, set()))}


@_result_cache_off
def _hbo_second_run(sql: str, sf: float, workers: int, iters: int) -> dict:
    """Three runs of the BENCH_r13 wrong-side-broadcast leg against one
    isolated history journal:

    - **static** — HBO=0, adaptive=0: the mis-estimated BROADCAST plan
      runs uncorrected (reference floor; records nothing).
    - **run1** — HBO=1, adaptive=1: the first execution still plans
      BROADCAST (empty history), the runtime flip corrects it at the
      activation barrier AND the observed stats are journaled at query
      end.
    - **run2** — HBO=1, adaptive=0: a fresh runner re-plans from history
      and must choose PARTITIONED up front — no runtime correction left.
    """
    import tempfile

    from trino_tpu import caching
    from trino_tpu.connectors.catalog import default_catalog
    from trino_tpu.execution.distributed_runner import DistributedQueryRunner
    from trino_tpu.planner.history import reset_for_test as history_reset
    from trino_tpu.planner.iterative.driver import last_report
    from trino_tpu.planner.plan import Join
    from trino_tpu.runner import Session
    from trino_tpu.telemetry import runtime as rt

    env = {
        "TRINO_TPU_JOURNAL_DIR": tempfile.mkdtemp(prefix="hbo_bench_"),
        # plan-time history and the adaptive activation barrier compare
        # observed build bytes against the SAME threshold: 1 MiB, far
        # under the real orders build side at this scale factor
        "TRINO_TPU_BROADCAST_THRESHOLD_BYTES": str(1 << 20),
    }
    saved = {k: os.environ.get(k) for k in list(env) + ["TRINO_TPU_HBO"]}
    os.environ.update(env)
    try:
        out: dict = {}
        rows: dict[str, list] = {}

        def fresh_runner(hbo: str, adaptive: str):
            os.environ["TRINO_TPU_HBO"] = hbo
            caching.reset_for_test()
            history_reset()
            return DistributedQueryRunner(
                default_catalog(scale_factor=sf), worker_count=workers,
                session=Session(node_count=workers, adaptive=adaptive))

        def timed(r, name: str) -> None:
            samples = []
            for _ in range(iters):
                t0 = time.perf_counter()
                res = r.execute(sql)
                samples.append(time.perf_counter() - t0)
            samples.sort()
            rows[name] = sorted(res.rows())
            out[f"wall_s_{name}"] = round(samples[len(samples) // 2], 3)

        # static floor: wrong BROADCAST, nothing corrects it, no recording
        r = fresh_runner(hbo="0", adaptive="0")
        out["static_plan"] = _hbo_build_side(r, sql)
        r.execute(sql)  # warmup: compile every jitted program
        timed(r, "static")

        # run 1: adaptive corrects at runtime, stats land in the journal
        r = fresh_runner(hbo="1", adaptive="1")
        out["run1_first_plan"] = _hbo_build_side(r, sql)
        r.execute(sql)  # warmup; also the first history-recorded execution
        out["run1_decisions"] = rt.queries()[-1].adaptive_decisions
        timed(r, "run1")

        # run 2: fresh runner, second-run planning — history must pick the
        # correct build side before a single row moves
        r = fresh_runner(hbo="1", adaptive="0")
        out["run2_plan"] = _hbo_build_side(r, sql)
        rep = last_report()
        if rep is not None:
            out["run2_planning_ms"] = round(rep.planning_ms, 2)
            out["run2_history_lookups"] = rep.history_lookups
            out["run2_history_hits"] = rep.history_hits
        r.execute(sql)  # warmup
        out["run2_decisions"] = rt.queries()[-1].adaptive_decisions
        timed(r, "run2")

        out["rows_identical"] = (rows["static"] == rows["run1"] ==
                                 rows["run2"])
        out["wall_ratio_run2_vs_run1"] = round(
            out["wall_s_run2"] / max(out["wall_s_run1"], 1e-9), 3)
        out["speedup_vs_static"] = round(
            out["wall_s_static"] / max(out["wall_s_run2"], 1e-9), 2)
        return out
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def run_hbo_bench(write: bool = True) -> dict:
    """``bench.py --hbo``: the history-based-optimization acceptance leg.

    Re-runs the BENCH_r13 wrong-side-broadcast mis-estimate with history
    in the loop: run 1 (adaptive on, empty history) plans the broadcast
    wrong and gets corrected at runtime while plan_stats are journaled;
    run 2 (HBO on, adaptive OFF) must plan the correct PARTITIONED build
    side up front from the recorded stats, with wall <= 1.15x the
    adaptive-on run-1 wall, identical rows, and planning-time overhead
    recorded from the iterative optimizer trace.

    Env knobs: BENCH_ADAPTIVE_SF (default 0.3), BENCH_ADAPTIVE_WORKERS
    (default 4), BENCH_ITERS (default 3).  Writes BENCH_r18.json."""
    sf = float(os.environ.get("BENCH_ADAPTIVE_SF", "0.3"))
    workers = int(os.environ.get("BENCH_ADAPTIVE_WORKERS", "4"))
    iters = int(os.environ.get("BENCH_ITERS", "3"))
    _device()

    from trino_tpu.telemetry.metrics import REGISTRY

    print(f"hbo second-run: sf={sf:g} workers={workers}", file=sys.stderr)
    leg = _hbo_second_run(_ADAPTIVE_WRONG_SQL, sf, workers, iters)
    print(f"wrong_side_broadcast: {leg}", file=sys.stderr)

    # run2 may carry plan-time hbo_fanout tags, but every RUNTIME
    # correction (flip/skew-split) must be gone: history planned it right.
    # "correct build side up front" = the mis-estimated orders subquery is
    # no longer the broadcast build; with true stats the reorderer either
    # partitions it or puts the genuinely small customer side on build.
    runtime_fixes = ("flip_to" in leg["run2_decisions"]
                     or "skew_split" in leg["run2_decisions"])
    ok = (leg["rows_identical"]
          and leg["static_plan"] == {"distribution": "BROADCAST",
                                     "build_tables": ["orders"]}
          and leg["run1_first_plan"] == leg["static_plan"]
          and "flip_to_partitioned" in leg["run1_decisions"]
          and "orders" not in leg["run2_plan"]["build_tables"]
          and not runtime_fixes
          and leg["wall_ratio_run2_vs_run1"] <= 1.15)
    result = {
        "metric": f"hbo_second_run_wall_ratio_sf{sf:g}",
        "value": leg["wall_ratio_run2_vs_run1"],
        "unit": "run-2 wall (HBO=1, adaptive=0) / adaptive-on run-1 wall "
                "(target <= 1.15; run-2 must plan the build side right "
                "up front)",
        "workers": workers,
        "iters": iters,
        "wrong_side_broadcast": leg,
        "pass": ok,
        "metrics": {k: v for k, v in REGISTRY.snapshot().items()
                    if k.startswith(("trino_hbo", "trino_optimizer"))},
    }
    print(json.dumps(result))
    if write:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_r18.json"), "w") as f:
            json.dump(result, f, indent=1)
            f.write("\n")
    return result


def run_baseline() -> None:
    """CPU reference: same engine, same data, 8-worker DistributedQueryRunner.
    Runs in a subprocess with JAX_PLATFORMS=cpu (BASELINE.md config #1)."""
    sf = float(os.environ.get("BENCH_SF", "2"))
    workers = int(os.environ.get("BENCH_BASELINE_WORKERS", "8"))
    from trino_tpu.execution.distributed_runner import DistributedQueryRunner
    from trino_tpu.runner import Session

    catalog = _stage_memory_tables(sf)
    runner = DistributedQueryRunner(
        catalog, worker_count=workers,
        session=Session(default_catalog="memory", node_count=workers))
    times: dict[str, float] = {}
    for name, sql in QUERIES.items():
        runner.execute(sql)  # warmup
        t0 = time.perf_counter()
        runner.execute(sql)
        times[name] = time.perf_counter() - t0
    print(json.dumps(times))


def run_scan_bench() -> None:
    """`bench.py --scan`: the scan-ingest microbench.  Drains TPC-H lineitem
    through ScanOperator three ways and reports GB/s from the ScanIngestStats
    counters, so the ingest trajectory is tracked per round independently of
    the full-query bench:

    - ``legacy``:   the pre-PR synchronous path — string-materializing decode
                    (TRINO_TPU_TPCH_VECTOR_DECODE=0), no prefetch.  This is
                    the acceptance baseline.
    - ``sync``:     vectorized decode, synchronous scan (TRINO_TPU_PREFETCH=0).
    - ``prefetch``: vectorized decode + async prefetch/coalesce/staging.

    ``vs_baseline`` in the JSON is prefetch over legacy.  Note prefetch vs
    sync (``vs_sync``) only wins wall-clock when decode can overlap with
    something — on a single-core host with the now-cheap vectorized decode it
    hovers near 1.0; the ingest win lives in the decode itself and in
    transfer/compute overlap during real queries.

    Env knobs: BENCH_SCAN_SF (default 0.2), BENCH_SCAN_SPLITS (default 8),
    plus the TRINO_TPU_PREFETCH_* family."""
    from trino_tpu.connectors.catalog import default_catalog
    from trino_tpu.exec.operators import ScanOperator

    sf = float(os.environ.get("BENCH_SCAN_SF", "0.2"))
    n_splits = int(os.environ.get("BENCH_SCAN_SPLITS", "8"))

    def drain(tpch) -> tuple[float, "object"]:
        cols = tpch.get_table_schema("lineitem").column_names()
        splits = tpch.get_splits("lineitem", n_splits, 1)
        scan = ScanOperator(tpch, splits, cols)
        t0 = time.perf_counter()
        while not scan.is_finished():
            if scan.get_output() is None:
                break
        return time.perf_counter() - t0, scan.ingest_stats

    results = {}
    for mode, prefetch, vector in (("legacy", "0", "0"), ("sync", "0", "1"),
                                   ("prefetch", "1", "1")):
        os.environ["TRINO_TPU_PREFETCH"] = prefetch
        os.environ["TRINO_TPU_TPCH_VECTOR_DECODE"] = vector
        # fresh connector per leg: the decode flag is read at construction
        tpch = default_catalog(scale_factor=sf).connector("tpch")
        drain(tpch)  # warmup: dictionaries + code tables + jit caches
        wall, stats = drain(tpch)
        gbps = stats.scan_bytes / wall / 1e9
        results[mode] = (wall, gbps, stats)
        print(f"scan[{mode}]: {stats.scan_bytes/1e6:.1f} MB in "
              f"{wall*1e3:.1f} ms = {gbps:.2f} GB/s | {stats.text()}",
              file=sys.stderr)
    os.environ.pop("TRINO_TPU_TPCH_VECTOR_DECODE", None)

    st = results["prefetch"][2]
    print(json.dumps({
        "metric": f"scan_ingest_sf{sf:g}_gb_per_sec",
        "value": round(results["prefetch"][1], 3),
        "unit": "GB/s",
        "vs_baseline": round(results["prefetch"][1] / results["legacy"][1], 3),
        "vs_sync": round(results["prefetch"][1] / results["sync"][1], 3),
        "legacy_gb_per_sec": round(results["legacy"][1], 3),
        "sync_gb_per_sec": round(results["sync"][1], 3),
        "queue_depth_max": st.queue_depth_max,
        "queue_depth_avg": round(st.queue_depth_avg, 2),
        "coalesced_batches": st.coalesced_batches,
        "source_read_ms": round(st.source_read_s * 1e3, 1),
        "consumer_wait_ms": round(st.consumer_wait_s * 1e3, 1),
        "stage_ms": round(st.stage_s * 1e3, 1),
    }))


def run_ndv_bench() -> None:
    """`bench.py --ndv [1e3,1e4,...]`: the hash-vs-sort NDV-ladder bake-off
    behind the ROADMAP "Pallas hash build/probe — or a measured waiver" item.

    For each NDV rung, times the two hottest inner loops under every
    TRINO_TPU_HASH_IMPL implementation:

    - ``agg``:  group-id assignment + one segment-sum over int64 keys
                (the HashAggregationOperator inner loop).
    - ``join``: hash-table build + probe-ranges + total fetch
                (the LookupJoin build/probe inner loop).

    Implementations: ``sort`` (lexsort + searchsorted), ``pallas-interpret``
    (the open-addressing kernels as pure XLA — runs anywhere, NOT a TPU
    performance number), and ``pallas`` (compiled kernels — requires a real
    TPU backend and recorded as ``"skipped"`` otherwise; on a v5e the compiler
    refuses the kernels, README "Open-addressing hash build/probe").  Keys
    are drawn from a SPARSE 62-bit domain so the sort leg cannot sneak onto
    the dense direct-address join fast path.  Emits ONE JSON object with per-leg rows/s + GB/s.

    Env knobs: BENCH_ITERS (default 3), BENCH_NDV_ROWS (default 1e6),
    BENCH_NDV_INTERPRET_ROWS (default 2e5 — interpret mode executes the
    probe loops sequentially and would dominate wall time at full width)."""
    _device()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from trino_tpu.exec import join_exec as JX
    from trino_tpu.exec import kernels as K

    arg = ""
    i = sys.argv.index("--ndv")
    if i + 1 < len(sys.argv) and not sys.argv[i + 1].startswith("-"):
        arg = sys.argv[i + 1]
    ndvs = ([int(float(x)) for x in arg.split(",") if x]
            or [1_000, 10_000, 100_000, 1_000_000])

    iters = int(os.environ.get("BENCH_ITERS", "3"))
    full_rows = int(float(os.environ.get("BENCH_NDV_ROWS", "1e6")))
    interp_rows = int(float(os.environ.get("BENCH_NDV_INTERPRET_ROWS",
                                           "2e5")))
    on_tpu = jax.default_backend() == "tpu"
    impls = [
        ("sort", {"TRINO_TPU_HASH_IMPL": "sort"}, False),
        ("pallas-interpret",
         {"TRINO_TPU_HASH_IMPL": "pallas", "TRINO_TPU_HASH_INTERPRET": "1"},
         False),
        ("pallas", {"TRINO_TPU_HASH_IMPL": "pallas"}, True),  # needs TPU
    ]

    def timed(fn) -> float:
        fn()  # warmup: compile at this shape
        samples = []
        for _ in range(iters):
            t0 = time.perf_counter()
            fn()
            samples.append(time.perf_counter() - t0)
        samples.sort()
        return samples[len(samples) // 2]

    rng = np.random.default_rng(0)
    legs: list[dict] = []
    for ndv in ndvs:
        domain = rng.integers(0, 1 << 62, size=ndv, dtype=np.int64)
        for impl, env, needs_tpu in impls:
            n = interp_rows if impl == "pallas-interpret" else full_rows
            nb = max(n // 2, 1)
            if needs_tpu and not on_tpu:
                for leg in ("agg", "join"):
                    legs.append({"leg": leg, "impl": impl, "ndv": ndv,
                                 "status": "skipped",
                                 "reason": "no TPU backend"})
                continue
            for k in ("TRINO_TPU_HASH_IMPL", "TRINO_TPU_HASH_INTERPRET"):
                os.environ.pop(k, None)
            os.environ.update(env)
            jk = jnp.asarray(domain[rng.integers(0, ndv, size=n)])
            jv = jnp.asarray(rng.standard_normal(n))
            jbk = jnp.asarray(domain[rng.integers(0, ndv, size=nb)])
            jax.block_until_ready((jk, jv, jbk))

            def agg_leg():
                perm, gid, ng = K.group_ids_auto([(jk, None)], None)
                jax.block_until_ready(
                    jax.ops.segment_sum(jv[perm], gid, ng + 1))

            def join_leg():
                t = JX.build_table([(jbk, None)], num_rows=nb)
                _lo, _counts, total = JX.probe_ranges_device(
                    t, [(jk, None)], [None])
                total.get()

            for leg, fn, nbytes in (
                    ("agg", agg_leg, n * 16),
                    ("join", join_leg, (n + nb) * 8)):
                wall = timed(fn)
                row = {"leg": leg, "impl": impl, "ndv": ndv, "rows": n,
                       "wall_ms": round(wall * 1e3, 2),
                       "rows_per_s": round(n / wall),
                       "gb_per_s": round(nbytes / wall / 1e9, 3),
                       "status": "ok"}
                legs.append(row)
                print(f"ndv[{ndv}] {leg}/{impl}: {row['wall_ms']} ms = "
                      f"{row['rows_per_s']:,} rows/s", file=sys.stderr)
    for k in ("TRINO_TPU_HASH_IMPL", "TRINO_TPU_HASH_INTERPRET"):
        os.environ.pop(k, None)

    print(json.dumps({
        "metric": "hash_bakeoff_ndv",
        "unit": "rows/s",
        "backend": jax.default_backend(),
        "iters": iters,
        "legs": legs,
    }))


_JIT_COUNTER = {"on": False, "jit_calls": 0, "eager_binds": 0}
_REGION_TLS = None  # threading.local; armed per-thread so one task's stage
# region doesn't count another task's concurrent scan/feed launches


def _region_armed() -> bool:
    return _REGION_TLS is not None and getattr(_REGION_TLS, "depth", 0) > 0


def _install_jit_call_counter() -> None:
    """Count every Python->device dispatch: (a) wrap ``jax.jit`` so each call
    into a jitted callable is one program launch (installed BEFORE any
    trino_tpu import — module-level jitted kernels capture the wrapper at
    import time), and (b) patch ``jax.core.Primitive.bind`` so each EAGER op
    (the legacy flush path is lexsort/gather/segment-sum outside jit) counts
    too.  A cached jit call binds nothing (C++ fast path), so the two buckets
    don't double-count; trace-time binds are avoided by counting only
    pre-warmed runs.  This is the honest unit for "per-batch Python
    dispatch": each one is a Python->device launch, the thing that costs
    dispatch latency per batch on a real TPU."""
    import functools

    import jax

    orig_jit = jax.jit

    def counting_jit(fun=None, **kw):
        if fun is None:
            return lambda f: counting_jit(f, **kw)
        compiled = orig_jit(fun, **kw)

        @functools.wraps(fun)
        def dispatch(*a, **k):
            if _JIT_COUNTER["on"] or _region_armed():
                _JIT_COUNTER["jit_calls"] += 1
            return compiled(*a, **k)

        return dispatch

    jax.jit = counting_jit

    prim = jax.core.Primitive
    orig_bind = prim.bind

    def counting_bind(self, *a, **k):
        if _JIT_COUNTER["on"] or _region_armed():
            _JIT_COUNTER["eager_binds"] += 1
        return orig_bind(self, *a, **k)

    prim.bind = counting_bind


def _count_jit_dispatches(runner, sql: str) -> dict[str, int]:
    """One un-timed (pre-warmed) run with the dispatch counter armed: total
    Python->device launches (jitted-program calls + eager primitive binds)
    for the whole query.  The scan / feed side is identical in both legs, so
    including it only DILUTES the fused-vs-legacy ratio — the headline
    number is conservative."""
    _JIT_COUNTER["jit_calls"] = 0
    _JIT_COUNTER["eager_binds"] = 0
    _JIT_COUNTER["on"] = True
    try:
        runner.execute(sql)
    finally:
        _JIT_COUNTER["on"] = False
    return {"jit_calls": _JIT_COUNTER["jit_calls"],
            "eager_binds": _JIT_COUNTER["eager_binds"],
            "total": _JIT_COUNTER["jit_calls"] + _JIT_COUNTER["eager_binds"]}


def _count_stage_dispatches(runner, sql: str) -> tuple[dict[str, int], int]:
    """One un-timed (pre-warmed) run with the stage-region operators wrapped
    by counting shims.  Returns (operator-method counts, region device
    dispatches): every Python-level ``add_input``/``get_output`` crossing of
    the PARTIAL->shuffle->FINAL region is one operator dispatch, and the
    launch counter is armed ONLY while a region operator method is on the
    stack, so the region launch total excludes the scan/feed side that both
    legs share.  Filter/project is tallied but NEVER armed — the chain's
    filter/project work runs INSIDE the fused program (fully counted there)
    while the legacy leg's equivalent jit call is excluded, which biases the
    comparison AGAINST the fused path."""
    import threading

    import trino_tpu.exec.operators as O
    import trino_tpu.execution.collective_exchange as CE
    import trino_tpu.execution.plan_compiler as PC
    import trino_tpu.execution.stage_compiler as SC

    global _REGION_TLS
    _REGION_TLS = threading.local()
    tls = _REGION_TLS
    counts: dict[str, int] = {}
    targets = [
        (O.FilterProjectOperator, "add_input", "filter_project", False),
        (O.HashAggregationOperator, "add_input", "hash_agg", True),
        (O.HashAggregationOperator, "get_output", "hash_agg", True),
        (O.HashAggregationOperator, "finish_input", None, True),
        (CE.CollectiveOutputSink, "add_input", "exchange", True),
        (CE.CollectiveOutputSink, "finish_input", None, True),
        (CE.CollectiveSourceOperator, "get_output", "exchange", True),
        (SC.FusedStageSinkOperator, "add_input", "fused_sink", True),
        (SC.FusedStageSinkOperator, "finish_input", None, True),
        (SC.FusedStageSourceOperator, "get_output", "fused_source", True),
        (PC.ResidentPlanSinkOperator, "add_input", "resident_sink", True),
        (PC.ResidentPlanSinkOperator, "finish_input", None, True),
        (PC.ResidentBuildSinkOperator, "add_input", "resident_build", True),
        (PC.ResidentBuildSinkOperator, "finish_input", None, True),
    ]
    saved = []
    for cls, meth, label, arm in targets:
        orig = getattr(cls, meth)

        def shim(self, *a, _orig=orig, _label=label, _arm=arm, **k):
            if _label is not None:
                counts[_label] = counts.get(_label, 0) + 1
            if not _arm:
                return _orig(self, *a, **k)
            tls.depth = getattr(tls, "depth", 0) + 1
            try:
                return _orig(self, *a, **k)
            finally:
                tls.depth -= 1

        saved.append((cls, meth, orig))
        setattr(cls, meth, shim)
    _JIT_COUNTER["jit_calls"] = 0
    _JIT_COUNTER["eager_binds"] = 0
    try:
        runner.execute(sql)
    finally:
        _REGION_TLS = None
        for cls, meth, orig in saved:
            setattr(cls, meth, orig)
    region_launches = _JIT_COUNTER["jit_calls"] + _JIT_COUNTER["eager_binds"]
    return counts, region_launches


def run_fused_bench() -> None:
    """`bench.py --fused`: whole-query resident compilation vs whole-stage
    compilation vs the legacy per-operator + collective-exchange path
    (TRINO_TPU_RESIDENT_PLAN / TRINO_TPU_FUSED_STAGE) on the 8-device CPU
    mesh, plus a mesh-width scaling curve (1/2/4/8 host-platform devices)
    for the fully-resident q3.  Per query: median wall, input rows/s,
    program compile count + shape-bucket cache hit rate, and the per-batch
    Python dispatch counts of the stage region; results land in
    BENCH_r17.json.  Env knobs: BENCH_FUSED_SF (default 0.1),
    BENCH_FUSED_WORKERS (default 4), BENCH_ITERS (default 3)."""
    if os.environ.get("BENCH_FUSED_INNER") != "1":
        # the mesh needs --xla_force_host_platform_device_count before jax
        # imports; re-exec in a subprocess (same pattern as --baseline)
        base_xla = os.environ.get("XLA_FLAGS", "")

        def inner(n_dev: int, extra_env: dict) -> dict:
            xla = (base_xla
                   + f" --xla_force_host_platform_device_count={n_dev}"
                   ).strip()
            env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=xla,
                       BENCH_FUSED_INNER="1", **extra_env)
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--fused"],
                env=env, capture_output=True, text=True, timeout=7200)
            if proc.stderr:
                print(proc.stderr[-4000:], file=sys.stderr)
            if proc.returncode != 0:
                raise SystemExit("fused bench inner run failed")
            return json.loads(proc.stdout.strip().splitlines()[-1])

        data = inner(8, {})
        data["harness"] = ("CPU harness: every leg is a JAX_PLATFORMS=cpu "
                           "child on forced host-platform devices")
        # mesh-width scaling: one subprocess per width so the forced
        # host-platform device count (and the mesh it bounds) matches
        data["mesh_scaling"] = {
            str(w): inner(w, {"BENCH_FUSED_SCALE_WIDTH": str(w),
                              "BENCH_FUSED_WORKERS": str(w)})
            for w in (1, 2, 4, 8)}
        line = json.dumps(data)
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_r17.json")
        with open(path, "w") as f:
            f.write(line + "\n")
        print(line)
        return

    if os.environ.get("BENCH_FUSED_SCALE_WIDTH"):
        _run_fused_scale_leg()
        return

    sf = float(os.environ.get("BENCH_FUSED_SF", "0.1"))
    iters = int(os.environ.get("BENCH_ITERS", "3"))
    workers = int(os.environ.get("BENCH_FUSED_WORKERS", "4"))
    # the A/B re-executes identical statements: a served cached result
    # would measure the PR 12 result cache, not the execution legs
    os.environ["TRINO_TPU_RESULT_CACHE"] = "0"
    import jax

    _install_jit_call_counter()  # must precede the trino_tpu imports

    from trino_tpu.connectors.catalog import default_catalog
    from trino_tpu.exec.stats import FusedStageStats, ResidentPlanStats
    from trino_tpu.execution.distributed_runner import DistributedQueryRunner
    from trino_tpu.execution.plan_compiler import ResidentPlanExec
    from trino_tpu.runner import Session

    # tpch connector directly (NOT the consolidated memory tables): the
    # per-batch dispatch story needs the natural multi-batch scan stream,
    # and both legs read the identical stream so the A/B stays fair
    catalog = default_catalog(scale_factor=sf)
    runner = DistributedQueryRunner(
        catalog, worker_count=workers, session=Session(node_count=workers))

    import trino_tpu.exec.operators as O

    # four legs: resident (whole-QUERY compilation — joins inlined), fused
    # (PR 6 whole-stage seam only), the default legacy path (which BUFFERS
    # a task's whole input and aggregates once — per-TASK amortization the
    # CPU mesh can afford), and the legacy path with a memory-bounded flush
    # window sized to the batch bucket (the streaming regime a device-
    # resident stage actually runs in: HBM cannot buffer a task's whole
    # input, so PARTIAL flushes per window — this is the per-batch dispatch
    # regime whole-stage/whole-query compilation eliminates)
    stream_flush = 1 << 15
    modes = (("resident", "auto", "auto", None),
             ("fused", "auto", "0", None),
             ("legacy", "0", "0", None),
             ("legacy_streaming", "0", "0", stream_flush))
    queries: dict[str, dict] = {}
    for name, sql in QUERIES.items():
        rows, _ = _scan_stats(runner, sql)
        per_mode: dict[str, dict] = {}
        for mode, env_val, resident_val, flush_rows in modes:
            os.environ["TRINO_TPU_FUSED_STAGE"] = env_val
            os.environ["TRINO_TPU_RESIDENT_PLAN"] = resident_val
            default_flush = O.HashAggregationOperator.FLUSH_ROWS
            if flush_rows is not None:
                O.HashAggregationOperator.FLUSH_ROWS = flush_rows
            try:
                runner.execute(sql)  # warmup: compile every program
                samples = []
                for _ in range(iters):
                    t0 = time.perf_counter()
                    runner.execute(sql)
                    samples.append(time.perf_counter() - t0)
                samples.sort()
                wall = samples[len(samples) // 2]
                counts, region_launches = _count_stage_dispatches(runner, sql)
                launches = _count_jit_dispatches(runner, sql)
            finally:
                O.HashAggregationOperator.FLUSH_ROWS = default_flush
            region = {k: v for k, v in counts.items()
                      if k != "filter_project"}
            entry = {
                "wall_ms": round(wall * 1e3, 1),
                "input_rows_per_sec": round(rows / wall),
                "region_device_dispatches": region_launches,
                "query_device_dispatches": launches["total"],
                "stage_dispatches": sum(region.values()),
                "dispatch_detail": counts,
            }
            if flush_rows is not None:
                entry["flush_rows"] = flush_rows
            if mode == "resident":
                rroll = ResidentPlanStats()
                for ex in runner._resident_edges.values():
                    if isinstance(ex, ResidentPlanExec):
                        rroll.merge(ex.rstats)
                entry["resident_plans"] = rroll.plans
                if rroll.plans:
                    # the whole point: the entire join tree + agg is ONE
                    # jitted dispatch per probe batch
                    entry.update({
                        "batches": rroll.batches,
                        "jit_calls": rroll.jit_calls,
                        "seams_fused": rroll.seams,
                        "seam_merges": rroll.merges,
                        "code_seam_columns": rroll.code_seam_columns,
                        "launches_per_batch": round(
                            rroll.launches_per_batch, 2),
                    })
            if mode == "fused":
                assert runner._fused_edges, \
                    f"{name}: expected a fused stage seam"
                roll = FusedStageStats()
                for ex in runner._fused_edges.values():
                    roll.merge(ex.stats)
                entry.update({
                    "batches": roll.batches,
                    "jit_calls": roll.jit_calls,
                    "compiles": roll.compiles,
                    "cache_hits": roll.cache_hits,
                    "cache_hit_rate": round(
                        roll.cache_hits / roll.jit_calls, 3)
                    if roll.jit_calls else 0.0,
                    "seam_merges": roll.merges,
                    # the whole point: ONE jitted call per input batch
                    "dispatches_per_batch": round(
                        (roll.jit_calls + roll.merges)
                        / max(roll.batches, 1), 2),
                })
            per_mode[mode] = entry
            print(f"{name}[{mode}]: {entry['wall_ms']} ms, "
                  f"{entry['input_rows_per_sec']:,} rows/s, "
                  f"{entry['stage_dispatches']} stage dispatches",
                  file=sys.stderr)
        os.environ.pop("TRINO_TPU_FUSED_STAGE", None)
        os.environ.pop("TRINO_TPU_RESIDENT_PLAN", None)
        fused = per_mode["fused"]
        batches = max(fused.get("batches", 1), 1)
        # per-batch normalization over the input batches the stage absorbed
        # (the batch stream is identical in every leg).  The region launch
        # count is armed only inside stage-region operator methods, with the
        # legacy chain's filter/project jit call EXCLUDED (it runs inside
        # the fused program, which is fully counted) — both choices bias
        # against the fused path, so the ratios are underestimates.
        res_batches = max(per_mode["resident"].get("batches", batches), 1)
        for m, b in (("resident", res_batches), ("fused", batches),
                     ("legacy", batches), ("legacy_streaming", batches)):
            per_mode[m]["region_dispatches_per_batch"] = round(
                per_mode[m]["region_device_dispatches"] / b, 2)
        fused_r = max(fused["region_device_dispatches"], 1)
        per_mode["dispatch_reduction"] = round(
            per_mode["legacy_streaming"]["region_device_dispatches"]
            / fused_r, 2)
        per_mode["dispatch_reduction_vs_buffered"] = round(
            per_mode["legacy"]["region_device_dispatches"] / fused_r, 2)
        if per_mode["resident"].get("resident_plans"):
            # the resident region ALSO covers the inlined joins, which the
            # other legs run un-armed on the operator pipeline — the ratio
            # still undercounts the resident win
            res_r = max(per_mode["resident"]["region_device_dispatches"], 1)
            per_mode["resident_dispatch_reduction"] = round(
                per_mode["legacy_streaming"]["region_device_dispatches"]
                / res_r, 2)
        queries[name] = per_mode

    print(json.dumps({
        "metric": f"resident_plan_sf{sf:g}",
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "workers": workers,
        "iters": iters,
        "queries": queries,
    }))


def _run_fused_scale_leg() -> None:
    """One mesh-width point of the scaling curve: q3 fully resident on a
    BENCH_FUSED_SCALE_WIDTH-task mesh (the forced host-platform device
    count matches, so the mesh is exactly that wide).  Width 1 has no
    collectives — the resident plan is ineligible there and the point
    records the serial baseline."""
    width = int(os.environ["BENCH_FUSED_SCALE_WIDTH"])
    sf = float(os.environ.get("BENCH_FUSED_SF", "0.1"))
    iters = int(os.environ.get("BENCH_ITERS", "3"))
    os.environ["TRINO_TPU_RESULT_CACHE"] = "0"
    import jax

    from trino_tpu.connectors.catalog import default_catalog
    from trino_tpu.exec.stats import ResidentPlanStats
    from trino_tpu.execution.distributed_runner import DistributedQueryRunner
    from trino_tpu.execution.plan_compiler import ResidentPlanExec
    from trino_tpu.runner import Session

    os.environ["TRINO_TPU_RESIDENT_PLAN"] = "auto"
    catalog = default_catalog(scale_factor=sf)
    runner = DistributedQueryRunner(
        catalog, worker_count=width, session=Session(node_count=width))
    sql = QUERIES["q3"]
    rows, _ = _scan_stats(runner, sql)
    runner.execute(sql)  # warmup: compile every program for this width
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        runner.execute(sql)
        samples.append(time.perf_counter() - t0)
    samples.sort()
    wall = samples[len(samples) // 2]
    roll = ResidentPlanStats()
    for ex in runner._resident_edges.values():
        if isinstance(ex, ResidentPlanExec):
            roll.merge(ex.rstats)
    out = {
        "devices": len(jax.devices()),
        "workers": width,
        "wall_ms": round(wall * 1e3, 1),
        "input_rows_per_sec": round(rows / wall),
        "resident_plans": roll.plans,
    }
    if roll.plans:
        out.update({
            "batches": roll.batches,
            "jit_calls": roll.jit_calls,
            "launches_per_batch": round(roll.launches_per_batch, 2),
        })
    print(json.dumps(out))


def run_profile_bench() -> None:
    """``--profile``: run Q1 through the engine with the flight recorder on
    and dump the merged Chrome trace (open in Perfetto / chrome://tracing).
    BENCH_PROFILE_OUT sets the output path; BENCH_PROFILE_FULL=1 switches
    to TRINO_TPU_PROFILE=full device-time attribution."""
    sf = float(os.environ.get("BENCH_SF", "0.1"))
    out_path = os.environ.get("BENCH_PROFILE_OUT", "/tmp/trino_tpu_trace.json")
    _device()

    from trino_tpu.runner import Session, StandaloneQueryRunner
    from trino_tpu.telemetry import profiler

    prev = None
    if os.environ.get("BENCH_PROFILE_FULL", "") == "1":
        prev = profiler.set_level(2)
    catalog = _stage_memory_tables(sf)
    runner = StandaloneQueryRunner(
        catalog, session=Session(default_catalog="memory", splits_per_node=1))
    runner.execute(Q1, query_id="bench_warm")  # warm compile caches
    t0 = time.perf_counter()
    runner.execute(Q1, query_id="bench_profile")
    wall_s = time.perf_counter() - t0
    trace = runner.profile("bench_profile")
    if prev is not None:
        profiler.set_level(prev)
    assert trace is not None, "profiler produced no trace"
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump(trace, f)
    by_cat: dict[str, int] = {}
    for ev in trace["traceEvents"]:
        if ev.get("ph") == "X":
            by_cat[ev["cat"]] = by_cat.get(ev["cat"], 0) + 1
    print(json.dumps({
        "metric": f"profile_sf{sf:g}",
        "wall_ms": round(wall_s * 1e3, 1),
        "trace_path": out_path,
        "events": sum(by_cat.values()),
        "events_by_cat": by_cat,
        "full_mode": prev is not None,
    }))


# ----------------------------------------------------- compressed execution

ENCODED_QUERIES = {
    # every group key is a dictionary column: encoded execution group-bys on
    # int32 codes; the decode-off leg hashes materialized python strings
    # min/max over the wide-vocabulary comment column run as int32 code
    # comparisons (the connector's dictionaries are sorted, so code order IS
    # lexical order); the decode-off leg compares materialized strings
    "dict_groupby": """
select l_returnflag, l_linestatus, count(*), sum(l_quantity),
       min(l_comment), max(l_comment)
from lineitem group by l_returnflag, l_linestatus""",
    # low-selectivity filter over wide payload: the mask computes from
    # l_orderkey alone, payload columns stage LAZY and are dropped unread
    # for every batch with zero survivors.  The modulo keeps the predicate
    # out of the scan's advisory TupleDomain (planner/domains.py would push
    # a plain equality into the connector and prune the scan itself, which
    # benchmarks pushdown, not late materialization).
    "lazy_filter": """
select l_extendedprice, l_discount, l_tax, l_comment
from lineitem where l_orderkey % 1000000000 = 1""",
}


def run_encoded_leg() -> None:
    """``--encoded-leg``: one leg of the compressed-execution ladder, run in
    a fresh interpreter (TRINO_TPU_TPCH_VECTOR_DECODE is read at connector
    construction, so legs cannot share a process).  Prints one JSON object
    keyed by query with wall time, rows/s, and staged-bytes accounting from
    the trino_scan_* / trino_encoding_* registry deltas."""
    sf = float(os.environ.get("BENCH_SF", "0.2"))
    iters = int(os.environ.get("BENCH_ITERS", "3"))
    # measure execution, not the cache plane: a Tier C hit would serve the
    # repeat submissions without ever touching the encoded operators
    os.environ["TRINO_TPU_PLAN_CACHE"] = "0"
    os.environ["TRINO_TPU_RESULT_CACHE"] = "0"
    _device()

    import jax

    import trino_tpu.exec.operators as ops
    from trino_tpu.connectors.catalog import default_catalog
    from trino_tpu.runner import Session, StandaloneQueryRunner
    from trino_tpu.telemetry.metrics import REGISTRY

    # track the peak host-resident batch crossing a bucketing boundary
    # (LAZY columns count only once materialized — their bytes are exactly
    # what late materialization keeps off the device)
    peak = {"v": 0}
    orig_pad = ops.pad_to_bucket

    def pad_spy(batch):
        out = orig_pad(batch)
        resident = sum(c.nbytes for c in out.columns
                       if c.encoding != "LAZY" or c.is_materialized)
        peak["v"] = max(peak["v"], resident)
        return out

    ops.pad_to_bucket = pad_spy

    # many small splits -> many scan batches: late materialization drops
    # payload at batch granularity, so batch count is the lazy resolution
    splits = int(os.environ.get("BENCH_ENCODED_SPLITS", "32"))
    runner = StandaloneQueryRunner(
        default_catalog(scale_factor=sf),
        session=Session(splits_per_node=splits))

    def snap() -> dict:
        s = REGISTRY.snapshot()
        return {k: s[k]["value"] for k in (
            "trino_scan_bytes_total",
            "trino_encoding_bytes_saved_total",
            "trino_encoding_lazy_skipped_bytes_total",
            "trino_encoding_lazy_materialized_bytes_total",
            "trino_encoding_lazy_columns_total",
            "trino_encoding_lazy_materialized_total",
            "trino_encoding_rle_agg_rows_total",
        )}

    out: dict[str, dict] = {}
    for name, sql in ENCODED_QUERIES.items():
        input_rows, _ = _scan_stats(runner, sql)
        runner.execute(sql)  # warmup: compile every jitted program
        peak["v"] = 0
        before = snap()
        samples = []
        for _ in range(iters):
            t0 = time.perf_counter()
            r = runner.execute(sql)
            for c in r.batch.columns:
                jax.block_until_ready(c.data)
            samples.append(time.perf_counter() - t0)
        delta = {k: (v - before[k]) / iters for k, v in snap().items()}
        samples.sort()
        wall = samples[len(samples) // 2]
        scan_b = delta["trino_scan_bytes_total"]
        # deferred = bytes that never moved: RLE/dict shrinkage plus lazy
        # deferrals, minus the lazy thunks a surviving row forced to run
        deferred = (delta["trino_encoding_bytes_saved_total"]
                    + delta["trino_encoding_lazy_skipped_bytes_total"]
                    - delta["trino_encoding_lazy_materialized_bytes_total"])
        out[name] = {
            "wall_ms": round(wall * 1e3, 1),
            "input_rows_per_sec": round(input_rows / wall),
            "scan_bytes": round(scan_b),
            "staged_bytes": round(scan_b - deferred),
            "deferred_bytes": round(deferred),
            # payload view: bytes the filter COULD have skipped (all
            # lazy-staged columns) vs the part survivor batches forced in
            "lazy_payload_bytes": round(
                delta["trino_encoding_lazy_skipped_bytes_total"]),
            "lazy_payload_staged_bytes": round(
                delta["trino_encoding_lazy_materialized_bytes_total"]),
            "peak_batch_bytes": peak["v"],
            "lazy_columns": delta["trino_encoding_lazy_columns_total"],
            "lazy_materialized":
                delta["trino_encoding_lazy_materialized_total"],
            "rle_agg_rows": delta["trino_encoding_rle_agg_rows_total"],
        }
    print(json.dumps(out))


def run_encoded_bench() -> None:
    """``bench.py --encoded``: the compressed-execution ladder (PR 16).
    Three legs, each a fresh interpreter over the sf-scaled TPC-H connector:

    - **encoded** — TRINO_TPU_ENCODED_EXEC=1: dictionary codes, RLE runs and
      lazy payload columns flow end-to-end.
    - **legacy** — TRINO_TPU_ENCODED_EXEC=0: same vectorized connector, but
      every batch expands at the scan boundary (the bit-for-bit oracle leg).
    - **legacy_decode_off** — additionally TRINO_TPU_TPCH_VECTOR_DECODE=0:
      the string-materializing row decoder, i.e. execution with no
      dictionary anywhere (what a row-oriented engine would stage).

    Acceptance: >=2x rows/s on the dictionary-heavy group-by vs the decoded
    legacy, and the low-selectivity filter stages <10% of the payload bytes
    the legacy leg stages (>=5x staged-bytes reduction).  Writes
    BENCH_r16.json.  Env knobs: BENCH_SF (default 0.2), BENCH_ITERS (3)."""
    sf = float(os.environ.get("BENCH_SF", "0.2"))
    legs = {
        "encoded": {"TRINO_TPU_ENCODED_EXEC": "1"},
        "legacy": {"TRINO_TPU_ENCODED_EXEC": "0"},
        "legacy_decode_off": {"TRINO_TPU_ENCODED_EXEC": "0",
                              "TRINO_TPU_TPCH_VECTOR_DECODE": "0"},
    }
    results: dict[str, dict] = {}
    for leg, env_over in legs.items():
        env = dict(os.environ, **env_over)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--encoded-leg"],
            env=env, capture_output=True, text=True, timeout=7200)
        if proc.returncode != 0:
            raise SystemExit(
                f"encoded bench leg {leg!r} failed:\n{proc.stderr[-4000:]}")
        results[leg] = json.loads(proc.stdout.strip().splitlines()[-1])
        print(f"leg {leg}: " + ", ".join(
            f"{q} {r['wall_ms']}ms ({r['input_rows_per_sec']} rows/s, "
            f"{r['staged_bytes'] / 1e6:.2f} MB staged)"
            for q, r in results[leg].items()), file=sys.stderr)

    gb_enc = results["encoded"]["dict_groupby"]
    gb_leg = results["legacy"]["dict_groupby"]
    gb_str = results["legacy_decode_off"]["dict_groupby"]
    lf_enc = results["encoded"]["lazy_filter"]
    lf_leg = results["legacy"]["lazy_filter"]
    # the legacy leg stages every payload byte; encoded stages only the
    # columns of batches that had a surviving row
    payload = max(lf_enc["lazy_payload_bytes"], 1)
    payload_staged = lf_enc["lazy_payload_staged_bytes"]
    staged_frac = payload_staged / payload
    summary = {
        "dict_groupby_speedup_vs_legacy": round(
            gb_enc["input_rows_per_sec"] / gb_leg["input_rows_per_sec"], 2),
        "dict_groupby_speedup_vs_decode_legacy": round(
            gb_enc["input_rows_per_sec"] / gb_str["input_rows_per_sec"], 2),
        "lazy_filter_payload_staged_fraction": round(staged_frac, 4),
        "lazy_filter_staged_bytes_reduction": round(1 / max(
            staged_frac, 1e-9), 1),
        "lazy_filter_total_staged_vs_legacy": round(
            lf_enc["staged_bytes"] / max(lf_leg["staged_bytes"], 1), 4),
        "lazy_filter_peak_batch_reduction": round(
            lf_leg["peak_batch_bytes"] / max(lf_enc["peak_batch_bytes"], 1),
            2),
    }
    result = {
        "metric": f"encoded_exec_sf{sf:g}",
        "iters": int(os.environ.get("BENCH_ITERS", "3")),
        "legs": results,
        "summary": summary,
        "acceptance": {
            "dict_groupby_2x": summary[
                "dict_groupby_speedup_vs_decode_legacy"] >= 2.0,
            "lazy_filter_staged_under_10pct": staged_frac < 0.10,
            "lazy_filter_5x_reduction": summary[
                "lazy_filter_staged_bytes_reduction"] >= 5.0,
        },
    }
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_r16.json"), "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
    print(json.dumps(result))


def main() -> None:
    if "--baseline" in sys.argv:
        run_baseline()
        return
    if "--profile" in sys.argv:
        run_profile_bench()
        return
    if "--scan" in sys.argv:
        run_scan_bench()
        return
    if "--ndv" in sys.argv:
        run_ndv_bench()
        return
    if "--fused" in sys.argv:
        run_fused_bench()
        return
    if "--qps" in sys.argv:
        run_qps_bench()
        return
    if "--chaos-fte" in sys.argv:
        run_fte_chaos_bench()
        return
    if "--ha" in sys.argv:
        run_ha_bench()
        return
    if "--chaos" in sys.argv:
        run_chaos_bench()
        run_fte_chaos_bench()
        return
    if "--warm" in sys.argv:
        run_warm_bench()
        return
    if "--adaptive" in sys.argv:
        run_adaptive_bench()
        return
    if "--hbo" in sys.argv:
        run_hbo_bench()
        return
    if "--encoded-leg" in sys.argv:
        run_encoded_leg()
        return
    if "--encoded" in sys.argv:
        run_encoded_bench()
        return

    sf = float(os.environ.get("BENCH_SF", "2"))
    iters = int(os.environ.get("BENCH_ITERS", "3"))
    device = _device()
    if device["device_kind"] not in HBM_PEAK_BYTES_PER_SEC:
        raise SystemExit(
            f"bench: no HBM peak on record for device_kind "
            f"{device['device_kind']!r} (known: "
            f"{sorted(HBM_PEAK_BYTES_PER_SEC)}); add it with its source")
    hbm_peak = HBM_PEAK_BYTES_PER_SEC[device["device_kind"]]

    from trino_tpu.exec import syncguard
    from trino_tpu.runner import Session, StandaloneQueryRunner

    catalog = _stage_memory_tables(sf)
    runner = StandaloneQueryRunner(
        catalog, session=Session(default_catalog="memory", splits_per_node=1))

    sync_before = syncguard.snapshot()
    times = _time_queries(runner, iters)
    sync = syncguard.take_delta(sync_before)
    chips = device["device_count"]
    per_query: dict[str, dict] = {}
    total_rows = total_bytes = 0.0
    for name, sql in QUERIES.items():
        r, b = _scan_stats(runner, sql)
        total_rows += r
        total_bytes += b
        per_query[name] = {
            "wall_ms": round(times[name] * 1e3, 1),
            "input_rows_per_sec": round(r / times[name]),
            "input_rows_per_sec_per_chip": round(r / times[name] / chips),
            "scan_gb_per_sec": round(b / times[name] / 1e9, 3),
        }
    total_time = sum(times.values())
    rows_per_sec = total_rows / total_time
    bytes_per_sec = total_bytes / total_time

    # the tables are pinned on ONE device, so one chip's peak is the bound
    sane = bytes_per_sec <= hbm_peak
    print(
        f"sanity: scanned {total_bytes/1e6:.1f} MB in {total_time*1e3:.1f} ms "
        f"= {bytes_per_sec/1e9:.2f} GB/s on {device['device_kind']} vs HBM "
        f"peak {hbm_peak/1e9:.0f} GB/s -> "
        f"{'OK' if sane else 'EXCEEDS HARDWARE — MEASUREMENT REJECTED'}",
        file=sys.stderr)
    if not sane:
        raise SystemExit("bench measurement exceeds hardware bandwidth")

    vs_baseline = 0.0
    if os.environ.get("BENCH_SKIP_BASELINE", "0") != "1":
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--baseline"],
            env=env, capture_output=True, text=True, timeout=7200)
        if proc.returncode != 0:
            raise SystemExit(
                f"bench: --baseline child failed rc={proc.returncode}:\n"
                f"{proc.stderr[-2000:]}")
        base = json.loads(proc.stdout.strip().splitlines()[-1])
        base_total = sum(base[q] for q in QUERIES)
        vs_baseline = base_total / total_time
        print(f"baseline (CPU harness: engine on "
              f"{os.environ.get('BENCH_BASELINE_WORKERS', '8')} in-process "
              f"workers in a JAX_PLATFORMS=cpu child): {base} -> speedup "
              f"{vs_baseline:.2f}x", file=sys.stderr)

    from trino_tpu.telemetry.metrics import REGISTRY

    result = {
        "metric": f"tpch_q1_q3_engine_sf{sf:g}_input_rows_per_sec",
        "value": round(rows_per_sec),
        "unit": "rows/s",
        "vs_baseline": round(vs_baseline, 3),
        "platform": device["platform"],
        "device_kind": device["device_kind"],
        "device_count": chips,
        "per_query_ms": {q: round(t * 1e3, 1) for q, t in times.items()},
        "per_query": per_query,
        "scan_gb_per_sec": round(bytes_per_sec / 1e9, 3),
        "input_rows_per_sec_per_chip": round(rows_per_sec / chips),
        # host-transfer counters over the timed region (exec/syncguard.py):
        # the sync-free contract makes these flat in batch count
        "host_syncs": sync.host_syncs,
        "blocking_syncs": sync.blocking_syncs,
        "hot_loop_syncs": sync.hot_loop_syncs,
        "expand_overflows": sync.expand_overflows,
        # full process-wide metrics registry (telemetry/metrics.py): the
        # same snapshot /v1/metrics serves, archived with the bench run
        "metrics": REGISTRY.snapshot(),
    }
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_r07.json"), "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
    print(json.dumps(result))


if __name__ == "__main__":
    main()
