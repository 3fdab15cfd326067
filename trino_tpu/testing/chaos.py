"""Randomized fault-injection soak harness ("chaos certification").

Seeded scenario generator that drives the engine-level FailureInjector
(TASK_FAILURE / TASK_STALL / TASK_OOM / GET_RESULTS_FAILURE /
PROCESS_EXIT) plus live coordinator-driven drains under a sustained
TPC-H query mix, and checks the invariant the resilience plane promises:

    every query either returns oracle-correct rows (possibly after a
    classified retry under retry_policy=QUERY), or fails fast with a
    correctly classified USER / unretryable error.  Nothing hangs.

Scenarios are a pure function of ``(base_seed, scenario_index)`` —
``random.Random(seed)`` picks the SQL, the fault kind, the target task
and the drain victims — so any failing scenario replays exactly from
its seed.  Two modes:

- ``inproc``  : DistributedQueryRunner (threads), cheap; covers the
  in-process injection sites, speculation and logical drain/restore.
- ``process`` : ProcessDistributedQueryRunner (real worker processes),
  expensive; adds PROCESS_EXIT hard-kills and real PUT /v1/shutdown
  drains with worker replacement mid-query.

Every query runs under a watchdog thread: a query that neither returns
nor raises within the budget is recorded as outcome="hang" (the soak's
acceptance gate requires zero of those).

Entry points: ``run_scenario`` (one seeded scenario) and ``run_chaos``
(the full soak; ``bench.py --chaos`` wraps it and writes BENCH_r09.json).
"""

from __future__ import annotations

import random
import threading
import time
from typing import Optional

from ..connectors.catalog import default_catalog
from ..execution.distributed_runner import DistributedQueryRunner
from ..execution.failure_injector import (
    GET_RESULTS_FAILURE,
    PROCESS_EXIT,
    SPOOL_CORRUPTION,
    TASK_FAILURE,
    TASK_OOM,
    TASK_STALL,
    FailureInjector,
)
from ..runner import Session
from .oracle import SqliteOracle, assert_same_rows

__all__ = ["QUERY_MIX", "USER_ERROR_SQL", "build_expected",
           "run_scenario", "run_chaos", "run_fte_scenario", "run_fte_chaos",
           "run_coordinator_kill_drill", "run_ha_takeover_drill"]

CATALOG_SPEC = {
    "factory": "trino_tpu.connectors.catalog:default_catalog",
    "kwargs": {"scale_factor": 0.01},
}

_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
}

# carried in every result this module returns: the harnesses here start
# worker/coordinator processes, and a chip belongs to one process, so those
# children are forced onto the CPU backend — nothing here is a device number
CPU_HARNESS = ("CPU harness: spawned worker/coordinator processes run under "
               "JAX_PLATFORMS=cpu")

_TABLES = ["customer", "orders", "lineitem"]

# Sustained mix: scans, multi-key aggregation, filtered join — all
# checkable against the sqlite oracle with an unordered row compare.
QUERY_MIX = [
    "select count(*) from lineitem",
    "select l_returnflag, l_linestatus, count(*), sum(l_quantity) "
    "from lineitem group by l_returnflag, l_linestatus "
    "order by l_returnflag, l_linestatus",
    "select o_orderstatus, count(*), sum(o_totalprice) from orders "
    "group by o_orderstatus order by o_orderstatus",
    "select c_mktsegment, count(*), sum(c_acctbal) from customer "
    "group by c_mktsegment order by c_mktsegment",
    "select o_orderpriority, count(*) from orders, customer "
    "where o_custkey = c_custkey and c_mktsegment = 'BUILDING' "
    "group by o_orderpriority order by o_orderpriority",
    "select count(*), sum(o_totalprice) from orders "
    "where o_totalprice > 100000",
]

# USER-classified error: must fail fast with ZERO retries even while
# faults are being injected around it.
USER_ERROR_SQL = \
    "select o_orderkey / (o_orderkey - o_orderkey) from orders"

# Fault menu per mode.  "none" keeps a healthy baseline inside every
# scenario; "drain" is a live coordinator-driven drain mid-query.
_INPROC_FAULTS = ["none", "none", TASK_FAILURE, TASK_STALL, TASK_OOM,
                  GET_RESULTS_FAILURE, "drain"]
_PROCESS_FAULTS = _INPROC_FAULTS + [PROCESS_EXIT]
# FTE (retry_policy=TASK) leg: the streaming menu minus drains (FTE's
# stage-by-stage loop has no placement to drain in-process) plus
# SPOOL_CORRUPTION — a byte flipped inside a committed spool part file
# right before a consumer reads it, which must surface as a CRC-classified
# SpoolCorruptionError and re-execute only the corrupted producer
_FTE_FAULTS = ["none", "none", TASK_FAILURE, TASK_STALL, TASK_OOM,
               GET_RESULTS_FAILURE, SPOOL_CORRUPTION]


def build_expected() -> dict:
    """Oracle rows for every SQL in QUERY_MIX (computed once per soak —
    expected rows are a pure function of the sf=0.01 dataset)."""
    catalog = default_catalog(scale_factor=0.01)
    conn = catalog.connector("tpch")
    oracle = SqliteOracle()
    for t in _TABLES:
        cols = conn.get_table_schema(t).column_names()
        batches = []
        for s in conn.get_splits(t, 2, 1):
            src = conn.create_page_source(s, cols)
            while not src.is_finished():
                b = src.get_next_batch()
                if b is not None:
                    batches.append(b)
        oracle.load_table(t, batches)
    return {sql: oracle.query(sql) for sql in QUERY_MIX}


def _execute_watched(runner, sql: str, timeout_s: float):
    """Run ``runner.execute(sql)`` under a watchdog.  Returns
    (rows | None, exception | None, hung: bool, wall_s)."""
    holder: dict = {}

    def _work():
        try:
            holder["rows"] = runner.execute(sql).rows()
        except BaseException as e:  # noqa: BLE001 - classified by caller
            holder["exc"] = e

    t0 = time.monotonic()
    th = threading.Thread(target=_work, daemon=True, name="chaos-query")
    th.start()
    th.join(timeout_s)
    wall = time.monotonic() - t0
    if th.is_alive():
        return None, None, True, wall
    return holder.get("rows"), holder.get("exc"), False, wall


def _classify_outcome(sql, rows, exc, hung, retried, expected):
    if hung:
        return "hang", "watchdog timeout"
    if sql == USER_ERROR_SQL:
        if exc is not None and "DIVISION_BY_ZERO" in str(exc):
            return "classified_failure", None
        return "unexpected", f"user error not classified: {exc!r}"
    if exc is not None:
        return "unexpected", f"{type(exc).__name__}: {exc}"
    try:
        assert_same_rows(rows, expected[sql], ordered=False)
    except AssertionError as e:
        return "unexpected", f"oracle mismatch: {e}"
    return ("ok_after_retry" if retried else "ok"), None


def run_scenario(seed: int, mode: str = "inproc", n_queries: int = 8,
                 expected: Optional[dict] = None,
                 query_timeout_s: Optional[float] = None) -> dict:
    """One seeded chaos scenario: a fresh 2-worker runner, ``n_queries``
    queries from the mix, each with a seeded fault (or none), plus live
    drains.  Returns {"seed", "mode", "outcomes": [...], counts...}."""
    if expected is None:
        expected = build_expected()
    rng = random.Random(seed)
    timeout = query_timeout_s or (30.0 if mode == "inproc" else 90.0)
    inj = FailureInjector()
    session = Session(node_count=2, retry_policy="QUERY",
                      failure_injector=inj, retry_initial_delay_s=0.01,
                      heartbeat_interval_s=0.2, speculation=True,
                      drain_timeout_s=5.0)
    if mode == "inproc":
        runner = DistributedQueryRunner(
            default_catalog(scale_factor=0.01), worker_count=2,
            session=session)
        faults = _INPROC_FAULTS
    else:
        from ..execution.remote import ProcessDistributedQueryRunner
        runner = ProcessDistributedQueryRunner(
            CATALOG_SPEC, worker_count=2, session=session,
            env_overrides=_ENV)
        faults = _PROCESS_FAULTS

    from ..caching import result_cache

    outcomes = []
    try:
        # the soak certifies *execution* under faults — a cached result for
        # a repeated mix query would skip the fragment path and leave the
        # armed injection waiting for the wrong query
        with result_cache.disabled():
            for qi in range(n_queries):
                sql = (USER_ERROR_SQL if rng.random() < 0.12
                       else rng.choice(QUERY_MIX))
                fault = rng.choice(faults)
                task_index = rng.randrange(2)
                if fault == TASK_STALL:
                    inj.inject(TASK_STALL, fragment_id=None,
                               task_index=task_index, attempt=0, times=1,
                               stall_s=round(0.3 + rng.random() * 0.5, 2))
                elif fault not in ("none", "drain"):
                    inj.inject(fault, fragment_id=None,
                               task_index=task_index, attempt=0, times=1)

                retries_before = runner.resilience.query_retries
                if fault == "drain":
                    rows, exc, hung, wall = _run_with_drain(
                        runner, sql, mode, rng, timeout)
                else:
                    rows, exc, hung, wall = _execute_watched(
                        runner, sql, timeout)
                retried = runner.resilience.query_retries > retries_before
                outcome, detail = _classify_outcome(
                    sql, rows, exc, hung, retried, expected)
                outcomes.append({
                    "query": qi, "sql": sql, "fault": fault,
                    "outcome": outcome, "detail": detail,
                    "wall_s": round(wall, 3), "retried": retried,
                })
                if outcome == "hang":
                    break  # the runner is wedged; stop the scenario here
    finally:
        close = getattr(runner, "close", None)
        if close is not None:
            try:
                close()
            except Exception:
                pass

    counts: dict = {}
    for o in outcomes:
        counts[o["outcome"]] = counts.get(o["outcome"], 0) + 1
    return {"seed": seed, "mode": mode, "outcomes": outcomes,
            "counts": counts,
            "speculative_starts": getattr(runner, "speculative_starts", 0),
            "speculative_wins": getattr(runner, "speculative_wins", 0)}


def _run_with_drain(runner, sql, mode, rng, timeout_s):
    """Run a query and drain a seeded-random worker mid-flight.  In-proc
    the drain is logical (stop scheduling; restore afterwards); process
    mode issues a real PUT /v1/shutdown and replaces the worker."""
    holder: dict = {}

    def _work():
        try:
            holder["rows"] = runner.execute(sql).rows()
        except BaseException as e:  # noqa: BLE001
            holder["exc"] = e

    t0 = time.monotonic()
    th = threading.Thread(target=_work, daemon=True, name="chaos-query")
    th.start()
    time.sleep(0.02 + rng.random() * 0.15)
    if mode == "inproc":
        victim = f"worker-{rng.randrange(2)}"
        try:
            runner.drain_worker(victim)
            th.join(timeout_s)
        finally:
            runner.restore_worker(victim)
    else:
        victim = runner.workers[rng.randrange(2)]
        runner.drain_worker(victim, replace=True)
        th.join(timeout_s)
    wall = time.monotonic() - t0
    if th.is_alive():
        return None, None, True, wall
    return holder.get("rows"), holder.get("exc"), False, wall


def run_fte_scenario(seed: int, n_queries: int = 6,
                     expected: Optional[dict] = None,
                     query_timeout_s: float = 45.0) -> dict:
    """One seeded FTE chaos scenario: a fresh 2-worker runner under
    ``retry_policy="TASK"``, each query with a seeded fault from the FTE
    menu (including SPOOL_CORRUPTION bit flips on committed spool files).
    The acceptance invariant is the streaming soak's: every query is
    oracle-correct, classified, or — never — hung."""
    from ..telemetry import metrics as tm

    if expected is None:
        expected = build_expected()
    rng = random.Random(seed)
    inj = FailureInjector()
    session = Session(node_count=2, retry_policy="TASK",
                      failure_injector=inj, task_retry_attempts=4,
                      fte_speculative=True, fte_speculative_delay_s=0.3)
    runner = DistributedQueryRunner(
        default_catalog(scale_factor=0.01), worker_count=2,
        session=session)

    from ..caching import result_cache

    outcomes = []
    with result_cache.disabled():
        for qi in range(n_queries):
            sql = (USER_ERROR_SQL if rng.random() < 0.12
                   else rng.choice(QUERY_MIX))
            fault = rng.choice(_FTE_FAULTS)
            task_index = rng.randrange(2)
            if fault == TASK_STALL:
                inj.inject(TASK_STALL, fragment_id=None,
                           task_index=task_index, attempt=0, times=1,
                           stall_s=round(0.5 + rng.random() * 0.8, 2))
            elif fault != "none":
                inj.inject(fault, fragment_id=None,
                           task_index=task_index, attempt=0, times=1)
            retries_before = tm.FTE_ATTEMPT_RETRIES.value()
            corrupt_before = tm.FTE_SPOOL_CORRUPTIONS.value()
            rows, exc, hung, wall = _execute_watched(
                runner, sql, query_timeout_s)
            retried = tm.FTE_ATTEMPT_RETRIES.value() > retries_before
            outcome, detail = _classify_outcome(
                sql, rows, exc, hung, retried, expected)
            outcomes.append({
                "query": qi, "sql": sql, "fault": fault,
                "outcome": outcome, "detail": detail,
                "wall_s": round(wall, 3), "retried": retried,
                "spool_corruption_repairs":
                    tm.FTE_SPOOL_CORRUPTIONS.value() - corrupt_before,
            })
            if outcome == "hang":
                break

    counts: dict = {}
    for o in outcomes:
        counts[o["outcome"]] = counts.get(o["outcome"], 0) + 1
    return {"seed": seed, "mode": "fte", "outcomes": outcomes,
            "counts": counts}


def run_fte_chaos(n_scenarios: int = 12, base_seed: int = 1515,
                  fte_queries: int = 6, verbose: bool = True) -> dict:
    """The FTE chaos leg: seeded scenarios over the FTE fault menu.
    Same acceptance booleans as ``run_chaos`` (PR-9 bar: 100%% of queries
    accounted, zero hangs)."""
    expected = build_expected()
    scenarios = []
    for i in range(n_scenarios):
        t0 = time.monotonic()
        rec = run_fte_scenario(base_seed + i, n_queries=fte_queries,
                               expected=expected)
        rec["scenario"] = i
        rec["wall_s"] = round(time.monotonic() - t0, 2)
        scenarios.append(rec)
        if verbose:
            print(f"  fte chaos scenario {i:2d} seed={base_seed + i} "
                  f"{rec['counts']} ({rec['wall_s']:.1f}s)", flush=True)
    totals: dict = {}
    for rec in scenarios:
        for k, v in rec["counts"].items():
            totals[k] = totals.get(k, 0) + v
    n_queries = sum(len(r["outcomes"]) for r in scenarios)
    return {
        "harness": CPU_HARNESS,
        "n_scenarios": n_scenarios,
        "base_seed": base_seed,
        "n_queries": n_queries,
        "totals": totals,
        "hangs": totals.get("hang", 0),
        "unexpected": totals.get("unexpected", 0),
        "all_accounted": (totals.get("hang", 0) == 0
                          and totals.get("unexpected", 0) == 0),
        "scenarios": scenarios,
    }


# ---------------------------------------------------- coordinator kill -9
_DRILL_SQL = ("select l_returnflag, l_linestatus, count(*), "
              "sum(l_quantity) from lineitem group by l_returnflag, "
              "l_linestatus order by l_returnflag, l_linestatus")


def _coordinator_child() -> None:
    """Subprocess entry for the coordinator-kill drill: boot a 2-worker
    FTE coordinator behind the HTTP statement protocol, write the bound
    port to ``CHAOS_PORT_FILE`` (atomic rename), and serve until killed.
    ``CHAOS_STALL_S`` arms a one-shot TASK_STALL on task 0 of the first
    stage scheduled — the deterministic 'mid-query' the parent kills
    into; with ``fte_speculative`` off nothing can rescue the stall, so
    the kill is guaranteed to land with the query in flight."""
    import os

    from ..connectors.catalog import default_catalog as _catalog
    from ..execution.distributed_runner import DistributedQueryRunner as _R
    from ..execution.failure_injector import FailureInjector as _Inj
    from ..execution.failure_injector import TASK_STALL as _STALL
    from ..runner import Session as _S
    from ..server.protocol import TrinoTpuServer

    inj = None
    stall_s = float(os.environ.get("CHAOS_STALL_S", "0") or 0)
    if stall_s > 0:
        inj = _Inj()
        inj.inject(_STALL, fragment_id=None, task_index=0, attempt=0,
                   times=1, stall_s=stall_s)
    session = _S(node_count=2, retry_policy="TASK", fte_speculative=False,
                 failure_injector=inj)
    runner = _R(_catalog(scale_factor=0.01), worker_count=2,
                session=session)
    srv = TrinoTpuServer(runner).start()
    port_file = os.environ["CHAOS_PORT_FILE"]
    with open(port_file + ".tmp", "w", encoding="utf-8") as f:
        f.write(str(srv.address[1]))
    os.replace(port_file + ".tmp", port_file)
    while True:
        time.sleep(1.0)


def _http_json(method: str, url: str, body: Optional[bytes] = None,
               timeout: float = 10.0) -> dict:
    import json
    from urllib.request import Request, urlopen

    req = Request(url, data=body, method=method)
    with urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def run_coordinator_kill_drill(stall_s: float = 300.0,
                               boot_timeout_s: float = 180.0,
                               finish_timeout_s: float = 180.0,
                               workdir: Optional[str] = None) -> dict:
    """The tentpole drill: kill -9 a coordinator mid-FTE-query, restart
    it, and certify durable recovery end to end.

    Epoch 1 boots a subprocess coordinator with a one-shot un-rescuable
    stall, submits ``_DRILL_SQL`` over POST /v1/statement, waits (by
    reading the query-state WAL) until at least one task attempt has
    committed, then SIGKILLs the process.  Epoch 2 boots a fresh
    coordinator against the same state/spool dirs; its dispatcher must
    rehydrate the query under the ORIGINAL id, resume from the committed-
    attempt map, and finish.  Asserts, from the WAL's attempt counters:
    committed attempts were NEVER re-executed.  Returns the full record
    (also the shape tests/test_query_state.py consumes)."""
    import os
    import shutil
    import signal
    import subprocess
    import sys
    import tempfile

    from ..execution import query_state

    work = workdir or tempfile.mkdtemp(prefix="trino-tpu-kill-drill-")
    state_dir = os.path.join(work, "query-state")
    spool_dir = os.path.join(work, "spool")
    port_file = os.path.join(work, "port")
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "TRINO_TPU_QUERY_STATE": "1",
        "TRINO_TPU_QUERY_STATE_DIR": state_dir,
        "TRINO_TPU_SPOOL_DIR": spool_dir,
        "TRINO_TPU_RESULT_CACHE": "0",
        "CHAOS_PORT_FILE": port_file,
        "PYTHONPATH": repo_root + os.pathsep + env.get("PYTHONPATH", ""),
    })
    child_cmd = [sys.executable, "-c",
                 "from trino_tpu.testing.chaos import _coordinator_child; "
                 "_coordinator_child()"]

    def _boot(extra_env: dict) -> tuple:
        try:
            os.remove(port_file)
        except OSError:
            pass
        proc = subprocess.Popen(child_cmd, env={**env, **extra_env},
                                cwd=repo_root)
        deadline = time.monotonic() + boot_timeout_s
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"coordinator child died at boot (rc={proc.returncode})")
            if os.path.exists(port_file):
                with open(port_file, encoding="utf-8") as f:
                    return proc, int(f.read().strip())
            time.sleep(0.1)
        proc.kill()
        raise TimeoutError("coordinator child never wrote its port")

    record: dict = {"sql": _DRILL_SQL, "workdir": work,
                    "harness": CPU_HARNESS}
    proc2 = None
    proc1, port1 = _boot({"CHAOS_STALL_S": str(stall_s)})
    try:
        # epoch 1: submit, wait for >=1 committed attempt, kill -9
        sub = _http_json("POST", f"http://127.0.0.1:{port1}/v1/statement",
                         _DRILL_SQL.encode("utf-8"))
        qid = sub["id"]
        record["query_id"] = qid
        wal_path = None
        pq = None
        deadline = time.monotonic() + boot_timeout_s
        while time.monotonic() < deadline:
            walls = [os.path.join(state_dir, n)
                     for n in os.listdir(state_dir)] \
                if os.path.isdir(state_dir) else []
            walls = [w for w in walls if w.endswith(".wal")]
            if walls:
                wal_path = walls[0]
                pq = query_state.load(wal_path)
                if pq is not None and len(pq.committed) >= 1:
                    break
            time.sleep(0.1)
        if pq is None or not pq.committed:
            raise TimeoutError("no committed attempt before the kill")
        committed_at_kill = dict(pq.committed)
        starts_at_kill = dict(pq.attempt_counts)
        record["committed_at_kill"] = len(committed_at_kill)
        os.kill(proc1.pid, signal.SIGKILL)
        proc1.wait(timeout=30)

        # epoch 2: fresh coordinator, same dirs — recovery must finish the
        # query under its original id
        proc2, port2 = _boot({})
        rows: list = []
        state = None
        token = 0
        deadline = time.monotonic() + finish_timeout_s
        while time.monotonic() < deadline:
            out = _http_json(
                "GET", f"http://127.0.0.1:{port2}/v1/statement/{qid}/{token}")
            state = out.get("stats", {}).get("state")
            if state == "FAILED":
                record["error"] = out.get("error")
                break
            rows += out.get("data", [])
            nxt = out.get("nextUri")
            if state == "FINISHED":
                if not nxt:
                    break
                token += 1
                continue
            time.sleep(0.2)
        record["state"] = state
        record["rows"] = rows

        final = query_state.load(wal_path)
        re_executed = {
            f"f{fid}_t{t}": final.attempt_counts.get((fid, t), 0)
            - starts_at_kill.get((fid, t), 0)
            for (fid, t) in committed_at_kill
            if final.attempt_counts.get((fid, t), 0)
            > starts_at_kill.get((fid, t), 0)
        }
        record["committed_reexecuted"] = re_executed
        record["resumed_attempt_starts"] = {
            f"f{fid}_t{t}": n - starts_at_kill.get((fid, t), 0)
            for (fid, t), n in final.attempt_counts.items()
            if n > starts_at_kill.get((fid, t), 0)
        }
        record["wal_ended"] = final.ended

        # spool GC: the resumed query's root must be reclaimed at its end
        spool_root = pq.spool_root
        deadline = time.monotonic() + 30.0
        while os.path.isdir(spool_root) and time.monotonic() < deadline:
            time.sleep(0.2)
        record["spool_reclaimed"] = not os.path.isdir(spool_root)
        record["pass"] = (state == "FINISHED" and not re_executed
                         and record["spool_reclaimed"]
                         and final.ended == "FINISHED")
        return record
    finally:
        for p in (proc1, proc2):
            if p is not None and p.poll() is None:
                p.kill()
                p.wait(timeout=15)
        if workdir is None:
            shutil.rmtree(work, ignore_errors=True)


# ------------------------------------------------- HA fleet lease takeover

def _ha_coordinator_child() -> None:
    """Subprocess entry for the HA takeover drill: one fleet member.  Boots
    the 2-worker FTE coordinator behind the statement protocol, wraps it in
    an :class:`~trino_tpu.execution.ha.HACoordinator` (lease + failover
    watcher), writes its bound port to ``CHAOS_PORT_FILE``, and serves
    until killed.  ``CHAOS_STALL_S`` arms the same one-shot unrescuable
    stall as the single-coordinator drill — only the victim node gets it."""
    import os

    from ..connectors.catalog import default_catalog as _catalog
    from ..execution.distributed_runner import DistributedQueryRunner as _R
    from ..execution.failure_injector import FailureInjector as _Inj
    from ..execution.failure_injector import TASK_STALL as _STALL
    from ..execution.ha import HACoordinator
    from ..runner import Session as _S
    from ..server.protocol import TrinoTpuServer

    inj = None
    stall_s = float(os.environ.get("CHAOS_STALL_S", "0") or 0)
    if stall_s > 0:
        inj = _Inj()
        inj.inject(_STALL, fragment_id=None, task_index=0, attempt=0,
                   times=1, stall_s=stall_s)
    session = _S(node_count=2, retry_policy="TASK", fte_speculative=False,
                 failure_injector=inj)
    runner = _R(_catalog(scale_factor=0.01), worker_count=2,
                session=session)
    srv = TrinoTpuServer(runner).start()
    HACoordinator(srv).start()
    port_file = os.environ["CHAOS_PORT_FILE"]
    with open(port_file + ".tmp", "w", encoding="utf-8") as f:
        f.write(str(srv.address[1]))
    os.replace(port_file + ".tmp", port_file)
    while True:
        time.sleep(1.0)


def run_ha_takeover_drill(stall_s: float = 300.0,
                          lease_ttl_s: float = 2.0,
                          heartbeat_s: float = 0.5,
                          boot_timeout_s: float = 180.0,
                          finish_timeout_s: float = 180.0,
                          workdir: Optional[str] = None) -> dict:
    """The HA tentpole drill: kill -9 one coordinator of a two-member
    fleet mid-FTE-query and certify a PEER (not a restart) finishes it.

    Coordinator A boots with an unrescuable one-shot stall and owns the
    drill query; B is healthy.  After >=1 fsync'd committed attempt lands
    in A's WAL, A is SIGKILLed.  B's failover watcher must claim A's
    expired lease (atomic lease-file rename), take custody of A's WAL
    directory, adopt the query under its ORIGINAL id, resume from the
    committed-attempt map and finish — the parent polls B's ordinary
    ``GET /v1/statement/{qid}/{token}`` surface throughout.  Asserts from
    the claimed WAL's attempt counters that committed attempts were never
    re-executed, and that A's lease is gone from the cluster directory."""
    import os
    import shutil
    import signal
    import subprocess
    import sys
    import tempfile

    from ..execution import query_state

    work = workdir or tempfile.mkdtemp(prefix="trino-tpu-ha-drill-")
    ha_root = os.path.join(work, "ha")
    spool_dir = os.path.join(work, "spool")
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    base_env = dict(os.environ)
    base_env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "TRINO_TPU_HA": "1",
        "TRINO_TPU_HA_DIR": ha_root,
        "TRINO_TPU_HA_LEASE_TTL_S": str(lease_ttl_s),
        "TRINO_TPU_HA_HEARTBEAT_S": str(heartbeat_s),
        "TRINO_TPU_QUERY_STATE": "1",
        "TRINO_TPU_SPOOL_DIR": spool_dir,
        "TRINO_TPU_JOURNAL_DIR": os.path.join(work, "journal"),
        "TRINO_TPU_RESULT_CACHE": "0",
        "PYTHONPATH": repo_root + os.pathsep + base_env.get("PYTHONPATH",
                                                            ""),
    })
    child_cmd = [sys.executable, "-c",
                 "from trino_tpu.testing.chaos import _ha_coordinator_child;"
                 " _ha_coordinator_child()"]

    def _boot(node: str, extra_env: dict) -> tuple:
        port_file = os.path.join(work, f"port-{node}")
        env = {**base_env,
               "TRINO_TPU_HA_NODE_ID": node,
               "TRINO_TPU_QUERY_STATE_DIR": os.path.join(
                   ha_root, "wal", node),
               "CHAOS_PORT_FILE": port_file,
               **extra_env}
        proc = subprocess.Popen(child_cmd, env=env, cwd=repo_root)
        deadline = time.monotonic() + boot_timeout_s
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"HA child {node} died at boot (rc={proc.returncode})")
            if os.path.exists(port_file):
                with open(port_file, encoding="utf-8") as f:
                    return proc, int(f.read().strip())
            time.sleep(0.1)
        proc.kill()
        raise TimeoutError(f"HA child {node} never wrote its port")

    record: dict = {"sql": _DRILL_SQL, "workdir": work,
                    "harness": CPU_HARNESS}
    proc_a = proc_b = None
    try:
        proc_a, port_a = _boot("coordA", {"CHAOS_STALL_S": str(stall_s)})
        proc_b, port_b = _boot("coordB", {})

        # the query must land on A (the stalled victim): submit straight to
        # A's statement endpoint — ownership in the drill is by submission,
        # the front-tier hash path is exercised by bench.py --ha
        sub = _http_json("POST", f"http://127.0.0.1:{port_a}/v1/statement",
                         _DRILL_SQL.encode("utf-8"))
        qid = sub["id"]
        record["query_id"] = qid
        wal_a = os.path.join(ha_root, "wal", "coordA", qid + ".wal")
        pq = None
        deadline = time.monotonic() + boot_timeout_s
        while time.monotonic() < deadline:
            pq = query_state.load(wal_a)
            if pq is not None and len(pq.committed) >= 1:
                break
            time.sleep(0.1)
        if pq is None or not pq.committed:
            raise TimeoutError("no committed attempt before the kill")
        committed_at_kill = dict(pq.committed)
        starts_at_kill = dict(pq.attempt_counts)
        record["committed_at_kill"] = len(committed_at_kill)
        t_kill = time.monotonic()
        os.kill(proc_a.pid, signal.SIGKILL)
        proc_a.wait(timeout=30)

        # B's watcher claims the expired lease and finishes the query under
        # its original id; the client just switches which address it polls
        rows: list = []
        state = None
        token = 0
        deadline = time.monotonic() + finish_timeout_s
        while time.monotonic() < deadline:
            try:
                out = _http_json(
                    "GET",
                    f"http://127.0.0.1:{port_b}/v1/statement/{qid}/{token}")
            except Exception:  # 404 until B adopts; keep polling
                time.sleep(0.2)
                continue
            state = out.get("stats", {}).get("state")
            if state == "FAILED":
                record["error"] = out.get("error")
                break
            rows += out.get("data", [])
            nxt = out.get("nextUri")
            if state == "FINISHED":
                if not nxt:
                    break
                token += 1
                continue
            time.sleep(0.2)
        record["state"] = state
        record["rows"] = rows
        record["takeover_s"] = round(time.monotonic() - t_kill, 2)

        # A's WAL now lives under B's claimed custody
        wal_root = os.path.join(ha_root, "wal")
        claimed = [d for d in sorted(os.listdir(wal_root))
                   if d.startswith("coordA.claimed-coordB-")]
        record["claimed_dirs"] = claimed
        final = None
        if claimed:
            final = query_state.load(
                os.path.join(wal_root, claimed[0], qid + ".wal"))
        re_executed = {}
        if final is not None:
            re_executed = {
                f"f{fid}_t{t}": final.attempt_counts.get((fid, t), 0)
                - starts_at_kill.get((fid, t), 0)
                for (fid, t) in committed_at_kill
                if final.attempt_counts.get((fid, t), 0)
                > starts_at_kill.get((fid, t), 0)
            }
        record["committed_reexecuted"] = re_executed
        record["wal_ended"] = final.ended if final is not None else None
        record["lease_a_gone"] = not os.path.exists(
            os.path.join(ha_root, "coordinators", "coordA.json"))
        record["pass"] = (state == "FINISHED" and bool(claimed)
                          and final is not None and not re_executed
                          and final.ended == "FINISHED"
                          and record["lease_a_gone"])
        return record
    finally:
        for p in (proc_a, proc_b):
            if p is not None and p.poll() is None:
                p.kill()
                p.wait(timeout=15)
        if workdir is None:
            shutil.rmtree(work, ignore_errors=True)


def run_chaos(n_scenarios: int = 25, base_seed: int = 1009,
              inproc_queries: int = 8, process_queries: int = 4,
              process_stride: int = 4, verbose: bool = True) -> dict:
    """The full soak.  Every ``process_stride``-th scenario runs against
    real worker processes; the rest are in-process.  Returns a summary
    with per-scenario records and the acceptance booleans."""
    expected = build_expected()
    scenarios = []
    for i in range(n_scenarios):
        mode = ("process" if process_stride and i % process_stride
                == process_stride - 1 else "inproc")
        n_q = process_queries if mode == "process" else inproc_queries
        t0 = time.monotonic()
        rec = run_scenario(base_seed + i, mode=mode, n_queries=n_q,
                           expected=expected)
        rec["scenario"] = i
        rec["wall_s"] = round(time.monotonic() - t0, 2)
        scenarios.append(rec)
        if verbose:
            print(f"  chaos scenario {i:2d} seed={base_seed + i} "
                  f"mode={mode:7s} {rec['counts']} "
                  f"({rec['wall_s']:.1f}s)", flush=True)

    totals: dict = {}
    retry_walls = []
    for rec in scenarios:
        for k, v in rec["counts"].items():
            totals[k] = totals.get(k, 0) + v
        retry_walls += [o["wall_s"] for o in rec["outcomes"]
                        if o["retried"]]
    n_queries = sum(len(r["outcomes"]) for r in scenarios)
    return {
        "harness": CPU_HARNESS,
        "n_scenarios": n_scenarios,
        "base_seed": base_seed,
        "n_queries": n_queries,
        "totals": totals,
        "hangs": totals.get("hang", 0),
        "unexpected": totals.get("unexpected", 0),
        "max_recovery_s": round(max(retry_walls), 3) if retry_walls
        else 0.0,
        "all_accounted": (totals.get("hang", 0) == 0
                          and totals.get("unexpected", 0) == 0),
        "scenarios": scenarios,
    }
