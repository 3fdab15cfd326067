"""Query flight recorder (telemetry/profiler.py): ring mechanics, context
attribution, the SyncGuard zero-hot-sync invariant at the default level,
full-mode device-time attribution, and the merged coordinator+worker
Chrome trace_event export — in-process (fused-region events included) and
across real worker processes via ``GET /v1/query/{id}/profile``."""

import json
import os
import threading
import urllib.error
import urllib.request

import pytest

from trino_tpu.connectors.catalog import default_catalog
from trino_tpu.exec import syncguard as SG
from trino_tpu.execution.distributed_runner import DistributedQueryRunner
from trino_tpu.runner import Session, StandaloneQueryRunner
from trino_tpu.telemetry import profiler

AGG_SQL = """
select l_returnflag, l_linestatus, sum(l_quantity), count(*)
from lineitem group by l_returnflag, l_linestatus
"""


@pytest.fixture(autouse=True)
def _fresh_profiler(monkeypatch):
    # profiler tests assert on execution timelines of repeated statements —
    # a served cached result would produce an empty timeline
    monkeypatch.setenv("TRINO_TPU_RESULT_CACHE", "0")
    prev = profiler.set_level(1)
    profiler.reset_for_test()
    yield
    profiler.set_level(prev)
    profiler.reset_for_test()


# ---------------------------------------------------------------- ring units


def test_ring_wraps_at_capacity_and_counts_overwrites():
    r = profiler._Ring(4)
    for i in range(7):
        r.push((float(i), 0.0, "operator", f"op{i}", "q", "", None))
    assert len(r.buf) == 4
    assert r.overwrites == 3
    kept = sorted(ev[0] for ev in r.buf)
    assert kept == [3.0, 4.0, 5.0, 6.0]  # oldest overwritten first


def test_context_stamping_and_restore():
    prev = profiler.set_context("q_ctx", "t_0")
    t0 = profiler.now()
    profiler.event(profiler.OPERATOR, "ScanOperator", t0)
    evs = profiler.collect("q_ctx")
    assert len(evs) == 1 and evs[0]["task"] == "t_0"
    profiler.set_context(*prev)
    profiler.event(profiler.OPERATOR, "after-restore", profiler.now())
    assert len(profiler.collect("q_ctx")) == 1  # restored context ≠ q_ctx


def test_group_threads_inherit_context():
    profiler.set_context("q_inherit", "t_9")
    ctx = profiler.capture_context()

    def work():
        profiler.apply_context(ctx)
        profiler.event(profiler.OPERATOR, "worker-thread-op", profiler.now())

    th = threading.Thread(target=work)
    th.start()
    th.join()
    evs = profiler.collect("q_inherit")
    assert [e["name"] for e in evs] == ["worker-thread-op"]
    assert evs[0]["task"] == "t_9"
    profiler.set_context("", "")


def test_disabled_level_records_nothing():
    profiler.set_level(0)
    profiler.set_context("q_off", "")
    profiler.event(profiler.OPERATOR, "invisible", profiler.now())
    profiler.instant(profiler.SPECULATION, "invisible-too")
    profiler.set_level(1)
    assert profiler.collect("q_off") == []


def test_take_task_events_bounds_and_keeps_tail():
    profiler.set_context("q_tail", "t_0")
    for i in range(50):
        profiler.event(profiler.OPERATOR, f"op{i}", float(i), float(i))
    evs = profiler.take_task_events("q_tail", "t_0", limit=10)
    assert len(evs) == 10
    assert evs[-1]["name"] == "op49"  # newest kept: failures live at the end
    profiler.set_context("", "")


def test_profile_store_is_bounded():
    for i in range(profiler._MAX_PROFILES + 10):
        profiler.add_remote_events(
            f"q_{i}", [{"ts": 0.0, "dur": 0.0, "kind": "operator",
                        "name": "x", "task": "", "pid": 1, "tid": 1,
                        "thread": "t"}])
    with profiler._PROFILES_LOCK:
        assert len(profiler._PROFILES) == profiler._MAX_PROFILES
        assert "q_0" not in profiler._PROFILES  # oldest evicted


# -------------------------------------------------------- chrome trace shape


def _validate_chrome_trace(trace):
    """The subset of the trace_event spec Perfetto/chrome://tracing needs."""
    assert set(trace) >= {"traceEvents", "displayTimeUnit"}
    json.dumps(trace)  # must serialize
    for ev in trace["traceEvents"]:
        assert ev["ph"] in ("X", "M")
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        if ev["ph"] == "X":
            assert ev["ts"] >= 0 and ev["dur"] >= 0  # µs, normalized
            assert ev["name"] and ev["cat"]
        else:
            assert ev["name"] in ("process_name", "thread_name")
    # every X event's process got an M process_name record
    named = {e["pid"] for e in trace["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    used = {e["pid"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert used <= named


def test_chrome_trace_unit_roundtrip():
    profiler.set_context("q_trace", "t_1")
    t0 = profiler.now()
    profiler.event(profiler.OPERATOR, "ScanOperator", t0 - 0.01, t0,
                   rows=128)
    profiler.harvest("q_trace")
    profiler.set_context("", "")
    trace = profiler.chrome_trace("q_trace")
    _validate_chrome_trace(trace)
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert xs[0]["args"]["rows"] == 128 and xs[0]["args"]["task"] == "t_1"
    assert trace["otherData"]["query_id"] == "q_trace"
    assert profiler.chrome_trace("q_unknown") is None


# ------------------------------------------- engine integration (in-process)


@pytest.fixture(scope="module")
def dist():
    catalog = default_catalog(scale_factor=0.01)
    return DistributedQueryRunner(catalog, worker_count=2,
                                  session=Session(node_count=2))


def test_default_profiling_keeps_hot_regions_free_of_syncs(dist):
    """THE overhead guard: with the flight recorder at its default level,
    a fused-stage query still runs with zero blocking syncs inside
    SyncGuard hot regions (recording is a clock read + a tuple store)."""
    assert profiler.enabled() and not profiler.is_full()
    dist.execute(AGG_SQL)  # warm-up: compiles may sync
    before = SG.snapshot()
    with SG.forbidden():
        dist.execute(AGG_SQL, query_id="q_sync_guard")
    assert SG.take_delta(before).hot_loop_syncs == 0
    assert profiler.chrome_trace("q_sync_guard") is not None


def test_fused_query_timeline_has_all_event_kinds(dist):
    """One in-process 2-worker TPC-H aggregation: operator, fused-region
    AND exchange-wait events land in one merged timeline."""
    dist.execute(AGG_SQL, query_id="q_fused_profile")
    assert dist._fused_edges, "expected the whole-stage compilation path"
    trace = dist.profile("q_fused_profile")
    _validate_chrome_trace(trace)
    cats = {e["cat"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert {"operator", "fused-region", "exchange-wait"} <= cats
    fused = [e["name"] for e in trace["traceEvents"]
             if e["ph"] == "X" and e["cat"] == "fused-region"]
    assert any(n.startswith("fused-accumulate") for n in fused)
    assert any(n.startswith("fused-merge") for n in fused)


def test_full_mode_syncs_are_attributed_not_hot(dist):
    """TRINO_TPU_PROFILE=full brackets operator output with
    block_until_ready: the syncs happen (tagged ``profiler.full``) but
    never inside a hot region — SyncGuard accounting stays honest."""
    profiler.set_level(2)
    before = SG.snapshot()
    dist.execute(AGG_SQL, query_id="q_full_mode")
    delta = SG.take_delta(before)
    profiler.set_level(1)
    assert delta.by_tag.get("profiler.full", 0) > 0
    assert delta.hot_loop_syncs == 0


def test_runner_profile_unknown_query_returns_none(dist):
    assert dist.profile("never-ran") is None


def test_standalone_runner_profile():
    r = StandaloneQueryRunner(default_catalog(scale_factor=0.01))
    r.execute("select count(*) from tpch.tiny.region",
              query_id="q_standalone")
    trace = r.profile("q_standalone")
    _validate_chrome_trace(trace)
    cats = {e["cat"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert "operator" in cats


# ---------------------------------- worker processes + coordinator endpoints


@pytest.fixture(scope="module")
def served_cluster():
    """2 real worker processes behind a coordinator HTTP server."""
    from trino_tpu.execution.remote import ProcessDistributedQueryRunner
    from trino_tpu.server.protocol import TrinoTpuServer

    runner = ProcessDistributedQueryRunner(
        {"factory": "trino_tpu.connectors.catalog:default_catalog",
         "kwargs": {"scale_factor": 0.01}},
        worker_count=2, session=Session(node_count=2),
        env_overrides={
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
    server = TrinoTpuServer(runner).start()
    host, port = server.address
    yield runner, f"http://{host}:{port}"
    server.stop()
    runner.close()


def _run_statement(base: str, sql: str) -> tuple[str, dict]:
    req = urllib.request.Request(f"{base}/v1/statement",
                                 data=sql.encode(), method="POST")
    with urllib.request.urlopen(req) as resp:
        payload = json.load(resp)
    qid = payload["id"]
    while payload.get("nextUri"):
        with urllib.request.urlopen(base + payload["nextUri"]) as resp:
            payload = json.load(resp)
    return qid, payload


def test_profile_endpoint_merges_worker_timelines(served_cluster):
    """The acceptance path: a 2-worker TPC-H query's profile over HTTP is
    valid Chrome trace JSON with events from the coordinator AND both
    worker pids in one timeline."""
    runner, base = served_cluster
    qid, payload = _run_statement(
        base, "select l_returnflag, count(*) from lineitem "
              "group by l_returnflag order by l_returnflag")
    assert payload["stats"]["state"] == "FINISHED"
    with urllib.request.urlopen(f"{base}/v1/query/{qid}/profile") as resp:
        trace = json.load(resp)
    _validate_chrome_trace(trace)
    cats = {e["cat"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert {"operator", "exchange-wait"} <= cats
    procs = trace["otherData"]["processes"]
    workers = [p for p in procs.values() if p.startswith("worker:")]
    assert len(workers) == 2, f"expected both worker pids, got {procs}"
    assert "coordinator" in procs.values()
    assert os.getpid() in {e["pid"] for e in trace["traceEvents"]}


def test_profile_endpoint_unknown_query_404(served_cluster):
    _, base = served_cluster
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(f"{base}/v1/query/never-ran/profile")
    assert ei.value.code == 404


def test_cluster_scope_metrics_fold_workers(served_cluster):
    """/v1/metrics?scope=cluster folds both workers' registries into the
    coordinator's: worker-side counters (tasks created) appear summed, and
    merged distributions stay one histogram series."""
    runner, base = served_cluster
    _run_statement(base, "select count(*) from region")
    with urllib.request.urlopen(f"{base}/v1/metrics?scope=cluster") as resp:
        assert resp.headers["Content-Type"].startswith("text/plain")
        cluster = resp.read().decode()
    with urllib.request.urlopen(f"{base}/v1/metrics") as resp:
        local = resp.read().decode()

    def val(text, name):
        for line in text.splitlines():
            if line.startswith(name + " "):
                return float(line.split()[1])
        return 0.0

    # tasks ran in worker processes: invisible to the coordinator-local
    # registry (which may carry counts from in-process runners in this
    # test process), folded in by scope=cluster
    assert val(cluster, "trino_tasks_created_total") >= \
        val(local, "trino_tasks_created_total") + 2
    # merged histogram: one bucket series, cumulative, with +Inf
    buckets = [l for l in cluster.splitlines()
               if l.startswith("trino_task_wall_seconds_bucket")]
    assert buckets and '+Inf' in buckets[-1]
    assert val(cluster, "trino_task_wall_seconds_count") >= 2
