"""Streaming-path resilience fault matrix (ISSUE 5).

Deterministic drills over the error-classification + retry_policy=QUERY +
heartbeat-detection + worker-replacement machinery, driven by the existing
engine-level FailureInjector on the CPU mesh:

- classified PROCESS_EXIT mid-stage recovers under ``retry_policy="QUERY"``
  with bit-identical results and a logged worker replacement;
- USER-classified errors fail fast with ZERO retries, everywhere;
- an unreachable producer trips the exchange Backoff's
  ``max_failure_duration`` as a classified EXTERNAL error in bounded time;
- the failure detector walks ACTIVE -> UNRESPONSIVE -> GONE (drain and
  authoritative-death shortcuts included) and GONE is sticky;
- worker replacement honors ``Session.max_worker_replacements``.
"""

import os
import time

import pytest

from trino_tpu.connectors.catalog import default_catalog
from trino_tpu.connectors.tpch_queries import QUERIES
from trino_tpu.execution import remote
from trino_tpu.execution.distributed_runner import DistributedQueryRunner
from trino_tpu.execution.failure_detector import (
    ACTIVE,
    GONE,
    SHUTTING_DOWN,
    UNRESPONSIVE,
    NodeGoneError,
    WorkerFailureDetector,
)
from trino_tpu.execution.failure_injector import (
    PROCESS_EXIT,
    TASK_FAILURE,
    FailureInjector,
    InjectedFailure,
)
from trino_tpu.execution.remote import (
    HttpExchangeClient,
    ProcessDistributedQueryRunner,
    WorkerProcess,
)
from trino_tpu.runner import Session, StandaloneQueryRunner
from trino_tpu.spi.errors import (
    EXTERNAL,
    INSUFFICIENT_RESOURCES,
    INTERNAL,
    USER,
    Backoff,
    TrinoError,
    classify,
)
from trino_tpu.spi.memory import ExceededMemoryLimitError

CATALOG_SPEC = {
    "factory": "trino_tpu.connectors.catalog:default_catalog",
    "kwargs": {"scale_factor": 0.01},
}

_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
}

DIV_BY_ZERO_SQL = \
    "select o_orderkey / (o_orderkey - o_orderkey) from orders"


# --------------------------------------------------------------- unit layer
def test_backoff_is_deterministic():
    """Delays are a pure function of the failure count (no jitter), the
    duration budget measures from the FIRST failure of a streak, and
    success() resets everything."""
    now = [0.0]
    b = Backoff(min_delay_s=0.1, max_delay_s=0.8,
                max_failure_duration_s=10.0, clock=lambda: now[0])
    assert b.delay_s == 0.0 and b.ready()
    assert b.failure() is False  # a single blip never trips the budget
    assert b.delay_s == pytest.approx(0.1)
    assert not b.ready()
    now[0] = 0.1
    assert b.ready()
    assert b.failure() is False
    assert b.delay_s == pytest.approx(0.2)
    b.failure()
    assert b.delay_s == pytest.approx(0.4)
    b.failure()
    assert b.delay_s == pytest.approx(0.8)
    b.failure()
    assert b.delay_s == pytest.approx(0.8)  # capped at max_delay
    now[0] = 10.0
    assert b.failure() is True  # budget exceeded: declare the peer failed
    b.success()
    assert b.failure_count == 0 and b.delay_s == 0.0 and b.ready()


@pytest.mark.parametrize("exc,expected_type,retryable", [
    (ExceededMemoryLimitError("pool", 1, 1), INSUFFICIENT_RESOURCES, True),
    (InjectedFailure("boom"), INTERNAL, True),
    (ConnectionError("refused"), EXTERNAL, True),
    (TimeoutError("late"), EXTERNAL, True),
    (RuntimeError("anything else"), INTERNAL, True),
])
def test_classification_table(exc, expected_type, retryable):
    te = classify(exc)
    assert te.error_type == expected_type
    assert te.is_retryable() is retryable


def test_classification_user_errors_never_retry():
    from trino_tpu.ops.expr import QueryError
    from trino_tpu.sql.analyzer import AnalysisError

    div = classify(QueryError("DIVISION_BY_ZERO: division by zero"))
    assert div.error_type == USER and not div.is_retryable()
    assert div.code.name == "DIVISION_BY_ZERO"
    bad = classify(AnalysisError("no such column"))
    assert bad.error_type == USER and not bad.is_retryable()


def test_classification_is_identity_on_trino_error():
    te = TrinoError(classify(ConnectionError("x")).code, "wrapped",
                    remote_host="http://w:1")
    assert classify(te) is te


# ---------------------------------------------------------- failure detector
def test_detector_state_machine():
    events = []
    det = WorkerFailureDetector(heartbeat_interval_s=0.0,
                                failure_threshold=2, events=events)
    mode = {"w": "ok"}

    def probe():
        m = mode["w"]
        if m == "ok":
            return {"state": "ACTIVE", "tasks": {}}
        if m == "drain":
            return {"state": "SHUTTING_DOWN", "tasks": {}}
        if m == "dead":
            raise NodeGoneError("process exited rc=17")
        raise ConnectionError("refused")

    det.monitor("w", probe)
    det.sweep_once()
    assert det.state_of("w") == ACTIVE and det.active() == ["w"]

    # one miss: UNRESPONSIVE, excluded from placement, tasks not yet lost
    mode["w"] = "fail"
    det.sweep_once()
    assert det.state_of("w") == UNRESPONSIVE and det.active() == []
    # recovery before the threshold resets the miss counter
    mode["w"] = "ok"
    det.sweep_once()
    assert det.state_of("w") == ACTIVE

    # threshold consecutive misses: GONE, and GONE is sticky
    mode["w"] = "fail"
    det.sweep_once()
    det.sweep_once()
    assert det.state_of("w") == GONE and det.gone() == ["w"]
    mode["w"] = "ok"
    det.sweep_once()
    assert det.state_of("w") == GONE  # terminal for this incarnation

    transitions = [e for e in events if e[0] == "heartbeat"]
    assert [(e[2], e[3]) for e in transitions] == [
        (ACTIVE, UNRESPONSIVE), (UNRESPONSIVE, ACTIVE),
        (ACTIVE, UNRESPONSIVE), (UNRESPONSIVE, GONE)]
    assert det.transitions == 4


def test_detector_drain_and_authoritative_death():
    det = WorkerFailureDetector(failure_threshold=3)
    det.monitor("draining", lambda: {"state": "SHUTTING_DOWN", "tasks": {}})

    def dead_probe():
        raise NodeGoneError("process exited rc=17")

    det.monitor("dead", dead_probe)
    det.sweep_once()
    # draining: responsive but gets no new tasks
    assert det.state_of("draining") == SHUTTING_DOWN
    assert det.active() == []
    # authoritative death skips the miss-counting path entirely
    assert det.state_of("dead") == GONE
    assert "exited" in det.last_error("dead")


# ----------------------------------------------------------- exchange client
def test_unreachable_producer_trips_backoff_in_bounded_time():
    """An unreachable producer surfaces as a classified EXTERNAL failure
    once failures persist past max_failure_duration — not a silent stall
    until the 600 s query deadline."""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()  # nothing listens here now
    client = HttpExchangeClient(
        [f"http://127.0.0.1:{port}/v1/task/ghost"], 0,
        backoff={"min_delay_s": 0.01, "max_delay_s": 0.05,
                 "max_failure_duration_s": 0.3})
    t0 = time.monotonic()
    with pytest.raises(TrinoError) as ei:
        while time.monotonic() - t0 < 30.0:
            client.poll(timeout=0.0)
            time.sleep(0.005)
    elapsed = time.monotonic() - t0
    assert elapsed < 5.0, f"backoff trip took {elapsed:.1f}s"
    assert ei.value.code.name == "PAGE_TRANSPORT_TIMEOUT"
    assert ei.value.error_type == EXTERNAL
    assert ei.value.remote_host == f"http://127.0.0.1:{port}"
    assert client.stats["fetch_failures"] >= 2
    assert client.stats["backoff_trips"] == 1
    assert client.stats["backoff_skips"] >= 1  # delay gate actually closed


def test_fetch_honors_caller_poll_timeout(monkeypatch):
    """A non-blocking poll must NOT be silently promoted to a 5 s long-poll
    (the old ``timeout=max(timeout, 5.0)``); the requested wait travels to
    the server as ?maxwait= and the socket timeout only adds grace."""
    captured = []

    class FakeResp:
        status = 200
        headers = {"X-Next-Token": "0", "X-Done": "1"}

        def read(self):
            return b""

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

    def fake_http(method, url, data=None, timeout=30.0, headers=None):
        captured.append((url, timeout))
        return FakeResp()

    monkeypatch.setattr(remote, "_http", fake_http)
    HttpExchangeClient(["http://w/v1/task/t"], 0).poll(timeout=0.0)
    url, timeout = captured[0]
    assert "maxwait=0" in url
    assert timeout < 5.5  # grace only, not a hidden long-poll floor
    HttpExchangeClient(["http://w/v1/task/t"], 0).poll(timeout=3.0)
    url, timeout = captured[1]
    assert "maxwait=3" in url
    assert timeout == pytest.approx(8.0)  # asked-for long-poll + grace


# ------------------------------------------------------- in-process QUERY
def test_query_retry_in_process_recovers_task_failure():
    sql = ("select o_orderstatus, count(*) from orders "
           "group by o_orderstatus order by o_orderstatus")
    expected = StandaloneQueryRunner(
        default_catalog(scale_factor=0.01)).execute(sql).rows()
    inj = FailureInjector()
    inj.inject(TASK_FAILURE, fragment_id=None, task_index=0, attempt=0,
               times=1)
    r = DistributedQueryRunner(
        worker_count=2,
        session=Session(node_count=2, retry_policy="QUERY",
                        failure_injector=inj, retry_initial_delay_s=0.01))
    assert r.execute(sql).rows() == expected
    assert r.resilience.query_retries == 1
    assert [e[0] for e in r.resilience_events] == ["query_retry"]


def test_query_retry_exhausts_attempt_budget():
    inj = FailureInjector()
    # injected failure on EVERY attempt: 1 initial + 2 retries, then raise
    inj.inject(TASK_FAILURE, fragment_id=None, task_index=0, attempt=None,
               times=100)
    r = DistributedQueryRunner(
        worker_count=2,
        session=Session(node_count=2, retry_policy="QUERY",
                        query_retry_attempts=2, failure_injector=inj,
                        retry_initial_delay_s=0.01))
    with pytest.raises(InjectedFailure):
        r.execute("select count(*) from nation")
    assert r.resilience.query_retries == 2


def test_user_error_fails_fast_in_process():
    r = DistributedQueryRunner(
        worker_count=2, session=Session(node_count=2, retry_policy="QUERY"))
    t0 = time.monotonic()
    with pytest.raises(Exception, match="DIVISION_BY_ZERO"):
        r.execute(DIV_BY_ZERO_SQL)
    assert time.monotonic() - t0 < 5.0
    assert r.resilience.query_retries == 0
    assert r.resilience_events == []


def test_fte_fails_fast_on_user_error():
    """The FTE retry chain also consults classification: a USER error gets
    NO retry attempts (re-running re-runs the same bug)."""
    from trino_tpu.execution.fte import TaskFailure

    r = DistributedQueryRunner(
        worker_count=2,
        session=Session(node_count=2, retry_policy="TASK",
                        task_retry_attempts=5))
    t0 = time.monotonic()
    with pytest.raises(TaskFailure, match="after 1 attempts"):
        r.execute(DIV_BY_ZERO_SQL)
    assert time.monotonic() - t0 < 5.0


def test_resilience_session_knobs_settable():
    r = DistributedQueryRunner(worker_count=1, session=Session())
    r.execute("set session query_retry_attempts = 5")
    assert r.session.query_retry_attempts == 5
    r.execute("set session retry_policy = 'QUERY'")
    assert r.session.retry_policy == "QUERY"
    with pytest.raises(KeyError):
        r.execute("set session failure_injector = 1")


# ------------------------------------------------------------ process layer
def test_worker_boot_failure_raises_with_stderr():
    """A worker that dies before printing LISTENING surfaces as a bounded
    RuntimeError carrying its stderr — not an eternal readline() hang."""
    env = dict(_ENV)
    env["TRINO_TPU_TEST_BOOT_FAIL"] = "1"
    t0 = time.monotonic()
    with pytest.raises(RuntimeError) as ei:
        WorkerProcess(env_overrides=env, boot_timeout_s=60.0)
    assert time.monotonic() - t0 < 60.0
    msg = str(ei.value)
    assert "failed to boot" in msg
    assert "TRINO_TPU_TEST_BOOT_FAIL" in msg  # the captured stderr


def test_worker_without_a_device_is_a_classified_boot_error():
    """One process for each chip: a worker whose JAX backend cannot be
    opened (on a TPU host: the coordinator or an earlier worker holds the
    chip) fails its spawn at once with NO_NODES_AVAILABLE and the reason —
    it neither boots and fails every task nor waits out the boot timeout."""
    from trino_tpu.spi.errors import NO_NODES_AVAILABLE, TrinoError

    env = dict(_ENV)
    env["JAX_PLATFORMS"] = "no_such_platform"
    t0 = time.monotonic()
    with pytest.raises(TrinoError) as ei:
        WorkerProcess(env_overrides=env, boot_timeout_s=60.0)
    assert time.monotonic() - t0 < 60.0
    assert ei.value.code is NO_NODES_AVAILABLE
    msg = str(ei.value)
    assert "cannot open its accelerator" in msg
    assert "one process at a time" in msg
    assert "no_such_platform" in msg  # JAX's own words, from the stderr


def test_worker_status_endpoint_reports_all_tasks():
    """GET /v1/status returns node state + EVERY task's classified state in
    one payload — the one-poll-per-worker sweep's data source."""
    import json
    import urllib.request

    w = WorkerProcess(env_overrides=_ENV)
    try:
        with urllib.request.urlopen(f"{w.url}/v1/status",
                                    timeout=10) as resp:
            st = json.loads(resp.read())
        assert st["state"] == "ACTIVE"
        assert st["tasks"] == {}
    finally:
        w.kill()


def test_streaming_process_exit_recovers_bit_identical():
    """THE acceptance drill: PROCESS_EXIT kills a worker mid-stage in
    STREAMING mode; retry_policy=QUERY blacklists it, replaces it, re-runs,
    and the rows are bit-identical to a fault-free run — with the
    replacement in the event log."""
    sql = QUERIES[3]
    expected = StandaloneQueryRunner(
        default_catalog(scale_factor=0.01)).execute(sql).rows()
    inj = FailureInjector()
    r = ProcessDistributedQueryRunner(
        CATALOG_SPEC, worker_count=2,
        session=Session(node_count=2, retry_policy="QUERY",
                        failure_injector=inj, retry_initial_delay_s=0.05,
                        heartbeat_interval_s=0.2),
        env_overrides=_ENV)
    try:
        leaf = r.create_subplan(sql).all_fragments()[0]
        inj.inject(PROCESS_EXIT, fragment_id=leaf.id, task_index=0,
                   attempt=0)
        rows = r.execute(sql).rows()
        assert rows == expected  # bit-identical, order included
        kinds = [e[0] for e in r.resilience_events]
        assert "worker_replaced" in kinds
        assert "blacklist" in kinds
        assert "query_retry" in kinds
        assert r.resilience.query_retries >= 1
        assert r.resilience.worker_replacements == 1
        assert r.resilience.heartbeat_transitions >= 1
        # capacity self-healed: both slots live again
        assert [w.alive() for w in r.workers].count(True) == 2
    finally:
        r.close()


def test_streaming_user_error_fails_fast_across_processes():
    """The same drill with a USER-classified error: < 5 s, ZERO retries —
    the worker's error_type survives the wire."""
    r = ProcessDistributedQueryRunner(
        CATALOG_SPEC, worker_count=1,
        session=Session(node_count=1, retry_policy="QUERY",
                        heartbeat_interval_s=0.2),
        env_overrides=_ENV)
    try:
        t0 = time.monotonic()
        with pytest.raises(Exception, match="DIVISION_BY_ZERO"):
            r.execute(DIV_BY_ZERO_SQL)
        assert time.monotonic() - t0 < 5.0
        assert r.resilience.query_retries == 0
        assert not [e for e in r.resilience_events
                    if e[0] in ("query_retry", "blacklist")]
    finally:
        r.close()


def test_worker_replacement_cap_honored():
    """max_worker_replacements=0: the dead worker is NOT respawned; the
    retry still succeeds on the survivor and the cap refusal is logged."""
    inj = FailureInjector()
    r = ProcessDistributedQueryRunner(
        CATALOG_SPEC, worker_count=2,
        session=Session(node_count=2, retry_policy="QUERY",
                        failure_injector=inj, retry_initial_delay_s=0.05,
                        heartbeat_interval_s=0.2,
                        max_worker_replacements=0),
        env_overrides=_ENV)
    try:
        leaf = r.create_subplan(
            "select count(*) from orders").all_fragments()[0]
        inj.inject(PROCESS_EXIT, fragment_id=leaf.id, task_index=0,
                   attempt=0)
        rows = r.execute("select count(*) from orders").rows()
        assert rows == [(15000,)]
        kinds = [e[0] for e in r.resilience_events]
        assert "worker_replaced" not in kinds
        assert "replacement_cap" in kinds
        assert r.resilience.worker_replacements == 0
        assert [w.alive() for w in r.workers].count(True) == 1
    finally:
        r.close()


# ----------------------------- satellite: fleet-shared durable blacklist
def test_shared_blacklist_two_writers_merge_and_ttl(tmp_path):
    """Two coordinators pointing TRINO_TPU_BLACKLIST_PATH at one file:
    strikes recorded under A are visible (and additive) under B — no
    last-writer-wins clobbering — and TTL decay applies fleet-wide."""
    from trino_tpu.execution.speculation import ClusterBlacklist

    shared = str(tmp_path / "blacklist.jsonl")
    a = ClusterBlacklist(ttl_s=3600.0, threshold=2.0, persist=True,
                         path=shared)
    b = ClusterBlacklist(ttl_s=3600.0, threshold=2.0, persist=True,
                         path=shared)

    a.record_failure("worker-1", reason="REMOTE_HOST_GONE", query_id="qa")
    assert a.score("worker-1") == 1.0
    assert b.score("worker-1") == 1.0, "A's strike must merge into B"
    assert not b.is_blacklisted("worker-1")
    # the second strike comes from the OTHER coordinator: the scores fold
    b.record_failure("worker-1", reason="REMOTE_TASK_ERROR", query_id="qb")
    assert b.is_blacklisted("worker-1")
    assert a.is_blacklisted("worker-1"), \
        "the blacklisting must be cluster-wide, not per-coordinator"
    # no double counting of a writer's own appends
    assert a.score("worker-1") == 2.0
    assert b.score("worker-1") == 2.0

    # a third coordinator booting later merges the whole history on load
    c = ClusterBlacklist(ttl_s=3600.0, threshold=2.0, persist=True,
                         path=shared)
    assert c.is_blacklisted("worker-1")

    # TTL decay: to a tiny-TTL member every recorded strike is expired
    tiny = ClusterBlacklist(ttl_s=1e-9, threshold=2.0, persist=True,
                            path=shared)
    import time as _t
    _t.sleep(0.01)
    assert tiny.score("worker-1") == 0.0


def test_shared_blacklist_survives_interleaved_subprocess_writers(tmp_path):
    """Cross-process: two real subprocesses interleave O_APPEND strikes
    into the same file; a fresh reader folds every record."""
    import subprocess
    import sys

    shared = str(tmp_path / "bl.jsonl")
    child = (
        "import sys\n"
        "from trino_tpu.execution.resilience import SharedBlacklistStore\n"
        "s = SharedBlacklistStore(sys.argv[1])\n"
        "for i in range(50):\n"
        "    s.append('worker-x', 1.0, 'REMOTE_TASK_ERROR', sys.argv[2])\n"
    )
    procs = [subprocess.run([sys.executable, "-c", child, shared, tag],
                            cwd=os.path.dirname(os.path.dirname(
                                os.path.abspath(__file__))),
                            capture_output=True, text=True, timeout=300)
             for tag in ("qa", "qb")]
    for p in procs:
        assert p.returncode == 0, p.stderr[-2000:]

    from trino_tpu.execution.resilience import SharedBlacklistStore
    recs = SharedBlacklistStore(shared).poll()
    assert len(recs) == 100, "no torn or clobbered records"
    assert {r["query_id"] for r in recs} == {"qa", "qb"}
