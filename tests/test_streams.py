"""More than one query in flight on the served path: three client threads
over one ``TrinoTpuServer`` and one ``DistributedQueryRunner``, as the cell
``sf10_streams3`` runs them — result cache off, history-based planning on
with a fresh journal, no fused stage, no collectives.  Every answer of
every stream is the sqlite oracle's and the one-stream answer; nothing a
query holds (aggregation state, plan, residual, join estimate) crosses into
another; a one-client warm-up leaves the three-client phase no program to
get; and the flight recorder's events say whose they are and how the
queries shared the runner (``cpu_s``, ``in_flight``, ``queued_ms``)."""

import json
import random
import threading
import urllib.request

import pytest

from test_unique_gather_sizing import _pinned_catalog
from trino_tpu.connectors.tpch_queries import QUERIES
from trino_tpu.planner import history
from trino_tpu.runner import Session
from trino_tpu.telemetry import journal, profiler
from trino_tpu.testing.oracle import SqliteOracle, assert_same_rows

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

STREAMS = 3
Q1, Q3, Q6 = QUERIES[1], QUERIES[3], QUERIES[6]


@pytest.fixture(scope="module")
def catalog():
    """SF0.01 behind the memory connector in pinned 16384-row device
    batches with the source's statistics: the cells' load, small."""
    return _pinned_catalog(0.01)


@pytest.fixture(scope="module")
def oracle(catalog):
    o = SqliteOracle()
    o.load_connector_tables(catalog.connector("tpch"),
                            ("customer", "orders", "lineitem"))
    return o


@pytest.fixture
def served(catalog, tmp_path, monkeypatch):
    """(runner, base url) of a fresh server as the cells deploy it."""
    from trino_tpu.caching import plan_cache, result_cache
    from trino_tpu.execution.distributed_runner import DistributedQueryRunner
    from trino_tpu.server.protocol import TrinoTpuServer

    def reset():
        journal.reset_for_test()
        plan_cache.reset_for_test()
        result_cache.reset_for_test()
        history.reset_for_test()

    monkeypatch.setenv("TRINO_TPU_JOURNAL_DIR", str(tmp_path / "journal"))
    monkeypatch.setenv("TRINO_TPU_HBO", "1")
    monkeypatch.setenv("TRINO_TPU_FUSED_STAGE", "0")
    reset()
    runner = DistributedQueryRunner(
        catalog, worker_count=2,
        session=Session(default_catalog="memory", node_count=2,
                        use_collectives=False))
    server = TrinoTpuServer(runner).start()
    try:
        with result_cache.disabled():
            yield runner, "http://%s:%d" % server.address
    finally:
        server.stop()
        reset()


def statement(base: str, sql: str) -> tuple:
    """(rows as the protocol's JSON gives them, every page) of one query."""
    req = urllib.request.Request(f"{base}/v1/statement", data=sql.encode(),
                                 method="POST")
    with urllib.request.urlopen(req) as resp:
        page = json.load(resp)
    pages, rows = [page], list(page.get("data", []))
    while page.get("nextUri"):
        with urllib.request.urlopen(base + page["nextUri"]) as resp:
            page = json.load(resp)
        pages.append(page)
        rows.extend(page.get("data", []))
    assert "error" not in page, page
    return rows, pages


def as_json(rows) -> list:
    from trino_tpu.server.protocol import _json_value

    return [[_json_value(v) for v in row] for row in rows]


def streams(base: str, orders: list) -> list:
    """One closed-loop client thread per list of statements; returns, per
    stream, [(sql, rows, pages)] in the order sent.  A barrier starts the
    streams together, so their first queries overlap."""
    out = [[] for _ in orders]
    errors = []
    barrier = threading.Barrier(len(orders))

    def client(i):
        try:
            barrier.wait(timeout=60)
            for sql in orders[i]:
                out[i].append((sql, *statement(base, sql)))
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(i,),
                                name=f"stream-{i}")
               for i in range(len(orders))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    if errors:
        raise errors[0]
    assert all(len(o) == len(sqls) for o, sqls in zip(out, orders))
    return out


def events_with_queries(t0: float, n: int) -> list:
    """The recorder's events since ``t0``, once it holds ``n`` ``query``
    events: the server writes a query's after it has sent the last page,
    so the client can be a moment ahead of it."""
    import time

    deadline = time.monotonic() + 10.0
    while True:
        events = profiler.events_since(t0)
        if sum(e["kind"] == "query" for e in events) >= n \
                or time.monotonic() > deadline:
            return events
        time.sleep(0.01)


def blocks(block: list, seed: int, n_blocks: int = 2) -> list:
    """Each stream's order: ``n_blocks`` blocks, each shuffled from (seed,
    stream, block) as benchmark/harness/load.sequence does."""
    orders = []
    for stream in range(STREAMS):
        order = []
        for n in range(n_blocks):
            b = list(block)
            random.Random(f"{seed}/{stream}/{n}").shuffle(b)
            order += b
        orders.append(order)
    return orders


def one_stream_answers(runner, base, oracle, sqls) -> dict:
    """{sql: its JSON rows, one query at a time}, each checked against the
    sqlite oracle first (both the runner's rows and the served ones)."""
    expected = {}
    for sql in sqls:
        direct = runner.execute(sql).rows()
        assert_same_rows(direct, oracle.query(sql), ordered=True)
        expected[sql], _ = statement(base, sql)
        assert expected[sql] == as_json(direct)
    return expected


# (1), (2): the cell's block, and the same with the join path in it
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
@pytest.mark.parametrize("block", [(Q6, Q6, Q1), (Q6, Q3, Q6, Q1)],
                         ids=["q6q6q1", "with_q3"])
def test_every_answer_of_every_stream_is_the_one_stream_answer(
        block, seed, served, oracle):
    runner, base = served
    expected = one_stream_answers(runner, base, oracle, set(block))
    for stream in streams(base, blocks(list(block), seed)):
        for sql, rows, _ in stream:
            assert rows == expected[sql]


# (3): nothing a query holds crosses into the one beside it
@pytest.mark.parametrize("days", [(90, 400, 1200), (1200, 90, 400)],
                         ids=str)
def test_concurrent_q1_with_different_literals_get_different_right_answers(
        days, served, oracle):
    runner, base = served
    sqls = [Q1.replace("interval '90' day", f"interval '{d}' day")
            for d in days]
    assert len(set(sqls)) == STREAMS
    # the first time any of the three runs is the concurrent one: plans,
    # residuals and aggregation states are all made side by side
    got = streams(base, [[sql, sql] for sql in sqls])
    answers = []
    for (sql, first, _), (_, second, _) in got:
        want = as_json(oracle.query(sql))
        assert_same_rows([tuple(r) for r in _numbers(first)],
                         [tuple(r) for r in _numbers(want)], ordered=True)
        assert first == second
        answers.append(json.dumps(first))
    assert len(set(answers)) == STREAMS
    for sql, answer in zip(sqls, answers):   # and alone afterwards
        assert json.dumps(statement(base, sql)[0]) == answer


def _numbers(rows: list) -> list:
    """Decimal strings of the protocol's JSON as floats, for the oracle's
    comparison (sqlite sums in floating point)."""
    def num(v):
        try:
            return float(v) if isinstance(v, str) else v
        except ValueError:
            return v

    return [[num(v) for v in row] for row in rows]


# (4): a one-client warm-up leaves the three-client phase no program to get
def test_three_clients_get_no_new_program_after_a_one_client_warm_up(served):
    import jax.monitoring as mon

    from trino_tpu.caching import executable_cache

    runner, base = served
    for sql in (Q6, Q1):           # as run.warm_up: until two quiet ones
        for _ in range(3):
            statement(base, sql)
    compiled = []

    def on_compile(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(kw.get("fun_name", "?"))

    mon.register_event_duration_secs_listener(on_compile)
    try:
        misses = executable_cache.aggregate_stats()["misses"]
        streams(base, blocks([Q6, Q6, Q1], seed=3))
        assert executable_cache.aggregate_stats()["misses"] == misses
        assert compiled == []
    finally:
        mon.unregister_event_duration_listener(on_compile)


# (4b): once the recorded numbers repeat, three clients plan from the cache
def test_three_clients_hit_the_plan_cache_after_their_first_block(served):
    """A finished query appends to the journal, but the history epoch is
    the folded table's: three clients' lookups go to the same Tier A keys,
    each reads what the others appended (not the journal again), and the
    recorder's ``plan`` span says so."""
    from trino_tpu.caching import plan_cache
    from trino_tpu.telemetry import metrics as tm

    runner, base = served
    for sql in (Q6, Q1):
        for _ in range(3):
            statement(base, sql)
    streams(base, blocks([Q6, Q6, Q1], seed=11, n_blocks=1))
    before = plan_cache.stats()
    counters = {c: c.value() for c in (
        tm.JOURNAL_BYTES, tm.HBO_JOURNAL_BYTES_READ, tm.HBO_TABLE_REBUILDS)}
    t0 = profiler.now()
    got = streams(base, blocks([Q6, Q6, Q1], seed=12, n_blocks=3))
    after = plan_cache.stats()
    hits = after["hits"] - before["hits"]
    lookups = hits + after["misses"] - before["misses"]
    assert lookups == sum(len(s) for s in got) == 9 * STREAMS
    assert hits / lookups >= 0.9, (before, after)
    moved = {c: c.value() - v for c, v in counters.items()}
    assert moved[tm.HBO_TABLE_REBUILDS] == 0
    assert 0 < moved[tm.HBO_JOURNAL_BYTES_READ] \
        <= 1.1 * moved[tm.JOURNAL_BYTES]
    lookups = [e for e in events_with_queries(t0, 9 * STREAMS)
               if e["kind"] == "plan" and e["name"] == "cache-lookup"]
    assert len(lookups) == 9 * STREAMS
    assert sum(e["args"]["cache_hit"] for e in lookups) == hits
    assert len({e["args"]["epoch"] for e in lookups}) <= 2
    assert all(e["args"]["epoch"] and e["args"]["journal_bytes_read"] >= 0
               for e in lookups)
    # a lookup reads what was appended since the last one, by any stream
    assert sum(e["args"]["journal_bytes_read"] for e in lookups) \
        <= moved[tm.HBO_JOURNAL_BYTES_READ]
    with urllib.request.urlopen(f"{base}/v1/metrics") as resp:
        text = resp.read().decode()
    for family in ("trino_hbo_journal_bytes_read_total",
                   "trino_hbo_table_folds_total",
                   "trino_hbo_table_rebuilds_total"):
        assert f"\n{family} " in text


# (5), (6): the recorder under three open queries
@pytest.fixture
def three_open(served):
    """Three streams of {Q6, Q1} after a warm-up: (base, every query's
    last page, the recorder's events since the streams began)."""
    runner, base = served
    for sql in (Q6, Q1):
        statement(base, sql)
    t0 = profiler.now()
    got = streams(base, blocks([Q6, Q1], seed=5, n_blocks=1))
    last = {pages[-1]["id"]: pages[-1] for s in got for _, _, pages in s}
    assert len(last) == 2 * STREAMS
    assert profiler.dropped_since(t0) == 0
    return base, last, events_with_queries(t0, len(last))


def test_every_event_carries_its_own_querys_id(three_open):
    _, last, events = three_open
    tasks = [e for e in events if e["kind"] == "task"]
    assert {e["query"] for e in tasks} == set(last)
    inner = [e for e in events if e["kind"] in ("launch", "operator")]
    assert inner and {e["query"] for e in inner} == set(last)
    for e in inner:
        # the task that was open on the event's own thread when it began
        # (task threads are fresh per task; an id may come round again)
        on_thread = [t for t in tasks if t["tid"] == e["tid"]
                     and t["ts"] <= e["ts"] <= t["ts"] + t["dur"]]
        assert len(on_thread) == 1, e
        assert (e["query"], e["task"]) == \
            (on_thread[0]["query"], on_thread[0]["task"]), e
    for qid in last:
        (ex,) = [e for e in events
                 if e["kind"] == "execute" and e["query"] == qid]
        for t in (t for t in tasks if t["query"] == qid):
            assert ex["ts"] <= t["ts"] and \
                t["ts"] + t["dur"] <= ex["ts"] + ex["dur"] + 1e-3


def test_sharing_attributes_on_task_execute_and_query_events(three_open):
    base, last, events = three_open
    tasks = [e for e in events if e["kind"] == "task"]
    assert len(tasks) >= 3 * len(last)      # two PARTIAL tasks and a FINAL
    for t in tasks:
        assert 0.0 <= t["args"]["cpu_s"] <= t["dur"] + 0.02, t
    assert sum(t["args"]["cpu_s"] for t in tasks) > 0
    executes = [e for e in events if e["kind"] == "execute"]
    assert len(executes) == len(last)
    starts = sorted(e["args"]["in_flight"] for e in executes)
    assert 1 <= starts[0] and starts[-1] <= STREAMS
    # in_flight is what the spans themselves say: the executions open at
    # this one's start, itself included
    for e in executes:
        open_then = sum(1 for o in executes
                        if o["ts"] <= e["ts"] < o["ts"] + o["dur"])
        assert abs(e["args"]["in_flight"] - open_then) <= 1, (e, open_then)
    assert starts[-1] >= 2      # the barrier made the streams overlap
    queries = [e for e in events if e["kind"] == "query"]
    assert {e["query"] for e in queries} == set(last)
    for q in queries:
        assert q["args"]["queued_ms"] >= 0.0
        (ex,) = [e for e in executes if e["query"] == q["query"]]
        assert q["args"]["queued_ms"] == pytest.approx(
            (ex["ts"] - q["ts"]) * 1e3, abs=1.0)
    # ... and the served profile shows them
    qid = next(iter(last))
    with urllib.request.urlopen(f"{base}/v1/query/{qid}/profile") as r:
        shown = [e for e in json.load(r)["traceEvents"] if e["ph"] == "X"]
    by_cat = {e["cat"]: e.get("args", {}) for e in shown}
    assert "cpu_s" in by_cat["task"] and "in_flight" in by_cat["execute"] \
        and "queued_ms" in by_cat["query"]


def test_queued_time_of_the_protocol_is_the_recorded_queued_ms(three_open):
    from trino_tpu.telemetry import runtime as rt

    _, last, events = three_open
    for q in (e for e in events if e["kind"] == "query"):
        stats = last[q["query"]]["stats"]
        in_group = rt.find_query(q["query"]).queued_ms
        assert abs(stats["queuedTimeMillis"]
                   - (q["args"]["queued_ms"] + in_group)) <= 1.0
        assert stats["queuedTimeMillis"] <= stats["elapsedTimeMillis"]


def test_more_streams_than_slots_wait_and_say_so(served):
    """One dispatcher slot for three streams: two queries queue behind the
    first, ``queued_ms`` and ``queuedTimeMillis`` say for how long, and
    ``in_flight`` never passes 1."""
    from trino_tpu.server.protocol import TrinoTpuServer

    runner, _ = served
    server = TrinoTpuServer(runner, max_concurrent=1).start()
    try:
        base = "http://%s:%d" % server.address
        statement(base, Q6)
        t0 = profiler.now()
        got = streams(base, [[Q6]] * STREAMS)
        events = events_with_queries(t0, STREAMS)
    finally:
        server.stop()
    executes = sorted((e for e in events if e["kind"] == "execute"),
                      key=lambda e: e["ts"])
    assert [e["args"]["in_flight"] for e in executes] == [1] * STREAMS
    for a, b in zip(executes, executes[1:]):
        assert a["ts"] + a["dur"] <= b["ts"]
    queued = sorted(e["args"]["queued_ms"] for e in events
                    if e["kind"] == "query")
    # the second waited about one execution, the third about two
    assert queued[1] >= executes[0]["dur"] * 1e3 * 0.5
    assert queued[2] >= queued[1]
    stats = sorted(pages[-1]["stats"]["queuedTimeMillis"]
                   for (_, _, pages), in got)
    assert [abs(s - q) <= 1.0 for s, q in zip(stats, queued)] == [True] * 3


def test_explain_analyze_has_one_sharing_line_and_the_gauge_returns_to_zero(
        served):
    from trino_tpu.telemetry.metrics import DISPATCHER_IN_FLIGHT

    runner, base = served
    rows, _ = statement(base, "explain analyze " + Q6)
    sharing = [r[0].strip() for r in rows
               if r[0].strip().startswith("sharing: ")]
    assert len(sharing) == 1
    assert sharing[0].startswith("sharing: 1 in flight at start, task cpu ")
    assert DISPATCHER_IN_FLIGHT.value() == 0
    with urllib.request.urlopen(f"{base}/v1/metrics") as r:
        assert "trino_dispatcher_in_flight 0" in r.read().decode()


def test_open_executions_are_counted_under_contention():
    """More threads than cores opening and closing executions on one
    tracer under a short switch interval: no update is lost, and no thread
    ever sees more open than there are threads."""
    import sys

    from trino_tpu.execution.tracing import Tracer

    tracer, threads_n, rounds = Tracer(), 16, 500
    seen = [0] * threads_n

    def worker(i):
        for _ in range(rounds):
            seen[i] = max(seen[i], tracer.query_opened())
            tracer.query_closed()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(threads_n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert 1 <= min(seen) and max(seen) <= threads_n
    assert tracer.query_opened() == 1    # every open was closed
