"""One recorder, one clock (PR 26): the flight recorder's layer spans, the
mechanisms folded onto them (Tracer, OperatorStats), their mapping onto a
``jax.profiler`` trace's clock (benchmark/harness/program_spans.py), and
the statement protocol's ``stats`` and ``query`` span."""

import glob
import json
import os
import random
import sys
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from trino_tpu.connectors.catalog import default_catalog
from trino_tpu.exec import syncguard as SG
from trino_tpu.execution.distributed_runner import DistributedQueryRunner
from trino_tpu.execution.tracing import Tracer
from trino_tpu.server.protocol import TrinoTpuServer
from trino_tpu.telemetry import profiler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from harness import program_spans as P  # noqa: E402
from harness import trace as T  # noqa: E402

sys.path.pop(0)

AGG_SQL = ("select l_returnflag, l_linestatus, sum(l_quantity), count(*) "
           "from lineitem group by l_returnflag, l_linestatus")


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.setenv("TRINO_TPU_RESULT_CACHE", "0")
    prev = profiler.set_level(1)
    profiler.reset_for_test()
    yield
    profiler.set_level(prev)
    profiler.reset_for_test()


# ------------------------------------------------ offset: synthetic spans

def _calls(starts, skew=0.0, jitter=()):
    """(outside on the trace clock, inside on the recorder's) for calls that
    start at ``starts`` (recorder clock); trace = recorder + skew."""
    jitter = list(jitter) + [0.0] * len(starts)
    inside = [(s, 0.010) for s in starts]
    outside = [(s + skew - 20e-6 + j, 0.0101) for s, j in zip(starts, jitter)]
    return outside, inside


@pytest.mark.parametrize("skew", [0.0, -1.79e9, 12.5, 3600.0])
def test_offset_is_the_median_start_difference(skew):
    outside, inside = _calls([1.79e9 + k for k in (0.1, 0.9, 1.7)], skew,
                             jitter=(0.0, 40e-6, -30e-6))
    off = P.offset(outside, inside)
    assert off.seconds == pytest.approx(skew - 20e-6, abs=2e-6)
    assert off.pairs == 3 and off.spread == pytest.approx(70e-6, abs=2e-6)
    # pairing is by time order, not list order
    assert P.offset(outside[::-1], inside).seconds == off.seconds


@pytest.mark.parametrize("outside, inside, why", [
    ([], [], "0 spans outside"),
    ([(1.0, 0.1)], [], "1 spans outside, 0 inside"),
    ([(1.0, 0.1)], [(1.0, 0.1), (2.0, 0.1)], "not the same calls"),
])
def test_offset_refuses_when_the_counts_differ(outside, inside, why):
    off = P.offset(outside, inside)
    assert off.seconds is None and why in off.why


def test_offset_refuses_on_spread():
    outside, inside = _calls([10.0, 11.0, 12.0], 5.0,
                             jitter=(0.0, 0.0, 0.6e-3))
    off = P.offset(outside, inside)
    assert off.seconds is None and "spread 0.600 ms" in off.why
    assert P.offset(outside, inside, max_spread=1e-3).seconds is not None


def test_mapped_moves_events_and_keeps_those_touching_the_window():
    evs = [{"ts": 100.0, "dur": 1.0, "kind": "task", "name": "f0.t0",
            "tid": 7, "query": "q", "task": "f0.t0"},
           {"ts": 100.2, "dur": 0.1, "kind": "launch", "name": "trino_x",
            "tid": 7, "query": "q", "args": {"rows": 3}},
           {"ts": 90.0, "dur": 1.0, "kind": "task", "name": "old", "tid": 7}]
    spans = P.mapped(evs, -95.0, 5.0, 5.5)
    assert [(s.kind, s.name) for s in spans] == [("task", "f0.t0"),
                                                 ("launch", "trino_x")]
    assert spans[0].start == pytest.approx(5.0) and spans[0].end == 6.0
    assert spans[1].args == {"rows": 3} and spans[1].query == "q"
    assert P.by_kind(spans)["launch"] == [(pytest.approx(5.2), 0.1)]
    assert P.union_seconds(spans) == pytest.approx(1.0)


@pytest.mark.parametrize("seed", range(5))
def test_timeline_sweep_equals_the_harness_timeline(seed):
    rnd = random.Random(seed)
    spans = {k: [(round(rnd.uniform(0, 10), 3), round(rnd.uniform(0.01, 3), 3))
                 for _ in range(rnd.randint(1, 12))]
             for k in ("task", "operator", "launch", "host-sync")}
    assert P.timeline(spans) == T.timeline(spans)


def test_timeline_with_depth_lets_the_deepest_kind_win():
    # a task that starts late on one thread does not hide the operator at
    # work on another; among equally deep spans the later start wins
    spans = {"task": [(0.0, 10.0), (3.0, 5.0)], "operator": [(1.0, 6.0)],
             "launch": [(2.0, 0.5), (2.2, 0.1)],
             "exchange-wait": [(4.0, 1.0), (7.5, 1.0)]}
    times, names = P.timeline(spans, P.DEPTH)
    at = dict(zip(times, names))
    assert at[0.0] == "task" and at[1.0] == "operator"
    assert at[2.0] == at[2.2] == "launch" and at[2.5] == "operator"
    assert at[3.0] == "operator"             # not the second task
    # a thread in an exchange poll does not hide the operator either; it
    # shows where only tasks are open
    assert at[4.0] == "operator" and at[7.5] == "exchange-wait"
    assert at[7.0] == at[8.5] == "task" and at[10.0] == "none"
    assert T.timeline(spans)[1][times.index(3.0)] == "task"   # the harness's


def test_idle_seconds_go_to_the_innermost_kind():
    ops = [(1.0, 1.0, "a"), (4.0, 0.5, "a")]       # idle 0-1, 2-4, 4.5-8
    spans = P.mapped(
        [{"ts": 0.5, "dur": 7.0, "kind": "execute", "name": "q", "tid": 1},
         {"ts": 2.0, "dur": 3.0, "kind": "task", "name": "t", "tid": 2},
         {"ts": 2.5, "dur": 1.0, "kind": "operator", "name": "o", "tid": 2},
         {"ts": 2.6, "dur": 0.2, "kind": "host-sync", "name": "s", "tid": 2}],
        0.0, 0.0, 8.0)
    idle = P.idle_seconds_by_kind(ops, spans, 0.0, 8.0)
    assert sum(idle.values()) == pytest.approx(6.5)
    assert idle == pytest.approx({
        "none": 0.5 + 0.5, "execute": 0.5 + 2.5, "task": 0.5 + 0.5 + 0.5,
        "operator": 0.1 + 0.7, "host-sync": 0.2})
    coarse = sum(idle.get(k, 0.0) for k in P.COARSE)
    assert coarse == pytest.approx(5.5)


# ------------------------------------- the recorder's additions (program)

def test_span_is_one_event_with_attributes_and_keeps_its_clock_reads():
    profiler.set_context("q_span", "t_1")
    with profiler.span(profiler.SCHEDULE, "subplan", stages=3) as sp:
        sp.set(extra=True)
    evs = profiler.collect("q_span")
    assert len(evs) == 1
    assert evs[0]["kind"] == "schedule" and evs[0]["task"] == "t_1"
    assert evs[0]["args"] == {"stages": 3, "extra": True}
    assert evs[0]["ts"] == sp.t0 and evs[0]["dur"] == sp.t1 - sp.t0
    profiler.set_level(0)
    with profiler.span(profiler.SCHEDULE, "off") as off:
        pass
    assert off.t1 >= off.t0 > 0          # the clock is read at every level
    profiler.set_level(1)
    assert len(profiler.collect("q_span")) == 1


def test_events_since_gives_stored_and_live_events_once():
    profiler.set_context("q_a")
    t_old = profiler.now()
    profiler.event(profiler.OPERATOR, "old", t_old)
    since = profiler.now()
    profiler.event(profiler.OPERATOR, "a1", profiler.now())
    profiler.harvest("q_a")                  # stored AND still in the ring
    profiler.set_context("q_b")
    profiler.event(profiler.LAUNCH, "b1", profiler.now())   # live only
    profiler.query_event("q_a", since, profiler.now(), state="FINISHED")
    evs = profiler.events_since(since)
    assert [(e["query"], e["name"]) for e in evs] == [
        ("q_a", "q_a"), ("q_a", "a1"), ("q_b", "b1")]
    assert evs[0]["kind"] == "query"
    assert profiler.dropped_since(since) == 0
    assert [e["name"] for e in profiler.find("q_a", "query")] == ["q_a"]


def test_dropped_since_counts_only_unharvested_events_of_the_stretch(
        monkeypatch):
    monkeypatch.setattr(profiler, "_CAP", 4)
    profiler.reset_for_test()
    profiler.set_context("q_d")
    for i in range(4):
        profiler.event(profiler.OPERATOR, f"old{i}", profiler.now())
    since = profiler.now()
    for i in range(4):                       # overwrites the four old ones
        profiler.event(profiler.OPERATOR, f"new{i}", profiler.now())
    assert profiler.dropped_since(since) == 0
    profiler.harvest("q_d")
    for i in range(2):                       # overwrites new0, new1: stored
        profiler.event(profiler.OPERATOR, f"late{i}", profiler.now())
    assert profiler.dropped_since(since) == 0
    for i in range(4):                       # overwrites new2.. and late0..
        profiler.event(profiler.OPERATOR, f"last{i}", profiler.now())
    assert profiler.dropped_since(since) == 2     # late0, late1 never stored
    assert len(profiler.events_since(since)) == 4 + 4


@pytest.mark.parametrize("ready, spans", [(False, 1), (True, 0)])
def test_host_sync_span_only_when_the_transfer_blocks(monkeypatch, ready,
                                                      spans):
    monkeypatch.setattr(SG, "_is_ready", lambda x: ready)
    profiler.set_context("q_sync", "t_0")
    before = SG.snapshot()
    assert int(SG.fetch(jnp.arange(4).sum(), "test.fetch-tag")) == 6
    assert int(SG.async_scalar(jnp.arange(5).sum(), "test.async-tag")
               .get()) == 10
    evs = [e for e in profiler.collect("q_sync")
           if e["kind"] == profiler.HOST_SYNC]
    assert sorted(e["name"] for e in evs) == \
        ["test.async-tag", "test.fetch-tag"][:2 * spans]
    assert all(e["dur"] > 0 and e["task"] == "t_0" for e in evs)
    delta = SG.take_delta(before)
    assert delta.blocking_syncs == 2 * spans
    assert delta.host_syncs == 1 + spans     # a poll hit is not a host sync


@pytest.mark.parametrize("name, kind, attrs, rec_name", [
    ("trino.query", "execute", {"query_id": "q9"}, "q9"),
    ("trino.planner", "plan", {}, "planner"),
    ("trino.execution", "schedule", {}, "execution"),
    ("trino.task", "task", {"trino.task.id": "f1.t0"}, "f1.t0"),
])
def test_tracer_span_is_the_recorder_span(name, kind, attrs, rec_name):
    profiler.set_context("q_tr")
    tracer = Tracer()
    with tracer.span(name, **attrs) as sp:
        sp.record(state="FINISHED")
    evs = profiler.collect("q_tr")
    assert [(e["kind"], e["name"]) for e in evs] == [(kind, rec_name)]
    assert evs[0]["args"] == {"state": "FINISHED"}
    # one clock read each way: the span's start and end ARE the event's
    assert sp.start == evs[0]["ts"]
    assert sp.end - sp.start == evs[0]["dur"]
    assert sp.duration_ms == pytest.approx(evs[0]["dur"] * 1e3)


def test_tracer_span_without_a_recorder_kind_reads_the_same_clock():
    profiler.set_context("q_tr2")
    t0 = profiler.now()
    with Tracer().span("custom.step") as sp:
        sp.record(ignored=True)              # no recorder span: a no-op
    assert t0 <= sp.start <= sp.end <= profiler.now()
    assert profiler.collect("q_tr2") == []


def test_operator_stats_and_recorder_share_their_clock_reads():
    runner = DistributedQueryRunner(default_catalog(scale_factor=0.001),
                                    worker_count=1)
    runner.execute(AGG_SQL)                   # compile outside the reading
    res = runner.execute("explain analyze " + AGG_SQL, query_id="q_ops")
    evs = [e for e in profiler.events_for("q_ops")
           if e["kind"] == profiler.OPERATOR]
    assert evs
    text = "\n".join(r[0] for r in res.rows())
    # the wall EXPLAIN ANALYZE prints per operator is the sum of the very
    # durations the recorder stored for it
    by_name: dict = {}
    for e in evs:
        n = e["name"].removesuffix(".finish")
        by_name[n] = by_name.get(n, 0.0) + e["dur"]
    assert any(n in text for n in by_name)
    total_ms = sum(by_name.values()) * 1e3
    assert 0 < total_ms < 60_000


# ----------------------------------------- on a jax.profiler trace (CPU)

def _host_events(xplane):
    data = jax.profiler.ProfileData.from_file(xplane)
    for plane in data.planes:
        if plane.name == T.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    yield e.name, e.start_ns / 1e9, e.duration_ns / 1e9


def test_trace_annotations_and_recorder_agree_through_the_offset(tmp_path):
    runner = DistributedQueryRunner(default_catalog(scale_factor=0.001),
                                    worker_count=2)
    runner.execute(AGG_SQL)
    runner.execute(AGG_SQL)                   # warm: no compile in the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    since = profiler.now()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for k in range(3):
            # what the benchmark's host_spans wrapper does from outside
            with jax.profiler.TraceAnnotation("bench.execute"):
                runner.execute(AGG_SQL, query_id=f"q_trace_{k}")
    finally:
        jax.profiler.stop_trace()
    xplane = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    host = list(_host_events(xplane))
    outside = [(s, d) for n, s, d in host if n == "bench.execute"]
    events = profiler.events_since(since)
    assert profiler.dropped_since(since) == 0
    inside = [(e["ts"], e["dur"]) for e in events if e["kind"] == "execute"]
    off = P.offset(outside, inside, max_spread=1e-3)
    assert off.seconds is not None, off.why
    window = (min(s for s, _ in outside), max(s + d for s, d in outside))
    spans = P.mapped(events, off.seconds, *window)

    # every coarse kind is a trino.<kind>[:<name>] row of the trace, and
    # each trino.task row is the recorder's task span, within a millisecond
    rows = {}
    for n, s, d in host:
        if n.startswith("trino."):
            rows.setdefault(n.split(":")[0], []).append((n, s, d))
    assert {"trino.execute", "trino.plan", "trino.schedule",
            "trino.task"} <= set(rows)
    assert "trino.operator" not in rows and "trino.launch" not in rows
    tasks = sorted(P.of_kind(spans, "task"), key=lambda x: x.start)
    annotated = sorted(rows["trino.task"], key=lambda r: r[1])
    assert len(tasks) == len(annotated) >= 6
    for span, (name, s, d) in zip(tasks, annotated):
        assert name == f"trino.task:{span.name}"
        assert abs(span.start - s) < 1e-3 and abs(span.end - (s + d)) < 1e-3
        assert s <= span.start + 1e-3 and d >= span.seconds - 1e-3

    # every engine launch of the recorder is a PjitFunction(trino_...) of
    # the host plane under the same name, and no engine program is anonymous
    pjit = [(n[len("PjitFunction("):-1], s, d) for n, s, d in host
            if n.startswith("PjitFunction(")]
    launches = P.of_kind(spans, "launch")
    assert launches
    named = {n for n, _, _ in pjit if n.startswith("trino_")}
    assert {s.name for s in launches} <= named
    for span in launches:
        assert any(n == span.name and span.start - 1e-3 <= s
                   and s + d <= span.end + 1e-3 for n, s, d in pjit), span
    assert not {"fn", "run", "prog", "program"} & {n for n, _, _ in pjit}

    # the readers' reduction runs on it: idle seconds by innermost kind
    ops = [(s, d, n) for n, s, d in host if n.startswith("PjRtCpuExecutable")]
    idle = P.idle_seconds_by_kind(T.clip(ops, *window), spans, *window)
    assert sum(idle.values()) > 0 and set(idle) & {"operator", "launch"}


# ------------------------------------------------ the statement protocol

def _statement(base, sql):
    req = urllib.request.Request(f"{base}/v1/statement", data=sql.encode(),
                                 method="POST")
    with urllib.request.urlopen(req) as resp:
        payload = json.load(resp)
    pages = [payload]
    while payload.get("nextUri"):
        with urllib.request.urlopen(base + payload["nextUri"]) as resp:
            payload = json.load(resp)
        pages.append(payload)
    return pages


def test_protocol_stats_and_query_span():
    runner = DistributedQueryRunner(default_catalog(scale_factor=0.001),
                                    worker_count=2)
    server = TrinoTpuServer(runner).start()
    try:
        base = "http://%s:%d" % server.address
        pages = _statement(base, AGG_SQL)
        first, last = pages[0]["stats"], pages[-1]["stats"]
        assert set(first) == {"state", "queuedTimeMillis",
                              "elapsedTimeMillis", "processedRows"}
        assert last["state"] == "FINISHED"
        (n_rows,), = runner.execute("select count(*) from lineitem").rows()
        assert last["processedRows"] == n_rows > 0  # what the scans read
        assert 0 <= last["queuedTimeMillis"] <= last["elapsedTimeMillis"]
        assert last["elapsedTimeMillis"] >= 1
        qid = pages[-1]["id"]
        # the recorder's execute span is what "queued" ends at
        (ex,) = profiler.find(qid, "execute")
        (q,) = profiler.find(qid, "query")
        assert q["ts"] <= ex["ts"] and \
            q["ts"] + q["dur"] >= ex["ts"] + ex["dur"]
        from trino_tpu.telemetry import runtime as rt

        waited_ms = (ex["ts"] - q["ts"]) * 1e3 + rt.find_query(qid).queued_ms
        assert abs(last["queuedTimeMillis"] - waited_ms) <= 1.0
        # ... taken at the hand-over itself, a few microseconds earlier
        queued_ms = q["args"].pop("queued_ms")
        assert 0.0 <= queued_ms <= (ex["ts"] - q["ts"]) * 1e3 + 1e-3
        assert q["args"] == {"state": "FINISHED", "polls": len(pages) - 1}
        # ... and all kinds are in the served profile
        with urllib.request.urlopen(f"{base}/v1/query/{qid}/profile") as r:
            cats = {e["cat"] for e in json.load(r)["traceEvents"]
                    if e["ph"] == "X"}
        assert {"query", "execute", "plan", "schedule", "task", "operator",
                "launch"} <= cats
        failed = _statement(base, "select nope from lineitem")[-1]
        assert failed["stats"]["state"] == "FAILED" and "error" in failed
        assert failed["stats"]["elapsedTimeMillis"] >= 0
    finally:
        server.stop()
