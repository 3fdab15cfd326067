"""The five readers of PR 26 on a hand-made traced run, against a program
that records nothing (the parent), and in one traced CPU rehearsal."""

import json
from types import SimpleNamespace

import pytest

from harness import manifest
from harness import program_spans as P
from harness import trace as T
from test_rehearsal import rehearse

MAN = manifest.Manifest()
NEW = ["schedule_ms", "sync_wait_ms", "launch_ms",
       "eager_programs_per_query", "idle_unattributed_share"]
READERS = {n: MAN.metric_reader("per_layer", n) for n in NEW}


def span(start, seconds, kind, name, tid=1, query="q1", **args):
    return P.Span(start, seconds, kind, name, tid, query, "", args)


def a_run(**kw):
    """Two queries of 1 s in a 2.2 s window; the device works 0.2 s in each."""
    spans = []
    for k, q in enumerate(("q1", "q2")):
        t = 0.1 + k * 1.1
        spans += [
            span(t, 1.0, "execute", q, query=q),
            span(t + 0.01, 0.04, "plan", "planner", query=q),
            span(t + 0.06, 0.92, "schedule", "subplan", query=q, stages=2),
            span(t + 0.10, 0.80, "task", "f1.t0", tid=2, query=q),
            span(t + 0.20, 0.60, "task", "f1.t1", tid=3, query=q),
            span(t + 0.12, 0.70, "operator", "Agg", tid=2, query=q),
            span(t + 0.15, 0.10, "launch", "trino_kernels_reduce", tid=2,
                 query=q),
            span(t + 0.30, 0.20, "host-sync", "agg.live", tid=2, query=q),
            span(t + 0.40, 0.20, "host-sync", "agg.live", tid=3, query=q),
            span(t + 0.45, 0.05, "host-sync", "join.total", tid=3, query=q),
            span(t + 0.70, 0.02, "launch", "trino_kernels_compact", tid=3,
                 query=q),
        ]
    ops = [(0.1 + k * 1.1 + 0.5, 0.2, "fusion") for k in range(2)]
    programs = [(0.6, 0.1, "jit_trino_kernels_reduce(123)"),
                (0.7, 0.1, "jit_trino_kernels_reduce(123)"),
                (1.7, 0.2, "jit_trino_kernels_compact(9)"),
                (1.9, 0.001, "jit_add(5)"), (1.95, 0.001, "jit_add(5)"),
                (1.96, 0.002, "jit_iota(7)"), (5.0, 1.0, "jit_outside(1)")]
    run = SimpleNamespace(
        queries=2, window_s=2.2,
        trace=T.Trace(ops={"/device:TPU:0": ops},
                      programs={"/device:TPU:0": programs},
                      spans={"execute": [(0.1, 1.0), (1.2, 1.0)]},
                      window=(0.0, 2.2)),
        program_spans=sorted(spans, key=lambda s: s.start))
    for k, v in kw.items():
        setattr(run, k, v)
    return run


def test_schedule_is_execute_minus_plan_minus_the_union_of_tasks():
    # 1.0 - 0.04 - (0.10..0.90 = 0.80) = 0.16 s a query
    assert READERS["schedule_ms"].read(a_run(), 0.0) == pytest.approx(160.0)


def test_sync_wait_is_the_union_per_thread_summed(capsys):
    # thread 2: 0.20; thread 3: 0.40..0.60 and 0.45..0.50 overlap = 0.20
    assert READERS["sync_wait_ms"].read(a_run(), 0.0) == pytest.approx(400.0)
    out = capsys.readouterr().out
    assert "6 blocking syncs on 2 threads" in out
    assert "agg.live 800.000 ms in 4; join.total 100.000 ms in 2" in out


def test_launch_is_the_sum_of_launch_spans(capsys):
    assert READERS["launch_ms"].read(a_run(), 0.0) == pytest.approx(120.0)
    out = capsys.readouterr().out
    assert "trino_kernels_reduce 200.000 ms in 2 (mean 100000 us)" in out


def test_eager_programs_are_those_without_the_engines_name(capsys):
    run = a_run()
    assert READERS["eager_programs_per_query"].read(run, None) == 1.5
    out = capsys.readouterr().out
    assert "6 program executions in the window, 3 not named jit_trino_*" in out
    assert "jit_trino_kernels_reduce 0.200000 s in 2 (mean 100.000 ms)" in out
    assert "jit_add x2; jit_iota x1" in out
    # a program that names none of its own (the parent): nothing to read
    run.trace.programs = {"/device:TPU:0": [(0.5, 0.1, "jit_fn(1)")]}
    assert READERS["eager_programs_per_query"].read(run, None) is None
    run.trace.programs = {}
    assert READERS["eager_programs_per_query"].read(run, None) is None


def test_idle_unattributed_is_what_lies_under_coarse_spans_only(capsys):
    share = READERS["idle_unattributed_share"].read(a_run(), 0.0)
    out = capsys.readouterr().out
    assert "device idle 1.800000 s of the 2.200000 s window" in out
    # idle under none: 0.1 before each query; per query under execute
    # 0.01 + 0.01 + 0.02 and under task 0.02 + 0.08 (the second task's
    # start does not hide the first thread's operator); the rest under
    # plan, schedule, operator, launch and host-sync
    coarse = 2 * (0.1 + 0.04 + 0.10)
    assert share == pytest.approx(100.0 * coarse / 1.8, rel=1e-6)
    for kind in ("operator", "host-sync", "launch", "schedule", "plan"):
        assert f"{kind} 0." in out


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_events_since_gives_none_and_does_not_raise(
        name, monkeypatch, capsys):
    monkeypatch.setattr(P, "_recorder", lambda: None)
    run = SimpleNamespace(queries=1, window_s=1.0,
                          trace=T.Trace(window=(0.0, 1.0)))
    reader = READERS[name]
    since = reader.begin(run) if hasattr(reader, "begin") else None
    assert since is None
    assert reader.read(run, since) is None


@pytest.mark.parametrize("dropped, outside, why", [
    (3, [(1.0, 0.5)], "dropped 3 events"),
    (0, [], "0 spans outside, 1 inside"),
])
def test_for_run_refuses_on_drops_and_on_unmatched_calls(
        dropped, outside, why, monkeypatch, capsys):
    events = [{"ts": 100.0, "dur": 0.5, "kind": "execute", "name": "q",
               "tid": 1, "query": "q"}]
    rec = SimpleNamespace(now=lambda: 99.0,
                          events_since=lambda t: events,
                          dropped_since=lambda t: dropped)
    monkeypatch.setattr(P, "_recorder", lambda: rec)
    run = SimpleNamespace(queries=1, trace=T.Trace(
        spans={"execute": outside}, window=(0.0, 2.0)))
    assert P.begin(run) == 99.0
    assert P.for_run(run, 99.0) is None
    assert why in capsys.readouterr().out
    assert READERS["launch_ms"].read(run, 99.0) is None   # computed once


def test_for_run_maps_through_the_offset(monkeypatch, capsys):
    events = [{"ts": 100.0, "dur": 0.5, "kind": "execute", "name": "q",
               "tid": 1, "query": "q"},
              {"ts": 100.1, "dur": 0.1, "kind": "launch", "name": "trino_x",
               "tid": 1, "query": "q"}]
    rec = SimpleNamespace(now=lambda: 99.0, events_since=lambda t: events,
                          dropped_since=lambda t: 0)
    monkeypatch.setattr(P, "_recorder", lambda: rec)
    run = SimpleNamespace(queries=1, trace=T.Trace(
        spans={"execute": [(1.0, 0.5)]}, window=(0.9, 1.6)))
    spans = P.for_run(run, P.begin(run))
    assert [(s.kind, round(s.start, 6)) for s in spans] == [
        ("execute", 1.0), ("launch", 1.1)]
    assert "trace = recorder + -99.000000 s from 1 execute pairs" \
        in capsys.readouterr().out
    assert READERS["launch_ms"].read(run, 99.0) == pytest.approx(100.0)


def test_traced_rehearsal_reports_the_program_span_metrics():
    p = rehearse("sf10_q1_agg", 1, "--rehearse-sf", "0.01")
    assert p.returncode == 1, p.stderr[-2000:]
    line = [ln for ln in p.stderr.splitlines()
            if ln.startswith("REHEARSAL on cpu (not a result): ")][0]
    metrics = json.loads(line.split(": ", 1)[1])["metrics"]
    said = [ln for ln in p.stdout.splitlines() if "program spans: " in ln]
    # the CPU backend has no device plane: no XLA Modules line to count
    assert set(NEW) - set(metrics) == {"eager_programs_per_query"}, said
    assert metrics["schedule_ms"]["value"] > 0
    assert metrics["launch_ms"]["value"] > 0
    assert 0 <= metrics["idle_unattributed_share"]["value"] <= 100
    assert "program spans: " in p.stdout and " 0 dropped" in p.stdout
    assert "trino_operators_filter_project" in p.stdout


def test_longest_idle_gaps_name_the_span_under_them(capsys):
    run = a_run()
    gaps = P.longest_idle_gaps(run.trace.first_plane_ops(),
                               run.program_spans, *run.trace.window, 2)
    # the longest gap: from a query's last device operation (t + 0.7) to
    # the next query's first (t + 1.1 + 0.5); its largest part lies under
    # the next query's sync on thread 2 (0.30..0.50; the later, equally
    # deep syncs on thread 3 take over from 0.40)
    assert gaps[0][0] == pytest.approx(0.9)
    assert gaps[0][1][0][0].startswith(("operator:Agg", "host-sync:agg.live"))
    assert sum(v for _, v in gaps[0][1]) <= 0.9 + 1e-9
    READERS["idle_unattributed_share"].read(run, 0.0)
    assert "idle gap 900.000 ms under " in capsys.readouterr().out
