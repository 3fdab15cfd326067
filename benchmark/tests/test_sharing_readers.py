"""The three readers of PR 34 (``admission_wait_ms``, ``queries_in_flight``,
``task_cpu_share``) on hand-made recorder events: overlapping executions, a
task that waited on an exchange and on a transfer, a program whose task
events carry no ``cpu_s`` (the parent), and one that records nothing."""

from types import SimpleNamespace

import pytest

from harness import manifest, sharing
from harness import program_spans as P

MAN = manifest.Manifest()
NEW = ["admission_wait_ms", "queries_in_flight", "task_cpu_share"]
READERS = {n: MAN.metric_reader("per_layer", n) for n in NEW}


def ev(ts, dur, kind, name, query, task="", tid=1, **args):
    e = {"ts": ts, "dur": dur, "kind": kind, "name": name, "query": query,
         "task": task, "tid": tid, "pid": 1, "thread": "t"}
    if args:
        e["args"] = args
    return e


def three_streams(cpu=True):
    """Three queries posted at 10.0, 10.1 and 10.2 s; the runner takes each
    10, 20 and 30 ms later and holds it 1 s; the last page goes out 50 ms
    after each execution ends.  Each has two tasks of 0.8 s: the first ran
    0.2 s of CPU and waited 0.3 s in an exchange poll and 0.1 s in a
    transfer that overlaps it by 0.05 s (union 0.35 s); the second ran
    0.4 s and waited for nothing."""
    out = []
    for k, q in enumerate(("qa", "qb", "qc")):
        post = 10.0 + 0.1 * k
        start = post + 0.01 * (k + 1)
        out += [
            ev(post, start - post + 1.05, "query", q, q, state="FINISHED",
               polls=1, queued_ms=10.0 * (k + 1)),
            ev(start, 1.0, "execute", q, q, in_flight=k + 1),
            ev(start + 0.1, 0.8, "task", "f1.t0", q, "f1.t0", tid=10 + k,
               **({"cpu_s": 0.2} if cpu else {}), state="FINISHED"),
            ev(start + 0.2, 0.3, "exchange-wait", "exchange.poll", q,
               "f1.t0", tid=10 + k),
            ev(start + 0.45, 0.1, "host-sync", "agg.live", q, "f1.t0",
               tid=10 + k),
            ev(start + 0.1, 0.8, "task", "f1.t1", q, "f1.t1", tid=20 + k,
               **({"cpu_s": 0.4} if cpu else {}), state="FINISHED"),
            # another query's wait on a recycled thread id, and this task's
            # own wait before the task began: neither is this task's
            ev(start + 0.3, 0.2, "host-sync", "agg.live", "other", "f1.t1",
               tid=20 + k),
            ev(start - 0.5, 0.2, "exchange-wait", "exchange.poll", q,
               "f1.t1", tid=20 + k),
        ]
    return sorted(out, key=lambda e: e["ts"])


def a_run(monkeypatch, events, dropped=0):
    """A run whose recorder holds ``events`` since the window began."""
    rec = SimpleNamespace(now=lambda: 0.0, events_since=lambda t: events,
                          dropped_since=lambda t: dropped)
    monkeypatch.setattr(P, "_recorder", lambda: rec)
    return SimpleNamespace(queries=3, window_s=1.3)


def read(name, run):
    reader = READERS[name]
    return reader.read(run, reader.begin(run))


def test_admission_wait_is_post_to_the_runners_entry(monkeypatch, capsys):
    run = a_run(monkeypatch, three_streams())
    assert read("admission_wait_ms", run) == pytest.approx(20.0)
    out = capsys.readouterr().out
    assert "3 queries, least 10.000 most 30.000 ms" in out
    assert "queued_ms: mean 20.000 most 30.000 ms on 3 query events" in out


def test_in_flight_is_the_time_weighted_mean_of_open_executions(
        monkeypatch, capsys):
    # 3 s of open executions over the window 10.0 .. 11.28 s
    run = a_run(monkeypatch, three_streams())
    assert read("queries_in_flight", run) == pytest.approx(3.0 / 1.28)
    assert "in_flight at each start: 1 2 3" in capsys.readouterr().out
    assert sharing.in_flight_mean(
        [ev(0.0, 2.0, "execute", "a", "a")]) == (1.0, 2.0)
    # back to back, never together: exactly one open
    serial = [ev(float(k), 1.0, "execute", q, q)
              for k, q in enumerate("abc")]
    assert sharing.in_flight_mean(serial)[0] == pytest.approx(1.0)
    assert sharing.in_flight_mean([]) is None


def test_task_cpu_share_takes_the_tasks_own_waits_off_the_wall(
        monkeypatch, capsys):
    run = a_run(monkeypatch, three_streams())
    # a query: cpu 0.6 s of (0.8 - 0.35) + 0.8 = 1.25 s
    assert read("task_cpu_share", run) == pytest.approx(100 * 0.6 / 1.25)
    out = capsys.readouterr().out
    assert "6 tasks, wall 4.800000 s, of it in named waits 1.050000 s, " \
        "thread CPU 1.800000 s (least above zero 0.200000); runnable and " \
        "not running 1.950000 s" in out
    times = sharing.task_times(three_streams())
    assert [round(t["waited"], 6) for t in times
            if t["query"] == "qa"] == [0.35, 0.0]


def test_events_without_cpu_s_give_none_and_the_others_still_read(
        monkeypatch, capsys):
    run = a_run(monkeypatch, three_streams(cpu=False))
    assert read("task_cpu_share", run) is None
    assert "carry no cpu_s" in capsys.readouterr().out
    assert read("admission_wait_ms", run) == pytest.approx(20.0)
    assert read("queries_in_flight", run) == pytest.approx(3.0 / 1.28)
    # ... and a program that writes neither queued_ms nor in_flight
    bare = [dict(e, args={}) for e in three_streams(cpu=False)]
    run = a_run(monkeypatch, bare)
    assert read("admission_wait_ms", run) == pytest.approx(20.0)
    assert read("queries_in_flight", run) == pytest.approx(3.0 / 1.28)
    assert "not recorded" in capsys.readouterr().out


@pytest.mark.parametrize("name", NEW)
def test_no_recorder_a_dropped_event_or_an_empty_window_give_none(
        name, monkeypatch, capsys):
    monkeypatch.setattr(P, "_recorder", lambda: None)
    run = SimpleNamespace(queries=1, window_s=1.0)
    assert read(name, run) is None
    run = a_run(monkeypatch, three_streams(), dropped=2)
    assert read(name, run) is None
    assert "dropped 2 events" in capsys.readouterr().out
    assert read(name, a_run(monkeypatch, [])) is None
