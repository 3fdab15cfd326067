"""The latency line and the tail split on hand-made windows."""

import pytest

import prove
from harness import spread, stats, tail


def test_latency_line_is_parsed_back():
    walls = [0.0686212345, 0.0701, 0.1234567, 0.06, 0.25, 0.07, 0.07, 0.07,
             0.07, 0.07, 0.3]
    rec = tail.latency_record(walls, clients=1)
    line = "[  150.1s] " + tail.marked(tail.LATENCY_MARK, rec)
    text = "earlier\n" + line + "\n{\"correct\": true}\n"
    back = tail.parse_marked(text, tail.LATENCY_MARK)
    assert back == rec
    assert back["n"] == len(walls) == len(back["walls_s"])
    assert back["walls_s"][:3] == [0.06862, 0.0701, 0.1235]   # 4 digits
    assert back["order"] == "sent"
    # beyond: positions of the walls above the nearest-rank 90th percentile
    assert stats.percentile(walls, 90) == 0.25
    assert back["beyond"] == [10]
    assert tail.latency_record(walls, clients=3)["order"] == "completed"
    assert tail.parse_marked("no such line\n", tail.LATENCY_MARK) is None
    assert tail.latency_record([], 1)["beyond"] == []


def ev(kind, ts, dur, query, name="", tid=1):
    return {"ts": ts, "dur": dur, "kind": kind, "name": name, "tid": tid,
            "query": query}


def a_query(qid, t, slow=0.0):
    """A query of 100 ms on the server (+ ``slow`` inside its tasks): plan
    10, schedule span 70 holding two overlapping tasks (union 50 + slow),
    execute 90 + slow, query 100 + slow."""
    return [ev("query", t, 0.100 + slow, qid, qid),
            ev("execute", t + 0.005, 0.090 + slow, qid),
            ev("plan", t + 0.006, 0.004, qid), ev("plan", t + 0.010, 0.006, qid),
            ev("schedule", t + 0.020, 0.070 + slow, qid),
            ev("task", t + 0.030, 0.030 + slow, qid, tid=2),
            ev("task", t + 0.040, 0.040 + slow, qid, tid=3),
            ev("operator", t + 0.041, 0.001, qid, tid=3)]


def test_parts_sum_to_the_wall():
    spans = tail.by_query(a_query("q", 10.0))["q"]
    parts = tail.parts_of(0.103, spans)
    assert parts == pytest.approx({
        "client_minus_query": 0.003, "query_minus_execute": 0.010,
        "plan": 0.010, "schedule": 0.020, "tasks": 0.050,
        "remainder": 0.010})
    assert sum(parts.values()) == pytest.approx(0.103)


def test_split_over_the_queries_the_recorder_still_holds():
    # 20 samples; the recorder holds the last 10 only; sample 15 stalls in
    # its tasks, sample 3 stalled too but is no longer held
    walls = [0.103] * 20
    walls[3] = walls[15] = 0.203
    events = []
    for k in range(10, 20):
        events += a_query(f"q{k}", 100.0 + k, slow=0.1 if k == 15 else 0.0)
    out = tail.split(walls, [True] * 20, events, dropped=0)
    assert out["held"] == 10 and out["right"] == 20
    assert out["threshold_s"] == stats.percentile(walls, 90) == 0.103
    assert out["beyond"]["n"] == 1 and out["rest"]["n"] == 9
    assert out["beyond"]["tasks_ms"] == pytest.approx(150.0)
    assert out["rest"]["tasks_ms"] == pytest.approx(50.0)
    for side in ("beyond", "rest"):
        assert sum(out[side][p + "_ms"] for p in tail.PARTS) == \
            pytest.approx(out[side]["wall_ms"])
    # a wrong answer is in no split, and a query without its ``query`` span
    # (evicted from the recorder's store) is not held
    right = [True] * 20
    right[19] = False
    fewer = [e for e in events
             if not (e["kind"] == "query" and e["query"] == "q10")]
    out = tail.split(walls, right, fewer, dropped=7)
    assert out["held"] == 8 and out["recorder_dropped"] == 7


def test_a_pairing_that_cannot_be_is_refused():
    # the client waited 40 ms for a query the server took 100 ms over
    out = tail.split([0.040], [True], a_query("q", 1.0), dropped=0)
    assert "refused" in out and out["held"] == 0
    # nothing recorded: nothing held, nothing refused
    out = tail.split([0.1, 0.1], [True, True], [], dropped=0)
    assert out["held"] == 0 and out["beyond"] is None


def test_pooled_split_weights_by_queries_held():
    a = {"beyond": {"n": 1, "wall_ms": 200.0, "tasks_ms": 150.0},
         "rest": {"n": 9, "wall_ms": 100.0, "tasks_ms": 50.0}}
    b = {"beyond": {"n": 3, "wall_ms": 300.0, "tasks_ms": 250.0},
         "rest": None}
    out = tail.pooled_split([a, b, None])
    assert out["beyond"] == {"n": 4, "wall_ms": 275.0, "tasks_ms": 225.0}
    assert out["rest"] == {"n": 9, "wall_ms": 100.0, "tasks_ms": 50.0}
    assert tail.pooled_split([None]) == {"beyond": None, "rest": None}


def a_result(p50, p90):
    return {"correct": True, "metrics": {
        "query_p50_s": {"value": p50, "unit": "s"},
        "query_p90_s": {"value": p90, "unit": "s"}}}


def test_prove_summarizes_sets_latency_lines_and_the_bound_rule():
    runs = []
    for s in range(2):
        for i in range(6):
            walls = [0.068 + 0.0002 * ((i + k) % 7) + (0.02 if k % 9 == s else 0)
                     for k in range(200)]
            stdout = ("[ 1.0s] " + tail.marked(
                tail.LATENCY_MARK, tail.latency_record(walls, 1)) + "\n")
            runs.append({
                "label": f"set{s}_run{i}", "seed": i, "trace": 0, "rc": 0,
                "wall_s": 1.0,
                "result": a_result(0.068 + 0.00001 * i, 0.075 + 0.001 * i * (s + 1)),
                "latencies": tail.parse_marked(stdout, tail.LATENCY_MARK),
                "tail_split": None})
    out = prove.summarize("cell", 46, 2, runs)
    p90 = out["sets"]["set1"]["query_p90_s"]
    assert len(p90["values"]) == 6
    assert p90["driver_spread"] == pytest.approx(0.008 / 0.080)
    assert p90["spread"] == pytest.approx(
        spread.iqr_spread(p90["values"]))
    assert out["bound_rule"]["query_p90_s"] == spread.bound_rule(
        [out["sets"][k]["query_p90_s"]["values"] for k in ("set0", "set1")])
    assert out["bound_rule"]["query_p90_s"] > 0.10
    assert out["bound_rule"]["query_p50_s"] == 0.01
    lat = out["latency"]["set0"]
    assert lat["pooled"]["n"] == 1200
    assert len(lat["tail"]["bootstrap_se_per_run"]) == 6
    assert out["tail_split"]["set0"] == {"beyond": None, "rest": None}
    assert all(r["correct"] for r in out["runs"])
