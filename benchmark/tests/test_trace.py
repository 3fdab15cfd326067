"""The trace reduction on small hand-made interval lists."""

import pytest

from harness import trace as T

OPS = [(1.0, 1.0, "a"), (1.5, 1.0, "b"), (4.0, 0.5, "a"), (6.0, 1.0, "c")]


def test_union_merges_overlap_and_nesting():
    assert T.union(OPS) == [(1.0, 2.5), (4.0, 4.5), (6.0, 7.0)]
    assert T.union([(0, 10, "w"), (2, 1, "in")]) == [(0, 10)]
    assert T.union([]) == []


def test_busy_clip_and_idle_share():
    assert T.busy_seconds(OPS) == pytest.approx(3.0)
    assert T.busy_seconds(T.clip(OPS, 2.0, 6.5)) == pytest.approx(1.5)
    tr = T.Trace(ops={"/device:TPU:0": OPS}, window=(0.0, 8.0))
    assert tr.busy_s() == pytest.approx(3.0)
    assert tr.idle_share() == pytest.approx(5.0 / 8.0)
    # two chips: busy seconds are averaged over them
    tr2 = T.Trace(ops={"/device:TPU:0": OPS, "/device:TPU:1": []},
                  window=(0.0, 8.0))
    assert tr2.busy_s() == pytest.approx(1.5)


def test_gaps_cover_what_no_operation_covers():
    assert T.gaps(OPS, 0.0, 8.0) == [
        (0.0, 1.0), (2.5, 1.5), (4.5, 1.5), (7.0, 1.0)]
    assert T.gaps([], 1.0, 2.0) == [(1.0, 1.0)]
    assert T.gaps([(0, 10, "w")], 1.0, 2.0) == []


SPANS = {"client": [(0.5, 7.0)], "execute": [(0.8, 6.0)],
         "plan": [(0.9, 0.05)], "task": [(2.6, 1.0), (3.0, 3.5)]}


def test_innermost_is_the_open_span_that_started_last():
    assert T.innermost(SPANS, 0.1) == "none"
    assert T.innermost(SPANS, 0.6) == "client"
    assert T.innermost(SPANS, 0.92) == "plan"
    assert T.innermost(SPANS, 2.0) == "execute"
    assert T.innermost(SPANS, 3.2) == "task"
    assert T.innermost(SPANS, 7.2) == "client"
    assert T.innermost(SPANS, 7.6) == "none"


def test_gap_attribution_cuts_gaps_at_span_boundaries():
    line = T.timeline(SPANS)
    parts = T.attribute((2.5, 1.5), line)  # 2.5..2.6 execute, then task
    assert parts == pytest.approx({"execute": 0.1, "task": 1.4})
    parts = T.attribute((0.0, 1.0), line)
    assert parts == pytest.approx(
        {"none": 0.5, "client": 0.3, "execute": 0.15, "plan": 0.05})
    total = T.idle_seconds_by_span(OPS, SPANS, 0.0, 8.0)
    assert sum(total.values()) == pytest.approx(5.0)
    assert total["none"] == pytest.approx(0.5 + 0.5)
    longest = T.idle_gaps_by_span(OPS, SPANS, 0.0, 8.0, n=2)
    assert [g[1] for g in longest] == pytest.approx([1.5, 1.5])
    assert [g[0] for g in longest] == ["task", "task"]


def test_top_operations_and_program_runs():
    assert T.top_operations(OPS, n=2) == [["a", 1.5], ["b", 1.0]]
    tr = T.Trace(ops={"/device:TPU:0": OPS},
                 programs={"/device:TPU:0": [(1.0, 1.5, "jit_f"),
                                             (4.0, 0.5, "jit_g"),
                                             (9.0, 1.0, "outside")]},
                 spans=SPANS, window=(0.5, 7.5))
    assert tr.program_runs() == 2
    b = tr.breakdown()
    # by program since PR 31: "a" ran in jit_f and in jit_g, "c" in neither
    assert b["device_ops"] == [["c", 1.0], ["jit_f / a", 1.0],
                               ["jit_f / b", 1.0], ["jit_g / a", 0.5]]
    assert len(b["idle_gaps"]) <= T.TOP
    # no device plane: nothing to read, no division by zero
    assert T.Trace(window=(0.0, 1.0)).busy_s() == 0.0


def test_breakdown_by_program_keeps_every_sum():
    # "a" runs in two programs and once outside any; names change, seconds
    # do not
    programs = [(0.9, 1.7, "jit_trino_f(12)"), (3.9, 0.7, "jit_g(3)"),
                (9.0, 1.0, "jit_outside(1)")]
    ops = [(1.0, 1.0, "%a = f32[6] fusion()"), (1.5, 1.0, "b"),
           (4.0, 0.5, "%a = f32[6] fusion()"), (6.0, 0.9, "%a = f32[6] fusion()")]
    named = T.named_by_program(ops, programs)
    assert [n for _, _, n in named] == [
        "jit_trino_f / a = f32[6] fusion()", "jit_trino_f / b",
        "jit_g / a = f32[6] fusion()", "%a = f32[6] fusion()"]
    assert [(s, d) for s, d, _ in named] == [(s, d) for s, d, _ in ops]
    old, new = T.top_operations(ops), T.top_operations(named)
    assert sum(v for _, v in new) == pytest.approx(sum(v for _, v in old))
    assert len(new) == 4 and len(old) == 2
    tr = T.Trace(ops={"/device:TPU:0": ops},
                 programs={"/device:TPU:0": programs},
                 spans=SPANS, window=(0.5, 7.5))
    b = tr.breakdown()
    assert b["device_ops"][0] == ["jit_trino_f / a = f32[6] fusion()", 1.0]
    assert sum(v for _, v in b["device_ops"]) == pytest.approx(3.4)
    # idle gaps are read from the operations' intervals, not their names
    assert b["idle_gaps"] == T.idle_gaps_by_span(
        T.clip(ops, 0.5, 7.5), SPANS, 0.5, 7.5)
    # no program line (a CPU rehearsal): the names stay
    assert T.named_by_program(ops, []) == ops
