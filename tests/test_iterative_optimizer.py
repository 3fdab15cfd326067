"""Iterative rule engine: plan-shape tests — one fires/does-not-fire pair
per rule — plus memo dedup units and the multi-equality-conjunct
estimate regression (reference: the per-rule *Test classes under
core/trino-main/src/test/.../sql/planner/iterative/rule/ and
TestMemo.java)."""

import pytest

from trino_tpu.connectors.catalog import default_catalog
from trino_tpu.planner.iterative.driver import (IterativeOptimizer,
                                                last_report)
from trino_tpu.planner.iterative.memo import GroupRef, Memo
from trino_tpu.planner.iterative.rule import Context, Trace
from trino_tpu.planner.iterative.rules import (aggregates, decorrelate,
                                               limits, prune, reorder,
                                               simplify)
from trino_tpu.planner.optimizer import estimate_rows
from trino_tpu.planner.plan import (AggCall, Aggregate, CorrelatedJoin,
                                    Filter, Join, Limit, Project, SemiJoin,
                                    Union, Values)
from trino_tpu.sql.ir import Call, InputRef, Literal
from trino_tpu.spi.types import BIGINT, BOOLEAN


CATALOG = default_catalog(scale_factor=0.01)


def run_rules(root, rules):
    """One-phase fixpoint over the memo; -> (optimized tree, trace)."""
    ctx = Context(catalog=CATALOG, history=None, trace=Trace())
    out = IterativeOptimizer(phases=(("test", tuple(rules)),)).run(root, ctx)
    return out, ctx.trace


def vals(n=10, cols=("k", "v")):
    return Values(tuple(cols), (BIGINT,) * len(cols),
                  tuple(tuple(i * 10 + c for c in range(len(cols)))
                        for i in range(n)))


def gt(ch, lit):
    return Call(BOOLEAN, "gt", (InputRef(BIGINT, ch), Literal(BIGINT, lit)))


def _walk(node):
    yield node
    for c in node.children:
        yield from _walk(c)


# ------------------------------------------------------------- simplify

def test_merge_adjacent_filters_fires():
    v = vals()
    tree = Filter(v.output_names, v.output_types,
                  Filter(v.output_names, v.output_types, v, gt(0, 1)),
                  gt(1, 2))
    out, trace = run_rules(tree, [simplify.MergeAdjacentFilters()])
    assert trace.fired("MergeAdjacentFilters") == 1
    assert isinstance(out, Filter) and isinstance(out.source, Values)


def test_merge_adjacent_filters_does_not_fire_on_single_filter():
    v = vals()
    tree = Filter(v.output_names, v.output_types, v, gt(0, 1))
    out, trace = run_rules(tree, [simplify.MergeAdjacentFilters()])
    assert trace.fired("MergeAdjacentFilters") == 0
    assert out == tree


def test_merge_adjacent_projects_fires_on_trivial_inner():
    v = vals()
    inner = Project(("v", "k"), (BIGINT, BIGINT), v,
                    (InputRef(BIGINT, 1), InputRef(BIGINT, 0)))
    tree = Project(("k",), (BIGINT,), inner, (InputRef(BIGINT, 1),))
    out, trace = run_rules(tree, [simplify.MergeAdjacentProjects()])
    assert trace.fired("MergeAdjacentProjects") == 1
    assert isinstance(out, Project) and isinstance(out.source, Values)
    assert out.expressions == (InputRef(BIGINT, 0),)


def test_merge_adjacent_projects_does_not_fire_on_computed_inner():
    v = vals()
    inner = Project(("s",), (BIGINT,), v,
                    (Call(BIGINT, "add",
                          (InputRef(BIGINT, 0), InputRef(BIGINT, 1))),))
    tree = Project(("a", "b"), (BIGINT, BIGINT), inner,
                   (InputRef(BIGINT, 0), InputRef(BIGINT, 0)))
    _, trace = run_rules(tree, [simplify.MergeAdjacentProjects()])
    assert trace.fired("MergeAdjacentProjects") == 0


def test_inline_projections_fires_when_referenced_once():
    v = vals()
    inner = Project(("s",), (BIGINT,), v,
                    (Call(BIGINT, "add",
                          (InputRef(BIGINT, 0), InputRef(BIGINT, 1))),))
    tree = Project(("s2",), (BIGINT,), inner, (InputRef(BIGINT, 0),))
    out, trace = run_rules(tree, [simplify.InlineProjections()])
    assert trace.fired("InlineProjections") == 1
    assert isinstance(out, Project) and isinstance(out.source, Values)


def test_inline_projections_does_not_fire_when_referenced_twice():
    v = vals()
    inner = Project(("s",), (BIGINT,), v,
                    (Call(BIGINT, "add",
                          (InputRef(BIGINT, 0), InputRef(BIGINT, 1))),))
    tree = Project(("a", "b"), (BIGINT, BIGINT), inner,
                   (InputRef(BIGINT, 0), InputRef(BIGINT, 0)))
    _, trace = run_rules(tree, [simplify.InlineProjections()])
    assert trace.fired("InlineProjections") == 0


def test_remove_redundant_identity_projection_fires():
    v = vals()
    tree = Project(v.output_names, v.output_types, v,
                   (InputRef(BIGINT, 0), InputRef(BIGINT, 1)))
    out, trace = run_rules(tree,
                           [simplify.RemoveRedundantIdentityProjections()])
    assert trace.fired("RemoveRedundantIdentityProjections") == 1
    assert out == v


def test_remove_redundant_identity_projection_keeps_renames():
    v = vals()
    tree = Project(("x", "y"), v.output_types, v,
                   (InputRef(BIGINT, 0), InputRef(BIGINT, 1)))
    out, trace = run_rules(tree,
                           [simplify.RemoveRedundantIdentityProjections()])
    assert trace.fired("RemoveRedundantIdentityProjections") == 0
    assert out == tree


def test_remove_trivial_filters_fires_on_constant_true():
    v = vals()
    tree = Filter(v.output_names, v.output_types, v,
                  Literal(BOOLEAN, True))
    out, trace = run_rules(tree, [simplify.RemoveTrivialFilters()])
    assert trace.fired("RemoveTrivialFilters") == 1
    assert out == v


def test_remove_trivial_filters_false_becomes_empty_values():
    v = vals()
    tree = Filter(v.output_names, v.output_types, v,
                  Literal(BOOLEAN, False))
    out, trace = run_rules(tree, [simplify.RemoveTrivialFilters()])
    assert trace.fired("RemoveTrivialFilters") == 1
    assert isinstance(out, Values) and out.rows == ()
    assert out.output_names == v.output_names


def test_remove_trivial_filters_does_not_fire_on_real_predicate():
    v = vals()
    tree = Filter(v.output_names, v.output_types, v, gt(0, 1))
    _, trace = run_rules(tree, [simplify.RemoveTrivialFilters()])
    assert trace.fired("RemoveTrivialFilters") == 0


def test_evaluate_zero_input_fires_through_row_preserving_chain():
    empty = Values(("k", "v"), (BIGINT, BIGINT), ())
    tree = Filter(empty.output_names, empty.output_types, empty, gt(0, 1))
    out, trace = run_rules(tree, [simplify.EvaluateZeroInput()])
    assert trace.fired("EvaluateZeroInput") == 1
    assert isinstance(out, Values) and out.rows == ()


def test_evaluate_zero_input_empties_inner_join():
    empty = Values(("k",), (BIGINT,), ())
    right = vals(cols=("k2", "w"))
    tree = Join(("k", "k2", "w"), (BIGINT,) * 3, empty, right,
                "INNER", (0,), (0,), None)
    out, trace = run_rules(tree, [simplify.EvaluateZeroInput()])
    assert trace.fired("EvaluateZeroInput") == 1
    assert isinstance(out, Values) and out.rows == ()
    assert out.output_names == ("k", "k2", "w")


def test_evaluate_zero_input_does_not_fire_on_populated_inputs():
    v = vals()
    tree = Filter(v.output_names, v.output_types, v, gt(0, 1))
    _, trace = run_rules(tree, [simplify.EvaluateZeroInput()])
    assert trace.fired("EvaluateZeroInput") == 0


# --------------------------------------------------------------- limits

def test_push_limit_through_project_fires():
    v = vals()
    proj = Project(("v",), (BIGINT,), v, (InputRef(BIGINT, 1),))
    tree = Limit(("v",), (BIGINT,), proj, 5)
    out, trace = run_rules(tree, [limits.PushLimitThroughProject()])
    assert trace.fired("PushLimitThroughProject") == 1
    assert isinstance(out, Project) and isinstance(out.source, Limit)
    assert out.source.count == 5


def test_push_limit_through_project_does_not_fire_elsewhere():
    v = vals()
    tree = Limit(v.output_names, v.output_types, v, 5)
    _, trace = run_rules(tree, [limits.PushLimitThroughProject()])
    assert trace.fired("PushLimitThroughProject") == 0


def _semijoin(source):
    filt = vals(cols=("k2",))
    names = source.output_names + ("mark",)
    types = source.output_types + (BOOLEAN,)
    return SemiJoin(names, types, source, filt, (0,), (0,))


def test_push_limit_through_semijoin_fires_once():
    sj = _semijoin(vals())
    tree = Limit(sj.output_names, sj.output_types, sj, 5)
    out, trace = run_rules(tree, [limits.PushLimitThroughSemiJoin()])
    assert trace.fired("PushLimitThroughSemiJoin") == 1
    assert isinstance(out, SemiJoin)  # outer limit dropped: mark preserves n
    assert isinstance(out.source, Limit) and out.source.count == 5
    # fixpoint: re-running on its own output must not fire again
    _, trace2 = run_rules(out, [limits.PushLimitThroughSemiJoin()])
    assert trace2.fired("PushLimitThroughSemiJoin") == 0


def test_push_limit_through_left_join_fires_and_keeps_outer():
    left, right = vals(), vals(cols=("k2", "w"))
    join = Join(left.output_names + right.output_names, (BIGINT,) * 4,
                left, right, "LEFT", (0,), (0,), None)
    tree = Limit(join.output_names, join.output_types, join, 5)
    out, trace = run_rules(tree, [limits.PushLimitThroughJoin()])
    assert trace.fired("PushLimitThroughJoin") == 1
    assert isinstance(out, Limit)  # outer stays: join may expand rows
    inner = next(n for n in _walk(out) if isinstance(n, Join))
    assert isinstance(inner.left, Limit) and inner.left.count == 5


def test_push_limit_through_inner_join_does_not_fire():
    left, right = vals(), vals(cols=("k2", "w"))
    join = Join(left.output_names + right.output_names, (BIGINT,) * 4,
                left, right, "INNER", (0,), (0,), None)
    tree = Limit(join.output_names, join.output_types, join, 5)
    _, trace = run_rules(tree, [limits.PushLimitThroughJoin()])
    assert trace.fired("PushLimitThroughJoin") == 0


# ---------------------------------------------------------- aggregations

def _agg_over_join(join_type="INNER", fn="sum", arg=1, distinct=False):
    left, right = vals(), vals(cols=("k2", "w"))
    join = Join(left.output_names + right.output_names, (BIGINT,) * 4,
                left, right, join_type, (0,), (0,), None)
    return Aggregate(("k", "a"), (BIGINT, BIGINT), join, (0,),
                     (AggCall(fn, arg, BIGINT, distinct=distinct),))


def test_push_partial_aggregation_through_join_fires():
    tree = _agg_over_join()
    out, trace = run_rules(tree,
                           [aggregates.PushPartialAggregationThroughJoin()])
    assert trace.fired("PushPartialAggregationThroughJoin") == 1
    assert isinstance(out, Aggregate)
    join = next(n for n in _walk(out) if isinstance(n, Join))
    assert isinstance(join.left, Aggregate)  # pre-agg below the join
    assert out.aggregates[0].fn == "sum"     # sum merges as sum


def test_push_partial_aggregation_skips_distinct():
    tree = _agg_over_join(distinct=True)
    _, trace = run_rules(tree,
                         [aggregates.PushPartialAggregationThroughJoin()])
    assert trace.fired("PushPartialAggregationThroughJoin") == 0


def test_push_aggregation_through_outer_join_fires_with_coalesce():
    tree = _agg_over_join(join_type="LEFT", fn="count", arg=3)
    out, trace = run_rules(tree,
                           [aggregates.PushAggregationThroughOuterJoin()])
    assert trace.fired("PushAggregationThroughOuterJoin") == 1
    # all-unmatched groups must read 0, not NULL: a $coalesce lands on top
    assert isinstance(out, Project)
    assert any(isinstance(e, Call) and e.name == "$coalesce"
               for e in out.expressions)
    join = next(n for n in _walk(out) if isinstance(n, Join))
    assert join.join_type == "LEFT" and isinstance(join.right, Aggregate)


def test_push_aggregation_through_outer_join_skips_count_star():
    tree = _agg_over_join(join_type="LEFT", fn="count_star", arg=-1)
    _, trace = run_rules(tree,
                         [aggregates.PushAggregationThroughOuterJoin()])
    assert trace.fired("PushAggregationThroughOuterJoin") == 0


# ----------------------------------------------------------- decorrelate

def test_transform_correlated_in_predicate_fires():
    src, sub = vals(), vals(cols=("k2",))
    names = src.output_names + ("mark",)
    tree = CorrelatedJoin(names, src.output_types + (BOOLEAN,),
                          src, sub, "in", (0,), (0,))
    out, trace = run_rules(tree,
                           [decorrelate.TransformCorrelatedInPredicate()])
    assert trace.fired("TransformCorrelatedInPredicate") == 1
    assert isinstance(out, SemiJoin) and out.null_aware


def test_transform_correlated_scalar_subquery_fires():
    src, sub = vals(), vals(cols=("k2", "agg"))
    names = src.output_names + sub.output_names
    tree = CorrelatedJoin(names, (BIGINT,) * 4, src, sub,
                          "scalar_agg", (0,), (0,))
    out, trace = run_rules(
        tree, [decorrelate.TransformCorrelatedScalarSubquery()])
    assert trace.fired("TransformCorrelatedScalarSubquery") == 1
    assert isinstance(out, Join) and out.join_type == "LEFT"


def test_decorrelate_rules_do_not_fire_without_correlation():
    left, right = vals(), vals(cols=("k2",))
    tree = Join(left.output_names + right.output_names, (BIGINT,) * 3,
                left, right, "INNER", (0,), (0,), None)
    _, trace = run_rules(tree,
                         [decorrelate.TransformCorrelatedInPredicate(),
                          decorrelate.TransformCorrelatedScalarSubquery()])
    assert not trace.fires


# --------------------------------------------------- reorder/distribution

def test_determine_join_distribution_fires_on_right_join():
    left, right = vals(), vals(cols=("k2",))
    tree = Join(left.output_names + right.output_names, (BIGINT,) * 3,
                left, right, "RIGHT", (0,), (0,), None,
                distribution="BROADCAST")
    out, trace = run_rules(tree, [reorder.DetermineJoinDistribution()])
    assert trace.fired("DetermineJoinDistribution") == 1
    # a broadcast RIGHT join would duplicate unmatched build rows per task
    assert out.distribution == "PARTITIONED"


def test_determine_join_distribution_does_not_fire_when_settled():
    left, right = vals(), vals(cols=("k2",))
    tree = Join(left.output_names + right.output_names, (BIGINT,) * 3,
                left, right, "RIGHT", (0,), (0,), None,
                distribution="PARTITIONED")
    _, trace = run_rules(tree, [reorder.DetermineJoinDistribution()])
    assert trace.fired("DetermineJoinDistribution") == 0


def test_reorder_joins_fires_on_three_way_tpch_join():
    from trino_tpu.runner import StandaloneQueryRunner
    runner = StandaloneQueryRunner(CATALOG)
    runner.create_plan(
        "select c_name, o_totalprice, n_name from customer "
        "join orders on c_custkey = o_custkey "
        "join nation on c_nationkey = n_nationkey")
    rep = last_report()
    assert rep is not None and rep.fired("ReorderJoins") >= 1


def test_reorder_joins_does_not_fire_on_single_table():
    from trino_tpu.runner import StandaloneQueryRunner
    runner = StandaloneQueryRunner(CATALOG)
    runner.create_plan(
        "select l_orderkey from lineitem where l_quantity > 10")
    assert last_report().fired("ReorderJoins") == 0


# ----------------------------------------------------------------- prune

def test_prune_join_columns_fires_on_narrow_projection():
    left = vals(cols=("k", "v", "x"))
    right = vals(cols=("k2", "w", "y"))
    join = Join(left.output_names + right.output_names, (BIGINT,) * 6,
                left, right, "INNER", (0,), (0,), None)
    tree = Project(("v",), (BIGINT,), join, (InputRef(BIGINT, 1),))
    out, trace = run_rules(tree, [prune.PruneJoinColumns()])
    assert trace.fired("PruneJoinColumns") == 1
    narrowed = next(n for n in _walk(out) if isinstance(n, Join))
    assert len(narrowed.output_types) < 6  # unused x/w/y are gone
    # layout above the narrowed join is restored
    assert out.output_names == ("v",) and out.output_types == (BIGINT,)


def test_prune_join_columns_does_not_fire_when_all_used():
    left, right = vals(), vals(cols=("k2", "w"))
    join = Join(left.output_names + right.output_names, (BIGINT,) * 4,
                left, right, "INNER", (0,), (0,), None)
    tree = Project(join.output_names, join.output_types, join,
                   tuple(InputRef(BIGINT, i) for i in range(4)))
    _, trace = run_rules(tree, [prune.PruneJoinColumns()])
    assert trace.fired("PruneJoinColumns") == 0


# ------------------------------------------------------------------ memo

def test_memo_interns_identical_subtrees_into_one_group():
    v = vals()
    f1 = Filter(v.output_names, v.output_types, vals(), gt(0, 1))
    f2 = Filter(v.output_names, v.output_types, vals(), gt(0, 1))
    u = Union(v.output_names, v.output_types, (f1, f2))
    memo = Memo(u)
    kids = memo.child_groups(memo.root_group)
    assert len(kids) == 2 and kids[0] == kids[1]
    # distinct subtrees land in distinct groups
    f3 = Filter(v.output_names, v.output_types, vals(), gt(0, 99))
    u2 = Union(v.output_names, v.output_types, (f1, f3))
    memo2 = Memo(u2)
    k2 = memo2.child_groups(memo2.root_group)
    assert k2[0] != k2[1]


def test_memo_extract_round_trips_and_resolves_refs():
    v = vals()
    tree = Filter(v.output_names, v.output_types, v, gt(0, 1))
    memo = Memo(tree)
    assert memo.extract() == tree
    root = memo.node(memo.root_group)
    assert isinstance(root.source, GroupRef)
    assert memo.resolve(root.source) == v


def test_memo_replace_group_rewrites_extraction():
    v = vals()
    tree = Filter(v.output_names, v.output_types, v, gt(0, 1))
    memo = Memo(tree)
    memo.replace_group(memo.root_group, v)
    assert memo.extract() == v


# ----------------------------------------------- estimate_rows regression

def test_extra_equality_conjuncts_tighten_unknown_ndv_estimate():
    """Two-key equi-join over unknown-NDV inputs must estimate BELOW the
    one-key join (the old code multiplied by an implicit 1.0)."""
    left, right = vals(), vals(cols=("k2", "w"))
    one = Join(left.output_names + right.output_names, (BIGINT,) * 4,
               left, right, "INNER", (0,), (0,), None)
    two = Join(left.output_names + right.output_names, (BIGINT,) * 4,
               left, right, "INNER", (0, 1), (0, 1), None)
    est1 = estimate_rows(one, CATALOG)
    est2 = estimate_rows(two, CATALOG)
    assert est2 < est1
    assert est2 == pytest.approx(est1 * 0.9)


def test_single_key_join_estimate_unchanged_by_fix():
    left, right = vals(), vals(cols=("k2", "w"))
    one = Join(left.output_names + right.output_names, (BIGINT,) * 4,
               left, right, "INNER", (0,), (0,), None)
    # unknown NDV on both sides: textbook fallback is max(|L|, |R|)
    assert estimate_rows(one, CATALOG) == 10.0
