"""Ask the chip's compiler before asking the chip.

The TPU compiler is installed here and compiles for a chip that is described
and not attached (on-chip-measurement guide, section 2): these tests lower
and compile, for ONE described v5e device and from ``ShapeDtypeStruct``s at
real widths, the programs TPC-H Q1/Q3/Q6 launch on the served path — the
Pallas segment-sum kernel, the sort-route grouping and join programs, and
``static_grouped_agg`` with the TPU branch forced.  Nothing runs; a compile
that passes is not a chip run.

The topology is described inside a module-scoped fixture, never at import:
only one process may load libtpu, and under xdist every worker imports
every test file.  Keep these tests in this one file for the same reason.
The 64-bit sort programs take minutes to compile at the 2^20-row bucket the
engine uses at SF10 (CHANGES.md, PR 22, has the seconds), so tier-1 holds
them at 2^12 and a ``slow`` twin holds the real bucket.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from trino_tpu.exec import join_exec as JX
from trino_tpu.exec import kernels as K
from trino_tpu.ops import pallas_kernels as PK
from trino_tpu.parallel.static_agg import AggSpec, static_grouped_agg

BUCKETS = [
    pytest.param(1 << 12, id="2^12"),
    pytest.param(1 << 20, id="2^20", marks=pytest.mark.slow),
]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here: skip, visibly
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding on one described device, with the persistent compile cache
    off around the compiles: an entry written without a chip cannot be read
    back without one, and the next compile would warn and compile again."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def shape(one_chip):
    def of(n, dtype):
        n = n if isinstance(n, tuple) else (n,)
        return jax.ShapeDtypeStruct(n, dtype, sharding=one_chip)

    return of


@pytest.fixture
def tpu_backend(monkeypatch):
    """Code that asks jax.default_backend() sees the CPU here; steer it to
    the branch the chip takes — from the test, not from a program option."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_segment_sum_kernel_compiles(shape):
    # largest G grouped_reduce routes to the kernel (cap <= 64), 2^20 rows
    n = 1 << 20
    tile = (n // 128, 128)
    with jax.enable_x64(False):
        compiled = PK._build(64, n // 1024, False).lower(
            shape(tile, jnp.float32), shape(tile, jnp.int32),
            shape(tile, jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", BUCKETS)
def test_group_ids_two_bigint_keys(shape, rows):
    K._group_ids_fn(2, (False, False), True).lower(
        shape(rows, jnp.int64), shape(rows, jnp.int64),
        shape(rows, jnp.bool_)).compile()


@pytest.mark.parametrize("rows", BUCKETS)
def test_group_ids_nullable_double_key(shape, rows):
    K._group_ids_fn(1, (True,), True).lower(
        shape(rows, jnp.float64), shape(rows, jnp.bool_),
        shape(rows, jnp.bool_)).compile()


@pytest.mark.parametrize("rows", BUCKETS)
def test_join_build(shape, rows):
    # one BIGINT key with a live mask, key range wanted (the dense-table
    # probe of Q3's orders build)
    JX._build_fn(1, (False,), True, True).lower(
        shape(rows, jnp.int64), shape(rows, jnp.bool_)).compile()


@pytest.mark.parametrize("rows", BUCKETS)
def test_join_probe_ranges(shape, rows):
    JX._ranges_fn(1, (False,), True, (False,)).lower(
        shape(rows, jnp.uint64), shape(rows, jnp.int64),
        shape(rows, jnp.bool_)).compile()


@pytest.mark.parametrize("rows", BUCKETS)
def test_join_unique_gather_compact_leg(shape, rows):
    # Q3's lineitem-orders probe as the seeded runs launch it: 2^20 probe
    # lanes compacted to a sixteenth, three BIGINT and one INTEGER column a
    # side, a 2^18-row build (the leg had never run on the chip before
    # PR 33: no estimate ever reached it)
    from trino_tpu.spi.types import BIGINT, INTEGER

    types = [BIGINT] * 3 + [INTEGER]
    build = max(rows >> 2, 8)
    JX._make_ugather_fn(
        rows >> 4, types * 2, [None] * 8, 4, 4, (False,) * 4, (False,) * 4,
        None, False).lower(
        shape(rows, jnp.bool_), shape(rows, jnp.int64),
        *[shape(rows, t.storage_dtype) for t in types],
        *[shape(build, t.storage_dtype) for t in types]).compile()


@pytest.mark.parametrize("rows", BUCKETS)
def test_static_grouped_agg_as_the_fused_stage_calls_it(
        shape, tpu_backend, rows):
    """stage_compiler._agg_merge: cap 8192, every key carries a validity
    lane, the batch's live mask rides in as row_mask."""
    specs = [AggSpec("sum", np.dtype("float64")),
             AggSpec("min", np.dtype("int64")),
             AggSpec("count_star", np.dtype("int64"))]

    def partial_agg(k0, k1, v0, v1, d0, d1, live):
        r = static_grouped_agg(
            [k0, k1], [v0, v1],
            [(specs[0], d0, None), (specs[1], d1, v1), (specs[2], None, None)],
            8192, row_mask=live)
        return r.keys, r.values, r.slot_used, r.num_groups

    args = (shape(rows, jnp.int64), shape(rows, jnp.int64),
            shape(rows, jnp.bool_), shape(rows, jnp.bool_),
            shape(rows, jnp.float64), shape(rows, jnp.int64),
            shape(rows, jnp.bool_))
    lowered = jax.jit(partial_agg).lower(*args)
    # the selection the chip makes: the sort route, no pallas call inside
    assert "tpu_custom_call" not in lowered.as_text()
    lowered.compile()


@pytest.mark.parametrize("rows", BUCKETS)
def test_masked_aggregation_fold_with_a_donated_state(shape, rows):
    """kernels.small_agg_fold as a streaming Q1 PARTIAL task calls it: two
    dictionary keys (3 x 2 codes), the batch's live mask, DECIMAL sums as
    int64, an unpaired decimal avg's f64 sum of unscaled integers and its
    count, count(*), for a group of _FOLD_GROUP batches a launch (every
    slot after the first under ``lax.cond``); the state is donated, so the
    [6]-lane state columns (stacked by dtype) alias in and out."""
    from trino_tpu.exec.operators import _FOLD_GROUP

    spec = (("sum", 0, -1, "<i8", None), ("sum", 1, -1, "<i8", None),
            ("sum", 0, -1, "<f8", None), ("count", 0, -1, "<i8", None),
            ("min", 2, -1, "<i4", None), ("count_star", -1, -1, "<i8", None))
    layout = K.small_agg_state_layout(spec)
    state = tuple(shape(dims, np.dtype(d))
                  for dims, d in K.small_agg_state_shapes(layout, 6))
    assert len(state) == 3                    # int64, float64, int32 stacks
    slot = (shape(rows, jnp.int32), shape(rows, jnp.int32),
            shape(rows, jnp.bool_), shape(rows, jnp.int64),
            shape(rows, jnp.int64), shape(rows, jnp.int32))
    compiled = K._small_agg_fold_fn(
        spec, 2, (False, False), True, (3, 2), _FOLD_GROUP, True).lower(
        state, shape((), jnp.int32), (slot,) * _FOLD_GROUP).compile()
    assert compiled.memory_analysis().alias_size_in_bytes > 0
    assert compiled.as_text().count("conditional(") == _FOLD_GROUP - 1
    K._small_agg_zero_fn(layout, 6, True).lower().compile()
    K._small_agg_state_out_fn(spec, (3, 2),
                              (False, False)).lower(*state).compile()


TPCH_Q1 = """
select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
  sum(l_extendedprice) as sum_base_price,
  sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
  sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
  avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price,
  avg(l_discount) as avg_disc, count(*) as count_order
from lineitem where l_shipdate <= date '1998-12-01' - interval '90' day
group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus"""
TPCH_Q6 = """
select sum(l_extendedprice * l_discount) as revenue from lineitem
where l_shipdate >= date '1994-01-01'
  and l_shipdate < date '1994-01-01' + interval '1' year
  and l_discount between 0.06 - 0.01 and 0.06 + 0.01 and l_quantity < 24"""


def _fused_fold_of(sql, monkeypatch):
    """(the aggregation operator, a batch its feed handed through) of a
    PARTIAL task of ``sql``, run once at SF0.01 as one chip runs it (two
    tasks, no fused stage, no collectives)."""
    from trino_tpu.connectors.catalog import default_catalog
    from trino_tpu.exec.operators import HashAggregationOperator
    from trino_tpu.execution.distributed_runner import DistributedQueryRunner
    from trino_tpu.runner import Session

    monkeypatch.setenv("TRINO_TPU_FUSED_STAGE", "0")
    monkeypatch.setenv("TRINO_TPU_RESULT_CACHE", "0")
    seen = []
    absorbs = HashAggregationOperator.absorbs

    def spy(self, batch):
        took = absorbs(self, batch)
        if took and batch.num_rows:
            seen.append((self, batch))
        return took

    monkeypatch.setattr(HashAggregationOperator, "absorbs", spy)
    DistributedQueryRunner(
        default_catalog(scale_factor=0.01), worker_count=2,
        session=Session(node_count=2, use_collectives=False)).execute(sql)
    assert seen and seen[0][0].step == "PARTIAL"
    return seen[0]


@pytest.mark.parametrize("rows", BUCKETS)
@pytest.mark.parametrize("sql", [TPCH_Q6, TPCH_Q1], ids=["q6", "q1"])
def test_grouped_filter_project_agg(shape, monkeypatch, sql, rows):
    """operators.filter_project_agg as an SF10 scan task launches it since
    PR 37: Q6's and Q1's own bodies (predicate, projections, the fold)
    traced for a group of _FOLD_GROUP pinned batches -- live mask present,
    only the channels the body reads -- into the one donated state."""
    from trino_tpu.exec import operators as O
    from trino_tpu.spi.batch import pad_to_bucket

    agg, batch = _fused_fold_of(sql, monkeypatch)
    # the query ran as the CPU runs it; the program is built as the chip
    # builds it (kernels.donate_ok asks the backend: the state is donated)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    prog, sig, cols, _ = agg.feed.program_inputs(pad_to_bucket(batch))
    cols = [(d if d is None else shape(rows, d.dtype),
             v if v is None else shape(rows, v.dtype)) for d, v in cols]
    live = shape(rows, jnp.bool_)
    view, has_error = prog.view((rows, sig[1], False), cols, live)
    ops, _ = O._masked_operands(agg.group_keys, agg.aggs, agg.step, view)
    state = tuple(shape(dims, np.dtype(d)) for dims, d in ops.state_shapes)
    if has_error:
        state += (shape((), jnp.int32),)
    fold = O._filter_project_agg_program(
        prog, tuple(agg.group_keys), tuple(agg.aggs), agg.step)
    compiled = fold.lower(state, shape((), jnp.int32),
                          ((cols, live),) * O._FOLD_GROUP).compile()
    assert compiled.memory_analysis().alias_size_in_bytes > 0
    assert compiled.as_text().count("conditional(") == O._FOLD_GROUP - 1


@pytest.mark.parametrize("rows", BUCKETS)
def test_exchange_partition_masks(shape, rows):
    # a REPARTITION sink over a page that stays on the device (PR 36): a
    # nullable BIGINT key and a dictionary key hashed by value, under the
    # page's live mask, three consumers -- the 64-bit remainder included
    K._partition_masks_fn((True, False), (False, True), True, 3).lower(
        shape(rows, jnp.int64), shape(rows, jnp.bool_),
        shape(rows, jnp.int32), shape(64, jnp.int64),
        shape(rows, jnp.bool_)).compile()


@pytest.mark.parametrize("rows", BUCKETS)
def test_exchange_live_count_and_shrink(shape, rows):
    # the sink's count a page, and the shrink of a sparse page to a quarter
    # of its lanes (Q3's customer page: two BIGINT columns, one nullable)
    K.live_count.lower(shape(rows, jnp.bool_)).compile()
    K._compact_fn(2, (False, True), True, rows >> 2).lower(
        shape(rows, jnp.bool_), shape(rows, jnp.int64),
        shape(rows, jnp.int64), shape(rows, jnp.bool_)).compile()
