"""Benchmark kernels: the fused TPC-H Q1 program (single-chip + SPMD).

Q1 = scan(lineitem) → filter(shipdate <= cutoff) → project(disc_price,
charge) → group by (returnflag, linestatus) → 7 sums/counts.  In the
reference this is ScanFilterAndProjectOperator + HashAggregationOperator
(BenchmarkHashAndStreamingAggregationOperators.java); here the whole query
is ONE XLA program: the filter becomes a row mask folded into the reduction
(no compaction), money columns are decimal-scaled int64 summed in f64 lanes,
and the group table is 8 static slots.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .caching.executable_cache import program
from .parallel.static_agg import AggSpec, static_grouped_agg
from .parallel.distributed import distributed_grouped_agg, make_mesh

__all__ = ["Q1Batch", "make_q1_inputs", "q1_step", "q1_spmd", "q1_numpy"]

Q1_CUTOFF_DAYS = 10471  # date '1998-12-01' - interval '90' day = 1998-09-02

_SPECS = [
    AggSpec("sum", jnp.float64),   # sum_qty
    AggSpec("sum", jnp.float64),   # sum_base_price
    AggSpec("sum", jnp.float64),   # sum_disc_price
    AggSpec("sum", jnp.float64),   # sum_charge
    AggSpec("sum", jnp.float64),   # sum_discount (for avg_disc)
    AggSpec("count_star", jnp.int64),  # count_order (and avg divisors)
]


class Q1Batch(NamedTuple):
    returnflag: jnp.ndarray  # int32 codes
    linestatus: jnp.ndarray  # int32 codes
    quantity: jnp.ndarray    # int64 scale-2
    extendedprice: jnp.ndarray  # int64 scale-2
    discount: jnp.ndarray    # int64 scale-2
    tax: jnp.ndarray         # int64 scale-2
    shipdate: jnp.ndarray    # int32 days


def make_q1_inputs(sf: float, splits: int = 8):
    """Generate lineitem Q1 columns via the TPC-H connector (host, numpy)."""
    from .connectors.tpch import TpchConnector

    conn = TpchConnector(scale_factor=sf)
    cols = ["l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
            "l_discount", "l_tax", "l_shipdate"]
    batches = []
    for s in conn.get_splits("lineitem", splits, 1):
        src = conn.create_page_source(s, cols)
        while not src.is_finished():
            b = src.get_next_batch()
            if b is not None:
                batches.append(b)
    from .spi.batch import ColumnBatch

    all_ = ColumnBatch.concat(batches)
    return Q1Batch(
        np.asarray(all_.column("l_returnflag").data, np.int32),
        np.asarray(all_.column("l_linestatus").data, np.int32),
        np.asarray(all_.column("l_quantity").data, np.int64),
        np.asarray(all_.column("l_extendedprice").data, np.int64),
        np.asarray(all_.column("l_discount").data, np.int64),
        np.asarray(all_.column("l_tax").data, np.int64),
        np.asarray(all_.column("l_shipdate").data, np.int32),
    )


def _q1_project(b: Q1Batch):
    mask = b.shipdate <= Q1_CUTOFF_DAYS
    qty = b.quantity.astype(jnp.float64)
    price = b.extendedprice.astype(jnp.float64)
    disc = b.discount.astype(jnp.float64)
    tax = b.tax.astype(jnp.float64)
    disc_price = price * (100.0 - disc) / 100.0
    charge = disc_price * (100.0 + tax) / 100.0
    keys = [b.returnflag, b.linestatus]
    datas = [qty, price, disc_price, charge, disc, qty]
    return keys, datas, mask


@program("bench_kernels.q1_step")
def q1_step(b: Q1Batch):
    """Single-chip fused Q1: one jitted program, 8 group slots."""
    keys, datas, mask = _q1_project(b)
    agg_inputs = [(s, d, None) for s, d in zip(_SPECS, datas)]
    r = static_grouped_agg(keys, [None, None], agg_inputs, cap=8, row_mask=mask)
    return tuple(r.keys), tuple(r.values), r.slot_used


def q1_spmd(mesh, axis: str = "x"):
    """SPMD Q1 over a device mesh: dp row shards -> partial agg ->
    all_to_all repartition of group slots -> final agg."""
    inner = distributed_grouped_agg(
        mesh, axis, [jnp.int32, jnp.int32], _SPECS, cap=8)

    def step(b: Q1Batch):
        keys, datas, mask = _q1_project(b)
        return inner(*keys, *datas, mask)

    return step


def q1_numpy(b: Q1Batch):
    """Reference single-thread numpy implementation (the CPU baseline)."""
    mask = b.shipdate <= Q1_CUTOFF_DAYS
    rf = b.returnflag[mask]
    ls = b.linestatus[mask]
    qty = b.quantity[mask].astype(np.float64)
    price = b.extendedprice[mask].astype(np.float64)
    disc = b.discount[mask].astype(np.float64)
    tax = b.tax[mask].astype(np.float64)
    disc_price = price * (100.0 - disc) / 100.0
    charge = disc_price * (100.0 + tax) / 100.0
    key = rf.astype(np.int64) * 1000 + ls
    uniq, inv = np.unique(key, return_inverse=True)
    out = {}
    for name, col in (("qty", qty), ("price", price),
                      ("disc_price", disc_price), ("charge", charge),
                      ("disc", disc)):
        acc = np.zeros(len(uniq))
        np.add.at(acc, inv, col)
        out[name] = acc
    out["count"] = np.bincount(inv, minlength=len(uniq))
    return uniq, out
