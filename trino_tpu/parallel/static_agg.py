"""Fully-static grouped aggregation — jittable with NO host syncs.

The dynamic-shape kernel in exec/kernels.py syncs the group count to the host
to pick a bucket; that is fine between operators but illegal inside
``shard_map``/``pjit`` programs.  This variant promises a static group-slot
capacity ``cap`` up front (TPC-H Q1 has 4 groups; planners pick ``cap`` from
table stats / NDV estimates, mirroring how Trino sizes hash tables from
``EstimatedRowCount``), so the whole pipeline — filter, project, group, reduce
— is one XLA program and can fuse with the collectives around it.

Overflow contract: if the true group count exceeds ``cap``, ``num_groups``
in the result exceeds ``cap`` — the caller must check and re-run with a
bigger cap (the recompile-bucket strategy of SURVEY §7).

When ``TRINO_TPU_HASH_IMPL`` selects the Pallas open-addressing path, group
ids come straight from the hash-insert kernel: no lexsort, and the row count
stays a device scalar.  Slot ORDER then differs from the sort route (first
occurrence vs key order) — callers already must not rely on slot order, and
``combine_partials`` re-groups anyway.  One semantic divergence: the sort
route's raw ``!=`` comparison makes every NaN its own group, while the hash
route canonicalizes NaNs into one group (SQL semantics).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from .. import ops as _ops  # noqa: F401  (enables jax x64)
from ..exec import kernels as _K

__all__ = ["AggSpec", "StaticAggResult", "static_grouped_agg", "combine_partials"]


class AggSpec(NamedTuple):
    """One aggregate column in kernel form.

    fn: sum | count | count_star | min | max | any_value
    (avg is decomposed into sum+count by the caller).
    """

    fn: str
    dtype: jnp.dtype


class StaticAggResult(NamedTuple):
    keys: list  # [cap] per key column (representative values)
    key_valids: list  # [cap] bool or None per key column
    values: list  # [cap] per agg
    value_valids: list  # [cap] bool or None per agg
    slot_used: jnp.ndarray  # [cap] bool — slot holds a real group
    num_groups: jnp.ndarray  # scalar int32 (may exceed cap: overflow signal)


def _sentinel(fn: str, dtype):
    kind = jnp.dtype(dtype).kind
    if fn == "min":
        return jnp.inf if kind == "f" else (True if kind == "b" else jnp.iinfo(dtype).max)
    return -jnp.inf if kind == "f" else (False if kind == "b" else jnp.iinfo(dtype).min)


def _sort_gids(keys, key_valids, cap, row_mask):
    """lexsort route: (perm, live, gid, num_groups) with rows sorted so
    equal keys are adjacent and boundary flags derive dense group ids."""
    n = keys[0].shape[0]
    norm = []
    for k, v in zip(keys, key_valids):
        kk = k
        if v is not None:
            kk = jnp.where(v, kk, jnp.zeros((), kk.dtype))
        norm.append(kk)

    sort_keys = []
    for i in reversed(range(len(norm))):
        sort_keys.append(norm[i])
        if key_valids[i] is not None:
            sort_keys.append(key_valids[i])
    if row_mask is not None:
        # dead rows sort to the back so live groups get the low slot ids
        sort_keys.append(~row_mask)
    perm = jnp.lexsort(tuple(sort_keys)) if sort_keys else jnp.arange(n)

    live = row_mask[perm] if row_mask is not None else jnp.ones(n, jnp.bool_)
    new_group = jnp.zeros(n, jnp.bool_)
    for i, k in enumerate(norm):
        d = k[perm]
        diff = jnp.concatenate([jnp.ones((1,), jnp.bool_), d[1:] != d[:-1]])
        if key_valids[i] is not None:
            v = key_valids[i][perm]
            diff = diff | jnp.concatenate([jnp.ones((1,), jnp.bool_), v[1:] != v[:-1]])
        new_group = new_group | diff
    new_group = new_group & live
    # first live row starts group 0 even if its boundary flag got masked
    first_live = jnp.argmax(live) if n else jnp.zeros((), jnp.int64)
    new_group = jnp.where(live.any(), new_group.at[first_live].set(True), new_group)
    gid_all = jnp.cumsum(new_group.astype(jnp.int32)) - 1
    num_groups = jnp.where(live.any(), gid_all[-1] + 1, 0) if n else jnp.zeros((), jnp.int32)
    # dead rows scatter into the overflow slot
    gid = jnp.where(live, jnp.clip(gid_all, 0, cap - 1), cap)
    return perm, live, gid, num_groups


def static_grouped_agg(
    keys: Sequence[jnp.ndarray],
    key_valids: Sequence[Optional[jnp.ndarray]],
    agg_inputs: Sequence[tuple],  # (AggSpec, data|None, valid|None)
    cap: int,
    row_mask: Optional[jnp.ndarray] = None,
) -> StaticAggResult:
    """Group rows by ``keys`` and reduce; everything static-shaped.

    ``row_mask`` folds an upstream filter into the kernel (selection-vector
    style — SURVEY §7 shift 2): masked-out rows join group slot ``cap`` + are
    dropped by reduction identity values.
    """
    n = keys[0].shape[0]
    if _K.hash_kernels_selected(n):
        # hash route: the insert kernel hands every ORIGINAL row its dense
        # group id, so perm stays identity and the segment scatters below
        # work unsorted; the count stays a device scalar (still zero syncs)
        row_gid, num_groups = _K.hash_row_gids(
            list(zip(keys, key_valids)), live=row_mask)
        S = _K.bucket(2 * max(n, 1))
        perm = jnp.arange(n)
        live = row_mask if row_mask is not None else jnp.ones(n, jnp.bool_)
        gid = jnp.where(row_gid < S, jnp.minimum(row_gid, cap - 1), cap)
    else:
        perm, live, gid, num_groups = _sort_gids(keys, key_valids, cap,
                                                 row_mask)

    out_keys, out_kvalids = [], []
    for k, v in zip(keys, key_valids):
        rep = jnp.zeros((cap + 1,), k.dtype).at[gid].set(k[perm])
        out_keys.append(rep[:cap])
        if v is not None:
            rv = jnp.zeros((cap + 1,), jnp.bool_).at[gid].max(v[perm])
            out_kvalids.append(rv[:cap])
        else:
            out_kvalids.append(None)

    values, vvalids = [], []
    for spec, data, valid in agg_inputs:
        if spec.fn == "count_star":
            ones = live.astype(jnp.int64)
            values.append(jax.ops.segment_sum(ones, gid, cap + 1)[:cap])
            vvalids.append(None)
            continue
        d = data[perm]
        v = valid[perm] if valid is not None else None
        eff_valid = v if v is not None else None
        if spec.fn == "count":
            c = live if eff_valid is None else (live & eff_valid)
            values.append(jax.ops.segment_sum(c.astype(jnp.int64), gid, cap + 1)[:cap])
            vvalids.append(None)
        elif spec.fn == "sum":
            keep = live if eff_valid is None else (live & eff_valid)
            x = jnp.where(keep, d, jnp.zeros((), d.dtype)).astype(spec.dtype)
            values.append(jax.ops.segment_sum(x, gid, cap + 1)[:cap])
            vvalids.append(jax.ops.segment_max(keep, gid, cap + 1)[:cap])
        elif spec.fn in ("min", "max"):
            keep = live if eff_valid is None else (live & eff_valid)
            sent = _sentinel(spec.fn, d.dtype)
            x = jnp.where(keep, d, sent)
            red = jax.ops.segment_min if spec.fn == "min" else jax.ops.segment_max
            values.append(red(x, gid, cap + 1)[:cap])
            vvalids.append(jax.ops.segment_max(keep, gid, cap + 1)[:cap])
        elif spec.fn == "any_value":
            keep = live if eff_valid is None else (live & eff_valid)
            rep = jnp.zeros((cap + 1,), d.dtype).at[jnp.where(keep, gid, cap)].set(d)
            values.append(rep[:cap])
            vvalids.append(jax.ops.segment_max(keep, gid, cap + 1)[:cap])
        else:
            raise NotImplementedError(spec.fn)

    slot_used = jnp.arange(cap) < num_groups
    return StaticAggResult(out_keys, out_kvalids, values, vvalids, slot_used, num_groups)


_COMBINE = {"sum": "sum", "count": "sum", "count_star": "sum",
            "min": "min", "max": "max", "any_value": "any_value"}


def combine_partials(
    keys: Sequence[jnp.ndarray],
    key_valids: Sequence[Optional[jnp.ndarray]],
    partial_inputs: Sequence[tuple],  # (AggSpec, values, valid|None)
    slot_used: jnp.ndarray,
    cap: int,
) -> StaticAggResult:
    """FINAL step: re-group partial state rows by key, merge states
    (sum→sum, count→sum, min→min …) — Trino's partial/final split
    (AggregationNode.Step PARTIAL/FINAL)."""
    merged = []
    for spec, vals, valid in partial_inputs:
        merged.append((AggSpec(_COMBINE[spec.fn], spec.dtype), vals, valid))
    return static_grouped_agg(keys, key_valids, merged, cap, row_mask=slot_used)
