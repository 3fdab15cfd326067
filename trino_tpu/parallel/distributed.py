"""Mesh-parallel relational programs: repartition = all_to_all over ICI.

The PartitionedOutput → Exchange data path (reference: operator/output/
PagePartitioner.java:134 hash partition + HTTP page streaming) compiled into
a single SPMD program: every device holds a row-shard (data parallelism over
splits), aggregates locally (PARTIAL step), hash-routes group slots to owner
devices with ``jax.lax.all_to_all`` (the FIXED_HASH_DISTRIBUTION analog),
and reduces again (FINAL step).  Broadcast joins use ``all_gather`` of the
build side (FIXED_BROADCAST_DISTRIBUTION — SystemPartitioningHandle.java:52).

Capacity contract: each device sends at most ``cap`` group slots to each
destination (send buffer [n_dev, cap]); unused lanes carry a dead mask.  For
relational workloads cap is sized from NDV stats, so the buffers stay tiny
compared to the row data they summarize.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P

from ..caching.executable_cache import program
from .static_agg import AggSpec, combine_partials, static_grouped_agg

__all__ = [
    "make_mesh",
    "distributed_grouped_agg",
    "broadcast_gather",
]


def make_mesh(n_devices: Optional[int] = None, axis: str = "x") -> Mesh:
    devs = jax.devices()
    n = n_devices if n_devices is not None else len(devs)
    return Mesh(devs[:n], (axis,))


def _route_hash(keys: Sequence[jnp.ndarray], n_dev: int) -> jnp.ndarray:
    h = jnp.zeros(keys[0].shape, dtype=jnp.uint32)
    for k in keys:
        x = k.astype(jnp.int64).astype(jnp.uint32) if k.dtype != jnp.bool_ else k.astype(jnp.uint32)
        h = (h ^ x) * jnp.uint32(0x9E3779B1)
        h = h ^ (h >> 15)
    return (h % jnp.uint32(n_dev)).astype(jnp.int32)


def distributed_grouped_agg(
    mesh: Mesh,
    axis: str,
    key_dtypes: Sequence,
    agg_specs: Sequence[AggSpec],
    cap: int,
):
    """Build a jitted SPMD function: (sharded key cols, sharded agg inputs,
    sharded row mask) -> per-device final group slots.

    Returned callable signature:
        fn(*keys, *agg_datas, row_mask) -> (out_keys, out_values, slot_used)
    with every input sharded on axis 0 over ``axis`` and outputs likewise
    (each device owns the groups that hash to it).
    """
    n_dev = mesh.shape[axis]
    nk = len(key_dtypes)

    def local_program(*args):
        keys = list(args[:nk])
        datas = list(args[nk : nk + len(agg_specs)])
        row_mask = args[-1]

        # ---- PARTIAL: local grouped reduction ------------------------------
        agg_inputs = []
        for spec, d in zip(agg_specs, datas):
            agg_inputs.append((spec, d, None))
        part = static_grouped_agg(keys, [None] * nk, agg_inputs, cap, row_mask)

        # ---- route: slot -> owner device -----------------------------------
        dest = _route_hash(part.keys, n_dev)
        # send buffer [n_dev, cap]: lane (d, s) = slot s if it routes to d
        lane_live = part.slot_used[None, :] & (
            dest[None, :] == jnp.arange(n_dev, dtype=jnp.int32)[:, None]
        )

        def to_lanes(x):
            return jnp.broadcast_to(x[None, :], (n_dev, cap))

        sent_keys = [
            jax.lax.all_to_all(to_lanes(k), axis, 0, 0, tiled=False)
            for k in part.keys
        ]
        sent_vals = [
            jax.lax.all_to_all(to_lanes(v), axis, 0, 0, tiled=False)
            for v in part.values
        ]
        sent_vvalids = [
            None
            if v is None
            else jax.lax.all_to_all(to_lanes(v), axis, 0, 0, tiled=False)
            for v in part.value_valids
        ]
        sent_live = jax.lax.all_to_all(lane_live, axis, 0, 0, tiled=False)

        # ---- FINAL: merge partial states from all sources ------------------
        rk = [k.reshape(n_dev * cap) for k in sent_keys]
        rlive = sent_live.reshape(n_dev * cap)
        partial_inputs = []
        for spec, v, vv in zip(agg_specs, sent_vals, sent_vvalids):
            partial_inputs.append(
                (spec, v.reshape(n_dev * cap),
                 None if vv is None else vv.reshape(n_dev * cap))
            )
        fin = combine_partials(rk, [None] * nk, partial_inputs, rlive, cap)
        # overflow signal (static-agg contract): callers must check
        # max(overflow) <= cap, else re-run with a bigger cap
        overflow = jnp.maximum(part.num_groups, fin.num_groups).reshape(1)
        return tuple(fin.keys), tuple(fin.values), fin.slot_used, overflow

    sharded = jax.shard_map(
        local_program,
        mesh=mesh,
        in_specs=tuple([P(axis)] * (nk + len(agg_specs) + 1)),
        out_specs=(
            tuple([P(axis)] * nk),
            tuple([P(axis)] * len(agg_specs)),
            P(axis),
            P(axis),
        ),
        check_vma=False,
    )
    return program("parallel.sharded_aggregate", sharded)


def broadcast_gather(mesh: Mesh, axis: str):
    """all_gather of a sharded build side — the broadcast-join distribution
    (BroadcastOutputBuffer.java:56 → one collective)."""

    def local(x):
        return jax.lax.all_gather(x, axis, axis=0, tiled=True)

    return program(
        "parallel.broadcast_gather",
        jax.shard_map(
            local, mesh=mesh, in_specs=P(axis), out_specs=P(), check_vma=False
        )
    )
