"""Pallas TPU kernels for the hottest reduction shapes.

Hand-written kernels where the access pattern benefits from explicit VMEM
accumulation rather than XLA's scatter-based ``segment_sum`` lowering:
relational aggregations reduce millions of rows into a handful of group
slots (TPC-H Q1: 4 groups), so a block-resident accumulator that revisits
one [G, 128] VMEM tile per input block avoids the scatter entirely — the
Pallas analogue of the hand-specialized accumulators the reference
generates per aggregation (operator/aggregation/*, sql/gen).

Kernels are f32/int32 (the TPU-native lanes); the engine routes REAL
aggregations here (exec/kernels.grouped_reduce fast path) while
f64/decimal reductions stay on the XLA sort+segment path.  ``interpret=
True`` runs the same kernels on CPU for tests.
"""

from __future__ import annotations

from functools import partial

from ..caching.executable_cache import jit_memo, program

import jax
import jax.numpy as jnp
import numpy as np

# the engine traces these kernels in 32-bit mode because Mosaic rejects the
# stray i64 weak types x64 mode produces (jax 0.9: ``jax.enable_x64`` is the
# context manager; the ``jax.experimental`` spelling is gone)
from jax import enable_x64 as _enable_x64

__all__ = ["masked_segment_sum_f32", "hash_insert", "hash_probe"]

_BLOCK = 1024  # rows per grid step (8 sublanes x 128 lanes)
_LANES = 128
_HBLOCK = 1024  # rows per grid step for the open-addressing kernels


def _segment_sum_kernel(G: int, vals_ref, gid_ref, live_ref, out_ref):
    """One grid step: accumulate this [BLOCK] slice into the [G, LANES]
    output tile (same tile every step — the accumulator stays in VMEM)."""
    from jax.experimental import pallas as pl

    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[:, :] = jnp.zeros_like(out_ref)

    vals = vals_ref[:, :]  # [BLOCK//LANES, LANES] f32
    gid = gid_ref[:, :]  # [BLOCK//LANES, LANES] int32
    live = live_ref[:, :]  # [BLOCK//LANES, LANES] bool
    contrib = jnp.where(live, vals, 0.0)
    # G is tiny (<=64): accumulate each group's lane-sums with a vector
    # select — no scatter, pure VPU work
    for g in range(G):
        sel = jnp.where(gid == g, contrib, 0.0)
        out_ref[g, :] = out_ref[g, :] + jnp.sum(sel, axis=0)


@jit_memo("pallas._build")
def _build(G: int, n_blocks: int, interpret: bool):
    from jax.experimental import pallas as pl

    rows = _BLOCK // _LANES

    def run(vals, gid, live):
        return pl.pallas_call(
            partial(_segment_sum_kernel, G),
            grid=(n_blocks,),
            in_specs=[
                pl.BlockSpec((rows, _LANES), lambda i: (i, 0)),
                pl.BlockSpec((rows, _LANES), lambda i: (i, 0)),
                pl.BlockSpec((rows, _LANES), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((G, _LANES), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((G, _LANES), jnp.float32),
            interpret=interpret,
        )(vals, gid, live)

    return program("pallas.segment_sum", run)


def masked_segment_sum_f32(values, gid, live, num_groups: int,
                           interpret: bool = False):
    """Per-group sums of an f32 column: [N] values, [N] int32 group ids in
    [0, num_groups), [N] bool live mask -> [num_groups] f32.

    N is padded to the block size internally; lanes reduce at the end.
    """
    values = jnp.asarray(values, jnp.float32)
    gid = jnp.asarray(gid, jnp.int32)
    live = (jnp.ones(values.shape, jnp.bool_) if live is None
            else jnp.asarray(live))
    n = values.shape[0]
    pad = (-n) % _BLOCK
    if pad:
        values = jnp.concatenate([values, jnp.zeros(pad, jnp.float32)])
        gid = jnp.concatenate([gid, jnp.zeros(pad, jnp.int32)])
        live = jnp.concatenate([live, jnp.zeros(pad, jnp.bool_)])
    total = n + pad
    shape2d = (total // _LANES, _LANES)
    run = _build(int(num_groups), total // _BLOCK, interpret)
    # the engine runs with jax_enable_x64 on (BIGINT/decimal lanes), but
    # Mosaic rejects the stray i64 weak types x64 mode gives Python ints —
    # the kernel itself is pure f32/i32, so trace it in 32-bit mode
    with _enable_x64(False):
        tile = run(values.reshape(shape2d), gid.reshape(shape2d),
                   live.reshape(shape2d))
    return jnp.sum(tile, axis=1)


# ---------------------------------------------------------------------------
# open-addressing hash table: linear-probing insert + probe
#
# The device-resident alternative to the sort + searchsorted grouping path
# (exec/kernels.group_ids, exec/join_exec probe ranges): a power-of-two slot
# array holds one uint32 plane row per distinct key plus an int32 group id
# per slot, all VMEM-resident across the sequential grid steps.  Collision
# resolution happens in-kernel by comparing EVERY key plane (not just the
# hash), so two keys sharing a slot chain can never merge; callers encode
# NULL keys either as a dead row (sentinel hash -> ``live``=False) or as an
# extra validity plane so NULL forms its own group.  Rows are walked
# serially inside each grid step — the TPU grid is sequential, which is
# exactly what makes the shared table state sound.


def _hash_insert_kernel(P: int, S: int, block: int, planes_ref, hash_ref,
                        live_ref, gid_ref, table_ref, sgid_ref, count_ref):
    """One grid step: insert ``block`` rows into the slot table.  The table
    refs use constant index maps, so they persist across steps (same VMEM
    tiles every step — the accumulator pattern of _segment_sum_kernel)."""
    from jax.experimental import pallas as pl

    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        table_ref[:, :] = jnp.zeros_like(table_ref)
        sgid_ref[:, :] = jnp.full_like(sgid_ref, -1)
        count_ref[0, 0] = jnp.int32(0)

    # every literal is explicitly i32/u32: these kernels trace INSIDE
    # x64-mode jitted programs (static_agg, the join index builder), where a
    # weak-typed Python int would promote to i64 and break the while carry
    mask = jnp.uint32(S - 1)
    one = jnp.int32(1)
    smask = jnp.int32(S - 1)

    def insert_row(i, carry):
        lv = live_ref[0, i]
        slot0 = (hash_ref[0, i] & mask).astype(jnp.int32)

        def probe_body(st):
            slot, _done, _empty = st
            cur = sgid_ref[0, slot]
            empty = cur < jnp.int32(0)
            eq = jnp.bool_(True)
            for p in range(P):
                eq = jnp.logical_and(eq,
                                     table_ref[p, slot] == planes_ref[p, i])
            done = empty | ((~empty) & eq)
            nxt = jnp.where(done, slot, (slot + one) & smask)
            return nxt, done, empty

        # dead rows start done: they never touch the table and take gid S
        # (>= any real group id, matching the group_ids dead-row contract).
        # Live rows always terminate: count <= n <= S/2 leaves empty slots.
        slot, _done, empty = jax.lax.while_loop(
            lambda st: ~st[1], probe_body,
            (slot0, ~lv, jnp.bool_(False)))

        @pl.when(lv & empty)
        def _claim():
            c = count_ref[0, 0]
            sgid_ref[0, slot] = c
            for p in range(P):
                table_ref[p, slot] = planes_ref[p, i]
            count_ref[0, 0] = c + one

        gid_ref[0, i] = jnp.where(lv, sgid_ref[0, slot], jnp.int32(S))
        return carry

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(block), insert_row,
                      jnp.int32(0))


def _hash_probe_kernel(P: int, S: int, block: int, table_ref, sgid_ref,
                       planes_ref, hash_ref, live_ref, gid_ref):
    """One grid step: look up ``block`` rows in a built slot table.  Pure
    reads — the table is an input here, shared across steps."""
    mask = jnp.uint32(S - 1)
    one = jnp.int32(1)
    smask = jnp.int32(S - 1)

    def probe_row(i, carry):
        lv = live_ref[0, i]
        slot0 = (hash_ref[0, i] & mask).astype(jnp.int32)

        def probe_body(st):
            slot, _done, _gid = st
            cur = sgid_ref[0, slot]
            empty = cur < jnp.int32(0)
            eq = jnp.bool_(True)
            for p in range(P):
                eq = jnp.logical_and(eq,
                                     table_ref[p, slot] == planes_ref[p, i])
            hit = (~empty) & eq
            done = empty | hit
            g = jnp.where(hit, cur, jnp.int32(-1))
            nxt = jnp.where(done, slot, (slot + one) & smask)
            return nxt, done, g

        _slot, _done, g = jax.lax.while_loop(
            lambda st: ~st[1], probe_body,
            (slot0, ~lv, jnp.int32(-1)))
        gid_ref[0, i] = g  # dead rows keep the initial -1 (miss)
        return carry

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(block), probe_row,
                      jnp.int32(0))


@jit_memo("pallas._build_insert")
def _build_insert(P: int, S: int, n_blocks: int, interpret: bool):
    from jax.experimental import pallas as pl

    def run(planes, hash32, live):
        return pl.pallas_call(
            partial(_hash_insert_kernel, P, S, _HBLOCK),
            grid=(n_blocks,),
            in_specs=[
                pl.BlockSpec((P, _HBLOCK), lambda i: (0, i)),
                pl.BlockSpec((1, _HBLOCK), lambda i: (0, i)),
                pl.BlockSpec((1, _HBLOCK), lambda i: (0, i)),
            ],
            out_specs=[
                pl.BlockSpec((1, _HBLOCK), lambda i: (0, i)),
                pl.BlockSpec((P, S), lambda i: (0, 0)),
                pl.BlockSpec((1, S), lambda i: (0, 0)),
                pl.BlockSpec((1, 1), lambda i: (0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((1, n_blocks * _HBLOCK), jnp.int32),
                jax.ShapeDtypeStruct((P, S), jnp.uint32),
                jax.ShapeDtypeStruct((1, S), jnp.int32),
                jax.ShapeDtypeStruct((1, 1), jnp.int32),
            ],
            interpret=interpret,
        )(planes, hash32, live)

    return program("pallas.hash_insert", run)


@jit_memo("pallas._build_probe")
def _build_probe(P: int, S: int, n_blocks: int, interpret: bool):
    from jax.experimental import pallas as pl

    def run(table, sgid, planes, hash32, live):
        return pl.pallas_call(
            partial(_hash_probe_kernel, P, S, _HBLOCK),
            grid=(n_blocks,),
            in_specs=[
                pl.BlockSpec((P, S), lambda i: (0, 0)),
                pl.BlockSpec((1, S), lambda i: (0, 0)),
                pl.BlockSpec((P, _HBLOCK), lambda i: (0, i)),
                pl.BlockSpec((1, _HBLOCK), lambda i: (0, i)),
                pl.BlockSpec((1, _HBLOCK), lambda i: (0, i)),
            ],
            out_specs=pl.BlockSpec((1, _HBLOCK), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((1, n_blocks * _HBLOCK),
                                           jnp.int32),
            interpret=interpret,
        )(table, sgid, planes, hash32, live)

    return program("pallas.hash_probe", run)


def _pad_rows(planes, hash32, live, n: int):
    """Pad the row axis to the block size; padded rows are dead."""
    pad = (-n) % _HBLOCK
    if pad:
        planes = jnp.concatenate(
            [planes, jnp.zeros((planes.shape[0], pad), jnp.uint32)], axis=1)
        hash32 = jnp.concatenate([hash32, jnp.zeros(pad, jnp.uint32)])
        live = jnp.concatenate([live, jnp.zeros(pad, jnp.bool_)])
    return planes, hash32, live, n + pad


def hash_insert(planes, hash32, live, num_slots: int,
                interpret: bool = False):
    """Build an open-addressing table over ``planes`` [P, N] uint32 key
    planes (elementwise plane equality == key equality), ``hash32`` [N]
    uint32 slot hashes, ``live`` [N] bool (or None).  ``num_slots`` must be
    a power of two >= 2 * live rows.

    Returns (row_gid, count, table_planes, slot_gid): ``row_gid`` [N] int32
    assigns dense group ids in first-occurrence order (dead rows get
    ``num_slots``, >= any real id); ``count`` is the scalar group count
    (device-resident); the last two are the table state for hash_probe."""
    planes = jnp.asarray(planes, jnp.uint32)
    hash32 = jnp.asarray(hash32, jnp.uint32)
    P, n = int(planes.shape[0]), int(planes.shape[1])
    S = int(num_slots)
    if S & (S - 1) or S <= 0:
        raise ValueError(f"num_slots must be a power of two, got {S}")
    live = (jnp.ones(n, jnp.bool_) if live is None
            else jnp.asarray(live, jnp.bool_))
    planes, hash32, live, total = _pad_rows(planes, hash32, live, n)
    run = _build_insert(P, S, total // _HBLOCK, interpret)
    # engine mode is x64 (BIGINT lanes) but Mosaic rejects stray i64 weak
    # types; the kernel is pure u32/i32, so trace it in 32-bit mode
    with _enable_x64(False):
        gid, table, sgid, count = run(
            planes, hash32.reshape(1, total), live.reshape(1, total))
    return gid[0, :n], count[0, 0], table, sgid[0]


def hash_probe(table_planes, slot_gid, planes, hash32, live=None,
               interpret: bool = False):
    """Look up [P, N] ``planes`` rows in a table built by hash_insert.
    Returns [N] int32 group ids; -1 = miss (or dead probe row)."""
    table_planes = jnp.asarray(table_planes, jnp.uint32)
    slot_gid = jnp.asarray(slot_gid, jnp.int32)
    planes = jnp.asarray(planes, jnp.uint32)
    hash32 = jnp.asarray(hash32, jnp.uint32)
    P, n = int(planes.shape[0]), int(planes.shape[1])
    S = int(slot_gid.shape[0])
    live = (jnp.ones(n, jnp.bool_) if live is None
            else jnp.asarray(live, jnp.bool_))
    planes, hash32, live, total = _pad_rows(planes, hash32, live, n)
    run = _build_probe(P, S, total // _HBLOCK, interpret)
    with _enable_x64(False):
        gid = run(table_planes, slot_gid.reshape(1, S), planes,
                  hash32.reshape(1, total), live.reshape(1, total))
    return gid[0, :n]
