"""Pallas TPU kernel for the hottest reduction shape.

Hand-written kernels where the access pattern benefits from explicit VMEM
accumulation rather than XLA's scatter-based ``segment_sum`` lowering:
relational aggregations reduce millions of rows into a handful of group
slots (TPC-H Q1: 4 groups), so a block-resident accumulator that revisits
one [G, 128] VMEM tile per input block avoids the scatter entirely — the
Pallas analogue of the hand-specialized accumulators the reference
generates per aggregation (operator/aggregation/*, sql/gen).

The kernel is f32/int32 (the TPU-native lanes); the engine routes REAL
aggregations here (exec/kernels.grouped_reduce fast path) while
f64/decimal reductions stay on the XLA sort+segment path.  ``interpret=
True`` runs the same kernel on CPU for tests.
"""

from __future__ import annotations

from functools import partial

from ..caching.executable_cache import jit_memo, program

import jax
import jax.numpy as jnp

# the engine traces this kernel in 32-bit mode because Mosaic rejects the
# stray i64 weak types x64 mode produces (jax 0.9: ``jax.enable_x64`` is the
# context manager; the ``jax.experimental`` spelling is gone)
from jax import enable_x64 as _enable_x64

__all__ = ["masked_segment_sum_f32"]

_BLOCK = 1024  # rows per grid step (8 sublanes x 128 lanes)
_LANES = 128


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
