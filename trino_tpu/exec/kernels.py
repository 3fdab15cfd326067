"""Jitted relational kernels: grouped aggregation, key hashing, sort, partition.

These are the TPU-native replacements for Trino's hand-specialized flat-memory
data structures (reference: operator/FlatHash.java:42, operator/join/
PagesHash.java, sql/gen/OrderingCompiler.java:70, operator/output/
PagePartitioner.java:55).  Design rules:

- **Sort-first, hash as the measured alternative.**  Grouping and join build
  default to a *sort*: XLA lowers ``sort`` to an efficient on-chip bitonic
  network, and everything downstream (segment reduction, binary-search
  probe) is dense vector work on the MXU/VPU.  ``TRINO_TPU_HASH_IMPL``
  selects a second, open-addressing implementation of the same contracts
  (Pallas linear-probing kernels, ops/pallas_kernels.py) so the two can be
  baked off per NDV (bench.py --ndv) instead of argued about.
- **Static shapes via bucketing.**  Data-dependent sizes (group counts, join
  fan-out) are synced to host once per kernel invocation and rounded up to a
  power of two; jitted programs are cached per (spec, shape-bucket), so
  repeated batches hit the compile cache.
- **(data, valid) pairs everywhere** — same convention as ops/expr.py.

Null semantics baked in: GROUP BY treats NULL as a regular group (SQL
spec / Trino GroupByHash behavior); equi-join keys never match on NULL.
"""

from __future__ import annotations

import functools
import os
from ..caching.executable_cache import jit_memo, program
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import ops as _ops  # noqa: F401  (enables jax x64 lanes)
from ..spi.errors import GENERIC_INTERNAL_ERROR, TrinoError

__all__ = [
    "bucket",
    "group_ids",
    "group_ids_auto",
    "hash_group_ids",
    "hash_impl",
    "key_planes",
    "grouped_reduce",
    "sort_perm",
    "hash_combine",
    "partition_assignments",
    "partition_masks",
    "live_count",
    "rle_fill",
]


def bucket(n: int, minimum: int = 8) -> int:
    """Round up to a power of two (static-shape recompile bucket)."""
    c = minimum
    while c < n:
        c <<= 1
    return c


def rle_fill(value, length: int):
    """Expand an RLE run on device: ``jnp.full`` materializes the run from
    ONE host scalar, so no run-length payload ever crosses the host/device
    boundary (the expand-at-the-last-moment half of compressed execution)."""
    value = np.asarray(value)
    return jnp.full(length, value, dtype=value.dtype)


@jit_memo("kernels._searchsorted_method")
def _searchsorted_method(shape: tuple) -> str:
    n_needles = 1
    for s in shape:
        n_needles *= int(s)
    return "sort" if n_needles >= 4096 else "scan"


def searchsorted(a, v, side: str = "left"):
    """TPU-aware searchsorted: the default 'scan' method is a serial
    binary search — log(n) dependent HBM gathers PER NEEDLE — measured at
    ~1s for 2M needles on v5e, while the 'sort' method (sort the concat,
    derive positions) rides the optimized XLA bitonic sort at ~1ms.  Small
    needle counts keep 'scan' (sorting the haystack for 8 needles wastes a
    full pass).  The method pick is memoized per needle SHAPE: this runs on
    every trace of every jitted program, so the per-call product over the
    dims is hoisted into a registry memo keyed like the jit cache itself."""
    method = (_searchsorted_method(tuple(v.shape))
              if hasattr(v, "shape") else "scan")
    return jnp.searchsorted(a, v, side=side, method=method)


def _canon_float(x):
    """Canonicalize float keys so hashing/grouping agree with SQL equality:
    -0.0 -> +0.0 (they compare equal but have different bits) and every NaN
    to the one canonical quiet-NaN pattern (NaN is a single GROUP BY value —
    Trino treats NaN as equal to itself for grouping/joining).  The positive
    canonical NaN also keeps all NaNs adjacent under XLA's total-order sort
    (-NaN sorts first, +NaN last)."""
    x = jnp.where(x == 0, jnp.zeros((), x.dtype), x)
    return jnp.where(jnp.isnan(x), jnp.full((), jnp.nan, x.dtype), x)


def _neq(a, b):
    """Elementwise 'different group key' compare: IEEE != except that NaN
    equals NaN (SQL grouping semantics)."""
    r = a != b
    if np.dtype(a.dtype).kind == "f":
        r = r & ~(jnp.isnan(a) & jnp.isnan(b))
    return r


# ---------------------------------------------------------------------------
# grouped aggregation: sort -> boundary-detect -> segment reduce


@jit_memo("kernels._group_ids_fn")
def _group_ids_fn(num_keys: int, has_valid: tuple[bool, ...], has_live: bool):
    n_valid = sum(has_valid)

    @program("kernels.group_ids")
    def fn(*flat):
        datas = list(flat[:num_keys])
        valids = list(flat[num_keys:num_keys + n_valid])
        live = flat[num_keys + n_valid] if has_live else None
        # normalize: NULL lanes carry arbitrary fill (e.g. div-by-zero output);
        # zero them so every NULL is bit-identical and sorts into one run
        vmap = {}
        vi = 0
        for i in range(num_keys):
            if np.dtype(datas[i].dtype).kind == "f":
                # keys stay float (64-bit bitcasts don't survive the TPU x64
                # rewrite); canonicalization makes NaNs sort adjacent and the
                # NaN-aware boundary compare below makes them one group
                datas[i] = _canon_float(datas[i])
            if has_valid[i]:
                v = valids[vi]
                vi += 1
                datas[i] = jnp.where(v, datas[i], jnp.zeros((), datas[i].dtype))
                vmap[i] = v
        # lexsort: last key in the tuple is the primary sort key; dead rows
        # (selection-mask filtering) sort after every live row
        sort_keys = []
        for i in reversed(range(num_keys)):
            sort_keys.append(datas[i])
            if i in vmap:
                sort_keys.append(vmap[i])
        if live is not None:
            sort_keys.append(~live)
        perm = jnp.lexsort(tuple(sort_keys))
        new_group = jnp.zeros(datas[0].shape, dtype=jnp.bool_)
        for i in range(num_keys):
            d = datas[i][perm]
            diff = jnp.concatenate([jnp.ones((1,), jnp.bool_), _neq(d[1:], d[:-1])])
            if i in vmap:
                v = vmap[i][perm]
                diff = diff | jnp.concatenate(
                    [jnp.ones((1,), jnp.bool_), v[1:] != v[:-1]]
                )
            new_group = new_group | diff
        if live is not None:
            lv = live[perm]
            # force a boundary at the live->dead transition so dead rows can
            # never extend the last live group, and count live groups only;
            # dead rows get gids >= num_groups and fall out of every scatter
            new_group = new_group | jnp.concatenate(
                [jnp.ones((1,), jnp.bool_), lv[1:] != lv[:-1]])
            gid = jnp.cumsum(new_group.astype(jnp.int32)) - 1
            return perm, gid, jnp.sum(new_group & lv)
        gid = jnp.cumsum(new_group.astype(jnp.int32)) - 1
        return perm, gid, gid[-1] + 1

    return fn


def group_ids(keys: Sequence[tuple], live=None) -> tuple:
    """keys: [(data, valid|None), ...] equal-length 1-D arrays; ``live`` an
    optional row mask (False = dead padded/filtered row).

    Returns (perm, gid, num_groups): ``perm`` sorts rows so equal keys are
    adjacent (dead rows last); ``gid[i]`` is the dense group id of sorted row
    i; dead rows receive gids >= num_groups.  perm/gid stay on device."""
    num_keys = len(keys)
    has_valid = tuple(v is not None for _, v in keys)
    datas = [jnp.asarray(d) for d, _ in keys]
    valids = [jnp.asarray(v) for _, v in keys if v is not None]
    extra = [jnp.asarray(live)] if live is not None else []
    perm, gid, n = _group_ids_fn(num_keys, has_valid, live is not None)(
        *datas, *valids, *extra)
    return perm, gid, int(n)


# ---------------------------------------------------------------------------
# open-addressing grouping (TRINO_TPU_HASH_IMPL): Pallas linear-probing
# insert/probe kernels as a second implementation of the group_ids contract


def hash_impl() -> str:
    """Resolved TRINO_TPU_HASH_IMPL knob: 'pallas' forces the open-addressing
    kernels, 'sort' and 'auto' take the lexsort / searchsorted path.  Read per
    call, not cached: tests and the bench flip it between legs."""
    mode = os.environ.get("TRINO_TPU_HASH_IMPL", "auto").lower()
    return mode if mode in ("pallas", "sort") else "auto"


def hash_interpret() -> bool:
    """Interpret-mode pallas (identical kernels as pure XLA) everywhere but
    a real TPU backend; TRINO_TPU_HASH_INTERPRET=1 forces it for A/B runs."""
    if os.environ.get("TRINO_TPU_HASH_INTERPRET") == "1":
        return True
    return jax.default_backend() != "tpu"


def hash_kernels_selected(n_rows: int) -> bool:
    """THE TRINO_TPU_HASH_IMPL decision, shared by group_ids_auto, the join
    index build (join_exec.build_table) and parallel/static_agg.

    Only an explicit 'pallas' selects the open-addressing kernels, and a
    selected kernel that does not compile or run fails the query — nothing
    swaps implementations behind the caller.  'auto' resolves to sort on
    every backend, by rule: off-TPU the kernels only exist in interpret
    mode (a correctness vehicle), and on TPU Mosaic refuses both of them —
    ``ValueError: Cannot store scalars to VMEM`` (libtpu 0.0.34, compiled
    for v5e; tests/test_tpu_compile.py holds them as strict xfails).  The
    day that xfail flips, give 'auto' a TPU branch with a VMEM-fit test."""
    return n_rows > 0 and hash_impl() == "pallas"


def _f64_key_planes(c) -> list:
    """Four uint32 planes INJECTIVE over canonical float64 values: the same
    range-reduction as _f64_hash_word (the TPU x64 rewrite compiles no
    64-bit bitcast) but keeping the w1/w2/w3 words and the class/tag meta
    word separate instead of mixing them.  scaled = w1 + w2 + w3 exactly
    (each split removes >= 24 significand bits, 24*3 > 53), and the power-
    of-two scale is exact, so equal doubles give equal planes and distinct
    doubles distinct planes: plane equality IS SQL key equality."""
    fin = jnp.isfinite(c)
    mag = jnp.abs(c)
    safe_mag = jnp.where(mag > 0, mag, 1.0)
    cls = jnp.clip(jnp.floor(jnp.log2(safe_mag) / 120.0), -9.0, 9.0)
    s = 2.0 ** (-60.0 * cls)  # applied twice; 2**(-120*cls) would overflow
    scaled = jnp.where(fin, c * s * s, 0.0)
    w1 = scaled.astype(jnp.float32)
    r1 = scaled - w1.astype(jnp.float64)
    w2 = r1.astype(jnp.float32)
    r2 = r1 - w2.astype(jnp.float64)
    w3 = r2.astype(jnp.float32)
    tag = jnp.where(jnp.isnan(c), 3, jnp.where(c == jnp.inf, 1,
                    jnp.where(c == -jnp.inf, 2, 0)))
    meta = (cls.astype(jnp.int32) + 16) | (tag.astype(jnp.int32) << 8)

    def u32(w):
        return jax.lax.bitcast_convert_type(w, jnp.uint32)

    return [u32(w1), u32(w2), u32(w3), meta.astype(jnp.uint32)]


def key_planes(keys: Sequence[tuple]) -> list:
    """Normalize key columns into uint32 planes whose elementwise equality
    is exactly SQL group-key equality: ints/bools split into lo/hi 32-bit
    words, floats canonicalized (-0 -> +0, one NaN) then decomposed into the
    injective w1/w2/w3/meta cascade, nullable keys zero their data planes
    and append a validity plane (NULL is its own group, distinct from 0)."""
    out: list = []
    for d, v in keys:
        d = jnp.asarray(d)
        kind = np.dtype(d.dtype).kind
        if kind == "f":
            kp = _f64_key_planes(_canon_float(d.astype(jnp.float64)))
        elif kind == "b":
            kp = [d.astype(jnp.uint32)]
        else:
            x = d.astype(jnp.int64)
            kp = [(x & 0xFFFFFFFF).astype(jnp.uint32),
                  ((x >> 32) & 0xFFFFFFFF).astype(jnp.uint32)]
        if v is not None:
            vv = jnp.asarray(v)
            kp = [jnp.where(vv, p, jnp.zeros((), p.dtype)) for p in kp]
            kp.append(vv.astype(jnp.uint32))
        out.extend(kp)
    return out


def hash_row_gids(keys: Sequence[tuple], live=None,
                  num_slots: Optional[int] = None):
    """Open-addressing core: per-ORIGINAL-row dense group ids in first-
    occurrence order via the Pallas insert kernel.  Returns (row_gid,
    count): dead rows get ``num_slots`` (>= any real id), ``count`` stays a
    DEVICE scalar — zero host syncs, usable inside jitted programs."""
    from ..ops import pallas_kernels as PK

    datas = [jnp.asarray(d) for d, _ in keys]
    n = int(datas[0].shape[0])
    S = int(num_slots) if num_slots else bucket(2 * max(n, 1))
    planes = key_planes(keys)
    h = hash_combine(planes)
    h32 = (h ^ (h >> jnp.uint64(32))).astype(jnp.uint32)
    lv = None if live is None else jnp.asarray(live)
    row_gid, count, _table, _sgid = PK.hash_insert(
        jnp.stack(planes), h32, lv, S, interpret=hash_interpret())
    return row_gid, count


@jit_memo("kernels._hash_finish_fn")
def _hash_finish_fn():
    @program("kernels.hash_finish")
    def fn(row_gid):
        # jnp.argsort is stable: rows within a group keep input order, and
        # dead rows (gid = num_slots, beyond every real id) sort last
        perm = jnp.argsort(row_gid)
        return perm, row_gid[perm].astype(jnp.int32)

    return fn


def hash_group_ids(keys: Sequence[tuple], live=None) -> tuple:
    """Open-addressing alternative to :func:`group_ids` — same contract:
    (perm, gid, num_groups) with gid nondecreasing over sorted rows, equal
    keys adjacent, dead rows last with gid >= num_groups, and ONE host sync
    for the count.  Group ids come out in first-occurrence order instead of
    key order; both satisfy the documented contract, operator output is
    order-canonicalized downstream.  The expensive multi-key 64-bit lexsort
    becomes one int32 sort over the kernel-assigned ids."""
    if not keys:
        raise TrinoError(GENERIC_INTERNAL_ERROR,
                         "hash_group_ids needs at least one key")
    n = int(jnp.asarray(keys[0][0]).shape[0])
    if n == 0:
        return jnp.arange(0), jnp.zeros(0, jnp.int32), 0
    row_gid, count = hash_row_gids(keys, live)
    perm, gid = _hash_finish_fn()(row_gid)
    return perm, gid, int(count)


def group_ids_auto(keys: Sequence[tuple], live=None) -> tuple:
    """group_ids with the TRINO_TPU_HASH_IMPL knob applied (see
    hash_kernels_selected); the selected implementation's errors
    propagate."""
    n = int(jnp.asarray(keys[0][0]).shape[0]) if keys else 0
    if keys and hash_kernels_selected(n):
        return hash_group_ids(keys, live)
    return group_ids(keys, live)


SMALL_CODES_LIMIT = 4096  # max fused-code group space for the no-sort path
MASKED_AGG_LIMIT = 128  # masked-reduction aggregate path (no sort, no gather)


def _code_layout(sizes: tuple, has_valid: tuple):
    """Fused-code layout shared by the small-codes grouping paths: each key
    gets ``sizes[k]`` code slots plus one null slot when nullable; the fused
    group id is sum(code_k * strides[k]) in [0, total)."""
    slots = tuple(s + 1 if hv else s for s, hv in zip(sizes, has_valid))
    total = 1
    for s in slots:
        total *= s
    strides = []
    acc = 1
    for s in reversed(slots):
        strides.append(acc)
        acc *= s
    return slots, tuple(reversed(strides)), total


def _fuse_codes(codes, valids, live, sizes, strides, total):
    """Traced: dense fused gid per row; NULL keys take the null slot, dead
    rows get ``total`` (matching no group)."""
    fused = jnp.zeros(codes[0].shape, jnp.int32)
    for k in range(len(codes)):
        c = jnp.clip(codes[k].astype(jnp.int32), 0, sizes[k] - 1)
        if valids[k] is not None:
            c = jnp.where(valids[k], c, sizes[k])
        fused = fused + c * strides[k]
    if live is not None:
        fused = jnp.where(live, fused, total)
    return fused


def _decode_codes(r, sizes, slots, strides, has_valid):
    """Traced: representative (code, valid) per group id in ``r``."""
    keys_out = []
    for k in range(len(sizes)):
        ck = (r // strides[k]) % slots[k]
        if has_valid[k]:
            keys_out.append((jnp.minimum(ck, sizes[k] - 1), ck < sizes[k]))
        else:
            keys_out.append((ck, None))
    return keys_out


def _small_agg_reduce(spec: tuple, num_keys: int, has_valid: tuple,
                      has_live: bool, sizes: tuple, flat):
    """Traced: the masked reduction itself, shared by kernels.small_agg, the
    per-batch fold and an operator program that filters and folds in one
    launch.  ``flat`` is key codes (+ validity), the live mask when
    ``has_live``, then the deduped aggregate operands.  Returns one
    ``[group space]`` array per state column, in small_agg_state_layout's
    order: rows-per-group, then per spec entry the value and, for every
    aggregate that can be NULL, the count of rows that contributed."""
    slots, strides, total = _code_layout(sizes, has_valid)
    i = 0
    codes, valids = [], []
    for k in range(num_keys):
        codes.append(flat[i])
        i += 1
        if has_valid[k]:
            valids.append(flat[i])
            i += 1
        else:
            valids.append(None)
    live = flat[i] if has_live else None
    i += 1 if has_live else 0
    aggs_flat = flat[i:]
    if num_keys:
        fused = _fuse_codes(codes, valids, live, sizes, strides, total)
    else:
        shape_src = live if live is not None else aggs_flat[0]
        fused = jnp.zeros(shape_src.shape, jnp.int32)
        if live is not None:
            fused = jnp.where(live, fused, total)

    # a batch's lanes fit 31 bits: count in the chip's native width and
    # widen the [group space] result (64-bit lanes are emulated)
    count_dtype = jnp.int32 if fused.shape[0] < (1 << 31) else jnp.int64

    def count(mask):
        return jnp.sum(mask, dtype=count_dtype).astype(jnp.int64)

    def one_group(g):
        m = fused == g
        outs = []
        outs.append(count(m))  # rows-per-group
        for fname, data_idx, valid_idx, dtype_str, pre in spec:
            dtype = jnp.dtype(dtype_str)
            if fname == "count_star":
                outs.append(count(m))
                continue
            x = aggs_flat[data_idx]
            if pre is not None:
                if pre[0] == "scale":
                    x = x.astype(jnp.float64) / (10.0 ** pre[1])
                elif pre[0] == "square":
                    x64 = x.astype(jnp.float64)
                    x = x64 * x64
            v = aggs_flat[valid_idx] if valid_idx >= 0 else None
            mv = m if v is None else (m & v)
            if fname == "count":
                outs.append(count(mv))
            elif fname == "sum":
                outs.append(jnp.sum(
                    jnp.where(mv, x.astype(dtype), jnp.zeros((), dtype))))
                outs.append(count(mv))  # any-valid
            elif fname in ("min", "max", "any_value"):
                is_min = fname != "max"  # any_value: min is as good as any
                sent = _sentinel("min" if is_min else "max", x.dtype)
                masked = jnp.where(mv, x, sent)
                outs.append(jnp.min(masked) if is_min else jnp.max(masked))
                outs.append(count(mv))
            else:
                raise NotImplementedError(f"masked aggregate {fname}")
        return tuple(outs)

    return jax.vmap(one_group)(jnp.arange(total, dtype=jnp.int32))


def small_agg_state_layout(spec: tuple) -> tuple:
    """(merge, dtype) per state column _small_agg_reduce returns for
    ``spec``: how two states of disjoint row sets combine (``add``, ``min``
    or ``max``) and the column's dtype.  The state of a streaming masked
    aggregation is exactly these columns (held stacked by dtype:
    small_agg_state_shapes), so finalization reads a merged state the way
    it reads one reduction."""
    layout = [("add", "<i8")]  # rows-per-group
    for fname, _data_idx, _valid_idx, dtype_str, _pre in spec:
        if fname in ("count", "count_star"):
            layout.append(("add", "<i8"))
            continue
        merge = "add" if fname == "sum" else (
            "max" if fname == "max" else "min")
        layout.append((merge, np.dtype(dtype_str).str))
        layout.append(("add", "<i8"))  # any-valid count
    return tuple(layout)


def small_agg_state_shapes(layout: tuple, total: int) -> tuple:
    """((rows, total), dtype) of the arrays a state of ``layout`` is held
    in: its columns stacked by dtype, in order of first appearance.  A
    launch pays for every buffer it takes and returns (on a v5e some tenths
    of a millisecond each when freshly allocated: PERF.md section 6,
    PR 30), so twenty [6]-lane columns travel as two or three arrays."""
    rows: dict = {}
    for _merge, dtype_str in layout:
        rows[dtype_str] = rows.get(dtype_str, 0) + 1
    return tuple(((n, total), d) for d, n in rows.items())


def _pack_state(layout: tuple, cols) -> tuple:
    """Traced: state columns -> the stacked arrays (small_agg_state_shapes'
    order)."""
    by_dtype: dict = {}
    for (_merge, dtype_str), c in zip(layout, cols, strict=True):
        if c.dtype != jnp.dtype(dtype_str):
            raise TrinoError(
                GENERIC_INTERNAL_ERROR,
                f"masked aggregation state column is {dtype_str}, its "
                f"reduction came out {c.dtype}")
        by_dtype.setdefault(dtype_str, []).append(c)
    return tuple(jnp.stack(cs) for cs in by_dtype.values())


def _unpack_state(layout: tuple, packed) -> list:
    """Traced: the stacked arrays -> state columns in ``layout``'s order."""
    rows = {d: iter(stack) for d, stack in zip(
        dict.fromkeys(d for _, d in layout), packed, strict=True)}
    return [next(rows[d]) for _, d in layout]


def _merge_state(layout: tuple, state, cols) -> tuple:
    """Traced: fold one reduction's columns into the running (packed)
    state."""
    return _pack_state(layout, [
        old + new if merge == "add"
        else jnp.minimum(old, new) if merge == "min"
        else jnp.maximum(old, new)
        for (merge, _), old, new in zip(layout, _unpack_state(layout, state),
                                        cols, strict=True)])


def _small_agg_results(spec: tuple, cols, sizes: tuple, has_valid: tuple):
    """Traced: (results, presence, keys_out) from the state columns."""
    slots, strides, total = _code_layout(sizes, has_valid)
    presence = cols[0] > 0
    results = []
    ci = 1
    for fname, _data_idx, _valid_idx, _dtype_str, _pre in spec:
        if fname in ("count", "count_star"):
            results.append((cols[ci], None))
            ci += 1
        else:
            # the any-contributor flag applies even without a column
            # validity mask: an empty (or fully dead) group's
            # sum/min/max is NULL, not the fill value
            results.append((cols[ci], cols[ci + 1] > 0))
            ci += 2
    keys_out = _decode_codes(jnp.arange(total, dtype=jnp.int32),
                             sizes, slots, strides, has_valid)
    return results, presence, keys_out


@jit_memo("kernels._small_agg_fn")
def _small_agg_fn(spec: tuple, num_keys: int, has_valid: tuple,
                  has_live: bool, sizes: tuple):
    """Small-group aggregation with NO sort and NO gather: the group id is
    dictionary-code arithmetic and every aggregate is a vmapped masked
    reduction over the raw rows (measured ~100ms for 8 aggregates over 16M
    rows on v5e vs ~500ms per column for argsort+gather+cumsum — random
    gathers are the TPU's weak point, dense reductions its strength).

    Takes UNCOMPACTED input by design: ``live`` folds into the fused group
    id, so a dead lane costs one compare per group and nothing else, and the
    aggregation operator hands its concatenated, padded, sparsely-live
    bucket straight in (no count sync, no kernels.compact) while
    groups x reductions stays under its measured crossover.

    spec: (fn, data_idx, valid_idx, dtype_str, pre) per aggregate over the
    deduped flat operand list; num_keys may be 0 (global aggregate, one
    group).  Float sums need no NaN/Inf rescue here: a NaN only ever lands
    in its own group's reduction (IEEE semantics are exactly SQL's)."""

    @program("kernels.small_agg")
    def fn(*flat):
        cols = _small_agg_reduce(spec, num_keys, has_valid, has_live,
                                 sizes, flat)
        return _small_agg_results(spec, cols, sizes, has_valid)

    return fn


class MaskedOperands(NamedTuple):
    """What one masked reduction reads, in the shape its programs key on:
    the static ``spec`` / key layout, and the ``flat`` operand list (key
    codes and validity, the live mask when ``has_live``, then the deduped
    aggregate columns).  ``flat`` holds whatever the caller's columns held:
    arrays, tracers, or shapes when only the layout is asked for."""

    spec: tuple
    num_keys: int
    has_valid: tuple
    has_live: bool
    sizes: tuple
    flat: list

    @property
    def static(self) -> tuple:
        return self[:5]

    @property
    def layout(self) -> tuple:
        return small_agg_state_layout(self.spec)

    @property
    def space(self) -> int:
        return _code_layout(self.sizes, self.has_valid)[2]

    @property
    def state_shapes(self) -> tuple:
        return small_agg_state_shapes(self.layout, self.space)


def small_agg_operands(key_cols, live, aggs: Sequence[tuple]
                       ) -> MaskedOperands:
    """Operands of the masked programs from key columns, a live mask and
    aggs [(fn, data|None, valid|None, out_dtype, distinct[, pre]), ...]
    (grouped_reduce's input shape; distinct unsupported -- the caller falls
    back).  Operands are DEDUPED by object identity, so aggregates over the
    same column or mask share one."""
    flat: list = []
    for c in key_cols:
        flat.append(c.data)
        if c.valid is not None:
            flat.append(c.valid)
    if live is not None:
        flat.append(live)
    base = len(flat)
    flat_ids: dict = {}
    spec = []

    def idx_of(arr) -> int:
        if arr is None:
            return -1
        k = id(arr)
        if k not in flat_ids:
            flat_ids[k] = len(flat) - base
            flat.append(arr)
        return flat_ids[k]

    for entry in aggs:
        fn_name, data, valid, dtype, _distinct = entry[:5]
        pre = entry[5] if len(entry) > 5 else None
        if fn_name == "count_star" or data is None:
            # a live-masked count* folds live via the fused gid already
            spec.append(("count", -1, idx_of(valid), "int64", None)
                        if valid is not None else
                        ("count_star", -1, -1, "int64", None))
            continue
        spec.append((fn_name, idx_of(data), idx_of(valid),
                     np.dtype(dtype).str, pre))
    return MaskedOperands(
        tuple(spec), len(key_cols),
        tuple(c.valid is not None for c in key_cols), live is not None,
        tuple(len(c.dictionary) for c in key_cols), flat)


def small_grouped_aggregate(key_cols, live, aggs: Sequence[tuple]):
    """Returns (results, presence|None, keys_out, num_groups) for
    small_agg_operands' arguments: ONE program, zero host syncs, static
    group count."""
    ops = small_agg_operands(key_cols, live, aggs)
    results, presence, keys_out = _small_agg_fn(*ops.static)(*ops.flat)
    if ops.num_keys == 0:
        presence = None  # a global aggregate always emits its one row
    return results, presence, keys_out, ops.space


def small_agg_fold_body(spec: tuple, num_keys: int, has_valid: tuple,
                        has_live: bool, sizes: tuple):
    """``(state, *flat) -> state``: reduce one batch and merge it into the
    running state -- what kernels.small_agg_fold traces for each slot of
    its group, and what an operator's program may trace behind its own
    work instead."""
    layout = small_agg_state_layout(spec)

    def fold(state, *flat):
        cols = _small_agg_reduce(spec, num_keys, has_valid, has_live,
                                 sizes, flat)
        return _merge_state(layout, state, cols)

    return fold


def fold_group_body(fold_one, slots: int):
    """``(state, n, group) -> state``: ``fold_one(state, *group[i])`` for
    the first ``n`` of ``group``'s ``slots`` entries, one after the other
    in the order given, into the one state -- a launch for a group of
    batches where ``fold_one`` alone would take a launch a batch.  Unrolled
    in the trace (the batches are separate device buffers; stacking them to
    loop over would copy every row); a slot from the second on runs under
    ``lax.cond(i < n, ...)``, so ONE program serves every group size up to
    ``slots``: the caller fills the absent slots with slot 0's arrays
    again, which are then never read."""

    def run(state, n, group):
        state = fold_one(state, *group[0])
        for i in range(1, slots):
            state = jax.lax.cond(
                i < n, lambda s, slot=group[i]: fold_one(s, *slot),
                lambda s: s, state)
        return state

    return run


@functools.lru_cache(maxsize=64)
def slot_count(n: int):
    """``n`` as the int32 device scalar fold_group_body's programs take:
    made once a process, so a launch on the default device uploads nothing
    (an uncommitted array: beside operands committed to another chip of a
    mesh each launch copies these four bytes across)."""
    return jnp.int32(n)


def donate_ok() -> bool:
    """Buffer donation saves HBM (and a copy) on real accelerators; the CPU
    backend warns about unusable donations, so only donate off-CPU."""
    return jax.default_backend() != "cpu"


@jit_memo("kernels._small_agg_fold_fn")
def _small_agg_fold_fn(spec: tuple, num_keys: int, has_valid: tuple,
                       has_live: bool, sizes: tuple, slots: int,
                       donate: bool):
    """The program of a streaming masked aggregation that has no
    filter/project to share a launch with: ``(state, n, group of slots
    flat operand tuples) -> state``; the state is donated, each batch is
    read once, nothing is buffered, sorted or synced."""
    return program("kernels.small_agg_fold",
                   fold_group_body(
                       small_agg_fold_body(spec, num_keys, has_valid,
                                           has_live, sizes), slots),
                   donate_argnums=(0,) if donate else ())


def small_agg_fold_program(ops: MaskedOperands, slots: int):
    """kernels.small_agg_fold for ``ops``' layout and ``slots`` batches a
    launch (fold_group_body's contract; a slot is ``tuple(ops.flat)``)."""
    return _small_agg_fold_fn(*ops.static, slots, donate_ok())


@jit_memo("kernels._small_agg_zero_fn")
def _small_agg_zero_fn(layout: tuple, total: int, with_error: bool):
    @program("kernels.small_agg_zero")
    def fn():
        state = _pack_state(layout, [
            jnp.full((total,),
                     0 if merge == "add" else _sentinel(merge, dtype_str),
                     jnp.dtype(dtype_str))
            for merge, dtype_str in layout])
        return state + ((jnp.zeros((), jnp.int32),) if with_error else ())

    return fn


def small_agg_zero_state(ops: MaskedOperands, with_error: bool = False
                         ) -> tuple:
    """A fresh identity state for ``ops``' layout over its static group
    space, in one small launch (the state is donated to the first fold, so
    every stream needs buffers of its own): 0 under ``add``, the
    reduction's own sentinel under ``min``/``max``; ``with_error`` appends
    the int32 error scalar a fused filter/project carries along."""
    return _small_agg_zero_fn(ops.layout, ops.space, with_error)()


@jit_memo("kernels._small_agg_state_out_fn")
def _small_agg_state_out_fn(spec: tuple, sizes: tuple, has_valid: tuple):
    layout = small_agg_state_layout(spec)

    @program("kernels.small_agg_state_out")
    def fn(*state):
        return _small_agg_results(spec, _unpack_state(layout, state), sizes,
                                  has_valid)

    return fn


def small_agg_state_out(state: Sequence, ops: MaskedOperands):
    """What small_grouped_aggregate returns, from the final state of a
    stream over ``ops``' layout (one launch, once per stream)."""
    results, presence, keys_out = _small_agg_state_out_fn(
        tuple((s[0], -1, -1, s[3], None) for s in ops.spec), ops.sizes,
        ops.has_valid)(*state)
    if ops.num_keys == 0:
        presence = None
    return results, presence, keys_out, ops.space


@jit_memo("kernels._group_ids_codes_fn")
def _group_ids_codes_fn(num_keys: int, has_valid: tuple, has_live: bool,
                        sizes: tuple):
    """Fast path for group keys that are ALL small dictionary codes (the
    TPC-H Q1 shape: GROUP BY returnflag, linestatus): the dense group id is
    plain code arithmetic — no multi-key lexsort, and the group count is
    the static product of dictionary sizes (+1 null slot per nullable key),
    so the caller needs NO num_groups host sync.  One program returns
    (perm, gid, presence, decoded representative keys)."""
    slots, strides, total = _code_layout(sizes, has_valid)

    @program("kernels.group_ids_codes")
    def fn(*flat):
        i = 0
        codes, valids = [], []
        for k in range(num_keys):
            codes.append(flat[i])
            i += 1
            if has_valid[k]:
                valids.append(flat[i])
                i += 1
            else:
                valids.append(None)
        live = flat[i] if has_live else None
        fused = _fuse_codes(codes, valids, live, sizes, strides, total)
        perm = jnp.argsort(fused)
        gid = fused[perm]
        r = jnp.arange(total, dtype=gid.dtype)
        presence = (searchsorted(gid, r, side="right")
                    > searchsorted(gid, r))
        keys_out = _decode_codes(r, sizes, slots, strides, has_valid)
        return perm, gid, presence, keys_out

    return fn


def small_codes_group_space(key_cols, limit: int = SMALL_CODES_LIMIT):
    """If every key column is dictionary-encoded with a known-small code
    space, return the static group-space size (else None)."""
    total = 1
    for c in key_cols:
        d = c.dictionary
        if d is None or len(d) == 0:
            return None
        total *= len(d) + (1 if c.valid is not None else 0)
        if total > limit:
            return None
    return total


def group_ids_codes(key_cols, live):
    """Run the small-codes grouping program.  Returns
    (perm, gid, num_groups, presence, keys_out) with num_groups static
    (zero host syncs); ``presence[g]`` marks non-empty groups."""
    num_keys = len(key_cols)
    has_valid = tuple(c.valid is not None for c in key_cols)
    sizes = tuple(len(c.dictionary) for c in key_cols)
    flat: list = []
    for c in key_cols:
        flat.append(jnp.asarray(c.data))
        if c.valid is not None:
            flat.append(jnp.asarray(c.valid))
    if live is not None:
        flat.append(jnp.asarray(live))
    perm, gid, presence, keys_out = _group_ids_codes_fn(
        num_keys, has_valid, live is not None, sizes)(*flat)
    total = 1
    for s, hv in zip(sizes, has_valid):
        total *= s + (1 if hv else 0)
    return perm, gid, total, presence, keys_out


_LIMB_BASE = 1 << 31
_LIMB_COUNT = 5  # 5x31 bits = 155 > 127-bit magnitude; +1 sign limb


def decimal_limb_tables(dictionary) -> list[np.ndarray]:
    """Long-decimal dictionary (python scaled ints) -> 6 int64 limb tables:
    value = sum(limb_k * 2^(31k)) + sign_limb * 2^155.  Each limb is in
    [0, 2^31) (sign limb in {-1, 0}), so per-group int64 sums stay exact
    for up to 2^31 rows — the engine's Int128Math.java: exact wide-decimal
    SUM/AVG runs as ordinary int64 vector sums over limb planes, recombined
    with python bignums per group (spi/type/Int128Math.java's role)."""
    n = len(dictionary)
    tabs = [np.empty(n, np.int64) for _ in range(_LIMB_COUNT + 1)]
    for i, v in enumerate(dictionary):
        x = int(v)
        for k in range(_LIMB_COUNT):
            x, r = divmod(x, _LIMB_BASE)
            tabs[k][i] = r
        tabs[_LIMB_COUNT][i] = x  # 0 or -1
    return tabs


def combine_limb_sums(sums) -> int:
    """Per-group limb sums (python ints) -> exact scaled-int total."""
    total = 0
    for k in range(_LIMB_COUNT):
        total += int(sums[k]) << (31 * k)
    total += int(sums[_LIMB_COUNT]) << (31 * _LIMB_COUNT)
    return total


_SENTINELS = {
    "min": {
        "i": lambda dt: jnp.iinfo(dt).max,
        "f": lambda dt: jnp.inf,
        "b": lambda dt: True,
    },
    "max": {
        "i": lambda dt: jnp.iinfo(dt).min,
        "f": lambda dt: -jnp.inf,
        "b": lambda dt: False,
    },
}


def _sentinel(fn: str, dtype) -> object:
    kind = np.dtype(dtype).kind
    k = "f" if kind == "f" else ("b" if kind == "b" else "i")
    return _SENTINELS[fn][k](dtype)


@jit_memo("kernels._reduce_fn")
def _reduce_fn(spec: tuple, cap: int):
    """spec: tuple of (fn, data_idx, valid_idx, dtype_str, distinct, pre)
    per aggregate; data_idx/valid_idx index the DEDUPED flat input arrays
    (-1 = absent), so aggregates sharing a column or a validity/live mask
    share one prefix scan.  ``pre`` applies elementwise prep INSIDE the
    compiled program (("scale", s) = scale-free f64 avg state; ("square",) =
    x^2 f64 variance state) — the hot path never runs eager full-size ops.

    All reductions are prefix-scan + boundary-gather over the sorted rows
    (gid is nondecreasing): XLA scatters serialize on TPU; the scan path is
    log-depth vector work."""

    @program("kernels.reduce")
    def fn(perm, gid, *flat):
        outs = []
        n = perm.shape[0]
        ones = jnp.ones(perm.shape, dtype=jnp.int64)
        starts = searchsorted(gid, jnp.arange(cap))
        # end of group g = first row with gid > g (side='right'): when
        # num_groups == cap, ends[cap-1] must STOP at the dead-row region
        # (dead rows carry gid >= cap and form their own trailing segments)
        ends = searchsorted(gid, jnp.arange(cap), side="right")
        nonempty = ends > starts
        seg_first = jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), gid[1:] != gid[:-1]])

        sorted_cache: dict = {}

        def sorted_of(idx):
            if idx not in sorted_cache:
                sorted_cache[idx] = flat[idx][perm]
            return sorted_cache[idx]

        # trace-time memo keyed by LOGICAL identity: aggregates sharing a
        # (column, validity, dtype, prep) emit one scan, not one each — the
        # TPU compiler segfaults on dozens of megarow cumsums in one fusion
        _memo: dict = {}

        def seg_sum_raw(x, acc_dtype, key=None):
            mkey = None if key is None else ("raw",) + key
            if mkey is not None and mkey in _memo:
                return _memo[mkey]
            cs = jnp.cumsum(x.astype(acc_dtype))
            hi = cs[jnp.maximum(ends - 1, 0)]
            lo = jnp.where(starts > 0, cs[jnp.maximum(starts - 1, 0)],
                           jnp.zeros((), acc_dtype))
            out = jnp.where(nonempty, hi - lo, jnp.zeros((), acc_dtype))
            if mkey is not None:
                _memo[mkey] = out
            return out

        def seg_sum(x, acc_dtype, ieee: bool, key=None):
            if np.dtype(acc_dtype).kind != "f" or not ieee:
                return seg_sum_raw(x, acc_dtype, key)
            mkey = None if key is None else ("ieee",) + key
            if mkey is not None and mkey in _memo:
                return _memo[mkey]
            # float path: a NaN/Inf anywhere would poison the global prefix
            # sum for every LATER segment; zero them out and restore the
            # IEEE result per segment
            xa = x.astype(acc_dtype)
            finite = jnp.isfinite(xa)
            base = seg_sum_raw(jnp.where(finite, xa, 0.0), acc_dtype)
            has_nan = seg_sum_raw(jnp.isnan(xa).astype(jnp.int32),
                                  jnp.int32) > 0
            has_pos = seg_sum_raw((xa == jnp.inf).astype(jnp.int32),
                                  jnp.int32) > 0
            has_neg = seg_sum_raw((xa == -jnp.inf).astype(jnp.int32),
                                  jnp.int32) > 0
            out = jnp.where(has_pos, jnp.inf, base)
            out = jnp.where(has_neg, -jnp.inf, out)
            out = jnp.where(has_nan | (has_pos & has_neg), jnp.nan, out)
            out = out.astype(acc_dtype)
            if mkey is not None:
                _memo[mkey] = out
            return out

        def seg_minmax(x, is_min: bool):
            op = jnp.minimum if is_min else jnp.maximum

            def comb(a, b):
                fa, va = a
                fb, vb = b
                return (fa | fb, jnp.where(fb, vb, op(va, vb)))

            _, running = jax.lax.associative_scan(comb, (seg_first, x))
            return running[jnp.maximum(ends - 1, 0)]

        def seg_any(valid_idx):
            v = sorted_of(valid_idx)
            return seg_sum_raw(v.astype(jnp.int32), jnp.int32,
                               ("any", valid_idx)) > 0

        for fname, data_idx, valid_idx, dtype_str, distinct, pre in spec:
            dtype = jnp.dtype(dtype_str)
            if fname == "count_star":
                if valid_idx >= 0:  # the live mask of a padded batch
                    c = sorted_of(valid_idx).astype(jnp.int64)
                    outs.append((seg_sum_raw(c, jnp.int64,
                                             ("count", valid_idx)), None))
                else:
                    outs.append((seg_sum_raw(ones, jnp.int64,
                                             ("count", -1)), None))
                continue
            data = sorted_of(data_idx)
            # integer-sourced values can never be NaN/Inf: their float sums
            # skip the IEEE rescue scans entirely
            src_float = np.dtype(data.dtype).kind == "f"
            if pre is not None:
                if pre[0] == "scale":
                    data = data.astype(jnp.float64) / (10.0 ** pre[1])
                elif pre[0] == "square":
                    x64 = data.astype(jnp.float64)
                    data = x64 * x64
            valid = sorted_of(valid_idx) if valid_idx >= 0 else None
            skey = (data_idx, valid_idx, np.dtype(dtype_str).str, pre)
            if distinct:
                # rows sorted by group key only; distinct needs per-(group,
                # value) dedup: mark first occurrence within (gid, valid,
                # value) runs — validity participates so a NULL row whose
                # storage fill collides with a real value stays its own run
                if np.dtype(data.dtype).kind == "f":
                    data = _canon_float(data)  # NaN is ONE distinct value
                if valid is not None:
                    order = jnp.lexsort((data, valid, gid))
                    v2 = valid[order]
                else:
                    order = jnp.lexsort((data, gid))
                    v2 = None
                d2, g2 = data[order], gid[order]
                first = jnp.concatenate(
                    [jnp.ones((1,), jnp.bool_), _neq(d2[1:], d2[:-1]) | (g2[1:] != g2[:-1])]
                )
                if v2 is not None:
                    first = first | jnp.concatenate(
                        [jnp.ones((1,), jnp.bool_), v2[1:] != v2[:-1]])
                keep = first if v2 is None else (first & v2)
                # d2/g2 reorder rows within each segment only: the segment
                # boundary positions (starts/ends) are unchanged
                if fname in ("count", "count_star"):
                    outs.append((seg_sum_raw(keep.astype(jnp.int64),
                                             jnp.int64), None))
                    continue
                if fname == "sum":
                    x = jnp.where(keep, d2, jnp.zeros((), dtype))
                    anyk = seg_sum_raw(keep.astype(jnp.int32), jnp.int32) > 0
                    outs.append((seg_sum(x, dtype, src_float), anyk))
                    continue
                raise NotImplementedError(f"distinct {fname}")
            if fname == "count":
                if valid is None:
                    outs.append((seg_sum_raw(ones, jnp.int64,
                                             ("count", -1)), None))
                else:
                    outs.append((seg_sum_raw(valid.astype(jnp.int64),
                                             jnp.int64,
                                             ("count", valid_idx)), None))
            elif fname == "sum":
                x = data if valid is None else jnp.where(valid, data, jnp.zeros((), data.dtype))
                s = seg_sum(x, dtype, src_float, ("sum",) + skey)
                anyv = None if valid is None else seg_any(valid_idx)
                outs.append((s, anyv))
            elif fname in ("min", "max"):
                sent = _sentinel(fname, data.dtype)
                x = data if valid is None else jnp.where(valid, data, sent)
                r = seg_minmax(x, fname == "min")
                anyv = None if valid is None else seg_any(valid_idx)
                outs.append((r, anyv))
            elif fname == "any_value":
                # gather at each segment's first VALID row: re-sort rows so
                # invalid ones go last within their segment, then take starts
                if valid is None:
                    rows = jnp.minimum(starts, n - 1)
                    outs.append((data[rows], None))
                else:
                    order = jnp.lexsort((~valid, gid))
                    rows = jnp.minimum(starts, n - 1)
                    outs.append((data[order][rows], seg_any(valid_idx)))
            else:
                raise NotImplementedError(f"aggregate {fname}")
        return outs

    return fn


@jit_memo("kernels._finalize_fn")
def _finalize_fn(plan: tuple):
    """One compiled program for aggregation finalization (avg division,
    variance combine, output casts) over the tiny per-group arrays — the
    output columns stay ON DEVICE (the collective exchange path feeds them
    straight into all_to_all) and the host pays zero per-op dispatches.

    plan: per output column, one of
      ("copy", dtype_str|None, has_valid)            passthrough + cast
      ("avg_final", dtype_str, has_valid)            sum/count -> mean
      ("stat_final", fn, dtype_str, has_valid)       (s, sq, n) -> var/stddev
      ("count", None, has_valid)                     cast int64, drop valid
    inputs: flat (data [, valid]) per plan entry's source arity."""

    @program("kernels.finalize")
    def fn(*flat):
        outs = []
        i = 0
        for entry in plan:
            kind = entry[0]
            if kind == "copy":
                _, dtype_str, has_valid = entry
                d = flat[i]
                i += 1
                v = None
                if has_valid:
                    v = flat[i]
                    i += 1
                if dtype_str is not None:
                    d = d.astype(jnp.dtype(dtype_str))
                outs.append((d, v))
            elif kind == "count":
                _, _, has_valid = entry
                d = flat[i]
                i += 1
                if has_valid:
                    i += 1  # counts are never NULL
                outs.append((d.astype(jnp.int64), None))
            elif kind == "avg_final":
                _, dtype_str, has_valid = entry
                s = flat[i]
                i += 1
                sv = None
                if has_valid:
                    sv = flat[i]
                    i += 1
                c = flat[i]
                i += 1
                cnt = jnp.maximum(c, 1)
                vals = s / cnt
                valid = c > 0
                if sv is not None:
                    valid = valid & sv
                outs.append((vals.astype(jnp.dtype(dtype_str)), valid))
            elif kind == "stat_final":
                _, fname, dtype_str, has_valid = entry
                s = flat[i]
                i += 1
                sv = None
                if has_valid:
                    sv = flat[i]
                    i += 1
                q = flat[i]
                i += 1
                c = flat[i]
                i += 1
                n = c.astype(jnp.float64)
                safe_n = jnp.maximum(n, 1.0)
                mean = s / safe_n
                m2 = jnp.maximum(q - safe_n * mean * mean, 0.0)
                if fname in ("var_pop", "stddev_pop"):
                    var = m2 / safe_n
                    valid = n > 0
                else:  # sample variance: NULL for fewer than 2 values
                    var = m2 / jnp.maximum(n - 1.0, 1.0)
                    valid = n > 1
                vals = jnp.sqrt(var) if fname.startswith("stddev") else var
                if sv is not None:
                    valid = valid & sv
                outs.append((vals.astype(jnp.dtype(dtype_str)), valid))
            else:
                raise NotImplementedError(kind)
        return outs

    return fn


def finalize_groups(plan: Sequence[tuple], arrays: Sequence):
    """Run the cached finalize program; ``arrays`` is the flat (device or
    host) input list matching ``plan``."""
    return _finalize_fn(tuple(plan))(*[jnp.asarray(a) for a in arrays])


_FAILED_REDUCE_SPECS: set = set()


def _pallas_enabled() -> bool:
    """TRINO_TPU_PALLAS, read per call: compiled kernels only beat XLA on
    real TPU lanes; interpret mode is for tests (TRINO_TPU_PALLAS=force)."""
    mode = os.environ.get("TRINO_TPU_PALLAS", "1")
    return mode != "0" and (mode == "force"
                            or jax.default_backend() == "tpu")


def _pallas_f32_sum(perm, gid, cap: int, data, valid):
    """REAL-sum fast path: blockwise VMEM accumulation instead of XLA's
    scatter segment_sum (ops/pallas_kernels.py).  Returns (sums, anyvalid);
    a kernel failure fails the query."""
    from ..ops import pallas_kernels as PK

    interpret = jax.default_backend() != "tpu"
    vals = jnp.asarray(data)[perm]
    lv = None if valid is None else jnp.asarray(valid)[perm]
    s = PK.masked_segment_sum_f32(vals, gid, lv, cap, interpret=interpret)
    anyv = None
    if valid is not None:  # the validity bit is one cheap segment_max
        anyv = jax.ops.segment_max(lv, gid, cap)
    return s, anyv


def grouped_reduce(
    perm,
    gid,
    num_groups: int,
    aggs: Sequence[tuple],
) -> list[tuple[np.ndarray, Optional[np.ndarray]]]:
    """aggs: [(fn, data|None, valid|None, out_dtype, distinct[, pre]), ...].

    Returns per-agg (values, valid|None) arrays of length num_groups.
    Input arrays are DEDUPED by object identity before entering the jitted
    program, so aggregates over the same column / live mask share scans."""
    cap = bucket(num_groups)
    results: list = [None] * len(aggs)
    spec = []
    flat: list = []
    flat_ids: dict = {}
    xla_slots = []

    def idx_of(arr) -> int:
        if arr is None:
            return -1
        k = id(arr)
        if k not in flat_ids:
            flat_ids[k] = len(flat)
            flat.append(jnp.asarray(arr))
        return flat_ids[k]

    for idx, entry in enumerate(aggs):
        fn, data, valid, dtype, distinct = entry[:5]
        pre = entry[5] if len(entry) > 5 else None
        if (fn == "sum" and data is not None and not distinct and pre is None
                and np.dtype(dtype) == np.float32 and cap <= 64
                and _pallas_enabled()):
            sums, anyv = _pallas_f32_sum(jnp.asarray(perm), jnp.asarray(gid),
                                         cap, data, valid)
            results[idx] = (sums[:num_groups],
                            None if anyv is None else anyv[:num_groups])
            continue
        if fn == "count_star" or data is None:
            spec.append(("count_star", -1, idx_of(valid), "int64", False,
                         None))
            xla_slots.append(idx)
            continue
        spec.append((fn, idx_of(data), idx_of(valid), np.dtype(dtype).str,
                     bool(distinct), pre))
        xla_slots.append(idx)

    # the TPU compiler segfaults on programs mixing >=2 int64 prefix sums
    # (x64 lanes are emulated) with a float64 prefix sum: split the specs
    # into an integer-accumulator program and a float program
    def _int_class(s) -> bool:
        fn = s[0]
        if fn in ("count", "count_star"):
            return True
        return fn == "sum" and np.dtype(s[3]).kind in "iu"

    def _run(members) -> None:
        """Run one compiled program for ``members``; on a TPU compiler
        crash (flaky SIGSEGV on large mixed-dtype scan fusions) split the
        program in half and retry — smaller programs always compile.
        Failed (spec, cap) combos are remembered: the broken compile is
        NOT cached by jax, so without the memo every warm run would re-pay
        the multi-second failing compile before splitting."""
        # remap flat indices to the subset actually used by this program
        sub_flat: list = []
        remap: dict = {}

        def sub_idx(fi: int) -> int:
            if fi < 0:
                return -1
            if fi not in remap:
                remap[fi] = len(sub_flat)
                sub_flat.append(flat[fi])
            return remap[fi]

        sub_spec = tuple(
            (s[0], sub_idx(s[1]), sub_idx(s[2]), s[3], s[4], s[5])
            for _, s in members)

        def split() -> None:
            mid = len(members) // 2
            _run(members[:mid])
            _run(members[mid:])

        if (sub_spec, cap) in _FAILED_REDUCE_SPECS:
            split()
            return
        try:
            outs = _reduce_fn(sub_spec, cap)(
                jnp.asarray(perm), jnp.asarray(gid), *sub_flat)
        except jax.errors.JaxRuntimeError:
            # a compile the runtime refused (seen on large mixed-dtype scan
            # fusions): same reduction, smaller programs — counted in
            # _FAILED_REDUCE_SPECS, which chip_smoke.py reports; genuine
            # trace errors (NotImplementedError, dtype bugs) re-raise
            # immediately
            if len(members) == 1:
                raise
            _FAILED_REDUCE_SPECS.add((sub_spec, cap))
            split()
            return
        for (spec_i, _), (data, valid) in zip(members, outs):
            idx = xla_slots[spec_i]
            results[idx] = (data[:num_groups],
                            None if valid is None else valid[:num_groups])

    # the TPU compiler is unreliable on programs mixing several int64
    # prefix sums (x64 lanes are emulated) with float64 prefix sums: run
    # an integer-accumulator program and a float program, each with the
    # split-retry ladder above
    int_members = [(i, s) for i, s in enumerate(spec) if _int_class(s)]
    flt_members = [(i, s) for i, s in enumerate(spec) if not _int_class(s)]
    if int_members:
        _run(int_members)
    if flt_members:
        _run(flt_members)
    return results


@jit_memo("kernels._keys_out_fn")
def _keys_out_fn(has_valid: tuple, cap: int):
    @program("kernels.keys_out")
    def fn(perm, gid, *flat):
        # gid is sorted: group g's representative is its FIRST sorted row —
        # a binary-search gather, not a scatter (scatters serialize on TPU)
        n = perm.shape[0]
        starts = jnp.minimum(searchsorted(gid, jnp.arange(cap)), n - 1)
        rows = perm[starts]
        out = []
        i = 0
        for hv in has_valid:
            d = flat[i][rows]
            i += 1
            if hv:
                v = flat[i][rows]
                i += 1
                out.append((d, v))
            else:
                out.append((d, None))
        return out

    return fn


def group_keys_out(perm, gid, num_groups: int, keys: Sequence[tuple]):
    """Materialize one representative key row per group (device arrays out;
    dead rows carry gids >= cap-scatter range and are dropped).  One
    compiled program per (key structure, cap) — no eager scatters."""
    cap = bucket(num_groups)
    has_valid = tuple(v is not None for _, v in keys)
    flat = []
    for data, valid in keys:
        flat.append(jnp.asarray(data))
        if valid is not None:
            flat.append(jnp.asarray(valid))
    outs = _keys_out_fn(has_valid, cap)(
        jnp.asarray(perm), jnp.asarray(gid), *flat)
    return [(d[:num_groups], None if v is None else v[:num_groups])
            for d, v in outs]


# ---------------------------------------------------------------------------
# sort


_HOST_SORT_MAX = 1 << 16  # below this, device dispatch latency dominates


def _sort_columns(keys: Sequence[tuple], xp):
    """Build lexsort columns (shared by host/device paths); ``xp`` is numpy
    or jax.numpy."""
    sort_cols = []
    for data, valid, ascending, nulls_first in reversed(list(keys)):
        d = xp.asarray(data)
        kind = np.dtype(d.dtype).kind
        if not ascending:
            if kind == "b":
                d = ~d
            elif kind == "f":
                d = -d.astype(xp.float64)
            else:
                # bitwise NOT is a bijective order reversal; unary minus maps
                # INT64_MIN to itself under two's-complement wraparound
                d = ~d.astype(xp.int64)
        if valid is not None:
            # canonicalize NULL rows' payload FIRST (before NaN ranking):
            # two NULLs must tie exactly on every derived column, or their
            # garbage data would decide the less-significant keys
            v = xp.asarray(valid)
            d = xp.where(v, d, xp.zeros((), d.dtype))
        nan_rank = None
        if kind == "f":
            # NaN sorts largest (Trino convention) via its own rank column —
            # mapping NaN into the value domain (+/-inf) would tie with real
            # infinities; the rank is more significant than the value
            nan = xp.isnan(d)
            nan_rank = xp.where(nan, 1 if ascending else 0,
                                0 if ascending else 1)
            d = xp.where(nan, xp.zeros((), d.dtype), d)
        sort_cols.append(d)
        if nan_rank is not None:
            sort_cols.append(nan_rank)
        if valid is not None:
            # secondary column is sorted after; null rank must be primary
            null_rank = xp.where(v, 1, 0) if nulls_first else xp.where(v, 0, 1)
            sort_cols.append(null_rank)
    return sort_cols


@jit_memo("kernels._device_sort_fn")
def _device_sort_fn(num_keys: int, key_meta: tuple, col_has_valid: tuple,
                    has_live: bool, out_n: Optional[int]):
    """One jitted program: lexsort + gather every payload column (+ live).
    ``key_meta``: (has_valid, ascending, nulls_first) per key, major->minor.
    Dead rows sort last regardless of key values (the live rank is the most
    significant sort column), so a ``live``-masked batch stays valid after
    sorting and ``out_n`` (top-N) keeps the best live rows."""

    @program("kernels.device_sort")
    def fn(*flat):
        i = 0
        keys = []
        for hv, asc, nf in key_meta:
            d = flat[i]
            i += 1
            v = None
            if hv:
                v = flat[i]
                i += 1
            keys.append((d, v, asc, nf))
        cols = []
        for hv in col_has_valid:
            d = flat[i]
            i += 1
            v = None
            if hv:
                v = flat[i]
                i += 1
            cols.append((d, v))
        live = flat[i] if has_live else None
        sort_cols = _sort_columns(keys, jnp)
        if live is not None:
            sort_cols.append(~live)  # most significant: dead rows last
        perm = jnp.lexsort(tuple(sort_cols))
        if out_n is not None:
            perm = perm[:out_n]
        outs = [(d[perm], None if v is None else v[perm]) for d, v in cols]
        return outs, (None if live is None else live[perm])

    return fn


def device_sort(keys: Sequence[tuple], cols: Sequence[tuple], live,
                out_n: Optional[int] = None):
    """keys: [(data, valid|None, ascending, nulls_first), ...] major->minor;
    cols: [(data, valid|None), ...] payload.  Returns (sorted cols, sorted
    live) — all device, zero host syncs."""
    key_meta = tuple((v is not None, bool(a), bool(nf))
                     for _, v, a, nf in keys)
    col_has_valid = tuple(v is not None for _, v in cols)
    flat: list = []
    for d, v, _, _ in keys:
        flat.append(jnp.asarray(d))
        if v is not None:
            flat.append(jnp.asarray(v))
    for d, v in cols:
        flat.append(jnp.asarray(d))
        if v is not None:
            flat.append(jnp.asarray(v))
    if live is not None:
        flat.append(jnp.asarray(live))
    return _device_sort_fn(len(keys), key_meta, col_has_valid,
                           live is not None, out_n)(*flat)


def sort_perm(keys: Sequence[tuple]) -> np.ndarray:
    """keys: [(data, valid|None, ascending, nulls_first), ...] in major-to-
    minor significance order.  Returns the stable sorting permutation.

    Large/device-resident inputs run as one ``jnp.lexsort`` (XLA variadic
    sort on the chip).  Small host-resident inputs (the common post-
    aggregation final sort: a handful of rows) run ``np.lexsort`` on host —
    ten tiny columns are not worth an upload, a compiled sort program and a
    download."""
    host = keys and all(
        isinstance(k[0], np.ndarray)
        and (k[1] is None or isinstance(k[1], np.ndarray))
        for k in keys) and keys[0][0].shape[0] <= _HOST_SORT_MAX
    if host:
        return np.lexsort(tuple(_sort_columns(keys, np)))
    perm = jnp.lexsort(tuple(_sort_columns(keys, jnp)))
    return np.asarray(perm)


# ---------------------------------------------------------------------------
# key hashing (the join itself lives in exec/join_exec.py)

_M1 = np.uint64(0xBF58476D1CE4E5B9)
_M2 = np.uint64(0x94D049BB133111EB)


def _mix64(h):
    h = (h ^ (h >> 30)) * jnp.uint64(_M1)
    h = (h ^ (h >> 27)) * jnp.uint64(_M2)
    return h ^ (h >> 31)


def _f64_hash_word(a):
    """Full-entropy uint64 hash word for a canonical float64 column, built
    arithmetically — the TPU x64 rewrite cannot compile any 64-bit bitcast
    (f64->u64, f64->2xu32 and frexp all fail).  The value is range-reduced
    into an f32-friendly window by a log2-derived class, then split into
    three float32 words whose cascade captures the whole 53-bit significand
    (24*3 > 53), so equal doubles hash equal and distinct doubles collide
    with negligible probability across the full f64 range."""
    fin = jnp.isfinite(a)
    mag = jnp.abs(a)
    safe_mag = jnp.where(mag > 0, mag, 1.0)
    cls = jnp.clip(jnp.floor(jnp.log2(safe_mag) / 120.0), -9.0, 9.0)
    s = 2.0 ** (-60.0 * cls)  # applied twice; 2**(-120*cls) would overflow
    scaled = jnp.where(fin, a * s * s, 0.0)
    w1 = scaled.astype(jnp.float32)
    r1 = scaled - w1.astype(jnp.float64)
    w2 = r1.astype(jnp.float32)
    r2 = r1 - w2.astype(jnp.float64)
    w3 = r2.astype(jnp.float32)
    tag = jnp.where(jnp.isnan(a), 3, jnp.where(a == jnp.inf, 1,
                    jnp.where(a == -jnp.inf, 2, 0)))
    meta = (cls.astype(jnp.int32) + 16) | (tag.astype(jnp.int32) << 8)

    def u32(w):
        return jax.lax.bitcast_convert_type(w, jnp.uint32).astype(jnp.uint64)

    lo = u32(w1) | (u32(w2) << 32)
    hi = u32(w3) | (meta.astype(jnp.uint32).astype(jnp.uint64) << 32)
    return _mix64(lo) ^ hi


def hash_combine(datas: Sequence) -> jnp.ndarray:
    """Combine n key columns into one uint64 hash lane (splitmix64 mix).

    Used for candidate equality (verified exactly afterwards) and for
    partition assignment (no verification needed)."""
    h = jnp.zeros(jnp.asarray(datas[0]).shape, dtype=jnp.uint64)
    for d in datas:
        x = jnp.asarray(d)
        if x.dtype == jnp.bool_:
            x = x.astype(jnp.uint64)
        elif np.dtype(x.dtype).kind == "f":
            x = _f64_hash_word(_canon_float(x.astype(jnp.float64)))
        else:
            x = x.astype(jnp.int64).astype(jnp.uint64)
        h = _mix64(h ^ (x + jnp.uint64(0x9E3779B97F4A7C15)))
    return h


# ---------------------------------------------------------------------------
# partitioning (shuffle producer — PagePartitioner.partitionPage equivalent)


@jit_memo("kernels._domain_fn")
def _domain_fn(has_valid: bool, has_live: bool, dict_len: int):
    """Build-key domain for dynamic filtering, all on device: returns
    (valid_count, non-NaN count, min, max, presence-per-dictionary-code).
    Presence uses sort + binary search, not scatter (scatters serialize)."""

    @program("kernels.domain")
    def fn(data, *rest):
        i = 0
        valid = rest[i] if has_valid else None
        i += 1 if has_valid else 0
        live = rest[i] if has_live else None
        eligible = None
        if valid is not None:
            eligible = valid
        if live is not None:
            eligible = live if eligible is None else (eligible & live)
        kind = np.dtype(data.dtype).kind
        if eligible is None:
            cnt = jnp.asarray(data.shape[0], jnp.int64)
        else:
            cnt = jnp.sum(eligible)
        if kind == "f":
            nan = jnp.isnan(data)
            ok = ~nan if eligible is None else (eligible & ~nan)
            cnt_nonnan = jnp.sum(ok)
        else:
            ok = eligible
            cnt_nonnan = cnt
        big = _sentinel("min", data.dtype)
        small = _sentinel("max", data.dtype)
        vmin = jnp.min(data if ok is None else jnp.where(ok, data, big))
        vmax = jnp.max(data if ok is None else jnp.where(ok, data, small))
        if dict_len:
            sent = jnp.asarray(dict_len, data.dtype)
            codes = jnp.sort(data if eligible is None
                             else jnp.where(eligible, data, sent))
            r = jnp.arange(dict_len, dtype=data.dtype)
            presence = (jnp.searchsorted(codes, r, side="right")
                        > jnp.searchsorted(codes, r, side="left"))
        else:
            presence = jnp.zeros((0,), jnp.bool_)
        return cnt, cnt_nonnan, vmin, vmax, presence

    return fn


def _device_domain(data, valid, live, dict_len: int):
    flat = [jnp.asarray(data)]
    if valid is not None:
        flat.append(jnp.asarray(valid))
    if live is not None:
        flat.append(jnp.asarray(live))
    return _domain_fn(valid is not None, live is not None, dict_len)(*flat)


@jit_memo("kernels._compact_fn")
def _compact_fn(n_cols: int, valid_flags: tuple, has_live_out: bool, cap: int):
    """Gather live rows to the front and slice to ``cap`` lanes (one stable
    bool sort + gathers, all on device).  The sort is O(lanes log lanes) and
    each gather costs about what a pass over the lanes does, so only callers
    whose next step is at least a sort of the same lanes come here:
    operators._maybe_compact_device, for SortOperator and the aggregation's
    sorting paths.  An O(lanes) reduction is cheaper over the dead lanes
    than this program is (190-230 ms for 2^25 lanes on a v5e, PERF.md)."""

    @program("kernels.compact")
    def fn(live, *flat):
        order = jnp.argsort(~live, stable=True)[:cap]
        out = [x[order] for x in flat]
        if has_live_out:
            # live rows came first: the mask is a prefix, no gather of
            # ``live`` (5.3 ms for 2^18 of 2^19 lanes on a v5e: PERF.md 6)
            out.append(jnp.arange(cap, dtype=jnp.int32)
                       < jnp.sum(live, dtype=jnp.int32))
        return tuple(out)

    return fn


def compact_device_batch(batch, live_count: int):
    """Compact a live-masked device batch down to bucket(live_count) lanes.
    Dead lanes beyond the bucket are dropped; the (padded) tail keeps a live
    mask.  Called by operators._maybe_compact_device alone, on behalf of
    blocking operators about to do O(lanes log lanes) work (SortOperator,
    the aggregation's group_ids_codes / group_ids_auto / global-DISTINCT
    paths): a filter or join output riding a fat static shape with few
    survivors would otherwise drag its dead lanes through that sort.  Not
    for the masked aggregation, which reads dead lanes for less."""
    from ..spi.batch import Column, ColumnBatch

    cap = bucket(max(live_count, 1))
    if cap >= batch.num_rows:
        return batch
    flat = []
    valid_flags = []
    for c in batch.columns:
        flat.append(jnp.asarray(c.data))
        valid_flags.append(c.valid is not None)
        if c.valid is not None:
            flat.append(jnp.asarray(c.valid))
    outs = _compact_fn(batch.num_columns, tuple(valid_flags), True, cap)(
        jnp.asarray(batch.live), *flat)
    cols = []
    i = 0
    for c, hv in zip(batch.columns, valid_flags):
        d = outs[i]
        i += 1
        v = None
        if hv:
            v = outs[i]
            i += 1
        cols.append(Column(c.type, d, v, c.dictionary))
    return ColumnBatch(batch.names, cols, outs[-1])


def _routing_hash(keys: Sequence[tuple]):
    """Row -> uint64 routing hash, NULL keys forced to 0 (traceable)."""
    h = hash_combine([jnp.asarray(d) for d, _ in keys])
    null_mask = None
    for _, v in keys:
        if v is not None:
            nm = ~jnp.asarray(v)
            null_mask = nm if null_mask is None else (null_mask | nm)
    if null_mask is not None:
        h = jnp.where(null_mask, jnp.uint64(0), h)
    return h


def partition_key_hashes(keys: Sequence[tuple]) -> np.ndarray:
    """Row -> uint64 key hash with NULL keys forced to 0.  The single
    routing hash shared by the shuffle sink and the adaptive routers: both
    must agree bit-for-bit on where a key lands (``h % n`` with null->0
    matches the legacy null->partition-0 placement for any n)."""
    return np.asarray(_routing_hash(keys))


def partition_assignments(keys: Sequence[tuple], num_partitions: int) -> np.ndarray:
    """Row -> partition id by key hash (NULL keys -> partition 0)."""
    h = partition_key_hashes(keys)
    return (h % np.uint64(num_partitions)).astype(np.int32)


@jit_memo("kernels._partition_masks_fn")
def _partition_masks_fn(has_valid: tuple, has_table: tuple, has_live: bool,
                        num_partitions: int):
    """The shuffle sink's routing for a page that stays on the device: the
    host path's hash (``_routing_hash``: a row lands in the same partition
    whichever path routed it), one live mask a partition over the page's own
    lanes, and the partitions' live counts in one vector -- no row moves.
    A dictionary key hashes by VALUE: its codes index ``table``, the
    dictionary's value hashes (padded to a bucket by the caller)."""

    @program("kernels.partition_masks")
    def fn(*flat):
        it = iter(flat)
        keys = []
        for hv, ht in zip(has_valid, has_table):
            d = next(it)
            v = next(it) if hv else None
            if ht:
                d = next(it)[d]
            keys.append((d, v))
        live = next(it) if has_live else None
        parts = (_routing_hash(keys)
                 % jnp.uint64(num_partitions)).astype(jnp.int32)
        masks = tuple(
            (parts == p) if live is None else (live & (parts == p))
            for p in range(num_partitions))
        counts = jnp.stack([jnp.sum(m, dtype=jnp.int32) for m in masks])
        return masks, counts

    return fn


def partition_masks(keys: Sequence[tuple], live, num_partitions: int):
    """``keys``: (data, valid, value-hash table or None) per routing key.
    Returns (one bool[lanes] mask a partition, int32[partitions] counts),
    both on the device."""
    flat = []
    for d, v, table in keys:
        flat.append(d)
        if v is not None:
            flat.append(v)
        if table is not None:
            flat.append(table)
    if live is not None:
        flat.append(live)
    return _partition_masks_fn(
        tuple(v is not None for _, v, _ in keys),
        tuple(t is not None for _, _, t in keys),
        live is not None, num_partitions)(*flat)


@program("kernels.live_count")
def live_count(live):
    """A page's live rows, as a one-element device vector (the shuffle
    sink's count; a vector like partition_masks' counts)."""
    return jnp.sum(live, dtype=jnp.int32).reshape(1)
