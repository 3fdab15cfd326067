"""Jitted window-function kernels.

The TPU-native replacement for the reference's WindowOperator + per-function
window frame machinery (reference: operator/WindowOperator.java:69,
operator/window/FramedWindowFunction.java, operator/PagesIndex.java).  Where
the JVM design walks rows of a sorted PagesIndex per partition, this lowers
the WHOLE window computation — lexsort, partition/peer boundary detection,
every window function, scatter back to input order — into ONE jitted XLA
program per (window spec, shape bucket):

- partition / peer boundaries come from vectorized neighbor compares on the
  sorted keys (NaN-aware, validity-aware — same semantics as the grouping
  kernel in exec/kernels.py);
- ranking functions are index arithmetic over boundary prefix scans
  (``lax.cummax`` / ``cumsum``);
- framed aggregates are prefix-sum differences (sum/count/avg) or segmented
  scans (min/max) — O(n) work, no per-partition loop;
- navigation functions (lag/lead/first/last/nth_value) are clamped gathers.

Everything is fixed-shape; the only host interaction is the registry-memo
(caching/executable_cache.py) compile lookup.
"""

from __future__ import annotations

from typing import Optional, Sequence

from ..caching.executable_cache import jit_memo, program

import jax
import jax.numpy as jnp
import numpy as np

from .kernels import _canon_float, _neq

__all__ = ["compute_windows", "WINDOW_RANK_FNS", "WINDOW_VALUE_FNS",
           "WINDOW_AGG_FNS"]

WINDOW_RANK_FNS = {"row_number", "rank", "dense_rank", "percent_rank",
                   "cume_dist", "ntile"}
WINDOW_VALUE_FNS = {"lag", "lead", "first_value", "last_value", "nth_value"}
WINDOW_AGG_FNS = {"count", "count_star", "sum", "avg", "min", "max"}


def _sort_transform(d, ascending: bool, valid, nulls_first: bool):
    """Produce lexsort columns for one key, replicating kernels.sort_perm's
    rules (desc flip, NaN rank, NULL rank) inside a traced context.  Returns
    minor-to-major list fragments (value first, then rank columns)."""
    cols = []
    kind = np.dtype(d.dtype).kind
    if not ascending:
        if kind == "b":
            d = ~d
        elif kind == "f":
            d = -d.astype(jnp.float64)
        else:
            d = ~d.astype(jnp.int64)
    if kind == "f":
        nan = jnp.isnan(d)
        nan_rank = jnp.where(nan, 1 if ascending else 0, 0 if ascending else 1)
        d = jnp.where(nan, jnp.zeros((), d.dtype), d)
        cols.append(d)
        cols.append(nan_rank)
    else:
        cols.append(d)
    if valid is not None:
        null_rank = (jnp.where(valid, 1, 0) if nulls_first
                     else jnp.where(valid, 0, 1))
        cols.append(null_rank)
    return cols


def _boundary(datas, valids, n):
    """True where sorted row i starts a new run of the given key columns."""
    new = None
    for d, v in zip(datas, valids):
        if np.dtype(d.dtype).kind == "f":
            d = _canon_float(d)
        if v is not None:
            d = jnp.where(v, d, jnp.zeros((), d.dtype))
        diff = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                                _neq(d[1:], d[:-1])])
        if v is not None:
            diff = diff | jnp.concatenate(
                [jnp.ones((1,), jnp.bool_), v[1:] != v[:-1]])
        new = diff if new is None else (new | diff)
    if new is None:
        return jnp.zeros((n,), jnp.bool_).at[0].set(True)
    return new


def _seg_scan(op, x, starts):
    """Segmented inclusive scan: ``op`` accumulates within runs delimited by
    ``starts`` (True = first row of a segment)."""

    def combine(a, b):
        va, fa = a
        vb, fb = b
        return jnp.where(fb, vb, op(va, vb)), fa | fb

    v, _ = jax.lax.associative_scan(combine, (x, starts))
    return v


def _suffix_min_index(mask):
    """For each i: the smallest j >= i with mask[j] (n if none)."""
    n = mask.shape[0]
    idx = jnp.where(mask, jnp.arange(n), n)
    return jnp.flip(jax.lax.cummin(jnp.flip(idx)))


def _prefix_upto(x, part_start_idx):
    """Partition-relative inclusive prefix sum evaluated at arbitrary sorted
    index j: returns fn(j) usable with out-of-segment clamping."""
    cs = jnp.cumsum(x)
    zero = jnp.zeros((1,), cs.dtype)
    cs0 = jnp.concatenate([zero, cs])  # cs0[j+1] = sum x[0..j]

    def upto(j, start):
        """sum of x[start..j]; j < start -> 0 (empty)."""
        j = jnp.maximum(j, start - 1)
        return cs0[j + 1] - cs0[start]

    return upto


@jit_memo("window._window_program")
def _window_program(
    n_part: int,
    part_valid: tuple[bool, ...],
    order_spec: tuple[tuple[bool, bool, bool], ...],  # (has_valid, asc, nf)
    fn_spec: tuple,  # (fn, n_args, arg_valid tuple, offset, frame, dtype_str)
):
    @program("window.window")
    def fn(shape_carrier, *flat):
        i = 0
        part, pvalid = [], []
        for k in range(n_part):
            part.append(flat[i]); i += 1
            if part_valid[k]:
                pvalid.append(flat[i]); i += 1
            else:
                pvalid.append(None)
        order, ovalid = [], []
        for (hv, _asc, _nf) in order_spec:
            order.append(flat[i]); i += 1
            if hv:
                ovalid.append(flat[i]); i += 1
            else:
                ovalid.append(None)
        fn_args = []
        for (_fn, n_args, arg_valid, _off, _frame, _dt) in fn_spec:
            args = []
            for a in range(n_args):
                d = flat[i]; i += 1
                v = None
                if arg_valid[a]:
                    v = flat[i]; i += 1
                args.append((d, v))
            fn_args.append(args)

        n = shape_carrier.shape[0]
        arange = jnp.arange(n)

        # ---- sort: partition keys (major) then order keys ----------------
        lex = []  # built minor-to-major then reversed
        for (hv, asc, nf), d, v in zip(reversed(order_spec),
                                       list(reversed(order)),
                                       list(reversed(ovalid))):
            frag = _sort_transform(d, asc, v, nf)
            lex.extend(frag)
        for d, v in zip(reversed(part), reversed(pvalid)):
            frag = _sort_transform(d, True, v, False)
            lex.extend(frag)
        if lex:
            perm = jnp.lexsort(tuple(lex))
        else:
            perm = arange

        part_s = [d[perm] for d in part]
        pval_s = [None if v is None else v[perm] for v in pvalid]
        ord_s = [d[perm] for d in order]
        oval_s = [None if v is None else v[perm] for v in ovalid]

        # ---- boundaries ---------------------------------------------------
        part_start = _boundary(part_s, pval_s, n)
        if order:
            peer_start = part_start | _boundary(ord_s, oval_s, n)
        else:
            peer_start = part_start
        part_start_idx = jax.lax.cummax(jnp.where(part_start, arange, 0))
        peer_start_idx = jax.lax.cummax(jnp.where(peer_start, arange, 0))
        part_last = jnp.concatenate([part_start[1:], jnp.ones((1,), jnp.bool_)])
        peer_last = jnp.concatenate([peer_start[1:], jnp.ones((1,), jnp.bool_)])
        part_end_idx = _suffix_min_index(part_last)
        peer_end_idx = _suffix_min_index(peer_last)
        part_rows = part_end_idx - part_start_idx + 1

        outs = []
        for (fn, _n_args, _argv, offset, frame, dtype_str), args in zip(
                fn_spec, fn_args):
            dtype = jnp.dtype(dtype_str)
            x, xv = (args[0] if args else (None, None))
            xs = None if x is None else x[perm]
            xvs = (jnp.ones((n,), jnp.bool_) if (x is None or xv is None)
                   else xv[perm])

            if fn == "row_number":
                res = (arange - part_start_idx + 1).astype(dtype)
                val = jnp.ones((n,), jnp.bool_)
            elif fn == "rank":
                res = (peer_start_idx - part_start_idx + 1).astype(dtype)
                val = jnp.ones((n,), jnp.bool_)
            elif fn == "dense_rank":
                cs = jnp.cumsum(peer_start.astype(jnp.int64))
                res = (cs - cs[part_start_idx] + 1).astype(dtype)
                val = jnp.ones((n,), jnp.bool_)
            elif fn == "percent_rank":
                rank = peer_start_idx - part_start_idx + 1
                denom = jnp.maximum(part_rows - 1, 1)
                res = jnp.where(part_rows == 1, 0.0,
                                (rank - 1).astype(jnp.float64)
                                / denom.astype(jnp.float64))
                val = jnp.ones((n,), jnp.bool_)
            elif fn == "cume_dist":
                res = ((peer_end_idx - part_start_idx + 1).astype(jnp.float64)
                       / part_rows.astype(jnp.float64))
                val = jnp.ones((n,), jnp.bool_)
            elif fn == "ntile":
                tiles = offset
                rn0 = arange - part_start_idx  # 0-based row number
                size = part_rows // tiles
                rem = part_rows % tiles
                big = rem * (size + 1)
                in_big = rn0 < big
                safe_size = jnp.maximum(size, 1)
                res = jnp.where(
                    in_big,
                    rn0 // jnp.maximum(size + 1, 1),
                    rem + (rn0 - big) // safe_size,
                ) + 1
                # more partitions than rows: every row its own tile
                res = jnp.where(size == 0, rn0 + 1, res).astype(dtype)
                val = jnp.ones((n,), jnp.bool_)
            elif fn in ("lag", "lead"):
                j = arange - offset if fn == "lag" else arange + offset
                in_part = ((j >= part_start_idx) & (j <= part_end_idx)
                           if fn == "lag"
                           else (j <= part_end_idx) & (j >= part_start_idx))
                jc = jnp.clip(j, 0, n - 1)
                got = xs[jc]
                gotv = xvs[jc]
                if len(args) > 1:  # explicit default (evaluated at current row)
                    dd, dv = args[1]
                    dds = dd[perm]
                    ddv = (jnp.ones((n,), jnp.bool_) if dv is None
                           else dv[perm])
                    res = jnp.where(in_part, got, dds.astype(got.dtype))
                    val = jnp.where(in_part, gotv, ddv)
                else:
                    res = jnp.where(in_part, got, jnp.zeros((), got.dtype))
                    val = in_part & gotv
                res = res.astype(dtype)
            elif fn in ("first_value", "last_value", "nth_value"):
                fs, fe = _frame_indices(
                    frame, arange, part_start_idx, part_end_idx,
                    peer_start_idx, peer_end_idx)
                fs = jnp.maximum(fs, part_start_idx)
                fe = jnp.minimum(fe, part_end_idx)
                nonempty = fs <= fe
                if fn == "first_value":
                    j = fs
                elif fn == "last_value":
                    j = fe
                else:
                    j = fs + (offset - 1)
                    nonempty = nonempty & (j <= fe)
                jc = jnp.clip(j, 0, n - 1)
                res = jnp.where(nonempty, xs[jc], jnp.zeros((), xs.dtype))
                val = nonempty & xvs[jc]
                res = res.astype(dtype)
            else:  # framed aggregate
                fs, fe = _frame_indices(
                    frame, arange, part_start_idx, part_end_idx,
                    peer_start_idx, peer_end_idx)
                fs = jnp.maximum(fs, part_start_idx)
                fe = jnp.minimum(fe, part_end_idx)
                if fn == "count_star":
                    res = jnp.maximum(fe - fs + 1, 0).astype(dtype)
                    val = jnp.ones((n,), jnp.bool_)
                elif fn in ("count", "sum", "avg"):
                    cnt_upto = _prefix_upto(xvs.astype(jnp.int64),
                                            part_start_idx)
                    cnt = cnt_upto(fe, part_start_idx) - cnt_upto(
                        fs - 1, part_start_idx)
                    cnt = jnp.maximum(cnt, 0)  # empty frame
                    if fn == "count":
                        res = cnt.astype(dtype)
                        val = jnp.ones((n,), jnp.bool_)
                    else:
                        acc = jnp.where(xvs, xs, jnp.zeros((), xs.dtype)
                                        ).astype(dtype if fn == "sum"
                                                 else jnp.float64)
                        upto = _prefix_upto(acc, part_start_idx)
                        s = upto(fe, part_start_idx) - upto(fs - 1,
                                                            part_start_idx)
                        if fn == "sum":
                            res = s.astype(dtype)
                        else:
                            res = (s / jnp.maximum(cnt, 1)).astype(dtype)
                        val = cnt > 0
                elif fn in ("min", "max"):
                    # supported frames: start at partition/frame head
                    # (running) or whole partition / through UNBOUNDED
                    # FOLLOWING (reverse running).
                    op = jnp.minimum if fn == "min" else jnp.maximum
                    kind = np.dtype(xs.dtype).kind
                    if kind == "f":
                        sent = jnp.inf if fn == "min" else -jnp.inf
                    elif kind == "b":
                        sent = fn == "min"
                    else:
                        info = jnp.iinfo(xs.dtype)
                        sent = info.max if fn == "min" else info.min
                    acc = jnp.where(xvs, xs, jnp.full((), sent, xs.dtype))
                    run = _seg_scan(op, acc, part_start)
                    rev_run = jnp.flip(_seg_scan(
                        op, jnp.flip(acc), jnp.flip(part_last)))
                    unit, sk, _sv, ek, _ev = frame
                    if sk == "UNBOUNDED_PRECEDING" and ek != "UNBOUNDED_FOLLOWING":
                        res = run[jnp.clip(fe, 0, n - 1)]
                    elif ek == "UNBOUNDED_FOLLOWING" and sk != "UNBOUNDED_PRECEDING":
                        res = rev_run[jnp.clip(fs, 0, n - 1)]
                    elif sk == "UNBOUNDED_PRECEDING":
                        res = run[part_end_idx]
                    else:
                        raise NotImplementedError(
                            f"window {fn} over sliding frame {frame}")
                    cnt_upto = _prefix_upto(xvs.astype(jnp.int64),
                                            part_start_idx)
                    cnt = cnt_upto(fe, part_start_idx) - cnt_upto(
                        fs - 1, part_start_idx)
                    val = cnt > 0
                    res = jnp.where(val, res, jnp.zeros((), res.dtype)
                                    ).astype(dtype)
                else:
                    raise NotImplementedError(f"window function {fn}")

            # scatter back to input row order
            out_d = jnp.zeros((n,), res.dtype).at[perm].set(res)
            out_v = jnp.zeros((n,), jnp.bool_).at[perm].set(val)
            outs.append((out_d, out_v))
        return outs

    return fn


def _frame_indices(frame, arange, part_start_idx, part_end_idx,
                   peer_start_idx, peer_end_idx):
    """(frame_start, frame_end) sorted indices per row (unclamped)."""
    unit, sk, sv, ek, ev = frame
    if unit == "RANGE":
        if sk in ("PRECEDING", "FOLLOWING") or ek in ("PRECEDING", "FOLLOWING"):
            raise NotImplementedError("RANGE frames with numeric offsets")
        cur_s, cur_e = peer_start_idx, peer_end_idx
    else:
        cur_s, cur_e = arange, arange
    if sk == "UNBOUNDED_PRECEDING":
        fs = part_start_idx
    elif sk == "CURRENT":
        fs = cur_s
    elif sk == "PRECEDING":
        fs = arange - sv
    elif sk == "FOLLOWING":
        fs = arange + sv
    else:
        raise NotImplementedError(f"frame start {sk}")
    if ek == "UNBOUNDED_FOLLOWING":
        fe = part_end_idx
    elif ek == "CURRENT":
        fe = cur_e
    elif ek == "FOLLOWING":
        fe = arange + ev
    elif ek == "PRECEDING":
        fe = arange - ev
    else:
        raise NotImplementedError(f"frame end {ek}")
    return fs, fe


def compute_windows(
    partition_keys: Sequence[tuple],  # [(data, valid|None), ...]
    order_keys: Sequence[tuple],  # [(data, valid|None, asc, nulls_first), ...]
    functions: Sequence[dict],
    num_rows: int,
) -> list[tuple[np.ndarray, Optional[np.ndarray]]]:
    """Evaluate window functions over one materialized input.

    ``functions``: per call a dict with keys ``fn``, ``args``
    ([(data, valid|None), ...]), ``offset`` (int; lag/lead/ntile/nth_value
    constant), ``frame`` ((unit, start_kind, start_val, end_kind, end_val)),
    ``dtype`` (output numpy dtype).  Returns per call (data, valid) in the
    ORIGINAL row order (device arrays).
    """
    n_part = len(partition_keys)
    part_valid = tuple(v is not None for _, v in partition_keys)
    order_spec = tuple(
        (v is not None, bool(asc), bool(nf)) for _, v, asc, nf in order_keys)
    fn_spec = []
    flat: list = []
    for d, v in partition_keys:
        flat.append(jnp.asarray(d))
        if v is not None:
            flat.append(jnp.asarray(v))
    for d, v, _asc, _nf in order_keys:
        flat.append(jnp.asarray(d))
        if v is not None:
            flat.append(jnp.asarray(v))
    for f in functions:
        args = f.get("args", [])
        arg_valid = tuple(v is not None for _, v in args)
        fn_spec.append((
            f["fn"], len(args), arg_valid, int(f.get("offset", 1)),
            tuple(f["frame"]), np.dtype(f["dtype"]).str,
        ))
        for d, v in args:
            flat.append(jnp.asarray(d))
            if v is not None:
                flat.append(jnp.asarray(v))
    prog = _window_program(n_part, part_valid, order_spec, tuple(fn_spec))
    return prog(jnp.zeros((num_rows,), jnp.int8), *flat)
