"""Whole-stage GSPMD compilation: one jitted program per TPU-resident stage.

The fragmenter marks REPARTITION seams whose producer is an
``Aggregate(PARTIAL)`` over a Filter/Project chain and whose consumer
FINAL-aggregates that edge (execution/fragmenter.py: ``FusedSeam``).  This
module compiles each marked seam into exactly TWO jitted programs instead
of a per-batch operator chain plus an explicit collective rendezvous:

1. **Accumulate** (one call per input batch, per task): the Filter/Project
   chain, the static grouped partial aggregation, and the merge into a
   cap-slot carried state run as ONE ``jax.jit`` program with the state
   pytree DONATED (the state buffers are exclusively owned, so XLA updates
   them in place).  Batches are padded to power-of-two buckets first, so
   the program retraces O(#buckets), never O(#batches) — the shape-bucket
   compile cache of SURVEY §7.

2. **Seam merge** (one call per stage): the deposited per-task states ride
   a ``shard_map`` over the named mesh — hash-route group slots to owner
   devices, ``jax.lax.all_to_all`` fused inside the program, FINAL combine
   and finalize — subsuming ``collective_exchange._shuffle_program`` for
   fused stages.  In/out specs are both ``P("x")`` on dim 0 (the seam
   PartitionSpec contract recorded on the FusedSeam): producer deposit and
   consumer take agree on sharding, so no resharding happens on the seam.

Overflow contract: the carried state holds ``cap`` group slots per task
(``TRINO_TPU_FUSED_CAP``); if a task sees more distinct groups the device
overflow scalar trips at finish and the runner re-runs the subplan on the
legacy per-operator path (FusedStageOverflow).  The seam merge itself can
never overflow: its capacity is ``n_tasks * cap`` which bounds the distinct
groups that can arrive.

``TRINO_TPU_FUSED_STAGE={auto,1,0}``: 0 restores today's per-operator +
collective-exchange path bit-for-bit (same knob pattern as
TRINO_TPU_HASH_IMPL).
"""

from __future__ import annotations

import os
import threading
import warnings
from dataclasses import dataclass, field
from typing import Optional, Sequence

from ..caching.executable_cache import (jit_memo, program,
                                        register_external)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..exec import kernels as K
from ..exec import syncguard as SG
from ..exec.operators import Operator
from ..exec.stats import FusedStageStats
from ..ops.expr import compile_expression
from ..parallel.static_agg import AggSpec, combine_partials, static_grouped_agg
from ..planner import plan as PL
from ..spi.batch import Column, ColumnBatch
from ..spi.errors import (GENERIC_INTERNAL_ERROR, PAGE_TRANSPORT_TIMEOUT,
                          TrinoError)
from ..spi.types import DOUBLE, DecimalType

__all__ = ["FusedStageExec", "FusedStageOverflow", "FusedStageSinkOperator",
           "FusedStageSourceOperator", "FusedStageSpec", "build_fused_spec",
           "plan_fused_stages", "fused_stage_mode", "fused_cap"]

_AXIS = "x"

# CPU meshes can't honor buffer donation; the fallback is correct (copy),
# the warning is per-call noise on the hot path.
warnings.filterwarnings("ignore", message="Some donated buffers were not usable")


def fused_stage_mode() -> str:
    """TRINO_TPU_FUSED_STAGE: auto (default, fuse eligible seams), 1 (same),
    0 (legacy per-operator + collective-exchange path, bit-for-bit)."""
    v = os.environ.get("TRINO_TPU_FUSED_STAGE", "auto").strip().lower()
    return v if v in ("auto", "1", "0") else "auto"


def fused_cap() -> int:
    """Carried-state group-slot capacity per task (TRINO_TPU_FUSED_CAP)."""
    return int(os.environ.get("TRINO_TPU_FUSED_CAP", "8192"))


class FusedStageOverflow(RuntimeError):
    """A task saw more distinct groups than the fused state cap; the runner
    falls back to the legacy per-operator path for this subplan."""


# ---------------------------------------------------------------------------
# stage spec: what the fragmenter's FusedSeam lowers to


@dataclass(frozen=True)
class _StateSpec:
    """One mergeable state column of the carried aggregation state
    (mirrors HashAggregationOperator._agg_spec + the PARTIAL avg
    expansion of add_exchanges.partial_agg_layout)."""

    fn: str           # sum | count | count_star | min | max
    arg: int          # chain-output channel (-1 for count_star)
    dtype: str        # numpy dtype str of the state lane
    scale: int = 0    # decimal scale folded into the avg sum state
    has_valid: bool = True  # state carries a validity lane


@dataclass
class FusedStageSpec:
    producer_fid: int
    consumer_fid: int
    n_tasks: int
    feed: PL.PlanNode              # runs as the legacy operator pipeline
    chain: tuple                   # Filter|Project nodes, application order
    partial: PL.Aggregate
    final: PL.Aggregate
    nk: int
    cap: int
    state_specs: tuple = ()        # tuple[tuple[_StateSpec, ...], ...]

    @property
    def key_types(self):
        src = self.partial.source.output_types
        return tuple(src[c] for c in self.partial.group_keys)

    @property
    def flat_states(self) -> tuple:
        return tuple(s for group in self.state_specs for s in group)

    def cache_key(self) -> tuple:
        return (self.partial, tuple(self.chain),
                tuple(self.feed.output_types), self.cap)


def _derive_state_specs(partial: PL.Aggregate) -> tuple:
    src_types = partial.source.output_types
    out = []
    for a in partial.aggregates:
        if a.fn == "count" and a.arg < 0:
            out.append((_StateSpec("count_star", -1, "<i8", 0, False),))
        elif a.fn == "avg":
            t = src_types[a.arg]
            scale = t.scale if isinstance(t, DecimalType) else 0
            out.append((_StateSpec("sum", a.arg, "<f8", scale, True),
                        _StateSpec("count", a.arg, "<i8", 0, False)))
        elif a.fn == "sum":
            if a.type == DOUBLE:
                dt = "<f8"
            elif a.type.name == "real":
                dt = "<f4"
            else:
                dt = "<i8"
            out.append((_StateSpec("sum", a.arg, dt, 0, True),))
        elif a.fn == "count":
            out.append((_StateSpec("count", a.arg, "<i8", 0, False),))
        else:  # min | max
            dt = np.dtype(src_types[a.arg].storage_dtype).str
            out.append((_StateSpec(a.fn, a.arg, dt, 0, True),))
    return tuple(out)


def build_fused_spec(producer, consumer, n_tasks: int,
                     cap: int) -> "FusedStageSpec":
    """Lower a fragmenter-marked FusedSeam into the executable spec."""
    from .fragmenter import _walk

    root = producer.root  # Aggregate(PARTIAL), checked by the fragmenter
    chain = []
    node = root.source
    while isinstance(node, (PL.Filter, PL.Project)):
        chain.append(node)
        node = node.source
    chain.reverse()
    final = next(n for n in _walk(consumer.root)
                 if isinstance(n, PL.Aggregate) and n.step == "FINAL"
                 and isinstance(n.source, PL.RemoteSource)
                 and n.source.fragment_id == producer.id)
    spec = FusedStageSpec(
        producer_fid=producer.id, consumer_fid=consumer.id, n_tasks=n_tasks,
        feed=node, chain=tuple(chain), partial=root, final=final,
        nk=len(root.group_keys), cap=cap,
        state_specs=_derive_state_specs(root))
    n_states = len(spec.flat_states)
    assert n_states == len(root.output_types) - spec.nk, \
        "fused state layout disagrees with partial_agg_layout"
    return spec


def plan_fused_stages(fragments, session, task_counts: dict,
                      consumer_tasks: dict) -> dict:
    """Runtime gate over fragmenter-marked seams: returns {producer_fid:
    FusedStageExec} for seams where the mesh exists and producer/consumer
    task counts line up (same conditions as the collective exchange)."""
    if fused_stage_mode() == "0" or not getattr(session, "use_collectives", True):
        return {}
    from .collective_exchange import collectives_available

    by_id = {f.id: f for f in fragments}
    out: dict = {}
    for f in fragments:
        seam = getattr(f, "fused_seam", None)
        if seam is None or not getattr(f, "device_resident", False):
            continue
        tc = task_counts.get(f.id)
        if (tc is None or consumer_tasks.get(f.id) != tc
                or task_counts.get(seam.consumer_fid) != tc
                or not collectives_available(tc)):
            continue
        spec = build_fused_spec(f, by_id[seam.consumer_fid], tc, fused_cap())
        out[f.id] = FusedStageExec(spec)
    return out


# ---------------------------------------------------------------------------
# the accumulate program: chain -> partial agg -> state merge, ONE jit call


_ACCUM_CACHE: dict = {}
_ACCUM_LOCK = threading.Lock()
_ACCUM_STATS = {"hits": 0, "misses": 0, "evictions": 0}
_TRACE_SIGS: set = set()  # (program id, bucket signature) — compile counting


def _accum_cache_stats() -> dict:
    """system.runtime.caches row for the id()-keyed accumulate memo — it
    cannot live in the registry proper (keys are object identities, not
    replayable values) but must show up in the observability plane."""
    with _ACCUM_LOCK:
        return {"tier": "exec", "name": "stage._accumulate_program",
                "entries": len(_ACCUM_CACHE), "bytes": 0,
                "hits": _ACCUM_STATS["hits"],
                "misses": _ACCUM_STATS["misses"],
                "evictions": _ACCUM_STATS["evictions"], "invalidations": 0}


register_external("stage._accumulate_program", _accum_cache_stats)


class _AccumulateProgram:
    """One fused accumulate program: compiled expressions + static agg +
    carried-state combine under a single ``jax.jit`` with the state pytree
    donated.  Cached per (stage spec, feed dictionary identity); jax.jit
    itself buckets retraces by the padded batch shape."""

    def __init__(self, spec: FusedStageSpec, in_types, in_dicts):
        self.spec = spec
        self._compile_chain(in_types, in_dicts)
        self._fn = program("stage.accumulate", self._run,
                           donate_argnums=(0,))
        # one launch for the whole zero pytree (it is immediately donated to
        # the first accumulate call, so every task needs fresh buffers)
        self._init_fn = program("stage.initial_state", self._initial_state)

    def _compile_chain(self, in_types, in_dicts):
        spec = self.spec
        types = list(in_types)
        dicts = list(in_dicts)
        steps = []
        for node in spec.chain:
            if isinstance(node, PL.Filter):
                steps.append(("filter",
                              compile_expression(node.predicate, types, dicts),
                              None))
            else:
                ces = [compile_expression(e, types, dicts)
                       for e in node.expressions]
                steps.append(("project", ces,
                              [t.storage_dtype for t in node.output_types]))
                types = list(node.output_types)
                dicts = [ce.dictionary for ce in ces]
        self.steps = steps
        self.out_types = types
        # chain-output dictionaries: what the carried state's key codes mean
        self.key_dicts = [dicts[c] for c in spec.partial.group_keys]

    def initial_state(self) -> dict:
        return self._init_fn()

    def _initial_state(self) -> dict:
        spec = self.spec
        cap = spec.cap
        kd = tuple(jnp.zeros(cap, t.storage_dtype) for t in spec.key_types)
        kv = tuple(jnp.zeros(cap, jnp.bool_) for _ in spec.key_types)
        sd = tuple(jnp.zeros(cap, np.dtype(s.dtype)) for s in spec.flat_states)
        sv = tuple(jnp.zeros(cap, jnp.bool_) if s.has_valid else None
                   for s in spec.flat_states)
        return {"kd": kd, "kv": kv, "sd": sd, "sv": sv,
                "used": jnp.zeros(cap, jnp.bool_),
                "err": jnp.zeros((), jnp.int32),
                "ovf": jnp.zeros((), jnp.int32)}

    def __call__(self, state, cols, live, batch_remaps, state_remaps):
        return self._fn(state, cols, live, batch_remaps, state_remaps)

    # -- traced body --------------------------------------------------------
    def _run(self, state, cols, live, batch_remaps, state_remaps):
        n = cols[0][0].shape[0]
        cols, live, batch_err = self._apply_chain(cols, live, n)
        return self._agg_merge(state, cols, live, batch_remaps,
                               state_remaps, n, batch_err)

    def _apply_chain(self, cols, live, n):
        from ..ops.expr import (
            expr_condition_mask,
            expr_error_scope,
            reduce_error_lanes,
        )

        # ---- Filter/Project chain (mirrors FilterProjectOperator.run) -----
        with expr_error_scope() as errs:
            for kind, compiled, out_dtypes in self.steps:
                if kind == "filter":
                    with expr_condition_mask(live):
                        data, valid = compiled(cols)
                    mask = data if valid is None else data & valid
                    if getattr(mask, "ndim", 1) == 0:
                        mask = jnp.broadcast_to(mask, (n,))
                    live = live & mask
                else:
                    outs = []
                    with expr_condition_mask(live):
                        for ce, dt in zip(compiled, out_dtypes):
                            d, v = ce(cols)
                            d = jnp.asarray(d)
                            if d.ndim == 0:
                                d = jnp.broadcast_to(d, (n,))
                            d = d.astype(dt)
                            if v is not None:
                                v = jnp.asarray(v)
                                if v.ndim == 0:
                                    v = jnp.broadcast_to(v, (n,))
                            outs.append((d, v))
                    cols = outs
            err = reduce_error_lanes(errs, (n,))
        batch_err = (jnp.zeros((), jnp.int32) if err is None
                     else jnp.max(err).astype(jnp.int32))
        return cols, live, batch_err

    def _agg_merge(self, state, cols, live, batch_remaps, state_remaps,
                   n, batch_err):
        spec = self.spec
        cap = spec.cap
        # ---- partial aggregation of this batch ----------------------------
        keys, kvalids = [], []
        for j, ch in enumerate(spec.partial.group_keys):
            d, v = cols[ch]
            if batch_remaps[j] is not None:  # codes -> merged dict space
                d = batch_remaps[j][d]
            keys.append(d)
            kvalids.append(v if v is not None else jnp.ones(n, jnp.bool_))
        agg_inputs = []
        for ss in spec.flat_states:
            if ss.fn == "count_star":
                agg_inputs.append((AggSpec("count_star", jnp.int64),
                                   None, None))
                continue
            d, v = cols[ss.arg]
            if ss.fn == "sum" and ss.scale:
                d = d.astype(jnp.float64) / (10.0 ** ss.scale)
            agg_inputs.append((AggSpec(ss.fn, np.dtype(ss.dtype)), d, v))
        part = static_grouped_agg(keys, kvalids, agg_inputs, cap,
                                  row_mask=live)

        # ---- merge with the carried state ---------------------------------
        skd = list(state["kd"])
        for j in range(spec.nk):
            if state_remaps[j] is not None:
                skd[j] = state_remaps[j][skd[j]]
        ckd = [jnp.concatenate([skd[j], part.keys[j]])
               for j in range(spec.nk)]
        ckv = [jnp.concatenate([state["kv"][j],
                                part.key_valids[j]
                                if part.key_valids[j] is not None
                                else jnp.ones(cap, jnp.bool_)])
               for j in range(spec.nk)]
        cused = jnp.concatenate([state["used"], part.slot_used])
        partial_inputs = []
        for si, ss in enumerate(spec.flat_states):
            vals = jnp.concatenate([state["sd"][si], part.values[si]])
            if ss.has_valid:
                pv = part.value_valids[si]
                if pv is None:
                    pv = part.slot_used
                valid = jnp.concatenate([state["sv"][si], pv])
            else:
                valid = None
            partial_inputs.append(
                (AggSpec(ss.fn if ss.fn != "count_star" else "count",
                         np.dtype(ss.dtype)), vals, valid))
        merged = combine_partials(ckd, ckv, partial_inputs, cused, cap)

        new_sd, new_sv = [], []
        for si, ss in enumerate(spec.flat_states):
            new_sd.append(merged.values[si])
            if ss.has_valid:
                mv = merged.value_valids[si]
                new_sv.append(mv if mv is not None else merged.slot_used)
            else:
                new_sv.append(None)
        ovf = jnp.maximum(
            state["ovf"],
            jnp.maximum(part.num_groups, merged.num_groups).astype(jnp.int32))
        return {
            "kd": tuple(merged.keys),
            "kv": tuple(v if v is not None else merged.slot_used
                        for v in merged.key_valids),
            "sd": tuple(new_sd),
            "sv": tuple(new_sv),
            "used": merged.slot_used,
            "err": jnp.maximum(state["err"], batch_err),
            "ovf": ovf,
        }


@jit_memo("stage._ingest_program", maxsize=256)
def _ingest_program(n_out: int, miss_valid: tuple, has_live: bool):
    """ONE jitted pad-to-bucket program per pad pattern (jax's own cache
    keys the raw input shapes): pads every column to the power-of-two
    bucket, fills absent valid masks, and extends ``live`` as dead over the
    pad rows — the same semantics as spi.batch.pad_to_bucket plus the
    per-column mask fill, collapsed from ~3x #columns eager dispatches per
    batch into a single launch ahead of the accumulate call."""

    @program("stage.ingest")
    def run(cols, live):
        n_in = cols[0][0].shape[0]
        pad = n_out - n_in
        outs = []
        for (d, v), miss in zip(cols, miss_valid):
            if pad:
                d = jnp.concatenate([d, jnp.zeros(pad, d.dtype)])
            if miss:
                v = jnp.ones(n_out, jnp.bool_)
            elif pad:
                v = jnp.concatenate([v, jnp.zeros(pad, jnp.bool_)])
            outs.append((d, v))
        if not has_live:
            live = jnp.concatenate(
                [jnp.ones(n_in, jnp.bool_), jnp.zeros(pad, jnp.bool_)])
        return tuple(outs), live

    return run


def _accumulate_program(spec: FusedStageSpec, in_types,
                        in_dicts) -> _AccumulateProgram:
    key = (spec.cache_key(), tuple(in_types),
           tuple(id(d) if d is not None else None for d in in_dicts))
    with _ACCUM_LOCK:
        hit = _ACCUM_CACHE.get(key)
        if hit is not None:
            _ACCUM_STATS["hits"] += 1
            return hit[0]
        _ACCUM_STATS["misses"] += 1
        if len(_ACCUM_CACHE) >= 256:
            _ACCUM_CACHE.pop(next(iter(_ACCUM_CACHE)))
            _ACCUM_STATS["evictions"] += 1
    prog = _AccumulateProgram(spec, in_types, in_dicts)
    with _ACCUM_LOCK:
        # dict refs held in the value keep the id()-keyed entries stable
        _ACCUM_CACHE.setdefault(key, (prog, list(in_dicts)))
    return prog


# ---------------------------------------------------------------------------
# the seam merge program: route -> all_to_all -> FINAL combine -> finalize


@jit_memo("stage._merge_program")
def _merge_program(n_dev: int, cap: int, key_dtypes: tuple, dict_flags: tuple,
                   state_sig: tuple, final_sig: tuple, table_buckets: tuple):
    """One jitted shard_map over the stage mesh: remap state key codes into
    the unified dictionaries, hash-route group slots to owner devices
    (VALUE hashes for dictionary keys — same _dict_value_hashes contract as
    the host and collective exchanges), all_to_all every state lane, FINAL
    combine at capacity ``n_dev*cap`` (which can never overflow), and
    finalize the aggregate outputs.  All in/out specs are P(_AXIS) on dim 0
    — the seam PartitionSpec contract."""
    mesh = Mesh(jax.devices()[:n_dev], (_AXIS,))
    nk = len(key_dtypes)
    n_states = len(state_sig)
    fcap = n_dev * cap
    n_dict = sum(dict_flags)

    def local(*flat):
        i = 0
        kds = list(flat[i:i + nk]); i += nk
        kvs = list(flat[i:i + nk]); i += nk
        sds = list(flat[i:i + n_states]); i += n_states
        svs = []
        for fn, dt, has_valid in state_sig:
            if has_valid:
                svs.append(flat[i]); i += 1
            else:
                svs.append(None)
        used = flat[i]; i += 1
        remaps, vhs = {}, {}
        for j in range(nk):
            if dict_flags[j]:
                remaps[j] = flat[i]; i += 1
                vhs[j] = flat[i]; i += 1
        # ---- unify: task-local codes -> merged dictionary space -----------
        for j in remaps:
            kds[j] = remaps[j][kds[j]]
        # ---- destination by key-value hash (NULL keys -> device 0) --------
        route_keys = [vhs[j][kds[j]] if dict_flags[j] else kds[j]
                      for j in range(nk)]
        h = K.hash_combine(route_keys)
        dest = (h % jnp.uint64(n_dev)).astype(jnp.int32)
        null_key = None
        for j in range(nk):
            nkv = ~kvs[j]
            null_key = nkv if null_key is None else (null_key | nkv)
        if null_key is not None:
            dest = jnp.where(null_key, 0, dest)
        lane_live = used[None, :] & (
            dest[None, :] == jnp.arange(n_dev, dtype=jnp.int32)[:, None])

        def shuffle(x):
            lanes = jnp.broadcast_to(x[None, :], (n_dev, cap))
            out = jax.lax.all_to_all(lanes, _AXIS, 0, 0, tiled=False)
            return out.reshape(fcap)

        rkd = [shuffle(k) for k in kds]
        rkv = [shuffle(v) for v in kvs]
        rlive = jax.lax.all_to_all(lane_live, _AXIS, 0, 0,
                                   tiled=False).reshape(fcap)
        partial_inputs = []
        for (fn, dt, has_valid), sd, sv in zip(state_sig, sds, svs):
            partial_inputs.append(
                (AggSpec(fn, np.dtype(dt)), shuffle(sd),
                 shuffle(sv) if sv is not None else None))
        fin = combine_partials(rkd, rkv, partial_inputs, rlive, fcap)

        # ---- FINAL finalize (HashAggregationOperator FINAL semantics) -----
        outs = []
        si = 0
        for fn, out_dt, width in final_sig:
            if fn == "avg":
                s, sv_ = fin.values[si], fin.value_valids[si]
                c = fin.values[si + 1]
                cnt = jnp.maximum(c, 1)
                vals = (s / cnt).astype(out_dt)
                valid = (c > 0)
                if sv_ is not None:
                    valid = valid & sv_
                outs.append((vals, valid))
            elif fn == "count":
                outs.append((fin.values[si].astype(jnp.int64), None))
            else:  # sum | min | max
                outs.append((fin.values[si].astype(out_dt),
                             fin.value_valids[si]))
            si += width
        flat_out = list(fin.keys)
        flat_out += [v if v is not None else fin.slot_used
                     for v in fin.key_valids]
        flat_out += [d for d, _ in outs]
        flat_out += [v for _, v in outs if v is not None]
        flat_out.append(fin.slot_used)
        return tuple(flat_out)

    n_in = 2 * nk + n_states + sum(1 for s in state_sig if s[2]) + 1 + 2 * n_dict
    n_out = 2 * nk + len(final_sig) \
        + sum(1 for f in final_sig if f[0] not in ("count",)) + 1
    return mesh, program("stage.merge", jax.shard_map(
        local, mesh=mesh,
        in_specs=tuple([P(_AXIS)] * n_in),
        out_specs=tuple([P(_AXIS)] * n_out),
        check_vma=False,
    ))


# ---------------------------------------------------------------------------
# rendezvous + operators


class FusedStageExec:
    """Rendezvous for one fused seam: ``n_tasks`` producer sinks deposit
    their carried states; the last depositor runs the seam merge program
    inside a SyncGuard hot region (zero host syncs between deposit and
    take); consumer sources take their device shard."""

    def __init__(self, spec: FusedStageSpec):
        self.spec = spec
        n = spec.n_tasks
        self._deposits: list = [None] * n
        self._dicts: list = [None] * n
        self._count = 0
        self._lock = threading.Lock()
        self._done = threading.Event()
        self._results: list = [None] * n
        self._error: Optional[BaseException] = None
        self.stats = FusedStageStats(stages=1)

    # ------------------------------------------------------------ producers
    def deposit(self, task_index: int, state, key_dicts,
                sink_stats: FusedStageStats) -> None:
        run_it = False
        with self._lock:
            self._deposits[task_index] = state
            self._dicts[task_index] = key_dicts
            self.stats.merge(sink_stats)
            self._count += 1
            run_it = self._count == self.spec.n_tasks
        if run_it:
            from ..telemetry import profiler

            t0 = profiler.now() if profiler.enabled() else 0.0
            try:
                with SG.hot_region():
                    self._run_merge()
                self.stats.merges += 1
            except BaseException as e:  # surfaced to every waiting consumer
                self._error = e
            if t0:
                profiler.event(
                    profiler.FUSED,
                    f"fused-merge[f{self.spec.producer_fid}->"
                    f"f{self.spec.consumer_fid}]", t0,
                    tasks=self.spec.n_tasks)
            self._done.set()

    def abort(self) -> None:
        self._error = RuntimeError("fused stage aborted")
        self._done.set()

    # ------------------------------------------------------------- the merge
    def _run_merge(self) -> None:
        from .task import _dict_value_hashes

        spec = self.spec
        n, cap, nk = spec.n_tasks, spec.cap, spec.nk
        fcap = n * cap
        key_types = spec.key_types
        dict_flags = tuple(t.is_dictionary_encoded for t in key_types)
        states = [st if st is not None else self._empty_state_host()
                  for st in self._deposits]

        # unify each dictionary key column across tasks (host work over the
        # tiny dictionaries only; codes remap with a device gather inside
        # the merge program)
        empty = np.array([], dtype=object)
        merged_dicts: list = [None] * nk
        remap_tables: list = [None] * nk  # per key: [n] padded tables
        vh_tables: list = [None] * nk
        r_buckets: list = [0] * nk
        v_buckets: list = [0] * nk
        for j in range(nk):
            if not dict_flags[j]:
                continue
            task_dicts = [
                (self._dicts[i][j] if self._dicts[i] is not None
                 and self._dicts[i][j] is not None else empty)
                for i in range(n)]
            first = task_dicts[0]
            if all(d is first or (d.shape == first.shape and (d == first).all())
                   for d in task_dicts):
                merged = first
                remaps = [np.arange(max(len(first), 1), dtype=np.int32)
                          for _ in range(n)]
            else:
                merged = np.unique(np.concatenate(task_dicts))
                remaps = [np.searchsorted(merged, d).astype(np.int32)
                          if len(d) else np.zeros(1, np.int32)
                          for d in task_dicts]
            merged_dicts[j] = merged
            R = K.bucket(max(max(len(r) for r in remaps), 1))
            remap_tables[j] = [
                np.concatenate([r, np.zeros(R - len(r), np.int32)])
                for r in remaps]
            r_buckets[j] = R
            vh = _dict_value_hashes(merged) if len(merged) else \
                np.zeros(1, np.int64)
            V = K.bucket(max(len(vh), 1))
            vh_tables[j] = np.concatenate([vh, np.zeros(V - len(vh), np.int64)])
            v_buckets[j] = V

        state_sig = tuple((s.fn if s.fn != "count_star" else "count",
                           s.dtype, s.has_valid) for s in spec.flat_states)
        final_sig = tuple(
            (a.fn if not (a.fn == "count" and a.arg < 0) else "count",
             np.dtype(t.storage_dtype).str, len(group))
            for a, t, group in zip(spec.final.aggregates,
                                   spec.final.output_types[nk:],
                                   spec.state_specs))
        mesh, prog = _merge_program(
            n, cap, tuple(np.dtype(t.storage_dtype).str for t in key_types),
            dict_flags, state_sig, final_sig,
            (tuple(r_buckets), tuple(v_buckets)))

        srcs: list = []  # [flat][task] host or device arrays
        sizes: list = []

        def add_global(per_task, size):
            srcs.append(list(per_task))
            sizes.append(size)

        for j in range(nk):
            add_global([states[i]["kd"][j] for i in range(n)], cap)
        for j in range(nk):
            add_global([states[i]["kv"][j] for i in range(n)], cap)
        for si, ss in enumerate(spec.flat_states):
            add_global([states[i]["sd"][si] for i in range(n)], cap)
        for si, ss in enumerate(spec.flat_states):
            if ss.has_valid:
                add_global([states[i]["sv"][si] for i in range(n)], cap)
        add_global([states[i]["used"] for i in range(n)], cap)
        for j in range(nk):
            if dict_flags[j]:
                add_global(remap_tables[j], r_buckets[j])
                add_global([vh_tables[j]] * n, v_buckets[j])

        # ONE batched transfer for every shard of every flat input (instead
        # of a device_put launch per shard), then metadata-only global
        # array assembly
        moved = jax.device_put(
            srcs, [[mesh.devices[i] for i in range(n)] for _ in srcs])
        flat = [
            jax.make_array_from_single_device_arrays(
                (n * size,), NamedSharding(mesh, P(_AXIS)), shards)
            for shards, size in zip(moved, sizes)]

        outs = prog(*flat)

        def shards_of(garr):
            by_dev = {s.device: s.data for s in garr.addressable_shards}
            return [by_dev[mesh.devices[i]] for i in range(n)]

        i = 0
        kd_shards = [shards_of(outs[i + j]) for j in range(nk)]; i += nk
        kv_shards = [shards_of(outs[i + j]) for j in range(nk)]; i += nk
        data_shards = [shards_of(outs[i + j]) for j in range(len(final_sig))]
        i += len(final_sig)
        valid_shards: list = []
        for fn, _, _ in final_sig:
            if fn == "count":
                valid_shards.append(None)
            else:
                valid_shards.append(shards_of(outs[i])); i += 1
        live_shards = shards_of(outs[i])

        fin = spec.final
        for t in range(n):
            cols = []
            for j in range(nk):
                cols.append(Column(fin.output_types[j], kd_shards[j][t],
                                   kv_shards[j][t], merged_dicts[j]))
            for a in range(len(final_sig)):
                cols.append(Column(
                    fin.output_types[nk + a], data_shards[a][t],
                    None if valid_shards[a] is None else valid_shards[a][t]))
            self._results[t] = ColumnBatch(list(fin.output_names), cols,
                                           live_shards[t])

    def _empty_state_host(self) -> dict:
        """Zero state for a task that saw no input (numpy: built outside
        any jit, moved by the make_global device_puts)."""
        spec = self.spec
        cap = spec.cap
        return {
            "kd": tuple(np.zeros(cap, t.storage_dtype)
                        for t in spec.key_types),
            "kv": tuple(np.zeros(cap, np.bool_) for _ in spec.key_types),
            "sd": tuple(np.zeros(cap, np.dtype(s.dtype))
                        for s in spec.flat_states),
            "sv": tuple(np.zeros(cap, np.bool_) if s.has_valid else None
                        for s in spec.flat_states),
            "used": np.zeros(cap, np.bool_),
        }

    # ------------------------------------------------------------- consumers
    def take(self, task_index: int,
             timeout: Optional[float] = None) -> ColumnBatch:
        """Blocking take with the PR-5 timeout policy: default from
        TRINO_TPU_EXCHANGE_STALL_S, stall raises a retryable
        PAGE_TRANSPORT_TIMEOUT (same contract as CollectiveRepartitionExchange
        and the HTTP exchange client)."""
        if timeout is None:
            from .task import STALL_TIMEOUT_S

            timeout = STALL_TIMEOUT_S
        from ..telemetry import profiler

        t0 = profiler.now() if profiler.enabled() else 0.0
        ok = self._done.wait(timeout)
        if t0:
            profiler.event(
                profiler.EXCHANGE,
                f"fused-take[f{self.spec.producer_fid}->"
                f"f{self.spec.consumer_fid}]", t0, stalled=not ok)
        if not ok:
            raise TrinoError(
                PAGE_TRANSPORT_TIMEOUT,
                f"fused stage seam f{self.spec.producer_fid}->"
                f"f{self.spec.consumer_fid} stalled after {timeout:.0f}s")
        if self._error is not None:
            if isinstance(self._error, (FusedStageOverflow, TrinoError)):
                raise self._error
            raise TrinoError(
                GENERIC_INTERNAL_ERROR,
                f"fused stage failed: {self._error}") from self._error
        return self._results[task_index]


class FusedStageSinkOperator(Operator):
    """Producer-side terminal of a fused stage: absorbs the feed's device
    batches with ONE jitted accumulate call each (SyncGuard hot region —
    zero host syncs), checks the overflow scalar once at finish, then
    deposits the carried state into the seam rendezvous."""

    def __init__(self, exchange: FusedStageExec, task_index: int):
        self.exchange = exchange
        self.task_index = task_index
        self.spec = exchange.spec
        self._prog: Optional[_AccumulateProgram] = None
        self._state: Optional[dict] = None
        self._key_dicts: Optional[list] = None
        self._remap_cache: dict = {}
        self.stats = FusedStageStats()
        self.pending_errors: list = []

    def add_input(self, batch: ColumnBatch) -> None:
        if batch.num_rows == 0:
            return
        from ..telemetry import profiler

        t0 = profiler.now() if profiler.enabled() else 0.0
        with SG.hot_region():
            self._accumulate(batch)
        if t0:
            profiler.event(
                profiler.FUSED,
                f"fused-accumulate[f{self.spec.producer_fid}]", t0,
                rows=batch.num_rows)

    def _accumulate(self, batch: ColumnBatch) -> None:
        spec = self.spec
        raw_n = batch.num_rows
        # a live-carrying batch is already bucket-shaped (jitted pipeline
        # output) — same pass-through rule as spi.batch.pad_to_bucket
        n = raw_n if batch.live is not None else K.bucket(raw_n)
        in_types = [c.type for c in batch.columns]
        in_dicts = [c.dictionary for c in batch.columns]
        prog = _accumulate_program(spec, in_types, in_dicts)
        if self._state is None:
            self._state = prog.initial_state()
            self._key_dicts = list(prog.key_dicts)
        # dictionary drift: lift carried-state codes and batch codes into a
        # merged dictionary before the (donated) state combine
        batch_remaps: list = [None] * spec.nk
        state_remaps: list = [None] * spec.nk
        for j in range(spec.nk):
            bd, cur = prog.key_dicts[j], self._key_dicts[j]
            if bd is None or cur is None or bd is cur:
                continue
            ck = (id(bd), id(cur))
            hit = self._remap_cache.get(ck)
            if hit is None:
                if bd.shape == cur.shape and (bd == cur).all():
                    hit = (None, None, cur)
                else:
                    merged = np.unique(np.concatenate([cur, bd]))
                    hit = (_pad_table(np.searchsorted(merged, bd)),
                           _pad_table(np.searchsorted(merged, cur)), merged)
                self._remap_cache[ck] = hit
            batch_remaps[j], state_remaps[j], merged = hit
            self._key_dicts[j] = merged
        ingest = _ingest_program(
            n, tuple(c.valid is None for c in batch.columns),
            batch.live is not None)
        cols, live = ingest(
            tuple((c.data, c.valid) for c in batch.columns), batch.live)
        sig = (id(prog), raw_n, n,
               tuple(None if r is None else len(r) for r in batch_remaps),
               tuple(None if r is None else len(r) for r in state_remaps))
        with _ACCUM_LOCK:
            if sig in _TRACE_SIGS:
                fresh = False
                self.stats.cache_hits += 1
            else:
                fresh = True
                _TRACE_SIGS.add(sig)
                self.stats.compiles += 1
        if fresh:
            # a fresh (prog, shape-bucket) signature means this call traces
            # + compiles; its wall time goes to the compile histogram
            import time as _time

            from ..telemetry import metrics as tm

            t0 = _time.perf_counter()
            self._state = prog(self._state, cols, live,
                               tuple(batch_remaps), tuple(state_remaps))
            tm.FUSED_COMPILES.inc()
            tm.FUSED_COMPILE_SECONDS.record(_time.perf_counter() - t0)
        else:
            self._state = prog(self._state, cols, live,
                               tuple(batch_remaps), tuple(state_remaps))
        self._prog = prog
        self.stats.jit_calls += 1
        self.stats.batches += 1
        self.stats.input_rows += n

    def finish_input(self) -> None:
        super().finish_input()
        if self._state is not None:
            # the one data-dependent scalar of the stage, pulled OUTSIDE the
            # hot region, once per task (not per batch)
            ovf = int(SG.fetch(self._state["ovf"], "fused.overflow"))
            if ovf > self.spec.cap:
                raise FusedStageOverflow(
                    f"fused stage f{self.spec.producer_fid}: {ovf} groups "
                    f"exceed the {self.spec.cap}-slot state "
                    f"(TRINO_TPU_FUSED_CAP); falling back to the legacy path")
            self.pending_errors.append(self._state["err"])
        self.exchange.deposit(self.task_index, self._state, self._key_dicts,
                              self.stats)

    def is_finished(self) -> bool:
        return self.input_done


def _pad_table(t: np.ndarray) -> np.ndarray:
    t = t.astype(np.int32)
    R = K.bucket(max(len(t), 1))
    return np.concatenate([t, np.zeros(R - len(t), np.int32)])


class FusedStageSourceOperator(Operator):
    """Consumer-side source: emits this task's device shard of the fused
    FINAL aggregation once (replaces RemoteSource + HashAggregation(FINAL)
    in the consumer pipeline)."""

    blocking = True  # see RemoteExchangeSourceOperator

    def __init__(self, exchange: FusedStageExec, task_index: int):
        self.exchange = exchange
        self.task_index = task_index
        self.input_done = True
        self._emitted = False

    def needs_input(self) -> bool:
        return False

    def get_output(self) -> Optional[ColumnBatch]:
        if self._emitted or self._closed:
            return None
        if not self.blocking and not self.exchange._done.is_set():
            return None  # park; the executor reschedules us
        self._emitted = True
        batch = self.exchange.take(self.task_index)
        return batch if batch.num_rows else None

    def is_finished(self) -> bool:
        return self._emitted or self._closed
