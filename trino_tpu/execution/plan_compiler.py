"""Whole-query GSPMD compilation: one jitted program per maximal
TPU-resident plan.

The fragmenter coalesces maximal connected subtrees of device-resident
fragments — a broadcast multi-join tree under an already-fusable
PARTIAL->FINAL agg seam — into a ``ResidentPlan`` record carrying a
per-edge PartitionSpec contract (execution/fragmenter.py).  This module
lowers each record to ONE per-batch jitted program plus the inherited
seam-merge shard_map:

1. **Build prep** (once per build fragment, per query): every build
   task's deposited batches ride an in-program ``shard_map``
   ``all_gather`` over the named mesh — the BROADCAST interior edge,
   in_spec ``P("x")`` / out_spec ``P()`` (replicated) — then sort by key
   with dead lanes pushed to an int64 sentinel.  Dictionary codes cross
   this seam AS CODES: the tiny dictionaries unify host-side, the code
   lanes gather and permute on device, nothing materializes to values
   (PR 16's deferred follow-up).  Duplicate live build keys trip a
   replicated flag and the plan falls back (the sorted-probe inlined
   below has 1-match semantics).

2. **Whole-plan accumulate** (one call per probe batch, per task): the
   scan feed's batch probes every build via ``searchsorted`` on the
   replicated sorted keys, the Filter/Project chain and the partial
   aggregation + carried-state merge run inline — the whole multi-join
   tree is ONE ``jax.jit`` dispatch with the state pytree donated.
   Missing valid masks and absent live lanes normalize INSIDE the
   program, so launches/batch is ~1 (vs ~2.4 for the PR 6 fused seam).
   The program is cached via the PR 12 ``jit_memo`` registry under a
   JSON-able key (base64 of the zlib-pickled plan payload — same serde
   as query_state.encode_plan), so ``exec_warm.json`` boot replay warms
   resident programs too, unlike the id()-keyed fused accumulate memo.

3. **Seam merge** (inherited from FusedStageExec): the terminal
   REPARTITION edge stays the PR 6 shard_map all_to_all with matched
   ``P("x")`` in/out specs.

Multi-process: ``init_distributed`` wires ``jax.distributed`` with the
gloo CPU-collectives backend so one program spans hosts on a CPU mesh
(``--xla_force_host_platform_device_count`` per process in CI; real ICI
on hardware).

``TRINO_TPU_RESIDENT_PLAN={auto,1,0}``: 0 keeps the task-per-worker
fused/legacy path bit-for-bit.  Overflow, duplicate build keys, or any
build failure raise ``ResidentPlanOverflow`` and the runner re-runs the
subplan on the non-resident path (same contract as FusedStageOverflow).
"""

from __future__ import annotations

import base64
import os
import pickle
import threading
import zlib
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..caching.executable_cache import jit_memo, program
from ..exec import kernels as K
from ..exec import syncguard as SG
from ..exec.operators import Operator
from ..exec.stats import FusedStageStats, ResidentPlanStats
from ..planner import plan as PL
from ..spi.batch import ColumnBatch
from ..spi.errors import PAGE_TRANSPORT_TIMEOUT, TrinoError
from ..sql.ir import InputRef
from .stage_compiler import (
    _AXIS,
    FusedStageExec,
    FusedStageOverflow,
    FusedStageSpec,
    _AccumulateProgram,
    _ingest_program,
    _pad_table,
    build_fused_spec,
    fused_cap,
    fused_stage_mode,
)

__all__ = ["ResidentPlanExec", "ResidentPlanOverflow", "ResidentPlanSpec",
           "ResidentBuildHandle", "ResidentBuildSinkOperator",
           "ResidentPlanSinkOperator", "build_resident_spec",
           "plan_resident_plans", "resident_plan_mode",
           "resident_max_fragments", "init_distributed"]

_KEY_SENTINEL = np.iinfo(np.int64).max


def resident_plan_mode() -> str:
    """TRINO_TPU_RESIDENT_PLAN: auto (default, compile eligible resident
    plans), 1 (same), 0 (task-per-worker fused/legacy path, bit-for-bit)."""
    v = os.environ.get("TRINO_TPU_RESIDENT_PLAN", "auto").strip().lower()
    return v if v in ("auto", "1", "0") else "auto"


def resident_max_fragments() -> int:
    """Largest fragment count a single resident program may absorb
    (TRINO_TPU_RESIDENT_MAX_FRAGMENTS)."""
    return int(os.environ.get("TRINO_TPU_RESIDENT_MAX_FRAGMENTS", "8"))


def _mesh_device_cap() -> int:
    """TRINO_TPU_MESH_SHAPE override ("8" or "2x4"): product caps the
    mesh width a resident plan may claim; 0 = no override."""
    v = os.environ.get("TRINO_TPU_MESH_SHAPE", "").strip().lower()
    if not v:
        return 0
    try:
        dims = [int(p) for p in v.replace("x", " ").split()]
    except ValueError:
        return 0
    n = 1
    for d in dims:
        if d <= 0:
            return 0
        n *= d
    return n


def init_distributed(coordinator_address: str, num_processes: int,
                     process_id: int) -> None:
    """jax.distributed bring-up for multi-host resident plans.  The gloo
    CPU-collectives backend MUST be selected before initialize: the
    default XLA CPU backend rejects multi-process collectives outright."""
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


class ResidentPlanOverflow(FusedStageOverflow):
    """A resident plan can't hold (state overflow, duplicate build keys,
    build failure); the runner re-runs the subplan with resident+fused
    compilation disabled."""


# ---------------------------------------------------------------------------
# spec: what the fragmenter's ResidentPlan lowers to


@dataclass
class ResidentPlanSpec(FusedStageSpec):
    """FusedStageSpec plus the inlined broadcast joins.  ``feed`` is the
    scan chain BELOW the join spine (what the legacy operator pipeline
    executes per task); ``joins`` apply bottom-up, each widening the
    probe schema by its build fragment's output columns."""

    joins: tuple = ()              # tuple[fragmenter.ResidentJoin, ...]
    build_types: tuple = ()        # per-join tuple of build output types
    plan: object = None            # the fragmenter.ResidentPlan record


def build_resident_spec(frag, frags_by_id: dict, n_tasks: int,
                        cap: int) -> ResidentPlanSpec:
    """Lower a fragmenter-marked ResidentPlan into the executable spec."""
    rp = frag.resident_plan
    base = build_fused_spec(frag, frags_by_id[rp.consumer_fid], n_tasks, cap)
    feed = base.feed               # the topmost Join of the probe spine
    for _ in rp.joins:
        feed = feed.left
    build_types = tuple(
        tuple(frags_by_id[j.build_fid].root.output_types) for j in rp.joins)
    return ResidentPlanSpec(
        producer_fid=base.producer_fid, consumer_fid=base.consumer_fid,
        n_tasks=n_tasks, feed=feed, chain=base.chain, partial=base.partial,
        final=base.final, nk=base.nk, cap=cap, state_specs=base.state_specs,
        joins=tuple(rp.joins), build_types=build_types, plan=rp)


def _key_origins(spec: ResidentPlanSpec) -> list:
    """For each group key, its channel in the post-join (chain-input)
    schema, or None when the key is a computed expression.  Drives the
    sink's dictionary-drift handling: feed-origin dict keys drift per
    batch, build-origin dict keys are stable for the whole query."""
    if spec.chain:
        width = len(spec.chain[0].source.output_types)
    else:
        width = len(spec.partial.source.output_types)
    idx: list = list(range(width))
    for node in spec.chain:
        if isinstance(node, PL.Project):
            idx = [idx[e.index] if isinstance(e, InputRef) else None
                   for e in node.expressions]
    return [idx[c] for c in spec.partial.group_keys]


# ---------------------------------------------------------------------------
# the whole-plan program: probe every build + chain + partial agg + state
# merge, ONE jit call per batch


class _ResidentProgram(_AccumulateProgram):
    """The per-batch resident-plan program.  Joins are sorted-probe
    lookups against the replicated build tables (1-match semantics —
    duplicate build keys fall back at prep); the Filter/Project chain and
    the aggregation tail reuse the fused accumulate bodies.  Expressions
    compile WITHOUT dictionaries (eligibility guarantees the chain is
    dict-free; codes pass through as bare lanes), so the program is
    dictionary-independent and its memo key is a pure value."""

    def __init__(self, spec: ResidentPlanSpec):
        self.spec = spec
        if spec.chain:
            in_types = list(spec.chain[0].source.output_types)
        else:
            in_types = list(spec.partial.source.output_types)
        self._compile_chain(in_types, [None] * len(in_types))
        self._fn = program("resident.accumulate", self._run,
                           donate_argnums=(0,))
        self._init_fn = program("resident.initial_state",
                                self._initial_state)

    def __call__(self, state, feed_cols, live, builds, batch_remaps,
                 state_remaps):
        return self._fn(state, feed_cols, live, builds, batch_remaps,
                        state_remaps)

    def _run(self, state, feed_cols, live, builds, batch_remaps,
             state_remaps):
        n = feed_cols[0][0].shape[0]
        # normalize IN-program: no ingest launch ahead of the dispatch
        cols = [(d, v if v is not None else jnp.ones(n, jnp.bool_))
                for d, v in feed_cols]
        if live is None:
            live = jnp.ones(n, jnp.bool_)
        for join, (bk, blive, payload) in zip(self.spec.joins, builds):
            pk_d, pk_v = cols[join.probe_key]
            pk = pk_d.astype(jnp.int64)
            idx = jnp.clip(jnp.searchsorted(bk, pk),
                           0, bk.shape[0] - 1).astype(jnp.int32)
            hit = (bk[idx] == pk) & blive[idx] & pk_v
            for d, v in payload:
                cols.append((d[idx], hit if v is None else (v[idx] & hit)))
            if join.join_type == "INNER":
                live = live & hit
        cols, live, batch_err = self._apply_chain(cols, live, n)
        return self._agg_merge(state, cols, live, batch_remaps,
                               state_remaps, n, batch_err)


def _encode_resident_payload(spec: ResidentPlanSpec) -> str:
    """Value-serialize everything the program depends on — same base64 /
    zlib / pickle serde as query_state.encode_plan.  This string IS the
    jit_memo key: JSON-able, so exec_warm.json replay rebuilds resident
    programs at boot (the fused accumulate memo keys on id() and can't)."""
    raw = pickle.dumps((tuple(spec.chain), spec.partial, tuple(spec.joins),
                        tuple(spec.state_specs)), protocol=4)
    return base64.b64encode(zlib.compress(raw)).decode("ascii")


@jit_memo("resident._program", maxsize=64)
def _resident_program(spec_b64: str, cap: int) -> _ResidentProgram:
    chain, partial, joins, state_specs = pickle.loads(
        zlib.decompress(base64.b64decode(spec_b64)))
    feed = chain[0].source if chain else partial.source
    spec = ResidentPlanSpec(
        producer_fid=-1, consumer_fid=-1, n_tasks=0, feed=feed,
        chain=tuple(chain), partial=partial, final=partial,
        nk=len(partial.group_keys), cap=cap, state_specs=tuple(state_specs),
        joins=tuple(joins))
    return _ResidentProgram(spec)


# compile counting for resident dispatches (same TLS-free set discipline
# as stage_compiler._TRACE_SIGS)
_RES_TRACE_SIGS: set = set()
_RES_LOCK = threading.Lock()


# ---------------------------------------------------------------------------
# build prep: the in-program BROADCAST interior edge


@jit_memo("resident._build_prep")
def _build_prep_program(n_dev: int, n_payload: int):
    """ONE jitted shard_map per (mesh width, payload width): every build
    lane all_gathers over the mesh axis (the BROADCAST edge of the
    ResidentPlan contract — in_spec P("x"), out_spec P() replicated),
    dead/NULL-key lanes push to the int64 sentinel, one argsort orders
    the table for the sorted probe, and adjacent live duplicates raise a
    replicated flag (fallback: the probe is 1-match)."""
    mesh = Mesh(jax.devices()[:n_dev], (_AXIS,))

    def local(key, live, *payload_flat):
        gk = jax.lax.all_gather(key, _AXIS, tiled=True)
        gl = jax.lax.all_gather(live, _AXIS, tiled=True)
        sk = jnp.where(gl, gk, _KEY_SENTINEL)
        perm = jnp.argsort(sk)
        sk = sk[perm]
        sl = gl[perm]
        outs = [sk, sl]
        for arr in payload_flat:
            g = jax.lax.all_gather(arr, _AXIS, tiled=True)
            outs.append(g[perm])
        dup = jnp.any((sk[1:] == sk[:-1]) & sl[1:] & sl[:-1])
        outs.append(dup)
        return tuple(outs)

    n_in = 2 + 2 * n_payload
    return mesh, program("resident.build_prep", jax.shard_map(
        local, mesh=mesh,
        in_specs=tuple([P(_AXIS)] * n_in),
        out_specs=tuple([P()] * (n_in + 1)),
        check_vma=False,
    ))


# ---------------------------------------------------------------------------
# rendezvous


class ResidentPlanExec(FusedStageExec):
    """Rendezvous for one resident plan: build sinks deposit their
    fragments' batches (last depositor runs the broadcast prep), probe
    sinks wait for every build then absorb batches with one whole-plan
    dispatch each, and the inherited FusedStageExec seam merge + take
    serve the consumer.  The terminal REPARTITION edge keeps the PR 6
    P("x")->P("x") contract unchanged."""

    def __init__(self, spec: ResidentPlanSpec):
        super().__init__(spec)
        self.rstats = ResidentPlanStats(plans=1, seams=len(spec.joins) + 1)
        self.spec_b64 = _encode_resident_payload(spec)
        self.key_origins = _key_origins(spec)
        self.n_feed = len(spec.feed.output_types)
        self._build_lock = threading.Lock()
        self._builds: dict = {}
        for j in spec.joins:
            self._builds[j.build_fid] = {
                "deposits": [None] * spec.n_tasks, "count": 0,
                "ready": threading.Event(), "table": None, "dicts": None}

    # ------------------------------------------------------------ build side
    def build_deposit(self, build_fid: int, task_index: int,
                      batches: list) -> None:
        slot = self._builds[build_fid]
        run_prep = False
        with self._build_lock:
            slot["deposits"][task_index] = batches
            slot["count"] += 1
            run_prep = slot["count"] == self.spec.n_tasks
        if run_prep:
            try:
                self._prep_build(build_fid)
            except BaseException as e:
                self._fail(e)
            slot["ready"].set()

    def _fail(self, e: BaseException) -> None:
        self._error = e
        for slot in self._builds.values():
            slot["ready"].set()
        self._done.set()

    def abort(self) -> None:
        self._error = RuntimeError("resident plan aborted")
        for slot in self._builds.values():
            slot["ready"].set()
        self._done.set()

    def _prep_build(self, build_fid: int) -> None:
        from ..telemetry import metrics as tm
        from ..telemetry import profiler

        spec = self.spec
        t0 = profiler.now() if profiler.enabled() else 0.0
        ji = next(i for i, j in enumerate(spec.joins)
                  if j.build_fid == build_fid)
        join = spec.joins[ji]
        col_types = spec.build_types[ji]
        ncols = len(col_types)
        n = spec.n_tasks
        slot = self._builds[build_fid]
        per_task = [list(slot["deposits"][t] or []) for t in range(n)]
        all_batches = [b for bs in per_task for b in bs]

        # unify dictionaries per column across every deposited batch: the
        # tiny dictionaries merge host-side, the code LANES stay codes all
        # the way through the broadcast gather below
        merged_dicts: list = [None] * ncols
        for c in range(ncols):
            dicts = [b.columns[c].dictionary for b in all_batches]
            dicts = [d for d in dicts if d is not None]
            if not dicts:
                continue
            first = dicts[0]
            if all(d is first or (d.shape == first.shape and (d == first).all())
                   for d in dicts):
                merged_dicts[c] = first
            else:
                merged_dicts[c] = np.unique(np.concatenate(dicts))
        n_code_cols = sum(1 for d in merged_dicts if d is not None)

        # host assembly per task lane: concat rows, remap codes into the
        # merged dictionary space, key-validity folds into the live lane
        rows = [sum(b.num_rows for b in bs) for bs in per_task]
        pcap = K.bucket(max(max(rows, default=0), 1))

        def padded(parts, dtype):
            a = (np.concatenate(parts) if parts
                 else np.zeros(0, dtype)).astype(dtype, copy=False)
            out = np.zeros(pcap, dtype)
            out[:len(a)] = a
            return out

        keys, lives = [], []
        data: list = [[] for _ in range(ncols)]
        valid: list = [[] for _ in range(ncols)]
        for t in range(n):
            kparts, lparts = [], []
            dparts: list = [[] for _ in range(ncols)]
            vparts: list = [[] for _ in range(ncols)]
            for b in per_task[t]:
                m = b.num_rows
                bl = (np.asarray(b.live) if b.live is not None
                      else np.ones(m, bool))
                kc = b.columns[join.build_key]
                lv = bl if kc.valid is None else bl & np.asarray(kc.valid)
                kparts.append(np.asarray(kc.data).astype(np.int64))
                lparts.append(lv)
                for c in range(ncols):
                    col = b.columns[c]
                    d = np.asarray(col.data)
                    md = merged_dicts[c]
                    if md is not None and col.dictionary is not None \
                            and col.dictionary is not md:
                        d = np.searchsorted(
                            md, col.dictionary).astype(np.int32)[d]
                    dparts[c].append(d)
                    vparts[c].append(
                        np.asarray(col.valid) if col.valid is not None
                        else np.ones(m, bool))
            keys.append(padded(kparts, np.int64))
            lives.append(padded(lparts, np.bool_))
            for c in range(ncols):
                dt = (np.int32 if merged_dicts[c] is not None
                      else np.dtype(col_types[c].storage_dtype))
                data[c].append(padded(dparts[c], dt))
                valid[c].append(padded(vparts[c], np.bool_))

        mesh, prog = _build_prep_program(n, ncols)
        srcs = [keys, lives]
        for c in range(ncols):
            srcs.append(data[c])
            srcs.append(valid[c])
        moved = jax.device_put(
            srcs, [[mesh.devices[i] for i in range(n)] for _ in srcs])
        flat = [
            jax.make_array_from_single_device_arrays(
                (n * pcap,), NamedSharding(mesh, P(_AXIS)), shards)
            for shards in moved]
        outs = prog(*flat)

        def rep(g):
            return g.addressable_shards[0].data

        bk, blive = rep(outs[0]), rep(outs[1])
        payload = tuple((rep(outs[2 + 2 * c]), rep(outs[3 + 2 * c]))
                        for c in range(ncols))
        dup = int(SG.fetch(outs[-1], "resident.build-dup"))
        if dup:
            raise ResidentPlanOverflow(
                f"resident plan f{spec.producer_fid}: build f{build_fid} "
                "has duplicate join keys (sorted probe is 1-match); "
                "falling back to the task-per-worker path")
        slot["table"] = (bk, blive, payload)
        slot["dicts"] = merged_dicts
        with self._build_lock:
            self.rstats.code_seam_columns += n_code_cols
        if n_code_cols:
            tm.RESIDENT_CODE_SEAMS.inc(n_code_cols)
        if t0:
            profiler.event(
                profiler.RESIDENT,
                f"resident-build[f{build_fid}->f{spec.producer_fid}]", t0,
                rows=sum(rows), code_columns=n_code_cols)

    # ------------------------------------------------------------ probe side
    def wait_builds(self) -> None:
        from .task import STALL_TIMEOUT_S

        for fid, slot in self._builds.items():
            if not slot["ready"].wait(STALL_TIMEOUT_S):
                raise TrinoError(
                    PAGE_TRANSPORT_TIMEOUT,
                    f"resident build f{fid} stalled after "
                    f"{STALL_TIMEOUT_S:.0f}s")
        if self._error is not None:
            raise self._error

    def build_tables(self) -> tuple:
        return tuple(self._builds[j.build_fid]["table"]
                     for j in self.spec.joins)

    def _build_col_dict(self, post_join_channel: int):
        """Merged dictionary of a build-origin post-join channel."""
        off = post_join_channel - self.n_feed
        for ji, types in enumerate(self.spec.build_types):
            if off < len(types):
                dicts = self._builds[self.spec.joins[ji].build_fid]["dicts"]
                return dicts[off] if dicts is not None else None
            off -= len(types)
        return None

    def initial_key_dicts(self) -> list:
        """Starting key dictionaries for a probe sink's carried state:
        build-origin dict keys are pinned to the merged build dictionary
        (stable all query); feed-origin keys start None and track batch
        drift in the sink."""
        out: list = [None] * self.spec.nk
        for j, o in enumerate(self.key_origins):
            if o is not None and o >= self.n_feed:
                out[j] = self._build_col_dict(o)
        return out

    # ------------------------------------------------------------- producers
    def deposit(self, task_index: int, state, key_dicts,
                sink_stats: FusedStageStats) -> None:
        with self._build_lock:
            self.rstats.batches += sink_stats.batches
            self.rstats.jit_calls += sink_stats.jit_calls
            self.rstats.programs += sink_stats.compiles
            self.rstats.cache_hits += sink_stats.cache_hits
            self.rstats.input_rows += sink_stats.input_rows
        super().deposit(task_index, state, key_dicts, sink_stats)

    def _run_merge(self) -> None:
        super()._run_merge()
        self.rstats.merges += 1


class ResidentBuildHandle:
    """Edge value for a build fragment folded into a resident plan: its
    tasks terminate in a ResidentBuildSinkOperator that deposits into the
    owning ResidentPlanExec."""

    def __init__(self, exchange: ResidentPlanExec, build_fid: int):
        self.exchange = exchange
        self.build_fid = build_fid

    def abort(self) -> None:
        self.exchange.abort()


class ResidentBuildSinkOperator(Operator):
    """Build-side terminal: batches stay exactly as produced (codes and
    all) and hand off to the broadcast prep at finish."""

    def __init__(self, handle: ResidentBuildHandle, task_index: int):
        self.handle = handle
        self.task_index = task_index
        self._batches: list = []

    def add_input(self, batch: ColumnBatch) -> None:
        if batch.num_rows:
            self._batches.append(batch)

    def finish_input(self) -> None:
        super().finish_input()
        self.handle.exchange.build_deposit(
            self.handle.build_fid, self.task_index, self._batches)

    def is_finished(self) -> bool:
        return self.input_done


class ResidentPlanSinkOperator(Operator):
    """Probe-side terminal of a resident plan: one whole-plan jitted
    dispatch per feed batch (SyncGuard hot region — the joins, chain,
    partial agg and state merge are all inside), overflow checked once at
    finish, state deposited into the inherited seam rendezvous."""

    def __init__(self, exchange: ResidentPlanExec, task_index: int):
        self.exchange = exchange
        self.task_index = task_index
        self.spec: ResidentPlanSpec = exchange.spec
        self._state: Optional[dict] = None
        self._key_dicts: Optional[list] = None
        self._remap_cache: dict = {}
        self._builds: Optional[tuple] = None
        self.stats = FusedStageStats()
        self.pending_errors: list = []

    def add_input(self, batch: ColumnBatch) -> None:
        if batch.num_rows == 0:
            return
        if self._builds is None:
            self.exchange.wait_builds()  # blocks OUTSIDE the hot region
            self._builds = self.exchange.build_tables()
        from ..telemetry import profiler

        t0 = profiler.now() if profiler.enabled() else 0.0
        with SG.hot_region():
            self._accumulate(batch)
        if t0:
            profiler.event(
                profiler.RESIDENT,
                f"resident-accumulate[f{self.spec.producer_fid}]", t0,
                rows=batch.num_rows)

    def _accumulate(self, batch: ColumnBatch) -> None:
        spec = self.spec
        raw_n = batch.num_rows
        n = raw_n if batch.live is not None else K.bucket(raw_n)
        prog = _resident_program(self.exchange.spec_b64, spec.cap)
        if self._state is None:
            self._state = prog.initial_state()
            self._key_dicts = self.exchange.initial_key_dicts()
        # feed-origin dictionary drift: lift carried-state codes and batch
        # codes into a merged dictionary before the (donated) state combine
        batch_remaps: list = [None] * spec.nk
        state_remaps: list = [None] * spec.nk
        n_feed = self.exchange.n_feed
        for j, origin in enumerate(self.exchange.key_origins):
            if origin is None or origin >= n_feed:
                continue
            bd = batch.columns[origin].dictionary
            if bd is None:
                continue
            cur = self._key_dicts[j]
            if cur is None:
                self._key_dicts[j] = bd
                continue
            if bd is cur:
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
        miss_valid = tuple(c.valid is None for c in batch.columns)
        has_live = batch.live is not None
        if has_live or raw_n == n:
            # the common path: the program normalizes valids/live itself,
            # so the whole batch is exactly ONE dispatch
            feed_cols = tuple((c.data, c.valid) for c in batch.columns)
            live = batch.live
        else:
            ingest = _ingest_program(n, miss_valid, has_live)
            feed_cols, live = ingest(
                tuple((c.data, c.valid) for c in batch.columns), batch.live)
            miss_valid = tuple(False for _ in batch.columns)
            has_live = True
        sig = (id(prog), raw_n, n, miss_valid, has_live,
               tuple(None if r is None else len(r) for r in batch_remaps),
               tuple(None if r is None else len(r) for r in state_remaps))
        with _RES_LOCK:
            if sig in _RES_TRACE_SIGS:
                fresh = False
                self.stats.cache_hits += 1
            else:
                fresh = True
                _RES_TRACE_SIGS.add(sig)
                self.stats.compiles += 1
        if fresh:
            import time as _time

            from ..telemetry import metrics as tm

            t0 = _time.perf_counter()
            self._state = prog(self._state, feed_cols, live, self._builds,
                               tuple(batch_remaps), tuple(state_remaps))
            tm.RESIDENT_PROGRAMS.inc()
            tm.FUSED_COMPILE_SECONDS.record(_time.perf_counter() - t0)
        else:
            self._state = prog(self._state, feed_cols, live, self._builds,
                               tuple(batch_remaps), tuple(state_remaps))
        self.stats.jit_calls += 1
        self.stats.batches += 1
        self.stats.input_rows += n

    def finish_input(self) -> None:
        super().finish_input()
        if self._state is not None:
            # the one data-dependent scalar, pulled OUTSIDE the hot region,
            # once per task (not per batch)
            ovf = int(SG.fetch(self._state["ovf"], "resident.overflow"))
            if ovf > self.spec.cap:
                raise ResidentPlanOverflow(
                    f"resident plan f{self.spec.producer_fid}: {ovf} groups "
                    f"exceed the {self.spec.cap}-slot state "
                    f"(TRINO_TPU_FUSED_CAP); falling back to the "
                    f"task-per-worker path")
            self.pending_errors.append(self._state["err"])
        self.exchange.deposit(self.task_index, self._state, self._key_dicts,
                              self.stats)

    def is_finished(self) -> bool:
        return self.input_done


# ---------------------------------------------------------------------------
# runtime planning gate


def plan_resident_plans(fragments, session, task_counts: dict,
                        consumer_tasks: dict) -> dict:
    """Runtime gate over fragmenter-coalesced resident plans: returns
    {core_fid: ResidentPlanExec} plus {build_fid: ResidentBuildHandle}
    for plans where the mesh exists and every participating fragment's
    task count matches the mesh width (same conditions as the fused
    seam, extended over the whole subtree)."""
    if (resident_plan_mode() == "0" or fused_stage_mode() == "0"
            or not getattr(session, "use_collectives", True)):
        return {}
    from .collective_exchange import collectives_available

    by_id = {f.id: f for f in fragments}
    max_frags = resident_max_fragments()
    cap_dev = _mesh_device_cap()
    out: dict = {}
    for f in fragments:
        rp = getattr(f, "resident_plan", None)
        if rp is None or not getattr(f, "device_resident", False):
            continue
        if len(rp.fragment_ids) > max_frags:
            continue
        tc = task_counts.get(f.id)
        if (tc is None or consumer_tasks.get(f.id) != tc
                or task_counts.get(rp.consumer_fid) != tc
                or not collectives_available(tc)):
            continue
        if cap_dev and tc > cap_dev:
            continue
        if any(task_counts.get(j.build_fid) != tc for j in rp.joins):
            continue
        ex = ResidentPlanExec(
            build_resident_spec(f, by_id, tc, fused_cap()))
        out[f.id] = ex
        for j in rp.joins:
            out[j.build_fid] = ResidentBuildHandle(ex, j.build_fid)
    return out
