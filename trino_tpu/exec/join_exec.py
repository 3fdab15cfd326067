"""Device-resident hash-join execution programs.

Round-4 rework of the join hot path (reference: operator/join/
LookupJoinOperator.java:37, HashBuilderOperator.java:57, PagesHash).  The
round-3 engine pulled every (probe_idx, build_idx) match pair to the host
(`jax.device_get` of megarow int64 arrays) and re-uploaded them for gathers;
this module keeps the whole probe on device:

- ``build_table``: ONE jitted program hashes + sorts the build keys
  (``hash_combine`` + argsort on chip); one 2-scalar device_get fetches
  (has_null_key, live_rows) for planner-visible semantics.
- ``probe_ranges_device``: ONE jitted program computes candidate ranges via
  binary search in the sorted hash; the total candidate count comes back as
  an AsyncScalar.  The static expansion bucket — the only data-dependent
  shape in the join — is planned from build-side statistics
  (``ExpandPlanner``) and guarded by a deferred overflow flag.
- ``run_pairs``: ONE jitted program per (join shape, residual, bucket)
  expands candidates, verifies key equality exactly (hash candidates ->
  per-key compare, NaN=NaN), evaluates the residual predicate, gathers ALL
  output columns at the matched pairs, and computes per-probe matched flags
  for LEFT/SINGLE and the semi-join mark — outputs stay on device as a
  ``live``-masked batch.

Blocking host interaction per probe batch in the steady state: none.
"""

from __future__ import annotations

import threading
from ..caching.executable_cache import jit_memo, program
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.expr import compile_expression
from ..sql.ir import RowExpression
from . import kernels as K
from . import syncguard as SG

__all__ = ["DeviceJoinTable", "JoinHashTable", "build_table",
           "probe_ranges_device", "run_pairs", "run_unique",
           "ExpandPlanner", "OverflowQueue", "plan_unique_cap",
           "unique_cap_bucket", "key_input"]

_SENT_BUILD = 0xFFFFFFFFFFFFFFFF  # build rows with NULL keys / dead rows
_SENT_PROBE = 0xFFFFFFFFFFFFFFFE  # probe rows with NULL keys


def key_input(col):
    """Device-ready key data for a probe/build column under compressed
    execution: an RLE run expands device-side from its ONE stored scalar
    (kernels.rle_fill) instead of materializing a host broadcast view and
    shipping the full run over PCIe; everything else (flat arrays,
    dictionary codes, lazy columns on first touch) passes through as
    ``.data``."""
    if col.encoding == "RLE":
        return K.rle_fill(col.rle_value, len(col))
    return col.data


class DeviceJoinTable:
    """Sorted-hash build side, all arrays device-resident.

    The planner-visible scalars (has_null_key, live_rows, max duplicate run)
    stay on device until first access: building the table costs ZERO blocking
    host syncs, and the one combined scalar fetch happens lazily — per build,
    never per probe batch (a per-batch blocking sync would drain the dispatch
    pipeline once per batch)."""

    __slots__ = ("sorted_hash", "perm", "key_datas",
                 "num_rows", "_scalars", "_fetched", "dense", "dense_lo",
                 "hash_idx")

    def __init__(self, sorted_hash, perm, key_datas,
                 num_rows: int, scalars):
        self.sorted_hash = sorted_hash
        self.perm = perm
        self.key_datas = key_datas  # unsorted, for exact verify
        self.num_rows = num_rows  # physical slots (incl. dead padding)
        # (has_null, live_rows, max_run[, kmin, kmax]) device scalars OR a
        # host tuple
        self._scalars = scalars
        self._fetched: Optional[tuple] = None
        # direct-address table for a unique single-int-key build whose key
        # range is dense: dense[key - dense_lo] = build row (or -1).  Probes
        # become ONE gather — no hashing, no binary search, no verify.
        self.dense = None
        self.dense_lo = 0
        # open-addressing index over the build hashes (TRINO_TPU_HASH_IMPL):
        # probe_ranges_device dispatches on it; every downstream program is
        # shared
        self.hash_idx: Optional["JoinHashTable"] = None

    def _fetch(self) -> tuple:
        if self._fetched is None:
            s = self._scalars
            if isinstance(s, tuple) and all(
                    isinstance(x, (bool, int)) for x in s):
                self._fetched = s
            else:
                # ONE blocking fetch per BUILD (never per probe batch); the
                # async copy started at build time usually landed already
                self._fetched = tuple(
                    int(x) for x in SG.fetch(s, "join.build-scalars"))
        return self._fetched

    @property
    def has_null_key(self) -> bool:  # among LIVE rows
        return bool(self._fetch()[0])

    @property
    def live_rows(self) -> int:
        return self._fetch()[1]

    @property
    def unique(self) -> bool:
        """True when every live build HASH is distinct (implies the keys are
        distinct): each probe row matches at most one build row, so the
        probe runs the static-shape path with no candidate-count sync."""
        return self._fetch()[2] <= 1

    @property
    def max_run(self) -> int:
        """Longest duplicate-hash run among live build rows: each probe row
        yields at most this many candidates, so n_probe * max_run bounds the
        pair total — the provable padded-expand cap (ExpandPlanner)."""
        return self._fetch()[2]


class JoinHashTable:
    """Open-addressing index over the build side's 64-bit key hashes
    (TRINO_TPU_HASH_IMPL, ops/pallas_kernels.py): maps a probe hash to the
    contiguous run of matching rows in sorted-hash order, replacing the two
    binary searches of probe_ranges_device with one kernel probe plus two
    gathers.
    The (lo, counts) it yields are value-identical to the searchsorted
    implementation — both index the SAME sorted order — so every downstream
    expand/verify/gather program is shared between implementations, and
    ``build_id = perm[lo + within]`` holds unchanged."""

    __slots__ = ("table_planes", "slot_gid", "group_lo", "group_counts",
                 "num_slots")

    def __init__(self, table_planes, slot_gid, group_lo, group_counts,
                 num_slots: int):
        self.table_planes = table_planes
        self.slot_gid = slot_gid
        self.group_lo = group_lo  # [S] first sorted position per hash group
        self.group_counts = group_counts  # [S] live run length per group
        self.num_slots = num_slots


def _hash_planes(h):
    """uint64 hash -> the kernels' [2, N] uint32 planes + uint32 slot hash.
    Plane equality is exactly 64-bit hash equality, so the index reproduces
    the searchsorted candidate set bit for bit."""
    planes = jnp.stack([
        (h & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32),
        (h >> jnp.uint64(32)).astype(jnp.uint32)])
    h32 = (h ^ (h >> jnp.uint64(32))).astype(jnp.uint32)
    return planes, h32


@jit_memo("join._hash_index_fn")
def _hash_index_fn(S: int, n: int, interpret: bool):
    from ..ops import pallas_kernels as PK

    @program("join.hash_index")
    def fn(sorted_hash):
        live = sorted_hash < jnp.uint64(_SENT_PROBE)
        planes, h32 = _hash_planes(sorted_hash)
        row_gid, _count, table, sgid = PK.hash_insert(
            planes, h32, live, S, interpret=interpret)
        # the insert ran over the SORTED hashes: each distinct hash is one
        # contiguous run, so per-group lo/count are one min- and one
        # sum-scatter over positions (dead rows carry gid S -> trash slot)
        pos = jnp.arange(n, dtype=jnp.int64)
        glo = jnp.full((S + 1,), n, jnp.int64).at[row_gid].min(pos)
        gcnt = jnp.zeros((S + 1,), jnp.int64).at[row_gid].add(
            live.astype(jnp.int64))
        return table, sgid, glo[:S], gcnt[:S]

    return fn


@jit_memo("join._build_fn")
def _build_fn(num_keys: int, has_valid: tuple, has_live: bool,
              want_range: bool = False):
    @program("join.build")
    def fn(*flat):
        i = 0
        datas, valids = [], []
        for k in range(num_keys):
            datas.append(flat[i])
            i += 1
            if has_valid[k]:
                valids.append(flat[i])
                i += 1
            else:
                valids.append(None)
        live = flat[i] if has_live else None
        h = K.hash_combine(datas)
        null_mask = None
        for v in valids:
            if v is not None:
                nm = ~v
                null_mask = nm if null_mask is None else (null_mask | nm)
        n = datas[0].shape[0]
        live_rows = (jnp.asarray(n, jnp.int64) if live is None
                     else jnp.sum(live))
        if null_mask is not None:
            has_null = jnp.any(null_mask if live is None
                               else (null_mask & live))
            h = jnp.where(null_mask, jnp.uint64(_SENT_BUILD), h)
        else:
            has_null = jnp.asarray(False)
        if live is not None:
            h = jnp.where(live, h, jnp.uint64(_SENT_BUILD))
        perm = jnp.argsort(h)
        sh = h[perm]
        # max duplicate-hash run among live (non-sentinel) rows: 1 means the
        # build keys are provably unique -> probes take the sync-free path
        if n:
            run = (K.searchsorted(sh, sh, side="right")
                   - K.searchsorted(sh, sh, side="left"))
            in_region = sh < jnp.uint64(_SENT_PROBE)
            max_run = jnp.max(jnp.where(in_region, run, 0))
        else:
            max_run = jnp.zeros((), jnp.int64)
        if not want_range:
            return sh, perm, has_null, live_rows, max_run
        # live non-null key min/max, for the dense direct-address table
        big = jnp.asarray(1 << 62, jnp.int64)
        if n:
            k0 = datas[0].astype(jnp.int64)
            elig = jnp.ones(k0.shape, jnp.bool_)
            if valids[0] is not None:
                elig = elig & valids[0]
            if live is not None:
                elig = elig & live
            kmin = jnp.min(jnp.where(elig, k0, big))
            kmax = jnp.max(jnp.where(elig, k0, -big))
        else:
            kmin, kmax = big, -big
        return sh, perm, has_null, live_rows, max_run, kmin, kmax

    return fn


@jit_memo("join._dense_build_fn")
def _dense_build_fn(size: int, has_valid: bool, has_live: bool, lo: int):
    """Scatter live build rows into dense[key - lo] (one scatter; -1 =
    empty slot).  Exactness needs no verify: direct addressing cannot
    collide, and uniqueness was already proven by max_run == 1."""

    @program("join.dense_build")
    def fn(key, *rest):
        i = 0
        valid = rest[i] if has_valid else None
        i += 1 if has_valid else 0
        live = rest[i] if has_live else None
        n = key.shape[0]
        idx = key.astype(jnp.int64) - lo
        elig = (idx >= 0) & (idx < size)
        if valid is not None:
            elig = elig & valid
        if live is not None:
            elig = elig & live
        slot = jnp.where(elig, idx, size)  # trash slot for ineligible rows
        dense = jnp.full((size + 1,), -1, jnp.int32)
        dense = dense.at[slot].set(jnp.arange(n, dtype=jnp.int32))
        return dense[:size]

    return fn


DENSE_MAX_SLOTS = 1 << 27  # 128M * 4B = 512MB hard cap
DENSE_SLACK = 4  # range may exceed live rows by this factor


def maybe_build_dense(table: DeviceJoinTable, keys, live) -> None:
    """Attach a direct-address table when the single int-like build key is
    unique and densely ranged (every TPC-H PK/FK edge qualifies).  Costs the
    build's ONE combined scalar fetch (which LEFT/semi probes and dynamic
    filters want anyway) plus one scatter program."""
    if len(keys) != 1 or table.num_rows == 0:
        return
    d, v = keys[0]
    kind = np.dtype(jnp.asarray(d).dtype).kind
    if kind not in "iu":
        return
    f = table._fetch()
    if len(f) < 5:
        return
    _, live_rows, max_run, kmin, kmax = f[:5]
    if max_run != 1 or kmax < kmin:
        return
    size = kmax - kmin + 1
    if size > DENSE_MAX_SLOTS or size > max(DENSE_SLACK * live_rows, 1 << 16):
        return
    flat = [jnp.asarray(d)]
    if v is not None:
        flat.append(jnp.asarray(v))
    if live is not None:
        flat.append(jnp.asarray(live))
    table.dense = _dense_build_fn(
        int(size), v is not None, live is not None, int(kmin))(*flat)
    table.dense_lo = int(kmin)


def build_table(keys: Sequence[tuple], live=None,
                num_rows: Optional[int] = None) -> DeviceJoinTable:
    """keys: [(data, valid|None), ...]; ``live`` masks dead (padded) build
    rows — they never match and don't count toward live_rows/has_null."""
    if not keys:  # cross join: every probe row pairs with every live row
        n = int(num_rows or 0)
        if live is not None:
            # live count stays a device scalar: fetched lazily, per BUILD,
            # via the table's one combined scalar sync — never per batch
            lr = jnp.sum(jnp.asarray(live))
            try:
                lr.copy_to_host_async()
            # tpulint: disable=error-taxonomy -- async-copy is a hint; backends without it keep the lazy fetch
            except Exception:
                pass
            return DeviceJoinTable(None, None, [], n, (False, lr, n))
        return DeviceJoinTable(None, None, [], n, (False, n, n))
    has_valid = tuple(v is not None for _, v in keys)
    flat: list = []
    datas = []
    for (d, v), hv in zip(keys, has_valid):
        d = jnp.asarray(d)
        datas.append(d)
        flat.append(d)
        if hv:
            flat.append(jnp.asarray(v))
    if live is not None:
        flat.append(jnp.asarray(live))
    want_range = (len(keys) == 1
                  and np.dtype(datas[0].dtype).kind in "iu")
    outs = _build_fn(len(keys), has_valid, live is not None,
                     want_range)(*flat)
    sh, perm = outs[0], outs[1]
    scalars = outs[2:]
    for s in scalars:  # start the D2H transfer; the sync happens lazily
        try:
            s.copy_to_host_async()
        # tpulint: disable=error-taxonomy -- async-copy is a hint; backends without it keep the lazy fetch
        except Exception:
            pass
    table = DeviceJoinTable(sh, perm, datas, int(datas[0].shape[0]), scalars)
    n = table.num_rows
    if K.hash_kernels_selected(n):
        # open-addressing index over the sorted hashes: pure device
        # programs, zero extra syncs; a kernel failure fails the build
        S = K.bucket(2 * n)
        table.hash_idx = JoinHashTable(
            *_hash_index_fn(S, n, K.hash_interpret())(sh), S)
    if want_range:
        maybe_build_dense(table, keys, live)
    return table


def _probe_hash(num_keys: int, has_valid: tuple, has_remap: tuple,
                has_live: bool, flat):
    """Traced: probe-side key hash with NULL/dictionary-miss rows folded to
    the probe sentinel — the normalization shared by the searchsorted and
    the open-addressing range implementations.  Returns (h, live)."""
    i = 0
    datas, valids = [], []
    for k in range(num_keys):
        d = flat[i]
        i += 1
        if has_remap[k]:
            d = flat[i][d]  # dictionary remap table gather
            i += 1
        datas.append(d)
        if has_valid[k]:
            valids.append(flat[i])
            i += 1
        else:
            valids.append(None)
    live = flat[i] if has_live else None
    h = K.hash_combine(datas)
    pnull = None
    for k, v in enumerate(valids):
        nm = ~v if v is not None else None
        if has_remap[k]:
            # remapped code -1 = value absent from the build dictionary:
            # cannot match (but is NOT a null probe for null-aware marks)
            miss = datas[k] < 0
            nm = miss if nm is None else (nm | miss)
        if nm is not None:
            pnull = nm if pnull is None else (pnull | nm)
    if pnull is not None:
        h = jnp.where(pnull, jnp.uint64(_SENT_PROBE), h)
    return h, live


@jit_memo("join._ranges_fn")
def _ranges_fn(num_keys: int, has_valid: tuple, has_live: bool,
               has_remap: tuple):
    @program("join.ranges")
    def fn(sorted_hash, *flat):
        h, live = _probe_hash(num_keys, has_valid, has_remap, has_live, flat)
        lo = K.searchsorted(sorted_hash, h, side="left")
        hi = K.searchsorted(sorted_hash, h, side="right")
        counts = hi - lo
        if live is not None:
            counts = jnp.where(live, counts, 0)
        # the build sentinel region (null/dead rows) must never match, and
        # null/dictionary-miss probes (folded to the probe sentinel by
        # _probe_hash) must not hit it
        counts = jnp.where(h >= jnp.uint64(_SENT_PROBE), 0, counts)
        return lo, counts, jnp.sum(counts)

    return fn


@jit_memo("join._hash_ranges_fn")
def _hash_ranges_fn(num_keys: int, has_valid: tuple, has_live: bool,
                    has_remap: tuple, S: int, interpret: bool):
    from ..ops import pallas_kernels as PK

    @program("join.hash_ranges")
    def fn(table_planes, slot_gid, group_lo, group_counts, *flat):
        h, live = _probe_hash(num_keys, has_valid, has_remap, has_live, flat)
        ok = h < jnp.uint64(_SENT_PROBE)
        if live is not None:
            ok = ok & live
        planes, h32 = _hash_planes(h)
        pgid = PK.hash_probe(table_planes, slot_gid, planes, h32, ok,
                             interpret=interpret)
        hit = pgid >= 0  # dead/null/miss probe rows come back -1
        safe = jnp.where(hit, pgid, 0)
        lo = group_lo[safe]
        counts = jnp.where(hit, group_counts[safe],
                           jnp.zeros((), group_counts.dtype))
        return lo, counts, jnp.sum(counts)

    return fn


def probe_ranges_device(table: DeviceJoinTable, probe_keys: Sequence[tuple],
                        remaps: Sequence[Optional[np.ndarray]], live=None):
    """probe_keys: [(data, valid|None), ...]; ``remaps[k]`` an optional
    host int32 table translating probe dictionary codes into the build code
    space (-1 = value absent).  Returns (lo, counts, total) with ALL THREE
    on device — ZERO host syncs; ``total`` comes back as a SyncGuard
    AsyncScalar whose D2H copy is already in flight."""
    has_valid = tuple(v is not None for _, v in probe_keys)
    has_remap = tuple(r is not None for r in remaps)
    flat: list = []
    for (d, v), r in zip(probe_keys, remaps):
        flat.append(jnp.asarray(d))
        if r is not None:
            flat.append(jnp.asarray(r))
        if v is not None:
            flat.append(jnp.asarray(v))
    if live is not None:
        flat.append(jnp.asarray(live))
    idx = table.hash_idx
    if idx is not None:
        lo, counts, total = _hash_ranges_fn(
            len(probe_keys), has_valid, live is not None, has_remap,
            idx.num_slots, K.hash_interpret())(
            idx.table_planes, idx.slot_gid, idx.group_lo,
            idx.group_counts, *flat)
    else:
        lo, counts, total = _ranges_fn(
            len(probe_keys), has_valid, live is not None, has_remap)(
            table.sorted_hash, *flat)
    return lo, counts, SG.async_scalar(total, "join.pair-total")


# ---------------------------------------------------------------------------
# padded-expand capacity planning

# the provable cap (n_probe * max_run lanes can NEVER overflow, because each
# probe row yields at most max_run candidates) is used whenever it costs at
# most this many times the minimal bucket; beyond that the adaptive estimate
# takes over and the overflow flag guards correctness
PROVABLE_SLACK = 8
EST_HEADROOM = 2          # estimated cap = headroom * max recent total
EST_WINDOW = 8            # totals remembered for the estimate


# Cross-execution feedback: the max observed candidate total per stable
# operator identity.  A fresh operator for the same plan shape seeds its
# estimate from the last execution instead of cold-starting at n_probe —
# a repartitioned probe arriving as one large page otherwise overflows its
# first cap and re-runs the whole pair program (correct, but double work).
# A seed is written whenever a count is observed: when a later ``plan`` /
# ``estimate`` call of the same planner finds its copy landed, and — for a
# unique-build probe that sees ONE batch and so never makes that later
# call — when the operator calls ``land`` at its input's end.  Seeds only
# grow (the max over every execution and every task that shares the
# identity).
# Correctness never depends on a seed: the overflow flag still guards
# every estimated cap, a stale seed only costs padding or one counted
# re-run.
_EST_SEEDS: dict = {}
_EST_SEEDS_CAP = 4096
_EST_SEEDS_LOCK = threading.Lock()


def reset_estimate_seeds_for_test() -> None:
    with _EST_SEEDS_LOCK:
        _EST_SEEDS.clear()


class ExpandPlanner:
    """Per-probe-operator planner for the padded-expand output bucket.

    Sync-free contract: ``plan`` never touches the device.  It prefers a cap
    PROVABLY >= the candidate total (from the build's max duplicate-hash
    run — one scalar fetch per build, amortized over every batch), falling
    back to an adaptive estimate fed by asynchronously-landed totals of
    previous batches.  On the estimated path the caller must check the
    expand program's overflow flag before emitting; ``observe`` feeds the
    planner so steady state converges to zero overflows.  With a ``key``
    the planner also reads/writes the process-global seed store, so the
    convergence carries across executions of the same plan shape.

    When a count lands: a batch's count is handed over in flight
    (``observe_async``) and folded in by the first later ``plan`` /
    ``estimate`` / ``land`` call that finds its copy on the host — never by
    waiting.  The unique-build probe calls ``land`` once its input has ended
    (and after its last blocking overflow poll, when the copies are there):
    without it an operator that sees one batch would neither learn its own
    count nor leave a seed for the next execution — which is still so for
    the pair and semi-join planners, whose cap never goes under the probe's
    width whatever the seed."""

    __slots__ = ("_totals", "_pending", "_key", "_observed")

    def __init__(self, key=None):
        self._key = key
        seed = None
        if key is not None:
            with _EST_SEEDS_LOCK:
                seed = _EST_SEEDS.get(key)
        self._totals: list[int] = [seed] if seed else []
        self._pending: list[SG.AsyncScalar] = []
        self._observed = False  # a count of THIS operator's batches landed

    def plan(self, n_probe: int, max_run: Optional[int]) -> tuple[int, bool]:
        """Returns (cap, provable).  ``max_run`` None = unknown (cross joins
        or builds whose scalars were never fetched)."""
        self.land()
        floor = K.bucket(max(n_probe, 1))
        bound = None  # provable candidate-total upper bound
        if max_run is not None and max_run >= 0:
            bound = max(n_probe * max(max_run, 1), 1)
            if K.bucket(bound) <= PROVABLE_SLACK * floor:
                return K.bucket(bound), True
        est = max(self._totals) * EST_HEADROOM if self._totals else n_probe
        cap = K.bucket(max(est, n_probe, 1))
        if bound is not None and cap >= K.bucket(bound):
            return K.bucket(bound), True  # estimate crossed the bound
        return cap, False

    def observe_async(self, total: SG.AsyncScalar) -> None:
        """Feed a batch's device total; it is read only once its async copy
        landed (non-blocking polls on later ``plan`` / ``estimate`` /
        ``land`` calls)."""
        self._pending.append(total)

    def estimate(self) -> tuple[Optional[int], Optional[str]]:
        """(largest total of the recent window, where it came from) — the
        unique-path density estimate.  The origin is ``"batch"`` once a
        count of this operator's own batches landed, ``"seed"`` while only
        the seed store's value of an earlier execution is known, and the
        pair is (None, None) when there is neither: a statement's first
        execution in a process, before its first count."""
        self.land()
        if not self._totals:
            return None, None
        return max(self._totals), "batch" if self._observed else "seed"

    def observe(self, total: int) -> None:
        """Fold one landed count in, and raise the seed store's value for
        this planner's key to it (seeds are written here and only here)."""
        total = int(total)
        self._observed = True
        self._totals.append(total)
        del self._totals[:-EST_WINDOW]
        if self._key is not None:
            with _EST_SEEDS_LOCK:
                if total > _EST_SEEDS.get(self._key, 0):
                    if (self._key not in _EST_SEEDS
                            and len(_EST_SEEDS) >= _EST_SEEDS_CAP):
                        _EST_SEEDS.clear()  # coarse bound; seeds re-learn
                    _EST_SEEDS[self._key] = total

    def land(self) -> None:
        """Fold in every handed-over count whose copy is on the host by now
        (``get_if_ready``: never a wait).  Called by ``plan`` / ``estimate``
        before they read, and by the operator at its input's end."""
        still = []
        for h in self._pending:
            v = h.get_if_ready()
            if v is None:
                still.append(h)
            else:
                self.observe(int(v))
        self._pending = still[-EST_WINDOW:]


MAX_INFLIGHT = 4  # deferred estimated-cap batches before the host backs off


class OverflowQueue:
    """Deferred commits for estimated-cap expand programs.

    An estimated cap can truncate candidates, and the only proof it didn't
    is the program's device overflow flag — but blocking on that flag per
    batch would reintroduce exactly the sync the padded expand removed.  So
    the speculative result parks here with the flag's async copy in flight;
    the flag of batch N lands while the host dispatches batch N+1, and
    ``drain`` commits it with a non-blocking poll.  The rare landed-True
    entry re-runs via its ``retry`` thunk at the exact (by then host-known)
    total before committing — results are never silently truncated, and the
    retry is counted in SyncStats (``expand_overflows``/``expand_retries``).

    Entries commit in push order; only ``drain(block=True)`` (input end /
    more than MAX_INFLIGHT parked) ever blocks."""

    __slots__ = ("_q",)

    def __init__(self):
        from collections import deque

        self._q = deque()

    def __len__(self) -> int:
        return len(self._q)

    def push(self, overflow: SG.AsyncScalar, result, retry, commit) -> None:
        self._q.append((overflow, result, retry, commit))

    def drain(self, block: bool = False) -> None:
        while self._q:
            h, res, retry, commit = self._q[0]
            if block or len(self._q) > MAX_INFLIGHT:
                v = h.get()
            else:
                v = h.get_if_ready()
                if v is None:
                    return
            self._q.popleft()
            if bool(v):
                SG.count_overflow()
                res = retry()
            commit(res)


# ---------------------------------------------------------------------------
# pair expansion + verify + residual + output gather: one program

_PAIR_CACHE: dict = {}
_PAIR_LOCK = threading.Lock()
_PAIR_CACHE_MAX = 1024

# dictionary identity tokens: monotonically assigned, NEVER recycled while
# the dictionary object is alive (checked via weakref), so a cache key built
# from tokens cannot alias a new dictionary at a recycled id() — which made
# eviction unsafe in the r4 id()-keyed design (advisor r4 medium).  With
# stable tokens the LRU eviction below is safe and nothing needs pinning.
_DICT_TOKENS: dict[int, tuple] = {}  # id(d) -> (weakref|strong-thunk, token)
_DICT_SEQ = 0


def _dict_token(d):
    global _DICT_SEQ
    if d is None:
        return None
    import weakref

    i = id(d)
    ent = _DICT_TOKENS.get(i)
    if ent is not None and ent[0]() is d:
        return ent[1]
    _DICT_SEQ += 1
    tok = _DICT_SEQ
    try:
        # the collection callback fires before the id can be reused, so it
        # cannot delete a newer entry — keeps the table bounded by LIVE dicts
        ref = weakref.ref(d, lambda _r, _i=i: _DICT_TOKENS.pop(_i, None))
    except TypeError:  # not weakrefable: keep it alive so the id can't recycle
        ref = (lambda _d=d: _d)
    _DICT_TOKENS[i] = (ref, tok)
    return tok


def _make_pair_fn(cap: int, num_keys: int, has_pvalid: tuple,
                  has_remap: tuple, pair_types, pair_dicts,
                  n_probe_cols: int, n_build_cols: int,
                  pcol_has_valid: tuple, bcol_has_valid: tuple,
                  residual: Optional[RowExpression],
                  need_matched: bool, semi: Optional[tuple],
                  donate: bool = False):
    """Build the pair program.  Flat operand order:
    lo, counts, total, perm,
    per probe key: data [remap] [valid],
    per probe col: data [valid],
    per build col: data [valid],
    build key datas.

    Besides the pair outputs the program emits ``overflow`` — a device bool
    flagging ``total > cap`` (candidates truncated; caller must re-run at a
    larger bucket).  ``donate`` releases the lo/counts operand buffers into
    the program (only safe when the caller provably never retries).

    ``semi``: None for a regular join; (null_aware, has_null_build,
    build_nonempty) for the semi-join mark variant (outputs (mark, valid)
    instead of gathered pair columns)."""
    res_fn = (compile_expression(residual, list(pair_types), list(pair_dicts))
              if residual is not None else None)

    def fn(lo, counts, total, perm, *flat):
        i = 0
        pkeys, pkvalids = [], []
        for k in range(num_keys):
            d = flat[i]
            i += 1
            if has_remap[k]:
                d = flat[i][d]
                i += 1
            pkeys.append(d)
            if has_pvalid[k]:
                pkvalids.append(flat[i])
                i += 1
            else:
                pkvalids.append(None)
        pcols = []
        for c in range(n_probe_cols):
            d = flat[i]
            i += 1
            v = None
            if pcol_has_valid[c]:
                v = flat[i]
                i += 1
            pcols.append((d, v))
        bcols = []
        for c in range(n_build_cols):
            d = flat[i]
            i += 1
            v = None
            if bcol_has_valid[c]:
                v = flat[i]
                i += 1
            bcols.append((d, v))
        bkeys = list(flat[i:i + num_keys])

        n_probe = pkeys[0].shape[0] if pkeys else (
            pcols[0][0].shape[0] if pcols else 1)
        nb = perm.shape[0]
        ends = jnp.cumsum(counts)
        starts = ends - counts
        slot = jnp.arange(cap)
        probe_id = jnp.clip(
            K.searchsorted(ends, slot, side="right"), 0, n_probe - 1)
        within = slot - starts[probe_id]
        build_pos = lo[probe_id] + within
        build_id = perm[jnp.clip(build_pos, 0, nb - 1)]
        ok = slot < total
        for pk, bk in zip(pkeys, bkeys):
            ok = ok & ~K._neq(pk[probe_id], bk[build_id])

        pairs = None
        if semi is None or res_fn is not None:
            pairs = [(d[probe_id], None if v is None else v[probe_id])
                     for d, v in pcols]
            pairs += [(d[build_id], None if v is None else v[build_id])
                      for d, v in bcols]
        if res_fn is not None:
            rd, rv = res_fn(pairs)
            rmask = rd if rv is None else (rd & rv)
            if getattr(rmask, "ndim", 1) == 0:
                rmask = jnp.broadcast_to(rmask, (cap,))
            ok = ok & rmask

        matched = None
        max_per_probe = None
        if need_matched or semi is not None:
            # per-probe match count: pairs are sorted by probe_id, so the
            # count is a prefix-sum difference at segment boundaries
            # (scatters serialize on TPU; this is all gathers)
            cs = jnp.cumsum(ok.astype(jnp.int64))
            pr = jnp.arange(n_probe)
            pend = K.searchsorted(probe_id, pr, side="right")
            pstart = K.searchsorted(probe_id, pr, side="left")
            hi2 = cs[jnp.maximum(pend - 1, 0)]
            lo2 = jnp.where(pstart > 0, cs[jnp.maximum(pstart - 1, 0)],
                            jnp.zeros((), jnp.int64))
            cnt = jnp.where(pend > pstart, hi2 - lo2, 0)
            matched = cnt > 0
            max_per_probe = jnp.max(cnt)

        overflow = jnp.asarray(total, jnp.int64) > cap
        if semi is not None:
            # three-valued NOT IN: a non-match is UNKNOWN (NULL mark) when
            # the probe key is NULL or the build side contains a NULL key;
            # IN over the empty set is FALSE even for NULL probes
            null_aware, has_null_build, build_nonempty = semi
            mark_valid = None
            if null_aware and build_nonempty:
                if has_null_build:
                    unknown = ~matched
                else:
                    null_probe = jnp.zeros((n_probe,), jnp.bool_)
                    for v in pkvalids:
                        if v is not None:
                            null_probe = null_probe | ~v
                    unknown = ~matched & null_probe
                mark_valid = ~unknown
            return (None, ok, matched, max_per_probe, (matched, mark_valid),
                    overflow)
        return pairs, ok, matched, max_per_probe, build_id, overflow

    return program("join.pairs", fn,
                   donate_argnums=(0, 1) if donate else ())  # lo, counts


def run_pairs(table: DeviceJoinTable, lo, counts, total,
              probe_keys, remaps, probe_cols, build_cols,
              pair_types, pair_dicts,
              residual: Optional[RowExpression],
              need_matched: bool, semi: Optional[tuple] = None,
              cap: Optional[int] = None, donate: bool = False):
    """Execute the pair program.  Returns (pair_cols|None, pair_live,
    matched|None, max_per_probe|None, mark|None, overflow) — ALL device
    arrays, zero host syncs.  ``pair_cols`` is [(data, valid|None), ...]
    over probe cols then build cols, gathered at the matched pairs.  The
    5th element is the device build_id per pair slot for a regular join, or
    the (data, valid) semi-join mark when ``semi`` is set.

    ``total`` may be a host int (the overflow retry: picks ``cap`` exactly)
    or a device scalar (``cap`` must then be given, chosen from build-side
    statistics — see :class:`ExpandPlanner`).  ``overflow`` is a device bool:
    True means the ``cap`` bucket truncated candidates and the batch must be
    re-run at a larger cap (results are otherwise a silent subset).
    ``donate`` releases lo/counts into the program — only when no retry can
    follow (the provable-cap path)."""
    if cap is None:
        cap = K.bucket(max(int(total), 1))
    donate = donate and K.donate_ok()
    has_pvalid = tuple(v is not None for _, v in probe_keys)
    has_remap = tuple(r is not None for r in remaps)
    pcol_has_valid = tuple(v is not None for _, v in probe_cols)
    bcol_has_valid = tuple(v is not None for _, v in build_cols)
    with _PAIR_LOCK:
        key = (cap, len(probe_keys), has_pvalid, has_remap,
               tuple(str(t) for t in pair_types),
               tuple(_dict_token(d) for d in pair_dicts),
               len(probe_cols), len(build_cols), pcol_has_valid,
               bcol_has_valid, residual, need_matched, semi, donate)
        prog = _PAIR_CACHE.pop(key, None)
        if prog is not None:  # re-insert: dict ordering = LRU order
            _PAIR_CACHE[key] = prog
    if prog is None:
        prog = _make_pair_fn(cap, len(probe_keys), has_pvalid, has_remap,
                             list(pair_types), list(pair_dicts),
                             len(probe_cols), len(build_cols),
                             pcol_has_valid, bcol_has_valid,
                             residual, need_matched, semi, donate)
        with _PAIR_LOCK:
            prog = _PAIR_CACHE.setdefault(key, prog)
            while len(_PAIR_CACHE) > _PAIR_CACHE_MAX:
                _PAIR_CACHE.pop(next(iter(_PAIR_CACHE)))

    flat: list = []
    for (d, v), r in zip(probe_keys, remaps):
        flat.append(jnp.asarray(d))
        if r is not None:
            flat.append(jnp.asarray(r))
        if v is not None:
            flat.append(jnp.asarray(v))
    for d, v in probe_cols:
        flat.append(jnp.asarray(d))
        if v is not None:
            flat.append(jnp.asarray(v))
    for d, v in build_cols:
        flat.append(jnp.asarray(d))
        if v is not None:
            flat.append(jnp.asarray(v))
    flat.extend(table.key_datas)
    total_dev = (total.value if isinstance(total, SG.AsyncScalar)
                 else jnp.asarray(total, jnp.int64))
    pairs, ok, matched, maxc, extra, overflow = prog(
        lo, counts, total_dev, table.perm, *flat)
    return pairs, ok, matched, maxc, extra, overflow


# ---------------------------------------------------------------------------
# unique-build INNER/RIGHT probe: ranges + count, then a width-adaptive gather
#
# Profile-driven split (r5): gathering every output column at the probe
# batch's full static width costs O(probe_lanes) random reads per column —
# for a selective join that is the dominant device cost.  So the probe runs
# as TWO programs:
#   A (`run_unique_ranges_device`)  — hash + binary search + exact verify;
#       returns (match mask, build row per lane, match count), the count as
#       an AsyncScalar.  Whether the build is unique at all is the table's
#       per-BUILD scalar fetch (`DeviceJoinTable.unique`): a duplicate-key
#       build takes the pair path.
#   B (`run_unique_gather`)  — if matches are sparse, compact (probe cols +
#       build ids) to a cap of `unique_cap_bucket(count)` lanes FIRST and
#       gather build columns at O(count); if dense, gather wide.  Residual and the
#       RIGHT-join matched-build scatter evaluate on the narrow lanes.
# B's width is sized from a match count in EVERY batch
# (LookupJoinOperator._add_inner_unique; `plan_unique_cap` draws the line):
#   - an earlier batch's count of the same operator, landed asynchronously
#     (origin "batch"), or the seed an earlier execution of the same plan
#     shape left in `_EST_SEEDS` (origin "seed"): no wait between A and B,
#     the cap is EST_HEADROOM x the estimate and B's overflow flag guards
#     it (OverflowQueue: a landed True re-runs the batch wide, counted);
#   - neither known — a statement's first execution in a process, on its
#     first batch — the batch's own count (origin "count"): one scalar
#     fetch after A, the per-build `table.unique` precedent.  That cap
#     cannot overflow.  Going wide for want of an estimate cost a probe of
#     one batch a task 20 full-width gathers a query (PERF.md section 6,
#     PR 33), and everything downstream rides B's output shape.
# A count lands, and the seed is written, when a later estimate() of the
# operator finds the copy on the host and when the operator's input ends
# (ExpandPlanner.land): a probe of one batch leaves its seed too.


@jit_memo("join._uranges_fn")
def _uranges_fn(num_keys: int, has_pvalid: tuple, has_remap: tuple,
                has_live: bool):
    @program("join.uranges")
    def fn(sorted_hash, perm, *flat):
        i = 0
        pkeys, pkvalids = [], []
        for k in range(num_keys):
            d = flat[i]
            i += 1
            if has_remap[k]:
                d = flat[i][d]
                i += 1
            pkeys.append(d)
            if has_pvalid[k]:
                pkvalids.append(flat[i])
                i += 1
            else:
                pkvalids.append(None)
        bkeys = list(flat[i:i + num_keys])
        i += num_keys
        live = flat[i] if has_live else None

        h = K.hash_combine(pkeys)
        pnull = None
        for k, v in enumerate(pkvalids):
            nm = ~v if v is not None else None
            if has_remap[k]:
                miss = pkeys[k] < 0
                nm = miss if nm is None else (nm | miss)
            if nm is not None:
                pnull = nm if pnull is None else (pnull | nm)
        if pnull is not None:
            h = jnp.where(pnull, jnp.uint64(_SENT_PROBE), h)
        nb = perm.shape[0]
        lo = jnp.clip(K.searchsorted(sorted_hash, h, side="left"), 0, nb - 1)
        found = (sorted_hash[lo] == h) & (h < jnp.uint64(_SENT_PROBE))
        bid = perm[lo]
        ok = found
        for pk, bk in zip(pkeys, bkeys):
            ok = ok & ~K._neq(pk, bk[bid])
        if live is not None:
            ok = ok & live
        return ok, bid, jnp.sum(ok)

    return fn


@jit_memo("join._dense_uranges_fn")
def _dense_uranges_fn(size: int, lo: int, has_pvalid: bool, has_remap: bool,
                      has_live: bool):
    """Program A over a direct-address build: ONE gather per probe row —
    no hashing, no binary search, no verify (direct addressing is exact)."""

    @program("join.dense_uranges")
    def fn(dense, *flat):
        i = 0
        d = flat[i]
        i += 1
        if has_remap:
            d = flat[i][d]
            i += 1
        valid = flat[i] if has_pvalid else None
        i += 1 if has_pvalid else 0
        live = flat[i] if has_live else None
        idx = d.astype(jnp.int64) - lo
        in_range = (idx >= 0) & (idx < size)
        if has_remap:
            in_range = in_range & (d >= 0)
        bid = dense[jnp.clip(idx, 0, size - 1)]
        ok = in_range & (bid >= 0)
        if valid is not None:
            ok = ok & valid
        if live is not None:
            ok = ok & live
        return ok, bid.astype(jnp.int64), jnp.sum(ok)

    return fn


def run_unique_ranges_device(table: DeviceJoinTable, probe_keys, remaps,
                             live=None):
    """Program A, sync-free: returns (ok_live, bid, count) with the count a
    SyncGuard AsyncScalar (D2H copy in flight, never blocked on).  The
    caller must already know the build is unique (``table.unique`` — one
    scalar fetch per BUILD); probing a duplicate-key build through this
    entry point silently drops matches."""
    has_pvalid = tuple(v is not None for _, v in probe_keys)
    has_remap = tuple(r is not None for r in remaps)
    if table.dense is not None and len(probe_keys) == 1:
        d, v = probe_keys[0]
        flat = [jnp.asarray(d)]
        if remaps[0] is not None:
            flat.append(jnp.asarray(remaps[0]))
        if v is not None:
            flat.append(jnp.asarray(v))
        if live is not None:
            flat.append(jnp.asarray(live))
        ok, bid, cnt = _dense_uranges_fn(
            int(table.dense.shape[0]), table.dense_lo,
            has_pvalid[0], has_remap[0], live is not None)(
            table.dense, *flat)
        return ok, bid, SG.async_scalar(cnt, "join.unique-count")
    flat = []
    for (d, v), r in zip(probe_keys, remaps):
        flat.append(jnp.asarray(d))
        if r is not None:
            flat.append(jnp.asarray(r))
        if v is not None:
            flat.append(jnp.asarray(v))
    flat.extend(table.key_datas)
    if live is not None:
        flat.append(jnp.asarray(live))
    ok, bid, cnt = _uranges_fn(
        len(probe_keys), has_pvalid, has_remap, live is not None)(
        table.sorted_hash, table.perm, *flat)
    return ok, bid, SG.async_scalar(cnt, "join.unique-count")


def _live_lanes_first(ok_live, cap: int):
    """Gather index of the first ``cap`` lanes in (live first, lane order):
    what ``argsort(~ok_live)[:cap]`` gives, from ONE sorted operand — a live
    lane's key is its index, a dead lane's its index plus the lane count.
    On a v5e 2.2-4.3 ms against argsort's 2.5-4.9 at 2^20 lanes and half
    its compile time; the searches and scatters over a running count of the
    live lanes (jnp.nonzero, cumsum + scatter, cumsum + binary search) cost
    6-75 ms there (tools/unique_gather_crossover.py, PR 33)."""
    n = ok_live.shape[0]
    dtype = jnp.int32 if 2 * n <= np.iinfo(np.int32).max else jnp.int64
    lane = jnp.arange(n, dtype=dtype)
    key = jnp.sort(jnp.where(ok_live, lane, lane + n))[:cap]
    return jnp.where(key >= n, key - n, key)


def _make_ugather_fn(cap: Optional[int], pair_types, pair_dicts,
                     n_probe_cols: int, n_build_cols: int,
                     pcol_has_valid: tuple, bcol_has_valid: tuple,
                     residual: Optional[RowExpression],
                     need_build_matched: bool):
    """Program B.  ``cap`` None = wide (lanes = probe width, probe columns
    pass through untouched); otherwise compact to ``cap`` lanes first."""
    res_fn = (compile_expression(residual, list(pair_types), list(pair_dicts))
              if residual is not None else None)

    def fn(ok_live, bid, *flat):
        i = 0
        pcols = []
        for c in range(n_probe_cols):
            d = flat[i]
            i += 1
            v = None
            if pcol_has_valid[c]:
                v = flat[i]
                i += 1
            pcols.append((d, v))
        bcols = []
        for c in range(n_build_cols):
            d = flat[i]
            i += 1
            v = None
            if bcol_has_valid[c]:
                v = flat[i]
                i += 1
            bcols.append((d, v))

        overflow = None
        if cap is not None:
            # truncation guard: more matches than compact lanes means the
            # batch must re-run wide (or at a bigger cap)
            overflow = jnp.sum(ok_live.astype(jnp.int64)) > cap
            order = _live_lanes_first(ok_live, cap)
            ok_c = ok_live[order]
            bid_c = bid[order]
            p_out = [(d[order], None if v is None else v[order])
                     for d, v in pcols]
        else:
            ok_c, bid_c = ok_live, bid
            p_out = list(pcols)
        b_out = [(d[bid_c], None if v is None else v[bid_c])
                 for d, v in bcols]
        if res_fn is not None:
            rd, rv = res_fn(p_out + b_out)
            rmask = rd if rv is None else (rd & rv)
            if getattr(rmask, "ndim", 1) == 0:
                rmask = jnp.broadcast_to(rmask, ok_c.shape)
            ok_c = ok_c & rmask
        build_matched = None
        if need_build_matched:
            nb = 0
            for d, _ in bcols:
                nb = d.shape[0]
                break
            build_matched = jnp.zeros((nb,), jnp.bool_).at[bid_c].max(ok_c)
        b_out = [(d, (ok_c if v is None else (v & ok_c)))
                 for d, v in b_out]
        return tuple(p_out), tuple(b_out), ok_c, build_matched, overflow

    return program("join.unique_gather", fn)


# what plan_unique_cap weighs, as read on a v5e by
# tools/unique_gather_crossover.py at 2^20 probe lanes (PERF.md section 6,
# PR 33; device seconds)
_GATHER_S_PER_WORD_LANE = 8.3e-9  # one 32-bit gather, a lane, either leg
_INDEX_S_PER_LANE = 3.5e-9        # the compact leg's index of the live lanes
_COMPACT_EXTRA_WORDS = 3          # ... which also gathers the mask and bid


def gather_words(cols) -> int:
    """32-bit gathers a lane that [(data, valid|None), ...] costs: the chip
    has no 64-bit vector path, so an 8-byte column is two (15.9-23.2 ms
    against 9.8 ms at 2^20 lanes), and a validity mask is one more."""
    return sum((2 if d.dtype.itemsize > 4 else 1) + (v is not None)
               for d, v in cols)


def unique_cap_bucket(count: int) -> int:
    """The compact leg's caps come in powers of FOUR.  Everything downstream
    of the probe is compiled for the cap's shape, a 64-bit sort among it
    (minutes each on a cold start: one `group_ids` at 2^15 lanes took the
    v5e compiler 224 s, PR 33), and a statement's first execution sizes
    each task's gather from its own count where later ones read the seed,
    the maximum over tasks: with every power of two a cap, the first query
    compiled a set of shapes no later one used.  A cap one size up costs
    the compact leg a few milliseconds (7.7 -> 11.8 ms from 2^15 to 2^16)."""
    cap = K.bucket(max(count, 1))
    return cap if cap.bit_length() % 2 else 2 * cap


def plan_unique_cap(n_lanes: int, count: int, probe_words: int,
                    build_words: int) -> Optional[int]:
    """Compact-vs-wide decision for program B: the cap to compact to, or
    None to stay wide.  ``count`` is the caller's estimate of the matches
    with its headroom (an earlier batch's or execution's count, guarded by
    the overflow flag, or the batch's own).  The wide leg gathers the build
    columns over all the lanes; the compact leg indexes the live lanes (one
    sort over the lanes) and gathers probe AND build columns at
    unique_cap_bucket(count) lanes: whichever is cheaper by the unit costs
    above.  For TPC-H Q3's probes (7 probe words; 7 and 3 build words) the
    costs cross between a cap of lanes/4 and lanes/2, and between lanes/8
    and lanes/4 — where the chip put them (43.5 against 60.4 ms and 83
    against 60; 15.9 against 26.9 and 33.1 against 26.9).  A join that
    emits no build column stays wide: its probe columns pass through for
    nothing."""
    cap = unique_cap_bucket(count)
    if cap >= n_lanes:
        return None
    wide_s = n_lanes * build_words * _GATHER_S_PER_WORD_LANE
    compact_s = (n_lanes * _INDEX_S_PER_LANE
                 + cap * (probe_words + build_words + _COMPACT_EXTRA_WORDS)
                 * _GATHER_S_PER_WORD_LANE)
    return cap if compact_s <= wide_s else None


def run_unique_gather(table: DeviceJoinTable, ok_live, bid,
                      cap: Optional[int],
                      probe_cols, build_cols, pair_types, pair_dicts,
                      residual: Optional[RowExpression],
                      need_build_matched: bool):
    """Program B dispatch at a planner-chosen ``cap`` (None = wide).
    Returns (probe_out|None, build_out, live, build_matched, overflow) —
    probe_out is None on the wide path (original columns pass through);
    ``overflow`` is a device bool on the compact path (True = cap truncated
    matches, caller must re-run wide or bigger) and None on the wide path,
    which cannot overflow."""
    if cap is None and residual is None:
        # wide + residual-free: probe columns pass through OUTSIDE the
        # program (feeding them through a jit identity would copy them)
        probe_cols = []
    pcol_has_valid = tuple(v is not None for _, v in probe_cols)
    bcol_has_valid = tuple(v is not None for _, v in build_cols)
    with _PAIR_LOCK:
        key = ("ugather", cap, tuple(str(t) for t in pair_types),
               tuple(_dict_token(d) for d in pair_dicts),
               len(probe_cols), len(build_cols), pcol_has_valid,
               bcol_has_valid, residual, need_build_matched)
        prog = _PAIR_CACHE.pop(key, None)
        if prog is not None:
            _PAIR_CACHE[key] = prog
    if prog is None:
        prog = _make_ugather_fn(cap, list(pair_types), list(pair_dicts),
                                len(probe_cols), len(build_cols),
                                pcol_has_valid, bcol_has_valid,
                                residual, need_build_matched)
        with _PAIR_LOCK:
            prog = _PAIR_CACHE.setdefault(key, prog)
            while len(_PAIR_CACHE) > _PAIR_CACHE_MAX:
                _PAIR_CACHE.pop(next(iter(_PAIR_CACHE)))
    flat: list = []
    for d, v in probe_cols:
        flat.append(jnp.asarray(d))
        if v is not None:
            flat.append(jnp.asarray(v))
    for d, v in build_cols:
        flat.append(jnp.asarray(d))
        if v is not None:
            flat.append(jnp.asarray(v))
    p_out, b_out, live, bm, overflow = prog(ok_live, bid, *flat)
    return (None if cap is None else p_out), b_out, live, bm, overflow


# ---------------------------------------------------------------------------
# unique-build probe: the sync-free static-shape fast path

def _make_unique_fn(num_keys: int, has_pvalid: tuple, has_remap: tuple,
                    pair_types, pair_dicts,
                    n_probe_cols: int, n_build_cols: int,
                    pcol_has_valid: tuple, bcol_has_valid: tuple,
                    residual: Optional[RowExpression],
                    need_build_matched: bool, semi: Optional[tuple],
                    has_live: bool,
                    dense: Optional[tuple] = None):
    """Probe program for builds whose live hashes are all distinct (every
    FK->PK join): each probe row matches at most one build row, so the
    output keeps the PROBE batch's static shape — probe columns pass
    through untouched, build columns arrive as a single gather, and the
    match mask becomes the live mask.  No candidate-count sync, no
    expansion, no data-dependent shapes (reference contrast:
    operator/join/LookupJoinOperator.java:37 emits variable-length pages;
    here variable cardinality is impossible by construction).

    Flat operand order: per probe key: data [remap] [valid];
    per probe col: data [valid]; per build col: data [valid];
    build key datas; [live]."""
    res_fn = (compile_expression(residual, list(pair_types), list(pair_dicts))
              if residual is not None else None)

    def fn(sorted_hash, perm, *flat):
        i = 0
        pkeys, pkvalids = [], []
        for k in range(num_keys):
            d = flat[i]
            i += 1
            if has_remap[k]:
                d = flat[i][d]
                i += 1
            pkeys.append(d)
            if has_pvalid[k]:
                pkvalids.append(flat[i])
                i += 1
            else:
                pkvalids.append(None)
        pcols = []
        for c in range(n_probe_cols):
            d = flat[i]
            i += 1
            v = None
            if pcol_has_valid[c]:
                v = flat[i]
                i += 1
            pcols.append((d, v))
        bcols = []
        for c in range(n_build_cols):
            d = flat[i]
            i += 1
            v = None
            if bcol_has_valid[c]:
                v = flat[i]
                i += 1
            bcols.append((d, v))
        bkeys = list(flat[i:i + num_keys])
        i += num_keys
        live = flat[i] if has_live else None

        if dense is not None:
            # direct-address lookup: sorted_hash carries the dense table
            size, dlo = dense
            nb = bkeys[0].shape[0] if bkeys else 0
            idx = pkeys[0].astype(jnp.int64) - dlo
            in_range = (idx >= 0) & (idx < size)
            if has_remap[0]:
                in_range = in_range & (pkeys[0] >= 0)
            slot = sorted_hash[jnp.clip(idx, 0, size - 1)]
            ok = in_range & (slot >= 0)
            bid = jnp.clip(slot.astype(jnp.int64), 0, max(nb - 1, 0))
            if pkvalids[0] is not None:
                ok = ok & pkvalids[0]
        else:
            h = K.hash_combine(pkeys)
            pnull = None
            for k, v in enumerate(pkvalids):
                nm = ~v if v is not None else None
                if has_remap[k]:
                    miss = pkeys[k] < 0
                    nm = miss if nm is None else (nm | miss)
                if nm is not None:
                    pnull = nm if pnull is None else (pnull | nm)
            if pnull is not None:
                h = jnp.where(pnull, jnp.uint64(_SENT_PROBE), h)
            nb = perm.shape[0]
            lo = jnp.clip(K.searchsorted(sorted_hash, h, side="left"),
                          0, nb - 1)
            found = (sorted_hash[lo] == h) & (h < jnp.uint64(_SENT_PROBE))
            bid = perm[lo]
            ok = found
            for pk, bk in zip(pkeys, bkeys):
                ok = ok & ~K._neq(pk, bk[bid])

        bgather = [(d[bid], None if v is None else v[bid]) for d, v in bcols]
        if res_fn is not None:
            rd, rv = res_fn(list(pcols) + bgather)
            rmask = rd if rv is None else (rd & rv)
            if getattr(rmask, "ndim", 1) == 0:
                rmask = jnp.broadcast_to(rmask, ok.shape)
            ok = ok & rmask
        ok_live = ok if live is None else (ok & live)

        build_matched = None
        if need_build_matched:
            build_matched = jnp.zeros((nb,), jnp.bool_).at[bid].max(ok_live)

        if semi is not None:
            null_aware, has_null_build, build_nonempty = semi
            mark_valid = None
            if null_aware and build_nonempty:
                if has_null_build:
                    unknown = ~ok
                else:
                    null_probe = jnp.zeros(ok.shape, jnp.bool_)
                    for v in pkvalids:
                        if v is not None:
                            null_probe = null_probe | ~v
                    unknown = ~ok & null_probe
                mark_valid = ~unknown
            return (), ok_live, build_matched, (ok, mark_valid)

        out = tuple((d, (ok_live if v is None else (v & ok_live)))
                    for d, v in bgather)
        return out, ok_live, build_matched, None

    return program("join.unique", fn)


def run_unique(table: DeviceJoinTable, probe_keys, remaps,
               probe_cols, build_cols, pair_types, pair_dicts,
               residual: Optional[RowExpression],
               need_build_matched: bool, semi: Optional[tuple] = None,
               live=None):
    """Execute the unique-build probe.  Returns (build_out, ok_live,
    build_matched|None, mark|None) — all device, ZERO host syncs.
    ``build_out`` is [(data, valid)] over build cols gathered per probe row
    (valid already folds the match mask, so unmatched rows read NULL);
    ``ok_live`` is the per-probe match mask & live."""
    has_pvalid = tuple(v is not None for _, v in probe_keys)
    has_remap = tuple(r is not None for r in remaps)
    pcol_has_valid = tuple(v is not None for _, v in probe_cols)
    bcol_has_valid = tuple(v is not None for _, v in build_cols)
    dense = None
    if table.dense is not None and len(probe_keys) == 1:
        dense = (int(table.dense.shape[0]), table.dense_lo)
    with _PAIR_LOCK:
        key = ("unique", len(probe_keys), has_pvalid, has_remap,
               tuple(str(t) for t in pair_types),
               tuple(_dict_token(d) for d in pair_dicts),
               len(probe_cols), len(build_cols), pcol_has_valid,
               bcol_has_valid, residual, need_build_matched, semi,
               live is not None, dense)
        prog = _PAIR_CACHE.pop(key, None)
        if prog is not None:
            _PAIR_CACHE[key] = prog
    if prog is None:
        prog = _make_unique_fn(len(probe_keys), has_pvalid, has_remap,
                               list(pair_types), list(pair_dicts),
                               len(probe_cols), len(build_cols),
                               pcol_has_valid, bcol_has_valid,
                               residual, need_build_matched, semi,
                               live is not None, dense)
        with _PAIR_LOCK:
            prog = _PAIR_CACHE.setdefault(key, prog)
            while len(_PAIR_CACHE) > _PAIR_CACHE_MAX:
                _PAIR_CACHE.pop(next(iter(_PAIR_CACHE)))

    flat: list = []
    for (d, v), r in zip(probe_keys, remaps):
        flat.append(jnp.asarray(d))
        if r is not None:
            flat.append(jnp.asarray(r))
        if v is not None:
            flat.append(jnp.asarray(v))
    for d, v in probe_cols:
        flat.append(jnp.asarray(d))
        if v is not None:
            flat.append(jnp.asarray(v))
    for d, v in build_cols:
        flat.append(jnp.asarray(d))
        if v is not None:
            flat.append(jnp.asarray(v))
    flat.extend(table.key_datas)
    if live is not None:
        flat.append(jnp.asarray(live))
    first = table.dense if dense is not None else table.sorted_hash
    return prog(first, table.perm, *flat)
