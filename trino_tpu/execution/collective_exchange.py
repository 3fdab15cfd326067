"""Device-resident REPARTITION edges: the engine's ICI collective data plane.

When a REPARTITION edge connects two device-resident stages with equal task
counts (the PARTIAL->FINAL aggregation split being the canonical case), the
host exchange (PartitionedOutputSink hashing rows on host + pull-token
buffers) is replaced by ONE jitted ``shard_map`` program over a
``jax.sharding.Mesh``: every producer task deposits its padded device batch,
the last depositor launches the program — local hash routing +
``jax.lax.all_to_all`` per column — and each consumer task reads its
device shard.  Row data never touches the host; XLA lowers the all_to_all
onto ICI on a real TPU slice.

This is the engine-integrated form of ``parallel/distributed.py`` (which
demonstrates the same shuffle fused with static aggregation), standing in
for the reference's PagePartitioner + HTTP exchange
(operator/output/PagePartitioner.java:134, AddExchanges.java:138 choosing
FIXED_HASH_DISTRIBUTION) per SURVEY §2.4's collective mapping.
"""

from __future__ import annotations

import threading
from ..caching.executable_cache import jit_memo, program
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..exec import kernels as K
from ..exec import syncguard as SG
from ..exec.operators import Operator, _concat_device
from ..spi.batch import Column, ColumnBatch, unify_dictionaries
from ..spi.errors import (GENERIC_INTERNAL_ERROR, PAGE_TRANSPORT_TIMEOUT,
                          TrinoError)

__all__ = ["CollectiveRepartitionExchange", "CollectiveOutputSink",
           "CollectiveSourceOperator", "collectives_available"]

_AXIS = "x"

# deposits at or below this row bucket use the broadcast lane layout (one
# program, no extra host sync — right for slot-capped partial-agg states);
# larger deposits take the tiled sorted-bucket path (local sort by owner,
# per-destination tiles, ~1x data volume instead of n_dev x).  Tests force
# the tiled path by setting this to 0.
TILED_THRESHOLD_ROWS = 8192


def collectives_available(n_tasks: int) -> bool:
    try:
        return len(jax.devices()) >= n_tasks and n_tasks > 1
    except Exception:
        return False


@jit_memo("collective._shuffle_program")
def _shuffle_program(n_dev: int, n_cols: int, dtypes: tuple,
                     valid_flags: tuple, key_idx: tuple, cap: int):
    """One jitted shard_map: route rows of the local [cap] block to owner
    devices by key hash; outputs hold [n_dev*cap] lanes per device.

    Capacity contract (same as parallel/distributed.py): the lane layout
    sends a [n_dev, cap] block per column — each consumer receives
    ``n_dev*cap`` live-masked lanes.  Sized for the partial-state batches
    this edge carries (group slots, not raw rows); a tiled sorted-bucket
    all_to_all is the follow-up for raw-row repartitions.

    Routing hashes the trailing ``route key`` inputs, which the caller
    builds as VALUE hashes for dictionary columns — matching the host
    exchange's _dict_value_hashes routing so mixed collective/host edges of
    one join agree on row ownership."""
    mesh = Mesh(jax.devices()[:n_dev], (_AXIS,))
    n_keys = len(key_idx)

    def local(*flat):
        datas = list(flat[:n_cols])
        n_valid = sum(valid_flags)
        valids_in = list(flat[n_cols:n_cols + n_valid])
        route_keys = list(flat[n_cols + n_valid:n_cols + n_valid + n_keys])
        live = flat[-1]
        valids: list = []
        vi = 0
        for i in range(n_cols):
            if valid_flags[i]:
                valids.append(valids_in[vi])
                vi += 1
            else:
                valids.append(None)
        # ---- destination by key hash (NULL keys -> device 0) -------------
        h = K.hash_combine(route_keys)
        dest = (h % jnp.uint64(n_dev)).astype(jnp.int32)
        null_key = None
        for i in key_idx:
            if valids[i] is not None:
                nk = ~valids[i]
                null_key = nk if null_key is None else (null_key | nk)
        if null_key is not None:
            dest = jnp.where(null_key, 0, dest)
        # ---- lane layout [n_dev, cap]: lane (d, s) live iff row s -> d ----
        lane_live = live[None, :] & (
            dest[None, :] == jnp.arange(n_dev, dtype=jnp.int32)[:, None])

        def shuffle(x):
            lanes = jnp.broadcast_to(x[None, :], (n_dev, cap))
            out = jax.lax.all_to_all(lanes, _AXIS, 0, 0, tiled=False)
            return out.reshape(n_dev * cap)

        out_datas = [shuffle(d) for d in datas]
        out_valids = [None if v is None else shuffle(v) for v in valids]
        out_live = jax.lax.all_to_all(lane_live, _AXIS, 0, 0,
                                      tiled=False).reshape(n_dev * cap)
        flat_out = out_datas + [v for v in out_valids if v is not None]
        return (*flat_out, out_live)

    n_in = n_cols + sum(valid_flags) + n_keys + 1
    n_out = n_cols + sum(valid_flags) + 1
    return mesh, program("collective.shuffle", jax.shard_map(
        local, mesh=mesh,
        in_specs=tuple([P(_AXIS)] * n_in),
        out_specs=tuple([P(_AXIS)] * n_out),
        check_vma=False,
    ))


@jit_memo("collective._sort_by_dest_program")
def _sort_by_dest_program(n_dev: int, n_cols: int, valid_flags: tuple,
                          key_idx: tuple, cap: int):
    """Tiled path, stage 1: per device, route rows to owners by key hash and
    locally sort them by destination (stable argsort — all dense vector
    work); returns the dest-sorted columns plus per-destination counts.
    The [n_dev, n_dev] counts matrix is the only host-visible output — one
    small pull picks the global tile size (the single data-dependent shape
    of the shuffle, same contract as the join's candidate-total sync)."""
    mesh = Mesh(jax.devices()[:n_dev], (_AXIS,))
    n_keys = len(key_idx)

    def local(*flat):
        datas = list(flat[:n_cols])
        n_valid = sum(valid_flags)
        valids_in = list(flat[n_cols:n_cols + n_valid])
        route_keys = list(flat[n_cols + n_valid:n_cols + n_valid + n_keys])
        live = flat[-1]
        valids: list = []
        vi = 0
        for i in range(n_cols):
            if valid_flags[i]:
                valids.append(valids_in[vi])
                vi += 1
            else:
                valids.append(None)
        h = K.hash_combine(route_keys)
        dest = (h % jnp.uint64(n_dev)).astype(jnp.int32)
        # NULL keys -> consumer 0 (same contract as _shuffle_program and
        # the host exchange's partition_assignments)
        null_key = None
        for i in key_idx:
            if valids[i] is not None:
                nk = ~valids[i]
                null_key = nk if null_key is None else (null_key | nk)
        if null_key is not None:
            dest = jnp.where(null_key, 0, dest)
        dest = jnp.where(live, dest, n_dev)  # dead rows sort last
        order = jnp.argsort(dest, stable=True)
        dest_sorted = dest[order]
        r = jnp.arange(n_dev, dtype=dest_sorted.dtype)
        counts = (K.searchsorted(dest_sorted, r, side="right")
                  - K.searchsorted(dest_sorted, r)).astype(jnp.int32)
        out = [d[order] for d in datas]
        out += [v[order] for v in valids if v is not None]
        return (*out, counts)

    n_in = n_cols + sum(valid_flags) + n_keys + 1
    n_out = n_cols + sum(valid_flags) + 1
    return mesh, program("collective.sort_by_dest", jax.shard_map(
        local, mesh=mesh,
        in_specs=tuple([P(_AXIS)] * n_in),
        out_specs=tuple([P(_AXIS)] * n_out),
        check_vma=False,
    ))


@jit_memo("collective._tiled_all_to_all_program")
def _tiled_all_to_all_program(n_dev: int, n_cols: int, valid_flags: tuple,
                              cap: int, tile: int):
    """Tiled path, stage 2: pack each destination's dest-sorted run into a
    [n_dev, tile] lane block and all_to_all it over ICI; consumers flatten
    to n_dev*tile live-masked lanes.  Data volume per device is ~its own
    row count padded to tiles — the raw-row repartition the round-3
    exchange deferred (PagePartitioner.partitionPage equivalent)."""
    mesh = Mesh(jax.devices()[:n_dev], (_AXIS,))

    def local(*flat):
        datas = list(flat[:n_cols])
        n_valid = sum(valid_flags)
        valids_in = list(flat[n_cols:n_cols + n_valid])
        counts = flat[-1]
        ends = jnp.cumsum(counts)
        starts = ends - counts
        d_idx = jnp.arange(n_dev, dtype=jnp.int32)[:, None]
        s_idx = jnp.arange(tile, dtype=jnp.int32)[None, :]
        row = jnp.clip(starts[:, None] + s_idx, 0, cap - 1)
        lane_live = s_idx < counts[:, None]

        def shuffle(x):
            lanes = jnp.where(lane_live, x[row], jnp.zeros((), x.dtype)) \
                if x.dtype != jnp.bool_ else (x[row] & lane_live)
            out = jax.lax.all_to_all(lanes, _AXIS, 0, 0, tiled=False)
            return out.reshape(n_dev * tile)

        out = [shuffle(d) for d in datas]
        vi = 0
        for i in range(n_cols):
            if valid_flags[i]:
                out.append(shuffle(valids_in[vi]))
                vi += 1
        out_live = jax.lax.all_to_all(
            lane_live, _AXIS, 0, 0, tiled=False).reshape(n_dev * tile)
        return (*out, out_live)

    n_in = n_cols + sum(valid_flags) + 1
    n_out = n_cols + sum(valid_flags) + 1
    return mesh, program("collective.tiled_all_to_all", jax.shard_map(
        local, mesh=mesh,
        in_specs=tuple([P(_AXIS)] * n_in),
        out_specs=tuple([P(_AXIS)] * n_out),
        check_vma=False,
    ))


class CollectiveRepartitionExchange:
    """Rendezvous for one REPARTITION edge: ``n_tasks`` producers deposit,
    consumers take their device shard after the collective runs."""

    def __init__(self, n_tasks: int, key_channels: Sequence[int],
                 names: Sequence[str], types: Sequence):
        self.n = n_tasks
        self.key_channels = tuple(key_channels)
        self.names = list(names)
        self.types = list(types)
        self._deposits: list[Optional[ColumnBatch]] = [None] * n_tasks
        self._count = 0
        self._lock = threading.Lock()
        self._done = threading.Event()
        self._results: list[Optional[ColumnBatch]] = [None] * n_tasks
        self._error: Optional[BaseException] = None

    # ---------------------------------------------------------- producers
    def deposit(self, task_index: int, batches: list[ColumnBatch]) -> None:
        if batches:
            batch = _concat_device(batches)
        else:
            batch = ColumnBatch(self.names, [
                Column(t, np.empty(0, t.storage_dtype)) for t in self.types])
        run_it = False
        with self._lock:
            self._deposits[task_index] = batch
            self._count += 1
            run_it = self._count == self.n
        if run_it:
            try:
                self._run_collective()
            except BaseException as e:  # surfaced to every waiting consumer
                self._error = e
            self._done.set()

    def abort(self) -> None:
        self._error = RuntimeError("collective exchange aborted")
        self._done.set()

    # ----------------------------------------------------------- the program
    def _run_collective(self) -> None:
        deposits = list(self._deposits)
        n = self.n
        cap = K.bucket(max(max(b.num_rows for b in deposits), 1))

        # unify dictionary columns across deposits (host work over the tiny
        # dictionaries only; codes are remapped with a device gather)
        unified_dicts: list = []
        for ci, t in enumerate(self.types):
            if t.is_dictionary_encoded:
                cols = [b.columns[ci] for b in deposits]
                cols = unify_dictionaries(cols)
                for b, c in zip(deposits, cols):
                    b.columns[ci] = c
                unified_dicts.append(cols[0].dictionary)
            else:
                unified_dicts.append(None)

        valid_flags = tuple(
            any(b.columns[ci].valid is not None for b in deposits)
            for ci in range(len(self.types)))
        tiled = cap > TILED_THRESHOLD_ROWS

        if tiled:
            mesh, prog = _sort_by_dest_program(
                n, len(self.types), valid_flags, self.key_channels, cap)
        else:
            mesh, prog = _shuffle_program(
                n, len(self.types),
                tuple(np.dtype(t.storage_dtype).str for t in self.types),
                valid_flags, self.key_channels, cap)

        def pad(x, dtype, fill=0):
            x = jnp.asarray(x)
            if x.shape[0] < cap:
                x = jnp.concatenate(
                    [x, jnp.full((cap - x.shape[0],), fill, x.dtype)])
            return x

        def dev_col(c, dtype):
            # compressed execution: an RLE deposit expands device-side from
            # ONE scalar (rows past the deposit are dead lanes anyway), so
            # the run never crosses the host/device boundary expanded
            if c.encoding == "RLE":
                return K.rle_fill(c.rle_value, cap)
            return pad(c.data, dtype)

        # global [n*cap] arrays: shard i lives on mesh device i
        def make_global(per_task, dtype):
            sharding = NamedSharding(mesh, P(_AXIS))
            shards = [
                jax.device_put(per_task[i], mesh.devices[i])
                for i in range(n)
            ]
            return jax.make_array_from_single_device_arrays(
                (n * cap,), sharding, shards)

        flat = []
        for ci, t in enumerate(self.types):
            flat.append(make_global(
                [dev_col(deposits[i].columns[ci], t.storage_dtype)
                 for i in range(n)], t.storage_dtype))
        for ci in range(len(self.types)):
            if valid_flags[ci]:
                flat.append(make_global(
                    [pad(deposits[i].columns[ci].valid
                         if deposits[i].columns[ci].valid is not None
                         else jnp.ones(deposits[i].num_rows, jnp.bool_),
                         np.bool_) for i in range(n)], np.bool_))
        # route keys: dictionary columns hash by VALUE (the host exchange's
        # _dict_value_hashes scheme) so every edge of a join routes equal
        # values to the same consumer regardless of per-edge code spaces
        from .task import _dict_value_hashes

        for ki in self.key_channels:
            t = self.types[ki]
            per_task = []
            for i in range(n):
                c = deposits[i].columns[ki]
                if t.is_dictionary_encoded:
                    d = unified_dicts[ki]
                    vh = _dict_value_hashes(d) if d is not None else None
                    codes = jnp.asarray(c.data)
                    rk = (jnp.asarray(vh)[codes] if vh is not None and len(vh)
                          else jnp.zeros(c.data.shape[0], jnp.int64))
                else:
                    rk = c.data
                per_task.append(pad(rk, None))
            flat.append(make_global(per_task, None))
        lives = []
        for i in range(n):
            b = deposits[i]
            lv = (jnp.asarray(b.live) if b.live is not None
                  else jnp.ones(b.num_rows, jnp.bool_))
            lives.append(pad(lv, np.bool_, fill=False))
        flat.append(make_global(lives, np.bool_))

        outs = prog(*flat)
        if tiled:
            # stage 1 out: dest-sorted columns + per-destination counts;
            # ONE small pull picks the tile, then stage 2 moves the rows
            counts = np.asarray(
                SG.fetch(outs[-1], "exchange.tile-counts")).reshape(n, n)
            tile = K.bucket(max(int(counts.max()), 1))
            _, prog2 = _tiled_all_to_all_program(
                n, len(self.types), valid_flags, cap, tile)
            outs = prog2(*outs)
        out_live = outs[-1]
        out_datas = outs[:len(self.types)]
        out_valids_flat = list(outs[len(self.types):-1])
        out_valids: list = []
        for ci in range(len(self.types)):
            out_valids.append(out_valids_flat.pop(0) if valid_flags[ci] else None)

        # per-consumer shards: addressable single-device arrays
        def shards_of(garr):
            by_dev = {s.device: s.data for s in garr.addressable_shards}
            return [by_dev[mesh.devices[i]] for i in range(n)]

        data_shards = [shards_of(d) for d in out_datas]
        valid_shards = [None if v is None else shards_of(v) for v in out_valids]
        live_shards = shards_of(out_live)
        if any(d is not None for d in unified_dicts):
            # dictionary codes crossed the shuffle as resident int32 lanes —
            # each consumer shard is one code page that never decoded
            from ..telemetry import metrics as tm

            tm.ENCODING_EXCHANGE_CODE_PAGES.inc(n)
        for i in range(n):
            cols = []
            for ci, t in enumerate(self.types):
                cols.append(Column(
                    t, data_shards[ci][i],
                    None if valid_shards[ci] is None else valid_shards[ci][i],
                    unified_dicts[ci]))
            self._results[i] = ColumnBatch(list(self.names), cols,
                                           live_shards[i])

    # ----------------------------------------------------------- consumers
    def take(self, task_index: int,
             timeout: Optional[float] = None) -> ColumnBatch:
        """Blocking take under the PR-5 timeout policy: the default comes
        from TRINO_TPU_EXCHANGE_STALL_S (execution/task.py) instead of a
        hard-coded constant, and a stall raises a *retryable*
        PAGE_TRANSPORT_TIMEOUT — the same contract the HTTP exchange client
        carries, so retry_policy=QUERY treats a wedged collective exactly
        like a wedged page transport."""
        if timeout is None:
            from .task import STALL_TIMEOUT_S

            timeout = STALL_TIMEOUT_S
        from ..telemetry import profiler

        t0 = profiler.now() if profiler.enabled() else 0.0
        ok = self._done.wait(timeout)
        if t0:
            profiler.event(profiler.EXCHANGE, "collective.take", t0,
                           stalled=not ok)
        if not ok:
            raise TrinoError(
                PAGE_TRANSPORT_TIMEOUT,
                f"collective exchange stalled after {timeout:.0f}s")
        if self._error is not None:
            if isinstance(self._error, TrinoError):
                raise self._error      # keep the original classification
            raise TrinoError(
                GENERIC_INTERNAL_ERROR,
                f"collective exchange failed: {self._error}") from self._error
        return self._results[task_index]


class CollectiveOutputSink(Operator):
    """Producer-side terminal: buffers device batches, deposits at finish."""

    def __init__(self, exchange: CollectiveRepartitionExchange, task_index: int):
        self.exchange = exchange
        self.task_index = task_index
        self._batches: list[ColumnBatch] = []

    def add_input(self, batch: ColumnBatch) -> None:
        if batch.num_rows:
            self._batches.append(batch)

    def finish_input(self) -> None:
        super().finish_input()
        self.exchange.deposit(self.task_index, self._batches)

    def is_finished(self) -> bool:
        return self.input_done


class CollectiveSourceOperator(Operator):
    """Consumer-side source: emits this task's device shard once."""

    blocking = True  # see RemoteExchangeSourceOperator

    def __init__(self, exchange: CollectiveRepartitionExchange, task_index: int):
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
