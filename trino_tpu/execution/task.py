"""Task-side exchange operators + task execution.

RemoteExchangeSourceOperator = operator/ExchangeOperator.java:44 (pulls
upstream pages through an ExchangeClient); PartitionedOutputSink =
operator/output/PartitionedOutputOperator.java:47 + TaskOutputOperator
(hash/broadcast/gather placement into the task's OutputBuffer).

Where a page is densified (pulled to the host and cut to its live rows):
where it is SERIALIZED, and nowhere else.  ``serialize_batch`` does it for
the wire (``serde=True``: the HTTP worker plane, FTE's DurableSpoolWriter),
for a speculation tee's spool and for the statement protocol's result
pages; the heavy-hitter sketch and the consumers that read rows on the host
(MergeSourceOperator's heap merge, adaptive.Router, the output stage) ask
for host rows themselves.  An exchange between two tasks of one process is
an enqueue: the page crosses it as the producer made it -- on the device,
bucket-shaped, under its ``live`` mask -- because the consumer's first step
is a device program again, and a page cut to an arbitrary row count would
make every program behind the exchange compile once a count.  A page from a
remote producer arrives dense and is padded to its bucket on arrival
(``_arrived``), so the operators see powers of two from every source.
"""

from __future__ import annotations

import os
import time
import zlib
from collections import deque
from typing import Optional, Sequence

import numpy as np

from ..exec import join_exec as JX
from ..exec import kernels as K
from ..exec import syncguard as SG
from ..exec.operators import _COMPACT_MIN_LANES, Operator, _concat_device
from ..exec.revoking import batch_device_nbytes
from ..spi.batch import Column, ColumnBatch, encoded_exec, pad_to_bucket
from ..telemetry import metrics as tm
from .exchange import ExchangeClient, OutputBuffer, known_live_rows
from .serde import PageStreamEncoder, deserialize_batch, serialize_batch

__all__ = ["RemoteExchangeSourceOperator", "PartitionedOutputSink",
           "SerializedPage", "maybe_deserialize"]

# How long an exchange consumer waits with NO upstream page before declaring
# a stall.  First-run XLA compiles at large shapes can exceed several
# minutes on CPU (the self-measured bench baseline), so the default is
# generous; tests that probe deadlocks can lower it via the env knob.
STALL_TIMEOUT_S = float(os.environ.get("TRINO_TPU_EXCHANGE_STALL_S", "1800"))


class SerializedPage:
    """A batch serialized to wire bytes (execution/serde.py) — what a real
    network transport would carry (buffer/PageSerializer.java:58)."""

    __slots__ = ("data",)

    def __init__(self, data: bytes):
        self.data = data

    @property
    def nbytes(self) -> int:
        return len(self.data)


def maybe_deserialize(page):
    if isinstance(page, SerializedPage):
        return deserialize_batch(page.data)
    return page


def _arrived(page) -> ColumnBatch:
    """A page as an operator takes it: a serialized one (remote producer,
    spool) comes dense with an arbitrary row count and is padded to its
    bucket before the first program sees it, as the scan pads its batches;
    an in-process page already is bucket-shaped (PartitionedOutputSink)."""
    return pad_to_bucket(maybe_deserialize(page))


def _dict_value_hashes(dictionary: np.ndarray) -> np.ndarray:
    """Deterministic per-value hash of a string dictionary (crc32 over
    utf-8).  Partition routing must hash VALUES, not dictionary codes: code
    3 in one producer's dictionary is a different string than code 3 in
    another's, and all producers of a stage must route equal values to the
    same consumer task."""
    return np.array([zlib.crc32(str(s).encode()) for s in dictionary],
                    dtype=np.int64)


def _partition_key_tuple(c: Column):
    data = np.asarray(c.data)
    valid = None if c.valid is None else np.asarray(c.valid)
    if c.dictionary is not None:
        vh = _dict_value_hashes(c.dictionary)
        data = vh[data] if len(vh) else np.zeros(len(data), np.int64)
    return data, valid


class RemoteExchangeSourceOperator(Operator):
    # blocking=True: wait in place for upstream pages (thread-per-task mode).
    # The time-sharing executor flips this off so a waiting consumer parks
    # (yields its worker) instead of pinning it.
    blocking = True

    def __init__(self, client: ExchangeClient):
        self.client = client
        self.input_done = True

    def needs_input(self) -> bool:
        return False

    def get_output(self) -> Optional[ColumnBatch]:
        if self._closed:
            return None
        if not self.blocking:
            page = self.client.poll(timeout=0)
            return _arrived(page) if page is not None else None
        # block until a page or all upstream producers finish; the driver
        # treats a None from a non-finished source as "try again"
        deadline = time.monotonic() + STALL_TIMEOUT_S
        while not self.client.is_finished():
            page = self.client.poll(timeout=0.2)
            if page is not None:
                return _arrived(page)
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"exchange source stalled >{STALL_TIMEOUT_S:.0f}s")
        return None

    def is_finished(self) -> bool:
        return self._closed or self.client.is_finished()


class MergeSourceOperator(Operator):
    """Order-preserving gather of pre-sorted per-producer streams (the
    MergeOperator.java:46 consumer of a MERGE exchange).

    Small results (client-facing ORDER BY outputs) k-way heap-merge the
    producer streams row-wise, reproducing the global order without a
    re-sort; beyond ``MERGE_ROW_LIMIT`` rows the operator falls back to the
    vectorized sort kernel over the concatenated streams (same result,
    O(n log n) on device instead of Python-per-row)."""

    blocking = True  # executor flips off: parks instead of pinning a worker
    MERGE_ROW_LIMIT = 100_000

    def __init__(self, producer_clients, sort_keys, names, types):
        self.clients = list(producer_clients)
        self.sort_keys = list(sort_keys)
        self.names = list(names)
        self.types = list(types)
        self.input_done = True
        self._streams: list[list] = [[] for _ in self.clients]
        self._emitted = False

    def needs_input(self) -> bool:
        return False

    def _poll_all(self, wait: bool) -> bool:
        """Accumulate available pages; True when every stream is complete."""
        deadline = time.monotonic() + STALL_TIMEOUT_S
        while True:
            all_done = True
            progressed = False
            for i, c in enumerate(self.clients):
                if c.is_finished():
                    continue
                page = c.poll(timeout=0.05 if wait else 0)
                if page is not None:
                    # the row-wise merge reads host rows: it asks for them
                    # here (an in-process page arrives masked, on the device)
                    self._streams[i].append(
                        maybe_deserialize(page).compact())
                    progressed = True
                if not c.is_finished():
                    all_done = False
            if all_done or not wait:
                return all_done
            if progressed:
                deadline = time.monotonic() + STALL_TIMEOUT_S  # reset on activity
            elif time.monotonic() > deadline:
                raise TimeoutError(
                    f"merge source stalled >{STALL_TIMEOUT_S:.0f}s")

    def _row_key(self, row):
        key = []
        for k in self.sort_keys:
            v = row[k.channel]
            null_rank = (0 if k.nulls_first else 1) if v is None else \
                (1 if k.nulls_first else 0)
            if v is None:
                key.append((null_rank, 0, _MIN_TOKEN))
                continue
            nan = isinstance(v, float) and v != v
            nan_rank = (1 if k.ascending else 0) if nan else (
                0 if k.ascending else 1)
            key.append((null_rank, nan_rank,
                        _Reversed(v) if not k.ascending and not nan else
                        (_MIN_TOKEN if nan else v)))
        return tuple(key)

    def _merge(self) -> Optional[ColumnBatch]:
        batches = [b for s in self._streams for b in s]
        if not batches:
            return None
        total = sum(b.num_rows for b in batches)
        if total > self.MERGE_ROW_LIMIT:
            # vectorized fallback: one kernel re-sort of the gathered runs
            from ..exec import kernels as K
            from ..exec.operators import _sort_key_tuples

            inp = ColumnBatch.concat(batches)
            perm = K.sort_perm(_sort_key_tuples(inp, self.sort_keys))
            return inp.take(perm).rename(self.names)
        import heapq

        streams = []
        for s in self._streams:
            rows: list = []
            for b in s:
                rows.extend(b.to_pylist())
            streams.append(rows)
        merged = list(heapq.merge(*streams, key=self._row_key))
        if not merged:
            return None
        cols = [Column.from_values(t, [r[i] for r in merged])
                for i, t in enumerate(self.types)]
        return ColumnBatch(self.names, cols)

    def get_output(self):
        if self._emitted or self._closed:
            return None
        if not self._poll_all(wait=self.blocking):
            return None  # parked; the executor reschedules us
        self._emitted = True
        return self._merge()

    def is_finished(self) -> bool:
        return self._emitted or self._closed


class _Reversed:
    """Inverts comparison order for DESC sort keys in the merge heap."""

    __slots__ = ("v",)

    def __init__(self, v):
        self.v = v

    def __lt__(self, other):
        return other.v < self.v

    def __eq__(self, other):
        return self.v == other.v


class _MinToken:
    __slots__ = ()

    def __lt__(self, other):
        return not isinstance(other, _MinToken)

    def __eq__(self, other):
        return isinstance(other, _MinToken)


_MIN_TOKEN = _MinToken()


class _Pending:
    """One input batch's pages waiting for their live counts: ``pages`` is
    [(page, [target partitions])], ``counts`` a numpy vector (known) or a
    SyncGuard async handle (in flight)."""

    __slots__ = ("pages", "counts")

    def __init__(self, pages, counts):
        self.pages = pages
        self.counts = counts


class PartitionedOutputSink(Operator):
    """Routes task output into the OutputBuffer: REPARTITION hashes on the
    output keys, BROADCAST replicates, GATHER/OUTPUT lands in partition 0.

    **Where a page is densified** (pulled to the host and cut to its live
    rows).  Where it is SERIALIZED (``serde``: the HTTP worker plane, FTE's
    durable spool, the wire format) or sketched -- and where it is small:
    under ``_RESIDENT_MIN_LANES`` lanes one transfer and numpy cost a short
    query less than a count program, its fetch and every consumer's launches
    over a few rows on the device (measured, see the constant).  A large
    page between two tasks of one process is only enqueued, so it stays
    what the producer made: device-resident, bucket-shaped, under its
    ``live`` mask.  REPARTITION then computes one mask a partition on the
    device (kernels.partition_masks: the host path's hash) over the page's
    own columns; no row moves.  A page that turns out sparse (its live rows
    fit half its lanes or less) is shrunk to the bucket of its live rows by
    one device program before it is enqueued, once for all its consumers:
    what reads an exchange sorts or probes, and costs more a lane than the
    shrink.  Whichever way a page goes, it leaves bucket-shaped (a dense
    page is padded, ``_page``): a consumer's programs see powers of two
    from every producer, never a row count.

    **Counts.**  The buffer's ``rows_enqueued`` / ``bytes_enqueued`` are
    LIVE rows (the planner's history and the adaptive barriers read them,
    and the plan cache's epoch digests them).  A resident page's count is
    one device scalar copied back asynchronously: the page waits for it in
    ``_held`` (later pages behind it, to keep a stream's order) and goes out
    when the count is here -- asked at the next ``needs_input`` /
    ``add_input``, and at ``finish_input`` at the latest, which may block;
    ``add_input`` never does for a resident page."""

    # blocking=True: wait inside OutputBuffer.enqueue when the byte budget
    # is exhausted (thread-per-task mode).  The time-sharing executor flips
    # this off; the sink then refuses input via ``needs_input`` and its
    # driver parks until consumer acks free capacity — quantum-pinning is
    # never traded for unbounded buffer growth.
    blocking = True

    def __init__(self, buffer: OutputBuffer, kind: str,
                 keys: Sequence[int] = (), serde: bool = False,
                 sketch=None, sketch_keys: Sequence[int] = (),
                 coalesce_rows: int = 0):
        self.buffer = buffer
        self.kind = kind
        self.keys = list(keys)
        self.serde = serde  # serialize pages to wire bytes (network mode)
        self._rr = 0  # ROUND_ROBIN rotation cursor
        # adaptive deferred edges: a HeavyHitterSketch fed the join-key
        # hashes of every row so the coordinator can fold per-task key
        # distributions at the consumer's activation barrier
        self.sketch = sketch
        self.sketch_keys = list(sketch_keys)
        # serialized pages are dense by construction; the sketch hashes a
        # page's live key values on the host, and its staging buffer's
        # reader (adaptive.Router) cuts rows on the host anyway
        self._densify = serde or sketch is not None
        # >0: REPARTITION buffers each partition's pages and releases
        # ~coalesce_rows LIVE rows at a time, concatenated to a bucket — a
        # page split n ways otherwise hands the consumer one operator
        # dispatch per sliver
        self.coalesce_rows = coalesce_rows
        self._pend: dict[int, list] = {}  # partition -> [rows, [slivers]]
        self._held: "deque[_Pending]" = deque()  # in arrival order
        self._mem = None  # TaskMemoryContext (attach_memory)
        self._value_hashes: dict[int, tuple] = {}  # id(dict) -> (dict, table)
        self._pages = self._lanes = self._live_rows = 0
        # compressed execution: each partition's page stream gets its own
        # sidecar context, so dictionaries ship once per (task, partition).
        # Only the in-memory HTTP exchange plane guarantees the in-order,
        # from-the-start delivery the def/ref protocol needs — FTE durable
        # spools and speculation tees (facade buffers) replay frames across
        # attempts and stay on v1 pages.  BROADCAST serializes one page for
        # all partitions, which would share one stream across consumers.
        self._encode_pages = (serde and kind != "BROADCAST"
                              and isinstance(buffer, OutputBuffer)
                              and encoded_exec())
        self._encoders: dict[int, PageStreamEncoder] = {}

    def needs_input(self) -> bool:
        if self._held:
            # the driver asks between batches: a page that waited for its
            # count goes out as soon as the count is here, not only when
            # the next batch arrives
            self._drain(block=False)
        if (not self.blocking and hasattr(self.buffer, "has_capacity")
                and not self.buffer.has_capacity()):
            return False
        return super().needs_input()

    def _enqueue(self, partition: int, page, live_rows=None) -> None:
        # block= is only passed on the non-blocking path, live_rows= only
        # for a masked page: FTE wraps a DurableSpoolWriter in this sink,
        # whose enqueue has neither kwarg (it is never flipped non-blocking
        # — FTE bypasses the executor — and its pages are serialized)
        kw = {}
        if not self.blocking:
            kw["block"] = False
        if live_rows is not None:
            kw["live_rows"] = live_rows
        self.buffer.enqueue(partition, page, **kw)

    def _page(self, batch: ColumnBatch, partition: Optional[int] = None):
        if self.serde:
            ctx = None
            if self._encode_pages and partition is not None:
                ctx = self._encoders.get(partition)
                if ctx is None:
                    ctx = self._encoders[partition] = PageStreamEncoder()
            return SerializedPage(serialize_batch(batch, ctx=ctx))
        # not serialized: the rows take their bucket, so a consumer's
        # programs see powers of two (numpy; the live count stays known)
        return pad_to_bucket(batch)

    def _targets(self) -> list[int]:
        n = self.buffer.num_partitions
        if self.kind == "BROADCAST" and n > 1:
            return list(range(n))
        if self.kind == "ROUND_ROBIN" and n > 1:
            # batch-granular rotation (RandomExchanger / ArbitraryOutputBuffer
            # role: balance load without any key)
            self._rr += 1
            return [(self._rr - 1) % n]
        return [0]

    def add_input(self, batch: ColumnBatch) -> None:
        if batch.num_rows == 0:
            return
        self._pages += 1
        self._lanes += batch.num_rows
        if not self._densify and _stays_resident(batch):
            self._add_resident(batch)
            handed = "device"
        else:
            handed = self._add_densified(batch)
        self.trace_attrs = {
            "exchange": self.kind, "lanes": batch.num_rows, "handed": handed}
        self._account_memory()

    # -- pages cut to their rows on the host: serialized, sketched, small --

    def _add_densified(self, batch: ColumnBatch) -> str:
        # the one place an exchange pulls a page to the host and cuts it to
        # its rows: its next step is serialize_batch or the sketch, or the
        # page is small (one transfer and numpy beat a count program, a
        # count fetch and every consumer's launches over a few rows; a page
        # that is not serialized is padded to its bucket in _page)
        if self._held:
            self._drain(block=True)  # a stream's pages keep their order
        # a page some host operator made is handed on as ``host``: nothing
        # is pulled, and only the pulled ones are counted as densified
        handed = "densified" if batch_device_nbytes(batch) else "host"
        batch = batch.compact()
        if handed == "densified":
            SG.count_exchange_page(False, batch.nbytes)
            tm.observe_exchange_page(False, batch.nbytes)
        if batch.num_rows == 0:
            return handed
        self._live_rows += batch.num_rows
        if self.sketch is not None and self.sketch_keys:
            h = K.partition_key_hashes(
                [_partition_key_tuple(batch.columns[k])
                 for k in self.sketch_keys])
            self.sketch.update(h)
        n = self.buffer.num_partitions
        if self.kind == "REPARTITION" and n > 1:
            cols = [batch.columns[k] for k in self.keys]
            parts = K.partition_assignments(
                [_partition_key_tuple(c) for c in cols], n)
            for p in range(n):
                sub = batch.filter(parts == p)
                if not sub.num_rows:
                    continue
                if self.coalesce_rows:
                    self._buffer_sliver(p, sub, sub.num_rows)
                else:
                    self._enqueue(p, self._page(sub, p))
            return handed
        targets = self._targets()
        page = self._page(batch, None if len(targets) > 1 else targets[0])
        for p in targets:
            self._enqueue(p, page)
        return handed

    # -- pages that stay in the process: masked, bucket-shaped -------------

    def _add_resident(self, batch: ColumnBatch) -> None:
        self._drain(block=False)
        SG.count_exchange_page(True, 0)
        tm.observe_exchange_page(True)
        batch = pad_to_bucket(batch)
        n = self.buffer.num_partitions
        if self.kind == "REPARTITION" and n > 1:
            keys = [(JX.key_input(c), c.valid,
                     self._value_hash_table(c.dictionary))
                    for c in (batch.columns[k] for k in self.keys)]
            masks, counts = K.partition_masks(keys, batch.live, n)
            pages = [(ColumnBatch(batch.names, batch.columns, m), [p])
                     for p, m in enumerate(masks)]
        else:
            pages = [(batch, self._targets())]
            rows = known_live_rows(batch)
            counts = (K.live_count(batch.live) if rows is None
                      else np.array([rows]))
        entry = _Pending(pages, counts)
        if isinstance(counts, np.ndarray):  # a dense page: nothing to wait for
            if not self._held:
                self._release(entry, counts)
                return
        else:
            # the shrink (and the coalescing buffer) need the count first:
            # the page waits for it, and later pages behind it
            entry.counts = SG.async_scalar(counts, "exchange.live-rows")
        self._held.append(entry)

    def _value_hash_table(self, dictionary):
        """A dictionary's per-value routing hashes as a device table padded
        to a bucket (one program a bucket, not one a dictionary length)."""
        if dictionary is None:
            return None
        hit = self._value_hashes.get(id(dictionary))
        if hit is None:
            vh = _dict_value_hashes(dictionary)
            table = np.zeros(K.bucket(len(vh)), np.int64)
            table[:len(vh)] = vh
            hit = self._value_hashes[id(dictionary)] = (dictionary, table)
        return hit[1]

    def _drain(self, block: bool) -> None:
        """Land counts in arrival order: every entry whose count is here
        (with ``block``, every entry) is shrunk where sparse, enqueued if it
        waited, and credited to the buffer's live-row counters."""
        while self._held:
            h = self._held[0]
            counts = h.counts
            if not isinstance(counts, np.ndarray):
                counts = counts.get() if block else counts.get_if_ready()
                if counts is None:
                    return
                counts = np.asarray(counts)
            self._held.popleft()
            self._release(h, counts)

    def _release(self, entry: _Pending, counts: np.ndarray) -> None:
        """Enqueue an entry's pages now that their live counts are known:
        without the empty ones, the sparse ones shrunk to the bucket of
        their live rows, REPARTITION's through the coalescing buffer."""
        for (page, targets), rows in zip(entry.pages, counts.tolist()):
            if rows == 0:
                continue
            self._live_rows += rows
            page = _shrink(page, rows)
            if self.coalesce_rows and len(entry.pages) > 1:
                self._buffer_sliver(targets[0], page, rows)
                continue
            for p in targets:
                self._enqueue(p, page, rows)

    def _buffer_sliver(self, p: int, sub: ColumnBatch, rows: int) -> None:
        ent = self._pend.get(p)
        if ent is None:
            ent = self._pend[p] = [0, []]
        ent[0] += rows
        ent[1].append(sub)
        if ent[0] >= self.coalesce_rows:
            self._flush_pending(p)

    def _flush_pending(self, p: int) -> None:
        ent = self._pend.pop(p, None)
        if ent is None or not ent[1]:
            return
        if len(ent[1]) == 1:
            page = ent[1][0]
        elif any(batch_device_nbytes(b) for b in ent[1]):
            page = _concat_device(ent[1])  # to a bucket, masks kept
        else:
            page = ColumnBatch.concat(ent[1])
        self._enqueue(p, self._page(page, p),
                      None if self.serde else ent[0])

    def finish_input(self) -> None:
        super().finish_input()
        self._drain(block=True)
        for p in list(self._pend):
            self._flush_pending(p)
        self.buffer.set_finished()
        self.trace_attrs = {
            "exchange": self.kind, "pages": self._pages, "lanes": self._lanes,
            "live_rows": self._live_rows}
        if self._mem is not None:
            self._mem.update(self, 0)

    # -- memory: what the task's output holds on the device ----------------

    def attach_memory(self, mem) -> None:
        self._mem = mem
        if mem is not None:
            mem.register(self)

    def _device_bytes(self) -> int:
        held = sum(batch_device_nbytes(page)
                   for h in self._held for page, _ in h.pages)
        held += sum(batch_device_nbytes(b)
                    for ent in self._pend.values() for b in ent[1])
        return held + getattr(self.buffer, "device_bytes", 0)

    def _account_memory(self) -> None:
        if self._mem is not None and not self._densify:
            self._mem.update(self, self._device_bytes())

    def revoke_memory(self) -> int:
        """Pages still waiting in the buffer move to host memory (they stay
        masked and bucket-shaped: a consumer takes either)."""
        evict = getattr(self.buffer, "evict_to_host", None)
        return evict() if evict is not None else 0

    def is_finished(self) -> bool:
        return self.input_done


def _pinned_elsewhere(batch: ColumnBatch) -> bool:
    """In a process with several devices: does ``batch`` hold an array
    committed to one of them (a collective or fused stage's output)?  Such
    an array cannot meet another device's in one program; everything the
    one-device path makes is uncommitted and goes wherever it is used."""
    import jax

    if jax.device_count() == 1:
        return False
    for c in batch.columns:
        if c.encoding == "RLE":
            continue
        if getattr(c.data, "committed", False) or getattr(
                c.valid, "committed", False):
            return True
    return bool(getattr(batch.live, "committed", False))


# Below this many lanes a page goes by the host: what the device path adds a
# page -- a count program, its fetch, and every consumer's launches over
# device arrays where numpy would do -- costs a short query more than one
# transfer of a few rows (my chip runs, PR 36: Q6 +10 %, Q1 +9 % with every
# page resident).  From here up the transfer and the numpy cut are what
# costs (30-32 ms for Q3's 2^19-lane orders page).  The line is
# operators._COMPACT_MIN_LANES: below it a count does not pay either.
_RESIDENT_MIN_LANES = _COMPACT_MIN_LANES
_SHRINK_FACTOR = 2  # shrink where the live rows fit lanes / 2


def _stays_resident(batch: ColumnBatch) -> bool:
    """Does ``batch`` cross an in-process exchange as it is?  A large page
    that lives on the device, on a device its consumer can compute on."""
    if batch.num_rows < _RESIDENT_MIN_LANES:
        return False
    for c in batch.columns:
        if c.encoding == "LAZY":
            # a lazy column's first touch is not thread-safe and a page may
            # go to several consumers: it is touched here, once
            c.data  # noqa: B018
    return batch_device_nbytes(batch) > 0 and not _pinned_elsewhere(batch)


def _shrink(page: ColumnBatch, rows: int) -> ColumnBatch:
    """A sparse device page, cut to the bucket of its ``rows`` live rows by
    kernels.compact (live rows first, dead tail dropped; a run stays a
    run).  Never to an exact row count."""
    cap = K.bucket(rows)
    if page.live is None or cap * _SHRINK_FACTOR > page.num_rows:
        return page
    runs = {i: c for i, c in enumerate(page.columns)
            if c.encoding == "RLE" and c.valid is None}
    kept = [i for i in range(page.num_columns) if i not in runs]
    if not kept:
        return page
    out = K.compact_device_batch(
        ColumnBatch([page.names[i] for i in kept],
                    [page.columns[i] for i in kept], page.live), rows)
    cols = iter(out.columns)
    return ColumnBatch(page.names, [
        Column.rle(c.type, c.rle_value, cap, None, c.dictionary)
        if i in runs else next(cols)
        for i, c in enumerate(page.columns)], out.live)
