"""Asynchronous scan ingest: split prefetch, batch coalescing, device staging.

A scan that reads one split at a time serializes three things that have no
business serializing: host-side split decoding (connector
``get_next_batch``), host->device transfer, and device compute.  This
module supplies the three pieces ScanOperator composes to overlap them —
the ingest-side counterpart of Trino's split pipeline
(ScanFilterAndProjectOperator's lazy pages + MergePages coalescing;
reference: operator/ScanFilterAndProjectOperator.java:68,
operator/project/MergePages.java:38, split prefetch via
ConnectorSplitSource.getNextBatch futures):

- :class:`PrefetchingPageSource` drains connector splits on a bounded
  background thread pool into a memory-accounted queue.  Split order is
  preserved (batches of split k always precede batches of split k+1),
  backpressure parks producers when the queue exceeds its byte/depth budget,
  and ``close()`` aborts in-flight reads so a satisfied LIMIT stops paying
  for splits nobody will consume.  A crash on a prefetch thread is re-raised
  on the consumer.
- :class:`BatchCoalescer` merges small scan batches up to a target
  power-of-two bucket before staging, writing every part into ONE
  preallocated bucket-sized buffer per column (no per-column concatenates),
  so jitted programs run with full lanes instead of padding half-empty
  buckets.
- :class:`DeviceStager` double-buffers host->device transfer: ScanOperator
  stages batch N+1 with ``jax.device_put`` (async dispatch) while the
  downstream operators compute on batch N, so the transfer rides under
  compute instead of in front of it.

Every knob reads from the environment once per source (see
:class:`IngestConfig`).
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np

from ..spi.batch import (Column, ColumnBatch, encoded_exec, maybe_rle,
                         round_up_pow2, unify_dictionaries)
from ..spi.errors import GENERIC_INTERNAL_ERROR, TrinoError
from ..telemetry import profiler
from .stats import EncodingStats, ScanIngestStats

__all__ = [
    "IngestConfig",
    "PrefetchingPageSource",
    "BatchCoalescer",
    "DeviceStager",
    "coalesce_pad",
]


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


@dataclass(frozen=True)
class IngestConfig:
    """Scan-ingest knobs (one env read per scan, so tests can flip them)."""

    threads: int = 2                # TRINO_TPU_PREFETCH_THREADS
    queue_depth: int = 8            # TRINO_TPU_PREFETCH_QUEUE_DEPTH (batches)
    queue_bytes: int = 256 << 20    # TRINO_TPU_PREFETCH_QUEUE_BYTES
    coalesce_rows: int = 1 << 16    # TRINO_TPU_COALESCE_TARGET_ROWS

    @staticmethod
    def default_threads() -> int:
        """Prefetch decode threads auto-tuned from the host: cpu_count - 1
        (one core stays with the consumer/dispatch thread), at least 1,
        capped at 4 — the decode is memory-bandwidth-bound past that."""
        return min(4, max(1, (os.cpu_count() or 1) - 1))

    @staticmethod
    def from_env() -> "IngestConfig":
        threads = _env_int("TRINO_TPU_PREFETCH_THREADS", -1)
        if threads < 0:  # unset: auto-tune from the host core count
            threads = IngestConfig.default_threads()
        return IngestConfig(
            threads=max(1, threads),
            queue_depth=max(1, _env_int("TRINO_TPU_PREFETCH_QUEUE_DEPTH", 8)),
            queue_bytes=max(1, _env_int(
                "TRINO_TPU_PREFETCH_QUEUE_BYTES", 256 << 20)),
            coalesce_rows=max(1, _env_int(
                "TRINO_TPU_COALESCE_TARGET_ROWS", 1 << 16)),
        )


class PrefetchingPageSource:
    """Order-preserving multi-split prefetcher with a bounded queue.

    Worker threads claim splits in order and append decoded batches to a
    per-split buffer; the consumer drains buffers strictly in split order, so
    downstream row order matches reading the splits one after another.  Backpressure:
    producers park while the queue is over its byte or depth budget, except
    the producer of the consumer's current split while that split's buffer is
    empty (a starved consumer can always make progress — no deadlock with any
    budget >= 1 batch).
    """

    def __init__(self, connector, splits: Sequence, columns: Sequence[str],
                 constraint=None, config: Optional[IngestConfig] = None,
                 stats: Optional[ScanIngestStats] = None,
                 limit_rows: Optional[int] = None):
        self.connector = connector
        self.splits = list(splits)
        self.columns = list(columns)
        self.constraint = constraint
        self.cfg = config if config is not None else IngestConfig.from_env()
        self.stats = stats if stats is not None else ScanIngestStats()
        self.limit_rows = limit_rows
        self._cv = threading.Condition()
        self._buffers: list[deque] = [deque() for _ in self.splits]
        self._done = [False] * len(self.splits)
        self._next_claim = 0   # next split a worker picks up (in order)
        self._consume = 0      # split the consumer is draining
        self._inflight = 0     # splits claimed but not finished (limit gate)
        self._queued_bytes = 0
        self._queued_batches = 0
        self._queued_rows = 0
        self._error: Optional[BaseException] = None
        self._closed = False
        n = min(self.cfg.threads, max(1, len(self.splits)))
        self._threads = [
            threading.Thread(target=self._work, daemon=True,
                             name=f"scan-prefetch-{i}")
            for i in range(n)
        ]
        for t in self._threads:
            t.start()

    # -- producer side -----------------------------------------------------
    def _open_source(self, split):
        # kwarg only when constrained: wrapper connectors with the bare
        # (split, columns) signature keep working
        if self.constraint is not None:
            return self.connector.create_page_source(
                split, self.columns, constraint=self.constraint)
        return self.connector.create_page_source(split, self.columns)

    def _over_budget(self) -> bool:
        return (self._queued_bytes >= self.cfg.queue_bytes
                or self._queued_batches >= self.cfg.queue_depth)

    def _work(self) -> None:
        try:
            while True:
                with self._cv:
                    # a pushed-down LIMIT makes split claiming lazy: one
                    # split in flight at a time, and none while the queue
                    # already holds enough raw rows to satisfy the limit.
                    # Filters may drop rows, so this only PAUSES claiming —
                    # the consumer draining the queue resumes it (a pause,
                    # never a stop: correctness does not depend on it)
                    while (self.limit_rows is not None
                           and (self._inflight >= 1
                                or self._queued_rows >= self.limit_rows)
                           and self._next_claim < len(self.splits)
                           and not self._closed and self._error is None):
                        self._cv.wait(0.05)
                    if self._closed or self._error is not None:
                        return
                    if self._next_claim >= len(self.splits):
                        return
                    idx = self._next_claim
                    self._next_claim += 1
                    self._inflight += 1
                    self.stats.splits_opened += 1
                src = self._open_source(self.splits[idx])
                try:
                    while True:
                        with self._cv:
                            # park while over budget — UNLESS the consumer is
                            # starved waiting on THIS split (exemption keeps
                            # the in-order drain progressing: no deadlock for
                            # any budget >= 1 batch)
                            while (self._over_budget()
                                   and not (idx == self._consume
                                            and not self._buffers[idx])
                                   and not self._closed
                                   and self._error is None):
                                self._cv.wait(0.05)
                            if self._closed or self._error is not None:
                                return
                        if src.is_finished():
                            break
                        t0 = time.perf_counter()
                        b = src.get_next_batch()
                        dt = time.perf_counter() - t0
                        with self._cv:
                            self.stats.source_read_s += dt
                            if b is not None:
                                self._buffers[idx].append(b)
                                self._queued_bytes += b.nbytes
                                self._queued_batches += 1
                                self._queued_rows += b.num_rows
                                s = self.stats
                                s.queue_depth_max = max(
                                    s.queue_depth_max, self._queued_batches)
                            self._cv.notify_all()
                finally:
                    src.close()
                with self._cv:
                    self._done[idx] = True
                    self._inflight -= 1
                    self._cv.notify_all()
        except BaseException as e:  # noqa: BLE001 — re-raised on the consumer
            with self._cv:
                if self._error is None:
                    self._error = e
                self._cv.notify_all()

    # -- consumer side -----------------------------------------------------
    def _advance(self) -> None:
        while (self._consume < len(self.splits)
               and self._done[self._consume]
               and not self._buffers[self._consume]):
            self._consume += 1

    def get_next_batch(self) -> Optional[ColumnBatch]:
        """Next batch in split order; blocks while prefetch is behind.
        Returns None when every split is drained (or after close).

        A call that had to wait is timed once, on the recorder's clock,
        from its first wait to its return: ``consumer_wait_s`` and the
        recorder's ``source-wait`` event are the same two clock reads."""
        waits, split, t0 = 0, 0, 0.0
        try:
            with self._cv:
                while True:
                    if self._error is not None:
                        err = self._error
                        raise TrinoError(
                            GENERIC_INTERNAL_ERROR,
                            f"scan prefetch thread failed: {err}") from err
                    if self._closed:
                        return None
                    self._advance()
                    if self._consume >= len(self.splits):
                        return None
                    buf = self._buffers[self._consume]
                    if buf:
                        b = buf.popleft()
                        self._queued_bytes -= b.nbytes
                        self._queued_batches -= 1
                        self._queued_rows -= b.num_rows
                        s = self.stats
                        s.queue_depth_sum += self._queued_batches + 1
                        s.queue_samples += 1
                        s.observe_batch(b.nbytes, b.num_rows)
                        self._cv.notify_all()
                        return b
                    if not waits:
                        split, t0 = self._consume, profiler.now()
                    waits += 1
                    self._cv.wait(0.05)
        finally:
            if waits:
                t1 = profiler.now()
                self.stats.consumer_wait_s += t1 - t0
                profiler.event(profiler.SOURCE_WAIT, "prefetch", t0, t1,
                               waits=waits, split=split)

    def is_finished(self) -> bool:
        with self._cv:
            if self._closed or self._error is not None:
                return True
            self._advance()
            return self._consume >= len(self.splits)

    def close(self) -> None:
        """Early close (satisfied LIMIT / downstream done): producers abort
        at the next check and unclaimed splits are never opened."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()


def coalesce_pad(parts: Sequence[ColumnBatch],
                 min_rows: int = 8) -> ColumnBatch:
    """Merge dense host batches into ONE batch padded to the power-of-two
    bucket of the total, writing each part into a preallocated bucket-sized
    buffer per column (single allocation + one memcpy pass — replaces the
    per-batch per-column concatenates of pad_to_bucket on this path).
    Dictionary columns are unified onto one shared code space first."""
    assert parts, "coalesce_pad of no batches"
    names = parts[0].names
    total = sum(p.num_rows for p in parts)
    cap = round_up_pow2(total, min_rows)
    out_cols = []
    for i in range(len(names)):
        cs = [p.columns[i] for p in parts]
        if cs[0].type.is_dictionary_encoded:
            cs = unify_dictionaries(cs)
        data = np.zeros(cap, dtype=np.asarray(cs[0].data).dtype)
        valid = None
        if any(c.valid is not None for c in cs):
            valid = np.zeros(cap, dtype=np.bool_)
        pos = 0
        for c in cs:
            n = len(c)
            data[pos:pos + n] = np.asarray(c.data)
            if valid is not None:
                if c.valid is None:
                    valid[pos:pos + n] = True
                else:
                    valid[pos:pos + n] = np.asarray(c.valid)
            pos += n
        out_cols.append(Column(cs[0].type, data, valid, cs[0].dictionary))
    live = None
    if cap != total:
        live = np.zeros(cap, dtype=np.bool_)
        live[:total] = True
    return ColumnBatch(list(names), out_cols, live)


class BatchCoalescer:
    """Accumulate small dense host batches and emit bucket-padded merges.

    ``add`` buffers; once the buffered rows reach ``target_rows`` (or the
    caller flushes at end of input) the parts merge via :func:`coalesce_pad`.
    Batches that are already bucket-shaped (``live`` set — device-pinned
    tables) or device-resident must NOT enter the coalescer: pulling them to
    host would cost more than full lanes save (callers pass those through).
    """

    def __init__(self, target_rows: int,
                 stats: Optional[ScanIngestStats] = None):
        self.target_rows = target_rows
        self.stats = stats
        self._parts: list[ColumnBatch] = []
        self._rows = 0

    @property
    def buffered_rows(self) -> int:
        return self._rows

    def add(self, batch: ColumnBatch) -> None:
        assert batch.live is None, "coalescer input must be dense"
        if batch.num_rows:
            self._parts.append(batch)
            self._rows += batch.num_rows

    def ready(self) -> bool:
        return self._rows >= self.target_rows

    def flush(self) -> Optional[ColumnBatch]:
        """Merge-and-pad everything buffered (None when empty)."""
        if not self._parts:
            return None
        parts, self._parts, self._rows = self._parts, [], 0
        if self.stats is not None:
            self.stats.coalesced_batches += 1
            self.stats.coalesced_rows += sum(p.num_rows for p in parts)
        if len(parts) == 1 and round_up_pow2(
                parts[0].num_rows) == parts[0].num_rows:
            return parts[0]  # already exactly bucket-shaped: nothing to do
        return coalesce_pad(parts)


def encode_column(i: int, c: Column, lazy_channels,
                  enc_stats: Optional[EncodingStats] = None
                  ) -> Optional[Column]:
    """Compressed execution: RLE-collapse a constant column, or LAZY-wrap a
    channel the planner proved the filter never touches.  Returns None when
    the column should be handled the legacy way (staged / passed through)."""
    rle = maybe_rle(c)
    if rle is not c:
        # constant run: ONE host scalar represents the whole column; the
        # expand (if any) happens device-side via kernels.rle_fill
        if enc_stats is not None:
            enc_stats.bytes_saved += rle.flat_nbytes - rle.nbytes
        return rle
    if i in lazy_channels:
        data, valid = c.data, c.valid

        def thunk(data=data, valid=valid):
            return data, valid

        if enc_stats is not None:
            enc_stats.lazy_columns += 1
            enc_stats.lazy_skipped_bytes += c.nbytes
        return Column.lazy(c.type, len(c), thunk, c.dictionary,
                           nbytes_hint=c.nbytes)
    return None


class DeviceStager:
    """Double-buffered host->device staging.

    ``stage`` dispatches ``jax.device_put`` for every array of a padded host
    batch and returns immediately with the device handles — the transfer
    runs asynchronously, so staging batch N+1 before returning batch N to
    the driver overlaps its upload with downstream compute on N.  Batches
    that already live on device pass through untouched."""

    def __init__(self, stats: Optional[ScanIngestStats] = None,
                 lazy_channels=None,
                 enc_stats: Optional[EncodingStats] = None):
        self.stats = stats
        # compressed execution (plan_lazy_scan): these channels defer their
        # transfer behind a thunk instead of staging eagerly
        self.lazy_channels = frozenset(lazy_channels or ())
        self.enc_stats = enc_stats

    def _stage_encoded(self, i: int, c: Column) -> Optional[Column]:
        """RLE-collapse or LAZY-wrap one column instead of staging it; None
        means stage eagerly (the legacy device_put)."""
        return encode_column(i, c, self.lazy_channels, self.enc_stats)

    def stage(self, batch: ColumnBatch) -> ColumnBatch:
        if not batch.columns or not isinstance(
                batch.columns[0].data, np.ndarray):
            return batch
        import jax

        t0 = time.perf_counter()
        encoded = encoded_exec()
        any_rle = False
        cols = []
        for i, c in enumerate(batch.columns):
            if encoded:
                enc = self._stage_encoded(i, c)
                if enc is not None:
                    any_rle = any_rle or enc.encoding == "RLE"
                    cols.append(enc)
                    continue
            data = jax.device_put(c.data)
            valid = None if c.valid is None else jax.device_put(c.valid)
            cols.append(Column(c.type, data, valid, c.dictionary))
        if any_rle and self.enc_stats is not None:
            self.enc_stats.rle_batches += 1
        live = batch.live
        if live is not None:
            live = jax.device_put(live)
        if self.stats is not None:
            self.stats.stage_s += time.perf_counter() - t0
            self.stats.staged_batches += 1
        return ColumnBatch(batch.names, cols, live)
