"""SyncGuard: host-transfer accounting for the operator hot loops.

A blocking device->host scalar sync stalls the dispatch pipeline: the host
waits for every queued program before it can launch the next one (what one
costs on a locally attached chip is not measured yet — PERF.md).  The
sync-free rework needs an instrument that (a) COUNTS every host transfer the exec layer
performs, attributed to a tag, (b) distinguishes transfers that actually
blocked from polls of an async copy that had already landed, and (c) in
tests, FORBIDS any transfer inside a declared hot-loop region so the
zero-sync contract is asserted rather than assumed.

Usage in exec code — every deliberate host sync goes through this module
instead of raw ``int(np.asarray(...))`` / ``jax.device_get`` (the grep lint
in tools/lint_host_sync.py flags raw patterns):

    from . import syncguard as SG
    n = SG.fetch(jnp.sum(live), "join.cross-live")        # blocking, counted

    h = SG.async_scalar(total, "join.pair-total")          # starts D2H copy
    ...dispatch more device work...
    v = h.get()          # counted as a poll hit if the copy already landed

The counters roll up into :class:`SyncStats` (merged into QueryStats like
ScanIngestStats, rendered by EXPLAIN ANALYZE, exported as ``trino.exec.*``
span attributes).  A transfer that blocks is also a ``host-sync`` span of
the flight recorder (telemetry/profiler.py) under its tag, as long as the
wait lasts; one that was ready records nothing.  ``hot_region`` marks a
steady-state operator hot loop;
``forbidden`` mode (tests) raises :class:`SyncViolation` on any blocking
transfer inside a hot region.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from dataclasses import dataclass, field, fields

from ..telemetry import profiler

__all__ = [
    "SyncStats",
    "SyncViolation",
    "AsyncScalar",
    "fetch",
    "async_scalar",
    "count_sync",
    "hot_region",
    "forbidden",
    "snapshot",
    "take_delta",
    "stats",
]


class SyncViolation(AssertionError):
    """A blocking host sync happened inside a declared hot-loop region while
    SyncGuard was in ``forbidden`` mode (test enforcement)."""


@dataclass
class SyncStats:
    """Host-transfer counters for the exec layer (one global accumulator;
    ``take_delta`` snapshots per query).  ``host_syncs`` counts every
    device->host value materialization the exec layer asked for;
    ``blocking_syncs`` the subset that had to wait on the device;
    ``async_polls``/``poll_hits`` the async-copy handles created and how many
    had already landed when read (a hit costs ~0 instead of a device wait).
    ``expand_overflows``/``expand_retries`` count padded-expand buckets that
    proved too small and the re-runs that fixed them.
    ``unique_gather_wide``/``unique_gather_compact`` count the unique-build
    probe's gathers (join_exec.run_unique_gather) by the width they ran at:
    the probe batch's lanes, or a cap sized from a match count (an overflow's
    wide re-run counts as wide); ``unique_gather_seeded`` the subset whose
    estimate was the seed an earlier execution left, and
    ``unique_gather_compact_riders`` the compact ones that the later probes
    riding the output's width decided (the probe's own gather alone would
    have stayed wide); ``unique_gather_keys_from_probe`` the build key
    columns those probes took from their own page instead of gathering
    them (LookupJoinOperator: on a matched lane they equal the probe key).
    ``exchange_pages_device``/``exchange_pages_densified`` count the pages an
    exchange sink (execution/task.PartitionedOutputSink) handed on as they
    were -- on the device, bucket-shaped, masked -- and those it pulled to
    the host and cut to their rows because they were about to be serialized;
    ``exchange_densified_bytes`` the bytes of the latter."""

    host_syncs: int = 0
    blocking_syncs: int = 0
    async_polls: int = 0
    poll_hits: int = 0
    expand_overflows: int = 0
    expand_retries: int = 0
    unique_gather_wide: int = 0
    unique_gather_compact: int = 0
    unique_gather_seeded: int = 0
    unique_gather_compact_riders: int = 0
    unique_gather_keys_from_probe: int = 0
    exchange_pages_device: int = 0
    exchange_pages_densified: int = 0
    exchange_densified_bytes: int = 0
    hot_loop_syncs: int = 0      # blocking syncs inside hot regions (want: 0)
    by_tag: dict = field(default_factory=dict)

    def merge(self, other: "SyncStats") -> None:
        for f in fields(self):
            if f.name == "by_tag":
                for k, v in other.by_tag.items():
                    self.by_tag[k] = self.by_tag.get(k, 0) + v
            else:
                setattr(self, f.name, getattr(self, f.name)
                        + getattr(other, f.name))

    def text(self) -> str:
        tags = " ".join(f"{k}={v}" for k, v in sorted(self.by_tag.items()))
        return (
            f"exec: {self.host_syncs} host syncs "
            f"({self.blocking_syncs} blocking, {self.hot_loop_syncs} in hot "
            f"loops), {self.poll_hits}/{self.async_polls} async polls ready, "
            f"expand overflow {self.expand_overflows}/"
            f"retry {self.expand_retries}, unique gathers "
            f"{self.unique_gather_wide} wide/{self.unique_gather_compact} "
            f"compact ({self.unique_gather_seeded} seeded, "
            f"{self.unique_gather_compact_riders} for riders, "
            f"{self.unique_gather_keys_from_probe} keys from the probe), "
            f"exchange pages "
            f"{self.exchange_pages_device} device/"
            f"{self.exchange_pages_densified} densified "
            f"({self.exchange_densified_bytes} B)"
            + (f" [{tags}]" if tags else "")
        )


class _State(threading.local):
    hot_depth = 0


_STATE = _State()
_LOCK = threading.Lock()
_STATS = SyncStats()
_FORBID = False  # set only by tests via forbidden()


def stats() -> SyncStats:
    """The live global accumulator (shared across threads)."""
    return _STATS


def snapshot() -> SyncStats:
    """Copy of the current totals."""
    with _LOCK:
        s = SyncStats(**{f.name: getattr(_STATS, f.name)
                         for f in fields(_STATS) if f.name != "by_tag"})
        s.by_tag = dict(_STATS.by_tag)
        return s


def take_delta(since: SyncStats) -> SyncStats:
    """Counters accumulated after ``since`` (per-query attribution)."""
    now = snapshot()
    d = SyncStats()
    for f in fields(d):
        if f.name == "by_tag":
            for k, v in now.by_tag.items():
                dv = v - since.by_tag.get(k, 0)
                if dv:
                    d.by_tag[k] = dv
        else:
            setattr(d, f.name, getattr(now, f.name) - getattr(since, f.name))
    return d


def _is_ready(x) -> bool:
    if isinstance(x, (tuple, list)):
        return all(_is_ready(e) for e in x)
    try:
        return bool(x.is_ready())
    except AttributeError:
        return True  # numpy / python scalar: already host-resident


def count_sync(tag: str, blocking: bool = True) -> None:
    """Record a host sync performed elsewhere (e.g. batched result fetch)."""
    in_hot = _STATE.hot_depth > 0
    if blocking and in_hot and _FORBID:
        raise SyncViolation(
            f"blocking host sync '{tag}' inside a SyncGuard hot region")
    with _LOCK:
        _STATS.host_syncs += 1
        if blocking:
            _STATS.blocking_syncs += 1
            if in_hot:
                _STATS.hot_loop_syncs += 1
        _STATS.by_tag[tag] = _STATS.by_tag.get(tag, 0) + 1


def count_overflow(retried: bool = True) -> None:
    with _LOCK:
        _STATS.expand_overflows += 1
        if retried:
            _STATS.expand_retries += 1


def count_unique_gather(compact: bool, seeded: bool,
                        riders: bool = False) -> None:
    with _LOCK:
        if compact:
            _STATS.unique_gather_compact += 1
        else:
            _STATS.unique_gather_wide += 1
        if seeded:
            _STATS.unique_gather_seeded += 1
        if riders:
            _STATS.unique_gather_compact_riders += 1


def count_keys_from_probe(n: int) -> None:
    if n:
        with _LOCK:
            _STATS.unique_gather_keys_from_probe += n


def count_exchange_page(device: bool, nbytes: int) -> None:
    with _LOCK:
        if device:
            _STATS.exchange_pages_device += 1
        else:
            _STATS.exchange_pages_densified += 1
            _STATS.exchange_densified_bytes += nbytes


def fetch(x, tag: str):
    """Blocking device->host materialization, counted (and forbidden inside
    hot regions under test enforcement).  Returns a numpy value."""
    import jax

    blocking = not _is_ready(x)
    count_sync(tag, blocking=blocking)
    if not blocking:
        return jax.device_get(x)
    # the wait itself, on the flight recorder (and an open profiler
    # session): the host stands still here until the device catches up
    with profiler.span(profiler.HOST_SYNC, tag):
        return jax.device_get(x)


class AsyncScalar:
    """Handle for a device scalar whose D2H copy was started asynchronously.
    ``get()`` blocks only if the copy has not landed yet (counted as a poll
    hit when it has); ``ready()``/``get_if_ready()`` never block."""

    __slots__ = ("value", "tag")

    def __init__(self, value, tag: str):
        self.value = value
        self.tag = tag
        try:
            value.copy_to_host_async()
        except AttributeError:
            pass

    def ready(self) -> bool:
        return _is_ready(self.value)

    def get(self):
        import jax

        hit = self.ready()
        with _LOCK:
            _STATS.async_polls += 1
            if hit:
                _STATS.poll_hits += 1
        if hit:
            return jax.device_get(self.value)
        # the copy is in flight but we must wait: a genuine blocking sync
        count_sync(self.tag, blocking=True)
        with profiler.span(profiler.HOST_SYNC, self.tag):
            return jax.device_get(self.value)

    def get_if_ready(self):
        """Non-blocking: the value if the copy landed, else None."""
        if not self.ready():
            with _LOCK:
                _STATS.async_polls += 1
            return None
        import jax

        with _LOCK:
            _STATS.async_polls += 1
            _STATS.poll_hits += 1
        return jax.device_get(self.value)


def async_scalar(x, tag: str) -> AsyncScalar:
    return AsyncScalar(x, tag)


@contextmanager
def hot_region():
    """Marks an operator steady-state hot loop: blocking syncs inside are
    tallied separately (and raise under ``forbidden``)."""
    _STATE.hot_depth += 1
    try:
        yield
    finally:
        _STATE.hot_depth -= 1


@contextmanager
def forbidden():
    """Test enforcement: any blocking sync inside a hot region raises
    SyncViolation.  Not thread-safe by design — tests only."""
    global _FORBID
    prev = _FORBID
    _FORBID = True
    try:
        yield
    finally:
        _FORBID = prev
