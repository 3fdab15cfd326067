"""Tier B: the persistent compiled-executable cache registry.

Before this module, every jitted program memo in the engine was an ad-hoc
per-process ``functools.lru_cache(maxsize=None)`` — ~20 sites across
exec/kernels.py, exec/join_exec.py, exec/window_kernels.py,
ops/pallas_kernels.py and the stage compiler, each an unbounded-growth
hazard under long-lived multi-tenant serving (VERDICT §2.2 records
``trino-cache: no``; the reference ships a whole cache subsystem).  The
:func:`jit_memo` decorator replaces them with bounded, observable,
evictable entries in one process-wide registry:

- **bounded**: per-cache LRU capped at ``TRINO_TPU_EXEC_CACHE_ENTRIES``
  (default 256) keys; eviction drops the Python wrapper + its jitted
  closure (XLA's own trace cache is freed with it since the closure holds
  the only reference).
- **observable**: hits/misses/evictions per cache and in aggregate, via
  the lint-clean ``trino_cache_exec_*`` metrics and the
  ``system.runtime.caches`` table (caching/__init__.py cache_rows()).
- **persistent across restarts**, two ways.  (1) JAX's on-disk compilation
  cache, placed by :func:`init_compile_cache` (``JAX_COMPILATION_CACHE_DIR``
  or ``<checkout>/.jax_cache``), so an XLA compile performed by any past
  process is a disk load, not a recompile.  (2) JSON-serializable memo
  keys are journaled to ``exec_warm.json`` next to the query journal
  (telemetry/journal.py dir) at query end; :func:`warm_at_boot` — called
  from the worker boot path — replays them so the hottest shape buckets
  have live wrappers before the first query arrives, and their first
  invocation hits the disk compile cache instead of tracing cold.

``TRINO_TPU_EXEC_CACHE=0`` restores bit-for-bit legacy behavior: every
decorated site degrades to a plain unbounded ``lru_cache`` with no
registry, no metrics, no warm file (checked once, at import/decoration
time — flipping it requires a fresh process, exactly like the legacy
per-process caches it reproduces).
"""

from __future__ import annotations

import json
import os
import threading
from collections import OrderedDict
from functools import lru_cache
from typing import Any, Callable, Optional

from ..telemetry import profiler

__all__ = [
    "jit_memo", "program", "program_name", "register_external", "enabled",
    "default_maxsize",
    "registry_stats", "aggregate_stats", "clear_all", "warm_at_boot",
    "flush_warm_keys", "init_compile_cache", "warm_file_path",
    "reset_warm_state_for_test",
]

_WARM_FILE = "exec_warm.json"
_WARM_KEY_CAP = 256  # hottest keys journaled per process


def enabled() -> bool:
    return os.environ.get("TRINO_TPU_EXEC_CACHE", "1").strip().lower() \
        not in ("0", "off", "false", "no")


def default_maxsize() -> int:
    return int(os.environ.get("TRINO_TPU_EXEC_CACHE_ENTRIES", "256"))


def _metrics():
    # bound lazily once: telemetry.metrics is import-light, but binding at
    # decoration time would force it on every module that defines a kernel
    global _TM
    if _TM is None:
        from ..telemetry import metrics as tm

        _TM = tm
    return _TM


_TM = None


class _ExecutableCache:
    """One bounded LRU memo over a jit-wrapper factory.  Callable drop-in
    for the ``lru_cache`` it replaces; stats are plain ints under the same
    lock the OrderedDict needs anyway (these paths already pay a Python
    dispatch per batch — a dict move is noise next to the jnp work)."""

    def __init__(self, name: str, fn: Callable, maxsize: int):
        self.name = name
        self.fn = fn
        self.maxsize = maxsize
        self._entries: OrderedDict = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.__wrapped__ = fn
        self.__doc__ = fn.__doc__

    def __call__(self, *args, **kwargs):
        key = args if not kwargs else (args, tuple(sorted(kwargs.items())))
        with self._lock:
            hit = self._entries.get(key, _MISSING)
            if hit is not _MISSING:
                self._entries.move_to_end(key)
                self.hits += 1
                _metrics().CACHE_EXEC_HITS.inc()
                return hit
        # build outside the lock: factories trace/jit and may re-enter
        value = self.fn(*args, **kwargs)
        tm = _metrics()
        tm.CACHE_EXEC_MISSES.inc()
        with self._lock:
            self.misses += 1
            if key not in self._entries:
                self._entries[key] = value
                while len(self._entries) > self.maxsize:
                    self._entries.popitem(last=False)
                    self.evictions += 1
                    tm.CACHE_EXEC_EVICTIONS.inc()
        if not kwargs:
            _record_warm_key(self.name, args)
        return value

    def warm(self, key: tuple) -> bool:
        """Re-instantiate the wrapper for a journaled key; never raises —
        a stale key (code drift across restarts) is simply skipped."""
        try:
            self(*key)
            return True
        except Exception:  # noqa: BLE001 — boot warming is best-effort
            return False

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def stats(self) -> dict:
        with self._lock:
            return {
                "tier": "exec", "name": self.name,
                "entries": len(self._entries), "bytes": 0,
                "hits": self.hits, "misses": self.misses,
                "evictions": self.evictions, "invalidations": 0,
            }


class _MISSING:  # sentinel (None is a legal cached value)
    pass


_REGISTRY: dict[str, _ExecutableCache] = {}
_EXTERNAL: dict[str, Callable[[], dict]] = {}
_REGISTRY_LOCK = threading.Lock()

# JSON-able (cache_name, key) pairs seen this process, flushed to the warm
# file at query end (flush_warm_keys) and replayed at worker boot
_WARM_LOCK = threading.Lock()
_WARM_SEEN: OrderedDict = OrderedDict()
_WARM_DIRTY = False


def jit_memo(name: str, maxsize: Optional[int] = None):
    """Decorator for jit-wrapper factories — the registry's replacement
    for ``@lru_cache(maxsize=None)``.  ``name`` must be unique (dotted
    module.func convention); ``maxsize`` defaults to the
    TRINO_TPU_EXEC_CACHE_ENTRIES knob."""

    def deco(fn: Callable):
        if not enabled():
            return lru_cache(maxsize=None)(fn)
        cache = _ExecutableCache(
            name, fn, maxsize if maxsize is not None else default_maxsize())
        with _REGISTRY_LOCK:
            if name in _REGISTRY:
                raise ValueError(f"duplicate executable cache name: {name!r}")
            _REGISTRY[name] = cache
        return cache

    return deco


PROGRAM_PREFIX = "trino_"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_COMPILE_TLS = threading.local()
_COMPILE_LISTENING = False


def program_name(site: str) -> str:
    """``kernels.compact`` -> ``trino_kernels_compact``: the name a site's
    program carries wherever JAX and the profiler show one
    (``jit_trino_kernels_compact`` on the device plane's ``XLA Modules``
    line, ``PjitFunction(trino_kernels_compact)`` on the host plane,
    ``jit(trino_kernels_compact)`` in compile logs and events)."""
    return PROGRAM_PREFIX + site.replace(".", "_")


class _Program:
    """A jitted function under its stable name.  Calling it records one
    ``launch`` event in the flight recorder (the host's time inside the
    call: argument handling, dispatch and, where the runtime's queue is
    full, the wait for room); every other attribute (``lower``, ``trace``,
    ``clear_cache`` ...) is the jitted function's own."""

    def __init__(self, name: str, jitted):
        self.name = name
        self.jitted = jitted

    def __call__(self, *args, **kwargs):
        if not profiler.enabled():
            return self.jitted(*args, **kwargs)
        t0 = profiler.now()
        try:
            return self.jitted(*args, **kwargs)
        finally:
            profiler.event(profiler.LAUNCH, self.name, t0)

    def __getattr__(self, attr):
        return getattr(self.jitted, attr)


def program(site: str, fn: Optional[Callable] = None, **jit_kwargs):
    """THE way the engine jits: ``@program("kernels.compact")`` over the
    function to trace, or ``program("join.expand", fn, donate_argnums=...)``.
    ``site`` is unique per call site (tools/lint_program_names.py), dotted
    like the ``jit_memo`` names, and never holds a shape; a factory whose
    key changes the program's role may append a short static suffix.  The
    traced function is renamed ``trino_<site>`` before ``jax.jit`` sees it,
    so traces, compile logs and ``jax.monitoring`` events name the program
    instead of ``fn``/``run``/``prog``."""
    if fn is None:
        return lambda f: program(site, f, **jit_kwargs)
    import jax

    name = program_name(site)
    try:
        fn.__name__ = fn.__qualname__ = name
    except AttributeError:
        # a bound method or partial takes no name: trace through a
        # function that does (runs while tracing only)
        inner = fn

        def fn(*args, **kwargs):
            return inner(*args, **kwargs)

        fn.__name__ = fn.__qualname__ = name
    _listen_for_compiles()
    return _Program(name, jax.jit(fn, **jit_kwargs))


def _listen_for_compiles() -> None:
    """Register, once, the ``jax.monitoring`` listeners behind the flight
    recorder's ``compile`` events: which program this thread's query had to
    get, how long that took, and whether the persistent cache served it
    (JAX fires the duration event around compile-or-load, and the hit
    event inside it, on the same thread)."""
    global _COMPILE_LISTENING
    with _REGISTRY_LOCK:
        if _COMPILE_LISTENING:
            return
        _COMPILE_LISTENING = True
    import jax.monitoring as mon

    mon.register_event_listener(_on_cache_event)
    mon.register_event_duration_secs_listener(_on_compile)


def _on_cache_event(event: str, **kw) -> None:
    if event == _CACHE_HIT_EVENT:
        _COMPILE_TLS.hit = True


def _on_compile(event: str, secs: float, **kw) -> None:
    if event != _COMPILE_EVENT:
        return
    hit = getattr(_COMPILE_TLS, "hit", False)
    _COMPILE_TLS.hit = False
    if not profiler.enabled():
        return
    name = str(kw.get("fun_name", "?"))
    # the listener hears of a compile when it is over: the ring gets the
    # whole interval, an open profiler session a marker at its end
    t1 = profiler.now()
    profiler.event(profiler.COMPILE, name, t1 - secs, t1,
                   seconds=secs, cache_hit=hit)
    profiler.annotate(profiler.COMPILE, name, seconds=secs, cache_hit=hit)


def register_external(name: str, stats_fn: Callable[[], dict]) -> None:
    """Adopt a cache the registry doesn't own (e.g. the stage compiler's
    id()-keyed accumulate memo) into the observability plane: ``stats_fn``
    returns the same dict shape as _ExecutableCache.stats()."""
    with _REGISTRY_LOCK:
        _EXTERNAL[name] = stats_fn


def registry_stats() -> list[dict]:
    with _REGISTRY_LOCK:
        caches = list(_REGISTRY.values())
        external = list(_EXTERNAL.values())
    out = [c.stats() for c in caches]
    for fn in external:
        try:
            out.append(fn())
        except Exception:  # noqa: BLE001 — observability must not throw
            continue
    return sorted(out, key=lambda r: r["name"])


def aggregate_stats() -> dict:
    agg = {"tier": "exec", "name": "exec", "entries": 0, "bytes": 0,
           "hits": 0, "misses": 0, "evictions": 0, "invalidations": 0}
    for row in registry_stats():
        for k in ("entries", "bytes", "hits", "misses", "evictions",
                  "invalidations"):
            agg[k] += row[k]
    # the entries gauge is refreshed on the observability pull path (here)
    # rather than on every memo insert — summing the registry per insert
    # would put an O(#caches) walk on the batch hot path
    _metrics().CACHE_EXEC_ENTRIES.set(agg["entries"])
    return agg


def clear_all() -> None:
    with _REGISTRY_LOCK:
        caches = list(_REGISTRY.values())
    for c in caches:
        c.clear()


# ---------------------------------------------------------------------------
# persistence: the XLA disk compile cache + the warm-key journal


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def init_compile_cache() -> str:
    """THE compile-cache rule, for every entry point (runners, worker boot,
    bench.py, tools/sf1_check.py, chip_smoke.py): where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already keeps its persistent
    cache there and no directory is set in code; otherwise the cache lives
    at the fixed ``<checkout>/.jax_cache`` — the path is part of the cache
    key, so never a temp name, pid or time.  Every compile is kept (a cold
    64-bit program costs minutes on the chip's compiler).  Returns the
    directory in effect.  Idempotent."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_CHECKOUT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


def warm_file_path() -> str:
    from ..telemetry import journal as tj

    d = os.environ.get("TRINO_TPU_JOURNAL_DIR") or tj.default_dir()
    return os.path.join(d, _WARM_FILE)


def _record_warm_key(cache_name: str, key: tuple) -> None:
    """Remember a JSON-round-trippable memo key for the warm journal.
    Keys holding dtypes/Type objects fail json.dumps and are skipped."""
    global _WARM_DIRTY
    try:
        json.dumps(key)
    except (TypeError, ValueError):
        return
    pair = (cache_name, key)
    with _WARM_LOCK:
        if pair in _WARM_SEEN:
            _WARM_SEEN.move_to_end(pair)
            return
        _WARM_SEEN[pair] = True
        while len(_WARM_SEEN) > _WARM_KEY_CAP:
            _WARM_SEEN.popitem(last=False)
        _WARM_DIRTY = True


def flush_warm_keys() -> Optional[str]:
    """Write the seen-key set to the warm file if it changed since the
    last flush (called from the query-completion path — one stat + maybe
    one small atomic write per query, never on the batch hot path)."""
    global _WARM_DIRTY
    if not enabled():
        return None
    with _WARM_LOCK:
        if not _WARM_DIRTY:
            return None
        pairs = [[name, list(key)] for (name, key) in _WARM_SEEN]
        _WARM_DIRTY = False
    path = warm_file_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump({"version": 1, "keys": pairs}, f)
        os.replace(tmp, path)
    except OSError:
        return None
    return path


def _freeze(v):
    """JSON round trip turns tuples into lists; memo keys are tuples."""
    if isinstance(v, list):
        return tuple(_freeze(x) for x in v)
    return v


def warm_at_boot(limit: int = 64) -> int:
    """Replay the warm journal: import the cache-owning modules, then
    re-instantiate up to ``limit`` recorded wrappers (most recent first —
    the file is LRU-ordered oldest-first).  With the disk compile cache
    enabled the first real invocation of each is a cache load, not a cold
    XLA compile.  Returns the number of entries warmed."""
    if not enabled() or os.environ.get(
            "TRINO_TPU_EXEC_WARM", "1").strip().lower() in (
            "0", "off", "false", "no"):
        return 0
    try:
        with open(warm_file_path(), encoding="utf-8") as f:
            doc = json.load(f)
        pairs = doc.get("keys", [])
    except (OSError, ValueError):
        return 0
    # the decorated sites only exist once their modules are imported
    for mod in ("exec.kernels", "exec.join_exec", "exec.window_kernels",
                "ops.pallas_kernels", "execution.stage_compiler",
                "execution.collective_exchange", "execution.plan_compiler"):
        try:
            __import__(f"{__package__.rsplit('.', 1)[0]}.{mod}",
                       fromlist=["_"])
        except Exception:  # noqa: BLE001
            continue
    warmed = 0
    for name, key in reversed(pairs[-limit:] if limit else pairs):
        with _REGISTRY_LOCK:
            cache = _REGISTRY.get(name)
        if cache is None:
            continue
        if cache.warm(_freeze(key)):
            warmed += 1
    return warmed


def reset_warm_state_for_test() -> None:
    global _WARM_DIRTY
    with _WARM_LOCK:
        _WARM_SEEN.clear()
        _WARM_DIRTY = False
