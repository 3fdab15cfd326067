"""What a run observes of the program without changing it: backend compiles
and persistent-cache hits (``jax.monitoring``), and — in traced runs only —
host spans around the entry points a configuration names, written into the
profiler's own trace so that they share the device's clock."""

from __future__ import annotations

import contextlib
import importlib
import logging
import re
import threading

SPAN_PREFIX = "bench."
CLIENT_SPAN = "client"


class CompileLog(logging.Handler):
    """Programs this process had to get: (program, argument shapes,
    seconds) per compile-or-load — seconds from jax.monitoring, shapes from
    the 'Compiling ...' debug line of JAX's lowering — and how many of them
    the persistent cache served.  JAX fires the duration event around
    compile_or_get_cached, so a cache hit fires it too (a CPU rehearsal run
    twice shows n events and 0 hits, then n events and n hits).  The jitted programs carry no stable names yet (ROADMAP S2), so
    the shapes are what tells one ``jit(fn)`` from another.  Copied from
    chip_smoke.py (PR 22)."""

    _EVENT = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _LOGGER = "jax._src.interpreters.pxla"

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.compiles: list = []
        self.cache_hits = 0
        # tasks compile on their own threads: the 'Compiling' line and the
        # duration event of one compile arrive on the same one
        self._last = threading.local()

    def __enter__(self) -> "CompileLog":
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        lg = logging.getLogger(self._LOGGER)
        self._was = (lg.level, lg.propagate)
        lg.setLevel(logging.DEBUG)
        lg.propagate = False
        lg.addHandler(self)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)
        lg = logging.getLogger(self._LOGGER)
        lg.removeHandler(self)
        lg.setLevel(self._was[0])
        lg.propagate = self._was[1]

    def emit(self, record: logging.LogRecord) -> None:
        if str(record.msg).startswith("Compiling %s with global shapes"):
            self._last.shapes = re.sub(r"ShapedArray\(([^)]*)\)", r"\1",
                                       str(record.args[1]))

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event == self._EVENT:
            self.compiles.append((kw.get("fun_name", "?"),
                                  getattr(self._last, "shapes", ""), secs))

    def _on_event(self, event: str, **kw) -> None:
        if event == self._HIT:
            self.cache_hits += 1

    def mark(self) -> tuple:
        return len(self.compiles), self.cache_hits

    def since(self, mark: tuple = (0, 0)) -> dict:
        """programs: every program new to this process; cache_hits: those
        the persistent cache served; compiles: the rest, compiled here."""
        done = self.compiles[mark[0]:]
        hits = self.cache_hits - mark[1]
        return {"compiles": max(len(done) - hits, 0),
                "compile_s": round(sum(c[2] for c in done), 2),
                "cache_hits": hits,
                "programs": len(done),
                "slow": [f"{c[2]:.1f}s {c[0]}{c[1][:160]}"
                         for c in done if c[2] >= 5.0]}


def report(label: str, comp: dict) -> None:
    """One line for what ``CompileLog.since`` counted, and one per compile
    of five seconds or more with its argument shapes."""
    from .deploy import say

    say(f"{label} (programs={comp['programs']} compiles={comp['compiles']} "
        f"compile_s={comp['compile_s']} "
        f"persistent_cache_hits={comp['cache_hits']})")
    for line in comp["slow"]:
        say(f"{label.split(':')[0]}: compile {line}")


def span(name: str):
    """A host span in the profiler's trace (nothing outside a trace)."""
    import jax.profiler

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


@contextlib.contextmanager
def host_spans(targets: list):
    """Wrap each ``{"name", "target": "module:Class.method"}`` so that every
    call is a span named ``bench.<name>`` — an observation made from the
    benchmark (as chip_smoke._watch_mesh_inputs wraps a JAX function), not
    an option of the program.  Undone on exit."""
    undo = []
    try:
        for t in targets:
            mod_name, _, qual = t["target"].partition(":")
            owner = importlib.import_module(mod_name)
            *path, attr = qual.split(".")
            for p in path:
                owner = getattr(owner, p)
            inner = getattr(owner, attr)

            def wrapped(*a, _inner=inner, _name=t["name"], **kw):
                with span(_name):
                    return _inner(*a, **kw)

            setattr(owner, attr, wrapped)
            undo.append((owner, attr, inner))
        yield
    finally:
        for owner, attr, inner in reversed(undo):
            setattr(owner, attr, inner)


@contextlib.contextmanager
def profiler_trace(log_dir: str):
    """A device trace without the Python tracer (the per-operator path makes
    millions of Python calls) and without HLO protos (size)."""
    import jax.profiler

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
