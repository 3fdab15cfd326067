"""Named programs (caching/executable_cache.program): the lint that keeps
bare ``jax.jit`` and duplicate names out of ``trino_tpu/``, the name a site's
program carries in JAX's own events, and the ``launch``/``compile`` events
the wrapper writes into the flight recorder."""

import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from trino_tpu.caching import executable_cache as EC
from trino_tpu.telemetry import profiler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT = os.path.join(ROOT, "tools", "lint_program_names.py")


def _lint():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import lint_program_names as L
    finally:
        sys.path.pop(0)
    return L


@pytest.fixture()
def recorder():
    prev = profiler.set_level(1)
    profiler.reset_for_test()
    profiler.set_context("q_names", "t_0")
    yield
    profiler.set_level(prev)
    profiler.reset_for_test()


def test_no_bare_jit_and_no_duplicate_name_in_the_engine():
    proc = subprocess.run([sys.executable, LINT], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "program sites, 0 findings" in proc.stdout


@pytest.mark.parametrize("planted, message", [
    ("import jax\n\n@jax.jit\ndef fn(x):\n    return x\n", "bare jax.jit"),
    ("import jax\nf = jax.jit(lambda x: x, donate_argnums=(0,))\n",
     "bare jax.jit"),
    ("from jax import jit\n", "from jax import jit"),
    ("p = program(f'kernels.sort_{n}', fn)\n", "not a string literal"),
    ("p = program(name, fn)\n", "not a string literal"),
])
def test_lint_catches_planted_violation(planted, message):
    findings, _ = _lint().lint_source(planted, "planted.py")
    assert len(findings) == 1 and message in findings[0][2]


def test_lint_exempts_pragma_and_local_calls():
    L = _lint()
    findings, sites = L.lint_source(
        "f = jax.jit(g)  # jit-ok: a test double\n"
        "out = program(jnp.zeros((4,)), *flat)\n"     # a local, not the helper
        "p = program('kernels.x' + ('_live' if live else ''), fn)\n",
        "ok.py")
    assert findings == []
    assert sites == [("kernels.x", "ok.py", 3)]


def test_lint_reports_a_duplicate_site_once():
    L = _lint()
    _, a = L.lint_source("p = program('join.pairs', fn)\n", "a.py")
    _, b = L.lint_source("@program('join.pairs')\ndef fn(x):\n    return x\n",
                         "b.py")
    dup = L.duplicates(a + b)
    assert len(dup) == 1 and "'join.pairs' is used at 2 places" in dup[0][2]
    assert L.duplicates(a) == []


def test_engine_sites_cover_the_memo_registry():
    """Every ``jit_memo`` factory jits through ``program``: the site list
    is at least as long as the registry, and names are well-formed."""
    _, sites = _lint().run()
    names = [s[0] for s in sites]
    assert len(names) == len(set(names)) >= 40
    assert "kernels.compact" in names and "tpch.lineitem" in names
    for n in names:
        assert n.replace(".", "_").replace("_", "").isalnum(), n
        assert EC.program_name(n).startswith("trino_")
        # a query number is fine; a run of digits would be a shape
        assert not any(a.isdigit() and b.isdigit()
                       for a, b in zip(n, n[1:])), f"a shape in {n!r}?"


def test_program_renames_before_jit_and_keeps_jit_surface(recorder):
    @EC.program("tests.double")
    def fn(x):
        return x * 2

    assert fn.name == "trino_tests_double"
    x = jnp.arange(8.0)
    assert float(fn(x)[3]) == 6.0
    # the jitted function's own surface is still there
    assert "trino_tests_double" in fn.lower(x).as_text()
    assert fn.__name__ == "trino_tests_double"
    launches = [e for e in profiler.collect("q_names")
                if e["kind"] == profiler.LAUNCH]
    assert [e["name"] for e in launches] == ["trino_tests_double"]
    assert launches[0]["dur"] > 0 and launches[0]["task"] == "t_0"


def test_program_names_a_bound_method_and_passes_jit_arguments(recorder):
    class Acc:
        def run(self, state, x):
            return state + x

    prog = EC.program("tests.accumulate", Acc().run, donate_argnums=(0,))
    out = prog(jnp.zeros((4,)), jnp.ones((4,)))
    assert float(out.sum()) == 4.0
    assert "trino_tests_accumulate" in prog.lower(
        jnp.zeros((4,)), jnp.ones((4,))).as_text()


def test_launch_is_not_recorded_when_the_recorder_is_off(recorder):
    prog = EC.program("tests.off", lambda x: x + 1)
    prog(jnp.ones((2,)))
    profiler.set_level(0)
    prog(jnp.ones((2,)))
    profiler.set_level(1)
    assert len([e for e in profiler.collect("q_names")
                if e["kind"] == profiler.LAUNCH]) == 1


def test_compile_event_names_the_program_once(recorder):
    prog = EC.program("tests.compiled", lambda x: x * 3 + 1)
    prog(jnp.ones((16,)))
    prog(jnp.ones((16,)))           # second call: no compile
    evs = [e for e in profiler.collect("q_names")
           if e["kind"] == profiler.COMPILE
           and "trino_tests_compiled" in e["name"]]
    assert len(evs) == 1
    assert evs[0]["name"] == "jit(trino_tests_compiled)"
    assert evs[0]["args"]["seconds"] == pytest.approx(evs[0]["dur"])
    assert evs[0]["args"]["cache_hit"] is False
