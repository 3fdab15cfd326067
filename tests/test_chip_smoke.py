"""chip_smoke.py rehearsed on the CPU, in-process: its load-serve-check body
returns right answers at SF0.01, its ``main`` refuses to pass anywhere but
on a TPU, and the compile cache follows the one rule it relies on."""

import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402
from trino_tpu.caching import executable_cache  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_body_returns_right_answers_on_cpu(capsys):
    # raises on any wrong answer: SF0.01 against the sqlite oracle through
    # the server, Q1/Q6 at the staged size against numpy
    chip_smoke.run_one_chip(0.01, 1 << 14)
    out = capsys.readouterr().out
    for q in chip_smoke.SMOKE_QUERIES:
        assert f"q{q} sf0.01 cold: wall=" in out
        assert f"q{q} sf0.01 warm: wall=" in out
        assert f"q{q} sf0.01: " in out and "equal the sqlite oracle's" in out
    assert "q1 sf0.01: 4 groups equal the numpy evaluation" in out
    assert "equals the numpy evaluation" in out
    assert "sort/searchsorted" in out and "pallas hash kernels" not in out


def test_main_fails_fast_when_not_a_tpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    cap = capsys.readouterr()
    assert "not a TPU" in cap.err
    # the contract's result line is printed on a chip and nowhere else
    assert '"ok"' not in cap.out


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_rule_env_wins(monkeypatch, cache_dir_config):
    # JAX itself reads JAX_COMPILATION_CACHE_DIR at import; the engine must
    # then set no directory in code
    jax.config.update("jax_compilation_cache_dir", "/somewhere/jax/put/it")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/jax/put/it")
    assert executable_cache.init_compile_cache() == "/somewhere/jax/put/it"
    assert jax.config.jax_compilation_cache_dir == "/somewhere/jax/put/it"


def test_compile_cache_rule_fixed_checkout_dir(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert executable_cache.init_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
