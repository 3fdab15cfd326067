"""The three-tier cache plane (trino_tpu/caching/): plan-cache hits skip
planning, the versioned result cache never serves a stale row past an
INSERT, planning-env flips miss, the executable registry is bounded and
evictable, warm keys survive a (subprocess-simulated) worker restart, the
``=0`` kill switches reproduce legacy behavior bit-for-bit, the
``system.runtime.caches`` table, and the tools/lint_cache_bounds.py
contract."""

import json
import os
import subprocess
import sys

import pytest

from trino_tpu import caching
from trino_tpu.caching import executable_cache, plan_cache, result_cache
from trino_tpu.connectors.catalog import default_catalog
from trino_tpu.runner import Session, StandaloneQueryRunner
from trino_tpu.telemetry import journal

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_caches(tmp_path, monkeypatch):
    monkeypatch.setenv("TRINO_TPU_JOURNAL_DIR", str(tmp_path / "journal"))
    for knob in ("TRINO_TPU_PLAN_CACHE", "TRINO_TPU_RESULT_CACHE"):
        monkeypatch.delenv(knob, raising=False)
    caching.reset_for_test()
    journal.reset_for_test()
    yield
    caching.reset_for_test()
    journal.reset_for_test()


@pytest.fixture()
def runner():
    return StandaloneQueryRunner(
        default_catalog(scale_factor=0.01),
        session=Session(default_catalog="memory"))


# ------------------------------------------------------- Tier A: plan cache


def test_repeated_text_hits_plan_and_result_tiers(runner):
    q = "select count(*) from tpch.tiny.region"
    first = runner.execute(q).rows()
    hits0 = plan_cache.stats()["hits"]
    rhits0 = result_cache.stats()["hits"]
    assert runner.execute(q).rows() == first
    assert plan_cache.stats()["hits"] == hits0 + 1
    assert result_cache.stats()["hits"] == rhits0 + 1


def test_planning_env_flip_misses_plan_cache(runner):
    q = "select n_regionkey, count(*) from tpch.tiny.nation " \
        "group by n_regionkey"
    runner.execute(q)
    assert plan_cache.lookup(q, runner.session, runner.catalog) is not None
    # TRINO_TPU_BROADCAST_ROW_LIMIT steers the optimizer's join
    # distribution — a plan cached under another limit must not be reused
    was = os.environ.get("TRINO_TPU_BROADCAST_ROW_LIMIT")
    os.environ["TRINO_TPU_BROADCAST_ROW_LIMIT"] = \
        "12345" if was != "12345" else "54321"
    try:
        assert plan_cache.lookup(q, runner.session, runner.catalog) is None
    finally:
        if was is None:
            del os.environ["TRINO_TPU_BROADCAST_ROW_LIMIT"]
        else:
            os.environ["TRINO_TPU_BROADCAST_ROW_LIMIT"] = was
    assert plan_cache.lookup(q, runner.session, runner.catalog) is not None


def test_ddl_invalidates_cached_plans(runner):
    runner.execute("create table g as select n_nationkey from "
                   "tpch.tiny.nation")
    q = "select count(*) from g"
    assert runner.execute(q).rows() == [(25,)]
    assert plan_cache.lookup(q, runner.session, runner.catalog) is not None
    runner.execute("drop table g")
    # generation bump: the cached plan must not resolve the dropped table
    assert plan_cache.lookup(q, runner.session, runner.catalog) is None


# --------------------------------------------- Tier C: versioned result cache


def test_insert_mutation_never_serves_stale_results(runner):
    runner.execute("create table t as select n_nationkey from "
                   "tpch.tiny.nation")
    q = "select count(*) from t"
    assert runner.execute(q).rows() == [(25,)]
    assert runner.execute(q).rows() == [(25,)]
    assert result_cache.stats()["hits"] >= 1
    runner.execute("insert into t select n_nationkey from tpch.tiny.nation "
                   "where n_regionkey = 1")
    # the version vector moved: a hit here would be a stale serve
    assert runner.execute(q).rows() == [(30,)]
    assert result_cache.stats()["invalidations"] >= 1
    # and the post-mutation result is itself cacheable again
    rhits = result_cache.stats()["hits"]
    assert runner.execute(q).rows() == [(30,)]
    assert result_cache.stats()["hits"] == rhits + 1


def test_result_cache_byte_budget_evicts(runner, monkeypatch):
    monkeypatch.setenv("TRINO_TPU_RESULT_CACHE_BYTES", "4096")
    for i in range(40):
        runner.execute(f"select n_nationkey + {i} from tpch.tiny.nation")
    s = result_cache.stats()
    assert s["bytes"] <= 4096
    assert s["evictions"] > 0


# --------------------------------------------- Tier B: executable registry


def test_exec_registry_is_bounded_and_evicts():
    built = []

    @executable_cache.jit_memo("test.evict_probe", maxsize=2)
    def build(x):
        built.append(x)
        return x * 10

    assert build(1) == 10 and build(2) == 20
    assert build(1) == 10  # hit — no rebuild
    assert built == [1, 2]
    assert build(3) == 30  # evicts key 2 (LRU)
    s = build.stats()
    assert s["entries"] == 2
    assert s["evictions"] == 1
    assert build(2) == 20  # re-built after eviction
    assert built == [1, 2, 3, 2]


def test_warm_keys_survive_restart(tmp_path):
    """Process 1 runs a query and journals its memo keys; process 2 (a
    'rebooted worker') replays them into live wrappers before any query."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               TRINO_TPU_JOURNAL_DIR=os.environ["TRINO_TPU_JOURNAL_DIR"])
    out = subprocess.run([sys.executable, "-c", _CHILD_WARM_WRITE],
                         cwd=REPO, env=env, capture_output=True, text=True,
                         timeout=300)
    assert "WRITE_OK" in out.stdout, out.stderr[-2000:]
    out = subprocess.run([sys.executable, "-c", _CHILD_WARM_BOOT],
                         cwd=REPO, env=env, capture_output=True, text=True,
                         timeout=300)
    assert "BOOT_OK" in out.stdout, out.stderr[-2000:]


_CHILD_WARM_WRITE = r"""
import json, os
from trino_tpu.caching import executable_cache as ec
from trino_tpu.connectors.catalog import default_catalog
from trino_tpu.runner import StandaloneQueryRunner

r = StandaloneQueryRunner(default_catalog(scale_factor=0.01))
r.execute("select r_name, count(*) from tpch.tiny.region group by r_name")
ec.flush_warm_keys()
with open(ec.warm_file_path(), encoding="utf-8") as f:
    assert len(json.load(f)["keys"]) > 0
print("WRITE_OK")
"""

_CHILD_WARM_BOOT = r"""
from trino_tpu.caching import executable_cache as ec

n = ec.warm_at_boot()
assert n > 0, "expected journaled keys to re-instantiate wrappers"
assert sum(r["entries"] for r in ec.registry_stats()) >= n
print("BOOT_OK")
"""


# --------------------------------------------------- kill switches: =0 legacy


def test_disabled_tiers_match_enabled_results(runner):
    """Plan/result knobs are per-lookup; EXEC is decoration-time, so the
    full =0 stack runs in a subprocess and must be bit-for-bit."""
    q = ("select n_regionkey, count(*) from tpch.tiny.nation "
         "group by n_regionkey order by n_regionkey")
    enabled_rows = [list(r) for r in runner.execute(q).rows()]
    assert [list(r) for r in runner.execute(q).rows()] == enabled_rows

    env = dict(os.environ, JAX_PLATFORMS="cpu", TRINO_TPU_PLAN_CACHE="0",
               TRINO_TPU_RESULT_CACHE="0", TRINO_TPU_EXEC_CACHE="0")
    out = subprocess.run(
        [sys.executable, "-c", _CHILD_DISABLED % (q, q)], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300)
    assert "DISABLED_OK" in out.stdout, out.stderr[-2000:]
    child_rows = json.loads(out.stdout.splitlines()[0])
    assert child_rows == enabled_rows


_CHILD_DISABLED = r"""
import json
from trino_tpu.caching import executable_cache as ec
from trino_tpu.caching import plan_cache, result_cache
from trino_tpu.connectors.catalog import default_catalog
from trino_tpu.runner import StandaloneQueryRunner

r = StandaloneQueryRunner(default_catalog(scale_factor=0.01))
rows = [list(x) for x in r.execute(%r).rows()]
rows2 = [list(x) for x in r.execute(%r).rows()]
assert rows == rows2
# no tier may have engaged: no registry caches, no plan/result activity
assert not ec._REGISTRY
assert plan_cache.stats()["hits"] == plan_cache.stats()["entries"] == 0
assert result_cache.stats()["hits"] == result_cache.stats()["entries"] == 0
print(json.dumps(rows))
print("DISABLED_OK")
"""


def test_in_process_plan_result_kill_switches(runner, monkeypatch):
    monkeypatch.setenv("TRINO_TPU_PLAN_CACHE", "0")
    monkeypatch.setenv("TRINO_TPU_RESULT_CACHE", "0")
    q = "select count(*) from tpch.tiny.region"
    first = runner.execute(q).rows()
    assert runner.execute(q).rows() == first
    assert plan_cache.stats()["entries"] == 0
    assert result_cache.stats()["entries"] == 0


# ------------------------------------------------------------- observability


def test_runtime_caches_table_lists_all_tiers(runner):
    runner.execute("select count(*) from tpch.tiny.region")
    rows = runner.execute(
        "select tier, name, hits, misses from system.runtime.caches").rows()
    tiers = {r[0] for r in rows}
    assert {"plan", "exec", "result"} <= tiers
    plan_row = next(r for r in rows if r[0] == "plan")
    assert plan_row[2] + plan_row[3] > 0  # the probe query was counted


def test_rest_caches_endpoint(runner):
    import urllib.request

    from trino_tpu.server import TrinoTpuServer

    srv = TrinoTpuServer(runner, port=0).start()
    try:
        host, port = srv.address
        doc = json.load(urllib.request.urlopen(
            f"http://{host}:{port}/v1/caches"))
        assert {r["tier"] for r in doc["caches"]} == \
            {"plan", "exec", "result"}
        detail = json.load(urllib.request.urlopen(
            f"http://{host}:{port}/v1/caches?detail=1"))
        assert len(detail["caches"]) >= len(doc["caches"])
    finally:
        srv.stop()


# ------------------------------------------------- lint_cache_bounds contract


def test_cache_bounds_lint_clean():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint_cache_bounds.py")],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, \
        f"unbounded memo caches:\n{proc.stdout}\n{proc.stderr}"


def test_cache_bounds_lint_catches_planted(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import lint_cache_bounds as L
    finally:
        sys.path.pop(0)
    bad = tmp_path / "bad.py"
    bad.write_text(
        "from functools import lru_cache\n"
        "@lru_cache\n"
        "def a(): pass\n"
        "@lru_cache(maxsize=None)\n"
        "def b(): pass\n"
        "@lru_cache(maxsize=32)\n"
        "def c(): pass\n"
        "@lru_cache(maxsize=None)  # cache-ok: test pragma\n"
        "def d(): pass\n")
    findings = L.lint_file(str(bad))
    assert len(findings) == 2  # bounded + pragma lines pass
    assert {lineno for _, lineno, _ in findings} == {2, 4}
