"""Whole-query GSPMD compilation (execution/plan_compiler.py): the
fragmenter coalesces a maximal broadcast-join tree under a fusable
PARTIAL->FINAL seam into ONE ResidentPlan, and the runner compiles it as
one jitted program per feed batch — joins, chain, partial agg and state
merge inlined — with the build tables broadcast-replicated in-program.

Equivalence contract mirrors test_fused_stage: integer / decimal /
string / count outputs are bit-identical against the legacy path;
float64 sums/avgs compare at rel 1e-12 (state-merge reassociation).
``TRINO_TPU_RESIDENT_PLAN=0`` IS the task-per-worker path, bit-for-bit.
"""

import json
import math
import os
import socket
import subprocess
import sys
import textwrap

import pytest

from trino_tpu.connectors.catalog import default_catalog
from trino_tpu.connectors.tpch_queries import QUERIES
from trino_tpu.execution.distributed_runner import DistributedQueryRunner
from trino_tpu.execution.fragmenter import fragment_plan
from trino_tpu.execution.plan_compiler import ResidentPlanExec
from trino_tpu.runner import Session
from trino_tpu.testing.oracle import SqliteOracle, assert_same_rows

TABLES = ["customer", "orders", "lineitem"]


@pytest.fixture(autouse=True)
def _no_result_cache(monkeypatch):
    # these tests introspect execution internals (_resident_edges, rstats)
    # on repeated statements — a served cached result would skip the very
    # path under test
    monkeypatch.setenv("TRINO_TPU_RESULT_CACHE", "0")


@pytest.fixture(scope="module")
def harness():
    catalog = default_catalog(scale_factor=0.01)
    dist = DistributedQueryRunner(
        catalog, worker_count=4, session=Session(node_count=4))
    oracle = SqliteOracle()
    conn = catalog.connector("tpch")
    for t in TABLES:
        schema = conn.get_table_schema(t)
        cols = schema.column_names()
        batches = []
        for s in conn.get_splits(t, 2, 1):
            src = conn.create_page_source(s, cols)
            while not src.is_finished():
                b = src.get_next_batch()
                if b is not None:
                    batches.append(b)
        oracle.load_table(t, batches)
    yield dist, oracle
    # drop this module's compiled resident/build-prep programs: each holds
    # a jitted XLA executable, and the full tier-1 suite runs close enough
    # to the process mmap ceiling that keeping them segfaults a later
    # unrelated compile
    from trino_tpu.caching import executable_cache as ec
    import trino_tpu.execution.plan_compiler as pc

    for name in ("resident._program", "resident._build_prep"):
        cache = ec._REGISTRY.get(name)
        if cache is not None:
            cache.clear()
    with pc._RES_LOCK:
        pc._RES_TRACE_SIGS.clear()


def _rows(result):
    return sorted(map(tuple, result.rows()))


def _assert_equiv(res_rows, legacy_rows):
    assert len(res_rows) == len(legacy_rows)
    for rr, lr in zip(res_rows, legacy_rows):
        assert len(rr) == len(lr)
        for rv, lv in zip(rr, lr):
            if isinstance(rv, float) or isinstance(lv, float):
                assert math.isclose(float(rv), float(lv),
                                    rel_tol=1e-12, abs_tol=1e-12), (rv, lv)
            else:
                assert rv == lv, (rv, lv)


def _resident_execs(dist):
    return [e for e in dist._resident_edges.values()
            if isinstance(e, ResidentPlanExec)]


# ---------------------------------------------------------------------------
# fragmenter: plan coalescing + edge contracts


def test_fragmenter_coalesces_resident_plan(harness):
    dist, _ = harness
    plan = dist.create_plan(QUERIES[3])
    sp = fragment_plan(plan)
    marked = [f for f in sp.all_fragments()
              if getattr(f, "resident_plan", None) is not None]
    assert len(marked) == 1, "q3 must coalesce into ONE resident plan"
    f = marked[0]
    rp = f.resident_plan
    assert rp.core_fid == f.id and f.device_resident
    # q3: customer + orders builds + lineitem probe spine + FINAL consumer
    assert len(rp.fragment_ids) == 4
    assert len(rp.joins) == 2
    assert all(j.join_type == "INNER" for j in rp.joins)
    # per-edge PartitionSpec contracts: builds broadcast to replicated,
    # the terminal seam keeps dim 0 sharded on the mesh axis on BOTH sides
    bcast = [e for e in rp.edges if e.kind == "BROADCAST"]
    seam = [e for e in rp.edges if e.kind == "REPARTITION"]
    assert len(bcast) == 2 and len(seam) == 1
    for e in bcast:
        assert e.in_spec == ("x",) and e.out_spec == ()
    assert seam[0].in_spec == seam[0].out_spec == ("x",)
    assert seam[0].consumer_fid == rp.consumer_fid
    assert "resident-plan[4f/3e]" in sp.text()


# ---------------------------------------------------------------------------
# execution: one dispatch per batch, codes across seams, row equivalence


def test_q3_resident_vs_legacy(harness, monkeypatch):
    """The whole q3 join tree + agg runs as ONE jit dispatch per feed
    batch (launches/batch == 1), dictionary codes cross the customer
    broadcast seam as codes, and rows match the task-per-worker path."""
    dist, oracle = harness
    monkeypatch.setenv("TRINO_TPU_RESIDENT_PLAN", "auto")
    resident = dist.execute(QUERIES[3])
    execs = _resident_execs(dist)
    assert len(execs) == 1, "expected q3 to run as one resident plan"
    rs = execs[0].rstats
    assert rs.plans == 1 and rs.seams == 3
    assert rs.batches > 0
    assert rs.jit_calls == rs.batches, \
        "a resident plan must be ONE jitted call per batch"
    assert rs.launches_per_batch == 1.0
    # c_mktsegment's dict codes crossed the broadcast seam WITHOUT
    # materializing to values
    assert rs.code_seam_columns >= 1
    assert rs.merges == 1 and rs.fallbacks == 0

    monkeypatch.setenv("TRINO_TPU_RESIDENT_PLAN", "0")
    legacy = dist.execute(QUERIES[3])
    assert not dist._resident_edges, "=0 must disable resident compilation"
    assert dist._fused_edges, "=0 must restore the PR 6 fused seam"
    _assert_equiv(_rows(resident), _rows(legacy))
    assert_same_rows(resident.rows(), oracle.query(QUERIES[3]), ordered=True)
    assert_same_rows(legacy.rows(), oracle.query(QUERIES[3]), ordered=True)


def test_build_origin_dict_group_key(harness, monkeypatch):
    """Group key sourced from the BUILD side of an inlined join: the key's
    dictionary is the stable merged build dictionary, pinned for the whole
    query (no per-batch drift remaps)."""
    dist, oracle = harness
    sql = ("select c_mktsegment, count(*), sum(o_totalprice) "
           "from customer, orders where c_custkey = o_custkey "
           "group by c_mktsegment")
    monkeypatch.setenv("TRINO_TPU_RESIDENT_PLAN", "auto")
    result = dist.execute(sql)
    execs = _resident_execs(dist)
    assert execs, "expected a resident plan over the customer build"
    rs = execs[0].rstats
    assert rs.jit_calls == rs.batches and rs.code_seam_columns >= 1
    assert_same_rows(result.rows(), oracle.query(sql))

    monkeypatch.setenv("TRINO_TPU_RESIDENT_PLAN", "0")
    legacy = dist.execute(sql)
    _assert_equiv(_rows(result), _rows(legacy))


def test_steady_state_hits_program_cache(harness, monkeypatch):
    """Second identical run: every dispatch hits the resident program's
    shape-signature cache — compiles are O(#buckets), not O(#batches)."""
    dist, _ = harness
    monkeypatch.setenv("TRINO_TPU_RESIDENT_PLAN", "auto")
    dist.execute(QUERIES[3])  # warm
    dist.execute(QUERIES[3])
    (ex,) = _resident_execs(dist)
    rs = ex.rstats
    assert rs.batches > 0
    assert rs.programs == 0, "steady-state traffic must never retrace"
    assert rs.cache_hits == rs.jit_calls


# ---------------------------------------------------------------------------
# fallbacks: overflow + duplicate build keys re-run the legacy path


def test_overflow_falls_back(harness, monkeypatch):
    """More groups than TRINO_TPU_FUSED_CAP: the overflow scalar trips at
    finish, the runner counts a resident fallback and re-runs the subplan
    on the task-per-worker path (no group cap) — correct results."""
    dist, oracle = harness
    monkeypatch.setenv("TRINO_TPU_RESIDENT_PLAN", "auto")
    monkeypatch.setenv("TRINO_TPU_FUSED_CAP", "8")
    before = dist.resident_fallbacks
    result = dist.execute(QUERIES[3])
    assert dist.resident_fallbacks == before + 1
    assert_same_rows(result.rows(), oracle.query(QUERIES[3]), ordered=True)


def test_duplicate_build_keys_fall_back(harness, monkeypatch):
    """The inlined sorted probe is 1-match; a build side with duplicate
    join keys trips the replicated dup flag at prep and the plan falls
    back to the legacy multi-match join — results stay correct."""
    dist, oracle = harness
    # join keyed on o_custkey: customers place many orders, so the build
    # table carries duplicate live keys
    sql = ("select c_mktsegment, count(*) "
           "from customer, orders where c_nationkey = o_custkey "
           "group by c_mktsegment")
    monkeypatch.setenv("TRINO_TPU_RESIDENT_PLAN", "auto")
    plan = dist.create_plan(sql)
    sp = fragment_plan(plan)
    assert any(getattr(f, "resident_plan", None) is not None
               for f in sp.all_fragments()), \
        "the dup-key query must still COALESCE (dups are a runtime fact)"
    before = dist.resident_fallbacks
    result = dist.execute(sql)
    assert dist.resident_fallbacks == before + 1
    assert_same_rows(result.rows(), oracle.query(sql))


# ---------------------------------------------------------------------------
# gating knobs


def test_mesh_shape_cap_disables(harness, monkeypatch):
    """TRINO_TPU_MESH_SHAPE narrower than the task count: the plan can't
    claim its mesh, the PR 6 fused seam takes the edge back."""
    dist, oracle = harness
    monkeypatch.setenv("TRINO_TPU_RESIDENT_PLAN", "auto")
    monkeypatch.setenv("TRINO_TPU_MESH_SHAPE", "2")
    result = dist.execute(QUERIES[3])
    assert not dist._resident_edges
    assert dist._fused_edges
    assert_same_rows(result.rows(), oracle.query(QUERIES[3]), ordered=True)


def test_max_fragments_gate(harness, monkeypatch):
    """A 4-fragment plan under TRINO_TPU_RESIDENT_MAX_FRAGMENTS=2 stays on
    the fused path."""
    dist, _ = harness
    monkeypatch.setenv("TRINO_TPU_RESIDENT_PLAN", "auto")
    monkeypatch.setenv("TRINO_TPU_RESIDENT_MAX_FRAGMENTS", "2")
    dist.execute(QUERIES[3])
    assert not dist._resident_edges
    assert dist._fused_edges


# ---------------------------------------------------------------------------
# warm journal: resident program keys are JSON-able and replayable


def test_resident_program_memo_key_warms(harness, monkeypatch):
    """The resident accumulate memo keys on a VALUE (base64 plan payload),
    unlike the id()-keyed fused memo — so the key survives json round-trip
    and cache.warm() re-instantiates the program at boot."""
    from trino_tpu.caching import executable_cache as ec

    dist, _ = harness
    monkeypatch.setenv("TRINO_TPU_RESIDENT_PLAN", "auto")
    dist.execute(QUERIES[3])
    with ec._WARM_LOCK:
        keys = [list(key) for (name, key) in ec._WARM_SEEN
                if name == "resident._program"]
    assert keys, "resident._program must journal a warm key"
    round_tripped = json.loads(json.dumps(keys[0]))
    cache = ec._REGISTRY["resident._program"]
    assert cache.warm(tuple(round_tripped)), \
        "boot replay must rebuild the resident program from the journal"


# ---------------------------------------------------------------------------
# multi-process: one program spans two host processes on a CPU mesh


def test_init_distributed_gloo_before_initialize(monkeypatch):
    """The gloo CPU-collectives backend must be selected BEFORE
    jax.distributed.initialize — the default XLA CPU backend rejects
    multi-process collectives outright."""
    import trino_tpu.execution.plan_compiler as pc

    seen = []
    monkeypatch.setattr(pc.jax.config, "update",
                        lambda k, v: seen.append((k, v)))
    monkeypatch.setattr(pc.jax.distributed, "initialize",
                        lambda **kw: seen.append(("initialize", kw)))
    pc.init_distributed("127.0.0.1:9999", num_processes=2, process_id=1)
    assert seen[0] == ("jax_cpu_collectives_implementation", "gloo")
    assert seen[1] == ("initialize", {
        "coordinator_address": "127.0.0.1:9999",
        "num_processes": 2, "process_id": 1})


_CHILD = textwrap.dedent("""
    import sys

    port, pid = sys.argv[1], int(sys.argv[2])

    # worker boot order matters: importing the engine itself traces jax
    # programs, and jax.distributed.initialize refuses to run after ANY
    # computation — so distributed bring-up comes first, with the same
    # gloo-before-initialize recipe as plan_compiler.init_distributed
    import jax
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                               num_processes=2, process_id=pid)

    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from trino_tpu.execution.plan_compiler import _AXIS

    assert jax.process_count() == 2, jax.process_count()
    assert jax.device_count() == 8, jax.device_count()
    assert jax.local_device_count() == 4, jax.local_device_count()

    mesh = Mesh(jax.devices(), (_AXIS,))
    per = 3
    local = np.arange(4 * per, dtype=np.int64) + pid * 4 * per
    shards = [jax.device_put(local[i * per:(i + 1) * per], d)
              for i, d in enumerate(jax.local_devices())]
    g = jax.make_array_from_single_device_arrays(
        (8 * per,), NamedSharding(mesh, P(_AXIS)), shards)

    fn = jax.jit(jax.shard_map(
        lambda x: jax.lax.all_gather(x, _AXIS, tiled=True),
        mesh=mesh, in_specs=P(_AXIS), out_specs=P(), check_vma=False))
    rep = np.asarray(fn(g).addressable_shards[0].data)
    assert (rep == np.arange(8 * per)).all(), rep
    print(f"RESIDENT-MP-OK {pid}")
""")


def test_two_process_cpu_mesh_collectives(tmp_path):
    """jax.distributed bring-up with the gloo CPU-collectives backend: two
    host processes, 4 forced devices each, one 8-device global mesh; the
    resident plan's broadcast gather (all_gather P("x") -> P()) produces
    the full replicated table in BOTH processes."""
    script = tmp_path / "resident_mp_child.py"
    script.write_text(_CHILD)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", ""),
    })
    # a child inheriting the parent's 8-device forcing would skew the
    # global mesh; the env above overrides it explicitly
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(port), str(pid)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env)
        for pid in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=180)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {pid} failed:\n{out}"
        assert f"RESIDENT-MP-OK {pid}" in out
