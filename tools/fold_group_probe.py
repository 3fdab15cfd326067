"""What does the host pay for one launch that folds K batches?

The streamed aggregation launches ``operators.filter_project_agg`` once a
batch; PR 30's probe found a launch's host time going with the result
buffers it allocates, and the fold's results are the donated state whatever
it reads.  This times, on the attached device, Q1's and Q6's own bodies
(the benchmark's SQL, planned and run once through the engine over a pinned
``lineitem`` of 2^20-lane batches, the operators taken from that run) in
programs that fold K batches in one launch -- unrolled, and with every slot
after the first under ``lax.cond(i < n, ...)`` -- against K launches of one:
the host's time inside a launch, the device's time of one, and the compile
seconds of each.  operators._FOLD_GROUP is read off its output.

    chiprun --timeout 1500 -- python3 tools/fold_group_probe.py

Prints one JSON line per program and a summary; the same goes to
chiprun_out/fold_group_probe.json as it comes.  ``--queries q1 --plan
cond8 --ops`` narrows the run to one program and also writes, for each
program timed and for the engine's own ``operators.filter_project_agg``,
its optimized HLO text (chiprun_out/fold_group_probe_<name>.hlo.txt) and
the device's time in each of its top-level operations a launch
(``ops_us_a_launch`` in the JSON): which fusion of the fold costs what.
A CPU run (JAX_PLATFORMS=cpu --lanes-log2 12) rehearses the body and
measures nothing worth keeping.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

QUERIES = ("q6", "q1")
COLUMNS = ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
           "l_returnflag", "l_linestatus", "l_shipdate"]
# (variant, slots) in the order they are timed: what decides first
PLAN = (("unrolled", 1), ("unrolled", 8), ("cond", 8), ("unrolled", 4),
        ("cond", 4), ("unrolled", 2), ("cond", 2), ("unrolled", 16),
        ("cond", 16))
OUT = os.path.join(ROOT, "chiprun_out", "fold_group_probe.json")


def pinned_lineitem(sf: float, lanes: int, batches: int):
    """A memory-connector ``lineitem`` of ``batches`` x ``lanes`` rows, the
    tpch connector's own rows at ``sf`` tiled (each batch from another
    offset), pinned to the device as the benchmark's deployment pins it."""
    import numpy as np

    from trino_tpu.connectors.catalog import default_catalog
    from trino_tpu.spi.batch import Column, ColumnBatch
    from trino_tpu.spi.connector import TableSchema

    catalog = default_catalog(scale_factor=sf)
    tpch, mem = catalog.connector("tpch"), catalog.connector("memory")
    parts = []
    for split in tpch.get_splits("lineitem", 1, 1):
        src = tpch.create_page_source(split, COLUMNS)
        while not src.is_finished():
            b = src.get_next_batch()
            if b is not None:
                parts.append(b.compact())
    whole = ColumnBatch.concat(parts).to_host()
    at = np.arange(lanes)
    chunks = []
    for i in range(batches):
        take = (at + i * 7919) % whole.num_rows
        chunks.append(ColumnBatch(COLUMNS, [
            Column(c.type, np.asarray(c.data)[take],
                   None if c.valid is None else np.asarray(c.valid)[take],
                   c.dictionary) for c in whole.columns]))
    schema = tpch.get_table_schema("lineitem")
    mem.create_table(TableSchema(
        "lineitem", [c for c in schema.columns if c.name in COLUMNS]))
    mem.finish_insert("lineitem", [chunks])
    mem.pin_to_device("lineitem")
    mem.set_analyzed_statistics(
        "lineitem", tpch.get_table_statistics("lineitem"))
    return catalog


def captured_fold(catalog, sql: str):
    """Run ``sql`` once as the benchmark's server runs it and return (the
    fused aggregation operator of one task, every batch its feed handed
    through, over all tasks)."""
    from trino_tpu.exec.operators import HashAggregationOperator
    from trino_tpu.execution.distributed_runner import DistributedQueryRunner
    from trino_tpu.runner import Session

    seen: dict = {}
    absorbs = HashAggregationOperator.absorbs

    def spy(self, batch):
        took = absorbs(self, batch)
        if took and batch.num_rows:
            seen.setdefault(id(self), (self, []))[1].append(batch)
        return took

    HashAggregationOperator.absorbs = spy
    try:
        runner = DistributedQueryRunner(
            catalog, worker_count=2,
            session=Session(default_catalog="memory", node_count=2))
        rows = runner.execute(sql).rows()
    finally:
        HashAggregationOperator.absorbs = absorbs
    if not seen:
        raise SystemExit("no aggregation absorbed its filter/project")
    ops = list(seen.values())
    return ops[0][0], [b for _, bs in ops for b in bs], rows


def group_program(site: str, prog, agg, variant: str, slots: int,
                  donate: bool):
    """``(state, n, group) -> state``: the engine's one-batch body
    (operators._filter_project_fold_body) for each of ``slots`` slots in
    turn; ``cond`` is the engine's own grouping (kernels.fold_group_body:
    every slot after the first under ``i < n``), ``unrolled`` folds every
    slot whatever ``n``."""
    from trino_tpu.caching.executable_cache import program
    from trino_tpu.exec import kernels as K
    from trino_tpu.exec.operators import _filter_project_fold_body

    fold_one = _filter_project_fold_body(
        prog, tuple(agg.group_keys), tuple(agg.aggs), agg.step)

    def unrolled(state, n, group):
        for cols, live in group:
            state = fold_one(state, cols, live)
        return state

    return program(
        "tools.fold_group_probe." + site,
        K.fold_group_body(fold_one, slots) if variant == "cond" else unrolled,
        donate_argnums=(0,) if donate else ())


def device_times(trace_dir: str) -> tuple:
    """({program name: [seconds of each execution]}, {program name: {op:
    seconds over all executions}}) from the trace's device planes (``XLA
    Modules`` and the ``XLA Ops`` each execution holds); empty where the
    backend has none (the CPU)."""
    import bisect

    import jax.profiler

    out: dict = {}
    ops: dict = {}
    for path in glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb")):
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if not plane.name.startswith("/device:TPU:"):
                continue
            runs, events = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        name = e.name.split("(")[0]
                        out.setdefault(name, []).append(e.duration_ns / 1e9)
                        runs.append((e.start_ns, e.start_ns + e.duration_ns,
                                     name))
                elif line.name == "XLA Ops":
                    events.extend((e.start_ns, e.duration_ns, e.name)
                                  for e in line.events)
            runs.sort()
            starts = [r[0] for r in runs]
            for s, d, op in events:
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s < runs[i][1]:
                    mine = ops.setdefault(runs[i][2], {})
                    mine[op] = mine.get(op, 0.0) + d / 1e9
    return out, ops


def write_hlo(name: str, fn, *args) -> None:
    """``fn``'s optimized HLO for ``args`` into chiprun_out."""
    text = fn.lower(*args).compile().as_text()
    with open(os.path.join(os.path.dirname(OUT),
                           f"fold_group_probe_{name}.hlo.txt"), "w") as f:
        f.write(text)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=0.05,
                    help="scale of the tpch rows that are tiled")
    ap.add_argument("--lanes-log2", type=int, default=20)
    ap.add_argument("--batches", type=int, default=16)
    ap.add_argument("--passes", type=int, default=30)
    ap.add_argument("--budget-s", type=float, default=1200.0,
                    help="start no new program after this many seconds")
    ap.add_argument("--queries", default=",".join(QUERIES),
                    help="comma-separated, of " + ", ".join(QUERIES))
    ap.add_argument("--plan", default=None,
                    help="comma-separated variants to time, e.g. cond8 "
                         "(default: every one, in the order that decides)")
    ap.add_argument("--ops", action="store_true",
                    help="also write each program's HLO and its device "
                         "time by operation")
    args = ap.parse_args(argv)
    queries = tuple(args.queries.split(","))
    plan = PLAN if args.plan is None else tuple(
        (v, k) for v, k in PLAN if f"{v}{k}" in args.plan.split(","))
    t_start = time.monotonic()

    import jax
    import jax.numpy as jnp

    import trino_tpu  # noqa: F401  (x64 on)
    from trino_tpu.caching import result_cache
    from trino_tpu.caching.executable_cache import init_compile_cache
    from trino_tpu.exec import kernels as K
    from trino_tpu.exec.operators import (
        _FOLD_GROUP, _filter_project_agg_program, _masked_operands)
    from trino_tpu.spi.batch import pad_to_bucket

    init_compile_cache()
    dev = jax.devices()[0]
    lanes = 1 << args.lanes_log2
    catalog = pinned_lineitem(args.sf, lanes, args.batches)
    taken = {}
    with result_cache.disabled():
        for q in queries:
            with open(os.path.join(ROOT, "benchmark", "queries",
                                   q + ".sql")) as f:
                taken[q] = captured_fold(catalog, f.read())
            print(f"# {q}: {len(taken[q][1])} batches of "
                  f"{taken[q][1][0].num_rows} lanes absorbed, answer "
                  f"{taken[q][2][:2]}", flush=True)

    # from here on every compile is a cold one, and is timed
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    points: list = []
    built: list = []
    donate = K.donate_ok()
    counts = [jnp.int32(i) for i in range(max(k for _, k in PLAN) + 1)]
    os.makedirs(os.path.dirname(OUT), exist_ok=True)

    def emit(point: dict) -> None:
        point["platform"], point["device_kind"] = dev.platform, dev.device_kind
        points.append(point)
        print(json.dumps(point), flush=True)
        os.makedirs(os.path.dirname(OUT), exist_ok=True)
        with open(OUT, "w") as f:
            json.dump({"points": points}, f, indent=1)

    for variant, slots in plan:
        for q in queries:
            if time.monotonic() - t_start > args.budget_s:
                print(f"# over budget: {q} {variant} {slots} not run",
                      flush=True)
                continue
            agg, batches, _ = taken[q]
            fp = agg.feed
            inputs = [fp.program_inputs(pad_to_bucket(b)) for b in batches]
            prog, sig, cols0, live0 = inputs[0]
            view, has_error = prog.view(sig, cols0, live0)
            ops, _ = _masked_operands(agg.group_keys, agg.aggs, agg.step,
                                      view)
            held = [(c, m) for _, _, c, m in inputs]
            groups = [tuple(held[i:i + slots])
                      for i in range(0, len(held) - slots + 1, slots)]
            site = f"{q}_{variant}{slots}"
            fn = group_program(site, prog, agg, variant, slots, donate)
            n = counts[slots]

            def one_pass(n=n, fn=fn, groups=groups, ops=ops,
                         has_error=has_error):
                state = K.small_agg_zero_state(ops, has_error)
                jax.block_until_ready(state)
                host = []
                t0 = time.perf_counter()
                for g in groups:
                    t1 = time.perf_counter()
                    state = fn(state, n, g)
                    host.append(time.perf_counter() - t1)
                jax.block_until_ready(state)
                return host, time.perf_counter() - t0, state

            t0 = time.perf_counter()
            _, _, first_state = one_pass()
            compile_s = time.perf_counter() - t0
            host, walls = [], []
            for _ in range(args.passes):
                h, w, state = one_pass()
                host.extend(h)
                walls.append(w)
            folded = len(groups) * slots
            point = {
                "query": q, "variant": variant, "slots": slots,
                "lanes": lanes, "launches_a_pass": len(groups),
                "first_pass_s": compile_s,
                "host_us_a_launch": statistics.median(host) * 1e6,
                "host_us_a_batch": statistics.median(host) * 1e6 / slots,
                "host_us_a_launch_p90": statistics.quantiles(
                    host, n=10)[-1] * 1e6,
                "pass_us_a_batch": statistics.median(walls) * 1e6 / folded,
                # the state of every program over the same batches in the
                # same order: equal to the single launches' bit for bit on
                # integer columns
                "state_digest": [float(jnp.sum(s.astype(jnp.float64)))
                                 for s in state],
                "arguments_a_launch": len(jax.tree_util.tree_leaves(
                    (groups[0],))) + len(state) + 1,
            }
            if variant == "cond" and slots == 8:
                # three absent slots, fed slot 0's arrays: never read?
                short = tuple(groups[0][:5]) + (groups[0][0],) * 3
                five = []
                for _ in range(args.passes):
                    state = K.small_agg_zero_state(ops, has_error)
                    jax.block_until_ready(state)
                    t1 = time.perf_counter()
                    state = fn(state, counts[5], short)
                    five.append(time.perf_counter() - t1)
                    jax.block_until_ready(state)
                point["host_us_a_launch_5_of_8"] = (
                    statistics.median(five) * 1e6)
            built.append((site, one_pass))
            if args.ops:
                write_hlo(site, fn, first_state, n, groups[0])
                if variant == "cond" and slots == _FOLD_GROUP:
                    engine = _filter_project_agg_program(
                        prog, tuple(agg.group_keys), tuple(agg.aggs),
                        agg.step)
                    write_hlo(f"{q}_engine", engine,
                              K.small_agg_zero_state(ops, has_error), n,
                              groups[0])
            emit(point)

    # the device's time of one execution of each, from one traced pass
    trace_dir = tempfile.mkdtemp(prefix="fold_group_probe_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        for _, one_pass in built:
            one_pass()
    finally:
        jax.profiler.stop_trace()
    times, by_op = device_times(trace_dir)
    for p in points:
        name = ("jit_trino_tools_fold_group_probe_"
                f"{p['query']}_{p['variant']}{p['slots']}")
        runs = times.get(name)
        if runs:
            p["device_us_a_launch"] = statistics.median(runs) * 1e6
            p["device_us_a_batch"] = p["device_us_a_launch"] / p["slots"]
        if args.ops and runs:
            p["ops_us_a_launch"] = {
                op: t * 1e6 / len(runs) for op, t in sorted(
                    by_op.get(name, {}).items(), key=lambda kv: -kv[1])}

    summary = {}
    for q in queries:
        single = next((p for p in points if p["query"] == q
                       and p["slots"] == 1), None)
        if single is None:
            continue
        for p in points:
            if p["query"] == q and p["slots"] > 1:
                summary[f"{q}_{p['variant']}{p['slots']}"] = {
                    "host_launch_over_singles": p["host_us_a_launch"] / (
                        p["slots"] * single["host_us_a_launch"]),
                    "host_us_a_batch": p["host_us_a_batch"],
                    "device_us_a_batch": p.get("device_us_a_batch"),
                    "first_pass_s": p["first_pass_s"]}
    with open(OUT, "w") as f:
        json.dump({"points": points, "summary": summary}, f, indent=1)
    for p in points:
        print(json.dumps(p))
    print(json.dumps({"summary": summary}, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
