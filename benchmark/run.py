"""The benchmark's command (BENCHMARK.json, ``command``).

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, the only one that touches JAX.  It brings the cell's
configuration up (harness/deploy.py), warms up the cell's queries until they
get no new program, counts everything up to there as set-up, then either
measures a closed-loop window of ``--seconds`` on the client's clock
(``--trace 0``: the cell's end-to-end metrics) or traces a few queries with
the profiler (``--trace 1``: its per-layer metrics, ``busy_s``/``window_s``
and a breakdown).  Every answer of the window is then compared with the
query's plain reference.  The last stdout line is the result; every earlier
line is an observation.

This file holds no cell, query or metric: each is a file found through
BENCHMARK.json (harness/manifest.py).  No TPU, or fewer chips than the cell
asks for, is a failure with no result line.  ``--rehearse-sf <sf>`` runs the
same body at a tiny size on whatever backend there is (the CPU rehearsal of
on-chip-measurement 2), prints what would have been the result to stderr,
and still exits non-zero.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import functools  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import check, deploy, load, manifest, observe, stats  # noqa: E402
from harness import program_spans, tail  # noqa: E402
from harness import trace as tracing  # noqa: E402
from harness.client import Client  # noqa: E402
from harness.deploy import say  # noqa: E402

REHEARSAL_BATCH_ROWS = 16384
TAILS = (50, 90, 95, 99)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-sf", type=float, default=None,
                    help="run the body at this scale factor on any backend; "
                         "never a result, always a non-zero exit")
    args = ap.parse_args(argv)
    man = manifest.Manifest()
    cell = man.cell(args.workload)
    if args.seconds is None:
        args.seconds = float(man.doc["run_seconds"])

    # The compile cache: one fixed directory inside the checkout, whatever
    # the environment held — only files in the checkout outlast a run, and
    # the path is part of the cache's key.  The engine's rule is that the
    # variable wins (executable_cache.init_compile_cache).
    came_with = {k: os.environ.get(k) for k in (
        "JAX_COMPILATION_CACHE_DIR", "JAX_COMPILATION_CACHE_MAX_SIZE")}
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    # ... and never evicted: the chip tool's machines bound their own cache
    # (JAX_COMPILATION_CACHE_MAX_SIZE), and under JAX's LRU eviction every
    # write into this directory failed on the chip (FileNotFoundError on an
    # entry's -atime file; my chip run, PR 24), so no run ever started warm
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    # history-based planning reads a durable journal: a run starts from none
    journal = tempfile.mkdtemp(prefix="bench-journal-")
    os.environ["TRINO_TPU_JOURNAL_DIR"] = journal
    try:
        say(f"environment came with {came_with}")
        return body(args, man, cell)
    finally:
        shutil.rmtree(journal, ignore_errors=True)


def body(args, man, cell) -> int:
    cfg = man.config(cell["config"])
    traffic = man.traffic(cell["traffic"])
    load.check_traffic(traffic)
    queries = sorted({q["query"] for q in traffic["queries"]})
    sqls = {q: man.query_sql(q) for q in queries}
    references = {q: man.reference(q) for q in queries}
    group = "per_layer" if args.trace else "end_to_end"
    wanted = man.metrics(group, cell["name"])
    readers = {m["name"]: man.metric_reader(group, m["name"]) for m in wanted}

    import jax

    from trino_tpu.caching import result_cache
    from trino_tpu.caching.executable_cache import init_compile_cache

    t_import = time.monotonic()
    device = deploy.device_report(init_compile_cache())
    say("environment: " + " ".join(
        f"{k}={v}" for k, v in sorted(os.environ.items())
        if k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU", "TRINO_TPU_"))))
    rehearsal = args.rehearse_sf is not None
    if device["platform"] != "tpu" and not rehearsal:
        print(f"benchmark: not a TPU — JAX reports platform "
              f"{device['platform']!r}; no result", file=sys.stderr)
        return 1
    if device["count"] < cell["chips"]:
        print(f"benchmark: {cell['name']} asks for {cell['chips']} chip(s), "
              f"JAX reports {device['count']}; no result", file=sys.stderr)
        return 1
    devices = jax.devices()[:cell["chips"]]
    say(f"cell: {cell['name']} = config {cell['config']} x traffic "
        f"{cell['traffic']}; seed={args.seed} seconds={args.seconds:g} "
        f"trace={args.trace}")
    for key in cfg["_entry"]["reduced"]:
        say(f"reduced: {key} — {cfg['reduced'][key]}")
    sf = args.rehearse_sf if rehearsal else cfg["scale_factor"]
    batch_rows = REHEARSAL_BATCH_ROWS if rehearsal else cfg["batch_rows"]
    if rehearsal:
        say(f"REHEARSAL at sf={sf:g} in {batch_rows}-row batches: not the "
            f"configuration, never a result")

    run = SimpleNamespace(device=device, devices=devices, trace=None)
    with contextlib.ExitStack() as stack:
        log = run.compile_log = stack.enter_context(observe.CompileLog())
        t_device = time.monotonic()
        catalog, rows_of = deploy.load_tables(sf, batch_rows,
                                              list(cfg["tables"]))
        stated = {t: v["rows"] for t, v in cfg["tables"].items()}
        if not rehearsal and rows_of != stated:
            raise SystemExit(f"benchmark: loaded {rows_of}, the "
                             f"configuration states {stated}")
        mem = deploy.hbm(devices[0])
        observe.report(
            f"load: resident={deploy.gb(mem['in_use'])} peak="
            f"{deploy.gb(mem['peak'])} of {deploy.gb(mem['limit'])}",
            log.since())
        t_load = time.monotonic()
        run.runner, server = deploy.start_server(catalog, cfg["workers"])
        stack.callback(server.stop)
        if not cfg["result_cache"]:
            stack.enter_context(result_cache.disabled())
        make_client = functools.partial(Client, *server.address)
        t_server = time.monotonic()
        warm_up(make_client(), sqls, traffic, log)
        comp = log.since()
        run.setup_programs = comp["programs"]
        t_ready = time.monotonic()
        run.setup_s = t_ready - _T0
        say(f"setup: {run.setup_s:.1f}s = import {t_import - _T0:.1f} + "
            f"device {t_device - t_import:.1f} + load "
            f"{t_load - t_device:.1f} + server {t_server - t_load:.1f} + "
            f"warm-up {t_ready - t_server:.1f}; programs got during set-up: "
            f"{comp['programs']} ({comp['compiles']} compiled, "
            f"{comp['cache_hits']} from the persistent cache)")

        mark = log.mark()
        program_spans.begin(run)
        begun = {n: r.begin(run) if hasattr(r, "begin") else None
                 for n, r in readers.items()}
        if args.trace:
            window = traced_window(run, make_client, sqls, traffic,
                                   args.seed, cfg["host_spans"])
        else:
            window = load.run_window(make_client, sqls, traffic, args.seed,
                                     seconds=args.seconds)
        observe.report(f"window: {window.seconds:.3f}s, {window.attempted} "
                       f"queries; new programs inside it", log.since(mark))
        # what the program's recorder still holds of the window's queries,
        # before anything else runs in this process (the tail split, below)
        recorded = program_spans.recorded(run)
        # counters and memory are read here, before the check moves columns
        run.queries = window.attempted
        run.window_s = window.seconds
        run.least_bytes = sum(
            stats.least_bytes(references[s.query].COLUMNS, rows_of)
            for s in window.samples)
        counted = {n: r.read(run, begun[n]) for n, r in readers.items()} \
            if args.trace else {}
        memory_peak = deploy.peak_bytes(devices)

        verdicts = check.judge(window, references, catalog,
                               cfg["guarantees"]["double_rel"])
    wrong = [(s.query, v) for s, v in zip(window.samples, verdicts) if v]
    for q, v in wrong[:5]:
        say(f"WRONG {q}: {v}")
    right = [s for s, v in zip(window.samples, verdicts) if v is None]
    run.latencies = [s.seconds for s in right]
    run.rows_scanned = sum(
        stats.rows_read(references[s.query].COLUMNS, rows_of)
        for s in right)
    n = len(run.latencies)
    say(f"samples: n={n} right answers of {window.attempted} attempted; "
        + " ".join(f"beyond_p{q}={stats.beyond(n, q)}"
                   f"{'' if stats.supported(n, q) else '(<10)'}"
                   for q in TAILS)
        + "; slowest: " + " ".join(
            f"{x:.3f}" for x in sorted(run.latencies, reverse=True)[:5]))
    # every query's wall, and the tail beside the rest: observations for the
    # next reader of a tail, after the window and outside every timed region
    say(tail.marked(tail.LATENCY_MARK,
                    tail.latency_record(run.latencies, traffic["clients"])))
    if recorded is None:
        say("tail split: the program's recorder hands out no events")
    else:
        say(tail.marked(tail.SPLIT_MARK, tail.split(
            [s.seconds for s in window.samples],
            [v is None for v in verdicts], *recorded)))
    if not right:
        print("benchmark: no query of the window returned a right answer; "
              "no result", file=sys.stderr)
        return 1

    if args.trace:
        values = counted
    else:
        values = {name: r.read(run) for name, r in readers.items()}
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in wanted if values[m["name"]] is not None}
    device_out = dict(device, memory_peak_bytes=memory_peak)
    result = {"correct": not wrong, "attempted": window.attempted,
              "failed": len(wrong), "metrics": metrics, "device": device_out}
    if args.trace:
        device_out["busy_s"] = run.trace.busy_s()
        device_out["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    line = json.dumps(result)
    if rehearsal:
        print(f"REHEARSAL on {device['platform']} (not a result): {line}",
              file=sys.stderr)
        return 1
    print(line, flush=True)
    return 0


def warm_up(client, sqls: dict, traffic: dict, log) -> None:
    """Each query of the cell, until ``warmup_settled`` executions in a row
    got no new program (none compiled, none loaded from the persistent
    cache), at most ``warmup_max`` times.  PR 22's second runs were not
    warm: history-based planning re-plans at other buckets."""
    for q, sql in sqls.items():
        quiet = 0
        for i in range(1, traffic["warmup_max"] + 1):
            mark = log.mark()
            t0 = time.perf_counter()
            client.execute(sql)
            wall = time.perf_counter() - t0
            comp = log.since(mark)
            observe.report(f"warm-up: {q} #{i} wall={wall:.3f}s", comp)
            quiet = quiet + 1 if comp["programs"] == 0 else 0
            if quiet >= traffic["warmup_settled"]:
                break
        else:
            say(f"warm-up: {q} did NOT settle in {traffic['warmup_max']} "
                f"executions; expect new programs inside the window")


def traced_window(run, make_client, sqls, traffic, seed, host_spans):
    """``traced_queries`` queries per client under the profiler, with host
    spans around the client's call and the configuration's entry points."""
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        with observe.host_spans(host_spans), observe.profiler_trace(log_dir):
            window = load.run_window(
                make_client, sqls, traffic, seed,
                per_client=traffic["traced_queries"],
                around=lambda q: observe.span(observe.CLIENT_SPAN))
        run.trace = tracing.read_xplane(
            tracing.newest_xplane(log_dir), observe.SPAN_PREFIX,
            [s["name"] for s in host_spans] + [observe.CLIENT_SPAN],
            observe.CLIENT_SPAN,
            host_ops_stat=None if run.device["platform"] == "tpu"
            else "hlo_op")
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    for line in run.trace.seen:
        say(f"trace: {line}")
    say(f"trace: window {run.trace.window_s:.3f}s, device busy "
        f"{run.trace.busy_s():.3f}s, {run.trace.program_runs()} program "
        f"executions; idle seconds by innermost host span: "
        + json.dumps(tracing.idle_seconds_by_span(
            run.trace.first_plane_ops(), run.trace.spans,
            *run.trace.window)))
    return window


if __name__ == "__main__":
    sys.exit(main())
