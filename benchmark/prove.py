"""Proves one cell inside ONE chip-tool call: one cold run, then k fresh
processes without a trace and t with one, each with another seed, and the
spread of every metric read from files.

    chiprun --timeout 3000 -- python3 benchmark/prove.py --workload <cell> \\
        --runs 6 --traced 1 [--seconds S] [--keep-cache]

This process never touches JAX (a parent that has holds the chip).  Every
run's whole output goes to chiprun_out/prove/<cell>/<label>.log, its last
line to <label>.json, and the summary to summary.json and to stdout: per set
and metric every value, the median and both spreads (the contract's
interquartile one and the driver's, harness/spread.py); from the runs'
latency lines (harness/tail.py) the pooled p50 / p90 / p99 and the bootstrap
error of one run's p90 beside the run-to-run spread; the tail split pooled
over the runs; and the bound each metric would get by the written rule.

The compile cache of a check is cold at first: without ``--keep-cache`` the
checkout's ``.jax_cache`` is emptied before the first run, so that run's
``setup_s`` is a cold start's.  With it, and where the chip tool's machine
keeps a directory between calls (``JAX_COMPILATION_CACHE_DIR`` in its
environment), the checkout's cache is first filled from there and copied
back at the end — the benchmark itself always uses ``.jax_cache``, so the
entries are keyed alike — which saves a builder the same compiles in every
call.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from harness import spread, tail  # noqa: E402  (host arithmetic, no JAX)

CACHE = os.path.join(ROOT, ".jax_cache")
BIG = 2**31 + 11  # the driver's seeds are large: more than 32 signed bits


def copy_cache(src: str, dst: str) -> int:
    """Entries of ``src`` that ``dst`` lacks.  Each entry gets the ``-atime``
    file JAX's evicting cache expects beside it (the benchmark's own cache
    never evicts and writes none; the tool's directory may)."""
    os.makedirs(dst, exist_ok=True)
    n = 0
    for name in os.listdir(src):
        if not name.endswith("-cache") \
                or os.path.exists(os.path.join(dst, name)):
            continue
        shutil.copy2(os.path.join(src, name), os.path.join(dst, name))
        with open(os.path.join(dst, name[:-len("-cache")] + "-atime"),
                  "wb") as f:
            f.write(time.time_ns().to_bytes(8, "little"))
        n += 1
    return n


def one_run(manifest: dict, out_dir: str, label: str, cell: str, seed: int,
            seconds: float, trace: int) -> dict:
    cmd = manifest["command"] + ["--workload", cell, "--seed", str(seed),
                                 "--seconds", str(seconds),
                                 "--trace", str(trace)]
    t0 = time.monotonic()
    with open(os.path.join(out_dir, f"{label}.log"), "w") as log:
        p = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE, stderr=log,
                           text=True)
        log.write("\n--- stdout ---\n" + p.stdout)
    wall = time.monotonic() - t0
    lines = p.stdout.strip().splitlines()
    result = None
    if p.returncode == 0 and lines:
        result = json.loads(lines[-1])
        with open(os.path.join(out_dir, f"{label}.json"), "w") as f:
            f.write(lines[-1] + "\n")
    print(f"prove: {label} seed={seed} trace={trace} rc={p.returncode} "
          f"wall={wall:.1f}s " + (json.dumps(
              {k: v["value"] for k, v in result["metrics"].items()})
              if result else "NO RESULT"), flush=True)
    return {"label": label, "seed": seed, "trace": trace,
            "rc": p.returncode, "wall_s": wall, "result": result,
            "latencies": tail.parse_marked(p.stdout, tail.LATENCY_MARK),
            "tail_split": tail.parse_marked(p.stdout, tail.SPLIT_MARK)}


def summarize(cell: str, seconds: float, sets: int, runs: list) -> dict:
    """``runs``: what ``one_run`` returned, labelled ``set<k>_run<i>``."""
    summary: dict = {"cell": cell, "seconds": seconds, "sets": {},
                     "latency": {}, "tail_split": {}}
    for s in range(sets):
        mine = [r for r in runs
                if r["label"].startswith(f"set{s}_") and r["result"]]
        done = [r["result"] for r in mine]
        per_metric = {}
        for name in (done[0]["metrics"] if done else {}):
            vals = [r["metrics"][name]["value"] for r in done]
            several = len(vals) >= 2
            per_metric[name] = {
                "values": vals, "median": statistics.median(vals),
                "spread": spread.iqr_spread(vals) if several else None,
                "driver_spread": spread.driver_spread(vals)
                if several else None}
        summary["sets"][f"set{s}"] = per_metric
        walls = [r["latencies"]["walls_s"] for r in mine if r["latencies"]]
        if walls:
            summary["latency"][f"set{s}"] = {
                "pooled": spread.pooled(walls),
                "tail": spread.tail_estimate(walls, tail.Q)}
        summary["tail_split"][f"set{s}"] = tail.pooled_split(
            [r["tail_split"] for r in mine])
    summary["bound_rule"] = {}
    for name in summary["sets"].get("set0", {}):
        per_set = [m[name]["values"] for m in summary["sets"].values()
                   if len(m.get(name, {}).get("values", [])) >= 2]
        if per_set:
            summary["bound_rule"][name] = spread.bound_rule(per_set)
    summary["runs"] = [{k: v for k, v in r.items() if k != "result"}
                       | {"correct": r["result"] and r["result"]["correct"]}
                       for r in runs]
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--sets", type=int, default=1,
                    help="sets of --runs runs, the same seeds in each")
    ap.add_argument("--traced", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--keep-cache", action="store_true")
    ap.add_argument("--no-cold", action="store_true",
                    help="skip the extra first run")
    ap.add_argument("--stop-after", type=float, default=None,
                    help="start no run that would end later than this many "
                         "seconds after the start (a call's time limit)")
    ap.add_argument("--max-setup", type=float, default=None,
                    help="stop when a run after the first sets up longer "
                         "than this many seconds (the cache is not working)")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    seconds = args.seconds or manifest["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out", "prove", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    kept = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if args.keep_cache:
        if kept and os.path.isdir(kept):
            print(f"prove: {copy_cache(kept, CACHE)} cache entries from "
                  f"{kept} (holds {sorted(os.listdir(kept))[:4]} ...)",
                  flush=True)
    else:
        shutil.rmtree(CACHE, ignore_errors=True)

    plan = [] if args.no_cold else [("first", BIG, 0)]
    plan += [(f"set{s}_run{i}", BIG + 1 + 7919 * i, 0)
             for s in range(args.sets) for i in range(args.runs)]
    # seeds of their own: every run of a call but a set's twin is a new seed
    plan += [(f"traced{i}", BIG + 500009 + 7919 * i, 1)
             for i in range(args.traced)]
    runs = []
    started = time.monotonic()
    for label, seed, trace in plan:
        spent = time.monotonic() - started
        if args.stop_after and runs and \
                spent + runs[-1]["wall_s"] > args.stop_after:
            print(f"prove: no time left for {label} and what follows",
                  flush=True)
            break
        runs.append(one_run(manifest, out_dir, label, args.workload, seed,
                            seconds, trace))
        r = runs[-1]["result"]
        slow = (args.max_setup and len(runs) > 1 and r and "setup_s" in
                r["metrics"] and r["metrics"]["setup_s"]["value"]
                > args.max_setup)
        if runs[-1]["rc"] != 0 or slow:
            print(f"prove: stopping after {label}: "
                  f"{'set-up too long' if slow else 'the run failed'}",
                  flush=True)
            break
    if args.keep_cache and kept and os.path.isdir(CACHE):
        print(f"prove: {copy_cache(CACHE, kept)} cache entries to {kept}",
              flush=True)

    summary = summarize(args.workload, seconds, args.sets, runs)
    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({k: summary[k] for k in (
        "sets", "latency", "tail_split", "bound_rule")}, indent=1))
    bad = [r["label"] for r in runs
           if r["rc"] != 0 or not r["result"] or not r["result"]["correct"]]
    if bad:
        print(f"prove: FAILED runs: {bad}", file=sys.stderr)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
