"""Reads BENCHMARK.json and finds the files that belong to each name in it.

A cell, a configuration, a traffic mix, a query, a reference and a metric
are each a file of their own; this module is the only place that knows
where they live:

    <config>        the ``file`` its BENCHMARK.json entry names
    <traffic>       benchmark/traffic/<traffic>.json
    <query>         benchmark/queries/<query>.sql, benchmark/reference/<query>.py
    <metric>        benchmark/end_to_end/<metric>.py or
                    benchmark/layer_metrics/<metric>.py
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent.parent
ROOT = BENCH_DIR.parent
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
METRIC_DIRS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


class ManifestError(ValueError):
    pass


def check_name(name) -> str:
    if not isinstance(name, str) or not NAME.fullmatch(name):
        raise ManifestError(
            f"bad name {name!r}: at most 64 letters, digits, '_', '.', '-', "
            f"not starting with '.' or '-'")
    return name


def existing(path: Path) -> Path:
    if not path.is_file():
        raise ManifestError(f"no such file: {path}")
    return path


def load_module(path: Path):
    """A module from a file whose stem may hold dots (a metric's name)."""
    existing(path)
    mod_name = "bench_" + re.sub(r"\W", "_", str(path.relative_to(BENCH_DIR)))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Manifest:
    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.bench_dir = self.root / BENCH_DIR.name
        self.doc = json.loads((self.root / "BENCHMARK.json").read_text())
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            for entry in self.doc[group]:
                check_name(entry["name"])
        for w in self.doc["workloads"]:
            check_name(w["config"])
            check_name(w["traffic"])

    # ------------------------------------------------------------- entries
    def cell(self, name: str) -> dict:
        check_name(name)
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise ManifestError(
            f"no workload {name!r} in BENCHMARK.json (has: "
            f"{[w['name'] for w in self.doc['workloads']]})")

    def config(self, name: str) -> dict:
        """The configuration's file, with its BENCHMARK.json entry's
        ``reduced`` and ``source`` beside it (the file is what is run)."""
        for c in self.doc["configs"]:
            if c["name"] == name:
                cfg = json.loads((self.root / c["file"]).read_text())
                cfg["_entry"] = c
                return cfg
        raise ManifestError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads(existing(
            self.bench_dir / "traffic" / f"{check_name(name)}.json"
        ).read_text())

    def metrics(self, group: str, cell: str) -> list:
        """Entries of ``group`` this cell reports: those with no
        ``workloads`` key, and those that list the cell."""
        return [m for m in self.doc[group]
                if "workloads" not in m or cell in m["workloads"]]

    # --------------------------------------------------------------- files
    def query_sql(self, query: str) -> str:
        return existing(
            self.bench_dir / "queries" / f"{check_name(query)}.sql"
        ).read_text().strip()

    def reference(self, query: str):
        return load_module(
            self.bench_dir / "reference" / f"{check_name(query)}.py")

    def metric_reader(self, group: str, metric: str):
        return load_module(
            self.bench_dir / METRIC_DIRS[group] / f"{check_name(metric)}.py")

    def files(self) -> list:
        """Every file BENCHMARK.json's names lead to (the tests check that
        each exists)."""
        out = []
        for c in self.doc["configs"]:
            out.append(self.root / c["file"])
        for w in self.doc["workloads"]:
            out.append(self.bench_dir / "traffic" / f"{w['traffic']}.json")
            for q in self.traffic(w["traffic"])["queries"]:
                out.append(self.bench_dir / "queries" / f"{q['query']}.sql")
                out.append(self.bench_dir / "reference" / f"{q['query']}.py")
        for group, d in METRIC_DIRS.items():
            for m in self.doc[group]:
                out.append(self.bench_dir / d / f"{m['name']}.py")
        return sorted(set(out))
