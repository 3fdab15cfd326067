#!/usr/bin/env python3
"""Every compiled program of the engine carries a stable name.

``caching/executable_cache.program(site, fn, **jit_kwargs)`` is the one
place under ``trino_tpu/`` that calls ``jax.jit``: it renames the traced
function ``trino_<site>`` first, so device traces, compile logs and
``jax.monitoring`` events name the program instead of ``fn``/``run``/
``prog``, and it records the flight recorder's ``launch`` event.  This lint
fails on

- a bare ``jax.jit`` (call, decorator, or ``from jax import jit``) outside
  that helper — a line ending in ``# jit-ok: <reason>`` is exempt;
- a ``program(...)`` whose site is not a string literal (a name never
  holds a shape or anything else computed; a static suffix may be added
  with ``+`` to a literal);
- a site name used at more than one place.

    python tools/lint_program_names.py        # exit 1 and one line a finding
"""

from __future__ import annotations

import ast
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCAN_DIR = os.path.join(REPO, "trino_tpu")
HELPER = os.path.join("trino_tpu", "caching", "executable_cache.py")
PRAGMA = "# jit-ok:"


def _is_jax_jit(node: ast.AST) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == "jit"
            and isinstance(node.value, ast.Name) and node.value.id == "jax")


def _site(node: ast.AST):
    """The literal site of a ``program(...)`` call's first argument: the
    string, or the literal left of a ``+`` (static suffix); else None."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        return _site(node.left)
    return None


def lint_source(source: str, path: str) -> tuple[list, list]:
    """(findings, sites) of one file: findings are (path, line, message),
    sites are (name, path, line) of every ``program`` call."""
    findings, sites = [], []
    lines = source.splitlines()
    tree = ast.parse(source, path)
    for node in ast.walk(tree):
        line = getattr(node, "lineno", 0)
        exempt = 0 < line <= len(lines) and PRAGMA in lines[line - 1]
        if _is_jax_jit(node) and not exempt:
            findings.append((path, line, "bare jax.jit: use "
                             "executable_cache.program(site, fn, ...)"))
        elif isinstance(node, ast.ImportFrom) and node.module == "jax" \
                and any(a.name == "jit" for a in node.names) and not exempt:
            findings.append((path, line, "from jax import jit: use "
                             "executable_cache.program(site, fn, ...)"))
        elif isinstance(node, ast.Call) and node.args and (
                (isinstance(node.func, ast.Name)
                 and node.func.id == "program")
                or (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "program")):
            site = _site(node.args[0])
            if site is None:
                # a local variable called ``program`` being called with
                # arrays is not the helper; the helper's first argument is
                # always written as a literal
                if isinstance(node.args[0], (ast.JoinedStr, ast.Name)):
                    findings.append((path, line, "program(...) site is "
                                     "not a string literal"))
                continue
            sites.append((site, path, line))
    return findings, sites


def duplicates(sites: list) -> list:
    seen: dict = {}
    for name, path, line in sites:
        seen.setdefault(name, []).append((path, line))
    return [(path, line, f"program site {name!r} is used at "
             f"{len(places)} places: " + ", ".join(
                 f"{p}:{n}" for p, n in places))
            for name, places in sorted(seen.items()) if len(places) > 1
            for path, line in places[:1]]


def run(scan_dir: str = SCAN_DIR) -> tuple[list, list]:
    findings, sites = [], []
    for root, _, files in sorted(os.walk(scan_dir)):
        for f in sorted(files):
            if not f.endswith(".py"):
                continue
            path = os.path.join(root, f)
            rel = os.path.relpath(path, REPO)
            if rel == HELPER:
                continue
            with open(path, encoding="utf-8") as fh:
                got, here = lint_source(fh.read(), rel)
            findings += got
            sites += here
    return findings + duplicates(sites), sites


def main() -> int:
    findings, sites = run()
    for path, line, msg in findings:
        print(f"{path}:{line}: {msg}", file=sys.stderr)
    print(f"{len(sites)} program sites, {len(findings)} findings")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
