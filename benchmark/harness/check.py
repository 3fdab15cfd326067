"""The comparison that decides ``correct``: every answer a window returned
against the query's plain reference on the same data, to the tolerance the
configuration states.  Runs after the window, outside ``setup_s``."""

from __future__ import annotations

import math
import time

from . import deploy


def mismatch(got: list, want: list, double_rel: float) -> str | None:
    """None when ``got`` equals ``want``: same rows in the same order, exact
    for everything but a reference ``float``, which holds to ``double_rel``
    relative."""
    if len(got) != len(want):
        return f"{len(got)} rows != reference {len(want)}"
    for i, (g_row, w_row) in enumerate(zip(got, want)):
        if len(g_row) != len(w_row):
            return f"row {i}: {len(g_row)} columns != reference {len(w_row)}"
        for j, (g, w) in enumerate(zip(g_row, w_row)):
            if isinstance(w, float):
                ok = g is not None and math.isclose(
                    float(g), w, rel_tol=double_rel, abs_tol=0.0)
            else:
                ok = g == w
            if not ok:
                return f"row {i} col {j}: served {g!r} != reference {w!r}"
    return None


def judge(window, references: dict, catalog, double_rel: float) -> list:
    """One verdict per sample, in order: None (right) or what is wrong.
    ``references`` is {query: module with COLUMNS and reference()}; each
    table's columns are pulled to the host once and each distinct answer is
    compared once."""
    tables: dict = {}
    want: dict = {}
    seen: dict = {}
    verdicts = []
    for s in window.samples:
        if s.answer is None:
            verdicts.append(s.error or "failed")
            continue
        if s.query not in want:
            ref = references[s.query]
            t0 = time.monotonic()
            for t, cols in ref.COLUMNS.items():
                have = tables.setdefault(t, {})
                need = [c for c in cols if c not in have]
                if need:
                    have.update(deploy.host_columns(catalog, t, need))
            want[s.query] = ref.reference(tables)
            deploy.say(f"check: reference for {s.query} over "
                       f"{ {t: len(next(iter(c.values()))) for t, c in tables.items()} } "
                       f"rows in {time.monotonic() - t0:.1f}s")
        key = (s.query, repr(s.answer))
        if key not in seen:
            seen[key] = mismatch(s.answer, want[s.query], double_rel)
        verdicts.append(seen[key])
    return verdicts
