"""What a window's tail is made of: every query's wall kept on one
observation line, and the queries beyond a percentile split, beside the
rest, by the program's own flight-recorder spans (durations only; no clock
is matched).  Printed after the window, outside every timed region; never a
metric.  Pure functions of lists and of ``profiler.events_since`` dicts."""

from __future__ import annotations

import json

from . import stats
from . import trace as T

Q = 90
LATENCY_MARK = "latencies: "
SPLIT_MARK = "tail split: "
PARTS = ("client_minus_query", "query_minus_execute", "plan", "schedule",
         "tasks", "remainder")
# the server closes its ``query`` span after the last page is sent, so a
# client that reads it at once can have waited a little LESS than the span
# (the handler thread's turn at the interpreter); half of it is another query
PAIRING_LEAST_SHARE = 0.5


def sig4(x: float) -> float:
    return float(f"{x:.4g}")


def beyond(walls: list, q: float = Q) -> list:
    """Positions of the walls above the nearest-rank q-th percentile."""
    if not walls:
        return []
    p = stats.percentile(walls, q)
    return [i for i, w in enumerate(walls) if w > p]


def latency_record(walls: list, clients: int, q: float = Q) -> dict:
    """``walls``: seconds of every right answer, in the order the samples
    were kept — with one client the order sent, with more the order
    completed (the generator keeps no client with a sample)."""
    return {"n": len(walls), "clients": clients,
            "order": "sent" if clients == 1 else "completed", "q": q,
            "beyond": beyond(walls, q), "walls_s": [sig4(w) for w in walls]}


def marked(mark: str, record: dict) -> str:
    return mark + json.dumps(record, separators=(",", ":"))


def parse_marked(text: str, mark: str) -> dict | None:
    """The record of the last line of ``text`` that carries ``mark``."""
    for line in reversed(text.splitlines()):
        at = line.find(mark + "{")
        if at >= 0:
            return json.loads(line[at + len(mark):])
    return None


# ------------------------------------------------------------- the split

def by_query(events: list) -> dict:
    """{query id: {kind: [(start, seconds)]}} for the five layer kinds."""
    out: dict = {}
    for e in events:
        if e["kind"] in ("query", "execute", "plan", "schedule", "task") \
                and e.get("query"):
            out.setdefault(e["query"], {}).setdefault(e["kind"], []).append(
                (e["ts"], e["dur"]))
    return out


def parts_of(wall: float, spans: dict) -> dict:
    """One query's wall as a sum: client wall - ``query``, ``query`` -
    ``execute``, ``plan``, the ``schedule`` span outside its tasks, the
    union of ``task``, and what is left of ``execute``."""
    total = {k: sum(d for _, d in spans.get(k, []))
             for k in ("query", "execute", "plan", "schedule")}
    tasks = T.busy_seconds(spans.get("task", []))
    return {"client_minus_query": wall - total["query"],
            "query_minus_execute": total["query"] - total["execute"],
            "plan": total["plan"], "schedule": total["schedule"] - tasks,
            "tasks": tasks,
            "remainder": total["execute"] - total["plan"] - total["schedule"]}


def pair(walls: list, events: list) -> tuple:
    """The recorder's queries laid beside the samples: ``walls`` in the order
    the window kept them (all attempted, completion order), the ``query``
    spans in the order they ended.  The recorder keeps the newest queries
    only, so the k it still holds are the LAST k samples.  Returns
    ([(position, wall, spans)], why-not): a pairing in which a client waited
    under half of what its server took is refused, not guessed at (with one
    client the order alone pairs them; the check is for mixes)."""
    held = sorted(((q, s) for q, s in by_query(events).items()
                   if len(s.get("query", [])) == 1 and s.get("execute")),
                  key=lambda qs: sum(qs[1]["query"][0]))
    held = held[-len(walls):] if walls else []
    first = len(walls) - len(held)
    pairs = [(first + k, walls[first + k], s)
             for k, (_, s) in enumerate(held)]
    for pos, wall, s in pairs:
        if wall < PAIRING_LEAST_SHARE * s["query"][0][1]:
            return [], (f"sample {pos} waited {wall:.6f} s, the query span "
                        f"laid beside it took {s['query'][0][1]:.6f} s: not "
                        f"the same queries")
    return pairs, ""


def mean_parts(rows: list) -> dict | None:
    """Mean wall and mean of every part over [(wall, parts)], in ms."""
    if not rows:
        return None
    n = len(rows)
    out = {"n": n, "wall_ms": sum(w for w, _ in rows) / n * 1e3}
    for k in PARTS:
        out[k + "_ms"] = sum(p[k] for _, p in rows) / n * 1e3
    return out


def split(walls: list, right: list, events: list, dropped: int,
          q: float = Q) -> dict:
    """``walls``: every attempted sample's seconds; ``right``: which of them
    were right answers.  The threshold is the q-th percentile over the right
    answers (the metric's own); the split is over the right answers the
    recorder still holds."""
    good = [w for w, ok in zip(walls, right) if ok]
    pairs, why = pair(walls, events)
    out = {"q": q, "right": len(good), "recorder_dropped": dropped,
           "held": 0, "beyond": None, "rest": None}
    if why:
        return out | {"refused": why}
    if not good or not pairs:
        return out
    p = stats.percentile(good, q)
    rows = [(w, parts_of(w, s)) for pos, w, s in pairs if right[pos]]
    return out | {
        "held": len(rows), "threshold_s": p,
        "beyond": mean_parts([r for r in rows if r[0] > p]),
        "rest": mean_parts([r for r in rows if r[0] <= p])}


def pooled_split(splits: list) -> dict:
    """Several runs' splits as one: means weighted by how many queries each
    run still held."""
    out = {}
    for side in ("beyond", "rest"):
        have = [s[side] for s in splits if s and s.get(side)]
        n = sum(h["n"] for h in have)
        out[side] = None if not n else {"n": n} | {
            k: sum(h[k] * h["n"] for h in have) / n
            for k in have[0] if k != "n"}
    return out
