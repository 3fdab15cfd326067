"""Dispatch and schedule: what a query spends in the runner outside
planning and outside every task — fragmenting, stage set-up, spawning and
joining the task threads, collecting the result — per traced query: the
program's ``execute`` span minus its ``plan`` spans minus the union of its
``task`` spans (flight recorder, mapped onto the trace's clock)."""

from harness import program_spans as P
from harness.deploy import say


def begin(run):
    return P.begin(run)


def read(run, since):
    spans = P.for_run(run, since)
    if spans is None:
        return None
    per_query = []
    for ex in P.of_kind(spans, "execute"):
        own = [s for s in spans if s.query == ex.query]
        plan = sum(s.seconds for s in P.of_kind(own, "plan"))
        tasks = P.union_seconds(P.of_kind(own, "task"))
        per_query.append((ex.seconds - plan - tasks) * 1e3)
        say(f"schedule_ms: {ex.query}: execute {ex.seconds * 1e3:.3f} = plan "
            f"{plan * 1e3:.3f} + tasks (union) {tasks * 1e3:.3f} + schedule "
            f"{per_query[-1]:.3f} ms; schedule span "
            + ", ".join(f"{s.seconds * 1e3:.3f} ms stages="
                        f"{s.args.get('stages')}"
                        for s in P.of_kind(own, "schedule")))
    return sum(per_query) / len(per_query) if per_query else None
