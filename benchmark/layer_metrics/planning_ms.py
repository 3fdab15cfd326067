"""Parse, plan, optimize: the ``_plan_stmt`` spans per traced query.  0 when
every traced query hit the plan cache (same text as the warm-up's)."""


def read(run, _):
    client = run.trace.spans.get("client")
    if not client:
        return None
    return sum(d for _, d in run.trace.spans.get("plan", [])) \
        / len(client) * 1e3
