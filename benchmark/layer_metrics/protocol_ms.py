"""Statement protocol: the client's span minus the runner's ``execute`` span,
per traced query — HTTP, JSON, the dispatcher's hand-over and the poll."""


def read(run, _):
    client, execute = run.trace.spans.get("client"), \
        run.trace.spans.get("execute")
    if not client or not execute:
        return None
    return (sum(d for _, d in client) - sum(d for _, d in execute)) \
        / len(client) * 1e3
