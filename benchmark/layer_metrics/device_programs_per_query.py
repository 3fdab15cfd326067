"""Compiled programs: program executions on the device planes of the trace
(the ``XLA Modules`` line), per traced query — the launch count."""


def read(run, _):
    if not run.trace.programs:
        return None
    return run.trace.program_runs() / run.queries
