"""Dispatch and schedule: reruns on the per-operator path after a fused
stage or a resident plan gave up, over the traced window."""


def _count(run) -> int:
    return run.runner.fused_fallbacks + run.runner.resident_fallbacks


def begin(run):
    return _count(run)


def read(run, before):
    return _count(run) - before
