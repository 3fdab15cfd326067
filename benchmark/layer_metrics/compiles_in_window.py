"""Operators: programs new to the process inside the window (backend
compiles and persistent-cache loads, jax.monitoring).  Should be 0: a
warmed-up cell runs only programs it already holds."""


def begin(run):
    return run.compile_log.mark()


def read(run, before):
    return run.compile_log.since(before)["programs"]
