"""Compiled programs: every program this process had to get during set-up
— compiled here or loaded from the persistent cache — which is what a cold start
compiles and a warm start loads."""


def read(run, _):
    return run.setup_programs
