"""Process start to the first timed query: import, device open, load, pin,
server start, warm-up."""


def read(run) -> float:
    return run.setup_s
