"""Device: ``memory_stats()["peak_bytes_in_use"]`` on the fullest chip after
the window — set by the load (generator output plus its re-batched copy)."""

from harness import deploy


def read(run, _):
    peak = deploy.peak_bytes(run.devices)
    return peak / 1e9 if peak else None
