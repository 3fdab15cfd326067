"""Device: 1 - (union of the device-operation intervals) / (traced
window), averaged over the chips used."""


def read(run, _):
    if not run.trace.ops or run.trace.window_s <= 0:
        return None
    return 100.0 * run.trace.idle_share()
