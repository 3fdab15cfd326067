"""Operators: device-to-host value materializations the exec layer asked
for (exec.syncguard), per traced query."""


def begin(run):
    from trino_tpu.exec import syncguard

    return syncguard.snapshot()


def read(run, before):
    from trino_tpu.exec import syncguard

    return syncguard.take_delta(before).host_syncs / run.queries
