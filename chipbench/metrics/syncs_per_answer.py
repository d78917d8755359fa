"""Device-to-host polls (`scheduler.host_syncs`) in the window per certified answer."""


def read(run):
    return run.counters["host_syncs"] / run.answers if run.answers else None
