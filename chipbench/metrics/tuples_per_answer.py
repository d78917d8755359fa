"""Tuples the scheduler read in the window per certified answer (its counter)."""


def read(run):
    return run.counters["tuples_read"] / run.answers if run.answers else None
