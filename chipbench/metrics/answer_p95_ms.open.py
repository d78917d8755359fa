"""95th percentile of due-to-answer time in an open-loop cell, in ms.

Reported per layer: a host stall of a few seconds backs up the queue of
an open loop near capacity, and moves this tail by several times in one
run of four or five, too widely for a bound (PERF.md)."""

import numpy as np


def read(run):
    return float(np.percentile(np.asarray(run.latencies_s) * 1e3, 95)) if run.latencies_s else None
