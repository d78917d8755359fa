"""95th percentile of how late the client submitted a query after its due
time, in ms: a starved load generator shows here, not as a slow server."""

import numpy as np


def read(run):
    return float(np.percentile(np.asarray(run.lags_s) * 1e3, 95)) if run.lags_s else None
