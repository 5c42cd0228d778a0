"""99th percentile latency (ms) over every request due in the window,
from due time to delivery: one tail over all requests, never a median of
chunks."""
import numpy as np


def read(run):
    if run.n == 0:
        return None
    lat = np.where(np.isnan(run.deliver), np.inf,
                   (run.deliver - run.due) * 1e3)
    return float(np.percentile(lat, 99, method="inverted_cdf"))
