"""Median latency (ms) of every request due in the window, from the time
it was due to the time its answer was delivered. A request never answered
counts as infinitely late. Percentiles are nearest-rank (numpy's
"inverted_cdf"), so an infinite latency never meets a finite one in an
interpolation."""
import numpy as np


def latencies_ms(run):
    return np.where(np.isnan(run.deliver), np.inf,
                    (run.deliver - run.due) * 1e3)


def read(run):
    if run.n == 0:
        return None
    return float(np.percentile(latencies_ms(run), 50, method="inverted_cdf"))
