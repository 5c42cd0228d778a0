"""Load generator: 99th percentile of how late each request was
submitted after it was due (ms). A late generator is read here, not as a
fast server."""
import numpy as np


def read(run):
    if run.n == 0:
        return None
    return float(np.percentile((run.submit - run.due) * 1e3, 99,
                                 method="inverted_cdf"))
