"""Device: the share of the traced window in which no operation ran on
the chip (1 - union of op intervals / window), in %, averaged over the
cell's chips."""


def read(run):
    if run.trace is None or not run.trace.busy_s:
        return None
    return 100.0 * (1.0 - run.trace.mean_busy_s() / run.trace.window_s)
