"""Front end (core/serve.py): host microseconds spent inside the server's
submit, poll and result calls in the window, per request submitted. The
harness times every call; the drain waits inside them are included."""


def read(run):
    if run.n == 0:
        return None
    return float(sum(run.host_s.values()) / run.n * 1e6)
