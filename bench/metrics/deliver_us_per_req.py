"""Front end, delivery (core/serve.py _drain): host microseconds the
server spends handing drained answers out — result dicts, version and
mode stamps, memo writes — per answer a drain delivered in the window,
requests that rode another's batch slot included. From the server's
ServeStats.deliver_time_s and .delivered, timed with the stamps of its
drain.deliver spans."""


def read(run):
    n = run.stats.get("delivered")
    if not n:
        return None
    return 1e6 * run.stats["deliver_time_s"] / n
