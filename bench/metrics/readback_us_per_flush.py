"""Front end, readback (core/serve.py _await_handle): host microseconds a
drain spends inside a flush handle's wait() once ready() has said the
device work is done — the answers' device-to-host copy and their
materialisation — per flush dispatched in the window. From
ServeStats.readback_time_s and .batches; None where the server keeps no
readback counter, or dispatched no flush."""


def read(run):
    t, n = run.stats.get("readback_time_s"), run.stats.get("batches")
    if t is None or not n:
        return None
    return 1e6 * t / n
