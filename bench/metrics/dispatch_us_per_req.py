"""Planner + staging (core/serve.py flush_async, core/query.py engines):
host microseconds the server spends dispatching flushes in the window —
building the batch arrays, planning the worklist, placing the staged
batch on the device and calling the jitted flush — per request the
device served (not answered from a memo, not riding another request's
batch slot), point and profile alike. From ServeStats.dispatch_time_s,
which the server times with the stamps of its flush.stage spans."""


def read(run):
    served = (run.stats.get("requests", 0)
              + run.stats.get("profile_requests", 0)
              - run.stats.get("memo_hits", 0))
    if served <= 0 or "dispatch_time_s" not in run.stats:
        return None
    return 1e6 * run.stats["dispatch_time_s"] / served
