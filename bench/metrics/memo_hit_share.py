"""Front-end memo: the share of the window's requests the server answered
without device work, in % (its memo_hits counter, which also counts duplicates
that rode an in-flight or queued identical request), over its requests, point
and profile (memo_hits counts both kinds)."""


def read(run):
    req = run.stats.get("requests", 0) + run.stats.get("profile_requests", 0)
    if not req:
        return None
    return 100.0 * run.stats["memo_hits"] / req
