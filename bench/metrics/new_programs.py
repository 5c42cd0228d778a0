"""Planner + staging (core/query.py engines): the programs the serving
engine met for the first time inside the window — each new static shape
of a ragged flush (padded batch, worklist length, gather capacity,
scalar or profile) lowers, and often compiles, a program while requests
wait. The window's delta of the server's ServeStats.new_programs
counter; a warm-up that covers every shape leaves it at 0."""


def read(run):
    count = run.stats.get("new_programs")
    return None if count is None else float(count)
