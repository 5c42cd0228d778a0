"""Seconds from process start to the window's open: index load (or
build), server set-up, compiles, warm-up traffic."""


def read(run):
    return float(run.setup_s)
