"""Seconds from the start of the run to the first request offered: weights,
ranker training, and warming (compiling, or loading from the persistent
cache) every program the cell's traffic reaches."""


def read(run):
    return run.setup_s
