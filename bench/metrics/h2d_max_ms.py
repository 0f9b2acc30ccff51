"""Longest ``engine.h2d`` span in the window (host clock): the worst
host -> device copy of a batch, where a transfer stall shows."""


def read(run):
    return max(run.span_ms("engine.h2d"), default=None)
