"""Seconds of the warm-up calls of the cell's own shapes: plans,
executor compiles or cache loads, first runs."""


def read(run):
    return run.warmup_s
