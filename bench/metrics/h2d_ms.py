"""Mean ``engine.h2d`` span (host clock): the host -> device copies of
one batch's or chunk's frames, per executor call."""
from bench.record import mean


def read(run):
    return mean(run.span_ms("engine.h2d"))
