"""Mean ``engine.deliver`` span (host clock): handing each frame of a
served batch or chunk its own output array, per executor call."""
from bench.record import mean


def read(run):
    return mean(run.span_ms("engine.deliver"))
