"""Mean ``engine.assemble`` span (host clock): batch assembly and the
host -> device copy of the batch's frames, per executor call."""
from bench.record import mean


def read(run):
    return mean(run.span_ms("engine.assemble"))
