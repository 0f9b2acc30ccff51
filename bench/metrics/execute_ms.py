"""Mean ``engine.execute`` span (host clock): the executor call on the
served path, with its wait for the device, per call."""
from bench.record import mean


def read(run):
    return mean(run.span_ms("engine.execute"))
