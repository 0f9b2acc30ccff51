"""Mean ``engine.stack`` span (host clock): padding a batch with zero
frames and stacking the copied frames into one array, per call."""
from bench.record import mean


def read(run):
    return mean(run.span_ms("engine.stack"))
