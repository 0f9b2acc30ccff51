"""95th percentile of due time -> start of the ``step()`` that served
the frame: time queued in the engine, and behind the generator."""
from bench.record import percentile


def read(run):
    return percentile([(f.started - f.due) * 1e3 for f in run.served()], 95)
