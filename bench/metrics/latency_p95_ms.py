"""95th percentile over the frames due in the window of due time -> the
end of the ``step()`` that returned the frame's output."""
from bench.record import percentile


def read(run):
    return percentile([(f.done - f.due) * 1e3 for f in run.served()], 95)
