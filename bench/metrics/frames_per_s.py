"""Frames served in the window over the window's seconds (closed mixes:
the window closes at the end of the first step past its length)."""


def read(run):
    if run.traffic["kind"] != "closed":
        return None
    return run.completed_in_window / (run.t_close - run.t_start)
