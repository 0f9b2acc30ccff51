"""Frames served over the engine's batches times its slots (max_batch
or chunk), from the engine's own counters over the window, in %."""


def read(run):
    c = run.counters
    if not c.get("batches"):
        return None
    return 100.0 * c["frames_completed"] / (c["batches"] * c["slots"])
