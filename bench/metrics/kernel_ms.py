"""Device time of the Pallas kernel per executor call, from the trace."""


def read(run):
    d = run.device
    if d is None or not d.executor_runs:
        return None
    return d.kernel_s / d.executor_runs * 1e3
