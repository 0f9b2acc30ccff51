"""1 - device-busy time / traced window, in %: the union of the device
op intervals inside the benchmark's window."""


def read(run):
    if run.device is None:
        return None
    return 100.0 * run.device.idle_share
