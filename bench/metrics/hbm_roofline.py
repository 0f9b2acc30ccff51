"""Share of the HBM roofline reached by the executor programs, in %:
the least bytes of every executor call in the window at the chip's
published HBM bandwidth, over the device-busy time of every op of the
executor programs (kernel, pads, slices, copies) in the trace."""
from bench.peaks import peaks_for
from bench.roofline import least_seconds


def read(run):
    d = run.device
    calls = [e for e in run.spans if e.name == "executor.call"]
    if d is None or not d.executor_busy_s or not calls:
        return None
    frames = [e.attrs.get("batch") or e.attrs.get("chunk") or 1
              for e in calls]
    peaks = peaks_for(run.device_kind)
    least = sum(least_seconds(run.config, n, peaks) for n in frames)
    return 100.0 * least / d.executor_busy_s
