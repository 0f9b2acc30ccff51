"""Rate of the fused kernel in billions of window elements a second:
the window elements of every executor call in the window (its ``taps``
per output pixel, times its frames, times the frame's pixels) over the
device time of the kernels of the executor programs in the trace. A
rate, not a share: no vector-unit peak is published (``bench/peaks.py``).
A program whose calls carry no ``taps`` reads nothing."""


def read(run):
    d = run.device
    calls = [e for e in run.spans if e.name == "executor.call"]
    if d is None or not d.kernel_s or not calls \
            or any("taps" not in e.attrs for e in calls):
        return None
    f = run.config["frame"]
    taps = sum(e.attrs["taps"]
               * (e.attrs.get("batch") or e.attrs.get("chunk") or 1)
               for e in calls)
    return taps * f["height"] * f["width"] / d.kernel_s / 1e9
