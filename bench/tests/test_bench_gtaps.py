"""``kernel_gtaps_s``, the fused kernel's rate in window elements, on a
trace recorded on a TPU v5e (two 4-frame canny-m batches of 64x256
frames, ``test_bench_trace.py``'s fixture) and on runs with nothing to
read."""
import os

import pytest

from bench import xtrace
from bench.record import Run
from bench.spec import Bench
from repro.obs.trace import TraceEvent

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "canny-m-64x256.xplane.pb")
CELLS = ["canny-m-1080p.backlog", "tbackground-t-1080p.backlog",
         "sift-dog-1080p.backlog"]


def _call(**attrs):
    return TraceEvent(name="executor.call", ts_ns=0, dur_ns=1, tid=1,
                      depth=2, parent="engine.execute", attrs=attrs)


@pytest.fixture(scope="module")
def device():
    trace = xtrace.load(FIXTURE)
    steps = [a for a in trace.annotations if a.name == "bench.step"]
    return xtrace.summarize(trace, steps[0].start, steps[-1].end)


@pytest.fixture(scope="module")
def read():
    return Bench().cell("sift-dog-1080p.backlog").reader("kernel_gtaps_s")


def _run(device, spans):
    return Run(config={"frame": {"height": 64, "width": 256}}, traffic={},
               seconds=1.0, t_start=0.0, t_close=1.0, frames=[],
               completed_in_window=0, setup_s=0.0, warmup_s=0.0,
               counters={}, spans=spans, device=device)


def test_rate_on_a_recorded_trace(device, read):
    # canny-m reads 38 window elements a pixel; the two kernels ran
    # 15796 + 15777 ns (test_bench_trace.py)
    calls = [_call(batch=4, taps=38), _call(batch=4, taps=38)]
    assert device.kernel_s == pytest.approx(31573e-9, abs=1e-15)
    expect = 2 * 4 * 38 * 64 * 256 / 31573e-9 / 1e9
    assert read(_run(device, calls)) == pytest.approx(expect)


@pytest.mark.parametrize("case", ["no-device", "no-taps", "no-calls"])
def test_nothing_to_read(device, read, case):
    """No trace, calls of a program that does not count taps, or no
    calls: the metric is left out."""
    if case == "no-device":
        run = _run(None, [_call(batch=4, taps=38)])
    elif case == "no-taps":
        run = _run(device, [_call(batch=4)])
    else:
        run = _run(device, [])
    assert read(run) is None


def test_listed_for_the_backlog_cells():
    bench = Bench()
    for name in CELLS:
        (m,) = [m for m in bench.cell(name).per_layer
                if m["name"] == "kernel_gtaps_s"]
        assert (m["unit"], m["layer"], m["moves"]) == (
            "Gtaps/s", "fused kernel", "frames_per_s")
