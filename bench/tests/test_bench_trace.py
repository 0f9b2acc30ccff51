"""The trace reduction, checked on a trace recorded on a TPU v5e: two
4-frame canny-m batches of 64x256 frames through FrameEngine, with the
benchmark's ``bench.step`` and the engine's annotations."""
import os

import numpy as np
import pytest

from bench import xtrace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "canny-m-64x256.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return xtrace.load(FIXTURE)


@pytest.fixture(scope="module")
def window(trace):
    steps = [a for a in trace.annotations if a.name == "bench.step"]
    return steps[0].start, steps[-1].end


def test_load(trace):
    (dev,) = trace.devices
    assert dev.name == "/device:TPU:0"
    assert (len(dev.ops), len(dev.modules)) == (44, 28)
    assert sorted(a.name for a in trace.annotations) == [
        "bench.step", "bench.step", "engine.execute", "engine.execute",
        "executor.call", "executor.call"]
    with pytest.raises(ValueError, match="bench.window"):
        trace.window()


def test_kernel_time_by_hand(trace, window):
    s = xtrace.summarize(trace, *window)
    # the two jit_fn programs each hold one tpu_custom_call op:
    # 48554542..48570338 ns and 57000397..57016174 ns
    assert s.executor_runs == 2
    assert s.kernel_s == pytest.approx((15796 + 15777) * 1e-9, abs=1e-15)
    assert s.executor_busy_s == pytest.approx(s.kernel_s, abs=1e-15)
    assert s.top_ops[0] == ("jit_fn/fn.1", pytest.approx(31573e-9))


def test_busy_and_idle_by_hand(trace, window):
    """Busy time against a 1 ns timeline of the window's ops."""
    lo, hi = window
    s = xtrace.summarize(trace, lo, hi)
    line = np.zeros(int(hi - lo) + 1, bool)
    for op in trace.devices[0].ops:
        a, b = max(op.start, lo), min(op.end, hi)
        if b > a:
            line[int(a - lo):int(b - lo)] = True
    assert s.window_s == pytest.approx((hi - lo) * 1e-9)
    assert s.busy_s == pytest.approx(line.sum() * 1e-9, abs=2e-9)
    assert s.idle_share == pytest.approx(1 - s.busy_s / s.window_s)
    assert 0.99 < s.idle_share < 1.0
    gaps = dict(s.idle_gaps)
    assert set(gaps) <= {"bench.step", "engine.execute", "executor.call"}
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)
    assert max(gaps, key=gaps.get) == "bench.step"


def test_merged_and_gap_names():
    E = xtrace.Event
    ops = [E("a", 0, 10), E("b", 5, 20), E("c", 30, 40), E("d", 50, 60)]
    assert xtrace.merged(ops, 2, 55) == [(2, 20), (30, 40), (50, 55)]
    anns = sorted([E("bench.step", 0, 100), E("engine.execute", 20, 45),
                   E("executor.call", 21, 25)],
                  key=lambda e: (e.start, -e.end))
    assert xtrace._gap_names([(22, 32), (44, 50), (60, 70), (100, 120)],
                             anns) == [
        "engine.execute", "bench.step", "bench.step", xtrace.NO_ANNOTATION]
