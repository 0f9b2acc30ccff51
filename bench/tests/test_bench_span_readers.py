"""The readers of the serving spans, on a hand-built run: two steps of
one thread and a step of another, each with its copy, stack, call and
delivery."""
import pytest

from bench.record import Run
from bench.spec import Bench
from repro.obs.trace import TraceEvent

MS = 1_000_000      # ns


def _ev(name, ts_ms, dur_ms, depth, tid=1, parent=None):
    return TraceEvent(name=name, ts_ns=int(ts_ms * MS),
                      dur_ns=int(dur_ms * MS), tid=tid, depth=depth,
                      parent=parent, attrs={})


def _step(t, tid, h2d, stack, execute, deliver, total):
    """One step at ``t`` ms; its children follow each other from t + 1."""
    a = t + 1
    return [
        _ev("engine.step", t, total, 0, tid),
        _ev("engine.assemble", a, h2d + stack, 1, tid, "engine.step"),
        _ev("engine.h2d", a, h2d, 2, tid, "engine.assemble"),
        _ev("engine.stack", a + h2d, stack, 2, tid, "engine.assemble"),
        _ev("engine.execute", a + h2d + stack, execute, 1, tid,
            "engine.step"),
        _ev("executor.call", a + h2d + stack, 0.5, 2, tid,
            "engine.execute"),
        _ev("engine.deliver", a + h2d + stack + execute, deliver, 1, tid,
            "engine.step"),
    ]


SPANS = (_step(0, 1, h2d=2.0, stack=0.5, execute=1.0, deliver=4.0,
               total=9.0)
         + _step(10, 1, h2d=3.0, stack=0.25, execute=1.0, deliver=3.0,
                 total=9.0)
         # another thread's step overlaps the second: none of its
         # children count for a step of thread 1
         + _step(11, 2, h2d=6.0, stack=0.25, execute=1.0, deliver=0.75,
                 total=9.0))


@pytest.fixture(scope="module")
def run():
    return Run(config={}, traffic={}, seconds=1.0, t_start=0.0,
               t_close=1.0, frames=[], completed_in_window=0, setup_s=0.0,
               warmup_s=0.0, counters={}, spans=SPANS)


@pytest.fixture(scope="module")
def cell():
    return Bench().cell("canny-m-1080p.backlog")


@pytest.mark.parametrize("metric,expect", [
    ("h2d_ms", (2.0 + 3.0 + 6.0) / 3),
    ("h2d_max_ms", 6.0),
    ("stack_ms", (0.5 + 0.25 + 0.25) / 3),
    ("deliver_ms", (4.0 + 3.0 + 0.75) / 3),
    # 9 - 2.5 - 1 - 4, 9 - 3.25 - 1 - 3, 9 - 6.25 - 1 - 0.75
    ("step_self_ms", (1.5 + 1.75 + 1.0) / 3),
    ("assemble_ms", (2.5 + 3.25 + 6.25) / 3),
    ("execute_ms", 1.0),
])
def test_span_reader(cell, run, metric, expect):
    assert cell.reader(metric)(run) == pytest.approx(expect)


@pytest.mark.parametrize("metric", ["h2d_ms", "h2d_max_ms", "stack_ms",
                                    "deliver_ms", "step_self_ms",
                                    "execute_ms"])
def test_span_reader_finds_nothing(cell, run, metric):
    """A program without these spans (one before them) reads nothing."""
    empty = Run(**{**run.__dict__, "spans": []})
    assert cell.reader(metric)(empty) is None


SPAN_METRICS = {"h2d_ms", "h2d_max_ms", "stack_ms", "deliver_ms",
                "step_self_ms", "execute_ms", "assemble_ms"}


def test_cells_report_the_span_metrics():
    """Both backlog cells read every serving span, the executor call
    among them."""
    bench = Bench()
    for name in ("canny-m-1080p.backlog", "tbackground-t-1080p.backlog"):
        assert SPAN_METRICS <= {m["name"] for m in bench.cell(name).per_layer}
