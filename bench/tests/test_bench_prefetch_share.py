"""The reader of ``prefetch_share``: the share of a window's batch
assemblies that FrameEngine made while the batch before them ran."""
import pytest

from bench.record import Run
from bench.spec import Bench
from repro.obs.trace import TraceEvent

MS = 1_000_000      # ns


def _assemble(ts_ms, **attrs):
    return TraceEvent(name="engine.assemble", ts_ns=int(ts_ms * MS),
                      dur_ns=MS, tid=1, depth=1, parent="engine.step",
                      attrs=attrs)


def _run(spans):
    return Run(config={}, traffic={}, seconds=1.0, t_start=0.0,
               t_close=1.0, frames=[], completed_in_window=0, setup_s=0.0,
               warmup_s=0.0, counters={}, spans=spans)


@pytest.fixture(scope="module")
def read():
    return Bench().cell("sift-dog-1080p.backlog").reader("prefetch_share")


def test_share_of_prefetched_assemblies(read):
    """Three of four assemblies prefetched: 75%. Spans of other names,
    and an assembly without the attr, do not count."""
    spans = [_assemble(0, prefetched=False)]
    spans += [_assemble(t, prefetched=True) for t in (10, 20, 30)]
    spans += [_assemble(40, pipeline="p"),
              TraceEvent(name="engine.h2d", ts_ns=41 * MS, dur_ns=MS,
                         tid=1, depth=2, parent="engine.assemble",
                         attrs={"prefetched": True})]
    assert read(_run(spans)) == pytest.approx(75.0)


@pytest.mark.parametrize("spans", [[], [_assemble(0), _assemble(10)]],
                         ids=["no-spans", "no-attr"])
def test_reads_nothing_from_a_program_without_it(read, spans):
    """A window with no assembly, or with assemblies of a program that
    does not mark them (one before prefetching), reads nothing."""
    assert read(_run(spans)) is None


def test_listed_for_the_cells_that_prefetch():
    bench = Bench()
    (m,) = [m for m in bench.spec["per_layer"]
            if m["name"] == "prefetch_share"]
    assert m["layer"] == "engine host-to-device copy"
    assert m["moves"] == "frames_per_s"
    for name in m["workloads"]:
        assert bench.cell(name).config["engine"]["kind"] == "frame"
