"""With the VideoEngine's timed path broken underneath, a run's
``correct`` comes out false: a step that returns its stream state
unchanged, half of a chunk left out, an answer altered where it is
produced. (One chip: there is no exchange between chips to leave
out.)"""
import pytest

from bench.tests.faults import plant
from bench.tests.tiny import make_root, run_cell


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch):
    from bench import run
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "")


@pytest.mark.parametrize("workload,fault", [
    ("tbackground-t-1080p.cams30", "state_unchanged"),
    ("tbackground-t-1080p.cams30", "altered"),
    ("tbackground-t-1080p.backlog", "state_unchanged"),
    ("tbackground-t-1080p.backlog", "half_batch"),
    ("tbackground-t-1080p.backlog", "altered"),
])
def test_fault_is_not_correct(root, capsys, monkeypatch, workload, fault):
    plant(monkeypatch, "video", fault)
    res = run_cell(root, workload, capsys)
    assert res["correct"] is False
    assert res["compared"]["max_scale_ulp"]["value"] > \
        res["compared"]["max_scale_ulp"]["limit"]
