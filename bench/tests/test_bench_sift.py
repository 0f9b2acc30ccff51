"""The sift-dog cell through ``bench/run.py`` on the CPU (look for a
chip skipped), at a frame taller than the detector's 98-row extent so
the outputs compared hold keypoints."""
import numpy as np
import pytest

from bench import run
from bench.loadgen import make_pool
from bench.spec import Bench
from bench.tests import tiny

H, W = 144, 256
SEED = 2**31 + 11


@pytest.fixture
def root(tmp_path, monkeypatch):
    monkeypatch.setattr(tiny, "H", H)
    monkeypatch.setattr(tiny, "W", W)
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "")
    return tiny.make_root(tmp_path)


def test_cell_is_correct_on_keypoints(root, capsys):
    res = tiny.run_cell(root, "sift-dog-1080p.backlog", capsys, seed=SEED)
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"frames_per_s", "setup_s"}
    cell = Bench(root).cell("sift-dog-1080p.backlog")
    assert (cell.config["frame"]["height"], cell.config["pipeline"]) == (
        H, "sift-dog")
    pool = make_pool(SEED, run.POOL_FRAMES, H, W)
    ref = np.asarray(cell.reference().output(pool[:1]))
    assert (ref > 0).sum() >= 1
