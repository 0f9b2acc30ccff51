"""bench/run.py refuses to report without a TPU, and without the
program beside it; on the CPU (look for a chip skipped) a traced and an
untraced run of each kind of cell report what their cells name."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.spec import ROOT
from bench.tests.tiny import make_root, run_cell


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "canny-m-1080p.backlog", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _result_lines(out):
    return [ln for ln in out.splitlines() if ln.startswith("{")]


def test_no_tpu_no_result():
    p = _run(ROOT)
    assert p.returncode == 2
    assert not _result_lines(p.stdout)
    assert "no" in p.stderr.lower() and "TPU" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ has no program
    to measure."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert not _result_lines(p.stdout)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch):
    from bench import run
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "")


# what a traced backlog cell reads on the CPU: every span metric, and
# idle_share from the host's own trace
BACKLOG_TRACED = {"assemble_ms", "h2d_ms", "h2d_max_ms", "stack_ms",
                  "execute_ms", "deliver_ms", "step_self_ms", "idle_share",
                  "warmup_s"}


@pytest.mark.parametrize("workload,trace,expect", [
    ("canny-m-1080p.backlog", 0, {"frames_per_s", "setup_s"}),
    ("tbackground-t-1080p.cams30", 0,
     {"latency_p50_ms", "latency_p95_ms", "setup_s"}),
    ("tbackground-t-1080p.cams30", 1,
     {"queue_wait_p95_ms", "batch_fill", "execute_ms", "warmup_s"}),
    ("canny-m-1080p.backlog", 1, BACKLOG_TRACED),
    ("tbackground-t-1080p.backlog", 1, BACKLOG_TRACED),
])
def test_run_reports_its_metrics(root, capsys, workload, trace, expect):
    res = run_cell(root, workload, capsys, trace=trace)
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    # a CPU trace has no TPU plane: the device readers find nothing
    assert set(res["metrics"]) == expect
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "compared"
    assert res["device"]["platform"] == "cpu"
    if trace:
        assert res["device"]["window_s"] > 0
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    json.dumps(res, allow_nan=False)
