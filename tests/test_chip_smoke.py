"""chip_smoke.py rehearsed on the CPU: its phases at tiny shapes.

The script's phases are the same functions the chip run calls at 1080p;
here they run through the Pallas interpreter on small frames, so a wrong
path, argument or check fails before any chip time is spent. The script
as a whole must refuse to report a result without a TPU.
"""
import importlib.util
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import algorithms
from repro.imaging import PlanCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cache():
    return PlanCache()


def test_spatial_phase_small(smoke, cache):
    rep = smoke.spatial_phase(cache, np.random.default_rng(0), 16, 64,
                              n_frames=4, max_batch=2)
    assert len(rep) == len(algorithms.ALGORITHMS)
    assert all(r["max_scale_ulp"] <= smoke.SCALE_ULP_BOUND
               for r in rep.values())


def test_tiled_phase_small(smoke, cache):
    rep = smoke.tiled_phase(cache, np.random.default_rng(1), 24, 200)
    assert rep["canny-m"]["max_scale_ulp"] <= smoke.SCALE_ULP_BOUND


@pytest.mark.parametrize("depth", [1, 2])
def test_temporal_phase_small(smoke, cache, depth):
    pipelines = None if depth == 1 else ["tdenoise-t"]
    rep = smoke.temporal_phase(cache, np.random.default_rng(2), 16, 64,
                               pipelines=pipelines, prefetch_depth=depth)
    assert len(rep) == (4 if depth == 1 else 1)
    assert all(r["prefetch_depth"] == depth for r in rep.values())


def test_phase_check_fails_loudly(smoke):
    with pytest.raises(smoke.SmokeFailure, match="scale-ULP"):
        smoke._compare("x", np.full((2, 2), 3.0, np.float32),
                       np.full((2, 2), 2.0, np.float32))


def test_script_refuses_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                          cwd=tmp_path, env=env, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_compile_cache_dir(monkeypatch, smoke):
    from benchmarks.common import REPO_ROOT, enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = enable_compile_cache()
        assert path == os.path.join(REPO_ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
