"""sift-dog, SIFT's DoG octave: its taps, its shape, its delay windows,
and the fused kernel against the reference executor and the benchmark's
plain reference (``bench/configs/sift-dog.ref.py``)."""
import os

import jax
import numpy as np
import pytest

from benchmarks.common import scale_ulp
from repro.core import algorithms
from repro.core.algorithms import execute_reference
from repro.core.codegen import compile_pipeline
from repro.core.dag import Edge, PipelineDAG, _TapCounter, window_keys
from repro.kernels.stencil_pipeline import make_executor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# taller than the output's 98-row extent: a shorter frame is all zeros
H, W = 144, 256
BATCH, R = 2, 8
# scale-spacings: XLA:CPU contracts the ~200-tap blur chains into FMAs
# differently in the interpreted kernel and the reference executor
# (measured 8-24 here); the chip, with no contraction, reads 0
LIMIT = 64


@pytest.fixture(scope="module")
def dag():
    return algorithms.sift_dog()


@pytest.fixture(scope="module")
def reference():
    from bench.spec import load_module
    return load_module(os.path.join(ROOT, "bench", "configs",
                                    "sift-dog.ref.py"))


@pytest.fixture(scope="module")
def frames():
    return {seed: np.random.default_rng(seed).random((BATCH, H, W),
                                                     dtype=np.float32)
            for seed in (0, 1, 2)}


@pytest.fixture(scope="module")
def expected(dag, frames):
    return {seed: [np.asarray(execute_reference(dag, {"in": f})["out"])
                   for f in x] for seed, x in frames.items()}


def test_gauss_taps_follow_opencv():
    taps = [algorithms.gauss_taps(s) for s in algorithms.sift_sigmas()]
    assert [len(t) for t in taps] == [13, 11, 13, 17, 21, 27]
    for t in taps:
        assert t.dtype == np.float32
        assert float(t.sum(dtype=np.float64)) == pytest.approx(1.0, abs=1e-6)
        np.testing.assert_array_equal(t, t[::-1])


def test_shape(dag):
    assert (dag.num_stages(), len(dag.edges),
            len(dag.multi_consumer_stages())) == (23, 35, 8)
    assert dag.cumulative_extent() == (98, 98)
    assert dag.taps == 299


def _lags(dag: PipelineDAG) -> dict[str, set[tuple[float, float]]]:
    """For each stage of several reads, where each read's centre lies
    behind the stage's pixel, in input pixels (rows, cols): the window's
    lag to the middle of the elements the function reads, plus half the
    producer's extent (every stage here is centred on its extent)."""
    ext = dag.stage_extents()
    lags = {}
    for name in dag.topo_order:
        ins = dag.in_edges(name)
        if len(ins) < 2:
            continue
        wins = [_TapCounter(e) for e in ins]
        jax.eval_shape(lambda: dag.stages[name].fn(
            dict(zip(window_keys(ins), wins))))
        lags[name] = set()
        for e, w in zip(ins, wins):
            rows = [i[0] for i in w.read]
            cols = [i[1] for i in w.read]
            up, left = ext[e.producer]
            lags[name].add((e.sh - 1 - (min(rows) + max(rows)) / 2 + up / 2,
                            e.sw - 1 - (min(cols) + max(cols)) / 2
                            + left / 2))
    return lags


def test_reads_meet_at_one_source_pixel(dag):
    """Every DoG, extremum and combine stage reads its producers at one
    source pixel, by the extents of ``stage_extents()``."""
    lags = _lags(dag)
    assert sorted(lags) == ["comb", "d0", "d1", "d2", "d3", "d4",
                            "x1", "x2", "x3"]
    assert all(len(v) == 1 for v in lags.values()), lags


def _short_delay(dag: PipelineDAG) -> PipelineDAG:
    """The DAG with d2's delay window on g2v one row short."""
    edges = [Edge(e.producer, e.consumer, e.sh - 1, e.sw)
             if (e.producer, e.consumer) == ("g2v", "d2") else e
             for e in dag.edges]
    assert edges != dag.edges
    return PipelineDAG(dag.name, list(dag.stages.values()), edges)


def test_short_delay_differs_from_reference(dag, frames, expected,
                                            reference):
    bad = _short_delay(dag)
    assert len(_lags(bad)["d2"]) == 2
    x = frames[0][0]
    got = np.asarray(execute_reference(bad, {"in": x})["out"])
    ref = np.asarray(reference.output(x[None]))
    assert np.abs(got - ref).max() > 0
    np.testing.assert_array_equal(expected[0][0], ref)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_plain_reference_agrees(frames, expected, reference, seed):
    """The benchmark's reference and the reference executor, both op by
    op in float32, agree bitwise."""
    for x, exp in zip(frames[seed], expected[seed]):
        np.testing.assert_array_equal(np.asarray(reference.output(x[None])),
                                      exp)


@pytest.fixture(scope="module")
def executor(dag):
    plan = compile_pipeline(dag, W, rows_per_step=R)
    return make_executor(dag, H, W, batch=BATCH, plan=plan, rows_per_step=R)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_kernel_matches_reference(executor, frames, expected, seed):
    got = np.asarray(executor({"in": frames[seed]}))
    for g, exp in zip(got, expected[seed]):
        assert (exp > 0).sum() >= 1         # keypoints, not zeros, compared
        np.testing.assert_array_equal(g > 0, exp > 0)
        assert scale_ulp(g, exp) <= LIMIT
