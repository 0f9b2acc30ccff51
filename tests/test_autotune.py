"""Memory-config autotuner: search invariants + serving-stack threading.

The acceptance contract (enforced end-to-end by benchmarks/tune_sweep.py
--smoke) is pinned here at unit granularity: the tuned plan can never be
worse than the serving default on VMEM bytes, the tuned executor's
output still matches the oracle, and the PlanCache runs the design-space
search exactly once per (pipeline, width) no matter how many row-group /
batch / chunk variants are served from it.
"""
import json

import numpy as np
import pytest

from repro.core import algorithms, dse
from repro.core.linebuffer import DP, MemConfig
from repro.imaging import PlanCache
from repro.imaging.engine import FrameEngine, FrameRequest
from repro.kernels import ref
from repro.video import VideoEngine, VideoFrame

W = 48
ALL = sorted(algorithms.ALGORITHMS)
RNG = np.random.RandomState(7)


@pytest.fixture(scope="module")
def results():
    """One autotune per registered spatial pipeline (module-cached)."""
    return {name: dse.autotune(algorithms.ALGORITHMS[name](), W,
                               max_candidates=64)
            for name in ALL}


@pytest.mark.parametrize("name", ALL)
def test_best_never_worse_than_default(results, name):
    res = results[name]
    assert res.best.vmem_bytes <= res.default.vmem_bytes
    # lexicographic tie-break: equal vmem must not cost extra power
    if res.best.vmem_bytes == res.default.vmem_bytes:
        assert res.best.power <= res.default.power


@pytest.mark.parametrize("name", ALL)
def test_default_candidate_is_serving_default(results, name):
    res = results[name]
    assert all(c is DP for c in res.default.mem_cfg.values())
    assert res.default in res.candidates


@pytest.mark.parametrize("name", ALL)
def test_pareto_frontier_is_nondominated(results, name):
    res = results[name]
    front = res.pareto()
    assert front, "at least one candidate is always non-dominated"
    assert res.best in front, "the lexicographic best is non-dominated"
    for c in front:
        assert not any(
            q.vmem_bytes <= c.vmem_bytes and q.power <= c.power
            and q.contention_slack >= c.contention_slack
            and (q.vmem_bytes, q.power, q.contention_slack)
            != (c.vmem_bytes, c.power, c.contention_slack)
            for q in res.candidates)


@pytest.mark.parametrize("name", ALL)
def test_candidates_pass_contention_model(results, name):
    """Every scored candidate survived the cycle-accurate simulator, so
    slack (spare ports at the worst-case cycle) is never negative."""
    for c in results[name].candidates:
        assert c.contention_slack >= 0


def test_result_to_dict_is_json(results):
    blob = json.dumps(results["unsharp-m"].to_dict())
    back = json.loads(blob)
    assert back["pipeline"] == "unsharp-m"
    assert back["best"]["vmem_bytes"] <= back["default"]["vmem_bytes"]


def test_memoizes_solves_across_sized_variants():
    """DP and DP_SIZED induce the same constraint problem; the signature
    memo must collapse their solves to one."""
    from repro.core.linebuffer import DP_SIZED
    dag = algorithms.unsharp_m()
    res = dse.autotune(dag, W, options=(DP, DP_SIZED))
    assert res.stats.n_sched_memo_hits > 0
    # sized blocks change alloc bits, never the schedule objective
    by_alloc = {c.alloc_bits for c in res.candidates}
    assert len(by_alloc) > 1
    assert len({c.total_pixels for c in res.candidates}) == 1


def test_infeasible_default_raises():
    """A default the scheduler cannot satisfy must fail loudly (here: a
    0-port memory makes every combination infeasible)."""
    zp = MemConfig("ZP", ports=0, block_bits=64 * 1024)
    with pytest.raises(ValueError, match="default config is infeasible"):
        dse.autotune(algorithms.harris_m(), W, options=(zp,), default=zp)


# ------------------------------------------------------------- plan cache
def test_pruned_draws_count_toward_the_cap():
    """sift-dog's mixed combos all exceed the branch cap; the search
    still ends at ``max_candidates`` draws, on the serving path too."""
    cache = PlanCache()
    plan = cache.plan_for("sift-dog", W, tune=True)
    res = cache.tuning_for("sift-dog", W)
    s = res.stats
    assert s.truncated and s.n_enumerated == cache.tune_max_candidates
    assert s.n_pruned_branches > 0 and s.n_compiled < s.n_enumerated
    assert plan.vmem_ring_bytes == res.best.vmem_bytes


def test_plan_cache_tunes_once_and_derives_siblings():
    cache = PlanCache()
    p1 = cache.plan_for("unsharp-m", W, rows_per_step=1, tune=True)
    assert cache.stats.tunes == 1
    # the tuner seeded its best plan: the first tuned plan_for is a hit
    assert cache.stats.plan_hits == 1 and cache.stats.plan_misses == 0
    p8 = cache.plan_for("unsharp-m", W, rows_per_step=8, tune=True)
    ex = cache.executor_for("unsharp-m", 24, W, batch=2, tune=True)
    cache.video_executor_for("unsharp-m", 24, W, tune=True)
    assert cache.stats.tunes == 1, "one search serves every variant"
    assert p8.mem_cfg == p1.mem_cfg and p8.rows_per_step == 8
    assert ex.plan.mem_cfg == p1.mem_cfg
    assert p1.mem_cfg == cache.tuning_for("unsharp-m", W).best.mem_cfg


def test_plan_cache_rejects_mem_with_tune():
    cache = PlanCache()
    with pytest.raises(ValueError, match="not both"):
        cache.plan_for("unsharp-m", W, mem=DP, tune=True)
    with pytest.raises(ValueError, match="not both"):
        cache.executor_for("unsharp-m", 16, W, mem=DP, tune=True)


def test_tuned_executor_matches_oracle():
    """Two-sided correctness split: tuned vs the *default* executor must
    be bitwise-or-≤3-ULP (any drift here is tuner-attributable — a ring
    resize changing trace shapes at most wobbles FMA contraction); tuned
    vs the pure-jnp *oracle* inherits the documented fused-kernel wobble
    bound (32 ULP at array scale, see test_video.py / PR-2 notes), which
    the default config pays identically."""
    cache = PlanCache()
    img = RNG.rand(24, W).astype(np.float32)
    for name in ["canny-m", "denoise-m"]:
        got = np.asarray(
            cache.executor_for(name, 24, W, tune=True)({"in": img}))
        base = np.asarray(cache.executor_for(name, 24, W)({"in": img}))
        exp = np.asarray(ref.stencil_pipeline_ref(cache.dag_for(name),
                                                  {"in": img}))
        if not (got == base).all():
            np.testing.assert_allclose(
                got, base, rtol=0, atol=3 * np.spacing(np.abs(base).max()))
        np.testing.assert_allclose(
            got, exp, rtol=0, atol=32 * np.spacing(np.abs(exp).max()))


# --------------------------------------------------------------- engines
def test_frame_engine_autotune_flag():
    eng = FrameEngine(autotune=True, max_batch=2)
    img = RNG.rand(16, W).astype(np.float32)
    out = eng.run([FrameRequest(0, "harris-m", {"in": img})])
    assert eng.cache.stats.tunes == 1
    exp = np.asarray(ref.stencil_pipeline_ref(
        eng.cache.dag_for("harris-m"), {"in": img}))
    got = np.asarray(out[0])
    tol = 32 * np.spacing(np.abs(exp).max())   # fused-kernel FMA wobble
    np.testing.assert_allclose(got, exp, rtol=0, atol=tol)


def test_video_engine_autotune_flag():
    eng = VideoEngine(autotune=True, chunk=2)
    vid = RNG.rand(5, 16, W).astype(np.float32)
    sid = eng.open_stream("tmotion-t", 16, W)
    outs = eng.run({sid: [{"in": f} for f in vid]})
    assert eng.cache.stats.tunes == 1
    got = np.stack([np.asarray(o) for o in outs[sid]])
    exp = np.asarray(ref.video_pipeline_ref(eng.cache.dag_for("tmotion-t"),
                                            {"in": vid}))
    tol = 32 * np.spacing(np.abs(exp).max())   # fused-kernel FMA wobble
    np.testing.assert_allclose(got, exp, rtol=0, atol=tol)
