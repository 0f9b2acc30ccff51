#!/usr/bin/env python3
"""Chip smoke test: the serving path on one TPU at the paper's 1080p.

Drives every registered pipeline through the engines a user calls —
``FrameEngine`` over the default ``PlanCache`` for the 8 spatial
pipelines (native 1920x1080 batches, then one canny-m frame through the
tiled path), ``VideoEngine`` for the 4 temporal pipelines (one stream
each, plus tdenoise-t once more at ``prefetch_depth=2``) — on seeded
random frames, and checks every output against the pure-jnp reference
(``kernels/ref.py``) within the scale-ULP bound the tests use.

Run it from the checkout root with no arguments::

    python3 chip_smoke.py

It exits non-zero, printing no result, when JAX finds no TPU, and on any
mismatch, failed frame, fallback rung or interpreted executor. Earlier
lines report first-batch (compile + run) seconds, steady-batch seconds,
smoke frames/s and the cache counters: smoke readings from a handful of
batches, not a benchmark. The last line is one JSON object naming the
device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

ROWS_PER_STEP = 8
MAX_BATCH = 4
N_FRAMES = 8
# the tests' bound (tests/test_video.py::assert_video_equal): max |got -
# ref| within 32 float32 spacings at the reference's scale
SCALE_ULP_BOUND = 32.0


class SmokeFailure(RuntimeError):
    """A phase produced a wrong, failed or off-path result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _frames(rng, n: int, h: int, w: int) -> np.ndarray:
    return rng.random((n, h, w), dtype=np.float32)


def _compare(name: str, got, exp) -> float:
    from benchmarks.common import scale_ulp
    got, exp = np.asarray(got), np.asarray(exp)
    check(got.shape == exp.shape,
          f"{name}: output shape {got.shape} != reference {exp.shape}")
    check(bool(np.isfinite(got).all()), f"{name}: non-finite output")
    ulp = scale_ulp(got, exp)
    check(ulp <= SCALE_ULP_BOUND,
          f"{name}: {ulp} scale-ULP from the reference "
          f"(bound {SCALE_ULP_BOUND})")
    return ulp


def _drain(engine, expected_type, n: int) -> tuple[list, list[float]]:
    """Step until ``n`` results arrived; every one must be a default-rung
    ``expected_type``. Returns (results, per-step seconds)."""
    results, steps = [], []
    while len(results) < n:
        t0 = time.perf_counter()
        out = engine.step()
        steps.append(time.perf_counter() - t0)
        check(bool(out), f"engine idle with {n - len(results)} results due")
        for r in out:
            check(isinstance(r, expected_type),
                  f"expected {expected_type.__name__}, got {r!r}")
            check(r.rung == "default", f"served off the default rung: {r!r}")
        results.extend(out)
    return results, steps


def spatial_phase(cache, rng, h: int, w: int, n_frames: int = N_FRAMES,
                  max_batch: int = MAX_BATCH) -> dict:
    """Every spatial pipeline, ``n_frames`` native (h, w) frames each,
    through one FrameEngine whose tile covers the whole frame."""
    import jax
    from repro.core import algorithms
    from repro.imaging import CompletedFrame, FrameEngine, FrameRequest
    from repro.kernels import ref

    eng = FrameEngine(cache=cache, rows_per_step=ROWS_PER_STEP,
                      max_batch=max_batch, tile_shape=(h, w))
    report = {}
    rid = 0
    for name in sorted(algorithms.ALGORITHMS):
        dag = cache.dag_for(name)
        frames = _frames(rng, n_frames, h, w)
        reqs = [FrameRequest(rid=rid + i, pipeline=name, frames={"in": f})
                for i, f in enumerate(frames)]
        rid += n_frames
        for r in reqs:
            check(eng.submit(r) is True, f"{name}: request {r.rid} refused")
        results, steps = _drain(eng, CompletedFrame, n_frames)
        out = {c.rid: c.output for c in results}
        reference = jax.jit(lambda x, dag=dag:
                            ref.stencil_pipeline_ref(dag, {"in": x}))
        ulp = max(_compare(f"{name} frame {i}", out[r.rid],
                           reference(frames[i]))
                  for i, r in enumerate(reqs))
        report[name] = _timing(steps, max_batch) | {"max_scale_ulp": ulp}
    return report


def tiled_phase(cache, rng, h: int, w: int,
                tile_shape: tuple[int, int] = (128, 128)) -> dict:
    """One canny-m frame larger than ``tile_shape``: the engine serves
    it through ``execute_tiled``."""
    from repro.imaging import CompletedFrame, FrameEngine, FrameRequest
    from repro.kernels import ref

    check(h > tile_shape[0] or w > tile_shape[1],
          f"a {h}x{w} frame would not tile at {tile_shape}")
    name = "canny-m"
    eng = FrameEngine(cache=cache, rows_per_step=ROWS_PER_STEP,
                      max_batch=MAX_BATCH, tile_shape=tile_shape)
    frame = _frames(rng, 1, h, w)[0]
    check(eng.submit(FrameRequest(rid=0, pipeline=name,
                                  frames={"in": frame})) is True,
          "tiled request refused")
    (c,), steps = _drain(eng, CompletedFrame, 1)
    ulp = _compare(f"{name} tiled", c.output,
                   ref.stencil_pipeline_ref(cache.dag_for(name),
                                            {"in": frame}))
    return {name: {"tile_shape": list(tile_shape), "frame_s": steps[0],
                   "max_scale_ulp": ulp}}


def temporal_phase(cache, rng, h: int, w: int, n_frames: int = N_FRAMES,
                   pipelines: list[str] | None = None,
                   prefetch_depth: int = 1) -> dict:
    """One stream per temporal pipeline through one VideoEngine,
    ``n_frames`` frames each, against the multi-frame reference."""
    import jax
    from repro.core import algorithms
    from repro.kernels import ref
    from repro.video import CompletedVideoFrame, VideoEngine, VideoFrame

    eng = VideoEngine(cache=cache, chunk=MAX_BATCH,
                      rows_per_step=ROWS_PER_STEP,
                      prefetch_depth=prefetch_depth)
    report = {}
    for name in pipelines or sorted(algorithms.VIDEO_ALGORITHMS):
        dag = cache.dag_for(name)
        vid = _frames(rng, n_frames, h, w)
        sid = eng.open_stream(name, h, w)
        for f in vid:
            check(eng.submit(VideoFrame(sid, {"in": f})) is True,
                  f"{name}: frame refused")
        results, steps = _drain(eng, CompletedVideoFrame, n_frames)
        eng.close_stream(sid)
        check([c.index for c in results] == list(range(n_frames)),
              f"{name}: frames delivered out of order")
        exp = jax.jit(lambda v, dag=dag:
                      ref.video_pipeline_ref(dag, {"in": v}))(vid)
        ulp = max(_compare(f"{name} frame {c.index}", c.output,
                           exp[c.index]) for c in results)
        report[name] = (_timing(steps, MAX_BATCH)
                        | {"prefetch_depth": prefetch_depth,
                           "max_scale_ulp": ulp})
    return report


def _timing(steps: list[float], frames_per_step: int) -> dict:
    """First step (its executor's compile + one batch) and the steady
    steps after it."""
    steady = steps[1:]
    return {"first_step_s": steps[0],
            "steady_step_s": min(steady) if steady else None,
            "smoke_fps": (frames_per_step * len(steady) / sum(steady)
                          if steady else None)}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random frames")
    args = ap.parse_args(argv)

    from benchmarks.common import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    from repro.core.algorithms import RESOLUTIONS
    from repro.imaging import PlanCache

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU; nothing was run",
              file=sys.stderr)
        return 1
    print(f"compile cache: {cache_dir}", flush=True)

    w, h = RESOLUTIONS["1080p"]          # the paper's Sec. 7 1920x1080
    rng = np.random.default_rng(args.seed)
    cache = PlanCache()
    t0 = time.perf_counter()
    phases = {
        "spatial": spatial_phase(cache, rng, h, w),
        "tiled": tiled_phase(cache, rng, h, w),
        "temporal": temporal_phase(cache, rng, h, w),
        "temporal_depth2": temporal_phase(cache, rng, h, w,
                                          pipelines=["tdenoise-t"],
                                          prefetch_depth=2),
    }
    interpreted = [f"{ex.dag.name}@{ex.h}x{ex.w}"
                   for ex in cache.executors() if ex.interpret]
    check(not interpreted, f"interpreted executors: {interpreted}")
    for phase, rows in phases.items():
        for name, row in rows.items():
            print(f"smoke {phase} {name}: " + json.dumps(row), flush=True)
    snap = cache.snapshot()
    print("plan cache: " + json.dumps(
        {k: snap[k] for k in ("plan_hits", "plan_misses", "exec_hits",
                              "exec_misses", "execs_resident")}))
    print(f"smoke wall s: {time.perf_counter() - t0}")
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
