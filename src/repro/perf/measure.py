"""Measured-side performance extraction: timing, XLA costs, trace splits.

Three independent measurement channels, joined with the analytic model
by :mod:`repro.perf.attribution`:

  * **steady-state timing** (:func:`measure_executor`) — warm the
    compiled executor, then time a seeded frame stream with
    ``block_until_ready`` per frame. This is the wall-clock truth the
    model's cycle counts are confronted with.
  * **XLA cost analysis** (:func:`executor_cost`) —
    ``fn.lower(args).compile().cost_analysis()`` flops / bytes-accessed
    per executor call, plus ``memory_analysis`` arg/out/temp bytes.

    Caveats (measured against XLA:CPU; carried here from the old
    benchmarks/roofline.py so they live next to the numbers they
    qualify): cost_analysis counts ``while``/``scan`` loop *bodies
    once*, not x trip count, and the Pallas kernels run in interpret
    mode on CPU — the HLO the analysis sees is the interpreter's
    program, so treat flops/bytes as a consistent *relative* signal
    between pipelines, not device truth.
  * **trace breakdown** (:func:`step_breakdown`) — queue-wait vs
    assemble vs execute *self*-time per pipeline, aggregated from the
    obs plane's ``engine.step`` spans (reusing the flame summary's
    per-thread interval-containment arithmetic in
    :func:`repro.obs.export._self_times_us`).

Roofline peaks and the DMA-bound vs compute-bound classification also
live here (:class:`Peaks`, :func:`classify`): a pipeline whose
memory-transfer term exceeds its compute term at the given peaks is
DMA-bound — the prerequisite breakdown for making DMA/compute-overlap
buffering depth an autotuner axis (ROADMAP).
"""
from __future__ import annotations

import bisect
import dataclasses
import math
import time
from typing import Callable, Sequence

import numpy as np

from repro.obs.export import _self_times_us, _span_rows

# TPU v5e-class peaks, kept for summarizing real-device dryruns (the old
# benchmarks/roofline.py constants; that module now imports them from
# here). Arbitrary for the CPU/interpret environment — see calibrate().
TPU_V5E_PEAK_FLOPS = 197e12
TPU_V5E_HBM_BPS = 819e9
TPU_V5E_ICI_BPS = 50e9 * 4


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Machine peaks the roofline classification is evaluated against."""
    flops_per_s: float
    hbm_bytes_per_s: float

    @property
    def ridge_intensity(self) -> float:
        """Flops/byte above which a kernel is compute-bound."""
        return self.flops_per_s / self.hbm_bytes_per_s

    def to_dict(self) -> dict:
        return {"flops_per_s": self.flops_per_s,
                "hbm_bytes_per_s": self.hbm_bytes_per_s,
                "ridge_intensity": self.ridge_intensity}


TPU_V5E_PEAKS = Peaks(TPU_V5E_PEAK_FLOPS, TPU_V5E_HBM_BPS)


def calibrate(n: int = 384, reps: int = 5) -> Peaks:
    """Measure this machine's achievable peaks with two tiny probes.

    A dense f32 matmul bounds the flops peak; a large contiguous copy
    bounds the memory-bandwidth peak. Both run through numpy (BLAS /
    memcpy), so the result tracks the host the benchmarks run on — the
    point is a *machine-relative* normalizer for the ledger (dividing a
    pipeline's fps by a peak measured in the same process cancels
    machine speed to first order), not a vendor datasheet number.
    """
    a = np.random.RandomState(0).rand(n, n).astype(np.float32)
    b = a.T.copy()
    a @ b                                    # warm BLAS threads
    t0 = time.perf_counter()
    for _ in range(reps):
        a @ b
    flops = 2.0 * n * n * n * reps / (time.perf_counter() - t0)

    big = np.random.RandomState(1).rand(1 << 22).astype(np.float32)  # 16 MiB
    big.copy()
    t0 = time.perf_counter()
    for _ in range(reps):
        big.copy()
    bw = 2.0 * big.nbytes * reps / (time.perf_counter() - t0)  # read+write
    return Peaks(flops_per_s=flops, hbm_bytes_per_s=bw)


def classify(flops: float, bytes_moved: float, peaks: Peaks) -> dict:
    """Roofline-style classification of one executor call.

    Returns ``{"bound": "dma" | "compute", "t_compute_s", "t_memory_s",
    "intensity"}`` — DMA-bound when the memory-transfer term is at least
    the compute term at the given peaks (ties classify as DMA-bound:
    at the ridge point, transfers are what overlap would hide).
    """
    t_comp = flops / peaks.flops_per_s if peaks.flops_per_s else 0.0
    t_mem = (bytes_moved / peaks.hbm_bytes_per_s
             if peaks.hbm_bytes_per_s else 0.0)
    return {
        "bound": "dma" if t_mem >= t_comp else "compute",
        "t_compute_s": t_comp,
        "t_memory_s": t_mem,
        "intensity": flops / bytes_moved if bytes_moved else 0.0,
    }


# ------------------------------------------------------------- cost side
def _example_args(ex) -> tuple:
    """Zero-filled example arguments matching the executor's signature."""
    shape = (ex.h, ex.w)
    leading = getattr(ex, "batch", None)
    if leading is None:
        leading = getattr(ex, "chunk", None)
    if leading is not None:
        shape = (leading,) + shape
    images = {n: np.zeros(shape, np.float32)
              for n in ex.dag.input_stages()}
    if hasattr(ex, "init_state"):        # VideoExecutor: (images, state)
        return (images, ex.init_state())
    return (images,)


def executor_cost(ex) -> dict | None:
    """XLA compiled-cost view of one executor call, or None on failure.

    Works on both :class:`~repro.kernels.stencil_pipeline.StencilExecutor`
    and :class:`VideoExecutor` (the jitted ``_fn`` is lowered with
    zero example inputs — cost analysis is shape-only). Returns
    ``{"flops", "bytes_accessed", "arg_bytes", "out_bytes",
    "temp_bytes"}`` per *call* (divide by batch/chunk for per-frame).
    """
    try:
        args = _example_args(ex)
        compiled = ex._fn.lower(*args).compile()
        ca = compiled.cost_analysis() or {}
        out = {"flops": float(ca.get("flops", 0.0)),
               "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
               "arg_bytes": 0, "out_bytes": 0, "temp_bytes": 0}
        ma = compiled.memory_analysis()
        if ma is not None:
            out["arg_bytes"] = int(ma.argument_size_in_bytes)
            out["out_bytes"] = int(ma.output_size_in_bytes)
            out["temp_bytes"] = int(ma.temp_size_in_bytes)
        return out
    except Exception:                    # noqa: BLE001 — best-effort probe:
        # cost analysis is advisory; a backend that cannot lower or
        # analyze must degrade the report, never fail the benchmark
        return None


# ----------------------------------------------------------- timing side
@dataclasses.dataclass(frozen=True)
class MeasuredPerf:
    """Steady-state measurement of one executor at one shape."""
    pipeline: str
    h: int
    w: int
    frames: int
    wall_s: float                   # timed-loop wall clock
    fps: float                      # frames (not batches) per second
    flops_per_frame: float | None   # from executor_cost, per frame
    bytes_per_frame: float | None

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def timed_stream(call: Callable, stream: Sequence, settle: int = 2,
                 per_frame_sleep_s: float = 0.0) -> tuple[float, object]:
    """Run ``call`` over ``stream`` and return (seconds, last output).

    The shared steady-state timing loop (benchmarks/common.py re-exports
    it): the first ``settle`` items run un-timed to absorb trace/jit and
    allocator warm-up, then every item is dispatched and blocked on.
    ``per_frame_sleep_s`` is the regression-gate's fault-injection seam
    (benchmarks/perf_lab.py ``--inject-slowdown``): a deliberate stall
    per frame that a healthy gate must flag.
    """
    for fr in stream[:settle]:
        call(fr).block_until_ready()
    t0 = time.perf_counter()
    out = None
    for fr in stream:
        out = call(fr)
        out.block_until_ready()
        if per_frame_sleep_s > 0.0:
            time.sleep(per_frame_sleep_s)
    return time.perf_counter() - t0, out


def measure_executor(ex, frames: int, rng: np.random.RandomState,
                     settle: int = 2,
                     per_frame_sleep_s: float = 0.0) -> MeasuredPerf:
    """Steady-state measurement of a frame or video executor.

    Frame executors stream independent frames; video executors carry
    their frame-ring state through the loop (the steady-state serving
    shape). The per-call cost_analysis numbers are normalized to
    per-frame using the executor's batch/chunk.
    """
    h, w = ex.h, ex.w
    batch = getattr(ex, "batch", None)
    chunk = getattr(ex, "chunk", None)
    is_video = hasattr(ex, "init_state")
    per_call = (batch or chunk or 1)
    n_calls = max(1, frames // per_call)

    names = ex.dag.input_stages()
    shape = ((per_call, h, w) if (batch or chunk) else (h, w))
    stream = [{n: rng.rand(*shape).astype(np.float32) for n in names}
              for _ in range(n_calls + settle)]

    if is_video:
        state_box = [ex.init_state()]

        def call(fr):
            out, state_box[0] = ex(fr, state_box[0])
            return out
    else:
        call = ex

    wall, _ = timed_stream(call, stream, settle=settle,
                           per_frame_sleep_s=per_frame_sleep_s)
    cost = executor_cost(ex)
    return MeasuredPerf(
        pipeline=ex.dag.name, h=h, w=w, frames=n_calls * per_call,
        wall_s=wall, fps=n_calls * per_call / wall,
        flops_per_frame=(cost["flops"] / per_call
                         if cost is not None else None),
        bytes_per_frame=(cost["bytes_accessed"] / per_call
                         if cost is not None else None),
    )


# ------------------------------------------------------------ trace side
def step_breakdown(trace_data: dict, pipeline: str) -> dict | None:
    """Queue-wait / assemble / execute / deliver split for one
    pipeline's steps.

    Reads a Chrome-trace dict (``export.to_chrome_trace`` output or a
    ``--trace`` file) and aggregates, over every ``engine.step`` span
    whose ``pipeline`` attr matches: the summed queue wait (span attr,
    clocked by the engine), the total durations of the nested
    ``engine.assemble`` / ``engine.execute`` / ``engine.deliver``
    children, and the step *self* time left over (batching, results,
    metrics — computed with the flame summary's containment
    arithmetic). A batch assembled while the previous batch ran (a
    prefetched ``engine.assemble``, nested in that batch's
    ``engine.execute``) counts as assembly and is taken out of the
    execute time, so no span counts twice. Returns seconds, or None
    when the trace holds no matching step spans; the returned parts
    feed :func:`repro.perf.model.exact_fractions` so the report's time
    split provably partitions the step total.
    """
    spans = _span_rows(trace_data)
    if not spans:
        return None
    self_us = _self_times_us(spans)
    executes: dict = {}          # tid -> sorted (start, end) of calls
    for e in spans:
        if e["name"] == "engine.execute":
            executes.setdefault(e.get("tid"), []).append(
                (e["ts"], e["ts"] + e["dur"]))
    for runs in executes.values():
        runs.sort()

    def nested(e) -> bool:
        runs = executes.get(e.get("tid"), [])
        i = bisect.bisect_right(runs, (e["ts"], math.inf)) - 1
        return i >= 0 and e["ts"] + e["dur"] <= runs[i][1]
    step_us = queue_s = 0.0
    parts_us = {"assemble": 0.0, "execute": 0.0, "deliver": 0.0,
                "step_self": 0.0}
    n_steps = 0
    for e, s in zip(spans, self_us):
        if (e.get("args") or {}).get("pipeline") != pipeline:
            continue
        if e["name"] == "engine.step":
            n_steps += 1
            step_us += float(e["dur"])
            parts_us["step_self"] += s
            queue_s += float(e["args"].get("queue_wait_s", 0.0))
        elif e["name"] == "engine.assemble":
            parts_us["assemble"] += float(e["dur"])
            if nested(e):
                parts_us["execute"] -= float(e["dur"])
        elif e["name"] == "engine.execute":
            parts_us["execute"] += float(e["dur"])
        elif e["name"] == "engine.deliver":
            parts_us["deliver"] += float(e["dur"])
    if n_steps == 0:
        return None
    return {
        "n_steps": n_steps,
        "step_s": step_us / 1e6,
        "queue_wait_s": queue_s,
        "assemble_s": parts_us["assemble"] / 1e6,
        "execute_s": parts_us["execute"] / 1e6,
        "deliver_s": parts_us["deliver"] / 1e6,
        "step_self_s": parts_us["step_self"] / 1e6,
    }
