"""Shared benchmark plumbing: timing loops, drift metrics, CLI, reports.

Every benchmark in this directory used to carry its own copy of the
same four things — a warmup/``block_until_ready`` steady-state timing
loop, a ULP drift metric, the ``--widths/--height/--frames/--smoke/
--trace/--out`` argument block, and the write-the-JSON-report tail.
They live here once now; ``perf_lab.py`` (the unified harness) and the
per-subsystem benchmarks (serve_frames, serve_video, tune_sweep) all
use these helpers, so a timing-methodology fix lands everywhere at
once.

The steady-state timing loop itself is
:func:`repro.perf.measure.timed_stream` (the perf subsystem owns the
measurement methodology; benchmarks re-export it) — settle frames
un-timed, then dispatch + block per frame.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO_ROOT, "src"))

from repro.obs import export as obs_export  # noqa: E402
from repro.obs import trace  # noqa: E402
from repro.perf.measure import timed_stream  # noqa: E402,F401 (re-export)


# ----------------------------------------------------------- compile cache
def enable_compile_cache() -> str:
    """Put JAX's persistent compilation cache in place; returns its dir.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads
    it itself; nothing else is set here). Otherwise the cache lives at
    the fixed ``<checkout>/.jax_cache`` — a fixed path, because the path
    is part of what the cache matches on. Called by entry points before
    their first compile, never at import, so the tests keep JAX's
    defaults.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


# --------------------------------------------------------------- metrics
def max_ulp(a: np.ndarray, b: np.ndarray) -> float:
    """Max per-element ULP distance (0.0 when bitwise equal)."""
    if (a == b).all():
        return 0.0
    scale = np.spacing(np.maximum(np.abs(a), np.abs(b)).astype(np.float32))
    return float(np.max(np.abs(a - b) / scale))


def scale_ulp(got: np.ndarray, exp: np.ndarray) -> float:
    """Max |got-exp| as a multiple of the float32 spacing at the
    reference's overall scale; 0.0 when bitwise equal. Coarser than
    :func:`max_ulp` (one spacing for the whole array) — the bound the
    FMA-wobble gates are written against."""
    if (got == exp).all():
        return 0.0
    err = np.abs(got - exp).max()
    return float(err / np.spacing(np.abs(exp).max()))


def geomean(xs) -> float:
    xs = list(xs)
    return float(np.exp(np.mean(np.log(xs)))) if xs else 0.0


# ---------------------------------------------------------- timing loops
def steady_fps(call, stream, settle: int = 2,
               frames_per_item: int = 1) -> tuple[float, object]:
    """(frames/sec, last output) for a stateless per-item callable."""
    wall, out = timed_stream(call, stream, settle=settle)
    return frames_per_item * len(stream) / wall, out


def timed_scan(call, items, state, settle: int = 0):
    """Video-style carry loop: ``call(item, state) -> (out, state)``.

    Returns (outputs list, final state, seconds). Only the last output
    is blocked on — matching the pipelined steady-state serving shape
    (tune_sweep's original loop).
    """
    for it in items[:settle]:
        out, state = call(it, state)
        out.block_until_ready()
    t0 = time.perf_counter()
    outs = []
    for it in items:
        out, state = call(it, state)
        outs.append(out)
    outs[-1].block_until_ready()
    return outs, state, time.perf_counter() - t0


# ------------------------------------------------------------------- CLI
def make_parser(description: str, out_default: str,
                pipelines_default: list[str] | None = None,
                pipelines_choices: list[str] | None = None,
                widths_default: list[int] = (48, 96),
                height_default: int = 64,
                frames_default: int = 24) -> argparse.ArgumentParser:
    """The argument block shared by every benchmark entry point."""
    ap = argparse.ArgumentParser(description=description)
    if pipelines_default is not None:
        ap.add_argument("--pipelines", nargs="+",
                        default=list(pipelines_default),
                        choices=pipelines_choices)
    ap.add_argument("--widths", nargs="+", type=int,
                    default=list(widths_default))
    ap.add_argument("--height", type=int, default=height_default)
    ap.add_argument("--frames", type=int, default=frames_default)
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate mode: tiny seeded sweep, nonzero exit "
                         "on regression")
    ap.add_argument("--trace", default=None, metavar="OUT_JSON",
                    help="capture a Chrome/Perfetto span trace of the "
                         "run and write it here")
    ap.add_argument("--out", default=out_default)
    return ap


def init_trace(args) -> None:
    if getattr(args, "trace", None):
        trace.enable()


def write_report(path: str | None, report: dict) -> None:
    if not path:
        return
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
    print(f"wrote {path}")


def finish_trace(args, process_name: str, top: int = 12,
                 memtraces: list[dict] | None = None) -> None:
    """Export + validate the global trace and print its flame summary.

    ``memtraces``: ``memtrace/v1`` dicts to overlay as Perfetto counter
    tracks, each anchored to its pipeline's first execute span — one
    file then shows the wall-clock spans *and* the cycle-domain buffer
    occupancy / port pressure of the design that served them.
    """
    if not getattr(args, "trace", None):
        return
    data = obs_export.export_global_trace(args.trace,
                                          process_name=process_name)
    if memtraces:
        data = obs_export.merge_counter_tracks(data, memtraces)
        errs = obs_export.validate_trace(data)
        if errs:
            raise ValueError("merged counter tracks broke the trace "
                             "schema: " + "; ".join(errs))
        obs_export.write_trace(args.trace, data)
    n = sum(e.get("ph") == "X" for e in data["traceEvents"])
    n_c = sum(e.get("ph") == "C" for e in data["traceEvents"])
    counters = f", {n_c} counter samples" if n_c else ""
    print(f"wrote {args.trace} ({n} spans{counters})\n"
          + obs_export.flame_summary(data, top=top))
