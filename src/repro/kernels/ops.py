"""Jit'd public wrappers for the Pallas kernels.

``interpret=None`` (the default) derives the mode from the backend: the
kernels compile with Mosaic on a TPU and run in the Pallas interpreter
everywhere else (see ``stencil_pipeline.default_interpret``).
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict

import jax.numpy as jnp

from repro.core.codegen import PipelinePlan
from repro.core.dag import PipelineDAG

from .conv2d_stencil import conv2d
from .stencil_pipeline import (_resolve_depth, _resolve_interpret,
                               _resolve_rows, make_pipeline_kernel)
from .swa_decode import swa_decode

__all__ = ["conv2d", "swa_decode", "fused_pipeline", "make_pipeline_kernel",
           "pipeline_vmem_bytes"]

# sentinel fingerprint for plan-less builds: keys must never collide with
# a real plan's sha256 hex digest (which is lowercase hex, no colons)
_NO_PLAN = "no-plan"


@dataclasses.dataclass
class _KernelCacheStats:
    hits: int = 0
    misses: int = 0
    evictions: int = 0


class _KernelCache:
    """Bounded LRU memo of compiled fused kernels.

    Keyed on the **plan fingerprint** — not ``plan is not None`` — so two
    plans at the same (pipeline, h, w, R) that differ anywhere that
    matters (mem config, schedule, prefetch depth, ...) compile distinct
    kernels; the fingerprint covers the full canonical plan dict.
    Bounded the same way PlanCache's levels are: least-recently-used
    entry evicted past ``max_entries`` (tiled tail chunks would otherwise
    leak one compiled kernel per distinct shape forever), with
    hit/miss/eviction counters for tests and telemetry.
    """

    def __init__(self, max_entries: int = 64):
        if max_entries < 1:
            raise ValueError(f"max_entries must be >= 1, got {max_entries}")
        self.max_entries = max_entries
        self._entries: OrderedDict[tuple, tuple] = OrderedDict()
        self.stats = _KernelCacheStats()

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: tuple) -> bool:
        return key in self._entries

    def get_or_build(self, key: tuple, build) -> tuple:
        entry = self._entries.get(key)
        if entry is not None:
            self.stats.hits += 1
            self._entries.move_to_end(key)
            return entry
        self.stats.misses += 1
        entry = build()
        self._entries[key] = entry
        while len(self._entries) > self.max_entries:
            self._entries.popitem(last=False)
            self.stats.evictions += 1
        return entry

    def clear(self) -> None:
        self._entries.clear()
        self.stats = _KernelCacheStats()


_PIPE_CACHE = _KernelCache()


def _pipe_key(dag: PipelineDAG, h: int, w: int, plan: PipelinePlan | None,
              interpret: bool | None, rows_per_step: int | None,
              prefetch_depth: int | None) -> tuple:
    """Compiled-kernel identity: shape + interpret mode + the resolved
    execution-granularity knobs + the plan's content fingerprint."""
    return (dag.name, h, w,
            plan.fingerprint() if plan is not None else _NO_PLAN,
            _resolve_interpret(interpret),
            _resolve_rows(rows_per_step, plan),
            _resolve_depth(prefetch_depth, plan))


def fused_pipeline(dag: PipelineDAG, images: dict[str, jnp.ndarray],
                   plan: PipelinePlan | None = None,
                   interpret: bool | None = None,
                   rows_per_step: int | None = None,
                   prefetch_depth: int | None = None) -> jnp.ndarray:
    """Run a whole pipeline DAG as one fused line-buffered kernel.

    ``rows_per_step`` is the row-group blocking factor and
    ``prefetch_depth`` the DMA/compute overlap depth (None defers to the
    plan's fields; 1 when no plan)."""
    h, w = next(iter(images.values())).shape
    key = _pipe_key(dag, h, w, plan, interpret, rows_per_step,
                    prefetch_depth)
    fn, _ = _PIPE_CACHE.get_or_build(
        key, lambda: make_pipeline_kernel(dag, h, w, plan=plan,
                                          interpret=interpret,
                                          rows_per_step=rows_per_step,
                                          prefetch_depth=prefetch_depth))
    return fn(images)


def pipeline_vmem_bytes(dag: PipelineDAG, h: int, w: int,
                        plan: PipelinePlan | None = None,
                        rows_per_step: int | None = None,
                        prefetch_depth: int | None = None) -> int:
    key = _pipe_key(dag, h, w, plan, None, rows_per_step, prefetch_depth)
    return _PIPE_CACHE.get_or_build(
        key, lambda: make_pipeline_kernel(dag, h, w, plan=plan,
                                          rows_per_step=rows_per_step,
                                          prefetch_depth=prefetch_depth))[1]
