"""Fused line-buffered stencil pipeline — the paper's accelerator on TPU.

One pl.pallas_call executes the *entire* pipeline DAG: the grid walks the
image in **row groups** of ``rows_per_step`` (R) rows; every stage computes
its R rows of the frame each step, reading its producers' rows from VMEM
ring buffers ("line buffers") and writing its own ring. Only the input
rows and the output rows cross HBM per step — the HBM traffic of the
whole pipeline is ~2 frames instead of ~2 frames *per stage* (what
stage-by-stage XLA execution would do). This is the TPU-native embodiment
of the paper's design:

  * line buffer   -> VMEM scratch ring of shape (ring_rows, W_pad)
  * ring sizing   -> from the ImaGen plan (ilp.py / linebuffer.py) grown
    to cover one read slab: with R rows per step and same-step topological
    execution, a consumer with stencil height SH reads its producer's last
    ``R + SH - 1`` rows as one contiguous slab, so rings hold
    ``max(plan physical lines, R + SH - 1)`` rows (codegen.row_group_rings)
  * row-group blocking -> the TPU analogue of the coarser-granularity
    mappings in push-memory / HWTool line-buffer chunking: at R=1 each
    grid step moves one (1, W) row and the per-step grid overhead
    dominates; at R=8 each step moves a full (8, 128k) float32 VMEM tile
    per stage and the VPU sees 8x the work per step. Blocking changes the
    schedule, not the math: the per-pixel computation graph is identical
    across R. (The one caveat: XLA contracts mul+add chains into FMAs
    differently per trace shape, so FMA-sensitive stages can differ by
    ~1 ULP between R variants — see tests/test_row_group.py.)
  * line coalescing -> ring rows are padded to lcm(R, 8) so every R-row
    write slab is contiguous (write slots are multiples of R, stores
    never wrap) and the ring is a whole number of (8, 128) sublane tiles
    — the paper's Sec. 6 packing in TPU layout terms.
  * SRAM ports    -> no TPU analogue (VMEM is compiler-scheduled); the
    port-contention machinery matters for the ASIC/FPGA backend only.

Ring I/O is vectorized: each edge read is a single contiguous R-aligned
load when it provably cannot wrap (SH == 1 — slab start and ring size
are both multiples of R), and otherwise one aligned whole-ring load
rotated (``pltpu.roll``) so the slab starts at row 0. Slot arithmetic is
one positive-mod on the slab origin — not one rem per row. Every
dynamic offset the TPU lowering sees is a multiple of R, which is why it
takes R % 8 == 0 only. Top-of-frame masking is per-row within the slab,
so frames batched back-to-back through the same rings never observe
each other's residue, and the final partial row group of an
``h % R != 0`` frame computes into padding rows that are cropped
before returning (they are never read back: causal windows only look
upward).

The kernel body is generated from the DAG: stages execute in topological
order inside the row-group loop, so the whole thing stays a single fused
Pallas program. Stencil window math is plain VPU work over 2-D (R, W_pad)
planes: stage functions read shifted planes of each slab through a lazy
window view (:class:`_WindowView`), so no tensor of rank > 2 is built —
the TPU lowering refuses the reshapes such tensors need. Lanes past the
frame width compute on zero padding and are cropped on return; windows
only look left, so they never reach a real column.
"""
from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.codegen import (PipelinePlan, frame_outputs,
                                prefetch_ring_bytes, row_group_rings,
                                tap_name, temporal_tap_rings, temporal_taps)
from repro.obs import trace
from repro.core.dag import PipelineDAG, window_index, window_keys


def default_interpret() -> bool:
    """Whether Pallas kernels run in the interpreter by default: only
    off the TPU. The single place the serving path derives it from —
    every ``interpret=None`` argument resolves here."""
    return jax.default_backend() != "tpu"


def _resolve_interpret(interpret: bool | None) -> bool:
    return default_interpret() if interpret is None else interpret


def _device_name(prefix: str, dag: PipelineDAG) -> str:
    """``<prefix>_<pipeline>``, the pipeline's name made an identifier:
    the name a kernel or program carries in the device trace."""
    return prefix + "_" + re.sub(r"\W", "_", dag.name)


def _named_jit(fn, name: str):
    """``jax.jit(fn)`` whose program is ``jit_<name>`` in the trace."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _stage_read(ring_ref, ring_rows: int, row0: jnp.ndarray, rows_per_step: int,
                sh: int) -> jnp.ndarray:
    """Read the (R + sh - 1, W) slab of rows [row0 - sh + 1, row0 + R - 1]
    from a ring buffer, masking rows above the frame top to zero.

    Row r lives at slot r % ring_rows; the slab is contiguous in ring
    space except when it crosses the ring end:

      * sh == 1 fast path — the slab origin is ``row0``, a multiple of R,
        and ring_rows is a multiple of R, so ``slot + R <= ring_rows``
        always: one contiguous R-aligned load, no wrap possible.
      * otherwise — load the whole ring (offset 0, so sublane-aligned),
        rotate it so the slab origin's slot lands on row 0, and take the
        first R + sh - 1 rows: the rotation handles the wrap, and every
        offset the TPU sees is static or aligned. ``row0 - sh + 1`` can
        be negative by at most sh - 1 < ring_rows, so one positive-mod
        on the slab origin gives its slot.
    """
    r = rows_per_step
    s = r + sh - 1
    if sh == 1:
        # base = row0 >= 0: no row can be above the frame top, skip the mask
        slot = pl.multiple_of(jax.lax.rem(row0, ring_rows), r)
        return ring_ref[pl.ds(slot, r), :]
    base = row0 - (sh - 1)
    slot = jax.lax.rem(base + ring_rows, ring_rows)   # one rem per slab
    ring = ring_ref[...]
    slab = pltpu.roll(ring, jax.lax.rem(ring_rows - slot, ring_rows), 0)[:s]
    live = base + jax.lax.broadcasted_iota(jnp.int32, slab.shape, 0) >= 0
    return jnp.where(live, slab, 0.0)                  # per-row top mask


class _WindowView:
    """Bottom-right-aligned stencil windows over row slabs, built lazily.

    Stands in for the reference executor's ``(R, W, sh, sw)`` (spatial)
    or ``(R, W, st, sh, sw)`` (temporal) window arrays: ``view[..., dy,
    dx]`` (``view[..., dt, dy, dx]``) is the (R, W) plane of pixel
    (row0 + i - sh + 1 + dy, x - sw + 1 + dx) of temporal tap dt — the
    same causal alignment as ``algorithms._windows`` — and ``.shape``
    reports the array it stands for. Nothing of rank > 2 is built, which
    is what the TPU lowering accepts: a column shift is the slab padded
    with zero columns on the left (the frame's left padding) and sliced
    back to its width, a row shift a static sublane slice. Shifted slabs
    are memoized per (dt, dx). Stage functions index it with
    non-negative static ints only.
    """

    def __init__(self, slabs: list, rows_per_step: int, sh: int, sw: int,
                 temporal: bool):
        self._slabs = slabs               # one (R + sh - 1, W) slab per dt
        self._r, self._sw = rows_per_step, sw
        self._temporal = temporal
        self._cols: dict[tuple[int, int], jnp.ndarray] = {}
        w = slabs[0].shape[1]
        self.shape = ((rows_per_step, w)
                      + ((len(slabs),) if temporal else ()) + (sh, sw))

    def __getitem__(self, key) -> jnp.ndarray:
        idx = window_index(key, self.shape)
        dt, dy, dx = idx if self._temporal else (0, *idx)
        cols = self._cols.get((dt, dx))
        if cols is None:
            slab, k = self._slabs[dt], self._sw - 1 - dx
            if k:
                slab = jnp.pad(slab, ((0, 0), (k, 0)))[:, :slab.shape[1]]
            cols = self._cols[(dt, dx)] = slab
        return cols[dy:dy + self._r]


def _build_pipeline_call(dag: PipelineDAG, h: int, w: int,
                         plan: PipelinePlan | None, interpret: bool | None,
                         batch: int | None, rows_per_step: int = 1,
                         prefetch_depth: int = 1):
    """Shared kernel builder for the single-frame and batched executors.

    The two variants differ only in rank: ``batch=None`` runs
    grid=(ceil(h/R),) over (h_pad, w_pad) arrays; an integer batch runs
    grid=(batch, ceil(h/R)) over (batch, h_pad, w_pad). The topological
    stage loop — slab ring reads with per-row top-of-frame masking,
    window assembly with same-producer key dedup, R-row ring writes — is
    identical and lives here exactly once.

    ``prefetch_depth`` selects the I/O discipline around that loop:

      * **1 (default)** — today's synchronous path: row-group blocks
        stream through BlockSpec grid slices, the Pallas pipeline
        double-buffers implicitly. Bit-for-bit the historical behavior.
      * **2 / 4 (multi-buffered)** — inputs and outputs become whole
        ``pl.ANY`` (HBM) operands and every feed/output owns a
        (depth, R, W_pad) VMEM prefetch/staging ring driven by
        ``pltpu.make_async_copy``: step t computes from ring slot
        ``t % depth`` while the DMAs for steps t+1..t+depth-1 are in
        flight, and output slabs drain asynchronously behind compute —
        the paper's push-memory overlap, depth slabs deep. Grid steps
        are linearized ``t = b * n_groups + g`` so one ring and one
        semaphore array serve the whole batch.

    Temporal pipelines add two kinds of operands around that same loop:

      * **tap pseudo-inputs** — for every (producer, j frames back) tap
        the DAG needs, a history frame streamed from the caller-held
        frame ring. Each tap is handled exactly like an input stage: its
        R-row block is written to a private VMEM tap ring, and consumers
        assemble (st, R+sh-1, W) slabs by reading the producer's live
        ring (tap 0) plus the tap rings — the row-group slab loader,
        reused per temporal tap. Frames older than the stream start are
        zeros in the frame ring, matching the reference's causal zero
        padding along time.
      * **frame outputs** — internal (non-input) temporal producers emit
        their full frame alongside the pipeline output so the caller can
        push it into the frame ring for the next call. Batched execution
        is refused for those DAGs: batch slots would need frames the
        same call is still computing.

    The return contract is ``fn(images) -> out`` as before, except when
    the DAG has internal temporal producers: then ``fn(images) ->
    (out, {producer: frame})``. ``images`` must carry one entry per
    input stage plus one per tap (keyed ``codegen.tap_name(p, j)``).
    """
    interpret = _resolve_interpret(interpret)
    r = rows_per_step
    if r < 1:
        raise ValueError(f"rows_per_step must be >= 1, got {r}")
    if not interpret and r % 8:
        # the TPU lowering moves row groups as whole (8, 128) float32
        # tiles: a BlockSpec or DMA slab of R % 8 != 0 rows is refused
        raise ValueError(f"rows_per_step={r} does not lower for the TPU: "
                         f"it must be a multiple of 8")
    depth = prefetch_depth
    if depth < 1:
        raise ValueError(f"prefetch_depth must be >= 1, got {depth}")
    n_groups = -(-h // r)
    h_pad = n_groups * r
    rings = row_group_rings(dag, plan.alloc.buffers if plan else None, r)
    w_pad = _round_up(w, 128)
    ring_shapes = {p: (rr, w_pad) for p, rr in rings.items()}
    taps = temporal_taps(dag)
    for (p, j), rr in temporal_tap_rings(dag, r).items():
        name = tap_name(p, j)
        if name in dag.stages:
            raise ValueError(f"stage name {name!r} collides with the "
                             f"temporal tap naming scheme")
        ring_shapes[name] = (rr, w_pad)
    vmem_bytes = sum(rr * c * 4 for (rr, c) in ring_shapes.values())
    if depth > 1:
        vmem_bytes += prefetch_ring_bytes(dag, r, depth, w)
    ring_owners = list(ring_shapes)
    inputs = dag.input_stages()
    feeds = inputs + [tap_name(p, j) for (p, j) in taps]
    # internal temporal producers: their frames must round-trip through
    # the caller's frame ring, so the kernel emits them as extra outputs
    frame_outs = frame_outputs(dag)
    out_stage = dag.output_stages()[0]
    # the stage the output stage reads (it streams 1x1 from it)
    final = dag.in_edges(out_stage)[0].producer

    batched = batch is not None
    if batched and frame_outs:
        raise ValueError(
            f"{dag.name}: batched execution needs input-only temporal "
            f"taps, but {sorted(frame_outs)} are internal temporal "
            f"producers (frame t would need frame t-1 from the same call)")
    group_axis = 1 if batched else 0    # program_id axis walking row groups
    lead = (0,) if batched else ()      # block-local leading index

    def stage_pass(read_feed, store_out, store_frame, ring_refs, row0):
        """The topological stage loop, shared by both I/O disciplines.

        ``read_feed(name)`` yields a feed's (R, W_pad) block for this
        step; ``store_out(val)`` / ``store_frame(p, val)`` emit the pipeline
        output and the internal temporal producers' frames. Everything
        between — tap-ring staging, slab reads, window assembly, ring
        writes — is identical whether blocks arrive via BlockSpec or
        through DMA prefetch rings.
        """
        def store_ring(name, val):
            # rr % R == 0 and row0 % R == 0: the write never wraps
            rr = ring_shapes[name][0]
            slot = pl.multiple_of(jax.lax.rem(row0, rr), r)
            ring_refs[name][pl.ds(slot, r), :] = val

        def slab(src: str, sh: int) -> jnp.ndarray:
            return _stage_read(ring_refs[src], ring_shapes[src][0], row0,
                               r, sh)

        # stream the history taps into their rings first: consumers later
        # in this same grid step read their slabs like any producer ring
        for (p, j) in taps:
            store_ring(tap_name(p, j), read_feed(tap_name(p, j)))

        for name in dag.topo_order:
            st = dag.stages[name]
            if st.is_output:
                continue
            if st.is_input:
                val = read_feed(name)
            elif st.fn is None:  # relay: identity on the producer's R rows
                val = slab(dag.in_edges(name)[0].producer, 1)
            else:
                ins = dag.in_edges(name)
                # temporal index dt is tap st-1-dt, so index st-1 is the
                # current frame — causal alignment, like the spatial axes
                wins = {key: _WindowView(
                    [slab(e.producer if j == 0 else tap_name(e.producer, j),
                          e.sh) for j in range(e.st - 1, -1, -1)],
                    r, e.sh, e.sw, temporal=e.st > 1)
                    for key, e in zip(window_keys(ins), ins)}
                val = st.fn(wins)  # (R, W_pad)
            if name in ring_refs:
                store_ring(name, val)
            if name in frame_outs:
                store_frame(name, val)
            if name == final:
                store_out(val)

    if batched:
        grid, out_dims = (batch, n_groups), (batch, h_pad, w_pad)
    else:
        grid, out_dims = (n_groups,), (h_pad, w_pad)
    n_outs = 1 + len(frame_outs)
    out_shape = [jax.ShapeDtypeStruct(out_dims, jnp.float32)] * n_outs

    if depth == 1:
        def kernel(*refs):
            in_refs = {name: refs[i] for i, name in enumerate(feeds)}
            out_ref = refs[len(feeds)]
            frame_refs = {p: refs[len(feeds) + 1 + i]
                          for i, p in enumerate(frame_outs)}
            ring_refs = {p: refs[len(feeds) + 1 + len(frame_outs) + i]
                         for i, p in enumerate(ring_owners)}
            row0 = pl.program_id(group_axis) * r   # first row of this group

            def read_feed(name):
                return in_refs[name][lead]

            def store_out(val):
                out_ref[lead] = val

            def store_frame(p, val):
                frame_refs[p][lead] = val

            stage_pass(read_feed, store_out, store_frame, ring_refs, row0)

        if batched:
            blk, index_map = (1, r, w_pad), (lambda b, g: (b, g, 0))
        else:
            blk, index_map = (r, w_pad), (lambda g: (g, 0))
        in_specs = [pl.BlockSpec(blk, index_map) for _ in feeds]
        out_specs = [pl.BlockSpec(blk, index_map)] * n_outs
        scratch = [pltpu.VMEM(ring_shapes[p], jnp.float32)
                   for p in ring_owners]
    else:
        outs = ["__out__"] + frame_outs
        total = (batch if batched else 1) * n_groups

        def kernel(*refs):
            i = iter(range(len(refs)))
            hbm_in = {name: refs[next(i)] for name in feeds}
            hbm_out = {o: refs[next(i)] for o in outs}
            ring_refs = {p: refs[next(i)] for p in ring_owners}
            pf_in = {name: refs[next(i)] for name in feeds}
            pf_out = {o: refs[next(i)] for o in outs}
            in_sems = {name: refs[next(i)] for name in feeds}
            out_sems = {o: refs[next(i)] for o in outs}

            g = pl.program_id(group_axis)
            row0 = g * r
            # linearized step: the ring/semaphore clock across the batch
            t = pl.program_id(0) * n_groups + g if batched else g
            slot = jax.lax.rem(t, depth)

            def in_dma(name, u, s):
                """Async copy of step u's (R, w_pad) input slab of
                ``name`` into prefetch slot s."""
                if batched:
                    bb = u // n_groups
                    gg = u - bb * n_groups
                    src = hbm_in[name].at[bb, pl.ds(gg * r, r), :]
                else:
                    src = hbm_in[name].at[pl.ds(u * r, r), :]
                return pltpu.make_async_copy(src, pf_in[name].at[s],
                                             in_sems[name].at[s])

            def out_dma(o, u, s):
                """Async drain of staging slot s to step u's output rows."""
                if batched:
                    bb = u // n_groups
                    gg = u - bb * n_groups
                    dst = hbm_out[o].at[bb, pl.ds(gg * r, r), :]
                else:
                    dst = hbm_out[o].at[pl.ds(u * r, r), :]
                return pltpu.make_async_copy(pf_out[o].at[s], dst,
                                             out_sems[o].at[s])

            @pl.when(t == 0)
            def _prologue():
                # fill the pipeline: the first min(depth, total) input
                # slabs start in flight before any compute
                for u in range(min(depth, total)):
                    for name in feeds:
                        in_dma(name, u, u % depth).start()

            # own slabs must have landed before compute touches them
            for name in feeds:
                in_dma(name, t, slot).wait()

            # the staging slot is recycled every ``depth`` steps: its
            # previous drain must complete before this step overwrites it
            @pl.when(t >= depth)
            def _reclaim():
                for o in outs:
                    out_dma(o, t - depth, slot).wait()

            def read_feed(name):
                return pf_in[name][slot]

            def store_out(val):
                pf_out["__out__"][slot] = val

            def store_frame(p, val):
                pf_out[p][slot] = val

            stage_pass(read_feed, store_out, store_frame, ring_refs, row0)

            # drain this step behind compute, prefetch depth steps ahead
            for o in outs:
                out_dma(o, t, slot).start()
            nxt = t + depth
            @pl.when(nxt < total)
            def _prefetch():
                for name in feeds:
                    in_dma(name, nxt, slot).start()

            @pl.when(t == total - 1)
            def _epilogue():
                # at the last step u = t - d >= 0 for every d below:
                # d < min(depth, total) <= total = t + 1
                for d in range(min(depth, total)):
                    u = t - d
                    for o in outs:
                        out_dma(o, u, jax.lax.rem(u, depth)).wait()

        any_spec = pl.BlockSpec(memory_space=pl.ANY)
        in_specs = [any_spec for _ in feeds]
        out_specs = [any_spec] * n_outs
        scratch = (
            [pltpu.VMEM(ring_shapes[p], jnp.float32) for p in ring_owners]
            + [pltpu.VMEM((depth, r, w_pad), jnp.float32) for _ in feeds]
            + [pltpu.VMEM((depth, r, w_pad), jnp.float32) for _ in outs]
            + [pltpu.SemaphoreType.DMA((depth,)) for _ in feeds]
            + [pltpu.SemaphoreType.DMA((depth,)) for _ in outs])

    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        # the VMEM rings carry rows from one grid step to the next and
        # from one batch frame to the next: no axis may be split
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid)),
        interpret=interpret,
        name=_device_name("imagen_stencil", dag),
    )

    def fn(images: dict[str, jnp.ndarray]):
        # pad rows to the row-group boundary and cols to the lane tile;
        # padding rows compute garbage that is cropped here and, being
        # below every real row, is never read back (windows are causal)
        padded = [jnp.pad(jnp.asarray(images[n], jnp.float32),
                          [(0, 0)] * (len(out_dims) - 2)
                          + [(0, h_pad - h), (0, w_pad - w)])
                  for n in feeds]
        outs = call(*padded)
        out = outs[0][..., :h, :w]
        if not frame_outs:
            return out
        return out, {p: outs[1 + i][..., :h, :w]
                     for i, p in enumerate(frame_outs)}

    program = "imagen_frame_batch" if batched else "imagen_frame"
    return _named_jit(fn, _device_name(program, dag)), vmem_bytes


def _resolve_rows(rows_per_step: int | None,
                  plan: PipelinePlan | None) -> int:
    if rows_per_step is not None:
        return rows_per_step
    return plan.rows_per_step if plan is not None else 1


def _resolve_depth(prefetch_depth: int | None,
                   plan: PipelinePlan | None) -> int:
    if prefetch_depth is not None:
        return prefetch_depth
    return plan.prefetch_depth if plan is not None else 1


def make_pipeline_kernel(dag: PipelineDAG, h: int, w: int,
                         plan: PipelinePlan | None = None,
                         interpret: bool | None = None,
                         rows_per_step: int | None = None,
                         prefetch_depth: int | None = None):
    """Build a jit-compiled fused executor for ``dag`` on (h, w) images.

    ``rows_per_step`` and ``prefetch_depth`` default to the plan's
    fields (1 when no plan). Returns (fn, vmem_bytes): fn maps
    {input_name: (h, w) float32} to the (h, w) float32 output of the
    pipeline's output stage.
    """
    return _build_pipeline_call(dag, h, w, plan, interpret, batch=None,
                                rows_per_step=_resolve_rows(rows_per_step,
                                                            plan),
                                prefetch_depth=_resolve_depth(
                                    prefetch_depth, plan))


def make_batched_pipeline_kernel(dag: PipelineDAG, batch: int, h: int, w: int,
                                 plan: PipelinePlan | None = None,
                                 interpret: bool | None = None,
                                 rows_per_step: int | None = None,
                                 prefetch_depth: int | None = None):
    """Batched variant: one fused Pallas program over a frame batch.

    The grid is (batch, ceil(h/R)); frames execute back-to-back through
    the SAME VMEM ring buffers — no per-frame re-allocation, no extra
    VMEM. This is sound because every ring read is top-of-frame masked
    per slab row (rows above row 0 of the *current* frame read as zero),
    so frame b never observes frame b-1's residue: any unmasked slot was
    rewritten earlier in frame b.

    Returns (fn, vmem_bytes): fn maps {input: (B, h, w)} -> (B, h, w).
    """
    return _build_pipeline_call(dag, h, w, plan, interpret, batch=batch,
                                rows_per_step=_resolve_rows(rows_per_step,
                                                            plan),
                                prefetch_depth=_resolve_depth(
                                    prefetch_depth, plan))


@dataclasses.dataclass(frozen=True)
class StencilExecutor:
    """A compiled, reusable frame executor — the serving-side artifact.

    ``batch=None`` wraps the single-frame kernel ((h, w) -> (h, w));
    an integer batch wraps the batched kernel ((B, h, w) -> (B, h, w)).
    ``rows_per_step`` is the row-group blocking factor the kernel was
    traced at; outputs are identical across values of it up to XLA's
    shape-dependent FMA contraction (~1 ULP, see tests/test_row_group.py).
    The callable is jitted once at construction; every subsequent call is
    the steady-state cost only.
    """
    dag: PipelineDAG
    h: int
    w: int
    batch: int | None
    rows_per_step: int
    prefetch_depth: int
    vmem_bytes: int
    interpret: bool
    # window elements the stage functions read per output pixel
    # (PipelineDAG.taps, counted once per DAG)
    taps: int
    # the ImaGen plan this executor embodies (None for plan-less ad-hoc
    # builds): the serving stack reports per-executor memory/power
    # accounting — e.g. an autotuned config's SRAM bill — through it
    plan: PipelinePlan | None = dataclasses.field(repr=False, default=None)
    # kw_only: keeps _fn a *required* argument despite following a
    # defaulted field — a fn-less executor must fail at construction
    _fn: "callable" = dataclasses.field(repr=False, kw_only=True)

    def __call__(self, images: dict[str, jnp.ndarray]) -> jnp.ndarray:
        # span covers the dispatch (async under jit); xla=True wraps the
        # call in a jax.profiler.TraceAnnotation so it lines up with the
        # XLA profile when both are captured
        with trace.span("executor.call", xla=True, pipeline=self.dag.name,
                        batch=self.batch, rows_per_step=self.rows_per_step,
                        prefetch_depth=self.prefetch_depth, taps=self.taps):
            return self._fn(images)

    @property
    def frame_shape(self) -> tuple[int, int]:
        return (self.h, self.w)


def make_executor(dag: PipelineDAG, h: int, w: int,
                  batch: int | None = None,
                  plan: PipelinePlan | None = None,
                  interpret: bool | None = None,
                  rows_per_step: int | None = None,
                  prefetch_depth: int | None = None) -> StencilExecutor:
    """Executor factory: DAG + shape (+ optional plan) -> StencilExecutor."""
    if dag.is_temporal():
        raise ValueError(f"{dag.name} reads frame history; build it with "
                         f"make_video_executor")
    r = _resolve_rows(rows_per_step, plan)
    d = _resolve_depth(prefetch_depth, plan)
    interpret = _resolve_interpret(interpret)
    fn, vmem = _build_pipeline_call(dag, h, w, plan, interpret, batch,
                                    rows_per_step=r, prefetch_depth=d)
    return StencilExecutor(dag=dag, h=h, w=w, batch=batch, rows_per_step=r,
                           prefetch_depth=d, vmem_bytes=vmem,
                           interpret=interpret, taps=dag.taps,
                           plan=plan, _fn=fn)


def init_frame_state(depths: dict[str, int], h: int,
                     w: int) -> dict[str, jnp.ndarray]:
    """Zero frame rings for a fresh stream: one (d-1, h, w) float32 ring
    per temporal producer, newest frame first along axis 0. The single
    definition of the state layout — the executor's concatenate/flip
    rolls and the engine's sessions both build state through here."""
    return {p: jnp.zeros((d - 1, h, w), jnp.float32)
            for p, d in depths.items()}


def _unstack(x: jnp.ndarray) -> tuple[jnp.ndarray, ...]:
    return tuple(x[i] for i in range(x.shape[0]))


# (B, h, w) -> B separate (h, w) arrays, each a copy of its row: how the
# engines hand out a batch's or chunk's frames, one host dispatch per
# batch. jit keys it on the input's shape and dtype, so a padded batch
# compiles it once per frame shape whatever its fill.
unstack = _named_jit(_unstack, "imagen_unstack")


@dataclasses.dataclass(frozen=True)
class VideoExecutor:
    """A compiled frame-stream executor — stateless across streams.

    The temporal analogue of :class:`StencilExecutor`: the jitted Pallas
    call is compiled once and shared by every stream of the pipeline; all
    per-stream state — the frame rings holding each temporal producer's
    last ``d-1`` frames — is an explicit argument and result of
    ``__call__``, so N concurrent streams multiplex over ONE executor
    without cross-talk.

    ``chunk=None`` advances one frame per call ({input: (h, w)} ->
    (h, w)); ``chunk=B`` advances B *consecutive* frames of one stream
    per call ({input: (B, h, w)} -> (B, h, w)) through the batched grid —
    frame b's history taps are served from the time-shifted input
    sequence itself, which is why chunking requires input-only temporal
    taps (enforced at construction).
    """
    dag: PipelineDAG
    h: int
    w: int
    chunk: int | None
    rows_per_step: int
    prefetch_depth: int
    vmem_bytes: int                 # VMEM rings (spatial + tap + prefetch)
    frame_state_bytes: int          # device-resident frame-ring state
    interpret: bool
    taps: int                       # as StencilExecutor.taps
    depths: dict = dataclasses.field(repr=False)   # producer -> frames
    # compiled ImaGen plan (see StencilExecutor.plan) — None when ad hoc
    plan: PipelinePlan | None = dataclasses.field(repr=False, default=None)
    _fn: "callable" = dataclasses.field(repr=False, kw_only=True)

    def init_state(self) -> dict[str, jnp.ndarray]:
        """Zero frame rings — the stream-start (warm-up) state. Frames
        read from the zero region reproduce the reference's causal zero
        padding along time."""
        return init_frame_state(self.depths, self.h, self.w)

    def __call__(self, images: dict[str, jnp.ndarray],
                 state: dict[str, jnp.ndarray]
                 ) -> tuple[jnp.ndarray, dict[str, jnp.ndarray]]:
        with trace.span("executor.call", xla=True, pipeline=self.dag.name,
                        chunk=self.chunk, rows_per_step=self.rows_per_step,
                        prefetch_depth=self.prefetch_depth, taps=self.taps):
            return self._fn(images, state)

    @property
    def warmup_frames(self) -> int:
        """Frames before the output stops depending on the zero history."""
        return self.dag.cumulative_extent(temporal=True)[0]


def make_video_executor(dag: PipelineDAG, h: int, w: int,
                        plan: PipelinePlan | None = None,
                        interpret: bool | None = None,
                        rows_per_step: int | None = None,
                        chunk: int | None = None,
                        prefetch_depth: int | None = None) -> VideoExecutor:
    """Build a streaming executor for a (possibly temporal) pipeline.

    Wraps the fused Pallas call with the frame-ring plumbing: history
    taps are sliced out of the caller's state (single-frame mode) or
    time-shifted out of the input chunk itself (chunk mode), and the
    returned state rolls the newest frames in. A DAG with no temporal
    edges degenerates to the plain executor with empty state.
    """
    r = _resolve_rows(rows_per_step, plan)
    d = _resolve_depth(prefetch_depth, plan)
    interpret = _resolve_interpret(interpret)
    depths = dag.temporal_depths()
    inputs = set(dag.input_stages())
    internal = sorted(p for p in depths if p not in inputs)
    fn, vmem = _build_pipeline_call(dag, h, w, plan, interpret, batch=chunk,
                                    rows_per_step=r, prefetch_depth=d)
    taps = temporal_taps(dag)

    def step(images, state):
        feed = {n: jnp.asarray(images[n], jnp.float32)
                for n in dag.input_stages()}
        for (p, j) in taps:
            if chunk is None:
                feed[tap_name(p, j)] = state[p][j - 1]
            else:
                # tap j of chunk frame b is stream frame t0+b-j: the
                # first j frames come from the ring (newest-first, so
                # flipped), the rest are the chunk itself shifted by j
                feed[tap_name(p, j)] = jnp.concatenate(
                    [jnp.flip(state[p][:j], axis=0), feed[p]],
                    axis=0)[:chunk]
        out = fn(feed)
        frames = {}
        if internal:
            out, frames = out
        new_state = {}
        for p, d in depths.items():
            if chunk is None:
                cur = feed[p] if p in inputs else frames[p]
                new_state[p] = jnp.concatenate(
                    [cur[None], state[p]], axis=0)[:d - 1]
            else:
                new_state[p] = jnp.concatenate(
                    [jnp.flip(feed[p], axis=0), state[p]], axis=0)[:d - 1]
        return out, new_state

    return VideoExecutor(dag=dag, h=h, w=w, chunk=chunk, rows_per_step=r,
                         prefetch_depth=d, vmem_bytes=vmem,
                         frame_state_bytes=sum((d - 1) * h * w * 4
                                               for d in depths.values()),
                         interpret=interpret, taps=dag.taps,
                         depths=dict(depths), plan=plan,
                         _fn=_named_jit(step, _device_name(
                             "imagen_video_step", dag)))
