"""Pipeline parallelism planned by the ImaGen formulation (DESIGN.md §3.2).

Mapping: PP stage -> DAG node, microbatch index -> cycle t (W = 1), the
activation stash -> line buffer, per-step send/recv slot -> memory port.
The forward chain f0 -> f1 -> ... -> f{N-1} -> b{N-1} -> ... -> b0 with the
stash edge f_i -> b_i is exactly a multi-consumer pipeline; the ILP's
optimal buffer sizes reproduce the classic 1F1B activation-stash bound
LB(f_i) = 2*(N - i) - 1 (tests/test_pipeline.py asserts this).

The executor below runs the *forward* schedule with shard_map +
ppermute on a 'stage' mesh axis: microbatches stream through stages with
the ILP's start offsets; numerics are validated against the unsharded
reference on host devices.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import Pipeline as CorePipeline
from repro.core.algorithms import identity_fn
from repro.core.ilp import build_problem, solve_schedule


def plan_1f1b(n_stages: int):
    """Schedule fwd/bwd stage offsets + stash sizes via the paper's ILP.

    Returns (starts, stash) where stash[i] = microbatches of activations
    stage i must hold between its forward and backward passes.
    """
    p = CorePipeline(f"pp-{n_stages}")
    prev = p.input("f0")
    fwd = [prev]
    for i in range(1, n_stages):
        prev = p.stage(f"f{i}", [(prev, 1, 1)], identity_fn)
        fwd.append(prev)
    # backward chain; b_i consumes f_i's stashed activation
    prev_b = p.stage(f"b{n_stages-1}", [(fwd[-1], 1, 1)], identity_fn)
    for i in range(n_stages - 2, -1, -1):
        prev_b = p.stage(f"b{i}", [(prev_b, 1, 1), (fwd[i], 1, 1)],
                         identity_fn)
    p.output("out", [(prev_b, 1, 1)])
    dag = p.build()
    # W=1: one "pixel" per microbatch; 2 ports = send+recv per step
    prob = build_problem(dag, w=1, ports=2)
    sched = solve_schedule(prob)
    starts = dict(sched.starts)
    # stash depth = how many microbatches sit between f_i and b_i. (The
    # schedule's buffer_lines add the +1 ring-aliasing slot from the
    # hardware correction in ilp.py — PP stashes are discrete buffers
    # with read-then-free semantics, so the raw start delta is the bound.)
    stash = {i: starts[f"b{i}"] - starts[f"f{i}"] for i in range(n_stages)}
    return starts, stash


def pipeline_forward(params_stacked, x_micro, apply_fn, mesh,
                     stage_axis: str = "stage"):
    """GPipe-style forward over a 'stage' mesh axis.

    params_stacked: pytree with leading dim n_stages (stage-sharded).
    x_micro: (n_micro, mb, d) microbatches. apply_fn(params_i, x) -> y.
    Returns (n_micro, mb, d) outputs of the last stage.
    """
    n_stages = mesh.shape[stage_axis]
    n_micro = x_micro.shape[0]
    steps = n_micro + n_stages - 1

    def per_stage(params, xs):
        # params: this stage's slice (leading dim 1); xs: all microbatches
        # (replicated). Each device runs `steps` ticks; data moves stage ->
        # stage+1 with ppermute.
        stage = jax.lax.axis_index(stage_axis)
        p = jax.tree.map(lambda a: a[0], params)
        # mark xs device-varying: each stage writes its own outputs
        xs = jax.lax.pcast(xs, (stage_axis,), to="varying")
        buf = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t from the host-visible xs
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            inject = jnp.where(stage == 0,
                               jnp.where(t < n_micro, 1, 0), 0)
            cur = jnp.where(inject, xs[mb_idx], buf)
            # every stage processes its current occupant when active:
            # stage s works on microbatch (t - s)
            active = (t >= stage) & (t - stage < n_micro)
            y = apply_fn(p, cur)
            y = jnp.where(active, y, cur)
            # last stage records its finished microbatch
            done_idx = jnp.clip(t - stage, 0, n_micro - 1)
            record = active & (stage == n_stages - 1)
            outs = jnp.where(record, outs.at[done_idx].set(y), outs)
            # shift to the next stage
            buf = jax.lax.ppermute(
                y, stage_axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return buf, outs

        buf, outs = jax.lax.fori_loop(0, steps, tick, (buf, outs))
        # only the last stage's outs are meaningful; psum-broadcast them
        outs = jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, stage_axis)

    from jax.experimental.shard_map import shard_map
    fn = shard_map(per_stage, mesh=mesh,
                   in_specs=(P(stage_axis), P()),
                   out_specs=P())
    return fn(params_stacked, x_micro)
