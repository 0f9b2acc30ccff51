import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# ^ MUST run before any jax import: jax locks the device count on first
# init. The dry-run (and only the dry-run) builds the 512-chip mesh.

# Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.
#
# For each cell this produces, per device:
#   * memory_analysis  — argument/output/temp bytes (proves it fits HBM)
#   * cost_analysis    — HLO FLOPs + bytes accessed
#   * collective bytes — parsed from the post-SPMD optimized HLO, by op
# plus the three roofline terms (seconds) from the TPU v5e constants.
#
# Usage:
#   python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k
#   python -m repro.launch.dryrun --all --mesh both --out results/dryrun.json

import argparse
import dataclasses
import json
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed import sharding as shd
from repro.launch.mesh import TPU_V5E, make_production_mesh
from repro.launch.shapes import (SHAPES, cell_status, decode_input_specs,
                                 prefill_input_specs, train_input_specs)
from repro.models import build_model, get_config
from repro.train import OptConfig, make_train_step
from repro.train.optimizer import init_opt_state

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f64": 8, "s16": 2,
                "u16": 2, "c64": 8}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\S+))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"(f32|bf16|f16|f64|s64|s32|s16|s8|u64|u32|u16|u8|pred|c64)"
                       r"\[([\d,]*)\]")


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Per-device bytes moved per collective op type.

    Bytes = result-shape bytes x a per-op traffic factor for ring
    algorithms (all-reduce moves ~2x the tensor through each chip;
    gather/scatter/permute/all-to-all ~1x). '-done' duplicates of async
    ops are skipped.
    """
    out: dict[str, float] = {}
    seen_done = set()
    for m in _COLL_RE.finditer(hlo_text):
        typestr, op = m.group(1), m.group(2)
        if "-done(" in m.group(0):
            continue
        nbytes = 0.0
        for dt, dims in _SHAPE_RE.findall(typestr):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        factor = 2.0 if op == "all-reduce" else 1.0
        out[op] = out.get(op, 0.0) + nbytes * factor
    return out


# per-arch microbatch counts for train_4k (global batch 256 stays fixed)
# MoE sharding mode override per arch: "tp" = replicate experts, shard
# d_ff over 'model' (kills EP dispatch all-to-alls; §Perf iteration 3)
MOE_MODE = {}

GRAD_ACCUM = {
    "mixtral-8x22b": 8,
    "granite-moe-1b-a400m": 4,
    "recurrentgemma-2b": 4,
    "qwen2-vl-7b": 2,
    "phi4-mini-3.8b": 2,
}


@dataclasses.dataclass
class CellResult:
    arch: str
    shape: str
    mesh: str
    status: str
    flops_per_dev: float = 0.0
    bytes_per_dev: float = 0.0
    coll_bytes: dict = dataclasses.field(default_factory=dict)
    arg_bytes: int = 0
    out_bytes: int = 0
    temp_bytes: int = 0
    compile_s: float = 0.0
    roofline: dict = dataclasses.field(default_factory=dict)

    def to_json(self):
        return dataclasses.asdict(self)


def roofline_terms(flops: float, bytes_acc: float, coll: dict,
                   links_per_chip: float = 4.0) -> dict:
    t_compute = flops / TPU_V5E["peak_flops_bf16"]
    t_memory = bytes_acc / TPU_V5E["hbm_bw"]
    total_coll = sum(coll.values())
    t_coll = total_coll / (TPU_V5E["ici_bw"] * links_per_chip)
    dom = max(("compute", t_compute), ("memory", t_memory),
              ("collective", t_coll), key=lambda kv: kv[1])[0]
    return {"compute_s": t_compute, "memory_s": t_memory,
            "collective_s": t_coll, "dominant": dom}


def _abstract_state(model, opt_cfg):
    def mk(key):
        params = model.init(key)
        params = jax.tree.map(
            lambda p: p.astype(model.cfg.compute_dtype)
            if p.dtype == jnp.float32 and p.ndim >= 2 else p, params)
        return {"params": params, "opt": init_opt_state(params)}
    return jax.eval_shape(mk, jax.random.PRNGKey(0))


def lower_cell(arch: str, shape_name: str, mesh, mesh_name: str,
               verbose: bool = True) -> CellResult:
    import contextlib

    from repro.models.layers import activation_sharding
    from repro.models.moe import moe_sharding

    status = cell_status(arch, shape_name)
    res = CellResult(arch=arch, shape=shape_name, mesh=mesh_name,
                     status=status)
    if status != "run":
        return res
    cfg = get_config(arch)
    model = build_model(cfg)
    sh = SHAPES[shape_name]
    kind = sh["kind"]
    tp = mesh.shape["model"]
    dp = shd.dp_axes(mesh)
    # explicit activation constraints for every cell; archs whose head
    # count cannot shard over 'model' additionally run attention
    # sequence-parallel (see models/layers.py activation_sharding)
    needs_seq = (cfg.family != "ssm" and cfg.n_heads % tp != 0)
    ctx = activation_sharding(dp, seq_axis=("model" if needs_seq else None),
                              tp=tp)
    moe_tp = MOE_MODE.get(arch) == "tp"
    if cfg.n_experts:
        ep = ("model" if (cfg.n_experts % tp == 0 and not moe_tp) else None)
        ff = None if ep else ("model" if cfg.d_ff % tp == 0 else None)
        mctx = moe_sharding(dp, expert_axis=ep, ff_axis=ff)
    else:
        mctx = contextlib.nullcontext()
    t0 = time.time()
    with jax.set_mesh(mesh), ctx, mctx:
        return _lower_cell_inner(res, model, cfg, sh, kind, mesh, mesh_name,
                                 t0, verbose)


def _lower_cell_inner(res, model, cfg, sh, kind, mesh, mesh_name, t0,
                      verbose):
    arch, shape_name = res.arch, res.shape

    if kind == "train":
        opt_cfg = OptConfig()
        state_shape = _abstract_state(model, opt_cfg)
        sspec = shd.state_specs(model, state_shape, mesh,
                                moe_tp=MOE_MODE.get(res.arch) == "tp")
        batch = train_input_specs(cfg, sh["batch"], sh["seq"])
        bspec = shd.batch_specs(batch, mesh)
        # microbatching: the global batch is fixed by the assignment; big
        # models split it into serially-scanned microbatches (the standard
        # production memory lever — activations scale 1/grad_accum)
        step = make_train_step(model, opt_cfg,
                               grad_accum=GRAD_ACCUM.get(res.arch, 1))
        jf = jax.jit(step,
                     in_shardings=(_named(mesh, sspec), _named(mesh, bspec)),
                     out_shardings=(_named(mesh, sspec), None),
                     donate_argnums=(0,))
        lowered = jf.lower(state_shape, batch)
    elif kind == "prefill":
        params_shape = jax.eval_shape(
            lambda k: _cast_params(model, model.init(k)),
            jax.random.PRNGKey(0))
        pspec = shd.param_specs(model, params_shape, mesh,
                                moe_tp=MOE_MODE.get(res.arch) == "tp")
        batch = prefill_input_specs(cfg, sh["batch"], sh["seq"])
        bspec = shd.batch_specs(batch, mesh)

        def serve_prefill(params, batch):
            # serving prefill emits only the next-token logits: unembedding
            # the whole sequence all-reduces a (B, S, V) fp32 tensor when
            # the vocab can't shard (granite: 12 GiB/device at 32k —
            # §Perf iteration 3)
            hidden, _ = model._hidden(params, batch)
            from repro.models import layers as L
            logits = L.unembed(params["embed"],
                               hidden[:, -1:].astype(jnp.float32),
                               params.get("lm_head"))
            return logits
        jf = jax.jit(serve_prefill,
                     in_shardings=(_named(mesh, pspec), _named(mesh, bspec)),
                     out_shardings=None)
        lowered = jf.lower(params_shape, batch)
    else:  # decode
        params_shape = jax.eval_shape(
            lambda k: _cast_params(model, model.init(k)),
            jax.random.PRNGKey(0))
        pspec = shd.param_specs(model, params_shape, mesh,
                                moe_tp=MOE_MODE.get(res.arch) == "tp")
        specs = decode_input_specs(model, sh["batch"], sh["seq"])
        cspec = shd.cache_specs(model, specs["caches"], mesh)
        tspec = shd.batch_specs({"tokens": specs["tokens"],
                                 "pos": specs["pos"]}, mesh)

        def serve_step(params, caches, tokens, pos):
            return model.decode_step(params, caches, tokens, pos)
        jf = jax.jit(serve_step,
                     in_shardings=(_named(mesh, pspec), _named(mesh, cspec),
                                   _named(mesh, tspec["tokens"]),
                                   _named(mesh, tspec["pos"])),
                     out_shardings=(None, _named(mesh, cspec)),
                     donate_argnums=(1,))
        lowered = jf.lower(params_shape, specs["caches"], specs["tokens"],
                           specs["pos"])

    compiled = lowered.compile()
    res._compiled = compiled  # transient handle for tools/debug_memory.py
    res.compile_s = time.time() - t0
    ma = compiled.memory_analysis()
    if ma is not None:
        res.arg_bytes = int(ma.argument_size_in_bytes)
        res.out_bytes = int(ma.output_size_in_bytes)
        res.temp_bytes = int(ma.temp_size_in_bytes)
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):  # pre-0.5 jax: one dict per program
        ca = ca[0] if ca else {}
    res.flops_per_dev = float(ca.get("flops", 0.0))
    res.bytes_per_dev = float(ca.get("bytes accessed", 0.0))
    res.coll_bytes = collective_bytes(compiled.as_text())
    res.roofline = roofline_terms(res.flops_per_dev, res.bytes_per_dev,
                                  res.coll_bytes)
    if verbose:
        hbm = (res.arg_bytes + res.temp_bytes + res.out_bytes) / (1 << 30)
        print(f"[{mesh_name}] {arch} x {shape_name}: compile {res.compile_s:.1f}s "
              f"flops/dev={res.flops_per_dev:.3e} bytes/dev={res.bytes_per_dev:.3e} "
              f"coll={sum(res.coll_bytes.values()):.3e}B hbm={hbm:.2f}GiB "
              f"dom={res.roofline['dominant']}")
        print(f"    memory_analysis: {ma}")
        print(f"    cost_analysis: flops={ca.get('flops')} "
              f"bytes={ca.get('bytes accessed')}")
    return res


def _cast_params(model, params):
    return jax.tree.map(
        lambda p: p.astype(model.cfg.compute_dtype)
        if p.dtype == jnp.float32 and p.ndim >= 2 else p, params)


def _named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree, is_leaf=lambda x: isinstance(x, P))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod",
                                                      "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from repro.configs import ALL_ARCHS
    archs = ALL_ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = (["pod", "multipod"] if args.mesh == "both" else [args.mesh])

    results = []
    for mesh_name in meshes:
        mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
        for arch in archs:
            for shape in shapes:
                try:
                    r = lower_cell(arch, shape, mesh, mesh_name)
                except Exception as e:  # a failing cell is a bug: surface it
                    r = CellResult(arch=arch, shape=shape, mesh=mesh_name,
                                   status=f"FAIL: {type(e).__name__}: {e}")
                    print(f"[{mesh_name}] {arch} x {shape}: {r.status}")
                results.append(r)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump([r.to_json() for r in results], f, indent=1)
        print(f"wrote {args.out}")
    n_fail = sum(1 for r in results if r.status.startswith("FAIL"))
    print(f"cells: {len(results)}  run: "
          f"{sum(1 for r in results if r.status == 'run')}  "
          f"skip: {sum(1 for r in results if r.status.startswith('SKIP'))}  "
          f"fail: {n_fail}")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
