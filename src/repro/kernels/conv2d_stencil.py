"""Single-stage 2D stencil (conv) kernel — the building-block version.

Output rows are tiled across the grid ((TR, W_pad) blocks); the input stays
VMEM-resident across steps (same-block index map) so each output tile reads
its halo without HBM round trips. The fused multi-stage version (the
paper's actual design) is stencil_pipeline.py; this kernel exists as the
minimal, separately-testable stencil primitive and as the patch-embed /
conv-frontend building block for the model zoo's stubs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .stencil_pipeline import default_interpret


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _kernel(img_ref, w_ref, o_ref, *, kh: int, kw: int, tr: int, w: int):
    tile = pl.program_id(0)
    r0 = tile * tr
    acc = jnp.zeros((tr, w), jnp.float32)
    for dy in range(kh):
        # output row r reads input rows r-kh+1 .. r (causal alignment)
        rows = []
        for t in range(tr):
            r = r0 + t - (kh - 1) + dy
            row = img_ref[pl.ds(jnp.maximum(r, 0), 1), pl.ds(0, w)]
            rows.append(jnp.where(r >= 0, row[0], 0.0))
        block = jnp.stack(rows)                       # (TR, W)
        padded = jnp.pad(block, ((0, 0), (kw - 1, 0)))
        for dx in range(kw):
            acc = acc + w_ref[dy, dx] * padded[:, dx:dx + w]
    o_ref[:, :w] = acc


@functools.partial(jax.jit, static_argnames=("interpret", "tile_rows"))
def conv2d(img: jnp.ndarray, weights: jnp.ndarray,
           tile_rows: int = 8, interpret: bool | None = None) -> jnp.ndarray:
    """Causal (bottom-right aligned) conv with zero padding, fp32.
    ``interpret=None`` runs the interpreter only off the TPU."""
    h, w = img.shape
    kh, kw = weights.shape
    w_pad = _round_up(w, 128)
    h_pad = _round_up(h, tile_rows)
    img_p = jnp.pad(img.astype(jnp.float32),
                    ((0, h_pad - h), (0, w_pad - w)))
    out = pl.pallas_call(
        functools.partial(_kernel, kh=kh, kw=kw, tr=tile_rows, w=w),
        grid=(h_pad // tile_rows,),
        in_specs=[
            pl.BlockSpec((h_pad, w_pad), lambda i: (0, 0)),  # resident
            pl.BlockSpec((kh, kw), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_rows, w_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((h_pad, w_pad), jnp.float32),
        interpret=default_interpret() if interpret is None else interpret,
    )(img_p, weights.astype(jnp.float32))
    return out[:h, :w]
