"""Whether the timed path's outputs are right.

A seeded reservoir keeps a uniform sample of the outputs served in the
window. Once the window has closed and the engine is gone, the plain
reference recomputes each sampled output from the frames the traffic
sent (a temporal output from its stream's last ``HISTORY + 1`` frames,
zeros before the stream's start) and the largest gap is read as a
multiple of the float32 spacing at the reference's largest magnitude.
"""
from __future__ import annotations

import numpy as np

from bench.loadgen import rng_for


class Reservoir:
    """A uniform sample of up to ``size`` served outputs (seeded)."""

    def __init__(self, size: int, seed: int):
        self.size = size
        self.items: list[tuple[int, int, object]] = []
        self.seen = 0
        self._rng = rng_for(seed, 3)

    def offer(self, stream: int, index: int, output) -> None:
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append((stream, index, output))
            return
        j = int(self._rng.integers(0, self.seen))
        if j < self.size:
            self.items[j] = (stream, index, output)


def history(pool: np.ndarray, traffic, stream: int, index: int,
            depth: int) -> np.ndarray:
    """(depth + 1, H, W) input frames of ``stream`` ending at ``index``,
    oldest first, zeros before the stream's first frame."""
    h, w = pool.shape[1:]
    out = np.zeros((depth + 1, h, w), np.float32)
    for j in range(depth + 1):
        k = index - depth + j
        if k >= 0:
            out[j] = pool[traffic.content(stream, k)]
    return out


def scale_ulp(err: float, scale: float) -> float:
    """``err`` in float32 spacings at ``scale``; 0 when equal."""
    if err == 0.0:
        return 0.0
    if not np.isfinite(err):
        return float(np.finfo(np.float64).max)
    return float(err / np.spacing(np.float32(scale)))


def gaps(reference, pool, traffic, items, block: int = 8,
         dtype=None) -> list[float]:
    """Scale-ULP gap of each sampled output from the reference. With
    ``dtype`` set, the sample is the reference itself computed in that
    dtype (the lower-precision control) instead of the served output."""
    import jax
    import jax.numpy as jnp

    ref = jax.jit(jax.vmap(reference.output))
    # the control runs op by op: inside one jitted program XLA may keep
    # float32 between ops (excess precision) and drop the bfloat16
    # rounding altogether, which on the TPU left it equal to float32
    low = (jax.vmap(lambda f: reference.output(f, dtype))
           if dtype is not None else None)

    @jax.jit
    def gap(got, exp):
        diff = jnp.where(jnp.isfinite(got), jnp.abs(got - exp), jnp.inf)
        return (jnp.max(diff, axis=(1, 2)),
                jnp.max(jnp.abs(exp), axis=(1, 2)))

    out = []
    for i in range(0, len(items), block):
        part = items[i:i + block]
        ins = jnp.asarray(np.stack([
            history(pool, traffic, s, k, reference.HISTORY)
            for s, k, _ in part]))
        exp = ref(ins)
        got = low(ins) if low is not None else \
            jnp.stack([jnp.asarray(o) for _, _, o in part])
        err, scale = jax.device_get(gap(got, exp))
        out += [scale_ulp(float(e), float(m)) for e, m in zip(err, scale)]
    return out
