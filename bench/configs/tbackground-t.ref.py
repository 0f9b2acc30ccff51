"""Plain reference of tbackground-t: background subtraction against a
running mean of the last 8 frames, as a fixed camera's change detection
does (CDnet 2014's task; ImaGen, arXiv:2304.03352, at 1080p).

bg = (f[t-7] + f[t-6] + ... + f[t]) * (1/8), summed oldest first, frames
before the stream's first reading as zero; d = |f[t] - bg|; the output
keeps d where d > 0.25 and is 0 elsewhere. Nothing here comes from the
program under test.
"""
import jax.numpy as jnp

HISTORY = 7          # frames before the current one that an output reads


def output(frames, dtype=jnp.float32):
    """frames: (HISTORY + 1, H, W), oldest first -> the (H, W) float32
    output for the last frame, computed in ``dtype``."""
    f = frames.astype(dtype)
    acc = f[0]
    for t in range(1, HISTORY + 1):
        acc = acc + f[t]
    bg = acc * (1.0 / (HISTORY + 1))
    d = jnp.abs(f[-1] - bg)
    return jnp.where(d > 0.25, d, 0.0).astype(jnp.float32)
