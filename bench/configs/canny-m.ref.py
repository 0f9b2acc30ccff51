"""Plain reference of canny-m (ImaGen, arXiv:2304.03352, Tbl. 3: Canny,
10 stages, 1 multi-consumer stage), written from its stage list.

in -> bx: 1x5 Gaussian -> by: 5x1 Gaussian -> gx: 1x3 [-1, 0, 1] and
gy: 3x1 [-1, 0, 1] (both read by) -> mag: sqrt(gx^2 + gy^2 + 1e-6) ->
nms: 3x3 non-maximum suppression -> hyst: the same again ->
th: values above 0.1 kept, the rest 0 -> out.

Windows are causal: the (sh, sw) window of output pixel (r, x) covers
rows r-sh+1..r and columns x-sw+1..x, zero outside the frame. Sums run
in window order, one term at a time, each weight a float32 constant.
Nothing here comes from the program under test.
"""
import numpy as np
import jax.numpy as jnp

HISTORY = 0          # frames before the current one that an output reads


def _gauss(n: int) -> np.ndarray:
    x = np.arange(n) - (n - 1) / 2
    g = np.exp(-0.5 * (x / max(n / 4.0, 1.0)) ** 2)
    return (g / g.sum()).astype(np.float32)


G5 = _gauss(5)
DERIV = np.array([-1.0, 0.0, 1.0], np.float32)


def _tap(img, dy: int, dx: int, sh: int, sw: int):
    """Element (dy, dx) of every pixel's causal (sh, sw) window."""
    h, w = img.shape[-2:]
    pad = jnp.pad(img, ((sh - 1, 0), (sw - 1, 0)))
    return pad[dy:dy + h, dx:dx + w]


def _conv(img, weights: np.ndarray):
    sh, sw = weights.shape
    acc = None
    for dy in range(sh):
        for dx in range(sw):
            term = float(weights[dy, dx]) * _tap(img, dy, dx, sh, sw)
            acc = term if acc is None else acc + term
    return acc


def _nms(img):
    taps = [_tap(img, dy, dx, 3, 3) for dy in range(3) for dx in range(3)]
    center = taps[4]
    mx = taps[0]
    for t in taps[1:]:
        mx = jnp.maximum(mx, t)
    return jnp.where(center >= mx, center, 0.0)


def output(frames, dtype=jnp.float32):
    """frames: (HISTORY + 1, H, W), oldest first -> the (H, W) float32
    output for the last frame, computed in ``dtype``."""
    x = frames[-1].astype(dtype)
    bx = _conv(x, G5[None, :])
    by = _conv(bx, G5[:, None])
    gx = _conv(by, DERIV[None, :])
    gy = _conv(by, DERIV[:, None])
    mag = jnp.sqrt(gx * gx + gy * gy + 1e-6)
    th = _nms(_nms(mag))
    return jnp.where(th > 0.1, th, 0.0).astype(jnp.float32)
