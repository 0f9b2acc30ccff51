"""Plain reference of sift-dog: the first octave of SIFT's
difference-of-Gaussians keypoint detector (D. G. Lowe, IJCV 60(2), 2004,
Sec. 3-4) at OpenCV's cv::SIFT::create defaults (nOctaveLayers=3,
contrastThreshold=0.04, edgeThreshold=10, sigma=1.6, input blur 0.5),
written from its stage list.

in -> G0: a 1xn then nx1 Gaussian at sigma0 = sqrt(1.6^2 - 0.5^2) ->
G1..G5: the same on the level before at sigma_i = sqrt((1.6 k^i)^2 -
(1.6 k^(i-1))^2), k = 2^(1/3). Taps as OpenCV's getGaussianKernel gives
them for float images: n = round(8 sigma + 1) | 1, exp(-x^2 / 2
sigma^2) normalised to sum 1 (13, 11, 13, 17, 21, 27 taps). DoG planes
D_i = G_(i+1) - G_i for i = 0..4. For layers l = 1..3, a pixel of D_l
is a keypoint when it is a 26-neighbour extremum over D_(l-1), D_l,
D_(l+1) (c > 0 and c >= all, or c < 0 and c <= all), |c| > 0.5 * 0.04 /
3, 3 |c| >= 0.04, and on D_l's Hessian det > 0 and tr^2 * 10 < 11^2 *
det (dxy with the factor 0.25); its value is |c|, else 0. The output is
the max over the three layers.

Windows are causal: the (sh, sw) window of output pixel (r, x) covers
rows r-sh+1..r and columns x-sw+1..x, zero outside the frame. A blur
of n = 2h + 1 taps so leaves its level h rows and columns behind the
input; planes are compared at one source pixel by reading the earlier
ones that far back (element [0, 0] of an (e + 1) x (e + 1) window, the
top-left 3x3 of a (3 + e) x (3 + e) one). The output at (r, x) is the
centred detector at (r - 49, x - 49). Sums run in window order, one
term at a time, each weight a float32 constant. Nothing here comes from
the program under test.
"""
import math
from functools import reduce

import numpy as np
import jax.numpy as jnp

HISTORY = 0          # frames before the current one that an output reads

SIGMA, INIT_SIGMA, LAYERS = 1.6, 0.5, 3
CONTRAST, EDGE = 0.04, 10.0


def _gauss(sigma: float) -> np.ndarray:
    n = round(8 * sigma + 1) | 1
    x = np.arange(n) - (n - 1) / 2
    g = np.exp(-0.5 / (sigma * sigma) * x * x)   # as getGaussianKernel
    return (g / g.sum()).astype(np.float32)


def _sigmas() -> list:
    k = 2.0 ** (1.0 / LAYERS)
    out = [math.sqrt(SIGMA ** 2 - INIT_SIGMA ** 2)]
    for i in range(1, LAYERS + 3):
        prev = SIGMA * k ** (i - 1)
        out.append(math.sqrt((prev * k) ** 2 - prev ** 2))
    return out


KERNELS = [_gauss(s) for s in _sigmas()]
HALF = [(len(g) - 1) // 2 for g in KERNELS]


def _tap(img, dy: int, dx: int, sh: int, sw: int):
    """Element (dy, dx) of every pixel's causal (sh, sw) window."""
    h, w = img.shape[-2:]
    pad = jnp.pad(img, ((sh - 1, 0), (sw - 1, 0)))
    return pad[dy:dy + h, dx:dx + w]


def _blur(img, g: np.ndarray):
    n = len(g)
    acc = None
    for dx in range(n):
        term = float(g[dx]) * _tap(img, 0, dx, 1, n)
        acc = term if acc is None else acc + term
    out = None
    for dy in range(n):
        term = float(g[dy]) * _tap(acc, dy, 0, n, 1)
        out = term if out is None else out + term
    return out


def _keypoints(below, centre, above, a: int, b: int):
    """|c| at the keypoints of ``centre``; ``below`` and ``centre`` are
    read through (a, a) and (b, b) windows, ``above`` through 3x3."""
    planes = [(below, a), (centre, b), (above, 3)]
    nb = [[[_tap(p, dy, dx, s, s) for dx in range(3)] for dy in range(3)]
          for p, s in planes]
    m = nb[1]
    c = m[1][1]
    vals = [v for plane in nb for row in plane for v in row]
    mx = reduce(jnp.maximum, vals)
    mn = reduce(jnp.minimum, vals)
    extremum = ((c > 0) & (c >= mx)) | ((c < 0) & (c <= mn))
    mag = jnp.abs(c)
    dxx = m[1][2] + m[1][0] - 2.0 * c
    dyy = m[2][1] + m[0][1] - 2.0 * c
    dxy = (m[2][2] - m[2][0] - m[0][2] + m[0][0]) * 0.25
    tr = dxx + dyy
    det = dxx * dyy - dxy * dxy
    keep = (extremum & (mag > 0.5 * CONTRAST / LAYERS)
            & (LAYERS * mag >= CONTRAST) & (det > 0)
            & (tr * tr * EDGE < (EDGE + 1) ** 2 * det))
    return jnp.where(keep, mag, 0.0)


def output(frames, dtype=jnp.float32):
    """frames: (HISTORY + 1, H, W), oldest first -> the (H, W) float32
    output for the last frame, computed in ``dtype``."""
    g = [frames[-1].astype(dtype)]
    for k in KERNELS:
        g.append(_blur(g[-1], k))
    g = g[1:]
    # D_i lines G_i up with G_(i+1), HALF[i + 1] ahead of it
    d = [g[i + 1] - _tap(g[i], 0, 0, HALF[i + 1] + 1, HALF[i + 1] + 1)
         for i in range(len(g) - 1)]
    layers = [_keypoints(d[l - 1], d[l], d[l + 1],
                         3 + HALF[l + 1] + HALF[l + 2], 3 + HALF[l + 2])
              for l in range(1, LAYERS + 1)]
    # layer l lines up with D_(l+1), sum(HALF[l + 3:]) behind the last
    lagged = [_tap(x, 0, 0, sum(HALF[l + 3:]) + 1, sum(HALF[l + 3:]) + 1)
              for l, x in zip(range(1, LAYERS + 1), layers)]
    return reduce(jnp.maximum, lagged).astype(jnp.float32)
