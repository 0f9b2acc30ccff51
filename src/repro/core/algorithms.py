"""Evaluation pipelines (paper Tbl. 3) + pure-jnp reference executor.

Stage/MC counts match Tbl. 3 exactly (stage counts include the input and
output stages, per the Darkroom-style DSL). The arithmetic payloads are
representative stencil math (separable Gaussian, Sobel, Laplacian, NMS,
unsharp, 18x1 cross-correlation) so functional tests are meaningful.
``sift-dog``, outside Tbl. 3, is one octave of SIFT's DoG detector as
OpenCV computes it, departures listed in its docstring.

Window convention (matches the scheduling model / simulator): the window
for output pixel (r, x) covers rows r-sh+1..r and cols x-sw+1..x of each
producer, with zero padding — i.e. bottom-right (causal) alignment.
"""
from __future__ import annotations

import math
from functools import partial, reduce

import jax.numpy as jnp
import numpy as np

from .dag import PipelineDAG, window_keys
from .dsl import Pipeline


# ------------------------------------------------------------- window fns
# A stage function receives {producer key: window}; a window is indexed
# ``win[..., dy, dx]`` (temporal: ``win[..., dt, dy, dx]``) with
# non-negative static ints and read for its ``.shape``, nothing else: the
# reference passes real arrays, the fused kernel a lazy view of its row
# slabs (kernels.stencil_pipeline._WindowView) that obeys the same rules,
# and PipelineDAG.taps a counter of the indices read (dag.window_index
# checks a key against the rules).
def _single(wins):
    (v,) = wins.values()
    return v


def conv_fn(weights: np.ndarray):
    # unroll with python-float taps so Pallas kernel tracing inlines them
    # as scalar literals instead of captured device constants
    w = np.asarray(weights, dtype=np.float32)

    def fn(wins):
        win = _single(wins)
        acc = None
        for dy in range(w.shape[0]):
            for dx in range(w.shape[1]):
                term = float(w[dy, dx]) * win[..., dy, dx]
                acc = term if acc is None else acc + term
        return acc
    return fn


def square_fn(wins):
    return _single(wins)[..., 0, 0] ** 2


def identity_fn(wins):
    return _single(wins)[..., 0, 0]


def mag_fn(wins):
    a, b = (wins[k][..., 0, 0] for k in sorted(wins))
    return jnp.sqrt(a * a + b * b + 1e-6)


def prod_fn(wins):
    a, b = (wins[k][..., 0, 0] for k in sorted(wins))
    return a * b


def nms_fn(wins):
    win = _single(wins)
    sh, sw = win.shape[-2:]
    center = win[..., sh - 2, sw - 2] if sw >= 2 else win[..., sh - 1, sw - 1]
    mx = reduce(jnp.maximum, (win[..., dy, dx] for dy in range(sh)
                              for dx in range(sw)))
    return jnp.where(center >= mx, center, 0.0)


def thresh_fn(wins, lo=0.1):
    v = _single(wins)[..., 0, 0]
    return jnp.where(v > lo, v, 0.0)


def gauss1d(n: int) -> np.ndarray:
    x = np.arange(n) - (n - 1) / 2
    g = np.exp(-0.5 * (x / max(n / 4.0, 1.0)) ** 2)
    return (g / g.sum()).astype(np.float32)


SOBEL_X = np.array([[-1.0, 0.0, 1.0]], dtype=np.float32)          # 1x3
SOBEL_Y = SOBEL_X.T                                               # 3x1
LAPLACE = np.array([[0, 1, 0], [1, -4, 1], [0, 1, 0]], np.float32)
G5H = gauss1d(5)[None, :]
G5V = gauss1d(5)[:, None]
G3 = np.outer(gauss1d(3), gauss1d(3)).astype(np.float32)
XCORR_T = gauss1d(18)[:, None]                                    # 18x1


def unsharp_fn(wins):
    orig = wins["in"][..., 0, 0]
    blur = [v for k, v in wins.items() if k != "in"][0][..., 0, 0]
    return orig + 1.5 * (orig - blur)


def xcorr_fn(wins):
    tall = [v for v in wins.values() if v.shape[-2] == 18][0]
    center = [v for v in wins.values() if v.shape[-2] == 1][0][..., 0, 0]
    corr = None
    for dy in range(18):  # scalar taps (Pallas-friendly, see conv_fn)
        term = float(XCORR_T[dy, 0]) * tall[..., dy, 0]
        corr = term if corr is None else corr + term
    return corr - center


def denoise_comb_fn(wins):
    orig = wins["in"][..., 0, 0]
    blur = wins["b"][..., 0, 0]
    lap = wins["lap"][..., 0, 0]
    edge_w = jnp.clip(jnp.abs(lap), 0.0, 1.0)
    return edge_w * orig + (1.0 - edge_w) * blur


def harris_resp_fn(wins):
    v = _single(wins)[..., 0, 0]
    return v - 0.04 * v * v


# ------------------------------------------------------------- pipelines
def canny_s() -> PipelineDAG:
    """9 stages, 0 MC — linear chain."""
    p = Pipeline("canny-s")
    x = p.input("in")
    bx = p.stage("bx", [(x, 1, 5)], conv_fn(G5H))
    by = p.stage("by", [(bx, 5, 1)], conv_fn(G5V))
    gx = p.stage("gx", [(by, 1, 3)], conv_fn(SOBEL_X))
    gy = p.stage("gy", [(gx, 3, 1)], conv_fn(SOBEL_Y))
    sq = p.stage("sq", [(gy, 1, 1)], square_fn)
    nms = p.stage("nms", [(sq, 3, 3)], nms_fn)
    th = p.stage("th", [(nms, 1, 1)], thresh_fn)
    p.output("out", [(th, 1, 1)])
    return p.build()


def canny_m() -> PipelineDAG:
    """10 stages, 1 MC — blurred image feeds both gradient directions."""
    p = Pipeline("canny-m")
    x = p.input("in")
    bx = p.stage("bx", [(x, 1, 5)], conv_fn(G5H))
    by = p.stage("by", [(bx, 5, 1)], conv_fn(G5V))       # MC stage
    gx = p.stage("gx", [(by, 1, 3)], conv_fn(SOBEL_X))
    gy = p.stage("gy", [(by, 3, 1)], conv_fn(SOBEL_Y))
    mag = p.stage("mag", [(gx, 1, 1), (gy, 1, 1)], mag_fn)
    nms = p.stage("nms", [(mag, 3, 3)], nms_fn)
    hyst = p.stage("hyst", [(nms, 3, 3)], nms_fn)
    th = p.stage("th", [(hyst, 1, 1)], thresh_fn)
    p.output("out", [(th, 1, 1)])
    return p.build()


def harris_s() -> PipelineDAG:
    """7 stages, 0 MC."""
    p = Pipeline("harris-s")
    x = p.input("in")
    g = p.stage("g", [(x, 1, 3)], conv_fn(SOBEL_X))
    g2 = p.stage("g2", [(g, 1, 1)], square_fn)
    s = p.stage("s", [(g2, 3, 3)], conv_fn(G3))
    r = p.stage("r", [(s, 1, 1)], harris_resp_fn)
    nms = p.stage("nms", [(r, 3, 3)], nms_fn)
    p.output("out", [(nms, 1, 1)])
    return p.build()


def harris_m() -> PipelineDAG:
    """7 stages, 1 MC — the input feeds both gradient directions."""
    p = Pipeline("harris-m")
    x = p.input("in")                                    # MC stage
    gx = p.stage("gx", [(x, 1, 3)], conv_fn(SOBEL_X))
    gy = p.stage("gy", [(x, 3, 1)], conv_fn(SOBEL_Y))
    ixy = p.stage("ixy", [(gx, 1, 1), (gy, 1, 1)], prod_fn)
    s = p.stage("s", [(ixy, 3, 3)], conv_fn(G3))
    r = p.stage("r", [(s, 1, 1)], harris_resp_fn)
    p.output("out", [(r, 1, 1)])
    return p.build()


def unsharp_m() -> PipelineDAG:
    """5 stages, 1 MC — classic unsharp mask (paper Sec. 1, 3.1)."""
    p = Pipeline("unsharp-m")
    x = p.input("in")                                    # MC stage
    bx = p.stage("bx", [(x, 1, 5)], conv_fn(G5H))
    by = p.stage("by", [(bx, 5, 1)], conv_fn(G5V))
    sh = p.stage("sharp", [(x, 1, 1), (by, 1, 1)], unsharp_fn)
    p.output("out", [(sh, 1, 1)])
    return p.build()


def xcorr_m() -> PipelineDAG:
    """3 stages, 1 MC — 18x1 template correlation (paper Sec. 8.3)."""
    p = Pipeline("xcorr-m")
    x = p.input("in")                                    # MC stage
    xc = p.stage("xc", [(x, 18, 1), (x, 1, 1)], xcorr_fn)
    p.output("out", [(xc, 1, 1)])
    return p.build()


def denoise_m() -> PipelineDAG:
    """5 stages, 2 MC — edge-aware blend."""
    p = Pipeline("denoise-m")
    x = p.input("in")                                    # MC stage 1
    b = p.stage("b", [(x, 3, 3)], conv_fn(G3))           # MC stage 2
    lap = p.stage("lap", [(b, 3, 3)], conv_fn(LAPLACE))
    comb = p.stage("comb", [(x, 1, 1), (b, 1, 1), (lap, 1, 1)],
                   denoise_comb_fn)
    p.output("out", [(comb, 1, 1)])
    return p.build()


# ----------------------------------------------------- SIFT scale space
# One octave of SIFT's difference-of-Gaussians detector (D. G. Lowe,
# IJCV 60(2), 2004, Sec. 3-4) at OpenCV's cv::SIFT::create defaults.
SIFT_SIGMA = 1.6            # sigma of the first level
SIFT_INIT_SIGMA = 0.5       # blur the input is assumed to carry
SIFT_LAYERS = 3             # nOctaveLayers: the DoG layers tested
SIFT_CONTRAST = 0.04        # contrastThreshold
SIFT_EDGE = 10.0            # edgeThreshold


def gauss_taps(sigma: float) -> np.ndarray:
    """OpenCV's ``getGaussianKernel`` for float images: ``round(8 sigma
    + 1) | 1`` taps of ``exp(-x^2 / 2 sigma^2)``, ``x`` centred, summed
    to 1 in float64 and stored as float32."""
    n = round(8 * sigma + 1) | 1
    x = np.arange(n) - (n - 1) / 2
    g = np.exp(-0.5 / (sigma * sigma) * x * x)
    return (g / g.sum()).astype(np.float32)


def sift_sigmas() -> list[float]:
    """The incremental blur of each of the ``SIFT_LAYERS + 3`` Gaussian
    levels of an octave, as OpenCV's ``buildGaussianPyramid`` gives
    them: level 0 takes the input from ``SIFT_INIT_SIGMA`` to
    ``SIFT_SIGMA``, level i from ``SIFT_SIGMA k^(i-1)`` to ``SIFT_SIGMA
    k^i`` with ``k = 2^(1/SIFT_LAYERS)``."""
    k = 2.0 ** (1.0 / SIFT_LAYERS)
    sig = [math.sqrt(SIFT_SIGMA ** 2 - SIFT_INIT_SIGMA ** 2)]
    for i in range(1, SIFT_LAYERS + 3):
        prev = SIFT_SIGMA * k ** (i - 1)
        sig.append(math.sqrt((prev * k) ** 2 - prev ** 2))
    return sig


def dog_fn(hi: str, lo: str):
    """``hi - lo``: the next Gaussian level less the (aligned) last."""
    def fn(wins):
        return wins[hi][..., 0, 0] - wins[lo][..., 0, 0]
    return fn


def extremum_fn(below: str, centre: str, above: str):
    """SIFT's keypoint test on three DoG planes, read through windows
    whose top-left 3x3 lines up: ``|c|`` where the centre sample ``c`` of
    ``centre`` is a keypoint, else 0. A keypoint is a 26-neighbour
    extremum (``c > 0`` and ``c`` >= all, or ``c < 0`` and <= all) above
    OpenCV's pre-threshold ``0.5 contrastThreshold / nOctaveLayers`` and
    its contrast test ``nOctaveLayers |c| >= contrastThreshold`` (taken
    on the sample: no sub-pixel fit), whose Hessian on the centre plane
    passes the edge test ``det > 0``, ``tr^2 r < (r + 1)^2 det``."""
    pre = 0.5 * SIFT_CONTRAST / SIFT_LAYERS
    r = SIFT_EDGE

    def fn(wins):
        planes = [wins[below], wins[centre], wins[above]]
        mid = planes[1]
        c = mid[..., 1, 1]
        vals = [p[..., dy, dx] for p in planes
                for dy in range(3) for dx in range(3)]
        mx = reduce(jnp.maximum, vals)
        mn = reduce(jnp.minimum, vals)
        extremum = ((c > 0) & (c >= mx)) | ((c < 0) & (c <= mn))
        a = jnp.abs(c)
        dxx = mid[..., 1, 2] + mid[..., 1, 0] - 2.0 * c
        dyy = mid[..., 2, 1] + mid[..., 0, 1] - 2.0 * c
        dxy = (mid[..., 2, 2] - mid[..., 2, 0] - mid[..., 0, 2]
               + mid[..., 0, 0]) * 0.25
        tr = dxx + dyy
        det = dxx * dyy - dxy * dxy
        keep = (extremum & (a > pre) & (SIFT_LAYERS * a >= SIFT_CONTRAST)
                & (det > 0) & (tr * tr * r < (r + 1) ** 2 * det))
        return jnp.where(keep, a, 0.0)
    return fn


def max_fn(wins):
    return reduce(jnp.maximum, (v[..., 0, 0] for v in wins.values()))


def sift_dog() -> PipelineDAG:
    """23 stages, 8 MC — the first octave of SIFT's DoG detector.

    Six Gaussian levels (separable ``gauss_taps`` blurs of 13, 11, 13,
    17, 21, 27 taps, each on the level before), five DoG planes, the
    keypoint test on the three middle ones, and their max as the one
    output: a dense map holding ``|DoG|`` at each keypoint, 0 elsewhere.

    Windows are causal, so a blur of ``n = 2h + 1`` taps delays its
    level by ``h`` rows and columns. Reads that must meet at one source
    pixel are delay windows: a producer ``e`` pixels behind is read
    through an ``(e + 1) x (e + 1)`` window at element [0, 0] (or, for a
    3x3 neighbourhood, at its top-left 3x3).

    Departures from OpenCV's octave (also in the benchmark
    configuration's ``assumed``): every stage reads zeros above and
    left of the frame, not reflect-101 borders; output (r, x) is the
    centred detector at (r - 49, x - 49); no 5-pixel border skip,
    sub-pixel refinement, orientation or descriptor; the dense response
    map is the output; thresholds apply to normalised [0, 1] frames,
    without OpenCV's floor to whole grey levels; the octave is built from
    the input itself (``firstOctave = 0``, no doubled image).
    """
    p = Pipeline("sift-dog")
    taps = [gauss_taps(s) for s in sift_sigmas()]
    half = [(len(t) - 1) // 2 for t in taps]
    levels = [p.input("in")]
    for i, t in enumerate(taps):
        n = len(t)
        gh = p.stage(f"g{i}h", [(levels[-1], 1, n)], conv_fn(t[None, :]))
        levels.append(p.stage(f"g{i}v", [(gh, n, 1)], conv_fn(t[:, None])))
    levels = levels[1:]
    dogs = []
    for i in range(len(levels) - 1):   # level i is half[i + 1] behind i + 1
        e = half[i + 1] + 1
        hi, lo = levels[i + 1], levels[i]
        dogs.append(p.stage(f"d{i}", [(hi, 1, 1), (lo, e, e)],
                            dog_fn(hi.name, lo.name)))
    layers, lags = [], []
    for k in range(1, len(dogs) - 1):
        # plane k + 1 is half[k + 2] ahead of plane k, which is
        # half[k + 1] ahead of plane k - 1: both are read that far back
        below, centre, above = dogs[k - 1], dogs[k], dogs[k + 1]
        a, b = 3 + half[k + 1] + half[k + 2], 3 + half[k + 2]
        layers.append(p.stage(
            f"x{k}", [(below, a, a), (centre, b, b), (above, 3, 3)],
            extremum_fn(below.name, centre.name, above.name)))
        # layer k lines up with plane k + 1, this far behind the last
        lags.append(sum(half[k + 3:]))
    comb = p.stage("comb", [(x, e + 1, e + 1) for x, e in zip(layers, lags)],
                   max_fn)
    p.output("out", [(comb, 1, 1)])
    return p.build()


ALGORITHMS = {
    "canny-s": canny_s, "canny-m": canny_m,
    "harris-s": harris_s, "harris-m": harris_m,
    "unsharp-m": unsharp_m, "xcorr-m": xcorr_m, "denoise-m": denoise_m,
    "sift-dog": sift_dog,
}
# the paper's Tbl. 3 pipelines: what its tables and figures reproduce
PAPER_ALGORITHMS = ("canny-s", "canny-m", "harris-s", "harris-m",
                    "unsharp-m", "xcorr-m", "denoise-m")


# ---------------------------------------------------- temporal window fns
# Temporal windows arrive as [..., st, sh, sw] (axis -3 is time, causal:
# index st-1 is the current frame, index 0 the oldest; frames before the
# stream start read as zero, exactly like the spatial zero padding).
# Reductions are unrolled with python loops and scalar taps — the same
# discipline as conv_fn — so the reference executor and the Pallas kernel
# trace identical accumulation orders and can be compared bitwise.
def stmean_fn(st: int, sh: int = 1, sw: int = 1):
    """Mean over an (st, sh, sw) spatio-temporal box."""
    k = 1.0 / float(st * sh * sw)

    def fn(wins):
        win = _single(wins)
        acc = None
        for dt in range(st):
            for dy in range(sh):
                for dx in range(sw):
                    term = win[..., dt, dy, dx]
                    acc = term if acc is None else acc + term
        return acc * k
    return fn


def frame_diff_fn(wins):
    """|current - previous| of a (2, 1, 1) temporal window."""
    win = _single(wins)
    return jnp.abs(win[..., 1, 0, 0] - win[..., 0, 0, 0])


def bg_subtract_fn(wins, lo=0.25):
    """Foreground mask: |current - background| thresholded."""
    cur = wins["in"][..., 0, 0]
    bg = [v for k, v in wins.items() if k != "in"][0][..., 0, 0]
    d = jnp.abs(cur - bg)
    return jnp.where(d > lo, d, 0.0)


def tunsharp_fn(wins):
    """Unsharp along time: boost what moved vs. the temporal average."""
    cur = wins["in"][..., 0, 0]
    avg = [v for k, v in wins.items() if k != "in"][0][..., 0, 0]
    return cur + 1.5 * (cur - avg)


# ------------------------------------------------------- video pipelines
def tdenoise_t() -> PipelineDAG:
    """Temporal-average denoise: mean of the last 4 frames, then a 3x3
    spatial blur — a spatial stage downstream of a temporal one."""
    p = Pipeline("tdenoise-t")
    x = p.input("in")
    ta = p.stage("tavg", [(x, 4, 1, 1)], stmean_fn(4))
    b = p.stage("blur", [(ta, 3, 3)], conv_fn(G3))
    p.output("out", [(b, 1, 1)])
    return p.build()


def tmotion_t() -> PipelineDAG:
    """Frame-difference motion mask: |in_t - in_{t-1}|, spatially
    smoothed, thresholded."""
    p = Pipeline("tmotion-t")
    x = p.input("in")
    d = p.stage("diff", [(x, 2, 1, 1)], frame_diff_fn)
    b = p.stage("blur", [(d, 3, 3)], conv_fn(G3))
    th = p.stage("th", [(b, 1, 1)], partial(thresh_fn, lo=0.05))
    p.output("out", [(th, 1, 1)])
    return p.build()


def tbackground_t() -> PipelineDAG:
    """Background subtraction with a running mean: the background
    estimate is the mean of the last 8 input frames (the frame-ring
    embodiment of a running mean — a box window over the ring depth,
    where a true EMA would need recursive state)."""
    p = Pipeline("tbackground-t")
    x = p.input("in")                                    # MC stage
    bg = p.stage("bg", [(x, 8, 1, 1)], stmean_fn(8))
    fg = p.stage("fg", [(x, 1, 1), (bg, 1, 1)], bg_subtract_fn)
    p.output("out", [(fg, 1, 1)])
    return p.build()


def tunsharp_t() -> PipelineDAG:
    """3-frame unsharp-over-time: sharpen against a 3x3x3 spatio-temporal
    mean — the one pipeline whose temporal taps carry a spatial window,
    so each tap streams an (R + 2, W) slab, not a row."""
    p = Pipeline("tunsharp-t")
    x = p.input("in")                                    # MC stage
    sa = p.stage("stavg", [(x, 3, 3, 3)], stmean_fn(3, 3, 3))
    sh = p.stage("sharp", [(x, 1, 1), (sa, 1, 1)], tunsharp_fn)
    p.output("out", [(sh, 1, 1)])
    return p.build()


VIDEO_ALGORITHMS = {
    "tdenoise-t": tdenoise_t, "tmotion-t": tmotion_t,
    "tbackground-t": tbackground_t, "tunsharp-t": tunsharp_t,
}

# Paper Sec. 7: 320p = 480x320, 1080p = 1920x1080 (W x H)
RESOLUTIONS = {"320p": (480, 320), "1080p": (1920, 1080)}


def synthetic_pipeline(n_stages: int, mc_fraction: float = 1 / 3,
                       seed: int = 0) -> PipelineDAG:
    """Random chains with MC branches for the Sec. 8.2 scalability sweep."""
    rng = np.random.RandomState(seed)
    p = Pipeline(f"synth-{n_stages}")
    prev = p.input("in")
    budget = n_stages - 3            # minus input, final join, output
    n_mc = max(1, int(n_stages * mc_fraction))
    pending = []   # side branches waiting to re-join
    i = 0
    side_spent = 0
    while i + side_spent < budget:
        i += 1
        reads = [(prev, int(rng.choice([1, 3])), int(rng.choice([1, 3])))]
        if pending and rng.rand() < 0.5:
            side = pending.pop()
            reads.append((side, 1, 1))
        cur = p.stage(f"k{i}", reads, identity_fn)
        if side_spent < n_mc and i + side_spent + 1 < budget and rng.rand() < 0.6:
            side = p.stage(f"k{i}b", [(prev, 3, 1)], identity_fn)
            pending.append(side)
            side_spent += 1
        prev = cur
    # drain leftover branches into the final stage
    reads = [(prev, 1, 1)] + [(s, 1, 1) for s in pending]
    last = p.stage("klast", reads, identity_fn)
    p.output("out", [(last, 1, 1)])
    return p.build()


# -------------------------------------------------------- reference exec
def _windows(img: jnp.ndarray, sh: int, sw: int) -> jnp.ndarray:
    """(H, W) -> (H, W, sh, sw) bottom-right-aligned windows, zero padded."""
    h, w = img.shape[-2], img.shape[-1]
    pad = jnp.pad(img, [(sh - 1, 0), (sw - 1, 0)])
    cols = []
    for dy in range(sh):
        row = []
        for dx in range(sw):
            row.append(pad[dy:dy + h, dx:dx + w])
        cols.append(jnp.stack(row, axis=-1))
    return jnp.stack(cols, axis=-2)


def execute_reference(dag: PipelineDAG, inputs: dict[str, jnp.ndarray]
                      ) -> dict[str, jnp.ndarray]:
    """Pure-jnp oracle: run every stage over full images, topo order.

    Single-frame only: a temporal pipeline (any edge with st > 1) has no
    meaning on one frame — use :func:`execute_reference_video`.
    """
    if dag.is_temporal():
        raise ValueError(f"{dag.name} has temporal edges; use "
                         f"execute_reference_video")
    vals: dict[str, jnp.ndarray] = {}
    for name in dag.topo_order:
        st = dag.stages[name]
        if st.is_input:
            vals[name] = jnp.asarray(inputs[name], dtype=jnp.float32)
            continue
        ins = dag.in_edges(name)
        if st.fn is None:  # relay or output: identity on single producer
            vals[name] = vals[ins[0].producer]
            continue
        wins = {k: _windows(vals[e.producer], e.sh, e.sw)
                for k, e in zip(window_keys(ins), ins)}
        vals[name] = st.fn(wins)
    return vals


def execute_reference_video(dag: PipelineDAG,
                            videos: dict[str, jnp.ndarray],
                            return_history: bool = False):
    """Multi-frame oracle: (T, H, W) inputs -> (T, H, W) output.

    Frames run in stream order through plain per-frame stage evaluation;
    each temporal producer's last d-1 frames are kept in a python-side
    history list (most recent first). Frames before t = 0 read as zero —
    the same causal zero padding as the spatial frame top/left, and the
    warm-up semantics the VideoEngine reproduces.

    With ``return_history=True`` returns ``(output, history)`` where
    ``history`` maps each temporal producer to its last d-1 frames,
    newest first (shorter when T < d-1) — exactly the state a serving
    session needs to resume the stream, which is how the VideoEngine's
    reference fallback rung resynchronizes device frame rings after
    serving frames off the compiled path.
    """
    t_frames = next(iter(videos.values())).shape[0]
    depths = dag.temporal_depths()
    history: dict[str, list[jnp.ndarray]] = {p: [] for p in depths}
    outs = []
    zero = None
    for t in range(t_frames):
        vals: dict[str, jnp.ndarray] = {}
        for name in dag.topo_order:
            st = dag.stages[name]
            if st.is_input:
                vals[name] = jnp.asarray(videos[name][t], dtype=jnp.float32)
                if zero is None:
                    zero = jnp.zeros_like(vals[name])
                continue
            ins = dag.in_edges(name)
            if st.fn is None:
                vals[name] = vals[ins[0].producer]
                continue
            wins = {}
            for k, e in zip(window_keys(ins), ins):
                if e.st == 1:
                    wins[k] = _windows(vals[e.producer], e.sh, e.sw)
                    continue
                past = history[e.producer]
                taps = []
                for dt in range(e.st):           # dt=0 oldest .. st-1 now
                    j = e.st - 1 - dt            # frames back
                    if j == 0:
                        frame = vals[e.producer]
                    elif j <= len(past):
                        frame = past[j - 1]
                    else:
                        frame = zero
                    taps.append(_windows(frame, e.sh, e.sw))
                wins[k] = jnp.stack(taps, axis=2)    # (H, W, st, sh, sw)
            vals[name] = st.fn(wins)
        for p, d in depths.items():
            history[p] = [vals[p]] + history[p][:d - 2]
        outs.append(vals[dag.output_stages()[0]])
    out = jnp.stack(outs)
    if return_history:
        return out, history
    return out
