"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DP, algorithms, compile_pipeline
from repro.kernels import ops, ref

RNG = np.random.RandomState(42)


@pytest.mark.parametrize("hw", [(8, 16), (20, 24), (13, 130), (9, 257)])
@pytest.mark.parametrize("k", [(1, 1), (3, 3), (1, 5), (5, 1), (2, 4)])
def test_conv2d_sweep(hw, k):
    h, w = hw
    img = RNG.rand(h, w).astype(np.float32)
    wts = RNG.randn(*k).astype(np.float32)
    got = ops.conv2d(jnp.asarray(img), jnp.asarray(wts))
    exp = ref.conv2d_ref(jnp.asarray(img), jnp.asarray(wts))
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name", list(algorithms.ALGORITHMS))
def test_fused_pipeline_matches_ref(name):
    dag = algorithms.ALGORITHMS[name]()
    plan = compile_pipeline(dag, 24, mem=DP)
    img = RNG.rand(26, 24).astype(np.float32)
    got = ops.fused_pipeline(dag, {"in": img}, plan=plan)
    exp = ref.stencil_pipeline_ref(dag, {"in": img})
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("name", ["unsharp-m", "denoise-m"])
def test_fused_pipeline_unplanned_rings(name):
    """Minimal SH-sized rings (no ImaGen plan) are also correct at row
    granularity — the plan only ever grows them."""
    dag = algorithms.ALGORITHMS[name]()
    img = RNG.rand(18, 16).astype(np.float32)
    got = ops.fused_pipeline(dag, {"in": img}, plan=None)
    exp = ref.stencil_pipeline_ref(dag, {"in": img})
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("shape", [
    # B, Hq, Hkv, D, S
    (1, 4, 4, 32, 16),     # MHA
    (2, 8, 2, 64, 32),     # GQA
    (3, 8, 1, 16, 64),     # MQA
])
def test_swa_decode_sweep(shape):
    b, hq, hkv, d, s = shape
    q = RNG.randn(b, hq, d).astype(np.float32)
    k = RNG.randn(b, s, hkv, d).astype(np.float32)
    v = RNG.randn(b, s, hkv, d).astype(np.float32)
    length = RNG.randint(1, s + 1, size=(b,)).astype(np.int32)
    start = RNG.randint(0, s, size=(b,)).astype(np.int32)
    got = ops.swa_decode(*map(jnp.asarray, (q, k, v, length, start)))
    exp = ref.swa_decode_ref(*map(jnp.asarray, (q, k, v, length, start)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=2e-4, atol=2e-5)


def test_swa_decode_bf16_inputs():
    b, hq, hkv, d, s = 2, 4, 2, 32, 16
    q = jnp.asarray(RNG.randn(b, hq, d), jnp.bfloat16)
    k = jnp.asarray(RNG.randn(b, s, hkv, d), jnp.bfloat16)
    v = jnp.asarray(RNG.randn(b, s, hkv, d), jnp.bfloat16)
    length = jnp.full((b,), s, jnp.int32)
    start = jnp.zeros((b,), jnp.int32)
    got = ops.swa_decode(q, k, v, length, start)
    exp = ref.swa_decode_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                             v.astype(jnp.float32), length, start)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("name", ["canny-m", "denoise-m"])
def test_batched_pipeline_matches_per_frame(name):
    """grid=(B, H) batched kernel: frames stream through the same VMEM
    rings back-to-back; top-of-frame masking isolates them."""
    from repro.kernels.stencil_pipeline import make_executor
    dag = algorithms.ALGORITHMS[name]()
    plan = compile_pipeline(dag, 24, mem=DP)
    ex = make_executor(dag, 18, 24, batch=3, plan=plan)
    frames = RNG.rand(3, 18, 24).astype(np.float32)
    got = np.asarray(ex({"in": jnp.asarray(frames)}))
    for b in range(3):
        exp = ref.stencil_pipeline_ref(dag, {"in": frames[b]})
        np.testing.assert_allclose(got[b], np.asarray(exp),
                                   rtol=1e-4, atol=1e-5)


def test_pipeline_vmem_accounting():
    dag = algorithms.ALGORITHMS["canny-m"]()
    plan = compile_pipeline(dag, 24, mem=DP)
    vb = ops.pipeline_vmem_bytes(dag, 20, 24, plan)
    # rings padded to (8k, 128) fp32 tiles
    assert vb % (8 * 128 * 4) == 0
    assert vb > 0


def test_interpret_derived_from_backend():
    """Off the TPU the serving path runs the Pallas interpreter without
    anyone asking for it; on a TPU the same defaults compile Mosaic."""
    import jax
    from repro.imaging import PlanCache
    from repro.kernels.stencil_pipeline import default_interpret
    assert default_interpret() == (jax.default_backend() != "tpu")
    cache = PlanCache()
    ex = cache.executor_for("canny-m", 8, 16, batch=2, rows_per_step=8)
    vex = cache.video_executor_for("tdenoise-t", 8, 16, rows_per_step=8)
    assert ex.interpret is vex.interpret is default_interpret()
    assert cache.executors() == [ex, vex]


def _view_and_array(rows, sh, sw, w=20, st=None):
    """The same windows twice: as the kernel's lazy view over row slabs
    and as the reference's real array, for ``rows`` output rows."""
    from repro.kernels.stencil_pipeline import _WindowView
    slabs = [jnp.asarray(RNG.rand(rows + sh - 1, w).astype(np.float32))
             for _ in range(st or 1)]
    arrs = [algorithms._windows(s, sh, sw)[sh - 1:] for s in slabs]
    arr = arrs[0] if st is None else jnp.stack(arrs, axis=2)
    return _WindowView(slabs, rows, sh, sw, temporal=st is not None), arr


@pytest.mark.parametrize("sh,sw", [(3, 3), (5, 3), (3, 1), (1, 1)])
def test_window_view_matches_array_nms(sh, sw):
    view, arr = _view_and_array(8, sh, sw)
    assert view.shape == arr.shape
    np.testing.assert_array_equal(
        np.asarray(algorithms.nms_fn({"x": view})),
        np.asarray(algorithms.nms_fn({"x": arr})))


def test_window_view_matches_array_xcorr_and_temporal():
    tall_v, tall_a = _view_and_array(8, 18, 1)
    ctr_v, ctr_a = _view_and_array(8, 1, 1)
    np.testing.assert_array_equal(
        np.asarray(algorithms.xcorr_fn({"a": tall_v, "b": ctr_v})),
        np.asarray(algorithms.xcorr_fn({"a": tall_a, "b": ctr_a})))
    tv, ta = _view_and_array(8, 3, 3, st=3)
    assert tv.shape == ta.shape
    fn = algorithms.stmean_fn(3, 3, 3)
    np.testing.assert_array_equal(np.asarray(fn({"x": tv})),
                                  np.asarray(fn({"x": ta})))


def test_window_view_takes_only_static_in_range_indices():
    view, _ = _view_and_array(8, 3, 3)
    with pytest.raises(IndexError):
        view[..., -1, 0]
    with pytest.raises(IndexError):
        view[..., 0, 3]
    with pytest.raises(TypeError):
        view[0]
