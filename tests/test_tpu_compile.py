"""The fused stencil kernel compiled for a described TPU v5e chip.

Interpret mode accepts what the TPU's compiler refuses (unaligned slices,
rank-changing reshapes, dynamic slices), so the CPU tests alone cannot
show that the kernel lowers. These tests compile the served kernel at
the paper's 1080p (1920x1080, batch 4, R=8) for one chip of a described
``v5e:2x2`` topology — nothing runs — and check that the program holds
the Mosaic kernel (``tpu_custom_call``). The topology is described in a
fixture, never at import: only one process may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import algorithms
from repro.core.codegen import compile_pipeline, tap_name, temporal_taps
from repro.kernels.stencil_pipeline import (make_batched_pipeline_kernel,
                                            make_executor,
                                            make_video_executor, unstack)

W, H = algorithms.RESOLUTIONS["1080p"]
BATCH, R = 4, 8
ALL = {**algorithms.ALGORITHMS, **algorithms.VIDEO_ALGORITHMS}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name,depth,h,w", [
    ("unsharp-m", 1, H, W), ("canny-m", 1, H, W), ("xcorr-m", 1, H, W),
    ("tdenoise-t", 1, H, W), ("tdenoise-t", 2, H, W), ("sift-dog", 1, H, W),
    ("canny-m", 1, 128, 128)])        # the engine's default tile
def test_fused_kernel_compiles_for_v5e(one_chip, no_persistent_cache,
                                        name, depth, h, w):
    dag = ALL[name]()
    plan = compile_pipeline(dag, w, rows_per_step=R, prefetch_depth=depth)
    fn, _ = make_batched_pipeline_kernel(dag, BATCH, h, w, plan=plan,
                                         interpret=False)
    feeds = dag.input_stages() + [tap_name(p, j)
                                  for p, j in temporal_taps(dag)]
    args = {n: jax.ShapeDtypeStruct((BATCH, h, w), jnp.float32,
                                    sharding=one_chip) for n in feeds}
    compiled = fn.lower(args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_unaligned_row_group_is_refused():
    """R % 8 != 0 cannot lower (row groups move as whole (8, 128) tiles):
    the executor factory says so instead of failing inside Mosaic."""
    with pytest.raises(ValueError, match="multiple of 8"):
        make_executor(algorithms.canny_m(), H, W, rows_per_step=4,
                      interpret=False)


@pytest.mark.parametrize("name,program", [
    ("canny-m", "imagen_frame_batch_canny_m"),
    ("tbackground-t", "imagen_video_step_tbackground_t")])
def test_device_names_for_v5e(one_chip, no_persistent_cache, name, program):
    """The served programs and their kernel carry fixed names on the
    chip, which the device trace shows."""
    dag, h, w = ALL[name](), 16, 256
    plan = compile_pipeline(dag, w, rows_per_step=R)
    arg = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=one_chip)
    images = {"in": arg((BATCH, h, w))}
    if dag.is_temporal():
        ex = make_video_executor(dag, h, w, plan=plan, interpret=False,
                                 chunk=BATCH)
        lowered = ex._fn.lower(images, {p: arg(s.shape) for p, s in
                                        ex.init_state().items()})
    else:
        ex = make_executor(dag, h, w, batch=BATCH, plan=plan,
                           interpret=False)
        lowered = ex._fn.lower(images)
    text = lowered.compile().as_text()
    assert f"HloModule jit_{program}," in text
    kernel = "imagen_stencil_" + name.replace("-", "_")
    assert any(f"%{kernel}" in ln and "tpu_custom_call" in ln
               for ln in text.splitlines())


def test_output_split_compiles_for_v5e(one_chip, no_persistent_cache):
    """The engines' output split at 1080p, batch 4: a program of its own
    name holding no kernel, so the trace reduction never counts it as
    an executor program."""
    x = jax.ShapeDtypeStruct((BATCH, H, W), jnp.float32, sharding=one_chip)
    compiled = unstack.lower(x).compile()
    text = compiled.as_text()
    assert "HloModule jit_imagen_unstack," in text
    assert "tpu_custom_call" not in text
    assert [o.shape for o in jax.tree.leaves(compiled.out_info)] == \
        [(H, W)] * BATCH
