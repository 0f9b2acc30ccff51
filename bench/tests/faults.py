"""Faults planted under the timed path, for the fault tests: each
replaces an executor's call for the length of one test."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.stencil_pipeline import StencilExecutor, VideoExecutor


def _half_batch(images):
    """The second half of a batch or chunk left out (zero frames)."""
    out = {}
    for k, v in images.items():
        v = jnp.asarray(v, jnp.float32)
        out[k] = v.at[v.shape[0] // 2:].set(0.0) if v.ndim == 3 else v
    return out


def _alter(out):
    """One pixel of every output frame changed where it is produced."""
    return out.at[..., 0, 0].add(1.0)


def _swap(out):
    """Outputs of the batch's first two slots exchanged."""
    return out.at[jnp.array([0, 1])].set(out[jnp.array([1, 0])])


def frame_call(fault):
    def call(self, images):
        if fault == "half_batch":
            return self._fn(_half_batch(images))
        out = self._fn(images)
        return _alter(out) if fault == "altered" else _swap(out)
    return call


def video_call(fault):
    def call(self, images, state):
        if fault == "half_batch":
            return self._fn(_half_batch(images), state)
        out, new_state = self._fn(images, state)
        if fault == "state_unchanged":
            return out, state
        return _alter(out), new_state
    return call


def plant(monkeypatch, kind: str, fault: str) -> None:
    cls, make = ((StencilExecutor, frame_call) if kind == "frame"
                 else (VideoExecutor, video_call))
    monkeypatch.setattr(cls, "__call__", make(fault))
