"""The least HBM traffic an executor call needs, from the configuration.

Counted from the work, not from how the program does it: each input
frame an output depends on is read once, each output written once, no
state is rewritten. A call that serves n frames of a pipeline with
``inputs_per_output`` inputs and ``history_frames`` frames of history
per temporal input reads ``(n + history_frames) * inputs_per_output``
frames and writes n, each ``height * width * bytes_per_pixel`` bytes.
"""
from __future__ import annotations


def frame_bytes(config: dict) -> int:
    f = config["frame"]
    return f["height"] * f["width"] * config["roofline"]["bytes_per_pixel"]


def least_bytes(config: dict, frames: int) -> int:
    r = config["roofline"]
    read = (frames + r["history_frames"]) * r["inputs_per_output"]
    return (read + frames) * frame_bytes(config)


def least_seconds(config: dict, frames: int, peaks: dict) -> float:
    """The call's time at the chip's HBM bandwidth."""
    return least_bytes(config, frames) / peaks["hbm_bytes_per_s"]
