"""What one run leaves for the metric readers.

Each ``bench/metrics/<name>.py`` defines ``read(run) -> float | None``
over a :class:`Run`; ``None`` means the run holds nothing to read for
that metric, and the metric is left out of the result line.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.loadgen import FrameRecord


@dataclasses.dataclass
class Run:
    config: dict                  # the configuration file, as run
    traffic: dict                 # the traffic mix, as run
    seconds: float                # the window asked for
    t_start: float                # window opened (perf_counter s)
    t_close: float                # window closed
    frames: list[FrameRecord]     # every frame offered in the window
    completed_in_window: int      # frames served by steps in the window
    setup_s: float                # process start -> window start
    warmup_s: float               # the warm-up calls of the cell's shapes
    counters: dict                # engine counters over the window
    spans: list = dataclasses.field(default_factory=list)   # obs spans
    device: object = None         # xtrace.Summary of the traced window
    device_kind: str = ""         # as JAX reports it

    def served(self) -> list[FrameRecord]:
        return [f for f in self.frames if f.ok]

    def span_ms(self, name: str) -> list[float]:
        return [e.dur_ns / 1e6 for e in self.spans if e.name == name]


def percentile(values, q: float) -> float | None:
    return float(np.percentile(values, q)) if len(values) else None


def mean(values) -> float | None:
    return float(np.mean(values)) if len(values) else None
