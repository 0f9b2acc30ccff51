"""Device time from a JAX profiler trace (``.xplane.pb``).

The reduction every cell uses, kept as code so that every run computes
each number the same way:

* busy: the union of the intervals in which an operation ran on a device
  (the ``XLA Ops`` line of each ``/device:TPU:n`` plane), inside the
  window the benchmark marked with its ``bench.window`` annotation,
  averaged over the devices that ran anything there; idle share =
  1 - busy / window;
* executor runs: the device programs (``XLA Modules``) that hold a Pallas
  kernel (an op with ``custom_call_target="tpu_custom_call"``); their
  kernel time and the busy time of all their ops;
* top ops, by total device time, named ``<program>/<op>``;
* idle gaps, each attributed to the innermost host annotation open at
  its midpoint (the benchmark's ``bench.*`` ones and the engine's
  ``engine.execute`` / ``executor.call``), summed by name.

Host annotations and device ops share one clock in the trace.
"""
from __future__ import annotations

import dataclasses
from collections import defaultdict

KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
ANNOTATIONS = ("bench.", "engine.", "executor.")
NO_ANNOTATION = "host:unannotated"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float            # ns, the trace's clock
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class DeviceEvents:
    name: str
    ops: list[Event]
    modules: list[Event]


@dataclasses.dataclass
class Trace:
    devices: list[DeviceEvents]
    annotations: list[Event]

    def window(self, name: str = "bench.window") -> tuple[float, float]:
        spans = [a for a in self.annotations if a.name == name]
        if len(spans) != 1:
            raise ValueError(f"{len(spans)} {name!r} annotations in the "
                             f"trace; expected 1")
        return spans[0].start, spans[0].end


def load(path: str) -> Trace:
    """Device ops and programs, and the host annotations of interest."""
    from jax.profiler import ProfileData

    devices, anns = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            grab = (lambda ln: sorted(
                (Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                 for e in ln.events), key=lambda e: e.start)
                if ln is not None else [])
            devices.append(DeviceEvents(plane.name,
                                        grab(lines.get("XLA Ops")),
                                        grab(lines.get("XLA Modules"))))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                anns += [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in ln.events if e.name.startswith(ANNOTATIONS)]
    anns.sort(key=lambda e: (e.start, -e.end))
    return Trace(devices, anns)


def merged(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of the events' intervals, clipped to [lo, hi]."""
    out: list[list[float]] = []
    for e in sorted(events, key=lambda e: e.start):
        s, t = max(e.start, lo), min(e.end, hi)
        if t <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def _length(spans) -> float:
    return sum(t - s for s, t in spans)


def _base(module: str) -> str:
    return module.split("(", 1)[0]


def _op_name(op: str) -> str:
    return op.split(" = ", 1)[0].lstrip("%")


def is_kernel(op: Event) -> bool:
    return KERNEL_TARGET in op.name


def runs(dev: DeviceEvents, lo: float, hi: float):
    """[(program, [its ops])] for every program that started in [lo, hi),
    ops given to the program whose interval holds their start."""
    mods = [m for m in dev.modules if lo <= m.start < hi]
    out = [(m, []) for m in mods]
    i = 0
    for op in dev.ops:
        while i < len(mods) and mods[i].end < op.start:
            i += 1
        if i < len(mods) and mods[i].start <= op.start <= mods[i].end:
            out[i][1].append(op)
    return out


def _gap_names(gaps, anns) -> list[str]:
    """Innermost annotation open at each gap's midpoint (annotations of
    one thread nest, so the newest still open one is the innermost)."""
    names, stack, j = [], [], 0
    for s, t in gaps:                      # gaps are in time order
        mid = (s + t) / 2
        while j < len(anns) and anns[j].start <= mid:
            stack.append(anns[j])
            j += 1
        while stack and stack[-1].end < mid:
            stack.pop()
        names.append(stack[-1].name if stack else NO_ANNOTATION)
    return names


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                 # averaged over the devices used
    executor_runs: int
    kernel_s: float               # Pallas kernel ops of executor runs
    executor_busy_s: float        # busy time of executor runs' ops
    top_ops: list[tuple[str, float]]
    idle_gaps: list[tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def summarize(trace: Trace, lo: float, hi: float, top: int = 10) -> Summary:
    busy, n_runs, kernel, ex_busy = [], 0, 0.0, 0.0
    per_op: dict[str, float] = defaultdict(float)
    per_gap: dict[str, float] = defaultdict(float)
    used = [d for d in trace.devices if merged(d.ops, lo, hi)]
    for dev in used:
        spans = merged(dev.ops, lo, hi)
        busy.append(_length(spans))
        for mod, ops in runs(dev, lo, hi):
            for op in ops:
                per_op[f"{_base(mod.name)}/{_op_name(op.name)}"] += op.dur
            if any(is_kernel(op) for op in ops):
                n_runs += 1
                kernel += sum(op.dur for op in ops if is_kernel(op))
                ex_busy += _length(merged(ops, mod.start, mod.end))
        edges = [lo] + [x for span in spans for x in span] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for (s, t), name in zip(gaps, _gap_names(gaps, trace.annotations)):
            per_gap[name] += t - s
    n_dev = max(len(used), 1)
    ns = 1e-9
    rank = lambda d: [(k, v * ns / n_dev) for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return Summary(window_s=(hi - lo) * ns, busy_s=sum(busy) / n_dev * ns,
                   executor_runs=n_runs, kernel_s=kernel * ns,
                   executor_busy_s=ex_busy * ns, top_ops=rank(per_op),
                   idle_gaps=rank(per_gap))
