#!/usr/bin/env python3
"""Run one cell of the chip benchmark once and print one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``: a
configuration (``bench/configs/``), a traffic mix (``bench/traffic/``),
and the metrics that name it, each read by ``bench/metrics/<name>.py``.
The run makes its frames from ``--seed``, builds the engine, warms up
the shapes its traffic uses (set-up), drives the engine from the client
side for ``--seconds`` (the window), checks a seeded sample of the
served outputs against the plain reference, and prints, as the last
line of standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones, read from a profiler trace of the
window and the engine's spans), ``device``, ``breakdown`` (traced runs)
and, last, ``compared``: each number compared with its limit. The same
numbers end standard error.

It exits 2, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

T_PROCESS = __import__("time").perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import time  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src"))
                if p not in sys.path]

from bench import compare, xtrace  # noqa: E402
from bench.loadgen import Loop, Traffic, make_pool  # noqa: E402
from bench.record import Run  # noqa: E402
from bench.spec import Bench, Cell  # noqa: E402

POOL_FRAMES = 32       # host-resident frames the traffic draws from


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoAccelerator(f"JAX found {len(devs)} {devs[0].platform} "
                            f"device(s); the cell needs {n} TPU chip(s)")
    return devs


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else the fixed ``<checkout>/.jax_cache`` (the path is part
    of the cache's key, so it never moves). Every program is kept, also
    those that compile in under a second."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class WindowWatch:
    """Counts backend compiles (none belong in the window) and the
    longest garbage-collector pause while ``on``."""

    def __init__(self):
        import jax.monitoring
        self.on, self.compiles, self.gc_max_s, self._t = False, 0, 0.0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._event)
        gc.callbacks.append(self._gc)

    def _event(self, event: str, duration: float, **_) -> None:
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self.on:
            self.gc_max_s = max(self.gc_max_s, time.perf_counter() - self._t)

    def close(self) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._event)
        gc.callbacks.remove(self._gc)


def warm_fills(traffic: Traffic, slots: int) -> list[int]:
    """Batch fills the traffic will serve: a closed mix that keeps a
    full batch queued serves full ones only; an open one any fill."""
    if traffic.kind == "closed" and traffic.queued >= slots:
        return [slots]
    return [1, slots]


def _profile_start():
    from jax.profiler import ProfileOptions
    import jax
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1        # keeps TraceAnnotations
    path = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(path, profiler_options=opts)
    return path


def _profile_stop(path: str):
    import jax
    jax.profiler.stop_trace()
    try:
        (xplane,) = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                              recursive=True)
        trace = xtrace.load(xplane)
        return xtrace.summarize(trace, *trace.window())
    finally:
        shutil.rmtree(path, ignore_errors=True)


def measure(cell: Cell, seed: int, seconds: float, traced: bool,
            t_process: float = T_PROCESS, on_chip: bool = True,
            controls: tuple = ()) -> tuple[dict, Run]:
    """One run of ``cell``: set-up, window, check. Returns the result
    object and the run's record. ``on_chip=False`` skips the look for
    a TPU (the tests drive the rest of a run on the CPU). For each
    dtype in ``controls`` the result's ``control`` also gives the widest
    gap of the reference computed in that dtype, on the same sample."""
    devs = require_chips(cell.chips) if on_chip else None
    import jax
    from repro.obs import trace as obs

    enable_compile_cache()
    watch = WindowWatch()
    cfg = cell.config
    h, w = cfg["frame"]["height"], cfg["frame"]["width"]
    if cfg["frame"]["dtype"] != "float32":
        raise ValueError(f"frame dtype {cfg['frame']['dtype']!r}: the "
                         f"engines take float32 frames")
    pool = make_pool(seed, POOL_FRAMES, h, w)
    traffic = Traffic(cell.traffic, seed, POOL_FRAMES)
    driver = cell.driver()(cfg, traffic.streams)
    t0 = time.perf_counter()
    driver.warmup(pool, warm_fills(traffic, driver.slots))
    warmup_s = time.perf_counter() - t0
    interpreted = [e for e in driver.executors() if e.interpret]
    if on_chip and interpreted:
        raise RuntimeError(f"{len(interpreted)} executor(s) run in the "
                           f"Pallas interpreter on the chip")

    sample = compare.Reservoir(cfg["compare"]["sample"], seed)
    loop = Loop(driver, traffic, pool, sample.offer, trace=traced)
    trace_dir = None
    if traced:
        obs.enable(capacity=1 << 20)
        obs.clear()
        trace_dir = _profile_start()
    before = driver.counters()
    # what imports, plans and compiles left is the long-lived heap of a
    # serving process: collected once, then kept out of the collector's
    # walks (a full walk of it stalls the loop for ~0.1 s)
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_process
    watch.on = True
    loop.run(seconds)
    watch.on = False
    watch.close()
    gc.unfreeze()
    after = driver.counters()
    device_sum, spans = None, []
    if traced:
        device_sum = _profile_stop(trace_dir)
        epoch = obs.get_tracer().epoch_ns
        lo, hi = loop.t_start * 1e9 - epoch, loop.t_close * 1e9 - epoch
        spans = [e for e in obs.events() if lo <= e.ts_ns <= hi]
        obs.disable()
        obs.clear()
    devs = devs or jax.devices()
    dev = devs[0]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    misordered = loop.misordered + driver.misordered
    driver.close()
    del driver
    gc.collect()

    reference = cell.reference()
    gaps = compare.gaps(reference, pool, traffic, sample.items)
    control = {jax.numpy.dtype(d).name:
               max(compare.gaps(reference, pool, traffic, sample.items,
                                dtype=d), default=0.0)
               for d in controls}
    run = Run(config=cfg, traffic=cell.traffic, seconds=seconds,
              t_start=loop.t_start, t_close=loop.t_close,
              frames=loop.records,
              completed_in_window=loop.completed_in_window,
              setup_s=setup_s, warmup_s=warmup_s,
              counters={"frames_completed": after["frames_completed"]
                        - before["frames_completed"],
                        "batches": after["batches"] - before["batches"],
                        "slots": after["slots"]},
              spans=spans, device=device_sum, device_kind=dev.device_kind)
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = cell.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    compared = {
        "max_scale_ulp": {"value": max(gaps, default=0.0),
                          "limit": cfg["compare"]["max_scale_ulp"]},
        "missing": {"value": loop.missing, "limit": 0},
        "misordered": {"value": misordered, "limit": 0},
        "unchecked": {"value": int(not gaps), "limit": 0},
    }
    result = {
        "correct": all(c["value"] <= c["limit"] for c in compared.values()),
        "attempted": len(loop.records),
        "failed": loop.missing,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devs),
                   "memory_peak_bytes": int(peak)},
    }
    if device_sum is not None:
        result["device"]["busy_s"] = device_sum.busy_s
        result["device"]["window_s"] = device_sum.window_s
        result["breakdown"] = {
            "device_ops": [list(x) for x in device_sum.top_ops],
            "idle_gaps": [list(x) for x in device_sum.idle_gaps]}
    if control:
        result["control"] = control
    result["compared"] = compared
    lateness = [r.submitted - r.due for r in loop.records]
    _log(f"window {loop.t_close - loop.t_start:.3f} s, "
         f"{loop.completed_in_window} frames served in it, "
         f"{len(loop.records)} offered, {loop.refused} refused; "
         f"checked {len(gaps)} outputs; compiles in the window "
         f"{watch.compiles}; longest collector pause "
         f"{watch.gc_max_s * 1e3:.3f} ms; generator late by at most "
         f"{max(lateness, default=0.0) * 1e3:.3f} ms")
    return result, run


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, root: str = ROOT, on_chip: bool = True) -> int:
    args = parse(argv)
    cell = Bench(root).cell(args.workload)
    try:
        result, _ = measure(cell, args.seed, args.seconds, bool(args.trace),
                            on_chip=on_chip)
    except NoAccelerator as e:
        _log(f"bench: {e}; nothing was run")
        return 2
    for name, c in result["compared"].items():
        _log(f"compared {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
