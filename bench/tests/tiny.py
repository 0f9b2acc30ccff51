"""A benchmark root in a temporary directory: the cells of
``BENCHMARK.json`` cut to a frame the Pallas interpreter runs in
seconds, their files written beside it, nothing of ``bench/`` edited."""
from __future__ import annotations

import json
import os
import shutil

from bench.spec import ROOT, load_json

H, W = 16, 128

# cells that BENCHMARK.json does not hold, on its configurations: the
# camera cells' latency tails were not steady on a shared one-chip host
# (PERF.md, Open questions); the tests still drive the open loop, the
# camera traffic and their readers through them
CAMERA_CELLS = [
    {"name": "tbackground-t-1080p.cams30", "config": "tbackground-t-1080p",
     "traffic": "cams11x30", "chips": 1, "why": "test"},
    {"name": "canny-m-1080p.cams30", "config": "canny-m-1080p",
     "traffic": "cams9x30", "chips": 1, "why": "test"},
]
CAMERA_METRICS = {
    "end_to_end": [("latency_p50_ms", "ms"), ("latency_p95_ms", "ms")],
    "per_layer": [("queue_wait_p95_ms", "ms"), ("batch_fill", "%"),
                  ("execute_ms", "ms"), ("warmup_s", "s")],
}


def make_root(path, sample: int = 8) -> str:
    """Write BENCHMARK.json, the configurations (at H x W) with their
    references, and the traffic mixes under ``path``; returns it."""
    path = str(path)
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cams = [c["name"] for c in CAMERA_CELLS]
    spec["workloads"] += CAMERA_CELLS
    for kind, metrics in CAMERA_METRICS.items():
        have = {m["name"]: m for m in spec[kind]}
        for name, unit in metrics:
            m = have.get(name)
            if m is None:
                m = {"name": name, "unit": unit, "better": "lower",
                     "source": "host_clock", "workloads": []}
                if kind == "end_to_end":
                    m["bound"] = 0.25
                else:
                    m.update(layer="test", moves="latency_p95_ms")
                spec[kind].append(m)
            m["workloads"] = m.get("workloads", []) + cams
    os.makedirs(os.path.join(path, "bench", "configs"), exist_ok=True)
    os.makedirs(os.path.join(path, "bench", "traffic"), exist_ok=True)
    for c in spec["configs"]:
        src = os.path.join(ROOT, c["file"])
        cfg = load_json(src)
        cfg["frame"].update(height=H, width=W)
        if "tile_shape" in cfg["engine"]:
            cfg["engine"]["tile_shape"] = [H, W]
        cfg["compare"]["sample"] = sample
        with open(os.path.join(path, c["file"]), "w") as f:
            json.dump(cfg, f)
        shutil.copy(os.path.join(os.path.dirname(src), cfg["reference"]),
                    os.path.join(path, "bench", "configs", cfg["reference"]))
    for w in spec["workloads"]:
        shutil.copy(os.path.join(ROOT, "bench", "traffic",
                                 f"{w['traffic']}.json"),
                    os.path.join(path, "bench", "traffic"))
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return path


def run_cell(root: str, workload: str, capsys, seed: int = 2**31 + 7,
             seconds: float = 0.5, trace: int = 0) -> dict:
    """``bench/run.py`` on ``workload`` of ``root`` without the look
    for a chip; returns its result line."""
    from bench import run
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
                  root=root, on_chip=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
