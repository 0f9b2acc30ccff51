#!/usr/bin/env python3
"""Knee sweep of an open (camera) cell: the most cameras the chip serves.

    python3 bench/knee.py --workload <cams cell> --cameras 6 8 10 12 --seconds 8 --seed <n>

Runs the cell's traffic at each camera count in turn, in one process,
and prints one JSON line per count: p50 and p95 latency (due time ->
output), frames refused or failed, and whether the backlog grew (the p95
of the window's last quarter over that of its first, and how long the
queue took to drain after the last arrival). The knee is the largest
count whose p95 stays within one frame period with no refusal and no
growing backlog; the last line gives it and 80% of it, rounded down,
the count for a cell below capacity. Run once on the chip to size a
cell; the cell then offers its fixed count.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run as bench_run  # noqa: E402
from bench.record import percentile  # noqa: E402
from bench.spec import Bench  # noqa: E402

GROWTH_LIMIT = 1.5     # last-quarter p95 over first-quarter p95


def reading(rec, cameras: int) -> dict:
    """Latency and backlog of one window (an open mix)."""
    served = rec.served()
    lat = [(f.done - f.due) * 1e3 for f in served]
    span = rec.seconds / 4
    first = [(f.done - f.due) * 1e3 for f in served
             if f.due - rec.t_start < span]
    last = [(f.done - f.due) * 1e3 for f in served
            if f.due - rec.t_start >= rec.seconds - span]
    p_first, p_last = percentile(first, 95), percentile(last, 95)
    return {"cameras": cameras, "frames": len(rec.frames),
            "not_served": len(rec.frames) - len(served),
            "p50_ms": percentile(lat, 50), "p95_ms": percentile(lat, 95),
            "growth": (p_last / p_first if p_first and p_last else None),
            "drain_ms": (rec.t_close - rec.t_start - rec.seconds) * 1e3}


def knee(readings: list[dict], period_ms: float) -> int | None:
    """Largest camera count below which every count was sustained."""
    best = None
    for r in sorted(readings, key=lambda r: r["cameras"]):
        ok = (r["not_served"] == 0 and r["p95_ms"] is not None
              and r["p95_ms"] <= period_ms
              and (r["growth"] or 0.0) <= GROWTH_LIMIT
              and r["drain_ms"] <= period_ms)
        if not ok:
            break
        best = r["cameras"]
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--cameras", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    cell = Bench().cell(args.workload)
    if cell.traffic["kind"] != "open":
        raise SystemExit(f"{args.workload} is not an open (camera) cell")
    out = []
    for n in args.cameras:
        c = dataclasses.replace(cell, traffic=dict(cell.traffic, cameras=n))
        try:
            _, rec = bench_run.measure(c, args.seed, args.seconds, False)
        except bench_run.NoAccelerator as e:
            print(f"knee: {e}", file=sys.stderr)
            return 2
        out.append(reading(rec, n))
        print(json.dumps(out[-1]), flush=True)
    period_ms = 1e3 / cell.traffic["fps"]
    k = knee(out, period_ms)
    print(json.dumps({"knee_cameras": k,
                      "cell_cameras": math.floor(0.8 * k) if k else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
