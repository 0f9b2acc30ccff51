#!/usr/bin/env python3
"""Lower-precision control of a cell's correctness check.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 --seconds 3

For each seed, in one process: a run of the cell (set-up, a window of
``--seconds``, the check), then the same sample of outputs recomputed
by the plain reference in bfloat16, the precision below the float32
the configurations state. Prints one JSON line per seed with the
program's widest gap (``program``) and the control's (``control``), both
in float32 scale-ULP, beside the limit: the program's readings over a
dozen seeds set the lower end of the limit, the control's the upper
end, and the control has to read above the limit. Not part of a
benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run as bench_run  # noqa: E402
from bench.spec import Bench  # noqa: E402

CONTROL = "bfloat16"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    import jax.numpy as jnp
    cell = Bench().cell(args.workload)
    for seed in args.seeds:
        try:
            res, _ = bench_run.measure(cell, seed, args.seconds, False,
                                       controls=(jnp.bfloat16,))
        except bench_run.NoAccelerator as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        cmp = res["compared"]
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "program": cmp["max_scale_ulp"]["value"],
            "control": res["control"][CONTROL],
            "limit": cmp["max_scale_ulp"]["limit"],
            "correct": res["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
