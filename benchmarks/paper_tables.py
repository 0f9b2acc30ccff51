"""Paper-table benchmarks (one function per table/figure).

Each function returns a list of CSV rows ("name,us_per_call,derived").
The derived column carries the table's headline quantity so diffs against
the paper's claims are one grep away.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import DP, DPLC, SP, algorithms, compile_pipeline
from repro.core.baselines import darkroom_schedule, fixynn_schedule, soda_allocate
from repro.core.dse import sweep
from repro.core.ilp import build_problem, solve_schedule
from repro.core.linebuffer import (ASIC_SRAM_BITS, DP_SIZED, DPLC_SIZED,
                                   FPGA_BRAM_BITS, FPGA_DP, allocate)
from repro.core.power import memory_power

RES = {"320p": 480, "1080p": 1920}
ALGOS = list(algorithms.PAPER_ALGORITHMS)


def _time(fn, reps=3):
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    return (time.perf_counter() - t0) / reps * 1e6, out


def memory_table(res: str = "320p"):
    """Fig. 8a / 9a: SRAM allocated bits, ours vs baselines."""
    w = RES[res]
    rows = []
    totals = {k: 0.0 for k in ["ours", "ours_lc", "fixynn", "darkroom",
                               "soda"]}
    for name in ALGOS:
        dag = algorithms.ALGORITHMS[name]()
        us, ours = _time(lambda: compile_pipeline(dag, w, mem=DP), 1)
        lc = compile_pipeline(dag, w, mem=DPLC)
        fx = compile_pipeline(dag, w, mem=SP)
        lin, dsched = darkroom_schedule(dag, w)
        dalloc = allocate(lin, dsched, {s: DP for s in lin.stages}, w)
        soda = soda_allocate(dag, w, ASIC_SRAM_BITS, sized=False)
        vals = {"ours": ours.total_alloc_bits, "ours_lc": lc.total_alloc_bits,
                "fixynn": fx.total_alloc_bits,
                "darkroom": dalloc.total_alloc_bits,
                "soda": soda.alloc.total_alloc_bits}
        for k, v in vals.items():
            totals[k] += v
        rows.append(f"mem_{res}_{name},{us:.0f},"
                    + ";".join(f"{k}={v/1024:.0f}Kb" for k, v in vals.items()))
    m = totals
    rows.append(
        f"mem_{res}_MEAN,0,"
        f"ours_vs_fixynn={100*(m['ours']/m['fixynn']-1):+.1f}%"
        f";ours_vs_darkroom={100*(m['ours']/m['darkroom']-1):+.1f}%"
        f";ours_vs_soda={100*(m['ours']/m['soda']-1):+.1f}%"
        f";lc_vs_fixynn={100*(m['ours_lc']/m['fixynn']-1):+.1f}%"
        f";lc_vs_darkroom={100*(m['ours_lc']/m['darkroom']-1):+.1f}%"
        f";paper=-28.0%/-10.2%/+31.0%/-86.0%/-56.8%")
    return rows


def power_table(res: str = "320p"):
    """Fig. 8b / 9b: memory power, ours vs baselines."""
    w = RES[res]
    rows = []
    totals = {k: 0.0 for k in ["ours", "ours_lc", "fixynn", "darkroom",
                               "soda"]}
    for name in ALGOS:
        dag = algorithms.ALGORITHMS[name]()
        ours = compile_pipeline(dag, w, mem=DP)
        lc = compile_pipeline(dag, w, mem=DPLC)
        fx = compile_pipeline(dag, w, mem=SP)
        lin, dsched = darkroom_schedule(dag, w)
        dalloc = allocate(lin, dsched, {s: DP for s in lin.stages}, w)
        soda = soda_allocate(dag, w, ASIC_SRAM_BITS, sized=False)
        vals = {"ours": ours.power, "ours_lc": lc.power, "fixynn": fx.power,
                "darkroom": memory_power(dalloc),
                "soda": memory_power(soda.alloc)}
        for k, v in vals.items():
            totals[k] += v
        rows.append(f"power_{res}_{name},0,"
                    + ";".join(f"{k}={v:.1f}" for k, v in vals.items()))
    m = totals
    rows.append(
        f"power_{res}_MEAN,0,"
        f"ours_vs_fixynn={100*(m['ours']/m['fixynn']-1):+.1f}%"
        f";ours_vs_darkroom={100*(m['ours']/m['darkroom']-1):+.1f}%"
        f";ours_vs_soda={100*(m['ours']/m['soda']-1):+.1f}%"
        f";paper=-7.8%/-13.8%/-56.0%")
    return rows


def throughput_table(res: str = "320p"):
    """Sec. 8.1: 1 px/cycle, no stalls; latency overhead vs ASAP."""
    w = RES[res]
    h = 320 if res == "320p" else 1080
    rows = []
    for name in ALGOS:
        dag = algorithms.ALGORITHMS[name]()
        plan = compile_pipeline(dag, w, mem=DP)
        us, rep = _time(lambda: plan.verify(h), 1)
        overhead = rep.output_start / (w * h)
        rows.append(f"throughput_{res}_{name},{us:.0f},"
                    f"px_per_cycle={rep.throughput:.1f};ok={rep.ok};"
                    f"latency_overhead={overhead*100:.3f}%")
    return rows


def compile_speed_table():
    """Sec. 8.2: compile times + scalability sweep + pruning ablation."""
    rows = []
    times = []
    for name in ALGOS:
        dag = algorithms.ALGORITHMS[name]()
        us, _ = _time(lambda: compile_pipeline(dag, 480, mem=DP), 3)
        times.append(us)
        rows.append(f"compile_{name},{us:.0f},ms={us/1e3:.2f}")
    rows.append(f"compile_MEAN,{np.mean(times):.0f},"
                f"ms={np.mean(times)/1e3:.2f};paper_ms=14.5")
    for n in [9, 20, 40, 60]:
        dag = algorithms.synthetic_pipeline(n)
        us, s = _time(lambda: solve_schedule(build_problem(dag, 480, ports=2)), 1)
        rows.append(f"scalability_{n}stages,{us:.0f},branches={s.n_branches}")
    # pruning ablation (paper: 4x average speedup on MC pipelines)
    sp_tot, no_tot = 0.0, 0.0
    for name in ["canny-m", "harris-m", "unsharp-m", "denoise-m", "xcorr-m"]:
        dag = algorithms.ALGORITHMS[name]()
        us_p, sched_p = _time(lambda: solve_schedule(
            build_problem(dag, 480, ports=2, prune=True)), 3)
        us_n, sched_n = _time(lambda: solve_schedule(
            build_problem(dag, 480, ports=2, prune=False)), 3)
        sp_tot += us_p
        no_tot += us_n
        rows.append(f"pruning_{name},{us_p:.0f},"
                    f"speedup={us_n/us_p:.2f}x;branches={sched_p.n_branches}"
                    f"vs{sched_n.n_branches};same_obj="
                    f"{sched_p.total_pixels == sched_n.total_pixels}")
    rows.append(f"pruning_MEAN,{sp_tot/5:.0f},speedup={no_tot/sp_tot:.2f}x"
                f";paper=4x")
    return rows


def dse_table():
    """Fig. 10: Pareto frontiers, canny-m vs denoise-m (sized-macro DSE)."""
    rows = []
    for name in ["canny-m", "denoise-m"]:
        dag = algorithms.ALGORITHMS[name]()
        us, pts = _time(lambda: sweep(dag, 480, [DP_SIZED, DPLC_SIZED],
                                      max_points=300), 1)
        par = sorted([p for p in pts if p.pareto], key=lambda p: p.area)
        desc = "|".join(
            f"area={p.area/1e6:.2f},power={p.power:.1f},"
            f"nLC={sum(1 for v in p.combo.values() if v == 'DPLC')}"
            for p in par)
        rows.append(f"dse_{name},{us:.0f},n_designs={len(pts)};"
                    f"n_pareto={len(par)};{desc}")
    return rows


def multi_algorithm_fit():
    """Sec. 8.3: all algorithms resident on one 120-BRAM FPGA."""
    rows = []
    for mem, label in [(FPGA_DP, "ours"), (None, "ours_lc")]:
        total = 0
        from repro.core.linebuffer import FPGA_DPLC
        cfg = FPGA_DPLC if mem is None else mem
        for name in ALGOS:
            if name in ("canny-s", "harris-s"):
                continue  # paper: "all six algorithms"
            dag = algorithms.ALGORITHMS[name]()
            plan = compile_pipeline(dag, 480, mem=cfg)
            total += plan.alloc.total_blocks
        rows.append(f"fpga_fit_{label},0,brams={total};"
                    f"fits_120={total <= 120};paper_lc=84")
    return rows
