"""End-to-end: Tbl. 3 structure, compile+verify, baselines ordering, LC."""
import pytest

from repro.core import DP, DPLC, SP, algorithms, compile_pipeline
from repro.core.baselines import (darkroom_linearize, darkroom_schedule,
                                  fixynn_schedule, soda_allocate)
from repro.core.linebuffer import (ASIC_SRAM_BITS, FPGA_DP, FPGA_DPLC,
                                   allocate)
from repro.core.power import memory_power

TABLE3 = {  # name -> (stages, mc_stages)
    "canny-s": (9, 0), "canny-m": (10, 1),
    "harris-s": (7, 0), "harris-m": (7, 1),
    "unsharp-m": (5, 1), "xcorr-m": (3, 1), "denoise-m": (5, 2),
}


def test_paper_catalogue_is_table3():
    """The paper tables reproduce Tbl. 3 alone, not every registered
    pipeline."""
    from repro.configs import PIPELINES
    assert sorted(algorithms.PAPER_ALGORITHMS) == sorted(TABLE3)
    assert sorted(PIPELINES) == sorted(TABLE3)
    assert set(TABLE3) < set(algorithms.ALGORITHMS)


@pytest.mark.parametrize("name", list(TABLE3))
def test_table3_structure(name):
    dag = algorithms.ALGORITHMS[name]()
    stages, mc = TABLE3[name]
    assert dag.num_stages() == stages
    assert len(dag.multi_consumer_stages()) == mc


@pytest.mark.parametrize("name", list(TABLE3))
@pytest.mark.parametrize("mem", [DP, SP, DPLC], ids=["DP", "SP", "DPLC"])
def test_compile_and_verify(name, mem):
    dag = algorithms.ALGORITHMS[name]()
    plan = compile_pipeline(dag, 48, mem=mem)
    rep = plan.verify(64)
    assert rep.ok, rep.violations
    assert rep.throughput == 1.0


@pytest.mark.parametrize("name", list(TABLE3))
def test_darkroom_never_smaller(name):
    """Linearization adds relay buffers: Darkroom >= Ours in memory."""
    dag = algorithms.ALGORITHMS[name]()
    w = 48
    ours = compile_pipeline(dag, w, mem=DP)
    lin, dsched = darkroom_schedule(dag, w)
    dalloc = allocate(lin, dsched, {s: DP for s in lin.stages}, w)
    assert dalloc.total_alloc_bits >= ours.total_alloc_bits


@pytest.mark.parametrize("name", list(TABLE3))
def test_fixynn_never_smaller(name):
    dag = algorithms.ALGORITHMS[name]()
    ours = compile_pipeline(dag, 48, mem=DP)
    fx = compile_pipeline(dag, 48, mem=SP)
    assert fx.total_alloc_bits >= ours.total_alloc_bits


def test_xcorr_darkroom_blowup():
    """Paper Sec. 8.3: linearizing xcorr-m replicates the tall buffer."""
    dag = algorithms.ALGORITHMS["xcorr-m"]()
    w = 48
    ours = compile_pipeline(dag, w, mem=DP)
    lin, dsched = darkroom_schedule(dag, w)
    dalloc = allocate(lin, dsched, {s: DP for s in lin.stages}, w)
    assert dalloc.total_alloc_bits >= 1.8 * ours.total_alloc_bits


def test_lc_noop_when_blocks_hold_one_line():
    """Paper Sec. 7: coalescing applies at 320p but not 1080p."""
    dag = algorithms.ALGORITHMS["canny-m"]()
    ours = compile_pipeline(dag, 1920, mem=DP)
    lc = compile_pipeline(dag, 1920, mem=DPLC)
    assert lc.total_alloc_bits == ours.total_alloc_bits


def test_lc_saves_at_320p():
    for name in TABLE3:
        dag = algorithms.ALGORITHMS[name]()
        ours = compile_pipeline(dag, 480, mem=DP)
        lc = compile_pipeline(dag, 480, mem=DPLC)
        assert lc.total_alloc_bits < ours.total_alloc_bits, name
        assert lc.verify(96).ok


def test_darkroom_linearize_single_consumer_patterns():
    """After linearization every buffer has <= 2 effective accessors."""
    from repro.core.pruning import buffer_accessors
    for name in ["canny-m", "unsharp-m", "denoise-m", "harris-m"]:
        dag = algorithms.ALGORITHMS[name]()
        lin, ties = darkroom_linearize(dag)
        for p in lin.topo_order:
            if lin.stages[p].is_output or not lin.out_edges(p):
                continue
            accs = buffer_accessors(lin, p, ties)
            assert len(accs) <= 2, (name, p, accs)


def test_soda_sizing_single_consumer():
    """SODA saves the head line as DFFs: SRAM = (sh-1) lines per buffer."""
    dag = algorithms.ALGORITHMS["canny-s"]()
    w = 48
    soda = soda_allocate(dag, w, ASIC_SRAM_BITS, sized=True)
    ours = compile_pipeline(dag, w, mem=DP)
    # SODA SRAM bits strictly below ours (paper: ours +31% over SODA)
    assert soda.alloc.total_logical_bits < ours.alloc.total_logical_bits
    assert soda.dff_pixels > 0


def test_fpga_configs_compile():
    dag = algorithms.ALGORITHMS["canny-m"]()
    plan = compile_pipeline(dag, 480, mem=FPGA_DP)
    lc = compile_pipeline(dag, 480, mem=FPGA_DPLC)
    assert plan.verify(64).ok and lc.verify(64).ok
    assert lc.alloc.total_blocks < plan.alloc.total_blocks


def test_pseudo_rtl_dump():
    dag = algorithms.ALGORITHMS["unsharp-m"]()
    plan = compile_pipeline(dag, 48, mem=DP)
    rtl = plan.pseudo_rtl()
    assert "linebuffer" in rtl and "stage" in rtl
