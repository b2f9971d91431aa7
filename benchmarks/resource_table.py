"""Paper Table III: per-benchmark overlay implementation metrics —
PAR time, pipeline depth / Fmax model, resources (FUs, DSPs, wires),
config size, and the paper's measured direct-FPGA comparison columns for
reference."""

from __future__ import annotations

from typing import Dict, List

from repro.configs.paper_suite import BENCHMARKS
from repro.core.jit import jit_compile
from repro.core.options import CompileOptions
from repro.core.overlay import OverlaySpec

SPEC = OverlaySpec(width=8, height=8, dsp_per_fu=2)

# paper Table III 'Direct FPGA implementations' (Vivado 2014.2, XC7Z020)
PAPER_DIRECT = {
    "chebyshev": dict(par_s=240, fmax=225, dsp=48, slices=251),
    "sgfilter": dict(par_s=396, fmax=185, dsp=100, slices=797),
    "mibench": dict(par_s=245, fmax=230, dsp=21, slices=403),
    "qspline": dict(par_s=242, fmax=165, dsp=36, slices=307),
    "poly1": dict(par_s=256, fmax=175, dsp=36, slices=425),
    "poly2": dict(par_s=270, fmax=172, dsp=40, slices=453),
}


def run() -> List[Dict]:
    rows = []
    for name, (src, paper_replicas, _) in sorted(BENCHMARKS.items()):
        ck = jit_compile(src, SPEC,
                         opts=CompileOptions(max_replicas=paper_replicas))
        res = ck.resources()
        direct = PAPER_DIRECT[name]
        rows.append({
            "name": f"resource_table/{name}({ck.plan.replicas})",
            "us_per_call": ck.par_time_ms * 1e3,
            "derived": (
                f"fus={res['fus']} dsp={res['dsp']} wires={res['wires']} "
                f"cfg_bytes={res['config_bytes']} "
                f"depth={ck.pipeline_depth}cyc fmax={SPEC.fclk_mhz:.0f}MHz "
                f"paper_direct_par={direct['par_s']}s "
                f"paper_direct_fmax={direct['fmax']}MHz "
                f"par_speedup_vs_paper_direct="
                f"{direct['par_s'] * 1e3 / max(ck.par_time_ms, 1e-9):.0f}x"),
        })
    return rows
