"""The overlay executor's share of its HBM roofline: the bytes the
OpenCL kernels' own inputs and outputs need ((n_in + n_out) x items x 4,
never the executor's padded image) over peak HBM bandwidth, divided by the
executor's device time.  No compute bound: the VPU has no published
peak."""

from pathlib import Path

from chipbench import harness


def read(r):
    kernel = harness.load_module(Path(__file__).with_name(
        "exec_kernel_ms_per_mitem.py"))
    t = kernel.kernel_seconds(r.get("events"))
    if not t or not r.get("overlay_bytes") or not r.get("peaks"):
        return None
    return 100.0 * r["overlay_bytes"] / r["peaks"]["hbm_bw"] / t
