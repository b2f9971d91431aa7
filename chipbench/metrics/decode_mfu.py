"""The whole decode step's share of the chip's peak: for each step of the
traced window the least time its work allows (the larger of its FLOPs
over peak FLOP/s and its bytes over peak HBM bandwidth, counted from the
configuration's shapes: every weight once per chip sharing a layer, and
the keys and values each sequence holds), summed and divided by the
traced window's wall time.  Decode is bound by the bytes."""

from chipbench import counts, tracefile


def read(r):
    ev = r.get("events")
    if not ev or not r.get("step_counts") or not r.get("peaks"):
        return None
    lo, hi = tracefile.window(ev)
    least = sum(counts.least_seconds(s["flops"], s["bytes"], r["peaks"])
                for s in r["step_counts"])
    return 100.0 * least / ((hi - lo) * 1e-9)
