"""The Mamba layers' share of their roofline: the least time their work
allows per step (the larger of the ``mamba_layer`` FLOPs over peak FLOP/s
and its bytes over peak HBM bandwidth, from ``counts_hybrid``: every
Mamba weight once, the SSM and conv state read and written), averaged
over the traced steps, over the device time per step in that scope
(``mamba_ms.zamba2``: the scope's own operations and the copies and
slices that stream its weights and state), in %.  The bytes bound it."""

from chipbench import counts, scopes


def read(r):
    inherit = r.get("inherited_scopes")
    ms = scopes.ms_per_step(r.get("events"), "mamba_layer", inherit) \
        if inherit is not None else None
    steps, peaks = r.get("step_counts"), r.get("peaks")
    if not ms or not steps or not peaks:
        return None
    least = sum(counts.least_seconds(s["scopes"]["mamba_layer"]["flops"],
                                     s["scopes"]["mamba_layer"]["bytes"],
                                     peaks) for s in steps) / len(steps)
    return 100.0 * least / (ms * 1e-3)
