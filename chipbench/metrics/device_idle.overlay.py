"""Share of the traced window in which no operation ran on the device
(1 - union of busy intervals / window), averaged over the chips."""

from chipbench import tracefile


def read(r):
    ev = r.get("events")
    if not ev or not tracefile.device_planes(ev):
        return None
    busy, window = tracefile.busy_s(ev)
    return 100.0 * (1.0 - busy / window)
