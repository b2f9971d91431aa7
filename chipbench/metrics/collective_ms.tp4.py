"""Device milliseconds of collective operations (all-reduce, all-gather,
reduce-scatter, all-to-all, collective-permute) per decode step on the
first device."""

from chipbench import tracefile


def read(r):
    ev = r.get("events")
    planes = tracefile.device_planes(ev or [])
    if not planes or not r.get("kv_lens"):
        return None
    ns = tracefile.collective_ns(ev, planes[0])
    return 1e3 * ns * 1e-9 / len(r["kv_lens"]) if ns > 0 else None
