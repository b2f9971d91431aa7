"""Device milliseconds of the overlay executor per million work-items:
the ``overlay_execute`` program's events on the first device, over every
launch of the traced window."""

from chipbench import tracefile

PROGRAM = r"overlay_execute"


def kernel_seconds(events):
    planes = tracefile.device_planes(events or [])
    if not planes:
        return None
    ns = tracefile.total_ns(tracefile.matching(
        events, planes[0], tracefile.MODULES_LINE, PROGRAM))
    return ns * 1e-9 if ns > 0 else None


def read(r):
    t = kernel_seconds(r.get("events"))
    return 1e3 * t / (r["items"] * 1e-6) if t and r.get("items") else None
