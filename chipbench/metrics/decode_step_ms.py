"""Device milliseconds per serve step: the mean duration of the
``serve_step`` program's events on the first device in the trace."""

from chipbench import tracefile

PROGRAM = r"serve_step"


def read(r):
    ev = r.get("events")
    planes = tracefile.device_planes(ev or [])
    if not planes:
        return None
    steps = tracefile.matching(ev, planes[0], tracefile.MODULES_LINE,
                               PROGRAM)
    return 1e3 * tracefile.total_ns(steps) * 1e-9 / len(steps) if steps \
        else None
