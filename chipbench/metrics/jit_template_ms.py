"""Milliseconds of the program's ``jit:template_build`` spans in the trace
per ``jit:build``: the cost of building templates (a template-cache miss
places and routes one replica), the miss ratio included."""

from chipbench import programspans


def read(r):
    ev = r.get("events")
    n = programspans.count(ev, "jit:build")
    if not n:
        return None
    # no template built in the window: every build hit the template cache
    t = programspans.total_ns(ev, ["jit:template_build"]) or 0.0
    return 1e-6 * t / n
