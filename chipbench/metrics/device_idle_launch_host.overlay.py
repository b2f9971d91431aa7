"""Share of the traced window in which no operation ran on the device
while at least one thread was inside the launch's host copies or
transfers (the program's ``launch:stage``, ``launch:h2d``, ``launch:d2h``
and ``buffer:read`` spans), averaged over the chips."""

from chipbench import programspans, tracefile

HOST = ("launch:stage", "launch:h2d", "launch:d2h", "buffer:read")


def read(r):
    ev = r.get("events")
    ns = programspans.idle_while_ns(ev, HOST)
    if ns is None:
        return None
    lo, hi = tracefile.window(ev)
    return 100.0 * ns / (hi - lo)
