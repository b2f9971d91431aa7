"""Host milliseconds per million work-items of the launch's host copies,
from the program's spans in the trace: ``launch:stage`` (the inputs
stacked and padded into one float32 array) and ``buffer:read`` (the
output copied out of its ``Buffer``), summed over every thread."""

from chipbench import programspans


def read(r):
    ev = r.get("events")
    return programspans.ms_per_mitem(programspans.total_ns(
        ev, ["launch:stage", "buffer:read"]), r.get("items"))
