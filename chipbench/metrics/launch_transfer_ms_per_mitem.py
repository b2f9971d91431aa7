"""Host milliseconds per million work-items of the launch's transfers,
from the program's spans in the trace: ``launch:h2d`` (image, immediates
and inputs to the device) and ``launch:d2h`` (the output back, unpadded),
summed over every thread."""

from chipbench import programspans


def read(r):
    ev = r.get("events")
    return programspans.ms_per_mitem(programspans.total_ns(
        ev, ["launch:h2d", "launch:d2h"]), r.get("items"))
