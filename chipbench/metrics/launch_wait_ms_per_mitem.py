"""Host milliseconds per million work-items that launches wait for the
executor's result, from the program's ``launch:wait`` spans in the trace:
the transfer's completion, queueing behind other tenants' launches and
the executor itself, summed over every thread."""

from chipbench import programspans


def read(r):
    ev = r.get("events")
    return programspans.ms_per_mitem(programspans.total_ns(
        ev, ["launch:wait"]), r.get("items"))
