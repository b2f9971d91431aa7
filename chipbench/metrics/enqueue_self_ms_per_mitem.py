"""Host milliseconds per million work-items of the Session's and queue's
own bookkeeping in a launch: the self time of the program's
``session:enqueue`` spans in the trace, less the ``launch:*`` spans
inside them (queue lookup, the modelled timeline, ``Buffer`` wrapping,
the executor image and call, retries), summed over every thread."""

from chipbench import programspans


def read(r):
    ev = r.get("events")
    return programspans.ms_per_mitem(programspans.self_ns(
        ev, "session:enqueue", programspans.LAUNCH), r.get("items"))
