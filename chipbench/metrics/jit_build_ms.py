"""Mean milliseconds of the program's ``jit:build`` spans in the trace:
the overlay JIT's own time on its worker per compile, beside ``jit_ms``,
which times ``Session.compile(...).result()`` from the tenant."""

from chipbench import programspans


def read(r):
    ev = r.get("events")
    n = programspans.count(ev, "jit:build")
    return 1e-6 * programspans.total_ns(ev, ["jit:build"]) / n if n \
        else None
