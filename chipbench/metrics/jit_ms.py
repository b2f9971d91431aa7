"""Mean host milliseconds of ``Session.compile(...).result()`` over the
window's compiles: the overlay JIT, cold, as a new kernel sees it."""


def read(r):
    n = r["spans"].count.get("jit", 0)
    return 1e3 * r["spans"].seconds["jit"] / n if n else None
