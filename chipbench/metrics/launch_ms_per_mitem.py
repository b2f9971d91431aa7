"""Host milliseconds per million work-items of ``Session.enqueue(...)
.wait()`` and the output read, summed over the window's launches."""


def read(r):
    items = r["spans"].totals.get("launch.items", 0)
    return 1e3 * r["spans"].seconds["launch"] / (items * 1e-6) if items \
        else None
