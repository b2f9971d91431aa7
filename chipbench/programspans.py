"""The program's own host spans in a profiler trace.

While the profiler collects, the program writes its spans
(``repro.obs.trace``) as host events of the trace, named as the program
names them: ``launch:stage``, ``launch:h2d``, ``launch:wait``,
``launch:d2h``, ``buffer:read``, ``session:enqueue``, ``jit:build``,
``jit:template_build`` and the other ``jit:*`` stages.  These reductions
take the flat event list of :mod:`chipbench.tracefile` and sum them
within the harness's window, per thread line (plane and line name).

The profiler names a thread's line by the operating system's thread
name, which Python does not set, so the lines of several Python threads
may share one name.  Self time therefore subtracts the named children of
a span, never whatever else lies inside it in time: a child is counted
once if it lies inside some event of the parent's name on its line, so
another thread's spans that overlap in time are not taken for children.
Every reduction returns ``None`` where the trace holds none of the
events it reads, as a trace of a program without these spans does.
"""

from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from chipbench import tracefile
from chipbench.tracefile import Event

#: the executor launch's spans on the host, in their order
LAUNCH = ("launch:stage", "launch:h2d", "launch:wait", "launch:d2h")


def in_window(events: Optional[Sequence[Event]], names: Iterable[str]
              ) -> Optional[List[Tuple[Event, float, float]]]:
    """The host events named in ``names``, each with its interval clipped
    to the window, dropping those wholly outside it; ``None`` where the
    trace holds no event of those names at all."""
    names = set(names)
    found = [e for e in events or () if e.name in names
             and not e.plane.startswith("/device:")]
    if not found:
        return None
    lo, hi = tracefile.window(events)
    out = []
    for e in found:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append((e, s, t))
    return out


def count(events: Optional[Sequence[Event]], name: str) -> Optional[int]:
    """Events named ``name`` that overlap the window."""
    got = in_window(events, [name])
    return None if got is None else len(got)


def total_ns(events: Optional[Sequence[Event]], names: Iterable[str]
             ) -> Optional[float]:
    """Window-clipped ns of the host events named in ``names``, summed
    over every thread (overlapping threads each count)."""
    got = in_window(events, names)
    return None if got is None else sum(t - s for _, s, t in got)


def self_ns(events: Optional[Sequence[Event]], parent: str,
            children: Iterable[str]) -> Optional[float]:
    """Window-clipped ns of the events named ``parent``, less those of the
    events named in ``children`` that lie inside a ``parent`` event on the
    same thread line, each child counted once."""
    got = in_window(events, [parent, *children])
    if got is None or not any(e.name == parent for e, _, _ in got):
        return None
    by_line: Dict[Tuple[str, str], List] = defaultdict(list)
    for item in got:
        by_line[(item[0].plane, item[0].line)].append(item)
    out = 0.0
    for items in by_line.values():
        starts, reach = [], []        # parents by start; furthest end yet
        for p0, p1 in sorted((e.start_ns, e.end_ns) for e, _, _ in items
                             if e.name == parent):
            starts.append(p0)
            reach.append(max(p1, reach[-1]) if reach else p1)
        for e, s, t in items:
            if e.name == parent:
                out += t - s
            else:
                k = bisect.bisect_right(starts, e.start_ns)
                if k and reach[k - 1] >= e.end_ns:
                    out -= t - s
    return out


def idle_while_ns(events: Optional[Sequence[Event]], names: Iterable[str]
                  ) -> Optional[float]:
    """ns of the window in which no operation ran on the device and at
    least one thread was inside an event named in ``names``, averaged over
    the device planes; ``None`` without such events or device planes."""
    got = in_window(events, names)
    planes = tracefile.device_planes(events or [])
    if got is None or not planes:
        return None
    lo, hi = tracefile.window(events)
    host = [(s, t) for _, s, t in got]
    out = 0.0
    for plane in planes:
        ops = [(max(e.start_ns, lo), min(e.end_ns, hi)) for e in
               tracefile._line_events(events, plane, tracefile.OPS_LINE)]
        # |host and idle| = |host or busy| - |busy|
        either = tracefile.merge(host + [iv for iv in ops if iv[1] > iv[0]])
        out += sum(t - s for s, t in either) - \
            tracefile.busy_ns(events, plane, lo, hi)
    return out / len(planes)


def ms_per_mitem(ns: Optional[float], items) -> Optional[float]:
    """Milliseconds per million work-items, or ``None``."""
    if ns is None or not items:
        return None
    return 1e-6 * ns / (items * 1e-6)
