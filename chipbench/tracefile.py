"""Profiler traces: capture one, and reduce it to device busy time, idle
share, kernel time, collective time and the breakdown of a result line.

A trace is reduced to a flat list of :class:`Event` (plane, line, name,
start, end in ns on the profiler's one clock, and a device operation's
scope), so the reductions below can be checked on a hand-built list.
Device planes are the ``/device:TPU:n`` planes; an operation's interval
comes from their ``XLA Ops`` line, a jitted program's from ``XLA
Modules``.  The harness marks the traced window
with a host annotation named :data:`WINDOW`.
"""

from __future__ import annotations

import contextlib
import functools
import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Tuple

WINDOW = "chipbench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# the stat of a device operation's metadata that holds its HLO ``op_name``:
# the jitted function and the ``jax.named_scope`` names it was traced in,
# as ``jit(f)/attn/dot_general:`` on a TPU v5e
SCOPE_STAT = "tf_op"
COLLECTIVE = \
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    end_ns: float
    scope: str = ""


class Capture:
    """A JAX profiler trace of the stretch from :meth:`start` to
    :meth:`stop`, marked by a :data:`WINDOW` annotation.  The profile is
    written to a temporary directory under ``TMPDIR`` and removed once
    :meth:`stop` has read it."""

    def __init__(self):
        self._dir = None
        self._mark = None

    def start(self) -> None:
        import jax
        self._dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        # no Python function tracing: it costs every host call of the
        # program, and only the harness's own annotations are read
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._mark = jax.profiler.TraceAnnotation(WINDOW)
        self._mark.__enter__()

    def stop(self) -> List[Event]:
        import jax
        try:
            self._mark.__exit__(None, None, None)
            jax.profiler.stop_trace()
            return load(self._dir)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)


def annotate(name: str, enabled: bool):
    """A host annotation ``chipbench.<name>`` in the trace, or nothing."""
    if not enabled:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(f"chipbench.{name}")


def load(directory: str) -> List[Event]:
    """Every event of the ``.xplane.pb`` profile under ``directory``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one profile under {directory}, "
                           f"found {len(paths)}")
    scopes = op_scopes(paths[0])
    out: List[Event] = []
    for plane in ProfileData.from_file(paths[0]).planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.end_ns),
                                 scopes.get((plane.name, e.name), "")))
    return out


@functools.lru_cache(maxsize=None)
def _xspace():
    """The message class of the part of the profiler's ``XSpace``
    (``tsl/profiler/protobuf/xplane.proto``, same field numbers) that holds
    each operation's metadata, whose stats ``ProfileData`` does not show.
    A map is read in its wire form, repeated key/value entries; strings
    are read as bytes; every other field is skipped unparsed."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(name="chipbench_xspace.proto",
                                            package="chipbench_xspace")
    for name, fields in {
            "Stat": [("metadata_id", 1, "int64"), ("str_value", 5, "bytes"),
                     ("ref_value", 7, "uint64")],
            "EventMetadata": [("name", 2, "bytes"),
                              ("display_name", 4, "bytes"),
                              ("stats", 5, "*Stat")],
            "StatMetadata": [("name", 2, "bytes")],
            "EventMetadataEntry": [("key", 1, "int64"),
                                   ("value", 2, "EventMetadata")],
            "StatMetadataEntry": [("key", 1, "int64"),
                                  ("value", 2, "StatMetadata")],
            "Plane": [("name", 2, "bytes"),
                      ("event_metadata", 4, "*EventMetadataEntry"),
                      ("stat_metadata", 5, "*StatMetadataEntry")],
            "Space": [("planes", 1, "*Plane")]}.items():
        m = fd.message_type.add(name=name)
        for fname, number, kind in fields:
            f = m.field.add(name=fname, number=number)
            f.label = F.LABEL_REPEATED if kind[0] == "*" else \
                F.LABEL_OPTIONAL
            kind = kind.lstrip("*")
            if kind in ("int64", "uint64", "bytes"):
                f.type = getattr(F, f"TYPE_{kind.upper()}")
            else:
                f.type, f.type_name = F.TYPE_MESSAGE, \
                    f".chipbench_xspace.{kind}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench_xspace.Space"))


def op_scopes(path: str) -> Dict[Tuple[str, str], str]:
    """(device plane, operation name) -> the operation's
    :data:`SCOPE_STAT`, read from the operations' metadata in the
    ``.xplane.pb`` profile at ``path``; an operation keyed by its name and
    by its display name."""
    space = _xspace()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    out: Dict[Tuple[str, str], str] = {}
    for plane in space.planes:
        pname = plane.name.decode(errors="replace")
        if not pname.startswith("/device:"):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        for entry in plane.event_metadata:
            meta = entry.value
            for st in meta.stats:
                if stat_names.get(st.metadata_id) != SCOPE_STAT.encode():
                    continue
                scope = (st.str_value or stat_names.get(st.ref_value, b"")) \
                    .decode(errors="replace")
                for n in (meta.name, meta.display_name):
                    if n:
                        out[(pname, n.decode(errors="replace"))] = scope
    return out


# --------------------------------------------------------------- reduction
def window(events: Iterable[Event]) -> Tuple[float, float]:
    """(start, end) ns of the harness's window annotation."""
    marks = [e for e in events if e.name == WINDOW]
    if len(marks) != 1:
        raise RuntimeError(f"expected one {WINDOW!r} annotation, found "
                           f"{len(marks)}")
    return marks[0].start_ns, marks[0].end_ns


def device_planes(events: Iterable[Event]) -> List[str]:
    return sorted({e.plane for e in events
                   if e.plane.startswith("/device:TPU:")},
                  key=lambda p: int(p.rsplit(":", 1)[1]))


def _clip(iv, lo, hi):
    s, e = max(iv[0], lo), min(iv[1], hi)
    return (s, e) if e > s else None


def merge(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Union of intervals, as sorted disjoint [start, end] pairs."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _line_events(events, plane, line):
    """Events of ``line`` on ``plane``; every line of the plane where it
    has no line of that name."""
    on_plane = [e for e in events if e.plane == plane]
    named = [e for e in on_plane if e.line == line]
    return named if named else on_plane


def busy_ns(events: List[Event], plane: str, lo: float, hi: float) -> float:
    """ns within [lo, hi] in which some operation ran on ``plane``."""
    ivs = [_clip((e.start_ns, e.end_ns), lo, hi)
           for e in _line_events(events, plane, OPS_LINE)]
    return sum(e - s for s, e in merge(iv for iv in ivs if iv))


def busy_s(events: List[Event]) -> Tuple[float, float]:
    """(busy seconds averaged over the device planes, window seconds)."""
    lo, hi = window(events)
    planes = device_planes(events)
    if not planes:
        raise RuntimeError("the trace holds no device plane")
    busy = sum(busy_ns(events, p, lo, hi) for p in planes) / len(planes)
    return busy * 1e-9, (hi - lo) * 1e-9


def matching(events: List[Event], plane: str, line: str,
             pattern: str) -> List[Event]:
    """Events of ``line`` on ``plane`` inside the window whose name matches
    the regular expression ``pattern``."""
    lo, hi = window(events)
    rx = re.compile(pattern)
    return [e for e in events if e.plane == plane and e.line == line
            and rx.search(e.name) and e.start_ns >= lo and e.end_ns <= hi]


def total_ns(events: Iterable[Event]) -> float:
    return sum(e.end_ns - e.start_ns for e in events)


def collective_ns(events: List[Event], plane: str) -> float:
    """Device ns of collective operations on ``plane`` in the window."""
    return total_ns(matching(events, plane, OPS_LINE, COLLECTIVE))


def leaves(events: List[Event]) -> List[Event]:
    """The events that contain no other event of the list: a loop's
    operation holds its body's operations on the same line."""
    out, stack = [], []
    for e in sorted(events, key=lambda e: (e.start_ns, -e.end_ns)):
        while stack and stack[-1][0].end_ns <= e.start_ns:
            top, has_child = stack.pop()
            if not has_child:
                out.append(top)
        if stack and e.end_ns <= stack[-1][0].end_ns:
            stack[-1][1] = True
        stack.append([e, False])
    out += [e for e, has_child in stack if not has_child]
    return out


def op_name(name: str) -> str:
    """An operation's instruction name: the trace gives TPU operations as
    their whole HLO text, ``%fusion.3 = bf16[...] fusion(...)``."""
    return name.split(" = ", 1)[0].lstrip("%")


def breakdown(events: List[Event], top: int = 10) -> Dict[str, list]:
    """The device operations that took most time on the first device (the
    innermost operations, by instruction name), and the longest idle gaps
    there, each named by the innermost harness annotation
    (``chipbench.*``) the host was in at the gap's middle."""
    lo, hi = window(events)
    plane = device_planes(events)[0]
    ops = _line_events(events, plane, OPS_LINE)
    by_name: Dict[str, float] = defaultdict(float)
    for e in leaves(ops):
        iv = _clip((e.start_ns, e.end_ns), lo, hi)
        if iv:
            by_name[op_name(e.name)] += (iv[1] - iv[0]) * 1e-9
    device_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]

    busy = merge(iv for iv in (_clip((e.start_ns, e.end_ns), lo, hi)
                               for e in ops) if iv)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    marks = [e for e in events if e.name.startswith("chipbench.")
             and e.name != WINDOW and not e.plane.startswith("/device:")]
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        inside = [m for m in marks if m.start_ns <= mid <= m.end_ns]
        label = max(inside, key=lambda m: m.start_ns).name if inside \
            else "host:other"
        named.append([label, (e - s) * 1e-9])
    return dict(device_ops=[[n, s] for n, s in device_ops], idle_gaps=named)

