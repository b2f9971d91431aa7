"""Device time by the program's ``jax.named_scope`` names: the leaf
operations of a traced window whose scope (:attr:`tracefile.Event.scope`,
the HLO ``op_name``, as ``jit(serve_step)/mamba_layer/ssm_update/mul``)
holds a name as one of its parts.  A fused operation carries the scope of
the operation its fusion was rooted at.

Operations that XLA adds carry no ``op_name``: the asynchronous
``copy-start``/``copy-done`` and ``slice-start``/``slice-done`` pairs that
stream weights and state into on-chip memory, layout copies, concatenating
bitcasts.  Each is given the scope of its nearest user that has one
(:func:`inherited`, from the compiled program's HLO text), so that the
time a scope waits for its operands is counted in that scope."""

from __future__ import annotations

import bisect
import re
from collections import defaultdict, deque
from typing import Dict, List, Optional

from chipbench import tracefile
from chipbench.tracefile import Event

STEP = r"serve_step"

_INSTR = re.compile(r"\s*(?:ROOT\s+)?%([\w.\-]+) = ")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _close(s: str, i: int) -> int:
    """The index after the parenthesis that closes the one at ``s[i]``."""
    depth = 0
    for j in range(i, len(s)):
        depth += {"(": 1, ")": -1}.get(s[j], 0)
        if depth == 0:
            return j + 1
    raise ValueError(f"unbalanced parentheses in {s[:80]!r}")


def parse_hlo(text: str) -> Dict[str, tuple]:
    """Instruction name -> (operand names, ``op_name`` or "") for every
    instruction of every computation of an HLO module's text."""
    out: Dict[str, tuple] = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        i = m.end()
        # the result type: a tuple's holds parentheses and spaces, an
        # array's layout parentheses but no space
        i = _close(line, i) if line[i] == "(" else line.index(" ", i)
        j = line.index("(", i)
        k = _close(line, j)
        scope = _OP_NAME.search(line, k)
        out[m.group(1)] = (_OPERAND.findall(line[j:k]),
                           scope.group(1) if scope else "")
    return out


def inherited(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> scope for each instruction of the module that
    carries no ``op_name`` but has a user, or a user's user, that does:
    the ``op_name`` of the nearest such user, breadth first through the
    users without one.  An instruction whose users never carry one (the
    outputs' tuple) is left out."""
    instrs = parse_hlo(hlo_text)
    users: Dict[str, List[str]] = defaultdict(list)
    for name, (operands, _) in instrs.items():
        for o in operands:
            users[o].append(name)
    out: Dict[str, str] = {}
    for name, (_, scope) in instrs.items():
        if scope:
            continue
        seen, todo = {name}, deque(users[name])
        while todo:
            u = todo.popleft()
            if instrs[u][1]:
                out[name] = instrs[u][1]
                break
            for v in users[u]:
                if v not in seen:
                    seen.add(v)
                    todo.append(v)
    return out


def scope_ns(events: List[Event], plane: str, name: str,
             inherit: Dict[str, str]) -> float:
    """Device ns inside the window of the leaf operations on ``plane``
    whose scope holds ``name``.  An operation without a scope inside a
    ``serve_step`` program takes its scope from ``inherit`` (see
    :func:`inherited`) under its instruction name."""
    lo, hi = tracefile.window(events)
    rx = re.compile(rf"(^|/){re.escape(name)}(/|$)")
    steps = sorted((e.start_ns, e.end_ns) for e in tracefile.matching(
        events, plane, tracefile.MODULES_LINE, STEP)) if inherit else []
    starts = [s for s, _ in steps]

    def scope(e: Event) -> str:
        if e.scope:
            return e.scope
        i = bisect.bisect_right(starts, e.start_ns) - 1
        if i < 0 or e.end_ns > steps[i][1]:
            return ""
        return inherit.get(tracefile.op_name(e.name), "")

    ops = [e for e in events if e.plane == plane
           and e.line == tracefile.OPS_LINE]
    return sum(e.end_ns - e.start_ns for e in tracefile.leaves(ops)
               if e.start_ns >= lo and e.end_ns <= hi and rx.search(scope(e)))


def ms_per_step(events: Optional[List[Event]], name: str,
                inherit: Dict[str, str]) -> Optional[float]:
    """Device ms per serve step of scope ``name`` on the first device: its
    leaf operations' time, with ``inherit`` as in :func:`scope_ns`, over
    the number of ``serve_step`` programs in the window; None where the
    trace holds no such step or operation."""
    planes = tracefile.device_planes(events or [])
    if not planes:
        return None
    steps = tracefile.matching(events, planes[0], tracefile.MODULES_LINE,
                               STEP)
    ns = scope_ns(events, planes[0], name, inherit) if steps else 0.0
    return 1e-6 * ns / len(steps) if ns > 0 else None
