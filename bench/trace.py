"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the metrics read.

A device plane (``/device:TPU:<n>``) holds the operations that ran on that
chip on its ``XLA Ops`` line, and one event per program execution on its
``XLA Modules`` line, with start and duration in nanoseconds.  The host plane
(``/host:CPU``) holds one line per thread with its annotations: the
benchmark's ``TraceAnnotation``s, the program's ``jax_profiler_span``s and
JAX's own dispatch events.

Busy time is the union of a device's operation intervals, so overlapping
operations count once; idle share is one minus busy over the window.

Device and host timestamps are aligned only to about a millisecond (on a
v5e a program's device events showed up to 1.2 ms before the host
annotation that dispatched and waited for it), so a call's device time is
never read from inside a host annotation: ``call_times`` takes the last
program executions of the trace, in the order the host dispatched them.
"""

from __future__ import annotations

import heapq
import re
from dataclasses import dataclass
from typing import Dict, List, Tuple

Event = Tuple[int, int, str]  # start ns, end ns, name

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
#: device op events are named by their whole HLO instruction; this many
#: characters tell the instructions of a step apart (a kernel's operands)
NAME_CHARS = 120


@dataclass
class Trace:
    #: per device, in device order: its operations, sorted by start
    devices: List[List[Event]]
    #: per device: its program executions, sorted by start
    modules: List[List[Event]]
    #: host annotations of every thread, sorted by start
    host: List[Event]

    def window(self, name: str) -> Tuple[int, int]:
        """The interval of the first host annotation called ``name``."""
        for start, end, n in self.host:
            if n == name:
                return start, end
        raise KeyError(f"bench: no host annotation {name!r} in the trace")

    def busy_ns(self, ops: List[Event], t0: int, t1: int) -> int:
        return _union(ops, t0, t1)

    def call_times(self, names: List[str], device: int = 0) -> Dict[str, List[int]]:
        """Device ns of each of the trace's last ``len(names)`` program
        executions, by the name of the call that the host dispatched in that
        order (each waited for before the next); empty when the trace holds
        fewer executions."""
        last = self.modules[device][-len(names):] if names else []
        if len(last) < len(names):
            return {}
        out: Dict[str, List[int]] = {}
        for name, (s, e, _) in zip(names, last):
            out.setdefault(name, []).append(e - s)
        return out


def _union(ops: List[Event], t0: int, t1: int) -> int:
    """Length of the union of ``ops`` clipped to ``[t0, t1)``; ``ops`` sorted by start."""
    total, cur_s, cur_e = 0, None, None
    for s, e, _ in ops:
        if e <= t0:
            continue
        if s >= t1:
            break
        s, e = max(s, t0), min(e, t1)
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(ops: List[Event], t0: int, t1: int) -> List[Tuple[int, int]]:
    gaps, cursor = [], t0
    for s, e, _ in ops:
        if e <= cursor:
            continue
        if s >= t1:
            break
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < t1:
        gaps.append((cursor, t1))
    return gaps


def load(path) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    lines: Dict[Tuple[int, str], List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines if m or plane.name == HOST_PLANE else ():
            events = [(int(e.start_ns), int(e.end_ns), e.name) for e in line.events]
            if not m:
                host.extend(events)
            elif line.name in (OPS_LINE, MODULES_LINE):
                lines.setdefault((int(m.group(1)), line.name), []).extend(events)
    ids = sorted({d for d, _ in lines})
    return Trace(devices=[sorted(lines.get((d, OPS_LINE), [])) for d in ids],
                 modules=[sorted(lines.get((d, MODULES_LINE), [])) for d in ids], host=sorted(host))


def breakdown(trace: Trace, t0: int, t1: int, top: int = 10) -> Dict[str, List[List]]:
    """On the first device within ``[t0, t1)``: the operations that took most
    time (grouped by the start of their HLO text), and idle time by what the
    host was doing, which is the innermost host event that spans the middle
    of each gap."""
    ops = trace.devices[0]
    by_op: Dict[str, int] = {}
    for s, e, n in ops:
        if e > t0 and s < t1:
            key = n[:NAME_CHARS]
            by_op[key] = by_op.get(key, 0) + min(e, t1) - max(s, t0)
    by_host: Dict[str, int] = {}
    gaps = sorted(_gaps(ops, t0, t1), key=lambda g: g[0] + g[1])
    active: List[Tuple[int, int, str]] = []  # (duration, end, name)
    i = 0
    for gs, ge in gaps:
        mid = (gs + ge) // 2
        while i < len(trace.host) and trace.host[i][0] <= mid:
            s, e, n = trace.host[i]
            heapq.heappush(active, (e - s, e, n))
            i += 1
        while active and active[0][1] <= mid:
            heapq.heappop(active)
        name = active[0][2] if active else "(no host annotation)"
        by_host[name] = by_host.get(name, 0) + ge - gs

    def ranked(d: Dict[str, int]) -> List[List]:
        return [[k, v / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"device_ops": ranked(by_op), "idle_gaps": ranked(by_host)}
