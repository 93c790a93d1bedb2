"""Arithmetic the readers of the program's own spans share.

The program's tracer writes each span into a collecting profile under its
name (``repro.obs.trace``, the profiler bridge), so its spans sit on the
host plane beside the device's operations, on one clock.  A program that
writes no such span, or runs no kernel of that name, leaves these readers
nothing to read: they return None and never raise.
"""

from __future__ import annotations

import statistics
from typing import Iterable, List, Optional, Tuple

from bench import harness
from bench import trace as btrace

#: the first characters of a generated Pallas kernel's device operation:
#: ``%`` and the codegen's ``KERNEL_PREFIX`` (``repro/core/codegen_pallas.py``)
KERNEL_OP = "%gt_"


def spans_in_window(rec, names: Iterable[str]) -> List[btrace.Event]:
    """The host spans called one of ``names`` that start in the window."""
    if rec.trace is None:
        return []
    t0, t1 = rec.trace.window(harness.WINDOW)
    names = set(names)
    return [ev for ev in rec.trace.host if ev[2] in names and t0 <= ev[0] < t1]


def median_ms(rec, name: str) -> Optional[float]:
    """Median milliseconds of the window's ``name`` spans."""
    spans = spans_in_window(rec, [name])
    return statistics.median(e - s for s, e, _ in spans) / 1e6 if spans else None


def ms_per_request(rec, names: Iterable[str]) -> Optional[float]:
    """Host milliseconds in the window's spans called one of ``names``, over
    the requests answered in the window."""
    spans, done = spans_in_window(rec, names), rec.counters.get("completed")
    return sum(e - s for s, e, _ in spans) / 1e6 / done if spans and done else None


def kernel_share(rec) -> Optional[float]:
    """% of the first device's busy time in the window spent in generated
    kernels; the rest is the code around them (pads, slices, writes)."""
    if rec.trace is None:
        return None
    t0, t1 = rec.trace.window(harness.WINDOW)
    ops = rec.trace.devices[0]
    kernels = btrace._union([op for op in ops if op[2].startswith(KERNEL_OP)], t0, t1)
    busy = rec.trace.busy_ns(ops, t0, t1)
    return 100.0 * kernels / busy if kernels and busy else None


def _merged(spans: List[btrace.Event]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e, _ in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _overlap_ns(a: List[Tuple[int, int]], b: List[Tuple[int, int]]) -> int:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        total += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_share_under(rec, names: Iterable[str]) -> Optional[float]:
    """% of the first device's idle time in the window during which some
    host thread was inside a span called one of ``names``."""
    spans = spans_in_window(rec, names)
    if not spans:
        return None
    t0, t1 = rec.trace.window(harness.WINDOW)
    gaps = btrace._gaps(rec.trace.devices[0], t0, t1)
    idle = sum(e - s for s, e in gaps)
    return 100.0 * _overlap_ns(gaps, _merged(spans)) / idle if idle else None
