"""Device time in collectives, chip by chip: the arithmetic the mesh cell's
readers share.

A collective's device operations are named by their HLO text, which starts
with ``%`` and the opcode: ``collective-permute``, ``all-gather``,
``all-reduce`` or ``all-to-all``, an asynchronous one split into its
``-start`` and ``-done`` halves.  A run with no collective on the path, or
no trace, leaves these readers nothing to read: they return None and never
raise.
"""

from __future__ import annotations

import re
from typing import List, Tuple

from bench import harness
from bench import trace as btrace

COLLECTIVE_OP = re.compile(r"^%(collective-permute|all-gather|all-reduce|all-to-all)(-start|-done)?([.\s=]|$)")


def per_chip(rec) -> List[Tuple[int, int, int]]:
    """``(window, busy, collective)`` nanoseconds of each of the run's chips
    in the window: the union of all its operations, and of its collectives."""
    if rec.trace is None:
        return []
    t0, t1 = rec.trace.window(harness.WINDOW)
    out = []
    for ops in rec.trace.devices[: rec.chips]:
        coll = [op for op in ops if COLLECTIVE_OP.match(op[2])]
        out.append((t1 - t0, rec.trace.busy_ns(ops, t0, t1), btrace._union(coll, t0, t1)))
    return out


def collective_chips(rec) -> List[Tuple[int, int, int]]:
    """``per_chip``, or nothing when no chip ran a collective in the window."""
    chips = per_chip(rec)
    return chips if any(c for _, _, c in chips) else []
