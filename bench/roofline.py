"""Arithmetic the per-layer readers share: a kernel's share of its HBM
roofline, the whole step's, and the device's idle share."""

from __future__ import annotations

import importlib

from bench import harness


def share(rec, stencil: str):
    """% of the HBM roofline of one ``stencil`` call: its algorithmic bytes
    (``bench/work/<stencil>.py``) over the peak bandwidth, divided by the
    mean device time of its program executions among the calls the traced
    run made one at a time after the window (``counters["synced"]``, in
    dispatch order).  The bound is the HBM term alone: the chip publishes no
    float32 vector peak, and every stencil here does a few operations per
    byte."""
    if rec.trace is None:
        return None
    times = rec.trace.call_times(rec.counters.get("synced", [])).get(stencil)
    if not times:
        return None
    work = importlib.import_module(f"bench.work.{stencil}")
    nbytes = work.bytes_moved(rec.counters["stencils"][stencil], rec.counters["itemsize"])
    floor_s = nbytes / harness.peaks(rec.device_kind)["hbm_bytes_per_s"]
    return 100.0 * floor_s / (sum(times) / len(times) / 1e9)


def step_share(rec):
    """% of the HBM roofline of a whole model step: the algorithmic bytes of
    every call of a step (``counters["calls"]`` per stencil) over the peak
    bandwidth, divided by the step's time in the window.  The host's gaps
    and the code around the kernels count against it, so it is at most the
    best kernel's share."""
    c = rec.counters
    nbytes = sum(n * importlib.import_module(f"bench.work.{s}").bytes_moved(c["stencils"][s], c["itemsize"])
                 for s, n in c["calls"].items())
    floor_s = nbytes / harness.peaks(rec.device_kind)["hbm_bytes_per_s"]
    return 100.0 * floor_s / (rec.window_s / c["steps"])


def idle_share(rec):
    """% of the window in which the first device ran no operation."""
    if rec.trace is None:
        return None
    t0, t1 = rec.trace.window(harness.WINDOW)
    return 100.0 * (1.0 - rec.trace.busy_ns(rec.trace.devices[0], t0, t1) / (t1 - t0))
