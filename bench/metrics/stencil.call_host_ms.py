"""Median host milliseconds of ``StencilObject.__call__`` until it returns,
with no wait for the device, over the window's calls."""

import statistics


def read(rec):
    calls = rec.counters.get("call_host_s")
    return statistics.median(calls) * 1e3 if calls else None
