"""The reduction from a profiler trace to metrics: on hand-made events, and
on a small trace recorded on a TPU v5e and kept beside this file."""

from __future__ import annotations

from pathlib import Path

import pytest

from bench import trace as btrace

#: hdiff then vadv on a 64x64x80 float32 field, each waited for, twice; the
#: same twice more without waiting; then one unrelated program
#: (``bench.hdiff``, ``bench.vadv`` and ``bench.other`` host annotations)
RECORDED = Path(__file__).resolve().parent / "data" / "v5e_dycore_64x64x80.xplane.pb"


def _hand_made() -> btrace.Trace:
    ops = [(10, 20, "fusion.1"), (15, 30, "fusion.2"), (50, 60, "collective-permute.3"), (80, 100, "kernel.4")]
    modules = [(10, 30, "jit_a(1)"), (50, 60, "jit_b(2)"), (80, 100, "jit_a(1)")]
    host = [(0, 120, "bench.window"), (30, 50, "PjitFunction(step)"), (32, 48, "inner"), (60, 80, "bench.wait"),
            (78, 101, "bench.hdiff")]
    return btrace.Trace(devices=[sorted(ops)], modules=[modules], host=sorted(host))


def test_union_window_and_calls_on_hand_made_events():
    t = _hand_made()
    assert t.window("bench.window") == (0, 120)
    assert t.busy_ns(t.devices[0], 0, 120) == 20 + 10 + 20  # [10,30) [50,60) [80,100)
    assert t.busy_ns(t.devices[0], 12, 55) == 18 + 5
    assert t.call_times(["b", "a"]) == {"b": [10], "a": [20]}  # the last two executions
    assert t.call_times(["x"] * 4) == {}  # more calls than executions: nothing to read
    with pytest.raises(KeyError):
        t.window("bench.nothing")


def test_breakdown_names_the_innermost_host_event_of_each_gap():
    b = btrace.breakdown(_hand_made(), 0, 120)
    assert dict(b["device_ops"]) == {"kernel.4": 20e-9, "fusion.2": 15e-9, "fusion.1": 10e-9,
                                     "collective-permute.3": 10e-9}
    # gaps [0,10) [30,50) [60,80) [100,120): their middles fall in
    # bench.window, inner, bench.wait and bench.window
    assert dict(b["idle_gaps"]) == {"bench.window": 30e-9, "inner": 20e-9, "bench.wait": 20e-9}


def test_a_trace_recorded_on_a_v5e():
    t = btrace.load(RECORDED)
    assert len(t.devices) == 1 and len(t.devices[0]) == 302 and len(t.modules[0]) == 9
    assert len([n for _, _, n in t.host if n == "bench.hdiff"]) == 4
    # the last nine programs: four hdiff/vadv pairs, then the unrelated one
    calls = t.call_times(["hdiff", "vadv"] * 4 + ["other"])
    assert [len(calls[k]) for k in ("hdiff", "vadv", "other")] == [4, 4, 1]
    # vadv's sweeps take longer than hdiff at this size, on every call
    assert min(calls["vadv"]) > max(calls["hdiff"])
    t0, t1 = t.window("bench.hdiff")[0], t.window("bench.other")[1]
    busy = t.busy_ns(t.devices[0], t0, t1)
    assert 0 < busy < t1 - t0
    b = btrace.breakdown(t, t0, t1)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert "custom-call" in b["device_ops"][0][0]  # the Pallas kernel leads
    assert all(len(name) <= btrace.NAME_CHARS for name, _ in b["device_ops"])
