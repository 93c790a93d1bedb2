"""The readers of the program's own spans and kernel names: on hand-made
traces, on a trace of a program that writes none of them (which reads
nothing), and on a profile taken on the CPU, where the tracer's bridge
writes the spans."""

import numpy as np
import pytest

from bench import harness, spans
from bench import trace as btrace

NEW = ["stencil.call_ms", "kernel_share.step", "to_host_ms.ensemble", "to_device_ms.ensemble",
       "device_wait_ms.ensemble", "io_idle_share.ensemble"]


def _read(name, rec):
    return harness.load_module("metrics", name).read(rec)


def _record(ops, host, completed=None) -> harness.Record:
    rec = harness.Record(cell=None)
    rec.trace = btrace.Trace(devices=[sorted(ops)], modules=[[]], host=sorted([(0, 1000, harness.WINDOW)] + host))
    if completed is not None:
        rec.counters["completed"] = completed
    return rec


def test_dycore_readers_on_hand_made_events():
    ops = [(100, 300, "%gt_hdiff_defs_float32.1 = f32[8,8,128] custom-call()"),
           (300, 400, "%pad_maximum_fusion.2 = f32[8,8,128] fusion()"),
           (400, 800, "%gt_vadv_defs_float32.1 = f32[8,8,128] custom-call()"),
           (1100, 1200, "%gt_vadv_defs_float32.1 = f32[8,8,128] custom-call()")]  # after the window
    host = [(50, 60, "stencil.call"), (150, 170, "stencil.call"), (300, 330, "stencil.call"),
            (1050, 1500, "stencil.call"), (-40, -10, "stencil.call")]  # the last two outside
    rec = _record(ops, host)
    assert _read("kernel_share.step", rec) == pytest.approx(100.0 * 600 / 700)
    assert _read("stencil.call_ms", rec) == pytest.approx(20e-6)


def test_ensemble_readers_on_hand_made_events():
    # device busy [0,100) and [500,600): idle [100,500) and [600,1000), 800 ns
    ops = [(0, 100, "%while.44 = f64[8,10,10,4] while()"), (500, 600, "%fusion.3 = f64[8] fusion()")]
    host = [(120, 200, "ensemble.stack"), (190, 260, "ensemble.to_device"),  # overlap: [120,260) idle
            (550, 700, "ensemble.to_host"),  # idle [600,700)
            (700, 900, "ensemble.wait"),  # not a copy
            (950, 1100, "ensemble.to_host"),  # idle [950,1000) in the window, all 150 ns counted
            (-50, -10, "ensemble.to_host"),  # before the window
            (100, 500, "serving.scatter")]
    rec = _record(ops, host, completed=2)
    assert _read("to_host_ms.ensemble", rec) == pytest.approx((150 + 150) / 2 / 1e6)
    assert _read("to_device_ms.ensemble", rec) == pytest.approx((80 + 70) / 2 / 1e6)
    assert _read("device_wait_ms.ensemble", rec) == pytest.approx(200 / 2 / 1e6)
    assert _read("io_idle_share.ensemble", rec) == pytest.approx(100.0 * (140 + 100 + 50) / 800)


def test_readers_read_nothing_where_the_program_writes_nothing():
    """A program without the spans and kernel names (the parent of this
    change) and an untraced run leave every new metric unread, not 0."""
    parent = _record([(100, 900, "%_pure.1 = f32[8,8,128] custom-call()")],
                     [(100, 500, "serving.scatter"), (500, 900, "serving.dispatch")], completed=3)
    untraced = harness.Record(cell=None, counters={"completed": 3})
    for rec in (parent, untraced):
        assert {name: _read(name, rec) for name in NEW} == dict.fromkeys(NEW)
    # answered no request: no rate per request
    assert _read("to_host_ms.ensemble", _record([], [(10, 20, "ensemble.to_host")], completed=0)) is None


def test_the_reader_matches_the_generated_kernel_name():
    from repro.core.codegen_pallas import KERNEL_PREFIX
    from repro.stencils.vadv import build_vadv

    assert spans.KERNEL_OP == "%" + KERNEL_PREFIX
    assert f"name={KERNEL_PREFIX + build_vadv('pallas', dtype='float32').name!r}" in (
        build_vadv("pallas", dtype="float32").generated_source)


def test_the_program_spans_reach_a_cpu_profile_by_name():
    """With the tracer off, a profile taken as the benchmark takes it holds
    the program's spans on ``/host:CPU`` under their names; outside the
    session the bridge writes nothing."""
    import jax

    from repro.core import gtscript, storage
    from repro.core.gtscript import PARALLEL, Field, computation, interval
    from repro.ensemble import batch as ens_batch
    from repro.obs import trace as otrace

    def plus_one(a: Field[np.float64], b: Field[np.float64]):
        with computation(PARALLEL), interval(...):
            b = a + 1.0  # noqa: F841

    st = gtscript.stencil(backend="jax")(plus_one)
    a = storage.from_array(np.zeros((4, 4, 3)), backend="jax")
    b = storage.from_array(np.zeros((4, 4, 3)), backend="jax")
    st(a, b, domain=(4, 4, 3))  # compiled before the profile
    tracer = otrace.Tracer(enabled=False)
    assert tracer.span("unit.outside") is otrace.NOOP_SPAN
    out: dict = {}
    with harness.profiled(True, "test_bench_spans", out):
        assert otrace._profiling()
        with jax.profiler.TraceAnnotation(harness.WINDOW):
            with tracer.span("unit.bridged", trace_id="r1", heavy=1):
                st(a, b, domain=(4, 4, 3))
            batched = ens_batch.scatter_members([np.ones((4, 4, 3))] * 2, 2, template=a)
            got = ens_batch.gather_member(batched, 1)
    assert not otrace._profiling() and tracer.span("unit.outside") is otrace.NOOP_SPAN
    np.testing.assert_array_equal(got, np.ones((4, 4, 3)))
    names = {n for _, _, n in out["trace"].host}
    assert {"unit.bridged", "stencil.call", "ensemble.stack", "ensemble.to_device", "ensemble.wait",
            "ensemble.to_host"} <= names
    assert "unit.outside" not in names and len(tracer) == 0
    rec = harness.Record(cell=None, counters={"completed": 1})
    rec.trace = out["trace"]
    assert _read("stencil.call_ms", rec) > 0 and _read("to_host_ms.ensemble", rec) > 0
