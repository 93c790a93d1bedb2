"""Pallas backend schedule tests: double-buffered halo DMAs, k-blocked
sweeps (rolling plane windows), the K-major layout of sweep kernels, and the
exported SCHEDULE metadata.

Correctness is locked differentially: every scheduling decision must be
bit-identical (float64) to the debug oracle on numpy, jax and pallas, at
``opt_level=0`` and at the default pipeline.
"""

import re

import numpy as np
import pytest

from repro.core import analysis, frontend, gtscript, passes, storage
from repro.core.gtscript import FORWARD, PARALLEL, Field, computation, interval
from repro.stencils.vintg import vintg_defs

from test_passes import run_differential

NI, NJ, NK = 7, 6, 5
#: vertical grids below (COSMO-1) and above (ECMWF IFS) one 128-lane tile;
#: the (4, 4) test tile divides neither NI nor NJ
LEVELS = (NK, 80, 137)


def _rand(shape, seed=0):
    return np.random.default_rng(seed).normal(size=shape)


def _impl(defs, externals=None, name=None):
    impl = analysis.analyze(
        frontend.parse_stencil_definition(defs, externals=externals or {}, name=name or defs.__name__)
    )
    opt, _ = passes.run_pipeline(impl)
    return opt


# ---------------------------------------------------------------------------
# carry-plan analysis
# ---------------------------------------------------------------------------


def test_vintg_carry_plan_windows_accumulators():
    plans = analysis.sequential_carry_plan(_impl(vintg_defs))
    assert len(plans) == 2
    fwd, bwd = plans[0], plans[1]
    assert fwd.full == ("out_dn",) and fwd.window == (("acc_dn", 1),)
    assert bwd.full == ("out_up",) and bwd.window == (("acc_up", 1),)
    # the k-blocking payoff: 1 full field + 1 plane instead of 2 full fields
    assert fwd.carried_planes(NK) == NK + 1
    assert fwd.baseline_planes(NK) == 2 * NK


def test_vadv_carry_plan_keeps_cross_sweep_temps_full():
    from repro.core import ir
    from repro.stencils.vadv import vadv_defs

    impl = _impl(vadv_defs, name="vadv")
    # interval_splitting peels both boundary intervals (the k=0 Thomas init
    # and the k=nk-1 substitution seed) into PARALLEL multi-stages around
    # the two interior sweeps
    orders = [ms.order for ms in impl.multi_stages]
    assert orders == [
        ir.IterationOrder.PARALLEL,
        ir.IterationOrder.FORWARD,
        ir.IterationOrder.PARALLEL,
        ir.IterationOrder.BACKWARD,
    ]
    plans = analysis.sequential_carry_plan(impl)
    fwd, bwd = plans[1], plans[3]
    # cp/dp are read by the BACKWARD substitution sweep → must stay full 3-D
    assert set(fwd.full) == {"cp", "dp"} and fwd.window == ()
    assert bwd.full == ("out",) and bwd.window == ()


def test_sweep_local_temp_written_in_two_sweeps_stays_full():
    def defs(a: Field[np.float64], o: Field[np.float64]):
        with computation(FORWARD), interval(...):
            t = a * 2.0
            o = t
        with computation(FORWARD), interval(...):
            t = a * 3.0
            o = o[0, 0, 0] + t

    plans = analysis.sequential_carry_plan(_impl(defs))
    # t is written by two multi-stages — the rolling window may not be split
    assert all("t" not in dict(p.window) for p in plans.values())


# ---------------------------------------------------------------------------
# windowed sweep codegen (jax + pallas)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("nk", LEVELS)
def test_vintg_differential_all_backends(nk):
    shape = (NI, NJ, nk)
    fields = {
        "rho": (_rand(shape, seed=1) * 0.5 + 1.0, (0, 0, 0)),
        "w": (_rand(shape, seed=2) * 0.5 + 1.0, (0, 0, 0)),
        "out_dn": (np.zeros(shape), (0, 0, 0)),
        "out_up": (np.zeros(shape), (0, 0, 0)),
    }
    run_differential(vintg_defs, fields, {"decay": np.float64(0.9)}, shape)


# ---------------------------------------------------------------------------
# K-major sweep kernels
# ---------------------------------------------------------------------------


def _sweep_bodies(src):
    """The source of every generated ``_body_*`` loop body."""
    return re.findall(r"def _body_\d+_\d+\(.*?\n(.*?)\n\s*return ", src, re.S)


@pytest.mark.parametrize("build", ["vadv", "vintg"])
def test_sweep_kernels_index_k_major_planes(build):
    """A kernel with a FORWARD/BACKWARD multi-stage holds its (I, J, K)
    arrays K-major: a sweep level is one plane, read and stored by a leading
    index, never a masked select or reduction over the K lanes."""
    from repro.stencils.vadv import build_vadv
    from repro.stencils.vintg import build_vintg

    st = {"vadv": build_vadv, "vintg": build_vintg}[build]("pallas", dtype="float32")
    src = st.generated_source
    assert st._module.SCHEDULE["layout"] == "k_major"
    assert "_kget" not in src
    bodies = _sweep_bodies(src)
    assert bodies and all("_put(" not in b for b in bodies)
    # every (I, J, K) read and write in a sweep indexes the level first
    assert all(re.search(r"\w\[_ok_\w+ \+ k(?: [+-] \d)?, ", b) for b in bodies)
    # each window is transposed into its K-major array once, per I row
    assert "[:, _i, :] = _s_" in src and "].T" in src


def test_parallel_only_kernel_keeps_k_minor_blocks():
    """hdiff has no sweep: its horizontal offsets stay on I (major) and J
    (sublanes), so it keeps (bi, bj, nkp) blocks and K on lanes."""
    from repro.stencils.hdiff import build_hdiff

    st = build_hdiff("pallas", dtype="float32")
    src = st.generated_source
    assert st._module.SCHEDULE["layout"] == "k_minor"
    assert "pl.BlockSpec((bi, bj, nkp), lambda i, j: (i, j, 0))" in src
    assert "in_phi_out_ref" not in src and "out_phi_out_ref[:, :, :nk] = out_phi" in src
    assert "_arrays" not in src and "_fit(" not in src


def test_jax_backend_source_has_no_k_major_constructs():
    """The jax backend shares the sweep emitter: it still carries its full
    fields through the loop and writes them with ``_dus``."""
    from repro.stencils.vadv import build_vadv

    src = build_vadv("jax", dtype="float32").generated_source
    assert "_dus(" in src and "(cp, dp) = _carry" in src
    for construct in ("_fit(", "_arrays", "_kget", "_put(", "].T", "[:, _i, :]", "_ok_cp + k, "):
        assert construct not in src, construct


def test_k_major_sweep_reads_ij_and_k_fields():
    """A K-major kernel reads an (I, J) field as a plane and a K field as an
    (nk, 1, 1) column, in its PARALLEL blocks and its sweeps alike."""

    def defs(
        a: Field[np.float64],
        sfc: Field[np.float64, gtscript.IJ],
        prof: Field[np.float64, gtscript.K],
        o: Field[np.float64],
        col: Field[np.float64],
    ):
        with computation(PARALLEL), interval(...):
            t = a * prof
        with computation(FORWARD):
            with interval(0, 1):
                o = t + sfc
                col = prof + 0.0
            with interval(1, None):
                o = t + 0.5 * o[0, 0, -1] + prof[-1]
                col = col[0, 0, -1] + prof

    rng = np.random.default_rng(10)
    shape = (NI, NJ, NK)
    run_differential(
        defs,
        {
            "a": (rng.normal(size=shape), (0, 0, 0)),
            "sfc": (rng.normal(size=(NI, NJ)), (0, 0)),
            "prof": (rng.normal(size=(NK,)), (0,)),
            "o": (rng.normal(size=shape), (0, 0, 0)),
            "col": (np.zeros(shape), (0, 0, 0)),
        },
        {},
        shape,
    )
    st = gtscript.stencil(backend="pallas", block=(4, 4))(defs)
    assert st._module.SCHEDULE["layout"] == "k_major"
    assert "prof_vmem[_ok_prof + k - 1]" in st.generated_source


def test_vintg_generated_code_carries_planes_not_arrays():
    for backend in ("jax", "pallas"):
        st = gtscript.stencil(backend=backend)(vintg_defs)
        src = st.generated_source
        assert "_wh_acc_dn_1" in src and "_wp_acc_dn" in src
        assert "_wh_acc_up_1" in src and "_wp_acc_up" in src
        # the accumulators must not be materialized as (ni, nj, nk) arrays
        assert "acc_dn = jnp.zeros((ni, nj, nk" not in src
        assert "acc_up = jnp.zeros((ni, nj, nk" not in src


def test_window_depth_two_recurrence():
    def defs(a: Field[np.float64], o: Field[np.float64]):
        with computation(FORWARD):
            with interval(0, 2):
                acc = a
                o = acc
            with interval(2, None):
                acc = 0.5 * acc[0, 0, -1] + 0.25 * acc[0, 0, -2] + a
                o = acc

    plans = analysis.sequential_carry_plan(_impl(defs))
    assert plans[0].window == (("acc", 2),)

    x = _rand((NI, NJ, NK), seed=3)
    run_differential(
        defs,
        {"a": (x, (0, 0, 0)), "o": (np.zeros_like(x), (0, 0, 0))},
        {},
        (NI, NJ, NK),
    )


def test_windowed_temp_with_horizontal_halo():
    def defs(a: Field[np.float64], o: Field[np.float64]):
        with computation(FORWARD):
            with interval(0, 1):
                s = a
                acc = a
                o = acc
            with interval(1, None):
                s = a * 2.0
                acc = 0.5 * (s[1, 0, -1] + s[-1, 0, -1]) + a
                o = acc

    impl = _impl(defs)
    plans = analysis.sequential_carry_plan(impl)
    # s carries one trailing plane (read horizontally off-center a level
    # behind); acc never crosses an iteration → depth-0 window, no carry
    assert dict(plans[0].window) == {"s": 1, "acc": 0}
    assert impl.extent_of("s").i == (-1, 1)  # plane windows keep their halo

    H = 1
    shape = (NI + 2 * H, NJ + 2 * H, NK)
    x = _rand(shape, seed=4)
    run_differential(
        defs,
        {"a": (x, (H, H, 0)), "o": (np.zeros(shape), (H, H, 0))},
        {},
        (NI, NJ, NK),
    )


# ---------------------------------------------------------------------------
# DMA schedule
# ---------------------------------------------------------------------------


def _two_ms_defs(a: Field[np.float64, gtscript.IJ], b: Field[np.float64, gtscript.IJ],
                 o1: Field[np.float64], o2: Field[np.float64]):
    with computation(PARALLEL), interval(...):
        t = (a[1, 0] + a[-1, 0]) * 0.5
        o1 = t + a
    with computation(FORWARD):
        with interval(0, 1):
            o2 = b + o1
        with interval(1, None):
            o2 = b + o2[0, 0, -1]


def test_dma_waits_deferred_to_first_use():
    # interval_splitting would peel the carry-free [0, 1) init off the sweep
    # and fuse it into multi-stage 0 (moving b's first use earlier); this
    # test is about DMA-wait deferral, so pin the two-multi-stage shape.
    st = gtscript.stencil(
        backend="pallas", block=(4, 4), disable_passes=("interval_splitting",)
    )(_two_ms_defs)
    src = st.generated_source
    # per-field semaphores, all copies started before any compute
    assert "_dma_sems.at[0]" in src and "_dma_sems.at[1]" in src
    i_start_a = src.index("_cp_a.start()")
    i_start_b = src.index("_cp_b.start()")
    i_ms0 = src.index("# === multi-stage 0")
    i_ms1 = src.index("# === multi-stage 1")
    assert max(i_start_a, i_start_b) < i_ms0
    # a is consumed by multi-stage 0, b only by multi-stage 1: its wait (and
    # binding) overlap multi-stage 0's compute
    assert i_ms0 < src.index("_cp_a.wait()") < i_ms1
    assert src.index("_cp_b.wait()") > i_ms1
    sched = st._module.SCHEDULE
    # (I, J) inputs take the hand-DMA'd padded windows; o1/o2 are
    # written-and-read (inout) (I, J, K) fields, whose element windows the
    # pipeline copies in: each binds at its own first-touching multi-stage
    assert sched["windows"] == {"a": "padded", "b": "padded", "o1": "direct", "o2": "direct"}
    assert "_cp_o1" not in src and "_cp_o2" not in src
    assert sched["dma_first_use_ms"] == {"a": 0, "b": 1, "o1": 0, "o2": 1}
    assert i_ms0 < src.index("o1[:, _i, :] = _s_o1[") < i_ms1
    assert src.index("o2[:, _i, :] = _s_o2[") > i_ms1


def test_dma_deferred_schedule_differential():
    H = 1
    shape = (NI + 2 * H, NJ + 2 * H, NK)
    a, b = _rand(shape[:2], seed=5), _rand(shape[:2], seed=6)
    run_differential(
        _two_ms_defs,
        {
            "a": (a, (H, H)),
            "b": (b, (H, H)),
            "o1": (np.zeros(shape), (H, H, 0)),
            "o2": (np.zeros(shape), (H, H, 0)),
        },
        {},
        (NI, NJ, NK),
    )


@pytest.mark.parametrize("nk", LEVELS)
def test_partially_written_outputs_preserve_caller_values(nk):
    """Regression (differential fuzzer): an API output written only on some
    k-intervals, or only under a mask, must keep the caller's values on the
    unwritten planes / false lanes.  The pallas backend used to zero-init
    pure outputs and write back the whole domain — now such outputs DMA
    their tile in as the kernel's initial value (inout)."""

    def defs(a: Field[np.float64], o: Field[np.float64], ob: Field[np.float64]):
        with computation(FORWARD):
            with interval(0, 1):
                ob = a * 2.0  # boundary-only write: planes 1..nk-1 untouched
                o = a
            with interval(1, None):
                o = a + 0.5 * o[0, 0, -1]
        with computation(PARALLEL), interval(...):
            if a > 0.0:
                ob = ob + 1.0  # masked write: false lanes untouched

    rng = np.random.default_rng(9)
    shape = (NI, NJ, nk)
    # nonzero initial output values are what expose the clobbering
    run_differential(
        defs,
        {
            "a": (rng.normal(size=shape), (0, 0, 0)),
            "o": (rng.normal(size=shape), (0, 0, 0)),
            "ob": (rng.normal(size=shape), (0, 0, 0)),
        },
        {},
        shape,
    )
    st = gtscript.stencil(backend="pallas", block=(4, 4))(defs)
    # ob is partially written → must arrive via the inout DMA path, and
    # its K-major copy keeps the caller's values on the unwritten planes
    assert "ob" in st._module.SCHEDULE["dma_inputs"]
    assert st._module.SCHEDULE["layout"] == "k_major"


def test_schedule_surfaces_in_exec_info():
    st = gtscript.stencil(backend="pallas", block=(4, 4))(vintg_defs)
    fs = {
        n: storage.from_array(v, backend="pallas")
        for n, v in {
            "rho": _rand((NI, NJ, NK), seed=7) + 2.0,
            "w": _rand((NI, NJ, NK), seed=8) + 2.0,
            "out_dn": np.zeros((NI, NJ, NK)),
            "out_up": np.zeros((NI, NJ, NK)),
        }.items()
    }
    info = {}
    st(**fs, decay=np.float64(0.9), domain=(NI, NJ, NK), exec_info=info)
    sched = info["schedule"]
    assert sched["layout"] == "k_major"
    assert sched["dma_inputs"] == ["rho", "w"]
    assert sched["window_fields"] == 2 and sched["window_planes"] == 2
    assert sched["full_carry_fields"] == 2


def test_exec_info_says_the_kernel_ran_interpreted():
    st = gtscript.stencil(backend="pallas", block=(4, 4))(vintg_defs)
    assert st.interpreted  # no TPU here: the Pallas interpreter runs it
    fs = {
        n: storage.from_array(_rand((NI, NJ, NK), seed=s) + 2.0, backend="pallas")
        for s, n in enumerate(("rho", "w", "out_dn", "out_up"))
    }
    info = {}
    st(**fs, decay=np.float64(0.9), domain=(NI, NJ, NK), exec_info=info)
    assert info["interpret"] is True
    assert not gtscript.stencil(backend="jax")(vintg_defs).interpreted


def test_float64_pallas_kernel_fails_to_build_on_a_tpu(monkeypatch):
    """Mosaic has no 64-bit vector types: where the first device is a TPU, a
    float64 Pallas stencil is refused when it is built, naming the dtype;
    its float32 retyping builds and compiles instead of interpreting."""
    import jax

    from repro.core import ir
    from repro.core.stencil import build_from_definition

    class _Tpu:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Tpu()])
    with pytest.raises(TypeError, match="float64"):
        gtscript.stencil(backend="pallas", rebuild=True)(vintg_defs)
    f32 = ir.retype_definition(frontend.parse_stencil_definition(vintg_defs, externals={}), {"float64": "float32"})
    st = build_from_definition(f32, "pallas", rebuild=True)
    assert not st.interpreted
    st._module.INTERPRET = True  # the module stays cached for later tests
