"""The pass pipeline's IR counts, locked case by case.

The matrix: the five paper stencils (``hdiff``, ``hdiff_smag``, ``vadv``,
``vadv_system``, ``vintg``) in float64 and float32, built on numpy and jax
at ``opt_level=0`` and at the default pipeline, and on Pallas at the
default pipeline (interpreted off the TPU), on a 12 x 10 x 16 domain.  Each
case checks two things:

* the outputs agree with the debug backend at ``opt_level=0`` in the same
  dtype (``TOLERANCE`` says how closely, and why);
* the IR counts equal ``COUNTS``: multi-stages, stages, temporaries, CSE
  eliminations, intervals split, and the sweeps' carry plan with its
  carried planes at nk = 16.  On Pallas the generated ``SCHEDULE``'s DMA'd
  inputs and sweep plan equal ``PALLAS_SCHEDULE`` too.

The counts depend on the pipeline alone: one row serves every backend and
both dtypes.  A pass change that moves a count must update its row here.
The last tests lock the one-off counts of ``vadv_boundary``: its carried
planes before and after the pipeline, and the CSE hit that algebraic
reassociation buys it.
"""

from typing import NamedTuple

import numpy as np
import pytest

import repro  # noqa: F401
from repro.core import analysis, passes, storage
from repro.stencils.hdiff import build_hdiff, build_hdiff_smag
from repro.stencils.vadv import build_vadv, build_vadv_boundary, build_vadv_system
from repro.stencils.vintg import build_vintg

NI, NJ, NK = 12, 10, 16
DOMAIN = (NI, NJ, NK)


class Counts(NamedTuple):
    multi_stages: int
    stages: int
    temporaries: int
    cse_eliminated: int
    intervals_split: int
    full_fields: int  # carried whole through a sweep
    window_fields: int  # carried as a rolling window of planes
    window_planes: int
    carried_planes: int  # at nk = NK
    baseline_planes: int  # every sweep field carried whole


# (stencil, "opt0" | "default") -> counts.
COUNTS = {
    ("hdiff", "opt0"): Counts(1, 9, 8, 0, 0, 0, 0, 0, 0, 0),
    ("hdiff", "default"): Counts(1, 9, 4, 0, 0, 0, 0, 0, 0, 0),
    ("hdiff_smag", "opt0"): Counts(1, 5, 3, 0, 0, 0, 0, 0, 0, 0),
    ("hdiff_smag", "default"): Counts(1, 7, 0, 2, 0, 0, 0, 0, 0, 0),
    ("vadv", "opt0"): Counts(2, 7, 3, 0, 0, 3, 1, 0, 48, 64),
    ("vadv", "default"): Counts(4, 7, 2, 0, 2, 3, 0, 0, 48, 48),
    ("vadv_system", "opt0"): Counts(3, 16, 2, 0, 0, 0, 0, 0, 0, 0),
    ("vadv_system", "default"): Counts(1, 18, 4, 2, 0, 0, 0, 0, 0, 0),
    ("vintg", "opt0"): Counts(2, 8, 2, 0, 0, 2, 2, 2, 34, 64),
    ("vintg", "default"): Counts(2, 8, 2, 0, 0, 2, 2, 2, 34, 64),
}

# stencil -> (SCHEDULE["dma_inputs"], SCHEDULE["sweeps"]) of the Pallas kernel.
PALLAS_SCHEDULE = {
    "hdiff": (["in_phi"], {}),
    "hdiff_smag": (["u", "v"], {}),
    "vadv": (
        ["a", "b", "c", "d", "out"],
        {1: {"full": ["cp", "dp"], "window": {}}, 3: {"full": ["out"], "window": {}}},
    ),
    "vadv_system": (["w", "phi"], {}),
    "vintg": (
        ["rho", "w"],
        {0: {"full": ["out_dn"], "window": {"acc_dn": 1}}, 1: {"full": ["out_up"], "window": {"acc_up": 1}}},
    ),
}

# (backend, dtype) -> the largest |got - oracle| a case may show, over the
# largest |oracle| of the field.  numpy runs the oracle's IEEE operations in
# the oracle's order (every default pass is exact by contract): float64 is
# bit-identical.  In float32 the debug backend evaluates a math call (the
# ``sqrt`` of ``hdiff_smag``) through Python's ``math`` module in double
# precision, so those outputs round differently in the last bit (6e-8
# measured); the corpus's float32 leg, which calls no such function, keeps
# numpy bit-identical.  XLA (jax, and Pallas interpreted) may contract a
# multiply and an add into one FMA: 3.4e-16 in float64 and 1.8e-7 in float32
# measured.  Each bound is some fifty times the measured error, and far
# below the O(1) error of a wrong plane, boundary or mask.
TOLERANCE = {
    ("numpy", "float64"): 0.0,
    ("numpy", "float32"): 1e-6,
    ("jax", "float64"): 1e-14,
    ("jax", "float32"): 1e-5,
    ("pallas", "float64"): 1e-14,
    ("pallas", "float32"): 1e-5,
}


def _hdiff_fields(rng):
    shape = (NI + 6, NJ + 6, NK)
    return [(rng.normal(size=shape), (3, 3, 0)), (np.zeros(shape), (3, 3, 0))], {"alpha": 0.05}


def _hdiff_smag_fields(rng):
    shape = (NI + 2, NJ + 2, NK)
    fields = [(rng.normal(size=shape), (1, 1, 0)), (rng.normal(size=shape), (1, 1, 0))]
    return fields + [(np.zeros(shape), (1, 1, 0))] * 2, {"dt": 0.1}


def _vadv_fields(rng):
    fields = [
        rng.normal(size=DOMAIN) * 0.1,
        2.0 + rng.random(DOMAIN),
        rng.normal(size=DOMAIN) * 0.1,
        rng.normal(size=DOMAIN),
        np.zeros(DOMAIN),
    ]
    return [(f, (0, 0, 0)) for f in fields], {}


def _vadv_system_fields(rng):
    fields = [rng.normal(size=DOMAIN), rng.normal(size=DOMAIN)] + [np.zeros(DOMAIN)] * 4
    return [(f, (0, 0, 0)) for f in fields], {"dt": 0.5, "dz": 1.5}


def _vintg_fields(rng):
    fields = [0.5 + rng.random(DOMAIN), 0.5 + rng.random(DOMAIN), np.zeros(DOMAIN), np.zeros(DOMAIN)]
    return [(f, (0, 0, 0)) for f in fields], {"decay": 0.9}


STENCILS = {
    "hdiff": (build_hdiff, _hdiff_fields),
    "hdiff_smag": (build_hdiff_smag, _hdiff_smag_fields),
    "vadv": (build_vadv, _vadv_fields),
    "vadv_system": (build_vadv_system, _vadv_system_fields),
    "vintg": (build_vintg, _vintg_fields),
}
CONFIGS = {
    "numpy@opt0": ("numpy", {"opt_level": 0}),
    "numpy@default": ("numpy", {}),
    "jax@opt0": ("jax", {"opt_level": 0}),
    "jax@default": ("jax", {}),
    "pallas@default": ("pallas", {}),
}


def _run(name, dtype, backend, opts):
    """Build ``name`` and call it once on seeded fields; the stencil and
    every field's values after the call."""
    build, make_fields = STENCILS[name]
    st = build(backend, dtype=dtype, **opts)
    fields, scalars = make_fields(np.random.default_rng(sorted(STENCILS).index(name)))
    stores = [
        storage.from_array(a.astype(dtype), backend=backend, default_origin=origin)
        for a, origin in fields
    ]
    st(*stores, **{k: np.dtype(dtype).type(v) for k, v in scalars.items()}, domain=DOMAIN)
    return st, [s.to_numpy() for s in stores]


def _counts(st):
    stats = passes.impl_stats(st.implementation_ir)
    detail = {r["pass"]: r.get("detail") or {} for r in st.pass_report}
    plans = analysis.sequential_carry_plan(st.implementation_ir).values()
    return Counts(
        multi_stages=stats["multi_stages"],
        stages=stats["stages"],
        temporaries=stats["temporaries"],
        cse_eliminated=detail.get("cross_stage_cse", {}).get("eliminated", 0),
        intervals_split=detail.get("interval_splitting", {}).get("intervals_split", 0),
        full_fields=sum(len(p.full) for p in plans),
        window_fields=sum(len(p.window) for p in plans),
        window_planes=sum(d for p in plans for _, d in p.window),
        carried_planes=sum(p.carried_planes(NK) for p in plans),
        baseline_planes=sum(p.baseline_planes(NK) for p in plans),
    )


@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("dtype", ["float64", "float32"])
@pytest.mark.parametrize("name", STENCILS)
def test_pipeline_counts(name, dtype, config):
    backend, opts = CONFIGS[config]
    st, got = _run(name, dtype, backend, opts)
    _, oracle = _run(name, dtype, "debug", {"opt_level": 0})
    for i, (g, o) in enumerate(zip(got, oracle)):
        assert g.dtype == np.dtype(dtype)
        err = np.abs(g.astype(np.float64) - o).max() / (np.abs(o).max() or 1.0)
        assert err <= TOLERANCE[backend, dtype], f"{name} {config} field {i}: {err:.3g}"

    assert _counts(st) == COUNTS[name, config.split("@")[1]]
    if backend == "pallas":
        schedule = st._module.SCHEDULE
        assert (schedule["dma_inputs"], schedule["sweeps"]) == PALLAS_SCHEDULE[name]


@pytest.mark.parametrize("opt, planes", [("opt0", 64), ("default", 32)])
def test_vadv_boundary_carried_planes(opt, planes):
    """Interval splitting peels the boundary-only flux outputs out of the
    interior sweeps: half the planes of the verbatim lowering stay carried."""
    st = build_vadv_boundary("numpy", **({"opt_level": 0} if opt == "opt0" else {}))
    assert _counts(st).carried_planes == planes


def test_vadv_boundary_reassociation_buys_one_cse_hit():
    """Reassociation's literal-first canonical order makes one commuted
    repeat visible to cross-stage CSE."""
    with_reassoc = _counts(build_vadv_boundary("numpy")).cse_eliminated
    without = _counts(
        build_vadv_boundary("numpy", disable_passes=("algebraic_reassociation",))
    ).cse_eliminated
    assert with_reassoc - without == 1
