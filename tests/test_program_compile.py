"""Compiled-program tests: fusion, bit-identity vs the eager path, rotation,
iterate, caching, and the generated orchestrator artifact."""

import numpy as np
import pytest

import repro  # noqa: F401
from repro.core import gtscript, storage
from repro.core.gtscript import Field, PARALLEL, computation, interval
from repro.program import ProgramError, program
from repro.stencils.library import laplacian
from repro.stencils.vadv import vadv_defs


# ---------------------------------------------------------------------------
# the miniature climate step (examples/climate_model.py motif)
# ---------------------------------------------------------------------------


def diffuse_defs(phi: Field[np.float64], out: Field[np.float64], *, alpha: np.float64):
    with computation(PARALLEL), interval(...):
        out = phi + alpha * laplacian(phi)


def advect_defs(
    phi: Field[np.float64],
    u: Field[np.float64],
    v: Field[np.float64],
    adv: Field[np.float64],
    *,
    dx: np.float64,
    dy: np.float64,
):
    with computation(PARALLEL), interval(...):
        fx = (phi[0, 0, 0] - phi[-1, 0, 0]) / dx if u > 0.0 else (phi[1, 0, 0] - phi[0, 0, 0]) / dx
        fy = (phi[0, 0, 0] - phi[0, -1, 0]) / dy if v > 0.0 else (phi[0, 1, 0] - phi[0, 0, 0]) / dy
        adv = -(u * fx + v * fy)


def wsystem_defs(
    w: Field[np.float64],
    phi: Field[np.float64],
    a: Field[np.float64],
    b: Field[np.float64],
    c: Field[np.float64],
    d: Field[np.float64],
    *,
    dtdz: np.float64,
):
    with computation(PARALLEL):
        with interval(1, -1):
            gcv = 0.25 * (w[0, 0, 1] + w[0, 0, 0]) * dtdz
            gcm = 0.25 * (w[0, 0, 0] + w[0, 0, -1]) * dtdz
            a = -gcm
            c = gcv
            b = 1.0 + gcv - gcm
            d = phi[0, 0, 0] - gcv * (phi[0, 0, 1] - phi[0, 0, 0]) + gcm * (phi[0, 0, 0] - phi[0, 0, -1])
        with interval(0, 1):
            gcv = 0.25 * (w[0, 0, 1] + w[0, 0, 0]) * dtdz
            a = 0.0
            c = gcv
            b = 1.0 + gcv
            d = phi[0, 0, 0] - gcv * (phi[0, 0, 1] - phi[0, 0, 0])
        with interval(-1, None):
            gcm = 0.25 * (w[0, 0, 0] + w[0, 0, -1]) * dtdz
            a = -gcm
            c = 0.0
            b = 1.0 - gcm
            d = phi[0, 0, 0] + gcm * (phi[0, 0, 0] - phi[0, 0, -1])


def euler_defs(phi: Field[np.float64], adv: Field[np.float64], out: Field[np.float64], *, dt: np.float64):
    with computation(PARALLEL), interval(...):
        out = phi + dt * adv


H = 3
NI = NJ = 16
NK = 8
DOM = (NI, NJ, NK)
SHAPE = (NI + 2 * H, NJ + 2 * H, NK)
NT = 10
SCALARS = dict(
    dt=np.float64(0.1),
    dx=np.float64(1.0),
    dy=np.float64(1.0),
    dtdz=np.float64(0.1),
    alpha=np.float64(0.05),
)
FIELD_NAMES = ("phi", "u", "v", "w", "adv", "phi_star", "phi_h", "a", "b", "c", "d", "phi_new")


def _initial_arrays():
    rng = np.random.default_rng(0)
    xx, yy = np.meshgrid(np.linspace(-2, 2, SHAPE[0]), np.linspace(-2, 2, SHAPE[1]), indexing="ij")
    blob = np.exp(-(xx**2 + yy**2))[:, :, None] * np.ones((1, 1, NK))
    return {
        "phi": blob,
        "u": np.full(SHAPE, 0.8),
        "v": np.full(SHAPE, -0.4),
        "w": 0.2 * rng.random(SHAPE),
    }


def _stores(backend):
    init = _initial_arrays()
    out = {}
    for n in FIELD_NAMES:
        if n in init:
            out[n] = storage.from_array(np.array(init[n]), backend=backend, default_origin=(H, H, 0))
        else:
            out[n] = storage.zeros(SHAPE, backend=backend, default_origin=(H, H, 0))
    return out


def _build_all(backend):
    build = gtscript.stencil(backend=backend)
    return (
        build(advect_defs),
        build(euler_defs),
        build(diffuse_defs),
        build(wsystem_defs),
        build(vadv_defs),
    )


def _eager_steps(backend, nt):
    advect, euler, diffuse, wsys, vsolve = _build_all(backend)
    s = _stores(backend)
    for _ in range(nt):
        advect(s["phi"], s["u"], s["v"], s["adv"], dx=SCALARS["dx"], dy=SCALARS["dy"], domain=DOM)
        euler(s["phi"], s["adv"], s["phi_star"], dt=SCALARS["dt"], domain=DOM)
        diffuse(s["phi_star"], s["phi_h"], alpha=SCALARS["alpha"], domain=DOM)
        wsys(s["w"], s["phi_h"], s["a"], s["b"], s["c"], s["d"], dtdz=SCALARS["dtdz"], domain=DOM)
        vsolve(s["a"], s["b"], s["c"], s["d"], s["phi_new"], domain=DOM)
        s["phi"], s["phi_new"] = s["phi_new"], s["phi"]
    return np.asarray(s["phi"]).copy()


def _make_program(backend):
    advect, euler, diffuse, wsys, vsolve = _build_all(backend)

    @program(backend=backend, name=f"climate_step_{backend}")
    def climate_step(phi, u, v, w, adv, phi_star, phi_h, a, b, c, d, phi_new, *, dt, dx, dy, dtdz, alpha):
        advect(phi, u, v, adv, dx=dx, dy=dy, domain=DOM)
        euler(phi, adv, phi_star, dt=dt, domain=DOM)
        diffuse(phi_star, phi_h, alpha=alpha, domain=DOM)
        wsys(w, phi_h, a, b, c, d, dtdz=dtdz, domain=DOM)
        vsolve(a, b, c, d, phi_new, domain=DOM)
        return {"phi": phi_new, "phi_new": phi}

    return climate_step


def _program_steps(backend, nt, exec_info=None):
    step = _make_program(backend)
    p = _stores(backend)
    for t in range(nt):
        step(*[p[n] for n in FIELD_NAMES], **SCALARS, exec_info=exec_info if t == 0 else None)
    return np.asarray(p["phi"]).copy(), step, p


# ---------------------------------------------------------------------------
# bit-identity vs the eager per-stencil path
# ---------------------------------------------------------------------------


def test_program_bit_identical_to_eager_debug_oracle_10_steps():
    eager = _eager_steps("debug", NT)
    prog, _, _ = _program_steps("debug", NT)
    assert np.array_equal(prog, eager)  # bit-identical, float64


def test_program_bit_identical_to_eager_numpy_10_steps():
    eager = _eager_steps("numpy", NT)
    prog, _, _ = _program_steps("numpy", NT)
    assert np.array_equal(prog, eager)


def test_program_matches_eager_jax_10_steps():
    eager = _eager_steps("jax", NT)
    info = {}
    prog, _, _ = _program_steps("jax", NT, exec_info=info)
    # one fused jit vs five jits: XLA instruction selection may differ by
    # rounding (ulp-level); the debug-oracle comparison above is the bit gate
    assert np.abs(prog - eager).max() < 1e-12
    # and the jax program agrees bit-for-bit with the numpy program
    assert np.abs(prog - _program_steps("numpy", NT)[0]).max() < 1e-12


def test_program_fusion_and_eliminated_temporaries():
    info = {}
    _program_steps("numpy", 1, exec_info=info)
    rep = info["program_report"]
    assert rep["nodes"] == 5
    assert rep["groups"] == 1
    assert rep["fused_stencils"] >= 1
    # adv and the tridiagonal coefficients never materialize at program level
    assert set(rep["eliminated_temporaries"]) == {"adv", "a", "b", "c", "d"}
    assert rep["rotation"] == {"phi_new": "phi"}
    # PARALLEL stages all fused into one multi-stage; FORWARD/BACKWARD remain,
    # and interval_splitting peels the Thomas solver's carry-free boundary
    # interval(s) into PARALLEL multi-stages of their own
    assert rep["group_multi_stages"] == [4]
    assert [t["group"] for t in rep["node_timings"]] == [0]


def test_program_groups_ride_pass_config():
    """backend_opts thread into the fused groups' builds: fused programs
    split/tile exactly like standalone stencils, and disabling a pass at
    program scope disables it inside every merged group."""
    advect, euler, diffuse, wsys, vsolve = _build_all("numpy")

    def make(**opts):
        @program(backend="numpy", name=f"climate_step_cfg_{sorted(opts.items())!r}", **opts)
        def climate_step(phi, u, v, w, adv, phi_star, phi_h, a, b, c, d, phi_new, *, dt, dx, dy, dtdz, alpha):
            advect(phi, u, v, adv, dx=dx, dy=dy, domain=DOM)
            euler(phi, adv, phi_star, dt=dt, domain=DOM)
            diffuse(phi_star, phi_h, alpha=alpha, domain=DOM)
            wsys(w, phi_h, a, b, c, d, dtdz=dtdz, domain=DOM)
            vsolve(a, b, c, d, phi_new, domain=DOM)
            return {"phi": phi_new, "phi_new": phi}

        p = _stores("numpy")
        info = {}
        climate_step(*[p[n] for n in FIELD_NAMES], **SCALARS, exec_info=info)
        return info["program_report"], np.asarray(p["phi"]).copy()

    rep_default, phi_default = make()
    rep_nosplit, phi_nosplit = make(disable_passes=("interval_splitting",))
    # the peel happens inside the merged group (4 multi-stages), and turning
    # the pass off at program scope removes it (back to 3)
    assert rep_default["group_multi_stages"] == [4]
    assert rep_nosplit["group_multi_stages"] == [3]
    np.testing.assert_array_equal(phi_default, phi_nosplit)


def test_non_output_written_fields_persist_on_all_backends():
    """Writes to program fields the return binding does not name must still
    land in the caller's storages — matching the eager per-stencil path —
    on the functional backends too, not just the mutating ones."""
    for backend in ("numpy", "jax"):
        step = _make_program(backend)
        p = _stores(backend)
        step(*[p[n] for n in FIELD_NAMES], **SCALARS)
        s = _stores(backend)
        advect, euler, diffuse, wsys, vsolve = _build_all(backend)
        advect(s["phi"], s["u"], s["v"], s["adv"], dx=SCALARS["dx"], dy=SCALARS["dy"], domain=DOM)
        euler(s["phi"], s["adv"], s["phi_star"], dt=SCALARS["dt"], domain=DOM)
        diffuse(s["phi_star"], s["phi_h"], alpha=SCALARS["alpha"], domain=DOM)
        # phi_star / phi_h are written inside the program but not returned
        for name in ("phi_star", "phi_h"):
            assert np.abs(np.asarray(p[name]) - np.asarray(s[name])).max() < 1e-12, (backend, name)
            assert float(np.abs(np.asarray(p[name])).max()) > 0.0


def test_compiled_cache_is_keyword_order_insensitive():
    step = _make_program("numpy")
    p = _stores("numpy")
    step(**{n: p[n] for n in FIELD_NAMES}, **SCALARS)
    step(**{n: p[n] for n in reversed(FIELD_NAMES)}, **SCALARS)
    assert len(step._cache) == 1  # no spurious retrace/recompile


def test_stencil_apply_accepts_superset_fields_dict():
    diffuse = _build_all("numpy")[2]
    s = _stores("numpy")
    updates = diffuse.apply(
        {"phi": s["phi"], "out": s["phi_h"], "unrelated": s["w"]},
        {"alpha": SCALARS["alpha"]},
        domain=DOM,
    )
    assert set(updates) == {"out"}


def test_program_rotation_rebinds_storages():
    step = _make_program("numpy")
    p = _stores("numpy")
    before_phi, before_new = p["phi"].data, p["phi_new"].data
    step(*[p[n] for n in FIELD_NAMES], **SCALARS)
    # ping-pong: the arrays swapped owners, no copy was made
    assert p["phi"].data is before_new
    assert p["phi_new"].data is before_phi


# ---------------------------------------------------------------------------
# iterate: n steps in one dispatch
# ---------------------------------------------------------------------------


def test_iterate_matches_stepwise():
    stepwise, _, _ = _program_steps("jax", NT)
    step = _make_program("jax")
    p = _stores("jax")
    step.iterate(NT, *[p[n] for n in FIELD_NAMES], **SCALARS)
    assert np.abs(np.asarray(p["phi"]) - stepwise).max() < 1e-12


def test_iterate_requires_rotation_closed_outputs():
    sc = gtscript.stencil(backend="jax")(euler_defs)

    @program(backend="jax", name="t_noniter")
    def step(phi, adv, out, *, dt):
        sc(phi, adv, out, dt=dt, domain=DOM)
        return {"result": out}  # not a program field name

    p = _stores("jax")
    with pytest.raises(ProgramError, match="cannot iterate"):
        step.iterate(3, p["phi"], p["adv"], p["phi_new"], dt=SCALARS["dt"])


def test_iterate_rejected_on_numpy_backend():
    step = _make_program("numpy")
    p = _stores("numpy")
    with pytest.raises(ProgramError, match="iterate\\(\\) requires"):
        step.iterate(2, *[p[n] for n in FIELD_NAMES], **SCALARS)


# ---------------------------------------------------------------------------
# caching & the generated artifact
# ---------------------------------------------------------------------------


def test_compiled_program_cached_per_geometry():
    step = _make_program("numpy")
    p = _stores("numpy")
    step(*[p[n] for n in FIELD_NAMES], **SCALARS)
    assert len(step._cache) == 1
    cp = next(iter(step._cache.values()))
    step(*[p[n] for n in FIELD_NAMES], **SCALARS)
    assert next(iter(step._cache.values())) is cp  # no retrace, no rebuild
    assert len(cp.fingerprint) == 16


def test_generated_orchestrator_is_inspectable():
    step = _make_program("jax")
    p = _stores("jax")
    step(*[p[n] for n in FIELD_NAMES], **SCALARS)
    cp = next(iter(step._cache.values()))
    src = cp.generated_source
    assert "Auto-generated by repro.program" in src
    assert "group_runs[0]" in src
    # the rotation is a dict rewiring in the artifact, not a copy
    assert "'phi': vals['phi_new']" in src
    assert "'phi_new': vals['phi']" in src
    # group modules are real cached stencil modules
    assert cp.group_objects[0].generated_source.startswith('"""Auto-generated')


def test_program_runs_on_pallas_backend():
    eager = _eager_steps("numpy", 2)
    prog, _, _ = _program_steps("pallas", 2)
    assert np.abs(prog - eager).max() < 1e-12


def test_different_domains_split_groups_and_stay_exact():
    sc = gtscript.stencil(backend="numpy")(euler_defs)
    small = (NI // 2, NJ // 2, NK)

    @program(backend="numpy", name="t_twodoms")
    def step(phi, adv, phi_star, phi_new, *, dt):
        sc(phi, adv, phi_star, dt=dt, domain=DOM)
        sc(phi_star, adv, phi_new, dt=dt, domain=small)
        return {"phi_new": phi_new, "phi_star": phi_star}

    p = _stores("numpy")
    info = {}
    step(p["phi"], p["adv"], p["phi_star"], p["phi_new"], dt=SCALARS["dt"], exec_info=info)
    assert info["program_report"]["groups"] == 2

    s = _stores("numpy")
    sc(s["phi"], s["adv"], s["phi_star"], dt=SCALARS["dt"], domain=DOM)
    sc(s["phi_star"], s["adv"], s["phi_new"], dt=SCALARS["dt"], domain=small)
    assert np.array_equal(np.asarray(p["phi_new"]), np.asarray(s["phi_new"]))
    assert np.array_equal(np.asarray(p["phi_star"]), np.asarray(s["phi_star"]))


# ---------------------------------------------------------------------------
# the climate step's fusion, halo plan and ensemble fields on jax
# ---------------------------------------------------------------------------


def test_climate_step_fuses_its_five_calls_into_one_jax_group():
    info = {}
    _program_steps("jax", 1, exec_info=info)
    rep = info["program_report"]
    assert (rep["groups"], rep["fused_stencils"]) == (1, 4)


def test_climate_step_plans_two_exchanges_a_step_against_six():
    """The halo plan a mesh decomposition of the step would run, planned
    from its graph with no devices: the vertical solve starts a second
    group, and two fields cross a shard edge per step where the eager chain
    exchanges six."""
    from repro.program.graph import ProgramGraph
    from repro.program.halo import plan_halo_exchanges
    from repro.program.passes import eliminate_dead_stores, plan_groups

    graph = ProgramGraph(_make_program("jax").trace(_stores("jax"), SCALARS))
    live, _dropped = eliminate_dead_stores(graph)
    graph.nodes = live
    groups, markers = plan_groups(graph, live, distributed=True)
    summary = plan_halo_exchanges(graph, groups, markers).summary()
    assert (len(groups), summary["inserted"], summary["baseline_per_step"]) == (2, 2, 6)


@pytest.mark.parametrize(
    "report_key, fields",
    [
        ("batched_fields", ["a", "adv", "b", "c", "d", "phi", "phi_h", "phi_new", "phi_star"]),
        ("shared_fields", ["u", "v", "w"]),
    ],
)
def test_climate_ensemble_batches_the_state_and_shares_the_winds(report_key, fields):
    """Eight members of the step: a field handed over member-batched is
    batched, the unbatched winds are shared by every member."""
    from repro.ensemble import Ensemble, batch, perturb

    members = 8
    stores = _stores("jax")
    args = {}
    for n in FIELD_NAMES:
        if n in ("u", "v", "w"):
            args[n] = stores[n]
        elif n == "phi":
            args[n] = perturb(stores[n], members, seed=0, amplitude=1e-3)
        else:
            args[n] = batch.broadcast(stores[n], members, backend="jax")
    info = {}
    Ensemble(_make_program("jax"), members)(*[args[n] for n in FIELD_NAMES], **SCALARS, exec_info=info)
    assert sorted(info["ensemble_report"][report_key]) == fields
