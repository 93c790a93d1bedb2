"""Distributed program: fused sharded step vs the eager per-stencil chain.

jax fixes the device count at first init, so multi-device tests run in a
subprocess with ``--xla_force_host_platform_device_count=8`` (same harness
as ``test_distributed.py``).
"""

import json
import os
import subprocess
import sys
import tempfile
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parent.parent / "src")


def _run_subprocess(body: str) -> dict:
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys, json
        sys.path.insert(0, {src!r})
        import repro
        import jax, jax.numpy as jnp
        import numpy as np
        """
    ).format(src=SRC) + textwrap.dedent(body)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    with tempfile.NamedTemporaryFile("w", suffix=".py", delete=False) as f:
        f.write(script)
        path = f.name
    try:
        res = subprocess.run([sys.executable, path], capture_output=True, text=True, timeout=600, env=env)
    finally:
        os.unlink(path)
    if res.returncode != 0:
        raise AssertionError(f"subprocess failed:\n{res.stderr[-3000:]}")
    return json.loads(res.stdout.strip().splitlines()[-1])


_STEP_DEFS = """
from repro.core import gtscript
from repro.core.gtscript import Field, PARALLEL, computation, interval
from repro.program import program
from repro.stencils.library import laplacian
from repro.stencils.distributed import DistributedStencil

def diffuse_defs(phi: Field[np.float64], out: Field[np.float64], *, alpha: np.float64):
    with computation(PARALLEL), interval(...):
        out = phi + alpha * laplacian(phi)

def advect_defs(phi: Field[np.float64], u: Field[np.float64], v: Field[np.float64],
                adv: Field[np.float64], *, dx: np.float64, dy: np.float64):
    with computation(PARALLEL), interval(...):
        fx = (phi[0, 0, 0] - phi[-1, 0, 0]) / dx if u > 0.0 else (phi[1, 0, 0] - phi[0, 0, 0]) / dx
        fy = (phi[0, 0, 0] - phi[0, -1, 0]) / dy if v > 0.0 else (phi[0, 1, 0] - phi[0, 0, 0]) / dy
        adv = -(u * fx + v * fy)

def euler_defs(phi: Field[np.float64], adv: Field[np.float64], out: Field[np.float64],
               *, dt: np.float64):
    with computation(PARALLEL), interval(...):
        out = phi + dt * adv

be = "jax"
build = gtscript.stencil(backend=be)
advect, euler, diffuse = build(advect_defs), build(euler_defs), build(diffuse_defs)

mesh = jax.make_mesh((4, 2), ("data", "model"))
NI, NJ, NK, NT = 32, 16, 6, 10
rng = np.random.default_rng(0)
phi0 = rng.normal(size=(NI, NJ, NK))
u0 = np.full((NI, NJ, NK), 0.8)
v0 = np.full((NI, NJ, NK), -0.4)
sc = {"dx": np.float64(1.0), "dy": np.float64(1.0), "dt": np.float64(0.1),
      "alpha": np.float64(0.05)}

def fresh_fields():
    return {"phi": jnp.asarray(phi0), "u": jnp.asarray(u0), "v": jnp.asarray(v0),
            "adv": jnp.zeros((NI, NJ, NK)), "phi_star": jnp.zeros((NI, NJ, NK)),
            "phi_new": jnp.zeros((NI, NJ, NK))}

@program(backend=be, name="dist_climate")
def step(phi, u, v, adv, phi_star, phi_new, *, dx, dy, dt, alpha):
    advect(phi, u, v, adv, dx=dx, dy=dy)
    euler(phi, adv, phi_star, dt=dt)
    diffuse(phi_star, phi_new, alpha=alpha)
    return {"phi": phi_new, "phi_new": phi}
"""


def test_distributed_program_bit_identical_to_eager_chain():
    out = _run_subprocess(
        _STEP_DEFS
        + textwrap.dedent("""
        # ---- eager chain: one DistributedStencil call per stencil per step
        d_advect = DistributedStencil(advect, mesh)
        d_euler = DistributedStencil(euler, mesh)
        d_diffuse = DistributedStencil(diffuse, mesh)
        f = fresh_fields()
        for _ in range(NT):
            f["adv"] = d_advect({"phi": f["phi"], "u": f["u"], "v": f["v"],
                                 "adv": f["adv"]}, {"dx": sc["dx"], "dy": sc["dy"]})["adv"]
            f["phi_star"] = d_euler({"phi": f["phi"], "adv": f["adv"],
                                     "out": f["phi_star"]}, {"dt": sc["dt"]})["out"]
            new = d_diffuse({"phi": f["phi_star"], "out": f["phi_new"]},
                            {"alpha": sc["alpha"]})["out"]
            f["phi"], f["phi_new"] = new, f["phi"]

        # ---- fused program: one shard_map jit per step, minimal exchanges
        dp = step.distribute(mesh)
        g = fresh_fields()
        info = {}
        for t in range(NT):
            out = dp(g, sc, exec_info=info if t == 0 else None)
            g["phi"], g["phi_new"] = out["phi"], out["phi_new"]

        rep = info["program_report"]
        err = float(np.abs(np.asarray(g["phi"]) - np.asarray(f["phi"])).max())
        print(json.dumps({
            "err": err,
            "groups": rep["groups"],
            "fused": rep["fused_stencils"],
            "eliminated": rep["eliminated_temporaries"],
            "inserted": rep["halo_plan"]["inserted"],
            "baseline": rep["halo_plan"]["baseline_per_step"],
        }))
        """)
    )
    assert out["err"] == 0.0  # bit-identical across 10 sharded steps
    assert out["fused"] >= 1
    assert out["eliminated"] == ["adv"]
    # minimal plan: phi before the advect group, phi_star before diffuse —
    # vs six per step for the eager chain (every field of every call)
    assert out["inserted"] == 2
    assert out["baseline"] == 6
    assert out["inserted"] < out["baseline"]


def test_distributed_program_matches_single_device():
    out = _run_subprocess(
        _STEP_DEFS
        + textwrap.dedent("""
        # single-device numpy oracle with the same zero-halo boundary: embed
        # the global domain in a zero-padded buffer
        from repro.core import storage
        buildn = gtscript.stencil(backend="numpy")
        n_advect, n_euler, n_diffuse = (buildn(advect_defs), buildn(euler_defs),
                                        buildn(diffuse_defs))
        H = 1
        shape = (NI + 2 * H, NJ + 2 * H, NK)
        def pad(x):
            p = np.zeros(shape)
            p[H:-H, H:-H, :] = x
            return p
        s = {n: storage.from_array(pad(a), default_origin=(H, H, 0))
             for n, a in (("phi", phi0), ("u", u0), ("v", v0))}
        for n in ("adv", "phi_star", "phi_new"):
            s[n] = storage.zeros(shape, default_origin=(H, H, 0))
        dom = (NI, NJ, NK)
        for _ in range(NT):
            n_advect(s["phi"], s["u"], s["v"], s["adv"], dx=sc["dx"], dy=sc["dy"], domain=dom)
            n_euler(s["phi"], s["adv"], s["phi_star"], dt=sc["dt"], domain=dom)
            n_diffuse(s["phi_star"], s["phi_new"], alpha=sc["alpha"], domain=dom)
            s["phi"], s["phi_new"] = s["phi_new"], s["phi"]
        ref = s["phi"].to_numpy()[H:-H, H:-H, :]

        dp = step.distribute(mesh)
        g = fresh_fields()
        for _ in range(NT):
            out = dp(g, sc)
            g["phi"], g["phi_new"] = out["phi"], out["phi_new"]
        err = float(np.abs(np.asarray(g["phi"]) - ref).max())
        print(json.dumps({"err": err}))
        """)
    )
    # cross-backend (XLA vs numpy) agreement at rounding level over 10 steps
    assert out["err"] < 1e-12


def test_forced_exchange_marker_honoured():
    out = _run_subprocess(
        _STEP_DEFS
        + textwrap.dedent("""
        from repro.parallel.halo import request_exchange

        @program(backend=be, name="dist_forced")
        def fstep(phi, u, v, adv, phi_star, phi_new, *, dx, dy, dt, alpha):
            request_exchange(phi, 2)
            advect(phi, u, v, adv, dx=dx, dy=dy)
            euler(phi, adv, phi_star, dt=dt)
            diffuse(phi_star, phi_new, alpha=alpha)
            return {"phi": phi_new, "phi_new": phi}

        dp = fstep.distribute(mesh)
        g = fresh_fields()
        info = {}
        out = dp(g, sc, exec_info=info)
        ops = info["program_report"]["halo_plan"]["ops"]
        forced = [o for o in ops if o["forced"]]
        print(json.dumps({"n_ops": len(ops), "forced": forced}))
        """)
    )
    assert out["forced"] == [{"buffer": "phi", "halo": 2, "before_group": 0, "forced": True}]
    # the forced depth-2 exchange covers advect's depth-1 need: no extra op
    assert out["n_ops"] == 2


def test_distributed_iterate_bit_identical_to_eager_distributed_loop():
    """``DistributedProgram.iterate(n)``: n sharded steps in ONE fori_loop
    dispatch, the 2-exchange/step plan applied per iteration — bit-identical
    to n eager distributed calls."""
    out = _run_subprocess(
        _STEP_DEFS
        + textwrap.dedent("""
        dp = step.distribute(mesh)

        # eager: NT separate sharded dispatches with host-side rotation
        g = fresh_fields()
        for _ in range(NT):
            o = dp(g, sc)
            g["phi"], g["phi_new"] = o["phi"], o["phi_new"]

        # fused: one fori_loop dispatch
        info = {}
        final = dp.iterate(NT, fresh_fields(), sc, exec_info=info)
        rep = info["program_report"]
        err = float(np.abs(np.asarray(final["phi"]) - np.asarray(g["phi"])).max())
        print(json.dumps({
            "err": err,
            "iterated": rep["iterated_steps"],
            "inserted": rep["halo_plan"]["inserted"],
        }))
        """)
    )
    assert out["err"] == 0.0  # bit-identical across 10 fused sharded steps
    assert out["iterated"] == 10
    assert out["inserted"] == 2  # the minimal plan runs inside every iteration


def test_distributed_iterate_requires_rotation_closed_outputs():
    out = _run_subprocess(
        _STEP_DEFS
        + textwrap.dedent("""
        from repro.program import ProgramError

        @program(backend=be, name="dist_open")
        def open_step(phi, u, v, adv, *, dx, dy):
            advect(phi, u, v, adv, dx=dx, dy=dy)
            return {"tendency": adv}

        dp = open_step.distribute(mesh)
        f = {"phi": jnp.asarray(phi0), "u": jnp.asarray(u0), "v": jnp.asarray(v0),
             "adv": jnp.zeros((NI, NJ, NK))}
        try:
            dp.iterate(3, f, {"dx": sc["dx"], "dy": sc["dy"]})
            failed = False
        except ProgramError:
            failed = True
        print(json.dumps({"raised": failed}))
        """)
    )
    assert out["raised"] is True


def test_distributed_ensemble_members_times_domain_sharding():
    """Member x domain co-sharding: the member axis shards over its own mesh
    axis, domain tiles over (data, model), local members advance under vmap
    (batched halo exchanges) — and the result matches the single-device
    ensemble at rounding level."""
    out = _run_subprocess(
        _STEP_DEFS.replace(
            'mesh = jax.make_mesh((4, 2), ("data", "model"))',
            'mesh = jax.make_mesh((2, 2, 2), ("ens", "data", "model"))',
        )
        + textwrap.dedent("""
        from repro.core.storage import Storage
        from repro.ensemble import Ensemble, perturb
        from repro.ensemble import batch as B

        NMEM = 4
        ens = Ensemble(step, NMEM)

        # single-device oracle: python loop over per-member compiled programs
        # on padded (zero-halo-matching) storages
        Hh = 1
        shape = (NI + 2 * Hh, NJ + 2 * Hh, NK)
        def pad(x):
            p = np.zeros(shape)
            p[Hh:-Hh, Hh:-Hh, :] = x
            return p
        phi_b = perturb(
            Storage(pad(phi0), backend="jax", default_origin=(Hh, Hh, 0)),
            NMEM, seed=0, amplitude=1e-3)
        # zero the perturbation outside the interior so the zero-halo
        # boundary of the mesh decomposition is reproduced exactly
        noise_masked = np.zeros((NMEM,) + shape)
        noise_masked[:, Hh:-Hh, Hh:-Hh, :] = np.asarray(phi_b.data)[:, Hh:-Hh, Hh:-Hh, :]
        phi_b = Storage(noise_masked, backend="jax", default_origin=(0, Hh, Hh, 0),
                        axes=("N", "I", "J", "K"))

        refs = []
        for m in range(NMEM):
            mf = {
                "phi": Storage(np.asarray(phi_b.data)[m].copy(), backend="jax",
                               default_origin=(Hh, Hh, 0)),
                "u": Storage(pad(u0), backend="jax", default_origin=(Hh, Hh, 0)),
                "v": Storage(pad(v0), backend="jax", default_origin=(Hh, Hh, 0)),
            }
            for n in ("adv", "phi_star", "phi_new"):
                mf[n] = Storage(np.zeros(shape), backend="jax", default_origin=(Hh, Hh, 0))
            step(mf["phi"], mf["u"], mf["v"], mf["adv"], mf["phi_star"], mf["phi_new"], **sc)
            refs.append(np.asarray(mf["phi"].data)[Hh:-Hh, Hh:-Hh, :])
        ref = np.stack(refs)

        # distributed ensemble: GLOBAL interior-only arrays, members sharded
        # over the "ens" mesh axis, domain tiles over (data, model)
        dens = ens.distribute(mesh, member_axis="ens")
        g = {
            "phi": jnp.asarray(np.asarray(phi_b.data)[:, Hh:-Hh, Hh:-Hh, :]),
            "u": jnp.asarray(u0), "v": jnp.asarray(v0),
            "adv": jnp.zeros((NMEM, NI, NJ, NK)),
            "phi_star": jnp.zeros((NMEM, NI, NJ, NK)),
            "phi_new": jnp.zeros((NMEM, NI, NJ, NK)),
        }
        info = {}
        o = dens(g, sc, exec_info=info)
        rep = info["ensemble_report"]
        err = float(np.abs(np.asarray(o["phi"]) - ref).max())
        print(json.dumps({
            "err": err,
            "members": rep["members"],
            "per_shard": rep["members_per_shard"],
            "inserted": rep["program_report"]["halo_plan"]["inserted"],
            "out_shape": list(np.asarray(o["phi"]).shape),
        }))
        """)
    )
    assert out["err"] < 1e-12  # member x domain sharding matches the oracle
    assert out["members"] == 4 and out["per_shard"] == 2
    assert out["inserted"] == 2  # one exchange serves ALL local members
    assert out["out_shape"] == [4, 32, 16, 6]


# --- DistributedEnsemble.iterate -------------------------------------------------

_ENS_ITERATE = """
from jax.sharding import Mesh
from repro.ensemble import Ensemble

MESH, NMEM = {mesh!r}, {members}
NI, NJ, NK, NT = 18, 14, 6, 5  # odd tiles on both meshes
devs = np.array(jax.devices()[: int(np.prod(MESH))])
mesh3 = Mesh(devs.reshape(MESH), ("ens", "data", "model"))
mesh2 = Mesh(devs.reshape(MESH)[0], ("data", "model"))
rng = np.random.default_rng(3)
phi0 = rng.normal(size=(NMEM, NI, NJ, NK))
u0 = np.full((NI, NJ, NK), 0.8)
v0 = np.full((NI, NJ, NK), -0.4)
u0[: NI // 2] = -0.8  # both upwind branches, across a tile edge
v0[:, : NJ // 3] = 0.4

def fresh():
    z = np.zeros((NMEM, NI, NJ, NK))
    return {{"phi": jnp.asarray(phi0), "u": jnp.asarray(u0), "v": jnp.asarray(v0), "adv": jnp.asarray(z),
            "phi_star": jnp.asarray(z), "phi_new": jnp.asarray(z)}}
"""


def _ens_iterate_script(mesh, members, body: str) -> str:
    return _STEP_DEFS + textwrap.dedent(_ENS_ITERATE.format(mesh=mesh, members=members)) + textwrap.dedent(body)


ENSEMBLE_MESHES = {"1x2x2": ((1, 2, 2), 3), "2x2x1": ((2, 2, 1), 4)}


@pytest.mark.parametrize("layout", sorted(ENSEMBLE_MESHES))
def test_distributed_ensemble_iterate_matches_every_other_path(layout):
    """``DistributedEnsemble.iterate(n)`` against n ``__call__`` steps and
    per-member ``DistributedProgram.iterate(n)`` (bit for bit), and against
    the undistributed ``Ensemble.iterate`` on the same interior in a zero
    ring, the boundary the mesh sees (rounding level)."""
    out = _run_subprocess(_ens_iterate_script(*ENSEMBLE_MESHES[layout], """
        from repro.core.storage import Storage

        dens = Ensemble(step, NMEM).distribute(mesh3)
        stepped = fresh()
        for _ in range(NT):
            o = dens(stepped, sc)
            stepped["phi"], stepped["phi_new"] = o["phi"], o["phi_new"]
        fused = dens.iterate(NT, fresh(), sc)

        dp = step.distribute(mesh2)
        per_member = []
        for m in range(NMEM):
            f = {n: (a[m] if a.ndim == 4 else a) for n, a in fresh().items()}
            per_member.append(np.asarray(dp.iterate(NT, f, sc)["phi"]))

        H = 1
        def ring(a):
            p = np.zeros(a.shape[:-3] + (NI + 2 * H, NJ + 2 * H, NK))
            p[..., H:-H, H:-H, :] = a
            return p
        st = {n: Storage(ring(np.asarray(a)), backend="jax",
                         default_origin=(0, H, H, 0) if a.ndim == 4 else (H, H, 0),
                         axes=("N", "I", "J", "K") if a.ndim == 4 else ("I", "J", "K"))
              for n, a in fresh().items()}
        single = np.asarray(Ensemble(step, NMEM).iterate(NT, **st, **sc)["phi"])[:, H:-H, H:-H, :]

        got = np.asarray(fused["phi"])
        print(json.dumps({
            "keys": sorted(fused),
            "vs_call": float(np.abs(got - np.asarray(stepped["phi"])).max()),
            "vs_call_new": float(np.abs(np.asarray(fused["phi_new"]) - np.asarray(stepped["phi_new"])).max()),
            "vs_program": float(np.abs(got - np.stack(per_member)).max()),
            "vs_single": float(np.abs(got - single).max()),
            "moved": float(np.abs(got - phi0).max()),
            "shards": sorted({tuple(x.data.shape) for x in fused["phi"].addressable_shards}),
            "tile": [NMEM // MESH[0], NI // MESH[1], NJ // MESH[2], NK],
        }))
        """))
    assert out["keys"] == ["phi", "phi_new", "phi_star"]  # the carried state, fed back as is
    assert out["vs_call"] == 0.0 and out["vs_call_new"] == 0.0
    assert out["vs_program"] == 0.0
    assert out["vs_single"] < 1e-12
    assert out["moved"] > 1e-3  # the steps did something
    assert out["shards"] == [out["tile"]]  # left sharded as it came


def test_distributed_ensemble_iterate_donates_the_carried_state():
    """The member-batched fields the loop carries are donated (their buffers
    become the result's); shared fields and unused ones are left alone, and a
    batched Storage is rebound to the result."""
    out = _run_subprocess(_ens_iterate_script((1, 2, 2), 3, """
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.storage import Storage

        dens = Ensemble(step, NMEM).distribute(mesh3)
        placed = {n: jax.device_put(a, NamedSharding(mesh3, P("ens", "data", "model", None) if a.ndim == 4
                                                     else P("data", "model", None)))
                  for n, a in fresh().items()}
        given = dict(placed)
        out = dens.iterate(2, placed, sc)
        deleted = {n: a.is_deleted() for n, a in given.items()}

        st = Storage(np.asarray(phi0), backend="jax", default_origin=(0, 0, 0, 0), axes=("N", "I", "J", "K"))
        f = {**fresh(), "phi": st}
        res = dens.iterate(2, f, sc)
        print(json.dumps({"deleted": deleted, "rebound": st.data is res["phi"],
                          "same_answer": bool(np.array_equal(np.asarray(res["phi"]), np.asarray(out["phi"])))}))
        """))
    assert out["deleted"] == {"phi": True, "phi_star": True, "phi_new": True,
                              "u": False, "v": False, "adv": False}
    assert out["rebound"] and out["same_answer"]


@pytest.mark.parametrize("layout", sorted(ENSEMBLE_MESHES))
def test_distributed_ensemble_report_counts_exchanges_and_bytes(layout):
    """The report carries the exchanges per step and the bytes a chip ships
    per step: one-point I stripes, then J stripes that carry the I halo, for
    every local member in one stripe, on a mesh with no wrap-around."""
    mesh, members = ENSEMBLE_MESHES[layout]
    out = _run_subprocess(_ens_iterate_script(mesh, members, """
        dens = Ensemble(step, NMEM).distribute(mesh3)
        info, iinfo = {}, {}
        dens(fresh(), sc, exec_info=info)
        dens.iterate(3, fresh(), sc, exec_info=iinfo)
        r, ri = info["ensemble_report"], iinfo["ensemble_report"]
        print(json.dumps({k: r[k] for k in ("exchanges_per_step", "exchange_bytes_per_step", "members_per_shard")}
                         | {"program": r["program_report"]["exchange_bytes_per_step"],
                            "iterated": ri["iterated_steps"], "same": ri["exchange_bytes_per_step"]}))
        """))
    # float64, halo 1, two exchanges (phi, phi_star) a step; local tiles 9 x 7
    # on (1, 2, 2), each chip with one neighbour in I and one in J; 9 x 14 on
    # (2, 2, 1), with one in I and none in J
    per_member = {"1x2x2": 2 * 8 * 6 * (7 + (9 + 2)), "2x2x1": 2 * 8 * 6 * 14}[layout]
    assert out["exchanges_per_step"] == 2 and out["program"] == per_member
    assert out["members_per_shard"] == {"1x2x2": 3, "2x2x1": 2}[layout]
    assert out["exchange_bytes_per_step"] == out["same"] == out["members_per_shard"] * per_member
    assert out["iterated"] == 3
