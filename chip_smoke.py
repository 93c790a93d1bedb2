#!/usr/bin/env python3
"""Smoke run of the main path on a TPU: a check that it starts and is right,
not a benchmark.  Every time it prints is a smoke-run time.

    python chip_smoke.py             # one chip: Pallas kernels, then the forecast server
    python chip_smoke.py --chips 4   # a 2x2 mesh: the sharded forecast against one chip, nothing else

One chip runs, in one process and in this order:

1. device:  the first JAX device must be a TPU; there is no CPU path.
2. caches:  JAX's compile cache follows ``$JAX_COMPILATION_CACHE_DIR`` or sits
   at ``.jax_cache/``; the generated-module and tune store starts empty at
   ``.gt_chip/``, so nothing generated elsewhere is read.
3. kernels: ``hdiff`` and ``vadv`` built on the ``pallas`` backend in float32
   at 256x256x80 (one chip's subdomain of a production-resolution run: COSMO-1
   has 80 levels), compiled by Mosaic, against ``kernels/*/ref.py`` in float64
   on the host.
4. serving: a ``ServingEngine`` holding the float64 forecast program at
   256x256x80 answers 8 concurrent requests of 8 steps, streamed every 4 steps;
   each final ``phi`` is held to a plain numpy float64 forecast.

``--chips 4`` runs only ``DistributedProgram.iterate(10)`` of the forecast
step at 512x512x80 over a 2x2 mesh (each chip holds the one-chip subdomain)
against the same program on one chip.

Nothing is caught and continued: a failing phase ends the script with a
nonzero exit code.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import asyncio
import functools
import json
import os
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import repro  # noqa: E402,F401  (float64 on: the served program and the references)

#: generated-module and tune store of this script, emptied at every run
GT_STORE = ROOT / ".gt_chip"

# Tolerances, each as the largest |got - ref| over the largest |ref|.
#
# float32 kernels against a float64 reference: an f32 operation rounds at
# 6e-8 relative and hdiff chains about thirty of them per point (Thomas'
# elimination in vadv is stable on a diagonally dominant system, so its error
# stays near the same size).  1e-5 leaves two orders of magnitude for the
# error to grow, and a bfloat16 kernel, rounding at 4e-3, misses it by more.
KERNEL_RTOL = 1e-5
# The served program is float64, which the TPU emulates in software: its
# error is not zero, but an f64 operation rounds at 1e-16 and eight steps
# chain a few dozen.  1e-9 leaves room for the emulation, and a float32 path
# (6e-8 per operation) misses it.
FORECAST_RTOL = 1e-9

#: hdiff flux-limiter threshold.  Zero is the COSMO form (a flux against the
#: gradient is cut), and it makes the limiter's decision depend on signs only:
#: a float32 difference has the sign of the exact one, and a flux whose sign
#: rounds differently is within rounding of zero, so cutting it moves the
#: result by less than the tolerance.
HDIFF_LIM = 0.0
HDIFF_ALPHA = 0.05


def log(phase: str, **values) -> None:
    print(f"[smoke] {phase}: " + " ".join(f"{k}={v}" for k, v in values.items()), flush=True)


def rel_err(got, ref) -> float:
    ref = np.asarray(ref, dtype=np.float64)
    return float(np.max(np.abs(np.asarray(got, dtype=np.float64) - ref)) / np.max(np.abs(ref)))


def check(name: str, err: float, tol: float) -> None:
    if not err <= tol:
        raise AssertionError(f"{name}: error {err!r} exceeds its tolerance {tol!r}")


def on_host(fn, *arrays, **kwargs):
    """``fn`` of ``arrays`` evaluated on the host CPU in float64, whatever the
    default device: the arrays are placed there inside the context."""
    with jax.default_device(jax.devices("cpu")[0]):
        return np.asarray(fn(*(jnp.asarray(a, jnp.float64) for a in arrays), **kwargs))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def device_phase(chips: int):
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: the first JAX device is {d.platform!r}, not a TPU; there is no CPU path")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} devices, JAX found {len(devices)}")
    log("device", platform=d.platform, kind=repr(d.device_kind), count=len(devices))
    return d


def caches_phase() -> None:
    from repro.core.caching import configure_compile_cache

    shutil.rmtree(GT_STORE, ignore_errors=True)
    os.environ["REPRO_GT_CACHE"] = str(GT_STORE)
    log("caches", compile_cache=configure_compile_cache(), gt_store=GT_STORE)


def _timed_call(fn):
    """(first-call seconds, compile included; second-call seconds)."""
    t0 = time.perf_counter()
    fn()
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    fn()
    return first, time.perf_counter() - t0


def kernels_phase(domain=(256, 256, 80), *, seed: int = 0) -> dict:
    """hdiff and vadv on the pallas backend in float32 against their plain
    references in float64 on the host.  Returns, per kernel, the error and
    whether the kernel ran interpreted."""
    from repro.core import storage
    from repro.kernels.hdiff.ref import hdiff_ref
    from repro.kernels.vadv.ref import vadv_ref
    from repro.stencils.hdiff import HALO, build_hdiff
    from repro.stencils.vadv import build_vadv

    ni, nj, nk = domain
    rng = np.random.default_rng(seed)
    out = {}

    st = build_hdiff("pallas", lim=HDIFF_LIM, dtype="float32")
    x = rng.normal(size=(ni + 2 * HALO, nj + 2 * HALO, nk)).astype(np.float32)
    inp = storage.from_array(x, backend="pallas", default_origin=(HALO, HALO, 0))
    res = storage.from_array(x, backend="pallas", default_origin=(HALO, HALO, 0))
    info: dict = {}

    def call_hdiff():
        st(inp, res, alpha=np.float32(HDIFF_ALPHA), domain=domain, exec_info=info)
        res.synchronize()

    first, second = _timed_call(call_hdiff)
    ref = on_host(functools.partial(hdiff_ref, alpha=HDIFF_ALPHA, lim=HDIFF_LIM), x)
    interior = (slice(HALO, -HALO), slice(HALO, -HALO))
    err = rel_err(res.to_numpy()[interior], ref[interior])
    out["hdiff"] = {"err": err, "interpret": info["interpret"]}
    log("kernels", stencil="hdiff", dtype="float32", domain=domain, interpret=info["interpret"],
        first_call_s=first, second_call_s=second, rel_err=err, tol=KERNEL_RTOL)
    check("hdiff float32 vs float64 reference", err, KERNEL_RTOL)

    # diagonally dominant tridiagonal systems, as the benchmark draws them
    st = build_vadv("pallas", dtype="float32")
    abcd = {
        "a": rng.normal(size=domain) * 0.1,
        "b": 2.0 + rng.random(domain),
        "c": rng.normal(size=domain) * 0.1,
        "d": rng.normal(size=domain),
    }
    abcd = {n: v.astype(np.float32) for n, v in abcd.items()}
    fs = {n: storage.from_array(v, backend="pallas") for n, v in abcd.items()}
    sol = storage.zeros(domain, dtype="float32", backend="pallas")
    info = {}

    def call_vadv():
        st(fs["a"], fs["b"], fs["c"], fs["d"], sol, domain=domain, exec_info=info)
        sol.synchronize()

    first, second = _timed_call(call_vadv)
    ref = on_host(vadv_ref, *(abcd[n] for n in "abcd"))
    err = rel_err(sol.to_numpy(), ref)
    out["vadv"] = {"err": err, "interpret": info["interpret"]}
    log("kernels", stencil="vadv", dtype="float32", domain=domain, interpret=info["interpret"],
        first_call_s=first, second_call_s=second, rel_err=err, tol=KERNEL_RTOL)
    check("vadv float32 vs float64 reference", err, KERNEL_RTOL)
    return out


def forecast_reference(phi, u, v, scalars, steps: int) -> np.ndarray:
    """Plain numpy float64 forecast: ``steps`` of upwind advection, an Euler
    update and Laplacian diffusion on the interior, with the program's
    ``phi``/``phi_new`` buffer rotation.  Every array carries a one-point
    halo that no step writes; the workspace starts at zero."""
    dx, dy, dt, alpha = (float(scalars[k]) for k in ("dx", "dy", "dt", "alpha"))
    c = (slice(1, -1), slice(1, -1))
    w, e, s, n = (slice(None, -2), slice(1, -1)), (slice(2, None), slice(1, -1)), \
        (slice(1, -1), slice(None, -2)), (slice(1, -1), slice(2, None))
    phi = np.array(phi, dtype=np.float64)
    adv, phi_star, phi_new = np.zeros_like(phi), np.zeros_like(phi), np.zeros_like(phi)
    for _ in range(steps):
        fx = np.where(u[c] > 0.0, (phi[c] - phi[w]) / dx, (phi[e] - phi[c]) / dx)
        fy = np.where(v[c] > 0.0, (phi[c] - phi[s]) / dy, (phi[n] - phi[c]) / dy)
        adv[c] = -(u[c] * fx + v[c] * fy)
        phi_star[c] = phi[c] + dt * adv[c]
        p = phi_star
        phi_new[c] = p[c] + alpha * (-4.0 * p[c] + (p[w] + p[e] + p[s] + p[n]))
        phi, phi_new = phi_new, phi
    return phi


def serving_phase(
    domain=(256, 256, 80), *, requests: int = 8, steps: int = 8, stream_every: int = 4,
    member_counts=(1, 2, 4, 8),
) -> dict:
    """The forecast program served on its default path (jax backend, float64,
    warmed) answering ``requests`` concurrent requests, each held to
    :func:`forecast_reference`, then the first request again on its own."""
    from repro.launch.serve import build_forecast_entry
    from repro.serving import RequestSpec, ServingEngine, drive_engine
    from repro.stencils.forecast import request_state

    engine = ServingEngine()
    t0 = time.perf_counter()
    entry = build_forecast_entry(
        engine, backend="jax", domain=domain, member_counts=member_counts, warm=True, warm_chunk=stream_every
    )
    warm_s = time.perf_counter() - t0
    tmpl = {n: np.asarray(entry.fields[n].data) for n in ("u", "v")}
    states = [request_state(domain, seed=i + 1) for i in range(requests)]
    specs = [
        RequestSpec(program=entry.name, fields={"phi": s}, steps=steps, stream_every=stream_every)
        for s in states
    ]

    async def drive():
        async with engine:
            batched = await drive_engine(engine, specs, keep_fields="final")
            alone = await drive_engine(engine, specs[:1], keep_fields="final")
        return batched, alone

    t0 = time.perf_counter()
    batched, alone = asyncio.run(drive())
    drive_s = time.perf_counter() - t0
    want_steps = list(range(stream_every, steps + 1, stream_every))
    for r in batched.results + alone.results:
        if not r.ok:
            raise AssertionError(f"request {r.request_id} failed: {r.error_code} {r.error_reason}")
        if not r.in_order or r.steps_seen != want_steps:
            raise AssertionError(f"request {r.request_id} streamed steps {r.steps_seen}, want {want_steps}")
    errs = [
        rel_err(r.final_fields["phi"], forecast_reference(s, tmpl["u"], tmpl["v"], entry.scalars, steps))
        for r, s in zip(batched.results, states)
    ]
    solo_dev = float(np.max(np.abs(batched.results[0].final_fields["phi"] - alone.results[0].final_fields["phi"])))
    log("serving", batched_vs_alone_max_abs_dev=solo_dev,
        batch_members=sorted({r.members for r in batched.results}))
    out = {"errs": errs, "batched_vs_alone": solo_dev, "warm_s": warm_s, "drive_s": drive_s}
    log("serving", program=entry.name, dtype="float64", domain=domain, requests=requests, steps=steps,
        stream_every=stream_every, warm_and_compile_s=warm_s, drive_s=drive_s, all_in_order=True,
        max_rel_err=max(errs), tol=FORECAST_RTOL)
    check("served forecast vs numpy float64 reference", max(errs), FORECAST_RTOL)
    return out


def mesh_phase(devices, domain=(512, 512, 80), *, steps: int = 10) -> dict:
    """``DistributedProgram.iterate(steps)`` of the forecast step on a 2x2
    mesh of ``devices`` against the undistributed program on the first one,
    over the same zero-boundary domain."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import storage
    from repro.stencils.forecast import HALO, build_forecast_step, make_forecast_fields

    mesh = Mesh(np.array(devices[:4]).reshape(2, 2), ("data", "model"))
    template, scalars = make_forecast_fields("numpy", domain)
    interior = {n: np.asarray(f.data)[HALO:-HALO, HALO:-HALO, :] for n, f in template.items()}
    step = build_forecast_step("jax", domain)

    # one chip: the same interior in a zero ring, the boundary the mesh sees
    def padded(a):
        p = np.zeros(template["phi"].shape)
        p[HALO:-HALO, HALO:-HALO, :] = a
        return storage.from_array(p, backend="jax", default_origin=(HALO, HALO, 0))

    with jax.default_device(devices[0]):
        single = {n: padded(a) for n, a in interior.items()}
        t0 = time.perf_counter()
        ref = step.iterate(steps, **single, **scalars)["phi"]
        ref = np.asarray(ref)[HALO:-HALO, HALO:-HALO, :]
        single_s = time.perf_counter() - t0

    sharding = NamedSharding(mesh, P("data", "model", None))
    sharded = {n: jax.device_put(a, sharding) for n, a in interior.items()}
    dp = step.distribute(mesh)
    info: dict = {}
    t0 = time.perf_counter()
    got = dp.iterate(steps, sharded, scalars, exec_info=info)
    jax.block_until_ready(got)
    dist_s = time.perf_counter() - t0

    quarter = (domain[0] // 2, domain[1] // 2, domain[2])
    for name, arr in list(sharded.items()) + list(got.items()):
        shards = arr.addressable_shards
        held = {s.device for s in shards}
        if len(shards) != 4 or held != set(devices[:4]) or any(s.data.shape != quarter for s in shards):
            raise AssertionError(
                f"{name}: shards {[(str(s.device), s.data.shape) for s in shards]}, want one {quarter} per device"
            )
    exchanges = info["program_report"]["halo_plan"]["inserted"]
    if exchanges != 2:
        raise AssertionError(f"halo plan has {exchanges} exchanges per step, want 2")
    err = rel_err(got["phi"], ref)
    log("mesh", mesh="2x2", domain=domain, per_chip=quarter, steps=steps, exchanges_per_step=exchanges,
        bit_identical=bool(np.array_equal(np.asarray(got["phi"]), ref)), rel_err=err, tol=FORECAST_RTOL,
        single_chip_s=single_s, mesh_s=dist_s)
    check("2x2 mesh vs one chip", err, FORECAST_RTOL)
    return {"err": err, "exchanges": exchanges}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 2x2-mesh phase (needs four chips)")
    args = ap.parse_args(argv)

    device = device_phase(args.chips)
    caches_phase()
    t0 = time.perf_counter()
    if args.chips == 4:
        mesh_phase(jax.devices())
    else:
        kernels = kernels_phase()
        ran = [name for name, k in kernels.items() if k["interpret"]]
        if ran:
            raise AssertionError(f"kernels ran interpreted on the TPU: {ran}")
        serving_phase()
    stats = device.memory_stats() or {}
    log("done", smoke_run_s=time.perf_counter() - t0, peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind, "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
