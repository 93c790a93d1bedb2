"""Driver: model steps of a dynamical core through the stencil API.

Each step calls, for every prognostic field, ``hdiff(phi -> phi_h)`` and then
``vadv(a, b, c, d=phi_h -> phi_new)``, and rotates ``phi``/``phi_new``.  The
window calls back to back, keeping one pair of calls in flight: after
dispatching a field's pair it waits for the field before, so the host never
runs far ahead of the device and holds few buffers beyond the state, and
every counted step is done when the window closes.

Traffic keys: ``driver``, ``check_steps`` and ``check_within`` (the sampled
calls, see ``_samples``).
Configuration keys: ``domain``, ``dtype``, ``fields``, ``hdiff`` (``alpha``,
``lim``), ``vadv`` (``courant_max``), ``limits``.
"""

from __future__ import annotations

import functools
import math
import sys
import time
from typing import Any, Dict, List

import numpy as np

from bench import harness, reference

HALO = 3  # read extent of hdiff's input (bench/work/hdiff.py)


def _init(key, ni, nj, nk, nfields, courant, dtype):
    """Prognostic fields (waves of random phase plus noise) and the shared
    Crank-Nicolson vertical-advection system, all on the device and all in
    ``dtype``: under float64 the chip's compiler takes minutes over the
    emulated arithmetic at a whole grid."""
    import jax
    import jax.numpy as jnp

    shape = (ni + 2 * HALO, nj + 2 * HALO, nk)
    k_noise, k_phase, k_wind = jax.random.split(key, 3)
    x = jnp.linspace(0.0, 1.0, shape[0], dtype=dtype)[:, None, None]
    y = jnp.linspace(0.0, 1.0, shape[1], dtype=dtype)[None, :, None]
    z = jnp.linspace(0.0, 1.0, nk, dtype=dtype)[None, None, :]
    phase = 2 * math.pi * jax.random.uniform(k_phase, (nfields, 2), dtype=dtype)
    # noise field by field: all at once would take several times the state
    fields = [
        (1.0 + 0.5 * z) * jnp.cos(4 * math.pi * x + phase[f, 0]) * jnp.sin(6 * math.pi * y + phase[f, 1])
        + 0.1 * jax.random.normal(jax.random.fold_in(k_noise, f), shape, dtype=dtype)
        for f in range(nfields)
    ]
    # vertical wind: a random amplitude per column, zero at the ground and the
    # top, |w| <= 1, so |w| dt/dz <= courant everywhere
    amp = jax.random.uniform(k_wind, (ni, nj, 1), dtype=dtype, minval=-1.0, maxval=1.0)
    w = amp * jnp.sin(math.pi * jnp.linspace(0.0, 1.0, nk, dtype=dtype))[None, None, :]
    w_up = jnp.concatenate([w[..., 1:], w[..., -1:]], axis=-1)
    w_dn = jnp.concatenate([w[..., :1], w[..., :-1]], axis=-1)
    gcv = 0.25 * (w_up + w) * courant
    gcm = 0.25 * (w + w_dn) * courant
    a = (-gcm).at[..., 0].set(0.0)
    c = gcv.at[..., -1].set(0.0)
    b = (1.0 + gcv - gcm).at[..., 0].set(1.0 + gcv[..., 0]).at[..., -1].set(1.0 - gcm[..., -1])
    return fields, a.astype(dtype), b.astype(dtype), c.astype(dtype)


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool) -> harness.Record:
    import jax

    from repro.core import storage
    from repro.stencils.hdiff import build_hdiff
    from repro.stencils.vadv import build_vadv

    cfg = cell.config
    ni, nj, nk = (int(d) for d in cfg["domain"])
    dom = (ni, nj, nk)
    dtype = np.dtype(cfg["dtype"])
    nf = len(cfg["fields"])
    rec = harness.Record(cell=cell, device_kind=jax.devices()[0].device_kind)

    hd = build_hdiff("pallas", lim=float(cfg["hdiff"]["lim"]), dtype=dtype.name)
    va = build_vadv("pallas", dtype=dtype.name)
    alpha = dtype.type(cfg["hdiff"]["alpha"])
    init = jax.jit(functools.partial(
        _init, ni=ni, nj=nj, nk=nk, nfields=nf, courant=float(cfg["vadv"]["courant_max"]), dtype=dtype))
    fields, a, b, c = init(jax.random.key(harness.seed_ints(seed, 1)[0]))

    def halo_storage(arr):
        return storage.Storage(arr, backend="pallas", default_origin=(HALO, HALO, 0))

    phi = [halo_storage(f) for f in fields]
    new = [halo_storage(f) for f in fields]  # same ring: the boundary stays fixed
    del fields
    phi_h = halo_storage(phi[0].data)
    abc = [storage.Storage(v, backend="pallas") for v in (a, b, c)]
    samples = _samples(cell.traffic, nf, seed)
    call_s: List[float] = []

    def step(n: int, captured: List[tuple], synced: bool = False):
        """Model step ``n``: for each field, hdiff then vadv, waiting for the
        field before (one pair of calls in flight), then the rotation."""
        nonlocal phi, new
        prev = None
        for f in range(nf):
            src = phi[f].data
            if synced:
                hd(phi[f], phi_h, alpha=alpha, domain=dom)
                phi_h.data.block_until_ready()
                va(*abc, phi_h, new[f], domain=dom)
                new[f].data.block_until_ready()
            else:
                t = time.perf_counter()
                hd(phi[f], phi_h, alpha=alpha, domain=dom)
                call_s.append(time.perf_counter() - t)
                t = time.perf_counter()
                va(*abc, phi_h, new[f], domain=dom)
                call_s.append(time.perf_counter() - t)
            if (n, f) in samples:
                arrays = (src, phi_h.data, new[f].data)
                for x in arrays:
                    x.copy_to_host_async()
                captured.append(arrays)
            if prev is not None:
                prev.block_until_ready()
            prev = new[f].data
        prev.block_until_ready()
        phi, new = new, phi

    # warm-up: every shape and program the window and the traced calls use
    step(0, [])
    call_s.clear()

    captured: List[tuple] = []
    on_host: List[tuple] = []
    out: Dict[str, Any] = {}
    with harness.profiled(trace, cell.name, out):
        rec.setup_s = harness.process_age_s()
        with jax.profiler.TraceAnnotation(harness.WINDOW):
            t0 = time.perf_counter()
            steps = 0
            while steps == 0 or time.perf_counter() - t0 < seconds:
                steps += 1
                step(steps, captured)
                # a sampled call's arrays were copied while the step ran:
                # keep them on the host and free the device's copies
                on_host += [tuple(np.asarray(x) for x in arrays) for arrays in captured]
                captured.clear()
            rec.window_s = time.perf_counter() - t0
        if trace:
            # one step more, each call waited for: the last programs of the
            # trace are these calls, in this order (the roofline readers)
            step(-1, [], synced=True)
    rec.trace = out.get("trace")
    rec.attempted = steps
    rec.counters.update(
        points=ni * nj * nk, steps=steps, calls={"hdiff": nf, "vadv": nf},
        call_host_s=list(call_s), stencils={"hdiff": dom, "vadv": dom}, itemsize=dtype.itemsize,
        synced=["hdiff", "vadv"] * nf if trace else [],
    )
    rec.memory_peak_bytes = harness.memory_peak_bytes(jax.devices()[:1])
    del phi, new, phi_h, abc
    rec.checks = _checks(cell, on_host, [np.asarray(v) for v in (a, b, c)])
    reached = sorted((n, f) for n, f in samples if n <= steps)
    print(f"bench: checked the calls of {len(on_host)} sampled (step, field) pairs {reached} of {steps} steps",
          file=sys.stderr)
    return rec


def _samples(traffic, nfields: int, seed: int) -> set:
    """The (step, field) calls whose outputs are checked: the first step and
    ``check_steps - 1`` more drawn from the seed among the first
    ``check_within`` steps of the window, each with a field drawn from the
    seed.  A drawn step that the window does not reach is not checked."""
    r = harness.rng(seed, 1)
    later = r.choice(np.arange(2, int(traffic["check_within"]) + 1), size=int(traffic["check_steps"]) - 1,
                     replace=False)
    return {(int(n), int(r.integers(nfields))) for n in [1, *later]}


def _checks(cell: harness.Cell, samples, abc) -> List[harness.Check]:
    """Each sampled call's output against the float64 reference of the same
    input: hdiff of the field it read, vadv of the ``phi_h`` hdiff wrote.
    With ``cell.control`` the reference in the next lower precision is read
    in the program's place too.  No sample at all fails every check."""
    cfg = cell.config
    alpha, lim = cfg["hdiff"]["alpha"], cfg["hdiff"]["lim"]
    low = harness.lower_precision(cfg["dtype"])
    errs = dict.fromkeys(["hdiff_err", "vadv_err"] + (["control.hdiff_err", "control.vadv_err"]
                                                      if cell.control else []), 0.0)
    interior = (slice(HALO, -HALO), slice(HALO, -HALO))

    def worst(name, got, ref):
        errs[name] = max(errs[name], reference.rel_err(got, ref))

    for src, h, out in samples:
        h, out = h[interior], out[interior]
        ref_h, ref_v = reference.hdiff(src, alpha, lim), reference.vadv(*abc, h)
        worst("hdiff_err", h, ref_h)
        worst("vadv_err", out, ref_v)
        if cell.control:
            worst("control.hdiff_err", reference.hdiff(src, alpha, lim, low), ref_h)
            worst("control.vadv_err", reference.vadv(*abc, h, dtype=low), ref_v)
    if not samples:
        errs = dict.fromkeys(errs, math.inf)
    limits = cfg["limits"]
    return [harness.Check(n, v, float(limits[n.removeprefix("control.")])) for n, v in errs.items()]
