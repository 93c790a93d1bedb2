"""Driver: the whole ensemble advanced on a device mesh by
``DistributedEnsemble.iterate``, dispatch after dispatch.

Every member's state is seeded on the chips, each shard on its own, and
never leaves them: each dispatch advances all members ``steps_per_call``
steps and its result, donated, is the next dispatch's input.  The window
waits for each dispatch before the next, so every counted step is done
when it closes.  Two members drawn from the seed are sliced out of the
state on the device after step ``check_first`` and after one step drawn
from ``check_later``; the slices reach the host after the window, and each
is compared on the whole grid with the plain reference from the same start.

Traffic keys: ``driver``, ``check_members``, ``check_first``,
``check_later``.
Configuration keys: ``domain``, ``dtype``, ``members``, ``mesh``,
``scalars``, ``steps_per_call``, ``limits``.
"""

from __future__ import annotations

import functools
import math
import sys
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from bench import harness, reference

from . import _serving

HALO = _serving.HALO
#: the mesh's axes, in the order of the batched fields' leading axes
AXES = ("ens", "data", "model")


def _seed(key, ni, nj, nk, members, plane):
    """The served forecast's seeding (``_serving._init``) one member at a
    time, each from its own key, and the winds from the run's key; without
    the ring: the mesh holds interiors, and its boundary is zero.  Each
    member is built on its ``plane`` sharding, so a chip holds its own tile
    and one member's temporaries."""
    import jax
    import jax.numpy as jnp

    inner = (slice(HALO, -HALO), slice(HALO, -HALO))

    def member(m):
        blob = _serving._init(jax.random.fold_in(key, m), ni, nj, nk, 1)[0][0]
        return jax.lax.with_sharding_constraint(blob, plane)[inner]

    _, u, v = _serving._init(key, ni, nj, nk, 1)
    return jax.lax.map(member, jnp.arange(members)), u[inner], v[inner]


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool) -> harness.Record:
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.ensemble import Ensemble
    from repro.stencils.forecast import build_forecast_step

    cfg, traffic = cell.config, cell.traffic
    ni, nj, nk = (int(d) for d in cfg["domain"])
    members, per_call = int(cfg["members"]), int(cfg["steps_per_call"])
    shape = tuple(int(cfg["mesh"][a]) for a in AXES)
    mesh = Mesh(np.array(jax.devices()[: cell.chips]).reshape(shape), AXES)
    batched = NamedSharding(mesh, P(*AXES, None))
    shared = NamedSharding(mesh, P(*AXES[1:], None))
    scalars = dict(cfg["scalars"])
    rec = harness.Record(cell=cell, device_kind=jax.devices()[0].device_kind, chips=cell.chips)

    dens = Ensemble(build_forecast_step("jax", (ni, nj, nk)), members).distribute(mesh, member_axis=AXES[0])
    iterate = dens.iterate
    checked, later = _samples(traffic, members, seed)
    sampled_steps = {int(traffic["check_first"]), later}

    seeding = jax.jit(functools.partial(_seed, ni=ni, nj=nj, nk=nk, members=members, plane=shared),
                      out_shardings=(batched, shared, shared))
    phi, u, v = seeding(jax.random.key(harness.seed_ints(seed, 1)[0]))

    def zeros(like, sharding):
        return jax.jit(lambda: jnp.zeros(like.shape, like.dtype), out_shardings=sharding)()

    # the workspace starts at zero; ``adv``, the step's eliminated temporary,
    # is never read, so one shared copy stands for it
    fields = {"phi": phi, "u": u, "v": v, "phi_star": zeros(phi, batched), "phi_new": zeros(phi, batched),
              "adv": zeros(u, shared)}
    del phi
    take = jax.jit(lambda a, m: lax.dynamic_index_in_dim(a, m, 0, keepdims=False), out_shardings=shared)

    # warm-up: compiles the dispatch; its result is step 0 of the checks,
    # whose members go to the host now, leaving the chips' memory to the state
    info: Dict[str, Any] = {}
    fields.update(iterate(per_call, fields, scalars, exec_info=info))
    start = {m: np.asarray(take(fields["phi"], m)) for m in checked}

    slices: Dict[Tuple[int, int], Any] = {}
    out: Dict[str, Any] = {}
    with harness.profiled(trace, cell.name, out):
        rec.setup_s = harness.process_age_s()
        with jax.profiler.TraceAnnotation(harness.WINDOW):
            t0 = time.perf_counter()
            steps = 0
            while steps < max(sampled_steps) or time.perf_counter() - t0 < seconds:
                fields.update(iterate(per_call, fields, scalars))
                steps += per_call
                if steps in sampled_steps:
                    # sliced before the next dispatch donates the state
                    slices.update({(m, steps): take(fields["phi"], m) for m in checked})
                fields["phi"].block_until_ready()
            rec.window_s = time.perf_counter() - t0
    rec.trace = out.get("trace")
    rec.attempted = steps // per_call
    report = info["ensemble_report"]
    rec.counters.update(
        points=ni * nj * nk * members, steps=steps, calls=rec.attempted,
        exchange_bytes_per_step=report["exchange_bytes_per_step"],
        exchanges_per_step=report["exchanges_per_step"],
        tile=[ni // shape[1], nj // shape[2], nk], members_per_chip=members // shape[0],
        itemsize=np.dtype(cfg["dtype"]).itemsize,
    )
    rec.memory_peak_bytes = harness.memory_peak_bytes(jax.devices()[: cell.chips])
    del fields
    got = {k: np.asarray(a) for k, a in slices.items()}
    winds = np.asarray(u), np.asarray(v)
    del slices, u, v
    rec.checks = _checks(cell, start, got, winds, later)
    print(f"bench: {steps} steps in {rec.attempted} dispatches; compared members {checked} after steps "
          f"{sorted(sampled_steps)} on the whole grid", file=sys.stderr)
    return rec


def _samples(traffic, members: int, seed: int) -> Tuple[List[int], int]:
    """The checked members, and the later checked step, drawn from the seed."""
    r = harness.rng(seed, 1)
    checked = sorted(int(m) for m in r.choice(members, size=int(traffic["check_members"]), replace=False))
    return checked, int(r.choice(traffic["check_later"]))


def _ring(a: np.ndarray) -> np.ndarray:
    """``a`` in a zero ring of the halo's width: the boundary the mesh sees."""
    out = np.zeros((a.shape[0] + 2 * HALO, a.shape[1] + 2 * HALO) + a.shape[2:], dtype=a.dtype)
    out[HALO:-HALO, HALO:-HALO] = a
    return out


def _checks(cell: harness.Cell, start, got, winds, later: int) -> List[harness.Check]:
    """Each sampled member's state against the float64 reference from its
    start, on the whole grid; with ``cell.control`` the reference in float32
    is read in the program's place too.  The later state's reference carries
    on from the first's: the ring stays zero, so the two make one run."""
    cfg = cell.config
    scalars = cfg["scalars"]
    first = int(cell.traffic["check_first"])
    u, v = (_ring(w) for w in winds)
    low = harness.lower_precision(cfg["dtype"])
    inner = (slice(HALO, -HALO), slice(HALO, -HALO))
    errs = dict.fromkeys(["forecast_err"] + (["control.forecast_err"] if cell.control else []), 0.0)
    dtypes = {"forecast_err": np.float64, "control.forecast_err": low}
    for m, phi0 in start.items():
        refs = {}
        for name in errs:
            state = _ring(phi0)
            for steps, ran in ((first, 0), (later, first)):
                state = reference.forecast(state, u, v, scalars, steps - ran, dtypes[name])
                refs.setdefault(steps, {})[name] = state
        for steps, ref in refs.items():
            exact = ref["forecast_err"][inner]
            for name in errs:
                answer = got.get((m, steps)) if name == "forecast_err" else ref[name][inner]
                errs[name] = max(errs[name], reference.rel_err(answer, exact) if answer is not None else math.inf)
    if not start:
        errs = dict.fromkeys(errs, math.inf)
    limit = float(cfg["limits"]["forecast_err"])
    return [harness.Check(n, v, limit) for n, v in errs.items()]
