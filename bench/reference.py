"""Plain references the benchmark holds the timed path to.

Straightforward array code, written from the algorithms and importing
nothing of the program.  It runs with ``jax.numpy`` on the host CPU, which
is many times faster than numpy at the timed sizes.  Every function computes in ``dtype``: float64 is the reference,
and a lower precision (``ml_dtypes.bfloat16``, float32) is the control that a
sound limit must refuse.
"""

from __future__ import annotations

import functools

import numpy as np


def rel_err(got, ref) -> float:
    """Largest ``|got - ref|`` over the largest ``|ref|``, in float64."""
    ref = np.asarray(ref, dtype=np.float64)
    got = np.asarray(got, dtype=np.float64)
    scale = float(np.max(np.abs(ref)))
    return float(np.max(np.abs(got - ref)) / scale) if scale > 0 else float(np.max(np.abs(got)))


def _on_cpu(fn, arrays, dtype, *static):
    """``fn`` jitted over ``arrays`` cast to ``dtype``, on the host CPU."""
    import jax

    cpu = jax.devices("cpu")[0]
    dt_ = np.dtype(dtype)
    with jax.default_device(cpu), jax.enable_x64(dt_ == np.float64):
        args = [jax.device_put(np.asarray(a).astype(dt_), cpu) for a in arrays]
        return np.asarray(_jitted(fn)(*args, *static))


@functools.lru_cache(maxsize=None)
def _jitted(fn):
    import jax

    return jax.jit(fn)


def hdiff(x, alpha: float, lim: float, dtype=np.float64) -> np.ndarray:
    """Horizontal diffusion with a flux limiter on ``x`` shaped
    ``(ni + 6, nj + 6, nk)``; returns the ``(ni, nj, nk)`` interior update."""
    return _on_cpu(_hdiff, [x, np.asarray(alpha), np.asarray(lim)], dtype)


def _hdiff(x, alpha, lim):
    import jax.numpy as jnp

    def lap(a):  # on a[1:-1, 1:-1]
        return -4.0 * a[1:-1, 1:-1] + a[:-2, 1:-1] + a[2:, 1:-1] + a[1:-1, :-2] + a[1:-1, 2:]

    bilap = lap(lap(x))  # on x[2:-2, 2:-2]
    c = x[2:-2, 2:-2]
    fx = bilap[1:, :] - bilap[:-1, :]  # flux between i and i+1, i over x[2:-3]
    fy = bilap[:, 1:] - bilap[:, :-1]
    gx = c[1:, :] - c[:-1, :]
    gy = c[:, 1:] - c[:, :-1]
    fx = jnp.where(fx * gx > lim, fx, lim)
    fy = jnp.where(fy * gy > lim, fy, lim)
    # interior points are x[3:-3]: fluxes on the east/west and north/south faces
    dfx = fx[1:, 1:-1] - fx[:-1, 1:-1]
    dfy = fy[1:-1, 1:] - fy[1:-1, :-1]
    return x[3:-3, 3:-3] + alpha * (dfx + dfy)


def vadv(a, b, c, d, dtype=np.float64) -> np.ndarray:
    """Solve the tridiagonal systems ``(a, b, c) x = d`` along the last axis
    (the Thomas algorithm)."""
    return _on_cpu(_vadv, [a, b, c, d], dtype)


def _vadv(a, b, c, d):
    import jax
    import jax.numpy as jnp

    a, b, c, d = (jnp.moveaxis(v, -1, 0) for v in (a, b, c, d))  # levels first

    def forward(carry, abcd):
        cp_prev, dp_prev = carry
        ak, bk, ck, dk = abcd
        denom = bk - ak * cp_prev
        cp, dp = ck / denom, (dk - ak * dp_prev) / denom
        return (cp, dp), (cp, dp)

    zero = jnp.zeros_like(d[0])
    _, (cp, dp) = jax.lax.scan(forward, (zero, zero), (a, b, c, d))

    def backward(x_next, cpdp):
        cpk, dpk = cpdp
        x = dpk - cpk * x_next
        return x, x

    _, x = jax.lax.scan(backward, zero, (cp, dp), reverse=True)
    return jnp.moveaxis(x, 0, -1)


def forecast(phi, u, v, scalars, steps: int, dtype=np.float64) -> np.ndarray:
    """``steps`` of the served forecast: upwind advection, an Euler update and
    Laplacian diffusion on the interior, with the program's ``phi``/``phi_new``
    buffer rotation.  Arrays carry a one-point halo that no step writes, and
    the workspace (``phi_star``, ``phi_new``) starts at zero, so the halo
    alternates between ``phi``'s and zero.  Returns the final ``phi``."""
    import jax

    cpu = jax.devices("cpu")[0]
    dt_ = np.dtype(dtype)
    with jax.default_device(cpu), jax.enable_x64(dt_ == np.float64):
        args = [jax.device_put(np.asarray(a, dtype=dt_), cpu) for a in (phi, u, v)]
        sc = [np.asarray(scalars[k], dtype=dt_) for k in ("dx", "dy", "dt", "alpha")]
        return np.asarray(_forecast_jit()(*args, *sc, steps=int(steps)))


def _forecast_steps(phi, u, v, dx, dy, dt, alpha, *, steps):
    import jax
    import jax.numpy as jnp

    i = (slice(1, -1), slice(1, -1))
    w, e, s, n = (slice(None, -2), slice(1, -1)), (slice(2, None), slice(1, -1)), \
        (slice(1, -1), slice(None, -2)), (slice(1, -1), slice(2, None))
    east, north, uu, vv = u[i] > 0.0, v[i] > 0.0, u[i], v[i]
    star0 = jnp.zeros_like(phi)

    def step(_, pair):
        phi, new = pair
        fx = jnp.where(east, (phi[i] - phi[w]) / dx, (phi[e] - phi[i]) / dx)
        fy = jnp.where(north, (phi[i] - phi[s]) / dy, (phi[n] - phi[i]) / dy)
        star = star0.at[i].set(phi[i] + dt * (-(uu * fx + vv * fy)))
        lap = (star[w] + star[e] + star[s] + star[n]) - 4.0 * star[i]
        return new.at[i].set(star[i] + alpha * lap), phi

    return jax.lax.fori_loop(0, steps, step, (phi, jnp.zeros_like(phi)))[0]


@functools.lru_cache(maxsize=None)
def _forecast_jit():
    import jax

    return jax.jit(_forecast_steps, static_argnames=("steps",))
