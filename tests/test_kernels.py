"""The DSL-generated hdiff and vadv kernels against their pure-jnp ref.py
oracles (Pallas interpret mode on CPU), with a hypothesis property test of
the vadv solver."""

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dep: property tests skip, the sweeps still run

    class _NoStrategies:
        def __getattr__(self, _name):
            return lambda *a, **k: None

    st = _NoStrategies()

    def given(*_a, **_k):
        return pytest.mark.skip(reason="hypothesis not installed")

    def settings(*_a, **_k):
        return lambda fn: fn


def _rand(shape, dtype=np.float32, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape).astype(dtype))


# ---------------------------------------------------------------------------
# DSL-generated hdiff / vadv kernels
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(12, 12, 4), (17, 23, 7)])
def test_hdiff_kernel_vs_ref(shape):
    from repro.kernels.hdiff.ops import hdiff
    from repro.kernels.hdiff.ref import hdiff_ref

    ni, nj, nk = shape
    x = _rand((ni + 6, nj + 6, nk), dtype=np.float64, seed=11)
    o = hdiff(x, 0.05, block=(4, 8))
    r = hdiff_ref(x, 0.05)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-12)


@pytest.mark.parametrize("shape", [(6, 6, 8), (5, 9, 17)])
def test_vadv_kernel_vs_ref(shape):
    from repro.kernels.vadv.ops import vadv
    from repro.kernels.vadv.ref import vadv_ref

    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=shape) * 0.1)
    b = jnp.asarray(2.0 + rng.random(shape))
    c = jnp.asarray(rng.normal(size=shape) * 0.1)
    d = jnp.asarray(rng.normal(size=shape))
    o = vadv(a, b, c, d, block=(4, 4))
    r = vadv_ref(a, b, c, d)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-10)


@settings(max_examples=10, deadline=None)
@given(nk=st.integers(2, 12))
def test_vadv_property_solves_system(nk):
    """M·x = d ⇒ residual ≈ 0 for random diagonally-dominant systems."""
    from repro.kernels.vadv.ops import vadv

    rng = np.random.default_rng(nk)
    shape = (3, 4, nk)
    a = jnp.asarray(rng.normal(size=shape) * 0.2)
    b = jnp.asarray(3.0 + rng.random(shape))
    c = jnp.asarray(rng.normal(size=shape) * 0.2)
    d = jnp.asarray(rng.normal(size=shape))
    x = np.asarray(vadv(a, b, c, d, block=(4, 4)))
    an, bn, cn, dn = map(np.asarray, (a, b, c, d))
    resid = bn * x + an * np.roll(x, 1, axis=2) * (np.arange(nk) > 0) \
        + cn * np.roll(x, -1, axis=2) * (np.arange(nk) < nk - 1) - dn
    assert np.max(np.abs(resid)) < 1e-8
