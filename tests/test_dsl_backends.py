"""Backend agreement tests: debug (oracle) vs numpy vs jax vs pallas.

The debug backend is generated scalar triple-loops with true per-point
semantics; every other backend must agree with it bit-for-bit (float64) or
to tight tolerance.
"""

import numpy as np
import pytest

from repro.core import gtscript, storage
from repro.core.gtscript import (
    FORWARD,
    PARALLEL,
    Field,
    computation,
    interval,
)

BACKENDS = ["numpy", "jax", "pallas"]


def run_all_backends(defs, fields_np, scalars, domain, externals=None, block=(4, 4)):
    """Run ``defs`` on the debug oracle + all backends; return dict of outputs."""
    results = {}
    for backend in ["debug"] + BACKENDS:
        opts = {"block": block} if backend == "pallas" else {}
        st = gtscript.stencil(backend=backend, externals=externals or {}, **opts)(defs)
        fs = {}
        for name, (arr, origin) in fields_np.items():
            fs[name] = storage.from_array(arr, backend=backend, default_origin=origin)
        st(**fs, **scalars, domain=domain)
        results[backend] = {n: f.to_numpy() for n, f in fs.items()}
    return results


def assert_backends_agree(results, rtol=1e-13, atol=1e-13):
    ref = results["debug"]
    for backend in BACKENDS:
        for name in ref:
            np.testing.assert_allclose(
                results[backend][name], ref[name], rtol=rtol, atol=atol,
                err_msg=f"{backend} disagrees with debug oracle on {name}",
            )


# ---------------------------------------------------------------------------


def _rand(shape, seed=0):
    return np.random.default_rng(seed).normal(size=shape)


def test_hdiff_all_backends():
    from repro.stencils.hdiff import hdiff_defs

    NI, NJ, NK, H = 11, 13, 5, 3
    x = _rand((NI + 2 * H, NJ + 2 * H, NK))
    results = run_all_backends(
        hdiff_defs,
        {
            "in_phi": (x, (H, H, 0)),
            "out_phi": (np.zeros_like(x), (H, H, 0)),
        },
        {"alpha": np.float64(0.07)},
        (NI, NJ, NK),
        externals={"LIM": 0.01},
    )
    assert_backends_agree(results)


@pytest.mark.parametrize("NK", [11, 80, 137])
def test_vadv_all_backends_and_oracle(NK):
    """The Thomas solver on every backend, at nk below and above one
    128-lane tile (Pallas runs it K-major), on a domain the tile does not
    divide."""
    from repro.stencils.vadv import vadv_defs

    NI, NJ = 6, 7
    rng = np.random.default_rng(3)
    a = rng.normal(size=(NI, NJ, NK)) * 0.1
    b = 2.0 + rng.random((NI, NJ, NK))
    c = rng.normal(size=(NI, NJ, NK)) * 0.1
    d = rng.normal(size=(NI, NJ, NK))

    results = run_all_backends(
        vadv_defs,
        {
            "a": (a, (0, 0, 0)),
            "b": (b, (0, 0, 0)),
            "c": (c, (0, 0, 0)),
            "d": (d, (0, 0, 0)),
            "out": (np.zeros_like(d), (0, 0, 0)),
        },
        {},
        (NI, NJ, NK),
    )
    assert_backends_agree(results)

    # dense oracle
    out = results["debug"]["out"]
    for i in range(0, NI, 3):
        for j in range(0, NJ, 3):
            M = np.diag(b[i, j])
            for k in range(1, NK):
                M[k, k - 1] = a[i, j, k]
            for k in range(NK - 1):
                M[k, k + 1] = c[i, j, k]
            np.testing.assert_allclose(M @ out[i, j], d[i, j], atol=1e-10)


def test_vadv_system_assembly():
    from repro.stencils.vadv import vadv_system_defs

    NI, NJ, NK = 5, 4, 8
    rng = np.random.default_rng(1)
    w = rng.normal(size=(NI, NJ, NK))
    phi = rng.normal(size=(NI, NJ, NK))
    zeros = lambda: (np.zeros((NI, NJ, NK)), (0, 0, 0))  # noqa: E731

    results = run_all_backends(
        vadv_system_defs,
        {
            "w": (w, (0, 0, 0)),
            "phi": (phi, (0, 0, 0)),
            "a": zeros(),
            "b": zeros(),
            "c": zeros(),
            "d": zeros(),
        },
        {"dt": np.float64(0.5), "dz": np.float64(1.5)},
        (NI, NJ, NK),
    )
    assert_backends_agree(results)
    # boundary specialization happened
    assert np.all(results["debug"]["a"][:, :, 0] == 0.0)
    assert np.all(results["debug"]["c"][:, :, -1] == 0.0)


def test_conditional_with_else_and_nesting():
    def defs(a: Field[np.float64], o: Field[np.float64], *, thr: np.float64):
        with computation(PARALLEL), interval(...):
            if a > thr:
                if a > thr * 2.0:
                    o = a * 4.0
                else:
                    o = a * 2.0
            else:
                o = -a

    NI, NJ, NK = 9, 8, 4
    x = _rand((NI, NJ, NK), seed=5)
    results = run_all_backends(
        defs,
        {"a": (x, (0, 0, 0)), "o": (np.zeros_like(x), (0, 0, 0))},
        {"thr": np.float64(0.3)},
        (NI, NJ, NK),
    )
    assert_backends_agree(results)
    ref = np.where(x > 0.3, np.where(x > 0.6, x * 4.0, x * 2.0), -x)
    np.testing.assert_allclose(results["debug"]["o"], ref)


def test_ij_and_k_fields():
    def defs(
        a: Field[np.float64],
        sfc: Field[np.float64, gtscript.IJ],
        prof: Field[np.float64, gtscript.K],
        o: Field[np.float64],
    ):
        with computation(PARALLEL), interval(...):
            o = a * prof + sfc

    NI, NJ, NK = 7, 6, 5
    a = _rand((NI, NJ, NK), seed=7)
    sfc = _rand((NI, NJ), seed=8)
    prof = _rand((NK,), seed=9)
    results = run_all_backends(
        defs,
        {
            "a": (a, (0, 0, 0)),
            "sfc": (sfc, (0, 0)),
            "prof": (prof, (0,)),
            "o": (np.zeros_like(a), (0, 0, 0)),
        },
        {},
        (NI, NJ, NK),
    )
    assert_backends_agree(results)
    np.testing.assert_allclose(results["debug"]["o"], a * prof[None, None, :] + sfc[:, :, None])


def test_forward_accumulation_with_interval_specialization():
    def defs(rho: Field[np.float64], colsum: Field[np.float64]):
        with computation(FORWARD):
            with interval(0, 1):
                colsum = rho
            with interval(1, None):
                colsum = colsum[0, 0, -1] + rho

    NI, NJ, NK = 5, 5, 9
    rho = np.abs(_rand((NI, NJ, NK), seed=11))
    results = run_all_backends(
        defs,
        {"rho": (rho, (0, 0, 0)), "colsum": (np.zeros_like(rho), (0, 0, 0))},
        {},
        (NI, NJ, NK),
    )
    assert_backends_agree(results)
    np.testing.assert_allclose(results["debug"]["colsum"], np.cumsum(rho, axis=2))


def test_swap_numerics():
    def defs(a: Field[np.float64], o: Field[np.float64]):
        with computation(PARALLEL), interval(...):
            x = a * 1.0
            y = a * 2.0
            x, y = y, x
            o = x - y  # = 2a - a = a

    NI, NJ, NK = 4, 4, 3
    x = _rand((NI, NJ, NK), seed=2)
    results = run_all_backends(
        defs,
        {"a": (x, (0, 0, 0)), "o": (np.zeros_like(x), (0, 0, 0))},
        {},
        (NI, NJ, NK),
    )
    assert_backends_agree(results)
    np.testing.assert_allclose(results["debug"]["o"], x)


def test_native_functions():
    def defs(a: Field[np.float64], o: Field[np.float64]):
        with computation(PARALLEL), interval(...):
            o = min(max(sqrt(abs(a)), 0.1), exp(a) + tanh(a))  # noqa: F821

    NI, NJ, NK = 6, 5, 4
    x = _rand((NI, NJ, NK), seed=13)
    results = run_all_backends(
        defs,
        {"a": (x, (0, 0, 0)), "o": (np.zeros_like(x), (0, 0, 0))},
        {},
        (NI, NJ, NK),
    )
    assert_backends_agree(results)
    ref = np.minimum(np.maximum(np.sqrt(np.abs(x)), 0.1), np.exp(x) + np.tanh(x))
    np.testing.assert_allclose(results["debug"]["o"], ref)


def test_validate_args_errors():
    from repro.stencils.hdiff import build_hdiff

    hd = build_hdiff("numpy")
    NI = NJ = 8
    NK = 4
    ok_in = storage.from_array(_rand((NI + 6, NJ + 6, NK)), default_origin=(3, 3, 0))
    ok_out = storage.zeros((NI + 6, NJ + 6, NK), default_origin=(3, 3, 0))

    # halo too small
    bad_in = storage.from_array(_rand((NI + 2, NJ + 2, NK)), default_origin=(1, 1, 0))
    with pytest.raises(ValueError, match="halo"):
        hd(bad_in, ok_out, alpha=np.float64(0.1), domain=(NI, NJ, NK))

    # wrong dtype
    bad_dtype = storage.from_array(_rand((NI + 6, NJ + 6, NK)).astype(np.float32),
                                   default_origin=(3, 3, 0))
    with pytest.raises(TypeError, match="dtype"):
        hd(bad_dtype, ok_out, alpha=np.float64(0.1), domain=(NI, NJ, NK))

    # missing scalar
    with pytest.raises(TypeError, match="missing scalar"):
        hd(ok_in, ok_out, domain=(NI, NJ, NK))


def test_domain_deduction_from_smallest_field():
    def defs(a: Field[np.float64], o: Field[np.float64]):
        with computation(PARALLEL), interval(...):
            o = a[1, 0, 0] - a[-1, 0, 0]

    a = storage.from_array(_rand((12, 10, 4)), default_origin=(1, 0, 0))
    o = storage.zeros((10, 10, 4), default_origin=(0, 0, 0))
    st = gtscript.stencil(backend="numpy")(defs)
    st(a, o)  # deduced domain = (10, 10, 4)
    ref = np.asarray(a)[2:, :, :] - np.asarray(a)[:-2, :, :]
    np.testing.assert_allclose(np.asarray(o), ref)


def test_exec_info_timings():
    from repro.stencils.hdiff import build_hdiff

    hd = build_hdiff("numpy")
    H = 3
    i = storage.from_array(_rand((14, 14, 3)), default_origin=(H, H, 0))
    o = storage.zeros((14, 14, 3), default_origin=(H, H, 0))
    info = {}
    hd(i, o, alpha=np.float64(0.1), exec_info=info)
    assert info["call_start_time"] <= info["run_start_time"] <= info["run_end_time"]
