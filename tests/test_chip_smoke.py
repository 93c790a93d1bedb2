"""CPU rehearsal of ``chip_smoke.py``: its phases at a tiny size, with the
Pallas kernels interpreted, and its numpy forecast reference against the
``debug`` backend.  ``main()`` (which insists on a TPU) is not run here."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_kernels_phase_within_tolerance_interpreted():
    out = chip_smoke.kernels_phase((16, 24, 12))
    for name in ("hdiff", "vadv"):
        assert out[name]["interpret"] is True  # no TPU here
        assert out[name]["err"] <= chip_smoke.KERNEL_RTOL


def test_serving_phase_matches_reference():
    out = chip_smoke.serving_phase((12, 10, 4), requests=8, steps=8, stream_every=4)
    assert len(out["errs"]) == 8
    assert max(out["errs"]) <= chip_smoke.FORECAST_RTOL
    assert out["batched_vs_alone"] == 0.0  # bit-identical on the CPU


def test_forecast_reference_is_the_debug_backend_exactly():
    from repro.core import gtscript, storage
    from repro.stencils.forecast import HALO, advect_defs, diffuse_defs, euler_defs, make_forecast_fields

    domain, steps = (9, 7, 3), 5
    fields, scalars = make_forecast_fields("debug", domain, seed=3)
    phi0 = fields["phi"].to_numpy().copy()
    u, v = fields["u"].to_numpy(), fields["v"].to_numpy()
    build = gtscript.stencil(backend="debug")
    advect, euler, diffuse = build(advect_defs), build(euler_defs), build(diffuse_defs)
    s = dict(fields)
    for _ in range(steps):
        advect(s["phi"], s["u"], s["v"], s["adv"], dx=scalars["dx"], dy=scalars["dy"], domain=domain)
        euler(s["phi"], s["adv"], s["phi_star"], dt=scalars["dt"], domain=domain)
        diffuse(s["phi_star"], s["phi_new"], alpha=scalars["alpha"], domain=domain)
        s["phi"], s["phi_new"] = s["phi_new"], s["phi"]
    got = s["phi"].to_numpy()
    ref = chip_smoke.forecast_reference(phi0, u, v, scalars, steps)
    assert got.dtype == ref.dtype == np.float64
    assert got.shape == (domain[0] + 2 * HALO, domain[1] + 2 * HALO, domain[2])
    np.testing.assert_array_equal(got, ref)
    assert not np.array_equal(ref, phi0)


def test_device_phase_refuses_the_cpu():
    with pytest.raises(SystemExit, match="not a TPU"):
        chip_smoke.device_phase(1)


def test_mesh_phase_on_four_virtual_devices():
    """The ``--chips 4`` phase on a 2x2 mesh of virtual CPU devices (JAX fixes
    the device count at start-up, so it runs in a child process)."""
    script = textwrap.dedent(
        f"""
        import os, sys, json
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, {str(ROOT)!r})
        import jax
        import chip_smoke
        out = chip_smoke.mesh_phase(jax.devices(), (16, 24, 6), steps=10)
        print(json.dumps(out))
        """
    )
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    res = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True, timeout=600, env=env)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["exchanges"] == 2
    assert out["err"] == 0.0  # bit-identical on the CPU
