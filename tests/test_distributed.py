"""Distributed halo-exchange stencil.

jax fixes the device count at first init, so multi-device tests run in a
subprocess with ``--xla_force_host_platform_device_count=8``.
"""

import json
import os
import subprocess
import sys
import tempfile
import textwrap
from pathlib import Path


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
    # run from a real file (not ``python -c``) so inspect.getsource works on
    # stencil definitions in the script — the frontend parses their source
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


def test_distributed_hdiff_matches_single_device():
    out = _run_subprocess(
        """
        from repro.stencils.hdiff import build_hdiff
        from repro.stencils.distributed import DistributedStencil
        from repro.core import storage

        NI, NJ, NK, H = 64, 32, 5, 3
        rng = np.random.default_rng(0)
        inner = rng.normal(size=(NI, NJ, NK))

        # single-device reference via the numpy backend (zero halo boundary)
        padded = np.zeros((NI + 2*H, NJ + 2*H, NK))
        padded[H:-H, H:-H, :] = inner
        st_np = build_hdiff("numpy")
        i_s = storage.from_array(padded, default_origin=(H, H, 0))
        o_s = storage.zeros(padded.shape, default_origin=(H, H, 0))
        st_np(i_s, o_s, alpha=np.float64(0.05), domain=(NI, NJ, NK))
        ref = o_s.to_numpy()[H:-H, H:-H, :]

        # distributed over a (4, 2) mesh
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        dist = DistributedStencil(build_hdiff("jax"), mesh)
        fields = {"in_phi": jnp.asarray(inner), "out_phi": jnp.zeros_like(jnp.asarray(inner))}
        out = dist(fields, {"alpha": np.float64(0.05)})
        err = float(np.abs(np.asarray(out["out_phi"]) - ref).max())
        print(json.dumps({"err": err}))
        """
    )
    assert out["err"] < 1e-12


def test_distributed_periodic_shift():
    """Periodic halo exchange: a pure i-shift stencil wraps around."""
    out = _run_subprocess(
        """
        from repro.core import gtscript
        from repro.core.gtscript import Field, PARALLEL, computation, interval
        from repro.stencils.distributed import DistributedStencil

        def shift_defs(a: Field[np.float64], o: Field[np.float64]):
            with computation(PARALLEL), interval(...):
                o = a[-1, 0, 0]

        st = gtscript.stencil(backend="jax")(shift_defs)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        dist = DistributedStencil(st, mesh, periodic=(True, True))
        NI, NJ, NK = 16, 8, 3
        rng = np.random.default_rng(1)
        x = rng.normal(size=(NI, NJ, NK))
        out = dist({"a": jnp.asarray(x), "o": jnp.zeros((NI, NJ, NK))}, {})
        got = np.asarray(out["o"])
        ref = np.roll(x, 1, axis=0)   # o[i] = a[i-1] with periodic wrap
        err = float(np.abs(got - ref).max())
        print(json.dumps({"err": err}))
        """
    )
    assert out["err"] < 1e-12


def test_halo_collectives_present_in_hlo():
    """The distributed stencil lowers to collective-permute (ICI traffic)."""
    out = _run_subprocess(
        """
        from repro.stencils.hdiff import build_hdiff
        from repro.stencils.distributed import DistributedStencil

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        dist = DistributedStencil(build_hdiff("jax"), mesh)
        specs = {
            "in_phi": jax.ShapeDtypeStruct((64, 32, 4), jnp.float64),
            "out_phi": jax.ShapeDtypeStruct((64, 32, 4), jnp.float64),
        }
        lowered = dist.lower(specs, {"alpha": np.float64(0.05)})
        txt = lowered.compile().as_text()
        print(json.dumps({"n_permute": txt.count("collective-permute")}))
        """
    )
    assert out["n_permute"] >= 4  # 2 stripes × 2 directions minimum

