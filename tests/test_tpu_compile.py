"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler is installed with JAX and compiles for a topology that is
described rather than attached (``jax.experimental.topologies``).  Nothing
runs: these tests show only that Mosaic and XLA accept the programs at the
sizes the chip runs, which interpret mode on the CPU cannot show (a slice not
aligned to the vector tiling, or a kernel that needs more VMEM than it may
use).

The topology is described inside a module-scoped fixture: only the worker
that runs this file loads the TPU compiler, and it is skipped where the
topology cannot be described.  The Pallas backend interprets its kernels
whenever the first device is not a TPU, so each kernel test turns the
generated module's ``INTERPRET`` off itself and restores it afterwards.
"""

from __future__ import annotations

import contextlib
import os
import re

import numpy as np
import pytest

#: one chip's subdomain of a production-resolution run, with vertical grids
#: below (COSMO-1: 80 levels) and above (ECMWF IFS: 137) one 128-lane tile
NIJ = 256
LEVELS = (80, 137)
MESH_DOMAIN = (512, 512, 80)
#: the dycore cell's grid (COSMO-1) and storages: halo-3 fields, and vadv's
#: coefficients a, b, c stored without a halo (bench/drivers/dycore_step.py)
DYCORE = (1158, 774, 80)
DYCORE_HALO = 3
BARE = ("a", "b", "c")


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile written to the persistent cache cannot be read back without
    # the chip; keep these compiles out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def compiled_kernels(module):
    """The generated Pallas module with Mosaic compilation on (as on a TPU)."""
    module._build.cache_clear()
    module.INTERPRET = False
    try:
        yield module
    finally:
        module.INTERPRET = True
        module._build.cache_clear()


def _compile_stencil(st, sharding, domain, block, halo=None, bare=()):
    """Compile ``st``'s call on storages that hold the domain in a ring of
    ``halo`` points (default: the stencil's own), the fields in ``bare`` in
    none."""
    import jax
    import jax.numpy as jnp

    mod = st._module
    ni, nj, nk = domain
    fields, origins = {}, {}
    for n in mod._AXES:
        h = 0 if n in bare else mod._H if halo is None else halo
        shape = (ni + 2 * h, nj + 2 * h, nk)
        fields[n] = jax.ShapeDtypeStruct(shape, jnp.dtype(mod._DTYPES[n]), sharding=sharding)
        origins[n] = (h, h, 0)
    scalars = {s: jax.ShapeDtypeStruct((), jnp.float32, sharding=sharding) for s in mod._SCALARS}
    with compiled_kernels(mod):
        fn = jax.jit(lambda f, s: mod.run(f, s, domain, origins, block=block))
        return fn.lower(fields, scalars).compile()


def _build(name):
    from repro.stencils.hdiff import build_hdiff
    from repro.stencils.vadv import build_vadv
    from repro.stencils.vintg import build_vintg

    return {"hdiff": build_hdiff, "vadv": build_vadv, "vintg": build_vintg}[name]("pallas", dtype="float32")


@pytest.mark.parametrize("nk", LEVELS)
@pytest.mark.parametrize("name", ["hdiff", "vadv", "vintg"])
def test_pallas_kernel_compiles_for_v5e(one_chip, name, nk):
    """The default tile and every tile the autotuner's VMEM filter keeps
    compile: a kept tile that Mosaic refuses is a bug in ``_vmem_bytes``.
    hdiff keeps K on lanes; vadv and vintg sweep K-major, transposing each
    window in the kernel.  The compiled custom-call is named for its
    stencil, so a device profile tells the kernels apart."""
    from repro.core import autotune
    from repro.core.codegen_pallas import KERNEL_PREFIX

    st = _build(name)
    assert "dynamic_update_slice" not in st.generated_source
    assert "dynamic_slice" not in st.generated_source.split("def run(")[0]
    domain = (NIJ, NIJ, nk)
    default = tuple(st._module._BLOCK_DEFAULT)
    kept = autotune.candidate_blocks(st._module, domain)
    assert default in kept
    for block in kept:
        assert st._module._vmem_bytes(*block, nk) <= autotune.VMEM_BUDGET_BYTES
        compiled = _compile_stencil(st, one_chip, domain, block)
        text = compiled.as_text()
        assert "tpu_custom_call" in text
        kernel = re.escape(KERNEL_PREFIX + st.name)
        assert re.search(rf"%{kernel}(\.\d+)? = .*custom-call\(", text), f"no custom-call named {kernel}"


@pytest.mark.parametrize("name", ["hdiff", "vadv"])
def test_dycore_kernels_read_the_callers_arrays(one_chip, name):
    """At the dycore cell's geometry and default tile the kernel reads every
    input from the caller's own array: the compiled call holds no op of
    ``run``'s edge pad (``jit(_pad)``), and the kernel's operands are the
    storages as they are, 80 levels deep rather than padded to 128 lanes."""
    from repro.core.codegen_pallas import KERNEL_PREFIX

    st = _build(name)
    ni, nj, nk = DYCORE
    block = tuple(st._module._BLOCK_DEFAULT)
    text = _compile_stencil(st, one_chip, DYCORE, block, halo=DYCORE_HALO, bare=BARE).as_text()
    assert "jit(_pad)" not in text
    kernel = re.escape(KERNEL_PREFIX + st.name)
    call = re.search(rf"%{kernel}(\.\d+)? = .*custom-call\(.*operand_layout_constraints=\{{(.*?)\}}, ", text)
    assert call, f"no custom-call named {kernel}"
    operands = re.findall(r"f32\[([\d,]+)\]", call.group(2))
    storages = {f"{ni + 2 * h},{nj + 2 * h},{nk}" for h in (0, DYCORE_HALO)}
    assert operands and set(operands) - {"1"} <= storages, operands


def _forecast_program(domain):
    from repro.stencils.forecast import build_forecast_step

    return build_forecast_step("jax", domain)


def _stand_in(shape):
    return np.broadcast_to(np.zeros((), np.float64), shape)


def _shapes(named_shapes, sharding):
    import jax

    return {n: jax.ShapeDtypeStruct(s, d, sharding=sharding) for n, (s, d) in named_shapes.items()}


def _single_chip_compile(domain, sharding):
    """The forecast step's jitted program (one device, halo-1 storages)."""
    import jax
    import jax.numpy as jnp

    from repro.stencils.forecast import DEFAULT_SCALARS, FIELD_NAMES, HALO

    ni, nj, nk = domain
    shape = (ni + 2 * HALO, nj + 2 * HALO, nk)
    step = _forecast_program(domain)
    # tracing reads shapes only: zero-stride stand-ins hold no memory
    cp = step.compiled({n: _stand_in(shape) for n in FIELD_NAMES}, dict(DEFAULT_SCALARS))
    args = _shapes({n: (shape, jnp.float64) for n in FIELD_NAMES}, sharding)
    vals = {
        n: jax.ShapeDtypeStruct((), jnp.float64, sharding=sharding)
        for n in cp.runtime_scalars(dict(DEFAULT_SCALARS))
    }
    return cp._jit().lower(args, vals).compile()


def test_forecast_step_compiles_for_v5e(one_chip):
    compiled = _single_chip_compile((NIJ, NIJ, 80), one_chip)
    mem = compiled.memory_analysis()
    # six float64 fields of (258, 258, 80) go in; the step fits one chip
    assert mem.argument_size_in_bytes >= 6 * 258 * 258 * 80 * 8
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30


def test_distributed_program_compiles_for_v5e_mesh(topo, one_chip):
    """``DistributedProgram.iterate`` over the 2x2 mesh of a v5e host: each
    device holds about a quarter of what the one-device compile holds."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.stencils.forecast import FIELD_NAMES, DEFAULT_SCALARS

    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("data", "model"))
    sharded = NamedSharding(mesh, P("data", "model", None))
    step = _forecast_program(MESH_DOMAIN)
    dp = step.distribute(mesh)
    fields = {n: jax.ShapeDtypeStruct(MESH_DOMAIN, jnp.float64, sharding=sharded) for n in FIELD_NAMES}
    scalars = {n: np.float64(v) for n, v in DEFAULT_SCALARS.items()}
    local, key = dp._geometry(fields)
    assert local == (MESH_DOMAIN[0] // 2, MESH_DOMAIN[1] // 2, MESH_DOMAIN[2])
    plan = dp._plan_for({n: _stand_in(MESH_DOMAIN) for n in FIELD_NAMES}, scalars, local, key)
    assert plan.report["halo_plan"]["inserted"] == 2
    fn = dp._compile_iterate(plan, 10)
    scal = {n: jax.ShapeDtypeStruct((), jnp.float64, sharding=NamedSharding(mesh, P())) for n in scalars}
    compiled = jax.jit(fn).lower(fields, scal).compile()
    text = compiled.as_text()
    assert "collective-permute" in text

    def held(c):
        m = c.memory_analysis()
        return m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes - m.alias_size_in_bytes

    whole = held(_single_chip_compile(MESH_DOMAIN, one_chip))
    ratio = held(compiled) / whole
    assert 0.2 <= ratio <= 0.35, ratio


def test_distributed_ensemble_iterate_fits_a_v5e_host(topo):
    """``DistributedEnsemble.iterate`` of the forecast step for COSMO-1E's 11
    members on the whole COSMO-1 grid over the 2x2 mesh of a v5e host: the
    carried state is donated (aliased to the result), the exchanges are
    collective-permutes, and each chip's share fits the 15.75 GiB that XLA
    may use."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.ensemble import Ensemble
    from repro.stencils.forecast import DEFAULT_SCALARS

    members, domain = 11, (1158, 774, 80)
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 2, 2), ("ens", "data", "model"))
    batched = NamedSharding(mesh, P("ens", "data", "model", None))
    shared = NamedSharding(mesh, P("data", "model", None))
    dens = Ensemble(_forecast_program(domain), members).distribute(mesh)
    state = {n: jax.ShapeDtypeStruct((members,) + domain, jnp.float64, sharding=batched)
             for n in ("phi", "phi_star", "phi_new")}
    winds = {n: jax.ShapeDtypeStruct(domain, jnp.float64, sharding=shared) for n in ("u", "v", "adv")}
    scalars = {n: np.float64(v) for n, v in DEFAULT_SCALARS.items()}
    _raw, batched_fields, samples, local, key = dens._bind({**state, **winds})
    fn, report = dens._compile_iterate(samples, scalars, local, batched_fields, key, 4)
    assert report["exchanges_per_step"] == 2 and report["members_per_shard"] == members
    scal = {n: jax.ShapeDtypeStruct((), jnp.float64, sharding=NamedSharding(mesh, P())) for n in scalars}
    compiled = jax.jit(lambda s, w, c: fn({**s, **w}, c), donate_argnums=0).lower(
        state, {n: winds[n] for n in ("u", "v")}, scal).compile()
    assert "collective-permute" in compiled.as_text()
    mem = compiled.memory_analysis()
    tile = members * (domain[0] // 2) * (domain[1] // 2) * domain[2] * 8
    assert mem.alias_size_in_bytes >= 3 * tile  # phi, phi_star and phi_new
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2**30
