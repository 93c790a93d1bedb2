"""How the Pallas kernels read their inputs' halo windows.

An (I, J, K) input is the caller's own array: each grid step reads its
tile-plus-halo window through an element-indexed block, J offset rounded
down to a sublane row and the remainder a static shift of the kernel's
reads, K whole, the last tile's overrun padded in the kernel.  An (I, J)
input keeps the edge-padded region and the hand-issued DMA.  Both are
checked against the numpy backend at ragged geometries: domains that are
not a multiple of the tile, origins at every alignment, levels below and
above one 128-lane tile, and origins below the stencil's halo.
"""

import numpy as np
import pytest

from repro.core import gtscript, storage
from repro.core.gtscript import PARALLEL, Field, computation, interval
from repro.obs import trace as otrace
from repro.stencils.hdiff import build_hdiff
from repro.stencils.vadv import build_vadv
from repro.stencils.vintg import build_vintg

#: a domain that is a multiple of the (8, 16) test tile in neither I nor J
DOMAIN_IJ = (13, 37)
BLOCK = (8, 16)
BUILDERS = {"hdiff": build_hdiff, "vadv": build_vadv, "vintg": build_vintg}
SCALARS = {"hdiff": {"alpha": 0.05}, "vadv": {}, "vintg": {"decay": 0.9}}


def _fields(st, origin, nk, deeper, seed):
    """Random fields for ``st``: each array holds the domain at ``origin`` and
    a ring of ``_H`` + 2 points around it, ``deeper`` levels below and above."""
    rng = np.random.default_rng(seed)
    h = st._module._H
    ni, nj = DOMAIN_IJ
    o = (origin, origin, deeper)
    shape = (origin + ni + h + 2, origin + nj + h + 2, nk + 2 * deeper)
    # away from zero, so the vertical solvers' divisions stay well posed
    arrays = {n: rng.uniform(0.5, 1.5, size=shape) for n in st._module._AXES}
    if "b" in arrays:
        arrays["b"] += 4.0  # a diagonally dominant tridiagonal system
    return arrays, o


@pytest.mark.parametrize(
    "name, shift, nk",
    [
        ("hdiff", 0, 80), ("hdiff", 3, 80), ("hdiff", 5, 80), ("hdiff", 3, 137),
        ("vadv", 0, 80), ("vadv", 3, 80), ("vadv", 5, 80), ("vadv", 5, 137),
        ("vintg", 0, 80), ("vintg", 3, 80), ("vintg", 5, 80), ("vintg", 0, 137),
    ],
)
def test_direct_windows_match_numpy(name, shift, nk):
    """Pallas against the numpy backend, every input on the direct path,
    with the J origin ``shift`` rows past a sublane row below the halo and,
    for odd shifts, the field one level deep in a deeper array."""
    pal = BUILDERS[name]("pallas", block=BLOCK)
    ref = BUILDERS[name]("numpy")
    h = pal._module._H
    assert set(pal._module.SCHEDULE["windows"].values()) == {"direct"}
    arrays, origin = _fields(pal, h + shift, nk, shift % 2, seed=shift + nk)
    scalars = SCALARS[name]
    domain = DOMAIN_IJ + (nk,)
    got = {n: storage.from_array(v.copy(), backend="pallas") for n, v in arrays.items()}
    want = {n: storage.from_array(v.copy(), backend="numpy") for n, v in arrays.items()}
    pal(**got, **scalars, domain=domain, origin=origin)
    ref(**want, **scalars, domain=domain, origin=origin)
    for n in arrays:
        np.testing.assert_allclose(got[n].to_numpy(), want[n].to_numpy(), rtol=1e-13, atol=1e-13,
                                   err_msg=f"{name}: pallas disagrees with numpy on {n!r}")


def _mixed_defs(a: Field[np.float64], sfc: Field[np.float64, gtscript.IJ], o: Field[np.float64]):
    with computation(PARALLEL), interval(...):
        o = a[1, 0, 0] - a[-1, 0, 0] + sfc[0, 1] + sfc[0, -1]


@pytest.mark.parametrize("nk", [80, 137])
def test_ij_input_keeps_the_padded_path(nk):
    """An (I, J) input takes the padded, hand-DMA'd window beside a direct
    one.  Each field is stored with only its own halo, below the stencil's:
    ``a`` has none in J, ``sfc`` none in I, so both windows start at the
    array's edge rather than ``_H`` rows below the tile."""
    st = gtscript.stencil(backend="pallas", block=BLOCK)(_mixed_defs)
    ref = gtscript.stencil(backend="numpy")(_mixed_defs)
    assert st._module._H == 1
    assert st._module.SCHEDULE["windows"] == {"a": "direct", "sfc": "padded"}
    assert "_cp_sfc" in st.generated_source and "_cp_a" not in st.generated_source
    ni, nj = DOMAIN_IJ
    rng = np.random.default_rng(nk)
    arrays = {
        "a": (rng.normal(size=(ni + 2, nj, nk)), (1, 0, 0)),
        "sfc": (rng.normal(size=(ni, nj + 2)), (0, 1)),
        "o": (np.zeros((ni, nj, nk)), (0, 0, 0)),
    }
    out = {}
    for key, stencil, backend in (("pallas", st, "pallas"), ("numpy", ref, "numpy")):
        fs = {n: storage.from_array(v.copy(), backend=backend, default_origin=o) for n, (v, o) in arrays.items()}
        stencil(**fs, domain=(ni, nj, nk))
        out[key] = fs["o"].to_numpy()
    np.testing.assert_allclose(out["pallas"], out["numpy"], rtol=1e-13, atol=1e-13)


@pytest.mark.parametrize("name", ["hdiff", "vadv"])
def test_dycore_stencils_read_every_field_directly(name):
    """Every (I, J, K) input of the dycore's two stencils takes the direct
    path, and a call's ``stencil.call`` span counts no padded input."""
    st = BUILDERS[name]("pallas", dtype="float32")
    sched = st._module.SCHEDULE
    assert sched["windows"] == {n: "direct" for n in sched["dma_inputs"]}
    assert "jnp.pad" not in st.generated_source.split("def run(")[1].split("if n not in _PADDED")[0]
    arrays, origin = _fields(st, 3, 4, 0, seed=1)
    fs = {n: storage.from_array(v.astype(np.float32), backend="pallas") for n, v in arrays.items()}
    scalars = {"alpha": np.float32(0.05)} if name == "hdiff" else {}
    with otrace.capture() as cap:
        st(**fs, **scalars, domain=DOMAIN_IJ + (4,), origin=origin)
    (span,) = [s for s in cap.snapshot() if s["name"] == "stencil.call"]
    assert span["attrs"]["padded_inputs"] == 0


def test_padded_inputs_counts_the_hand_dmad_windows():
    st = gtscript.stencil(backend="pallas", block=BLOCK)(_mixed_defs)
    ni, nj = DOMAIN_IJ
    fs = {
        "a": storage.from_array(np.ones((ni + 2, nj, 3)), backend="pallas", default_origin=(1, 0, 0)),
        "sfc": storage.from_array(np.ones((ni, nj + 2)), backend="pallas", default_origin=(0, 1)),
        "o": storage.from_array(np.zeros((ni, nj, 3)), backend="pallas"),
    }
    with otrace.capture() as cap:
        st(**fs, domain=(ni, nj, 3))
    (span,) = [s for s in cap.snapshot() if s["name"] == "stencil.call"]
    assert span["attrs"]["padded_inputs"] == 1


@pytest.mark.parametrize("nk, buffers", [(80, 2), (137, 1)])
def test_vmem_estimate_counts_both_pipeline_buffers(nk, buffers):
    """A direct window counts once per pipeline buffer: two where both fit the
    scoped VMEM limit, one where two would overrun it (vadv's five windows
    at 137 levels, each 256 lanes deep, on the default tile)."""
    mod = build_vadv("pallas", dtype="float32")._module
    two, one = mod._vmem_bytes(8, 128, nk, 2), mod._vmem_bytes(8, 128, nk, 1)
    windows = sum(mod._tile_bytes(*t, 8, 128, nk) for t in mod._VMEM_WINDOWS)
    assert two - one == windows > 0
    assert (two <= mod._VMEM_LIMIT) == (buffers == 2)
    assert mod._buffers(8, 128, nk) == buffers
    assert mod._vmem_bytes(8, 128, nk) == (two if buffers == 2 else one)


def test_single_buffered_windows_match_numpy():
    """Where two pipeline buffers of every window overrun the scoped VMEM
    limit (vadv at 137 levels on the default tile), each window takes one,
    and the results stay those of the numpy backend."""
    nk, domain_ij = 137, (9, 130)
    pal = build_vadv("pallas", block=(8, 128))
    ref = build_vadv("numpy")
    assert pal._module._buffers(8, 128, nk) == 1
    rng = np.random.default_rng(137)
    shape = domain_ij[0] + 6, domain_ij[1] + 6, nk
    arrays = {n: rng.uniform(0.5, 1.5, size=shape) for n in pal._module._AXES}
    arrays["b"] += 4.0
    got = {n: storage.from_array(v.copy(), backend="pallas") for n, v in arrays.items()}
    want = {n: storage.from_array(v.copy(), backend="numpy") for n, v in arrays.items()}
    pal(**got, domain=domain_ij + (nk,), origin=(3, 3, 0))
    ref(**want, domain=domain_ij + (nk,), origin=(3, 3, 0))
    np.testing.assert_allclose(got["out"].to_numpy(), want["out"].to_numpy(), rtol=1e-13, atol=1e-13)
