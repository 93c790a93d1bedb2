"""The corpus differential in float32.

The chip runs the dycore in float32, while ``test_dsl_property.py`` runs
the committed corpus (``tests/corpus/prog_*.json``) in float64 only.  Here
each program is retyped to float32 (``ir.retype_definition``) and run on
numpy, jax and Pallas (where ``corpus_gen.pallas_compatible``; interpreted
off the TPU, on (4, 4) tiles so that tile edges fall inside the domain) at
the default pipeline, against the float32 debug backend at
``opt_level=0``:

* numpy is bit-identical, as in float64: every default pass is IEEE-exact,
  and the corpus calls no math function that the debug backend would
  evaluate in double precision (``min``, ``max`` and ``abs`` only);
* jax and Pallas agree to ``RTOL``, relative to the largest value of the
  field: XLA may contract a multiply and an add into one FMA, which moves
  the last bits of a float32 result (about one ulp, 6e-8, per operation,
  compounded through the programs' chained temporaries and divisions).
  The bound is far below the O(1) error of a wrong plane, boundary or mask.
"""

from pathlib import Path

import numpy as np
import pytest

import corpus_gen
from repro.core import ir, storage
from repro.core.stencil import build_from_definition

CORPUS = sorted((Path(__file__).resolve().parent / "corpus").glob("prog_*.json"))
DOMAIN = (corpus_gen.NI, corpus_gen.NJ, corpus_gen.NK)
RTOL = 1e-5


def _run(defn, backend, opts, data, scalar):
    st = build_from_definition(defn, backend, backend_opts=dict(opts))
    halo = corpus_gen.HALO
    fields = {
        n: storage.from_array(a.copy(), backend=backend, default_origin=(halo, halo, 0))
        for n, a in data.items()
    }
    st(**fields, s=scalar, domain=DOMAIN)
    written = set(st.implementation_ir.written_api_fields())
    return {n: f.to_numpy() for n, f in fields.items() if n in written}


@pytest.mark.parametrize("path", CORPUS, ids=lambda p: p.stem)
def test_corpus_float32_differential(path):
    index = int(path.stem.split("_")[1])
    defn = ir.retype_definition(corpus_gen.load_program(path), {"float64": "float32"})
    shape = (DOMAIN[0] + 2 * corpus_gen.HALO, DOMAIN[1] + 2 * corpus_gen.HALO, DOMAIN[2])
    rng = np.random.default_rng(index)
    data = {f.name: rng.normal(size=shape).astype(np.float32) for f in defn.api_fields if f.is_api}
    scalar = np.float32(rng.normal())

    oracle = _run(defn, "debug", {"opt_level": 0}, data, scalar)
    assert oracle, f"{defn.name}: no written outputs"
    configs = [("numpy", {}), ("jax", {})]
    if corpus_gen.pallas_compatible(defn):
        configs.append(("pallas", {"block": (4, 4)}))
    for backend, opts in configs:
        got = _run(defn, backend, opts, data, scalar)
        assert got.keys() == oracle.keys()
        for n, ref in oracle.items():
            assert got[n].dtype == np.float32
            if backend == "numpy":
                np.testing.assert_array_equal(got[n], ref, err_msg=f"{defn.name} {backend} {n}")
            else:
                np.testing.assert_allclose(
                    got[n], ref, rtol=RTOL, atol=RTOL * np.abs(ref).max(),
                    err_msg=f"{defn.name} {backend} {n}",
                )
