"""Horizontal diffusion with a flux limiter (``stencils/hdiff.py``).

Ops per output point, counted from the algorithm:
laplacian 5, laplacian of it 5, two flux differences 2, two input gradients 2,
two limiters (product and compare) 4, update (two differences, a sum, the
``alpha`` product, the add to the input) 5: 23.  At 4-byte floats that is
23 / 8 = 2.9 operations per byte, far below any TPU's ridge point, so the
roofline of hdiff is the HBM term.
"""

from __future__ import annotations

#: read extent of ``in_phi`` on each side in I and J (laplacian of a
#: laplacian, then a flux difference)
HALO = 3
OPS_PER_POINT = 23


def ops(domain) -> int:
    ni, nj, nk = domain
    return OPS_PER_POINT * ni * nj * nk


def bytes_moved(domain, itemsize: int) -> int:
    """One read of ``in_phi`` over its read extent, one write of ``out_phi``."""
    ni, nj, nk = domain
    return itemsize * ((ni + 2 * HALO) * (nj + 2 * HALO) * nk + ni * nj * nk)
