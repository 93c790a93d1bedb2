"""The served forecast step (``stencils/forecast.py``): upwind advection, an
Euler update and Laplacian diffusion.

Ops per output point, counted from the algorithm: two upwind differences,
each a subtraction and a division behind a comparison of the wind, 6; the
tendency ``-(u fx + v fy)``, 4; the Euler update, 2; the Laplacian, 5, and
its ``alpha`` product and add, 2: 19.  The step reads ``phi`` over a
one-point ring and the winds ``u`` and ``v``, and writes the new ``phi``:
four float64 values, 32 bytes a point, so 0.6 operations per byte, and the
roofline is the HBM term.  The intermediate fields (the tendency and the
Euler state) are the program's, not the algorithm's, and are not counted.
"""

from __future__ import annotations

#: read extent of ``phi`` on each side in I and J (the upwind differences;
#: the Laplacian of the Euler state reaches one point further, through it)
HALO = 1
OPS_PER_POINT = 19
#: ``u`` and ``v`` read, the new ``phi`` written, over the domain
INPUTS, OUTPUTS = 2, 1


def ops(domain) -> int:
    ni, nj, nk = domain
    return OPS_PER_POINT * ni * nj * nk


def bytes_moved(domain, itemsize: int) -> int:
    """``phi`` read over its read extent, ``u`` and ``v`` read and the new
    ``phi`` written over the domain."""
    ni, nj, nk = domain
    return itemsize * ((ni + 2 * HALO) * (nj + 2 * HALO) * nk + (INPUTS + OUTPUTS) * ni * nj * nk)
