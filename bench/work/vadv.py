"""Tridiagonal vertical solve, Thomas' algorithm (``stencils/vadv.py``).

Ops per point: forward elimination 6 (``b - a*cp``, ``c/denom``,
``(d - a*dp)/denom``), back substitution 2: 8.  Four inputs read and one
output written per point, 20 bytes at 4-byte floats: 0.4 operations per byte.
The roofline of vadv is the HBM term.
"""

from __future__ import annotations

OPS_PER_POINT = 8
INPUTS, OUTPUTS = 4, 1


def ops(domain) -> int:
    ni, nj, nk = domain
    return OPS_PER_POINT * ni * nj * nk


def bytes_moved(domain, itemsize: int) -> int:
    """``a``, ``b``, ``c`` and ``d`` read once, ``out`` written once, over the domain."""
    ni, nj, nk = domain
    return itemsize * (INPUTS + OUTPUTS) * ni * nj * nk
