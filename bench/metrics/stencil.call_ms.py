"""Median host milliseconds of the program's ``stencil.call`` spans (all of
``StencilObject.__call__``: bind, validate, tile, jit lookup, dispatch) that
start in the window."""

from bench import spans


def read(rec):
    return spans.median_ms(rec, "stencil.call")
