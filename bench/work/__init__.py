"""Algorithmic work of each stencil, from shapes alone.

One module per stencil, each with ``ops(domain)`` and ``bytes_moved(domain,
itemsize)``: what the algorithm needs, whatever implements it.  Bytes are one
read of every API input over its read extent plus one write of every API
output over the compute domain, at logical sizes (no padding of any axis).
"""
