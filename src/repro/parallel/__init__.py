"""Halo exchange for distributed stencils (``halo.py``): the collective
permutes a sharded stencil or program runs between its shards."""
