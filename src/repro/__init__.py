"""repro — a GT4Py-style performance-portable stencil DSL on JAX and Pallas,
with a multi-stencil program orchestrator, member-batched ensembles and a
batching forecast server.

Weather & climate stencils (the paper's domain) default to float64, so x64
is enabled globally; a float32 stencil states its dtype in its fields.
"""

import jax

jax.config.update("jax_enable_x64", True)

__version__ = "0.1.0"
