"""Pallas TPU kernel for the RG-LRU linear recurrence h_t = a_t·h_{t−1} + b_t.

This is the GT4Py ``computation(FORWARD)`` schedule on TPU (DESIGN.md §4):
sequential in time, fully vectorized over (batch, channel) planes.  The grid
is (B/BB, D/BD, S/CHUNK) with the trailing (time-chunk) dimension sequential,
carrying the hidden state in VMEM scratch across chunks — the same
plane-carried scheme the DSL's pallas backend uses for vertical solvers.
Within a chunk, a fori_loop steps the recurrence on (BB, BD) tiles in f32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, h0_ref, y_ref, h_scratch, *, chunk: int):
    sc = pl.program_id(2)

    @pl.when(sc == 0)
    def _init():
        h_scratch[...] = h0_ref[...].astype(jnp.float32)

    def step(t, h):
        a_t = a_ref[:, t, :].astype(jnp.float32)
        b_t = b_ref[:, t, :].astype(jnp.float32)
        h = a_t * h + b_t
        y_ref[:, t, :] = h.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scratch[...])
    h_scratch[...] = h


def rglru_scan_bsd(
    a: jax.Array,  # (B, S, D) decay
    b: jax.Array,  # (B, S, D) input term
    h0: jax.Array,  # (B, D) initial state
    *,
    bb: int = 8,
    bd: int = 512,
    chunk: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    B, S, D = a.shape
    bb = min(bb, B)
    bd = min(bd, D)
    chunk = min(chunk, S)
    assert B % bb == 0 and D % bd == 0 and S % chunk == 0, "ops.py pads first"
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"

    grid = (B // bb, D // bd, S // chunk)
    kernel = functools.partial(_rglru_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, chunk, bd), lambda i, j, s: (i, s, j)),
            pl.BlockSpec((bb, chunk, bd), lambda i, j, s: (i, s, j)),
            pl.BlockSpec((bb, bd), lambda i, j, s: (i, j)),
        ],
        out_specs=pl.BlockSpec((bb, chunk, bd), lambda i, j, s: (i, s, j)),
        out_shape=jax.ShapeDtypeStruct((B, S, D), a.dtype),
        scratch_shapes=[pltpu.VMEM((bb, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b, h0)
